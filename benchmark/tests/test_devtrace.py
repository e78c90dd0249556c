"""The trace reduction on a small trace in the plain form ``load``
produces: two calls on the host, device operations inside them."""
import pytest

from benchmark.devtrace import Trace, module_name, op_label

MS = 1_000_000


def small_trace():
    host = [("bench.call", 0, 10 * MS), ("bench.partition", 1 * MS, 2 * MS),
            ("bench.convert", 3 * MS, 1 * MS),
            ("bench.call", 12 * MS, 8 * MS), ("other", 0, 30 * MS)]
    ops = [("fusion.1", 4 * MS, 2 * MS), ("while.2", 5 * MS, 3 * MS),
           ("fusion.1", 13 * MS, 1 * MS), ("copy.3", 25 * MS, 5 * MS)]
    mods = [("jit_check(17)", 4 * MS, 4 * MS), ("jit_other", 13 * MS, 1 * MS),
            ("jit_check(17)", 25 * MS, 5 * MS)]
    return Trace([
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/device:TPU:1", "lines": []},
    ])


def test_window_busy_and_idle():
    tr = small_trace()
    assert list(tr.devices) == ["/device:TPU:0"]     # idle devices ignored
    assert tr.window_s == pytest.approx(0.020)       # first call to last
    # union of [4,6], [5,8], [13,14]; the op at 25 ms lies outside
    assert tr.busy_s == pytest.approx(0.005)


def test_kernel_time_sums_named_programs_inside_the_window():
    tr = small_trace()
    assert tr.kernel_s(["jit_check"]) == pytest.approx(0.004)
    assert tr.kernel_s(["jit_check", "jit_other"]) == pytest.approx(0.005)
    assert tr.kernel_s(["jit_none"]) == 0.0
    assert module_name("jit_check(17)") == "jit_check"
    assert module_name("jit_fused.3") == "jit_fused"


def test_breakdown_names_ops_and_gaps():
    b = small_trace().breakdown()
    ops = dict(b["device_ops"])
    assert ops["while.2"] == pytest.approx(0.003)
    assert ops["fusion.1"] == pytest.approx(0.003)
    assert "copy.3" not in ops
    gaps = b["idle_gaps"]
    # gaps: [0,4] (partition covers 2 ms: midpoint 2 ms), [8,13]
    # (midpoint 10.5 ms: between calls), [14,20] (inside call 2)
    assert gaps[0] == ["call", pytest.approx(0.006)]
    assert gaps[1] == ["between calls", pytest.approx(0.005)]
    assert gaps[2] == ["partition", pytest.approx(0.004)]
    assert len(b["device_ops"]) <= 10 and len(gaps) <= 10


def test_op_label_keeps_name_and_first_shape():
    text = ("%while.84 = (s32[]{:T(128)}, u32[64,65536]{0,1:T(8,128)}, "
            "pred[64]{0}) while((s32[]{:T(128)}) %tuple.601)")
    assert op_label(text) == "%while.84 u32[64,65536]"
    assert op_label("fusion.1") == "fusion.1"
