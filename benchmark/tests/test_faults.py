"""A whole run on the CPU with the harness's look for a chip skipped and
the timed path broken underneath: ``correct`` must come out false for
each fault the cells can have (one chip each, so no exchange between
chips to leave out)."""
import pytest

from benchmark.systems import check_batch_columnar as sut
from benchmark.tests._tiny import TINY, drive, tiny_config


def real(cell):
    return sut.make(tiny_config(cell))


def altered(call):
    """One answer altered where it is produced: the first verdict of
    every call flipped."""
    def f(hs):
        out = [dict(r) for r in call(hs)]
        out[0] = {"valid": True} if out[0]["valid"] is False else \
            {"valid": False, "op": {"index": 0}}
        return out
    return f


def half_left_out(call):
    """Half of each batch left out, the rest reported valid."""
    def f(hs):
        k = len(hs) // 2
        return (call(hs[:k]) if k else []) + [{"valid": True}] * (
            len(hs) - k)
    return f


def unchanged(call):
    """The state never moves on: every call returns the first call's
    answers."""
    first = []

    def f(hs):
        if not first:
            first.append(call(hs))
        return first[0]
    return f


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    line = drive(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    # a CPU run never reports a device-trace metric
    assert set(line["metrics"]) <= {"hist_per_s", "setup_s",
                                    "verdict_p50_ms", "verdict_p95_ms"}


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_run_reports_host_layers_only_on_cpu(cell):
    line = drive(cell, trace=1)
    assert line["correct"] is True
    assert {"partition_ms_per_khist", "convert_ms_per_khist",
            "device_decided_share", "window_compiles"} <= set(line["metrics"])
    for name in ("wgl_kernel_ms_per_khist", "wgl_roofline",
                 "device_idle_share"):
        assert name not in line["metrics"]
    assert "busy_s" not in line["device"] and "breakdown" not in line


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_fault_is_caught(cell, fault):
    line = drive(cell, fault(real(cell)))
    assert line["correct"] is False, line["compared"]
