"""The readers of the program's own telemetry: idle time laid against
its stage spans on the trace's clock, the accepted readers unmoved by
``jt.*`` annotations and stage spans, and the host readers' numbers on
a tiny traced run."""
import pytest

from benchmark import generator, run
from benchmark.devtrace import Trace
from benchmark.metrics import idle_unattributed_ms_per_khist as idle
from benchmark.tests._tiny import TINY, drive, tiny_config
from jepsen_tpu import telemetry

MS = 1_000_000
#: Trace clock less monotonic clock in the fake runs, ns.
OFFSET = 5_000 * MS

ACCEPTED = ("partition_ms_per_khist", "convert_ms_per_khist",
            "encode_ms_per_khist", "device_decided_share",
            "window_compiles", "wgl_kernel_ms_per_khist", "wgl_roofline",
            "device_idle_share")


def planes(extra_host=()):
    """The plain-form trace of test_devtrace.small_trace: calls at
    0-10 and 12-20 ms, device busy 4-8 and 13-14 ms."""
    host = [("bench.call", 0, 10 * MS), ("bench.partition", 1 * MS, 2 * MS),
            ("bench.convert", 3 * MS, 1 * MS),
            ("bench.call", 12 * MS, 8 * MS), ("other", 0, 30 * MS)]
    ops = [("fusion.1", 4 * MS, 2 * MS), ("while.2", 5 * MS, 3 * MS),
           ("fusion.1", 13 * MS, 1 * MS), ("copy.3", 25 * MS, 5 * MS)]
    mods = [("jit_check(17)", 4 * MS, 4 * MS), ("jit_other", 13 * MS, 1 * MS),
            ("jit_check(17)", 25 * MS, 5 * MS)]
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host + list(extra_host)}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/device:TPU:1", "lines": []},
    ]


def span(name, t0_ms, t1_ms, sid, parent=None):
    """A program span record at trace time t0-t1 ms."""
    rec = {"ph": "X", "name": name, "cat": "host", "id": sid,
           "ts": (t0_ms * MS - OFFSET - telemetry.EPOCH_NS) / 1e3,
           "dur": (t1_ms - t0_ms) * MS / 1e3, "tid": 1}
    if parent is not None:
        rec["parent"] = parent
    return rec


#: Stage spans: the idle 0-4 ms lies under partition and encode, 8-13 ms
#: under none, 14-20 ms under the host tail (recombine innermost at its
#: midpoint); scheduler.run encloses everything and is no stage.
STAGES = [span("scheduler.run", 0, 20, 1),
          span("partition.histories", 0, 3, 2),
          span("encode.walk", 3, 4, 3, 1), span("host.tail", 14, 20, 4),
          span("recombine", 16, 18, 5)]
#: The accepted readers' spans: an encode with a child, which the new
#: stages never are.
ENCODE = [span("encode", 4, 6, 10, 1), span("dispatch", 5, 6, 11, 10)]


def fake_run(trace, program_spans):
    pool = generator.generate(tiny_config("cockroach-register.one-test"), 2,
                              2**31 + 3, 1)
    rec = run.Recorder()
    rec.spans = [("partition", 0.001, 0.003), ("convert", 0.003, 0.004)]
    rec.subs.update({"device": 5, "host-fallback": 1})
    rec.program_spans = program_spans
    calls = [{"t0": (s - OFFSET) / 1e9, "t1": (s + d - OFFSET) / 1e9,
              "n": 1, "batch": [i]}
             for i, (s, d) in enumerate([(0, 10 * MS), (12 * MS, 8 * MS)])]
    return run.Run(calls=calls, window_s=0.02, setup_s=1.0, pool=pool,
                   recorder=rec, window_compiles=0, trace=trace,
                   device={"kind": "TPU v5 lite"}, on_chip=True)


def test_idle_time_is_laid_against_the_stage_spans():
    r = fake_run(Trace(planes()), STAGES)
    assert idle.clock_offset_ns(r.trace, r.calls) == OFFSET
    got = idle.read(r)
    # 15 ms idle (0-4, 8-13, 14-20); 8-13 ms under no stage; 2 histories
    assert got["value"] == pytest.approx(5 / 0.002)
    assert got["share"] == pytest.approx(100.0 * 5 / 15)
    assert got["gaps"] == ("recombine 0.006000; between calls 0.005000; "
                           "partition.histories 0.004000")


def test_idle_gaps_fall_back_to_the_benchmark_spans():
    r = fake_run(Trace(planes()), [span("host.tail", 15, 20, 1)])
    got = idle.read(r)
    # 0-4 ms: bench.partition covers its midpoint; 8-13 ms: no call
    assert got["gaps"] == ("host.tail 0.006000; between calls 0.005000; "
                           "partition 0.004000")
    assert got["value"] == pytest.approx(10 / 0.002)
    assert got["share"] == pytest.approx(100.0 * 10 / 15)


def test_no_reading_without_stages_or_pairing():
    assert idle.read(fake_run(Trace(planes()), [])) is None
    r = fake_run(Trace(planes()), STAGES)
    r.calls = r.calls[:1]                  # annotations and calls unpaired
    assert idle.read(r) is None


def test_spans_laid_by_the_call_pairing_land_on_their_annotations(
        tmp_path):
    """On a real (CPU) profiler trace, the flight recorder's spans laid
    on the trace's clock by the ``bench.call`` pairing start where their
    own ``jt.*`` annotations do."""
    import time

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    telemetry.configure(True)
    telemetry.reset()
    calls = []
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for i in range(7):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                time.sleep(0.001)
                with telemetry.span("stage", rows=i):
                    time.sleep(0.002)
            calls.append({"t0": t0})
    finally:
        jax.profiler.stop_trace()
        rec = run.Recorder()
        rec.program_spans = telemetry.spans()
        telemetry.configure("env")
    f, = tmp_path.glob("**/*.xplane.pb")
    host = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
            for pl in ProfileData.from_file(str(f)).planes
            for ln in pl.lines for ev in ln.events]
    tr = Trace([{"name": "/host:CPU",
                 "lines": [{"name": "python", "events": host}]}])
    offset = idle.clock_offset_ns(tr, calls)
    laid = sorted(s for s, _, _ in idle.stages(run.Run(recorder=rec),
                                               offset))
    want = sorted(s for n, s, _ in host if n == "jt.stage")
    assert len(laid) == len(want) == 7
    assert max(abs(a - b) for a, b in zip(laid, want)) < 100_000   # ns


def test_accepted_readers_unmoved_by_program_events():
    """The same numbers with ``jt.*`` events in the trace and the new
    stage spans in the flight recorder as without them."""
    jt = [("jt." + s["name"], 0, 1 * MS) for s in STAGES + ENCODE]
    plain = fake_run(Trace(planes()), ENCODE)
    more = fake_run(Trace(planes(jt)), ENCODE + STAGES)
    for name in ACCEPTED:
        want = run.module("metrics", name).read(plain)
        assert want is not None, name
        assert run.module("metrics", name).read(more) == want, name
    assert Trace(planes(jt)).breakdown() == Trace(planes()).breakdown()


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_readers_read_on_a_tiny_traced_run(cell):
    m = drive(cell, trace=1)["metrics"]
    for name in ("prog_partition_ms_per_khist", "prog_convert_ms_per_khist",
                 "encode_walk_ms_per_khist", "decode_ms_per_khist",
                 "prog_device_share"):
        assert m[name]["value"] > 0, name
    assert "idle_unattributed_ms_per_khist" not in m      # a device metric
    assert m["prog_device_share"]["value"] == \
        m["device_decided_share"]["value"]
    for prog, bench in (("prog_partition_ms_per_khist",
                         "partition_ms_per_khist"),
                        ("prog_convert_ms_per_khist",
                         "convert_ms_per_khist")):
        assert m[prog]["value"] <= m[bench]["value"]
