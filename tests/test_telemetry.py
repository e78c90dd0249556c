"""Telemetry spine: span tracer, metrics registry, gap analyzer.

Pins the observability contracts (doc/observability.md): span
nesting/attributes, ring-buffer wraparound, Chrome-trace export
validity, registry snapshot determinism, the zero-allocation no-op when
JT_TRACE is unset, the traced-overhead budget, end-to-end span coverage
of the checked path (encode → dispatch → decode → journal per chunk),
the Op-list path's stage spans and provenance counter, the ``jt.*``
profiler annotations (and none with tracing off), the thread-safe
scheduler stats the registry replaced, the results.json
``telemetry`` block with its source tag, and the web ``/live`` view.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from jepsen_tpu import telemetry

pytestmark = pytest.mark.telemetry


@pytest.fixture
def traced():
    """Tracer on (flight recorder only), restored to the env default
    (JT_TRACE=0 under tier-1) afterwards."""
    telemetry.configure(True)
    telemetry.reset()
    yield telemetry
    telemetry.configure("env")


# ------------------------------------------------------------- tracer

def test_span_nesting_and_attributes(traced):
    with telemetry.span("outer", W=9, rows=128) as outer:
        with telemetry.span("inner", cat="device", chunk=3):
            time.sleep(0.001)
        outer.set(late=True)
    recs = telemetry.spans()
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert inner["cat"] == "device" and outer["cat"] == "host"
    assert inner["args"] == {"chunk": 3}
    assert outer["args"] == {"W": 9, "rows": 128, "late": True}
    # The inner span's parent is the outer span, and it nests in time.
    assert inner["parent"] == outer["id"]
    assert inner["ts"] >= outer["ts"]
    assert inner["dur"] >= 1000        # slept 1ms; durations are µs
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_sibling_spans_share_parent(traced):
    with telemetry.span("root"):
        with telemetry.span("a"):
            pass
        with telemetry.span("b"):
            pass
    a, b, root = telemetry.spans()
    assert (a["name"], b["name"], root["name"]) == ("a", "b", "root")
    assert a["parent"] == root["id"] and b["parent"] == root["id"]


def test_events_record_instants(traced):
    telemetry.event("scheduler.retry", W=7, attempt=1)
    recs = telemetry.spans()
    assert len(recs) == 1 and recs[0]["ph"] == "i"
    assert recs[0]["args"] == {"W": 7, "attempt": 1}


def test_ring_buffer_wraparound():
    telemetry.configure(True, ring=16)
    try:
        for i in range(50):
            with telemetry.span("s", i=i):
                pass
        recs = telemetry.spans()
        assert len(recs) == 16
        # The flight recorder keeps the NEWEST spans.
        assert [r["args"]["i"] for r in recs] == list(range(34, 50))
    finally:
        telemetry.configure("env")


def test_chrome_export_is_loadable(traced, tmp_path):
    with telemetry.span("dispatch", cat="device", W=8):
        pass
    telemetry.event("scheduler.retry")
    out = tmp_path / "trace.json"
    n = telemetry.export_chrome(out)
    doc = json.loads(out.read_text())      # valid JSON, full stop
    evs = doc["traceEvents"]
    assert n == len(evs) and n >= 2
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(
        {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        for e in xs)
    # Instant events carry a scope, metadata names the threads.
    assert any(e["ph"] == "i" and e["s"] == "t" for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in evs)


def test_noop_when_disabled():
    telemetry.configure(False)
    try:
        assert not telemetry.enabled()
        # span()/begin() return the one shared singleton — no Span
        # object, no record, nothing retained.
        s1 = telemetry.span("x", W=9)
        s2 = telemetry.begin("y")
        assert s1 is telemetry.NOP and s2 is telemetry.NOP
        with telemetry.span("z") as sp:
            sp.set(rows=1)
        telemetry.event("e", n=1)
        assert telemetry.spans() == []
    finally:
        telemetry.configure("env")


def test_jsonl_sink_round_trip(tmp_path):
    sink = tmp_path / "run.trace.jsonl"
    telemetry.configure(str(sink))
    try:
        with telemetry.span("encode", W=5):
            pass
        telemetry.event("ping")
        telemetry.flush()
        recs = telemetry.read_trace(sink)
        assert [r["name"] for r in recs] == ["encode", "ping"]
        s = telemetry.summarize(recs)
        assert s["spans"] == 1 and s["events"] == 1
        assert s["by_name"]["encode"]["count"] == 1
    finally:
        telemetry.configure("env")


def test_traced_overhead_budget(traced):
    """The 5% overhead budget: a span around real work (the bench-loop
    shape — milliseconds of numpy per span) must not slow it
    measurably. Best-of-5 on both sides irons out scheduler jitter."""
    x = np.random.default_rng(0).integers(0, 1 << 30, 100_000)

    def work():
        return int(np.sort(x)[0])

    def loop(trace):
        t0 = time.perf_counter()
        for i in range(30):
            if trace:
                with telemetry.span("w", i=i):
                    work()
            else:
                work()
        return time.perf_counter() - t0

    loop(True)                        # warm both paths
    loop(False)
    off = min(loop(False) for _ in range(5))
    on = min(loop(True) for _ in range(5))
    assert on <= off * 1.05 + 0.010, (on, off)


# ------------------------------------------------------- gap analyzer

def _spanrec(name, cat, t0_us, dur_us):
    return {"ph": "X", "name": name, "cat": cat, "ts": t0_us,
            "dur": dur_us, "tid": 1}


def test_gap_report_math():
    recs = [
        _spanrec("dispatch", "device", 0, 100),
        _spanrec("dispatch", "device", 300, 100),    # gap 100..300
        _spanrec("encode", "host", 120, 150),        # covers 150 of it
        _spanrec("dispatch", "device", 400, 100),    # contiguous
        # Wrapper spans that CONTAIN device intervals (scheduler.run,
        # run.case...) must not soak up attribution — they enclose
        # every gap by construction and would always top the ranking.
        _spanrec("scheduler.run", "host", 0, 500),
    ]
    g = telemetry.gaps(recs)
    assert "scheduler.run" not in dict(g["top_gap_causes"])
    assert g["n_gaps"] == 1
    assert g["window_s"] == pytest.approx(500 / 1e6)
    assert g["device_busy_s"] == pytest.approx(300 / 1e6)
    assert g["host_gap_s"] == pytest.approx(200 / 1e6)
    assert g["device_busy_frac"] == pytest.approx(0.6)
    assert g["host_gap_frac"] == pytest.approx(0.4)
    causes = dict((k, v) for k, v in g["top_gap_causes"])
    assert causes["encode"] == pytest.approx(150 / 1e6)
    assert causes["(untraced)"] == pytest.approx(50 / 1e6)


def test_gap_report_empty():
    g = telemetry.gaps([])
    assert g["n_gaps"] == 0 and g["device_busy_frac"] is None


# ---------------------------------------------------- metrics registry

def test_registry_snapshot_deterministic():
    reg = telemetry.Registry()
    # Insertion order scrambled on purpose: snapshots sort.
    reg.counter("z.last").inc()
    reg.counter("scheduler.retries", family="wgl").inc(2)
    reg.counter("scheduler.retries", family="graph").inc()
    reg.gauge("wal.ops").set(42)
    for v in (5.0, 1.0, 3.0):
        reg.histogram("wal.flush_ms").observe(v)
    s1, s2 = reg.snapshot(), reg.snapshot()
    assert s1 == s2
    assert json.dumps(s1) == json.dumps(s2)      # stable serialization
    assert list(s1["counters"]) == sorted(s1["counters"])
    assert s1["counters"]["scheduler.retries{family=wgl}"] == 2
    assert s1["counters"]["scheduler.retries{family=graph}"] == 1
    assert s1["gauges"]["wal.ops"] == 42
    h = s1["histograms"]["wal.flush_ms"]
    assert h["count"] == 3 and h["sum"] == 9.0
    assert h["min"] == 1.0 and h["max"] == 5.0 and h["p50"] == 3.0
    assert telemetry.Registry().snapshot() == {}   # empty stays empty


def test_registry_concurrent_increments():
    """The BucketScheduler.stats race, fixed: N threads hammering one
    counter must lose zero increments."""
    reg = telemetry.Registry()

    def bump():
        c = reg.counter("hot", family="wgl")
        for _ in range(2000):
            c.inc()

    ts = [threading.Thread(target=bump) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.get("hot", family="wgl") == 16000


def test_scheduler_inc_thread_safe():
    from jepsen_tpu.ops.schedule import BucketScheduler
    sch = BucketScheduler(prewarm=False)

    def bump():
        for _ in range(2000):
            sch._inc("retries")

    ts = [threading.Thread(target=bump) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sch.stats["retries"] == 16000


# --------------------------------------- end-to-end span coverage

def test_checked_path_span_coverage(traced, tmp_path):
    """One journaled columnar check emits encode, dispatch, decode and
    journal spans for every chunk — the acceptance spine — and the gap
    analyzer sees a non-degenerate device window."""
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops.linearize import check_columnar
    from jepsen_tpu.store import ChunkJournal
    from jepsen_tpu.workloads.synth import synth_cas_columnar

    cols = synth_cas_columnar(48, seed=5, n_procs=3, n_ops=30,
                              n_values=3, corrupt=0.2)
    j = ChunkJournal(tmp_path / "j.jsonl", {"t": "telemetry"})
    valid, bad = check_columnar(cas_register(), cols, journal=j)
    j.finish()
    assert len(valid) == 48
    recs = telemetry.spans()
    names = {r["name"] for r in recs if r["ph"] == "X"}
    assert {"encode", "dispatch", "decode", "journal",
            "scheduler.run"} <= names
    # Every dispatch span is device-category and carries its W class,
    # rows, and chunk ordinal (wide-route dispatches carry V/W/rows).
    disp = [r for r in recs
            if r["name"] == "dispatch" and r["ph"] == "X"]
    assert disp
    assert all(r["cat"] == "device" for r in disp)
    chunked = [r for r in disp if "chunk" in r["args"]]
    assert chunked and all(
        {"V", "W", "rows"} <= set(r["args"]) for r in chunked)
    # Chunk ordinals are unique per scheduler run.
    ords = [r["args"]["chunk"] for r in chunked]
    assert len(set(ords)) == len(ords)
    g = telemetry.gaps()
    assert g["device_busy_frac"] is not None
    assert 0.0 <= g["device_busy_frac"] <= 1.0
    # The registry saw the same run: dispatch/chunk counters moved.
    snap = telemetry.snapshot()
    assert snap["counters"]["scheduler.dispatches{family=wgl}"] >= 1
    assert snap["counters"]["journal.rows"] >= 48


def test_graph_path_span_coverage(traced):
    from jepsen_tpu.checkers.cycle import check_graphs_batch
    from jepsen_tpu.ops.graph import extract_graph
    from jepsen_tpu.workloads.synth import synth_la_history

    hs = [synth_la_history(s, n_ops=12,
                           corrupt=1.0 if s % 3 == 0 else 0.0)
          for s in range(6)]
    rs = check_graphs_batch([extract_graph(h, "list-append")
                             for h in hs])
    assert len(rs) == 6
    names = {r["name"] for r in telemetry.spans() if r["ph"] == "X"}
    assert {"graph.pack", "encode", "dispatch", "decode"} <= names
    disp = [r for r in telemetry.spans()
            if r["name"] == "dispatch"
            and r.get("args", {}).get("family") == "graph"]
    assert disp and all(r["cat"] == "device" for r in disp)


def _kv_batch():
    """Four 3-key KV histories; one key of the first is corrupted."""
    from jepsen_tpu.ops.partition import merge_kv_histories
    from jepsen_tpu.workloads.synth import synth_cas_history
    return [merge_kv_histories(
        {k: synth_cas_history(300 + 10 * i + k, n_procs=3, n_ops=8,
                              corrupt=1.0 if (i, k) == (0, 0) else 0.0)
         for k in range(3)}) for i in range(4)]


def test_oplist_path_stage_spans_and_provenance_counter(traced):
    """The Op-list checked path emits one span per stage, from partition
    to recombine, none inside an ``encode`` span, and counts every
    sub-history under its provenance."""
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops.linearize import check_batch_columnar

    rs = check_batch_columnar(cas_register(), _kv_batch(),
                              details="invalid")
    assert rs[0]["valid"] is False
    assert all(r["valid"] is True for r in rs[1:])
    recs = [r for r in telemetry.spans() if r["ph"] == "X"]
    names = {r["name"] for r in recs}
    assert {"partition.histories", "columnar.convert", "encode.walk",
            "scheduler.plan", "decode.details", "host.tail",
            "recombine"} <= names
    enc = {r["id"] for r in recs if r["name"] == "encode"}
    assert enc and not any(r.get("parent") in enc for r in recs)
    subs = {k: v for k, v in telemetry.traced_counters().items()
            if k.startswith("check.subs{")}
    assert sum(subs.values()) == 12            # 4 histories x 3 keys
    assert subs.get("check.subs{provenance=device}", 0) >= 9


def test_decode_self_time_leaves_out_the_device_wait(traced):
    """The read-back, which blocks on the device, is ``decode.fetch``,
    a child of ``decode``: the self time of ``decode`` is host work."""
    from types import SimpleNamespace

    from jepsen_tpu.ops.linearize import INT32_MAX
    from jepsen_tpu.ops.schedule import BucketScheduler

    class Pending:
        """A device output still computing: reading it, or waiting for
        it, takes 0.2 s the first time."""
        def __init__(self, a):
            self.a, self.ready = a, False

        def block_until_ready(self):
            if not self.ready:
                time.sleep(0.2)
                self.ready = True
            return self

        def __array__(self, dtype=None, copy=None):
            return self.block_until_ready().a

    out = (Pending(np.ones(4, bool)),
           Pending(np.full(4, INT32_MAX, np.int32)), None)
    v, b, fr = BucketScheduler()._decode_member(
        out, 3, SimpleNamespace(V=2, W=3, n_events=8))
    assert v.tolist() == [True] * 3 and fr is None
    recs = telemetry.spans()
    dec, = [r for r in recs if r["name"] == "decode"]
    fetch, = [r for r in recs if r["name"] == "decode.fetch"]
    assert fetch["parent"] == dec["id"] and fetch["dur"] >= 200_000  # µs
    assert dec["dur"] - fetch["dur"] < 200_000


def test_traced_counters_cover_the_traced_stretch():
    c = telemetry.REGISTRY.counter("test.stretch")
    telemetry.configure(False)
    try:
        c.inc(5)
        telemetry.configure(True)
        c.inc(2)
        assert telemetry.traced_counters()["test.stretch"] == 2
        telemetry.configure(False)
        c.inc(7)
        assert telemetry.traced_counters()["test.stretch"] == 2
    finally:
        telemetry.configure("env")


# ------------------------------------------------ profiler bridge

def _profiled(out_dir, body):
    """Run ``body`` under a CPU profiler session; the ``jt.*`` host
    events it recorded as (span name, start ns, end ns, stats), by
    start."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    f, = out_dir.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(f))
    return sorted(((ev.name[3:], ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats))
                   for pl in pd.planes for ln in pl.lines
                   for ev in ln.events if ev.name.startswith("jt.")),
                  key=lambda e: e[1])


def test_spans_reach_the_profiler_trace(traced, tmp_path):
    """Under a profiler session every span is a ``jt.<name>`` host event
    with its cheap integer attributes, nested as the spans are,
    including a ``begin`` span held across a generator's yields."""
    def drive():
        sp = telemetry.begin("drive", W=7)
        try:
            for i in range(3):
                with telemetry.span("step", chunk=i, tag="x"):
                    time.sleep(0.001)
                yield i
        finally:
            sp.end()

    def body():
        with telemetry.span("outer", rows=4):
            with telemetry.span("inner"):
                time.sleep(0.001)
        for _ in drive():
            with telemetry.span("between"):
                time.sleep(0.001)

    evs = _profiled(tmp_path, body)
    by = {}
    for name, s, e, stats in evs:
        by.setdefault(name, []).append((s, e, stats))
    assert sorted(by) == ["between", "drive", "inner", "outer", "step"]
    (o0, o1, ostats), = by["outer"]
    (i0, i1, _), = by["inner"]
    assert o0 <= i0 and i1 <= o1 and ostats == {"rows": 4}
    (d0, d1, dstats), = by["drive"]
    assert dstats == {"W": 7}
    assert [st for *_, st in by["step"]] == [{"chunk": i} for i in range(3)]
    assert len(by["between"]) == 3
    assert all(d0 <= s and e <= d1 for s, e, _ in by["step"] + by["between"])
    # The flight recorder holds the same spans.
    assert sorted(r["name"] for r in telemetry.spans()) == sorted(
        name for name, *_ in evs)


def test_span_ended_on_another_thread_gets_no_annotation(traced,
                                                         tmp_path):
    def body():
        box = []
        t = threading.Thread(
            target=lambda: box.append(telemetry.begin("handoff")))
        t.start()
        t.join()
        box[0].end()
        with telemetry.span("local"):
            pass

    evs = _profiled(tmp_path, body)
    assert [name for name, *_ in evs] == ["local"]
    assert {r["name"] for r in telemetry.spans()} == {"handoff", "local"}


def test_no_annotation_is_built_with_tracing_off(tmp_path, monkeypatch):
    """Tracing off, the checked path's span sites build no annotation
    even under a profiler session: ``span``/``begin`` return the no-op
    after the one ``enabled`` predicate."""
    import jax
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops.linearize import check_batch_columnar

    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(telemetry, "_TRACE_ME", Counting)
    hists = _kv_batch()
    telemetry.configure(False)
    try:
        def off():
            assert telemetry.span("x") is telemetry.NOP
            assert telemetry.begin("y") is telemetry.NOP
            check_batch_columnar(cas_register(), hists, details="invalid")

        assert _profiled(tmp_path / "off", off) == []
        assert built == []
        telemetry.configure(True)
        evs = _profiled(tmp_path / "on", lambda: check_batch_columnar(
            cas_register(), hists, details="invalid"))
        assert built and len(built) == len(evs)
        assert "jt.partition.histories" in built
    finally:
        telemetry.configure("env")


# ------------------------------------------- results.json integration

def test_save_results_telemetry_block(tmp_path):
    from jepsen_tpu.store import Store

    store = Store(tmp_path / "store")
    h = store.create("tel-live")
    # Counters are per-RUN deltas against the handle's creation-time
    # baseline — the process-cumulative registry must not re-report
    # earlier runs' traffic as this run's.
    telemetry.REGISTRY.counter("scheduler.dispatches",
                               family="wgl").inc(3)
    h.save_results({"valid": True})
    res = json.loads((h.dir / "results.json").read_text())
    tel = res["telemetry"]
    assert tel["source"] == "live"
    assert tel["counters"]["scheduler.dispatches{family=wgl}"] == 3
    # A salvaged run's results are tagged distinguishably.
    h2 = store.create("tel-salvaged")
    (h2.dir / "salvage.json").write_text("{}")
    telemetry.REGISTRY.counter("journal.rows").inc(2)
    h2.save_results({"valid": True})
    res2 = json.loads((h2.dir / "results.json").read_text())
    assert res2["telemetry"]["source"] == "salvaged"
    # A caller-provided telemetry block wins untouched.
    h3 = store.create("tel-explicit")
    h3.save_results({"valid": True, "telemetry": {"source": "custom"}})
    res3 = json.loads((h3.dir / "results.json").read_text())
    assert res3["telemetry"] == {"source": "custom"}


def test_recheck_carries_source_tag(tmp_path):
    from jepsen_tpu.history.core import index
    from jepsen_tpu.history.ops import invoke_op, ok_op
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.store import Store

    h = index([invoke_op(0, "write", 1), ok_op(0, "write", 1),
               invoke_op(1, "read", 1), ok_op(1, "read", 1)])
    store = Store(tmp_path / "store")
    store.create("rt", ts="r0").save_history(h)
    out = store.recheck("rt", cas_register())
    assert out["valid"] is True
    assert out["telemetry"]["source"] == "recheck"
    assert "salvaged_runs" not in out["telemetry"]
    # Salvaged runs in the recheck set are named.
    (store.run_dir("rt", "r0") / "salvage.json").write_text("{}")
    out = store.recheck("rt", cas_register())
    assert out["telemetry"]["salvaged_runs"] == ["r0"]


# --------------------------------------------------------- CLI + web

def test_trace_cli_summary_and_export(tmp_path, capsys):
    from jepsen_tpu.cli import main

    sink = tmp_path / "t.jsonl"
    telemetry.configure(str(sink))
    try:
        with telemetry.span("dispatch", cat="device", W=6):
            pass
        with telemetry.span("encode"):
            pass
        telemetry.flush()
    finally:
        telemetry.configure("env")
    out_json = tmp_path / "trace.json"
    with pytest.raises(SystemExit) as e:
        main(["trace", "--file", str(sink), "--gaps",
              "--export", str(out_json)])
    assert e.value.code == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["spans"] == 2
    assert "dispatch" in line["by_name"] and "encode" in line["by_name"]
    assert line["gaps"]["device_busy_frac"] is not None
    assert line["trace_events"] >= 2
    assert json.loads(out_json.read_text())["traceEvents"]


def test_web_live_view_and_incomplete_badge(tmp_path):
    from jepsen_tpu.history.wal import WAL_FILE, HistoryWAL
    from jepsen_tpu.store import Store
    from jepsen_tpu.web import serve

    store = Store(tmp_path / "store")
    # A crashed run: live WAL on disk, no results.json, writer pid
    # dead. The WAL is written here (so the header carries THIS pid —
    # which would badge "live": an in-process server IS the writer),
    # then the header pid is rewritten to a long-gone pid to simulate
    # the crash.
    h = store.create("crashy")
    wal = HistoryWAL(h.path(WAL_FILE), header={"seed": 7})
    wal.stamp_phase("run")
    from jepsen_tpu.history.ops import invoke_op, ok_op
    wal.append_op(invoke_op(0, "write", 1))
    wal.append_op(ok_op(0, "write", 1))
    wal.close()
    wal_path = h.dir / WAL_FILE
    head, rest = wal_path.read_bytes().split(b"\n", 1)
    hd = json.loads(head)
    hd["pid"] = (1 << 22) - 3            # no such process
    wal_path.write_bytes(json.dumps(hd).encode() + b"\n" + rest)
    # An IN-FLIGHT run whose writer is this very process: badged live.
    h2 = store.create("inflight")
    wal2 = HistoryWAL(h2.path(WAL_FILE), header={"seed": 8})
    wal2.stamp_phase("run")
    # And one completed run for contrast.
    done = store.create("done")
    done.save_history([])
    done.save_results({"valid": True})

    srv = serve(host="127.0.0.1", port=0, store=store)
    try:
        port = srv.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as r:
                return r.status, r.read()

        status, body = get("/")
        assert status == 200
        assert b"badge-crashed" in body       # the distinct badge
        assert b"badge-live" in body          # own-process writer
        assert b"valid-incomplete" in body
        assert b'href="/live"' in body

        status, body = get("/live")
        assert status == 200
        assert b"crashy" in body and b"phase" in body
        assert b"run" in body                 # the WAL's last phase
        assert b"crashed" in body
        assert b"inflight" in body and b"badge-live" in body
        # Incremental progress: more ops appended show up next poll.
        wal2.append_op(invoke_op(1, "read", None))
        wal2.append_op(ok_op(1, "read", 1))
        wal2.close()
        _, body2 = get("/live")
        assert b"inflight" in body2
    finally:
        srv.shutdown()
        wal2.close()
