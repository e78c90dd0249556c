#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is comes from files found by name: the cell in
``BENCHMARK.json``, its configuration (``configs[].file``), its traffic
mix (``benchmark/traffic/<traffic>.json``), the system under test and
the plain reference the configuration names
(``benchmark/systems/<system>.py``, ``benchmark/references/<reference>.py``)
and one reader per metric (``benchmark/metrics/<metric>.py``).

Set-up (counted in ``setup_s``, from process start): imports, the pool
of distinct histories made from ``--seed``, and one call over every
batch of the pool, which compiles or loads every shape the window uses.
The window is a closed loop of one client: call after call, cycling
through the pool, until ``--seconds`` have passed; it ends when the call
in flight then returns. With ``--trace 1`` the window runs under the
JAX profiler and the benchmark's spans, and the line carries the
cell's per-layer metrics instead of its end-to-end ones.

After the window (outside every metric): the device's peak memory, then
the plain reference over a sample of the pool drawn from the seed, in
worker processes that never touch JAX, and the comparison that decides
``correct``. The last line of standard output is the result; the last
lines of standard error are the numbers compared, each with its limit.
A run that finds no TPU, or fewer chips than the cell asks for, exits 3
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import collections   # noqa: E402
import contextlib    # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: Numbers compared, each with its limit: every one is an exact count,
#: so every limit is 0 (PERF.md gives the readings behind each).
LIMITS = {"unanswered": 0, "verdict_mismatch": 0, "bad_op_mismatch": 0}

#: The traced run profiles the calls of the window's first seconds only:
#: stopping the profiler takes minutes, and past some hundreds of small
#: calls the device trace stops recording (PERF.md, section 6).
TRACE_SECONDS = 3

#: Scheduler counters that mean the device path did not decide cleanly.
RESILIENCE = ("retries", "bisections", "watchdog_fired", "oom_events",
              "quarantined_rows", "prewarm_wedged", "abandoned_buckets")


class Refused(Exception):
    """The run cannot be made here; no result is printed."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as fh:
        return json.load(fh)


def load_cell(name: str) -> dict:
    """The cell, its configuration, traffic and metrics, by name."""
    spec_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(spec_path):
        raise Refused(2, "no BENCHMARK.json at the checkout's root")
    if not os.path.isdir(os.path.join(ROOT, "jepsen_tpu")):
        raise Refused(2, "no jepsen_tpu package beside the benchmark")
    spec = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(2, f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return {"cell": cell, "config": load_json(conf["file"]),
            "traffic": load_json(f"benchmark/traffic/{cell['traffic']}.json"),
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def module(kind: str, name: str):
    return importlib.import_module(f"benchmark.{kind}.{name}")


def sample_rows(pool, n_sample: int, seed: int):
    """The pool rows whose answers are compared: every history the
    generator corrupted and, drawn from the seed, valid ones up to
    ``n_sample`` in all."""
    import numpy as np
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    bad = np.flatnonzero(pool.corrupt)
    rest = np.flatnonzero(~pool.corrupt)
    k = max(0, min(len(rest), n_sample - len(bad)))
    return sorted(bad.tolist() + rng.choice(rest, k, replace=False).tolist())


def reference_verdicts(ref_name: str, pool, rows, *, info_as_fail=False,
                       workers: int = 0) -> dict:
    """{row: (valid, bad)} from the plain reference, rows spread over
    spawned worker processes (which import numpy and the reference,
    never JAX)."""
    ref = module("references", ref_name)
    rows = list(rows)
    workers = workers or min(len(rows), max(1, (os.cpu_count() or 2) - 1))
    if workers <= 1 or len(rows) < 4:
        return ref.check_histories(pool, rows, info_as_fail=info_as_fail)
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor
    parts = [rows[i::workers] for i in range(workers)]
    sub = [pool.take(p) for p in parts]
    out = {}
    with ProcessPoolExecutor(workers, mp_context=mp.get_context("spawn"),
                             initializer=_worker_path,
                             initargs=(ROOT,)) as ex:
        futs = [ex.submit(_ref_part, ref_name, s, info_as_fail)
                for s in sub]
        for p, fut in zip(parts, futs):
            res = fut.result()
            out.update({p[j]: res[j] for j in range(len(p))})
    return out


def _worker_path(root: str) -> None:
    if root not in sys.path:
        sys.path.insert(0, root)


def _ref_part(ref_name, pool, info_as_fail):
    ref = importlib.import_module(f"benchmark.references.{ref_name}")
    return ref.check_histories(pool, range(pool.n),
                               info_as_fail=info_as_fail)


def compare(answers: dict, want: dict) -> dict:
    """The numbers compared. ``answers``: {row: [(valid, bad), ...]}
    over every call of the window that held the row; ``want``: the
    reference's {row: (valid, bad)}. A sampled row the window never
    answered counts as unanswered."""
    c = dict.fromkeys(LIMITS, 0)
    for row, (valid, bad) in want.items():
        got = answers.get(row) or [(None, None)]
        for v, b in got:
            if v is None:
                c["unanswered"] += 1
            elif v != valid:
                c["verdict_mismatch"] += 1
            elif v is False and b != bad:
                c["bad_op_mismatch"] += 1
    return c


class Recorder:
    """The traced run's benchmark spans (perf_counter seconds) and
    per-sub-history provenance counts."""

    def __init__(self):
        self.spans = []
        self.subs = collections.Counter()

    def span(self, name, t0, t1):
        self.spans.append((name, t0, t1))


class Run:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def khist(self) -> float:
        return sum(c["n"] for c in self.calls) / 1000.0


def device_identity() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(n: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:n]:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:       # a backend without memory stats
            pass
    return peak


def sched_counters() -> collections.Counter:
    from jepsen_tpu import telemetry
    out = collections.Counter()
    for k, v in (telemetry.snapshot().get("counters") or {}).items():
        if k.startswith("scheduler."):
            name = k[len("scheduler."):].split("{")[0]
            if name in RESILIENCE:
                out[name] += v
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    """One run of one cell, on the chips it asks for or not at all."""
    args = parse_args(argv)
    try:
        return _run(args)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code


def _run(args) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    c = load_cell(args.workload)
    chips = c["cell"]["chips"]
    # libtpu logs under /tmp/tpu_logs unless told otherwise: a fixed path
    # outside the checkout, which two runs side by side would share.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    dev = device_identity()
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise Refused(3, f"need {chips} TPU chip(s), found {dev}")
    dev["count"] = chips
    # JAX's persistent compilation cache: a fixed directory inside the
    # checkout (the program's own default), whatever the environment says.
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    sut = module("systems", c["config"]["system"])
    return measure(args, c, c["config"], c["traffic"], sut,
                   sut.make(c["config"]), dev)


def measure(args, c, config, traffic, sut, call, dev) -> int:
    """Set-up from the pool on, the window, the comparison and the
    result line. ``call`` is the timed entry (``sut.make(config)``) and
    ``dev`` the devices it runs on; off a TPU no device metric is
    read (the benchmark's own tests drive this on the CPU)."""
    import jax
    on_chip = dev["platform"] == "tpu"

    # Every program built, compiled or loaded from the persistent cache
    # (JAX times both as a backend compile), and the cache's hits.
    compiles = {"n": 0, "hits": 0}

    def on_compile(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            compiles["hits"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_event)

    from benchmark import generator
    per = traffic["histories_per_call"]
    marks = {"imports": time.perf_counter() - T_START}
    pool = generator.generate(config, per * traffic["pool_calls"],
                              args.seed, traffic["corrupt_histories"])
    hists = [pool.op_list(i) for i in range(pool.n)]
    batches = [list(range(i, i + per)) for i in range(0, pool.n, per)]
    marks["pool"] = time.perf_counter() - T_START
    for b in batches:                                  # warm-up
        call([hists[i] for i in b])
    setup_s = time.perf_counter() - T_START
    marks["setup_programs"] = compiles["n"]
    marks["setup_cache_hits"] = compiles["hits"]

    rec = Recorder() if args.trace else None
    trace_dir = os.path.join(BENCH, ".trace", f"{args.workload}.{os.getpid()}")
    answers = collections.defaultdict(list)
    calls = []
    failed = 0
    c0, res0 = compiles["n"], sched_counters()
    tracer = _Tracer(args.trace, trace_dir, sut, rec)
    n_traced = 0
    try:
        tracer.start()
        t_win = time.perf_counter()
        j = 0
        while True:
            b = batches[j % len(batches)]
            j += 1
            before = sched_counters()
            t0 = time.perf_counter()
            try:
                with tracer.call():
                    out = call([hists[i] for i in b])
            except Exception as e:          # the call's answers never came
                print(f"benchmark: call raised {e!r}", file=sys.stderr)
                out = None
            t1 = time.perf_counter()
            if out is None or len(out) != len(b) or \
                    sched_counters() - before:
                failed += len(b)
            for k, i in enumerate(b):
                answers[i].append(sut.verdict(out[k]) if out is not None
                                  and k < len(out) else (None, None))
            calls.append({"t0": t0, "t1": t1, "n": len(b), "batch": b})
            if tracer.live and t1 - t_win >= min(TRACE_SECONDS, args.seconds):
                t0 = time.perf_counter()
                tracer.stop()
                marks["trace_stop_s"] = time.perf_counter() - t0
                n_traced = len(calls)
            if t1 - t_win >= args.seconds:
                break
        window_s = calls[-1]["t1"] - t_win
    finally:
        tracer.stop()
    window_compiles = compiles["n"] - c0
    resilience = dict(sched_counters() - res0)
    peak = memory_peak(dev["count"]) if on_chip else 0

    if args.trace:       # per-layer metrics read the traced calls only
        calls_read = calls[:n_traced]
        window_read = calls_read[-1]["t1"] - t_win
    else:
        calls_read, window_read = calls, window_s
    run = Run(calls=calls_read, window_s=window_read, setup_s=setup_s,
              pool=pool, recorder=rec, window_compiles=window_compiles,
              device=dev, on_chip=on_chip)
    metrics, breakdown = {}, None
    if args.trace:
        from benchmark import devtrace
        t0 = time.perf_counter()
        run.trace = devtrace.load(trace_dir) if on_chip else None
        marks["trace_load_s"] = time.perf_counter() - t0
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in c["per_layer"]:
            _put(metrics, m, _read(m, run))
        if run.trace is not None:
            print(f"benchmark: trace of {n_traced} calls: "
                  f"{run.trace.coverage()}", file=sys.stderr)
            dev["busy_s"] = run.trace.busy_s
            dev["window_s"] = run.trace.window_s
            breakdown = run.trace.breakdown()
    else:
        for m in c["end_to_end"]:
            _put(metrics, m, _read(m, run))
    dev["memory_peak_bytes"] = peak
    del hists, call

    rows = sample_rows(pool, traffic["sample_histories"], args.seed)
    t0 = time.perf_counter()
    want = reference_verdicts(config["reference"], pool, rows)
    marks["reference_s"] = time.perf_counter() - t0
    cmp_ = compare(answers, want)
    correct = all(cmp_[k] <= LIMITS[k] for k in LIMITS)
    compared = {k: {"value": cmp_[k], "limit": LIMITS[k]} for k in LIMITS}
    line = {"correct": correct, "attempted": sum(x["n"] for x in calls),
            "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print("benchmark: " + ", ".join(f"{k} {v:.3f}" for k, v in
                                    marks.items()), file=sys.stderr)
    print(f"benchmark: window {window_s:.3f} s, {len(calls)} calls, "
          f"{window_compiles} compiles in the window, resilience "
          f"{resilience or 'none'}, {len(rows)} histories compared over "
          f"{sum(len(answers.get(r, [])) for r in rows)} answers",
          file=sys.stderr)
    for k in LIMITS:
        print(f"compared {k} = {cmp_[k]} (limit {LIMITS[k]})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def _put(metrics: dict, m: dict, v) -> None:
    """A reading into the line: a number, or a dict with ``value`` and
    keys of its own (``wgl_roofline``'s ``bound``); nothing for None."""
    if v is None:
        return
    extra = v if isinstance(v, dict) else {"value": v}
    metrics[m["name"]] = {"value": extra["value"], "unit": m["unit"],
                          **{k: x for k, x in extra.items() if k != "value"}}


def _read(m: dict, run):
    """A metric from its reader; device-trace metrics only from a chip."""
    if m["source"] == "device_trace" and not run.on_chip:
        return None
    return module("metrics", m["name"]).read(run)


class _Tracer:
    """The traced part of the window: JAX profiler, program spans and the
    benchmark's own spans over the calls of its first ``TRACE_SECONDS``;
    nothing at all with ``--trace 0``."""

    def __init__(self, on, trace_dir, sut, rec):
        self.on, self.dir, self.sut, self.rec = on, trace_dir, sut, rec
        self.live = False

    def call(self):
        if not self.live:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench.call")

    def start(self):
        if not self.on:
            return
        import jax
        from jepsen_tpu import telemetry
        telemetry.configure(True)
        telemetry.reset()
        self.spans = self.sut.spans(self.rec)
        self.spans.__enter__()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1          # annotations, not JAX's own
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.live = True

    def stop(self):
        if not self.live:
            return
        import jax
        from jepsen_tpu import telemetry
        self.live = False
        jax.profiler.stop_trace()
        self.spans.__exit__(None, None, None)
        self.rec.program_spans = telemetry.spans()
        telemetry.configure(False)


if __name__ == "__main__":
    sys.exit(main())
