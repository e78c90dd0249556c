#!/usr/bin/env python3
"""Bring-up smoke of the checker's main path on a TPU chip.

One process drives the analysis layer through the entry points a user
calls, at the north-star deployment size, and holds every verdict to
an independent oracle:

  0. identity  — the first device must be a TPU; anything else exits
     non-zero before any result is printed;
  1. jepsen    — ``jepsen-tpu test --suite etcd-casd --seeds 4`` (local
     casd nodes, generator, nemesis, WAL, one pooled device dispatch)
     and ``recheck --model cas`` of that store, in this process;
  2. headline  — 10,000 keyed CAS-register histories of 1,000 lines
     (bench.py's defaults) through ``check_batch_columnar``, valid and
     first bad op compared with the native C++ engine on every row;
  3. backends  — the W<=10 rows forced through each WGL device backend
     (pallas, dc, xla), compared field by field;
  4. families  — 2,000 list-append graphs through the cycle checker and
     512 transactional histories through the isolation certifier, each
     against its host oracle.

Each phase prints one JSON line (rows, wall and compile seconds,
parity, provenance counts, scheduler resilience counters). Any
retry, bisection, watchdog, OOM, quarantine, wedged pre-warm or
abandoned bucket fails the run: on this path the device must decide.
The last line is ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the multi-chip path (data- and frontier-sharded
routes over the host's four chips, each compared with one device and
the host oracle).

Usage: ``python chip_smoke.py [--chips 4]`` on the chip host.
"""
import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import time
from collections import Counter

REPO = os.path.dirname(os.path.abspath(__file__))

RESILIENCE = ("retries", "bisections", "watchdog_fired", "oom_events",
              "quarantined_rows", "prewarm_wedged", "abandoned_buckets")


class Meter:
    """Per-phase wall clock, backend compile seconds and persistent
    compile-cache hits (jax.monitoring), and scheduler counters (the
    process telemetry registry, summed over families)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _ev(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @staticmethod
    def _sched():
        from jepsen_tpu import telemetry
        out = Counter()
        for k, v in (telemetry.snapshot().get("counters") or {}).items():
            if k.startswith("scheduler."):
                out[k[len("scheduler."):].split("{")[0]] += v
        return out

    @contextlib.contextmanager
    def phase(self, rec: dict):
        c0, h0, s0 = self.compile_s, self.cache_hits, self._sched()
        t0 = time.perf_counter()
        try:
            yield rec
        finally:
            rec["wall_s"] = time.perf_counter() - t0
            rec["compile_s"] = self.compile_s - c0
            rec["cache_hits"] = self.cache_hits - h0
            d = self._sched() - s0
            rec["resilience"] = {k: int(d.get(k, 0)) for k in RESILIENCE}


def provenance(results) -> dict:
    """Counts of where each verdict came from; host fallbacks keyed by
    their reason, so capability routing is shown, never hidden. Rows
    with no reason were routed by design (the exact re-derivation of a
    bad op inside a fused run, small or wide buckets sent to the native
    engine); device errors always carry ``quarantined: ...``."""
    c = Counter()
    for r in results:
        p = r.get("provenance") or "device"
        if p == "host-fallback":
            p = f"host-fallback:{r.get('fallback', 'routed')}"
        c[p] += 1
    return dict(sorted(c.items()))


def verdict(r) -> tuple:
    return (r["valid"], (r.get("op") or {}).get("index"))


def emit(rec: dict) -> None:
    print(json.dumps(rec, default=str), flush=True)


def require(rec: dict) -> None:
    """A phase passes only with full parity, no device-error fallback
    and every resilience counter at zero."""
    bad = [k for k, v in rec["resilience"].items() if v]
    if bad:
        raise SystemExit(f"{rec['phase']}: resilience counters {bad}")
    if any(k.startswith("host-fallback:quarantined")
           for k in rec.get("provenance", {})):
        raise SystemExit(f"{rec['phase']}: device-error host fallback")
    if rec.get("parity") is not True:
        raise SystemExit(f"{rec['phase']}: parity {rec.get('parity')}")


# --------------------------------------------------------------- phases

def phase_jepsen(meter: Meter, work: str, seeds: int = 4) -> dict:
    """A real Jepsen campaign through the CLI, then a store recheck,
    each against its own reference: the suite's pooled device verdicts
    must be valid (casd is linearizable), and each recheck's per-run
    verdicts must equal the host engine's over the stored histories."""
    from jepsen_tpu import cli
    from jepsen_tpu.checkers.linearizable import wgl_check
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops.partition import partition_histories
    from jepsen_tpu.store import Store
    from jepsen_tpu.suites.etcd import ABSENT

    def run(argv):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            try:
                cli.main(argv)
                rc = 0
            except SystemExit as e:
                rc = e.code
        lines = [ln for ln in buf.getvalue().splitlines()
                 if ln.startswith("{")]
        return rc, json.loads(lines[-1]) if lines else {}

    rec = {"phase": "jepsen", "seeds": seeds}
    with meter.phase(rec):
        rc, out = run(["test", "--suite", "etcd-casd", "--n-ops", "40",
                       "--ops-per-key", "20", "--threads-per-key", "2",
                       "--base-port", "25310", "--time-limit", "8",
                       "--seeds", str(seeds),
                       "--casd-dir", os.path.join(work, "casd")])
        rec["test_rc"] = rc
        rec["test_valid"] = [s["valid"] for s in out["seeds"].values()]
        # The suite's own model (registers start ABSENT) and the plain
        # cas model, under which a read of a never-written key is a
        # violation: each recheck must match the host engine.
        store = Store()
        runs = store.tests()["etcd-casd"]
        hs = [store.load("etcd-casd", ts)["history"] for ts in runs]
        subs = [partition_histories([h])[0] for h in hs]
        rec["rows"] = len(runs)
        rec["recheck"], parity = {}, []
        for name, model in (("cas-absent", cas_register(ABSENT)),
                            ("cas", cas_register())):
            _, rout = run(["recheck", "--test", "etcd-casd",
                           "--model", name])
            host = {ts: all(wgl_check(model, s)["valid"] is True
                            for s in ss) for ts, ss in zip(runs, subs)}
            rec["recheck"][name] = rout.get("runs")
            parity.append(rout.get("runs") == host)
        rec["parity"] = (rc == 0 and len(runs) == seeds and all(parity)
                         and all(v is True for v in rec["test_valid"]))
    return rec


def keyed_histories(cols):
    """Columnar keyed batch -> KV-valued Op-list histories, the form a
    recorded ``independent`` workload takes."""
    from jepsen_tpu.history.columnar import PAD, columnar_to_ops
    from jepsen_tpu.independent import KV
    out = []
    for i in range(cols.batch):
        keys = cols.key[i][cols.type[i] != PAD].tolist()
        out.append([op.with_(value=KV(k, op.value))
                    for op, k in zip(columnar_to_ops(cols, i), keys)])
    return out


def phase_headline(meter: Meter, n_hist: int = 10_000,
                   n_ops: int = 500, n_keys: int = 8) -> tuple:
    """The north-star batch through check_batch_columnar (default
    scheduler), every row against the native engine. Returns the
    phase record and the per-key sub-histories for phase 3."""
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.native import check_batch_native
    from jepsen_tpu.ops.linearize import check_batch_columnar
    from jepsen_tpu.ops.partition import (partition_histories,
                                          recombine_details)
    from jepsen_tpu.workloads.synth import synth_cas_columnar

    model = cas_register()
    rec = {"phase": "headline", "rows": n_hist,
           "lines_per_history": 2 * n_ops, "keys": n_keys}
    t0 = time.perf_counter()
    cols = synth_cas_columnar(n_hist, seed=1, n_procs=5, n_ops=n_ops,
                              n_values=5, corrupt=0.1, p_info=0.01,
                              n_keys=n_keys)
    hists = keyed_histories(cols)
    rec["setup_s"] = time.perf_counter() - t0
    with meter.phase(rec):
        rs = check_batch_columnar(model, hists, details="invalid")
    t0 = time.perf_counter()
    subs, sub_hist, sub_key = partition_histories(hists)
    native = recombine_details(check_batch_native(model, subs), sub_hist,
                               sub_key, len(hists))
    rec["oracle_s"] = time.perf_counter() - t0
    rec["sub_histories"] = len(subs)
    rec["invalid"] = sum(r["valid"] is False for r in rs)
    rec["provenance"] = provenance(rs)
    mism = [i for i, (a, b) in enumerate(zip(rs, native))
            if verdict(a) != verdict(b)]
    rec["mismatches"] = mism[:10]
    rec["parity"] = not mism and len(rs) == len(native) == n_hist
    return rec, subs


def pending_w(h) -> int:
    """Peak pending window of an Op-list history (invokes allocate,
    ok completions free; info ops stay pinned) — the encoder's W."""
    live = peak = 0
    for op in h:
        if op.type == "invoke":
            live += 1
            peak = max(peak, live)
        elif op.type == "ok":
            live -= 1
    return peak


def phase_backends(meter: Meter, cas_subs, n_rw: int = 1024,
                   max_w: int = 10) -> dict:
    """Every WGL device backend forced in turn over the same rows,
    compared field by field. The rows are the headline batch's W<=10
    sub-histories plus register-class read/write histories — the peel
    backend certifies only the register class (no cas kinds), so
    without those it would never dispatch."""
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops import linearize as lin
    from jepsen_tpu.workloads.synth import synth_rw_history

    cas_rows = [h for h in cas_subs if pending_w(h) <= max_w]
    rw_rows = [synth_rw_history(s, n_procs=max_w, n_ops=48,
                                stale=0.1 if s % 4 == 0 else 0.0)
               for s in range(n_rw)]
    model = cas_register()
    rec = {"phase": "backends", "rows": len(cas_rows) + len(rw_rows),
           "cas_rows": len(cas_rows), "rw_rows": len(rw_rows),
           "backends": {}}
    got = {}
    with meter.phase(rec):
        for be in ("pallas", "dc", "xla"):
            lin.DISPATCH_LOG.clear()
            t0 = time.perf_counter()
            # Two calls: one shared kind vocabulary would carry the cas
            # kinds into the read/write rows and make them incapable.
            got[be] = [r for rows in (cas_rows, rw_rows)
                       for r in lin.check_batch_columnar(
                           model, rows, details="invalid",
                           scheduler_opts={"wgl_backend": be})]
            rec["backends"][be] = {
                "wall_s": time.perf_counter() - t0,
                "dispatch_log": dict(Counter(p for p, *_ in
                                             lin.DISPATCH_LOG)),
                "provenance": provenance(got[be])}
    rec["provenance"] = provenance(got["xla"])
    fields = ("valid", "op", "configs")
    mism = {be: sum(any(a.get(f) != b.get(f) for f in fields)
                    for a, b in zip(got[be], got["xla"]))
            for be in ("pallas", "dc")}
    rec["mismatches"] = mism
    rec["invalid"] = sum(r["valid"] is False for r in got["xla"])
    ran = all(rec["backends"][be]["dispatch_log"].get(be, 0) > 0
              for be in ("pallas", "dc"))
    rec["parity"] = ran and not any(mism.values())
    return rec


def phase_families(meter: Meter, n_graph: int = 2000,
                   n_iso: int = 512) -> dict:
    """The cycle checker and the isolation certifier at bench.py's
    default batch sizes, each against its host oracle."""
    from jepsen_tpu.checkers.cycle import check_graphs_batch
    from jepsen_tpu.isolation import certify_batch, certify_host
    from jepsen_tpu.ops.graph import check_graph_host, extract_graph
    from jepsen_tpu.ops.synth_txn import TxnSpec, synth_txn_batch
    from jepsen_tpu.ops.txn_graph import extract_txn_graph
    from jepsen_tpu.workloads.synth import synth_la_history

    graphs = [extract_graph(synth_la_history(
        s, n_ops=30, corrupt=1.0 if s % 7 == 0 else 0.0), "list-append")
        for s in range(n_graph)]
    txns = [extract_txn_graph(h) for h, _ in
            synth_txn_batch(TxnSpec(n=n_iso, seed=7, anomaly="mix"))]
    rec = {"phase": "families", "rows": n_graph + n_iso,
           "graphs": n_graph, "txn_histories": n_iso}
    with meter.phase(rec):
        grs = check_graphs_batch(graphs)
        irs = certify_batch(txns)
    g_host = [check_graph_host(g) for g in graphs]
    i_host = certify_host(txns)
    def same(a, b):                   # field for field, provenance aside
        return ({k: v for k, v in a.items() if k != "provenance"}
                == {k: v for k, v in b.items() if k != "provenance"})

    g_ok = all(same(a, b) for a, b in zip(grs, g_host))
    i_ok = all(same(a, b) for a, b in zip(irs, i_host))
    rec["anomalies"] = sum(r["valid"] is not True for r in grs)
    rec["levels"] = dict(Counter(r.get("level") for r in irs))
    rec["provenance"] = provenance(grs + irs)
    rec["parity"] = g_ok and i_ok and len(grs) == n_graph \
        and len(irs) == n_iso
    return rec


def phase_multichip(meter: Meter, n: int) -> dict:
    """The data- and frontier-sharded routes over ``n`` chips, each
    compared with one device and the host oracle
    (__graft_entry__.multichip_checks)."""
    import __graft_entry__ as g
    rec = {"phase": "multichip"}
    with meter.phase(rec):
        rec.update(g.multichip_checks(n))
    rec["parity"] = True              # multichip_checks asserts it
    return rec


# ----------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "jepsen_tpu")):
        print("chip_smoke: no jepsen_tpu package beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from jepsen_tpu.provision import device_identity
    dev = device_identity()
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        print(f"chip_smoke: need {args.chips} TPU chip(s), found {dev}",
              file=sys.stderr)
        return 3
    emit({"phase": "identity", **dev})

    from jepsen_tpu.ops.schedule import enable_compilation_cache
    rec = {"phase": "compile_cache", "dir": enable_compilation_cache()}
    emit(rec)
    meter = Meter()
    if args.chips == 4:
        rec = phase_multichip(meter, 4)
        emit(rec)
        require(rec)
    else:
        work = os.path.join(REPO, ".smoke")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        cwd = os.getcwd()
        os.chdir(work)                 # the suite's store lands here
        try:
            rec = phase_jepsen(meter, work)
        finally:
            os.chdir(cwd)
        emit(rec)
        require(rec)
        rec, subs = phase_headline(meter)
        emit(rec)
        require(rec)
        rec = phase_backends(meter, subs)
        emit(rec)
        require(rec)
        rec = phase_families(meter)
        emit(rec)
        require(rec)
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
