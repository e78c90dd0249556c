#!/usr/bin/env python
"""Headline benchmark: batched linearizability checking throughput.

North star (BASELINE.md): 10k CAS-register histories of 1k ops each,
checked for linearizability in < 60 s on a TPU v5e-8 — i.e. ≥ 166.7
histories/sec with Knossos-parity verdicts. This bench measures the
*end-to-end* checking rate — vectorized columnar encode + device scan —
of that workload shape on whatever accelerator is attached (one chip
here; the batch axis scales linearly over a mesh — jepsen_tpu.parallel).

Parity is FULL, not sampled: every row's valid? verdict and every
invalid row's first-bad-op index are compared against the native C++
engine, and every invalid device row with W <= 16 gets a config-set
comparison against the exact host oracle (BASELINE.md:
"valid?/counterexample parity").

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Env knobs: JT_BENCH_B (histories, default 10000), JT_BENCH_OPS (op pairs
per history, default 500 → 1k history lines), JT_BENCH_KEYS (independent
registers per history, default 8; the P-compositional pre-partition
strains each history per key before encoding and the partition section
reports the W collapse — 1 restores the unkeyed r05 run), JT_BENCH_REPEATS,
JT_BENCH_STORE_B (runs in the store→recheck figure),
JT_BENCH_FULL_PARITY=0 (fall back to sampled parity for quick local
runs), JT_SCHED_CLASSES / JT_SCHED_CHUNK_ROWS / JT_SCHED_ENCODE_ROWS
(streaming scheduler knobs, see ops/schedule.py), JT_BENCH_XLONG_B/
JT_BENCH_XLONG_OPS (the 100-history x 100k-line probe; 0 skips),
JT_BENCH_GRAPH_B (dependency-graph cycle-checker
figure; 0 skips), JT_BENCH_ISO_B (isolation-ladder certifier figure:
histories/s over a seeded anomaly mix with the per-level breakdown;
0 skips), JT_BENCH_WAL_OPS (run-durability figure: live-WAL
worker-loop overhead, group-commit flush percentiles, salvage
throughput; 0 skips),
JT_FUSE_KINDS (event-fusion vocabulary budget, ops/encode.py),
JT_BENCH_SYNTH=device|host (headline workload generator: ``host`` is
the legacy lockstep numpy generator, byte-identical to every earlier
round; ``device`` synthesizes the headline batch with the jitted
counter-PRNG generator of ops/synth_device.py — same logical
parameters, its own stream), JT_BENCH_SYNTH_B (rows for the
synth_device section's host-vs-device rate comparison; 0 skips it),
JT_BENCH_FUZZ=0 (skip the fuzz-loop figure), JT_BENCH_FLEET=0 (skip
the fleet-orchestrator scaling sweep; JT_BENCH_FLEET_WORKERS /
JT_BENCH_FLEET_SEEDS / JT_BENCH_FLEET_B size it and
JT_BENCH_FLEET_CURVE=<path> writes the standalone MULTICHIP_r07-shape
curve file), JT_BENCH_ONLINE=0 (skip
the online-checker-daemon figure: time-to-first-verdict percentiles,
verdicts/s while writing, and the forced-overload-burst shed fraction;
JT_BENCH_ONLINE_TENANTS / JT_BENCH_ONLINE_OPS size it),
JT_BENCH_SERVICE=0 (skip the federated checking-service figure:
tenants-per-SLO vs real worker processes plus the kill-a-worker
takeover-latency probe; JT_BENCH_SERVICE_WORKERS /
JT_BENCH_SERVICE_TENANTS / JT_BENCH_SERVICE_OPS /
JT_BENCH_SERVICE_SLO_S size it and JT_BENCH_SERVICE_CURVE=<path>
writes the standalone MULTICHIP_r08-shape curve file —
doc/service.md), JT_BENCH_TRACE=0 (skip
the telemetry section) / JT_BENCH_TRACE_B (its workload size; the
section measures span-tracing overhead against the ≤5% budget and the
device-busy vs host-gap breakdown — doc/observability.md). JT_TRACE=1
traces the WHOLE bench through the flight recorder and exports a
Chrome-trace ``trace.json`` ($JT_TRACE_EXPORT overrides the path).
JT_BENCH_BACKEND=pallas|xla|auto pins the WGL dispatch backend for
the whole run (default auto: the cost router prices the Pallas
megakernel against the lax.scan kernel from the startup rate probe);
JT_BENCH_PROBE=0 skips that probe, JT_BENCH_BACKEND_COMPARE=0 skips
the Pallas-vs-XLA rate table (JT_BENCH_COMPARE_WS / _B / _EVENTS size
it — doc/scaling.md "Hand-schedule the inner loop").
Narrow
buckets all stay on device (the scheduler consolidates them into W
classes); only tiny wide buckets route to the native CPU engine. The
encode runs the production shrink passes (event fusion + state
renumbering); parity stays full because fused-run failures are
re-derived exactly before comparing.
"""
import json
import os
import time
from pathlib import Path

# ------------------------------------------------ regression sentinel
#
# ``python bench.py --compare PREV.json`` runs the bench and adds a
# ``regression`` section to the one JSON line: every rate-like metric
# below, present in both rounds, compared at a relative tolerance
# (``--tolerance``, default 0.20 — CPU containers are noisy; a real
# TPU round can tighten it). Exit code 3 when any rate regressed past
# tolerance — the next BENCH round machine-checks itself against the
# last instead of trusting a human diff. ``--current CUR.json`` skips
# the bench and compares two committed files (the self-compare /
# fixture mode tests and CI use; no jax import on that path).

#: Dotted paths of the throughput figures a round must not silently
#: lose. Higher is better for every one of them; keys absent from
#: either side (older rounds, skipped sections) are skipped, never
#: guessed.
RATE_KEYS = (
    "value",                                # the headline hist/s
    "device_rate",
    "native_cpu_rate",
    "converted_e2e_rate",
    "store_recheck_rate",
    "fold_total_queue_rate",
    "scheduler.streamed_e2e_rate",
    "graph_checker.graphs_per_s",
    # Isolation-ladder certifier (ISSUE 19): gated from the first
    # round both sides carry it, same new-key-skipped rule as ingest.
    "isolation.hist_per_s",
    "run_durability.ops_per_s_wal_on",
    "run_durability.salvage_ops_per_s",
    "long_history.routed.events_per_s",
    "xlong_history.events_per_s",
    "synth_device.device_hist_per_s",
    "synth_device.host_hist_per_s",
    "synth_device.streamed_gen_check_subs_per_s",
    "online.verdicts_per_s_while_writing",
    # Wire-ingest plane (ISSUE 18): keys added to the curated list in
    # the SAME round the section ships, so --compare gates wire
    # throughput from the first round BOTH sides carry it (keys
    # absent from the baseline are skipped by design, never guessed).
    "ingest.wire_ops_per_s",
    "ingest.wire_ops_per_s_per_core",
)


def _dig(d, path):
    cur = d
    for part in path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def unwrap_bench(d: dict) -> dict:
    """Accept both the raw bench line and the committed BENCH_r*.json
    shape (the driver wraps the parsed line under ``parsed`` next to
    cmd/rc/note)."""
    if isinstance(d, dict) and "metric" not in d and \
            isinstance(d.get("parsed"), dict):
        return d["parsed"]
    return d


def compare_bench(prev: dict, cur: dict,
                  tolerance: float = 0.20) -> dict:
    """Per-rate deltas of ``cur`` vs ``prev`` (both bench JSON
    objects) under a relative tolerance. A metric REGRESSES when
    ``cur < prev * (1 - tolerance)``; improvements are reported but
    never fail. Returns the ``regression`` section: ``{"baseline",
    "tolerance", "rates": {key: {prev, cur, ratio, regressed}},
    "regressions": [keys], "ok": bool}``."""
    prev = unwrap_bench(prev)
    cur = unwrap_bench(cur)
    rates = {}
    regressions = []
    for key in RATE_KEYS:
        pv, cv = _dig(prev, key), _dig(cur, key)
        if not isinstance(pv, (int, float)) or \
                not isinstance(cv, (int, float)) or \
                isinstance(pv, bool) or isinstance(cv, bool) or \
                pv <= 0:
            continue
        ratio = cv / pv
        regressed = cv < pv * (1.0 - tolerance)
        rates[key] = {"prev": round(float(pv), 3),
                      "cur": round(float(cv), 3),
                      "ratio": round(ratio, 4),
                      "regressed": regressed}
        if regressed:
            regressions.append(key)
    out = {"tolerance": tolerance, "compared": len(rates),
           "rates": rates, "regressions": regressions,
           "ok": bool(rates) and not regressions}
    if not rates:
        # Zero comparable rates is a FAILED comparison, not a pass: a
        # malformed baseline (a failed round's wrapper with parsed:
        # null, a foreign schema) must not read as "machine-checked
        # clean" in CI.
        out["error"] = ("no comparable rate metrics between the two "
                        "files (malformed baseline?)")
    return out


def _pct_nearest(xs, p, digits=4):
    """Nearest-rank percentile over a SORTED list — the telemetry
    registry's convention (``int(round(p·n/100 + 0.5)) − 1``, clamped),
    shared by every section (WAL flush, online TTFV, service TTFV and
    takeover latency) so their percentile figures stay comparable."""
    if not xs:
        return None
    i = min(len(xs) - 1,
            max(0, int(round(p / 100.0 * len(xs) + 0.5)) - 1))
    return round(xs[i], digits)


#: Published per-chip peaks, keyed by jax's ``device_kind``. HBM and
#: MXU: Google Cloud documentation, "TPU v5e" (819 GB/s; 197 TFLOP/s
#: bf16 = 98.5 TMAC/s at 2 flops per MAC). VPU: 8x128 lanes x 4 ALUs x
#: ~1.66 GHz int32 ops, the derivation in doc/scaling.md. A TPU kind
#: missing here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "mxu_tmacs": 98.5,
                    "vpu_gops": 6800.0,
                    "source": "Google Cloud 'TPU v5e' docs; VPU per "
                              "doc/scaling.md"},
}


def device_peaks():
    """The attached chip's peaks, or None off the chip (utilizations
    then read null: not measured)."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in DEVICE_PEAKS:
        raise SystemExit(f"bench: no published peaks for device kind "
                         f"{dev.device_kind!r}; add it to DEVICE_PEAKS")
    return DEVICE_PEAKS[dev.device_kind]


def main(compare: dict = None, tolerance: float = 0.20) -> int:
    B = int(os.environ.get("JT_BENCH_B", "10000"))
    n_ops = int(os.environ.get("JT_BENCH_OPS", "500"))
    repeats = int(os.environ.get("JT_BENCH_REPEATS", "3"))
    full_parity = os.environ.get("JT_BENCH_FULL_PARITY", "1") != "0"
    baseline_rate = 10_000 / 60.0  # north-star target, histories/sec

    import jax  # noqa: F401 — backend selected before first dispatch
    from jepsen_tpu.ops.schedule import (AOT_STATS, BucketScheduler,
                                         aot_warm_probe, cache_root,
                                         default_fuse_width,
                                         enable_compilation_cache,
                                         iter_columnar_groups)
    from jepsen_tpu.provision import device_identity
    # Persistent compile cache: repeat bench runs (and store rechecks)
    # deserialize kernels instead of recompiling. The AOT shipping dir
    # goes further — it holds FINAL serialized executables keyed by
    # kernel shape (ops/schedule.py _aot_key), so a fresh process skips
    # trace+lower+compile entirely: that is the cold-compile cut
    # (16.5 s -> <5 s) the partition section reports.
    enable_compilation_cache()
    os.environ.setdefault("JT_AOT_DIR", os.path.join(cache_root(), "aot"))

    # WGL dispatch backend for every scheduler this process builds:
    # JT_BENCH_BACKEND=pallas|xla|auto pins it ("auto" = the cost
    # router decides per bucket from the measured rates below).
    bench_backend = os.environ.get("JT_BENCH_BACKEND")
    if bench_backend:
        os.environ["JT_WGL_BACKEND"] = bench_backend
    # Startup rate probe (ISSUE 12): measure both WGL device backends
    # (lax.scan vs the Pallas megakernel) on one tiny workload and
    # install the rates as the router overlay — what "chosen by the
    # cost router, never hardcoded" prices from. JT_BENCH_PROBE=0
    # skips (the router then keeps its unprobed defaults: scan only).
    rate_probe = None
    if os.environ.get("JT_BENCH_PROBE", "1") != "0":
        from jepsen_tpu import fleet as _fleet
        from jepsen_tpu.ops.dc_monitor import probe_rates as _dc_probe
        from jepsen_tpu.ops.pallas_wgl import probe_rates as _probe_rates
        rate_probe = _probe_rates()
        _dcp = _dc_probe()
        rate_probe["dc_events_per_s"] = _dcp.get("dc_events_per_s", 0.0)
        rate_probe["dc_parity"] = _dcp.get("parity")
        _fleet.set_measured_rates(rate_probe)
    import numpy as np
    from jepsen_tpu.checkers.linearizable import wgl_check
    from jepsen_tpu.history.columnar import columnar_to_ops
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops.encode import encode_columnar
    from jepsen_tpu.ops.linearize import (DATA_MAX_SLOTS,
                                          device_frontier_capacity)
    from jepsen_tpu.ops.statespace import enumerate_statespace
    from jepsen_tpu.workloads.synth import synth_cas_columnar

    model = cas_register()

    # The workload is the r05 shape lifted to the production
    # ``independent`` form: JT_BENCH_KEYS (default 8) independent
    # registers per history, same B/ops/procs/values/corruption.
    # P-compositional pre-partition (ops.partition) strains each
    # history into per-key sub-histories BEFORE encoding — W collapses
    # from the r05 5–17 spread (pinned info ops + concurrency
    # accumulate across keys) to <= ~9, i.e. the 2^W frontier cost of
    # the expensive tail drops ~100x. Verdicts recombine per history;
    # parity below runs over the sub-histories (each one a plain
    # single-register history the exact engines understand).
    # JT_BENCH_KEYS=1 restores the literal unkeyed r05 run.
    n_keys = int(os.environ.get("JT_BENCH_KEYS", "8"))
    synth_mode = os.environ.get("JT_BENCH_SYNTH", "host")
    from dataclasses import replace as _dc_replace

    from jepsen_tpu.ops.synth_device import SynthSpec, synthesize
    headline_spec = SynthSpec(family="cas", n=B, seed=1, n_procs=5,
                              n_ops=n_ops, n_values=5, corrupt=0.1,
                              p_info=0.01, n_keys=n_keys)
    synth_meta = None
    if synth_mode == "device":
        # Generate the headline batch ON DEVICE (ops/synth_device):
        # born in the columnar layout, partition metadata included —
        # the generate-where-you-check path. Compile warms outside the
        # clock like every other section.
        synthesize(headline_spec, "device")
        t0 = time.monotonic()
        cols_raw, synth_meta = synthesize(headline_spec, "device")
        t_synth = time.monotonic() - t0
    else:
        # The legacy lockstep generator — byte-identical to r06.
        t0 = time.monotonic()
        cols_raw = synth_cas_columnar(B, seed=1, n_procs=5,
                                      n_ops=n_ops, n_values=5,
                                      corrupt=0.1, p_info=0.01,
                                      n_keys=n_keys)
        t_synth = time.monotonic() - t0

    from jepsen_tpu.ops.partition import (partition_columnar,
                                          pending_w_hist,
                                          recombine_verdicts)
    # Device-synthesized batches answer both histograms from generator
    # metadata (pending_w_hist consults cols.meta; the post hist comes
    # straight off SynthMeta) — no full-batch line-grid re-scan.
    pre_w_hist = pending_w_hist(cols_raw)
    t0 = time.monotonic()
    pb = partition_columnar(cols_raw)
    t_partition = time.monotonic() - t0
    cols = pb.cols if pb is not None else cols_raw
    post_w_hist = (synth_meta.sub_w_hist()
                   if synth_meta is not None
                   and synth_meta.sub_w_hist() is not None
                   else pending_w_hist(cols))
    S = cols.batch                    # sub-history rows (== B unkeyed)

    # Window headroom: the device wide path (data1wide / frontier mesh)
    # covers W up to 16 + capacity, so those rows never pay the
    # pure-Python fallback (the -Xmx32g analog, linearize.py:335-388).
    # Two-phase encode: the 16-slot table covers ~99.98% of rows at the
    # cheaper width; only overflow rows re-encode wide.
    #
    # W classes: r05 measured NAIVE fixed-grid consolidation losing at
    # every granularity ({8,12,16} 5.8->23.2s; tail-only {13..16->16}
    # 5.8->15.5s) — those grids pad the fat mid-W buckets into the next
    # power of two, multiplying the dominant frontier work. The bucket
    # scheduler instead picks classes by a DP over the observed
    # rows x events x 2^W distribution (ops.schedule.choose_w_classes),
    # which keeps the expensive windows near-exact and folds only the
    # cheap long tail — JT_SCHED_CLASSES tunes the budget (large value
    # ~ exact-W bucketing).
    eff_slots = DATA_MAX_SLOTS + device_frontier_capacity()

    def encode(c):
        # Production encode settings: event fusion (single-candidate
        # runs collapse to EV_FUSED steps) + live-alphabet state
        # renumbering. The parity section below still compares against
        # the exact engines, and rows that fail INSIDE a fused run are
        # re-derived exactly (fused-bad refinement, also timed).
        space = enumerate_statespace(model, c.kinds, 64)
        buckets, failures = encode_columnar(space, c,
                                            max_slots=DATA_MAX_SLOTS,
                                            fuse=True, renumber=True)
        if failures and eff_slots > DATA_MAX_SLOTS:
            rows = [i for i, _ in failures]
            sub = type(c)(type=c.type[rows],
                          process=c.process[rows],
                          kind=c.kind[rows], kinds=c.kinds,
                          index=(c.index[rows]
                                 if c.index is not None else None))
            wide, failures = encode_columnar(space, sub,
                                             max_slots=eff_slots,
                                             fuse=True, renumber=True)
            for b in wide:
                b.indices = [rows[i] for i in b.indices]
            failures = [(rows[i], why) for i, why in failures]
            buckets = buckets + wide
        return buckets, failures

    t0 = time.monotonic()
    buckets, failures = encode(cols)
    t_encode = time.monotonic() - t0

    try:
        from jepsen_tpu.native import check_batch_native, lib as _native_lib
        _native_lib()                          # build/load outside timing
    except Exception:
        check_batch_native = None

    def route(bkts, fails):
        """Narrow (W <= 16) buckets ALL stay on device: the scheduler
        folds small ones into consolidated W classes, so they no longer
        pay a per-bucket XLA compile (r05 routed them to the CPU
        instead). Wide windows (W > 16) are still cost-routed: the
        device wide path (HBM-resident mask axis) wins on utilization
        once a few rows share the dispatch, but one or two rows leave
        its 2000-step sequential scan latency-bound — slower than
        letting the exact host engine chew them on the otherwise-idle
        CPU UNDER the device window. Encoder-overflow rows (beyond
        even the wide path) go to the CPU engines. Returns
        (dev_buckets, oversize_rows, overflow_rows) — the routing
        reasons the cpu_routed breakdown reports."""
        overflow = [i for i, _ in fails]
        if check_batch_native is None:
            return bkts, [], overflow
        dev = [b for b in bkts
               if b.W <= DATA_MAX_SLOTS or b.batch > 2]
        dev_ids = {id(b) for b in dev}
        oversize = [i for b in bkts if id(b) not in dev_ids
                    for i in b.indices]
        return dev, oversize, overflow

    dev_buckets, cpu_oversize, cpu_overflow = route(buckets, failures)
    cpu_rows = cpu_oversize + cpu_overflow
    cpu_hists = [columnar_to_ops(cols, i) for i in cpu_rows]

    def cpu_tail():
        """Per-row CPU-tail results (the caller folds them into the
        row-verdict arrays for history-level recombination)."""
        if not cpu_hists:
            return []
        if check_batch_native is not None:
            return check_batch_native(model, cpu_hists)
        return [wgl_check(model, h) for h in cpu_hists]

    def refine_fused(pairs):
        # Rows whose first impossible completion fell inside a fused
        # run only know the run's first member: re-derive the exact
        # bad index on the native engine (part of verdict production,
        # so it stays inside the timed window).
        from jepsen_tpu.ops.linearize import fused_bad_rows
        rows = []
        for b, (v, bd, _) in pairs:
            rows.extend(b.indices[int(r)]
                        for r in fused_bad_rows(b, v, bd))
        if not rows:
            return {}
        hs = [columnar_to_ops(cols, i) for i in rows]
        rs = (check_batch_native(model, hs) if check_batch_native
              else [wgl_check(model, h) for h in hs])
        return {i: r["op"]["index"] for i, r in zip(rows, rs)
                if r["valid"] is False}

    def run_all(stats_out=None):
        # Device buckets ride the streaming scheduler (W-class
        # consolidation + chunked double-buffered dispatch); the CPU
        # tail rides another thread under the device window. NOTE: the
        # yielded buckets are the scheduler's consolidated classes —
        # results scatter through batch.indices, never positional zips
        # against dev_buckets.
        from concurrent.futures import ThreadPoolExecutor

        sch = BucketScheduler()
        with ThreadPoolExecutor(1) as ex:
            tail = ex.submit(cpu_tail)
            pairs = list(sch.run(dev_buckets))
            refined = refine_fused(pairs)
            tail_rs = tail.result()
        if stats_out is not None:
            stats_out.update(sch.stats)
        return pairs, tail_rs, refined

    # Warmup / compile. The first run pays every kernel compile this
    # mix needs (persistent cache: near-zero on repeat processes);
    # sched_stats["compiled_shapes"] is the headline compile count.
    sched_stats = {}
    aot_pre = dict(AOT_STATS)
    t0 = time.monotonic()
    pairs, cpu_tail_rs, refined = run_all(stats_out=sched_stats)
    t_compile = time.monotonic() - t0
    kernel_compiles = sched_stats.get("compiled_shapes")
    w_classes = sched_stats.get("classes")
    fusion_ratio = sched_stats.get("fusion_ratio")
    # Shipped-executable accounting for THIS process's compile phase:
    # hits mean the shipping dir was warm and t_compile is the warm
    # figure; a fresh checkout pays the cold compile once and exports.
    aot_run = {k: AOT_STATS[k] - aot_pre.get(k, 0) for k in AOT_STATS}

    # Median-of-N: honest against host-clock jitter in both directions
    # (min-of-N hid slow outliers; a single slow run would lie the
    # other way).
    import statistics
    times = []
    for _ in range(repeats):
        t0 = time.monotonic()
        pairs, cpu_tail_rs, refined = run_all()
        times.append(time.monotonic() - t0)
    t_dev = statistics.median(times)

    n_checked = sum(b.batch for b in dev_buckets) + len(cpu_rows)
    cpu_bad = sum(1 for r in cpu_tail_rs if r["valid"] is not True)
    n_invalid = int(sum(int((~v).sum())
                        for _, (v, _, _) in pairs)) + cpu_bad
    t_e2e = t_partition + t_encode + t_dev
    # Headline rate is per ORIGINAL history — the unit every earlier
    # round reported; sub-history figures ride the partition section.
    rate = B * (n_checked / max(S, 1)) / t_e2e

    # Streamed end-to-end: the columnar encode walk chunks into groups
    # that overlap device dispatch (one pipeline from raw columns to
    # verdicts), which is where time-to-first-verdict is measured.
    # Encode work re-runs inside, so this
    # figure is directly comparable to t_e2e.
    def run_streamed():
        from concurrent.futures import ThreadPoolExecutor

        from jepsen_tpu.ops.linearize import WindowOverflow
        from jepsen_tpu.ops.schedule import DIVERTED
        # Divert small wide buckets only when the native engine is
        # there to actually check them — otherwise they must stay on
        # device (check_columnar's own routing rule), and the streamed
        # count must only include rows that got a verdict.
        sch = BucketScheduler(
            min_device_rows=4 if check_batch_native is not None else 0)
        space_s = enumerate_statespace(model, cols.kinds, 64)
        n_dev, diverted = 0, []
        with ThreadPoolExecutor(1) as ex:
            tail = ex.submit(cpu_tail)
            groups = iter_columnar_groups(space_s, cols,
                                          max_slots=eff_slots,
                                          failures=[], fuse=True,
                                          renumber=True)
            for bt, out in sch.run(groups):
                if out is DIVERTED:
                    diverted.extend(bt.indices)
                    continue
                if isinstance(out, WindowOverflow):
                    continue        # unverdicted: not a checked row
                n_dev += bt.batch
            tail.result()          # cpu_rows already cover the fails
        cpu_set = set(cpu_rows)
        extra = [i for i in diverted if i not in cpu_set]
        if extra:
            check_batch_native(model, [columnar_to_ops(cols, i)
                                       for i in extra])
        n = n_dev + len(cpu_set | set(diverted))
        return n, sch.stats

    run_streamed()        # warmup: streamed-only shapes compile here
    streamed_times, streamed_stats = [], {}
    for _ in range(max(2, repeats)):
        t0 = time.monotonic()
        n_streamed, streamed_stats = run_streamed()
        streamed_times.append(time.monotonic() - t0)
    t_streamed = statistics.median(streamed_times)
    # Per original history, like the headline (the streamed loop rides
    # the pre-strained sub batch; partition time is included so the
    # figure stays an honest raw-columns-to-verdicts rate).
    streamed_rate = (B * (n_streamed / max(S, 1))
                     / (t_streamed + t_partition))

    # ------------------------------------------------------ roofline
    # Achieved device bandwidth during the headline run, from analytic
    # traffic: the scan reads + writes each row's packed frontier
    # (V states x 2^W bits) once per event; event tables are noise
    # beside it. This backs the "bandwidth-competitive" claim with a
    # measured figure instead of an argument — utilization is against
    # the chip's HBM peak (DEVICE_PEAKS). Because the dominant buckets'
    # frontiers live in VMEM, the real ceiling is VPU integer
    # throughput: vpu_util divides the kernel's analytic lane-op count
    # (ops.linearize.vpu_op_model, fed by the instrumented kernel's
    # MEASURED closure-iteration totals) by the chip's VPU peak. Off
    # the chip every utilization reads null: not measured.
    peaks = device_peaks()
    peak_gbps = peaks["hbm_gbps"] if peaks else None
    vpu_gops = peaks["vpu_gops"] if peaks else None

    def bucket_traffic(b):
        return b.batch * b.ev_opidx.shape[-1] * b.V * (2 ** b.W) // 8 * 2

    # Traffic is analytic over the DISPATCHED class buckets (padded W),
    # not the exact-W input buckets — consolidation is real traffic.
    disp_buckets = [b for b, _ in pairs]
    traffic = sum(bucket_traffic(b) for b in disp_buckets)
    events = sum(b.batch * b.ev_opidx.shape[-1] for b in disp_buckets)
    orig_events = sum(
        int(b.orig_n_events.sum()) if b.orig_n_events is not None
        else b.batch * b.ev_opidx.shape[-1] for b in disp_buckets)
    # Device-only denominator: t_dev is run_all() wall time, i.e.
    # max(device, overlapped CPU tail) — a slow tail would deflate the
    # published bandwidth figure.
    dts = []
    for _ in range(repeats):
        t0 = time.monotonic()
        list(BucketScheduler().run(dev_buckets))
        dts.append(time.monotonic() - t0)
    t_dev_only = statistics.median(dts)

    # Measured VPU op count: one instrumented pass over the dispatched
    # narrow buckets collects total closure while_loop iterations per
    # row; the analytic per-iteration/per-event lane-op model turns
    # that into uint32 VPU ops. (Separate pass — the counter output
    # changes the compiled kernel — so it never pollutes the timings.)
    from jepsen_tpu.ops.linearize import (MAX_FRONTIER_ELEMENTS,
                                          get_kernel, n_state_words,
                                          vpu_op_model)
    vpu_ops = 0.0
    iters_total = 0
    for b in disp_buckets:
        if b.W > DATA_MAX_SLOTS or not b.batch:
            continue
        kern = get_kernel(b.V, b.W, shared_target=b.shared_target,
                          w_live=b.eff_w_live, instrument=True)
        per_hist = n_state_words(b.V) << b.W
        chunk = max(1, MAX_FRONTIER_ELEMENTS // per_hist)
        iters = 0
        for lo in range(0, b.batch, chunk):
            hi = min(lo + chunk, b.batch)
            out = kern(b.ev_type[lo:hi], b.ev_slot[lo:hi],
                       b.ev_slots[lo:hi],
                       b.target[0] if b.shared_target
                       else b.target[lo:hi])
            iters += int(np.asarray(out[3]).sum())
        m = vpu_op_model(b.V, b.W, b.eff_w_live)
        vpu_ops += (iters * m["per_iteration"]
                    + b.batch * b.ev_opidx.shape[-1] * m["per_event"])
        iters_total += iters
    vpu_util = (vpu_ops / t_dev_only / (vpu_gops * 1e9)
                if peaks else None)

    # Mean live pending slots per dispatched scan step — the closure's
    # real work bound (w_live kernels unroll only this neighborhood).
    live_sum = ev_n = 0
    for b in dev_buckets:
        sent = b.target.shape[1] - 1
        real = b.ev_type != 0                     # != EV_PAD
        live_sum += int(((b.ev_slots != sent).sum(axis=2) * real).sum())
        ev_n += int(real.sum())
    mean_live_slots = round(live_sum / max(ev_n, 1), 3)

    roofline = {
        "traffic_gb": round(traffic / 1e9, 2),
        "achieved_gbps": round(traffic / t_dev_only / 1e9, 2),
        "events_per_s": round(events / t_dev_only, 1),
        "source_events_per_s": round(orig_events / t_dev_only, 1),
        "hbm_util": (traffic / t_dev_only / (peak_gbps * 1e9)
                     if peaks else None),
        "peak_gbps": peak_gbps,
        "vpu_util": vpu_util,
        "vpu_ops_e12": round(vpu_ops / 1e12, 4),
        "vpu_gops": vpu_gops,
        "peaks_source": peaks["source"] if peaks else None,
        "closure_iters_total": iters_total,
        "device_only_time_s": round(t_dev_only, 3),
        "dominant_buckets": [
            [b.V, b.W, b.batch]
            for b in sorted(disp_buckets, key=bucket_traffic,
                            reverse=True)[:3]],
    }

    # Device verdicts/bad-indices by row (parity + converted compare),
    # scattered through the consolidated buckets' indices. Bad lines
    # map through the partition's index column, so they are already in
    # the ORIGINAL history's op-index space — the same space the
    # sub-history Op lists (columnar_to_ops) carry.
    dev_valid = np.ones(S, bool)
    dev_bad = np.full(S, -1, np.int64)
    for b, (v, bd, _) in pairs:
        idx = np.asarray(b.indices)
        dev_valid[idx] = v
        iv = idx[~np.asarray(v)]
        bad_lines = b.ev_opidx[np.nonzero(~np.asarray(v))[0],
                               np.asarray(bd)[~np.asarray(v)]]
        dev_bad[iv] = (cols.index[iv, bad_lines]
                       if cols.index is not None else bad_lines)
    for i, op_idx in refined.items():        # exact fused-run bad ops
        dev_bad[i] = op_idx
    skip = set(cpu_rows)                     # rows the device never saw
    row_w = np.zeros(S, np.int32)
    for b in disp_buckets:
        row_w[np.asarray(b.indices)] = b.W

    # Fold the CPU tail's verdicts in, then recombine sub-verdicts to
    # per-history verdicts (valid iff every key is — ops.partition):
    # invalid_found stays a HISTORY count across rounds.
    all_valid = dev_valid.copy()
    all_bad = dev_bad.copy()
    for i, r in zip(cpu_rows, cpu_tail_rs):
        all_valid[i] = r["valid"] is True
        if r["valid"] is False and r.get("op"):
            all_bad[i] = r["op"]["index"]
    if pb is not None:
        hist_valid, _, _ = recombine_verdicts(
            all_valid, all_bad, pb.sub_history, pb.sub_key, B)
        n_invalid = int((~hist_valid).sum())

    # All-rows Op-list reconstruction — shared setup for parity, the
    # converted figure, and the store figure (stands in for histories
    # the runtime recorded).
    conv_hists = [columnar_to_ops(cols, r) for r in range(S)]

    # ------------------------------------------------- parity (FULL)
    # Every row vs the native engine (valid? + first-bad-op index);
    # every invalid device row with W <= DATA_MAX_SLOTS vs the exact
    # host oracle's config set at the counterexample.
    native_rate = None
    parity_valid = parity_bad_index = parity_configs = None
    n_config_rows = 0
    if check_batch_native is not None and full_parity:
        t0 = time.monotonic()
        nrs = check_batch_native(model, conv_hists)
        native_rate = round(S / (time.monotonic() - t0), 2)
        dev_rows = [r for r in range(S) if r not in skip]
        parity_valid = all(
            (nrs[r]["valid"] is True) == bool(dev_valid[r])
            for r in dev_rows)
        parity_bad_index = all(
            nrs[r]["valid"] is False
            and nrs[r]["op"]["index"] == dev_bad[r]
            for r in dev_rows if not dev_valid[r])

        from jepsen_tpu.ops.linearize import check_batch_columnar
        inv_rows = [r for r in dev_rows
                    if not dev_valid[r] and row_w[r] <= DATA_MAX_SLOTS]
        n_config_rows = len(inv_rows)
        if inv_rows:
            drs = check_batch_columnar(model,
                                       [conv_hists[r] for r in inv_rows])
            parity_configs = all(
                dr["valid"] is False and hr["valid"] is False
                and dr["op"]["index"] == hr["op"]["index"]
                and dr["configs"] == hr["configs"]
                for dr, hr in zip(drs, (wgl_check(model, conv_hists[r])
                                        for r in inv_rows)))
    elif check_batch_native is not None:
        # Quick mode: sampled valid? parity only.
        sample = list(range(0, S, max(1, S // 24)))[:24]
        nrs = check_batch_native(model, [conv_hists[r] for r in sample])
        parity_valid = all(
            (nr["valid"] is True) == bool(dev_valid[r])
            for r, nr in zip(sample, nrs) if r not in skip)

    # Converted-history extra: recorded Op-list histories ride the fast
    # path end-to-end (native ingest walk + vectorized encode + device,
    # CPU tail overlapped with device work exactly like the main run).
    #
    # Why this sits ~25-30% under the synthetic headline and stays
    # there: the extra cost is exactly one native pairing walk over the
    # 20M recorded events (~0.15us/event, ingest.cpp) + re-encode —
    # the floor for ingesting per-op histories. The two cures both
    # measure worse: pipelining batch halves doubles the per-bucket
    # dispatch count (941 -> 631 hist/s measured), and skipping Op
    # objects via the serialized loader trades the walk for an
    # equal-cost byte scan (519 MiB). Histories that are BORN columnar
    # (the synth path, or independent-key strained batches) pay
    # neither, which is the design point.
    from jepsen_tpu.history.columnar import ops_to_columnar
    C = min(int(os.environ.get("JT_BENCH_CONVERTED", str(S))), S)
    ops_to_columnar(model, conv_hists[:2])       # warm the native build

    def run_converted():
        from concurrent.futures import ThreadPoolExecutor

        ccols = ops_to_columnar(model, conv_hists[:C])
        space_c = enumerate_statespace(model, ccols.kinds, 64)
        cbuckets, cfails = encode_columnar(space_c, ccols,
                                           max_slots=eff_slots,
                                           fuse=True, renumber=True)
        cdev, cover, cfail = route(cbuckets, cfails)
        ccpu = cover + cfail
        cvalid = np.ones(C, bool)

        def cpu_part():
            if not ccpu:
                return []
            hs = [conv_hists[i] for i in ccpu]
            return (check_batch_native(model, hs)
                    if check_batch_native is not None
                    else [wgl_check(model, h) for h in hs])

        with ThreadPoolExecutor(1) as ex:
            tail = ex.submit(cpu_part)
            for b, out in BucketScheduler().run(cdev):
                v, _, _ = out
                cvalid[np.asarray(b.indices)] = v
            for i, r in zip(ccpu, tail.result()):
                cvalid[i] = r["valid"] is True
        return cvalid

    run_converted()                              # warm compiles
    conv_times = []
    for _ in range(max(2, repeats)):             # median-of-n vs the
        t0 = time.monotonic()                         # host jitter
        cvalid = run_converted()
        conv_times.append(time.monotonic() - t0)
    t_conv = statistics.median(conv_times)
    converted_rate = C / t_conv
    # Compare against the main run's verdicts where both were on-device.
    cmp_rows = np.array([r for r in range(C) if r not in skip], int)
    converted_match = bool(
        (cvalid[cmp_rows] == dev_valid[cmp_rows]).all())

    # Store→recheck extra: the actual replay product scenario — save
    # runs to disk, load them back, re-check the batch on device
    # (store.clj:165-171's seam; Store.recheck).
    import tempfile

    from jepsen_tpu.store import Store
    # Default to the headline scale: the replay seam is batch-oriented,
    # and a small sample is dispatch-latency-bound rather than measuring
    # the path (500 rows ~ 13 round trips ~ fixed cost dominates).
    SB = min(int(os.environ.get("JT_BENCH_STORE_B", str(B))), S)
    store_rate = None
    if SB:
        with tempfile.TemporaryDirectory() as td:
            store = Store(base=td)
            for i in range(SB):
                h = store.create("bench-recheck", ts=f"r{i:05d}")
                # What the runtime writes per run, minus the .txt
                # render (setup, not the measured seam): jsonl + the
                # machine-form sidecar recheck rides.
                h.save_history(conv_hists[i], model=model, txt=False)
            store.recheck("bench-recheck", model)    # warm compiles
            store_times = []
            for _ in range(max(2, repeats)):         # median vs jitter
                t0 = time.monotonic()
                rr = store.recheck("bench-recheck", model)
                store_times.append(time.monotonic() - t0)
            t_store = statistics.median(store_times)
            store_rate = round(SB / t_store, 2)
            want = [bool(dev_valid[i]) for i in range(SB)
                    if i not in skip]
            got = [rr["runs"][f"r{i:05d}"]["valid"] is True
                   for i in range(SB) if i not in skip]
            assert got == want, "store recheck verdict mismatch"

    # O(n) fold-checker extra: batch total-queue accounting on device
    # (jepsen_tpu.ops.folds) — the reference's single-pass reducers
    # (checker.clj:214-271) as one scatter dispatch per batch.
    from jepsen_tpu.history.ops import invoke_op, ok_op
    from jepsen_tpu.ops.folds import check_total_queues_batch
    import random as _random

    def synth_tq(seed, n=100):
        rng = _random.Random(seed)
        h = []
        for i in range(n):
            h.append(invoke_op(0, "enqueue", i))
            h.append(ok_op(0, "enqueue", i))
        order = list(range(n))
        rng.shuffle(order)
        if rng.random() < 0.3:
            order.pop()                      # lost element
        for v in order:
            h.append(invoke_op(1, "dequeue", None))
            h.append(ok_op(1, "dequeue", v))
        return h

    FB = int(os.environ.get("JT_BENCH_FOLD_B", "2000"))
    fold_hists = [synth_tq(s) for s in range(FB)]
    check_total_queues_batch(fold_hists)         # warm (same shapes)
    t0 = time.monotonic()
    fold_rs = check_total_queues_batch(fold_hists)
    fold_rate = FB / (time.monotonic() - t0)
    fold_invalid = sum(1 for r in fold_rs if r["valid"] is not True)

    # Graph-checker extra: the second device checker family — batched
    # happens-before cycle detection (ops.graph, doc/graphs.md).
    # List-append histories lower to typed ww/wr/rw dependency graphs
    # on the host, pack to [B, 3, V, V/32] bitsets bucketed by vertex
    # count, and decide G0/G1c/G2 anomalies by vmapped boolean
    # transitive closure — O(log V) dense matmuls per mask, the MXU's
    # native shape, where the WGL scan is VPU-bound. mxu_util divides
    # the dispatched closure's analytic MAC count (GraphScheduler
    # stats, retries included) by the chip's MXU peak (DEVICE_PEAKS;
    # see doc/graphs.md for the derivation and caveats).
    GB = int(os.environ.get("JT_BENCH_GRAPH_B", "2000"))
    graph_section = None
    if GB:
        from collections import Counter

        from jepsen_tpu.checkers.cycle import check_graphs_batch
        from jepsen_tpu.ops.graph import bucket_v, extract_graph
        from jepsen_tpu.workloads.synth import synth_la_history
        mxu_tmacs = peaks["mxu_tmacs"] if peaks else None
        la_hists = [synth_la_history(s, n_ops=30,
                                     corrupt=1.0 if s % 7 == 0 else 0.0)
                    for s in range(GB)]
        t0 = time.monotonic()
        la_graphs = [extract_graph(h, "list-append") for h in la_hists]
        t_extract = time.monotonic() - t0
        check_graphs_batch(la_graphs)            # warm the compiles
        gtimes, gstats, grs = [], {}, []
        for _ in range(max(2, repeats)):
            gstats = {}
            t0 = time.monotonic()
            grs = check_graphs_batch(la_graphs, stats_out=gstats)
            gtimes.append(time.monotonic() - t0)
        t_graph = statistics.median(gtimes)
        graph_section = {
            "graphs_per_s": round(GB / t_graph, 2),
            "e2e_graphs_per_s": round(GB / (t_extract + t_graph), 2),
            "extract_s": round(t_extract, 3),
            "device_s": round(t_graph, 3),
            "graphs": GB,
            "anomalies": sum(1 for r in grs if r["valid"] is not True),
            "closure_matmuls": gstats.get("closure_matmuls"),
            "mxu_macs_e9": round(gstats.get("mxu_macs", 0.0) / 1e9, 3),
            "mxu_util": (gstats.get("mxu_macs", 0.0) / t_graph
                         / (mxu_tmacs * 1e12) if peaks else None),
            "mxu_tmacs": mxu_tmacs,
            "vertex_buckets": sorted(
                [v, n] for v, n in Counter(
                    bucket_v(g.n) for g in la_graphs).items()),
            "resilience": {k: gstats.get(k, 0) for k in
                           ("retries", "bisections", "watchdog_fired",
                            "oom_events", "corrupt_chunks",
                            "quarantined_rows", "faults_injected")},
        }

    # Isolation-certifier extra: the THIRD device checker family —
    # batched isolation-ladder certification of transactional
    # histories (jepsen_tpu.isolation, doc/isolation.md). A seeded
    # anomaly mix (synth_txn) lowers to 4 packed cumulative-plane
    # bitsets plus an in-kernel derived SI plane, and one vmapped
    # closure dispatch decides the highest level each history
    # satisfies; the per-level breakdown doubles as the injection-mix
    # audit.
    IB = int(os.environ.get("JT_BENCH_ISO_B", "512"))
    iso_section = None
    if IB:
        from collections import Counter

        from jepsen_tpu.isolation import certify_batch
        from jepsen_tpu.ops.txn_graph import extract_txn_graph
        from jepsen_tpu.ops.synth_txn import TxnSpec, synth_txn_batch
        pairs = synth_txn_batch(TxnSpec(n=IB, seed=7, anomaly="mix"))
        t0 = time.monotonic()
        txn_graphs = [extract_txn_graph(h) for h, _ in pairs]
        t_extract = time.monotonic() - t0
        certify_batch(txn_graphs)                # warm the compiles
        itimes, istats, irs = [], {}, []
        for _ in range(max(2, repeats)):
            istats = {}
            t0 = time.monotonic()
            irs = certify_batch(txn_graphs, stats_out=istats)
            itimes.append(time.monotonic() - t0)
        t_iso = statistics.median(itimes)
        iso_section = {
            "hist_per_s": round(IB / t_iso, 2),
            "e2e_hist_per_s": round(IB / (t_extract + t_iso), 2),
            "extract_s": round(t_extract, 3),
            "device_s": round(t_iso, 3),
            "histories": IB,
            "levels": dict(sorted(Counter(
                r["level"] for r in irs).items())),
            "anomaly_mix": dict(sorted(Counter(
                a or "clean" for _, a in pairs).items())),
            "closure_matmuls": istats.get("closure_matmuls"),
            "mxu_macs_e9": round(istats.get("mxu_macs", 0.0) / 1e9, 3),
            "resilience": {k: istats.get(k, 0) for k in
                           ("retries", "bisections", "watchdog_fired",
                            "oom_events", "corrupt_chunks",
                            "quarantined_rows", "faults_injected")},
        }

    # ------------------------------------- run-durability (live WAL)
    # The run layer's crash durability (doc/resilience.md "Run-level
    # durability"): every worker-loop op appends to a fsynced,
    # group-committed WAL. Three figures: worker-loop ops/s with the
    # WAL on vs off (the acceptance gate: within 10% at the default
    # JT_WAL_FLUSH_MS), group-commit fsync latency percentiles, and
    # salvage throughput (ops/s reconstructed from a WAL segment).
    WOPS = int(os.environ.get("JT_BENCH_WAL_OPS", "20000"))
    durability_section = None
    if WOPS:
        import random as _rand
        import tempfile as _tempfile

        from jepsen_tpu import runtime as _runtime
        from jepsen_tpu.history.wal import WAL_FILE, HistoryWAL
        from jepsen_tpu.store import Store as _Store
        from jepsen_tpu.testing import atom_cas_test as _atom_test
        from jepsen_tpu.utils.core import Relatime as _Relatime

        def _loop_time(seed: int, wal=None) -> float:
            """Time the WORKER LOOP alone (run_case: clients + op loop
            + history appends), with/without a live WAL attached — the
            persistence tail (save_history) is deliberately outside
            the window, it exists in both modes and measures IO, not
            the WAL's group-commit tax."""
            t = _atom_test(n_ops=WOPS, concurrency=4, seed=seed)
            t["rng"] = _rand.Random(seed)
            t["clock"] = _Relatime()
            t["active_histories"] = set()
            t["barrier"] = None
            t["wal"] = wal
            t0 = time.monotonic()
            _runtime.run_case(t)
            return time.monotonic() - t0

        _loop_time(seed=0)                            # warm the path
        t_off = statistics.median(
            _loop_time(seed=i) for i in range(max(2, repeats)))
        wal_times, sync_ns = [], []
        with _tempfile.TemporaryDirectory() as td:
            for i in range(max(2, repeats)):
                wal = HistoryWAL(os.path.join(td, f"w{i}.jsonl"),
                                 header={"seed": 100 + i})
                wal.stamp_phase("run")
                wal_times.append(_loop_time(seed=100 + i, wal=wal))
                wal.close()
                sync_ns.extend(wal.sync_ns)
        t_on = statistics.median(wal_times)
        sync_ms = sorted(ns / 1e6 for ns in sync_ns)

        def _pct(xs, p):
            return _pct_nearest(xs, p, digits=3)

        # Salvage throughput: reconstruct a checkable history from a
        # crashed run's WAL (torn-tail drop + dangling completion +
        # standard-file materialize).
        with _tempfile.TemporaryDirectory() as td:
            st = _Store(td)
            h = st.create("bench-wal")
            wal = HistoryWAL(h.path(WAL_FILE), header={"seed": 999})
            wal.stamp_phase("run")
            _loop_time(seed=999, wal=wal)
            wal.close()
            name, ts = st.incomplete()[0]
            t0 = time.monotonic()
            sv = st.salvage(name, ts)
            t_salvage = time.monotonic() - t0
        durability_section = {
            "wal_ops": WOPS,
            "flush_ms": float(os.environ.get("JT_WAL_FLUSH_MS", "50")),
            "ops_per_s_wal_off": round(2 * WOPS / t_off, 1),
            "ops_per_s_wal_on": round(2 * WOPS / t_on, 1),
            "wal_overhead_pct": round(100.0 * (t_on - t_off)
                                      / max(t_off, 1e-9), 2),
            "group_commits": len(sync_ms),
            "flush_p50_ms": _pct(sync_ms, 50),
            "flush_p99_ms": _pct(sync_ms, 99),
            "salvage_ops_per_s": round(sv["ops"] / max(t_salvage, 1e-9),
                                       1),
            "salvage_dangling_completed": sv["dangling_completed"],
        }

    # ---------------------------------------- op-axis probe (10k ops)
    # The north star fixes 1k-op histories; this probes the op axis at
    # LB histories x 10k history lines (5k op pairs). The kernel scan
    # is O(events) sequential per row, so events/s should hold roughly
    # flat vs the headline run; a collapse here would mean the event
    # loop stalls on length and needs chunking/double-buffering
    # (doc/scaling.md "History length").
    LB = int(os.environ.get("JT_BENCH_LONG_B", "1000"))
    LOPS = int(os.environ.get("JT_BENCH_LONG_OPS", "5000"))
    XB = int(os.environ.get("JT_BENCH_XLONG_B", "100"))
    XOPS = int(os.environ.get("JT_BENCH_XLONG_OPS", "50000"))
    long_stats = xlong_stats = None

    # p_info=0: pinned info slots accumulate with history LENGTH
    # (1% of 5k pairs ~ 50 pinned slots >> any window), which is
    # the W axis, not the op axis. The probe measures op-axis
    # scaling; info-density costs are the headline run's domain.
    def probe(n_hist, n_ops, seed, keep_dev=None, scheduler_opts=None):
        # Same keyed workload shape as the headline run: the op axis
        # is where the partition pays twice — per-sub scan LENGTH
        # drops n_keys-fold (the sequential axis the long probe is
        # bound by) on top of the W collapse.
        t0 = time.monotonic()
        c_raw = synth_cas_columnar(n_hist, seed=seed, n_procs=5,
                                   n_ops=n_ops, n_values=5,
                                   corrupt=0.1, p_info=0.0,
                                   n_keys=n_keys)
        t_probe_synth = time.monotonic() - t0
        t0 = time.monotonic()
        p = partition_columnar(c_raw)
        t_part = time.monotonic() - t0
        c = p.cols if p is not None else c_raw
        t0 = time.monotonic()
        bkts, fails = encode(c)
        t_enc = time.monotonic() - t0
        dev, over, fail = route(bkts, fails)
        cpu = over + fail
        if keep_dev is not None:
            keep_dev.extend(dev)
        so = scheduler_opts or {}
        list(BucketScheduler(**so).run(dev))      # warm compile
        ts = []
        sch_stats = {}
        for _ in range(max(2, repeats)):
            sch = BucketScheduler(**so)
            t0 = time.monotonic()
            outs_p = [o for _, o in sch.run(dev)]
            ts.append(time.monotonic() - t0)
            sch_stats = sch.stats
        t = statistics.median(ts)
        n = sum(b.batch for b in dev)
        ev = sum(b.batch * b.ev_opidx.shape[-1] for b in dev)
        # fusion_ratio is original events per REAL scan step — padding
        # is not (anti-)fusion, so count ev_type != EV_PAD, not the
        # padded event axis (which events_per_s deliberately keeps for
        # continuity with earlier rounds' dispatched-steps figure).
        real_ev = sum(int((b.ev_type != 0).sum()) for b in dev)
        oev = sum(int(b.orig_n_events.sum())
                  if b.orig_n_events is not None
                  else int((b.ev_type != 0).sum()) for b in dev)
        bad = int(sum(int((~v).sum()) for v, _, _ in outs_p))
        return {"histories": n_hist,
                "sub_histories": c.batch,
                "synth_s": round(t_probe_synth, 3),
                "rate": round(n_hist * (n / max(c.batch, 1))
                              / (t_part + t_enc + t), 2),
                "events_per_s": round(ev / t, 1),
                "source_events_per_s": round(oev / t, 1),
                "fusion_ratio": round(oev / max(real_ev, 1), 4),
                "partition_s": round(t_part, 3),
                "encode_s": round(t_enc, 3),
                "device_s": round(t, 3),
                "event_routed_rows":
                    sch_stats.get("event_routed_rows", 0),
                "event_routed_dispatches":
                    sch_stats.get("event_routed_dispatches", 0),
                "cpu_routed": len(cpu), "invalid": bad}

    if LB:
        # Same W profile (p_info=0) at both lengths, so events/s is an
        # apples-to-apples per-event cost — the op-axis ratio should
        # hold near (or above, amortized dispatch) 1.0.
        short = probe(LB, n_ops, seed=3)
        long_ = probe(LB, LOPS, seed=2)
        # The event-chunked COST route (ops/schedule.py
        # event_route_min_events): long buckets dispatch as carried
        # EVENT_CHUNK-step kernels instead of one monolithic scan —
        # no longer only the post-OOM fallback. The routed pass forces
        # the route at this probe's shape (threshold 1) so the figure
        # exists at every bench scale; ``threshold_default`` is where
        # the cost model engages it unforced.
        from jepsen_tpu.ops.schedule import event_route_min_events
        # shard_min_rows pinned high: the figure isolates the
        # event-chunked kernel against the monolithic scan — on a
        # multi-device mesh the dataN route would otherwise win the
        # bucket first (the route precedence is wide/shard, then
        # event length).
        routed = probe(LB, LOPS, seed=2,
                       scheduler_opts={"event_route_events": 1,
                                       "shard_min_rows": 10**9})
        long_stats = {
            "ops_per_history": LOPS * 2,
            "long": long_,
            "short_same_shape": short,
            "op_axis_events_ratio": round(
                long_["events_per_s"]
                / max(short["events_per_s"], 1e-9), 3),
            "routed": {
                "threshold_default": event_route_min_events(),
                "events_per_s": routed["events_per_s"],
                "rate": routed["rate"],
                "event_routed_rows": routed["event_routed_rows"],
                "event_routed_dispatches":
                    routed["event_routed_dispatches"],
                "vs_monolithic": round(
                    routed["events_per_s"]
                    / max(long_["events_per_s"], 1e-9), 3),
            },
        }

    if XB:
        # 100k-op probe: where does the time go when one history is 100
        # thousand lines — encode walk or device scan? encode_s vs
        # device_s is the breakdown VERDICT round 5 asked for. The
        # event axis can also dispatch in carried chunks
        # (run_event_chunked, double-buffered by jax's async dispatch);
        # JT_BENCH_EVENT_CHUNK > 0 measures that path too so a scan-
        # length stall would show up as chunking winning.
        xdev = []
        xlong_stats = {"ops_per_history": XOPS * 2,
                       **probe(XB, XOPS, seed=4, keep_dev=xdev)}
        echunk = int(os.environ.get("JT_BENCH_EVENT_CHUNK", "8192"))
        if echunk:
            from jepsen_tpu.ops.linearize import run_event_chunked
            dev = [b for b in xdev if b.W <= DATA_MAX_SLOTS]
            for b in dev:                         # warm the compiles
                run_event_chunked(b, echunk)
            ts = []
            for _ in range(max(2, repeats)):
                t0 = time.monotonic()
                for b in dev:
                    run_event_chunked(b, echunk)
                ts.append(time.monotonic() - t0)
            ev = sum(b.batch * b.ev_opidx.shape[-1] for b in dev)
            t = statistics.median(ts)
            xlong_stats["event_chunked"] = {
                "chunk_events": echunk,
                "device_s": round(t, 3),
                "events_per_s": round(ev / t, 1)}

    # ------------------------------------------- on-device synthesis
    # Generate-where-you-check (ops/synth_device, doc/scaling.md): the
    # host numpy generator vs the jitted counter-PRNG device generator
    # at the headline shape, the streamed generate→partition→encode→
    # dispatch source's time-to-first-dispatch, and the witness-guided
    # fuzz loop's iteration rate. The CPU backend is a proxy — the
    # generator is pure vmapped-style array code, so an accelerator
    # backend scales it with its parallel throughput while the host
    # generator stays a host generator.
    synth_section = None
    SDB = int(os.environ.get("JT_BENCH_SYNTH_B", str(B)))
    if SDB:
        from jepsen_tpu.ops.schedule import iter_synth_groups
        from jepsen_tpu.workloads.synth import cas_kind_vocabulary
        sd_spec = _dc_replace(headline_spec, n=SDB)
        if synth_mode == "host" and SDB == B:
            t_host_synth = t_synth
        else:
            t0 = time.monotonic()
            synth_cas_columnar(SDB, seed=1, n_procs=5, n_ops=n_ops,
                               n_values=5, corrupt=0.1, p_info=0.01,
                               n_keys=n_keys)
            t_host_synth = time.monotonic() - t0
        # key_meta=False is the generator exactly as the check source
        # consumes it (the per-key histograms are the headline device
        # mode's extra), and it lets the rate, streamed, and fuzz
        # figures below share ONE compiled generator shape — compiles
        # here run uncached under the hermetic test contract.
        synthesize(sd_spec, "device", key_meta=False)     # compile
        sd_times = []
        for _ in range(max(2, repeats)):
            t0 = time.monotonic()
            synthesize(sd_spec, "device", key_meta=False)
            sd_times.append(time.monotonic() - t0)
        t_dev_synth = statistics.median(sd_times)

        # Streamed synth source: the scheduler pulls generated groups
        # directly (zero host Op lists, zero full-batch materialize);
        # t_first_dispatch is how long the device idles before the
        # first generated chunk ships.
        from jepsen_tpu.ops.linearize import WindowOverflow as _WO
        from jepsen_tpu.ops.schedule import DIVERTED as _DIV
        space_sd = enumerate_statespace(model,
                                        cas_kind_vocabulary(5), 64)

        def run_synth_streamed():
            sch = BucketScheduler()
            n = 0
            for bt, out in sch.run(iter_synth_groups(space_sd, sd_spec,
                                                     max_slots=eff_slots)):
                if out is _DIV or isinstance(out, _WO):
                    continue
                n += bt.batch
            return n, sch.stats

        run_synth_streamed()                     # warm the shapes
        t0 = time.monotonic()
        n_sd, sd_stats = run_synth_streamed()
        t_sd_e2e = time.monotonic() - t0

        fuzz_section = None
        if os.environ.get("JT_BENCH_FUZZ", "1") != "0":
            from jepsen_tpu.fuzz import fuzz_campaign
            fz_spec = _dc_replace(sd_spec, n=min(SDB, 256))
            fuzz_campaign(fz_spec, rounds=1, neighborhood=2,
                          max_witnesses=4, name=None)   # warm
            t0 = time.monotonic()
            fz = fuzz_campaign(fz_spec, rounds=1, neighborhood=2,
                               max_witnesses=4, name=None)
            t_fz = time.monotonic() - t0
            fuzz_section = {
                "histories": fz["checked"],
                "neighborhoods": fz["neighborhoods"],
                "neighborhood_invalid": fz["neighborhood_invalid"],
                "iters_per_s": round((fz["checked"]
                                      + fz["neighborhoods"]) / t_fz, 2),
                "min_anomaly_lines": fz["min_anomaly_lines"],
            }
        synth_section = {
            "histories": SDB,
            "mode": synth_mode,
            "host_s": round(t_host_synth, 3),
            "device_s": round(t_dev_synth, 3),
            "host_hist_per_s": round(SDB / t_host_synth, 1),
            "device_hist_per_s": round(SDB / t_dev_synth, 1),
            "host_ops_per_s": round(SDB * 2 * n_ops / t_host_synth, 1),
            "device_ops_per_s": round(SDB * 2 * n_ops / t_dev_synth, 1),
            "device_vs_host_speedup": round(t_host_synth / t_dev_synth,
                                            2),
            # Explicitly per SUB-history: the streamed source yields
            # partitioned (history, key) rows, and normalizing back to
            # original histories would need a second full-batch
            # partition pass — so the unit is named instead of mixed
            # in with the per-history figures above.
            "streamed_gen_check_subs_per_s": round(n_sd / t_sd_e2e, 2)
            if n_sd else None,
            "streamed_subs_checked": n_sd,
            "t_first_dispatch_s": sd_stats.get("t_first_dispatch_s"),
            "fuzz": fuzz_section,
        }

    # ------------------------------------------------ telemetry (spans)
    # The observability spine (jepsen_tpu/telemetry.py,
    # doc/observability.md): a headline-shaped workload runs untraced
    # then traced (the ≤5% overhead budget, measured), a journaled
    # traced pass proves span coverage (encode / dispatch / decode /
    # journal per chunk), and the dispatch-gap analyzer reports
    # device-busy vs host-gap fractions with the top gap causes — the
    # direct diagnostic for the dispatch-latency plateau. With
    # JT_TRACE=1 on the whole process the HEADLINE run's spans are in
    # the flight recorder too, and everything exports as a
    # Chrome-trace/Perfetto trace.json ($JT_TRACE_EXPORT, default
    # ./trace.json). JT_BENCH_TRACE=0 skips; JT_BENCH_TRACE_B sizes
    # the section's workload.
    tel_section = None
    if os.environ.get("JT_BENCH_TRACE", "1") != "0":
        import tempfile as _tel_tf

        from jepsen_tpu import telemetry as _tel
        from jepsen_tpu.ops.linearize import check_columnar as _tel_cc
        from jepsen_tpu.store import ChunkJournal as _TelCJ

        ambient = _tel.enabled()
        headline_spans = _tel.spans() if ambient else []
        TB = min(int(os.environ.get("JT_BENCH_TRACE_B", "512")), B)
        tcols = synth_cas_columnar(TB, seed=11, n_procs=5, n_ops=n_ops,
                                   n_values=5, corrupt=0.1, p_info=0.01,
                                   n_keys=n_keys)

        def tel_run(journal=None):
            return _tel_cc(model, tcols, journal=journal)

        tel_run()                             # warm the shapes
        _tel.configure(False)
        off_ts = []
        for _ in range(max(2, repeats)):
            t0 = time.monotonic()
            tel_run()
            off_ts.append(time.monotonic() - t0)
        t_tr_off = statistics.median(off_ts)
        _tel.configure(True)
        on_ts = []
        for _ in range(max(2, repeats)):
            _tel.reset()
            t0 = time.monotonic()
            tel_run()
            on_ts.append(time.monotonic() - t0)
        t_tr_on = statistics.median(on_ts)
        gap = _tel.gaps()                     # the last traced pass
        # One journaled traced pass: the ChunkJournal sink adds the
        # journal span per retired chunk — full coverage proof.
        _tel.reset()
        with _tel_tf.TemporaryDirectory() as td:
            tj = _TelCJ(os.path.join(td, "bench-tel.journal.jsonl"),
                        {"bench": "telemetry"})
            tel_run(journal=tj)
            tj.finish()
        journaled = _tel.spans()
        kinds = sorted({r["name"] for r in journaled
                        if r.get("ph") == "X"})
        trace_json = None
        trace_events = 0
        if ambient:
            trace_json = os.environ.get("JT_TRACE_EXPORT", "trace.json")
            trace_events = _tel.export_chrome(
                trace_json, headline_spans + journaled)
        _tel.configure("env")                 # restore the ambient mode
        tel_section = {
            "histories": TB,
            "untraced_s": round(t_tr_off, 3),
            "traced_s": round(t_tr_on, 3),
            "overhead_pct": round(100.0 * (t_tr_on - t_tr_off)
                                  / max(t_tr_off, 1e-9), 2),
            "span_kinds": kinds,
            "spans": len(journaled),
            "device_busy_frac": gap["device_busy_frac"],
            "host_gap_frac": gap["host_gap_frac"],
            "n_gaps": gap["n_gaps"],
            "top_gap_causes": gap["top_gap_causes"][:5],
            # Device-busy union per backend family (the family= span
            # attribute): wgl = lax.scan kernels, wgl-pallas = the
            # Pallas megakernel, graph = the MXU closure.
            "device_busy_by_family": gap.get("device_busy_by_family",
                                             {}),
            "ambient_trace": ambient,
            "trace_json": trace_json,
            "trace_events": trace_events,
        }

    # ---- online checker daemon (jepsen_tpu.online, doc/online.md):
    # tenants' WALs written live by background writer threads while the
    # daemon polls — time-to-first-verdict percentiles and verdicts/s
    # WHILE the histories are still being written (the whole point of
    # the service), then a forced overload burst with shrunken ladder
    # thresholds proving graceful degradation (shed fraction, deferred
    # tenants) without losing any verdict. CPU-safe at the default toy
    # scale; JT_BENCH_ONLINE=0 skips, JT_BENCH_ONLINE_TENANTS /
    # JT_BENCH_ONLINE_OPS size it.
    online_section = None
    if os.environ.get("JT_BENCH_ONLINE", "1") != "0":
        import tempfile as _on_tf
        import threading as _on_thr

        from jepsen_tpu.history.codec import dumps_op as _on_dumps
        from jepsen_tpu.history.ops import invoke_op as _on_inv, \
            ok_op as _on_ok
        from jepsen_tpu.history.wal import WAL_MAGIC as _ON_MAGIC, \
            WAL_FILE as _ON_WAL
        from jepsen_tpu.online import OnlineConfig, OnlineDaemon
        from jepsen_tpu.store import Store as _OnStore

        OT = int(os.environ.get("JT_BENCH_ONLINE_TENANTS", "3"))
        OPAIRS = int(os.environ.get("JT_BENCH_ONLINE_OPS", "60"))

        def _on_ops(n_pairs, start=0, mod=None):
            # ``mod`` cycles the written values (bounded vocabulary —
            # the incremental subsection's live-stream shape); None
            # keeps the growing-value stream.
            ops, idx = [], start * 4
            for k in range(start, start + n_pairs):
                v = (k % mod) + 1 if mod else k + 1
                for op in (_on_inv(0, "write", v),
                           _on_ok(0, "write", v),
                           _on_inv(0, "read", None),
                           _on_ok(0, "read", v)):
                    op.index = idx
                    idx += 1
                    ops.append(op)
            return ops

        def _on_write(path, lines, mode="a"):
            with open(path, mode) as f:
                f.write("\n".join(lines) + "\n")

        def _on_head(seed):
            return [json.dumps({"wal": _ON_MAGIC, "pid": os.getpid(),
                                "seed": seed,
                                "test": {"name": f"bench-{seed}"},
                                "phase": "setup"}),
                    json.dumps({"phase": "run", "wal_ops": 0})]

        def _writer(path, seed, stages=6, pause=0.05):
            _on_write(path, _on_head(seed), mode="w")
            per = max(1, OPAIRS // stages)
            done = 0
            while done < OPAIRS:
                n = min(per, OPAIRS - done)
                _on_write(path, [_on_dumps(o)
                                 for o in _on_ops(n, start=done)])
                done += n
                time.sleep(pause)
            _on_write(path, [json.dumps({"phase": "analyzed",
                                         "wal_ops": OPAIRS * 4})])

        with _on_tf.TemporaryDirectory() as td:
            base = Path(td) / "store"
            paths = []
            for i in range(OT):
                d = base / f"bench-online-{i}" / "r1"
                d.mkdir(parents=True)
                paths.append(d / _ON_WAL)
            daemon = OnlineDaemon(
                store=_OnStore(base),
                config=OnlineConfig(model=model, poll_s=0,
                                    check_interval_ops=8,
                                    crash_quiet_s=3600))
            writers = [_on_thr.Thread(target=_writer, args=(p, i),
                                      daemon=True)
                       for i, p in enumerate(paths)]
            t0 = time.monotonic()
            for w in writers:
                w.start()
            while any(w.is_alive() for w in writers):
                daemon.tick()
                time.sleep(0.005)
            t_writing = time.monotonic() - t0
            checks_while_writing = daemon.stats["checks"]
            for _ in range(50):
                daemon.tick()
                if daemon.idle():
                    break
            ttfvs = sorted(t.t_first_verdict - t.t_admitted
                           for t in daemon.tenants.values()
                           if t.t_first_verdict is not None)
            tenants_valid = all(
                (t.result or {}).get("valid") is True
                for t in daemon.tenants.values())
            daemon.close()

            # Forced overload burst: pre-written backlogs + shrunken
            # ladder thresholds — the daemon must degrade (widen →
            # shed → defer), then still land every verdict.
            bbase = Path(td) / "burst"
            for i in range(OT):
                d = bbase / f"burst-{i}" / "r1"
                d.mkdir(parents=True)
                _on_write(d / _ON_WAL,
                          _on_head(100 + i)
                          + [_on_dumps(o) for o in _on_ops(OPAIRS)],
                          mode="w")
            pend = OPAIRS * 4
            burst = OnlineDaemon(
                store=_OnStore(bbase),
                config=OnlineConfig(model=model, poll_s=0,
                                    check_interval_ops=8,
                                    crash_quiet_s=3600,
                                    overload_pending_ops=pend // 2,
                                    shed_pending_ops=pend,
                                    defer_pending_ops=2 * pend))
            for _ in range(60):
                burst.tick()
                if all(t.status == "tailing" and t.pending == 0
                       and len(t.ops) == pend
                       for t in burst.tenants.values()):
                    break
            burst.cfg.crash_quiet_s = 0
            for t in burst.tenants.values():
                t.state.header = dict(t.state.header or {}, pid=-1)
                t.last_growth = 0.0
            for _ in range(10):
                burst.tick()
                if burst.idle():
                    break
            bs = burst.stats
            burst_valid = all((t.result or {}).get("valid") is True
                              for t in burst.tenants.values())
            burst.close()

        # ---- incremental subsection (ISSUE 14): per-tick check cost
        # vs a growing prefix under the resident device frontier
        # (JT_ONLINE_INCREMENTAL=1, the default) against full-recheck
        # mode (the =0 restore switch) as the baseline. The prefix
        # grows JT_BENCH_ONLINE_INC_STAGES-fold over the run; the
        # acceptance shape is the per-tick cost curve staying flat
        # (within 2x) in incremental mode while interim AND final
        # verdicts stay field-for-field identical between the modes.
        # Values cycle mod 8 so the state space is bounded — the live
        # production stream shape. Size up with the env knobs for the
        # committed figure (100+ tenants on a real box).
        IT = int(os.environ.get("JT_BENCH_ONLINE_INC_TENANTS", "3"))
        ISTAGES = int(os.environ.get("JT_BENCH_ONLINE_INC_STAGES",
                                     "10"))
        IPAIRS = int(os.environ.get("JT_BENCH_ONLINE_INC_PAIRS", "8"))

        from jepsen_tpu.history.codec import write_jsonl as _on_wj
        from jepsen_tpu.history.core import index as _on_index

        def _inc_ops(n_pairs, start=0):
            return _on_ops(n_pairs, start=start, mod=8)

        inc_modes = {}
        inc_verdicts = {}
        for inc_mode, inc_on in (("incremental", True),
                                 ("full", False)):
            with _on_tf.TemporaryDirectory() as td2:
                ibase = Path(td2) / "store"
                idirs = []
                for i in range(IT):
                    dd = ibase / f"inc-{i}" / "r1"
                    dd.mkdir(parents=True)
                    _on_write(dd / _ON_WAL,
                              _on_head(i) + [_on_dumps(o)
                                             for o in _inc_ops(IPAIRS)],
                              mode="w")
                    idirs.append(dd)
                idaemon = OnlineDaemon(
                    store=_OnStore(ibase),
                    config=OnlineConfig(model=model, poll_s=0,
                                        check_interval_ops=4,
                                        crash_quiet_s=3600,
                                        incremental=inc_on))
                t0 = time.perf_counter()
                idaemon.tick()
                boot_s = time.perf_counter() - t0
                tick_s = []
                interim = []
                for stage in range(1, ISTAGES):
                    for dd in idirs:
                        _on_write(dd / _ON_WAL,
                                  [_on_dumps(o) for o in
                                   _inc_ops(IPAIRS,
                                            start=stage * IPAIRS)])
                    t0 = time.perf_counter()
                    idaemon.tick()
                    tick_s.append(time.perf_counter() - t0)
                    interim.append(tuple(
                        t.valid_so_far for _, t in
                        sorted(idaemon.tenants.items())))
                full_h = _on_index([o.with_() for o in
                                    _inc_ops(ISTAGES * IPAIRS)])
                for dd in idirs:
                    _on_wj(dd / "history.jsonl", full_h)
                    _on_write(dd / _ON_WAL,
                              [json.dumps({"phase": "analyzed",
                                           "wal_ops": len(full_h)})])
                for _ in range(10):
                    idaemon.tick()
                    if idaemon.idle():
                        break
                ittfv = sorted(t.t_first_verdict - t.t_admitted
                               for t in idaemon.tenants.values()
                               if t.t_first_verdict is not None)
                inc_verdicts[inc_mode] = (interim, {
                    f"{k[0]}/{k[1]}": json.loads(json.dumps(
                        t.result, default=repr))
                    for k, t in sorted(idaemon.tenants.items())})
                st = idaemon.stats
                inc_modes[inc_mode] = {
                    "bootstrap_tick_s": round(boot_s, 4),
                    "tick_cost_s": [round(x, 4) for x in tick_s],
                    "tick_cost_first_s": round(tick_s[0], 4),
                    "tick_cost_last_s": round(tick_s[-1], 4),
                    "cost_ratio_last_vs_first": round(
                        tick_s[-1] / max(tick_s[0], 1e-9), 3),
                    "checks": st["checks"],
                    "delta_ops": st["delta_ops"],
                    "frontier_resumes": st["frontier_resumes"],
                    "frontier_invalidations":
                        st["frontier_invalidations"],
                    "ttfv_p99_s": _pct_nearest(ittfv, 99),
                    "verdicts_per_s": round(
                        st["checks"] / max(sum(tick_s) + boot_s,
                                           1e-9), 2),
                    "valid_ok": all(
                        (t.result or {}).get("valid") is True
                        for t in idaemon.tenants.values()),
                }
                idaemon.close()

        _pct = _pct_nearest

        online_section = {
            "tenants": OT,
            "ops_per_tenant": OPAIRS * 4,
            "ttfv_p50_s": _pct(ttfvs, 50),
            "ttfv_p99_s": _pct(ttfvs, 99),
            "verdicts_per_s_while_writing":
                round(checks_while_writing / max(t_writing, 1e-9), 2),
            "interim_checks_while_writing": checks_while_writing,
            "checks": daemon.stats["checks"],
            "finalized": daemon.stats["finalized"],
            "valid_ok": tenants_valid,
            "burst": {
                "checks": bs["checks"],
                "shed": bs["shed"],
                "shed_fraction": round(bs["shed"]
                                       / max(bs["checks"], 1), 4),
                "deferred": bs["deferred"],
                "widened": bs["widened"],
                "resumed": bs["resumed"],
                "valid_ok": burst_valid,
            },
            "incremental": {
                "tenants": IT,
                "stages": ISTAGES,
                "pairs_per_stage": IPAIRS,
                "prefix_growth": ISTAGES,
                "modes": inc_modes,
                # Field-for-field: every interim verdict tuple AND
                # every final result dict identical across the modes
                # (the ISSUE 14 acceptance parity).
                "verdicts_match":
                    inc_verdicts["incremental"] == inc_verdicts["full"],
            },
        }

    # -------------------------------------------------------- fleet
    # The campaign orchestrator (jepsen_tpu/fleet.py, doc/fleet.md):
    # the r05 headline workload split into JT_BENCH_FLEET_SEEDS seed
    # units and sharded across 1/2/4/8 local worker processes — the
    # MULTICHIP_r07 curve. Unlike the r06 virtual-mesh curve (one CPU
    # pretending to be 8 devices, wall-clock flat by construction),
    # fleet workers are real OS processes: speedup tracks the host's
    # real core count (reported per point as parallel_efficiency —
    # the schema addition r07 asks every later curve to carry).
    # JT_BENCH_FLEET=0 skips; JT_BENCH_FLEET_WORKERS sizes the sweep;
    # JT_BENCH_FLEET_CURVE=<path> also writes the standalone
    # MULTICHIP-shape file.
    fleet_section = None
    if os.environ.get("JT_BENCH_FLEET", "1") != "0":
        import shutil as _fl_shutil
        import tempfile as _fl_tf

        from jepsen_tpu.fleet import CostRouter, fleet_campaign
        from jepsen_tpu.store import Store as _FlStore

        # Ascending worker counts: the first (smallest) point is the
        # speedup/efficiency BASELINE — named in the section so an
        # override without a 1-worker point can't silently mislabel
        # the published curve as 1-worker-relative.
        FW = sorted({int(x) for x in
                     os.environ.get("JT_BENCH_FLEET_WORKERS",
                                    "1,2,4,8").split(",")
                     if x.strip()})
        FSEEDS = int(os.environ.get("JT_BENCH_FLEET_SEEDS", "8"))
        FB = int(os.environ.get("JT_BENCH_FLEET_B", str(B)))
        fl_spec = _dc_replace(headline_spec,
                              n=max(1, FB // max(FSEEDS, 1)))
        points = []
        t_base = None
        base_workers = FW[0] if FW else 1
        troot = _fl_tf.mkdtemp(prefix="jt-bench-fleet-")
        try:
            for w in FW:
                t0 = time.monotonic()
                fl_out = fleet_campaign(
                    name=f"bench-fleet-w{w}", kind="synth",
                    seeds=range(FSEEDS), spec=fl_spec, workers=w,
                    store_root=_FlStore(os.path.join(troot,
                                                     f"w{w}")))
                e2e = time.monotonic() - t0
                if t_base is None:
                    t_base = e2e
                points.append({
                    "workers": w,
                    # The pool the orchestrator actually ran: local
                    # width caps at host_cores by default
                    # (JT_FLEET_MAX_LOCAL_WORKERS) — oversubscribed
                    # local jax workers measure SLOWER than fewer.
                    "spawned": fl_out["spawned_workers"],
                    "e2e_s": round(e2e, 3),
                    "hist_per_s": round(FSEEDS * fl_spec.n / e2e, 2),
                    "speedup": round(t_base / e2e, 3),
                    "parallel_efficiency": round(
                        t_base * base_workers / (max(w, 1) * e2e), 4),
                    "invalid": fl_out["invalid"],
                    "takeovers": fl_out["leases"]["takeovers"],
                    # Local workers check on virtual CPU devices (one
                    # process per chip): a CPU figure, named as one.
                    "worker_platforms": sorted({
                        (wk.get("device") or {}).get("platform", "?")
                        for wk in fl_out["workers"].values()}),
                })
        finally:
            _fl_shutil.rmtree(troot, ignore_errors=True)
        # Monotone within 15% jitter: more workers never MEANINGFULLY
        # slower (each point is one wall-clock sample of a whole
        # multi-process campaign; single-sample noise on a loaded box
        # runs ~10%, and the capped pool makes beyond-cores points
        # flat rather than strictly faster).
        monotone = all(points[i + 1]["e2e_s"]
                       <= points[i]["e2e_s"] * 1.15
                       for i in range(len(points) - 1))
        at4 = next((p["speedup"] for p in points
                    if p["workers"] == 4), None)
        fleet_section = {
            "histories": FSEEDS * fl_spec.n,
            "seeds": FSEEDS,
            "ops_per_history": n_ops * 2,
            "host_cores": os.cpu_count(),
            "points": points,
            "baseline_workers": base_workers,
            "monotone": monotone,
            "speedup_at_4_workers": at4,
            "router_table": CostRouter().table(),
        }
        curve_path = os.environ.get("JT_BENCH_FLEET_CURVE")
        if curve_path:
            with open(curve_path, "w") as f:
                json.dump({
                    "batch": FSEEDS * fl_spec.n,
                    "ops_per_history": n_ops * 2,
                    "host_cores": os.cpu_count(),
                    "baseline_workers": base_workers,
                    "note": ("fleet campaign orchestrator: the r05 "
                             "headline workload sharded across real "
                             "worker PROCESSES via filesystem leases "
                             "— real parallelism bounded by host "
                             "cores, unlike the r06 virtual mesh"),
                    "points": points}, f, indent=2)
                f.write("\n")

    # ------------------------------------------------------- service
    # The federated checking service (jepsen_tpu/service.py,
    # doc/service.md): a store of crashed tenants served to final
    # verdicts by 1..N real worker PROCESSES coordinating purely
    # through tenant leases (tenants-per-SLO vs workers), then a
    # kill-a-worker probe — two workers split LIVE tenants, one is
    # SIGKILLed, and the per-tenant latency from the kill to the
    # survivor's gen+1 re-claim lands as p50/p99 (the lease TTL
    # dominates by construction; the figure proves the BOUND) — the
    # MULTICHIP_r08 measurement. JT_BENCH_SERVICE=0 skips;
    # _WORKERS/_TENANTS/_OPS/_SLO_S size it; JT_BENCH_SERVICE_CURVE
    # writes the standalone curve file.
    service_section = None
    if os.environ.get("JT_BENCH_SERVICE", "1") != "0":
        import shutil as _sv_shutil
        import tempfile as _sv_tf

        from jepsen_tpu.history.codec import dumps_op as _sv_dumps, \
            write_jsonl as _sv_wjsonl
        from jepsen_tpu.history.core import index as _sv_index
        from jepsen_tpu.history.ops import invoke_op as _sv_inv, \
            ok_op as _sv_ok
        from jepsen_tpu.history.wal import WAL_FILE as _SV_WAL, \
            WAL_MAGIC as _SV_MAGIC
        from jepsen_tpu.service import (_spawn_service_worker,
                                        save_budget as _sv_save_budget,
                                        serve_store, service_summary)
        from jepsen_tpu.store import Store as _SvStore

        SVW = sorted({int(x) for x in
                      os.environ.get("JT_BENCH_SERVICE_WORKERS",
                                     "1,2").split(",") if x.strip()})
        SVT = int(os.environ.get("JT_BENCH_SERVICE_TENANTS", "4"))
        SVP = int(os.environ.get("JT_BENCH_SERVICE_OPS", "24"))
        SV_SLO = float(os.environ.get("JT_BENCH_SERVICE_SLO_S", "30"))
        SV_TTL = 2.0

        _sv_pct = _pct_nearest

        def _sv_ops(n_pairs):
            ops, idx = [], 0
            for k in range(n_pairs):
                for op in (_sv_inv(0, "write", k + 1),
                           _sv_ok(0, "write", k + 1),
                           _sv_inv(0, "read", None),
                           _sv_ok(0, "read", k + 1)):
                    op.index = idx
                    idx += 1
                    ops.append(op)
            return ops

        def _sv_mkrun(base, i, pid):
            d = Path(base) / f"svc-{i}" / "r1"
            d.mkdir(parents=True, exist_ok=True)
            lines = [json.dumps({"wal": _SV_MAGIC, "pid": pid,
                                 "seed": i,
                                 "test": {"name": f"svc-{i}"},
                                 "phase": "setup"}),
                     json.dumps({"phase": "run", "wal_ops": 0})]
            lines += [_sv_dumps(o) for o in _sv_ops(SVP)]
            (d / _SV_WAL).write_text("\n".join(lines) + "\n")
            return d

        _sv_base_args = ["--model", "cas", "--poll", "0.05",
                         "--interval", "8",
                         "--lease-ttl", str(SV_TTL),
                         "--claim-budget", "8"]
        points = []
        for w in SVW:
            td = _sv_tf.mkdtemp(prefix="jt-bench-svc-")
            try:
                st = _SvStore(Path(td) / "store")
                for i in range(SVT):
                    _sv_mkrun(st.base, i, pid=-1)   # dead writers
                t0 = time.monotonic()
                serve_store(store=st, workers=max(w, 1),
                            until_idle=True, lease_ttl=SV_TTL,
                            poll_s=0.05,
                            worker_args=_sv_base_args
                            + ["--max-tenants", str(SVT)])
                e2e = time.monotonic() - t0
                ttfvs, ok = [], 0
                for i in range(SVT):
                    v = st.online_verdict(f"svc-{i}", "r1") or {}
                    ok += v.get("valid") is True
                    if v.get("ttfv_s") is not None:
                        ttfvs.append(float(v["ttfv_s"]))
                ttfvs.sort()
                points.append({
                    "workers": w,
                    "e2e_s": round(e2e, 3),
                    "tenants_per_s": round(SVT / max(e2e, 1e-9), 3),
                    "ttfv_p50_s": _sv_pct(ttfvs, 50),
                    "ttfv_p99_s": _sv_pct(ttfvs, 99),
                    "tenants_within_slo": sum(1 for x in ttfvs
                                              if x <= SV_SLO),
                    "valid_ok": ok == SVT,
                    # Local workers check on virtual CPU devices (one
                    # process per chip): a CPU figure, named as one.
                    "worker_platforms": sorted({
                        (r.get("device") or {}).get("platform", "?")
                        for r in st.service_workers().values()}),
                })
            finally:
                _sv_shutil.rmtree(td, ignore_errors=True)

        # Kill-a-worker takeover probe: two workers split LIVE
        # tenants (writer pid = this process), one dies by SIGKILL,
        # survivors re-claim at gen+1 — latency measured per orphan.
        takeover = None
        td = _sv_tf.mkdtemp(prefix="jt-bench-svc-kill-")
        try:
            st = _SvStore(Path(td) / "store")
            dirs = [_sv_mkrun(st.base, i, pid=os.getpid())
                    for i in range(SVT)]
            _sv_save_budget(st)
            half = max(1, SVT // 2)

            def _owned(wid):
                n = 0
                for i in range(SVT):
                    try:
                        rec = json.loads(st.service_tenant_lease_path(
                            f"svc-{i}", "r1").read_text())
                    except Exception:
                        continue
                    n += rec.get("worker") == wid
                return n

            pA = _spawn_service_worker(
                st, "kill-a", _sv_base_args
                + ["--max-tenants", str(half), "--until-idle"])
            pB = None
            try:
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline and \
                        _owned("kill-a") < half:
                    time.sleep(0.05)
                pB = _spawn_service_worker(
                    st, "kill-b", _sv_base_args
                    + ["--max-tenants", str(SVT), "--until-idle"])
                while time.monotonic() < deadline and \
                        _owned("kill-b") < SVT - half:
                    time.sleep(0.05)
                orphans = []
                for i in range(SVT):
                    try:
                        rec = json.loads(
                            st.service_tenant_lease_path(
                                f"svc-{i}", "r1").read_text())
                    except Exception:
                        continue        # never claimed: not an orphan
                    if rec.get("worker") == "kill-a":
                        orphans.append(i)
                t_kill = time.monotonic()
                pA.kill()
                pA.wait()
                lat = {}
                deadline = time.monotonic() + 90
                while time.monotonic() < deadline and \
                        len(lat) < len(orphans):
                    for i in orphans:
                        if i in lat:
                            continue
                        try:
                            rec = json.loads(
                                st.service_tenant_lease_path(
                                    f"svc-{i}", "r1").read_text())
                        except Exception:
                            continue
                        if int(rec.get("gen") or 0) >= 1:
                            lat[i] = round(time.monotonic() - t_kill, 4)
                    time.sleep(0.02)
                # Finalize everything so the survivor drains and
                # exits (analyzed stamp → stored-history path).
                for i in range(SVT):
                    _sv_wjsonl(dirs[i] / "history.jsonl", _sv_index(
                        [o.with_() for o in _sv_ops(SVP)]))
                    with open(dirs[i] / _SV_WAL, "a") as f:
                        f.write(json.dumps(
                            {"phase": "analyzed",
                             "wal_ops": SVP * 4}) + "\n")
                try:
                    pB.wait(timeout=180)
                except Exception:
                    pB.kill()
                    pB.wait()
            finally:
                for p in (pA, pB):
                    if p is None:
                        continue
                    if p.poll() is None:
                        p.kill()
                        p.wait()
                    getattr(p, "_jt_log", None) and p._jt_log.close()
            lats = sorted(lat.values())
            ksumm = service_summary(st)
            takeover = {
                "tenants": SVT,
                "killed_owned": len(orphans),
                "measured": len(lats),
                "lease_ttl_s": SV_TTL,
                "latency_p50_s": _sv_pct(lats, 50),
                "latency_p99_s": _sv_pct(lats, 99),
                "gen_bumps": ksumm["leases"]["gen_bumps"],
                "takeovers": ksumm["leases"]["takeovers"],
                "valid_ok": ksumm["valid"],
            }
        finally:
            _sv_shutil.rmtree(td, ignore_errors=True)

        service_section = {
            "tenants": SVT,
            "ops_per_tenant": SVP * 4,
            "host_cores": os.cpu_count(),
            "slo_s": SV_SLO,
            "points": points,
            "takeover": takeover,
        }
        curve_path = os.environ.get("JT_BENCH_SERVICE_CURVE")
        if curve_path:
            with open(curve_path, "w") as f:
                json.dump({
                    "tenants": SVT, "ops_per_tenant": SVP * 4,
                    "host_cores": os.cpu_count(),
                    "slo_s": SV_SLO, "lease_ttl_s": SV_TTL,
                    "note": ("federated checking service: crashed "
                             "tenants served to final verdicts by "
                             "real worker processes coordinating "
                             "through tenant leases; takeover = "
                             "SIGKILL one of two workers holding "
                             "live tenants, latency from the kill "
                             "to the survivor's gen+1 re-claim "
                             "(lease TTL dominates by construction)"),
                    "points": points, "takeover": takeover},
                    f, indent=2)
                f.write("\n")

    # ---- Pallas-vs-XLA backend comparison (ISSUE 12): the measured
    # rate table behind the cost router's crossover — both WGL device
    # backends timed on the same synthetic bucket per W class, plus
    # the startup probe the router actually priced from. The doc
    # rate table (doc/scaling.md "Hand-schedule the inner loop") is
    # this section, committed. JT_BENCH_BACKEND_COMPARE=0 skips;
    # JT_BENCH_COMPARE_WS / _B / _EVENTS size it.
    backend_compare = None
    if os.environ.get("JT_BENCH_BACKEND_COMPARE", "1") != "0":
        from jepsen_tpu.ops import dc_monitor as _dc
        from jepsen_tpu.ops import pallas_wgl as _pw
        from jepsen_tpu.ops.linearize import get_kernel as _bc_getk
        ws = [int(w) for w in os.environ.get(
            "JT_BENCH_COMPARE_WS",
            "4,6,8,10,11,12").split(",") if w.strip()]
        CBB = int(os.environ.get("JT_BENCH_COMPARE_B", "256"))
        CBE = int(os.environ.get("JT_BENCH_COMPARE_EVENTS", "256"))
        points = []
        for w in ws:
            args_w = _pw.make_probe_batch(V=8, W=w, rows=CBB,
                                          events=CBE)
            t_x = _pw._time_kernel(_bc_getk(8, w, shared_target=True),
                                   args_w, repeats)
            point = {"W": w, "rows": CBB, "events": CBE,
                     "xla_hist_per_s": round(CBB / max(t_x, 1e-9), 2),
                     "pallas_hist_per_s": None,
                     "pallas_speedup": None,
                     "dc_hist_per_s": None,
                     "dc_speedup": None, "winner": "xla"}
            if _pw.pallas_available() and _pw.pallas_supports(8, w):
                try:
                    pk = _pw.get_pallas_kernel(8, w, shared_target=True)
                    t_p = _pw._time_kernel(pk, args_w, repeats)
                    point["pallas_hist_per_s"] = round(
                        CBB / max(t_p, 1e-9), 2)
                    point["pallas_speedup"] = round(t_x / t_p, 3)
                    if t_p < t_x:
                        point["winner"] = "pallas"
                except Exception as e:
                    # A broken Pallas lowering must be DISTINGUISHABLE
                    # from a legitimately-lost race — a null rate with
                    # no error field would read as "scan won" on the
                    # TPU box this table exists to measure.
                    point["pallas_error"] = repr(e)[:200]
            if _dc.dc_available():
                # The peel loop on the same (rows, events) shape at
                # this W: flat in W by construction, so its column is
                # the 2^W tail's counter-curve made measurable.
                try:
                    d_inv, d_cl, d_act = _dc.make_probe_plan(
                        rows=CBB, events=CBE, w=w)
                    _dc.dc_decide(d_inv, d_cl, d_act)   # compile
                    t_d = None
                    for _ in range(max(1, repeats)):
                        _t0 = time.perf_counter()
                        _dc.dc_decide(d_inv, d_cl, d_act)
                        _dt = time.perf_counter() - _t0
                        t_d = _dt if t_d is None else min(t_d, _dt)
                    best_dev = min(v for v in (
                        t_x, None if point["pallas_hist_per_s"] is None
                        else CBB / point["pallas_hist_per_s"])
                        if v is not None)
                    point["dc_hist_per_s"] = round(
                        CBB / max(t_d, 1e-9), 2)
                    point["dc_speedup"] = round(best_dev / t_d, 3)
                    if t_d < best_dev:
                        point["winner"] = "dc"
                except Exception as e:
                    point["dc_error"] = repr(e)[:200]
            points.append(point)
        wins = [p["W"] for p in points if p["winner"] == "pallas"]
        dc_wins = [p["W"] for p in points if p["winner"] == "dc"]
        backend_compare = {
            "mode": _pw.pallas_mode(),
            "backend_forced": bench_backend or "auto",
            "points": points,
            # Largest W at which the measured Pallas rate still beats
            # the scan (None = the scan won everywhere, e.g. every
            # interpret-mode host).
            "crossover_w": max(wins) if wins else None,
            # Smallest W at which the peel loop beats every frontier
            # backend — past it the 2^W curve never catches back up
            # (None = dc never won, e.g. disabled).
            "dc_crossover_w": min(dc_wins) if dc_wins else None,
            "probe": rate_probe,
            "headline_pallas_dispatches":
                sched_stats.get("pallas_dispatches", 0) or 0,
            "headline_dc_dispatches":
                sched_stats.get("dc_dispatches", 0) or 0,
        }

    # ---- Wire-ingest plane (ISSUE 18): stream a corpus through the
    # socket ingest server and report landed wire ops/s (absolute and
    # per core) plus the shed path exercised as graceful degradation —
    # a deliberately-held admission slot forces counted BUSY sheds,
    # then the shed client retries to a verdict-ready landed WAL.
    # JT_BENCH_INGEST=0 skips.
    ingest_section = None
    if os.environ.get("JT_BENCH_INGEST", "1") != "0":
        import tempfile as _tempfile

        from jepsen_tpu import ingest as _ingest
        from jepsen_tpu import telemetry as _tel
        from jepsen_tpu.history.ops import Op as _Op
        from jepsen_tpu.store import Store as _Store
        n_ing = int(os.environ.get("JT_BENCH_INGEST_OPS", "2000"))
        ing_ops = []
        for i in range(n_ing // 2):
            ing_ops.append(_Op(process=i % 4, type="invoke",
                               f="write", value=i))
            ing_ops.append(_Op(process=i % 4, type="ok",
                               f="write", value=i))
        _pre = (_tel.snapshot().get("counters") or {})
        _shed0 = _pre.get("ingest.shed", 0)
        _env_ra = os.environ.get("JT_INGEST_RETRY_AFTER_S")
        os.environ["JT_INGEST_RETRY_AFTER_S"] = "0.05"
        try:
            with _tempfile.TemporaryDirectory() as _td:
                _istore = _Store(Path(_td) / "store")
                _isrv = _ingest.IngestServer(
                    _istore, core=_ingest.IngestCore(
                        _istore, tenant_bound=1)).serve()
                t0 = time.perf_counter()
                _r = _ingest.stream_ops(
                    _isrv.host, _isrv.port, "bench-wire", "t0",
                    ing_ops, batch=512)
                t_wire = time.perf_counter() - t0
                # Shed path: hold THE admission slot open (end=False
                # keeps the tenant active past the bound), burst a
                # second tenant into the full plane — it sheds
                # (counted, Retry-After honored), retries, and still
                # lands once the hold releases: graceful degradation,
                # not failure.
                _ingest.stream_ops(_isrv.host, _isrv.port, "hold",
                                   "t0", ing_ops[:2], end=False)
                import threading as _threading
                _burst = {}

                def _burst_in():
                    _burst["r"] = _ingest.stream_ops(
                        _isrv.host, _isrv.port, "burst", "t0",
                        ing_ops[:4], attempts=100)

                _bt = _threading.Thread(target=_burst_in)
                _bt.start()
                time.sleep(0.15)          # let it shed at least once
                _ingest.stream_ops(_isrv.host, _isrv.port, "hold",
                                   "t0", ing_ops[:2])  # release slot
                _bt.join(timeout=30)
                _isrv.shutdown()
                _audit = _ingest.sequence_audit(
                    _istore.run_dir("bench-wire", "t0")
                    / "history.wal.jsonl")
                _now = (_tel.snapshot().get("counters") or {})
                _sheds = _now.get("ingest.shed", 0) - _shed0
                _admitted = 3     # bench-wire, hold, burst
                wire_rate = _r["acked"] / max(t_wire, 1e-9)
                ingest_section = {
                    "wire_ops": _r["acked"],
                    "wire_ops_per_s": round(wire_rate, 2),
                    "wire_ops_per_s_per_core": round(
                        wire_rate / max(os.cpu_count() or 1, 1), 2),
                    "wire_time_s": round(t_wire, 3),
                    "audit_ok": _audit["ok"],
                    "shed": _sheds,
                    "shed_fraction": round(
                        _sheds / max(_sheds + _admitted, 1), 4),
                    "burst_landed": bool(
                        _burst.get("r", {}).get("acked") == 4),
                    "burst_sheds": _burst.get("r", {}).get("sheds"),
                }
        finally:
            if _env_ra is None:
                os.environ.pop("JT_INGEST_RETRY_AFTER_S", None)
            else:
                os.environ["JT_INGEST_RETRY_AFTER_S"] = _env_ra

    # ---- Static verification plane (ISSUE 15): run the full lint —
    # device-plane jaxpr tracing over every registered kernel family
    # plus the host-plane ast passes — and report rules run, findings,
    # and lint wall-clock. A finding here on a clean tree is itself a
    # regression (tier-1 runs `jepsen-tpu lint --strict` too; the
    # bench section is the measured cost + the observability hook).
    # JT_BENCH_ANALYSIS=0 skips.
    analysis_section = None
    if os.environ.get("JT_BENCH_ANALYSIS", "1") != "0":
        from jepsen_tpu.analysis import run_lint
        _lint = run_lint(root=Path(__file__).resolve().parent)
        analysis_section = {
            "rules_run": _lint.rules_run,
            "families": _lint.families,
            "files_scanned": _lint.files_scanned,
            "findings": len(_lint.findings),
            "suppressed": _lint.suppressed
            if isinstance(_lint.suppressed, int)
            else len(_lint.suppressed),
            "by_rule": {},
            "wall_s": round(_lint.wall_s, 3),
        }
        for f in _lint.findings:
            analysis_section["by_rule"][f.rule] = \
                analysis_section["by_rule"].get(f.rule, 0) + 1

    out = {
        "metric": "linearizability_check_throughput_1kop_cas_e2e",
        "value": round(rate, 2),
        "unit": "histories/sec",
        "vs_baseline": round(rate / baseline_rate, 3),
        "histories": B,
        "ops_per_history": n_ops * 2,
        "invalid_found": n_invalid,
        "parity": {"full": bool(full_parity and check_batch_native),
                   "rows": S if full_parity else 24,
                   "valid": parity_valid,
                   "bad_index": parity_bad_index,
                   "configs": parity_configs,
                   "config_rows": n_config_rows},
        "parity_sample_ok": parity_valid,        # legacy field name
        "host_fallbacks": len(failures),
        "cpu_routed_rows": len(cpu_rows),
        # Routing-reason breakdown: oversize_w = wide (W > 16) buckets
        # too small to earn a device dispatch, overflow = rows past
        # even the wide encoder, quarantine = poison rows the
        # degradation ladder handed to the host oracle mid-run.
        "cpu_routed": {
            "oversize_w": len(cpu_oversize),
            "overflow": len(cpu_overflow),
            "quarantine": (sched_stats.get("quarantined_rows", 0) or 0),
        },
        "partition": {
            "n_keys": n_keys,
            "enabled": pb is not None,
            "sub_histories": S,
            "subs_per_history": round(S / B, 3),
            "partition_s": round(t_partition, 3),
            # Pending-window histograms {W: rows} before/after the
            # strain — the P-compositional W collapse, measured.
            "pre_w_hist": {str(k): v
                           for k, v in sorted(pre_w_hist.items())},
            "post_w_hist": {str(k): v
                            for k, v in sorted(post_w_hist.items())},
            # One run's dispatch economics: XLA calls issued vs chunks
            # retired (fused groups amortize the per-dispatch fixed
            # overhead the cost model now charges).
            "dispatches_per_run": sched_stats.get("dispatches"),
            "fused_groups": sched_stats.get("fused_groups"),
            "chunks": sched_stats.get("chunks"),
            "fuse_width": default_fuse_width(),
            "dispatch_overhead_us":
                sched_stats.get("dispatch_overhead_us"),
            # AOT-serialized kernel shipping ($JT_AOT_DIR): hits mean
            # this process deserialized final executables instead of
            # compiling (compile_time_s is then the WARM figure);
            # warm_deserialize_s re-measures that load cost directly.
            "aot": {**aot_run,
                    "dir": os.environ.get("JT_AOT_DIR"),
                    "compile_s": round(t_compile, 2),
                    "mode": "warm" if aot_run.get("hits") else "cold",
                    "warm_deserialize_s": aot_warm_probe()},
        },
        "buckets": [[b.V, b.W, b.batch] for b in buckets],
        "device": device_identity(),
        "native_cpu_rate": native_rate,
        "converted_e2e_rate": round(converted_rate, 2),
        "converted_histories": C,
        "converted_verdict_match": converted_match,
        "store_recheck_rate": store_rate,
        "store_recheck_runs": SB,
        "fold_total_queue_rate": round(fold_rate, 2),
        "fold_histories": FB,
        "fold_invalid": fold_invalid,
        "graph_checker": graph_section,
        "isolation": iso_section,
        "run_durability": durability_section,
        "fusion_ratio": fusion_ratio,
        "mean_live_slots": mean_live_slots,
        "fused_bad_refined": len(refined),
        "scheduler": {
            # Compile count for the standard mix: distinct kernel
            # shapes the headline run dispatched (acceptance: <= 5,
            # down from 13 exact-W jits in r05).
            "kernel_compiles": kernel_compiles,
            "w_classes": w_classes,
            # Streamed pipeline figures (columnar encode chunked and
            # overlapped with dispatch/decode end-to-end).
            "t_first_verdict_s": streamed_stats.get("t_first_verdict_s"),
            "streamed_e2e_rate": round(streamed_rate, 2),
            "streamed_e2e_time_s": round(t_streamed, 3),
            "streamed_histories": n_streamed,
            "chunks": streamed_stats.get("chunks"),
            "pad_rows": streamed_stats.get("pad_rows"),
            "input_buckets": streamed_stats.get("input_buckets"),
            # Resilience counters (ops.faults / doc/resilience.md):
            # all zero on a healthy run — future BENCH_*.json track
            # fallback/retry rates, so a regression that starts
            # leaning on the degradation ladder is visible even while
            # verdicts stay correct. Summed over the headline +
            # streamed runs.
            "resilience": {
                k: (sched_stats.get(k, 0) or 0)
                + (streamed_stats.get(k, 0) or 0)
                for k in ("retries", "bisections", "watchdog_fired",
                          "oom_events", "corrupt_chunks",
                          "quarantined_rows", "prewarm_wedged",
                          "abandoned_buckets", "faults_injected")},
        },
        "roofline": roofline,
        "long_history": long_stats,
        "xlong_history": xlong_stats,
        "device_rate": round(B * (n_checked / max(S, 1)) / t_dev, 2),
        "device_time_s": round(t_dev, 3),
        "partition_time_s": round(t_partition, 3),
        "encode_time_s": round(t_encode, 3),
        "e2e_time_s": round(t_e2e, 3),
        "compile_time_s": round(t_compile, 2),
        "synth_time_s": round(t_synth, 2),
        # Headline synth broken out: which generator produced the
        # batch, and what share of the whole loop (synth + partition +
        # encode + device) generation cost — the ~38%-to-<10% axis.
        "synth": {
            "mode": synth_mode,
            "share_of_e2e": round(t_synth / (t_synth + t_e2e), 4),
        },
        "synth_device": synth_section,
        "backend_compare": backend_compare,
        "telemetry": tel_section,
        "online": online_section,
        "fleet": fleet_section,
        "service": service_section,
        "ingest": ingest_section,
        "analysis": analysis_section,
    }
    rc = 0
    if compare is not None:
        out["regression"] = compare_bench(compare, out,
                                          tolerance=tolerance)
        if not out["regression"]["ok"]:
            rc = 3
    print(json.dumps(out))
    return rc


def _cli() -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="headline bench; --compare PREV.json adds the "
                    "regression sentinel section")
    ap.add_argument("--compare", default=None, metavar="PREV",
                    help="Previous BENCH json to machine-check this "
                         "round against (exit 3 on a rate regression "
                         "past --tolerance)")
    ap.add_argument("--current", default=None, metavar="CUR",
                    help="With --compare: skip running the bench and "
                         "compare CUR.json against PREV.json (the "
                         "fixture/self-compare mode; no jax needed)")
    ap.add_argument("--tolerance", type=float, default=0.20,
                    help="Relative rate-regression tolerance "
                         "(default 0.20)")
    args = ap.parse_args()
    if args.current and not args.compare:
        ap.error("--current needs --compare")
    prev = None
    if args.compare:
        with open(args.compare) as f:
            prev = json.load(f)
    if args.current:
        with open(args.current) as f:
            cur = json.load(f)
        reg = compare_bench(prev, cur, tolerance=args.tolerance)
        print(json.dumps({"regression": reg}))
        return 0 if reg["ok"] else 3
    return main(compare=prev, tolerance=args.tolerance)


if __name__ == "__main__":
    raise SystemExit(_cli())
