"""bench.py contract smoke test.

The driver consumes bench.py's single JSON line blind; a regression
there loses the round's headline measurement. This runs the real
script at toy scale (quick parity mode) and pins the contract: one
JSON object on stdout with the metric/value/vs_baseline fields and
truthful parity flags.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_bench_emits_contract_json():
    env = dict(os.environ,
               JT_BENCH_B="200", JT_BENCH_OPS="100",
               JT_BENCH_REPEATS="1", JT_BENCH_FOLD_B="32",
               JT_BENCH_GRAPH_B="32", JT_BENCH_ISO_B="24",
               JT_BENCH_STORE_B="12", JT_BENCH_CONVERTED="120",
               JT_BENCH_FULL_PARITY="0", JT_BENCH_WAL_OPS="300",
               # Per-op commits: 400 toy ops can finish inside one
               # 50 ms window, which would leave zero time-triggered
               # group commits to measure.
               JT_WAL_FLUSH_MS="0",
               JT_BENCH_LONG_B="32", JT_BENCH_LONG_OPS="500",
               JT_BENCH_XLONG_B="6", JT_BENCH_XLONG_OPS="2000",
               JT_BENCH_SYNTH_B="64", JT_BENCH_TRACE_B="64",
               JT_BENCH_ONLINE_TENANTS="2", JT_BENCH_ONLINE_OPS="24",
               # Incremental subsection at toy scale: 2 tenants whose
               # prefixes grow 4x over 4 stages, both modes — the
               # tier-1 guard is the section's shape and the
               # cross-mode verdict parity, not the cost curve
               # (wall-clock flatness needs real scale).
               JT_BENCH_ONLINE_INC_TENANTS="2",
               JT_BENCH_ONLINE_INC_STAGES="4",
               JT_BENCH_ONLINE_INC_PAIRS="4",
               # Fleet sweep at toy scale: 1 vs 2 real worker
               # processes over 2 seed units (the tier-1 guard is the
               # section's shape + JT_BENCH_FLEET=0 skippability, not
               # the speedup — 2 toy units can't amortize worker
               # startup).
               JT_BENCH_FLEET_WORKERS="1,2", JT_BENCH_FLEET_SEEDS="2",
               JT_BENCH_FLEET_B="32",
               # Service probe at toy scale: one sweep point plus the
               # kill-takeover measurement (two real workers, short
               # lease TTL) — the tier-1 guard is the section's shape
               # and skippability, not the latency figure itself.
               JT_BENCH_SERVICE_WORKERS="1",
               JT_BENCH_SERVICE_TENANTS="2", JT_BENCH_SERVICE_OPS="6",
               JT_SERVICE_STAGGER_S="0", JT_LEASE_SKEW_S="0",
               # Backend sections at toy scale: the JT_BENCH_BACKEND
               # knob must be accepted, the startup probe must run,
               # and the Pallas-vs-XLA table must emit one honest
               # point (interpret mode on this CPU box — the guard is
               # the shape, not the crossover).
               JT_BENCH_BACKEND="auto",
               JT_BENCH_COMPARE_WS="4", JT_BENCH_COMPARE_B="8",
               JT_BENCH_COMPARE_EVENTS="64",
               # Wire-ingest section at toy scale (400 ops, 1 held
               # slot, 1 forced shed) — the guard is the section's
               # shape, audit, and counted-shed degradation.
               JT_BENCH_INGEST_OPS="400",
               # Tracing stays ambient-off: the section flips the
               # flight recorder on for its own traced passes only.
               JT_TRACE="0")
    r = subprocess.run([sys.executable, str(REPO / "bench.py")],
                       capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"exactly one JSON line expected: {lines}"
    d = json.loads(lines[0])
    assert d["metric"] == "linearizability_check_throughput_1kop_cas_e2e"
    assert d["unit"] == "histories/sec"
    assert d["value"] > 0 and d["vs_baseline"] > 0
    assert d["histories"] == 200
    assert d["ops_per_history"] == 200
    # Quick mode must not claim full parity.
    assert d["parity"]["full"] is False
    assert d["parity"]["valid"] is True          # sampled check ran
    assert d["converted_verdict_match"] is True
    assert d["store_recheck_runs"] == 12
    assert d["store_recheck_rate"] > 0
    assert d["fold_histories"] == 32
    # Fused/renumbered-scan instrumentation (ISSUE 2 acceptance).
    assert d["fusion_ratio"] >= 1.0
    assert d["mean_live_slots"] > 0
    # Off the chip every device figure names the CPU and every
    # utilization reads null: not measured.
    assert d["device"]["platform"] == "cpu" and d["device"]["count"] >= 1
    assert d["roofline"]["vpu_util"] is None
    assert d["roofline"]["hbm_util"] is None
    assert d["roofline"]["closure_iters_total"] > 0
    assert d["roofline"]["source_events_per_s"] > 0
    # Graph-checker section (ISSUE 4 acceptance): MXU op-model figures
    # next to the WGL VPU metrics.
    g = d["graph_checker"]
    assert g["graphs"] == 32 and g["graphs_per_s"] > 0
    assert g["closure_matmuls"] > 0 and g["mxu_util"] is None
    assert g["anomalies"] >= 1
    assert g["vertex_buckets"]
    assert g["resilience"]["quarantined_rows"] == 0
    # Isolation-certifier section (ISSUE 19 acceptance): ladder
    # throughput over a seeded anomaly mix, with the per-level
    # breakdown doubling as the injection-mix audit.
    iso = d["isolation"]
    assert iso["histories"] == 24 and iso["hist_per_s"] > 0
    assert iso["e2e_hist_per_s"] > 0 and iso["device_s"] > 0
    assert iso["closure_matmuls"] > 0
    assert sum(iso["levels"].values()) == 24
    assert set(iso["levels"]) <= {
        "none", "read-uncommitted", "read-committed",
        "repeatable-read", "snapshot-isolation", "serializability"}
    assert sum(iso["anomaly_mix"].values()) == 24
    assert "clean" in iso["anomaly_mix"]
    assert iso["resilience"]["quarantined_rows"] == 0
    # Run-durability section (ISSUE 5 acceptance): live-WAL worker-loop
    # overhead, group-commit flush percentiles, salvage throughput.
    rd = d["run_durability"]
    assert rd["wal_ops"] == 300
    assert rd["ops_per_s_wal_on"] > 0 and rd["ops_per_s_wal_off"] > 0
    assert rd["group_commits"] > 0 and rd["flush_p99_ms"] is not None
    assert rd["salvage_ops_per_s"] > 0
    x = d["xlong_history"]
    assert x["histories"] > 0 and x["events_per_s"] > 0
    assert x["encode_s"] >= 0 and x["device_s"] > 0   # the breakdown
    assert x["event_chunked"]["events_per_s"] > 0
    # Partition section (ISSUE 6 acceptance): P-compositional W
    # collapse + fused-dispatch economics + AOT shipping accounting.
    p = d["partition"]
    assert p["enabled"] is True and p["n_keys"] > 1
    assert p["sub_histories"] > d["histories"]
    assert p["subs_per_history"] > 1
    assert p["pre_w_hist"] and p["post_w_hist"]
    # The strain can only shrink pending windows.
    assert (max(int(w) for w in p["post_w_hist"])
            <= max(int(w) for w in p["pre_w_hist"]))
    # dispatches counts EVERY XLA call — chunked ships, fused groups,
    # and the wide/sharded routes (which bypass chunking entirely), so
    # it can legitimately exceed the chunk count at toy scale.
    assert p["dispatches_per_run"] >= 1
    assert p["dispatch_overhead_us"] is not None
    aot = p["aot"]
    assert aot["mode"] in ("cold", "warm")
    assert aot["compile_s"] >= 0
    for k in ("hits", "misses", "exported", "rejected"):
        assert aot[k] >= 0
    # Routing-reason breakdown sums to the legacy counter.
    cr = d["cpu_routed"]
    assert (cr["oversize_w"] + cr["overflow"]
            == d["cpu_routed_rows"])
    assert cr["quarantine"] == 0
    # On-device synthesis section (ISSUE 7 acceptance): host vs device
    # generator rates, streamed generate→check source, fuzz loop —
    # and the headline synth share broken out per section.
    assert d["synth"]["mode"] in ("host", "device")
    assert 0 <= d["synth"]["share_of_e2e"] <= 1
    sd = d["synth_device"]
    assert sd["histories"] == 64
    assert sd["host_hist_per_s"] > 0 and sd["device_hist_per_s"] > 0
    assert sd["host_ops_per_s"] > 0 and sd["device_ops_per_s"] > 0
    assert sd["device_vs_host_speedup"] > 0
    assert sd["t_first_dispatch_s"] is not None
    assert sd["streamed_gen_check_subs_per_s"] > 0
    assert sd["streamed_subs_checked"] > 0
    fz = sd["fuzz"]
    assert fz["iters_per_s"] > 0 and fz["neighborhoods"] >= 0
    # Per-section synth breakdown on the probes.
    assert d["long_history"]["long"]["synth_s"] >= 0
    # Long-history cost route (ISSUE 10 satellite): the event-chunked
    # kernel engaged as a ROUTE, its rate reported.
    lr = d["long_history"]["routed"]
    assert lr["threshold_default"] > 0
    assert lr["event_routed_rows"] > 0
    assert lr["event_routed_dispatches"] > 0
    assert lr["events_per_s"] > 0 and lr["rate"] > 0
    # Fleet section (ISSUE 10 acceptance): a MULTICHIP_r07-shape curve
    # — per-point e2e, speedup, parallel efficiency — over real worker
    # processes, plus the router cost table.
    fl = d["fleet"]
    assert fl["seeds"] == 2 and fl["histories"] == 32
    assert [p["workers"] for p in fl["points"]] == [1, 2]
    for p in fl["points"]:
        assert p["e2e_s"] > 0 and p["hist_per_s"] > 0
        assert p["speedup"] > 0 and p["parallel_efficiency"] > 0
        assert 1 <= p["spawned"] <= p["workers"]
        assert p["worker_platforms"] == ["cpu"]
    assert fl["points"][0]["speedup"] == 1.0
    assert fl["host_cores"] >= 1
    assert isinstance(fl["monotone"], bool)
    # Same spec + seeds at every point: identical verdicts.
    assert len({p["invalid"] for p in fl["points"]}) == 1
    tblw = {row["W"]: row["backend"] for row in fl["router_table"]}
    # Past max_device_w the 2^W frontier backends are ineligible: only
    # host-oracle and the r17 peel backend may appear, and a probed dc
    # rate routes W=20 to wgl-dc. At W=4 a device backend wins; which
    # one follows the measured rates of this host.
    assert tblw[4] in ("wgl-device", "wgl-dc", "wgl-pallas")
    assert tblw[20] in ("wgl-dc", "host-oracle")
    # Online checker-daemon section (ISSUE 9 acceptance): live-tailed
    # verdicts while the histories are still being written, plus the
    # forced overload burst degrading through the ladder without
    # dropping any tenant's eventual verdict.
    on = d["online"]
    assert on["tenants"] == 2 and on["ops_per_tenant"] == 96
    assert on["ttfv_p50_s"] is not None and on["ttfv_p99_s"] is not None
    assert on["verdicts_per_s_while_writing"] > 0
    assert on["finalized"] == 2 and on["valid_ok"] is True
    b = on["burst"]
    assert b["checks"] > 0 and b["valid_ok"] is True
    assert b["shed"] + b["deferred"] + b["widened"] > 0
    assert 0 <= b["shed_fraction"] <= 1
    # Incremental prefix checking (ISSUE 14 acceptance shape): both
    # modes ran, the delta path actually resumed a carried frontier,
    # the restore switch dispatched zero deltas, and interim + final
    # verdicts are field-for-field identical across the modes.
    inc = on["incremental"]
    assert inc["tenants"] == 2 and inc["prefix_growth"] == 4
    assert set(inc["modes"]) == {"incremental", "full"}
    mi = inc["modes"]["incremental"]
    assert mi["checks"] > 0 and mi["frontier_resumes"] > 0
    assert mi["delta_ops"] > 0 and mi["valid_ok"] is True
    assert mi["ttfv_p99_s"] is not None and mi["verdicts_per_s"] > 0
    assert len(mi["tick_cost_s"]) == 3
    assert mi["cost_ratio_last_vs_first"] > 0
    mf = inc["modes"]["full"]
    assert mf["delta_ops"] == 0 and mf["frontier_resumes"] == 0
    assert mf["valid_ok"] is True
    assert inc["verdicts_match"] is True
    assert d["xlong_history"]["synth_s"] >= 0
    # Service section (ISSUE 11 acceptance): tenants-per-SLO vs real
    # worker processes, plus the kill-a-worker takeover probe with
    # bounded latency recorded per orphaned tenant.
    sv = d["service"]
    assert sv["tenants"] == 2 and sv["ops_per_tenant"] == 24
    assert sv["host_cores"] >= 1
    assert [p["workers"] for p in sv["points"]] == [1]
    for p in sv["points"]:
        assert p["e2e_s"] > 0 and p["tenants_per_s"] > 0
        assert p["ttfv_p50_s"] is not None
        assert p["ttfv_p99_s"] is not None
        assert p["tenants_within_slo"] == 2
        assert p["valid_ok"] is True
        assert p["worker_platforms"] == ["cpu"]
    tk = sv["takeover"]
    assert tk["tenants"] == 2 and tk["killed_owned"] >= 1
    assert tk["measured"] == tk["killed_owned"]
    assert tk["gen_bumps"] >= 1
    assert tk["latency_p50_s"] is not None
    assert 0 < tk["latency_p99_s"] < 60   # bounded: TTL + claim, not ∞
    assert tk["valid_ok"] is True
    # Telemetry section (ISSUE 8 acceptance): the traced-overhead
    # measurement, span coverage of the checked path, and the
    # dispatch-gap (device-busy vs host-gap) breakdown.
    tl = d["telemetry"]
    assert tl["histories"] == 64
    assert tl["untraced_s"] > 0 and tl["traced_s"] > 0
    assert tl["overhead_pct"] is not None
    assert {"encode", "dispatch", "decode",
            "journal"} <= set(tl["span_kinds"])
    assert tl["spans"] > 0
    assert 0 <= tl["device_busy_frac"] <= 1
    assert 0 <= tl["host_gap_frac"] <= 1
    assert isinstance(tl["top_gap_causes"], list)
    # Per-backend-family device-busy breakdown (ISSUE 12 satellite):
    # the traced pass dispatched through the WGL family.
    assert isinstance(tl["device_busy_by_family"], dict)
    assert any(k.startswith("wgl") for k in tl["device_busy_by_family"])
    # JT_TRACE unset/0: no ambient trace, no trace.json emitted.
    assert tl["ambient_trace"] is False and tl["trace_json"] is None
    # Backend-compare section (ISSUE 12 acceptance): the measured
    # Pallas-vs-XLA rate per W class, the router's crossover, and the
    # startup probe cost — honest on a CPU box (interpret mode, scan
    # wins, crossover None is legal).
    bc = d["backend_compare"]
    assert bc["mode"] in ("compiled", "interpret", "off")
    assert bc["backend_forced"] == "auto"
    assert [p["W"] for p in bc["points"]] == [4]
    p0 = bc["points"][0]
    assert p0["rows"] == 8 and p0["xla_hist_per_s"] > 0
    assert p0["winner"] in ("xla", "pallas", "dc")
    if bc["mode"] != "off":
        assert p0["pallas_hist_per_s"] > 0
        assert p0["pallas_speedup"] > 0
        assert bc["probe"]["lane_ops_per_s"] > 0
        assert bc["probe"]["pallas_lane_ops_per_s"] > 0
        assert bc["probe"]["parity"] is True
    assert "crossover_w" in bc
    assert bc["headline_pallas_dispatches"] >= 0
    # Decrease-and-conquer column (ISSUE 17): the peel loop's W-flat
    # rate rides every point, plus its own crossover field — at W=4
    # the scan usually wins on this shape; the claim here is the
    # SHAPE, the W=11+ crossover is the slow-marked router test.
    assert "dc_hist_per_s" in p0 and "dc_speedup" in p0
    assert "dc_crossover_w" in bc
    assert bc["headline_dc_dispatches"] >= 0
    if "dc_error" not in p0:
        assert p0["dc_hist_per_s"] > 0 and p0["dc_speedup"] > 0
    assert "dc_events_per_s" in bc["probe"]
    # Static verification plane (ISSUE 15 acceptance shape): the full
    # lint ran inside bench — every rule, every registered kernel
    # family — found nothing on a clean tree, and reported its
    # wall-clock.
    an = d["analysis"]
    assert len(an["rules_run"]) == 13    # +JTL-H-SOCK (ISSUE 18)
    assert len(an["families"]) == 12     # +txn-closure (ISSUE 19)
    assert "wgl-scan" in an["families"] and \
        "pallas-wgl" in an["families"] and \
        "dc-peel" in an["families"] and \
        "txn-closure" in an["families"]
    assert an["files_scanned"] > 80
    assert an["findings"] == 0 and an["by_rule"] == {}
    assert an["suppressed"] == 0        # the committed baseline is empty
    assert an["wall_s"] > 0
    # Wire-ingest section (ISSUE 18 acceptance shape): a corpus
    # streamed through the real socket server at toy scale — landed
    # ops/s absolute and per core, a clean sequence audit, and the
    # forced burst shedding (counted) yet still landing.
    ing = d["ingest"]
    assert ing["wire_ops"] == 400
    assert ing["wire_ops_per_s"] > 0
    assert ing["wire_ops_per_s_per_core"] > 0
    assert ing["audit_ok"] is True
    assert ing["shed"] >= 1 and ing["burst_landed"] is True
    assert 0 < ing["shed_fraction"] < 1
