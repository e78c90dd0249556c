"""Central JT_* knob registry — the single source of truth.

Every environment knob the framework reads is declared here with its
default, type, and a one-line doc. The host-plane lint
(analysis.ast_lint, rule JTL-H-KNOB) walks the tree for ``JT_*``
string literals and flags any reference not declared here — so a
typo'd ``getenv`` is a finding, not a silently-ignored knob — and the
reverse direction (JTL-H-KNOB-STALE) flags declared knobs nothing
references, so the registry can't rot. ``doc/knobs.md`` is GENERATED
from this table (``generate_knobs_md``; tests pin the committed file
to the generator output) — never hand-edit it.

Types: ``int``/``float``/``str``/``path`` parse as named; ``flag`` is
the "0 disables" convention (any other value, including unset-with-
default-"1", enables); ``csv`` is a comma-separated list.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class Knob:
    name: str
    default: Optional[str]   # None = unset (feature off / probe wins)
    type: str                # int | float | flag | str | path | csv
    module: str              # declaring module (the primary read site)
    doc: str


KNOBS: Dict[str, Knob] = {}


def _k(name: str, default: Optional[str], type: str, module: str,
       doc: str) -> None:
    assert name not in KNOBS, f"duplicate knob {name}"
    KNOBS[name] = Knob(name, default, type, module, doc)


# --------------------------------------------------------- scheduler
_k("JT_SCHED_CHUNK_ROWS", "1024", "int", "ops/schedule.py",
   "Rows per dispatch chunk in the streaming bucket scheduler.")
_k("JT_SCHED_CLASSES", "5", "int", "ops/schedule.py",
   "Max consolidated W classes the DP may choose.")
_k("JT_SCHED_FUSE_WIDTH", "4", "int", "ops/schedule.py",
   "Chunks group-committed into one fused XLA call (1 = per-chunk "
   "dispatch; collapses to 1 under JT_COMPILE_CACHE=0).")
_k("JT_SCHED_MAX_QUEUE", "0", "int", "ops/schedule.py",
   "Bound on encoded-but-undispatched chunks at the encode->dispatch "
   "hand-off (0 = historical unbounded-behind-depth behavior).")
_k("JT_SCHED_ENCODE_ROWS", "4096", "int", "ops/schedule.py",
   "Rows per incremental encode slab in the graph scheduler.")
_k("JT_EVENT_ROUTE_EVENTS", "8192", "int", "ops/schedule.py",
   "Event-axis length past which a narrow history is cost-routed to "
   "the event-chunked resume kernel.")
_k("JT_EVENT_CHUNK", "2048", "int", "ops/schedule.py",
   "Events per dispatch on the event-chunked fallback path.")
_k("JT_RETRY_MAX", "3", "int", "ops/schedule.py",
   "Device-dispatch retries before the degradation ladder escalates.")
_k("JT_RETRY_BACKOFF_S", "0.25", "float", "ops/schedule.py",
   "Base backoff between dispatch retries (jittered exponential).")
_k("JT_BISECT_FLOOR_ROWS", "16", "int", "ops/schedule.py",
   "Smallest chunk the OOM row-bisection will split to.")
_k("JT_WATCHDOG_MIN_S", "120", "float", "ops/schedule.py",
   "Floor on the per-chunk decode watchdog deadline.")
_k("JT_WATCHDOG_LANE_OPS_PER_S", "1e8", "float", "ops/schedule.py",
   "VPU lane-op rate the watchdog prices chunk deadlines with.")
_k("JT_WATCHDOG_FACTOR", "32", "float", "ops/schedule.py",
   "Multiplier on the op-model estimate before a chunk is declared "
   "wedged.")
_k("JT_WATCHDOG_COMPILE_GRACE_S", "900", "float", "ops/schedule.py",
   "Extra watchdog grace for a chunk's first (compiling) dispatch.")
_k("JT_WATCHDOG_MXU_MACS_PER_S", "1e11", "float", "ops/schedule.py",
   "MXU MAC rate the graph scheduler's watchdog prices with.")
_k("JT_GRAPH_CHUNK_ROWS", "2048", "int", "ops/schedule.py",
   "Graphs per dispatch chunk in the graph scheduler.")
_k("JT_PREWARM_WAIT_S", "600", "float", "ops/schedule.py",
   "Bound on waiting for a pre-warm compile thread before dispatching "
   "cold.")
_k("JT_COMPILE_CACHE", "1", "flag", "ops/schedule.py",
   "Persistent XLA compile cache + AOT shipping (0 disables both — "
   "the hermetic-tests contract).")
_k("JT_AOT_DIR", None, "path", "ops/schedule.py",
   "AOT-serialized kernel directory; unset disables shipping.")
_k("JT_DISPATCH_OVERHEAD_US", None, "float", "ops/schedule.py",
   "Per-dispatch fixed overhead for the W-class DP (unset = startup "
   "probe; 0 = pre-r06 model).")
_k("JT_DISPATCH_COST_LANE_OPS_PER_S", "1e8", "float",
   "ops/schedule.py",
   "Lane-op rate the dispatch-cost model and router price WGL with.")
_k("JT_WGL_BACKEND", "auto", "str", "ops/schedule.py",
   "WGL backend force: auto | xla | pallas | dc.")
_k("JT_SHARD_MIN_ROWS", None, "int", "parallel/mesh.py",
   "Rows-per-device floor below which the dataN route falls back to "
   "the single-device kernel (default MIN_ROWS_PER_DEVICE).")

# ------------------------------------------------------------ pallas
_k("JT_PALLAS", "1", "flag", "ops/pallas_wgl.py",
   "Pallas WGL megakernel master switch (0 removes the backend).")
_k("JT_ROUTER_PALLAS", "1", "flag", "ops/pallas_wgl.py",
   "Cost-router Pallas backend restore switch (0 = route around it, "
   "bit-identically to pre-r12).")
_k("JT_PALLAS_MODE", None, "str", "ops/pallas_wgl.py",
   "Force compiled | interpret | off (default: compiled on TPU, "
   "interpret elsewhere).")
_k("JT_PALLAS_MAX_W", "10", "int", "ops/pallas_wgl.py",
   "Widest pending window the Pallas kernel accepts.")
_k("JT_PALLAS_EVENT_BLOCK", "256", "int", "ops/pallas_wgl.py",
   "Events per streamed HBM->VMEM block (the pipeline quantum).")
_k("JT_PALLAS_VMEM_BYTES", str(16 << 20), "int", "ops/pallas_wgl.py",
   "VMEM budget the static footprint model (vmem_plan) rejects "
   "against before launch (~16 MB/core on TPU).")
_k("JT_PALLAS_LANE_OPS_PER_S", "0.0", "float", "fleet.py",
   "Router rate override for the Pallas backend (0 = unpriced until "
   "probed).")

# ----------------------------------------- decrease-and-conquer (dc)
_k("JT_ROUTER_DC", "1", "flag", "ops/dc_monitor.py",
   "Decrease-and-conquer peel backend master switch (0 removes it "
   "from pricing, routing and forced dispatch — pre-r17 routing "
   "bit-for-bit).")
_k("JT_DC_MAX_ROUNDS", "0", "int", "ops/dc_monitor.py",
   "Peel-round cap per dispatch (0 = the sound structural bound, one "
   "round per value cluster; lower turns slow rows into scan "
   "residue).")
_k("JT_DC_RESIDUE_MAX_FRAC", "0.5", "float", "ops/dc_monitor.py",
   "Auto-routing gate: the peel pre-filter engages only when at most "
   "this fraction of a bucket's rows would fall through to the scan "
   "anyway.")
_k("JT_DC_EVENTS_PER_S", "0.0", "float", "fleet.py",
   "Router rate override for the peel backend (0 = unpriced until "
   "probed).")
_k("JT_ONLINE_DC", "0", "flag", "ops/dc_monitor.py",
   "Online daemon: serve interim ticks from the incremental peel "
   "carry before the resident frontier (1 enables; default off keeps "
   "the daemon bit-identical).")

# ----------------------------------------------------- store/runtime
_k("JT_WAL_FLUSH_MS", "50", "float", "history/wal.py",
   "Live-WAL group-commit window (0 = fsync per op).")
_k("JT_RUN_FAULT", None, "str", "ops/faults.py",
   "Run-level crash nemesis: op:K[@R] | phase:NAME[@R] | wedge:K[:S].")
_k("JT_FAULT_PLAN", None, "str", "ops/faults.py",
   "Checker-nemesis fault schedule (FaultPlan.parse syntax).")
_k("JT_WATCH_FAULT_PLAN", None, "str", "online.py",
   "Online-daemon fault schedule (DaemonFaultPlan syntax).")
_k("JT_BARRIER_TIMEOUT_S", "300", "float", "runtime.py",
   "DeadlineBarrier: wedged synchronize phase retires the barrier "
   "after this long.")
_k("JT_SNARF_TIMEOUT_S", "120", "float", "runtime.py",
   "Per-node deadline on teardown log collection.")
_k("JT_SALVAGE_MIN_AGE_S", "5", "float", "cli.py",
   "WAL quiescence window before a blind salvage sweep treats a run "
   "as dead.")
_k("JT_SSH_RETRIES", "3", "int", "control/core.py",
   "Control-plane transient retries for idempotent setup steps.")
_k("JT_SSH_BACKOFF_S", "0.5", "float", "control/core.py",
   "Base backoff between control-plane retries.")

# ------------------------------------------------------------ online
_k("JT_ONLINE_INCREMENTAL", "1", "flag", "online.py",
   "Resident-frontier incremental prefix checking (0 = full-prefix "
   "re-check per tick, the pre-r14 daemon bit-for-bit).")
_k("JT_DEFER_MAX_S", "300", "float", "online.py",
   "Hard re-admission deadline for a deferred tenant (starvation "
   "rescue).")
_k("JT_ONLINE_ISO", "1", "flag", "online.py",
   "Live isolation monitoring of transactional tenants (0 disables "
   "the per-tick IncrementalIsolation monitor; checks are unaffected).")
_k("JT_LIVE_STALE_S", "30", "float", "web.py",
   "WAL staleness past which a live run badges stalled vs crashed.")

# --------------------------------------------------------- isolation
_k("JT_TXN_DEVICE", "1", "flag", "isolation.py",
   "MXU isolation certification (0 = every transactional history "
   "certifies on the host DFS oracle — the restore switch).")

# ----------------------------------------------------- fleet/service
_k("JT_LEASE_TTL_S", "15", "float", "fleet.py",
   "Lease heartbeat staleness bound before takeover.")
_k("JT_LEASE_SKEW_S", "2", "float", "fleet.py",
   "Cross-host wall-clock skew allowance on lease expiry.")
_k("JT_FLEET_MAX_LOCAL_WORKERS", None, "int", "fleet.py",
   "Cap on local fleet worker processes (default: host cores).")
_k("JT_FLEET_WORKER_DEVICES", "1", "int", "fleet.py",
   "Virtual devices each spawned fleet worker provisions.")
_k("JT_FLEET_TEST_SLEEP_S", "0", "float", "fleet.py",
   "Test-only per-unit delay (exercises lease renewal under load).")
_k("JT_ROUTER_MAX_W", None, "int", "fleet.py",
   "Hard W capability cap for device backends in the cost router.")
_k("JT_ROUTER_PROBE", "0", "flag", "fleet.py",
   "1 = fleet workers probe-and-persist router rates at startup.")
_k("JT_HOST_S_PER_EVENT", "4e-4", "float", "fleet.py",
   "Router rate: host-oracle seconds per event (near-W-flat).")
_k("JT_GRAPH_MACS_PER_S", "1e12", "float", "fleet.py",
   "Router rate: MXU closure MACs per second.")
_k("JT_GRAPH_HOST_S_PER_EDGE", "2e-6", "float", "fleet.py",
   "Router rate: host DFS seconds per edge.")
_k("JT_SERVICE_CLAIM_BUDGET", "2", "int", "service.py",
   "Lease claims per worker per tick — the takeover-storm breaker.")
_k("JT_SERVICE_STAGGER_S", "0.5", "float", "service.py",
   "Deterministic per-(worker, tenant) takeover stagger window.")
_k("JT_SERVICE_PLACEMENT_PATIENCE_S", None, "float", "service.py",
   "How long placement defers a tenant toward a better-suited live "
   "peer (default 2x lease TTL).")

# --------------------------------------------------------- telemetry
_k("JT_TRACE", None, "str", "telemetry.py",
   "Span tracing: 0/unset off, 1 ring-buffer flight recorder, "
   "<path> JSONL sink.")
_k("JT_TRACE_RING", "65536", "int", "telemetry.py",
   "Flight-recorder ring capacity (records, newest-wins).")
_k("JT_TRACE_EXPORT", "trace.json", "path", "bench.py",
   "Chrome-trace export path for bench's traced pass.")
_k("JT_CORR", None, "str", "telemetry.py",
   "Process-default correlation id for cross-worker trace fusion.")
_k("JT_SERIES", "1", "flag", "series.py",
   "Durable per-worker metrics series recording (0 off).")
_k("JT_SERIES_INTERVAL_S", "5", "float", "series.py",
   "Seconds between series snapshot frames.")
_k("JT_SERIES_MAX_BYTES", str(4 << 20), "int", "series.py",
   "Series ring-file size bound before in-place compaction.")
_k("JT_SERIES_FSYNC_MS", "1000", "float", "series.py",
   "Series group-commit fsync window.")
_k("JT_ALERTS", "1", "flag", "alerts.py",
   "SLO burn-rate alert evaluation (0 off).")
_k("JT_ALERT_EVAL_S", "10", "float", "alerts.py",
   "Seconds between alert evaluations on the daemon tick.")
_k("JT_ALERT_BACKPRESSURE_RATE", "5.0", "float", "alerts.py",
   "Backpressure events/s threshold before the alert fires.")
_k("JT_ALERT_SHED_RATE", "1.0", "float", "alerts.py",
   "Shed-to-host checks/s threshold before the alert fires.")
_k("JT_ALERT_TAKEOVER_RATE", "0.5", "float", "alerts.py",
   "Service takeovers/s threshold before the alert fires.")

# ------------------------------------------------------------ encode
_k("JT_FUSE_KINDS", "24", "int", "ops/encode.py",
   "Synthetic-target-row budget for event fusion per history.")

# ------------------------------------------------------------- bench
_k("JT_BENCH_B", "10000", "int", "bench.py",
   "Headline batch size (histories).")
_k("JT_BENCH_OPS", "500", "int", "bench.py",
   "Ops per headline history.")
_k("JT_BENCH_REPEATS", "3", "int", "bench.py",
   "Timed repeats per measured section (best-of).")
_k("JT_BENCH_KEYS", "8", "int", "bench.py",
   "Independent keys per headline history (1 restores r05).")
_k("JT_BENCH_SYNTH", "host", "str", "bench.py",
   "Headline generator: host (historical stream) | device.")
_k("JT_BENCH_SYNTH_B", None, "int", "bench.py",
   "synth_device section batch size (default JT_BENCH_B).")
_k("JT_BENCH_FULL_PARITY", "1", "flag", "bench.py",
   "Full-corpus host parity sweep (0 = sampled).")
_k("JT_BENCH_PROBE", "1", "flag", "bench.py",
   "100x100k-op probe + backend rate probe (0 skips).")
_k("JT_BENCH_CONVERTED", None, "int", "bench.py",
   "Converted-history count for the storage replay section.")
_k("JT_BENCH_STORE_B", None, "int", "bench.py",
   "Stored runs for the store-recheck section (default JT_BENCH_B).")
_k("JT_BENCH_FOLD_B", "2000", "int", "bench.py",
   "Histories for the invariant-fold section.")
_k("JT_BENCH_GRAPH_B", "2000", "int", "bench.py",
   "Graphs for the graph-checker section.")
_k("JT_BENCH_ISO_B", "512", "int", "bench.py",
   "Transactional histories for the isolation-certifier section.")
_k("JT_BENCH_WAL_OPS", "20000", "int", "bench.py",
   "Ops for the run-durability WAL section.")
_k("JT_BENCH_LONG_B", "1000", "int", "bench.py",
   "Histories for the long-history section.")
_k("JT_BENCH_LONG_OPS", "5000", "int", "bench.py",
   "Ops per long history.")
_k("JT_BENCH_XLONG_B", "100", "int", "bench.py",
   "Histories for the event-chunked extra-long section.")
_k("JT_BENCH_XLONG_OPS", "50000", "int", "bench.py",
   "Ops per extra-long history.")
_k("JT_BENCH_EVENT_CHUNK", "8192", "int", "bench.py",
   "Events per chunk in the extra-long resume-kernel pass.")
_k("JT_BENCH_FUZZ", "1", "flag", "bench.py",
   "Fuzz-loop iteration rate subsection (0 skips).")
_k("JT_BENCH_TRACE", "1", "flag", "bench.py",
   "Telemetry overhead section (0 skips).")
_k("JT_BENCH_TRACE_B", "512", "int", "bench.py",
   "Histories for the traced-overhead passes.")
_k("JT_BENCH_ONLINE", "1", "flag", "bench.py",
   "Online daemon section (0 skips).")
_k("JT_BENCH_ONLINE_TENANTS", "3", "int", "bench.py",
   "Live writer tenants in the online section.")
_k("JT_BENCH_ONLINE_OPS", "60", "int", "bench.py",
   "Op pairs per online tenant.")
_k("JT_BENCH_ONLINE_INC_TENANTS", "3", "int", "bench.py",
   "Tenants for the incremental per-tick cost curve.")
_k("JT_BENCH_ONLINE_INC_STAGES", "10", "int", "bench.py",
   "Prefix-growth stages in the incremental curve.")
_k("JT_BENCH_ONLINE_INC_PAIRS", "8", "int", "bench.py",
   "Op pairs appended per incremental stage.")
_k("JT_BENCH_FLEET", "1", "flag", "bench.py",
   "Fleet scaling sweep (0 skips).")
_k("JT_BENCH_FLEET_WORKERS", "1,2,4,8", "csv", "bench.py",
   "Worker counts for the fleet sweep.")
_k("JT_BENCH_FLEET_SEEDS", "8", "int", "bench.py",
   "Seed units per fleet sweep point.")
_k("JT_BENCH_FLEET_B", None, "int", "bench.py",
   "Histories per fleet seed unit (default JT_BENCH_B).")
_k("JT_BENCH_FLEET_CURVE", None, "path", "bench.py",
   "Also write the fleet curve standalone here (MULTICHIP_r*).")
_k("JT_BENCH_SERVICE", "1", "flag", "bench.py",
   "Service tenants-per-SLO sweep (0 skips).")
_k("JT_BENCH_SERVICE_WORKERS", "1,2", "csv", "bench.py",
   "Worker counts for the service sweep.")
_k("JT_BENCH_SERVICE_TENANTS", "4", "int", "bench.py",
   "Live tenants per service sweep point.")
_k("JT_BENCH_SERVICE_OPS", "24", "int", "bench.py",
   "Op pairs per service tenant.")
_k("JT_BENCH_SERVICE_SLO_S", "30", "float", "bench.py",
   "ttfv SLO the service sweep measures against.")
_k("JT_BENCH_SERVICE_CURVE", None, "path", "bench.py",
   "Also write the service curve standalone here.")
_k("JT_BENCH_BACKEND", None, "str", "bench.py",
   "Force the headline WGL backend (auto | scan | pallas).")
_k("JT_BENCH_BACKEND_COMPARE", "1", "flag", "bench.py",
   "Pallas-vs-XLA per-W rate table (0 skips).")
_k("JT_BENCH_COMPARE_WS", "4,6,8,10", "csv", "bench.py",
   "W values for the backend-compare table.")
_k("JT_BENCH_COMPARE_B", "256", "int", "bench.py",
   "Rows per backend-compare point.")
_k("JT_BENCH_COMPARE_EVENTS", "256", "int", "bench.py",
   "Events per backend-compare row.")
_k("JT_BENCH_ANALYSIS", "1", "flag", "bench.py",
   "Static-verification lint section (0 skips).")
_k("JT_BENCH_INGEST", "1", "flag", "bench.py",
   "Wire-ingest section: stream a corpus through the socket plane "
   "and report landed ops/s, per-core rate, shed fraction (0 skips).")
_k("JT_BENCH_INGEST_OPS", "2000", "int", "bench.py",
   "Ops streamed per tenant in the bench ingest section.")

# ------------------------------------------------------ ingest plane
_k("JT_INGEST_FAULT_PLAN", None, "str", "ingest.py",
   "Wire nemesis schedule: stage:kind[:nth] comma-separated, stages "
   "accept/frame/land/ack, kinds disconnect/torn/dup/stall/kill, "
   "nth `*` = sticky (doc/ingest.md).")
_k("JT_INGEST_MAX_TENANTS", "64", "int", "ingest.py",
   "Active wire streams admitted before the plane sheds (counted "
   "BUSY/429 with Retry-After, never silent drop).")
_k("JT_INGEST_RETRY_AFTER_S", "1", "float", "ingest.py",
   "Retry-After a shed advertises when the router has no wire-ingest "
   "rate to price one with.")
_k("JT_INGEST_BATCH_OPS", "256", "int", "ingest.py",
   "Client ops per frame — the wire group-commit unit (one fsync and "
   "one ack per frame).")
_k("JT_INGEST_RETRIES", "5", "int", "ingest.py",
   "Reconnect attempts beyond the first in the client's "
   "resume-from-acked-offset loop (with_retry convention).")
_k("JT_INGEST_OPS_PER_S", None, "float", "fleet.py",
   "Assumed/measured wire-ingest landing rate; prices the ingest "
   "plane's Retry-After through router_rates (unset/0 = fall back "
   "to JT_INGEST_RETRY_AFTER_S).")


def knob_names() -> frozenset:
    return frozenset(KNOBS)


def generate_knobs_md() -> str:
    """Render doc/knobs.md from the registry — name, default, type,
    doc, declaring module — grouped by module. The committed file is
    pinned byte-for-byte to this output by tests/test_analysis.py."""
    by_mod: Dict[str, list] = {}
    for k in KNOBS.values():
        by_mod.setdefault(k.module, []).append(k)
    lines = [
        "# Environment knobs",
        "",
        "<!-- GENERATED from jepsen_tpu/analysis/knobs.py by",
        "     `jepsen-tpu lint --write-knobs-doc`. Do not hand-edit:",
        "     tests pin this file to the generator output. -->",
        "",
        "Every `JT_*` environment knob the framework reads, from the",
        "central registry (`jepsen_tpu/analysis/knobs.py`). The static",
        "lint (`jepsen-tpu lint`, doc/analysis.md) fails on any knob",
        "read in code but missing here, and on any entry here nothing",
        "reads — this table cannot drift from the tree.",
        "",
        "A `flag` knob follows the \"0 disables\" convention. A blank",
        "default means unset (feature off, or a measured probe wins).",
        "",
    ]
    for mod in sorted(by_mod):
        lines.append(f"## `{mod}`")
        lines.append("")
        lines.append("| knob | default | type | what it does |")
        lines.append("|---|---|---|---|")
        for k in sorted(by_mod[mod], key=lambda k: k.name):
            d = "" if k.default is None else f"`{k.default}`"
            lines.append(f"| `{k.name}` | {d} | {k.type} | {k.doc} |")
        lines.append("")
    return "\n".join(lines)
