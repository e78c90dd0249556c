"""Streaming bucket scheduler: encode → dispatch → decode as a pipeline.

The exact-W bucket flow (ops.encode.bucket_encode → ops.linearize.
run_buckets_threaded) treats scheduling as an afterthought: every
distinct pending-window width compiles its own kernel (13 on the bench
mix), the host encodes the *entire* batch before the first device byte
moves, and verdicts only exist once the last bucket lands. Following
the P-compositionality line of work (arXiv:1504.00204, 2410.04581) the
win at this scale is in how the work is partitioned and scheduled
around the search, not in the search itself. This module owns that
layer:

  * **W-class consolidation** — exact windows fold into a small set of
    W *classes* chosen by a dynamic program over the measured cost
    basis ``rows x events x 2^W`` (choose_w_classes): the partition of
    the observed W range into <= max_classes contiguous groups that
    minimizes total padded frontier work. Checking a history under a
    wider class is semantics-preserving (ops.encode.widen_batch: the
    extra slots stay empty in every snapshot, contribute all-zero
    packed target rows, and can never acquire mask bits — the config
    set is bit-identical, embedded in a wider mask axis). Classes past
    DATA_MAX_SLOTS are priced as their route runs them
    (frontier_class_cost): per event step on a frontier mesh, where
    narrower rows may ride them.

  * **persistent compilation cache + pre-warm** — the scheduler wires
    jax's persistent compilation cache (enable_compilation_cache) so
    repeat runs and store rechecks deserialize instead of recompiling,
    and AOT-compiles the consolidated kernel set on background daemon
    threads (via the process-wide registry, ops.linearize.get_kernel)
    while the host is still encoding.

  * **chunked double-buffered pipeline** — each class bucket splits
    into row chunks; at most ``depth`` chunks are in flight, so the
    host encodes/pads chunk k+1 and decodes chunk k-1 while the device
    runs chunk k (jax dispatch is async; np.asarray is the block
    point). Chunk event buffers are donated (donate_argnums) — each is
    shipped exactly once, so XLA may recycle them as scan scratch.

Contract for callers (check_batch_tpu / check_columnar / Store.recheck
all stream through here):

  * ``run(source)`` yields ``(batch, out)`` pairs where ``batch`` is a
    *consolidated* EncodedBatch (NOT an element of the input list) and
    ``out`` follows run_encoded_batch's contract — (valid, bad,
    frontier), a WindowOverflow, or the DIVERTED sentinel for small
    wide buckets the caller asked to keep off-device. Callers MUST
    scatter through ``batch.indices`` / ``batch.ev_opidx``; positional
    zips against the input bucket list are meaningless after
    consolidation.
  * Results stream: buckets yield in dispatch order as their last
    chunk decodes, and ``on_chunk(batch, lo, hi, valid, bad, front)``
    fires per decoded chunk — callers that scatter per chunk see first
    verdicts after one encode group + one chunk, not after the full
    batch. No ordering is promised *between* rows of different
    classes; within one yielded bucket, rows are in ``batch.indices``
    order.
  * The source may be a Sequence[EncodedBatch] (one consolidation over
    the full W distribution) or an iterator of bucket *groups* (the
    streaming-encode path, e.g. iter_columnar_groups): classes freeze
    after the first group and later groups ride the same kernel set.

The scheduler also owns the pipeline's own fault model (ops.faults):
every chunk decodes under a watchdog deadline derived from the VPU op
model, classified runtime failures walk a degradation ladder — bounded
retry with exponential backoff, RESOURCE_EXHAUSTED bisection of the
dispatch row count (the learned safe chunk size sticks per W class,
then the event-chunked resume kernel), and a binary search that
quarantines poison rows to the caller's host engine — so a single bad
chunk degrades instead of aborting a multi-thousand-history check.
Quarantined rows surface in ``quarantined`` (callers MUST re-decide
them host-side; the in-band verdict is an inert placeholder) and every
off-happy-path row is tagged in ``row_provenance``; every row a
wide/frontier/sharded dispatch decided is tagged with its route
(linearize.dispatch_route) in ``row_route``.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from .encode import (EMPTY, EV_CLOSE, EV_OK, EncodedBatch,
                     merge_batches)
from .faults import (CorruptOutput, FaultInjector, WatchdogExpired,
                     classify_failure, corrupt_arrays, validate_decoded)
from .linearize import (DATA_MAX_SLOTS, DISPATCH_LOG, INT32_MAX,
                        KERNEL_SHAPE_LOG, MAX_FRONTIER_ELEMENTS,
                        WindowOverflow, dispatch_route,
                        get_fused_kernel, get_kernel, log_kernel_shapes,
                        n_state_words, production_mesh, run_encoded_batch,
                        run_event_chunked, vpu_op_model)

log = logging.getLogger("jepsen.schedule")

# Small wide buckets the caller asked to divert (min_device_rows) are
# yielded with this sentinel instead of a device result.
DIVERTED = object()

# Rows per device dispatch (before the per-class memory cap shrinks it).
DEFAULT_CHUNK_ROWS = int(os.environ.get("JT_SCHED_CHUNK_ROWS", "1024"))

# Consolidation budget for the W <= DATA_MAX_SLOTS side.
DEFAULT_MAX_CLASSES = int(os.environ.get("JT_SCHED_CLASSES", "5"))

# Fused-dispatch group width: up to this many class chunks ride ONE
# XLA call (a tuple-input megakernel, linearize.get_fused_kernel), so
# the bucket histogram's long cheap head stops paying one dispatch
# each. 1 = the per-chunk dispatch flow (the pre-fusion behavior; the
# fault-ordinal tests pin it).
DEFAULT_FUSE_WIDTH = 4


def default_fuse_width() -> int:
    """The fuse width a BucketScheduler uses when the caller passes
    none. A fused megakernel is a compile-time investment — each group
    composition is a fresh XLA program roughly ``width`` bodies big —
    that only pays off when compiles amortize: across processes via
    the persistent cache + AOT shipping, or within one long streaming
    run. With the compile cache OFF (JT_COMPILE_CACHE=0, the hermetic
    tests contract) every short-lived process would pay full megakernel
    compiles for one-shot dispatch groups, so the default collapses to
    1 (the per-chunk flow). $JT_SCHED_FUSE_WIDTH and the explicit
    ``fuse_width=`` argument override unconditionally — how the
    dispatch-budget guard engages fusion under a disabled cache."""
    env = os.environ.get("JT_SCHED_FUSE_WIDTH")
    if env is not None:
        try:
            return max(1, int(env))
        except ValueError:
            log.warning("ignoring malformed JT_SCHED_FUSE_WIDTH=%r "
                        "(want an integer >= 1)", env)
    if os.environ.get("JT_COMPILE_CACHE") == "0":
        return 1
    return DEFAULT_FUSE_WIDTH


def sched_max_queue() -> int:
    """$JT_SCHED_MAX_QUEUE: bound on encoded-but-undispatched chunks
    buffered at the encode→dispatch hand-off. 0 (the default) keeps
    the historical behavior (the fuse buffer fills to fuse_width, then
    the pipeline's depth bound applies); a positive bound makes a
    stalled device WEDGE the pipeline behind a counted
    ``backpressure_events`` stat — bounded host memory with a visible
    signal — instead of letting a pathological fuse/depth configuration
    grow the hand-off without limit."""
    env = os.environ.get("JT_SCHED_MAX_QUEUE")
    if env is None:
        return 0
    try:
        return max(0, int(env))
    except ValueError:
        log.warning("ignoring malformed JT_SCHED_MAX_QUEUE=%r "
                    "(want an integer >= 0)", env)
        return 0


def event_route_min_events() -> int:
    """$JT_EVENT_ROUTE_EVENTS: event-axis length at which a narrow
    bucket routes through the event-chunked resume kernel BY COST
    instead of reaching it only as the post-OOM-bisection fallback.
    The crossover is measured, not derived: the r05 10k-op probe
    (651/s monolithic) showed the one-shot scan's per-event cost
    climbing with history length — a 100k-step scan is one giant XLA
    program whose compile and working set grow with N, while carried
    ``EVENT_CHUNK``-step dispatches keep one small compiled shape and
    double-buffer uploads under the scan for free. Default 8192
    (~4 event chunks — below that the extra per-chunk dispatch
    overhead outweighs the win); 0 disables the route."""
    env = os.environ.get("JT_EVENT_ROUTE_EVENTS")
    if env is None:
        return 8192
    try:
        return max(0, int(env))
    except ValueError:
        log.warning("ignoring malformed JT_EVENT_ROUTE_EVENTS=%r "
                    "(want an integer >= 0)", env)
        return 8192


# In-flight chunk budget: 2 = classic double buffering (host pads k+1,
# device runs k, host decodes k-1).
PIPELINE_DEPTH = 2

# Shape quanta: event axes round up to EVENT_QUANTUM and sub-chunk row
# counts to the power-of-two ladder (>= ROW_QUANTUM), so one class
# dispatches one or two static shapes per process — and the SAME shapes
# across processes, which is what makes the persistent compilation
# cache hit on reruns and rechecks.
EVENT_QUANTUM = 64
ROW_QUANTUM = 64

# ---- degradation-ladder knobs (ops.faults documents the fault model)

# Retries per failing dispatch beyond the first attempt.
RETRY_MAX = int(os.environ.get("JT_RETRY_MAX", "3"))

# Exponential backoff base between retries (doubles per attempt).
RETRY_BACKOFF_S = float(os.environ.get("JT_RETRY_BACKOFF_S", "0.25"))

# Watchdog floor: no chunk deadline below this, however small the
# chunk — transient host stalls must not masquerade as wedges.
WATCHDOG_MIN_S = float(os.environ.get("JT_WATCHDOG_MIN_S", "120"))

# Assumed worst-case sustained VPU throughput (lane-ops/s) for the
# deadline estimate; deliberately pessimistic — the watchdog exists to
# catch wedges, not to police slow chunks.
WATCHDOG_LANE_OPS_PER_S = float(
    os.environ.get("JT_WATCHDOG_LANE_OPS_PER_S", "1e8"))

# Safety multiplier over the op-model estimate.
WATCHDOG_FACTOR = float(os.environ.get("JT_WATCHDOG_FACTOR", "32"))

# Extra allowance the FIRST wait on a kernel shape gets: a cold
# dispatch may be paying an XLA compile, not running.
WATCHDOG_COMPILE_GRACE_S = float(
    os.environ.get("JT_WATCHDOG_COMPILE_GRACE_S", "900"))

# OOM bisection floor: below this many rows per dispatch, stop halving
# and switch to the event-chunked resume kernel (run_event_chunked).
BISECT_FLOOR_ROWS = int(os.environ.get("JT_BISECT_FLOOR_ROWS", "16"))

# Event-axis chunk for the post-floor fallback dispatch.
EVENT_CHUNK = int(os.environ.get("JT_EVENT_CHUNK", "2048"))

# Pre-warm wait bound (see _resolve): far past any legitimate compile.
PREWARM_WAIT_S = float(os.environ.get("JT_PREWARM_WAIT_S", "600"))


class ChunkAbandoned(WindowOverflow):
    """A bucket the ladder could not decide on device (wide-route
    persistent failure): subclassing WindowOverflow reuses the callers'
    existing route-to-host-engine handling."""


class _ChunkFailed(Exception):
    """Internal: a dispatch range exhausted its retry budget."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pow2_ceil(x: int) -> int:
    return 1 << max(x - 1, 1).bit_length()


# ------------------------------------------------ persistent compile cache

_CACHE_WIRED = False
_CACHE_LOCK = threading.Lock()


#: Where the compile cache lives when $JAX_COMPILATION_CACHE_DIR is
#: unset: a fixed path inside the checkout (gitignored). The path is
#: part of what makes a cache entry findable again, so it never moves.
DEFAULT_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_root() -> str:
    """The one compile-cache root: $JAX_COMPILATION_CACHE_DIR when set
    (jax reads it itself), else DEFAULT_CACHE_ROOT. The AOT shipping
    dir callers opt into lives under it (``<root>/aot``)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_ROOT


def enable_compilation_cache() -> Optional[str]:
    """Wire jax's persistent compilation cache (idempotent).

    Repeat bench runs and store rechecks then deserialize their kernels
    instead of recompiling — near-zero compile on the second process.
    The directory is cache_root(); set JT_COMPILE_CACHE=0 to disable.
    Returns the effective dir or None."""
    global _CACHE_WIRED
    if os.environ.get("JT_COMPILE_CACHE") == "0":
        return None
    with _CACHE_LOCK:
        import jax
        path = jax.config.jax_compilation_cache_dir or cache_root()
        if _CACHE_WIRED:
            return path
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # Cache every kernel, however small/fast to compile: the
        # checker's kernels are many and individually cheap — the
        # 13-kernel bench mix is exactly the long tail the default
        # thresholds would skip.
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        _CACHE_WIRED = True
        return path


# ------------------------------------------------------ W-class cost model

# Assumed sustained lane-op rate that converts the measured dispatch
# overhead (wall microseconds) into the DP's cost-base units
# (base x 2^W ~ lane-ops): overhead_units = overhead_s x rate. The
# same pessimism class as WATCHDOG_LANE_OPS_PER_S — only the RATIO of
# overhead to work matters to the partition choice.
DISPATCH_COST_LANE_OPS_PER_S = float(
    os.environ.get("JT_DISPATCH_COST_LANE_OPS_PER_S", "1e8"))

_DISPATCH_OVERHEAD_US: Optional[float] = None
_OVERHEAD_LOCK = threading.Lock()


def measure_dispatch_overhead_us(samples: int = 12) -> float:
    """The fixed cost of one device dispatch, in wall microseconds —
    a tiny jitted round trip timed after warmup, median over
    ``samples``. Calibrated once per process (the first BucketScheduler
    pays ~a millisecond); $JT_DISPATCH_OVERHEAD_US overrides the
    measurement entirely — how tests pin the DP and how deployments
    with known launch latency skip the probe. 0 disables the term
    (the pre-r06 cost model)."""
    global _DISPATCH_OVERHEAD_US
    env = os.environ.get("JT_DISPATCH_OVERHEAD_US")
    if env is not None:
        try:
            return max(0.0, float(env))
        except ValueError:
            # The env's contract is "override entirely": a typo must
            # not silently re-enable the machine-dependent probe the
            # caller meant to pin away. 0 = the term off (pre-r06).
            log.warning("ignoring malformed JT_DISPATCH_OVERHEAD_US=%r "
                        "(want a number of microseconds); dispatch "
                        "overhead term disabled", env)
            return 0.0
    with _OVERHEAD_LOCK:
        if _DISPATCH_OVERHEAD_US is not None:
            return _DISPATCH_OVERHEAD_US
        try:
            import jax
            import jax.numpy as jnp
            f = jax.jit(lambda x: x + 1)
            x = jnp.zeros(8, jnp.int32)
            f(x).block_until_ready()        # compile outside the clock
            ts = []
            for _ in range(samples):
                t0 = time.perf_counter()
                f(x).block_until_ready()
                ts.append(time.perf_counter() - t0)
            _DISPATCH_OVERHEAD_US = sorted(ts)[len(ts) // 2] * 1e6
        except Exception:
            _DISPATCH_OVERHEAD_US = 0.0
        return _DISPATCH_OVERHEAD_US


def dispatch_overhead_units() -> float:
    """The per-dispatch fixed-overhead term in cost-base units — what
    choose_w_classes charges each group beyond its frontier work."""
    return (measure_dispatch_overhead_us() * 1e-6
            * DISPATCH_COST_LANE_OPS_PER_S)


def frontier_class_cost(events: int, rows: int, W: int, devices: int,
                        boundary: int = DATA_MAX_SLOTS) -> float:
    """Cost-base units of ``rows`` rows of ``events`` event steps (the
    merged bucket's padded event axis) checked at a class ``W`` past
    ``boundary`` on ``devices`` devices, as the routes run it
    (linearize.dispatch_route). With the 2^(W - boundary) devices the
    frontier axis needs, every event step scans 2^boundary masks per
    device for the rows of its data shard, and a step over fewer than
    ROW_QUANTUM rows costs what a quantum's does: its latency, the
    hypercube exchanges and the convergence psums set it, not its rows.
    So the step is priced per device, as a narrow class's is on the one
    device it leaves the others idle beside. Without those devices the
    class runs on one device (data1wide), priced rows x events x 2^W as
    the narrow classes are."""
    D = 1 << (W - boundary)
    if D > devices:
        return float(rows * events) * float(1 << W)
    per_shard = max(ROW_QUANTUM, -(-rows // (devices // D)))
    return float(events * per_shard) * float(1 << boundary)


def choose_w_classes(stats: Dict[Tuple[int, int], float], *,
                     max_classes: int = DEFAULT_MAX_CLASSES,
                     boundary: int = DATA_MAX_SLOTS,
                     overhead: Optional[float] = None,
                     rows: Optional[Dict[Tuple[int, int], int]] = None,
                     devices: int = 1
                     ) -> Dict[Tuple[int, int], int]:
    """Pick the W classes: {(V, exact_W): class_W}.

    ``stats`` maps (V, exact_W) -> cost base (rows x events; anything
    proportional works, but past the boundary ``stats / rows`` is read
    as the bucket's event steps). Per V, the exact windows partition
    into at most ``max_classes`` contiguous groups, each checked at its
    widest member; the dynamic program minimizes the sum of the groups'
    costs plus a per-group dispatch tax. A group checked at or below
    ``boundary`` costs base_group x 2^class_W, its total padded
    frontier work. A group checked past the boundary costs what
    frontier_class_cost prices for its rows on ``devices`` devices
    (``rows``: the row count of each (V, exact_W); without it, the
    narrow formula), so on a frontier mesh narrower rows ride a wide
    class (ops.encode.widen_batch is exact) where that costs less than
    a dispatch of their own. A V with no window past the boundary gets
    the same classes whatever ``rows`` and ``devices`` say.

    ``overhead`` is the measured fixed cost of one dispatch in
    cost-base units (default dispatch_overhead_units(), i.e. the
    startup-calibrated $JT_DISPATCH_OVERHEAD_US probe): without it the
    DP undercounts many small classes — a class whose total frontier
    work is below the launch overhead is pure loss, and the plateau's
    long cheap bucket head was exactly that shape.
    """
    if overhead is None:
        overhead = dispatch_overhead_units()
    overhead = max(0.0, float(overhead))
    out: Dict[Tuple[int, int], int] = {}
    by_v: Dict[int, List[int]] = {}
    for (v, w) in stats:
        by_v.setdefault(v, []).append(w)
    for v, ws in by_v.items():
        ws = sorted(set(ws))
        if len(ws) <= max_classes and not overhead and ws[-1] <= boundary:
            out.update({(v, w): w for w in ws})
            continue
        base = [float(stats[(v, w)]) for w in ws]
        nrow = [int(rows[(v, w)]) for w in ws] if rows is not None else None
        pre = [0.0]
        for b in base:
            pre.append(pre[-1] + b)

        def cost(i, j):        # group ws[i..j] checked at ws[j]
            if ws[j] <= boundary or nrow is None:
                return (pre[j + 1] - pre[i]) * float(1 << ws[j]) + overhead
            events = max(base[k] / nrow[k] for k in range(i, j + 1))
            return frontier_class_cost(events, sum(nrow[i:j + 1]), ws[j],
                                       devices, boundary) + overhead

        n = len(ws)
        INF = float("inf")
        # dp[c][j] = min cost covering ws[:j] with exactly c groups
        dp = [[INF] * (n + 1) for _ in range(max_classes + 1)]
        cut = [[0] * (n + 1) for _ in range(max_classes + 1)]
        dp[0][0] = 0.0
        for c in range(1, max_classes + 1):
            for j in range(1, n + 1):
                for i in range(c - 1, j):
                    d = dp[c - 1][i] + cost(i, j - 1)
                    if d < dp[c][j]:
                        dp[c][j] = d
                        cut[c][j] = i
        c = min(range(1, max_classes + 1), key=lambda c: dp[c][n])
        j = n
        while c > 0:
            i = cut[c][j]
            cls = ws[j - 1]
            for k in range(i, j):
                out[(v, ws[k])] = cls
            j, c = i, c - 1
    return out


# ------------------------------------------------------------ AOT pre-warm

_AOT: Dict[Tuple, object] = {}
_AOT_INFLIGHT: Dict[Tuple, threading.Event] = {}
_AOT_LOCK = threading.Lock()

# AOT-serialized kernel shipping: executables exported to / imported
# from $JT_AOT_DIR keyed by _aot_key, so a fresh process on the same
# runtime deserializes instead of recompiling — the cold-compile cut
# beyond the persistent StableHLO cache (this ships the FINAL
# executable, skipping trace+lower+compile entirely). Disabled when
# unset or when JT_COMPILE_CACHE=0 (the hermetic-tests contract).
AOT_STATS = {"hits": 0, "misses": 0, "exported": 0, "rejected": 0,
             "unsupported": 0}
_AOT_MISSING: set = set()      # keys probed on disk and absent


def _aot_bump(key: str) -> None:
    """One AOT-shipping stat event: the legacy module dict (bench/test
    surface) plus the unified registry (results.json telemetry)."""
    AOT_STATS[key] += 1
    telemetry.REGISTRY.counter(f"aot.{key}").inc()


def aot_dir() -> Optional[str]:
    if os.environ.get("JT_COMPILE_CACHE") == "0":
        return None
    d = os.environ.get("JT_AOT_DIR")
    return d or None


def _aot_env_tag() -> str:
    """The runtime fingerprint an executable is only valid under."""
    import jax
    try:
        dev = jax.devices()[0]
        dev_kind = f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
    except Exception:
        dev_kind = "none"
    return f"jax-{jax.__version__}|{dev_kind}"


def _aot_path(key: Tuple) -> Optional[str]:
    d = aot_dir()
    if d is None:
        return None
    import hashlib
    h = hashlib.sha256(f"{_aot_env_tag()}|{key!r}".encode()).hexdigest()
    return os.path.join(d, f"{h[:24]}.aot")


def _aot_read(path: str):
    """Pure read half of shipping: deserialize one .aot file, or None
    on tag mismatch/corruption. No stats, no memo — safe to call from
    measurement probes while prewarm threads run."""
    import pickle

    from jax.experimental import serialize_executable as se
    with open(path, "rb") as f:
        tag, payload, in_tree, out_tree = pickle.load(f)
    if tag != _aot_env_tag():
        return None
    import jax
    # Every shipped kernel is a single-device jit: load it onto the
    # default device only (unpinned, jax loads onto every device).
    return se.deserialize_and_load(payload, in_tree, out_tree,
                                   execution_devices=jax.devices()[:1])


def _aot_load(key: Tuple):
    """Deserialize a shipped executable for ``key``, or None. Any
    mismatch/corruption just counts as a miss — shipping is an
    accelerator, never a failure mode."""
    path = _aot_path(key)
    if path is None or key in _AOT_MISSING:
        return None
    try:
        if not os.path.exists(path):
            _AOT_MISSING.add(key)
            _aot_bump("misses")
            return None
        compiled = _aot_read(path)
        if compiled is None:
            _aot_bump("rejected")
            return None
        _aot_bump("hits")
        return compiled
    except Exception:
        _aot_bump("rejected")
        return None


def _aot_store(key: Tuple, compiled) -> None:
    """Serialize one executable into the shipping dir (best-effort,
    atomic rename so a killed process never leaves a torn file). The
    dir is created owner-only and files land 0600: shipped payloads
    deserialize through pickle, so the shipping dir is a TRUSTED path
    — same trust domain as the persistent compile cache, never a
    world-writable drop box."""
    path = _aot_path(key)
    if path is None:
        return
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
    except Exception:
        # Not every executable serializes — Pallas custom-call
        # lowerings are the known case. Count it (aot.unsupported) and
        # fall through to the persistent compile cache / parked
        # in-memory executable instead of erroring the pre-warm
        # thread: shipping is an accelerator, never a failure mode.
        _aot_bump("unsupported")
        return
    try:
        import pickle
        os.makedirs(os.path.dirname(path), mode=0o700, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "wb") as f:
            pickle.dump((_aot_env_tag(), payload, in_tree, out_tree), f)
        os.replace(tmp, path)
        _AOT_MISSING.discard(key)
        _aot_bump("exported")
    except Exception:
        pass


def aot_warm_probe() -> Optional[float]:
    """Measured warm-start cost: re-deserialize every executable this
    process parked in the shipping dir and return the wall seconds —
    what a FRESH process pays instead of trace+lower+compile (the
    bench's cold-vs-warm compile figure). None when shipping is
    disabled or nothing was exported. Reads through _aot_read, which
    touches neither AOT_STATS nor the missing-key memo (the probe is
    measurement, not traffic — and prewarm threads may still be
    exporting while it runs)."""
    with _AOT_LOCK:
        keys = list(_AOT.keys())
    if not keys or aot_dir() is None:
        return None
    n = 0
    t0 = time.perf_counter()
    for k in keys:
        try:
            path = _aot_path(k)
            if path and os.path.exists(path) \
                    and _aot_read(path) is not None:
                n += 1
        except Exception:
            pass
    dt = time.perf_counter() - t0
    return round(dt, 3) if n else None


def _aot_key(V, W, w_live, shared, donate, Bp, Np, slot_dtype, K1):
    return (V, W, w_live, shared, donate, Bp, Np,
            np.dtype(slot_dtype).str, K1)


def _spec_key(spec: Tuple) -> Tuple:
    """Registry key for a pre-warm spec — a plain kernel-shape tuple,
    ("fused", (member specs...)) for a dispatch-group megakernel, or
    ("pallas",) + shape tuple for the Pallas WGL kernel."""
    if spec and spec[0] == "fused":
        return ("fused",) + tuple(_aot_key(*m) for m in spec[1])
    if spec and spec[0] == "pallas":
        return ("pallas",) + _aot_key(*spec[1:])
    return _aot_key(*spec)


def _member_shapes(spec: Tuple):
    import jax
    (V, W, w_live, shared, donate, Bp, Np, slot_dtype, K1) = spec
    ev = jax.ShapeDtypeStruct((Bp, Np), np.int8)
    slots = jax.ShapeDtypeStruct((Bp, Np, W), np.dtype(slot_dtype))
    tgt = jax.ShapeDtypeStruct((K1, V) if shared else (Bp, K1, V),
                               np.int32)
    return [ev, ev, slots, tgt]


def _compile_spec(spec: Tuple) -> None:
    """AOT-lower + compile one kernel shape (or fused group shape) and
    park the executable for dispatch to pick up — preferring a
    deserialized shipped executable (_aot_load) over a fresh compile,
    and exporting fresh compiles back to the shipping dir. Runs on a
    daemon thread; any failure just leaves dispatch on the plain jit
    path."""
    key = _spec_key(spec)
    try:
        compiled = _aot_load(key)
        if compiled is None:
            if spec[0] == "fused":
                members = spec[1]
                kern = get_fused_kernel(
                    tuple(m[:4] for m in members),
                    donate=bool(members[0][4]))
                shapes = [s for m in members for s in _member_shapes(m)]
            elif spec[0] == "pallas":
                from .pallas_wgl import get_pallas_kernel
                (V, W, w_live, shared, _donate, *_rest) = spec[1:]
                kern = get_pallas_kernel(V, W, shared_target=shared,
                                         w_live=w_live)
                shapes = _member_shapes(spec[1:])
            else:
                (V, W, w_live, shared, donate, *_rest) = spec
                kern = get_kernel(V, W, shared_target=shared,
                                  donate=donate, w_live=w_live)
                shapes = _member_shapes(spec)
            compiled = kern.lower(*shapes).compile()
            _aot_store(key, compiled)
    except Exception:
        compiled = None
    with _AOT_LOCK:
        if compiled is not None:
            _AOT[key] = compiled
        ev = _AOT_INFLIGHT.pop(key, None)
    if ev is not None:
        ev.set()


def prewarm_kernels(specs: Iterable[Tuple]) -> List[threading.Thread]:
    """Compile kernel shapes on background daemon threads (one each).
    ``specs``: (V, W, w_live, shared, donate, Bp, Np, slot_dtype, K1)
    per kernel — what BucketScheduler derives from the consolidated
    class set — or ("fused", (member specs...)) for a dispatch-group
    megakernel shape. Dispatch coordinates through _AOT_INFLIGHT: a
    chunk that reaches the device first WAITS for the in-flight
    compile instead of racing a duplicate jit compile of the same
    shape (``.lower().compile()`` does not populate the jit function's
    own cache, so the race would compile everything twice)."""
    threads = []
    for spec in specs:
        key = _spec_key(spec)
        with _AOT_LOCK:
            if key in _AOT or key in _AOT_INFLIGHT:
                continue
            _AOT_INFLIGHT[key] = threading.Event()
        name = ("jepsen-prewarm-fused" if spec[0] == "fused"
                else f"jepsen-prewarm-pallas-W{spec[2]}"
                if spec[0] == "pallas"
                else f"jepsen-prewarm-W{spec[1]}")
        t = threading.Thread(target=_compile_spec, args=(tuple(spec),),
                             name=name, daemon=True)
        try:
            t.start()
        except Exception:
            # Thread exhaustion must not leak the in-flight event —
            # a leaked unset event would make every dispatch of this
            # shape sit out the full wait timeout.
            with _AOT_LOCK:
                evt = _AOT_INFLIGHT.pop(key, None)
            if evt is not None:
                evt.set()
            continue
        threads.append(t)
    return threads



class ResidentState:
    """Cross-batch scheduler memory — the resident-buffer streaming
    entry for long-lived callers (the online checker's rolling prefix
    checks dispatch one BucketScheduler per check, many checks per
    second, for hours). Passed via ``scheduler_opts={"resident": rs}``,
    it threads the pieces worth keeping warm across per-batch
    scheduler instances:

      * ``safe_bp`` — OOM-bisected rows-per-dispatch caps, so check
        k+1 plans under the wall check k already discovered instead of
        re-OOMing into the ladder once per batch;
      * ``awaited`` — kernel shapes already awaited once, so the
        watchdog's one-time compile grace is paid once per daemon, not
        once per rolling check;
      * ``frontiers`` — per-tenant ResidentFrontier objects (the
        incremental online path's carried WGL search state), keyed by
        (tenant key, writer incarnation): the daemon's delta ticks
        resume the device frontier that the previous tick left off
        instead of re-walking from op 0.

    The process-wide kernel registry / AOT shipping already persists
    the compiled executables themselves; this carries the *learned*
    state that otherwise dies with each scheduler. Shared by reference
    (both schedulers mutate the same dict/set), which is exactly the
    point."""

    def __init__(self):
        self.safe_bp: Dict = {}
        self.awaited: set = set()
        self.frontiers: Dict = {}
        self.batches = 0

    def adopt(self, sch) -> None:
        """Wire a freshly built scheduler to this resident state."""
        sch._safe_bp = self.safe_bp
        sch._awaited_shapes = self.awaited
        self.batches += 1


# ------------------------------------------------- resident device frontier

class FrontierInvalid(Exception):
    """The carried frontier cannot soundly extend to the new prefix —
    the vocabulary outgrew the enumerated space non-monotonically, the
    pending window outgrew the compiled mask axis, or the buffer no
    longer contains the frontier's consumed prefix. Callers rebuild
    from op 0 (one full-cost tick, still exact) and resume delta ticks
    after; the online engine counts each as a frontier invalidation."""


class ResidentFrontier:
    """Per-tenant resident WGL search state: the online daemon's
    O(new ops) seam (ROADMAP item 2).

    Holds, across rolling prefix checks of ONE live history row:

      * the packed configs-so-far frontier carry (F / Fbad / valid /
        bad — linearize's resume-kernel contract), advanced permanently
        over the *stable* prefix;
      * the pending-invocation window at the stable point (slot table,
        free mask, live invocations awaiting completion) — the encode
        walk's state, so the next tick's events continue the same slot
        namespace;
      * the kind-vocabulary watermark (grow-only; growth re-enumerates
        the state space and keeps the carry only when the existing
        states survive as a prefix — the packed state bits stay
        aligned — else the frontier invalidates).

    The *stable* point is the earliest still-open invocation: every op
    before it has its completion in the buffer, so its encoding can
    never be rewritten by later arrivals (completion-value propagation
    and the failed-pair drop are position-local once the completion is
    known). Events at or past the stable point — the volatile tail:
    dangling invocations held open, per the daemon's checkable-prefix
    contract — are re-encoded each tick from a snapshot of the walk
    state and checked from a copy of the carry, so the interim verdict
    is exactly the full-prefix verdict while the per-tick device work
    is O(new ops + open window).

    Invalidation (FrontierInvalid) falls back to a full rebuild;
    serialization (``export``/``restore``) rides the tenant's
    ChunkJournal as the frontier-checkpoint row, inode-bound like every
    other online artifact, so a daemon restart or a service takeover
    resumes the carry with zero re-dispatched decided events."""

    #: Mask-axis headroom over the observed peak window at build time:
    #: absorbs the next invocation burst without a rebuild.
    W_HEADROOM = 1

    def __init__(self, model, *, max_states: Optional[int] = None,
                 w: Optional[int] = None):
        from .linearize import MAX_PACKED_STATES
        self.model = model
        self.max_states = max_states or MAX_PACKED_STATES
        self.kinds: List[tuple] = []
        self.kind_index: Dict[tuple, int] = {}
        self.space = None
        self.W = w
        self.pos = 0          # raw ops consumed into the frozen walk
        self.seen = 0         # raw ops ingested into bookkeeping
        self.n_events = 0     # frozen (permanently dispatched) events
        self.table: List[int] = []
        self.free = 0
        self.live = 0
        self.slot_of: Dict = {}       # process -> slot awaiting its OK
        self.peak_live = 0
        self.carry: Optional[dict] = None
        self.latched_bad: Optional[int] = None
        self.open_inv: Dict = {}      # process -> invoke position
        self.completion: Dict[int, tuple] = {}  # invoke pos -> (t, val)
        self._target_key = None
        self.target = None
        self.stats = {"advances": 0, "events": 0, "delta_ops": 0}
        self.last_events = 0
        self.last_delta_ops = 0

    # ------------------------------------------------------- vocabulary
    @property
    def v_pad(self) -> int:
        return 32 * max(1, -(-self.space.n_states // 32))

    @property
    def _k_rows(self) -> int:
        return max(16, _pow2_ceil(len(self.kinds) + 1))

    def _need_kind(self, kind: tuple) -> None:
        from .linearize import grow_frontier_states, n_state_words
        from .statespace import enumerate_statespace
        if kind in self.kind_index:
            return
        kinds2 = self.kinds + [kind]
        space2 = enumerate_statespace(self.model, kinds2,
                                      self.max_states)
        carried = self.carry is not None or self.n_events or self.pos
        if self.space is not None and carried:
            # The packed carry's state bits must stay aligned: growth
            # is only admissible when the existing states survive as a
            # PREFIX of the re-enumerated space (append-stable — flat
            # register vocabularies are; multi-level cas graphs
            # renumber and invalidate). Before anything is carried
            # (fresh build, mid-bootstrap) renumbering is harmless —
            # nothing references the old numbering yet.
            old_v = self.space.n_states
            if (list(space2.kinds[:len(self.kinds)]) != self.kinds
                    or space2.states[:old_v] != self.space.states):
                raise FrontierInvalid(
                    f"vocabulary growth renumbered the state space "
                    f"({old_v} -> {space2.n_states} states)")
            old_words = n_state_words(self.v_pad)
            self.space = space2
            new_words = n_state_words(self.v_pad)
            if self.carry is not None and new_words != old_words:
                self.carry = grow_frontier_states(self.carry, old_words,
                                                  new_words)
        else:
            self.space = space2
        self.kind_index[kind] = len(self.kinds)
        self.kinds.append(kind)

    def _refresh_target(self) -> None:
        key = (id(self.space), self.v_pad, self._k_rows)
        if key != self._target_key:
            self.target = self.space.padded_target(self.v_pad,
                                                   self._k_rows - 1)
            self._target_key = key

    # ---------------------------------------------------------- ingest
    def _ingest(self, ops) -> int:
        """Fold newly arrived ops into the bookkeeping maps (open
        invocations, completion knowledge, vocabulary). Returns the
        count of new ops consumed."""
        from ..history.ops import INVOKE, OK
        from .statespace import canonical_value
        n = len(ops)
        new = n - self.seen
        for p in range(self.seen, n):
            o = ops[p]
            if not o.is_client:
                continue
            if o.type == INVOKE:
                self._need_kind((o.f, canonical_value(o.value)))
                self.open_inv[o.process] = p
            elif o.is_completion:
                ip = self.open_inv.pop(o.process, None)
                if ip is None:
                    continue
                self.completion[ip] = (o.type, o.value)
                if o.type == OK:
                    inv = ops[ip]
                    v = inv.value if inv.value is not None else o.value
                    self._need_kind((inv.f, canonical_value(v)))
        self.seen = n
        return max(0, new)

    def _kind_of(self, inv, comp) -> int:
        from ..history.ops import OK
        from .statespace import canonical_value
        v = inv.value
        if v is None and comp is not None and comp[0] == OK:
            v = comp[1]
        return self.kind_index[(inv.f, canonical_value(v))]

    # ------------------------------------------------------------ walks
    def _walk(self, ops, lo: int, hi: int, state: dict,
              events: List[tuple], *, volatile: bool) -> None:
        """The encode walk over positions [lo, hi): the exact
        per-history semantics of ops.encode.encode_history — value-
        propagated invocations allocate lowest-free-first, failed pairs
        drop, never-ok identity invocations drop, :info (and, in the
        volatile tail, dangling) invocations pin their slot forever,
        ok completions emit one event snapshotting the pending table.
        Mutates ``state`` and appends (slot, table-copy, op-position)
        to ``events``."""
        from ..history.ops import FAIL, INFO, INVOKE, OK
        identity = self.space.identity_kinds if self.space else ()
        table, slot_of = state["table"], state["slot_of"]
        for p in range(lo, hi):
            o = ops[p]
            if not o.is_client:
                continue
            if o.type == INVOKE:
                comp = self.completion.get(p)
                if comp is not None and comp[0] == FAIL:
                    continue                  # failed pair: both drop
                kidx = self._kind_of(o, comp)
                dangles = comp is None or comp[0] == INFO
                if dangles and kidx in identity:
                    continue                  # the identity-drop rule
                if not volatile and comp is None:
                    raise FrontierInvalid(
                        "open invocation inside the frozen walk")
                if not state["free"]:
                    raise FrontierInvalid(
                        f"pending window outgrew the W={self.W} "
                        f"mask axis")
                slot = (state["free"] & -state["free"]).bit_length() - 1
                state["free"] &= state["free"] - 1
                table[slot] = kidx
                state["live"] += 1
                self.peak_live = max(self.peak_live, state["live"])
                if dangles:
                    continue                  # pinned: never freed
                slot_of[o.process] = slot
            elif o.type == OK:
                slot = slot_of.pop(o.process, None)
                if slot is None:
                    continue
                events.append((slot, table.copy(), p))
                table[slot] = EMPTY
                state["free"] |= 1 << slot
                state["live"] -= 1
            elif o.type in (FAIL, INFO):
                pass                          # handled at the invoke

    def _state(self) -> dict:
        return {"table": self.table, "free": self.free,
                "live": self.live, "slot_of": self.slot_of}

    def _dispatch(self, events: List[tuple], idx0: int, carry: dict,
                  close_table: Optional[List[int]] = None) -> dict:
        """Encode one event list (optionally + EV_CLOSE) and advance
        ``carry`` through the resume kernel — the delta-dispatch spans
        carry the ``frontier`` family tag so telemetry.gaps() can
        attribute incremental vs full-check device time."""
        from .linearize import run_carried_events
        n = len(events) + (1 if close_table is not None else 0)
        sent = self._k_rows - 1
        ev_type = np.zeros(n, np.int8)
        ev_slot = np.zeros(n, np.int8)
        ev_slots = np.full((n, self.W), sent, np.int32)
        for i, (slot, tab, _p) in enumerate(events):
            ev_type[i] = EV_OK
            ev_slot[i] = slot
            for s, k in enumerate(tab):
                if k != EMPTY:
                    ev_slots[i, s] = k
        if close_table is not None:
            ev_type[n - 1] = EV_CLOSE
            for s, k in enumerate(close_table):
                if k != EMPTY:
                    ev_slots[n - 1, s] = k
        self._refresh_target()
        with telemetry.span("dispatch", cat="device", family="frontier",
                            V=self.v_pad, W=self.W, events=n,
                            idx0=idx0):
            out = run_carried_events(self.v_pad, self.W, self.target,
                                     ev_type, ev_slot, ev_slots, idx0,
                                     carry)
        self.stats["events"] += n
        self.last_events += n
        return out

    # ---------------------------------------------------------- advance
    def advance(self, ops) -> Tuple[bool, Optional[int]]:
        """Fold the buffer's new ops into the carried frontier and
        decide the current full prefix: (valid, first-bad-op-position).
        O(new ops + open window) per call. Raises FrontierInvalid when
        the carry cannot soundly extend (callers rebuild); any other
        exception leaves the frontier poisoned — callers must drop it."""
        from .linearize import frontier_carry_init
        self.last_events = 0
        self.last_delta_ops = 0
        if self.latched_bad is not None:
            # Linearizability is prefix-closed: once invalid, every
            # longer prefix is invalid with the same first bad op.
            return False, self.latched_bad
        if self.pos > len(ops):
            raise FrontierInvalid(
                f"buffer ({len(ops)} ops) no longer contains the "
                f"frontier's consumed prefix ({self.pos} ops)")
        seen0 = self.seen
        if self.W is None:
            self._bootstrap(ops)
        self._ingest(ops)
        new = max(0, len(ops) - seen0)
        self.stats["delta_ops"] += new
        self.last_delta_ops = new
        self.stats["advances"] += 1
        if self.space is None:
            return True, None             # no client ops yet
        if self.carry is None:
            self.carry = frontier_carry_init(self.v_pad, self.W)
        stable = max(self.pos,
                     min(self.open_inv.values(), default=len(ops)))
        if stable > self.pos:
            frozen: List[tuple] = []
            st = self._state()
            self._walk(ops, self.pos, stable, st, frozen,
                       volatile=False)
            self.free, self.live = st["free"], st["live"]
            if frozen:
                self.carry = self._dispatch(frozen, self.n_events,
                                            self.carry)
                if not bool(self.carry["valid"][0]):
                    off = int(self.carry["bad"][0]) - self.n_events
                    self.latched_bad = frozen[off][2]
                    self.n_events += len(frozen)
                    self.pos = stable
                    return False, self.latched_bad
                self.n_events += len(frozen)
            self.pos = stable
            for p in [p for p in self.completion if p < self.pos]:
                del self.completion[p]
        # Volatile tail: re-encoded each tick from a snapshot, checked
        # from a COPY of the carry (the resume kernel never mutates its
        # inputs), dangling invocations held open + the EV_CLOSE flush.
        vstate = {"table": self.table.copy(), "free": self.free,
                  "live": self.live, "slot_of": dict(self.slot_of)}
        tail: List[tuple] = []
        self._walk(ops, self.pos, len(ops), vstate, tail, volatile=True)
        out = self._dispatch(tail, self.n_events, self.carry,
                             close_table=vstate["table"])
        if bool(out["valid"][0]):
            return True, None
        off = int(out["bad"][0]) - self.n_events
        if not 0 <= off < len(tail):
            raise FrontierInvalid(
                f"bad-event ordinal {int(out['bad'][0])} outside the "
                f"volatile tail")
        return False, tail[off][2]

    def _bootstrap(self, ops) -> None:
        """First advance: size the mask axis from the buffer's true
        peak window (one host scan — this IS the full-cost tick) with
        headroom for the next burst."""
        from .linearize import DATA_MAX_SLOTS
        self._ingest(ops)
        state = {"table": [EMPTY] * DATA_MAX_SLOTS,
                 "free": (1 << DATA_MAX_SLOTS) - 1, "live": 0,
                 "slot_of": {}}
        self.W = DATA_MAX_SLOTS          # probe walk at the full width
        if self.space is None:
            # No client ops at all yet: enumerate the empty vocabulary.
            self._need_kind(("__frontier_probe__", None))
            self.kinds.pop()
            del self.kind_index[("__frontier_probe__", None)]
        probe: List[tuple] = []
        self.peak_live = 0
        self._walk(ops, 0, len(ops), state, probe, volatile=True)
        w = max(2, self.peak_live + self.W_HEADROOM)
        if w > DATA_MAX_SLOTS:
            if self.peak_live <= DATA_MAX_SLOTS:
                w = DATA_MAX_SLOTS
            else:
                raise FrontierInvalid(
                    f"peak window {self.peak_live} beyond the "
                    f"single-device mask axis")
        self.W = w
        self.table = [EMPTY] * w
        self.free = (1 << w) - 1
        self.live = 0
        self.slot_of = {}
        self.peak_live = 0

    # ---------------------------------------------- checkpoint contract
    def export(self) -> dict:
        """The journal frontier-checkpoint row's payload: vocabulary
        watermark + pending window + carried bitsets (doc/online.md
        documents the format)."""
        from .linearize import export_frontier
        return {"v": 1, "W": self.W, "pos": self.pos,
                "n_events": self.n_events,
                "kinds": [[f, _json_value(v)] for f, v in self.kinds],
                "table": list(self.table), "free": self.free,
                "live": self.live,
                "slot_of": [[p, s] for p, s in self.slot_of.items()],
                "peak_live": self.peak_live,
                "latched_bad": self.latched_bad,
                "carry": (export_frontier(self.carry)
                          if self.carry is not None else None)}

    @classmethod
    def restore(cls, model, payload: dict, *,
                max_states: Optional[int] = None
                ) -> Optional["ResidentFrontier"]:
        """Rehydrate a checkpointed frontier; None on any mismatch —
        the caller rebuilds from op 0, exactly the cache-miss path."""
        from .linearize import import_frontier
        from .statespace import (StateSpaceExplosion, canonical_value,
                                 enumerate_statespace)
        try:
            if payload.get("v") != 1 or payload.get("W") is None:
                return None
            fr = cls(model, max_states=max_states, w=int(payload["W"]))
            kinds = [(f, canonical_value(v))
                     for f, v in payload["kinds"]]
            if kinds:
                fr.space = enumerate_statespace(model, kinds,
                                                fr.max_states)
                if list(fr.space.kinds) != kinds:
                    return None
            fr.kinds = kinds
            fr.kind_index = {k: i for i, k in enumerate(kinds)}
            fr.pos = fr.seen = int(payload["pos"])
            fr.n_events = int(payload["n_events"])
            fr.table = [int(x) for x in payload["table"]]
            fr.free = int(payload["free"])
            fr.live = int(payload["live"])
            fr.slot_of = {p: int(s) for p, s in payload["slot_of"]}
            fr.peak_live = int(payload["peak_live"])
            lb = payload.get("latched_bad")
            fr.latched_bad = None if lb is None else int(lb)
            if len(fr.table) != fr.W:
                return None
            if payload.get("carry") is not None:
                if fr.space is None:
                    return None
                fr.carry = import_frontier(payload["carry"], fr.v_pad,
                                           fr.W)
                if fr.carry is None:
                    return None
            return fr
        except StateSpaceExplosion:
            return None
        except Exception:
            return None


def _json_value(v):
    """Kind values round-trip through JSON: canonical tuples (from list
    values) become lists on disk and canonical_value() re-tuples them
    on restore."""
    if isinstance(v, tuple):
        return [_json_value(x) for x in v]
    if isinstance(v, frozenset):
        return sorted(_json_value(x) for x in v)
    return v


def _stat_inc(sch, family: str, key: str, n) -> None:
    """Shared locked stats+registry increment for both schedulers:
    bump the instance stats dict under its lock and mirror into the
    process registry as ``scheduler.<key>{family=...}`` through a
    memoized counter handle (the per-chunk hot path must not rebuild
    key strings)."""
    with sch._stats_lock:
        sch.stats[key] = sch.stats.get(key, 0) + n
        c = sch._mirrors.get(key)
        if c is None:
            c = sch._mirrors[key] = telemetry.REGISTRY.counter(
                f"scheduler.{key}", family=family)
    c.inc(n)


# --------------------------------------------------------------- scheduler

class _Run:
    """One consolidated bucket's in-flight accounting."""

    def __init__(self, batch: EncodedBatch, n_chunks: int):
        self.batch = batch
        self.remaining = n_chunks
        self.valid: List[np.ndarray] = []
        self.bad: List[np.ndarray] = []
        self.front: List = []

    def collect(self, v, b, fr):
        self.valid.append(v)
        self.bad.append(b)
        self.front.append(fr)
        self.remaining -= 1

    @property
    def done(self) -> bool:
        return self.remaining == 0

    def result(self, return_frontier):
        valid = np.concatenate(self.valid)
        bad = np.concatenate(self.bad)
        if return_frontier is True:
            front = np.concatenate(self.front)
        elif return_frontier == "invalid":
            front = {}
            off = 0
            for v, fm in zip(self.valid, self.front):
                for r, row in fm.items():
                    front[off + r] = row
                off += len(v)
        else:
            front = None
        return self.batch, (valid, bad, front)


class BucketScheduler:
    """The streaming scheduler. One instance per logical batch; not
    thread-safe; ``stats`` is a JSON-friendly dict filled as the run
    streams (wall_s lands when the generator finishes).

    ``min_device_rows``: consolidated wide buckets (W >= DATA_MAX_SLOTS)
    still smaller than this are yielded with the DIVERTED sentinel
    instead of dispatched — the caller's native-CPU tail contract. The
    check happens AFTER consolidation, so a healthy merged class stays
    on device where the exact-W flow would have routed its fragments to
    the CPU one by one.
    """

    def __init__(self, *, return_frontier=False,
                 max_classes: Optional[int] = None,
                 chunk_rows: Optional[int] = None,
                 depth: int = PIPELINE_DEPTH,
                 consolidate: bool = True,
                 prewarm: bool = True,
                 donate: bool = True,
                 min_device_rows: int = 0,
                 on_chunk=None,
                 compilation_cache: bool = True,
                 faults: Optional[FaultInjector] = None,
                 max_retries: Optional[int] = None,
                 backoff_s: Optional[float] = None,
                 fuse_width: Optional[int] = None,
                 shard_min_rows: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 event_route_events: Optional[int] = None,
                 resident: Optional[ResidentState] = None,
                 wgl_backend: Optional[str] = None):
        self.return_frontier = return_frontier
        # WGL dispatch backend for narrow chunks: "auto" (default)
        # asks the fleet cost router to price the Pallas megakernel
        # against the lax.scan kernel from the MEASURED backend rates
        # (fleet.router_rates — startup probe / persisted store rates /
        # env pins) per bucket shape; "pallas" / "xla" force. With no
        # measured pallas rate, or under $JT_ROUTER_PALLAS=0, auto is
        # bit-identical to the pre-pallas scheduler. "dc" pins the
        # decrease-and-conquer peel PRE-FILTER on (residue still rides
        # the xla scan in the same _ship sequence); in auto the
        # pre-filter engages per bucket shape only when the router
        # prices it under every frontier backend AND the bucket's
        # capable fraction clears $JT_DC_RESIDUE_MAX_FRAC.
        if wgl_backend is None:
            wgl_backend = os.environ.get("JT_WGL_BACKEND", "auto")
        if wgl_backend not in ("auto", "xla", "pallas", "dc"):
            log.warning("ignoring unknown wgl_backend=%r (want "
                        "auto|xla|pallas|dc)", wgl_backend)
            wgl_backend = "auto"
        self.wgl_backend = wgl_backend
        self._backend_choice: Dict[Tuple, bool] = {}
        self.max_classes = (DEFAULT_MAX_CLASSES if max_classes is None
                            else max_classes)
        self.chunk_rows = (DEFAULT_CHUNK_ROWS if chunk_rows is None
                           else chunk_rows)
        self.depth = max(1, depth)
        # Fused dispatch: up to fuse_width pipelined chunks (across W
        # classes) ride one XLA call; 1 keeps the per-chunk flow.
        self.fuse_width = max(1, default_fuse_width() if fuse_width is None
                              else int(fuse_width))
        self._fuse_buf: List[Tuple] = []
        self._warmed_groups: set = set()
        self.consolidate = consolidate
        self.prewarm = prewarm
        if donate:
            # CPU XLA can't alias donated buffers into anything — the
            # donation buys nothing and every dispatch would warn.
            import jax
            donate = jax.default_backend() != "cpu"
        self.donate = donate
        self.min_device_rows = min_device_rows
        # Routing floor for the batch-sharded (dataN) route: merged
        # buckets below it stay on the fused chunked pipeline — which
        # carries the fault hooks, chunk journal, and dispatch fusion —
        # instead of draining the pipeline for a blocking SPMD call.
        # None keeps the historical mesh-derived default
        # (data devices * MIN_ROWS_PER_DEVICE); dispatch-latency-bound
        # callers (and the hermetic partition tests) raise it.
        self.shard_min_rows = shard_min_rows
        # Long-history cost route: narrow buckets whose event axis
        # meets this length dispatch through the event-chunked resume
        # kernel (run_event_chunked) by COST MODEL — the measured
        # long-scan crossover — rather than only as the OOM fallback.
        self.event_route_events = (
            event_route_min_events() if event_route_events is None
            else max(0, int(event_route_events)))
        self.on_chunk = on_chunk
        if compilation_cache:
            enable_compilation_cache()
        # The checker nemesis (ops.faults): explicit injector, else the
        # ambient $JT_FAULT_PLAN schedule, else no faults.
        self.faults = faults if faults is not None \
            else FaultInjector.from_env()
        self.max_retries = RETRY_MAX if max_retries is None \
            else max(0, int(max_retries))
        if backoff_s is None:
            backoff_s = (self.faults.backoff_s
                         if self.faults is not None else None)
        self.backoff_s = RETRY_BACKOFF_S if backoff_s is None \
            else float(backoff_s)
        # Degradation-ladder state: caller-level indices of rows the
        # device could not decide (callers MUST re-decide them through
        # their host engine — the in-band verdict is an inert
        # placeholder), provenance tags for every off-happy-path row
        # ("device-retried" / "host-fallback"; untagged rows are plain
        # "device"), the route of every row a wide/frontier/sharded
        # dispatch decided (untagged rows ran the one-device chunk
        # pipeline), and the learned safe rows-per-dispatch per
        # (V, W class) after an OOM bisection.
        self.quarantined: Dict[int, str] = {}
        self.row_provenance: Dict[int, str] = {}
        self.row_route: Dict[int, str] = {}
        self._safe_bp: Dict[Tuple[int, int], int] = {}
        self._awaited_shapes: set = set()
        # Encode→dispatch hand-off bound (JT_SCHED_MAX_QUEUE): chunks
        # buffered past it force a blocking flush behind a counted
        # backpressure event.
        self.max_queue = sched_max_queue() if max_queue is None \
            else max(0, int(max_queue))
        if resident is not None:
            resident.adopt(self)
        # ``stats`` is read by callers as a plain dict, but increments
        # go through _inc: chunks of concurrent fused groups retire on
        # executor/retire threads, and an unlocked read-modify-write
        # would drop counts (they also mirror into the process-wide
        # telemetry registry — the results.json telemetry block).
        self._stats_lock = threading.Lock()
        self._mirrors: dict = {}       # key -> registry counter handle
        self._chunk_seq = 0            # trace chunk ordinals
        self.stats: dict = {
            "input_buckets": 0, "classes": [], "chunks": 0,
            "dispatches": 0, "fused_groups": 0,
            "rows": 0, "pad_rows": 0, "compiled_shapes": 0,
            "t_first_verdict_s": None, "t_first_dispatch_s": None,
            "wall_s": None,
            "events": 0, "orig_events": 0, "fusion_ratio": None,
            "retries": 0, "bisections": 0, "watchdog_fired": 0,
            "oom_events": 0, "corrupt_chunks": 0, "quarantined_rows": 0,
            "prewarm_wedged": 0, "abandoned_buckets": 0,
            "faults_injected": 0, "backpressure_events": 0,
            "event_routed_rows": 0, "event_routed_dispatches": 0,
            "pallas_dispatches": 0, "pallas_rows": 0,
            "dc_dispatches": 0, "dc_rows": 0, "dc_decided_rows": 0,
            "dc_skipped_scans": 0,
            "wgl_backend": self.wgl_backend,
        }
        self._t0 = None
        self._first_dispatch_t = None

    def _inc(self, key: str, n=1) -> None:
        _stat_inc(self, "wgl", key, n)

    # ------------------------------------------------------------ plumbing
    def _class_chunk(self, V: int, W: int) -> int:
        per_hist = n_state_words(V) << W
        chunk = max(1, min(self.chunk_rows,
                           MAX_FRONTIER_ELEMENTS // per_hist))
        # An OOM bisection taught us this class's real memory wall:
        # plan every later chunk under it instead of re-OOMing at the
        # full size and paying the ladder once per chunk.
        cap = self._safe_bp.get((V, W))
        return min(chunk, cap) if cap else chunk

    def _chunk_plan(self, batch: EncodedBatch) -> Tuple[int, List[Tuple]]:
        """(padded_rows_per_dispatch, [(lo, hi), ...])."""
        chunk = self._class_chunk(batch.V, batch.W)
        if batch.batch <= chunk:
            bp = min(chunk, max(ROW_QUANTUM, _pow2_ceil(batch.batch)))
            return bp, [(0, batch.batch)]
        return chunk, [(lo, min(lo + chunk, batch.batch))
                       for lo in range(0, batch.batch, chunk)]

    def _pad_chunk(self, batch: EncodedBatch, lo: int, hi: int,
                   Bp: int, Np: int):
        nb = hi - lo
        N = batch.n_events
        K1 = batch.target.shape[1]
        W = batch.ev_slots.shape[2]
        ev_type = np.zeros((Bp, Np), batch.ev_type.dtype)
        ev_slot = np.zeros((Bp, Np), batch.ev_slot.dtype)
        ev_slots = np.full((Bp, Np, W), K1 - 1, batch.ev_slots.dtype)
        ev_type[:nb, :N] = batch.ev_type[lo:hi]
        ev_slot[:nb, :N] = batch.ev_slot[lo:hi]
        ev_slots[:nb, :N] = batch.ev_slots[lo:hi]
        if batch.shared_target:
            return ev_type, ev_slot, ev_slots, None
        target = np.full((Bp, K1, batch.V), -1, np.int32)
        target[:nb] = batch.target[lo:hi]
        return ev_type, ev_slot, ev_slots, target

    def _resolve_key(self, key: Tuple):
        """Shared executable-resolution discipline for both the
        per-chunk and fused routes: parked pre-warm/shipped executable
        first, then a disk load, then a BOUNDED wait on an in-flight
        pre-warm compile. Returns None when the caller must fall back
        to the registry jit (a wedged pre-warm is logged and counted
        on the way out)."""
        with _AOT_LOCK:
            compiled = _AOT.get(key)
            waiting = _AOT_INFLIGHT.get(key)
        if compiled is None and waiting is None:
            # A shipped executable beats both waiting and compiling.
            compiled = _aot_load(key)
            if compiled is not None:
                with _AOT_LOCK:
                    _AOT[key] = compiled
        if compiled is None and waiting is not None:
            # The pre-warm thread is mid-compile for exactly this
            # shape: wait for it rather than racing a duplicate jit
            # compile (the whole point of warming). Bounded: a compile
            # RPC can wedge like any device call (the DaemonFuture
            # threat model), and a duplicate compile beats hanging the
            # whole check — the timeout is far past any legitimate
            # compile, so it only fires on a wedged runtime.
            done = waiting.wait(timeout=PREWARM_WAIT_S)
            with _AOT_LOCK:
                compiled = _AOT.get(key)
            if not done and compiled is None:
                # A wedged pre-warm is a real runtime fault, not
                # routine: say so and make it stats-visible before
                # paying the duplicate compile.
                log.warning(
                    "pre-warm compile for kernel shape %s wedged past "
                    "%.0fs; falling back to a duplicate jit compile",
                    key, PREWARM_WAIT_S)
                self._inc("prewarm_wedged")
        return compiled

    def _resolve(self, batch: EncodedBatch, Bp: int, Np: int):
        key = _aot_key(batch.V, batch.W, batch.eff_w_live,
                       batch.shared_target, self.donate,
                       Bp, Np, batch.ev_slots.dtype,
                       batch.target.shape[1])
        return self._resolve_key(key) or get_kernel(
            batch.V, batch.W, shared_target=batch.shared_target,
            donate=self.donate, w_live=batch.eff_w_live)

    def _pallas_for(self, batch: EncodedBatch) -> bool:
        """Does this bucket's dispatch ride the Pallas WGL megakernel?
        Forced backends short-circuit; "auto" asks the fleet cost
        router to price both device backends from the measured rates
        (memoized per bucket shape — the router's answer is stable
        within one run)."""
        if self.wgl_backend in ("xla", "dc"):
            # "dc" residue rides the deterministic lax.scan kernel —
            # one moving part per verdict path.
            return False
        from .pallas_wgl import (pallas_available, pallas_supports,
                                 router_prefers_pallas)
        if not (pallas_available()
                and pallas_supports(batch.V, batch.W,
                                    k1=batch.target.shape[1],
                                    shared=batch.shared_target)):
            return False
        if self.wgl_backend == "pallas":
            return True
        key = (batch.V, batch.W,
               _round_up(batch.n_events, EVENT_QUANTUM))
        hit = self._backend_choice.get(key)
        if hit is None:
            hit = router_prefers_pallas(batch.V, batch.W,
                                        batch.n_events,
                                        max(batch.batch, 1))
            self._backend_choice[key] = hit
        return hit

    def _dc_for(self, batch: EncodedBatch) -> bool:
        """Does this bucket's dispatch run the decrease-and-conquer
        peel PRE-FILTER first? Forced "dc" short-circuits (capability
        is still per row — the plan decides); "auto" engages only when
        the router prices the peel loop under every frontier backend
        (measured dc_events_per_s, never hardcoded) AND the bucket's
        capable fraction clears the residue gate — a mostly-incapable
        bucket must not pay dc + scan. Memoized per bucket shape like
        _pallas_for."""
        if self.wgl_backend in ("xla", "pallas"):
            return False
        from .dc_monitor import (dc_available, dc_plan_for,
                                 dc_residue_max_frac, router_prefers_dc)
        if not dc_available():
            return False
        if self.wgl_backend == "dc":
            return dc_plan_for(batch) is not None
        key = ("dc", batch.V, batch.W,
               _round_up(batch.n_events, EVENT_QUANTUM))
        hit = self._backend_choice.get(key)
        if hit is None:
            hit = router_prefers_dc(batch.W, batch.n_events,
                                    max(batch.batch, 1))
            self._backend_choice[key] = hit
        if not hit:
            return False
        plan = dc_plan_for(batch)
        return (plan is not None
                and plan.capable_frac >= 1.0 - dc_residue_max_frac())

    def _resolve_pallas(self, batch: EncodedBatch, Bp: int, Np: int):
        """Pallas twin of _resolve: a parked pre-warm/shipped
        executable first (the same _AOT registry, key prefixed
        "pallas"), else the jit-wrapped kernel registry."""
        key = ("pallas",) + _aot_key(
            batch.V, batch.W, batch.eff_w_live, batch.shared_target,
            False, Bp, Np, batch.ev_slots.dtype, batch.target.shape[1])
        compiled = self._resolve_key(key)
        if compiled is not None:
            return compiled
        from .pallas_wgl import get_pallas_kernel
        return get_pallas_kernel(batch.V, batch.W,
                                 shared_target=batch.shared_target,
                                 w_live=batch.eff_w_live)

    def _ship(self, batch: EncodedBatch, lo: int, hi: int, Bp: int,
              Np: int, tag: str):
        """The ONE dispatch sequence both the pipelined path and the
        ladder's synchronous re-dispatches run — fault hooks, pad,
        kernel launch (async) — so the retried path can never drift
        from the path it is retrying. Returns (lazy out, decode
        delay). The cost-routed backend choice (Pallas megakernel vs
        lax.scan) happens HERE, under the same fault hooks and
        telemetry spans, so the ladder retries whatever backend the
        router chose."""
        with self._stats_lock:
            ordinal = self._chunk_seq
            self._chunk_seq += 1
        with telemetry.span("encode", V=batch.V, W=batch.W,
                            rows=hi - lo, chunk=ordinal, tag=tag):
            if self.faults is not None:
                self.faults.fire("encode")
            ev_type, ev_slot, ev_slots, target = self._pad_chunk(
                batch, lo, hi, Bp, Np)
        delay = 0.0
        if self.faults is not None:
            delay = self.faults.sleep_for(self.faults.fire("dispatch"))
        if self._dc_for(batch):
            # Decrease-and-conquer pre-filter: peel the chunk's rows
            # on device; a fully-decided-valid chunk skips its scan
            # launch outright (synthesized all-valid verdicts carry
            # the INT32_MAX sentinel the validator demands), anything
            # else — residue, incapable rows, full-frontier decode
            # mode — falls through to the unchanged scan below.
            from .dc_monitor import dc_prefilter_chunk
            with telemetry.span("dispatch", cat="device",
                                family="wgl-dc", route="data1",
                                V=batch.V, W=batch.W,
                                rows=hi - lo, chunk=ordinal, tag=tag):
                decided = dc_prefilter_chunk(batch, lo, hi)
            if decided is not None:
                DISPATCH_LOG.append(("dc", batch.V, batch.W, hi - lo))
                self._inc("dc_dispatches")
                self._inc("dc_rows", hi - lo)
                nd = int(decided.sum())
                if nd:
                    self._inc("dc_decided_rows", nd)
                if nd == hi - lo and self.return_frontier is not True:
                    self._inc("dc_skipped_scans")
                    self._inc("dispatches")
                    for r in range(lo, hi):
                        self.row_provenance[batch.indices[r]] = "wgl-dc"
                    return (np.ones(Bp, bool),
                            np.full(Bp, INT32_MAX, np.int32),
                            None), delay
        use_pallas = self._pallas_for(batch)
        family = "wgl-pallas" if use_pallas else "wgl"
        with telemetry.span("dispatch", cat="device", family=family,
                            route="data1", V=batch.V, W=batch.W,
                            rows=hi - lo, chunk=ordinal, tag=tag):
            if use_pallas:
                kern = self._resolve_pallas(batch, Bp, Np)
                log_kernel_shapes(batch.V, batch.W, "pallas",
                                  batch.shared_target, False, Bp, Np,
                                  batch.eff_w_live)
                DISPATCH_LOG.append(("pallas", batch.V, batch.W,
                                     hi - lo))
                self._inc("pallas_dispatches")
                self._inc("pallas_rows", hi - lo)
            else:
                kern = self._resolve(batch, Bp, Np)
                log_kernel_shapes(batch.V, batch.W, "data1",
                                  batch.shared_target, self.donate, Bp,
                                  Np, batch.eff_w_live)
                DISPATCH_LOG.append((tag, batch.V, batch.W, hi - lo))
            self._inc("dispatches")
            out = kern(ev_type, ev_slot, ev_slots,
                       np.ascontiguousarray(batch.target[0])
                       if batch.shared_target else target)
        return out, delay

    def _member_spec(self, batch: EncodedBatch, Bp: int,
                     Np: int) -> Tuple:
        return (batch.V, batch.W, batch.eff_w_live, batch.shared_target,
                self.donate, Bp, Np, batch.ev_slots.dtype,
                batch.target.shape[1])

    def _resolve_group(self, specs: Tuple[Tuple, ...]):
        """Resolve the fused megakernel for one dispatch group —
        shipped/pre-warmed executable first (the _resolve_key
        discipline), else the registry jit."""
        key = ("fused",) + tuple(_aot_key(*s) for s in specs)
        return self._resolve_key(key) or get_fused_kernel(
            tuple(s[:4] for s in specs), donate=self.donate)

    def _dispatch_group(self, members: List[Tuple]):
        """Pipelined (async) dispatch of one fused group — one XLA call
        retires every member chunk. ``members`` is [(run, lo, hi, Bp)];
        single-member groups ride the plain per-chunk kernel (_ship),
        which keeps fuse_width=1 bit-compatible with the pre-fusion
        flow (same kernels, same fault ordinals). Failures the fault
        classifier recognizes are carried to retire time as the ``out``
        payload instead of raised, so the pipeline keeps streaming and
        the degradation ladder (_recover) runs per member when the
        group's turn to decode comes."""
        outs: object
        try:
            if len(members) == 1:
                run, lo, hi, Bp = members[0]
                Np = _round_up(run.batch.n_events, EVENT_QUANTUM)
                out, delay = self._ship(run.batch, lo, hi, Bp, Np,
                                        "data1")
                outs = [out]
            elif (pall := [self._pallas_for(run.batch)
                           or self._dc_for(run.batch)
                           for run, _, _, _ in members]) and \
                    any(pall) and pall.count(False) <= 1:
                # A Pallas member owns its launch economics (the whole
                # chunk retires in ONE kernel launch with the frontier
                # resident on-chip), so a fused XLA megakernel buys it
                # nothing — and one leftover scan member has nothing
                # to fuse WITH: ship each member through the one
                # dispatch sequence instead. Fault ordinals still fire
                # once per member, exactly as fusion promises.
                # (dc-routed members ride the same rule: the peel
                # pre-filter lives inside _ship, and a decided chunk
                # skips its scan launch entirely — fusing it away
                # would launch the scan it was about to skip.)
                outs = []
                delay = 0.0
                for run, lo, hi, Bp in members:
                    Np = _round_up(run.batch.n_events, EVENT_QUANTUM)
                    out, d = self._ship(run.batch, lo, hi, Bp, Np,
                                        "data1")
                    outs.append(out)
                    delay += d
            else:
                # >=2 scan members (plus possibly Pallas members, each
                # shipped individually IN MEMBER ORDER — ordinals and
                # fault hooks must fire in the same sequence either
                # way): the scan members still retire as ONE fused XLA
                # call, so a Pallas-routed shape in the group never
                # costs the rest of the group its fusion.
                outs_by_pos: List = [None] * len(members)
                fused_pos: List[int] = []
                flat: List = []
                specs: List[Tuple] = []
                delay = 0.0
                with self._stats_lock:
                    group_id = self.stats["fused_groups"]
                for pos, (run, lo, hi, Bp) in enumerate(members):
                    b = run.batch
                    Np = _round_up(b.n_events, EVENT_QUANTUM)
                    if pall[pos]:
                        out, d = self._ship(b, lo, hi, Bp, Np,
                                            "data1")
                        outs_by_pos[pos] = out
                        delay += d
                        continue
                    with self._stats_lock:
                        ordinal = self._chunk_seq
                        self._chunk_seq += 1
                    # Fault hooks fire once per MEMBER, not per group:
                    # the nemesis ordinals (FaultPlan chunk=N) count
                    # chunks, and fusion must not shift them — the
                    # fault-schedule parity tests pin the pre-fusion
                    # ordinals. Member delays accumulate (each would
                    # have stalled its own decode).
                    with telemetry.span("encode", V=b.V, W=b.W,
                                        rows=hi - lo, chunk=ordinal,
                                        fuse_group=group_id):
                        if self.faults is not None:
                            self.faults.fire("encode")
                        ev_type, ev_slot, ev_slots, target = \
                            self._pad_chunk(b, lo, hi, Bp, Np)
                    if self.faults is not None:
                        delay += self.faults.sleep_for(
                            self.faults.fire("dispatch"))
                    flat.extend([
                        ev_type, ev_slot, ev_slots,
                        np.ascontiguousarray(b.target[0])
                        if b.shared_target else target])
                    specs.append(self._member_spec(b, Bp, Np))
                    fused_pos.append(pos)
                    log_kernel_shapes(b.V, b.W, "data1",
                                      b.shared_target, self.donate, Bp,
                                      Np, b.eff_w_live)
                    DISPATCH_LOG.append(("data1fused", b.V, b.W,
                                         hi - lo))
                spec_t = tuple(specs)
                gspec = ("fused", spec_t)
                if self.prewarm and gspec not in self._warmed_groups:
                    # First sight of this group composition: compile it
                    # through the pre-warm path (daemon _compile_spec),
                    # which prefers a SHIPPED executable and exports a
                    # fresh compile back to the AOT dir — _resolve_group
                    # below waits on the in-flight event instead of
                    # racing a duplicate jit compile.
                    self._warmed_groups.add(gspec)
                    prewarm_kernels([gspec])
                with telemetry.span(
                        "dispatch", cat="device", family="wgl",
                        route="data1", fused=True,
                        fuse_group=group_id, members=len(fused_pos),
                        rows=sum(members[p][2] - members[p][1]
                                 for p in fused_pos),
                        ws=[m[1] for m in specs]):
                    kern = self._resolve_group(spec_t)
                    self._inc("dispatches")
                    self._inc("fused_groups")
                    out_flat = kern(*flat)
                for i, pos in enumerate(fused_pos):
                    outs_by_pos[pos] = tuple(out_flat[3 * i:3 * i + 3])
                outs = outs_by_pos
        except Exception as e:
            if classify_failure(e) is None:
                raise
            outs, delay = e, 0.0
        if self._first_dispatch_t is None:
            self._first_dispatch_t = time.monotonic()
            if self._t0 is not None:
                # Time-to-first-dispatch: how long the device sat idle
                # before the source (encode, or device synthesis)
                # produced its first shippable chunk.
                self.stats["t_first_dispatch_s"] = round(
                    self._first_dispatch_t - self._t0, 4)
        self._inc("chunks", len(members))
        for _, lo, hi, Bp in members:
            self._inc("pad_rows", Bp - (hi - lo))
        return (members, outs, delay)

    # ------------------------------------------------ watchdog + ladder
    def _deadline(self, batch: EncodedBatch, rows: int) -> float:
        """Per-chunk decode deadline from the VPU op model: estimated
        lane-ops at a pessimistic sustained rate, a wide safety factor,
        a hard floor, and a one-time compile grace for shapes this
        scheduler has not awaited before. An active fault plan
        overrides it (the nemesis runs on test-scale timings)."""
        if self.faults is not None and self.faults.deadline_s is not None:
            return self.faults.deadline_s
        m = vpu_op_model(batch.V, batch.W, batch.eff_w_live)
        est = rows * batch.n_events * (
            m["per_event"] + (m["w_live"] + 1) * m["per_iteration"])
        d = max(WATCHDOG_MIN_S,
                est / WATCHDOG_LANE_OPS_PER_S * WATCHDOG_FACTOR)
        shape = (batch.V, batch.W, batch.eff_w_live, batch.n_events)
        if shape not in self._awaited_shapes:
            self._awaited_shapes.add(shape)
            d += WATCHDOG_COMPILE_GRACE_S
        return d

    def _decode_member(self, out, nb: int, batch: EncodedBatch):
        """Decode one dispatch's outputs (runs ON the retire thread —
        the single copy both the per-chunk and fused-group awaits
        share): fire the decode-stage fault, slice off pad rows, apply
        a corrupt fault, validate (corrupt output becomes a retryable
        fault, never a wrong verdict), and shape the frontier per
        return_frontier. Each read-back blocks on the device and has
        its own ``decode.fetch`` span, so the self time of ``decode`` is
        host work only."""
        with telemetry.span("decode", V=batch.V, W=batch.W, rows=nb):
            kind = None
            if self.faults is not None:
                kind = self.faults.fire("decode")
                s = self.faults.sleep_for(kind)
                if s:
                    time.sleep(s)
            valid, bad, front = out
            with telemetry.span("decode.fetch", rows=nb):
                v = np.asarray(valid)[:nb]
                b = np.asarray(bad)[:nb]
            if kind == "corrupt":
                v, b = corrupt_arrays(v, b)
            validate_decoded(v, b, batch.n_events)
            fr = None
            if self.return_frontier is True:
                with telemetry.span("decode.fetch", rows=nb):
                    fr = np.asarray(front)[:nb]
            elif self.return_frontier == "invalid":
                fr = {}
                rows = np.nonzero(~v)[0]
                if rows.size:
                    with telemetry.span("decode.fetch", rows=rows.size):
                        sel = np.asarray(front[rows])   # device gather
                    for i, r in enumerate(rows):
                        fr[int(r)] = sel[i]
            return v, b, fr

    def _await(self, out, nb: int, batch: EncodedBatch,
               deadline: float, delay: float = 0.0):
        """Materialize one dispatch's outputs on a daemon thread under
        the watchdog deadline; decode-stage faults fire on that thread
        (so the watchdog sees them), decoded verdicts are validated
        (corrupt output becomes a retryable fault, never a wrong
        verdict). A blown deadline abandons the worker — daemon, per
        the DaemonFuture threat model — and raises WatchdogExpired."""
        import queue
        q: "queue.Queue" = queue.Queue(1)

        def work():
            try:
                if delay:
                    time.sleep(delay)
                q.put((self._decode_member(out, nb, batch), None))
            except BaseException as e:   # noqa: BLE001 — relayed below
                q.put((None, e))

        threading.Thread(target=work, name="jepsen-retire",
                         daemon=True).start()
        try:
            r, err = q.get(timeout=deadline)
        except queue.Empty:
            self._inc("watchdog_fired")
            telemetry.event("scheduler.watchdog", V=batch.V,
                            W=batch.W, rows=nb)
            raise WatchdogExpired(
                f"chunk (V={batch.V}, W={batch.W}, rows={nb}) exceeded "
                f"its {deadline:.2f}s decode deadline") from None
        if err is not None:
            raise err
        return r

    def _exec_once(self, batch: EncodedBatch, lo: int, hi: int, Bp: int):
        """One synchronous guarded pass over rows [lo, hi): dispatch in
        <= Bp-row sub-ranges, each awaited under the watchdog."""
        Np = _round_up(batch.n_events, EVENT_QUANTUM)
        pieces = []
        for s in range(lo, hi, Bp):
            e = min(s + Bp, hi)
            out, delay = self._ship(batch, s, e, Bp, Np, "data1retry")
            pieces.append(
                (self._await(out, e - s, batch,
                             self._deadline(batch, Bp), delay), e - s))
        return _concat_pieces(pieces, self.return_frontier)

    def _exec_retry(self, batch: EncodedBatch, lo: int, hi: int, Bp: int):
        """Bounded retry with exponential backoff around _exec_once.
        OOM escapes immediately (it is deterministic under a fixed
        shape — halving Bp is the cure, not patience); unclassified
        errors propagate untouched."""
        last: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                self._inc("retries")
                telemetry.event("scheduler.retry", V=batch.V,
                                W=batch.W, attempt=attempt)
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                return self._exec_once(batch, lo, hi, Bp)
            except Exception as e:
                c = classify_failure(e)
                if c is None or c == "oom":
                    raise
                if isinstance(e, CorruptOutput):
                    self._inc("corrupt_chunks")
                last = e
        raise _ChunkFailed(last)

    def _exec_event_chunked(self, batch: EncodedBatch, lo: int, hi: int):
        """Post-bisection-floor fallback: the event-chunked resume
        kernel bounds peak memory by the event axis instead — the last
        on-device rung before poison-row quarantine."""
        sub = _slice_rows(batch, lo, hi)
        v, b, fr = run_event_chunked(sub, EVENT_CHUNK,
                                     return_frontier=bool(
                                         self.return_frontier))
        validate_decoded(v, b, batch.n_events)
        if self.return_frontier == "invalid":
            fr = {int(r): fr[r] for r in np.nonzero(~v)[0]}
        elif not self.return_frontier:
            fr = None
        return v, b, fr

    def _placeholder(self, batch: EncodedBatch, n: int):
        """Inert verdicts for quarantined rows — shaped like a clean
        chunk so downstream concatenation works, and overwritten by the
        caller's host engine (the quarantine contract)."""
        v = np.ones(n, bool)
        b = np.full(n, INT32_MAX, np.int32)
        if self.return_frontier is True:
            fr = np.zeros((n, n_state_words(batch.V), 1 << batch.W),
                          np.uint32)
        elif self.return_frontier == "invalid":
            fr = {}
        else:
            fr = None
        return v, b, fr

    def _quarantine(self, batch: EncodedBatch, row: int,
                    cause: BaseException):
        i = batch.indices[row]
        reason = f"{type(cause).__name__}: {cause}"
        self.quarantined[i] = reason
        self.row_provenance[i] = "host-fallback"
        self._inc("quarantined_rows")
        telemetry.event("scheduler.quarantine", row=int(i),
                        reason=reason)
        log.warning("quarantining history %s after exhausting the "
                    "device ladder (%s); the host engine decides it", i,
                    reason)
        return self._placeholder(batch, 1)

    def _hunt_poison(self, batch: EncodedBatch, lo: int, hi: int,
                     Bp: int):
        """Binary-search a persistently failing range down to the
        poison row(s). Each level gets ONE attempt (the range already
        exhausted its retries); rows still failing alone are
        quarantined for the caller's host engine."""
        if hi - lo == 1:
            try:
                return self._exec_once(batch, lo, hi, min(Bp, ROW_QUANTUM))
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                return self._quarantine(batch, lo, e)
        mid = (lo + hi) // 2
        pieces = []
        for a, c in ((lo, mid), (mid, hi)):
            try:
                piece = self._exec_once(batch, a, c, Bp)
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                piece = self._hunt_poison(batch, a, c, Bp)
            pieces.append((piece, c - a))
        return _concat_pieces(pieces, self.return_frontier)

    def _exec_range(self, batch: EncodedBatch, lo: int, hi: int,
                    Bp: int, first_cause: Optional[BaseException] = None):
        """The degradation ladder for rows [lo, hi): retry → OOM
        Bp-bisection (the learned safe size sticks for the rest of the
        run) → event-chunked dispatch → poison-row hunt. Always returns
        a full (valid, bad, frontier) for the range; rows it could not
        decide are quarantined placeholders."""
        cls = (batch.V, batch.W)
        cap = self._safe_bp.get(cls)
        if cap:
            Bp = min(Bp, cap)
        oom = first_cause is not None and \
            classify_failure(first_cause) == "oom"
        while True:
            if not oom:
                try:
                    return self._exec_retry(batch, lo, hi, Bp)
                except _ChunkFailed:
                    return self._hunt_poison(batch, lo, hi, Bp)
                except Exception as e:
                    if classify_failure(e) != "oom":
                        raise
                    self._inc("oom_events")
                    oom = True
                    continue
            if Bp > BISECT_FLOOR_ROWS:
                # RESOURCE_EXHAUSTED: halve the rows per dispatch and
                # remember the safe size for this W class — later
                # chunks of the run start from it instead of
                # rediscovering the wall.
                Bp = max(BISECT_FLOOR_ROWS, Bp // 2)
                self._inc("bisections")
                telemetry.event("scheduler.bisection", V=batch.V,
                                W=batch.W, rows_per_dispatch=Bp)
                self._safe_bp[cls] = Bp
                log.warning("OOM on chunk (V=%s, W=%s): bisecting to "
                            "%s rows/dispatch", batch.V, batch.W, Bp)
                oom = False
                continue
            try:
                return self._exec_event_chunked(batch, lo, hi)
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                return self._hunt_poison(batch, lo, hi, Bp)

    def _recover(self, batch: EncodedBatch, lo: int, hi: int, Bp: int,
                 cause: BaseException):
        """Entry to the ladder from a failed pipelined chunk; tags the
        surviving rows device-retried (quarantined rows were already
        tagged host-fallback)."""
        c = classify_failure(cause)
        if c == "oom":
            self._inc("oom_events")
        if isinstance(cause, CorruptOutput):
            self._inc("corrupt_chunks")
        telemetry.event("scheduler.retry", V=batch.V, W=batch.W,
                        rows=hi - lo,
                        cause=type(cause).__name__)
        log.warning("chunk (V=%s, W=%s, rows %s:%s) failed in the "
                    "pipeline (%s: %s); entering the degradation "
                    "ladder", batch.V, batch.W, lo, hi,
                    type(cause).__name__, cause)
        # The ladder's first synchronous pass re-dispatches work the
        # pipeline already shipped once: that IS a retry, whatever
        # happens after.
        self._inc("retries")
        out = self._exec_range(batch, lo, hi, Bp, first_cause=cause)
        for r in range(lo, hi):
            self.row_provenance.setdefault(batch.indices[r],
                                           "device-retried")
        return out

    def _await_group(self, members: List[Tuple], outs, delay: float):
        """Materialize every member of one fused dispatch on a daemon
        thread under ONE group deadline (the sum of the members'
        per-chunk deadlines — the group is one device program, so the
        watchdog must budget for all of it). Decode-stage faults fire
        once per MEMBER (chunk ordinals, fusion-invariant — the
        fault-schedule parity tests pin them); a corrupt fault
        corrupts its member, and any member failing validation fails
        the whole group (the ladder then re-decides each member
        individually). Returns [(valid, bad, frontier)] per member."""
        import queue
        if self.faults is not None and self.faults.deadline_s is not None:
            deadline = self.faults.deadline_s
        else:
            deadline = sum(self._deadline(run.batch, hi - lo)
                           for run, lo, hi, _ in members)
        q: "queue.Queue" = queue.Queue(1)

        def work():
            try:
                if delay:
                    time.sleep(delay)
                # Decode-stage faults fire once per MEMBER inside
                # _decode_member (chunk ordinals, fusion-invariant).
                q.put(([self._decode_member(out, hi - lo, run.batch)
                        for (run, lo, hi, _), out
                        in zip(members, outs)], None))
            except BaseException as e:  # noqa: BLE001 — relayed below
                q.put((None, e))

        threading.Thread(target=work, name="jepsen-retire",
                         daemon=True).start()
        try:
            r, err = q.get(timeout=deadline)
        except queue.Empty:
            self._inc("watchdog_fired")
            telemetry.event("scheduler.watchdog",
                            members=len(members))
            rows = sum(hi - lo for _, lo, hi, _ in members)
            raise WatchdogExpired(
                f"fused group ({len(members)} chunks, {rows} rows) "
                f"exceeded its {deadline:.2f}s decode deadline") \
                from None
        if err is not None:
            raise err
        return r

    def _retire(self, item) -> None:
        members, outs, delay = item
        if isinstance(outs, BaseException):
            # The dispatch itself already failed: there is no device
            # work to wait on, so no device-category span — a phantom
            # zero-length interval here would pollute the gap
            # analyzer's device-busy union under fault injection.
            results, cause = None, outs
        else:
            # One wait covers the whole group; a group that mixed
            # backends gets the honest "mixed" label rather than
            # silently crediting all its wait to the scan family
            # (device_busy_by_family is the table doc/observability.md
            # tells readers to trust).
            fams = {"wgl-pallas" if self._pallas_for(run.batch)
                    else "wgl" for run, _, _, _ in members}
            wait_sp = telemetry.span(
                "device.wait", cat="device",
                family=fams.pop() if len(fams) == 1 else "mixed",
                members=len(members),
                rows=sum(hi - lo for _, lo, hi, _ in members))
            try:
                if len(members) == 1:
                    run, lo, hi, Bp = members[0]
                    results = [self._await(
                        outs[0], hi - lo, run.batch,
                        self._deadline(run.batch, hi - lo), delay)]
                else:
                    results = self._await_group(members, outs, delay)
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                results, cause = None, e
            finally:
                wait_sp.end()
        if results is None:
            # The group failed as a unit: every member walks the
            # degradation ladder individually — the resilience spine is
            # per-chunk, unchanged by fusion.
            results = [self._recover(run.batch, lo, hi, Bp, cause)
                       for run, lo, hi, Bp in members]
        if self.stats["t_first_verdict_s"] is None:
            self.stats["t_first_verdict_s"] = round(
                time.monotonic() - self._t0, 4)
        for (run, lo, hi, _), (v, b, fr) in zip(members, results):
            if self.on_chunk is not None:
                self.on_chunk(run.batch, lo, hi, v, b, fr)
            run.collect(v, b, fr)

    def _run_event_routed(self, mb: EncodedBatch):
        """Cost-routed long-history dispatch: the whole bucket runs
        through the event-chunked resume kernel (carried frontier,
        EVENT_CHUNK-step dispatches, uploads double-buffered under the
        scan). One attempt — any classified failure returns None and
        the bucket falls through to the standard chunked pipeline,
        whose full degradation ladder is the retry."""
        n_disp = -(-mb.n_events // EVENT_CHUNK)
        try:
            with telemetry.span("dispatch", cat="device", family="wgl",
                                route="event-chunked", V=mb.V, W=mb.W,
                                rows=mb.batch, events=mb.n_events):
                out = self._exec_event_chunked(mb, 0, mb.batch)
        except Exception as e:
            if classify_failure(e) is None:
                raise
            log.warning("event-chunked route failed for bucket "
                        "(V=%s, W=%s, %s rows, %s events): %s; "
                        "falling back to the standard chunk pipeline",
                        mb.V, mb.W, mb.batch, mb.n_events, e)
            return None
        self._inc("dispatches", n_disp)
        self._inc("event_routed_dispatches", n_disp)
        self._inc("event_routed_rows", mb.batch)
        return out

    def _run_wide(self, mb: EncodedBatch):
        """Blocking wide/frontier/sharded dispatch with bounded retry.
        Persistent failure returns ChunkAbandoned — a WindowOverflow
        subclass, so callers' existing host-engine routing re-decides
        every row (tagged host-fallback)."""
        last: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                self._inc("retries")
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                # One XLA call per attempt — the wide/frontier routes
                # count toward dispatch economics like any other ship.
                self._inc("dispatches")
                route = dispatch_route(mb)
                with telemetry.span("dispatch", cat="device",
                                    family="wgl", route=route[0],
                                    V=mb.V, W=mb.W, rows=mb.batch):
                    out = run_encoded_batch(mb, self.return_frontier,
                                            route=route)
                for i in mb.indices:
                    self.row_route[i] = route[0]
                    if attempt:
                        self.row_provenance.setdefault(i, "device-retried")
                return out
            except WindowOverflow as e:
                return e
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                last = e
        self._inc("abandoned_buckets")
        for i in mb.indices:
            self.row_provenance[i] = "host-fallback"
        log.warning("wide bucket (V=%s, W=%s, %s rows) abandoned after "
                    "%s attempts (%s); routing its rows to the host "
                    "engine", mb.V, mb.W, mb.batch, self.max_retries + 1,
                    last)
        return ChunkAbandoned(
            f"device failure persisted across {self.max_retries + 1} "
            f"attempts: {last}")

    # ---------------------------------------------------------- class plan
    def _freeze_classes(self, group: Sequence[EncodedBatch]) -> Dict:
        if not self.consolidate:
            return {(b.V, b.W): b.W for b in group}
        stats: Dict[Tuple[int, int], float] = {}
        rows: Dict[Tuple[int, int], int] = {}
        for b in group:
            if b.batch:
                stats[(b.V, b.W)] = (stats.get((b.V, b.W), 0.0)
                                     + b.batch * b.n_events)
                rows[(b.V, b.W)] = rows.get((b.V, b.W), 0) + b.batch
        self.stats["dispatch_overhead_us"] = round(
            measure_dispatch_overhead_us(), 2)
        import jax
        plan = dict(rows=rows, devices=len(jax.devices()))
        if self.wgl_backend in ("dc", "pallas"):
            # A pinned narrow-window backend keeps its rows: the wide
            # windows are planned apart, so no narrow row rides them.
            wide = {k: b for k, b in stats.items() if k[1] > DATA_MAX_SLOTS}
            narrow = {k: b for k, b in stats.items() if k not in wide}
            return {**choose_w_classes(narrow, max_classes=self.max_classes),
                    **choose_w_classes(wide, max_classes=self.max_classes,
                                       **plan)}
        return choose_w_classes(stats, max_classes=self.max_classes, **plan)

    def _class_of(self, class_map: Dict, V: int, W: int) -> int:
        cw = class_map.get((V, W))
        if cw is None:
            if not self.consolidate or W > DATA_MAX_SLOTS:
                # Exact class: consolidate=False promises exact-W for
                # EVERY window, including ones first seen in later
                # groups; and a wide window first seen in a later
                # group stays exact — on the wide/frontier route cost
                # is 2^W per row, so riding a wider compiled class
                # would multiply the dominant frontier traffic, not
                # save a compile.
                cw = W
            else:
                # A later streaming group surfaced a narrow window the
                # first group never saw: ride the next-wider frozen
                # narrow class (free — the kernel is already compiled),
                # or freeze a new exact class.
                ups = [c for (v, w), c in class_map.items()
                       if v == V and W <= c <= DATA_MAX_SLOTS]
                cw = min(ups) if ups else W
            class_map[(V, W)] = cw
        return cw

    # -------------------------------------------------------------- driver
    def run(self, source):
        """Yield (batch, out) per consolidated bucket — see the module
        docstring for the full contract."""
        return self._drive(source)

    def _drive(self, source):
        run_sp = telemetry.begin("scheduler.run")
        try:
            yield from self._drive_inner(source)
        finally:
            run_sp.set(chunks=self.stats["chunks"],
                       dispatches=self.stats["dispatches"],
                       rows=self.stats["rows"]).end()

    def _drive_inner(self, source):
        self._t0 = time.monotonic()
        shapes0 = len(KERNEL_SHAPE_LOG)
        groups = ([list(source)]
                  if isinstance(source, (list, tuple)) else source)
        class_map: Optional[Dict] = None
        acc: Dict[Tuple[int, int], List[EncodedBatch]] = {}
        inflight: deque = deque()
        order: deque = deque()      # _Run FIFO awaiting completion
        warmed = set()

        def yield_done():
            while order and order[0].done:
                yield order.popleft().result(self.return_frontier)

        def retire_ready():
            # Keep at most `depth` dispatch groups in flight, then
            # yield any bucket whose last chunk has decoded.
            while len(inflight) >= self.depth:
                self._retire(inflight.popleft())
            yield from yield_done()

        def flush():
            # Ship the accumulated chunk group as ONE fused XLA call.
            if self._fuse_buf:
                group, self._fuse_buf = self._fuse_buf, []
                yield from retire_ready()
                inflight.append(self._dispatch_group(group))

        def drain():
            yield from flush()
            while inflight:
                self._retire(inflight.popleft())
            yield from yield_done()

        def feed(mb: EncodedBatch):
            self._inc("rows", mb.batch)
            mesh = production_mesh(1)
            wide = mb.W > DATA_MAX_SLOTS
            if (mb.W >= DATA_MAX_SLOTS
                    and 0 < mb.batch < self.min_device_rows):
                yield mb, DIVERTED
                return
            ev = int((mb.ev_type != 0).sum())        # != EV_PAD
            self._inc("events", ev)
            self._inc("orig_events",
                      int(mb.orig_n_events.sum())
                      if mb.orig_n_events is not None else ev)
            if self.shard_min_rows is None:
                # The mesh-level per-device floor ($JT_SHARD_MIN_ROWS,
                # default MIN_ROWS_PER_DEVICE): sub-minimum sharding
                # regresses (MULTICHIP_r06's dataN tail), so thin
                # merged buckets stay on the fused chunked pipeline.
                from ..parallel.mesh import should_shard
                shard = should_shard(mb.batch, mesh)
            else:
                shard = (mesh is not None
                         and mb.batch >= self.shard_min_rows)
            if wide or shard:
                # Wide/frontier/sharded routes keep their own dispatch
                # logic (run_encoded_batch): drain the pipeline so
                # yields stay in dispatch order, then run blocking
                # (with the same bounded-retry discipline — a
                # persistently failing wide bucket is abandoned to the
                # caller's host engine, never an aborted check).
                yield from drain()
                out = self._run_wide(mb)
                if not isinstance(out, WindowOverflow):
                    if self.stats["t_first_verdict_s"] is None:
                        self.stats["t_first_verdict_s"] = round(
                            time.monotonic() - self._t0, 4)
                    if self.on_chunk is not None:
                        v, b, fr = out
                        self.on_chunk(mb, 0, mb.batch, v, b, fr)
                yield mb, out
                return
            if (self.event_route_events
                    and mb.n_events >= self.event_route_events):
                # Long-history cost route (the r05 10k-op probe's
                # regime): carried event chunks instead of one
                # N-step monolithic scan. Blocking like the wide
                # route, so yields stay in dispatch order.
                yield from drain()
                out = self._run_event_routed(mb)
                if out is not None:
                    if self.stats["t_first_verdict_s"] is None:
                        self.stats["t_first_verdict_s"] = round(
                            time.monotonic() - self._t0, 4)
                    if self.on_chunk is not None:
                        v, b, fr = out
                        self.on_chunk(mb, 0, mb.batch, v, b, fr)
                    yield mb, out
                    return
            Bp, chunks = self._chunk_plan(mb)
            if self.prewarm and mb.W <= DATA_MAX_SLOTS:
                if self._pallas_for(mb):
                    spec = ("pallas", mb.V, mb.W, mb.eff_w_live,
                            mb.shared_target, False, Bp,
                            _round_up(mb.n_events, EVENT_QUANTUM),
                            mb.ev_slots.dtype, mb.target.shape[1])
                else:
                    spec = (mb.V, mb.W, mb.eff_w_live,
                            mb.shared_target, self.donate, Bp,
                            _round_up(mb.n_events, EVENT_QUANTUM),
                            mb.ev_slots.dtype, mb.target.shape[1])
                skey = _spec_key(spec)
                if skey not in warmed:
                    warmed.add(skey)
                    prewarm_kernels([spec])
            st = _Run(mb, len(chunks))
            order.append(st)
            for lo, hi in chunks:
                # Adaptive group commit: while the pipeline has
                # capacity a chunk ships immediately (keeps the device
                # busy and time-to-first-verdict low); under
                # backpressure chunks accumulate and ship as ONE fused
                # XLA call of up to fuse_width members (flush) — the
                # many-small-buckets shape stops paying one dispatch
                # each exactly when dispatch is the bottleneck.
                # fuse_width=1 degenerates to the per-chunk flow.
                self._fuse_buf.append((st, lo, hi, Bp))
                # JT_SCHED_MAX_QUEUE: the hand-off is full while the
                # pipeline is saturated — a stalled device now WEDGES
                # here (flush → retire_ready blocks on the stalled
                # group; the watchdog owns a true wedge) behind a
                # counted event, instead of buffering encoded chunks
                # without bound.
                full = (self.max_queue
                        and len(self._fuse_buf) >= self.max_queue
                        and len(inflight) >= self.depth)
                if full:
                    self._inc("backpressure_events")
                    telemetry.event("scheduler.backpressure",
                                    queued=len(self._fuse_buf))
                if (len(inflight) < self.depth
                        or len(self._fuse_buf) >= self.fuse_width
                        or full):
                    yield from flush()

        for group in groups:
            group = [b for b in group if b.batch]
            self._inc("input_buckets", len(group))
            with telemetry.span("scheduler.plan", buckets=len(group)):
                if class_map is None and group:
                    # Freeze on the first NON-empty group: an
                    # all-failures prefix must not freeze an empty plan
                    # and silently disable consolidation for the whole
                    # run.
                    class_map = self._freeze_classes(group)
                fresh: Dict[Tuple[int, int], List[EncodedBatch]] = {}
                for b in group:
                    key = (b.V, self._class_of(class_map, b.V, b.W))
                    fresh.setdefault(key, []).append(b)
            for (V, cw), bs in sorted(fresh.items()):
                pend = acc.setdefault((V, cw), [])
                pend.extend(bs)
                rows = sum(b.batch for b in pend)
                chunk = self._class_chunk(V, cw)
                if rows >= chunk:
                    with telemetry.span("scheduler.plan", rows=rows):
                        mb = merge_batches(pend, cw)
                        full = (rows // chunk) * chunk
                        acc[(V, cw)] = ([_slice_rows(mb, full, rows)]
                                        if full < rows else [])
                    yield from feed(_slice_rows(mb, 0, full))
        # Final flush of sub-chunk accumulations.
        for (V, cw), pend in sorted(acc.items()):
            if pend:
                with telemetry.span("scheduler.plan"):
                    mb = merge_batches(pend, cw)
                yield from feed(mb)
        yield from drain()
        assert not order, "every dispatched bucket must have retired"

        wall = time.monotonic() - self._t0
        self.stats["wall_s"] = round(wall, 4)
        self.stats["compiled_shapes"] = len(KERNEL_SHAPE_LOG) - shapes0
        if self.faults is not None:
            self.stats["faults_injected"] = len(self.faults.log)
        if self.stats["events"]:
            # Scan steps saved by event fusion: original (unfused)
            # events per dispatched scan step, >= 1.0.
            self.stats["fusion_ratio"] = round(
                self.stats["orig_events"] / self.stats["events"], 4)
        if class_map:
            seen = {}
            for (v, w), c in class_map.items():
                seen.setdefault((v, c), []).append(w)
            self.stats["classes"] = [
                {"V": v, "W": c, "folds": sorted(ws)}
                for (v, c), ws in sorted(seen.items())]


def _concat_pieces(pieces, return_frontier):
    """Stitch sub-range (valid, bad, frontier) pieces — each paired
    with its row count — back into one range result, preserving the
    frontier mode's shape ("invalid" dicts re-key by range offset)."""
    vs = [p[0] for p, _ in pieces]
    bs = [p[1] for p, _ in pieces]
    valid = np.concatenate(vs) if len(vs) > 1 else vs[0]
    bad = np.concatenate(bs) if len(bs) > 1 else bs[0]
    if return_frontier is True:
        frs = [p[2] for p, _ in pieces]
        fr = np.concatenate(frs) if len(frs) > 1 else frs[0]
    elif return_frontier == "invalid":
        fr = {}
        off = 0
        for (_, _, fm), n in pieces:
            for r, row in fm.items():
                fr[off + int(r)] = row
            off += n
    else:
        fr = None
    return valid, bad, fr


def _slice_rows(b: EncodedBatch, lo: int, hi: int) -> EncodedBatch:
    if lo == 0 and hi == b.batch:
        return b
    return EncodedBatch(
        ev_type=b.ev_type[lo:hi], ev_slot=b.ev_slot[lo:hi],
        ev_slots=b.ev_slots[lo:hi], ev_opidx=b.ev_opidx[lo:hi],
        target=b.target if b.shared_target else b.target[lo:hi],
        V=b.V, W=b.W, indices=list(b.indices[lo:hi]),
        failures=list(b.failures) if lo == 0 else [],
        spaces=(b.spaces[lo:hi] if b.spaces else b.spaces),
        shared_target=b.shared_target, w_live=b.w_live,
        orig_n_events=(b.orig_n_events[lo:hi]
                       if b.orig_n_events is not None else None))


# ----------------------------------------- dependency-graph scheduler

# Rows per graph-kernel dispatch (the graph analog of
# DEFAULT_CHUNK_ROWS; graphs are dense [L, V, V] closures, so memory
# per row is L x V^2 floats — far below the WGL frontier).
GRAPH_CHUNK_ROWS = int(os.environ.get("JT_GRAPH_CHUNK_ROWS", "2048"))

# Assumed worst-case sustained MXU throughput (MACs/s) for the graph
# watchdog deadline — pessimistic for the same reason as
# WATCHDOG_LANE_OPS_PER_S: the watchdog catches wedges, not slowness.
WATCHDOG_MXU_MACS_PER_S = float(
    os.environ.get("JT_WATCHDOG_MXU_MACS_PER_S", "1e11"))


def _concat_graph_pieces(pieces):
    if len(pieces) == 1:
        return pieces[0]
    return (np.concatenate([p[0] for p in pieces]),
            np.concatenate([p[1] for p in pieces]))


class GraphScheduler:
    """Vertex-count bucket scheduler for the dependency-graph cycle
    kernels (ops.graph) — the MXU twin of BucketScheduler, sharing its
    fault model end to end: every chunk dispatches through the same
    FaultInjector stage hooks (encode/dispatch/decode), decodes under a
    watchdog deadline priced by the MXU op model, and degrades through
    the same ladder — bounded retry with backoff, RESOURCE_EXHAUSTED
    row bisection (the learned safe size sticks per vertex bucket),
    poison-row binary search with quarantine to the caller's host DFS
    oracle. Dispatch is synchronous per chunk (a graph chunk is one
    matmul-chain dispatch; jax's async dispatch already overlaps the
    host pad of the next chunk), results stream per bucket.

    Contract mirrors BucketScheduler: ``run(buckets)`` yields
    ``(bucket, (cyc, node))`` with cyc bool [B, L] / node int32 [B, L];
    quarantined rows surface in ``quarantined`` carrying inert
    placeholders (callers MUST re-decide them on the host oracle), and
    every off-happy-path row is tagged in ``row_provenance``
    ("device-retried" / "host-fallback"). ``on_chunk(bucket, lo, hi,
    cyc, node)`` fires per decided chunk — the store.ChunkJournal hook.
    Stats count DISPATCHED work (retries included), so
    closure_matmuls/mxu_macs price what the device actually ran.
    """

    def __init__(self, *, chunk_rows: Optional[int] = None,
                 faults: Optional[FaultInjector] = None,
                 max_retries: Optional[int] = None,
                 backoff_s: Optional[float] = None,
                 on_chunk=None,
                 compilation_cache: bool = True,
                 resident: Optional[ResidentState] = None,
                 family: str = "graph",
                 kernel=None, levels: Optional[int] = None,
                 op_model=None):
        # family/kernel/levels/op_model parameterize which closure
        # family this scheduler drives (default: the ops.graph anomaly
        # planes; the txn isolation ladder passes its own). The fault
        # ladder, journaling hooks and stats contract are identical.
        from .graph import N_LEVELS, graph_kernel, mxu_op_model
        self.family = family
        self.kernel = graph_kernel if kernel is None else kernel
        self.levels = N_LEVELS if levels is None else int(levels)
        self.op_model = mxu_op_model if op_model is None else op_model
        self.chunk_rows = (GRAPH_CHUNK_ROWS if chunk_rows is None
                           else max(1, int(chunk_rows)))
        if compilation_cache:
            enable_compilation_cache()
        self.faults = faults if faults is not None \
            else FaultInjector.from_env()
        self.max_retries = RETRY_MAX if max_retries is None \
            else max(0, int(max_retries))
        if backoff_s is None:
            backoff_s = (self.faults.backoff_s
                         if self.faults is not None else None)
        self.backoff_s = RETRY_BACKOFF_S if backoff_s is None \
            else float(backoff_s)
        self.on_chunk = on_chunk
        self.quarantined: Dict[int, str] = {}
        self.row_provenance: Dict[int, str] = {}
        self._safe_bp: Dict[int, int] = {}
        self._awaited_shapes: set = set()
        if resident is not None:
            # Graph buckets key safe_bp by bare V (the WGL side keys
            # by (V, W) tuples), so one ResidentState serves both
            # families without collisions.
            resident.adopt(self)
        self._stats_lock = threading.Lock()
        self._mirrors: dict = {}       # key -> registry counter handle
        self.stats: dict = {
            "graphs": 0, "buckets": 0, "chunks": 0,
            "closure_matmuls": 0, "mxu_macs": 0.0, "wall_s": None,
            "retries": 0, "bisections": 0, "watchdog_fired": 0,
            "oom_events": 0, "corrupt_chunks": 0, "quarantined_rows": 0,
            "faults_injected": 0,
        }

    def _inc(self, key: str, n=1) -> None:
        _stat_inc(self, self.family, key, n)

    # ------------------------------------------------------------ plumbing
    def _deadline(self, b, rows: int) -> float:
        if self.faults is not None and self.faults.deadline_s is not None:
            return self.faults.deadline_s
        est = rows * self.op_model(b.V)["macs"]
        d = max(WATCHDOG_MIN_S,
                est / WATCHDOG_MXU_MACS_PER_S * WATCHDOG_FACTOR)
        if b.V not in self._awaited_shapes:
            self._awaited_shapes.add(b.V)
            d += WATCHDOG_COMPILE_GRACE_S
        return d

    def _ship(self, b, lo: int, hi: int, Bp: int):
        """The ONE dispatch sequence for both the happy path and every
        ladder re-dispatch: fault hooks, zero-pad to Bp rows (padding
        graphs are edgeless, never cyclic), async kernel launch."""
        nb = hi - lo
        with telemetry.span("encode", family=self.family, V=b.V,
                            rows=nb):
            if self.faults is not None:
                self.faults.fire("encode")
            adj = np.zeros((Bp,) + b.adj.shape[1:], np.uint32)
            adj[:nb] = b.adj[lo:hi]
        delay = 0.0
        if self.faults is not None:
            delay = self.faults.sleep_for(self.faults.fire("dispatch"))
        with telemetry.span("dispatch", cat="device",
                            family=self.family, V=b.V, rows=nb):
            out = self.kernel(b.V)(adj)
        m = self.op_model(b.V)
        self._inc("chunks")
        self._inc("closure_matmuls", Bp * int(m["matmuls"]))
        self._inc("mxu_macs", Bp * m["macs"])
        return out, delay

    def _await(self, out, nb: int, b, deadline: float,
               delay: float = 0.0):
        """Materialize one dispatch on a daemon thread under the
        watchdog deadline; decode faults fire on that thread, decoded
        verdicts are shape-validated (corrupt output is a retryable
        fault, never a wrong verdict)."""
        from .graph import validate_graph_decoded
        import queue
        q: "queue.Queue" = queue.Queue(1)

        def work():
            try:
                if delay:
                    time.sleep(delay)
                with telemetry.span("decode", family=self.family,
                                    V=b.V, rows=nb):
                    kind = None
                    if self.faults is not None:
                        kind = self.faults.fire("decode")
                        s = self.faults.sleep_for(kind)
                        if s:
                            time.sleep(s)
                    cyc, node = out
                    with telemetry.span("decode.fetch", rows=nb):
                        c = np.asarray(cyc)[:nb]
                        nd = np.asarray(node)[:nb]
                    if kind == "corrupt":
                        c, nd = corrupt_arrays(c, nd)
                    validate_graph_decoded(c, nd, b.V)
                q.put(((c, nd), None))
            except BaseException as e:   # noqa: BLE001 — relayed below
                q.put((None, e))

        threading.Thread(target=work, name="jepsen-graph-retire",
                         daemon=True).start()
        try:
            r, err = q.get(timeout=deadline)
        except queue.Empty:
            self._inc("watchdog_fired")
            telemetry.event("scheduler.watchdog", family=self.family,
                            V=b.V, rows=nb)
            raise WatchdogExpired(
                f"{self.family} chunk (V={b.V}, rows={nb}) exceeded its "
                f"{deadline:.2f}s decode deadline") from None
        if err is not None:
            raise err
        return r

    # ------------------------------------------------ watchdog + ladder
    def _exec_once(self, b, lo: int, hi: int, Bp: int):
        pieces = []
        for s in range(lo, hi, Bp):
            e = min(s + Bp, hi)
            out, delay = self._ship(b, s, e, Bp)
            pieces.append(self._await(out, e - s, b,
                                      self._deadline(b, Bp), delay))
        return _concat_graph_pieces(pieces)

    def _exec_retry(self, b, lo: int, hi: int, Bp: int):
        last: Optional[BaseException] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                self._inc("retries")
                telemetry.event("scheduler.retry", family=self.family,
                                V=b.V, attempt=attempt)
                time.sleep(self.backoff_s * (2 ** (attempt - 1)))
            try:
                return self._exec_once(b, lo, hi, Bp)
            except Exception as e:
                c = classify_failure(e)
                if c is None or c == "oom":
                    raise
                if isinstance(e, CorruptOutput):
                    self._inc("corrupt_chunks")
                last = e
        raise _ChunkFailed(last)

    def _placeholder(self, n: int):
        return (np.zeros((n, self.levels), bool),
                np.full((n, self.levels), INT32_MAX, np.int32))

    def _quarantine(self, b, row: int, cause: BaseException):
        i = b.indices[row]
        reason = f"{type(cause).__name__}: {cause}"
        self.quarantined[i] = reason
        self.row_provenance[i] = "host-fallback"
        self._inc("quarantined_rows")
        telemetry.event("scheduler.quarantine", family=self.family,
                        row=int(i), reason=reason)
        log.warning("quarantining graph %s after exhausting the device "
                    "ladder (%s); the host DFS oracle decides it", i,
                    reason)
        return self._placeholder(1)

    def _hunt_poison(self, b, lo: int, hi: int, Bp: int):
        if hi - lo == 1:
            try:
                return self._exec_once(b, lo, hi, min(Bp, 8))
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                return self._quarantine(b, lo, e)
        mid = (lo + hi) // 2
        pieces = []
        for a, c in ((lo, mid), (mid, hi)):
            try:
                piece = self._exec_once(b, a, c, Bp)
            except Exception as e:
                if classify_failure(e) is None:
                    raise
                piece = self._hunt_poison(b, a, c, Bp)
            pieces.append(piece)
        return _concat_graph_pieces(pieces)

    def _exec_range(self, b, lo: int, hi: int, Bp: int,
                    first_cause: Optional[BaseException] = None):
        """retry → OOM row-bisection (learned safe size sticks per
        vertex bucket) → poison-row hunt with quarantine. Always
        returns full (cyc, node) for the range."""
        cap = self._safe_bp.get(b.V)
        if cap:
            Bp = min(Bp, cap)
        oom = first_cause is not None and \
            classify_failure(first_cause) == "oom"
        while True:
            if not oom:
                try:
                    return self._exec_retry(b, lo, hi, Bp)
                except _ChunkFailed:
                    return self._hunt_poison(b, lo, hi, Bp)
                except Exception as e:
                    if classify_failure(e) != "oom":
                        raise
                    self._inc("oom_events")
                    oom = True
                    continue
            if Bp > 1:
                Bp = max(1, Bp // 2)
                self._inc("bisections")
                telemetry.event("scheduler.bisection",
                                family=self.family, V=b.V,
                                rows_per_dispatch=Bp)
                self._safe_bp[b.V] = Bp
                log.warning("OOM on graph chunk (V=%s): bisecting to %s "
                            "rows/dispatch", b.V, Bp)
                oom = False
                continue
            return self._hunt_poison(b, lo, hi, 1)

    def _recover(self, b, lo: int, hi: int, Bp: int,
                 cause: BaseException):
        c = classify_failure(cause)
        if c == "oom":
            self._inc("oom_events")
        if isinstance(cause, CorruptOutput):
            self._inc("corrupt_chunks")
        telemetry.event("scheduler.retry", family=self.family, V=b.V,
                        rows=hi - lo, cause=type(cause).__name__)
        log.warning("graph chunk (V=%s, rows %s:%s) failed (%s: %s); "
                    "entering the degradation ladder", b.V, lo, hi,
                    type(cause).__name__, cause)
        self._inc("retries")
        out = self._exec_range(b, lo, hi, Bp, first_cause=cause)
        for r in range(lo, hi):
            self.row_provenance.setdefault(b.indices[r],
                                           "device-retried")
        return out

    # -------------------------------------------------------------- driver
    def run(self, buckets):
        """Yield (bucket, (cyc, node)) per vertex bucket — see the
        class docstring for the contract."""
        t0 = time.monotonic()
        for b in buckets:
            if not b.batch:
                continue
            self._inc("buckets")
            self._inc("graphs", b.batch)
            pieces = []
            for lo in range(0, b.batch, self.chunk_rows):
                hi = min(lo + self.chunk_rows, b.batch)
                Bp = min(self.chunk_rows, max(8, _pow2_ceil(hi - lo)))
                # An earlier OOM bisection taught us this bucket's real
                # memory wall: later chunks dispatch under it instead
                # of re-OOMing at full size and re-entering the ladder
                # (which would halve the learned size once per chunk).
                cap = self._safe_bp.get(b.V)
                if cap:
                    Bp = min(Bp, cap)
                try:
                    cyc, node = self._exec_once(b, lo, hi, Bp)
                except Exception as e:
                    if classify_failure(e) is None:
                        raise
                    cyc, node = self._recover(b, lo, hi, Bp, e)
                if self.on_chunk is not None:
                    self.on_chunk(b, lo, hi, cyc, node)
                pieces.append((cyc, node))
            yield b, _concat_graph_pieces(pieces)
        self.stats["wall_s"] = round(time.monotonic() - t0, 4)
        if self.faults is not None:
            self.stats["faults_injected"] = len(self.faults.log)


def run_buckets_streamed(batches, return_frontier=False, **kw):
    """Drop-in pipelined successor to run_buckets_threaded: same
    (batch, out) yield contract, but the yielded buckets are the
    scheduler's consolidated W classes — scatter through batch.indices,
    never positional zips. Accepts every BucketScheduler knob."""
    sch = BucketScheduler(return_frontier=return_frontier, **kw)
    return sch.run(batches)


def iter_columnar_groups(space, cols, *, max_slots: int = 16,
                         encode_rows: Optional[int] = None,
                         failures: Optional[list] = None,
                         fuse: bool = False, renumber: bool = False):
    """Chunked columnar encode: yield bucket groups of ``encode_rows``
    rows each, with indices/failures remapped to the full batch — the
    streaming source for BucketScheduler.run, so the native/numpy slot
    walk of group k+1 runs while the device still chews group k.
    Overflow failures append to ``failures`` as (row, reason).
    ``fuse``/``renumber`` enable the encode-side shrink passes
    (ops.encode: event fusion + live-alphabet state renumbering) — the
    streamed production setting; the exact oracle leaves them off."""
    from .encode import encode_columnar
    rows = cols.batch
    encode_rows = encode_rows or int(
        os.environ.get("JT_SCHED_ENCODE_ROWS", "4096"))
    # One composed-kind registry across all groups: stable fused ids
    # with append-only table content, so the scheduler can merge
    # buckets from different groups under ONE shared target table.
    fuse_registry = {} if fuse else None
    for lo in range(0, rows, encode_rows):
        hi = min(lo + encode_rows, rows)
        sub = type(cols)(
            type=cols.type[lo:hi], process=cols.process[lo:hi],
            kind=cols.kind[lo:hi], kinds=cols.kinds,
            index=cols.index[lo:hi] if cols.index is not None else None)
        with telemetry.span("encode.walk", rows=hi - lo):
            buckets, fails = encode_columnar(
                space, sub, max_slots=max_slots, fuse=fuse,
                renumber=renumber, fuse_registry=fuse_registry)
        for b in buckets:
            b.indices = [i + lo for i in b.indices]
            b.failures = []
        if failures is not None:
            failures.extend((i + lo, why) for i, why in fails)
        yield buckets


def iter_synth_groups(space, spec, *, synth: str = "device",
                      max_slots: int = 16,
                      rows_per_group: Optional[int] = None,
                      partition: bool = True,
                      failures: Optional[list] = None,
                      fuse: bool = False, renumber: bool = False):
    """Device synthesis as a first-class scheduler source: generate →
    partition → encode in row groups, so group k+1 synthesizes while
    the device still chews group k and no full batch (or host Op list)
    ever materializes. ``spec`` is an ops.synth_device.SynthSpec of a
    columnar family ("cas"/"wide"); ``synth`` picks the generator
    backend ("device" | "numpy" twin | "host" legacy). The counter
    PRNG keys by global row id, so grouped generation is bit-identical
    to one-shot generation at any group size.

    Keyed specs strain each group through the P-compositional
    pre-partition; yielded bucket indices are then global SUB ordinals
    (ascending (history, key) within a group, groups in row order) —
    the deterministic namespace journals/resume would key on. Unkeyed
    specs yield global history rows, like iter_columnar_groups.
    ``space`` must be enumerated over the spec family's kind
    vocabulary. Overflow failures append to ``failures`` in the same
    index namespace as the yielded buckets."""
    from .encode import encode_columnar
    from .partition import partition_columnar
    from .synth_device import synthesize
    # Same input contract as check_synth, asserted up front — the la
    # family (and host-mode wide) produce non-columnar batches that
    # would otherwise fail deep inside partition/encode.
    assert spec.family in ("cas", "wide"), spec.family
    assert synth != "host" or spec.family == "cas", \
        "host-mode synth groups support the cas family"
    rows_per_group = rows_per_group or int(
        os.environ.get("JT_SCHED_ENCODE_ROWS", "4096"))
    fuse_registry = {} if fuse else None
    base = 0
    for lo in range(0, spec.n, rows_per_group):
        hi = min(lo + rows_per_group, spec.n)
        cols, _meta = synthesize(spec, synth, rows=(lo, hi),
                                 key_meta=False)
        if partition and getattr(cols, "key", None) is not None:
            pb = partition_columnar(cols)
            if pb is not None:
                cols = pb.cols
        buckets, fails = encode_columnar(space, cols,
                                         max_slots=max_slots,
                                         fuse=fuse, renumber=renumber,
                                         fuse_registry=fuse_registry)
        for b in buckets:
            b.indices = [i + base for i in b.indices]
            b.failures = []
        if failures is not None:
            failures.extend((i + base, why) for i, why in fails)
        base += cols.batch
        yield buckets
