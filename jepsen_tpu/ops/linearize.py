"""Packed-frontier WGL linearizability kernel.

The WGL configuration set (see jepsen_tpu.checkers.linearizable for the
algorithm spec; the reference delegates the same search to Knossos at
jepsen/src/jepsen/checker.clj:82-107) is represented as a *state-packed*
boolean frontier: config (state s, linearized-pending-set m) is bit
``s % 32`` of word

    F[s // 32][m]        # one uint32 array of length M = 2^W per word

with ``m`` ranging over all 2^W subsets of the W pending-op slots. The
host encoder (jepsen_tpu.ops.encode) reduces the history to ok-completion
events, each carrying a precomputed snapshot of the pending-slot table;
a ``lax.scan`` drives one event per step:

  * close F under application of pending ops: for each occupied slot i,
    (s, m w/o i) → (target[s], m | i). One application splits mask bit i
    with a static reshape and applies the transition as V unrolled
    bit-extract / select-row / OR steps over packed words — pure VPU work
    on full 32-config lanes (V×V one-hot matmuls with V≈8 cannot feed
    the MXU; the packed formulation replaces them outright). Closure
    iterates to fixpoint via ``lax.while_loop`` (monotone OR, ≤ live
    slots iterations; re-running converged lanes under vmap is
    idempotent);
  * keep exactly the configs whose mask holds the completing slot's bit,
    clear it — a ``lax.switch`` over W static shift-halves of the mask
    axis (no gathers). An empty survivor set means the completed op
    cannot be linearized: the history is invalid, the event index is
    recorded, and the pre-completion frontier is latched so the host can
    decode a Knossos-parity counterexample config sample.

Shapes are fully static: [words(V), 2^W] per history, vmapped over the
batch and shardable over the device mesh on the batch axis
(jepsen_tpu.parallel). The mask axis provides long 128-lane vectors for
the VPU. Cost scales with 2^W * events, so callers bucket histories by
(V, W) cost class before batching.

Scheduling around the kernel lives in ops.schedule: production callers
(check_batch_tpu / check_columnar / Store.recheck) stream their cost
buckets through its BucketScheduler, which folds exact-W buckets into
a few W *classes* (padding a history into a wider class is semantics-
preserving — the extra slots stay empty in every snapshot and can never
acquire mask bits, see ops.encode.widen_batch), chunks each class for
double-buffered dispatch, and yields verdicts incrementally. Every
compiled checker variant resolves through this module's process-wide
``get_kernel`` registry, so compile accounting, AOT pre-warming, and
the persistent compilation cache see one coherent kernel set. The
exact-W flow (``scheduler=False`` / run_buckets_threaded) remains the
parity oracle the streamed path is tested against.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from .. import telemetry
from ..history.ops import Op
from ..models.core import Model
from .encode import (EV_CLOSE, EV_FUSED, EV_OK, EncodedBatch,
                     batch_encode, bucket_encode, encode_history,
                     slot_ops_at_event)

INT32_MAX = np.int32(2**31 - 1)

# Widest state space the packed kernel accepts: two 32-state words.
MAX_PACKED_STATES = 64


def n_state_words(V: int) -> int:
    return (V + 31) // 32


def pack_rows(target: jnp.ndarray, V: int) -> Tuple[jnp.ndarray, ...]:
    """Lower a transition table to packed one-hot target rows.

    target: [K+1, V] int32 (-1 = inconsistent; final row = empty-slot
    sentinel, all -1). Returns one [K+1, V] uint32 array per state word:
    rows[w][k, s] has bit (target[k, s] - 32w) set when the target state
    lands in word w, else 0.
    """
    out = []
    for w in range(n_state_words(V)):
        t = target - 32 * w
        in_word = (t >= 0) & (t < 32)
        shift = jnp.clip(t, 0, 31).astype(jnp.uint32)
        out.append(jnp.where(in_word, jnp.uint32(1) << shift, jnp.uint32(0)))
    return tuple(out)


def transition(src: Tuple[jnp.ndarray, ...], rows_i: Tuple[jnp.ndarray, ...],
               V: int) -> Tuple[jnp.ndarray, ...]:
    """Apply one op to every packed config: out = ⋃_s {src has state s} ·
    rows_i[s]. ``src`` words share any shape; ``rows_i`` is [V] per word.
    Empty slots carry all-zero rows, making their application a no-op.
    """
    out = [None] * len(src)
    for s in range(V):
        bit = (src[s >> 5] >> jnp.uint32(s & 31)) & jnp.uint32(1)
        for w in range(len(src)):
            contrib = bit * rows_i[w][s]
            out[w] = contrib if out[w] is None else out[w] | contrib
    return tuple(out)


def _apply_slot(F: Tuple[jnp.ndarray, ...], i: int,
                rows_i: Tuple[jnp.ndarray, ...], V: int,
                M: int) -> Tuple[jnp.ndarray, ...]:
    """Close F one step under the op in slot ``i``: every config without
    bit i spawns (target-state, mask | bit i)."""
    hi, lo = M >> (i + 1), 1 << i
    Fr = [f.reshape(hi, 2, lo) for f in F]
    src = tuple(fr[:, 0, :] for fr in Fr)
    new = transition(src, rows_i, V)
    return tuple(
        jnp.concatenate([fr[:, :1, :], fr[:, 1:, :] | n[:, None, :]], axis=1)
           .reshape(M)
        for fr, n in zip(Fr, new))


def _complete_slot(F: Tuple[jnp.ndarray, ...], slot: jnp.ndarray, M: int,
                   W: int) -> Tuple[jnp.ndarray, ...]:
    """OK-completion of the op in (dynamic) slot: keep configs whose mask
    has the slot bit set, with the bit cleared. Static mask-axis reshape
    per branch; ``lax.switch`` picks the branch."""
    def make(i):
        def branch(F):
            hi, lo = M >> (i + 1), 1 << i
            out = []
            for f in F:
                fr = f.reshape(hi, 2, lo)
                out.append(jnp.concatenate(
                    [fr[:, 1:, :], jnp.zeros_like(fr[:, 1:, :])],
                    axis=1).reshape(M))
            return tuple(out)
        return branch

    return lax.switch(slot, [make(i) for i in range(W)], F)


def _union(F: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    acc = F[0]
    for f in F[1:]:
        acc = acc | f
    return acc


def _changed(Fa, Fb) -> jnp.ndarray:
    acc = (Fa[0] != Fb[0]).any()
    for a, b in zip(Fa[1:], Fb[1:]):
        acc = acc | (a != b).any()
    return acc


def make_kernel(V: int, W: int, *, w_live: Optional[int] = None,
                instrument: bool = False, resume: bool = False):
    """Build the single-history checker for static bounds (V, W).

    Returns ``check(ev_type, ev_slot, ev_slots, target) ->
    (valid, bad, frontier)`` where ``bad`` is the event index of the
    first impossible completion (INT32_MAX when valid) and ``frontier``
    is the packed [words(V), 2^W] config set — the pre-failure closure
    when invalid, the final config set when valid (counterexample /
    result decoding: ``decode_frontier``). vmap/shard over a leading
    batch axis.

    ``w_live`` (<= W) bounds the closure/completion slot unroll to the
    rows' real peak-live window: a batch widened to a consolidated W
    class (ops.schedule) carries provably-empty upper slots whose
    applications are no-ops — skipping them statically cuts the VPU
    work per closure iteration by w_live/W while the mask axis keeps
    the class shape. ``instrument=True`` appends a fourth output: total
    closure while_loop iterations per row, the measured input to the
    VPU op-count roofline (vpu_op_model). ``resume=True`` builds the
    event-chunked variant instead: ``check(ev_type, ev_slot, ev_slots,
    target, F, Fbad, valid, bad) -> (valid, bad, F, Fbad)`` with the
    packed carry ([words, 2^W] uint32 per row) flowing between
    dispatches — see run_event_chunked.
    """
    assert V <= MAX_PACKED_STATES, "packed kernel bound; use host fallback"
    M = 1 << W
    NW = n_state_words(V)
    WL = W if w_live is None else max(1, min(int(w_live), W))

    def closure(F, slots_row, rows):
        # [WL, V] per word; empty slots gather zero rows. Slots >= WL
        # are empty in EVERY snapshot of the batch (encoder invariant:
        # lowest-free-first allocation keeps indices < peak-live), so
        # the static slice drops only no-op applications.
        tgt = tuple(r[slots_row[:WL]] for r in rows)

        def body(carry):
            F0, _, n = carry
            Fn = F0
            for i in range(WL):
                Fn = _apply_slot(Fn, i, tuple(t[i] for t in tgt), V, M)
            return Fn, _changed(Fn, F0), n + 1

        F, _, n = lax.while_loop(lambda c: c[1], body,
                                 (F, jnp.bool_(True), jnp.int32(0)))
        return F, n

    def step_fn(rows):
        def step(carry, ev):
            F, Fbad, valid, bad, iters = carry
            typ, slot, slots_row, idx = ev
            is_ok = (typ == EV_OK) | (typ == EV_FUSED)
            is_close = typ == EV_CLOSE  # final flush: keep the closure
            Fc, n = closure(F, slots_row, rows)
            F_ok = _complete_slot(Fc, slot, M, WL)
            empty = is_ok & ~(_union(F_ok) != 0).any()
            first = empty & valid
            F2 = tuple(jnp.where(is_ok, a, jnp.where(is_close, c, b))
                       for a, c, b in zip(F_ok, Fc, F))
            Fb2 = tuple(jnp.where(first, c, b) for c, b in zip(Fc, Fbad))
            return (F2, Fb2, valid & ~empty,
                    jnp.minimum(bad, jnp.where(empty, idx, INT32_MAX)),
                    iters + n), None
        return step

    def widen(ev_type, ev_slot, ev_slots):
        # Event arrays arrive narrow (int8 — transfer bytes are a real
        # cost off-chip); widen for gathers/switch on device.
        return (ev_type.astype(jnp.int32), ev_slot.astype(jnp.int32),
                ev_slots.astype(jnp.int32))

    def check(ev_type, ev_slot, ev_slots, target):
        ev_type, ev_slot, ev_slots = widen(ev_type, ev_slot, ev_slots)
        rows = pack_rows(target, V)
        N = ev_type.shape[0]
        Fz = tuple(jnp.zeros((M,), jnp.uint32) for _ in range(NW))
        F0 = (Fz[0].at[0].set(jnp.uint32(1)),) + Fz[1:]
        carry = (F0, Fz, jnp.bool_(True), jnp.int32(INT32_MAX),
                 jnp.int32(0))
        (F, Fbad, valid, bad, iters), _ = lax.scan(
            step_fn(rows), carry, (ev_type, ev_slot, ev_slots,
                                   jnp.arange(N, dtype=jnp.int32)))
        frontier = jnp.stack(
            [jnp.where(valid, a, b) for a, b in zip(F, Fbad)])
        if instrument:
            return valid, bad, frontier, iters
        return valid, bad, frontier

    def check_resume(ev_type, ev_slot, ev_slots, target, idx0, F_in,
                     Fb_in, valid_in, bad_in):
        ev_type, ev_slot, ev_slots = widen(ev_type, ev_slot, ev_slots)
        rows = pack_rows(target, V)
        N = ev_type.shape[0]
        carry = (tuple(F_in[i] for i in range(NW)),
                 tuple(Fb_in[i] for i in range(NW)),
                 valid_in, bad_in, jnp.int32(0))
        (F, Fbad, valid, bad, _), _ = lax.scan(
            step_fn(rows), carry,
            (ev_type, ev_slot, ev_slots,
             idx0 + jnp.arange(N, dtype=jnp.int32)))
        return valid, bad, jnp.stack(F), jnp.stack(Fbad)

    return check_resume if resume else check


# ------------------------------------------------------ kernel registry
# One process-wide registry resolves EVERY compiled checker variant —
# single-device ("data1"), batch-sharded ("data"), and mask-sharded
# ("frontier") — keyed by (kind, V, W, mesh, shared_target, donate).
# The streaming scheduler (ops.schedule) and the mesh dispatchers
# (parallel.mesh / parallel.frontier) all come through here, so compile
# accounting, pre-warming, and the persistent compilation cache see one
# coherent kernel set. ``donate=True`` builds the jit with the event
# arrays' device buffers donated (donate_argnums) — the chunked
# scheduler path ships each chunk exactly once, so XLA may recycle the
# input buffers as scan scratch instead of holding both live.
_KERNEL_REGISTRY: Dict[Tuple, object] = {}

# Every (registry key, batch shape, event shape) the process has
# dispatched — one entry per XLA compilation (jit compiles per shape).
# bench.py reports len() deltas; ops.schedule pre-warms against it.
KERNEL_SHAPE_LOG: "set" = set()

def _silence_donation_warning() -> None:
    # Donated buffers that XLA can't alias into outputs (ours never are
    # — the verdict outputs are tiny) warn on every call; the donation
    # is still honored for scratch reuse, so the warning is pure noise
    # here. Installed lazily on the first donated kernel build so
    # merely importing this library doesn't mutate global warning
    # state for applications that want the diagnostic for their own
    # donate_argnums jits.
    import warnings
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")


def get_kernel(V: int, W: int, *, kind: str = "data1", mesh=None,
               shared_target: bool = False, donate: bool = False,
               w_live: Optional[int] = None, instrument: bool = False,
               resume: bool = False):
    """Resolve (build + cache) a compiled checker kernel.

    kind "data1" is the single-device vmapped kernel; "data" shards the
    batch axis over ``mesh``; "frontier" splits the mask axis over the
    mesh's frontier devices. All variants share the contract
    check(ev_type, ev_slot, ev_slots, target) -> (valid, bad, frontier).
    The frontier variant does not support donation (its shard_map
    carries the event arrays through a collective scan), so ``donate``
    is normalized off there rather than cached under a key that lies.

    ``w_live`` bounds the slot unroll to the batch's real peak-live
    window (make_kernel); normalized to W when it wouldn't shrink the
    unroll so equivalent requests share one compile. ``instrument`` and
    ``resume`` are single-device (data1) variants only.
    """
    if kind == "frontier":
        donate = False
    if w_live is None or w_live >= W or kind == "frontier":
        w_live = W
    key = (kind, V, W, id(mesh) if mesh is not None else None,
           shared_target, donate, w_live, instrument, resume)
    k = _KERNEL_REGISTRY.get(key)
    if k is None:
        donate_argnums = (0, 1, 2) if donate else ()
        if donate:
            _silence_donation_warning()
        if kind == "data1":
            assert not (instrument and resume)
            kern = make_kernel(V, W, w_live=w_live,
                               instrument=instrument, resume=resume)
            if resume:
                # idx0 is a shared scalar; carry arrays batch like the
                # event tables.
                k = jax.jit(jax.vmap(
                    kern, in_axes=(0, 0, 0,
                                   None if shared_target else 0,
                                   None, 0, 0, 0, 0)))
            else:
                k = jax.jit(jax.vmap(kern,
                                     in_axes=(0, 0, 0,
                                              None if shared_target
                                              else 0)),
                            donate_argnums=donate_argnums)
        elif kind == "frontier":
            from ..parallel.frontier import frontier_sharded_kernel
            k = frontier_sharded_kernel(V, W, mesh, shared_target)
        elif kind == "data":
            from ..parallel.mesh import data_sharded_kernel
            k = data_sharded_kernel(V, W, mesh, shared_target,
                                    donate=donate, w_live=w_live)
        else:
            raise ValueError(f"unknown kernel kind {kind!r}")
        _KERNEL_REGISTRY[key] = k
    return k


def make_fused_kernel(members):
    """Build the multi-bucket megakernel body: one program that scans
    several class buckets back to back — ``members`` is a tuple of
    (V, W, w_live, shared_target) per bucket chunk, the callable takes
    4 flat args per member (ev_type, ev_slot, ev_slots, target) and
    returns 3 flat outputs per member (valid, bad, frontier). One jit
    of this retires a whole dispatch group in a single XLA call — the
    per-dispatch overhead (host round trip, launch latency) that
    dominates the many-small-buckets shape is paid once per group
    instead of once per bucket (ops.schedule's fused dispatch path)."""
    kerns = []
    for (V, W, wl, shared) in members:
        kerns.append(jax.vmap(make_kernel(V, W, w_live=wl),
                              in_axes=(0, 0, 0, None if shared else 0)))

    def fused(*flat):
        out = []
        for i, kern in enumerate(kerns):
            out.extend(kern(*flat[4 * i:4 * i + 4]))
        return tuple(out)

    return fused


def get_fused_kernel(members, donate: bool = False):
    """Resolve (build + cache) a compiled fused multi-bucket kernel —
    the dispatch-group twin of ``get_kernel``, sharing the process-wide
    registry so compile accounting and AOT shipping see one kernel
    set. ``members`` as in make_fused_kernel; ``donate`` donates every
    member's event buffers (each group ships exactly once)."""
    members = tuple(tuple(m) for m in members)
    key = ("fusedN", members, donate)
    k = _KERNEL_REGISTRY.get(key)
    if k is None:
        donate_argnums = tuple(j for i in range(len(members))
                               for j in (4 * i, 4 * i + 1, 4 * i + 2)) \
            if donate else ()
        if donate:
            _silence_donation_warning()
        k = jax.jit(make_fused_kernel(members),
                    donate_argnums=donate_argnums)
        _KERNEL_REGISTRY[key] = k
    return k


def log_kernel_shapes(V: int, W: int, kind: str, shared_target: bool,
                      donate: bool, B: int, N: int,
                      w_live: Optional[int] = None) -> None:
    """Record a dispatch shape (one registry entry per XLA compile)."""
    KERNEL_SHAPE_LOG.add((kind, V, W, shared_target, donate, B, N,
                          w_live if w_live and w_live < W else W))


def batch_kernel(V: int, W: int, shared_target: bool = False,
                 w_live: Optional[int] = None):
    """``shared_target``: every row uses one transition table — the
    table is passed unbatched ([K+1, V]) and broadcast on device,
    saving the per-row transfer."""
    return get_kernel(V, W, shared_target=shared_target, w_live=w_live)


# Frontier-words budget per device dispatch: B * words(V) * 2^W uint32.
# Keeps the scan carry (plus XLA's temporaries) well inside one chip's
# HBM even for info-heavy windows (W=16 → 0.5 MB/history).
MAX_FRONTIER_ELEMENTS = 1 << 26

# Pending-window width the single-device kernel accepts at its
# VMEM-resident sweet spot; wider windows split their mask axis over the
# mesh's "frontier" devices (the sequence-parallel path,
# jepsen_tpu.parallel.frontier) — the TPU answer to the reference
# handing Knossos a 32 GB JVM heap (project.clj:22).
DATA_MAX_SLOTS = 16

# Without enough frontier devices, a single device still hosts this many
# extra window bits by letting the mask axis spill to HBM (2^18 masks =
# 2 MB/history/word) and shrinking the batch chunk to compensate — time
# and bandwidth traded for not falling back to the host engine. W=17-18
# buckets on the one-chip bench env ride this instead of the CPU.
SINGLE_DEVICE_EXTRA_SLOTS = 2

# Don't pay an SPMD compile to spread a handful of rows: batches below
# this many rows per device stay on one device.
MIN_ROWS_PER_DEVICE = 8

# Dispatch telemetry: (path, V, W, B) per device call — "data1" (single
# device), "dataN" (batch sharded over the mesh), "frontier" (mask axis
# sharded). Tests and the multichip dryrun assert the route taken;
# bounded so long-lived checker processes don't grow it forever.
from collections import deque
DISPATCH_LOG: "deque" = deque(maxlen=256)

_PROD_MESHES: Dict[Tuple[int, int], object] = {}


def device_frontier_capacity() -> int:
    """Extra pending-window bits the attached devices can host beyond
    DATA_MAX_SLOTS: log2 of the largest power-of-two device count (the
    frontier-sharded path), and never less than the single-device
    HBM-spill margin (the data1wide path). The encoder may window up to
    DATA_MAX_SLOTS + capacity slots before a history must fall back to
    the host engine."""
    import jax
    nd = len(jax.devices())
    return max(nd.bit_length() - 1, SINGLE_DEVICE_EXTRA_SLOTS)


def production_mesh(n_frontier: int = 1):
    """The process-wide ("data", "frontier") mesh for production
    dispatch, or None when the devices can't host the frontier axis (or
    there is only one device and no frontier need)."""
    import jax
    nd = len(jax.devices())
    if n_frontier > nd or (nd < 2 and n_frontier == 1):
        return None
    key = (nd, n_frontier)
    mesh = _PROD_MESHES.get(key)
    if mesh is None:
        from ..parallel.mesh import checker_mesh
        mesh = checker_mesh(n_data=nd // n_frontier,
                            n_frontier=n_frontier)
        _PROD_MESHES[key] = mesh
    return mesh


def _sharded_kernel(kind: str, V: int, W: int, mesh,
                    shared_target: bool = False,
                    w_live: Optional[int] = None):
    return get_kernel(V, W, kind="frontier" if kind == "frontier"
                      else "data", mesh=mesh, shared_target=shared_target,
                      w_live=w_live)


def _pad_rows(batch: EncodedBatch, bp: int) -> Tuple[np.ndarray, ...]:
    """Pad a batch's arrays to ``bp`` rows with inert histories (all
    events PAD, empty slot tables, all-invalid targets): they scan to
    valid=True and are sliced off after the device call."""
    b, n, w = batch.batch, batch.n_events, batch.ev_slots.shape[2]
    K1, V = batch.target.shape[1], batch.target.shape[2]
    ev_type = np.zeros((bp, n), batch.ev_type.dtype)
    ev_slot = np.zeros((bp, n), batch.ev_slot.dtype)
    ev_slots = np.full((bp, n, w), K1 - 1, batch.ev_slots.dtype)
    ev_type[:b] = batch.ev_type
    ev_slot[:b] = batch.ev_slot
    ev_slots[:b] = batch.ev_slots
    if batch.shared_target:
        # Dispatch ships batch.target[0] once; don't materialize B copies.
        return ev_type, ev_slot, ev_slots, None
    target = np.full((bp, K1, V), -1, np.int32)
    target[:b] = batch.target
    return ev_type, ev_slot, ev_slots, target


def _round_up_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def dispatch_route(batch: EncodedBatch):
    """(route, mesh) of one bucket on the attached devices, as
    ``run_encoded_batch`` runs it: "frontier" (mask axis split over
    2^(W - DATA_MAX_SLOTS) devices), "data1wide" (a window past
    DATA_MAX_SLOTS on one device, HBM-resident, when the devices can't
    host the frontier axis), "dataN" (batch axis sharded) or "data1"
    (one device). Raises WindowOverflow when no device route can host
    the window — callers route those rows to a host engine."""
    if batch.W > DATA_MAX_SLOTS:
        D = 1 << (batch.W - DATA_MAX_SLOTS)
        mesh = production_mesh(D)
        if mesh is not None:
            return "frontier", mesh
        if batch.W - DATA_MAX_SLOTS <= SINGLE_DEVICE_EXTRA_SLOTS:
            # Not enough devices to shard the mask axis: run the wide
            # window on one device, HBM-resident, with the batch chunk
            # shrunk in proportion (time for memory — the single-chip
            # degradation path).
            return "data1wide", None
        raise WindowOverflow(
            f"window W={batch.W} needs {D} frontier devices")
    mesh = production_mesh(1)
    from ..parallel.mesh import should_shard
    if should_shard(batch.batch, mesh):
        return "dataN", mesh
    # Sub-minimum sharding (rows/device below the $JT_SHARD_MIN_ROWS
    # floor) regresses — MULTICHIP_r06's 4/8-device points — so thin
    # batches stay on one device.
    return "data1", None


def run_encoded_batch(batch: EncodedBatch, return_frontier: bool = False,
                      route=None):
    """Device-check one cost bucket; routes to the right kernel for the
    bucket's window and the attached devices:

      * W <= DATA_MAX_SLOTS, small batch or one device — single-device
        vmapped kernel, chunked to bound memory;
      * W <= DATA_MAX_SLOTS, large batch on a multi-device mesh — batch
        axis sharded over "data" (jepsen_tpu.parallel.mesh);
      * W > DATA_MAX_SLOTS — mask axis split over 2^(W - 16) "frontier"
        devices (jepsen_tpu.parallel.frontier). Raises WindowOverflow
        when the devices can't host the axis — callers route those rows
        to a host engine.

    Blocking; multi-bucket callers overlap the per-dispatch round trips
    with ``run_buckets_threaded``. Returns (valid [B] bool, bad [B],
    frontier) — frontier is [B, words(V), 2^W] uint32 when
    ``return_frontier=True``, None when False (skipping the
    device→host transfer, which verdict-only hot paths shouldn't pay),
    and with ``return_frontier="invalid"`` a dict {row: frontier_row}
    holding ONLY the invalid rows — gathered on device, so the replay
    product path never ships the valid majority's frontiers across a
    latency-bound link. ``route``: the bucket's ``dispatch_route``,
    when the caller has it already.
    """
    if batch.batch == 0:
        z = np.zeros((0,), bool)
        empty_front = ({} if return_frontier == "invalid" else
                       np.zeros((0, 1, 1 << batch.W), np.uint32)
                       if return_frontier else None)
        return z, np.zeros((0,), np.int32), empty_front

    route, mesh = route or dispatch_route(batch)
    if route in ("frontier", "dataN"):
        pending = _dispatch_sharded(route, batch, mesh, return_frontier)
    else:
        pending = _data1_dispatch(batch, return_frontier, label=route)

    valids, bads, fronts = [], [], []
    front_map = {} if return_frontier == "invalid" else None
    off = 0
    for valid, bad, front, nb in pending:
        v = np.asarray(valid)[:nb]
        valids.append(v)
        bads.append(np.asarray(bad)[:nb])
        if return_frontier is True:
            fronts.append(np.asarray(front)[:nb])
        elif return_frontier == "invalid":
            rows = np.nonzero(~v)[0]
            if rows.size:
                sel = np.asarray(front[rows])   # device-side gather
                for i, r in enumerate(rows):
                    front_map[off + int(r)] = sel[i]
        off += nb
    frontier = (np.concatenate(fronts) if return_frontier is True
                else front_map)
    return np.concatenate(valids), np.concatenate(bads), frontier


def _data1_dispatch(batch: EncodedBatch, return_frontier: bool,
                    label: str = "data1"):
    """Single-device vmapped dispatch, batch-chunked so the in-flight
    frontier words stay inside MAX_FRONTIER_ELEMENTS (wide windows get
    proportionally smaller chunks)."""
    kern = batch_kernel(batch.V, batch.W, batch.shared_target,
                        w_live=batch.eff_w_live)
    per_hist = n_state_words(batch.V) << batch.W
    chunk = max(1, MAX_FRONTIER_ELEMENTS // per_hist)
    DISPATCH_LOG.append((label, batch.V, batch.W, batch.batch))
    pending = []
    for lo in range(0, batch.batch, chunk):
        hi = min(lo + chunk, batch.batch)
        log_kernel_shapes(batch.V, batch.W, "data1", batch.shared_target,
                          False, hi - lo, batch.n_events,
                          batch.eff_w_live)
        valid, bad, front = kern(
            batch.ev_type[lo:hi], batch.ev_slot[lo:hi],
            batch.ev_slots[lo:hi],
            batch.target[0] if batch.shared_target
            else batch.target[lo:hi])
        pending.append((valid, bad,
                        front if return_frontier else None,
                        hi - lo))
    return pending


def run_event_chunked(batch: EncodedBatch, events_per_chunk: int,
                      return_frontier: bool = False):
    """Single-device dispatch with the EVENT axis chunked: the packed
    frontier carry ([words, 2^W] per row) flows between dispatches, so
    a 100k-op history never materializes one 100k-step scan. Chunks are
    double-buffered for free — jax dispatch is async, so chunk k+1's
    (narrow int8) event upload overlaps chunk k's device scan; rows
    whose frontier already emptied are closed early in the only sense
    that matters on a converged scan: every further step is an
    idempotent no-op on an all-zero carry. Same (valid, bad, frontier)
    contract as run_encoded_batch; parity-tested against the one-shot
    scan (tests/test_fusion.py)."""
    assert batch.W <= DATA_MAX_SLOTS + SINGLE_DEVICE_EXTRA_SLOTS
    B, N = batch.batch, batch.n_events
    NW, M = n_state_words(batch.V), 1 << batch.W
    if B == 0:
        return (np.zeros((0,), bool), np.zeros((0,), np.int32),
                np.zeros((0, NW, M), np.uint32) if return_frontier
                else None)
    kern = get_kernel(batch.V, batch.W, shared_target=batch.shared_target,
                      w_live=batch.eff_w_live, resume=True)
    C = max(8, int(events_per_chunk))
    F = np.zeros((B, NW, M), np.uint32)
    F[:, 0, 0] = 1                      # (initial state, empty mask)
    Fb = np.zeros((B, NW, M), np.uint32)
    valid = np.ones(B, bool)
    bad = np.full(B, INT32_MAX, np.int32)
    tgt = (np.ascontiguousarray(batch.target[0]) if batch.shared_target
           else batch.target)
    out = (valid, bad, F, Fb)
    for lo in range(0, N, C):
        hi = min(lo + C, N)
        if hi - lo == C:
            # Full chunks pass slices straight through; only the final
            # ragged chunk pads (EV_PAD steps are no-ops), keeping one
            # compiled shape without copying every chunk.
            ev_t = batch.ev_type[:, lo:hi]
            ev_s = batch.ev_slot[:, lo:hi]
            ev_ss = batch.ev_slots[:, lo:hi]
        else:
            ev_t = np.zeros((B, C), batch.ev_type.dtype)
            ev_s = np.zeros((B, C), batch.ev_slot.dtype)
            ev_ss = np.full((B, C, batch.ev_slots.shape[2]),
                            batch.target.shape[1] - 1,
                            batch.ev_slots.dtype)
            ev_t[:, :hi - lo] = batch.ev_type[:, lo:hi]
            ev_s[:, :hi - lo] = batch.ev_slot[:, lo:hi]
            ev_ss[:, :hi - lo] = batch.ev_slots[:, lo:hi]
        log_kernel_shapes(batch.V, batch.W, "data1ev",
                          batch.shared_target, False, B, C,
                          batch.eff_w_live)
        out = kern(ev_t, ev_s, ev_ss, tgt, np.int32(lo), out[2], out[3],
                   out[0], out[1])
    valid = np.asarray(out[0])
    bad = np.asarray(out[1])
    frontier = None
    if return_frontier:
        F, Fb = np.asarray(out[2]), np.asarray(out[3])
        frontier = np.where(valid[:, None, None], F, Fb)
    return valid, bad, frontier


# ------------------------------------------------ carried-frontier seam
#
# The kernel contract's resume variant (make_kernel(resume=True)) is the
# seam the ONLINE incremental path rides: the packed carry — configs-so-
# far frontier F, the latched pre-failure frontier Fbad, and the running
# (valid, bad) verdict — flows OUT of one dispatch and back INTO the
# next, so a live tenant's rolling prefix check resumes from where the
# last tick stopped instead of re-walking from op 0 (ROADMAP item 2 /
# the decrease-and-conquer monitoring argument, arXiv 2410.04581).
# run_event_chunked uses the same carry within one call; these helpers
# let a caller hold it ACROSS calls — and across processes, via the
# export/import pair (zlib+b64, the journal frontier-checkpoint row's
# payload). The Pallas megakernel has no resume entry (its frontier
# lives in VMEM for exactly one launch — ops.pallas_wgl), so carried
# dispatch always rides the lax.scan kernel.

# Event-axis chunk for carried dispatch; shapes pad to the power-of-two
# ladder (floor CARRY_QUANTUM) so a daemon's varying tick sizes share a
# handful of compiled shapes per (V, W) instead of one per length.
CARRY_EVENT_CHUNK = 2048
CARRY_QUANTUM = 64


def frontier_carry_init(V: int, W: int) -> dict:
    """A fresh single-row carry: the initial config (state 0, empty
    mask) present, verdict valid, no bad event."""
    NW, M = n_state_words(V), 1 << W
    F = np.zeros((1, NW, M), np.uint32)
    F[0, 0, 0] = 1
    return {"valid": np.ones(1, bool),
            "bad": np.full(1, INT32_MAX, np.int32),
            "F": F,
            "Fb": np.zeros((1, NW, M), np.uint32)}


def run_carried_events(V: int, W: int, target: np.ndarray,
                       ev_type: np.ndarray, ev_slot: np.ndarray,
                       ev_slots: np.ndarray, idx0: int,
                       carry: dict) -> dict:
    """Advance a carried frontier over ``N`` new events (single row,
    shared target) and return the new carry, host-materialized. Events
    are dispatched in CARRY_EVENT_CHUNK steps padded to the power-of-
    two ladder (EV_PAD steps are no-ops on the scan), so one tenant
    compiles a bounded shape set however its tick sizes vary. ``bad``
    in the carry is a GLOBAL event ordinal (``idx0`` continues the
    tenant's event numbering across calls)."""
    N = int(ev_type.shape[0])
    kern = get_kernel(V, W, shared_target=True, resume=True)
    out = (carry["valid"], carry["bad"], carry["F"], carry["Fb"])
    tgt = np.ascontiguousarray(target)
    for lo in range(0, N, CARRY_EVENT_CHUNK):
        hi = min(lo + CARRY_EVENT_CHUNK, N)
        n = hi - lo
        C = min(CARRY_EVENT_CHUNK,
                max(CARRY_QUANTUM, 1 << (n - 1).bit_length()))
        et = np.zeros((1, C), np.int8)
        es = np.zeros((1, C), np.int8)
        ess = np.full((1, C, W), target.shape[0] - 1, np.int32)
        et[0, :n] = ev_type[lo:hi]
        es[0, :n] = ev_slot[lo:hi]
        ess[0, :n] = ev_slots[lo:hi]
        log_kernel_shapes(V, W, "data1carry", True, False, 1, C, W)
        out = kern(et, es, ess, tgt, np.int32(idx0 + lo),
                   out[2], out[3], out[0], out[1])
    return {"valid": np.asarray(out[0]), "bad": np.asarray(out[1]),
            "F": np.asarray(out[2]), "Fb": np.asarray(out[3])}


def export_frontier(carry: dict) -> dict:
    """Serialize a carry for the journal frontier-checkpoint row
    (doc/online.md documents the format). The packed bitsets compress
    hard (config sets are sparse), so the row stays journal-sized."""
    import base64
    import zlib

    def pack(a):
        return base64.b64encode(
            zlib.compress(np.ascontiguousarray(a).tobytes())).decode()

    return {"v": 1, "shape": list(carry["F"].shape),
            "valid": bool(carry["valid"][0]),
            "bad": int(carry["bad"][0]),
            "F": pack(carry["F"]), "Fb": pack(carry["Fb"])}


def import_frontier(d: dict, V: int, W: int) -> Optional[dict]:
    """Deserialize an exported carry; None on any mismatch (a stale or
    foreign checkpoint is a cache miss, never a failure mode)."""
    import base64
    import zlib
    try:
        if d.get("v") != 1:
            return None
        shape = tuple(d["shape"])
        if shape != (1, n_state_words(V), 1 << W):
            return None

        def unpack(s):
            a = np.frombuffer(zlib.decompress(base64.b64decode(s)),
                              np.uint32)
            return a.reshape(shape).copy()

        return {"valid": np.array([bool(d["valid"])]),
                "bad": np.array([int(d["bad"])], np.int32),
                "F": unpack(d["F"]), "Fb": unpack(d["Fb"])}
    except Exception:
        return None


def grow_frontier_states(carry: dict, old_words: int,
                         new_words: int) -> dict:
    """Widen a carry's state axis (appended vocabulary reached new
    states past the current word pad): new states' bits start 0 in
    every config, which is exactly right — no existing config holds
    them. The mask axis (2^W) is untouched."""
    if new_words == old_words:
        return carry
    assert new_words > old_words
    out = dict(carry)
    for k in ("F", "Fb"):
        a = carry[k]
        wide = np.zeros((a.shape[0], new_words, a.shape[2]), np.uint32)
        wide[:, :old_words] = a
        out[k] = wide
    return out


def fused_bad_rows(batch: EncodedBatch, valid, bad) -> np.ndarray:
    """Row positions (within ``batch``) whose first impossible
    completion landed on an EV_FUSED step. The device only knows such
    a run's FIRST member, so every consumer — check_batch_tpu,
    check_columnar, bench parity — re-derives these rows' exact bad
    op/counterexample through a host-side engine; this is the one
    shared detector so the invariant can't drift between them."""
    v = np.asarray(valid)
    b = np.asarray(bad)
    inv = np.nonzero(~v)[0]
    return inv[batch.ev_type[inv, b[inv]] == EV_FUSED]


def vpu_op_model(V: int, W: int, w_live: Optional[int] = None) -> dict:
    """Analytic uint32 VPU lane-op counts for the packed kernel — the
    op-count basis behind the bench's measured ``vpu_util`` roofline.

    Per closure ITERATION (one while_loop body pass): each of the
    ``w_live`` slot applications walks V states, paying 2 lane-ops to
    extract the state bit and, per packed word, a multiply + OR over
    the M/2 spawned-mask lanes, plus the OR-merge back into the mask
    halves; the convergence check compares + reduces every frontier
    word. Per EVENT on top: the completion shift-half, the emptiness
    union/any, and the three latch selects, all over full [NW, M]
    words. Host-side constants only — the measured input (iterations
    per row) comes from the instrumented kernel (make_kernel
    ``instrument=True``)."""
    NW = n_state_words(V)
    M = 1 << W
    WL = W if w_live is None else max(1, min(int(w_live), W))
    per_apply = (M // 2) * (V * (2 + 2 * NW) + NW)
    per_iteration = WL * per_apply + 2 * NW * M
    per_event = 5 * NW * M
    return {"per_iteration": per_iteration, "per_event": per_event,
            "words": NW, "masks": M, "w_live": WL}


class WindowOverflow(Exception):
    """A cost bucket's pending window exceeds what the attached devices
    can host; the rows belong on a host/native engine."""


class DaemonFuture:
    """A one-shot future computed on a DAEMON thread. Device dispatch
    can wedge (a hung runtime) and neither jax nor the
    native engine is interruptible mid-call — a non-daemon worker (or a
    concurrent.futures pool, whose atexit hook joins its threads even
    after shutdown(wait=False)) would then block interpreter exit, the
    exact hazard LinearizableChecker._compete dodges the same way."""

    def __init__(self, fn, *args, name: str = "jepsen-async"):
        import queue
        self._q: "queue.Queue" = queue.Queue(1)

        def run():
            try:
                self._q.put((fn(*args), None))
            except BaseException as e:   # noqa: BLE001 — relayed below
                self._q.put((None, e))

        import threading
        threading.Thread(target=run, name=name, daemon=True).start()

    def result(self):
        r, err = self._q.get()
        if err is not None:
            raise err
        return r


def daemon_map(fn, items, max_workers: int = 12):
    """Map ``fn`` over ``items`` on daemon worker threads, yielding
    results in SUBMISSION order as they become consumable (the
    run_buckets_threaded delivery contract)."""
    items = list(items)
    if not items:
        return
    if len(items) == 1:
        yield fn(items[0])
        return
    import queue
    import threading

    slots = [queue.Queue(1) for _ in items]
    feed: "queue.Queue" = queue.Queue()
    for i, it in enumerate(items):
        feed.put((i, it))

    def work():
        while True:
            try:
                i, it = feed.get_nowait()
            except queue.Empty:
                return
            try:
                slots[i].put((fn(it), None))
            except BaseException as e:  # noqa: BLE001 — relayed below
                slots[i].put((None, e))

    for n in range(min(max_workers, len(items))):
        threading.Thread(target=work, name=f"jepsen-bucket-{n}",
                         daemon=True).start()
    for s in slots:
        r, err = s.get()
        if err is not None:
            raise err
        yield r


def run_buckets_threaded(batches: Sequence[EncodedBatch],
                         return_frontier: bool = False):
    """Run many cost buckets concurrently from a daemon-thread pool and
    yield (batch, (valid, bad, frontier) | WindowOverflow) pairs. JAX
    execution is thread-safe; overlapping the per-call round trips is
    what keeps many-bucket batches fast when the device sits behind a
    link with real latency (PCIe queues). Yields in SUBMISSION order
    (callers zip against their input list) as results become
    consumable, so the caller's per-bucket host work overlaps buckets
    still on device. Workers are daemon threads: a wedged device
    dispatch must not block interpreter exit.

    See ops.schedule.run_buckets_streamed for the pipelined variant
    (W-class consolidation + chunked double-buffered dispatch); this
    exact-bucket path remains the parity oracle for it."""
    if not batches:
        return []

    def one(batch):
        try:
            return batch, run_encoded_batch(batch, return_frontier)
        except WindowOverflow as e:
            return batch, e

    return daemon_map(one, batches)


def _dispatch_sharded(kind: str, batch: EncodedBatch, mesh,
                      return_frontier: bool):
    """Queue one bucket through a sharded kernel, padding the batch to
    the data-axis multiple and chunking to bound per-device memory."""
    n_data = mesh.shape["data"]
    kern = _sharded_kernel("frontier" if kind == "frontier" else "data",
                           batch.V, batch.W, mesh, batch.shared_target,
                           w_live=batch.eff_w_live)
    # Per-device budget: (chunk / n_data) rows x (per_hist / n_frontier)
    # words <= MAX_FRONTIER_ELEMENTS  =>  chunk <= MAX * size / per_hist.
    per_hist = n_state_words(batch.V) << batch.W
    chunk = _round_up_to(
        max(n_data, MAX_FRONTIER_ELEMENTS * mesh.size // max(per_hist, 1)),
        n_data)
    DISPATCH_LOG.append((kind, batch.V, batch.W, batch.batch))
    out = []
    for lo in range(0, batch.batch, chunk):
        hi = min(lo + chunk, batch.batch)
        nb = hi - lo
        bp = _round_up_to(nb, n_data)
        sub = EncodedBatch(
            ev_type=batch.ev_type[lo:hi], ev_slot=batch.ev_slot[lo:hi],
            ev_slots=batch.ev_slots[lo:hi], ev_opidx=batch.ev_opidx[lo:hi],
            target=batch.target[lo:hi], V=batch.V, W=batch.W,
            indices=[], failures=[], shared_target=batch.shared_target)
        ev_type, ev_slot, ev_slots, target = _pad_rows(sub, bp)
        log_kernel_shapes(batch.V, batch.W, kind, batch.shared_target,
                          False, bp, batch.n_events,
                          batch.eff_w_live if kind != "frontier"
                          else batch.W)
        valid, bad, front = kern(
            ev_type, ev_slot, ev_slots,
            batch.target[0] if batch.shared_target else target)
        out.append((valid, bad, front if return_frontier else None, nb))
    return out


def decode_frontier(frontier: np.ndarray, space, slot_to_op: Dict[int, int],
                    n: int = 10) -> List[dict]:
    """Decode a packed [words, M] frontier into a bounded, deterministic
    config sample matching the host engine's shape
    (checkers.linearizable._sample_configs): ``{"model": repr(state),
    "pending": sorted linearized op indices}``, sorted, truncated to n —
    the reference's truncate-to-10 discipline (checker.clj:104-107)."""
    words, masks = np.nonzero(np.asarray(frontier))
    configs = []
    for w, m in zip(words.tolist(), masks.tolist()):
        bits = int(frontier[w, m])
        s = 0
        while bits:
            if bits & 1:
                state = 32 * w + s
                if state < len(space.states):
                    pend = sorted(slot_to_op[i] for i in range(32)
                                  if (m >> i) & 1 and i in slot_to_op)
                    configs.append({"model": repr(space.states[state]),
                                    "pending": pend})
            bits >>= 1
            s += 1
    configs.sort(key=lambda c: (c["model"], c["pending"]))
    return configs[:n]


def _decode_result(space, ops: List[Op], valid: bool,
                   op_index: int, frontier_row,
                   predropped: bool = False) -> dict:
    """Host-shaped result dict from a kernel verdict: {"valid"} plus, on
    failure, the impossible op and a decoded config sample — one decoder
    for both device paths so counterexample discipline can't drift.
    ``predropped``: the op stream already had identity drops applied
    (columnar-sourced rows), so the slot replay can skip the per-op
    state-space recompute."""
    if valid:
        out = {"valid": True}
        if space is not None:
            table = slot_ops_at_event(space, ops, None,
                                      predropped=predropped)
            out["configs"] = decode_frontier(frontier_row, space, table)
        return out
    op = next((o for o in ops if o.index == op_index), None)
    out = {"valid": False,
           "op": op.to_dict() if op is not None else {"index": op_index}}
    if space is not None:
        # Locate the pending table by the bad op's history index, not
        # the device event ordinal — fusion compacts the event axis, so
        # ordinals no longer line up with the unfused walk.
        table = slot_ops_at_event(space, ops, None, predropped=predropped,
                                  op_index=op_index)
        out["configs"] = decode_frontier(frontier_row, space, table)
    return out


def _result_for(row: int, batch: EncodedBatch, valid: np.ndarray,
                bad: np.ndarray, frontier: np.ndarray, model: Model,
                prepared: List[Op]) -> dict:
    space = batch.spaces[row] if batch.spaces else None
    ev = int(bad[row])
    op_index = int(batch.ev_opidx[row, ev]) if not bool(valid[row]) else -1
    return _decode_result(space, prepared, bool(valid[row]), op_index,
                          frontier[row])


def _rehydrate_verdict(valid: bool, bad: Optional[int],
                       prov: str) -> dict:
    """Result dict for a row decided by a previous interrupted run
    (chunk journal). Bare — no config sample; the journal records
    verdicts, not frontiers — and marked ``resumed``."""
    out: dict = {"valid": valid, "provenance": prov, "resumed": True}
    if valid is False:
        out["op"] = {"index": bad}
    return out


def _journal_result(journal, i: int, r: dict) -> None:
    """Journal one host-decided row's final verdict (no-op without a
    journal). One translation for both checkers: _sink_verdict."""
    if journal is not None:
        _sink_verdict(journal.record, i, r)


def check_batch_tpu(model: Model, histories: Sequence[List[Op]], *,
                    max_states: int = MAX_PACKED_STATES, max_slots: int = 16,
                    host_fallback=None, min_device_batch: int = 1,
                    scheduler: bool = True, faults=None, journal=None,
                    scheduler_opts: Optional[dict] = None,
                    partition: object = "auto") -> List[dict]:
    """Check many raw histories on device; per-history result dicts.

    Histories the encoder cannot bound (state-space explosion, pending
    window overflow) are delegated to ``host_fallback(model, history)``
    (default: the exact host engine). Cost-class buckets smaller than
    ``min_device_batch`` go to the native CPU engine instead — the tail
    of info-heavy (large-W) histories is typically a handful of rows,
    not worth an XLA compile or the widest frontier.

    ``scheduler=True`` (default) dispatches through the streaming
    bucket scheduler (ops.schedule): exact-W buckets consolidate into
    few W classes, chunks pipeline against host decode, and — because
    small narrow buckets now merge into healthy classes — the
    ``min_device_batch`` CPU route only applies to *wide*
    (W >= DATA_MAX_SLOTS) stragglers. ``scheduler=False`` keeps the
    one-kernel-per-exact-W flow (the parity oracle for the scheduler).

    On the scheduler path every result carries a ``provenance`` tag —
    ``device`` / ``device-retried`` / ``host-fallback`` (which engine,
    and how hard the ladder had to work, decided the row; see
    doc/resilience.md). Rows the scheduler quarantines after its
    degradation ladder are re-decided by ``host_fallback`` (the exact
    parity oracle), so every history gets a verdict under any fault
    schedule. ``faults`` injects a checker nemesis (ops.faults);
    ``journal`` (store.ChunkJournal) makes retired chunk verdicts
    durable and resumes from them; ``scheduler_opts`` forwards knobs to
    BucketScheduler (chunk_rows, max_classes, ...).

    ``partition`` is the P-compositional pre-partition (ops.partition):
    KV-valued histories strain into per-key sub-histories BEFORE
    encoding — each key checks at its own (much smaller) pending
    window W, collapsing the 2^W frontier cost — and verdicts
    recombine host-side with the witness key preserved
    (``independent_key``). ``"auto"`` (default) samples each history's
    head for KV values; True forces the strain; False keeps the
    unpartitioned path. The journal's row namespace becomes the
    (deterministically ordered) sub-history list, so a resumed run
    re-dispatches ZERO decided sub-histories.
    """
    from ..checkers.linearizable import prepare_history, wgl_check
    from ..history.core import index as index_history
    from .encode import take_rows
    if partition:
        from .partition import partition_histories, recombine_details
        parts = partition_histories(histories,
                                    force=partition is True)
        if parts is not None:
            subs, sub_hist, sub_key = parts
            inner = check_batch_tpu(
                model, subs, max_states=max_states, max_slots=max_slots,
                host_fallback=host_fallback,
                min_device_batch=min_device_batch, scheduler=scheduler,
                faults=faults, journal=journal,
                scheduler_opts=scheduler_opts, partition=False)
            return recombine_details(inner, sub_hist, sub_key,
                                     len(histories))
    if host_fallback is None:
        _cache: dict = {}

        def host_fallback(m, h):
            return wgl_check(m, h, space_cache=_cache)

    for h in histories:
        if any(op.index is None for op in h):
            index_history(h)
    prepared = [prepare_history(h) for h in histories]
    # Windows beyond the single-device kernel are encodable when the
    # mesh can shard their mask axis (the frontier path).
    eff_slots = max_slots + (device_frontier_capacity()
                             if max_slots >= DATA_MAX_SLOTS else 0)
    # The streamed path encodes fused (single-candidate runs collapse
    # into EV_FUSED steps); the exact path stays the unfused oracle.
    buckets = bucket_encode(model, prepared,
                            max_states=min(max_states, MAX_PACKED_STATES),
                            max_slots=eff_slots, fuse=scheduler)

    results: List[Optional[dict]] = [None] * len(histories)
    decided: dict = {}
    if journal is not None and scheduler:
        decided = {i: d for i, d in journal.decided().items()
                   if 0 <= i < len(histories)}
        for i, (vl, bd, pv) in decided.items():
            results[i] = _rehydrate_verdict(vl, bd, pv)
    device_batches = []
    for batch in buckets:
        if decided:
            # Resume: rows with journaled verdicts never re-dispatch.
            batch = take_rows(batch, [r for r, i in
                                      enumerate(batch.indices)
                                      if i not in decided])
        if 0 < batch.batch < min_device_batch and \
                (not scheduler or batch.W >= DATA_MAX_SLOTS):
            # Small-bucket CPU route. Under the scheduler, narrow small
            # buckets instead merge into a consolidated class — only
            # wide stragglers still ride the native engine.
            try:
                from ..native import check_batch_native
                rs = check_batch_native(model,
                                        [histories[i] for i in batch.indices])
            except Exception:
                rs = [host_fallback(model, histories[i])
                      for i in batch.indices]
            for i, r in zip(batch.indices, rs):
                if scheduler:
                    r.setdefault("provenance", "host-fallback")
                    _journal_result(journal, i, r)
                results[i] = r
        elif batch.batch:
            device_batches.append(batch)
        for i, reason in batch.failures:
            if i in decided:
                continue
            r = host_fallback(model, histories[i])
            r.setdefault("fallback", reason)
            if scheduler:
                r.setdefault("provenance", "host-fallback")
                _journal_result(journal, i, r)
            results[i] = r
    sch = None
    if scheduler:
        from .schedule import BucketScheduler
        sch = BucketScheduler(return_frontier=True, faults=faults,
                              **(scheduler_opts or {}))
        if journal is not None:
            sch.on_chunk = _batch_chunk_recorder(sch, journal)
        stream = sch.run(device_batches)
    else:
        stream = run_buckets_threaded(device_batches, return_frontier=True)
    for batch, out in stream:
        if isinstance(out, WindowOverflow):
            for i in batch.indices:
                r = host_fallback(model, histories[i])
                r.setdefault("fallback", str(out))
                if scheduler:
                    r.setdefault("provenance", "host-fallback")
                    _journal_result(journal, i, r)
                results[i] = r
            continue
        valid, bad, front = out
        valid, bad = np.asarray(valid), np.asarray(bad)
        fused = set(fused_bad_rows(batch, valid, bad).tolist())
        for row, i in enumerate(batch.indices):
            if sch is not None and i in sch.quarantined:
                continue           # placeholder; re-decided below
            if row in fused:
                # The first impossible completion fell inside a fused
                # run: the device only knows the run's first member.
                # Re-derive the exact bad op + counterexample on the
                # host — rare (invalid rows failing in a sequential
                # stretch), and the host engine is the parity shape.
                results[i] = host_fallback(model, histories[i])
                if scheduler:
                    results[i].setdefault("provenance", "host-fallback")
                    _journal_result(journal, i, results[i])
                continue
            results[i] = _result_for(row, batch, valid, bad, front,
                                     model, prepared[i])
            if sch is not None:
                results[i]["provenance"] = sch.row_provenance.get(
                    i, "device")
    if sch is not None:
        # Quarantined rows: the degradation ladder gave up on device —
        # the exact host oracle decides them, so every history still
        # gets a verdict under any fault schedule.
        for i, why in sch.quarantined.items():
            r = host_fallback(model, histories[i])
            r.setdefault("fallback", f"quarantined: {why}")
            r["provenance"] = "host-fallback"
            _journal_result(journal, i, r)
            results[i] = r
    return results


def _batch_chunk_recorder(sch, journal):
    """on_chunk hook journaling device chunk verdicts as they retire
    (check_batch_tpu shape: bad is the history-op index). Rows that
    need host re-derivation — fused-run failures, quarantined rows —
    are skipped here and journaled when their final verdict lands."""
    def on_chunk(b, lo, hi, v, bad, fr):
        rows, vals, bads, provs = [], [], [], []
        for k in range(hi - lo):
            rp = lo + k
            i = b.indices[rp]
            if i in sch.quarantined:
                continue
            vk = bool(v[k])
            bd = None
            if not vk:
                ev = int(bad[k])
                if b.ev_type[rp, ev] == EV_FUSED:
                    continue
                bd = int(b.ev_opidx[rp, ev])
            rows.append(i)
            vals.append(vk)
            bads.append(bd)
            provs.append(sch.row_provenance.get(i, "device"))
        journal.record(rows, vals, bads, provs)
    return on_chunk


def check_one_tpu(model: Model, history: List[Op], **kw) -> dict:
    """Single-history device check (the Checker-protocol TPU backend)."""
    return check_batch_tpu(model, [history], **kw)[0]


class _NativeTailWorker:
    """Chews small wide (W >= DATA_MAX_SLOTS) buckets on the native CPU
    engine, on a daemon thread UNDER the device window — the measured
    device/native cost crossover for tiny wide dispatches. ``add``
    enqueues row indices as the stream discovers them; ``finish``
    returns [(row, result-or-None)] (None = native engine failed; the
    caller re-routes those rows)."""

    def __init__(self, model: Model, cols):
        import queue
        self.model = model
        self.cols = cols
        self._q: "queue.Queue" = queue.Queue()
        self._fut: Optional[DaemonFuture] = None

    def add(self, indices) -> None:
        if self._fut is None:
            self._fut = DaemonFuture(self._run, name="jepsen-native-tail")
        self._q.put(list(indices))

    def finish(self) -> list:
        if self._fut is None:
            return []
        self._q.put(None)
        return self._fut.result()

    def _run(self) -> list:
        from ..history.columnar import columnar_to_ops
        from ..native import check_batch_native
        out = []
        while True:
            idxs = self._q.get()
            if idxs is None:
                return out
            try:
                with telemetry.span("native.tail", rows=len(idxs)):
                    rs = check_batch_native(
                        self.model,
                        [columnar_to_ops(self.cols, i) for i in idxs])
            except Exception:
                out.extend((i, None) for i in idxs)
                continue
            out.extend(zip(idxs, rs))


def _cols_take(cols, rows):
    """Row-subset of a ColumnarOps batch (the journal-resume filter)."""
    r = np.asarray(rows, np.int64)
    key = getattr(cols, "key", None)
    return type(cols)(
        type=cols.type[r], process=cols.process[r], kind=cols.kind[r],
        kinds=cols.kinds,
        index=cols.index[r] if cols.index is not None else None,
        key=key[r] if key is not None else None)


def check_columnar(model: Model, cols, *, max_slots: int = 16,
                   host_fallback=None, details=False,
                   min_device_batch: int = 1, scheduler: bool = True,
                   faults=None, journal=None,
                   scheduler_opts: Optional[dict] = None,
                   partition: object = "auto"):
    """Device-check a ColumnarOps batch end-to-end at tensor speed.

    Returns (valid [B] bool, bad [B] int32) — ``bad`` is the op index of
    the first impossible completion (the original-history index for
    converted batches, else the line position; INT32_MAX when valid).
    Rows the encoder cannot bound are converted to Op lists and routed
    to ``host_fallback`` (default: the exact host engine). In
    verdict-only and ``details="invalid"`` modes, WIDE tail buckets
    (W >= 16) smaller than ``min_device_batch`` ride the native CPU
    engine on a side thread under the device window — the measured
    device/native cost crossover; narrow small buckets stay on device,
    and ``details=True`` keeps every row there.

    With ``details=True`` the return is a list of per-row result dicts
    matching the host engine's shape — {"valid", "op", "configs"} with
    the reference's truncate-to-10 config-sample discipline
    (checker.clj:104-107) — decoded from the latched device frontiers.
    ``details="invalid"`` decodes lazily: only invalid rows pay the
    per-row Python replay walk; valid rows return {"valid": True} bare.
    The reference renders analysis only for invalid results
    (checker.clj:98-103), so this is the replay product path's mode —
    it keeps the batch at tensor speed when most rows are clean.

    ``scheduler=True`` (default) runs the whole thing through the
    streaming bucket scheduler (ops.schedule): the columnar encode walk
    chunks into groups that overlap device dispatch, exact-W buckets
    consolidate into few W classes, and verdicts land incrementally as
    chunks decode. ``scheduler=False`` keeps the fully-encoded
    exact-W flow — the parity oracle the streamed path is tested
    against.

    Fault tolerance (scheduler path; doc/resilience.md): chunks run
    under the degradation ladder — watchdog + retry, OOM bisection,
    poison-row quarantine to ``host_fallback`` — so every row gets a
    verdict under any single fault. ``faults`` injects the checker
    nemesis (ops.faults). ``journal`` (store.ChunkJournal) makes
    retired chunk verdicts durable: rows the journal already holds are
    sliced out BEFORE encoding and never re-dispatched, and fresh
    verdicts append as chunks retire — the kill-and-resume seam.
    Resumed rows' detail dicts are bare verdicts (no config sample)
    marked ``resumed``. ``scheduler_opts`` forwards BucketScheduler
    knobs (chunk_rows, max_classes, ...).

    ``partition`` (default ``"auto"``): a KEYED batch (``cols.key``,
    the columnar form of a KV-valued workload) strains into its
    per-key sub-batch before encoding (ops.partition) — the
    P-compositional W collapse — and verdicts recombine per history:
    valid iff every key is, ``bad`` the smallest original bad-op index
    over the invalid keys, and (details mode) the witness sub's result
    verbatim plus ``independent_key``. The journal then rides the
    sub-batch's deterministic row order, so a resumed run
    re-dispatches zero decided sub-histories.
    """
    if partition and getattr(cols, "key", None) is not None:
        from .partition import (partition_columnar, recombine_details,
                                recombine_verdicts)
        pb = partition_columnar(cols)
        if pb is not None:
            inner = check_columnar(
                model, pb.cols, max_slots=max_slots,
                host_fallback=host_fallback, details=details,
                min_device_batch=min_device_batch, scheduler=scheduler,
                faults=faults, journal=journal,
                scheduler_opts=scheduler_opts, partition=False)
            if details:
                return recombine_details(inner, pb.sub_history,
                                         pb.sub_key, cols.batch)
            v, b, _ = recombine_verdicts(inner[0], inner[1],
                                         pb.sub_history, pb.sub_key,
                                         cols.batch)
            return v, b
    if journal is None or not scheduler:
        return _check_columnar_impl(
            model, cols, max_slots=max_slots, host_fallback=host_fallback,
            details=details, min_device_batch=min_device_batch,
            scheduler=scheduler, faults=faults,
            scheduler_opts=scheduler_opts, sink=None)
    decided = {r: d for r, d in journal.decided().items()
               if 0 <= r < cols.batch}
    keep = [r for r in range(cols.batch) if r not in decided]
    if len(keep) == cols.batch:
        sub = cols

        def sink(rows, valid, bad, prov):
            journal.record(rows, valid, bad, prov)
    else:
        sub = _cols_take(cols, keep)

        def sink(rows, valid, bad, prov):
            journal.record([keep[int(r)] for r in rows], valid, bad,
                           prov)
    inner = _check_columnar_impl(
        model, sub, max_slots=max_slots, host_fallback=host_fallback,
        details=details, min_device_batch=min_device_batch,
        scheduler=True, faults=faults, scheduler_opts=scheduler_opts,
        sink=sink)
    if not decided:
        return inner
    if details:
        results: List[Optional[dict]] = [None] * cols.batch
        for r, (vl, bd, pv) in decided.items():
            results[r] = _rehydrate_verdict(vl, bd, pv)
        for j, r in enumerate(keep):
            results[r] = inner[j]
        return results
    valid = np.ones(cols.batch, bool)
    bad = np.full(cols.batch, INT32_MAX, np.int32)
    for r, (vl, bd, pv) in decided.items():
        valid[r] = vl
        if vl is False and bd is not None:
            bad[r] = bd
    if keep:
        k = np.asarray(keep)
        iv, ib = inner
        valid[k] = iv
        bad[k] = ib
    return valid, bad


def _check_columnar_impl(model: Model, cols, *, max_slots, host_fallback,
                         details, min_device_batch, scheduler, faults,
                         scheduler_opts, sink):
    from ..checkers.linearizable import wgl_check
    from ..history.columnar import columnar_to_ops
    from .encode import encode_columnar
    from .statespace import enumerate_statespace

    assert details in (False, True, "invalid"), details
    space = enumerate_statespace(model, cols.kinds, MAX_PACKED_STATES)
    eff_slots = max_slots + (device_frontier_capacity()
                             if max_slots >= DATA_MAX_SLOTS else 0)
    valid = np.ones(cols.batch, bool)
    bad = np.full(cols.batch, INT32_MAX, np.int32)
    results: List[Optional[dict]] = [None] * cols.batch if details else None
    failures: List[Tuple[int, str]] = []
    fused_refine: List[int] = []
    host_fallback = host_fallback or wgl_check
    # Wide-tail shortcut: measured per-row device cost doubles per W
    # while the native engine's grows far more slowly — on one chip the
    # crossover sits at W~16 (W15: 0.12 s/row device vs ~0.3 native;
    # W16: 0.77 device vs ~0.4 native). Small W>=16 buckets therefore
    # ride the native engine ON A SIDE THREAD, chewed by the
    # otherwise-idle CPU UNDER the device window (the bench's overlap
    # discipline). Verdict-only and lazy-details callers only;
    # full-details mode keeps every row on device so valid rows'
    # config samples stay device-derived. Without the native engine the
    # wide rows must STAY on device: the host fallback's cost grows
    # exponentially in W, while the device check stays bounded.
    tail = None
    if min_device_batch > 1 and details in (False, "invalid"):
        try:
            from ..native import check_batch_native  # noqa: F401
            tail = _NativeTailWorker(model, cols)
        except Exception:
            tail = None
    sch = None
    if scheduler:
        from .schedule import (DIVERTED, BucketScheduler,
                               iter_columnar_groups)
        groups = iter_columnar_groups(space, cols, max_slots=eff_slots,
                                      failures=failures, fuse=True,
                                      renumber=True)
        sch = BucketScheduler(
            return_frontier=details,
            min_device_rows=min_device_batch if tail is not None else 0,
            faults=faults, **(scheduler_opts or {}))
        if sink is not None:
            sch.on_chunk = _columnar_chunk_recorder(sch, cols, sink)
        stream = sch.run(groups)
    else:
        DIVERTED = object()       # never yielded by the threaded path
        with telemetry.span("encode.walk", rows=cols.batch):
            buckets, fails = encode_columnar(space, cols,
                                             max_slots=eff_slots)
        failures.extend(fails)
        if tail is not None:
            small = [b for b in buckets
                     if b.W >= 16 and 0 < b.batch < min_device_batch]
            small_ids = {id(b) for b in small}
            buckets = [b for b in buckets if id(b) not in small_ids]
            for b in small:
                tail.add(b.indices)
        stream = run_buckets_threaded(buckets, return_frontier=details)
    for batch, out in stream:
        if out is DIVERTED:
            tail.add(batch.indices)
            continue
        if isinstance(out, WindowOverflow):
            failures.extend((i, str(out)) for i in batch.indices)
            continue
        v, b, front = out
        idx = np.asarray(batch.indices)
        valid[idx] = v
        inv = np.nonzero(~v)[0]
        bad_rows = idx[~v]
        bad_lines = batch.ev_opidx[inv, b[~v]]
        bad[bad_rows] = (cols.index[bad_rows, bad_lines]
                         if cols.index is not None else bad_lines)
        # Rows whose first impossible completion fell inside a fused
        # run only know the run's FIRST member: re-derive exactly on
        # the host after the stream drains (fused_refine).
        fb = fused_bad_rows(batch, v, b)
        fused_refine.extend(int(idx[x]) for x in fb)
        fused_local = set(fb.tolist())
        if details:
            with telemetry.span("decode.details", rows=len(idx)):
                for bi, row in enumerate(batch.indices):
                    if sch is not None and row in sch.quarantined:
                        continue       # placeholder; host-decided below
                    if details == "invalid" and bool(v[bi]):
                        # Lazy mode's valid rows stay the bare contract
                        # dict; provenance appears only when it carries
                        # information (the row left the happy path).
                        results[row] = {"valid": True}
                        if sch is not None and row in sch.row_provenance:
                            results[row]["provenance"] = \
                                sch.row_provenance[row]
                        continue
                    if bi in fused_local:
                        continue               # refined below
                    # The columnar form already applied the prepared-history
                    # contract (value propagation + identity drop) at
                    # conversion: reconstruct with propagated invokes and
                    # skip both complete() and the per-op drop recompute —
                    # the decode walk still sees exactly the encoder's op
                    # kinds and slot assignment. Renumbered rows decode
                    # against their own sub-space (batch.spaces).
                    ops = columnar_to_ops(cols, row, propagated=True)
                    sp = batch.spaces[bi] if batch.spaces else space
                    results[row] = _decode_result(
                        sp, ops, bool(v[bi]),
                        int(bad[row]) if not bool(v[bi]) else -1, front[bi],
                        predropped=True)
                    if sch is not None:
                        results[row]["provenance"] = \
                            sch.row_provenance.get(row, "device")
    if sch is not None:
        # Rows the degradation ladder quarantined carry inert
        # placeholder verdicts in the stream: re-decide each through
        # the host engine (the failures path below), so every row gets
        # a real verdict under any fault schedule.
        failures.extend((i, f"quarantined: {why}")
                        for i, why in sch.quarantined.items())
    with telemetry.span("host.tail"):
        done = tail.finish() if tail is not None else []
        for i, r in done:
            if r is None:                    # native engine unavailable
                failures.append((i, "small bucket"))
                continue
            valid[i] = r["valid"] is True
            if r["valid"] is False:
                bad[i] = r["op"].get("index", -1)
            if details == "invalid":
                # Native verdicts lack config samples; the rare invalid
                # row re-derives its full counterexample on the host.
                results[i] = ({"valid": True} if r["valid"] is True
                              else host_fallback(
                                  model, columnar_to_ops(cols, i)))
                results[i].setdefault("provenance", "host-fallback")
            if sink is not None:
                _sink_verdict(sink, i, r)
        if fused_refine:
            # Exact bad-index/counterexample recovery for rows that failed
            # inside a fused run. Verdict-only callers ride the native
            # batch engine when it exists; details callers take the host
            # engine's full result (the parity shape).
            hs = [columnar_to_ops(cols, i) for i in fused_refine]
            rs = None
            if not details:
                try:
                    from ..native import check_batch_native
                    rs = check_batch_native(model, hs)
                except Exception:
                    rs = None
            if rs is None:
                rs = [host_fallback(model, h) for h in hs]
            for i, r in zip(fused_refine, rs):
                valid[i] = r["valid"] is True
                if r["valid"] is False:
                    bad[i] = r["op"].get("index", -1)
                if details:
                    r.setdefault("provenance", "host-fallback")
                    results[i] = r
                if sink is not None:
                    _sink_verdict(sink, i, r)
        for row, reason in failures:
            r = host_fallback(model, columnar_to_ops(cols, row))
            valid[row] = r["valid"] is True
            if r["valid"] is False:
                bad[row] = r["op"].get("index", -1)
            if details:
                r.setdefault("fallback", reason)
                r.setdefault("provenance", "host-fallback")
                results[row] = r
            if sink is not None:
                _sink_verdict(sink, row, r)
    _count_subs(cols.batch, sch, {i for i, _ in done}
                | {i for i, _ in failures} | set(fused_refine))
    if details:
        return results
    return valid, bad


_SUBS_COUNTERS: Dict[Tuple[str, str], object] = {}


def _count_subs(n_rows: int, sch, host_rows: set) -> None:
    """``check.subs{provenance=...}``: the rows one check finalized under
    each provenance (``device`` when no other applies); and, when the
    scheduler ran, ``check.routes{route=...}``: the same rows by the
    route that decided them (the scheduler's ``row_route``; ``data1``,
    its one-device chunk pipeline, when no other applies; ``host`` for
    the host engines). One increment per value."""
    counts = {"host-fallback": len(host_rows)}
    tallies = [("check.subs", "provenance", counts)]
    if sch is not None:
        routes = {"host": len(host_rows)}
        for row, prov in sch.row_provenance.items():
            if row not in host_rows:
                counts[prov] = counts.get(prov, 0) + 1
        for row, route in sch.row_route.items():
            if row not in host_rows:
                routes[route] = routes.get(route, 0) + 1
        routes["data1"] = (routes.get("data1", 0) + n_rows
                           - sum(routes.values()))
        tallies.append(("check.routes", "route", routes))
    counts["device"] = n_rows - sum(counts.values())
    for name, label, tally in tallies:
        for value, n in tally.items():
            if n:
                c = _SUBS_COUNTERS.get((name, value))
                if c is None:
                    c = _SUBS_COUNTERS[(name, value)] = \
                        telemetry.REGISTRY.counter(name, **{label: value})
                c.inc(n)


def _sink_verdict(sink, row: int, r: dict) -> None:
    """Journal one host-decided row's final verdict through a write
    callable (a check_columnar sink that remaps sub-batch rows, or
    ChunkJournal.record directly) — the ONE result-dict→journal-record
    translation, so the two checkers' journal shapes cannot drift.
    Non-boolean verdicts ("unknown") are not journaled — a resumed run
    re-derives them."""
    if r.get("valid") is True:
        sink([row], [True], [None], ["host-fallback"])
    elif r.get("valid") is False:
        sink([row], [False], [r.get("op", {}).get("index")],
             ["host-fallback"])


def _columnar_chunk_recorder(sch, cols, sink):
    """on_chunk hook journaling device chunk verdicts as they retire
    (check_columnar shape: bad is the caller-level op index, mapped
    through cols.index). Fused-run failures and quarantined rows are
    skipped — they journal when their host-derived verdict lands."""
    def on_chunk(b, lo, hi, v, bad, fr):
        rows, vals, bads, provs = [], [], [], []
        for k in range(hi - lo):
            rp = lo + k
            i = b.indices[rp]
            if i in sch.quarantined:
                continue
            vk = bool(v[k])
            bd = None
            if not vk:
                ev = int(bad[k])
                if b.ev_type[rp, ev] == EV_FUSED:
                    continue
                line = int(b.ev_opidx[rp, ev])
                bd = (int(cols.index[i, line])
                      if cols.index is not None else line)
            rows.append(i)
            vals.append(vk)
            bads.append(bd)
            provs.append(sch.row_provenance.get(i, "device"))
        sink(rows, vals, bads, provs)
    return on_chunk


def check_batch_columnar(model: Model, histories: Sequence[List[Op]], *,
                         max_slots: int = 16, max_states: int = 64,
                         host_fallback=None, details=True,
                         min_device_batch: int = 1,
                         scheduler: bool = True, faults=None,
                         journal=None,
                         scheduler_opts: Optional[dict] = None,
                         partition: object = "auto") -> List[dict]:
    """Check recorded Op-list histories through the columnar fast path:
    one fused conversion walk (history.columnar.ops_to_columnar), one
    vectorized encode, one device dispatch per cost bucket. Falls back
    to the per-history path (``check_batch_tpu``) when the shared
    vocabulary's state space explodes. Per-history result dicts;
    ``details="invalid"`` skips the valid rows' Python decode (see
    check_columnar).

    KV-valued histories (``partition``, as check_batch_tpu's) are
    checked per key: one keyed conversion walk emits each line's key,
    ``partition.strain_keyed`` strains the batch into per-(history,
    key) sub-rows, and verdicts recombine per history. A batch the
    keyed walk declines (see ops_to_columnar) or whose vocabulary
    explodes strains as Op lists instead (``partition_histories``);
    ``partition.oplist{route=columnar|ops}`` counts the histories each
    way took. Both give the same subs in the same order, so results
    and journal rows are the same either way."""
    from ..history.columnar import ops_to_columnar
    from .statespace import StateSpaceExplosion

    if not histories:
        return []
    assert details in (True, "invalid"), details   # contract: List[dict]
    kw = dict(max_slots=max_slots, host_fallback=host_fallback,
              details=details, min_device_batch=min_device_batch,
              scheduler=scheduler, faults=faults, journal=journal,
              scheduler_opts=scheduler_opts)
    if partition:
        from .partition import (history_has_kv, partition_histories,
                                recombine_details, strain_keyed)
        force = partition is True
        if force or any(history_has_kv(h) for h in histories):
            try:
                kc = ops_to_columnar(model, histories, keyed=True,
                                     max_states=min(max_states,
                                                    MAX_PACKED_STATES))
            except StateSpaceExplosion:
                kc = None
            if kc is not None and any(kc.key_labels):
                pb = strain_keyed(kc)
                telemetry.REGISTRY.counter(
                    "partition.oplist", route="columnar").inc(len(histories))
                inner = check_columnar(model, pb.cols, partition=False, **kw)
                return recombine_details(inner, pb.sub_history, pb.sub_key,
                                         len(histories))
            parts = partition_histories(histories, force=force)
            if parts is not None:
                subs, sub_hist, sub_key = parts
                telemetry.REGISTRY.counter(
                    "partition.oplist", route="ops").inc(len(histories))
                inner = check_batch_columnar(
                    model, subs, max_states=max_states, partition=False,
                    **kw)
                return recombine_details(inner, sub_hist, sub_key,
                                         len(histories))
    try:
        cols = ops_to_columnar(model, histories,
                               max_states=min(max_states,
                                              MAX_PACKED_STATES))
    except StateSpaceExplosion:
        return check_batch_tpu(model, histories, max_states=max_states,
                               max_slots=max_slots,
                               host_fallback=host_fallback,
                               min_device_batch=min_device_batch,
                               scheduler=scheduler, faults=faults,
                               journal=journal,
                               scheduler_opts=scheduler_opts)
    return check_columnar(model, cols, **kw)


def check_synth(model: Model, spec, *, synth: str = "device",
                return_meta: bool = False, **kw):
    """Generate-and-check a deterministic synthetic batch
    (ops.synth_device.SynthSpec) — the campaign/fuzz workhorse: the
    histories are born in the columnar layout on the chosen backend
    (``synth="device"`` jitted JAX; ``"numpy"`` the bit-identical host
    twin; ``"host"`` the legacy lockstep generators, byte-compatible
    with earlier rounds), then ride the full check_columnar pipeline —
    P-compositional partition via the batch's key column, streaming
    scheduler, fault ladder, and ChunkJournal resume (key journals on
    store.spec_digest(spec): the spec NAMES the batch, so a resumable
    campaign never materializes histories just to fingerprint them).
    Only the columnar families check here ("cas"/"wide"); "la" lowers
    to dependency graphs (checkers.cycle) instead. Returns
    check_columnar's shapes, plus the SynthMeta when
    ``return_meta=True``."""
    from .synth_device import synthesize
    assert spec.family in ("cas", "wide"), spec.family
    # The legacy host generators return Op lists for the wide family;
    # only cas is columnar on every backend.
    assert synth != "host" or spec.family == "cas", \
        "host-mode check_synth supports the cas family"
    cols, meta = synthesize(spec, synth, key_meta=False)
    out = check_columnar(model, cols, **kw)
    return (out, meta) if return_meta else out
