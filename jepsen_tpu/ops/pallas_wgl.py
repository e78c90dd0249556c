"""Pallas TPU megakernel for the packed-frontier WGL search.

The ``lax.scan`` kernel (ops.linearize) runs one event per scan step
and re-enters ``lax.while_loop`` for every closure fixpoint — per-event
XLA scheduling that leaves the hot post-partition W<=10 buckets
dispatch/latency-bound (the r05 roofline: ``hbm_util`` 0.0018). This
module hand-schedules the same search as ONE Pallas program per
history batch:

  * the packed ``[words(V), 2^W]`` frontier stays RESIDENT in VMEM
    across *all* events of a history (grid = (row blocks of 8, event
    blocks); the frontier output block re-maps to the same VMEM tile
    for every event block of a row block, so it never round-trips to
    HBM until the history is decided);
  * events stream from HBM into SMEM in ``JT_PALLAS_EVENT_BLOCK``-sized
    blocks — Pallas' pipeline fetches block k+1 while block k
    computes, the double-buffering the scan kernel pays dispatch
    overhead for; the transition rows sit in SMEM beside them, read
    as scalars;
  * closure iterations run to fixpoint IN-KERNEL (a while loop over
    VPU work on the resident frontier) instead of per-iteration XLA
    round trips, and a decided row skips the remaining event blocks
    outright (the scan must idempotently no-op through them);
  * the OK-completion filter is a static select over the W shift-half
    variants — no gathers, no ``lax.switch`` lowering hazards.

Contract parity: ``check(ev_type, ev_slot, ev_slots, target) ->
(valid, bad, frontier)`` — bit-identical outputs to
``ops.linearize.make_kernel``'s vmapped form (same encoder arrays,
same latched pre-failure closure on the first impossible completion),
so ``fused_refine``, counterexample decode, the chunk journal, and the
degradation ladder all work unchanged. The scheduler (ops.schedule)
dispatches through this kernel when the COST ROUTER prices it under
the scan (fleet.CostRouter's ``wgl-pallas`` backend, fed by the
startup rate probe below) — never hardcoded; ``JT_ROUTER_PALLAS=0``
removes the backend entirely and restores the pre-pallas path
bit-identically.

On hosts without a TPU the kernel runs in ``pltpu`` interpret mode —
orders of magnitude slower (so the router never picks it there on
measured rates) but semantically identical, which is what keeps the
parity gate (tests/test_pallas.py) on the CPU tier-1 box.
"""
from __future__ import annotations

import os
import time
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .encode import EV_CLOSE, EV_FUSED, EV_OK, EV_PAD
from .linearize import INT32_MAX, n_state_words, pack_rows

# Widest state space the Pallas kernel accepts (two 32-state words —
# the same packed bound as the scan kernel).
PALLAS_MAX_STATES = 64


def pallas_max_w() -> int:
    """Widest pending window routed to the Pallas kernel. The win is
    frontier residency + fused closure for the hot post-partition
    buckets; past ~2^10 masks the frontier dominates VMEM and the
    scan/wide routes (HBM-resident mask axis, frontier sharding) are
    the right machinery. $JT_PALLAS_MAX_W overrides."""
    try:
        return max(1, int(os.environ.get("JT_PALLAS_MAX_W", "10")))
    except ValueError:
        return 10


def event_block() -> int:
    """Events per streamed block (the HBM->VMEM pipeline quantum).
    $JT_PALLAS_EVENT_BLOCK overrides; kept a multiple of the
    scheduler's EVENT_QUANTUM so padded chunk shapes divide evenly."""
    try:
        return max(64, int(os.environ.get("JT_PALLAS_EVENT_BLOCK",
                                          "256")))
    except ValueError:
        return 256


def pallas_mode() -> str:
    """"compiled" on a TPU backend, "interpret" elsewhere (the tier-1
    parity path), "off" when disabled. $JT_PALLAS_MODE forces a mode;
    $JT_PALLAS=0 or $JT_ROUTER_PALLAS=0 disables outright (the
    restore-the-scan-path switch the acceptance gate names)."""
    if os.environ.get("JT_PALLAS", "1") == "0" or \
            os.environ.get("JT_ROUTER_PALLAS", "1") == "0":
        return "off"
    m = os.environ.get("JT_PALLAS_MODE")
    if m in ("compiled", "interpret", "off"):
        return m
    return "compiled" if jax.default_backend() == "tpu" else "interpret"


def pallas_available() -> bool:
    return pallas_mode() != "off"


def vmem_budget_bytes() -> int:
    """$JT_PALLAS_VMEM_BYTES: the VMEM budget the static footprint
    model rejects against (default 16 MiB — one TPU core's VMEM)."""
    try:
        return max(1 << 16, int(os.environ.get("JT_PALLAS_VMEM_BYTES",
                                               str(16 << 20))))
    except ValueError:
        return 16 << 20


#: Closure working-set multiplier on the resident frontier tile: the
#: fixpoint body holds the tile plus the spawned-half/select
#: temporaries per packed word (a conservative static bound, not a
#: measurement — the model must reject before launch, so it errs big).
VMEM_SCRATCH_FACTOR = 3

#: SMEM the kernel's scalar tables may take: one TPU v5e core's 1 MiB
#: (jax's pallas tpu_info table), less a quarter for Mosaic's own use.
SMEM_BUDGET_BYTES = 768 << 10


def vmem_plan(V: int, W: int, *, K1: int = 256,
              eb: Optional[int] = None,
              budget: Optional[int] = None,
              shared: bool = True) -> Dict[str, object]:
    """Static VMEM/SMEM footprint of one Pallas program instance —
    the reject-before-launch model (analysis.jaxpr_lint rule
    JTL-D-VMEM prices every supported (V, W) against it, and
    ``pallas_supports`` consults it so an OOM config is never even
    routed). VMEM holds the double-buffered [ROW_BLOCK, words(V), L]
    frontier block with its closure scratch; SMEM holds the
    double-buffered event blocks (W slot columns) and the packed
    transition rows [words(V) * K1, V] (per row block when targets
    are not shared). ``K1`` bounds the kind vocabulary; callers with a
    real vocabulary pass theirs."""
    NW, L = n_state_words(V), max(1 << int(W), MIN_LANES)
    EB = event_block() if eb is None else int(eb)
    budget = vmem_budget_bytes() if budget is None else int(budget)
    frontier = ROW_BLOCK * NW * L * 4
    vmem = frontier * (2 + VMEM_SCRATCH_FACTOR)
    rows = NW * int(K1) * V * 4 * (1 if shared else ROW_BLOCK)
    events = ROW_BLOCK * EB * (2 + int(W)) * 4
    smem = 2 * (events + rows)
    return {"frontier_bytes": frontier, "rows_bytes": rows,
            "scratch_bytes": frontier * VMEM_SCRATCH_FACTOR,
            "vmem_bytes": vmem, "smem_bytes": smem,
            "budget_bytes": budget, "smem_budget_bytes": SMEM_BUDGET_BYTES,
            "fits": vmem <= budget and smem <= SMEM_BUDGET_BYTES}


def pallas_supports(V: int, W: int, k1: Optional[int] = None,
                    shared: bool = True) -> bool:
    """Capability gate: the shapes this kernel hosts. Wider windows
    belong to the scan/wide/frontier routes; the router only ever
    PRICES pallas for shapes this admits. A config whose static VMEM
    footprint (vmem_plan) exceeds the budget is rejected HERE —
    before routing, pricing, or launch. ``k1`` is the real kind-
    vocabulary bound (rows table [NW, K1, V]) and ``shared`` whether
    one table serves the whole batch; callers that have the encoded
    target in hand (the scheduler's route gate, the kernel builder)
    MUST pass both — the default prices vmem_plan's nominal bound,
    which a rich vocabulary can exceed many times over."""
    if not (1 <= int(W) <= pallas_max_w()
            and 1 <= int(V) <= PALLAS_MAX_STATES):
        return False
    kw = {} if k1 is None else {"K1": int(k1)}
    return bool(vmem_plan(V, W, shared=shared, **kw)["fits"])


def pallas_supports_resume() -> bool:
    """The kernel-contract resume seam (make_kernel(resume=True) —
    the packed carry flowing OUT of one dispatch and back IN to the
    next) has no Pallas twin: this kernel's frontier is VMEM-resident
    for exactly one launch and never round-trips through HBM between
    dispatches — that residency IS its launch economics. The online
    incremental path (ops.schedule.ResidentFrontier) therefore always
    carries its frontier through the lax.scan resume kernel; the
    router prices the delta path accordingly (fleet.CostRouter
    .price_online_tick)."""
    return False


# --------------------------------------------------------- kernel body

#: Batch rows per grid step: the sublane quantum, so every SMEM/VMEM
#: block's second-to-last dim divides by 8 (the Mosaic tiling rule).
ROW_BLOCK = 8

#: Narrowest mask axis the kernel computes on: one full 128-lane vreg
#: row. Narrower windows run at this width (a vreg op costs the same
#: at 8 lanes as at 128) and the wrapper slices the frontier back.
MIN_LANES = 128


def _kernel_body(V: int, W: int, WL: int, EB: int, Wt: int, K1: int,
                 shared: bool):
    """Build the Pallas kernel function for static (V, W, w_live,
    event-block, slot-width, vocabulary, target-sharing) bounds. Grid
    is (row blocks, event blocks); per grid step the body advances
    ROW_BLOCK rows' resident frontiers through EB events. Event
    tables and the packed transition rows live in SMEM (scalar reads
    at dynamic indices); the frontier is per-word ``[1, L]`` int32
    lane vectors in VMEM, and the mask-bit shuffles of the scan
    kernel's reshapes become lane rolls plus selects. The closure and
    completion math mirror ops.linearize word for word, which is what
    makes the parity gate bit-exact."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NW = n_state_words(V)
    L = max(1 << W, MIN_LANES)

    def _lanes():
        return lax.broadcasted_iota(jnp.int32, (1, L), 1)

    def _apply(Ft, coefs):
        # One slot application over every packed config: mirrors
        # linearize._apply_slot + transition. ``coefs[i][w][s]``: the
        # packed word w of state s's target under slot i's op (all
        # zero for empty slots => no-op). Masks with bit i set take the
        # successor computed at the mask with bit i clear (lane m-lo).
        lanes = _lanes()
        out_words = list(Ft)
        for i in range(WL):
            lo = 1 << i
            has_i = (lanes & lo) != 0
            new = [None] * NW
            for s in range(V):
                bit = (out_words[s >> 5] >> (s & 31)) & 1
                for w in range(NW):
                    contrib = bit * coefs[i][w][s]
                    new[w] = contrib if new[w] is None else new[w] | contrib
            out_words = [
                jnp.where(has_i, f | pltpu.roll(n, lo, 1), f)
                for f, n in zip(out_words, new)]
        return tuple(out_words)

    def _any(x):
        return jnp.max(jnp.where(x != 0, 1, 0)) > 0

    def _closure(Ft, coefs):
        # Fixpoint in-kernel: monotone OR, <= live-slot iterations;
        # the while carry is the resident frontier itself.
        def cond(c):
            return c[-1]

        def body(c):
            F0 = c[:NW]
            Fn = _apply(F0, coefs)
            diff = Fn[0] ^ F0[0]
            for a, b in zip(Fn[1:], F0[1:]):
                diff = diff | (a ^ b)
            return Fn + (_any(diff),)

        out = lax.while_loop(cond, body, Ft + (jnp.bool_(True),))
        return out[:NW]

    def _complete(Ft, slot):
        # OK-completion for a DYNAMIC slot as a select over the WL
        # static variants (linearize._complete_slot's branches): mask
        # m without bit i takes the config at m+lo, masks with it die.
        lanes = _lanes()
        out = None
        for i in range(WL):
            lo = 1 << i
            keep = (lanes & lo) == 0
            comp = tuple(jnp.where(keep, pltpu.roll(f, L - lo, 1), 0)
                         for f in Ft)
            if out is None:
                out = comp
            else:
                sel = slot == i
                out = tuple(jnp.where(sel, c, o)
                            for c, o in zip(comp, out))
        return out

    def _coef(rows_ref, r, w, k, s):
        if shared:
            return rows_ref[w * K1 + k, s]
        return rows_ref[r, (w * K1 + k) * V + s]

    def kernel(ev_type_ref, ev_slot_ref, ev_slots_ref, rows_ref,
               valid_ref, bad_ref, front_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            for r in range(ROW_BLOCK):
                valid_ref[r, 0] = jnp.int32(1)
                bad_ref[r, 0] = jnp.int32(INT32_MAX)
            w_ids = lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, NW, L), 1)
            m_ids = lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, NW, L), 2)
            front_ref[...] = jnp.where((w_ids == 0) & (m_ids == 0),
                                       jnp.int32(1), jnp.int32(0))

        def row_step(r, carry):
            def ev_step(e, carry):
                typ = ev_type_ref[r, e]
                # A decided row (first impossible completion already
                # latched) skips every remaining event outright — the
                # scan kernel has to idempotently no-op through them.
                live = (valid_ref[r, 0] == 1) & (typ != EV_PAD)

                @pl.when(live)
                def _():
                    slot = ev_slot_ref[r, e]
                    Ft = tuple(front_ref[r, pl.ds(w, 1), :]
                               for w in range(NW))
                    coefs = []
                    for i in range(WL):
                        k_i = ev_slots_ref[r, e * Wt + i]
                        coefs.append(tuple(
                            tuple(_coef(rows_ref, r, w, k_i, s)
                                  for s in range(V))
                            for w in range(NW)))
                    Fc = _closure(Ft, coefs)
                    F_ok = _complete(Fc, slot)
                    union = F_ok[0]
                    for f in F_ok[1:]:
                        union = union | f
                    is_ok = (typ == EV_OK) | (typ == EV_FUSED)
                    is_close = typ == EV_CLOSE
                    empty = is_ok & jnp.logical_not(_any(union))

                    @pl.when(empty)
                    def _fail():
                        # Latch the pre-completion closure — the
                        # frontier the host decodes the Knossos-parity
                        # counterexample sample from.
                        valid_ref[r, 0] = jnp.int32(0)
                        bad_ref[r, 0] = nb * EB + e
                        for w in range(NW):
                            front_ref[r, pl.ds(w, 1), :] = Fc[w]

                    @pl.when(jnp.logical_not(empty))
                    def _advance():
                        for w in range(NW):
                            front_ref[r, pl.ds(w, 1), :] = jnp.where(
                                is_ok, F_ok[w],
                                jnp.where(is_close, Fc[w], Ft[w]))

                return carry

            return lax.fori_loop(0, EB, ev_step, carry)

        lax.fori_loop(0, ROW_BLOCK, row_step, jnp.int32(0))

    return kernel


def make_pallas_kernel(V: int, W: int, *, shared_target: bool = False,
                       w_live: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Build the batched Pallas checker with the registry-kernel
    contract: ``check(ev_type [B,N], ev_slot [B,N], ev_slots [B,N,Wt],
    target [K+1,V] | [B,K+1,V]) -> (valid [B] bool, bad [B] int32,
    frontier [B, words(V), 2^W] uint32)``. jit-wrapped; one trace per
    input shape, exactly like the scan kernels."""
    assert pallas_supports(V, W), (V, W)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NW, M = n_state_words(V), 1 << W
    L = max(M, MIN_LANES)
    RB = ROW_BLOCK
    WL = W if w_live is None else max(1, min(int(w_live), W))
    EB = event_block()
    if interpret is None:
        interpret = pallas_mode() != "compiled"
    kw: dict = {}
    if not interpret:
        # Row blocks are independent grid steps; event blocks of one
        # row block run in order (the resident frontier carries).
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    def check(ev_type, ev_slot, ev_slots, target):
        B, N = ev_type.shape
        Wt = ev_slots.shape[2]
        K1 = int(target.shape[-2])
        # The launch gate with the REAL rows table: the build-time
        # pallas_supports assert prices the nominal K1 bound, but the
        # actual kind vocabulary arrives here, per shape — an
        # over-budget config must fail loudly at trace time, never
        # reach the pallas_call.
        plan = vmem_plan(V, W, K1=K1, shared=shared_target)
        if not plan["fits"]:
            raise ValueError(
                f"pallas config V={V} W={W} K1={K1} needs "
                f"{plan['vmem_bytes']} B VMEM / {plan['smem_bytes']} B "
                f"SMEM (> budgets {plan['budget_bytes']} / "
                f"{plan['smem_budget_bytes']}) — rejected before launch")
        Bp = -(-B // RB) * RB
        Np = -(-N // EB) * EB
        # EV_PAD steps are no-ops; slot tables pad to the all-invalid
        # sentinel row like every other pad path. Pad rows are EV_PAD
        # throughout and sliced off below.
        ev_type = jnp.pad(ev_type.astype(jnp.int32),
                          ((0, Bp - B), (0, Np - N)))
        ev_slot = jnp.pad(ev_slot.astype(jnp.int32),
                          ((0, Bp - B), (0, Np - N)))
        ev_slots = jnp.pad(ev_slots.astype(jnp.int32),
                           ((0, Bp - B), (0, Np - N), (0, 0)),
                           constant_values=K1 - 1).reshape(Bp, Np * Wt)
        packed = [lax.bitcast_convert_type(p, jnp.int32)
                  for p in pack_rows(target, V)]
        smem = pltpu.SMEM
        if shared_target:
            rows = jnp.concatenate(packed, axis=0)       # [NW*K1, V]
            rows_spec = pl.BlockSpec((NW * K1, V), lambda b, nb: (0, 0),
                                     memory_space=smem)
        else:
            rows = jnp.stack(packed, axis=1).reshape(B, NW * K1 * V)
            rows = jnp.pad(rows, ((0, Bp - B), (0, 0)))
            rows_spec = pl.BlockSpec((RB, NW * K1 * V),
                                     lambda b, nb: (b, 0),
                                     memory_space=smem)
        kernel = _kernel_body(V, W, WL, EB, Wt, K1, shared_target)
        valid_i, bad, front = pl.pallas_call(
            kernel,
            grid=(Bp // RB, Np // EB),
            in_specs=[
                pl.BlockSpec((RB, EB), lambda b, nb: (b, nb),
                             memory_space=smem),
                pl.BlockSpec((RB, EB), lambda b, nb: (b, nb),
                             memory_space=smem),
                pl.BlockSpec((RB, EB * Wt), lambda b, nb: (b, nb),
                             memory_space=smem),
                rows_spec,
            ],
            out_shape=(
                jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
                jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
                jax.ShapeDtypeStruct((Bp, NW, L), jnp.int32),
            ),
            out_specs=(
                pl.BlockSpec((RB, 1), lambda b, nb: (b, 0),
                             memory_space=smem),
                pl.BlockSpec((RB, 1), lambda b, nb: (b, 0),
                             memory_space=smem),
                pl.BlockSpec((RB, NW, L), lambda b, nb: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ),
            interpret=interpret,
            **kw,
        )(ev_type, ev_slot, ev_slots, rows)
        front = lax.bitcast_convert_type(front[:B, :, :M], jnp.uint32)
        return valid_i[:B, 0] != 0, bad[:B, 0], front

    return jax.jit(check)


# ------------------------------------------------------------ registry

_PALLAS_REGISTRY: Dict[Tuple, object] = {}


def get_pallas_kernel(V: int, W: int, *, shared_target: bool = False,
                      w_live: Optional[int] = None):
    """Resolve (build + cache) the compiled Pallas checker — the
    pallas twin of linearize.get_kernel. Keyed per (V, W, sharing,
    w_live, mode); jit handles per-shape compiles underneath."""
    if w_live is None or w_live >= W:
        w_live = W
    key = (V, W, bool(shared_target), int(w_live), pallas_mode())
    k = _PALLAS_REGISTRY.get(key)
    if k is None:
        k = make_pallas_kernel(V, W, shared_target=shared_target,
                               w_live=w_live)
        _PALLAS_REGISTRY[key] = k
    return k


# ----------------------------------------------------- startup probe

def make_probe_batch(V: int = 4, W: int = 6, rows: int = 32,
                     events: int = 64):
    """Synthetic always-valid encoded arrays exercising the full
    closure + completion math with no model machinery: one identity op
    resident in slot 0, completed every event. The probe and the
    bench's backend_compare section both measure against this."""
    K1 = 2
    ev_type = np.full((rows, events), EV_OK, np.int8)
    ev_slot = np.zeros((rows, events), np.int8)
    ev_slots = np.full((rows, events, W), K1 - 1, np.int8)
    ev_slots[:, :, 0] = 0
    target = np.full((K1, V), -1, np.int32)
    target[0] = np.arange(V, dtype=np.int32)
    return ev_type, ev_slot, ev_slots, target


def _time_kernel(kern, args, repeats: int = 3) -> float:
    jax.block_until_ready(kern(*args))          # compile outside clock
    ts = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(kern(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def probe_rates(rows: int = 32, events: int = 64, V: int = 4,
                W: int = 6, repeats: int = 3) -> dict:
    """The startup rate probe: measure both WGL device backends'
    sustained rate on the same tiny workload, in the cost router's
    own units (frontier-lane events per second — the ``n_events * 2^W
    / rate`` basis price_wgl divides by). Returns
    ``{"lane_ops_per_s", "pallas_lane_ops_per_s", "probe_s", "mode",
    "parity"}``; the pallas rate is 0.0 when the kernel is disabled or
    disagrees with the scan, which prices it out of every route. A
    kernel that fails to compile or run raises."""
    from .linearize import get_kernel

    args = make_probe_batch(V, W, rows, events)
    basis = rows * events * float(1 << W)
    out = {"mode": pallas_mode(), "probe_s": None, "parity": None,
           "lane_ops_per_s": 0.0, "pallas_lane_ops_per_s": 0.0}
    t_all = time.perf_counter()
    xk = get_kernel(V, W, shared_target=True)
    out["lane_ops_per_s"] = basis / max(_time_kernel(xk, args, repeats),
                                        1e-9)
    if pallas_available() and pallas_supports(V, W):
        pk = get_pallas_kernel(V, W, shared_target=True)
        out["pallas_lane_ops_per_s"] = basis / max(
            _time_kernel(pk, args, repeats), 1e-9)
        xv, xb, xf = (np.asarray(a) for a in xk(*args))
        pv, pb, pf = (np.asarray(a) for a in pk(*args))
        out["parity"] = bool(
            (xv == pv).all() and (xb == pb).all() and (xf == pf).all())
        if out["parity"] is False:
            # A kernel that disagrees with the scan must never win a
            # route on speed.
            out["pallas_lane_ops_per_s"] = 0.0
    out["probe_s"] = round(time.perf_counter() - t_all, 4)
    return out


def router_prefers_pallas(V: int, W: int, n_events: int,
                          rows: int = 1,
                          rates: Optional[dict] = None) -> bool:
    """The scheduler's routing question, answered by the fleet cost
    router's own pricing (never a hardcoded preference): does the
    measured ``wgl-pallas`` rate undercut ``wgl-device`` for this
    bucket shape? False whenever the kernel is unavailable,
    unsupported, or unpriced (no probe ran and no rate is pinned)."""
    if not (pallas_available() and pallas_supports(V, W)):
        return False
    from ..fleet import CostRouter
    costs = CostRouter(rates=rates).price_wgl(W, int(n_events),
                                              max(int(rows), 1))
    pc = costs.get("wgl-pallas")
    return pc is not None and pc < costs["wgl-device"]
