"""Frontier-sharded WGL checking — sequence parallelism for histories.

One history's WGL configuration frontier (packed words over 2^W mask
configs — jepsen_tpu.ops.linearize) can exceed a single core's VMEM when
the pending window W is large (long histories under heavy fault injection
accumulate indeterminate ops, each pinning a slot — SURVEY.md §5
"long-context"). The fix is the sequence-parallel analog for this domain:
split the mask axis across D = 2^log2D devices, so device d holds the
configs whose top log2D mask bits equal d.

  * applies/completions on slots < W_local touch only local mask bits —
    no communication;
  * an apply on top bit b maps configs (s, m w/o bit b) — which live
    entirely on devices with axis-index bit b clear — to (target s,
    m | bit b) on the partner device: a hypercube `lax.ppermute` exchange
    of the transitioned words;
  * a completion on top bit b moves the surviving words from bit-set
    devices to their bit-clear partners (the mask with the bit cleared);
  * emptiness and closure-convergence checks are `lax.psum` reductions
    over the frontier axis.

The encoder allocates low slots first (jepsen_tpu.ops.encode), so
histories whose live-pending count stays below W_local never touch the
top bits and pay only the convergence psums — communication is
proportional to how hard the history actually is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.encode import EV_CLOSE, EV_FUSED, EV_OK
from ..ops.linearize import (INT32_MAX, MAX_PACKED_STATES, _apply_slot,
                             _complete_slot, _changed, _union,
                             n_state_words, pack_rows, transition)


def _top_apply(F, b, rows_b, V, D):
    """Close one step under the op in top-bit slot b (cross-device):
    every config on a bit-clear device spawns its transitioned twin on
    the bit-set partner."""
    bit = 1 << b
    ax = lax.axis_index("frontier")
    is_clear = (ax & bit) == 0
    new = transition(F, rows_b, V)
    new = tuple(jnp.where(is_clear, n, jnp.uint32(0)) for n in new)
    perm = [(d, d | bit) for d in range(D) if not (d & bit)]
    recv = tuple(lax.ppermute(n, "frontier", perm) for n in new)
    return tuple(f | r for f, r in zip(F, recv))


def _top_complete(F, b, D):
    """OK-completion of the op in top-bit slot b: surviving words move
    from bit-set devices to their bit-clear partners."""
    bit = 1 << b
    perm = [(d | bit, d) for d in range(D) if not (d & bit)]
    recv = tuple(lax.ppermute(f, "frontier", perm) for f in F)
    ax = lax.axis_index("frontier")
    is_clear = (ax & bit) == 0
    return tuple(jnp.where(is_clear, r, jnp.uint32(0)) for r in recv)


def _pbool(x, axes=("frontier",)) -> jnp.ndarray:
    """Global any() of a local boolean over the given mesh axes."""
    return lax.psum(x.astype(jnp.int32), axes) > 0


def make_frontier_kernel(V: int, W: int, D: int,
                         sync_axes=("data", "frontier")):
    """Single-history checker with the frontier split over D devices.

    W is the *global* slot count; each device holds packed words over
    2^(W - log2 D) local mask configs. Must run inside a shard_map
    binding axis name "frontier".

    ``sync_axes``: the closure's convergence flag must reduce over EVERY
    mesh axis, not just "frontier" — a data-dependent while_loop that
    contains collectives deadlocks the moment different shards pick
    different trip counts (each device must issue an identical collective
    sequence). The global psum makes all devices run the global-max
    iteration count; extra iterations on converged shards are idempotent.
    """
    assert V <= MAX_PACKED_STATES
    log2d = D.bit_length() - 1
    assert 1 << log2d == D, "frontier axis size must be a power of two"
    W_local = W - log2d
    assert W_local >= 1
    M_local = 1 << W_local
    NW = n_state_words(V)

    def closure(F, slots_row, rows):
        tgt = tuple(r[slots_row] for r in rows)  # [W, V] per word

        def body(carry):
            F0, _ = carry
            Fn = F0
            for i in range(W_local):
                Fn = _apply_slot(Fn, i, tuple(t[i] for t in tgt),
                                 V, M_local)
            for b in range(log2d):
                Fn = _top_apply(Fn, b, tuple(t[W_local + b] for t in tgt),
                                V, D)
            return Fn, _pbool(_changed(Fn, F0), sync_axes)

        # F arrives varying over every mesh axis (the scan carry is
        # pcast below); the convergence flag is invariant — the psum in
        # the body spans every axis, so input and output types line up.
        F, _ = lax.while_loop(lambda c: c[1], body, (F, jnp.bool_(True)))
        return F

    def complete(F, slot):
        out = _complete_slot(F, jnp.minimum(slot, W_local - 1), M_local,
                             W_local)
        for b in range(log2d):
            top = _top_complete(F, b, D)
            out = tuple(jnp.where(slot == W_local + b, t, o)
                        for t, o in zip(top, out))
        return out

    def check(ev_type, ev_slot, ev_slots, target):
        # Narrow (int8) event arrays off the host; widen on device.
        ev_type = ev_type.astype(jnp.int32)
        ev_slot = ev_slot.astype(jnp.int32)
        ev_slots = ev_slots.astype(jnp.int32)
        rows = pack_rows(target, V)

        def step(carry, ev):
            F, Fbad, valid, bad = carry
            typ, slot, slots_row, idx = ev
            is_ok = (typ == EV_OK) | (typ == EV_FUSED)
            is_close = typ == EV_CLOSE
            Fc = closure(F, slots_row, rows)
            F_ok = complete(Fc, slot)
            empty = is_ok & ~_pbool((_union(F_ok) != 0).any())
            first = empty & valid
            F2 = tuple(jnp.where(is_ok, a, jnp.where(is_close, c, b))
                       for a, c, b in zip(F_ok, Fc, F))
            Fb2 = tuple(jnp.where(first, c, b) for c, b in zip(Fc, Fbad))
            return (F2, Fb2, valid & ~empty,
                    jnp.minimum(bad, jnp.where(empty, idx, INT32_MAX))), None

        N = ev_type.shape[0]
        ax = lax.axis_index("frontier")
        Fz = tuple(jnp.zeros((M_local,), jnp.uint32) for _ in range(NW))
        # Global config (state 0, mask 0) lives on frontier device 0.
        F0 = (Fz[0].at[0].set(jnp.where(ax == 0, jnp.uint32(1),
                                        jnp.uint32(0))),) + Fz[1:]
        # The scan consumes data-sharded events, so its carry is varying
        # over "data" — widen the initial carry's type to match.
        extra = tuple(a for a in sync_axes if a != "frontier")

        def pcast(x):
            return lax.pcast(x, extra, to="varying")

        def pcast_all(x):
            return lax.pcast(x, tuple(sync_axes), to="varying")

        # Fbad is written from Fc (varying over EVERY mesh axis — F0
        # derives from axis_index), so its initial value must be too.
        carry = (tuple(pcast(f) for f in F0),
                 tuple(pcast_all(f) for f in Fz),
                 pcast(jnp.bool_(True)), pcast(jnp.int32(INT32_MAX)))
        (F, Fbad, valid, bad), _ = lax.scan(
            step, carry, (ev_type, ev_slot, ev_slots,
                          jnp.arange(N, dtype=jnp.int32)))
        # Local shard of the latched frontier (mask-axis sharded; the
        # out_spec concatenation restores global mask order because the
        # top log2D mask bits ARE the frontier axis index).
        frontier = jnp.stack(
            [jnp.where(valid, a, b) for a, b in zip(F, Fbad)])
        return valid, bad, frontier

    return check


def frontier_sharded_kernel(V: int, W: int, mesh: Mesh,
                            shared_target: bool = False):
    """Batched checker over a ("data", "frontier") mesh: batch rows shard
    over "data", each row's frontier splits over "frontier". Returns
    check(ev_type [B,N], ev_slot [B,N], ev_slots [B,N,W], target)
    -> (valid [B], bad [B], frontier [B, words(V), 2^W]) — the same
    contract as the single-device kernel (ops.linearize.make_kernel), so
    production dispatch and counterexample decoding are path-agnostic.
    ``shared_target``: one replicated [K+1, V] transition table instead
    of a per-row batch."""
    D = mesh.shape["frontier"]
    kern = jax.vmap(make_frontier_kernel(V, W, D),
                    in_axes=(0, 0, 0, None if shared_target else 0))
    ev = P("data", None)
    tgt = P(None, None) if shared_target else P("data", None, None)
    sharded = shard_map(kern, mesh=mesh,
                        in_specs=(ev, ev, P("data", None, None), tgt),
                        out_specs=(P("data"), P("data"),
                                   P("data", None, "frontier")))

    # A name of its own: the profiler shows this program as
    # ``jit_frontier_check``, apart from the data-sharded kernel.
    def frontier_check(ev_type, ev_slot, ev_slots, target):
        return sharded(ev_type, ev_slot, ev_slots, target)

    return jax.jit(frontier_check)
