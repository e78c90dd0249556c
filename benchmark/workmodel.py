"""Least work of the dense packed-frontier WGL check, from each
register's lines alone.

The frontier holds, for every subset of the live pending operations (a
mask) and every register state, one bit; the states of one mask are
packed into ceil(V / 32) words of 32 lanes. A dense method must, at
least, once per group of invocations before an ``:ok`` completion,
apply every live operation once to every mask that lacks it (one
closure pass), and at every ``:ok`` keep the half of the masks that
hold the completed operation and test the rest for emptiness. The
counts follow the data only: real events, the live pending width at
each event and the state count, never the iterations an instrumented
kernel reports, so the number is the same whichever backend took the
row. A method that does less work per configuration (a sparse
frontier, a different search) reads above this model's share, and the
model must then be recounted in a benchmark PR.

Per closure pass at live width ``w`` over ``V`` states, in 32-bit lane
operations: ``w * 2**(w-1) * (4 * V + NW)``: for each of the
``2**(w-1)`` masks lacking the slot and each state, extract the state
bit (shift, and), select the target row (multiply) and merge it (or),
then one merge per word into the masks with the slot. Per ``:ok``:
``2**(w-1) * NW`` lane moves and ``2**w * NW`` for the emptiness test.
Bytes: the encoded event table a row must read once (a type, a slot
and a ``w_peak``-wide slot snapshot per real event, one byte each), its
transition table (4 bytes per state per operation kind) and a verdict.
"""
from __future__ import annotations

INVOKE, OK, FAIL, INFO = 0, 1, 2, 3
READ = 0


def words(V: int) -> int:
    return (V + 31) // 32


def closure_ops(w: int, V: int) -> int:
    if w <= 0:
        return 0
    return w * (1 << (w - 1)) * (4 * V + words(V))


def complete_ops(w: int, V: int) -> int:
    if w <= 0:
        return 0
    return ((1 << (w - 1)) + (1 << w)) * words(V)


def key_work(typ, proc, f, a, b) -> dict:
    """Least lane operations and bytes for one register's lines (the
    generator's codes, in history order)."""
    n = len(typ)
    done = [None] * n
    open_ = {}
    for j in range(n):
        if typ[j] == INVOKE:
            open_[proc[j]] = j
        else:
            i = open_.pop(proc[j], None)
            if i is not None:
                done[i] = j
    counted = set()          # completion lines of operations that count
    for j in range(n):
        c = done[j]
        if typ[j] == INVOKE and c is not None and typ[c] != FAIL and not (
                typ[c] == INFO and f[j] == READ):
            counted.add(c)
    states = {-2}            # the initial value
    kinds = set()
    for j in range(n):
        if typ[j] != INVOKE and j in counted:
            states.update(v for v in (a[j], b[j]) if v >= 0 or v == -2)
            kinds.add((f[j], a[j], b[j]))
    V = len(states)
    live = peak = events = ops = 0
    fresh = False            # an invocation since the last closure
    for j in range(n):
        if typ[j] == INVOKE:
            if done[j] in counted:
                live += 1
                peak = max(peak, live)
                fresh = True
        elif typ[j] == OK and j in counted:
            if fresh:
                ops += closure_ops(live, V)
                fresh = False
            ops += complete_ops(live, V)
            events += 1
            live -= 1
    nbytes = events * (2 + peak) + 4 * V * (len(kinds) + 1) + 8
    return {"ops": ops, "bytes": nbytes, "events": events, "peak": peak,
            "states": V}


def history_work(pool, i: int) -> dict:
    """Sum of ``key_work`` over the keys of pool history ``i``."""
    tot = {"ops": 0, "bytes": 0, "events": 0}
    for k in range(pool.keys):
        lines = pool.key_lines(i, k)
        w = key_work(*(x[i, lines].tolist() for x in (
            pool.typ, pool.proc, pool.f, pool.a, pool.b)))
        for name in tot:
            tot[name] += w[name]
    return tot


def least_time(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of the VPU and the HBM time, and
    which of the two it is."""
    vpu = ops / peaks["vpu_int32_ops_per_s"]["value"]
    hbm = nbytes / peaks["hbm_bytes_per_s"]["value"]
    return (vpu, "vpu") if vpu >= hbm else (hbm, "hbm")
