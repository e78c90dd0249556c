"""Least per-chip work of the frontier-sharded WGL check, from each
register's lines alone.

The frontier route splits a row's mask axis over the ``chips`` devices
of the mesh: each chip holds 2^W / chips masks of ``words(V)`` packed
words (``jepsen_tpu.parallel.frontier``). At each real event (an
``:ok`` completion that counts, as ``benchmark/workmodel.py`` finds
them) a chip must at least touch each of its words once, and the
closure's pass exchanges its local frontier with its hypercube partner
once per top mask bit (log2 chips ``ppermute`` exchanges). W here is
the row's own peak pending width, the least mask axis a dense frontier
of that row needs; a row checked at a wider class does more. Counted:
lane operations (one per word per event) and the bytes the exchanges
move (4 per word), per chip.
"""
from __future__ import annotations

from benchmark import workmodel


def key_work(typ, proc, f, a, b, chips: int) -> dict:
    """Least per-chip lane operations and exchanged bytes for one
    register's lines (the generator's codes, in history order)."""
    w = workmodel.key_work(typ, proc, f, a, b)
    local = (1 << w["peak"]) * workmodel.words(w["states"]) / chips
    hops = chips.bit_length() - 1
    return {"ops": w["events"] * local,
            "bytes": w["events"] * hops * local * 4}


def history_work(pool, i: int, chips: int) -> dict:
    """Sum of ``key_work`` over the keys of pool history ``i``."""
    tot = {"ops": 0.0, "bytes": 0.0}
    for k in range(pool.keys):
        lines = pool.key_lines(i, k)
        w = key_work(*(x[i, lines].tolist() for x in (
            pool.typ, pool.proc, pool.f, pool.a, pool.b)), chips)
        for name in tot:
            tot[name] += w[name]
    return tot
