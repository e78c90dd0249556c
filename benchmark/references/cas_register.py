"""Plain reference: linearizability of one compare-and-set register.

The same semantics as Knossos' ``cas-register`` model under the
Wing-Gong-Lowe search, written from the definitions and importing
nothing of the program. A configuration is (set of pending operations
already linearized, register value). An invocation adds a pending
operation and closes the set under linearizing any pending one; an
``:ok`` completion keeps the configurations in which its operation is
linearized, and the history is not linearizable at the first ``:ok``
that keeps none. ``:fail`` operations never happened; ``:info`` ones
stay pending to the end. A read takes the value its ``:ok`` returned;
a read that never returned constrains nothing and is left out.

``info_as_fail=True`` is the control: it breaks the guarantee the
configurations state for ``:info`` (an indeterminate operation may have
taken effect) by treating every ``:info`` operation as failed.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

INVOKE, OK, FAIL, INFO = 0, 1, 2, 3
READ, WRITE, CAS = 0, 1, 2
INIT = -2                   # the register's initial value
LO, NV = -2, 34             # values lie in [LO, LO + NV)


def check_key(typ, proc, f, a, b, index, *, info_as_fail=False
              ) -> Tuple[bool, Optional[int]]:
    """One register's lines, in history order (plain sequences).
    Values are small ints (the initial value included). Returns
    (linearizable, index of the first ``:ok`` line that no
    linearization admits, or None)."""
    n = len(typ)
    # Pair each invocation with its completion, by process.
    done = [None] * n
    open_ = {}
    for j in range(n):
        if typ[j] == INVOKE:
            open_[proc[j]] = j
        else:
            i = open_.pop(proc[j], None)
            if i is not None:
                done[i] = j
    slot_of = {}                 # completion line -> slot
    free = list(range(64))
    pending = {}                 # slot -> (bit, successor of each value)
    configs = {(0, INIT - LO)}   # values are kept as v - LO, from 0
    vals = range(NV)

    def successors(fn, x, y):
        """Register value after the operation, for every value before
        it (None where the operation cannot take effect)."""
        if fn == WRITE:
            return tuple(x - LO for _ in vals)
        if fn == CAS:
            return tuple(y - LO if v == x - LO else None for v in vals)
        return tuple(v if v == x - LO else None for v in vals)

    for j in range(n):
        t = typ[j]
        if t == INVOKE:
            c = done[j]
            if c is None or typ[c] == FAIL:
                continue
            if typ[c] == INFO and (f[j] == READ or info_as_fail):
                continue
            s = free.pop(0)
            slot_of[c] = s
            bit = 1 << s
            nxt = successors(f[j], a[c] if f[j] == READ else a[j], b[j])
            pending[s] = (bit, nxt)
            ops = list(pending.values())
            # The set is closed under the older pending operations, so
            # only configurations that linearize the new one are new.
            work = {(m | bit, nxt[v]) for m, v in configs
                    if nxt[v] is not None}
            work = [cf for cf in work if cf not in configs]
            configs.update(work)
            while work:
                m, v = work.pop()
                for bit2, nxt2 in ops:
                    v2 = nxt2[v]
                    if v2 is None or m & bit2:
                        continue
                    c2 = (m | bit2, v2)
                    if c2 not in configs:
                        configs.add(c2)
                        work.append(c2)
        elif t == OK:
            s = slot_of.pop(j, None)
            if s is None:
                continue
            bit = 1 << s
            configs = {(m & ~bit, v) for m, v in configs if m & bit}
            del pending[s]
            free.append(s)
            free.sort()
            if not configs:
                return False, int(index[j])
    return True, None


def check_history(pool, i: int, *, info_as_fail=False
                  ) -> Tuple[bool, Optional[int]]:
    """History ``i`` of a generator Pool: linearizable iff every key
    is (locality); the first bad line is the smallest over the keys."""
    bad = []
    for k in range(pool.keys):
        lines = pool.key_lines(i, k)
        ok, at = check_key(*(x[i, lines].tolist() for x in (
            pool.typ, pool.proc, pool.f, pool.a, pool.b)), lines.tolist(),
            info_as_fail=info_as_fail)
        if not ok:
            bad.append(at)
    return (not bad, min(bad) if bad else None)


def check_histories(pool, rows, *, info_as_fail=False):
    """{row: (valid, bad line)} over the given pool rows."""
    return {int(i): check_history(pool, int(i), info_as_fail=info_as_fail)
            for i in np.asarray(rows).tolist()}
