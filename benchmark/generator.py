"""The benchmark's one traffic generator: Jepsen ``independent`` register
histories, made from a seed.

Adapted from the repo's ``workloads/synth.py:synth_cas_columnar`` (a
lockstep numpy simulation of client threads against a register). The
copy differs where Jepsen's ``independent`` workload differs from that
generator: every key has a thread group of its own
(``suites/etcd.py:workload``, ``suites/cockroachdb.py:register_workload``
both build ``independent.concurrent_generator``), a thread whose
operation ends ``:info`` is retired and comes back under a fresh process
id, a cas that misses completes ``:fail``, a thread draws its operations
from its role's mix (cockroach reserves half its threads for
writes and cas), and the nemesis writes its own unkeyed lines.

Everything here is numpy over all (history, key) rows at once; the
result is a ``Pool`` of plain arrays. ``Pool.op_list(i)`` builds the
Op-list form the system under test receives (the only place the
program's types appear); the plain reference reads the arrays.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# Line types and functions in the arrays.
INVOKE, OK, FAIL, INFO = 0, 1, 2, 3
READ, WRITE, CAS = 0, 1, 2
FNAMES = ("read", "write", "cas")
TNAMES = ("invoke", "ok", "fail", "info")
NONE = -1          # value slot holds nothing (a read's invocation)
INIT = -2          # the register's initial value (config "initial")


@dataclass
class Pool:
    """``n`` histories of ``keys`` registers each. Per line, in each
    history's own order (arrays [n, L]): ``key`` (-1 for nemesis
    lines), ``proc`` (-1 for nemesis lines), ``typ``, ``f`` (-1 for
    nemesis), ``a`` and ``b`` (values; ``b`` only for cas). ``corrupt``
    marks the histories in which one read was altered."""

    key: np.ndarray
    proc: np.ndarray
    typ: np.ndarray
    f: np.ndarray
    a: np.ndarray
    b: np.ndarray
    corrupt: np.ndarray
    keys: int
    initial: object

    @property
    def n(self) -> int:
        return int(self.key.shape[0])

    def value(self, v: int):
        return None if v == NONE else self.initial if v == INIT else int(v)

    def take(self, rows) -> "Pool":
        """The pool of the given rows, in that order."""
        rows = np.asarray(rows, np.int64)
        return Pool(*(x[rows] for x in (self.key, self.proc, self.typ,
                                        self.f, self.a, self.b,
                                        self.corrupt)),
                    keys=self.keys, initial=self.initial)

    def key_lines(self, i: int, k: int) -> np.ndarray:
        """Line positions of key ``k`` in history ``i``."""
        return np.flatnonzero(self.key[i] == k)

    def op_list(self, i: int) -> list:
        """History ``i`` as the program's KV-valued Op list, indexed."""
        from jepsen_tpu.history.ops import Op
        from jepsen_tpu.independent import KV
        val = self.value
        out = []
        key, proc, typ, f, a, b = (x[i].tolist() for x in (
            self.key, self.proc, self.typ, self.f, self.a, self.b))
        for j in range(len(key)):
            t = TNAMES[typ[j]]
            if key[j] < 0:
                out.append(Op("nemesis", t, "start" if a[j] else "stop",
                              None, index=j))
                continue
            fj = f[j]
            v = [val(a[j]), val(b[j])] if fj == CAS else val(a[j])
            out.append(Op(proc[j], t, FNAMES[fj], KV(key[j], v), index=j))
        return out


def _simulate(rng, R: int, n_ops: int, T: int, roles: np.ndarray,
              mix: np.ndarray, n_values: int, p_start: float,
              n_info: int):
    """Lockstep simulation of R independent registers, T client threads
    each, ``n_ops`` operations per register, ``n_info`` of them writes or
    cas that complete ``:info``. Returns per-row line
    arrays [R, 2 * n_ops] (thread, generation, typ, f, a, b) in the
    row's own order. Every operation takes effect at its completion
    (an ``:info`` one with probability 1/2), so the histories are
    linearizable by construction."""
    N = 2 * n_ops
    thr = np.zeros((R, N), np.int32)
    gen_ = np.zeros((R, N), np.int32)
    typ = np.zeros((R, N), np.int8)
    f = np.zeros((R, N), np.int8)
    a = np.full((R, N), NONE, np.int32)
    b = np.full((R, N), NONE, np.int32)
    reg = np.full(R, INIT, np.int32)
    busy = np.full((R, T), -1, np.int8)
    ba = np.zeros((R, T), np.int32)
    bb = np.zeros((R, T), np.int32)
    gen = np.zeros((R, T), np.int32)
    started = np.zeros(R, np.int32)
    live = np.zeros(R, np.int32)
    rows = np.arange(R)
    cum = np.cumsum(mix, axis=1)             # per role: cumulative weights
    # Exactly ``n_info`` of each register's writes and cas complete
    # :info: those whose ordinal among its mutations was drawn from the
    # first ``M``, which every register reaches. So every seed gives
    # every register the same number of pinned slots, and the same
    # pending-window profile, in another order.
    mut_share = float(np.mean((mix[:, 1:].sum(1) / mix.sum(1))[roles]))
    M = max(n_info, int(0.5 * n_ops * mut_share))
    info_at = np.zeros((R, M + 1), bool)
    np.put_along_axis(info_at, np.argsort(rng.random((R, M)), axis=1)[
        :, :n_info], True, axis=1)
    mut_done = np.zeros(R, np.int32)
    for pos in range(N):
        start = (started < n_ops) & (live < T) & (
            (live == 0) | (rng.random(R) < p_start))
        i = rows[start]
        if len(i):
            score = rng.random((len(i), T))
            score[busy[i] != -1] = -1.0
            p = score.argmax(1)
            u = rng.random(len(i)) * cum[roles[p], -1]
            fi = (u[:, None] >= cum[roles[p]]).sum(1).astype(np.int8)
            va = rng.integers(0, n_values, len(i))
            vb = rng.integers(0, n_values, len(i))
            busy[i, p], ba[i, p], bb[i, p] = fi, va, vb
            thr[i, pos], gen_[i, pos], typ[i, pos], f[i, pos] = \
                p, gen[i, p], INVOKE, fi
            a[i, pos] = np.where(fi == READ, NONE, va)
            b[i, pos] = np.where(fi == CAS, vb, NONE)
            started[i] += 1
            live[i] += 1
        i = rows[~start]
        if len(i):
            score = rng.random((len(i), T))
            score[busy[i] == -1] = -1.0
            p = score.argmax(1)
            fi, va, vb = busy[i, p], ba[i, p], bb[i, p]
            mut = fi != READ
            info = mut & info_at[i, np.minimum(mut_done[i], M)]
            mut_done[i] += mut
            takes = ~info | (rng.random(len(i)) < 0.5)
            cur = reg[i]
            miss = (fi == CAS) & (cur != va)
            t = np.where(miss & ~info, FAIL, np.where(info, INFO, OK))
            apply = (fi != READ) & ~miss & takes
            reg[i[apply]] = np.where(fi[apply] == WRITE, va[apply],
                                     vb[apply])
            thr[i, pos], gen_[i, pos], typ[i, pos], f[i, pos] = \
                p, gen[i, p], t, fi
            a[i, pos] = np.where(fi == READ, cur, va)
            b[i, pos] = np.where(fi == CAS, vb, NONE)
            busy[i, p] = -1
            gen[i[info], p[info]] += 1       # a crashed thread's new process
            live[i] -= 1
    return thr, gen_, typ, f, a, b


def generate(config: dict, n: int, seed: int, n_corrupt: int) -> Pool:
    """``n`` histories of the configuration, from ``seed``, ``n_corrupt``
    of them with one read altered. The same seed gives the same pool;
    every seed gives the same sizes (keys, operations per key, lines per
    history) in a different order."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    K, n_ops = config["keys_per_history"], config["ops_per_key"]
    T, nv = config["threads_per_key"], config["n_values"]
    roles, mix = [], []
    for r, role in enumerate(config["thread_roles"]):
        roles += [r] * role["threads"]
        mix.append([role["mix"].get(fn, 0) for fn in FNAMES])
    if len(roles) != T:
        raise ValueError("thread_roles must add up to threads_per_key")
    R, N = n * K, 2 * n_ops
    thr, gen, typ, f, a, b = _simulate(
        rng, R, n_ops, T, np.asarray(roles), np.asarray(mix, float), nv,
        config["p_start"], int(round(config["info_share"] * n_ops)))

    # Alter one observed read in the chosen histories: another value of
    # the domain (the initial value among them), on a random key.
    corrupt = np.zeros(n, bool)
    corrupt[rng.permutation(n)[:n_corrupt]] = True
    reads = (typ == OK) & (f == READ)
    score = rng.random((R, N))
    score[~reads] = -1.0
    key_pick = rng.integers(0, K, n)
    for h in np.flatnonzero(corrupt):
        r = h * K + key_pick[h]
        c = int(score[r].argmax())
        if score[r, c] < 0:
            corrupt[h] = False
            continue
        dom = [INIT] + list(range(nv))
        dom.remove(int(a[r, c]))
        a[r, c] = dom[int(rng.integers(0, len(dom)))]

    # Interleave each history's keys, and the nemesis, by time: every
    # line of a key comes an exponential gap after the one before.
    times = np.cumsum(rng.exponential(1.0, (R, N)), axis=1)
    n_nem = 2 * ((K * N) // config["nemesis_every_lines"] // 2)
    span = times[:, -1].reshape(n, K).max(1)
    nem_t = (np.arange(1, n_nem + 1) / (n_nem + 1))[None, :] * span[:, None]
    t_all = np.concatenate([times.reshape(n, K * N), nem_t], axis=1)
    order = np.argsort(t_all, axis=1, kind="stable")

    def merge(x, nem):
        full = np.concatenate(
            [x.reshape(n, K * N),
             np.broadcast_to(nem, (n, n_nem)).astype(x.dtype)], axis=1)
        return np.take_along_axis(full, order, axis=1)

    kcol = np.broadcast_to(np.repeat(np.arange(K), N)[None, :], (n, K * N))
    krow = np.tile(np.arange(K), n)
    proc = krow[:, None] * T + thr + K * T * gen
    nem_typ = np.tile([INVOKE, INFO], n_nem // 2)
    nem_a = np.repeat(np.arange(n_nem // 2) % 2 == 0, 2).astype(np.int32)
    return Pool(key=merge(kcol.reshape(R, N), -1),
                proc=merge(proc, -1), typ=merge(typ, nem_typ),
                f=merge(f, -1), a=merge(a, nem_a), b=merge(b, NONE),
                corrupt=corrupt, keys=K, initial=config["initial"])
