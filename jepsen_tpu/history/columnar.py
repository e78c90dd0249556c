"""Columnar histories — the TPU-native batch data model.

The Op-list history (jepsen_tpu.history.core) is the general interface
between execution and analysis, but at checker-benchmark scale (10k
histories × 1k lines — BASELINE.md north star) per-op Python objects
dominate the wall clock. A ``ColumnarOps`` holds a *batch* of histories
as padded 2-D arrays, one row per history, and the whole host pipeline
(synthesis → encode → device tensors) runs as vectorized numpy over the
batch axis. The reference has no analog — its JVM harness materializes
every op as a map (jepsen/src/jepsen/core.clj:153-172); the columnar
form is what makes "histories as tensors" hold end-to-end.

Contract: a ColumnarOps is already *prepared* in the sense of
checkers.linearizable.prepare_history —

  * failed ops never happened: both their lines are PAD;
  * observed values are propagated: each invocation line carries the
    final op-kind index (e.g. ("read", observed-value)) in ``kind``;
  * never-ok total-identity ops (timed-out unconstrained reads) are
    dropped: PAD (the rule shared by every engine —
    jepsen_tpu.ops.encode.dropped_invocations).

Producers: workloads.synth.synth_cas_columnar (vectorized batch synth);
``ops_to_columnar``/``columnar_to_ops`` convert to/from Op lists (Python
walks — for tests and for routing individual rows to host engines).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from .ops import Op, invoke_op, ok_op, info_op

# Line type codes.
PAD = -1
C_INVOKE = 0
C_OK = 1
C_INFO = 2


@dataclass
class ColumnarOps:
    """A prepared batch of histories as padded columnar arrays.

    type    — int8  [B, N]: C_INVOKE / C_OK / C_INFO / PAD
    process — int16 [B, N]: logical process per line (< n_procs)
    kind    — int32 [B, N]: op-kind index into ``kinds`` (invoke lines;
              -1 elsewhere)
    kinds   — the shared op-kind vocabulary, index-aligned with the
              transition table callers build via
              ops.statespace.enumerate_statespace(model, kinds, ...)
    index   — optional int32 [B, N]: each line's index in the history it
              was converted from (-1 on PAD); present on converted
              batches (``ops_to_columnar``) so verdict line positions
              map back to original op indices
    key     — optional int32 [B, N]: independent-key id per line (the
              columnar form of a KV-valued history,
              jepsen_tpu.independent); -1 marks unkeyed lines. Present
              only on keyed batches (workloads.synth ``n_keys > 1``).
              Checkers never interpret it directly — the
              P-compositional pre-partition (ops.partition) strains a
              keyed batch into per-key sub-histories before encoding,
              and the sub-batches it produces carry no key column.
              Batches converted from KV-valued Op lists
              (``ops_to_columnar(keyed=True)``) carry row-local ids:
              the key's rank in its history's first-seen key order.
    key_labels — optional per-row lists of key labels by row-local id,
              on batches converted with ``keyed=True``.
    meta    — optional generator-side metadata
              (ops.synth_device.SynthMeta): per-history (and per-key)
              peak pending windows computed as part of generation, so
              the partition stage's W histograms need no host re-scan
              of the line grid (ops.partition.pending_w_hist consults
              it). Purely advisory — every consumer must behave
              identically with meta=None.
    """

    type: np.ndarray
    process: np.ndarray
    kind: np.ndarray
    kinds: List[Tuple]
    index: Optional[np.ndarray] = None
    key: Optional[np.ndarray] = None
    meta: Optional[object] = None
    key_labels: Optional[List[list]] = None

    @property
    def batch(self) -> int:
        return int(self.type.shape[0])

    @property
    def n_lines(self) -> int:
        return int(self.type.shape[1])

    def op_index(self, row: int, line: int) -> int:
        """Original-history op index for a line (the line itself when the
        batch was synthesized rather than converted)."""
        if self.index is None:
            return int(line)
        return int(self.index[row, line])


def _kind_value(kind: Tuple):
    f, cv = kind
    return list(cv) if isinstance(cv, tuple) else cv


def _walk_py(histories: Sequence[Sequence[Op]], vocab: dict,
             all_kinds: List[Tuple], kv=None):
    """Pure-Python twin of the native ingest walk (native/ingest.cpp):
    pairing, failure retraction, and value propagation over recorded
    histories, emitting flat line buffers. The oracle for the native
    walk's parity tests and the fallback when it can't build.

    With ``kv`` (the independent.KV type) the walk is keyed, as the
    native walk's keyed mode: KV values are unwrapped, each line carries
    its key's rank in the history's first-seen key order, and the
    result adds the lines' key ranks and each history's keys by rank
    (see ``ops_to_columnar``); None where an op would be placed
    otherwise than by the per-key walk of its sub-history."""
    from ..ops.statespace import canonical_value

    def value(op):
        v = op.value
        return v.value if kv is not None and isinstance(v, kv) else v

    def intern(f, v):
        k = (f, canonical_value(v))
        ki = vocab.get(k)
        if ki is None:
            ki = vocab[k] = len(all_kinds)
            all_kinds.append(k)
        return ki

    code: List[int] = []
    proc: List[int] = []
    kind: List[int] = []
    oidx: List[int] = []
    okflag: List[int] = []
    link: List[int] = []
    rowlen: List[int] = []
    key: List[int] = []
    labels: List[list] = []
    for h in histories:
        rowstart = len(code)
        opened: dict = {}        # process -> (invoke line, op, key)
        dense: dict = {}         # process -> per-row dense id
        ranks: dict = {}         # key -> rank, first seen first
        for pos, op in enumerate(h):
            okey = -1
            if kv is not None and isinstance(op.value, kv):
                okey = ranks.setdefault(op.value.key, len(ranks))
            p = op.process
            if not isinstance(p, int):
                continue
            t = op.type
            ix = op.index if op.index is not None else pos
            o = opened.get(p)
            if t == "invoke":
                if kv is not None and (op.index is None or (
                        o is not None and o[2] != okey)):
                    return None
                opened[p] = (len(code), op, okey)
                lkey = okey
                code.append(C_INVOKE)
                proc.append(dense.setdefault(p, len(dense)))
                oidx.append(ix)
                link.append(-1)
            elif t == "ok" or t == "info":
                if o is None:
                    continue
                if kv is not None and (op.index is None or (
                        okey >= 0 and okey != o[2])):
                    return None
                del opened[p]
                j, inv, lkey = o
                v = value(inv)
                if v is None and t == "ok":
                    # Only ok completions propagate observations
                    # (history.core.complete semantics): an info op's
                    # value is not an observation.
                    v = value(op)
                kind[j] = intern(inv.f, v)
                if t == "ok":
                    okflag[j] = 1
                    code.append(C_OK)
                    link.append(-1)
                else:
                    code.append(C_INFO)
                    link.append(j)
                proc.append(proc[j])
                oidx.append(ix)
            elif t == "fail" and o is not None:
                # Definitely didn't happen: retract the invoke line.
                if kv is not None and okey >= 0 and okey != o[2]:
                    return None
                del opened[p]
                code[o[0]] = PAD
                continue
            else:
                continue
            kind.append(-1)
            okflag.append(0)
            if kv is not None:
                key.append(lkey)
        # Crashed invocations (no completion): kind from the invoke.
        for j, inv, _ in sorted(opened.values(), key=lambda x: x[0]):
            kind[j] = intern(inv.f, value(inv))
        if kv is not None:
            labels.append(list(ranks))
            _key_row((code, proc, kind, oidx, okflag, link, key),
                     rowstart, len(ranks))
        rowlen.append(len(code) - rowstart)
    out = (np.asarray(code, np.int8), np.asarray(proc, np.int32),
           np.asarray(kind, np.int32), np.asarray(oidx, np.int32),
           np.asarray(okflag, np.int8), np.asarray(link, np.int32),
           np.asarray(rowlen, np.int64))
    if kv is None:
        return out
    return out + (np.asarray(key, np.int32), labels)


def _key_row(lines, start: int, n_keys: int) -> None:
    """A keyed row once walked (``_walk_py``'s twin of the native
    ``key_row``): each unkeyed line (key -1) of a row with keys is
    copied once per key, the copies adjacent, as subhistory puts it in
    every key's sub-history; then process ids are made dense per key in
    line order, retracted invokes included, as the per-key walk's
    are."""
    if not n_keys:
        return
    code, proc, kind, oidx, okflag, link, key = lines
    if -1 in key[start:]:
        old = [col[start:] for col in lines]
        for col in lines:
            del col[start:]
        at = []                            # first copy of each old line
        for c, p, ki, ix, ok, ln, k in zip(*old):
            at.append(len(code))
            for copy in (range(n_keys) if k < 0 else (k,)):
                for col, x in zip(lines, (c, p, ki, ix, ok)):
                    col.append(x)
                link.append(-1 if ln < 0 else
                            at[ln - start] + (copy if k < 0 else 0))
                key.append(copy)
    per_key: List[dict] = [{} for _ in range(n_keys)]
    for j in range(start, len(code)):
        d = per_key[key[j]]
        proc[j] = d.setdefault(proc[j], len(d))


def _pack_walk(model, bufs_or_arrays, all_kinds: List[Tuple],
               max_states: int, key=None) -> ColumnarOps:
    """Shared post-pass over a walk's flat buffers: identity-drop and
    padding into a ColumnarOps (the second half of ops_to_columnar).
    ``key``, a keyed walk's per-line key ranks, lands in ``.key``."""
    from ..ops.statespace import enumerate_statespace

    code, proc, kind, oidx, okflag, link, rowlen = bufs_or_arrays
    space = enumerate_statespace(model, all_kinds, max_states)
    identity = space.identity_kinds

    drop = code == PAD
    if identity:
        # Never-ok total-identity invocations and their info lines.
        ident_mask = np.zeros(len(all_kinds) + 1, bool)
        ident_mask[list(identity)] = True
        inv_ident = (code == C_INVOKE) & ident_mask[kind] & (okflag == 0)
        drop |= inv_ident
        linked = link >= 0
        drop |= linked & inv_ident[np.where(linked, link, 0)]
    keep = ~drop

    B = len(rowlen)
    rid = np.repeat(np.arange(B), rowlen)[keep]
    counts = np.bincount(rid, minlength=B)
    N = int(counts.max()) if B else 0
    starts = np.zeros(B, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    posin = np.arange(rid.size, dtype=np.int64) - starts[rid]

    typ = np.full((B, max(N, 1)), PAD, np.int8)
    procs = np.zeros((B, max(N, 1)), np.int16)
    kinds_arr = np.full((B, max(N, 1)), -1, np.int32)
    index = np.full((B, max(N, 1)), -1, np.int32)
    typ[rid, posin] = code[keep]
    procs[rid, posin] = proc[keep].astype(np.int16)
    kinds_arr[rid, posin] = kind[keep]
    index[rid, posin] = oidx[keep]
    keys = None
    if key is not None:
        keys = np.full((B, max(N, 1)), -1, np.int32)
        keys[rid, posin] = key[keep]
    return ColumnarOps(type=typ, process=procs, kind=kinds_arr,
                       kinds=all_kinds, index=index, key=keys)


def _from_bufs(bufs):
    """Native walk byte buffers -> typed arrays (Py_BuildValue("y#")
    yields None for an empty vector's nullptr); a keyed walk's key
    ranks and key labels follow."""
    out = (np.frombuffer(bufs[0] or b"", np.int8),
           np.frombuffer(bufs[1] or b"", np.int32),
           np.frombuffer(bufs[2] or b"", np.int32).copy(),
           np.frombuffer(bufs[3] or b"", np.int32),
           np.frombuffer(bufs[4] or b"", np.int8),
           np.frombuffer(bufs[5] or b"", np.int32),
           np.frombuffer(bufs[6] or b"", np.int64))
    if len(bufs) > 7:
        out += (np.frombuffer(bufs[7] or b"", np.int32), bufs[8])
    return out


def _seed_vocab(kinds: Optional[List[Tuple]]):
    vocab: dict = {}
    all_kinds: List[Tuple] = []
    for k in (kinds or []):
        if k not in vocab:
            vocab[k] = len(all_kinds)
            all_kinds.append(k)
    return vocab, all_kinds


def jsonl_to_columnar(model, texts: Sequence, *,
                      kinds: Optional[List[Tuple]] = None,
                      max_states: int = 64,
                      native: bool = True) -> ColumnarOps:
    """Serialized histories (one history.jsonl content per entry,
    str or bytes) straight onto the columnar fast path — the native
    replay loader (store.clj:165-171 is the seam; the reference reads
    its machine form through JVM-native fressian). The C scanner
    (native/ingest.cpp walk_jsonl) runs the pairing walk off the raw
    bytes with no per-op Python objects; any line it can't place makes
    the whole batch fall back to codec parsing + the Op walk."""
    import json as _json

    from .codec import loads_op, _revive

    ext = None
    if native:
        from ..native import ingest
        ext = ingest()
    if ext is not None:
        vocab, all_kinds = _seed_vocab(kinds)

        def parse(text):
            return _revive(_json.loads(text))

        bufs = ext.walk_jsonl(list(texts), vocab, all_kinds, parse)
        if bufs is not None:
            return _pack_walk(model, _from_bufs(bufs), all_kinds,
                              max_states)
    # Fallback: parse to Op lists, then the ordinary ingest walk (from
    # the ORIGINAL seed — the scanner may have partially extended its
    # own vocab before bailing).
    hists = [[loads_op(line) for line in
              (t.decode() if isinstance(t, bytes) else t).splitlines()
              if line.strip()]
             for t in texts]
    return ops_to_columnar(model, hists, kinds=kinds,
                           max_states=max_states, native=native)


def ops_to_columnar(model, histories: Sequence[Sequence[Op]], *,
                    kinds: Optional[List[Tuple]] = None,
                    max_states: int = 64,
                    native: bool = True,
                    keyed: bool = False) -> Optional[ColumnarOps]:
    """Convert recorded/stored Op-list histories into one prepared
    ColumnarOps — the ingest ramp onto the columnar fast path for
    histories the framework actually executed or reloaded
    (store.load_histories, independent subhistories; the reference's
    re-check seam is jepsen/src/jepsen/store.clj:165-171).

    One fused walk per history applies the full prepared-history
    contract (checkers.linearizable.prepare_history + the identity-drop
    rule of ops.encode.dropped_invocations):

      * non-client ops are skipped;
      * failed ops never happened — neither line is emitted;
      * observed values are propagated — each invoke line carries the
        final (f, value) op-kind (a read's observation, not None);
      * never-ok total-identity invocations (and their info completions)
        are dropped, keeping the pending window proportional to real
        concurrency.

    ``kinds`` seeds the shared vocabulary (indices preserved); new kinds
    found in the histories are appended. ``model`` is needed to decide
    which kinds are identity transitions; a state space past
    ``max_states`` raises StateSpaceExplosion — callers route the whole
    batch to a host/native engine in that case.

    Per-line op indices land in ``.index`` so invalid verdicts map back
    to original ops. Process ids are densified per row to bound the
    walk's process table. The walk itself runs in the native extension
    (native/ingest.cpp) when available (``native=False`` forces the
    pure-Python twin); the identity-drop + padding pass is vectorized
    numpy either way.

    ``keyed=True`` converts KV-valued histories (jepsen_tpu.independent)
    for the per-key strain (ops.partition.strain_keyed): values are
    unwrapped, ``.key`` holds each line's key rank in its history's
    first-seen key order (-1 on a history without keys) and
    ``.key_labels`` the keys by rank, and process ids are dense per
    (history, key); an unkeyed client op of a history with keys is
    copied into every key. The per-key lines are then exactly those
    ``independent.subhistory`` followed by the unkeyed conversion gives.
    Returns None where that cannot hold and the caller must strain Op
    lists instead: a completion naming another key than its
    invocation's, an invocation while the process has one open under
    another key, or an op line without an index.
    """
    with telemetry.span("columnar.convert"):
        vocab, all_kinds = _seed_vocab(kinds)
        kv = None
        if keyed:
            from ..independent import KV as kv
        ext = None
        if native:
            from ..native import ingest
            ext = ingest()
        if ext is not None:
            histories = [h if isinstance(h, (list, tuple)) else list(h)
                         for h in histories]
            out = ext.walk(histories, vocab, all_kinds, kv)
            if out is not None:
                out = _from_bufs(out)
        else:
            out = _walk_py(histories, vocab, all_kinds, kv)
        if out is None:
            return None
        if not keyed:
            return _pack_walk(model, out, all_kinds, max_states)
        cols = _pack_walk(model, out[:7], all_kinds, max_states,
                          key=out[7])
        cols.key_labels = out[8]
        return cols


def columnar_to_ops(cols: ColumnarOps, row: int,
                    propagated: bool = False) -> List[Op]:
    """One row as an indexed Op-list history (host-engine routing and
    oracle tests). Invoke values are un-propagated where the semantics
    require (a read invokes with value None, observes on completion);
    ``propagated=True`` keeps the columnar kinds' already-propagated
    values on the invokes instead — the decode path's form, sparing a
    full history.core.complete() copy pass per row. Op indices are the
    row's line positions, or the original-history op indices when the
    batch was converted (``cols.index``)."""
    out: List[Op] = []
    pending = {}
    for j in range(cols.n_lines):
        t = int(cols.type[row, j])
        if t == PAD:
            continue
        p = int(cols.process[row, j])
        if t == C_INVOKE:
            kind = cols.kinds[int(cols.kind[row, j])]
            f, v = kind[0], _kind_value(kind)
            pending[p] = (f, v)
            op = invoke_op(p, f,
                           None if f == "read" and not propagated else v)
        elif t == C_OK:
            f, v = pending.pop(p)
            op = ok_op(p, f, v)
        else:
            f, v = pending.pop(p)
            op = info_op(p, f, None if f == "read" else v, error="timeout")
        op.index = cols.op_index(row, j)
        out.append(op)
    return out
