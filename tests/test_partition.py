"""P-compositional pre-partition + fused multi-bucket dispatch.

Linearizability is local (Herlihy & Wing): a history over independent
keys is linearizable iff each per-key projection is. ops.partition
strains keyed histories into per-key sub-histories BEFORE encoding —
collapsing the 2^W frontier cost — and recombines verdicts host-side
with witness provenance. This suite pins:

  * the strain itself (columnar + Op-list forms, line-for-line against
    the per-key projection, deterministic sub order, unkeyed-line
    replication, W collapse);
  * partitioned-vs-exact verdict parity (valid bit, bad-op index
    mapped back through the partition into the original op-index
    space, witness key) — fault-free, under every single-fault
    FaultPlan schedule, and across kill-and-resume with ZERO decided
    sub-histories re-dispatched;
  * the keyed Op-list route (one keyed conversion walk, then the
    columnar strain): native walk == Python twin, strained sub-batch ==
    the Op-list strain's field for field, dict-equal results, every
    bail condition back on the Op-list strain, and journal resume;
  * the fused dispatch budget (the tier-1 guard against regressing to
    per-chunk dispatch) and the cost model's measured per-dispatch
    overhead term.

Deterministic, test-scale, hermetic (conftest pins JT_COMPILE_CACHE=0
and JT_DISPATCH_OVERHEAD_US=0) — this suite is tier-1.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import generator
from jepsen_tpu import telemetry
from jepsen_tpu.checkers.linearizable import wgl_check
from jepsen_tpu.history.columnar import (PAD, _from_bufs, _seed_vocab,
                                         _walk_py, columnar_to_ops,
                                         ops_to_columnar)
from jepsen_tpu.history.core import index as index_history
from jepsen_tpu.history.ops import (Op, fail_op, info_op, invoke_op,
                                    ok_op)
from jepsen_tpu.independent import KV, is_kv, subhistory
from jepsen_tpu.models.core import cas_register
from jepsen_tpu.ops.faults import (FaultInjector, FaultPlan,
                                   InjectedKill, single_fault_schedules)
from jepsen_tpu.native import ingest
from jepsen_tpu.ops.linearize import (DISPATCH_LOG, check_batch_columnar,
                                      check_batch_tpu, check_columnar)
from jepsen_tpu.ops.partition import (merge_kv_histories,
                                      partition_columnar,
                                      partition_histories,
                                      pending_w_hist,
                                      recombine_details,
                                      recombine_verdicts, strain_keyed)
from jepsen_tpu.ops.schedule import (BucketScheduler, choose_w_classes,
                                     measure_dispatch_overhead_us)
from jepsen_tpu.store import ChunkJournal
from jepsen_tpu.workloads.synth import (synth_cas_columnar,
                                        synth_cas_history)

pytestmark = pytest.mark.partition

MODEL = cas_register()


@pytest.fixture(scope="module")
def keyed_cols():
    """A keyed columnar batch with both verdicts and some key skew."""
    return synth_cas_columnar(48, seed=21, n_procs=4, n_ops=30,
                              n_values=3, corrupt=0.3, p_info=0.1,
                              n_keys=4)


# ----------------------------------------------------- the strain

def test_columnar_strain_matches_per_key_projection(keyed_cols):
    """Every sub row, converted to ops, is line-for-line the per-key
    projection of its original row; sub order is ascending
    (history, key) — the journal/resume contract."""
    pb = partition_columnar(keyed_cols)
    assert pb is not None and pb.n_histories == 48
    order = list(zip(pb.sub_history.tolist(),
                     [-1 if k is None else int(k) for k in pb.sub_key]))
    assert order == sorted(order), "sub order must be (history, key)"
    for s in range(pb.n_subs):
        row = int(pb.sub_history[s])
        k = pb.sub_key[s]
        want = [(int(keyed_cols.type[row, j]),
                 int(keyed_cols.process[row, j]),
                 int(keyed_cols.kind[row, j]), j)
                for j in range(keyed_cols.n_lines)
                if keyed_cols.type[row, j] != PAD
                and (int(keyed_cols.key[row, j]) == int(k)
                     or int(keyed_cols.key[row, j]) < 0)]
        got = [(int(pb.cols.type[s, j]), int(pb.cols.process[s, j]),
                int(pb.cols.kind[s, j]), int(pb.cols.index[s, j]))
               for j in range(pb.cols.n_lines)
               if pb.cols.type[s, j] != PAD]
        assert got == want, (s, row, k)


def test_columnar_strain_collapses_w(keyed_cols):
    pb = partition_columnar(keyed_cols)
    pre, post = pending_w_hist(keyed_cols), pending_w_hist(pb.cols)
    assert max(post) < max(pre)
    # The strain must relieve the axis the kernel actually pays —
    # total frontier words, n * 2^W — not just relabel rows (sub
    # COUNT grows; the exponential shrinks faster).
    assert sum(n << w for w, n in post.items()) \
        < sum(n << w for w, n in pre.items())


def test_unkeyed_batch_passes_through():
    cols = synth_cas_columnar(8, seed=3, n_ops=10)      # n_keys=1
    assert cols.key is None
    assert partition_columnar(cols) is None
    hists = [synth_cas_history(s, n_ops=8) for s in range(4)]
    assert partition_histories(hists) is None


def test_oplist_strain_shares_the_subhistory_machinery():
    """partition_histories == independent.subhistory per key, op
    identity preserved; unkeyed ops replicate into every sub."""
    parts = {0: [invoke_op(0, "write", 1), ok_op(0, "write", 1)],
             1: [invoke_op(0, "read", None), ok_op(0, "read", None)]}
    h = merge_kv_histories(parts)
    # One unkeyed (nemesis-style) op pair in the middle.
    nem = invoke_op(9, "read", None)
    nem_ok = ok_op(9, "read", None)
    h = h[:2] + [nem, nem_ok] + h[2:]
    for i, op in enumerate(h):
        op.index = i
    out = partition_histories([h])
    assert out is not None
    subs, sub_hist, sub_key = out
    assert sub_hist.tolist() == [0, 0]
    assert sub_key == [0, 1]
    for k, sub in zip(sub_key, subs):
        assert sub == subhistory(k, h)
        assert nem in sub and nem_ok in sub


def test_merge_kv_roundtrip():
    parts = {k: synth_cas_history(40 + k, n_procs=2, n_ops=6)
             for k in range(3)}
    h = merge_kv_histories(parts)
    assert all(is_kv(op.value) for op in h)
    subs, _, keys = partition_histories([h])
    for k, sub in zip(keys, subs):
        want = [(op.type, op.f, op.value) for op in parts[k]]
        got = [(op.type, op.f, op.value) for op in sub]
        assert got == want, k


# ------------------------------------------------- verdict parity

def exact_per_key(pb):
    """The oracle: every sub checked on the exact unpartitioned path,
    recombined host-side."""
    v, b = check_columnar(MODEL, pb.cols, partition=False,
                          scheduler=False)
    return recombine_verdicts(v, b, pb.sub_history, pb.sub_key,
                              pb.n_histories)


def test_partitioned_columnar_matches_exact_per_key(keyed_cols):
    pb = partition_columnar(keyed_cols)
    want_v, want_b, want_k = exact_per_key(pb)
    assert not want_v.all(), "corpus must exercise both verdicts"
    got_v, got_b = check_columnar(MODEL, keyed_cols)     # auto strain
    np.testing.assert_array_equal(got_v, want_v)
    np.testing.assert_array_equal(got_b[~got_v], want_b[~want_v])


def test_partitioned_details_carry_witness_key(keyed_cols):
    pb = partition_columnar(keyed_cols)
    want_v, want_b, want_k = exact_per_key(pb)
    rs = check_columnar(MODEL, keyed_cols, details="invalid")
    n_bad = 0
    for i, r in enumerate(rs):
        assert (r["valid"] is True) == bool(want_v[i]), i
        if r["valid"] is not False:
            continue
        n_bad += 1
        # The bad index is in the ORIGINAL row's op/line space, lands
        # on a line of the witness key, and the witness key is the
        # per-key oracle's.
        bad = r["op"]["index"]
        assert bad == int(want_b[i]), i
        assert r["independent_key"] == want_k[i], i
        assert int(keyed_cols.key[i, bad]) == int(r["independent_key"])
        # The witness sub's own exact check agrees line-for-line.
        sub = [s for s in range(pb.n_subs)
               if int(pb.sub_history[s]) == i
               and pb.sub_key[s] == r["independent_key"]][0]
        exact = wgl_check(MODEL, columnar_to_ops(pb.cols, sub))
        assert exact["valid"] is False and \
            exact["op"]["index"] == bad, i
    assert n_bad > 0


def test_partitioned_batch_tpu_oplists():
    """The Op-list entry (check_batch_tpu partition="auto") on KV
    histories: parity against per-key exact checks."""
    merged = [merge_kv_histories(
        {k: synth_cas_history(100 + 10 * i + k, n_procs=3, n_ops=8,
                              corrupt=0.5 if (i + k) % 2 else 0.0)
         for k in range(3)}) for i in range(6)]
    rs = check_batch_tpu(MODEL, merged)
    hit_invalid = False
    for h, r in zip(merged, rs):
        per_key = {k: wgl_check(MODEL, subhistory(k, h))
                   for k in (0, 1, 2)}
        want = all(x["valid"] is True for x in per_key.values())
        assert (r["valid"] is True) == want
        if r["valid"] is False:
            hit_invalid = True
            wk = r["independent_key"]
            assert per_key[wk]["valid"] is False
            assert r["op"]["index"] == per_key[wk]["op"]["index"]
    assert hit_invalid


def test_parity_under_every_single_fault_schedule(keyed_cols):
    """The resilience spine is fusion/partition-transparent: under
    every single-fault schedule the partitioned path returns the
    fault-free verdicts for 100% of histories."""
    want_v, want_b = check_columnar(MODEL, keyed_cols)
    # shard_min_rows keeps the strained sub-batch on the fused chunked
    # pipeline (the path that carries the fault hooks) instead of the
    # conftest virtual mesh's blocking dataN route.
    for name, plan in single_fault_schedules():
        inj = FaultInjector(plan)
        # fuse_width explicit (the hermetic default is 1): the claim
        # under test is that fused GROUPS stay fault-transparent.
        v, b = check_columnar(MODEL, keyed_cols, faults=inj,
                              scheduler_opts={"chunk_rows": 32,
                                              "fuse_width": 4,
                                              "shard_min_rows": 1 << 30})
        np.testing.assert_array_equal(v, want_v, err_msg=name)
        np.testing.assert_array_equal(b[~v], want_b[~want_v],
                                      err_msg=name)
        assert inj.log, f"schedule {name} never engaged"


def test_kill_and_resume_redispatches_zero_decided_subhistories(
        tmp_path, keyed_cols):
    """The partition/resume contract: the journal's row namespace is
    the deterministically ordered sub-history list, so an interrupted
    partitioned check resumes with ZERO decided sub-histories
    re-dispatched and unchanged final verdicts."""
    opts = {"chunk_rows": 16, "shard_min_rows": 1 << 30}
    want_v, want_b = check_columnar(MODEL, keyed_cols,
                                    scheduler_opts=opts)
    key = {"digest": "partition-kill"}
    j1 = ChunkJournal(tmp_path / "p.jsonl", key)
    inj = FaultInjector(FaultPlan.single("dispatch", "kill", chunk=3,
                                         deadline_s=5.0))
    with pytest.raises(InjectedKill):
        check_columnar(MODEL, keyed_cols, faults=inj, journal=j1,
                       scheduler_opts=opts)
    j1.close()
    j2 = ChunkJournal(tmp_path / "p.jsonl", key, resume=True)
    decided = j2.decided()
    assert decided, "sub-histories retired before the kill"
    n_subs = partition_columnar(keyed_cols).n_subs
    assert len(decided) < n_subs
    DISPATCH_LOG.clear()
    v, b = check_columnar(MODEL, keyed_cols, journal=j2,
                          scheduler_opts=opts)
    np.testing.assert_array_equal(v, want_v)
    np.testing.assert_array_equal(b[~v], want_b[~want_v])
    assert j2.resume_hits == len(decided)
    redispatched = sum(n for _, _, _, n in DISPATCH_LOG)
    assert redispatched <= n_subs - len(decided), \
        "decided sub-histories must not be re-dispatched"
    j2.finish()


# ---------------------------------------- keyed Op-list route

CONFIGS = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
SEED = 3900000092


def _pool(name, n, **scale):
    """``n`` seeded histories of a benchmark configuration (``scale``
    overrides its sizes) and the model its cells check them under."""
    cfg = dict(json.loads((CONFIGS / f"{name}.json").read_text()), **scale)
    pool = generator.generate(cfg, n, SEED, max(1, n // 4))
    return ([pool.op_list(i) for i in range(pool.n)],
            cas_register(cfg["initial"]))


def _mixed():
    """KV histories with every op the strain must place: nemesis ops,
    :fail and :info completions, crashed invocations, an :info that
    drops the key, an unkeyed client op pair, keys first seen out of
    label order with two of them invalid; then a history without keys
    and an empty one."""
    parts = {k: synth_cas_history(200 + i, n_procs=3, n_ops=12,
                                  p_info=0.2, corrupt=float(i > 0))
             for i, k in enumerate(("a", "b", 7))}
    h = merge_kv_histories(parts)
    h = ([Op("nemesis", "info", "start", None),
          invoke_op(51, "cas", KV(7, [1, 2]))]            # crashed
         + h[:9] + [invoke_op(60, "read", None), ok_op(60, "read", None)]
         + h[9:20] + [invoke_op(50, "write", KV("b", 3)),
                      fail_op(50, "write", KV("b", 3)),
                      invoke_op(52, "write", KV("a", 4)),
                      info_op(52, "write", None)]
         + h[20:] + [Op("nemesis", "info", "stop", None)])
    for op in h:
        op.index = None
    return [index_history(h),
            index_history(synth_cas_history(300, n_procs=2, n_ops=6)),
            []], cas_register()


CASES = {
    "etcd": lambda: _pool("etcd-register", 4),
    "cockroach": lambda: _pool("cockroach-register", 2),
    "mixed": _mixed,
}


def _kinds(cols):
    """Each line's op kind itself (None off invoke lines): kinds up to
    the vocabulary's numbering."""
    return [[cols.kinds[k] if k >= 0 else None for k in row]
            for row in cols.kind.tolist()]


def _oplist_route(model, histories, **kw):
    """Today's Op-list strain, spelled out: partition_histories, one
    unkeyed check of the subs, recombination."""
    subs, sub_hist, sub_key = partition_histories(histories)
    inner = check_batch_columnar(model, subs, partition=False, **kw)
    return recombine_details(inner, sub_hist, sub_key, len(histories))


def _routes():
    c = telemetry.REGISTRY.counters()
    return {r: c.get(f"partition.oplist{{route={r}}}", 0)
            for r in ("columnar", "ops")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_keyed_walk_native_matches_python(case):
    hists, _ = CASES[case]()
    ext = ingest()
    assert ext is not None
    vocab_n, kinds_n = _seed_vocab(None)
    vocab_p, kinds_p = _seed_vocab(None)
    native = _from_bufs(ext.walk(hists, vocab_n, kinds_n, KV))
    python = _walk_py(hists, vocab_p, kinds_p, KV)
    for n, p in zip(native[:8], python[:8]):
        np.testing.assert_array_equal(n, p)
    assert native[8] == python[8] and kinds_n == kinds_p


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("case", sorted(CASES))
def test_keyed_strain_matches_oplist_strain(case, native):
    """The keyed walk + columnar strain gives the Op-list strain's subs,
    in its order, field for field."""
    hists, model = CASES[case]()
    subs, sub_hist, sub_key = partition_histories(hists)
    want = ops_to_columnar(model, subs)
    pb = strain_keyed(ops_to_columnar(model, hists, keyed=True,
                                      native=native))
    assert pb.sub_history.tolist() == sub_hist.tolist()
    assert pb.sub_key == sub_key
    for f in ("type", "process", "index"):
        np.testing.assert_array_equal(getattr(pb.cols, f),
                                      getattr(want, f), err_msg=f)
    assert _kinds(pb.cols) == _kinds(want)


@pytest.mark.parametrize("details", [True, "invalid"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_keyed_route_results_match_oplist_route(case, details):
    """Dict for dict — verdict, first bad op, witness key, failures in
    first-seen key order, provenance — the keyed route answers as the
    Op-list strain does, on valid and altered histories."""
    hists, model = (_pool("etcd-register", 4, ops_per_key=40)
                    if case == "etcd" else
                    _pool("cockroach-register", 2, ops_per_key=20)
                    if case == "cockroach" else CASES[case]())
    before = _routes()
    got = check_batch_columnar(model, hists, details=details)
    after = _routes()
    assert after["columnar"] - before["columnar"] == len(hists)
    assert after["ops"] == before["ops"]
    assert got == _oplist_route(model, hists, details=details)
    assert any(r["valid"] is True for r in got)
    assert any(r["valid"] is False for r in got)
    if case == "mixed":
        assert got[0]["failures"] == [7, "b"]


def _bail_history(case):
    p = 3
    if case == "op-without-index":
        return [invoke_op(p, "write", KV("a", 1)),
                ok_op(p, "write", KV("a", 1))]
    h = {"completion-names-another-key":
         [invoke_op(p, "write", KV("a", 1)), ok_op(p, "write", KV("b", 1))],
         "fail-names-another-key":
         [invoke_op(p, "write", KV("a", 1)),
          fail_op(p, "write", KV("b", 1))],
         "unkeyed-invocation-keyed-completion":
         [invoke_op(p, "read", None), ok_op(p, "read", KV("a", None))],
         "invocation-under-another-open-key":
         [invoke_op(p, "write", KV("a", 1)),
          invoke_op(p, "write", KV("b", 2)),
          ok_op(p, "write", KV("b", 2))]}[case]
    return index_history([invoke_op(0, "write", KV("a", 2)),
                          ok_op(0, "write", KV("a", 2))] + h)


@pytest.mark.parametrize("case", [
    "completion-names-another-key", "fail-names-another-key",
    "unkeyed-invocation-keyed-completion",
    "invocation-under-another-open-key", "op-without-index"])
def test_keyed_walk_bails_to_the_oplist_strain(case):
    hists = [_bail_history(case)] + _pool("cockroach-register", 1,
                                          ops_per_key=10)[0]
    model = cas_register()
    for native in (True, False):
        assert ops_to_columnar(model, hists, keyed=True,
                               native=native) is None
    before = _routes()
    got = check_batch_columnar(model, hists)
    after = _routes()
    assert after["ops"] - before["ops"] == len(hists)
    assert after["columnar"] == before["columnar"]
    assert got == _oplist_route(model, hists, details=True)


def test_keyed_route_resume_redispatches_zero_decided_subhistories(
        tmp_path):
    """The journal contract on the keyed Op-list route: an interrupted
    check resumes with zero decided sub-histories re-dispatched and the
    same verdicts."""
    hists, model = _pool("cockroach-register", 4, ops_per_key=20)
    opts = {"chunk_rows": 16, "shard_min_rows": 1 << 30}
    want = check_batch_columnar(model, hists, scheduler_opts=opts)
    key = {"digest": "keyed-oplist-kill"}
    j1 = ChunkJournal(tmp_path / "k.jsonl", key)
    inj = FaultInjector(FaultPlan.single("dispatch", "kill", chunk=3,
                                         deadline_s=5.0))
    with pytest.raises(InjectedKill):
        check_batch_columnar(model, hists, faults=inj, journal=j1,
                             scheduler_opts=opts)
    j1.close()
    j2 = ChunkJournal(tmp_path / "k.jsonl", key, resume=True)
    decided = j2.decided()
    n_subs = len(partition_histories(hists)[0])
    assert decided and len(decided) < n_subs
    DISPATCH_LOG.clear()
    got = check_batch_columnar(model, hists, journal=j2,
                               scheduler_opts=opts)
    assert [(r["valid"], (r.get("op") or {}).get("index"))
            for r in got] == [(r["valid"], (r.get("op") or {}).get("index"))
                              for r in want]
    assert j2.resume_hits == len(decided)
    assert sum(n for _, _, _, n in DISPATCH_LOG) <= n_subs - len(decided)
    j2.finish()


# ------------------------------------- fused dispatch + cost model

DISPATCH_BUDGET = 12


def test_fused_scheduler_respects_dispatch_budget():
    """Tier-1 guard: a canned 512-history mixed-W batch must retire in
    at most DISPATCH_BUDGET XLA calls — catching any regression back
    to one-dispatch-per-chunk (hermetic: conftest pins
    JT_COMPILE_CACHE=0, so this measures dispatch structure, not cache
    state)."""
    from jepsen_tpu.ops.encode import encode_columnar
    from jepsen_tpu.ops.statespace import enumerate_statespace
    # Narrow vocabulary + modest concurrency: windows still span W
    # 2..7 (mixed classes, the shape under guard) but every member
    # kernel stays small, so the one-off megakernel compiles this
    # hermetic test pays (JT_COMPILE_CACHE=0) stay cheap.
    cols = synth_cas_columnar(512, seed=7, n_procs=3, n_ops=16,
                              n_values=2, corrupt=0.2, p_info=0.05)
    space = enumerate_statespace(MODEL, cols.kinds, 64)
    buckets, fails = encode_columnar(space, cols)
    assert not fails
    # fuse_width explicit: under JT_COMPILE_CACHE=0 the DEFAULT width
    # collapses to 1 (megakernel compiles can't amortize without the
    # cache), but this guard measures the fused dispatch structure.
    sch = BucketScheduler(chunk_rows=32, fuse_width=4,
                          shard_min_rows=1 << 30)
    outs = list(sch.run(buckets))
    assert sum(b.batch for b, _ in outs) == 512
    assert sch.stats["chunks"] >= 8, "the batch must be chunk-rich"
    assert sch.stats["dispatches"] <= DISPATCH_BUDGET, sch.stats
    assert sch.stats["dispatches"] < sch.stats["chunks"], \
        "fusion must amortize dispatches over chunks"
    assert sch.stats["fused_groups"] >= 1


def test_fuse_width_one_restores_per_chunk_flow():
    cols = synth_cas_columnar(128, seed=9, n_procs=3, n_ops=20)
    from jepsen_tpu.ops.encode import encode_columnar
    from jepsen_tpu.ops.statespace import enumerate_statespace
    space = enumerate_statespace(MODEL, cols.kinds, 64)
    buckets, _ = encode_columnar(space, cols)
    sch = BucketScheduler(chunk_rows=16, fuse_width=1,
                          shard_min_rows=1 << 30)
    list(sch.run(buckets))
    assert sch.stats["fused_groups"] == 0
    assert sch.stats["dispatches"] == sch.stats["chunks"]


def test_choose_w_classes_charges_dispatch_overhead():
    """The DP's fixed-overhead term: with zero overhead, few distinct
    windows keep exact classes; a large per-dispatch tax consolidates
    them below max_classes (many small classes stop being free)."""
    stats = {(8, w): 10.0 for w in (3, 4, 5)}
    free = choose_w_classes(stats, max_classes=5, overhead=0.0)
    assert sorted(set(free.values())) == [3, 4, 5]
    taxed = choose_w_classes(stats, max_classes=5, overhead=1e9)
    assert sorted(set(taxed.values())) == [5], taxed
    # The overhead term must never push work ABOVE the boundary class.
    assert all(c <= 5 for c in taxed.values())


def test_dispatch_overhead_env_override(monkeypatch):
    monkeypatch.setenv("JT_DISPATCH_OVERHEAD_US", "123.5")
    assert measure_dispatch_overhead_us() == 123.5
    monkeypatch.setenv("JT_DISPATCH_OVERHEAD_US", "-4")
    assert measure_dispatch_overhead_us() == 0.0


def test_aot_ship_and_load_roundtrip(tmp_path, monkeypatch):
    """AOT-serialized kernel shipping: a compiled executable exported
    to the shipping dir deserializes in a fresh registry and computes
    the same outputs; a corrupt file is rejected, never trusted."""
    jax = pytest.importorskip("jax")
    from jepsen_tpu.ops import schedule as sched_mod
    from jepsen_tpu.ops.linearize import get_kernel
    monkeypatch.setenv("JT_COMPILE_CACHE", "1")
    monkeypatch.setenv("JT_AOT_DIR", str(tmp_path))
    monkeypatch.setattr(sched_mod, "_AOT_MISSING", set())
    V, W, Bp, Np = 4, 2, 8, 8
    kern = get_kernel(V, W, shared_target=True)
    ev = np.zeros((Bp, Np), np.int8)
    slots = np.full((Bp, Np, W), 1, np.int8)
    tgt = np.full((2, V), -1, np.int32)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in (ev, ev, slots, tgt)]
    compiled = kern.lower(*shapes).compile()
    key = ("test-aot", V, W)
    sched_mod._aot_store(key, compiled)
    assert sched_mod.AOT_STATS["exported"] >= 1
    loaded = sched_mod._aot_load(key)
    assert loaded is not None
    want = [np.asarray(x) for x in compiled(ev, ev, slots, tgt)]
    got = [np.asarray(x) for x in loaded(ev, ev, slots, tgt)]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    # Corruption: flip bytes in the shipped file -> rejected miss.
    path = sched_mod._aot_path(key)
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    raw[8] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    before = sched_mod.AOT_STATS["rejected"]
    assert sched_mod._aot_load(key) is None
    assert sched_mod.AOT_STATS["rejected"] > before
