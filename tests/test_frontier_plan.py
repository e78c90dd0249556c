"""Wide windows on a frontier mesh: pause-nemesis etcd registers whose
``:info`` ops pin slots to the end (W 17-18) are planned by cost like
the narrow classes, ride one frontier-sharded dispatch, and agree with
the plain reference; the accepted configurations' class plans are
unchanged.

Runs on the virtual 8-device CPU mesh (tests/conftest.py), at a small
size: 40 ops per key with 8 of them pinned ``:info``.
"""
import json
from pathlib import Path

import pytest

from benchmark import generator, run
from jepsen_tpu import telemetry
from jepsen_tpu.history.columnar import ops_to_columnar
from jepsen_tpu.models.core import cas_register
from jepsen_tpu.ops import linearize as lin
from jepsen_tpu.ops.partition import strain_keyed
from jepsen_tpu.ops.schedule import (BucketScheduler, choose_w_classes,
                                     frontier_class_cost,
                                     iter_columnar_groups)
from jepsen_tpu.ops.statespace import enumerate_statespace

CONFIGS = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
SEED = 2**31 + 27
#: 8 of 40 ops per key pinned :info, as 8 of 300 in the configuration.
SMALL = {"ops_per_key": 40, "info_share": 0.2}


def _config(name, **scale):
    return dict(json.loads((CONFIGS / f"{name}.json").read_text()),
                **scale)


def _group(cfg, pool):
    """The first (only) encode group the columnar checker streams for
    the pool, at the widest window the attached devices host."""
    model = cas_register(cfg["initial"])
    pb = strain_keyed(ops_to_columnar(
        model, [pool.op_list(i) for i in range(pool.n)], keyed=True,
        max_states=lin.MAX_PACKED_STATES))
    space = enumerate_statespace(model, pb.cols.kinds,
                                 lin.MAX_PACKED_STATES)
    group, = iter_columnar_groups(
        space, pb.cols, max_slots=16 + lin.device_frontier_capacity(),
        fuse=True, renumber=True)
    return group


@pytest.fixture(scope="module")
def pause_run():
    """Four pause-nemesis histories (16 per-key rows), two with a read
    altered, checked once through the normal path with the counters
    read."""
    cfg = _config("etcd-register-pause", **SMALL)
    pool = generator.generate(cfg, 4, SEED, 2)
    was = telemetry.enabled()
    telemetry.configure(True)
    try:
        lin.DISPATCH_LOG.clear()
        out = lin.check_batch_columnar(
            cas_register(cfg["initial"]),
            [pool.op_list(i) for i in range(pool.n)], details="invalid")
        log = list(lin.DISPATCH_LOG)
        routes = {k: v for k, v in telemetry.traced_counters().items()
                  if k.startswith("check.routes{")}
    finally:
        telemetry.configure(was)
    return cfg, pool, out, log, routes


def test_pause_pool_reaches_w17_w18():
    cfg = _config("etcd-register-pause", **SMALL)
    ws = {b.W for b in _group(cfg, generator.generate(cfg, 4, SEED, 2))}
    assert {17, 18} <= ws, ws


def test_pause_verdicts_and_first_bad_op_match_the_reference(pause_run):
    _, pool, out, _, _ = pause_run
    # The plain reference's configurations multiply with every pinned
    # op: its histories run in worker processes, as the benchmark's do.
    want = run.reference_verdicts("cas_register", pool, range(pool.n),
                                  workers=pool.n)
    got = {i: (out[i]["valid"], (out[i].get("op") or {}).get("index")
               if out[i]["valid"] is False else None)
           for i in range(pool.n)}
    assert got == want
    assert {v for v, _ in want.values()} == {True, False}


def test_a_calls_rows_ride_one_frontier_dispatch(pause_run):
    _, pool, _, log, routes = pause_run
    rows = pool.n * pool.keys
    assert log == [("frontier", log[0][1], 18, rows)], log
    assert routes == {"check.routes{route=frontier}": rows}


def test_frontier_class_cost_prices_a_quantum_per_device():
    # W 18 on 4 devices: one data shard, 2^16 masks per device, rows up
    # to a quantum cost what a quantum does.
    q = 64 * 100 * 2**16
    assert frontier_class_cost(100, 1, 18, 4) == q
    assert frontier_class_cost(100, 64, 18, 4) == q
    assert frontier_class_cost(100, 128, 18, 4) == 2 * q
    # W 17 on 4 devices: two data shards share the rows.
    assert frontier_class_cost(100, 128, 17, 4) == q
    # Too few devices for the frontier axis: one device, 2^W per row.
    assert frontier_class_cost(100, 3, 18, 2) == 3 * 100 * 2**18


def test_wide_classes_are_chosen_by_cost_on_a_mesh():
    stats = {(8, 16): 1 * 208.0, (8, 17): 23 * 232.0, (8, 18): 40 * 232.0}
    rows = {(8, 16): 1, (8, 17): 23, (8, 18): 40}
    # A frontier mesh: one dispatch at W 18 for every row.
    assert choose_w_classes(stats, overhead=0, rows=rows, devices=4) == \
        {(8, 16): 18, (8, 17): 18, (8, 18): 18}
    # One device (or no row counts): wide windows stay exact.
    exact = {(8, 16): 16, (8, 17): 17, (8, 18): 18}
    assert choose_w_classes(stats, overhead=0, rows=rows,
                            devices=1) == exact
    assert choose_w_classes(stats, overhead=0) == exact
    # Narrow rows past a quantum's worth stay on their own class.
    many = {(8, 13): 1000 * 200.0, (8, 18): 4 * 200.0}
    assert choose_w_classes(many, overhead=0,
                            rows={(8, 13): 1000, (8, 18): 4},
                            devices=4) == {(8, 13): 13, (8, 18): 18}


#: Today's class plans of 16-history generator pools of the accepted
#: configurations, by dispatch overhead (us) and class budget.
PLANS = {
    ("etcd-register", "0", 5): {11: 11, 12: 12, 13: 13},
    ("etcd-register", "50", 2): {11: 12, 12: 12, 13: 13},
    ("etcd-register", "2000000", 5): {11: 13, 12: 13, 13: 13},
    ("cockroach-register", "0", 5): {9: 9, 10: 10, 11: 11},
    ("cockroach-register", "50", 2): {9: 10, 10: 10, 11: 11},
    ("cockroach-register", "2000000", 5): {9: 11, 10: 11, 11: 11},
}


@pytest.mark.parametrize("name,overhead_us,max_classes", sorted(PLANS))
def test_accepted_configurations_keep_their_class_plan(
        monkeypatch, name, overhead_us, max_classes):
    monkeypatch.setenv("JT_DISPATCH_OVERHEAD_US", overhead_us)
    cfg = _config(name)
    group = _group(cfg, generator.generate(cfg, 16, 2**31 + 5, 1))
    plan = BucketScheduler(max_classes=max_classes)._freeze_classes(group)
    assert plan == {(8, w): c for w, c in
                    PLANS[(name, overhead_us, max_classes)].items()}
