"""The comparison that decides ``correct`` separates sound answers from
the control's: the program at a small size reads 0 on every compared
number, and the control (the reference with :info taken as :fail)
exceeds a limit on every seed."""
import pytest

from benchmark import control, generator, run
from benchmark.tests._tiny import TINY, tiny_config

SEEDS = (1, 2**31 + 5, 4_000_000_123)


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails(cell, seed):
    c = run.load_cell(cell)
    config = tiny_config(cell)
    if cell.startswith("etcd"):     # etcd's :info ops are rare: more ops
        config["ops_per_key"] = 120
    traffic = {**c["traffic"], **TINY[cell]["traffic"],
               "corrupt_histories": 4, "sample_histories": 12,
               "pool_calls": 12}
    got = control.readings(config, traffic, seed)
    assert any(got[k] > run.LIMITS[k] for k in run.LIMITS), got


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_agrees_with_the_reference(cell):
    from jepsen_tpu.models.core import cas_register
    from jepsen_tpu.ops.linearize import check_batch_columnar
    from benchmark.systems.check_batch_columnar import verdict
    config = tiny_config(cell)
    pool = generator.generate(config, 16, 2**31 + 99, 8)
    rs = check_batch_columnar(cas_register(config["initial"]),
                              [pool.op_list(i) for i in range(pool.n)],
                              details="invalid")
    want = run.reference_verdicts(config["reference"], pool,
                                  range(pool.n), workers=1)
    got = {i: [verdict(r)] for i, r in enumerate(rs)}
    assert sum(v is False for v, _ in want.values()) >= 2
    assert run.compare(got, want) == dict.fromkeys(run.LIMITS, 0)
