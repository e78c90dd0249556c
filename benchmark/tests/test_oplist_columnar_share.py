"""``oplist_columnar_share``: the share of KV-valued Op-list histories
the checker strained inside its keyed conversion walk."""
import pytest

from benchmark import run
from benchmark.tests._tiny import TINY, drive
from jepsen_tpu import telemetry


def test_reads_the_route_counter():
    reader = run.module("metrics", "oplist_columnar_share")
    was = telemetry.enabled()
    telemetry.configure(True)
    try:
        assert reader.read(None) is None         # no Op list strained
        telemetry.REGISTRY.counter("partition.oplist",
                                   route="columnar").inc(3)
        telemetry.REGISTRY.counter("partition.oplist", route="ops").inc(1)
        assert reader.read(None) == 75.0
    finally:
        telemetry.configure(was)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_every_history_of_a_tiny_traced_run_takes_the_keyed_walk(cell):
    m = drive(cell, trace=1)["metrics"]
    assert m["oplist_columnar_share"]["value"] == 100.0
