"""Time in the per-key partition of Op-list histories, from the
program's own ``partition.histories`` spans (``ops/partition.py``
``partition_histories``), per thousand histories."""
from benchmark.metrics._program import total_ms_per_khist


def read(run):
    return total_ms_per_khist(run, "partition.histories")
