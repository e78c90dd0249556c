"""Time in the program's per-key partition (``ops/partition.py``
``partition_histories``), from the benchmark's span around each call
the checker makes, per thousand histories."""
from benchmark.metrics._spans import bench_ms_per_khist


def read(run):
    return bench_ms_per_khist(run, "partition")
