"""Time in the Op-list -> columnar conversion
(``history/columnar.py`` ``ops_to_columnar``), from the benchmark's
span around each call the checker makes, per thousand histories."""
from benchmark.metrics._spans import bench_ms_per_khist


def read(run):
    return bench_ms_per_khist(run, "convert")
