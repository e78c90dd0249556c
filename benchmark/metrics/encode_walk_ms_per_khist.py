"""Time in the columnar encode's slot walk, from the program's
``encode.walk`` spans (each ``encode_columnar`` call of
``ops/schedule.py`` ``iter_columnar_groups``), per thousand histories."""
from benchmark.metrics._program import total_ms_per_khist


def read(run):
    return total_ms_per_khist(run, "encode.walk")
