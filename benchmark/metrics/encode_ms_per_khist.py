"""Self time of the program's ``encode`` spans (``ops/schedule.py``),
per thousand histories."""
from benchmark.metrics._spans import program_self_ms_per_khist


def read(run):
    return program_self_ms_per_khist(run, "encode")
