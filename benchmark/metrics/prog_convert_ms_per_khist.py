"""Time in the Op-list -> columnar conversion, from the program's own
``columnar.convert`` spans (``history/columnar.py`` ``ops_to_columnar``),
per thousand histories."""
from benchmark.metrics._program import total_ms_per_khist


def read(run):
    return total_ms_per_khist(run, "columnar.convert")
