"""Host time decoding the device's verdicts: self time of the program's
``decode`` spans (each dispatch's outputs, ``ops/schedule.py``; their
``decode.fetch`` children, the read-backs that wait on the device, are
left out) plus its ``decode.details`` spans (each bucket's result dicts,
``ops/linearize.py``), per thousand histories."""
from benchmark.metrics._spans import program_self_ms_per_khist


def read(run):
    parts = [program_self_ms_per_khist(run, n)
             for n in ("decode", "decode.details")]
    if all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None)
