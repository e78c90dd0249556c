"""Share of the WGL kernels' device time that the least work of the
window's histories needs (benchmark/workmodel.py, over the peaks of
benchmark/peaks.json), in %. ``bound`` says whether the VPU or the HBM
time is the larger; it is the VPU on every row this benchmark makes."""
import json
import os

from benchmark import workmodel
from benchmark.metrics.wgl_kernel_ms_per_khist import kernel_s

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks_for(kind):
    with open(PEAKS) as fh:
        table = json.load(fh)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def read(run):
    ks = kernel_s(run)
    if ks is None:
        return None
    peaks = peaks_for(run.device["kind"])
    per = {}
    ops = nbytes = 0
    for c in run.calls:
        for i in c["batch"]:
            if i not in per:
                per[i] = workmodel.history_work(run.pool, i)
            ops += per[i]["ops"]
            nbytes += per[i]["bytes"]
    t, bound = workmodel.least_time(ops, nbytes, peaks)
    return {"value": 100.0 * t / ks, "bound": bound}
