"""Share of the frontier-sharded kernel's per-chip device time that the
least per-chip work of the window's histories needs
(benchmark/frontier_work.py, over the peaks of benchmark/peaks.json),
in %. ``bound`` says whether the VPU or the HBM time is the larger."""
from benchmark import frontier_work, workmodel
from benchmark.metrics.frontier_kernel_ms_per_khist import mean_chip_s
from benchmark.metrics.wgl_roofline import peaks_for


def read(run):
    ks = mean_chip_s(run)
    if ks is None:
        return None
    chips = run.device["count"]
    ops = nbytes = 0.0
    per = {}
    for c in run.calls:
        for i in c["batch"]:
            if i not in per:
                per[i] = frontier_work.history_work(run.pool, i, chips)
            ops += per[i]["ops"]
            nbytes += per[i]["bytes"]
    t, bound = workmodel.least_time(ops, nbytes,
                                    peaks_for(run.device["kind"]))
    return {"value": 100.0 * t / ks, "bound": bound}
