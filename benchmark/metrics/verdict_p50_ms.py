"""Median over every call of the window of the host-clock time from
handing the histories to the checker to its verdicts' return."""
from benchmark.stats import percentile


def read(run):
    return percentile([(c["t1"] - c["t0"]) * 1e3 for c in run.calls], 50)
