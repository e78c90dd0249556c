"""Histories whose verdicts returned in the window, per second of the
window's wall time (host clock): all the work over all the time."""
from benchmark.stats import rate


def read(run):
    return rate(sum(c["n"] for c in run.calls), run.window_s)
