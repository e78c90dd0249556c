"""1 - busy / window from the profiler trace: busy is the union of the
device's operation intervals inside the traced window, in %."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
