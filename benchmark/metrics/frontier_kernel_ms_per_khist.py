"""Device time of the frontier-sharded kernel (``XLA Modules`` events of
``jit_frontier_check`` in the profiler trace), the mean over the chips
that ran it (each runs the whole program on its share of the masks),
per thousand histories."""
from benchmark import devtrace

MODULE = "jit_frontier_check"


def per_chip_s(run):
    """{device: seconds of the frontier kernel inside the traced
    window}, for the devices that ran it; {} off the chip or without
    it."""
    tr = run.trace
    if tr is None:
        return {}
    out = {}
    for dev, lines in tr.devices.items():
        ivs = [(s, s + d) for n, s, d in lines.get(devtrace.MODULES_LINE, [])
               if devtrace.module_name(n) == MODULE]
        tot = sum(e - b for b, e in devtrace._clip(ivs, tr.lo, tr.hi))
        if tot > 0:
            out[dev] = tot / 1e9
    return out


def mean_chip_s(run):
    per = per_chip_s(run)
    return sum(per.values()) / len(per) if per else None


def read(run):
    s = mean_chip_s(run)
    if s is None or run.khist <= 0:
        return None
    return s * 1e3 / run.khist
