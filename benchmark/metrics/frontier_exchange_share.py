"""Share of the frontier-sharded kernel's device time spent in its
collective operations (``collective-permute`` exchanges and
``all-reduce`` psums, the union of their ``XLA Ops`` intervals inside
the kernel's own), the mean over the chips that ran it, in %."""
import re

from benchmark import devtrace
from benchmark.metrics.frontier_kernel_ms_per_khist import MODULE

COLLECTIVE = re.compile(r"^%?(collective-permute|all-reduce)")


def read(run):
    tr = run.trace
    if tr is None:
        return None
    shares = []
    for lines in tr.devices.values():
        kern = devtrace._union(devtrace._clip(
            [(s, s + d) for n, s, d in lines.get(devtrace.MODULES_LINE, [])
             if devtrace.module_name(n) == MODULE], tr.lo, tr.hi))
        k_ns = sum(e - s for s, e in kern)
        if k_ns <= 0:
            continue
        coll = devtrace._union(
            iv for n, s, d in lines[devtrace.OPS_LINE]
            if COLLECTIVE.match(n)
            for lo, hi in kern
            for iv in devtrace._clip([(s, s + d)], lo, hi))
        shares.append(sum(e - s for s, e in coll) / k_ns)
    return 100.0 * sum(shares) / len(shares) if shares else None
