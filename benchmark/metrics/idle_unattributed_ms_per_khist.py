"""Device idle time in the traced window that no stage of the program
covers, in ms per thousand histories.

The idle time is what ``device_idle_share`` counts: the stretches of
the window with no operation on the first device. A stage is any of the
program's telemetry spans, on any host thread, but the
``scheduler.run`` wrapper, which encloses every stage by construction.
The spans are stamped on ``time.monotonic_ns``; they are laid on the
trace's clock by the median, over the traced calls, of each
``bench.call`` annotation's start less the ``perf_counter`` time its
call began (``perf_counter`` is the monotonic clock).

The value falls only when host work no span names shrinks; a faster
stage that a span covers leaves it as it is. ``share`` gives the same
time as a share of the idle time, in %, which says how much of the
idle time the stage spans name. ``gaps`` names the longest idle
stretches, each by the innermost stage over its midpoint, else by the
benchmark's own span there, else ``between calls``, with its seconds.
"""
import statistics

from benchmark.devtrace import _clip, _union
from benchmark.metrics._program import program_spans

#: Spans that enclose every stage by construction.
WRAPPERS = ("scheduler.run",)


def clock_offset_ns(trace, calls):
    """The trace's clock less the monotonic clock, in ns; None where the
    traced calls and their annotations do not pair one to one."""
    starts = sorted(s for n, s, _ in trace.host if n == "bench.call")
    if not starts or len(starts) != len(calls):
        return None
    return statistics.median(s - round(c["t0"] * 1e9)
                             for s, c in zip(starts, calls))


def stages(run, offset):
    """(start, end, name) of every stage span, on the trace's clock."""
    from jepsen_tpu import telemetry
    epoch = getattr(telemetry, "EPOCH_NS", None)
    if epoch is None:
        return []
    out = []
    for s in program_spans(run):
        if s["name"] not in WRAPPERS:
            t0 = epoch + offset + round(s["ts"] * 1e3)
            out.append((t0, t0 + round(s["dur"] * 1e3), s["name"]))
    return out


def idle_stretches(trace):
    """The window's stretches with no operation on the first device."""
    gaps, at = [], trace.lo
    for s, e in trace.busy(sorted(trace.devices)[0]) + [(trace.hi,
                                                         trace.hi)]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    return gaps


def name_at(t, st, host):
    cover = [(e - s, n) for s, e, n in st if s <= t < e]
    if cover:
        return min(cover)[1]
    cover = [(d, n) for n, b, d in host if b <= t < b + d]
    return min(cover)[1][len("bench."):] if cover else "between calls"


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or run.khist <= 0:
        return None
    offset = clock_offset_ns(tr, run.calls)
    st = stages(run, offset) if offset is not None else []
    gaps = idle_stretches(tr)
    idle = sum(e - s for s, e in gaps)
    if not st or idle <= 0:
        return None
    covered = _union((s, e) for s, e, _ in st)
    hit = sum(e - s for g0, g1 in gaps for s, e in _clip(covered, g0, g1))
    top = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    names = "; ".join(f"{name_at((s + e) // 2, st, tr.host)} "
                      f"{(e - s) / 1e9:.6f}" for s, e in top)
    return {"value": (idle - hit) / 1e6 / run.khist,
            "share": 100.0 * (idle - hit) / idle, "gaps": names}
