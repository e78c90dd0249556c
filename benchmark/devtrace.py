"""Reduction of a JAX profiler trace to the device metrics.

``load`` reads the ``.xplane.pb`` a traced run wrote, with JAX's own
``ProfileData``, into plain planes -> lines -> events
(name, start ns, duration ns); ``Trace`` reduces those. The tests feed
``Trace`` a small recorded trace in the same plain form.

- The window is the span of the benchmark's ``bench.call`` host
  annotations: from the first call's start to the last call's end.
- Busy: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), inside the window, averaged over the
  devices that ran any.
- Kernel time: the summed device durations of the ``XLA Modules``
  events whose program name is one of the given kernels.
- Idle gaps: the stretches of the window with no operation on the
  device, each named by the innermost ``bench.*`` host annotation that
  covers its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(trace_dir: str) -> Optional["Trace"]:
    """The newest trace under ``trace_dir``, or None if there is none."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[-1])
    planes = []
    for plane in pd.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events]
            lines.append({"name": line.name, "events": evs if dev else [
                e for e in evs if e[0].startswith("bench.")]})
        planes.append({"name": plane.name, "lines": lines})
    tr = Trace(planes)
    return tr if tr.devices else None


def module_name(name: str) -> str:
    """``jit_check(123)`` / ``jit_check.4`` -> ``jit_check``."""
    return re.split(r"[(.]", name, maxsplit=1)[0]


def op_label(name: str) -> str:
    """A device op's short label: the HLO name and the first shaped
    type of its result (``%while.84 u32[64,65536]``), not the whole
    instruction text the trace carries."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"[a-z]+\d*\[[0-9,]+\]", rest)
    return f"{head} {m.group(0)}" if m else head


def _union(ivs: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


class Trace:
    def __init__(self, planes: List[dict]):
        self.devices: Dict[str, Dict[str, list]] = {}
        self.host: List[Tuple[str, int, int]] = []
        for p in planes:
            lines = {ln["name"]: ln["events"] for ln in p["lines"]}
            if DEVICE_PLANE.match(p["name"]):
                if lines.get(OPS_LINE):
                    self.devices[p["name"]] = lines
            else:
                for ln in p["lines"]:
                    self.host.extend(ev for ev in ln["events"]
                                     if ev[0].startswith("bench."))
        calls = [(s, s + d) for n, s, d in self.host if n == "bench.call"]
        self.n_calls = len(calls)
        if calls:
            self.lo, self.hi = min(s for s, _ in calls), max(
                e for _, e in calls)
        else:
            ends = [(s, s + d) for lines in self.devices.values()
                    for _, s, d in lines[OPS_LINE]]
            self.lo = min((s for s, _ in ends), default=0)
            self.hi = max((e for _, e in ends), default=0)

    def coverage(self) -> str:
        """Where the device events lie in the window, for the log: a
        trace that lost events shows it here first."""
        out = []
        for dev, lines in sorted(self.devices.items()):
            ops = lines[OPS_LINE]
            lo = min(s for _, s, _ in ops)
            hi = max(s + d for _, s, d in ops)
            out.append(f"{dev}: {len(ops)} ops from {(lo - self.lo) / 1e9:.3f}"
                       f" to {(hi - self.lo) / 1e9:.3f} s")
        return (f"{self.n_calls} bench.call annotations, window "
                f"{self.window_s:.3f} s; " + "; ".join(out))

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy(self, device: str) -> List[Tuple[int, int]]:
        ops = self.devices[device][OPS_LINE]
        return _clip(_union((s, s + d) for _, s, d in ops), self.lo,
                     self.hi)

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        tot = sum(e - s for dev in self.devices for s, e in self.busy(dev))
        return tot / len(self.devices) / 1e9

    def kernel_s(self, names: Iterable[str]) -> float:
        """Device seconds of the named programs, summed over devices."""
        names = set(names)
        tot = 0
        for lines in self.devices.values():
            for n, s, d in lines.get(MODULES_LINE, []):
                if module_name(n) in names:
                    tot += sum(e - b for b, e in _clip([(s, s + d)],
                                                       self.lo, self.hi))
        return tot / 1e9

    def device_ops(self, top: int = 10) -> List[list]:
        """The device operations that took most time: [name, seconds]."""
        tot: Dict[str, int] = {}
        for lines in self.devices.values():
            for n, s, d in lines[OPS_LINE]:
                for b, e in _clip([(s, s + d)], self.lo, self.hi):
                    k = op_label(n)
                    tot[k] = tot.get(k, 0) + (e - b)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[n, t / 1e9] for n, t in best]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """The longest idle stretches of the first device, each named by
        what the host was doing: [name, seconds]."""
        if not self.devices:
            return []
        busy = self.busy(sorted(self.devices)[0])
        gaps, at = [], self.lo
        for s, e in busy + [(self.hi, self.hi)]:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            mid = (s + e) // 2
            cover = [(d, n) for n, b, d in self.host if b <= mid < b + d]
            name = min(cover)[1][len("bench."):] if cover else "between calls"
            out.append([name, (e - s) / 1e9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}
