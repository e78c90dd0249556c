"""Pipeline telemetry spine: span tracing + the unified metrics registry.

The r05 roofline said the checker is dispatch/latency-bound (`hbm_util`
0.0018) — yet nothing could *show* where wall-clock goes between XLA
calls: timing lived in ad-hoc per-subsystem stats dicts
(``BucketScheduler.stats``, ``AOT_STATS``, WAL flush deques, run
resilience counters) that never composed into one picture. Following
the trace-level-observability argument of OmniLink (arXiv:2601.11836)
— live validation of an unmodified system needs its traces — this
module is the one spine every stage reports through:

  * **span tracer** — a process-wide, thread-local span stack. A span
    is an interval with a name, a category (``"device"`` for dispatch
    launches and device waits, ``"host"`` for everything else), and
    attributes (W class, rows, chunk ordinal, fuse-group id,
    provenance...). Completed spans land in a monotonic-clock
    ring-buffer flight recorder (bounded; the newest ``ring`` spans
    survive) and, when a sink path is configured, append to a JSONL
    trace file. ``export_chrome`` writes the standard Chrome-trace /
    Perfetto ``trace.json`` (load it at chrome://tracing or
    ui.perfetto.dev). Instant ``event``s record point occurrences
    (retries, bisections, quarantines, campaign resumes).

  * **metrics registry** — counters / gauges / histograms with labels,
    lock-protected, snapshot-to-dict (``REGISTRY``). The registry is
    ALWAYS on (increments are a dict bump under a lock — the
    scattered per-subsystem counters it replaces cost the same without
    the thread safety); only the span tracer is gated.

  * **dispatch-gap analyzer** (``gaps``) — the direct diagnostic for
    the 1.9k/s plateau: over a window of recorded spans, the union of
    ``"device"``-category intervals is the device-active time; the
    complement is host gap, and each gap is attributed to the host
    spans overlapping it. The bench's ``telemetry`` section reports
    the fractions and the top gap causes.

Enabling: ``JT_TRACE=1`` turns the tracer on (flight recorder only);
``JT_TRACE=<path>`` additionally streams every record to ``<path>`` as
JSONL (the ``jepsen-tpu trace`` subcommand summarizes/exports such a
file). Unset or ``0``: every ``span()``/``event()`` call is a no-op
returning a shared singleton — no Span object, no record, nothing
retained — so the instrumented hot paths cost one predicate each.
``JT_TRACE_RING`` sizes the flight recorder (default 65536 spans).

Profiler bridge: while the tracer is on and a JAX profiler session is
recording (``jax.profiler.start_trace``), every span also opens a
``jax.profiler.TraceAnnotation`` named ``jt.<span name>`` where it
begins and closes it where it ends, so program spans land in the
profiler's ``.xplane.pb`` on the device trace's own clock, next to the
device ops. The tracer never imports JAX itself: a process that has
not loaded it gets no annotations (and no profiler could be recording).
A span that ends on another thread than it began on gets none.
``traced_counters`` gives the registry's counter deltas over the same
stretch the tracer was on, to pair with its spans.

Metric naming scheme (doc/observability.md): dotted
``subsystem.metric`` names plus sorted ``{label=value}`` suffixes —
``scheduler.retries{family=wgl}``, ``aot.hits``, ``wal.flush_ms``
(histogram), ``run.barrier_timeouts``, ``journal.rows``. Snapshots are
deterministic: keys sort, floats round, and two snapshots of the same
state compare equal — ``store.save_results`` merges one canonical
``telemetry`` block into ``results.json`` from it.

The cluster observability plane (doc/observability.md "The cluster
plane") builds three layers on this spine:

  * **correlation ids** — every span/event record carries the active
    correlation id (``corr``): the campaign id for fleet units, the
    tenant key + writer incarnation for online/service tenants, the
    run dir for plain runs. A process-wide default
    (``set_correlation`` / $JT_CORR, inherited by spawned workers)
    plus a thread-local override (``correlation_scope``) mean a child
    worker's spans inherit the id that names the cluster-level unit
    of work, so ``merge_traces`` can lay N workers' traces on one
    timeline and draw flow arrows between the spans that belong to
    the same tenant/campaign.
  * **OpenMetrics export** — ``openmetrics(snapshot)`` renders any
    registry snapshot (live or series-merged) in the Prometheus text
    exposition format; ``web.py /metrics`` and ``jepsen-tpu metrics``
    serve it. Histograms carry real cumulative ``le`` buckets
    (maintained incrementally in ``observe`` — the reservoir only
    feeds p50/p99), so a scrape is a first-class histogram, not a
    summary impostor.
  * **series / alerts** — ``telemetry.series`` (durable per-worker
    snapshot frames under ``store/telemetry/``) and
    ``telemetry.alerts`` (the SLO burn-rate evaluator over them) are
    sibling modules re-exported here.
"""
from __future__ import annotations

import bisect
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence

# ------------------------------------------------------------- config

_CONF_LOCK = threading.Lock()
_ENABLED = False
_SINK_PATH: Optional[str] = None
_SINK = None                    # open file handle (lazy)
_RING: deque = deque(maxlen=65536)
_CONFIGURED = False

# Trace epoch: all timestamps are monotonic ns relative to this, so
# records from one process compose and export without clock skew.
# A record's ``ts`` (µs) is at ``EPOCH_NS + ts * 1e3`` on
# time.monotonic_ns().
EPOCH_NS = time.monotonic_ns()

# The registry's counters when configure() last turned the tracer on,
# and when it last turned it off (None while still on).
_COUNTS_ON: Optional[Dict[str, float]] = None
_COUNTS_OFF: Optional[Dict[str, float]] = None

_TLS = threading.local()
_IDS = iter(range(1, 1 << 62)).__next__
_ID_LOCK = threading.Lock()

# Correlation id: the cluster-level unit of work this process's (or
# this thread's) spans belong to. Process default inherits $JT_CORR so
# spawned fleet/service workers carry their parent's campaign id
# without any code in between; a thread-local stack overrides it for
# per-tenant scopes inside one multi-tenant daemon.
_CORR: Optional[str] = os.environ.get("JT_CORR") or None

# Per-sink wall-clock anchor: the first record appended to a JSONL
# sink additionally carries ``wall_s`` (time.time() at emit), so
# merge_traces can align N processes' monotonic-relative timestamps
# onto one wall-clock axis. Reset whenever the sink is reconfigured.
_SINK_ANCHORED = False


def set_correlation(cid: Optional[str]) -> Optional[str]:
    """Set the PROCESS-default correlation id (None clears). Returns
    the previous value so callers can restore it (runtime.run does:
    the run-dir id must not leak past the run, and must not clobber a
    campaign id a fleet worker already installed)."""
    global _CORR
    prev = _CORR
    _CORR = cid
    return prev


def correlation() -> Optional[str]:
    """The active correlation id: the innermost ``correlation_scope``
    on THIS thread, else the process default."""
    stack = getattr(_TLS, "corr", None)
    if stack:
        return stack[-1]
    return _CORR


@contextmanager
def correlation_scope(cid: Optional[str]):
    """Thread-local correlation override — the per-tenant scope a
    multi-tenant daemon wraps around one tenant's check/finalize so
    every span underneath (encode, dispatch, decode...) inherits the
    tenant's id while a sibling tenant's spans carry its own."""
    stack = getattr(_TLS, "corr", None)
    if stack is None:
        stack = _TLS.corr = []
    stack.append(cid)
    try:
        yield
    finally:
        stack.pop()


def _next_id() -> int:
    with _ID_LOCK:
        return _IDS()


def _ring_size() -> int:
    try:
        return max(16, int(os.environ.get("JT_TRACE_RING", "65536")))
    except ValueError:
        return 65536


def configure(trace=None, ring: Optional[int] = None) -> None:
    """(Re)configure the tracer. ``trace``: True (recorder only), a
    path (recorder + JSONL sink), False/None/"0" (off), or "env" to
    re-read $JT_TRACE. Reconfiguring swaps in a fresh ring buffer and
    closes any open sink — the test/bench seam."""
    global _ENABLED, _SINK_PATH, _SINK, _RING, _CONFIGURED, \
        _SINK_ANCHORED, _COUNTS_ON, _COUNTS_OFF
    with _CONF_LOCK:
        was = _ENABLED
        _SINK_ANCHORED = False
        if trace == "env":
            trace = os.environ.get("JT_TRACE")
            if trace in (None, "", "0"):
                trace = False
            elif trace == "1":
                trace = True
        if _SINK is not None:
            try:
                _SINK.close()
            except Exception:
                pass
            _SINK = None
        _SINK_PATH = None
        if trace in (None, False, "", "0"):
            _ENABLED = False
        elif trace is True or trace == "1":
            _ENABLED = True
        else:
            _ENABLED = True
            _SINK_PATH = str(trace)
        _RING = deque(maxlen=_ring_size() if ring is None else max(16,
                                                                   ring))
        _CONFIGURED = True
        if _ENABLED and not was:
            _COUNTS_ON, _COUNTS_OFF = REGISTRY.counters(), None
        elif was and not _ENABLED:
            _COUNTS_OFF = REGISTRY.counters()
    _drop_orphans()


def traced_counters() -> Dict[str, float]:
    """The registry's counter deltas over the last stretch the tracer
    was on: from when ``configure`` turned it on to when it turned it
    off, or to now while it is still on; zero deltas dropped, {} if it
    never was on. A traced window reads these beside its spans."""
    base, end = _COUNTS_ON, _COUNTS_OFF
    if base is None:
        return {}
    now = end if end is not None else REGISTRY.counters()
    return {k: v - base.get(k, 0) for k, v in now.items()
            if v - base.get(k, 0)}


def _ensure_config() -> None:
    if not _CONFIGURED:
        configure("env")


def enabled() -> bool:
    """Is the span tracer on? The one predicate the instrumented hot
    paths pay when tracing is off."""
    _ensure_config()
    return _ENABLED


def _emit(rec: dict) -> None:
    """Record one completed span/event: ring buffer always, sink when
    configured. Sink writes are whole-line appends under the config
    lock — records from retire/prewarm threads never interleave. The
    first record a sink sees is additionally stamped with ``wall_s``
    (the merge_traces cross-process clock anchor)."""
    global _SINK, _SINK_ANCHORED
    _RING.append(rec)
    if _SINK_PATH is None:
        return
    with _CONF_LOCK:
        try:
            if _SINK is None:
                _SINK = open(_SINK_PATH, "a")
            if not _SINK_ANCHORED:
                # wall_s names the wall-clock instant whose trace-
                # relative coordinate is wall_ts (NOT this record's
                # ts, which is its span's start): both are sampled at
                # the same emit instant, so the pair is skew-free.
                rec = {**rec, "wall_s": round(time.time(), 6),
                       "wall_ts": (time.monotonic_ns() - EPOCH_NS)
                       / 1e3,
                       "pid": os.getpid()}
                _SINK_ANCHORED = True
            _SINK.write(json.dumps(rec, default=str) + "\n")
            _SINK.flush()
        except Exception:
            pass                 # tracing is diagnostics, never a fault


def flush() -> None:
    """Flush/close the JSONL sink (idempotent; reopens on next emit)."""
    global _SINK
    with _CONF_LOCK:
        if _SINK is not None:
            try:
                _SINK.close()
            except Exception:
                pass
            _SINK = None


# ------------------------------------------------ profiler annotations

#: The span attributes an annotation carries: the cheap integers.
_ANN_ATTRS = ("W", "rows", "chunk")
_ANN_LABELS: Dict[str, str] = {}       # span name -> "jt.<name>"
_TRACE_ME = None             # jax.profiler.TraceAnnotation, once loaded
# Annotations whose span ended on another thread: never closed, kept
# until no profiler session records, as dropping one closes it.
_ORPHANS: list = []


def _annotation(name: str, attrs: Optional[dict]):
    """The profiler annotation ``jt.<name>``, opened now, or None when
    JAX is not loaded in this process or no profiler session records."""
    global _TRACE_ME
    ann = _TRACE_ME
    if ann is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:         # JAX still importing
            return None
        ann = _TRACE_ME = TraceAnnotation
    if not ann.is_enabled():
        _ORPHANS.clear()
        return None
    label = _ANN_LABELS.get(name)
    if label is None:
        label = _ANN_LABELS[name] = "jt." + name
    if not attrs:
        return ann(label)
    return ann(label, **{k: attrs[k] for k in _ANN_ATTRS if k in attrs})


def _drop_orphans() -> None:
    """Drop the orphaned annotations unless a profiler session records:
    then dropping one would record it."""
    if _TRACE_ME is None or not _TRACE_ME.is_enabled():
        _ORPHANS.clear()


# --------------------------------------------------------------- spans

class Span:
    """One in-flight interval. Created by ``begin``/``span``; ``end``
    completes it and emits the record. Attribute updates before end
    ride ``set(**attrs)`` (e.g. a count only known at the end)."""

    __slots__ = ("name", "cat", "t0", "attrs", "sid", "parent", "corr",
                 "_done", "_ann", "_tid")

    def __init__(self, name: str, cat: str, attrs: Optional[dict],
                 parent: Optional[int]):
        self.name = name
        self.cat = cat
        self.t0 = time.monotonic_ns()
        self.attrs = attrs
        self.sid = _next_id()
        self.parent = parent
        # Captured at creation: end() may run after the enclosing
        # correlation_scope already popped.
        self.corr = correlation()
        self._done = False
        self._ann = _annotation(name, attrs)
        self._tid = threading.get_ident() if self._ann is not None \
            else None

    def set(self, **attrs) -> "Span":
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        t1 = time.monotonic_ns()
        t = threading.current_thread()
        if self._ann is not None:
            if t.ident == self._tid:
                self._ann.__exit__(None, None, None)
            else:
                # Closing it here would record it on this thread.
                _ORPHANS.append(self._ann)
            self._ann = None
        stack = getattr(_TLS, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        rec = {"ph": "X", "name": self.name, "cat": self.cat,
               "ts": (self.t0 - EPOCH_NS) / 1e3,
               "dur": (t1 - self.t0) / 1e3,
               "tid": t.ident, "tname": t.name,
               "id": self.sid}
        if self.parent is not None:
            rec["parent"] = self.parent
        if self.corr is not None:
            rec["corr"] = self.corr
        if self.attrs:
            rec["args"] = self.attrs
        _emit(rec)

    # context-manager protocol
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _NopSpan:
    """The disabled-tracer singleton: every operation is a no-op, and
    ``span()``/``begin()`` return THIS object — no allocation, no
    record, no state. ``set`` discards its kwargs."""

    __slots__ = ()

    def set(self, **attrs) -> "_NopSpan":
        return self

    def end(self) -> None:
        pass

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NOP = _NopSpan()


def begin(name: str, /, cat: str = "host", **attrs):
    """Open a span (pushes the thread-local stack); caller must
    ``end()`` it. Use for intervals that outlive a lexical scope (a
    generator's whole drive); ``span`` is the with-statement form."""
    if not enabled():
        return NOP
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    parent = stack[-1].sid if stack else None
    sp = Span(name, cat, attrs or None, parent)
    stack.append(sp)
    return sp


def span(name: str, /, cat: str = "host", **attrs):
    """Context manager: ``with telemetry.span("encode", W=9): ...``.
    Nested spans record their parent (the enclosing span on THIS
    thread). When tracing is off, returns the shared no-op singleton."""
    if not enabled():
        return NOP
    return begin(name, cat, **attrs)


def event(name: str, /, cat: str = "event", **attrs) -> None:
    """Instant occurrence (retry, bisection, quarantine, resume...)."""
    if not enabled():
        return
    t = threading.current_thread()
    rec = {"ph": "i", "name": name, "cat": cat,
           "ts": (time.monotonic_ns() - EPOCH_NS) / 1e3,
           "tid": t.ident, "tname": t.name}
    corr = correlation()
    if corr is not None:
        rec["corr"] = corr
    if attrs:
        rec["args"] = attrs
    _emit(rec)


def spans() -> List[dict]:
    """The flight recorder's current contents (oldest first)."""
    _ensure_config()
    return list(_RING)


def reset() -> None:
    """Drop recorded spans (keeps the enabled/sink configuration)."""
    _ensure_config()
    _RING.clear()


# ------------------------------------------------------------- export

def export_chrome(path, records: Optional[Sequence[dict]] = None) -> int:
    """Write records (default: the flight recorder) as a Chrome-trace /
    Perfetto ``trace.json``. Returns the number of trace events.

    Accepts both raw single-process records and ``merge_traces``
    output: records may carry their own ``pid`` (per-worker process
    lanes), ``"M"`` metadata records (process/thread names) pass
    through, and flow records (``ph`` s/t/f — the correlation-id
    arrows) keep their binding id. Malformed records — an unclosed
    span a ring wrap orphaned, a torn line's partial dict — degrade to
    defaults; an export must never crash on its input."""
    recs = list(records) if records is not None else spans()
    pid = os.getpid()
    evs = []
    tnames: Dict[tuple, str] = {}
    for r in recs:
        if not isinstance(r, dict):
            continue
        ph = r.get("ph", "X")
        rpid = r.get("pid", pid)
        if ph == "M":
            evs.append({"name": r.get("name", "?"), "ph": "M",
                        "pid": rpid, "tid": r.get("tid", 0),
                        "args": r.get("args") or {}})
            continue
        args = dict(r.get("args") or {})
        if r.get("corr") is not None:
            args.setdefault("corr", r["corr"])
        ev = {"name": r.get("name", "?"), "cat": r.get("cat", "host"),
              "ph": ph, "ts": r.get("ts", 0.0),
              "pid": rpid, "tid": r.get("tid", 0),
              "args": args}
        if ph == "X":
            ev["dur"] = r.get("dur", 0.0)
        elif ph in ("s", "t", "f"):
            ev["id"] = r.get("id", 0)
            if ph == "f":
                ev["bp"] = "e"         # bind to the enclosing slice
        else:
            ev["s"] = "t"              # thread-scoped instant
        evs.append(ev)
        key = (rpid, r.get("tid"))
        if r.get("tname") and key not in tnames:
            tnames[key] = r["tname"]
    for (rpid, tid), tname in tnames.items():
        evs.append({"name": "thread_name", "ph": "M", "pid": rpid,
                    "tid": tid, "args": {"name": tname}})
    with open(path, "w") as f:
        # default=str matches the JSONL sink's _emit: attrs may carry
        # numpy scalars or other non-JSON-native values, and an export
        # must degrade them to strings, never crash.
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f,
                  default=str)
    return len(evs)


def read_trace(path) -> List[dict]:
    """Load a JSONL trace file (the sink format), tolerating a torn
    final line the way every other log reader here does."""
    out: List[dict] = []
    with open(path, "rb") as f:
        for line in f:
            if not line.endswith(b"\n"):
                break
            try:
                out.append(json.loads(line))
            except Exception:
                break
    return out


def merge_traces(paths: Sequence) -> List[dict]:
    """Fuse N per-worker JSONL traces into ONE record list on a
    common timeline — the cross-worker correlation view
    (``jepsen-tpu trace --merge DIR``).

    Each worker's timestamps are monotonic-relative to its own
    process epoch; the sink's first record carries a (wall_s,
    wall_ts) anchor pair sampled at one instant, so every file's
    records shift onto a shared wall-clock axis (a file with no
    anchor — an old-format sink — keeps its relative times). Every
    record gets the worker's ``pid`` lane (the sink-stamped pid when
    present, else a per-file ordinal) plus a ``process_name``
    metadata record naming the source file, so Chrome/Perfetto
    renders one lane per worker. Records sharing a correlation id
    across DIFFERENT workers additionally grow flow events (ph
    s/t/f, one chain per corr id): the takeover arrows — a killed
    worker's tenant spans connect to the survivor's.
    """
    def _anchor(r):
        """(origin-µs, pid) of an anchor record, or None. A sink can
        hold SEVERAL anchors: a restarted worker reusing the same
        JT_TRACE path appends a fresh anchor (configure resets
        _SINK_ANCHORED) with a new monotonic epoch and pid — each
        incarnation's records must shift by ITS anchor, not the first
        boot's, or they render hours off in a dead pid's lane."""
        if "wall_s" not in r or "wall_ts" not in r:
            return None
        try:
            return (float(r["wall_s"]) * 1e6 - float(r["wall_ts"]),
                    r.get("pid"))
        except (TypeError, ValueError):
            return None

    per_file: List[List[dict]] = [read_trace(p) for p in paths]
    # Re-base onto the earliest anchored origin so merged timestamps
    # start near zero (Chrome renders huge absolute µs poorly).
    known = [a[0] for recs in per_file
             for a in (_anchor(r) for r in recs) if a is not None]
    base = min(known) if known else 0.0
    merged: List[dict] = []
    by_corr: Dict[str, List[dict]] = {}
    for i, (p, recs) in enumerate(zip(paths, per_file)):
        first = next((a for a in (_anchor(r) for r in recs)
                      if a is not None), None)
        # Segment state: records before the first anchor inherit it
        # (the anchor is the file's first record by construction, but
        # stay tolerant of hand-edited sinks).
        origin = first[0] if first else None
        pid = first[1] if first and isinstance(first[1], int) \
            else i + 1
        named: set = set()
        for r in recs:
            a = _anchor(r)
            if a is not None:
                origin = a[0]
                pid = a[1] if isinstance(a[1], int) else pid
            if pid not in named:
                named.add(pid)
                merged.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": Path(p).stem}})
            r = dict(r)
            r["pid"] = pid
            shift = (origin - base) if origin is not None else 0.0
            try:
                r["ts"] = float(r.get("ts", 0.0)) + shift
            except (TypeError, ValueError):
                r["ts"] = shift
            merged.append(r)
            corr = r.get("corr")
            if corr is not None and r.get("ph", "X") in ("X", "i"):
                by_corr.setdefault(str(corr), []).append(r)
    # Flow arrows only where a corr id actually crosses workers — an
    # id confined to one process is already one lane.
    for corr, recs in sorted(by_corr.items()):
        if len({r["pid"] for r in recs}) < 2:
            continue
        recs.sort(key=lambda r: r.get("ts", 0.0))
        fid = _flow_id(corr)
        for j, r in enumerate(recs):
            ph = "s" if j == 0 else ("f" if j == len(recs) - 1
                                     else "t")
            merged.append({"ph": ph, "name": f"corr:{corr}",
                           "cat": "flow", "id": fid,
                           # Nudge inside the slice so the enclosing-
                           # slice binding holds for zero-offset spans.
                           "ts": float(r.get("ts", 0.0)) + 0.01,
                           "pid": r["pid"], "tid": r.get("tid", 0)})
    merged.sort(key=lambda r: (r.get("ph") != "M",
                               r.get("ts", 0.0)))
    return merged


def _flow_id(corr: str) -> int:
    import hashlib
    return int.from_bytes(
        hashlib.sha256(corr.encode()).digest()[:4], "big")


def summarize(records: Optional[Sequence[dict]] = None) -> dict:
    """Per-name span totals over a record set (default: the flight
    recorder) — the ``jepsen-tpu trace`` summary body."""
    recs = list(records) if records is not None else spans()
    by: Dict[str, dict] = {}
    n_spans = n_events = 0
    for r in recs:
        if not isinstance(r, dict):
            continue
        ph = r.get("ph", "X")
        if ph == "i":
            n_events += 1
            continue
        if ph != "X":
            continue           # metadata / flow records: not spans
        n_spans += 1
        d = by.setdefault(r.get("name", "?"),
                          {"count": 0, "total_us": 0.0, "max_us": 0.0})
        d["count"] += 1
        dur = float(r.get("dur", 0.0))
        d["total_us"] += dur
        if dur > d["max_us"]:
            d["max_us"] = dur
    for d in by.values():
        d["total_s"] = round(d.pop("total_us") / 1e6, 6)
        d["max_us"] = round(d["max_us"], 1)
        d["mean_us"] = round(d["total_s"] * 1e6 / max(d["count"], 1), 1)
    return {"spans": n_spans, "events": n_events,
            "by_name": {k: by[k] for k in sorted(by)}}


# ------------------------------------------------- dispatch-gap report

def gaps(records: Optional[Sequence[dict]] = None, *,
         top: int = 8) -> dict:
    """Device-busy vs host-gap breakdown — the plateau diagnostic.

    Over the window spanned by ``"device"``-category spans (dispatch
    launches + device waits — the honest proxy for device activity
    this side of a hardware profiler), the union of those intervals is
    device-busy time; the complement is host gap. Each gap interval is
    attributed to the LEAF host spans overlapping it: wrapper spans
    that fully contain a device interval (``scheduler.run``,
    ``campaign.seed``, ``run.case``...) are excluded — they enclose
    every gap by construction and would always top the ranking while
    naming nothing actionable. Time no leaf span covers is
    ``(untraced)``. Returns fractions, gap count, the top causes by
    attributed seconds, and ``device_busy_by_family`` — the busy union
    broken down per backend family (the ``family=`` span attribute:
    ``wgl`` for the lax.scan kernels, ``wgl-pallas`` for the Pallas
    megakernel, ``graph`` for the MXU closure). Over a merge_traces
    record set (records carrying per-worker ``pid`` lanes) the report
    additionally attributes cluster-wide device-busy per worker AND
    per family: ``device_busy_by_worker`` is {worker: {family:
    seconds}} — which worker's device did the cluster's work, and
    through which backend."""
    recs = list(records) if records is not None else spans()
    dev = []
    host = []
    for r in recs:
        if r.get("ph") != "X":
            continue
        t0 = float(r.get("ts", 0.0))
        t1 = t0 + float(r.get("dur", 0.0))
        if r.get("cat") == "device":
            fam = (r.get("args") or {}).get("family") or "(untagged)"
            dev.append((t0, t1, fam, r.get("pid")))
        else:
            host.append((t0, t1, r.get("name", "?")))
    if not dev:
        return {"window_s": 0.0, "device_busy_s": 0.0, "host_gap_s": 0.0,
                "device_busy_frac": None, "host_gap_frac": None,
                "n_gaps": 0, "top_gap_causes": [],
                "device_busy_by_family": {},
                "device_busy_by_worker": {}}

    def _merge(ivs):
        ivs = sorted(ivs)
        out = [list(ivs[0])]
        for t0, t1 in ivs[1:]:
            if t0 <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t1)
            else:
                out.append([t0, t1])
        return out

    by_fam_ivs: Dict[str, list] = {}
    by_worker_ivs: Dict[str, Dict[str, list]] = {}
    for t0, t1, fam, pid in dev:
        by_fam_ivs.setdefault(fam, []).append((t0, t1))
        if pid is not None:
            by_worker_ivs.setdefault(str(pid), {}) \
                .setdefault(fam, []).append((t0, t1))
    by_family = {
        fam: round(sum(b - a for a, b in _merge(ivs)) / 1e6, 6)
        for fam, ivs in sorted(by_fam_ivs.items())}
    by_worker = {
        w: {fam: round(sum(b - a for a, b in _merge(ivs)) / 1e6, 6)
            for fam, ivs in sorted(fams.items())}
        for w, fams in sorted(by_worker_ivs.items())}
    merged = _merge([(t0, t1) for t0, t1, _, _ in dev])
    # Leaf filter by bisect against the merged device intervals (a
    # full pairwise scan is O(hosts x devices) — minutes of CPU on a
    # default-size ring): a host span is a wrapper iff the first
    # merged interval starting at/after it also ends inside it.
    starts = [a for a, _ in merged]

    def _wrapper(h0, h1):
        i = bisect.bisect_left(starts, h0)
        return i < len(merged) and merged[i][1] <= h1

    host = [(h0, h1, name) for h0, h1, name in host
            if not _wrapper(h0, h1)]
    w0, w1 = merged[0][0], merged[-1][1]
    window = w1 - w0
    busy = sum(b - a for a, b in merged)
    gap_ivs = [(merged[i][1], merged[i + 1][0])
               for i in range(len(merged) - 1)]
    # Attribution by one event sweep (near-linear): walk gap and host
    # interval edges in time order; inside a gap, each time slice is
    # charged once to every distinct active leaf-span name, or to
    # ``(untraced)`` when none is active.
    evs: List[tuple] = []
    for a, b in gap_ivs:
        evs.append((a, 1, "\x00gap"))
        evs.append((b, 0, "\x00gap"))
    for h0, h1, name in host:
        evs.append((h0, 1, name))
        evs.append((h1, 0, name))
    evs.sort(key=lambda e: (e[0], e[1]))      # ends before starts
    causes: Dict[str, float] = {}
    active: Dict[str, int] = {}
    in_gap = 0
    gap_total = 0.0
    last_t = evs[0][0] if evs else 0.0
    for t, kind, name in evs:
        dt = t - last_t
        if dt > 0 and in_gap:
            gap_total += dt
            if active:
                for n in active:
                    causes[n] = causes.get(n, 0.0) + dt
            else:
                causes["(untraced)"] = \
                    causes.get("(untraced)", 0.0) + dt
        last_t = t
        if name == "\x00gap":
            in_gap += 1 if kind else -1
        elif kind:
            active[name] = active.get(name, 0) + 1
        else:
            if active.get(name, 0) <= 1:
                active.pop(name, None)
            else:
                active[name] -= 1
    order = sorted(causes.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": round(window / 1e6, 6),
        "device_busy_s": round(busy / 1e6, 6),
        "host_gap_s": round(gap_total / 1e6, 6),
        "device_busy_frac": round(busy / window, 4) if window else None,
        "host_gap_frac": round(gap_total / window, 4) if window else None,
        "n_gaps": len(gap_ivs),
        "top_gap_causes": [[name, round(s / 1e6, 6)]
                           for name, s in order],
        "device_busy_by_family": by_family,
        "device_busy_by_worker": by_worker,
    }


# ---------------------------------------------------- metrics registry

def _key(name: str, labels: dict) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class _Counter:
    __slots__ = ("_reg", "_k")

    def __init__(self, reg, k):
        self._reg, self._k = reg, k

    def inc(self, n=1) -> None:
        with self._reg._lock:
            self._reg._counters[self._k] = \
                self._reg._counters.get(self._k, 0) + n


class _Gauge:
    __slots__ = ("_reg", "_k")

    def __init__(self, reg, k):
        self._reg, self._k = reg, k

    def set(self, v) -> None:
        with self._reg._lock:
            self._reg._gauges[self._k] = v


#: Fixed histogram bucket upper bounds (seconds-or-ms scale agnostic —
#: log-spaced over the latency range every recorded histogram spans).
#: Maintained incrementally in observe() so a snapshot carries REAL
#: cumulative ``le`` buckets for the Prometheus exposition; the
#: reservoir keeps feeding p50/p99 (exact over the recent window).
HIST_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 120.0)


class _Histogram:
    __slots__ = ("_reg", "_k")

    RESERVOIR = 4096

    def __init__(self, reg, k):
        self._reg, self._k = reg, k

    def observe(self, v) -> None:
        v = float(v)
        with self._reg._lock:
            h = self._reg._hists.get(self._k)
            if h is None:
                h = self._reg._hists[self._k] = {
                    "count": 0, "sum": 0.0, "min": v, "max": v,
                    "_res": deque(maxlen=self.RESERVOIR),
                    "_b": [0] * (len(HIST_BUCKETS) + 1)}
            h["count"] += 1
            h["sum"] += v
            if v < h["min"]:
                h["min"] = v
            if v > h["max"]:
                h["max"] = v
            h["_res"].append(v)
            h["_b"][bisect.bisect_left(HIST_BUCKETS, v)] += 1


class Registry:
    """Lock-protected metrics store. Handles are cheap stateless views;
    every mutation takes the one registry lock, so concurrent bucket
    executor threads can't drop counts (the BucketScheduler.stats race
    this replaces). ``snapshot()`` is deterministic: sorted keys,
    rounded floats, plain JSON types."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, object] = {}
        self._hists: Dict[str, dict] = {}

    def counter(self, name: str, **labels) -> _Counter:
        return _Counter(self, _key(name, labels))

    def counters(self) -> Dict[str, float]:
        """A copy of every counter's current value, by key."""
        with self._lock:
            return dict(self._counters)

    def gauge(self, name: str, **labels) -> _Gauge:
        return _Gauge(self, _key(name, labels))

    def histogram(self, name: str, **labels) -> _Histogram:
        return _Histogram(self, _key(name, labels))

    def get(self, name: str, **labels):
        k = _key(name, labels)
        with self._lock:
            if k in self._counters:
                return self._counters[k]
            if k in self._gauges:
                return self._gauges[k]
            h = self._hists.get(k)
            return dict(h, _res=None) if h is not None else None

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    def snapshot(self) -> dict:
        """JSON-friendly deterministic state: {} when nothing was ever
        recorded (the save_results merge-only-when-non-empty rule)."""
        def _pct(xs: List[float], p: float):
            if not xs:
                return None
            i = min(len(xs) - 1,
                    max(0, int(round(p / 100.0 * len(xs) + 0.5)) - 1))
            return round(xs[i], 6)

        with self._lock:
            out: dict = {}
            if self._counters:
                out["counters"] = {k: self._counters[k]
                                   for k in sorted(self._counters)}
            if self._gauges:
                out["gauges"] = {k: self._gauges[k]
                                 for k in sorted(self._gauges)}
            if self._hists:
                hs = {}
                for k in sorted(self._hists):
                    h = self._hists[k]
                    xs = sorted(h["_res"])
                    hs[k] = {"count": h["count"],
                             "sum": round(h["sum"], 6),
                             "min": round(h["min"], 6),
                             "max": round(h["max"], 6),
                             "p50": _pct(xs, 50), "p99": _pct(xs, 99)}
                    raw = h.get("_b")
                    if raw:
                        # Cumulative le counts (Prometheus histogram
                        # semantics); "+Inf" always equals count.
                        cum, buckets = 0, {}
                        for le, n in zip(HIST_BUCKETS, raw):
                            cum += n
                            buckets[repr(le)] = cum
                        buckets["+Inf"] = h["count"]
                        hs[k]["buckets"] = buckets
                out["histograms"] = hs
            return out


REGISTRY = Registry()


def snapshot() -> dict:
    """The process-wide registry snapshot (store.save_results' source
    for the results.json ``telemetry`` block)."""
    return REGISTRY.snapshot()


def preregister(counters=()) -> None:
    """Create counter series at zero so /metrics exposes them before
    the first event — a scraper watching ``ingest.shed`` must see an
    explicit 0, not an absent series, to tell "no sheds" apart from
    "no ingest plane". (inc(0) materializes the entry; histograms are
    deliberately NOT pre-created — openmetrics skips count==0.)"""
    for name in counters:
        REGISTRY.counter(name).inc(0)


def metrics_prefixed(prefix: str) -> dict:
    """Flat {metric: value} slice of the registry under a name prefix
    — counters/gauges verbatim, histograms as their summary dicts
    (count/sum/min/max/p50/p99). The online daemon's status line and
    the web /live view read their ``online.*`` SLO histograms and
    queue gauges through this instead of re-walking the full
    snapshot."""
    snap = snapshot()
    out: dict = {}
    for kind in ("counters", "gauges", "histograms"):
        for k, v in (snap.get(kind) or {}).items():
            if k.startswith(prefix):
                out[k] = v
    return out


def counters_delta(base: Optional[dict], now: dict) -> dict:
    """``now`` with its counters re-expressed as deltas over ``base``
    (zero deltas dropped). The registry is process-cumulative; a
    per-RUN results.json block must not re-report the previous runs'
    traffic as this run's — StoreHandle captures ``base`` at create
    time and save_results diffs against it. Gauges stay current-value;
    histograms stay process-cumulative distributions (documented as
    such — their p50/p99 describe latency, which doesn't double-count).
    Returns {} when nothing beyond stale counters remains."""
    out = dict(now)
    b = (base or {}).get("counters") or {}
    if "counters" in out:
        c = {k: v - b.get(k, 0) for k, v in out["counters"].items()
             if v - b.get(k, 0)}
        if c:
            out["counters"] = c
        else:
            del out["counters"]
    return out


def merge_histogram_snapshots(snaps) -> dict:
    """Fold several processes' histogram SUMMARIES (the snapshot()
    shape: count/sum/min/max/p50/p99 + optional cumulative buckets)
    into one cluster-wide view — the checking service's cross-worker
    SLO aggregation. count/sum/min/max merge exactly, and bucket
    counts sum per ``le`` bound (identical bound sets — one code base
    emits them — otherwise buckets drop rather than lie); percentiles
    cannot be recombined from summaries, so the merged p50/p99 are the
    WORST (max) per-worker values — a conservative upper bound, which
    is the right direction for an SLO breach signal (doc/service.md).

    Tolerant by contract: empty input, None members, snapshots with no
    ``histograms`` block, empty-summary members, and members whose
    metric keys are disjoint (each worker's labels differ) all merge
    without a KeyError — a cluster view must survive whatever a
    half-written registry file serves it."""
    out: dict = {}
    for s in snaps:
        for k, h in ((s or {}).get("histograms") or {}).items():
            if not isinstance(h, dict) or not h.get("count"):
                continue
            m = out.get(k)
            if m is None:
                out[k] = dict(h)
                continue
            m["count"] = m.get("count", 0) + h["count"]
            m["sum"] = round(m.get("sum", 0.0)
                             + (h.get("sum") or 0.0), 6)
            for f, pick in (("min", min), ("max", max)):
                vals = [v for v in (m.get(f), h.get(f))
                        if v is not None]
                m[f] = pick(vals) if vals else None
            for p in ("p50", "p99"):
                vals = [v for v in (m.get(p), h.get(p))
                        if v is not None]
                m[p] = max(vals) if vals else None
            mb, hb = m.get("buckets"), h.get("buckets")
            if isinstance(mb, dict) and isinstance(hb, dict) and \
                    set(mb) == set(hb):
                m["buckets"] = {le: mb[le] + hb[le] for le in mb}
            else:
                m.pop("buckets", None)
    return out


def merge_counter_snapshots(snaps) -> dict:
    """Sum the ``counters`` blocks of several processes' snapshots (or
    counters_delta outputs) into one — the fleet orchestrator's
    cross-worker aggregation: each worker persists its own per-process
    counter deltas, and the campaign-level telemetry block must report
    the FLEET's total traffic, which no single registry ever saw.
    Tolerant like its histogram sibling: empty input, None members,
    counter-less snapshots, and disjoint key sets all sum cleanly."""
    out: dict = {}
    for s in snaps:
        for k, v in ((s or {}).get("counters") or {}).items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0) + v
    return out


def merge_gauge_snapshots(snaps) -> dict:
    """Sum the numeric ``gauges`` across workers — cluster totals for
    additive gauges (pending ops, tenant counts: the only gauges the
    registry records). Non-numeric values are skipped, not summed."""
    out: dict = {}
    for s in snaps:
        for k, v in ((s or {}).get("gauges") or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = out.get(k, 0) + v
    return out


# ------------------------------------------------ OpenMetrics export

def parse_key(key: str):
    """Split a registry key back into (name, labels):
    ``"scheduler.retries{family=wgl}"`` → ("scheduler.retries",
    {"family": "wgl"}) — the inverse of ``_key``."""
    if "{" not in key or not key.endswith("}"):
        return key, {}
    name, _, inner = key.partition("{")
    labels = {}
    for part in inner[:-1].split(","):
        if "=" in part:
            lk, _, lv = part.partition("=")
            labels[lk] = lv
    return name, labels


def _prom_name(name: str) -> str:
    out = "".join(c if c.isalnum() or c == "_" else "_"
                  for c in name)
    if out and out[0].isdigit():
        out = "_" + out
    return f"jt_{out}"


def _prom_labels(labels: dict) -> str:
    if not labels:
        return ""

    def esc(v):
        return str(v).replace("\\", "\\\\").replace('"', '\\"') \
            .replace("\n", "\\n")

    def lname(k):
        return "".join(c if c.isalnum() or c == "_" else "_"
                       for c in str(k)) or "_"

    inner = ",".join(f'{lname(k)}="{esc(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _prom_num(v) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def openmetrics(snap: dict, *, labels: Optional[dict] = None) -> str:
    """Render a registry snapshot (live ``snapshot()``, a series
    frame's ``snap``, or a series-merged view) as Prometheus text
    exposition (format 0.0.4 — what every standard scraper parses).

    Metric names are sanitized under a ``jt_`` prefix with the
    registry's ``{label=value}`` suffixes decoded into real label
    sets; ``labels`` adds constant labels to every sample (the
    per-worker exposition stamps ``worker=<host>-<pid>``). Counters
    gain the conventional ``_total`` suffix; histograms expose their
    cumulative ``le`` buckets plus ``_sum``/``_count`` (p50/p99/
    min/max ride along as ``_p50``-style gauges — summaries a scraper
    can alert on without bucket math). Served by ``web.py /metrics``
    and printed offline by ``jepsen-tpu metrics``."""
    extra = dict(labels or {})
    lines: List[str] = []
    seen_types: set = set()

    def type_line(pname: str, kind: str) -> None:
        if pname not in seen_types:
            seen_types.add(pname)
            lines.append(f"# TYPE {pname} {kind}")

    for key, v in sorted((snap.get("counters") or {}).items()):
        if not isinstance(v, (int, float)):
            continue
        name, lbl = parse_key(key)
        pname = _prom_name(name) + "_total"
        type_line(pname, "counter")
        lines.append(f"{pname}{_prom_labels({**lbl, **extra})} "
                     f"{_prom_num(v)}")
    for key, v in sorted((snap.get("gauges") or {}).items()):
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        name, lbl = parse_key(key)
        pname = _prom_name(name)
        type_line(pname, "gauge")
        lines.append(f"{pname}{_prom_labels({**lbl, **extra})} "
                     f"{_prom_num(v)}")
    for key, h in sorted((snap.get("histograms") or {}).items()):
        if not isinstance(h, dict) or not h.get("count"):
            continue
        name, lbl = parse_key(key)
        pname = _prom_name(name)
        type_line(pname, "histogram")
        base = {**lbl, **extra}
        buckets = h.get("buckets")
        if isinstance(buckets, dict):
            for le, n in buckets.items():
                lines.append(
                    f"{pname}_bucket"
                    f"{_prom_labels({**base, 'le': le})} "
                    f"{_prom_num(n)}")
        else:
            # Summary-only member (merged across mismatched bounds):
            # the +Inf bucket alone still makes it a valid histogram.
            lines.append(f"{pname}_bucket"
                         f"{_prom_labels({**base, 'le': '+Inf'})} "
                         f"{_prom_num(h['count'])}")
        lines.append(f"{pname}_sum{_prom_labels(base)} "
                     f"{_prom_num(h.get('sum') or 0.0)}")
        lines.append(f"{pname}_count{_prom_labels(base)} "
                     f"{_prom_num(h['count'])}")
        for stat in ("min", "max", "p50", "p99"):
            sv = h.get(stat)
            if sv is None:
                continue
            sname = f"{pname}_{stat}"
            type_line(sname, "gauge")
            lines.append(f"{sname}{_prom_labels(base)} "
                         f"{_prom_num(sv)}")
    return "\n".join(lines) + ("\n" if lines else "")


# Sibling modules of the cluster observability plane, re-exported so
# callers write ``telemetry.series`` / ``telemetry.alerts`` (the
# doc/observability.md names). Imported last: both consume the names
# defined above.
from . import series, alerts  # noqa: E402,F401  (re-export)
