"""Checker nemesis: deterministic fault injection for the pipeline itself.

Jepsen's premise is that a system must stay correct under injected
faults — and the batched device checker is itself a distributed system
(host encoder, XLA runtime, device, decode path), so it gets the same
treatment. This module is the fault layer ops.schedule's degradation
ladder is tested against: a FaultPlan names which fault fires at which
pipeline-stage boundary on which chunk, a FaultInjector executes it
deterministically, and tests assert verdict parity (field-for-field
against the fault-free run) under every schedule.

Stages mirror the streaming pipeline's boundaries:

  * ``encode``   — host-side chunk padding (before any bytes move);
  * ``dispatch`` — the device kernel call;
  * ``decode``   — the blocking device→host materialize.

Fault kinds model the real failure classes seen in production:

  * ``oom``     — raises a synthetic error carrying RESOURCE_EXHAUSTED
                  (the XLA allocator's message), driving the
                  scheduler's Bp-bisection path;
  * ``timeout`` — the chunk runs long enough to trip the watchdog
                  deadline once, then completes (late results are
                  discarded; the retry wins);
  * ``wedge``   — like timeout but far past the deadline: the dispatch
                  never comes back in useful time (the wedged-RPC /
                  hung-runtime threat model, see DaemonFuture);
  * ``corrupt`` — the decoded verdict arrays are garbage; caught by
                  ``validate_decoded`` and treated as a retryable
                  fault (persistent corruption bisects down to the
                  poison rows, which quarantine to the host engine);
  * ``kill``    — an unclassified error that aborts the whole check
                  mid-stream (the process-death model); the scheduler
                  deliberately does NOT absorb it — it exists to test
                  the durable chunk journal's resume path.

Every injection is seeded by (stage, chunk ordinal): the same plan over
the same input produces the same fault at the same point, so fault
schedules are reproducible the way nemesis seeds are for databases.
Classification of *real* runtime failures (``classify_failure``) lives
here too, so the ladder treats injected and genuine faults through one
code path and the tests exercise exactly what production runs.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

STAGES = ("encode", "dispatch", "decode")
KINDS = ("oom", "timeout", "wedge", "corrupt", "kill")

INT32_MAX = np.int32(2**31 - 1)

# Exception type names classified as runtime (retryable / bisectable)
# failures. jax raises jaxlib's XlaRuntimeError for device and
# allocator errors; newer jax aliases it as JaxRuntimeError.
_RUNTIME_ERROR_NAMES = {"XlaRuntimeError", "JaxRuntimeError"}


class InjectedFault(RuntimeError):
    """A synthetic pipeline fault. ``kind == "oom"`` carries the XLA
    allocator's RESOURCE_EXHAUSTED tag so the one classifier handles
    injected and genuine OOMs identically."""

    def __init__(self, kind: str, stage: str, ordinal: int):
        self.kind, self.stage, self.ordinal = kind, stage, ordinal
        msg = f"injected {kind} at {stage} chunk {ordinal}"
        if kind == "oom":
            msg = "RESOURCE_EXHAUSTED: " + msg
        super().__init__(msg)


class InjectedKill(RuntimeError):
    """Deliberately unclassified: aborts the check mid-stream (the
    process-death fault the chunk journal's resume path is for)."""


class CorruptOutput(RuntimeError):
    """A decoded chunk failed the verdict-shape invariants
    (validate_decoded) — garbage from the device or the transfer."""


class WatchdogExpired(RuntimeError):
    """A chunk's decode exceeded its VPU-op-model deadline."""


def classify_failure(e: BaseException) -> Optional[str]:
    """Map a failure to the degradation ladder's branch.

    Returns ``"oom"`` (bisect the chunk), ``"transient"`` (bounded
    retry with backoff), or None (not a pipeline fault — programming
    errors and InjectedKill propagate untouched). One classifier for
    injected AND genuine faults, so the tested path is the production
    path.
    """
    if isinstance(e, InjectedKill):
        return None
    if isinstance(e, InjectedFault):
        return "oom" if e.kind == "oom" else "transient"
    if isinstance(e, (CorruptOutput, WatchdogExpired)):
        return "transient"
    if type(e).__name__ in _RUNTIME_ERROR_NAMES:
        return "oom" if "RESOURCE_EXHAUSTED" in str(e) else "transient"
    return None


def validate_decoded(valid: np.ndarray, bad: np.ndarray,
                     n_events: int) -> None:
    """Verdict-shape invariants every decoded chunk must satisfy: valid
    rows carry the INT32_MAX sentinel, invalid rows a bad-event index
    inside the real event axis. Cheap (two vectorized comparisons per
    chunk) and always on — this is how corrupt device output becomes a
    retryable fault instead of a wrong verdict."""
    v = np.asarray(valid)
    b = np.asarray(bad)
    if v.dtype != np.bool_ or v.shape != b.shape:
        raise CorruptOutput(
            f"verdict arrays malformed: valid {v.dtype}{v.shape} "
            f"bad {b.dtype}{b.shape}")
    if v.size and not (b[v] == INT32_MAX).all():
        raise CorruptOutput("valid row without the INT32_MAX sentinel")
    inv = b[~v]
    if inv.size and ((inv < 0) | (inv >= n_events)).any():
        raise CorruptOutput(
            f"invalid row with bad-event index outside [0, {n_events})")


def corrupt_arrays(valid: np.ndarray, bad: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """The ``corrupt`` fault's payload: verdicts flipped, bad indices
    insane — the shape a trashed transfer actually produces, and
    exactly what validate_decoded must catch."""
    v = np.asarray(valid).copy()
    b = np.asarray(bad).copy()
    v[:] = ~v
    b[:] = -7
    return v, b


@dataclass(frozen=True)
class FaultSpec:
    """One fault: ``kind`` at ``stage``, firing on chunk ordinal
    ``chunk`` (per-stage dispatch counter) or on EVERY chunk when
    ``chunk`` is None (a sticky/persistent fault)."""

    stage: str
    kind: str
    chunk: Optional[int] = 0

    def __post_init__(self):
        assert self.stage in STAGES, self.stage
        assert self.kind in KINDS, self.kind

    def matches(self, stage: str, ordinal: int) -> bool:
        return self.stage == stage and (self.chunk is None
                                        or self.chunk == ordinal)


class FaultPlan:
    """A deterministic fault schedule plus the timing the nemesis runs
    under. An active plan also shrinks the watchdog deadline and retry
    backoff — a nemesis exists to make faults FAST to exercise, and the
    production values (minutes) would turn every schedule into a soak
    test. ``deadline_s=None`` keeps the scheduler's own op-model
    deadline."""

    def __init__(self, specs: List[FaultSpec], *,
                 deadline_s: Optional[float] = 0.75,
                 sleep_timeout_s: float = 1.2,
                 sleep_wedge_s: float = 2.5,
                 backoff_s: float = 0.01):
        self.specs = list(specs)
        self.deadline_s = deadline_s
        self.sleep_timeout_s = sleep_timeout_s
        self.sleep_wedge_s = sleep_wedge_s
        self.backoff_s = backoff_s

    @classmethod
    def single(cls, stage: str, kind: str, chunk: int = 0,
               **kw) -> "FaultPlan":
        """One fault, once, at a specific chunk — the single-fault
        schedules the parity tests sweep."""
        return cls([FaultSpec(stage, kind, chunk)], **kw)

    @classmethod
    def sticky(cls, stage: str, kind: str, **kw) -> "FaultPlan":
        """The fault fires on EVERY chunk at that stage — persistent
        corruption/pressure; drives the full ladder down to poison-row
        quarantine."""
        return cls([FaultSpec(stage, kind, None)], **kw)

    @classmethod
    def parse(cls, text: str, **kw) -> "FaultPlan":
        """``"stage:kind[:chunk]"`` specs, comma/semicolon-separated;
        chunk ``*`` means sticky (the $JT_FAULT_PLAN syntax)."""
        specs = []
        for part in text.replace(";", ",").split(","):
            part = part.strip()
            if not part:
                continue
            bits = part.split(":")
            stage, kind = bits[0], bits[1]
            chunk: Optional[int] = 0
            if len(bits) > 2:
                chunk = None if bits[2] == "*" else int(bits[2])
            specs.append(FaultSpec(stage, kind, chunk))
        return cls(specs, **kw)

    def match(self, stage: str, ordinal: int) -> Optional[FaultSpec]:
        for s in self.specs:
            if s.matches(stage, ordinal):
                return s
        return None


def single_fault_schedules() -> List[Tuple[str, FaultPlan]]:
    """The canonical single-fault matrix the parity tests sweep: OOM at
    every stage boundary, one deadline-tripping timeout, one wedge, and
    one corrupt-output — each fired exactly once, on the first chunk
    that reaches its stage."""
    out = [(f"oom@{stage}", FaultPlan.single(stage, "oom"))
           for stage in STAGES]
    out.append(("timeout@dispatch", FaultPlan.single("dispatch",
                                                     "timeout")))
    out.append(("wedge@dispatch", FaultPlan.single("dispatch", "wedge")))
    out.append(("corrupt@decode", FaultPlan.single("decode", "corrupt")))
    return out


class FaultInjector:
    """Executes a FaultPlan at the pipeline's stage boundaries.

    ``fire(stage)`` is called once per chunk per stage (thread-safe:
    decode fires on watchdog worker threads). It raises for oom/kill
    faults and otherwise returns the fired kind — the CALLER interprets
    timeout/wedge (sleep via ``sleep_for``, applied where the watchdog
    can see it) and corrupt (apply ``corrupt_arrays`` to the decoded
    verdicts). ``log`` records every firing as (stage, ordinal, kind)
    for stats and for tests to assert the schedule actually engaged.
    """

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self.log: List[Tuple[str, int, str]] = []
        self._ordinal: Dict[str, int] = {s: 0 for s in STAGES}
        self._lock = threading.Lock()

    @property
    def deadline_s(self) -> Optional[float]:
        return self.plan.deadline_s

    @property
    def backoff_s(self) -> Optional[float]:
        return self.plan.backoff_s

    def sleep_for(self, kind: Optional[str]) -> float:
        if kind == "timeout":
            return self.plan.sleep_timeout_s
        if kind == "wedge":
            return self.plan.sleep_wedge_s
        return 0.0

    def fire(self, stage: str) -> Optional[str]:
        with self._lock:
            n = self._ordinal[stage]
            self._ordinal[stage] = n + 1
            spec = self.plan.match(stage, n)
            if spec is None:
                return None
            self.log.append((stage, n, spec.kind))
        if spec.kind == "kill":
            raise InjectedKill(f"injected kill at {stage} chunk {n}")
        if spec.kind == "oom":
            raise InjectedFault("oom", stage, n)
        return spec.kind

    @classmethod
    def from_env(cls) -> Optional["FaultInjector"]:
        """$JT_FAULT_PLAN (e.g. ``dispatch:oom:0,decode:corrupt:*``)
        activates the nemesis process-wide — the CLI-level hook for
        running any suite or recheck under a fault schedule."""
        text = os.environ.get("JT_FAULT_PLAN")
        if not text:
            return None
        return cls(FaultPlan.parse(text))


# ------------------------------------------------- run-level nemesis

# Run lifecycle phases, in stamp order (runtime.run's WAL stamps).
RUN_PHASES = ("setup", "run", "teardown", "analyzed")

RUN_FAULT_KINDS = ("op", "phase", "wedge")


class RunFaultInjector:
    """The crash nemesis for the RUN layer — $JT_RUN_FAULT.

    Where FaultPlan kills the *checker* pipeline at chunk boundaries,
    this kills the *test run* itself at seeded lifecycle points, so
    crash-recovery parity (WAL salvage, campaign resume) is provable
    the same way checker-fault parity is. Grammar (one spec):

      * ``op:K[@R]``      — SIGKILL the process immediately after the
                            Kth history op (0-based, WAL-durable first)
                            of the Rth run in this process (default 0);
      * ``phase:NAME[@R]``— SIGKILL at that phase-stamp boundary (the
                            stamp is flushed first, so salvage sees the
                            boundary was reached);
      * ``wedge:K[:S]``   — the Kth barrier arrival (0-based, process-
                            wide) sleeps S seconds (default 3600) —
                            wedging a worker past the barrier deadline
                            so retirement is exercised, not simulated.

    Kills are SIGKILL — no handlers, no flushing beyond what already
    hit the disk: exactly the failure mode the WAL exists for. The
    fsync-before-kill for ``op:K`` is what makes schedules
    deterministic: salvage recovers exactly ops 0..K, every time.
    """

    def __init__(self, kind: str, arg, run: int = 0,
                 wedge_s: float = 3600.0):
        # ValueError, not assert: a typo'd $JT_RUN_FAULT must fail
        # loudly even under -O — a silently inert crash nemesis turns
        # every durability run into a vacuous pass.
        if kind not in RUN_FAULT_KINDS:
            raise ValueError(f"unknown run fault kind {kind!r} "
                             f"(kinds: {RUN_FAULT_KINDS})")
        if kind == "phase" and arg not in RUN_PHASES:
            raise ValueError(f"unknown run phase {arg!r} "
                             f"(phases: {RUN_PHASES})")
        self.kind = kind
        self.arg = arg
        self.run = run
        self.wedge_s = wedge_s
        self._runs = -1          # bumped by begin_run → 0-based ordinal
        self._arrivals = 0
        self._lock = threading.Lock()

    @classmethod
    def parse(cls, text: str) -> "RunFaultInjector":
        """``op:12``, ``op:12@2``, ``phase:teardown``, ``wedge:1:0.5``."""
        bits = text.strip().split(":")
        kind = bits[0]
        if kind == "op":
            arg, run = bits[1], 0
            if "@" in arg:
                arg, r = arg.split("@")
                run = int(r)
            return cls("op", int(arg), run)
        if kind == "phase":
            arg, run = bits[1], 0
            if "@" in arg:
                arg, r = arg.split("@")
                run = int(r)
            return cls("phase", arg, run)
        if kind == "wedge":
            wedge_s = float(bits[2]) if len(bits) > 2 else 3600.0
            return cls("wedge", int(bits[1]), wedge_s=wedge_s)
        raise ValueError(f"unknown run fault {text!r} "
                         f"(kinds: {RUN_FAULT_KINDS})")

    @classmethod
    def from_env(cls) -> Optional["RunFaultInjector"]:
        text = os.environ.get("JT_RUN_FAULT")
        if not text:
            return None
        return cls.parse(text)

    def begin_run(self) -> None:
        """Called once per runtime.run — op/phase specs target one run
        ordinal, so seed campaigns can kill mid-campaign."""
        with self._lock:
            self._runs += 1

    def _kill(self) -> None:
        import signal
        os.kill(os.getpid(), signal.SIGKILL)

    def on_op(self, wal, ordinal: int) -> None:
        """WAL hook: op ``ordinal`` just appended. For a matching spec,
        force the group commit (the op must be salvageable — that is
        the fault being modeled: death AFTER acknowledge) then die."""
        if self.kind == "op" and self._runs == self.run \
                and ordinal == self.arg:
            wal.sync()
            self._kill()

    def on_phase(self, wal, phase: str) -> None:
        """WAL hook: ``phase`` stamp just written (and flushed)."""
        if self.kind == "phase" and self._runs == self.run \
                and phase == self.arg:
            wal.sync()
            self._kill()

    def barrier_delay(self) -> float:
        """DeadlineBarrier hook: seconds this arrival should sleep
        before waiting (0 for non-matching arrivals)."""
        if self.kind != "wedge":
            return 0.0
        with self._lock:
            n = self._arrivals
            self._arrivals = n + 1
        return self.wedge_s if n == self.arg else 0.0
