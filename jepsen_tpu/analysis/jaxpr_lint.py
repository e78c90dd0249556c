"""Device-plane static analysis: trace every registered kernel family
through jax's tracing machinery WITHOUT executing, and walk the
resulting jaxprs for hazard classes nothing else checks before
dispatch (rule ids in analysis.__init__):

* ``JTL-D-HOST`` — host-callback / transfer primitives inside a
  kernel (``pure_callback`` and friends): a host round trip per scan
  step is the single worst thing that can happen to the hot path.
* ``JTL-D-DTYPE`` — dtype widening past the family's contract. The
  columnar pipeline is int32-by-construction (int64 silently diverges
  the device from the numpy twin; float64/x64 doubles every frontier
  word); the graph family alone uses float32 (its MXU formulation).
* ``JTL-D-DONATE`` — the scheduler's chunked dispatch ships each
  event buffer exactly once, so the registry builds those jits with
  ``donate_argnums``; a kernel that silently loses donation doubles
  peak HBM per chunk.
* ``JTL-D-SHAPE`` — the AOT cache-key contract: dispatch shapes pad
  to the power-of-two ladder (ROW_QUANTUM / CARRY_QUANTUM floors), so
  a varying workload compiles a bounded shape set. A pad helper that
  stops rounding fragments the compile/AOT cache silently.
* ``JTL-D-PRIM`` — unexpected primitive families inside the kernels
  (the closure fixpoint especially): each family carries a tight
  allowlist derived from its design (the WGL scan is pure VPU bit
  work — a ``sort`` or ``dot_general`` appearing there is a wrong
  turn, not an optimization).
* ``JTL-D-VMEM`` — the Pallas static footprint model
  (ops.pallas_wgl.vmem_plan) must fit every supported (V, W) inside
  the VMEM budget; an OOM config is rejected before launch.

Tracing happens per family through small ShapeDtypeStruct probes
built with the repo's own padding discipline; nothing executes and
nothing compiles (``jit(...).trace`` / ``jax.make_jaxpr``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import (D_DONATE, D_DTYPE, D_HOST, D_PRIM, D_SHAPE, D_VMEM,
               Finding)

#: Host-interaction primitives that must never appear in a kernel.
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback", "outside_call", "infeed", "outfeed",
})

#: Structural/elementwise primitives every family may use.
_COMMON = frozenset({
    "add", "sub", "mul", "and", "or", "xor", "not", "min", "max",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n",
    "broadcast_in_dim", "reshape", "concatenate", "slice", "squeeze",
    "transpose", "iota", "convert_element_type", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "clamp",
    "gather", "scatter", "jit", "stop_gradient", "scan", "while", "cond",
    "reduce_or", "reduce_and", "pad", "copy", "dynamic_slice",
    "dynamic_update_slice",
})

#: Per-family primitive allowlists (JTL-D-PRIM) and dtype contracts
#: (JTL-D-DTYPE). Tight on purpose: widening one is a reviewed diff.
FAMILY_PRIMS: Dict[str, frozenset] = {
    "wgl": _COMMON,
    "graph": _COMMON | {"dot_general", "argmax", "div", "rem"},
    "fold": _COMMON | {"scatter-add"},
    "synth": _COMMON | {"argmax", "cumsum", "device_put", "div",
                        "rem", "reduce_max", "sign"},
    # The Pallas kernel computes on int32 lane vectors: lane rolls
    # stand in for the scan's mask reshapes, a max-reduce answers
    # "any bit set", and the frontier bitcasts back to uint32.
    "pallas": _COMMON | {"pallas_call", "program_id", "get", "swap",
                         "roll", "reduce_max", "bitcast_convert_type"},
    # The decrease-and-conquer peel loop is segment folds + gathers on
    # the VPU: scatter-min/max by cluster id, argmin for the two-minima
    # outside bound, reduce_min for the second minimum. A dot_general
    # in a peel fold is a finding — there is no contraction anywhere
    # in the algorithm.
    "dc": _COMMON | {"argmin", "reduce_min", "scatter-min",
                     "scatter-max"},
    # The isolation-ladder closure is the graph family's kernel shape
    # — bitset unpack, per-plane boolean matmul squaring, the derived
    # SI composition — so it shares the graph allowlist exactly. A
    # divergence (a new primitive appearing in the txn kernel only)
    # is a reviewed diff, which is why the family is registered
    # separately rather than aliased.
    "txn": _COMMON | {"dot_general", "argmax", "div", "rem"},
}
FAMILY_DTYPES: Dict[str, frozenset] = {
    "wgl": frozenset({"bool", "int8", "int32", "uint32"}),
    "graph": frozenset({"bool", "int32", "uint32", "float32"}),
    "fold": frozenset({"bool", "int32"}),
    "synth": frozenset({"bool", "int8", "int16", "int32", "uint32"}),
    "pallas": frozenset({"bool", "int8", "int32", "uint32"}),
    "dc": frozenset({"bool", "int32"}),
    "txn": frozenset({"bool", "int32", "uint32", "float32"}),
}


@dataclass
class DeviceReport:
    findings: List[Finding] = field(default_factory=list)
    families: List[str] = field(default_factory=list)
    #: {family: sorted primitive names} — coverage evidence for tests.
    prims_seen: Dict[str, List[str]] = field(default_factory=dict)


def _finding(rule: str, family: str, msg: str,
             context: str = "") -> Finding:
    return Finding(rule=rule, file=f"<device:{family}>", line=0,
                   message=msg, context=context or family)


# ------------------------------------------------------- jaxpr walking

def walk_jaxpr(jaxpr, prims: set, dtypes: set) -> None:
    """Collect primitive names and aval dtypes over a jaxpr and every
    sub-jaxpr reachable through eqn params (scan/while/cond bodies,
    jit calls, pallas_call kernels)."""
    from jax.extend import core as jc

    for v in (list(jaxpr.invars) + list(jaxpr.outvars)
              + list(jaxpr.constvars)):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            dtypes.add(str(aval.dtype))
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                dtypes.add(str(aval.dtype))
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else (val,)
            for x in vals:
                if isinstance(x, jc.ClosedJaxpr):
                    walk_jaxpr(x.jaxpr, prims, dtypes)
                elif isinstance(x, jc.Jaxpr):
                    walk_jaxpr(x, prims, dtypes)


def trace_family(fn, args) -> Tuple[object, Optional[tuple]]:
    """(closed jaxpr, donate_argnums-or-None) for a jitted callable —
    tracing only, nothing lowers, compiles, or executes."""
    import jax

    if hasattr(fn, "trace"):
        tr = fn.trace(*args)
        return tr.jaxpr, tuple(getattr(tr, "donate_argnums", ()) or ())
    return jax.make_jaxpr(fn)(*args), None


def check_traced(family: str, kind: str, jaxpr,
                 donate: Optional[tuple] = None,
                 donate_expected: Optional[frozenset] = None,
                 report: Optional[DeviceReport] = None
                 ) -> List[Finding]:
    """The eqn-walk rules over one traced family: callback denylist,
    primitive allowlist, dtype contract, donation expectation.
    ``kind`` picks the allowlist/dtype row; split out so the kill
    tests can feed hand-built defective jaxprs."""
    out: List[Finding] = []
    prims: set = set()
    dtypes: set = set()
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    walk_jaxpr(inner, prims, dtypes)
    if report is not None:
        report.prims_seen[family] = sorted(prims)
    for p in sorted(prims & HOST_CALLBACK_PRIMS):
        out.append(_finding(
            D_HOST, family,
            f"host callback/transfer primitive {p!r} inside the "
            f"{family} kernel — a host round trip in the hot path",
            f"{family}:{p}"))
    allow = FAMILY_PRIMS[kind] | HOST_CALLBACK_PRIMS  # denied above
    for p in sorted(prims - allow):
        out.append(_finding(
            D_PRIM, family,
            f"unexpected primitive {p!r} in the {family} kernel "
            f"(allowlist {kind!r}) — the closure fixpoint admits "
            f"only its design's primitive families",
            f"{family}:{p}"))
    for d in sorted(dtypes - FAMILY_DTYPES[kind]):
        out.append(_finding(
            D_DTYPE, family,
            f"dtype {d} in the {family} kernel widens past the "
            f"{kind!r} contract ({sorted(FAMILY_DTYPES[kind])}) — "
            f"the columnar pipeline is int32-by-construction",
            f"{family}:{d}"))
    if donate_expected is not None:
        got = frozenset(donate or ())
        missing = sorted(donate_expected - got)
        if missing:
            out.append(_finding(
                D_DONATE, family,
                f"event operands {missing} not donated in the "
                f"{family} kernel — the chunked scheduler ships each "
                f"buffer once; losing donation doubles peak HBM",
                f"{family}:donate"))
    return out


# ------------------------------------------------------ shape contract

def _is_pow2(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


def check_dispatch_shapes(pow2_helpers: Optional[Sequence] = None,
                          quanta: Optional[Dict[str, int]] = None
                          ) -> List[Finding]:
    """The AOT cache-key shape contract: every pad helper rounds up
    to a power of two, and every dispatch quantum is itself a power
    of two — so however the workload varies, the compiled/AOT shape
    set stays bounded. Overridable inputs are the kill-test seam."""
    out: List[Finding] = []
    if pow2_helpers is None:
        from ..ops import folds, graph, schedule
        pow2_helpers = [("schedule._pow2_ceil", schedule._pow2_ceil),
                        ("folds._pow2", folds._pow2),
                        ("graph.bucket_v", graph.bucket_v)]
    for name, fn in pow2_helpers:
        for x in (1, 3, 17, 100, 1000):
            y = int(fn(x))
            if y < x or not _is_pow2(y):
                out.append(_finding(
                    D_SHAPE, name,
                    f"pad helper {name}({x}) = {y} — not a "
                    f"covering power of two; data-dependent shapes "
                    f"fragment the compile/AOT cache",
                    f"{name}:{x}"))
                break
    if quanta is None:
        from ..ops import linearize, schedule
        from ..ops.pallas_wgl import event_block
        quanta = {"schedule.ROW_QUANTUM": schedule.ROW_QUANTUM,
                  "schedule.EVENT_CHUNK": schedule.EVENT_CHUNK,
                  "linearize.CARRY_QUANTUM": linearize.CARRY_QUANTUM,
                  "linearize.CARRY_EVENT_CHUNK":
                      linearize.CARRY_EVENT_CHUNK,
                  "pallas.event_block": event_block()}
    for name, q in sorted(quanta.items()):
        if not _is_pow2(int(q)):
            out.append(_finding(
                D_SHAPE, name,
                f"dispatch quantum {name} = {q} is not a power of "
                f"two — padded shapes leave the pow2 ladder",
                f"{name}:{q}"))
    return out


# --------------------------------------------------------- VMEM model

def check_pallas_vmem(configs: Optional[Sequence[Tuple[int, int]]]
                      = None,
                      budget: Optional[int] = None) -> List[Finding]:
    """Every (V, W) the Pallas kernel ADMITS must fit the static VMEM
    model — an admitted-but-OOM config would reach the launch path.
    With explicit ``configs`` (the kill/REJECTION tests), price those
    instead and report the ones that do not fit."""
    from ..ops import pallas_wgl

    out: List[Finding] = []
    if configs is None:
        configs = [(V, W)
                   for V in (8, pallas_wgl.PALLAS_MAX_STATES)
                   for W in range(1, pallas_wgl.pallas_max_w() + 1)
                   if pallas_wgl.pallas_supports(V, W)]
    for V, W in configs:
        plan = pallas_wgl.vmem_plan(V, W, budget=budget)
        if not plan["fits"]:
            out.append(_finding(
                D_VMEM, "pallas-wgl",
                f"Pallas config V={V} W={W} needs "
                f"{plan['vmem_bytes']} B VMEM "
                f"(> budget {plan['budget_bytes']}) — reject before "
                f"launch", f"pallas:{V}:{W}"))
    return out


# ------------------------------------------------------ family probes

def _sd(shape, dtype):
    import jax
    import numpy as np
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def probe_specs() -> Dict[str, dict]:
    """The registered kernel families and how to trace each: builder
    -> (fn, args), the allowlist/dtype row, and the donation
    expectation. Probe shapes follow the repo's own padding
    discipline (pow2 batch, pow2 events) — asserted by D-SHAPE."""
    import numpy as np

    B, N, V, W = 16, 64, 8, 4
    NW, M = 1, 1 << W

    def wgl_scan():
        from ..ops.linearize import get_kernel
        return (get_kernel(V, W, donate=True),
                (_sd((B, N), np.int8), _sd((B, N), np.int8),
                 _sd((B, N, W), np.int8),
                 _sd((B, W + 1, V), np.int32)))

    def wgl_resume():
        from ..ops.linearize import get_kernel
        return (get_kernel(V, W, shared_target=True, resume=True),
                (_sd((B, N), np.int8), _sd((B, N), np.int8),
                 _sd((B, N, W), np.int8), _sd((W + 1, V), np.int32),
                 _sd((), np.int32), _sd((B, NW, M), np.uint32),
                 _sd((B, NW, M), np.uint32), _sd((B,), bool),
                 _sd((B,), np.int32)))

    def wgl_fused():
        from ..ops.linearize import get_fused_kernel
        members = ((V, W, W, False), (V, 6, 6, False))
        args = (_sd((B, N), np.int8), _sd((B, N), np.int8),
                _sd((B, N, W), np.int8),
                _sd((B, W + 1, V), np.int32),
                _sd((B, N), np.int8), _sd((B, N), np.int8),
                _sd((B, N, 6), np.int8),
                _sd((B, 7, V), np.int32))
        return get_fused_kernel(members, donate=True), args

    def graph_closure():
        from ..ops.graph import N_LEVELS, graph_kernel
        GV = 32
        return (graph_kernel(GV),
                (_sd((8, N_LEVELS, GV, GV // 32), np.uint32),))

    def txn_closure():
        from ..ops.txn_graph import N_TXN_PLANES, txn_kernel
        GV = 32
        return (txn_kernel(GV),
                (_sd((8, N_TXN_PLANES, GV, GV // 32), np.uint32),))

    def fold_set():
        from ..ops.folds import _set_kernel
        return (_set_kernel(16),
                (_sd((8, 32), np.int32), _sd((8, 32), np.int32),
                 _sd((8, 32), np.int32), _sd((8, 16), bool)))

    def fold_counter():
        from ..ops.folds import _counter_kernel
        return (_counter_kernel(),
                (_sd((8, 32), np.int32), _sd((8, 32), np.int32),
                 _sd((8, 32), np.int32), _sd((8, 32), np.int32), 4))

    def synth_keys():
        return {k: _sd((B,), np.uint32)
                for k in ("sched", "vals", "fault", "corr")}

    def synth_cas():
        from ..ops.synth_device import _cas_core, _jitted
        fn = _jitted("cas", _cas_core, dict(
            n_procs=3, n_ops=16, n_values=3, n_keys=2,
            with_info=True, with_crash=True, with_corrupt=True,
            key_meta=True))
        return (fn, (synth_keys(), _sd((B,), np.int32),
                     _sd((B,), np.int32), np.uint32(100),
                     np.uint32(100), np.uint32(100)))

    def synth_la():
        from ..ops.synth_device import _jitted, _la_core
        fn = _jitted("la", _la_core,
                     dict(n_procs=3, n_ops=16, n_keys=2))
        return fn, (synth_keys(), np.uint32(100))

    def synth_wide():
        import jax
        import jax.numpy as jnp

        from ..ops.synth_device import _wide_core
        fn = jax.jit(lambda kk: _wide_core(
            jnp, kk, width=6, n_values=3, invalid=True))
        return fn, (_sd((B,), np.uint32),)

    def dc_peel():
        from ..ops.dc_monitor import get_dc_kernel
        E = 64
        return (get_dc_kernel(E),
                (_sd((B, E), np.int32), _sd((B, E), np.int32),
                 _sd((B, E), bool)))

    def pallas_wgl():
        from ..ops.pallas_wgl import event_block, make_pallas_kernel
        EB = event_block()
        return (make_pallas_kernel(8, 6, shared_target=True,
                                   interpret=True),
                (_sd((8, EB), np.int8), _sd((8, EB), np.int8),
                 _sd((8, EB, 6), np.int8), _sd((7, 8), np.int32)))

    return {
        "wgl-scan": {"build": wgl_scan, "kind": "wgl",
                     "donate": frozenset({0, 1, 2})},
        "wgl-resume": {"build": wgl_resume, "kind": "wgl"},
        "wgl-fused": {"build": wgl_fused, "kind": "wgl",
                      "donate": frozenset({0, 1, 2, 4, 5, 6})},
        "graph-closure": {"build": graph_closure, "kind": "graph"},
        "txn-closure": {"build": txn_closure, "kind": "txn"},
        "fold-set": {"build": fold_set, "kind": "fold"},
        "fold-counter": {"build": fold_counter, "kind": "fold"},
        "synth-cas": {"build": synth_cas, "kind": "synth"},
        "synth-la": {"build": synth_la, "kind": "synth"},
        "synth-wide": {"build": synth_wide, "kind": "synth"},
        "pallas-wgl": {"build": pallas_wgl, "kind": "pallas"},
        "dc-peel": {"build": dc_peel, "kind": "dc"},
    }


def lint_device() -> DeviceReport:
    """Trace and check every registered kernel family, plus the shape
    contract and the Pallas VMEM model. A family that fails to even
    trace is itself a finding — the lint must never silently shrink
    its coverage."""
    report = DeviceReport()
    for family, spec in probe_specs().items():
        report.families.append(family)
        try:
            fn, args = spec["build"]()
            jaxpr, donate = trace_family(fn, args)
        except Exception as e:  # noqa: BLE001 — reported as finding
            report.findings.append(_finding(
                D_PRIM, family,
                f"family failed to trace: {type(e).__name__}: {e}",
                f"{family}:trace"))
            continue
        report.findings.extend(check_traced(
            family, spec["kind"], jaxpr, donate=donate,
            donate_expected=spec.get("donate"), report=report))
    report.findings.extend(check_dispatch_shapes())
    report.findings.extend(check_pallas_vmem())
    return report
