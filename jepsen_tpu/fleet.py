"""Fleet campaign orchestrator: sharded multi-worker dispatch with
cost-routed backends.

Five PRs built single-process machinery — the streaming bucket
scheduler, the resilience ladder, durable checkpoints, on-device
synthesis, the online daemon. This layer turns them into a fleet:

  * **Sharding.** A campaign (synth seed sweep, blind-sweep recheck of
    a stored test, fuzz rounds) becomes a WORK SPEC file plus a lease
    directory under ``store/<name>/fleet/``. Worker processes — local
    subprocesses spawned by the orchestrator, or processes started by
    hand on other hosts against the same (shared) store — claim seed
    ranges by lease, heartbeat while working, and write one durable
    summary per unit. Nothing but the filesystem coordinates them,
    which is exactly what makes the same spec multi-host-ready.

  * **Leases (the cluster-wide checkpoint).** The PR-5 durability
    format is extended, not replaced: per-unit summaries
    (``seed-<s>.json``, the exact ``run_synth_seeds`` artifact) and
    per-seed ChunkJournals stay the completed-work record; the lease
    files add WHO may produce them. A lease is claimed by exclusive
    create, renewed by heartbeat, and expires when its heartbeat goes
    stale (``JT_LEASE_TTL_S``) — a SIGKILLed worker's leases lapse and
    survivors take them over at a bumped generation, skipping every
    unit whose summary already landed: ZERO completed seeds re-run,
    and the in-flight seed resumes its journal with zero re-dispatched
    histories.

  * **Cost-based routing.** Each checkable unit is priced against the
    measured dispatch-overhead/op-model numbers the scheduler already
    owns (ops/schedule.py): the fused device WGL scan at
    ``2^W``/lane-rate, the MXU graph closure at ``mxu_op_model`` MACs,
    the host oracle at its near-W-flat per-event rate. The router
    sends each unit to the cheapest CAPABLE backend instead of the
    fixed per-family path — wide sub-histories stop paying exponential
    device frontiers when the host is cheaper, graph workloads stop
    paying DFS when the MXU isn't, and long histories ride the
    event-chunked kernel by the same arithmetic
    (``BucketScheduler.event_route_events``).

  * **Aggregation.** Workers write ordinary per-unit stores +
    journals; ``merge_campaign`` folds them into one campaign-level
    ``fleet/results.json`` and the orchestrator publishes a standard
    run dir (``store/<name>/<ts>/results.json``) so the web index
    renders the whole fleet as a single row with a ``fleet`` badge.

``jepsen-tpu fleet`` (cli.py) is the operator surface; ``--join DIR
--worker-id W`` runs one worker against an existing campaign dir (the
multi-host entry). doc/fleet.md documents the model.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import shutil
import socket
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from . import telemetry
from .store import (FLEET_DIR, CampaignMismatch, ChunkJournal, Store,
                    DEFAULT, atomic_write_json, spec_digest)

log = logging.getLogger("jepsen.fleet")

FLEET_MAGIC = "JTFLEET1"
SPEC_FILE = "fleet.json"
LEASES_DIR = "leases"
RESULTS_FILE = "results.json"

# Spec fields that IDENTIFY a campaign: a --resume against a dir whose
# spec differs in any of these is a different campaign (refused, the
# CampaignCheckpoint discipline). Worker count / TTLs may differ.
IDENTITY_KEYS = ("fleet", "name", "kind", "model", "synth", "units",
                 "spec", "test", "timestamps")


def max_local_workers() -> int:
    """$JT_FLEET_MAX_LOCAL_WORKERS: cap on worker processes spawned on
    THIS host (0 = uncapped). Default: the host's core count — local
    workers are CPU-bound jax processes, and oversubscribing them
    regresses outright (the 2-core MULTICHIP_r07 probe measured 4
    local workers at 0.92x of one); width beyond the cores belongs on
    more hosts (``fleet --join``), not more processes."""
    env = os.environ.get("JT_FLEET_MAX_LOCAL_WORKERS")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return os.cpu_count() or 1


def lease_ttl_s() -> float:
    """$JT_LEASE_TTL_S: heartbeat staleness past which a worker's
    lease is forfeit and its seeds redistribute. Default 15 s — many
    heartbeat periods (ttl/3), few enough that a killed worker's share
    of a campaign restarts within seconds."""
    try:
        return max(0.5, float(os.environ.get("JT_LEASE_TTL_S", "15")))
    except ValueError:
        return 15.0


def lease_skew_s() -> float:
    """$JT_LEASE_SKEW_S: wall-clock skew allowance between lease
    writers on a shared store. Expiry compares the local clock against
    the lease file's own stamped wall time, so two hosts whose clocks
    disagree by up to this much cannot double-own a unit: the live
    window extends by the allowance, and a lease stamped in the local
    FUTURE is never stolen at all (refused + counted —
    ``lease_skew_rejects``). Default 2 s; NFS-grade skew deployments
    raise it."""
    try:
        return max(0.0, float(os.environ.get("JT_LEASE_SKEW_S", "2")))
    except ValueError:
        return 2.0


# ------------------------------------------------------ cost-based router

# Process-wide probe-measured rate overlay (probe_and_persist /
# set_measured_rates): defaults < measured < explicit env pins.
_MEASURED_RATES: Dict[str, float] = {}

# Rate keys and the env pins that override them (env beats probe: a
# deployment that pins its crossover means it, exactly like
# $JT_DISPATCH_OVERHEAD_US).
_RATE_ENV = (("lane_ops_per_s", "JT_DISPATCH_COST_LANE_OPS_PER_S"),
             ("host_s_per_event", "JT_HOST_S_PER_EVENT"),
             ("macs_per_s", "JT_GRAPH_MACS_PER_S"),
             ("graph_host_s_per_edge", "JT_GRAPH_HOST_S_PER_EDGE"),
             ("pallas_lane_ops_per_s", "JT_PALLAS_LANE_OPS_PER_S"),
             ("dc_events_per_s", "JT_DC_EVENTS_PER_S"),
             ("ingest", "JT_INGEST_OPS_PER_S"))


def set_measured_rates(rates: Optional[Dict[str, float]]) -> None:
    """Install probe-measured per-backend rates as the process-wide
    overlay every fresh CostRouter prices from (None/{} clears). Only
    known rate keys with truthy values apply — a failed probe never
    zeroes a working default."""
    _MEASURED_RATES.clear()
    if rates:
        known = {k for k, _ in _RATE_ENV}
        _MEASURED_RATES.update({k: float(v) for k, v in rates.items()
                                if k in known and v})


def router_rates() -> Dict[str, float]:
    """The measured/assumed backend rates the router prices against.
    ``lane_ops_per_s`` is the scheduler's dispatch-cost rate (the same
    pessimism class the W-class DP and watchdog use);
    ``host_s_per_event`` calibrates the host oracle's near-W-flat
    per-event cost from the measured W15/W16 device/native crossover
    (ops/linearize.py's wide-tail comment: ~0.4 s per ~1k-event row);
    ``macs_per_s`` prices the MXU closure; ``graph_host_s_per_edge``
    the host DFS; ``pallas_lane_ops_per_s`` the Pallas WGL megakernel
    (0 = unprobed/unavailable, which prices it out of every route);
    ``dc_events_per_s`` the decrease-and-conquer peel loop's W-flat
    near-linear event rate (same 0 = priced-out convention, so an
    unprobed process routes bit-identically to the pre-dc tree).
    Precedence: defaults < probe-measured overlay (set_measured_rates
    / probe_and_persist / persisted store rates) < explicit env pins —
    a deployment that measures its own crossover pins it, exactly like
    $JT_DISPATCH_OVERHEAD_US."""
    from .ops.schedule import DISPATCH_COST_LANE_OPS_PER_S

    out = {
        "lane_ops_per_s": DISPATCH_COST_LANE_OPS_PER_S,
        "host_s_per_event": 4e-4,
        "macs_per_s": 1e12,
        "graph_host_s_per_edge": 2e-6,
        "pallas_lane_ops_per_s": 0.0,
        "dc_events_per_s": 0.0,
        # Wire-ingest landing rate (ops/s) — not a checker backend but
        # the same pricing surface: the ingest plane's Retry-After is
        # priced off it when positive ($JT_INGEST_OPS_PER_S, or a
        # measured overlay); 0 falls back to the fixed
        # $JT_INGEST_RETRY_AFTER_S.
        "ingest": 0.0,
    }
    out.update(_MEASURED_RATES)
    for key, env in _RATE_ENV:
        v = os.environ.get(env)
        if v is not None:
            try:
                out[key] = float(v)
            except ValueError:
                pass
    return out


# ------------------------------ probe-refreshed, store-persisted rates

ROUTER_RATES_DIR = "router-rates"

_PROBED_RATES: Optional[Dict[str, float]] = None


def rates_path(store_dir, host: Optional[str] = None) -> Path:
    """This host's rate file: one file PER HOST (never a shared
    read-modify-write document — concurrent workers on different
    hosts must not race each other's calibration, the same reason
    the lease protocol claims with O_EXCL)."""
    host = host or socket.gethostname()
    safe = "".join(c if c.isalnum() or c in "-._" else "_"
                   for c in host) or "unknown-host"
    return Path(store_dir) / ROUTER_RATES_DIR / f"{safe}.json"


def persist_rates(store_dir, rates: Dict[str, float],
                  host: Optional[str] = None) -> Path:
    """Record this host's measured backend rates in the shared store
    (one JSON file per hostname) so fleet workers on heterogeneous
    hosts route from measurements, not defaults. Only known rate keys
    persist; each host owns its own file outright, so workers never
    clobber each other's calibration."""
    path = rates_path(store_dir, host)
    path.parent.mkdir(parents=True, exist_ok=True)
    known = {k for k, _ in _RATE_ENV}
    atomic_write_json(path, {
        "host": host or socket.gethostname(),
        "rates": {k: float(v) for k, v in rates.items()
                  if k in known and v},
        "ts": time.time(),
    })
    return path


def load_persisted_rates(store_dir,
                         host: Optional[str] = None) -> Dict[str, float]:
    """This host's persisted rate entry (empty when it never probed —
    another host's calibration is wrong by definition on a
    heterogeneous fleet, so there is no cross-host fallback)."""
    ent = _read_json(rates_path(store_dir, host))
    if not isinstance(ent, dict):
        return {}
    known = {k for k, _ in _RATE_ENV}
    return {k: float(v) for k, v in (ent.get("rates") or {}).items()
            if k in known and v}


def probe_and_persist(store_dir=None, *, force: bool = False
                      ) -> Dict[str, float]:
    """The startup rate probe: measure the WGL device backends
    (lax.scan and Pallas, ops.pallas_wgl.probe_rates; the
    decrease-and-conquer peel loop, ops.dc_monitor.probe_rates) plus
    the host oracle's per-event cost on one tiny workload, install the result
    as the process-wide overlay (set_measured_rates), and persist it
    under this host's key when a store dir is given. Memoized per
    process — the probe pays two tiny kernel compiles once."""
    global _PROBED_RATES
    if _PROBED_RATES is None or force:
        from .ops.pallas_wgl import probe_rates
        out = probe_rates()
        rates = {"lane_ops_per_s": out.get("lane_ops_per_s") or 0.0,
                 "pallas_lane_ops_per_s":
                     out.get("pallas_lane_ops_per_s") or 0.0}
        try:
            from .ops.dc_monitor import probe_rates as dc_probe
            rates["dc_events_per_s"] = (
                dc_probe().get("dc_events_per_s") or 0.0)
        except Exception:
            rates["dc_events_per_s"] = 0.0
        try:
            from .checkers.linearizable import wgl_check
            from .workloads.synth import synth_cas_history
            hs = [synth_cas_history(7 + i, n_procs=3, n_ops=40)
                  for i in range(3)]
            t0 = time.perf_counter()
            for h in hs:
                wgl_check(cas_register_model(), h)
            dt = time.perf_counter() - t0
            ev = sum(len(h) for h in hs)
            if ev and dt > 0:
                rates["host_s_per_event"] = dt / ev
        except Exception:
            pass
        _PROBED_RATES = rates
    set_measured_rates(_PROBED_RATES)
    if store_dir is not None:
        try:
            persist_rates(store_dir, _PROBED_RATES)
        except Exception:
            log.warning("could not persist router rates", exc_info=True)
    return dict(_PROBED_RATES)


def cas_register_model():
    from .models.core import cas_register
    return cas_register()


def pending_window(history) -> int:
    """A history's peak pending window — the encoder's ``max_live``
    rule (invokes allocate a slot, only ok-completions free it) as one
    cheap host scan, no encode."""
    from .history.ops import INVOKE, OK

    live = peak = 0
    for op in history:
        if not op.is_client:
            continue
        if op.type == INVOKE:
            live += 1
            peak = max(peak, live)
        elif op.type == OK:
            live = max(0, live - 1)
    return peak


def estimate_w(history) -> int:
    """The unit's POST-PARTITION W class: KV-valued histories strain
    per key before encoding (ops.partition), so what the device will
    actually pay is the widest per-key window, not the merged one."""
    from .independent import history_keys, subhistory

    keys = history_keys(history)
    if not keys:
        return pending_window(history)
    return max(pending_window(subhistory(k, history)) for k in keys)


def classify_history(history) -> str:
    """Which checker family decides a unit: ``txn`` for transactional
    histories (the isolation-ladder certifier), ``graph`` for
    histories whose vocabulary lowers to dependency graphs
    (list-append / adya-g2 — ops.graph.extract_graph's own sniff
    rule), ``wgl`` for everything the linearizable frontier scan
    owns."""
    fs = {op.f for op in history if op.is_client}
    if "txn" in fs:
        return "txn"
    return "graph" if ("append" in fs or "insert" in fs) else "wgl"


def online_tick_costs(w: int, prefix_events: int, delta_events: int, *,
                      lane_ops_per_s: float, host_s_per_event: float,
                      overhead_s: float = 0.0,
                      incremental: bool = True) -> Dict[str, float]:
    """THE delta-path pricing arithmetic, shared by
    CostRouter.price_online_tick (router surface) and
    service.tenant_price (placement over peer caps) so the two
    surfaces cannot drift: the device term charges the delta when the
    worker carries a resident frontier, the whole prefix otherwise;
    the host oracle always pays the prefix."""
    dev_ev = (max(int(delta_events), 1) if incremental
              else max(int(prefix_events), 1))
    dev = (dev_ev * float(1 << min(max(int(w), 0), 30))
           / lane_ops_per_s + overhead_s)
    host = max(int(prefix_events), 1) * host_s_per_event
    return {"wgl-device": dev, "host-oracle": host}


class CostRouter:
    """Prices each checkable unit per backend and picks the cheapest
    CAPABLE one. Units are (family, W-or-vertex-bucket, length); the
    device terms amortize the measured per-dispatch overhead
    (ops.schedule.measure_dispatch_overhead_us) over the rows that
    would share the dispatch. Records every choice for the campaign's
    router summary."""

    #: W past which the packed frontier no longer fits any device
    #: route (beyond the frontier-sharded mask axis) — the host oracle
    #: is the only capable backend. $JT_ROUTER_MAX_W overrides.
    MAX_DEVICE_W = 22

    def __init__(self, rates: Optional[dict] = None,
                 max_device_w: Optional[int] = None,
                 store_dir=None):
        base = router_rates()
        if store_dir is not None:
            # Heterogeneous-fleet calibration: this host's persisted
            # probe measurements (probe_and_persist) beat defaults;
            # explicit ``rates`` beat everything.
            base.update(load_persisted_rates(store_dir))
        self.rates = {**base, **(rates or {})}
        if max_device_w is not None:
            self.max_device_w = int(max_device_w)
        else:
            try:
                self.max_device_w = int(
                    os.environ.get("JT_ROUTER_MAX_W", ""))
            except ValueError:
                self.max_device_w = self.MAX_DEVICE_W
        self.chosen: Dict[str, int] = {}
        self.est_cost_s: Dict[str, float] = {}

    def _overhead_s(self) -> float:
        from .ops.schedule import measure_dispatch_overhead_us
        return measure_dispatch_overhead_us() * 1e-6

    # ---------------------------------------------------------- pricing
    def price_wgl(self, w: int, n_events: int,
                  rows: int = 1, *, dc: bool = False) -> Dict[str, float]:
        """Per-unit cost of a linearizable unit at post-partition
        window ``w`` and ``n_events`` history lines: the device scan
        pays 2^w frontier lanes per event plus its amortized dispatch
        overhead; the host oracle's per-event cost is near W-flat.
        The Pallas megakernel (``wgl-pallas``) prices only when it is
        CAPABLE (narrow window, kernel available) and PROBED (a
        measured rate exists — startup probe, persisted store entry,
        or env pin); absent either, the cost dict is bit-identical to
        the pre-pallas router. The decrease-and-conquer peel loop
        (``wgl-dc``) prices under the same contract — capable
        (``dc=True``: the caller sniffed a register-class unit,
        ops.dc_monitor.dc_capable_history), available
        ($JT_ROUTER_DC), probed (``dc_events_per_s``) — and is the
        only device term FLAT in W: events/rate, no 2^w factor."""
        dev = (n_events * float(1 << min(int(w), 30))
               / self.rates["lane_ops_per_s"]
               + self._overhead_s() / max(int(rows), 1))
        host = n_events * self.rates["host_s_per_event"]
        costs = {"wgl-device": dev, "host-oracle": host}
        pr = float(self.rates.get("pallas_lane_ops_per_s") or 0.0)
        if pr > 0:
            from .ops.pallas_wgl import pallas_available, pallas_supports
            if pallas_available() and pallas_supports(1, w):
                costs["wgl-pallas"] = (
                    n_events * float(1 << min(int(w), 30)) / pr
                    + self._overhead_s() / max(int(rows), 1))
        if dc:
            dr = float(self.rates.get("dc_events_per_s") or 0.0)
            if dr > 0:
                from .ops.dc_monitor import dc_available
                if dc_available():
                    costs["wgl-dc"] = (
                        n_events / dr
                        + self._overhead_s() / max(int(rows), 1))
        return costs

    def price_online_tick(self, w: int, prefix_events: int,
                          delta_events: int, *,
                          incremental: bool = True) -> Dict[str, float]:
        """Per-tick cost of one ONLINE interim check (the daemon's
        rolling prefix check): the resident-frontier delta path
        (ops.schedule.ResidentFrontier, $JT_ONLINE_INCREMENTAL)
        charges the device scan only for the events that arrived since
        the last decided prefix — per-tick cost flat in prefix length
        — while full-recheck mode re-pays the whole prefix every tick.
        The host oracle has no carried state, so it always pays the
        prefix. Carried dispatch rides the lax.scan resume kernel
        exclusively (the Pallas megakernel's VMEM-resident frontier
        never round-trips between launches — pallas_wgl
        .pallas_supports_resume), so no pallas term appears here.
        service.tenant_price prices placement through the same shared
        arithmetic (online_tick_costs)."""
        return online_tick_costs(
            w, prefix_events, delta_events, incremental=incremental,
            lane_ops_per_s=self.rates["lane_ops_per_s"],
            host_s_per_event=self.rates["host_s_per_event"],
            overhead_s=self._overhead_s())

    def price_graph(self, n_vertices: int, n_edges: int,
                    rows: int = 1) -> Dict[str, float]:
        """Per-unit cost of a dependency-graph unit: the MXU closure
        pays mxu_op_model MACs at the padded vertex bucket; the host
        DFS is linear in vertices + edges."""
        from .ops.graph import bucket_v, mxu_op_model
        m = mxu_op_model(bucket_v(max(int(n_vertices), 1)))
        dev = (m["macs"] / self.rates["macs_per_s"]
               + self._overhead_s() / max(int(rows), 1))
        host = ((n_vertices + n_edges)
                * self.rates["graph_host_s_per_edge"])
        return {"graph-device": dev, "graph-host": host}

    def price_txn(self, n_vertices: int, n_edges: int,
                  rows: int = 1) -> Dict[str, float]:
        """Per-unit cost of a transactional (isolation-ladder) unit:
        the MXU ladder closure pays txn_op_model MACs (5 planes + the
        SI composition matmul) at the padded vertex bucket; the host
        DFS oracle is linear in vertices + edges per plane."""
        from .ops.graph import bucket_v
        from .ops.txn_graph import N_CYC_PLANES, txn_op_model
        m = txn_op_model(bucket_v(max(int(n_vertices), 1)))
        dev = (m["macs"] / self.rates["macs_per_s"]
               + self._overhead_s() / max(int(rows), 1))
        host = (N_CYC_PLANES * (n_vertices + n_edges)
                * self.rates["graph_host_s_per_edge"])
        return {"txn-device": dev, "txn-host": host}

    def _record(self, backend: str, costs: Dict[str, float]) -> None:
        self.chosen[backend] = self.chosen.get(backend, 0) + 1
        self.est_cost_s[backend] = (self.est_cost_s.get(backend, 0.0)
                                    + costs[backend])

    def choose_wgl(self, w: int, n_events: int, rows: int = 1, *,
                   dc: bool = False) -> Tuple[str, Dict[str, float]]:
        costs = self.price_wgl(w, n_events, rows, dc=dc)
        if w > self.max_device_w:
            # Past the frontier-sharded mask axis no 2^w backend is
            # capable — but the peel loop carries no frontier at all,
            # so it stays eligible at ANY width.
            elig = {k: v for k, v in costs.items()
                    if k in ("host-oracle", "wgl-dc")}
            backend = min(elig, key=elig.get)
        else:
            backend = min(costs, key=costs.get)
        self._record(backend, costs)
        return backend, costs

    def choose_graph(self, n_vertices: int, n_edges: int,
                     rows: int = 1) -> Tuple[str, Dict[str, float]]:
        costs = self.price_graph(n_vertices, n_edges, rows)
        backend = min(costs, key=costs.get)
        self._record(backend, costs)
        return backend, costs

    def choose_txn(self, n_vertices: int, n_edges: int,
                   rows: int = 1) -> Tuple[str, Dict[str, float]]:
        costs = self.price_txn(n_vertices, n_edges, rows)
        backend = min(costs, key=costs.get)
        self._record(backend, costs)
        return backend, costs

    def wgl_check_kwargs(self, spec) -> dict:
        """Scheduler knobs for a synth seed batch, cost-derived: the
        wide-tail host crossover (``min_device_batch`` — rows below
        which a W>=16 bucket's amortized dispatch overhead makes the
        native host engine cheaper) comes out of the same arithmetic
        instead of a caller-fixed constant. Post-partition, a cas
        spec's per-key window is bounded by its process count and its
        per-key event count by 2*n_ops/n_keys."""
        from .ops.linearize import DATA_MAX_SLOTS
        ev = max(1, 2 * spec.n_ops // max(spec.n_keys, 1))
        w = min(spec.n_procs, spec.n_ops, self.max_device_w)
        host_row = ev * self.rates["host_s_per_event"]
        dev_row = (ev * float(1 << max(int(w), DATA_MAX_SLOTS))
                   / self.rates["lane_ops_per_s"])
        if dev_row >= host_row:
            mdb = 4096                   # host beats the scan outright
        else:
            mdb = min(4096, max(1, int(self._overhead_s()
                                       / max(host_row - dev_row, 1e-12))
                                + 1))
        return {"min_device_batch": mdb}

    def table(self, ws=(4, 8, 12, 16, 18, 20),
              events: int = 1000) -> List[dict]:
        """The router cost table (doc/fleet.md, bench): per W, both
        backends' prices and the winner — the crossover made visible."""
        out = []
        for w in ws:
            costs = self.price_wgl(w, events, dc=True)
            if w > self.max_device_w:
                elig = {k: v for k, v in costs.items()
                        if k in ("host-oracle", "wgl-dc")}
                backend = min(elig, key=elig.get)
            else:
                backend = min(costs, key=costs.get)
            out.append({"W": w, "events": events, "backend": backend,
                        **{k: round(v, 6) for k, v in costs.items()}})
        return out

    def summary(self) -> dict:
        return {"chosen": dict(self.chosen),
                "est_cost_s": {k: round(v, 6)
                               for k, v in self.est_cost_s.items()},
                "max_device_w": self.max_device_w,
                "rates": self.rates}


def route_check(model, histories: Sequence, *, router: Optional[
        CostRouter] = None, details: str = "invalid") -> Tuple[
            List[dict], dict]:
    """Check a mixed corpus with every unit cost-routed: classify each
    history (wgl vs graph family), price it, and dispatch each backend
    group as one batch — fused device WGL
    (ops.linearize.check_batch_columnar), MXU graph closure
    (checkers.cycle.check_graphs_batch), or the host oracles. Returns
    (per-history result dicts in input order, each tagged with its
    ``backend``, and the routing summary). This is the fleet recheck
    path's engine and the router-parity test seam."""
    router = router if router is not None else CostRouter()
    n = len(histories)
    plan: List[Tuple[int, str]] = []
    graphs: Dict[int, object] = {}
    for i, h in enumerate(histories):
        fam = classify_history(h)
        if fam == "txn":
            from .ops.txn_graph import extract_txn_graph
            g = extract_txn_graph(h)
            graphs[i] = g
            edges = sum(int(e.shape[0]) for e in g.edges.values())
            backend, _ = router.choose_txn(g.n, edges)
        elif fam == "graph":
            from .ops.graph import extract_graph
            g = extract_graph(h)
            graphs[i] = g
            edges = sum(int(e.shape[0]) for e in g.edges.values())
            backend, _ = router.choose_graph(g.n, edges)
        else:
            from .ops.dc_monitor import dc_capable_history
            backend, _ = router.choose_wgl(estimate_w(h), len(h),
                                           dc=dc_capable_history(h))
        plan.append((i, backend))
    groups: Dict[str, List[int]] = {}
    for i, backend in plan:
        groups.setdefault(backend, []).append(i)
    results: List[Optional[dict]] = [None] * n

    # The WGL device groups ride the same fused columnar pipeline
    # with the scheduler's per-chunk backend PINNED to the router's
    # group decision (the router already decided the crossover;
    # letting the scheduler re-price per chunk — or pick up a stray
    # JT_WGL_BACKEND force — would let dispatches disagree with the
    # plan and with the ``backend`` tag on the results).
    for group, forced in (("wgl-device", "xla"),
                          ("wgl-pallas", "pallas"),
                          ("wgl-dc", "dc")):
        if not groups.get(group):
            continue
        from .ops.linearize import check_batch_columnar
        idx = groups[group]
        rs = check_batch_columnar(
            model, [histories[i] for i in idx], details=details,
            scheduler_opts={"wgl_backend": forced})
        for i, r in zip(idx, rs):
            results[i] = r
    if groups.get("host-oracle"):
        idx = groups["host-oracle"]
        hs = [histories[i] for i in idx]
        rs = None
        try:
            from .native import check_batch_native
            rs = check_batch_native(model, hs)
        except Exception:
            rs = None
        if rs is None:
            from .checkers.linearizable import wgl_check
            rs = [wgl_check(model, h) for h in hs]
        for i, r in zip(idx, rs):
            r.setdefault("provenance", "host-oracle")
            results[i] = r
    if groups.get("graph-device"):
        from .checkers.cycle import check_graphs_batch
        idx = groups["graph-device"]
        rs = check_graphs_batch([graphs[i] for i in idx])
        for i, r in zip(idx, rs):
            results[i] = r
    if groups.get("graph-host"):
        from .ops.graph import check_graph_host
        for i in groups["graph-host"]:
            results[i] = check_graph_host(graphs[i],
                                          provenance="host-oracle")
    if groups.get("txn-device"):
        from .isolation import certify_batch
        idx = groups["txn-device"]
        rs = certify_batch([graphs[i] for i in idx])
        for i, r in zip(idx, rs):
            results[i] = r
    if groups.get("txn-host"):
        from .ops.txn_graph import check_txn_host
        for i in groups["txn-host"]:
            results[i] = check_txn_host(graphs[i],
                                        provenance="host-oracle")
    for (i, backend) in plan:
        results[i]["backend"] = backend
    routing = {"units": n,
               "backends": {b: len(ix) for b, ix in groups.items()},
               **router.summary()}
    return results, routing  # type: ignore[return-value]


# ------------------------------------------------------------- leases

def _read_json(path) -> Optional[dict]:
    try:
        return json.loads(Path(path).read_text())
    except Exception:
        return None


def _lease_path(cdir: Path, chunk_id: int) -> Path:
    return cdir / LEASES_DIR / f"chunk-{chunk_id}.json"


def lease_payload(extra: dict, worker: str, gen: int,
                  done: bool = False, hb: Optional[float] = None) -> dict:
    """The generic lease record: WHO (worker/pid/host), WHEN (the
    stamped wall-time heartbeat every expiry decision compares
    against), the takeover generation, and caller fields (``extra``) —
    fleet chunks carry their unit list, service tenants their run
    key."""
    return {**extra, "worker": worker, "pid": os.getpid(),
            "host": socket.gethostname(),
            "hb": time.time() if hb is None else float(hb),
            "gen": int(gen), "done": bool(done)}


def claim_lease(path: Path, extra: dict, worker: str,
                ttl: float) -> Optional[int]:
    """Try to claim one lease file — the shared ownership primitive of
    the fleet campaign (seed chunks) and the checking service (live
    tenants). Returns the claimed generation (0 = first owner, >0 =
    takeover of an expired lease) or None when the unit is done or
    someone else holds a live lease.

    First claim is an atomic hard-link of a fully-written payload (two
    fresh workers cannot both win, and no reader ever sees an empty or
    partial lease file); takeover is atomic-replace at generation+1
    with a read-back — the loser of a takeover race sees the other
    worker's record and walks away. Expiry compares the local wall
    clock against the lease's OWN stamped time with a
    ``$JT_LEASE_SKEW_S`` allowance, and a lease stamped in the local
    future is refused outright (``lease_skew_rejects``): clock-skewed
    hosts on a shared store cannot double-own a unit."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = lease_payload(extra, worker, 0)
    # Worker id in the temp name: pids alone can collide across hosts
    # on a shared store.
    tmp = path.with_name(f"{path.name}.claim.{worker}.{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    try:
        os.link(tmp, path)
        return 0
    except FileExistsError:
        pass
    finally:
        try:
            tmp.unlink()
        except FileNotFoundError:
            pass
    cur = _read_json(path)
    if cur is None:
        # Unreadable lease: only a crashed writer of a bygone era can
        # leave one (claims land atomically above, renew/takeover use
        # atomic_write_json) — but stay conservative and treat a
        # RECENT file as live rather than stealing it.
        try:
            if time.time() - path.stat().st_mtime < ttl:
                return None
        except OSError:
            return None
        cur = {"gen": -1, "hb": 0.0}
    if cur.get("done"):
        return None
    if cur.get("worker") == worker and not cur.get("released"):
        # Already ours (re-entry: same worker id after a restart, or
        # a crashed campaign resumed under deterministic ids). Refresh
        # the heartbeat as part of the re-claim — a stale own stamp
        # would otherwise trip renew_lease's lapsed-owner guard and
        # churn claim→renew-refused→lost forever.
        gen = int(cur.get("gen", 0))
        atomic_write_json(path, lease_payload(extra, worker, gen))
        return gen
    if cur.get("worker") == worker:
        # Our own RELEASED lease (the peer we released to never came):
        # re-claim like a takeover — generation bumps and the released
        # flag clears, so renewals work again instead of churning
        # claim→renew-refused→lost forever.
        gen = int(cur.get("gen", 0)) + 1
        atomic_write_json(path, lease_payload(extra, worker, gen))
        back = _read_json(path)
        if back and back.get("worker") == worker and \
                int(back.get("gen", -1)) == gen:
            return gen
        return None
    now = time.time()
    hb = float(cur.get("hb", 0.0))
    skew = lease_skew_s()
    if hb > now + skew:
        # A heartbeat from our future: the other host's clock (or
        # ours) is off by more than the allowance. Stealing now could
        # double-own the unit — refuse, loudly, and let the operator
        # (or a raised JT_LEASE_SKEW_S) resolve it.
        telemetry.REGISTRY.counter("fleet.lease_skew_rejects").inc()
        log.warning(
            "lease %s is stamped %.1fs in the local future (skew "
            "allowance %.1fs); refusing takeover — check host clocks "
            "or raise JT_LEASE_SKEW_S", path, hb - now, skew)
        return None
    if now - hb < ttl + skew:
        return None                          # live somewhere else
    gen = int(cur.get("gen", 0)) + 1
    atomic_write_json(path, lease_payload(extra, worker, gen))
    back = _read_json(path)
    if back and back.get("worker") == worker and \
            int(back.get("gen", -1)) == gen:
        telemetry.event("fleet.takeover", path=str(path), gen=gen)
        return gen
    return None


def renew_lease(path: Path, extra: dict, worker: str,
                gen: int, ttl: Optional[float] = None) -> bool:
    """Refresh a held lease's heartbeat. False (nothing written) when
    the on-disk record names someone else — the owner's signal to
    abandon the unit instead of double-writing. A lease we already
    marked done is left untouched (True): a heartbeat racing the
    done-mark must never resurrect it as live.

    With ``ttl``, an owner whose OWN last stamp has already lapsed
    past ttl+skew also refuses (False): the unit is legally claimable
    by a peer at that point, and a renewal racing the peer's takeover
    write could resurrect the old ownership on top of it — the
    stalled owner must abandon and re-claim through the front door
    instead. (Both times compare on this host's clock, so host skew
    doesn't enter.)"""
    cur = _read_json(path)
    if cur is None or cur.get("worker") != worker or \
            int(cur.get("gen", -1)) != int(gen):
        return False
    if cur.get("done"):
        return True
    if cur.get("released"):
        return False                  # we handed it back: stay away
    if ttl is not None and time.time() - float(cur.get("hb", 0.0)) \
            >= float(ttl) + lease_skew_s():
        return False                  # lapsed: a takeover may be live
    atomic_write_json(path, lease_payload(extra, worker, gen))
    return True


def release_lease(path: Path, extra: dict, worker: str,
                  gen: int) -> bool:
    """Voluntarily hand a held lease back (cost-routed rebalancing):
    the record keeps its generation but its heartbeat is zeroed, so
    any peer's next claim takes over immediately at generation+1 —
    with all the unit's durable progress (summaries, journals) intact
    for the new owner to resume."""
    cur = _read_json(path)
    if cur is None or cur.get("worker") != worker or \
            int(cur.get("gen", -1)) != int(gen):
        return False
    atomic_write_json(path, {**lease_payload(extra, worker, gen,
                                             hb=0.0),
                             "released": True})
    return True


def mark_lease_done(path: Path, extra: dict, worker: str,
                    gen: int) -> None:
    """Retire a completed unit's lease — done leases never expire, so
    no survivor wastes a takeover on finished work."""
    cur = _read_json(path)
    if cur and cur.get("worker") == worker and \
            int(cur.get("gen", -1)) == int(gen):
        atomic_write_json(path, lease_payload(extra, worker, gen,
                                              done=True))


def _chunk_extra(chunk_id: int, units) -> dict:
    return {"chunk": int(chunk_id), "units": [int(u) for u in units]}


def claim_chunk(cdir: Path, chunk_id: int, units, worker: str,
                ttl: float) -> Optional[int]:
    """The fleet campaign's lease claim: one seed-range chunk
    (claim_lease over ``leases/chunk-<k>.json``)."""
    return claim_lease(_lease_path(cdir, chunk_id),
                       _chunk_extra(chunk_id, units), worker, ttl)


def mark_done(cdir: Path, chunk_id: int, units, worker: str,
              gen: int) -> None:
    mark_lease_done(_lease_path(cdir, chunk_id),
                    _chunk_extra(chunk_id, units), worker, gen)


class LeaseHeartbeat:
    """Renews a held lease every ttl/3 on a daemon thread; flips
    ``lost`` (and stops renewing) the moment the on-disk record names
    someone else — the worker's signal to abandon the unit at the
    next boundary instead of double-writing."""

    def __init__(self, cdir: Path, chunk_id: int, units, worker: str,
                 gen: int, ttl: float):
        self.path = _lease_path(cdir, chunk_id)
        self.extra = _chunk_extra(chunk_id, units)
        self.worker, self.gen, self.ttl = worker, int(gen), float(ttl)
        self.lost = threading.Event()
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name=f"fleet-hb-{chunk_id}")

    def start(self) -> "LeaseHeartbeat":
        self._t.start()
        return self

    def _run(self) -> None:
        period = max(0.1, self.ttl / 3.0)
        while not self._stop.wait(period):
            if not renew_lease(self.path, self.extra, self.worker,
                               self.gen, ttl=self.ttl):
                self.lost.set()
                return

    def stop(self) -> None:
        self._stop.set()


# ----------------------------------------------------------- work spec

def _chunk_map(ws: dict) -> Dict[int, List[int]]:
    units = [int(u) for u in ws["units"]]
    size = max(1, int(ws.get("lease_chunk", 1)))
    return {k: units[i:i + size]
            for k, i in enumerate(range(0, len(units), size))}


def _summary_path(cdir: Path, unit: int) -> Path:
    return cdir / f"seed-{int(unit)}.json"


def _load_spec(cdir: Path) -> dict:
    ws = _read_json(Path(cdir) / SPEC_FILE)
    if ws is None:
        raise FileNotFoundError(
            f"{Path(cdir) / SPEC_FILE}: no fleet work spec — not a "
            f"campaign dir (orchestrate one with `jepsen-tpu fleet`)")
    if ws.get("fleet") != FLEET_MAGIC:
        raise CampaignMismatch(
            f"{Path(cdir) / SPEC_FILE}: not a {FLEET_MAGIC} work spec")
    return ws


def _resolve_model(name: str):
    from .recheck import registry
    spec = registry()[name]
    assert spec["kind"] == "linear", \
        f"fleet campaigns check linearizable families, not {name!r}"
    return spec["model"]()


def campaign_complete(cdir: Path, ws: Optional[dict] = None,
                      seen: Optional[set] = None) -> bool:
    """Every unit durably summarized. ``seen`` memoizes units already
    observed complete — a summary never disappears once written, so
    pollers (the worker claim loop, the orchestrator's babysit loop)
    pass a per-process set and only re-stat the shrinking remainder
    instead of O(units) metadata round trips per poll (a real cost on
    the multi-host shared-store path)."""
    ws = ws if ws is not None else _load_spec(cdir)
    cdir = Path(cdir)
    for u in ws["units"]:
        u = int(u)
        if seen is not None and u in seen:
            continue
        if _summary_path(cdir, u).exists():
            if seen is not None:
                seen.add(u)
            continue
        return False
    return True


# -------------------------------------------------------------- worker

def fleet_worker(campaign_dir, worker_id: str, *,
                 stop: Optional[threading.Event] = None) -> dict:
    """One worker's whole life against a campaign dir: claim leases,
    process their units (skipping any unit whose summary already
    landed — the zero-re-run invariant at the finest grain), heartbeat
    while working, take over expired leases, and exit once the
    campaign is complete. Writes ``worker-<id>.json`` (unit counts,
    router summary, telemetry counter deltas) on the way out."""
    cdir = Path(campaign_dir)
    ws = _load_spec(cdir)
    ttl = float(ws.get("lease_ttl_s") or lease_ttl_s())
    chunks = _chunk_map(ws)
    # The campaign id from the WORK SPEC becomes this worker process's
    # correlation default: every unit span (and everything dispatched
    # under it) names the campaign in a merged cluster trace.
    prev_corr = telemetry.set_correlation(
        ws.get("corr") or f"fleet:{ws.get('name', '?')}")
    # Periodic registry frames into the shared STORE's series
    # namespace (the campaign dir is store/<name>/fleet — frames
    # belong to the store root, one ring per worker process).
    from . import series as series_mod
    swriter = series_mod.SeriesWriter(ws.get("store_base") or cdir) \
        if series_mod.enabled() else None
    # Heterogeneous-host routing: JT_ROUTER_PROBE=1 measures this
    # host's backend rates once and persists them under its hostname
    # in the campaign dir; with or without the probe, the router
    # prices from THIS host's persisted measurements when they exist
    # (another worker's calibration is wrong by definition).
    if os.environ.get("JT_ROUTER_PROBE", "0") == "1":
        probe_and_persist(cdir)
    router = CostRouter(store_dir=cdir)
    tel_base = telemetry.snapshot()
    seen: set = set()           # units observed complete (memoized)
    stats = {"worker": worker_id, "chunks": 0, "units": 0,
             "rehydrated": 0, "takeovers": 0, "abandoned": 0,
             "errors": 0}
    test_sleep = 0.0
    try:
        test_sleep = float(os.environ.get("JT_FLEET_TEST_SLEEP_S", "0"))
    except ValueError:
        pass

    try:
        with telemetry.span("fleet.worker", worker=worker_id):
            _fleet_worker_loop(cdir, ws, chunks, worker_id, ttl,
                               router, stats, stop, test_sleep, seen,
                               swriter)
    finally:
        telemetry.set_correlation(prev_corr)
        if swriter is not None:
            swriter.close(final_frame=True)
    from .provision import device_identity
    summary = {**stats, "router": router.summary(),
               "device": device_identity(),
               "telemetry": telemetry.counters_delta(
                   tel_base, telemetry.snapshot())}
    atomic_write_json(cdir / f"worker-{worker_id}.json", summary)
    return summary


def _fleet_worker_loop(cdir, ws, chunks, worker_id, ttl, router,
                       stats, stop, test_sleep, seen, swriter) -> None:
    def chunk_done(units) -> bool:
        for u in units:
            u = int(u)
            if u in seen:
                continue
            if _summary_path(cdir, u).exists():
                seen.add(u)
                continue
            return False
        return True

    while not (stop is not None and stop.is_set()):
        if swriter is not None:
            swriter.maybe_append()
        claimed_any = False
        for k, units in chunks.items():
            if stop is not None and stop.is_set():
                break
            if chunk_done(units):
                continue
            gen = claim_chunk(cdir, k, units, worker_id, ttl)
            if gen is None:
                continue
            claimed_any = True
            stats["chunks"] += 1
            if gen > 0:
                stats["takeovers"] += 1
                log.info("worker %s took over chunk %d at "
                         "generation %d (previous lease expired)",
                         worker_id, k, gen)
            hb = LeaseHeartbeat(cdir, k, units, worker_id, gen,
                                ttl).start()
            try:
                finished = _process_chunk(
                    cdir, ws, units, worker_id, hb, router, stats,
                    stop, test_sleep)
            finally:
                hb.stop()
            if finished and not hb.lost.is_set():
                mark_done(cdir, k, units, worker_id, gen)
            elif hb.lost.is_set():
                stats["abandoned"] += 1
                log.warning("worker %s lost chunk %d's lease "
                            "mid-flight; abandoning it cleanly",
                            worker_id, k)
        if campaign_complete(cdir, ws, seen=seen):
            break
        if not claimed_any:
            # Everything left is leased to live workers: wait for
            # them to finish — or for their heartbeats to lapse.
            if stop is not None and stop.wait(min(1.0, ttl / 3.0)):
                break
            if stop is None:
                time.sleep(min(1.0, ttl / 3.0))


def _process_chunk(cdir: Path, ws: dict, units, worker_id: str,
                   hb: LeaseHeartbeat, router: CostRouter, stats: dict,
                   stop, test_sleep: float) -> bool:
    """Run one leased chunk's units. Returns True iff every unit ended
    summarized; ownership is re-checked before each unit so a stolen
    lease abandons at the next boundary."""
    for u in units:
        if (stop is not None and stop.is_set()) or hb.lost.is_set():
            return False
        spath = _summary_path(cdir, u)
        if spath.exists():
            stats["rehydrated"] += 1
            continue
        finish = None
        try:
            summ, finish = _run_unit(cdir, ws, int(u), router)
        except Exception as e:          # noqa: BLE001 — unit-scoped
            # One failing unit must not wedge the whole fleet in a
            # claim/crash loop: the error IS the unit's durable result
            # (valid: unknown), visible in the merge.
            log.warning("fleet unit %s failed: %s", u, e,
                        exc_info=True)
            stats["errors"] += 1
            summ = {"error": f"{type(e).__name__}: {e}"}
        summ["worker"] = worker_id
        atomic_write_json(spath, summ)
        if finish is not None:
            # Journal cleanup strictly AFTER the summary lands (the
            # run_synth_seeds order): a kill between finish and the
            # summary write would leave neither, and the takeover
            # would re-dispatch the whole seed.
            finish()
        stats["units"] += 1
        telemetry.REGISTRY.counter("fleet.units").inc()
        if test_sleep:
            time.sleep(test_sleep)
    return True


def _run_unit(cdir: Path, ws: dict, unit: int,
              router: CostRouter) -> tuple:
    """Execute one work unit by campaign kind; returns (summary,
    cleanup-or-None) — the cleanup (journal finish) runs only AFTER
    the caller lands the summary durably, preserving the
    zero-re-dispatch window. ``synth`` re-uses the exact per-seed
    engine run_synth_seeds runs (runtime.synth_seed_summary under a
    spec-keyed ChunkJournal) — fleet and single-process campaigns
    produce field-for-field identical summaries by construction.
    ``recheck`` cost-routes the stored run's history; ``fuzz`` runs
    one witness-guided round."""
    kind = ws["kind"]
    with telemetry.span("fleet.unit", kind=kind, unit=unit):
        if kind == "synth":
            from .ops.synth_device import SynthSpec
            from .runtime import synth_seed_summary
            spec = SynthSpec(**ws["spec"])
            sspec = dataclasses.replace(spec, seed=int(unit))
            model = _resolve_model(ws["model"])
            # Record the batch-level routing decision (post-partition
            # W bound, per-key event count, the whole batch amortizing
            # one dispatch) alongside the knobs it derives.
            router.choose_wgl(min(spec.n_procs, spec.n_ops),
                              max(1, 2 * spec.n_ops
                                  // max(spec.n_keys, 1)),
                              rows=spec.n)
            journal = ChunkJournal(
                cdir / f"seed-{unit}.journal.jsonl",
                {"spec": spec_digest(sspec, synth=ws["synth"])},
                resume=True)
            check_kwargs = router.wgl_check_kwargs(sspec)
            try:
                summ = synth_seed_summary(
                    model, sspec, synth=ws["synth"], journal=journal,
                    check_kwargs=check_kwargs)
            finally:
                journal.close()
            summ["router"] = check_kwargs
            return summ, journal.finish
        if kind == "fuzz":
            from .fuzz import fuzz_campaign
            from .ops.synth_device import SynthSpec
            spec = SynthSpec(**ws["spec"])
            # Units ARE absolute seeds (the synth-kind contract);
            # fold the unit in as THE round seed, not an offset on
            # top of spec.seed.
            rspec = dataclasses.replace(spec, seed=int(unit))
            out = fuzz_campaign(
                rspec, rounds=1, synth=ws["synth"],
                neighborhood=int(ws.get("neighborhood", 4)),
                max_witnesses=int(ws.get("max_witnesses", 8)),
                name=None)
            return {k: out[k] for k in
                    ("checked", "invalid", "neighborhoods",
                     "neighborhood_invalid", "disagreements")}, None
        if kind == "recheck":
            ts = ws["timestamps"][int(unit)]
            root = Store(Path(ws["store_base"]))
            loaded = root.load(ws["test"], ts)
            h = loaded.get("history")
            if h is None:
                return {"ts": ts, "valid": "unknown",
                        "error": "no stored history"}, None
            model = _resolve_model(ws["model"])
            rs, routing = route_check(model, [h], router=router)
            return {"ts": ts, "valid": rs[0].get("valid"),
                    "backend": rs[0].get("backend"),
                    "backends": routing["backends"]}, None
        raise ValueError(f"unknown fleet kind {kind!r}")


# --------------------------------------------------------- aggregation

def _unit_valid(kind: str, summ: dict):
    if "error" in summ:
        return "unknown"
    if kind == "synth":
        return summ.get("invalid", 0) == 0
    if kind == "fuzz":
        # Finding invalid histories is the fuzz working; a checker
        # DISAGREEMENT is the alarm (the fuzz_cmd exit contract).
        return summ.get("disagreements", 0) == 0
    return summ.get("valid")


def merge_campaign(campaign_dir) -> dict:
    """Fold every worker's durable artifacts into the one campaign
    verdict: per-unit summaries, worker summaries (router choices +
    telemetry counter deltas, summed), and the lease ledger (chunks,
    takeover generations). Persisted as ``fleet/results.json``; the
    orchestrator additionally publishes it as a standard run dir so
    the web index renders the fleet as a single view."""
    from .checkers.core import merge_valid

    cdir = Path(campaign_dir)
    ws = _load_spec(cdir)
    kind = ws["kind"]
    units, missing, invalid = {}, [], 0
    for u in ws["units"]:
        summ = _read_json(_summary_path(cdir, u))
        if summ is None:
            missing.append(int(u))
            continue
        summ["valid"] = _unit_valid(kind, summ)
        units[str(u)] = summ
        if "invalid" in summ:
            # synth/fuzz: invalid HISTORIES found (workload signal).
            invalid += int(summ["invalid"] or 0)
        elif summ["valid"] is False:
            # recheck: one invalid stored run per failing unit — the
            # counter must agree with the merged verdict.
            invalid += 1
    workers, chosen, est = {}, {}, {}
    wsums = []
    for wf in sorted(cdir.glob("worker-*.json")):
        wsum = _read_json(wf) or {}
        wsums.append(wsum)
        wid = wsum.get("worker", wf.stem)
        workers[wid] = {k: wsum.get(k, 0) for k in
                        ("chunks", "units", "rehydrated", "takeovers",
                         "abandoned", "errors")}
        workers[wid]["device"] = wsum.get("device")
        r = wsum.get("router") or {}
        for k, v in (r.get("chosen") or {}).items():
            chosen[k] = chosen.get(k, 0) + v
        for k, v in (r.get("est_cost_s") or {}).items():
            est[k] = round(est.get(k, 0.0) + v, 6)
    leases = {"chunks": 0, "done": 0, "takeovers": 0}
    for lf in sorted((cdir / LEASES_DIR).glob("chunk-*.json")) \
            if (cdir / LEASES_DIR).exists() else []:
        le = _read_json(lf) or {}
        leases["chunks"] += 1
        leases["done"] += bool(le.get("done"))
        leases["takeovers"] += max(0, int(le.get("gen", 0)))
    complete = not missing
    valid = merge_valid(u["valid"] for u in units.values()) \
        if units else True
    if not complete:
        valid = "unknown" if valid is True else valid
    out = {"name": ws["name"], "kind": kind, "valid": valid,
           "created": ws.get("created"),
           "complete": complete, "units": len(ws["units"]),
           "missing": missing, "invalid": invalid, "seeds": units,
           "router": {"chosen": chosen, "est_cost_s": est,
                      "table": CostRouter(store_dir=cdir).table()},
           "workers": workers, "leases": leases,
           "telemetry": {"source": "fleet",
                         "counters": telemetry.merge_counter_snapshots(
                             w.get("telemetry") for w in wsums)}}
    atomic_write_json(cdir / RESULTS_FILE, out)
    return out


def publish_campaign(root: Store, name: str, merged: dict) -> Path:
    """One campaign-level run dir (``store/<name>/<ts>/``) carrying
    the merged verdict: the web index renders the whole fleet as a
    single row (with a ``fleet`` badge) exactly like any other run.
    Idempotent per campaign: a re-merge (e.g. ``--resume`` on a
    completed campaign) refreshes the run dir already published for
    this campaign's ``created`` stamp instead of adding a duplicate
    row."""
    from .store import StoreHandle

    h = None
    for ts in root.tests().get(name, []):
        prior = root._run_json(name, ts, "results.json") or {}
        if (prior.get("fleet") or {}).get("created") is not None and \
                prior["fleet"]["created"] == merged.get("created"):
            h = StoreHandle(root.run_dir(name, ts), store=root,
                            test_name=name)
            break
    if h is None:
        h = root.create(name)
    h.write_json("test.json", {
        "name": name, "fleet": True, "kind": merged["kind"],
        "units": merged["units"]})
    h.save_results({"valid": merged["valid"], "fleet": merged})
    return h.dir


# --------------------------------------------------------- orchestrator

def _spawn_worker(campaign_dir: Path, worker_id: str):
    """One local worker subprocess against the campaign dir — the
    same entry a remote host would run by hand (``jepsen-tpu fleet
    --join DIR --worker-id W``). Workers get their own (small) virtual
    device env: $JT_FLEET_WORKER_DEVICES, default 1 — fleet
    parallelism is across processes, not within them."""
    import subprocess
    import sys

    from .provision import virtual_cpu_env

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(repo) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    try:
        devs = int(os.environ.get("JT_FLEET_WORKER_DEVICES", "1"))
    except ValueError:
        devs = 1
    if devs > 0:
        virtual_cpu_env(devs, env=env)
    logf = open(Path(campaign_dir) / f"worker-{worker_id}.log", "ab")
    p = subprocess.Popen(
        [sys.executable, "-m", "jepsen_tpu.cli", "fleet",
         "--join", str(campaign_dir), "--worker-id", worker_id],
        env=env, stdout=logf, stderr=subprocess.STDOUT)
    p._jt_log = logf        # closed on reap
    return p


class LocalPool:
    """Spawn + babysit a pool of local worker subprocesses — the fleet
    driver's spawner, reusable by the checking service
    (jepsen_tpu.service). Owns worker-id allocation, dead-worker reap
    + bounded respawn, and SLO-advice-driven scale-up
    (``apply_scale_advice``); it knows nothing about what the workers
    do — the spawn callback does."""

    def __init__(self, spawn_fn, n: int, *,
                 max_respawns: Optional[int] = None,
                 cap: Optional[int] = None):
        self.spawn_fn = spawn_fn               # worker_id -> Popen
        self.cap = max_local_workers() if cap is None else int(cap)
        self.target = min(int(n), self.cap) if self.cap else int(n)
        self.procs: Dict[str, object] = {}
        self.spawned = 0
        self.budget = (self.target if max_respawns is None
                       else int(max_respawns))

    def start(self) -> "LocalPool":
        while len(self.procs) < self.target:
            self._spawn_one()
        return self

    def _spawn_one(self) -> str:
        wid = f"w{self.spawned}"
        self.spawned += 1
        self.procs[wid] = self.spawn_fn(wid)
        return wid

    def reap(self, respawn: bool = True) -> List[str]:
        """Collect exited workers; respawn (bounded) when the caller
        says the pool still has work. Returns the reaped ids."""
        dead = [wid for wid, p in self.procs.items()
                if p.poll() is not None]
        for wid in dead:
            p = self.procs.pop(wid)
            getattr(p, "_jt_log", None) and p._jt_log.close()
            if p.returncode != 0:
                log.warning("local worker %s exited rc=%s", wid,
                            p.returncode)
            if respawn and self.budget > 0:
                self.budget -= 1
                nid = self._spawn_one()
                log.info("respawning local worker (%s -> %s)", wid, nid)
        return dead

    def revive(self) -> bool:
        """Budgeted single respawn for a pool found EMPTY with work
        remaining (reap only replaces processes it catches dying —
        a caller that drained to zero between polls uses this).
        False when the respawn budget is spent."""
        if self.budget <= 0:
            return False
        self.budget -= 1
        wid = self._spawn_one()
        log.info("reviving empty worker pool (-> %s)", wid)
        return True

    def scale_to(self, n: int) -> int:
        """Widen the pool to ``n`` workers (bounded by the local core
        cap — width beyond the cores belongs on more hosts). Returns
        how many were spawned."""
        n = min(int(n), self.cap) if self.cap else int(n)
        added = 0
        while len(self.procs) < n:
            self._spawn_one()
            added += 1
        self.target = max(self.target, min(n, len(self.procs)))
        return added

    def apply_scale_advice(self, path, max_age_s: float = 300.0) -> int:
        """Act on a durable ``service/scale-advice.json`` (the SLO
        breach signal the checking service publishes): widen the pool
        toward ``want_workers``, then CONSUME the file — advice is a
        one-shot signal, not standing configuration, and a breach that
        subsided must not over-provision every future pool. Advice
        stamped more than ``max_age_s`` ago is discarded unacted (a
        days-old file found by a fresh serve session describes a
        days-old breach). Returns workers spawned (0 when the advice
        is absent, stale, or already satisfied)."""
        adv = _read_json(path)
        if not adv:
            return 0
        try:
            want = int(adv.get("want_workers", 0))
            age = time.time() - float(adv.get("at") or 0.0)
        except (TypeError, ValueError):
            return 0
        if age > max_age_s:
            try:
                Path(path).unlink()
            except OSError:
                pass
            return 0
        if want <= len(self.procs):
            return 0
        added = self.scale_to(want)
        if added:
            log.info("scale advice %s: spawned %d worker(s) (pool now "
                     "%d; reason: %s)", path, added, len(self.procs),
                     adv.get("reason"))
            telemetry.REGISTRY.counter("service.scaled_workers").inc(
                added)
            try:
                Path(path).unlink()      # consumed
            except OSError:
                pass
        return added

    def shutdown(self, timeout: float = 15.0,
                 terminate: bool = True) -> None:
        """Stop the pool: SIGTERM every worker first (their own
        GracefulShutdown finishes in-flight work and exits clean),
        wait out ``timeout`` each, SIGKILL stragglers. ``terminate=
        False`` waits for natural exit first — the --until-idle path,
        where workers are already draining."""
        if terminate:
            for p in self.procs.values():
                if p.poll() is None:
                    try:
                        p.terminate()
                    except Exception:
                        pass
        for p in self.procs.values():
            try:
                p.wait(timeout=timeout)
            except Exception:
                p.kill()
                p.wait()
            getattr(p, "_jt_log", None) and p._jt_log.close()
        self.procs.clear()


def fleet_campaign(*, name: str = "fleet", kind: str = "synth",
                   seeds: Optional[Sequence[int]] = None, spec=None,
                   model: str = "cas", synth: str = "device",
                   test: Optional[str] = None,
                   timestamps: Optional[Sequence[str]] = None,
                   workers: int = 2, store_root: Optional[Store] = None,
                   resume: bool = False,
                   lease_chunk: Optional[int] = None,
                   lease_ttl: Optional[float] = None,
                   neighborhood: int = 4, max_witnesses: int = 8,
                   poll_s: float = 0.25,
                   max_respawns: Optional[int] = None,
                   stop: Optional[threading.Event] = None) -> dict:
    """Orchestrate one fleet campaign end to end: write the work spec,
    spawn ``workers`` local worker processes (0 = run one worker
    inline, no subprocess), monitor them — a worker that dies while
    units remain is respawned (bounded), and a killed worker's leases
    expire under the survivors anyway — then merge every durable
    artifact into the campaign verdict and publish it as a standard
    run dir. ``resume=True`` continues a killed campaign: completed
    units rehydrate from their summaries (zero re-run), in-flight
    seeds resume their journals (zero re-dispatched histories).

    ``kind``: ``synth`` shards a run_synth_seeds-shaped seed campaign;
    ``recheck`` shards a store-wide blind-sweep recheck of ``test``'s
    stored runs (units are timestamp indices, each cost-routed);
    ``fuzz`` shards witness-guided fuzz rounds."""
    root = store_root if store_root is not None else DEFAULT
    base = Path(root.base).resolve()
    cdir = base / name / FLEET_DIR

    if kind == "recheck":
        assert test, "recheck campaigns name a stored --test"
        if timestamps is None:
            timestamps = root.tests().get(test, [])
        units = list(range(len(timestamps)))
    else:
        assert spec is not None or resume, \
            f"{kind} campaigns need a SynthSpec"
        units = [int(s) for s in seeds] if seeds is not None else None

    existing = _read_json(cdir / SPEC_FILE)
    if resume:
        if existing is None or existing.get("fleet") != FLEET_MAGIC:
            raise FileNotFoundError(
                f"--resume: no fleet campaign at {cdir}")
        if units is not None or spec is not None:
            fresh = _work_spec(name, kind, units, spec, model, synth,
                               test, timestamps, base, lease_chunk,
                               lease_ttl, neighborhood, max_witnesses,
                               workers)
            bad = [k for k in IDENTITY_KEYS
                   if k in fresh and fresh.get(k) != existing.get(k)]
            if bad:
                raise CampaignMismatch(
                    f"fleet campaign {cdir} differs on {bad}; start a "
                    f"fresh campaign (without --resume) to replace it")
        ws = existing
    else:
        if cdir.exists():
            shutil.rmtree(cdir)
        cdir.mkdir(parents=True, exist_ok=True)
        (cdir / LEASES_DIR).mkdir(exist_ok=True)
        ws = _work_spec(name, kind, units, spec, model, synth, test,
                        timestamps, base, lease_chunk, lease_ttl,
                        neighborhood, max_witnesses, workers)
        atomic_write_json(cdir / SPEC_FILE, ws)

    # Local pool width: capped at the host's cores by default
    # (JT_FLEET_MAX_LOCAL_WORKERS) — oversubscribed local jax workers
    # REGRESS; width beyond the cores belongs on more hosts (--join).
    cap = max_local_workers()
    spawn_n = min(workers, cap) if (workers > 0 and cap) else workers
    if 0 < spawn_n < workers:
        log.info("capping local fleet pool at %d worker(s) "
                 "(%d requested, %s cores; join more hosts for more "
                 "width, or set JT_FLEET_MAX_LOCAL_WORKERS=0)",
                 spawn_n, workers, os.cpu_count())
    sp = telemetry.begin("fleet.campaign", name=name, kind=kind,
                         units=len(ws["units"]), workers=spawn_n)
    try:
        if not campaign_complete(cdir, ws):
            if spawn_n <= 0:
                fleet_worker(cdir, "w0", stop=stop)
            else:
                _drive_workers(cdir, ws, spawn_n, poll_s,
                               max_respawns, stop)
    finally:
        sp.end()
    merged = merge_campaign(cdir)
    merged["requested_workers"] = workers
    merged["spawned_workers"] = spawn_n if spawn_n > 0 else 1
    merged["dir"] = str(publish_campaign(root, name, merged))
    return merged


def _work_spec(name, kind, units, spec, model, synth, test, timestamps,
               base, lease_chunk, lease_ttl, neighborhood,
               max_witnesses, workers) -> dict:
    if lease_chunk is None:
        # Several chunks per worker: takeover granularity (what a dead
        # worker forfeits) vs lease traffic.
        lease_chunk = max(1, len(units or ())
                          // max(4 * max(workers, 1), 1))
    created = time.time()
    return {
        "fleet": FLEET_MAGIC, "name": name, "kind": kind,
        # The campaign's correlation id: every worker that joins this
        # spec stamps its spans with it (telemetry.set_correlation),
        # so a merged cluster trace groups all workers' unit spans
        # under one flow (doc/observability.md).
        "corr": f"fleet:{name}:{int(created)}",
        "model": model, "synth": synth,
        "units": [int(u) for u in (units or ())],
        "spec": (dataclasses.asdict(spec) if spec is not None
                 else None),
        "test": test,
        "timestamps": list(timestamps) if timestamps else None,
        "store_base": str(base),
        "lease_chunk": int(lease_chunk),
        "lease_ttl_s": float(lease_ttl if lease_ttl is not None
                             else lease_ttl_s()),
        "neighborhood": int(neighborhood),
        "max_witnesses": int(max_witnesses),
        "created": created,
    }


def _drive_workers(cdir: Path, ws: dict, workers: int, poll_s: float,
                   max_respawns: Optional[int], stop) -> None:
    """Spawn + babysit the local worker pool until the campaign
    completes (LocalPool). Lease expiry already redistributes a dead
    worker's units to survivors; respawning (bounded) just restores
    pool width — and is the only recovery when EVERY worker died."""
    seen: set = set()            # memoized completed units (per poll)
    # cap=0: fleet_campaign already applied the local-core cap when it
    # sized the pool.
    pool = LocalPool(lambda wid: _spawn_worker(cdir, wid), workers,
                     max_respawns=max_respawns, cap=0).start()
    try:
        while True:
            if campaign_complete(cdir, ws, seen=seen):
                break
            if stop is not None and stop.is_set():
                break
            pool.reap(respawn=not campaign_complete(cdir, ws,
                                                    seen=seen))
            if not pool.procs:
                if campaign_complete(cdir, ws, seen=seen):
                    break
                raise RuntimeError(
                    "every fleet worker exited with units remaining "
                    "and the respawn budget exhausted; see "
                    f"{cdir}/worker-*.log")
            time.sleep(poll_s)
    finally:
        # No SIGTERM here: fleet workers have no graceful-shutdown
        # handler — a terminate would kill them mid-unit and lose
        # their worker-<id>.json summaries; they exit on their own
        # once the campaign completes.
        pool.shutdown(timeout=max(5.0, 3 * float(
            ws.get("lease_ttl_s", 15.0))), terminate=False)
