"""Federated online checking service: leasable live tenants across N
workers, dead-worker takeover, cluster-wide admission, SLO-guarded
degradation.

PR 9's ``watch`` daemon checks many live WALs but dies with its single
process; PR 10's fleet leases shard only *batch* campaigns. This module
fuses them (ROADMAP item 4, the federated-dispatch framing of arXiv
2606.02019 applied to OmniLink-style live validation, arXiv
2601.11836): every live run (tenant) becomes a **leasable unit** in the
shared store, so N ``watch``-style workers on N hosts split the tenant
population with nothing but the filesystem coordinating them.

Model
-----
``ServiceWorker`` IS an ``online.OnlineDaemon`` whose admission is
lease-governed: ``discover()`` claims per-tenant lease files
(``store/service/tenants/<name>__<ts>.json``) through the fleet layer's
``O_CREAT|O_EXCL`` + heartbeat protocol (fleet.claim_lease — skew-safe,
atomic, generation-bumping), renews them every TTL/3, and abandons a
tenant the moment its on-disk lease names someone else. Everything
below the admission layer — live tailing, rolling prefix checks,
decided-prefix journals, the overload ladder, parity-exact finalization
— is the PR-9 machinery untouched, which is exactly what makes takeover
cheap: a SIGKILLed worker's leases lapse after ``JT_LEASE_TTL_S``,
survivors re-claim at generation+1, and the new owner resumes the
tenant's inode-bound online ChunkJournal with **zero re-dispatched
decided prefixes** (``ChunkJournal.record`` structurally refuses a
double-decide) and no gap in first-violation detection.

Cluster-wide robustness ladder
------------------------------
  * **admission ledger** — ``store/service/budget.json`` holds the
    CLUSTER's limits (total tenants, wide-tenant count by W class,
    total ingest ops/s, the TTFV SLO); each worker publishes its usage
    in its registry entry (``service/workers/<id>.json``, heartbeat +
    usage + capability) and admits new tenants only while the summed
    live usage fits the ledger. Enforcement is optimistic (usage
    propagates at heartbeat cadence; transient overshoot of one
    heartbeat window is possible and documented) but cluster-scoped:
    no single process's view bounds the fleet.
  * **cost-routed placement** — each candidate tenant is priced per
    worker from a cheap bounded WAL probe (``wal.estimate_peak_w``)
    and the workers' advertised rates (the PR-10 CostRouter
    arithmetic): wide tenants steer to host-oracle-rich workers, long
    ones to event-chunk-capable ones. A worker defers claiming a
    tenant a live peer prices meaningfully cheaper — bounded by a
    patience window so nothing starves — and re-evaluates ownership
    only at lease RENEWAL (release_lease hands the unit over with all
    durable progress intact), so placement never thrashes mid-check.
  * **SLO scale signal** — a cluster-merged ``online.ttfv_s`` p99
    breach (telemetry.merge_histogram_snapshots over every worker's
    published slice) writes a durable ``service/scale-advice.json``;
    the local pool spawner (fleet.LocalPool.apply_scale_advice) widens
    the worker pool toward ``want_workers``, bounded by the host's
    core cap.
  * **takeover-storm breaker** — when a worker dies owning many
    tenants, survivors re-claim with a per-worker per-tick claim
    budget (``JT_SERVICE_CLAIM_BUDGET``), jittered candidate order,
    and a deterministic per-(worker, tenant) takeover stagger
    (``JT_SERVICE_STAGGER_S``), so one death costs bounded takeover
    latency instead of stampeding every survivor into overload — and
    when the inherited backlog IS overload, the PR-9 ladder (widen →
    shed → defer, now with the ``JT_DEFER_MAX_S`` starvation rescue)
    degrades and recovers without dropping a verdict.

``jepsen-tpu serve`` (cli.py) is the operator surface: the default
form orchestrates a local pool plus the web control plane (web.py's
``/service`` view renders every worker's tenants from the shared
registry); ``--join DIR --worker-id W`` runs one worker against an
existing store — the multi-host entry. doc/service.md documents the
formats and protocols; the bench ``service`` section measures
tenants-per-SLO vs workers and kill-a-worker takeover latency
(MULTICHIP_r08).
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import random
import threading
import time
from collections import deque
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from . import telemetry
from .fleet import (LocalPool, _read_json, claim_lease, lease_skew_s,
                    lease_ttl_s, mark_lease_done, release_lease,
                    renew_lease)
from .history.wal import WAL_FILE, estimate_peak_w
from .online import OnlineConfig, OnlineDaemon, OnlineTenant
from .store import DEFAULT, Store, atomic_write_json

log = logging.getLogger("jepsen.service")

SERVICE_MAGIC = "JTSVC1"

#: The cluster budget ledger's defaults (store/service/budget.json).
#: 0 = unlimited / disabled. ``wide_w`` is the W class past which a
#: tenant counts against the wide budget (exponential device cost —
#: the scarce resource the ledger rations cluster-wide).
DEFAULT_BUDGET = {
    "max_tenants": 256,
    "wide_w": 14,
    "max_wide_tenants": 0,
    "max_ingest_ops_s": 0.0,
    "slo_ttfv_s": 0.0,
}


def claim_budget_default() -> int:
    """$JT_SERVICE_CLAIM_BUDGET: lease claims one worker attempts per
    tick — the takeover-storm breaker's rate limit. Default 2: a dead
    worker's tenants redistribute over a few ticks instead of landing
    on one survivor in one burst."""
    try:
        return max(1, int(os.environ.get("JT_SERVICE_CLAIM_BUDGET",
                                         "2")))
    except ValueError:
        return 2


def stagger_s_default() -> float:
    """$JT_SERVICE_STAGGER_S: upper bound of the deterministic
    per-(worker, tenant) takeover jitter — expired leases are
    re-claimed staggered across the window so survivors don't
    stampede. Default 0.5 s (well under the lease TTL; tests set 0)."""
    try:
        return max(0.0, float(os.environ.get("JT_SERVICE_STAGGER_S",
                                             "0.5")))
    except ValueError:
        return 0.5


def load_budget(store: Store) -> dict:
    """The cluster admission ledger, defaults filled in. Unreadable or
    absent → pure defaults (a single-worker store needs no ledger)."""
    out = dict(DEFAULT_BUDGET)
    try:
        out.update(json.loads(store.service_budget_path().read_text()))
    except Exception:
        pass
    return out


def save_budget(store: Store, budget: Optional[dict] = None) -> dict:
    merged = {**DEFAULT_BUDGET, **(budget or {}),
              "service": SERVICE_MAGIC}
    store.service_budget_path().parent.mkdir(parents=True,
                                             exist_ok=True)
    atomic_write_json(store.service_budget_path(), merged)
    return merged


def tenant_price(est_w: int, est_ops: int, caps: dict) -> float:
    """Estimated cost (s) of serving one tenant's interim checks on a
    worker advertising ``caps`` — the PR-10 CostRouter arithmetic
    applied to placement: the device scan pays 2^W lanes per event
    (un-chunked long dispatches penalized on workers without the
    event-chunked resume kernel), the host oracle is near-W-flat, and
    a W past the worker's admission bound rides the host there
    regardless of price.

    A worker advertising ``incremental`` (the resident-frontier delta
    path, $JT_ONLINE_INCREMENTAL) charges its device interim checks
    against the DELTA — ``delta_ops``, its check interval — not the
    whole prefix: on such workers a long tenant's per-tick cost is
    flat in prefix length (fleet.CostRouter.price_online_tick is the
    same arithmetic), so long tenants steer toward frontier-capable
    workers exactly as wide ones steer toward host-oracle-rich ones."""
    from .fleet import online_tick_costs
    rates = caps.get("rates") or {}
    ev = max(int(est_ops), 1)
    inc = bool(caps.get("incremental"))
    delta = min(ev, max(int(caps.get("delta_ops") or 0), 1))
    costs = online_tick_costs(
        est_w, ev, delta, incremental=inc,
        lane_ops_per_s=float(rates.get("lane_ops_per_s") or 1e8),
        host_s_per_event=float(rates.get("host_s_per_event") or 4e-4))
    dev, host = costs["wgl-device"], costs["host-oracle"]
    if not inc and not caps.get("event_route") and ev >= int(
            caps.get("event_route_events") or 8192):
        # No resume kernel: a long prefix re-dispatches monolithically.
        dev *= 4.0
    if est_w > int(caps.get("max_w", 1 << 30)):
        return host             # device not admitted on this worker
    return min(dev, host)


def cluster_idle(store: Store) -> bool:
    """Every incomplete run in the store carries a durable online
    verdict for its CURRENT segment — the whole cluster's work is
    done. (The inode check mirrors OnlineTenant._verdict_stale: a WAL
    rotated after finalization is new work, not idleness.)"""
    for name, ts in store.incomplete(include_salvaged=True):
        v = store.online_verdict(name, ts)
        if v is None:
            return False
        ino = v.get("ino")
        if ino is not None:
            try:
                if os.stat(store.run_dir(name, ts)
                           / WAL_FILE).st_ino != ino:
                    return False
            except OSError:
                pass
    return True


class ServiceWorker(OnlineDaemon):
    """One federated checking worker: an OnlineDaemon whose tenant set
    is governed by per-tenant leases in the shared store. Everything
    the base daemon proves (journal-gated restart, ladder behavior,
    parity-exact finalization) holds per tenant; this layer adds WHO
    serves it, cluster-wide admission, placement, and the storm
    breaker."""

    def __init__(self, store: Optional[Store] = None,
                 config: Optional[OnlineConfig] = None, *,
                 worker_id: str = "w0",
                 lease_ttl: Optional[float] = None,
                 claim_budget: Optional[int] = None,
                 stagger_s: Optional[float] = None,
                 placement_patience_s: Optional[float] = None,
                 rebalance_factor: float = 0.5,
                 rates: Optional[dict] = None,
                 faults=None):
        super().__init__(store=store, config=config, faults=faults)
        self.worker_id = worker_id
        self.ttl = float(lease_ttl if lease_ttl is not None
                         else lease_ttl_s())
        self.claim_budget = int(claim_budget if claim_budget is not None
                                else claim_budget_default())
        self.stagger_s = float(stagger_s if stagger_s is not None
                               else stagger_s_default())
        self.placement_patience_s = float(
            placement_patience_s if placement_patience_s is not None
            else _env_f("JT_SERVICE_PLACEMENT_PATIENCE_S", 2 * self.ttl))
        self.rebalance_factor = float(rebalance_factor)
        self._rates = dict(rates) if rates else None
        from .provision import device_identity
        self._device = device_identity()
        # Lease bookkeeping: {key: {"gen", "path", "renewed"}}.
        self.owned: Dict[Tuple[str, str], dict] = {}
        # Heartbeats run on their own daemon thread (started by
        # ``run()``), decoupled from tick latency: a first-check
        # kernel compile or a long drain must not stall renewals past
        # the TTL and lose the lease to a takeover of a live worker.
        # Tests that drive tick() directly (and simulate death by NOT
        # ticking) get no thread — determinism over liveness there.
        self._hb_lock = threading.Lock()
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._hb_lost: set = set()
        for k in ("claims", "takeovers", "handoffs", "lease_lost",
                  "released", "claim_budget_deferred",
                  "takeover_staggered", "placement_deferred",
                  "cluster_refused", "wclass_refused",
                  "ingest_refused", "scale_advised"):
            self.stats.setdefault(k, 0)
        self._cluster_refused: set = set()
        self._wclass_refused: set = set()
        self._ingest_refused: set = set()
        self._first_seen: Dict[tuple, float] = {}
        # Release hold-down: a tenant we just handed back must not be
        # re-claimed by US before a peer had a whole TTL to take it —
        # otherwise release→re-claim thrashes inside one tick.
        self._released_at: Dict[tuple, float] = {}
        self._est_cache: Dict[tuple, tuple] = {}
        # Bounded: the full distribution lives on the
        # ``service.takeover_s`` histogram; this is the recent window
        # the registry/bench report — an always-on worker must not
        # grow its per-tick publish payload forever.
        self.takeover_latencies: deque = deque(maxlen=256)
        self._ingest_samples: deque = deque(maxlen=64)
        self._ingest_samples.append((time.monotonic(), 0))
        self._advice_cooldown_s = max(self.ttl, 5.0)
        self._budget: dict = load_budget(self.store)
        self._peers: Dict[str, dict] = {}
        # The base daemon's alert evaluator gains the cluster budget
        # (the ttfv SLO rule's threshold) and this worker's name.
        if self._alerts is not None:
            self._alerts.budget_fn = lambda: self._budget
            self._alerts.log.worker_id = worker_id

    # ------------------------------------------------------ capabilities
    def _caps(self) -> dict:
        """What this worker advertises in its registry entry — the
        inputs to every peer's placement pricing of a tenant on us."""
        if self._rates is not None:
            rates = dict(self._rates)
        else:
            from .fleet import router_rates
            rates = {k: router_rates()[k]
                     for k in ("lane_ops_per_s", "host_s_per_event")}
        from .ops.schedule import event_route_min_events
        ev_route = event_route_min_events()
        return {"max_tenants": self.cfg.max_tenants,
                "max_w": self.cfg.max_w,
                "rates": rates,
                "event_route": ev_route > 0,
                "event_route_events": ev_route or 8192,
                # The resident-frontier delta path: peers price this
                # worker's interim checks against the delta, not the
                # prefix (tenant_price).
                "incremental": bool(self.cfg.incremental),
                "delta_ops": max(self.cfg.check_interval_ops, 1)}

    def _svc_count(self, key: str, n: int = 1) -> None:
        self.stats[key] = self.stats.get(key, 0) + n
        telemetry.REGISTRY.counter(f"service.{key}").inc(n)

    def ingest_rate(self) -> float:
        """This worker's recent ingest rate (ops/s over a sliding
        window) — its contribution to the cluster ingest budget."""
        now = time.monotonic()
        cum = self.stats.get("ingested_ops", 0)
        self._ingest_samples.append((now, cum))
        while len(self._ingest_samples) > 2 and \
                now - self._ingest_samples[0][0] > 10.0:
            self._ingest_samples.popleft()
        t0, c0 = self._ingest_samples[0]
        return max(0.0, (cum - c0) / max(now - t0, 1.0))

    def live_peers(self, now: Optional[float] = None) -> Dict[str, dict]:
        """Other workers whose registry heartbeat is fresh (within TTL
        + skew) — the admission and placement peer set."""
        now = time.time() if now is None else now
        out = {}
        for wid, rec in self.store.service_workers().items():
            if wid == self.worker_id:
                continue
            hb = float(rec.get("hb") or 0.0)
            if now - hb < self.ttl + lease_skew_s():
                out[wid] = rec
        return out

    # -------------------------------------------------------- admission
    def _estimate(self, name: str, ts: str) -> Tuple[int, int]:
        """(peak_w, n_ops) estimate for a candidate tenant, cached
        with a TTL-bounded refresh: a run discovered right after its
        header flush (estimate (0, 0)) must not price as free forever
        — the WAL grows, and wide-budget admission prices the CURRENT
        shape, one bounded probe per lease-TTL at most."""
        key = (name, ts)
        now = time.monotonic()
        cached = self._est_cache.get(key)
        if cached is not None and now - cached[1] < self.ttl:
            return cached[0]
        est = estimate_peak_w(self.store.run_dir(name, ts) / WAL_FILE)
        est = est if est is not None else (0, 0)
        self._est_cache[key] = (est, now)
        return est

    def _tenant_corr(self, name: str, ts: str) -> str:
        """The tenant's correlation id — run key + WAL segment inode
        (OnlineTenant.corr_id's formula, computed before the tenant
        object exists): stamped into the lease record and onto the
        takeover span, so a killed owner's tenant spans and the
        survivor's takeover connect in a merged trace."""
        try:
            ino = os.stat(self.store.run_dir(name, ts)
                          / WAL_FILE).st_ino
            return f"{name}/{ts}#{ino}"
        except OSError:
            return f"{name}/{ts}"

    def _jitter(self, key: tuple) -> float:
        """Deterministic per-(worker, tenant) takeover stagger in
        [0, stagger_s): every survivor computes a different delay for
        the same orphaned tenant, spreading the re-claims."""
        if self.stagger_s <= 0:
            return 0.0
        h = hashlib.sha256(
            f"{self.worker_id}:{key[0]}/{key[1]}".encode()).digest()
        return (h[0] / 255.0) * self.stagger_s

    def discover(self) -> None:
        """Lease-governed admission — the override that turns the
        single-process daemon into a federated worker. Walks the
        store's incomplete runs in jittered order and claims what the
        cluster budget, the claim budget, placement pricing, and the
        stagger allow."""
        cfg = self.cfg
        now = time.time()
        self._budget = budget = load_budget(self.store)
        self._peers = peers = self.live_peers(now)
        wide_w = int(budget.get("wide_w") or 0)
        own_active = sum(1 for t in self.tenants.values()
                        if t.status != "done")
        own_wide = sum(1 for t in self.tenants.values()
                       if t.status != "done" and t.peak_w > wide_w)
        cl_tenants = own_active + sum(
            int((p.get("usage") or {}).get("tenants") or 0)
            for p in peers.values())
        cl_wide = own_wide + sum(
            int((p.get("usage") or {}).get("wide") or 0)
            for p in peers.values())
        cl_ingest = self.ingest_rate() + sum(
            float((p.get("usage") or {}).get("ingest_ops_s") or 0.0)
            for p in peers.values())
        claims_left = self.claim_budget
        my_caps = self._caps()

        cands = [(name, ts) for name, ts
                 in self.store.incomplete(include_salvaged=True)
                 if (name, ts) not in self.tenants]
        # Jittered claim order: each worker walks the candidates in its
        # own per-tick shuffle, so two survivors racing for a dead
        # worker's tenants spread their first claims instead of
        # colliding on the same file.
        rng = random.Random(f"{self.worker_id}:{self.stats['ticks']}")
        rng.shuffle(cands)
        for key in cands:
            name, ts = key
            self._first_seen.setdefault(key, now)
            if now - self._released_at.get(key, -1e18) \
                    < max(self.ttl, 1.0):
                continue                    # just released: peers first
            v = self.store.online_verdict(name, ts)
            if v is not None and not self._verdict_current(key, v):
                v = None
            if v is not None:
                continue                    # finalized: nothing to own
            lpath = self.store.service_tenant_lease_path(name, ts)
            cur = _read_json(lpath)
            hb = float((cur or {}).get("hb") or 0.0)
            if cur is not None:
                if cur.get("done"):
                    continue
                if cur.get("worker") != self.worker_id and \
                        not cur.get("released") and \
                        (hb > now + lease_skew_s()
                         or now - hb < self.ttl + lease_skew_s()):
                    continue                # live somewhere else
            if own_active >= cfg.max_tenants:
                continue                    # this worker is full
            max_t = int(budget.get("max_tenants") or 0)
            if max_t and cl_tenants >= max_t:
                if key not in self._cluster_refused:
                    self._cluster_refused.add(key)
                    self._svc_count("cluster_refused")
                continue
            self._cluster_refused.discard(key)
            est_w, est_ops = self._estimate(name, ts)
            wide = est_w > wide_w
            max_wide = int(budget.get("max_wide_tenants") or 0)
            if wide and max_wide and cl_wide >= max_wide:
                if key not in self._wclass_refused:
                    self._wclass_refused.add(key)
                    self._svc_count("wclass_refused")
                continue
            self._wclass_refused.discard(key)
            max_ingest = float(budget.get("max_ingest_ops_s") or 0.0)
            if max_ingest and cl_ingest >= max_ingest:
                # One refusal EVENT per run (the sibling counters'
                # rule): a steadily saturated ingest budget must not
                # grow the SLO signal at tick rate.
                if key not in self._ingest_refused:
                    self._ingest_refused.add(key)
                    self._svc_count("ingest_refused")
                continue
            self._ingest_refused.discard(key)
            if peers and now - self._first_seen[key] \
                    < self.placement_patience_s:
                mine = tenant_price(est_w, est_ops, my_caps)
                best = self._best_peer_price(est_w, est_ops, peers)
                if best is not None and \
                        best < mine * self.rebalance_factor:
                    # A live peer is meaningfully cheaper and has
                    # capacity: leave the tenant for it (bounded by
                    # the patience window — nothing starves).
                    self._svc_count("placement_deferred")
                    continue
            if cur is not None and not cur.get("released") and \
                    cur.get("worker") != self.worker_id and hb > 0:
                # Stagger from the moment the lease became CLAIMABLE
                # (expiry + skew — the same instant every survivor
                # first sees it), not from bare expiry, which the
                # liveness check above has already aged past. Our OWN
                # lease (same-id restart) re-enters immediately — no
                # peer is racing us for it.
                age = now - (hb + self.ttl + lease_skew_s())
                if age < self._jitter(key):
                    self._svc_count("takeover_staggered")
                    continue
            if claims_left <= 0:
                # Storm breaker: this tick's claim budget is spent —
                # the remaining orphans wait for the next tick (or a
                # peer).
                self._svc_count("claim_budget_deferred")
                continue
            corr = self._tenant_corr(name, ts)
            gen = claim_lease(lpath, {"run": f"{name}/{ts}",
                                      "corr": corr},
                              self.worker_id, self.ttl)
            if gen is None:
                continue
            claims_left -= 1
            t = OnlineTenant(self, name, ts,
                             self.store.run_dir(name, ts))
            t.lease_gen = gen
            self.tenants[key] = t
            with self._hb_lock:
                # corr cached at claim time: renewals re-stamp it
                # into the lease record without re-statting the WAL
                # every sweep (the segment identity is fixed for the
                # lease's whole life — a rotation drops the tenant
                # through the verdict/journal staleness paths anyway).
                self.owned[key] = {"gen": gen, "path": lpath,
                                   "renewed": time.monotonic(),
                                   "corr": corr}
            self._svc_count("claims")
            if t.status != "done":
                self._count("admitted")
                own_active += 1
                cl_tenants += 1
                if wide:
                    own_wide += 1
                    cl_wide += 1
            if gen > 0 and cur is not None and cur.get("released"):
                # A voluntary rebalance handoff, not a failure: the
                # generation bumps (journal resume semantics are the
                # same) but the r08 dead-worker takeover figure must
                # not count it.
                self._svc_count("handoffs")
            elif gen > 0:
                self._svc_count("takeovers")
                lat = None
                if hb > 0:
                    # Orphan latency: how long the tenant sat between
                    # its old owner's lease expiring and this re-claim
                    # — the MULTICHIP_r08 takeover figure.
                    lat = max(0.0, now - (hb + self.ttl))
                    self.takeover_latencies.append(round(lat, 4))
                    telemetry.REGISTRY.histogram(
                        "service.takeover_s").observe(lat)
                # The takeover SPAN carries the tenant's correlation
                # id: in a merged cluster trace the dead owner's
                # check spans for this tenant and this survivor's
                # takeover share one id (the r13 acceptance artifact).
                with telemetry.correlation_scope(corr), \
                        telemetry.span("service.takeover",
                                       tenant=f"{name}/{ts}",
                                       gen=gen,
                                       worker=self.worker_id,
                                       orphan_s=lat):
                    telemetry.event("service.takeover.claimed",
                                    tenant=f"{name}/{ts}", gen=gen)
                log.info("worker %s took over tenant %s/%s at "
                         "generation %d", self.worker_id, name, ts,
                         gen)
        # Prune per-run bookkeeping for runs that left the incomplete
        # set (finalized with results.json, deleted...): an always-on
        # worker must not leak an entry per run it ever saw.
        alive = set(cands) | set(self.tenants)
        for d in (self._first_seen, self._released_at,
                  self._est_cache):
            for k in [k for k in d if k not in alive]:
                del d[k]
        for s in (self._cluster_refused, self._wclass_refused,
                  self._ingest_refused):
            s.intersection_update(alive)

    def _verdict_current(self, key: tuple, v: dict) -> bool:
        ino = v.get("ino")
        if ino is None:
            return True
        try:
            return os.stat(self.store.run_dir(*key)
                           / WAL_FILE).st_ino == ino
        except OSError:
            return True

    def _best_peer_price(self, est_w: int, est_ops: int,
                         peers: Dict[str, dict]) -> Optional[float]:
        best = None
        for p in peers.values():
            caps = p.get("caps") or {}
            usage = p.get("usage") or {}
            if int(usage.get("tenants") or 0) >= \
                    int(caps.get("max_tenants") or 1 << 30):
                continue                    # peer is full
            price = tenant_price(est_w, est_ops, caps)
            if best is None or price < best:
                best = price
        return best

    # ---------------------------------------------------------- leases
    def start_heartbeat(self) -> None:
        """Start the background lease-renewal thread (idempotent) —
        the serving loop's liveness guarantee: heartbeats land every
        TTL/3 even while a tick is stalled in a kernel compile or a
        long finalize drain."""
        if self._hb_thread is not None:
            return
        self._hb_stop.clear()
        self._hb_thread = threading.Thread(
            target=self._hb_run, daemon=True,
            name=f"service-hb-{self.worker_id}")
        self._hb_thread.start()

    def stop_heartbeat(self) -> None:
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
            self._hb_thread = None

    def _hb_run(self) -> None:
        period = max(0.1, self.ttl / 3.0)
        while not self._hb_stop.wait(period):
            try:
                with self._hb_lock:
                    for key, lease in list(self.owned.items()):
                        if key in self._hb_lost:
                            continue
                        if renew_lease(lease["path"],
                                       {"run": f"{key[0]}/{key[1]}",
                                        "corr": lease.get("corr")},
                                       self.worker_id, lease["gen"],
                                       ttl=self.ttl):
                            lease["renewed"] = time.monotonic()
                        else:
                            self._hb_lost.add(key)
            except Exception:
                # The heartbeat is the worker's liveness — it must
                # survive any single renewal hiccup (transient I/O,
                # a racing mutation) and try again next period.
                log.warning("lease heartbeat sweep failed; retrying",
                            exc_info=True)

    def _renew_leases(self) -> None:
        nowm = time.monotonic()
        with self._hb_lock:
            for key, lease in list(self.owned.items()):
                t = self.tenants.get(key)
                extra = {"run": f"{key[0]}/{key[1]}",
                         "corr": lease.get("corr")}
                due = nowm - lease["renewed"] >= self.ttl / 3.0
                lost = key in self._hb_lost
                if not lost and due:
                    if renew_lease(lease["path"], extra,
                                   self.worker_id, lease["gen"],
                                   ttl=self.ttl):
                        lease["renewed"] = nowm
                    else:
                        lost = True
                if lost:
                    # The on-disk record names someone else: we
                    # stalled past the TTL and were taken over.
                    # Abandon cleanly — the usurper already resumed
                    # the journal.
                    self._hb_lost.discard(key)
                    self._svc_count("lease_lost")
                    log.warning("worker %s lost tenant %s/%s's "
                                "lease; abandoning it",
                                self.worker_id, *key)
                    if t is not None:
                        t.close()
                        self.tenants.pop(key, None)
                    del self.owned[key]
                    continue
                if due and t is not None and t.status == "tailing" \
                        and self._should_release(t):
                    # Rebalance ONLY at renewal cadence (anti-thrash).
                    if release_lease(lease["path"], extra,
                                     self.worker_id, lease["gen"]):
                        self._svc_count("released")
                        self._released_at[key] = time.time()
                        log.info("worker %s releasing tenant %s/%s "
                                 "to a cheaper-capable peer "
                                 "(rebalance at renewal)",
                                 self.worker_id, *key)
                        t.close()
                        del self.tenants[key]
                        del self.owned[key]

    def _should_release(self, t: OnlineTenant) -> bool:
        """Rebalance decision, evaluated ONLY at renewal cadence: hand
        a wide or long tenant to a live peer that prices it
        meaningfully cheaper. Conservative by construction — a factor-
        of-two advantage, capacity checked, never mid-finalize."""
        peers = self._peers
        if not peers:
            return False
        budget = self._budget
        est_w = t.peak_w
        est_ops = max(len(t.ops), t.checked_ops)
        caps = self._caps()
        wide = est_w > int(budget.get("wide_w") or 0) or \
            est_w > caps["max_w"]
        long_ = est_ops >= int(caps.get("event_route_events") or 8192) \
            and not caps.get("event_route")
        if not (wide or long_):
            return False
        mine = tenant_price(est_w, est_ops, caps)
        best = self._best_peer_price(est_w, est_ops, peers)
        return best is not None and best < mine * self.rebalance_factor

    def _retire_done(self) -> None:
        with self._hb_lock:
            for key, lease in list(self.owned.items()):
                t = self.tenants.get(key)
                if t is not None and t.status == "done":
                    mark_lease_done(lease["path"],
                                    {"run": f"{key[0]}/{key[1]}",
                                     "corr": lease.get("corr")},
                                    self.worker_id, lease["gen"])
                    del self.owned[key]

    # ------------------------------------------------------- registry
    def _ttfv_slice(self) -> Optional[dict]:
        snap = telemetry.snapshot()
        return (snap.get("histograms") or {}).get("online.ttfv_s")

    def publish(self) -> None:
        """This worker's registry entry — heartbeat, usage (the
        cluster-admission inputs), capability (the placement inputs),
        tenants (the web control plane's rows), and the per-worker
        TTFV slice (the cluster SLO merge's input)."""
        usage = {
            "tenants": sum(1 for t in self.tenants.values()
                           if t.status != "done"),
            "wide": sum(1 for t in self.tenants.values()
                        if t.status != "done" and t.peak_w
                        > int(self._budget.get("wide_w") or 0)),
            "ingest_ops_s": round(self.ingest_rate(), 3),
            # Wire-fed tenants admitted like file tenants: the count
            # is the only place the distinction surfaces (admission,
            # leases, takeover, SLOs are all transport-blind).
            "wire_tenants": sum(
                1 for t in self.tenants.values()
                if t.status != "done"
                and t.summary().get("wire")),
        }
        rec = {
            "service": SERVICE_MAGIC, "worker": self.worker_id,
            "pid": os.getpid(), "hb": time.time(),
            "device": self._device,
            "usage": usage, "caps": self._caps(),
            "stats": dict(self.stats),
            "takeover_latency_s": list(self.takeover_latencies),
            "slo": self._ttfv_slice(),
            "tenants": {f"{k[0]}/{k[1]}":
                        {**t.summary(),
                         "gen": getattr(t, "lease_gen", None)}
                        for k, t in self.tenants.items()},
        }
        try:
            path = self.store.service_worker_path(self.worker_id)
            path.parent.mkdir(parents=True, exist_ok=True)
            atomic_write_json(path, rec)
        except Exception:
            log.debug("service registry publish failed", exc_info=True)

    def _maybe_scale_advice(self) -> None:
        slo = float(self._budget.get("slo_ttfv_s") or 0.0)
        if slo <= 0:
            return
        slices = [self._ttfv_slice()] + [
            p.get("slo") for p in self._peers.values()]
        merged = telemetry.merge_histogram_snapshots(
            {"histograms": {"online.ttfv_s": s}}
            for s in slices if s)
        p99 = (merged.get("online.ttfv_s") or {}).get("p99")
        if p99 is None or p99 <= slo:
            return
        backlog = any(t.pending for t in self._active()) or any(
            (name, ts) not in self.tenants
            and self.store.online_verdict(name, ts) is None
            for name, ts
            in self.store.incomplete(include_salvaged=True))
        if not backlog:
            return
        path = self.store.service_advice_path()
        cur = _read_json(path)
        now = time.time()
        if cur and now - float(cur.get("at") or 0.0) \
                < self._advice_cooldown_s:
            return
        want = len(self._peers) + 2       # live peers + me + one more
        atomic_write_json(path, {
            "service": SERVICE_MAGIC, "want_workers": want,
            "reason": f"online.ttfv_s p99 {p99:.3f}s > SLO {slo:.3f}s "
                      f"with backlog", "ttfv_p99_s": p99,
            "slo_ttfv_s": slo, "by": self.worker_id, "at": now})
        self._svc_count("scale_advised")
        log.warning("SLO breach: cluster ttfv p99 %.3fs > %.3fs; "
                    "published scale advice (want %d workers)", p99,
                    slo, want)

    # ------------------------------------------------------------- tick
    def tick(self) -> int:
        self._renew_leases()
        level = super().tick()
        self._retire_done()
        self.publish()
        self._maybe_scale_advice()
        return level

    def run(self, *, stop=None, ticks=None,
            until_idle: bool = False) -> dict:
        """The serving loop, with the lease heartbeat thread alive for
        its whole duration — tick latency (a first-check compile, a
        finalize drain) never costs a live worker its leases."""
        self.start_heartbeat()
        try:
            return super().run(stop=stop, ticks=ticks,
                               until_idle=until_idle)
        finally:
            self.stop_heartbeat()

    def idle(self) -> bool:
        """A federated worker is idle only when the CLUSTER is: its
        own tenants are done and every incomplete run in the store has
        a current durable verdict (a peer may still be working its
        share — --until-idle waits for the fleet, not the process)."""
        return super().idle() and cluster_idle(self.store)

    def summary(self) -> dict:
        return {"worker": self.worker_id,
                "stats": dict(self.stats),
                "takeover_latency_s": list(self.takeover_latencies),
                "tenants": {f"{k[0]}/{k[1]}": t.summary()
                            for k, t in self.tenants.items()}}

    def close(self) -> None:
        self.stop_heartbeat()
        super().close()
        self.publish()


def _env_f(name: str, dflt: float) -> float:
    try:
        return float(os.environ.get(name, dflt))
    except (TypeError, ValueError):
        return float(dflt)


# --------------------------------------------------------- orchestrator

def _spawn_service_worker(store: Store, worker_id: str,
                          args: List[str]):
    """One local service-worker subprocess — the same entry a remote
    host runs by hand (``jepsen-tpu serve --join BASE --worker-id W``).
    One virtual device per worker: service parallelism is across
    processes, exactly like the fleet."""
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
    wdir = store.service_dir() / "workers"
    wdir.mkdir(parents=True, exist_ok=True)
    logf = open(wdir / f"{worker_id}.log", "ab")
    p = subprocess.Popen(
        [sys.executable, "-m", "jepsen_tpu.cli", "serve",
         "--join", str(store.base), "--worker-id", worker_id] + args,
        env=env, stdout=logf, stderr=subprocess.STDOUT)
    p._jt_log = logf
    return p


def service_summary(store: Store,
                    workers: Optional[Dict[str, dict]] = None) -> dict:
    """Fold the shared namespace into one cluster view — what the
    orchestrator returns and the web ``/service`` control plane
    renders: per-worker registry entries, lease ledger, verdict roll-
    up, merged SLO histograms, scale advice. ``workers`` lets a caller
    that already read the registry (the web handler renders tenant
    rows from the same records) avoid a second directory scan."""
    workers = store.service_workers() if workers is None else workers
    # "takeovers" are dead-worker recoveries as the WORKERS counted
    # them; the raw lease-generation sum ("gen_bumps") also includes
    # voluntary rebalance handoffs and same-id re-claims.
    leases = {"tenants": 0, "done": 0, "gen_bumps": 0}
    tdir = store.service_dir() / "tenants"
    if tdir.exists():
        for f in sorted(tdir.glob("*.json")):
            le = _read_json(f) or {}
            leases["tenants"] += 1
            leases["done"] += bool(le.get("done"))
            leases["gen_bumps"] += max(0, int(le.get("gen") or 0))
    leases["takeovers"] = sum(
        int((w.get("stats") or {}).get("takeovers") or 0)
        for w in workers.values())
    verdicts: Dict[str, object] = {}
    invalid = 0
    for name, ts in store.incomplete(include_salvaged=True):
        v = store.online_verdict(name, ts)
        if v is not None:
            verdicts[f"{name}/{ts}"] = v.get("valid")
            if v.get("valid") is False:
                invalid += 1
    slo = telemetry.merge_histogram_snapshots(
        {"histograms": {"online.ttfv_s": w.get("slo")}}
        for w in workers.values() if w.get("slo"))
    takeover_lat = sorted(
        x for w in workers.values()
        for x in (w.get("takeover_latency_s") or []))
    return {
        "service": SERVICE_MAGIC,
        "budget": load_budget(store),
        "workers": {wid: {"hb": w.get("hb"),
                          "usage": w.get("usage"),
                          "stats": {k: (w.get("stats") or {}).get(k, 0)
                                    for k in ("ticks", "checks",
                                              "finalized", "claims",
                                              "takeovers", "handoffs",
                                              "lease_lost",
                                              "released")}}
                    for wid, w in workers.items()},
        "leases": leases,
        "verdicts": verdicts,
        "invalid": invalid,
        "valid": invalid == 0,
        "slo": slo.get("online.ttfv_s"),
        "takeover_latency_s": takeover_lat,
        "scale_advice": _read_json(store.service_advice_path()),
    }


def serve_store(store: Optional[Store] = None, *, workers: int = 2,
                model=None, budget: Optional[dict] = None,
                until_idle: bool = False, ticks: Optional[int] = None,
                stop=None, poll_s: float = 0.5,
                lease_ttl: Optional[float] = None,
                claim_budget: Optional[int] = None,
                worker_args: Optional[List[str]] = None,
                max_respawns: Optional[int] = None,
                **cfg_kw) -> dict:
    """The ``jepsen-tpu serve`` body: write the cluster budget ledger,
    run the worker pool (N local subprocesses via fleet.LocalPool —
    0 = one worker inline, the test/bench seam), babysit it (dead
    workers respawn bounded; lease expiry already redistributes their
    tenants either way), act on durable SLO scale advice, and return
    the merged cluster summary."""
    root = store if store is not None else DEFAULT
    root.service_dir().mkdir(parents=True, exist_ok=True)
    save_budget(root, budget)
    sp = telemetry.begin("service.serve", workers=workers)
    try:
        if workers <= 0:
            # The inline worker ticks at the caller's poll cadence —
            # one --poll knob, honored on every path (join /
            # subprocess / inline).
            cfg_kw.setdefault("poll_s", poll_s)
            cfg = OnlineConfig(model=model, **cfg_kw)
            w = ServiceWorker(store=root, config=cfg,
                              worker_id="w0", lease_ttl=lease_ttl,
                              claim_budget=claim_budget)
            try:
                w.run(stop=stop, ticks=ticks, until_idle=until_idle)
            finally:
                w.close()
        else:
            args = list(worker_args or [])
            if until_idle:
                args.append("--until-idle")
            bounded = bool(ticks)
            pool = LocalPool(
                lambda wid: _spawn_service_worker(root, wid, args),
                workers, max_respawns=max_respawns).start()
            babysit_s = min(poll_s, 0.5)
            try:
                while True:
                    if stop is not None and stop.is_set():
                        break
                    idle = cluster_idle(root)
                    # Tick-bounded workers (--ticks rides through to
                    # them) exit naturally: don't respawn, and follow
                    # them out once the pool drains.
                    pool.reap(respawn=not idle and not bounded)
                    pool.apply_scale_advice(root.service_advice_path())
                    if not pool.procs:
                        if bounded or (until_idle and idle):
                            break
                        # Pool drained with work remaining (workers
                        # crashed, or a run landed right after an
                        # idle drain): revive within the respawn
                        # budget, never spin an empty pool forever.
                        if not pool.revive():
                            raise RuntimeError(
                                "every service worker exited with "
                                "work remaining and the respawn "
                                "budget exhausted; see "
                                f"{root.service_dir()}/workers/*.log")
                    time.sleep(babysit_s)
            finally:
                pool.shutdown(timeout=max(
                    15.0, 3 * float(lease_ttl or lease_ttl_s())))
    finally:
        sp.end()
    return service_summary(root)
