"""Tiering controller: activity-driven HBM / host / disk tenant residency.

The reference serves "millions of users" by making tenant residency a
lifecycle (multi-tenancy + offload modules); this module is the TPU
analogue for the scarce tier being HBM. Every multi-tenant tenant has a
residency state:

- **hot** — shard open, vector arrays (raw corpus or quantized code
  planes + beam tables) resident in HBM; searches are device dispatches.
- **warm** — shard open, device arrays demoted to host RAM; searches are
  served by the instrumented exact host fallback tier
  (``weaviate_tpu_tier_searches_total{tier="host"}``).
- **cold** — shard closed; its state lives on disk through the normal
  shard checkpoint (``storage/``) and, when configured, the
  ``backup/offload.py`` bucket tier. First touch re-opens it.

A background cycle (``tick``) refreshes footprints, evicts the
least-active hot tenants when the HBM byte budget is exceeded, promotes
active warm tenants back when room exists, and releases idle warm
tenants to disk. The first query after cold blocks on an ASYNC promotion
under the request's existing serving :class:`Deadline` — if the
promotion outlives the budget the request sheds with
:class:`ColdStartPending` (HTTP 503 + Retry-After), never by stalling a
device batch or hanging.

Activity is an exponentially decayed per-tenant event rate fed from the
query/ingest paths (``core/collection.py``) and the serving tenant
throttle (``serving/tenancy.py`` ``on_activity`` hook).
"""

from __future__ import annotations

import logging
import math
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Callable, Optional

from weaviate_tpu.monitoring.metrics import (
    TIER_BYTES,
    TIER_COLD_HITS,
    TIER_COLD_SHED,
    TIER_DEMOTIONS,
    TIER_PROMOTION_LATENCY,
    TIER_PROMOTIONS,
)
from weaviate_tpu.tiering.accountant import HbmAccountant, TenantKey

logger = logging.getLogger("weaviate_tpu.tiering")

HOT = "hot"
WARM = "warm"
COLD = "cold"

_UNSET = object()


class ColdStartPending(RuntimeError):
    """A promotion is in flight but the request's deadline expired first:
    shed with 503 + Retry-After (the promotion keeps running — the retry
    lands on a hot or warm tenant)."""

    def __init__(self, message: str, retry_after: float):
        super().__init__(message)
        self.retry_after = max(1.0, float(retry_after))


class _Tenant:
    __slots__ = ("key", "state", "score", "last_access", "last_decay",
                 "hbm_bytes", "host_bytes", "promote_bytes", "disk_bytes")

    def __init__(self, key: TenantKey, state: str, now: float):
        self.key = key
        self.state = state
        self.score = 0.0  # decayed access rate (events / half-life window)
        self.last_access = now
        self.last_decay = now
        self.hbm_bytes = 0  # last-known device footprint (hot: live)
        self.host_bytes = 0  # warm-tier host RAM (detached arrays)
        # HBM a warm -> hot promotion would charge: the detached arrays at
        # their RESIDENT width (a flat store's mirror is float32 whatever
        # its rows are on the device)
        self.promote_bytes = 0
        self.disk_bytes = 0  # cold-tier on-disk size, measured at release

    def measure_warm(self, shard) -> None:
        self.host_bytes = shard.host_tier_bytes()
        self.promote_bytes = shard.promote_bytes()


class TieringController:
    """One per DB. Created only when a budget (env / ctor / knob) or an
    explicit opt-in enables tiering; absent, nothing in the serving path
    changes."""

    def __init__(self, db, budget_bytes: int = 0, *,
                 half_life_s: float = 30.0,
                 cold_after_s: float = 300.0,
                 promote_min_score: float = 1.0,
                 swap_margin: float = 1.5,
                 max_cold_wait_s: float = 60.0,
                 coldstore=None,
                 clock: Callable[[], float] = time.monotonic):
        self.db = db
        self.accountant = HbmAccountant(budget_bytes)
        # bottomless cold tier (tiering/coldstore.py): when a blob store
        # is configured, a cold release offloads the tenant wholesale and
        # first touch hydrates through the promotion path below
        self.coldstore = coldstore
        self.half_life_s = float(half_life_s)
        self.cold_after_s = float(cold_after_s)
        self.promote_min_score = float(promote_min_score)
        self.swap_margin = float(swap_margin)
        self.max_cold_wait_s = float(max_cold_wait_s)
        self._clock = clock
        self._lock = threading.Lock()
        # serializes every residency move's check -> move -> charge, so
        # (a) two concurrent promotions can't each pass the budget check
        # and then both attach, and (b) a tick eviction can't interleave
        # with an in-flight promotion and leave a stale absolute charge.
        # Reentrant: promotions call _make_room (which demotes) while
        # holding it. NEVER held across a cold shard open — that is
        # seconds of replay an unrelated tenant's write would stall on.
        self._attach_lock = threading.RLock()
        self._entries: dict[TenantKey, _Tenant] = {}
        # tenant-name -> keys index for the serving front door's
        # name-only signal: one dict hit per request instead of an
        # O(all-tenants) scan under the lock
        self._by_name: dict[str, set[TenantKey]] = {}
        self._futures: dict[TenantKey, Future] = {}
        # sized to the collection shard-open limiter (_LOAD_LIMITER = 8):
        # promotions are IO/replay-bound and single-flight per tenant, so
        # the pool must never be a NARROWER bottleneck than the lazy-open
        # path it replaced (K cold tenants after a restart would queue
        # their first queries behind two replays and shed on deadline);
        # the device-attach legs are serialized by _attach_lock anyway,
        # so a wider pool only overlaps disk replays, which the limiter
        # already bounds
        self._pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="tiering-promote")
        self._promote_ewma_s = 0.5  # Retry-After estimate
        self._closed = False

    # -- activity ----------------------------------------------------------
    def _decay(self, ent: _Tenant, now: float) -> float:
        dt = max(0.0, now - ent.last_decay)
        if dt > 0:
            ent.score *= math.exp(-dt * math.log(2.0) / self.half_life_s)
            ent.last_decay = now
        return ent.score

    def _touch(self, key: TenantKey, now: float,
               weight: float = 1.0) -> _Tenant:
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                ent = _Tenant(key, COLD, now)
                self._entries[key] = ent
                self._by_name.setdefault(key[1], set()).add(key)
            self._decay(ent, now)
            ent.score += weight
            ent.last_access = now
            return ent

    def on_access(self, collection: str, tenant: str,
                  kind: str = "query") -> None:
        """Standalone activity signal for paths that do not run the
        :meth:`ensure_hot` gate (which carries its own event weight —
        callers use one or the other, never both, so an operation is
        ONE bump). Ingest weighs heavier: a tenant being loaded is
        about to be queried."""
        self._touch((collection, tenant), self._clock(),
                    weight=2.0 if kind == "ingest" else 1.0)

    def on_tenant_signal(self, tenant: str) -> None:
        """Tenant-name-only signal from the serving throttle (it does not
        know the collection); bumps every entry carrying the name. Runs
        per admitted request — one lock, one index hit."""
        now = self._clock()
        with self._lock:
            for key in self._by_name.get(tenant, ()):
                ent = self._entries.get(key)
                if ent is None:
                    continue
                self._decay(ent, now)
                ent.score += 0.5
                ent.last_access = now

    # -- bookkeeping hooks (collection lifecycle) --------------------------
    def note_shard_open(self, col, tenant: str, shard) -> None:
        """A tenant shard was (lazily) opened — start renting HBM."""
        key = (col.config.name, tenant)
        now = self._clock()
        with self._attach_lock:
            # footprint read + charge under the attach lock: an in-flight
            # promotion/demotion of the same tenant charging concurrently
            # would otherwise interleave with this read and leave a stale
            # absolute value in the ledger
            hbm = shard.hbm_bytes()
            with self._lock:
                ent = self._entries.get(key)
                if ent is None:
                    ent = _Tenant(key, HOT, now)
                    self._entries[key] = ent
                    self._by_name.setdefault(key[1], set()).add(key)
                ent.state = HOT if shard.device_resident() else WARM
                ent.hbm_bytes = hbm
            self.accountant.charge(key, hbm)

    def forget(self, collection: str, tenant: str) -> None:
        """Tenant removed: drop its ledger charge and entry."""
        key = (collection, tenant)
        with self._lock:
            self._entries.pop(key, None)
            self._futures.pop(key, None)
            self._unindex(key)
        self.accountant.release(key)
        self._refresh_tier_gauges()

    def forget_collection(self, collection: str) -> None:
        with self._lock:
            keys = [k for k in self._entries if k[0] == collection]
            for k in keys:
                self._entries.pop(k, None)
                self._futures.pop(k, None)
                self._unindex(k)
        for k in keys:
            self.accountant.release(k)
        self._refresh_tier_gauges()

    def _unindex(self, key: TenantKey) -> None:
        """Caller holds ``self._lock``."""
        keys = self._by_name.get(key[1])
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._by_name[key[1]]

    # -- the query-path gate ----------------------------------------------
    def ensure_hot(self, col, tenant: str, deadline=_UNSET,
                   weight: float = 1.0) -> None:
        """Block (under the request deadline) until the tenant's shard is
        open. A warm tenant serves immediately from the host tier; only a
        COLD tenant waits on the async promotion. Raises
        :class:`ColdStartPending` when the deadline expires first.
        ``weight`` is the operation's activity bump (2.0 for writes) —
        the gate doubles as the signal so one request is ONE event."""
        key = (col.config.name, tenant)
        now = self._clock()
        ent = self._touch(key, now, weight=weight)
        shard_open = f"tenant-{tenant}" in col._shards
        if shard_open and ent.state in (HOT, WARM):
            return
        if deadline is _UNSET:
            from weaviate_tpu.serving.context import current_deadline

            deadline = current_deadline()
        from_tier = ent.state if not shard_open else WARM
        TIER_COLD_HITS.inc(tier=from_tier)
        fut = self._promotion_future(key, col, tenant, from_tier)
        timeout = self.max_cold_wait_s
        if deadline is not None:
            timeout = max(0.0, deadline.remaining())
        # the cold-start wait as a child span of the request: first-query-
        # after-cold latency decomposes into THIS wait vs the search
        # itself (the promotion's own work traces under tiering.promote)
        from weaviate_tpu.monitoring.tracing import TRACER

        with TRACER.span("tiering.cold_wait", tier=from_tier,
                         collection=key[0], tenant=tenant):
            try:
                fut.result(timeout=timeout)
            except FuturesTimeout:
                TIER_COLD_SHED.inc()
                raise ColdStartPending(
                    f"tenant {tenant!r} is being promoted from the "
                    f"{from_tier} tier; retry shortly",
                    retry_after=math.ceil(self._promote_ewma_s)) from None

    def _promotion_future(self, key: TenantKey, col, tenant: str,
                          from_tier: str) -> Future:
        """Single-flight async promotion per tenant; concurrent cold
        queries all wait on the SAME open instead of stampeding the
        shard load limiter."""
        with self._lock:
            fut = self._futures.get(key)
            if fut is not None and not fut.done():
                return fut
            if self._closed:
                f: Future = Future()
                f.set_result(None)
                return f
            fut = self._pool.submit(self._promote, key, col, tenant,
                                    from_tier)
            self._futures[key] = fut
            return fut

    def _promote(self, key: TenantKey, col, tenant: str,
                 from_tier: str) -> None:
        # runs on the promotion pool: its own trace root (requests that
        # blocked on it hold tiering.cold_wait spans in THEIR traces)
        from weaviate_tpu.monitoring.tracing import TRACER

        with TRACER.span("tiering.promote", parent=None,
                         collection=key[0], tenant=tenant,
                         from_tier=from_tier) as _sp:
            self._promote_traced(key, col, tenant, from_tier, _sp)

    def _promote_traced(self, key: TenantKey, col, tenant: str,
                        from_tier: str, _sp) -> None:
        t0 = self._clock()
        with self._lock:
            ent0 = self._entries.get(key)
            est = max(ent0.hbm_bytes, ent0.promote_bytes) if ent0 else 0
        # make room FIRST with the last-known footprint, so the attach
        # never lands the ledger past the budget (a tenant never seen
        # before has no estimate — the post-open rebalance covers it)
        if est > 0:
            with self._attach_lock:
                self._make_room(est, exclude=key)
        # the cold open (checkpoint replay, possibly seconds) runs
        # OUTSIDE the attach lock: another tenant's warm attach or write
        # promotion must not queue behind this tenant's disk replay.
        # An OFFLOADED tenant hydrates from the blob tier first — inside
        # this single-flight future, so concurrent cold queries share one
        # download and the deadline shed (ColdStartPending) applies
        # unchanged. Hydration failure propagates: a torn manifest/blob
        # must fail the waiting queries loudly, never open an empty shard
        # in place of the tenant's data.
        if self.coldstore is not None:
            self.coldstore.hydrate(col, tenant)
        shard = col._get_shard(f"tenant-{tenant}")
        per_tenant = self._tenant_budget(col)
        with self._attach_lock:
            if not shard.device_resident():
                # WARM -> HOT leg: re-upload the detached arrays, but only
                # when they fit under both the tenant cap and the global
                # budget — otherwise the tenant keeps serving from host
                need = shard.promote_bytes()
                if ((per_tenant <= 0 or need <= per_tenant)
                        and not self.accountant.would_exceed(
                            max(0, need - self.accountant.charged(key)))):
                    shard.promote_device()  # graftlint: allow[device-array-leak] reason=absolute footprint re-charged via accountant.charge(hbm) below
            hbm = shard.hbm_bytes()
            if per_tenant > 0 and hbm > per_tenant:
                # over its own cap: this tenant is pinned to the warm tier
                freed = shard.demote_device()
                logger.info("tenant %s/%s over per-tenant HBM budget "
                            "(%d > %d): pinned warm, %d bytes released",
                            key[0], tenant, hbm, per_tenant, freed)
                hbm = shard.hbm_bytes()
            elif self.accountant.would_exceed(
                    max(0, hbm - self.accountant.charged(key))):
                # still no room after make-room (everyone else is hotter):
                # serve this tenant from the warm tier rather than
                # bursting the budget
                released = shard.demote_device()
                logger.info("tenant %s/%s opened warm (budget full, "
                            "%d bytes kept off device)", key[0], tenant,
                            released)
                hbm = shard.hbm_bytes()
            self.accountant.charge(key, hbm)
        dt = max(0.0, self._clock() - t0)
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                ent.state = HOT if shard.device_resident() else WARM
                ent.hbm_bytes = hbm
                ent.measure_warm(shard)
            self._promote_ewma_s = 0.8 * self._promote_ewma_s + 0.2 * dt
        _sp.set(promote_ms=round(dt * 1000, 3), hbm_bytes=hbm,
                device_resident=shard.device_resident())
        TIER_PROMOTIONS.inc(from_tier=from_tier)
        TIER_PROMOTION_LATENCY.observe(
            dt, from_tier=from_tier,
            exemplar=_sp.trace_id if _sp.sampled else "")
        self._refresh_tier_gauges()
        # compile-tax burn-down (utils/prewarm.py, gated on the compile
        # cache opt-in): the promoted tenant's shape-bucket lattice
        # compiles in the background so follow-up queries in ANY bucket
        # execute — tiering's cold-first-query SLO stays compile-free.
        # Async: the requester blocked on this promotion must not also
        # wait out the lattice.
        if shard.device_resident():
            from weaviate_tpu.utils import prewarm

            prewarm.prewarm_collection(
                col, reason="promotion", shards=[f"tenant-{tenant}"],
                block=False)

    def promote_for_write(self, key: TenantKey, shard) -> None:
        """Writers must be device-resident (demoted stores reject
        mutations). Promote under the attach lock with make-room so the
        global budget is respected; a tenant over its per-tenant cap
        still promotes to absorb the write — cap enforcement is the
        tick's re-demote backstop, never a write outage."""
        gained = 0
        with self._attach_lock:
            if not shard.device_resident():
                need = shard.promote_bytes()
                self._make_room(
                    max(0, need - self.accountant.charged(key)),
                    exclude=key)
                gained = shard.promote_device()
            hbm = shard.hbm_bytes()
            self.accountant.charge(key, hbm)
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                ent.state = HOT if shard.device_resident() else WARM
                ent.hbm_bytes = hbm
                ent.measure_warm(shard)
        if gained:
            TIER_PROMOTIONS.inc(from_tier=WARM)
        self._refresh_tier_gauges()

    def _tenant_budget(self, col) -> int:
        return int(getattr(col.config.multi_tenancy,
                           "tenant_hbm_budget_bytes", 0) or 0)

    # -- the background pass ----------------------------------------------
    def tick(self) -> None:
        """One controller pass: refresh footprints, evict past-budget,
        promote deserving warm tenants, release idle ones to disk."""
        if self._closed:
            return
        from weaviate_tpu.utils.runtime_config import TIERING_HBM_BUDGET

        knob = int(TIERING_HBM_BUDGET.get())
        if knob > 0 and knob != self.accountant.budget_bytes:
            self.accountant.set_budget(knob)
        now = self._clock()
        with self._lock:
            entries = list(self._entries.values())
            for ent in entries:
                self._decay(ent, now)
        # 1) refresh hot footprints (stores grow by doubling); re-demote
        # tenants a write pushed past their per-tenant cap (writes always
        # promote to land — this is the cap's enforcement backstop)
        for ent in entries:
            if ent.state != HOT:
                continue
            shard = self._open_shard(ent.key)
            if shard is None:
                continue
            with self._attach_lock:
                # read + charge as one unit: charging a footprint read
                # BEFORE a concurrent promotion/demotion settled would
                # plant a stale absolute value the overshoot loop then
                # "repairs" by evicting innocents
                ent.hbm_bytes = shard.hbm_bytes()
                self.accountant.charge(ent.key, ent.hbm_bytes)
            col = self._collection(ent.key)
            if col is not None:
                per = self._tenant_budget(col)
                if per > 0 and ent.hbm_bytes > per:
                    self._demote_warm(ent)
        # 2) evict least-active hot tenants while over budget; each
        # victim is tried at most once — a non-demotable index (e.g.
        # hfresh, no warm tier) stays HOT after the attempt and would
        # otherwise be re-picked forever
        tried: set = set()
        while self.accountant.overshoot() > 0:
            victim = self._coldest(
                [e for e in entries if e.key not in tried], HOT)
            if victim is None:
                break
            tried.add(victim.key)
            self._demote_warm(victim)
        # 3) promote the most active warm tenants while room exists (but
        # never one that step 4 is about to release — score decays on a
        # half-life while cold_after_s is a hard idle wall, so a freshly
        # ingested but now-idle tenant can satisfy both at once)
        for ent in sorted((e for e in entries if e.state == WARM),
                          key=lambda e: -e.score):
            if ent.score < self.promote_min_score:
                break
            if now - ent.last_access >= self.cold_after_s:
                continue
            col = self._collection(ent.key)
            if col is None:
                continue
            per_tenant = self._tenant_budget(col)
            if per_tenant > 0 and ent.promote_bytes > per_tenant:
                continue  # pinned warm by its own cap
            if self.accountant.would_exceed(ent.promote_bytes):
                # budget full: promote only by SWAP — when this tenant is
                # decisively hotter than the coldest hot incumbent (the
                # one the promotion's make-room pass will evict). Without
                # this, a full budget freezes residency forever: whoever
                # won the first eviction stays hot no matter how the
                # traffic shifts. The margin is hysteresis against
                # ping-ponging two near-equal tenants through HBM.
                victim = self._coldest(
                    [e for e in entries if e.key != ent.key], HOT)
                if (victim is None
                        or ent.score <= self.swap_margin * victim.score):
                    continue
            self._promotion_future(ent.key, col, ent.key[1], WARM)
        # 4) idle tenants drain out: hot->warm->cold after cold_after_s
        for ent in entries:
            if now - ent.last_access < self.cold_after_s:
                continue
            if ent.state == HOT:
                self._demote_warm(ent)
            elif ent.state == WARM:
                self._release_cold(ent)
        self._refresh_tier_gauges()

    def _demote_warm(self, ent: _Tenant) -> None:
        # under the attach lock (reentrant from _make_room): an eviction
        # interleaving with an in-flight promotion of the SAME tenant
        # would otherwise let the promotion re-charge bytes the eviction
        # just released — a stale ledger the controller would then
        # "repair" by evicting innocents
        with self._attach_lock:
            shard = self._open_shard(ent.key)
            if shard is None:
                ent.state = COLD
                self.accountant.release(ent.key)
                return
            freed = shard.demote_device()
            ent.hbm_bytes = shard.hbm_bytes()  # 0 unless a tier can't demote
            ent.measure_warm(shard)
            ent.state = WARM if ent.hbm_bytes == 0 else HOT
            self.accountant.charge(ent.key, ent.hbm_bytes)
        if ent.state == WARM:
            TIER_DEMOTIONS.inc(to_tier=WARM)
            logger.info("demoted tenant %s/%s to warm (%d HBM bytes "
                        "released)", ent.key[0], ent.key[1], freed)

    def _release_cold(self, ent: _Tenant) -> None:
        with self._lock:
            fut = self._futures.get(ent.key)
            if fut is not None and not fut.done():
                return  # a promotion is attaching; releasing now would
                # close the shard out from under it — next pass retries
        col = self._collection(ent.key)
        if col is None:
            return
        released = col.release_tenant(ent.key[1])
        if not released:
            return  # someone is using it; next pass retries
        ent.state = COLD
        ent.hbm_bytes = 0
        ent.host_bytes = 0
        ent.promote_bytes = 0
        ent.disk_bytes = _dir_bytes(
            os.path.join(col.dir, f"tenant-{ent.key[1]}"))
        self.accountant.release(ent.key)
        TIER_DEMOTIONS.inc(to_tier=COLD)
        logger.info("released tenant %s/%s to the cold tier (%d bytes "
                    "on disk)", ent.key[0], ent.key[1], ent.disk_bytes)
        if self.coldstore is not None:
            # wholesale offload of the closed shard dir: manifest-first,
            # verify-then-delete-local (coldstore.py). A failed offload
            # keeps the local copy — the tenant stays plain-cold and the
            # next release retries with a fresh generation.
            self.coldstore.offload(col, ent.key[1])

    def _coldest(self, entries: list, state: str) -> Optional[_Tenant]:
        cands = [e for e in entries if e.state == state]
        if not cands:
            return None
        return min(cands, key=lambda e: (e.score, e.last_access))

    # -- plumbing ----------------------------------------------------------
    def _collection(self, key: TenantKey):
        try:
            return self.db.get_collection(key[0])
        except KeyError:
            return None

    def _open_shard(self, key: TenantKey):
        col = self._collection(key)
        if col is None:
            return None
        return col._shards.get(f"tenant-{key[1]}")

    def _make_room(self, nbytes: int, exclude: TenantKey) -> None:
        tried: set = set()  # a non-demotable victim must not spin the loop
        while self.accountant.would_exceed(nbytes):
            with self._lock:
                entries = [e for e in self._entries.values()
                           if e.key != exclude and e.key not in tried]
            victim = self._coldest(entries, HOT)
            if victim is None:
                return
            tried.add(victim.key)
            self._demote_warm(victim)

    def _refresh_tier_gauges(self) -> None:
        with self._lock:
            host = sum(e.host_bytes for e in self._entries.values()
                       if e.state == WARM)
            disk = sum(e.disk_bytes for e in self._entries.values()
                       if e.state == COLD)
        TIER_BYTES.set(host, tier="host")
        TIER_BYTES.set(disk, tier="disk")

    def stats(self) -> dict:
        with self._lock:
            tenants = {
                f"{k[0]}/{k[1]}": {
                    "state": e.state,
                    "score": round(e.score, 3),
                    "hbm_bytes": e.hbm_bytes,
                    "host_bytes": e.host_bytes,
                    "disk_bytes": e.disk_bytes,
                }
                for k, e in sorted(self._entries.items())
            }
        return {"accountant": self.accountant.snapshot(),
                "tenants": tenants}

    def close(self) -> None:
        self._closed = True
        self._pool.shutdown(wait=False, cancel_futures=True)


def _dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for fn in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, fn))
            except OSError:
                continue
    return total
