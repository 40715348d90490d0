"""Prometheus-compatible metrics registry (text exposition format).

Reference: ``usecases/monitoring/prometheus.go:40`` (~100 instruments over
batch/query/LSM/vector-index/queue paths, served on :2112). This is a
dependency-free implementation of the counter/gauge/histogram subset the
framework instruments, rendered in the Prometheus text format at /metrics.
"""

from __future__ import annotations

import threading
from typing import Optional

_DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    2.5, 5.0, 10.0,
)


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class _Metric:
    def __init__(self, name: str, help_: str, kind: str):
        self.name = name
        self.help = help_
        self.kind = kind
        # re-entrant: a collection can start between two bytecodes of a
        # thread that holds this lock, and the collector's callback
        # (monitoring/interp.py) finishes a span on that same thread,
        # which counts it in TRACE_SPANS
        self._lock = threading.RLock()


class Counter(_Metric):
    def __init__(self, name, help_=""):
        super().__init__(name, help_, "counter")
        self._values: dict[tuple, float] = {}

    def inc(self, amount: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} counter"]
        for key, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(dict(key))} {v}")
        return out


class Gauge(_Metric):
    def __init__(self, name, help_=""):
        super().__init__(name, help_, "gauge")
        self._values: dict[tuple, float] = {}

    def set(self, value: float, **labels):
        with self._lock:
            self._values[tuple(sorted(labels.items()))] = float(value)

    def inc(self, amount: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels):
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} gauge"]
        for key, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(dict(key))} {v}")
        return out


class Histogram(_Metric):
    def __init__(self, name, help_="", buckets=_DEFAULT_BUCKETS):
        super().__init__(name, help_, "histogram")
        self.buckets = tuple(sorted(buckets))
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        self._totals: dict[tuple, int] = {}
        # exemplar per label set: (value, trace_id) of the WORST
        # observation — the handle that turns "p99 regressed" into a
        # concrete trace tree at /v1/debug/traces?trace=<id>
        self._exemplars: dict[tuple, tuple[float, str]] = {}

    def observe(self, value: float, exemplar: str = "", **labels):
        """``exemplar``: trace id of this observation (usually
        ``tracing.current_trace_id()``); kept only while it is the
        worst seen for its label set."""
        key = tuple(sorted(labels.items()))
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1
            if exemplar:
                worst = self._exemplars.get(key)
                if worst is None or value > worst[0]:
                    self._exemplars[key] = (value, exemplar)

    def count(self, **labels) -> int:
        return self._totals.get(tuple(sorted(labels.items())), 0)

    def exemplar(self, **labels):
        """(worst_value, trace_id) for one label set, or None."""
        return self._exemplars.get(tuple(sorted(labels.items())))

    def exemplars(self) -> dict:
        with self._lock:
            return {
                _fmt_labels(dict(key)) or "{}":
                    {"value": v, "trace_id": t}
                for key, (v, t) in sorted(self._exemplars.items())
            }

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        for key in sorted(self._counts):
            labels = dict(key)
            for i, ub in enumerate(self.buckets):
                lb = dict(labels)
                lb["le"] = repr(ub)
                out.append(
                    f"{self.name}_bucket{_fmt_labels(lb)} "
                    f"{self._counts[key][i]}")
            lb = dict(labels)
            lb["le"] = "+Inf"
            out.append(f"{self.name}_bucket{_fmt_labels(lb)} "
                       f"{self._totals[key]}")
            out.append(f"{self.name}_sum{_fmt_labels(labels)} "
                       f"{self._sums[key]}")
            out.append(f"{self.name}_count{_fmt_labels(labels)} "
                       f"{self._totals[key]}")
        return out


class Registry:
    def __init__(self):
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(name, lambda: Counter(name, help_), Counter)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help_), Gauge)

    def histogram(self, name: str, help_: str = "",
                  buckets=_DEFAULT_BUCKETS) -> Histogram:
        return self._get(
            name, lambda: Histogram(name, help_, buckets), Histogram)

    def _get(self, name, factory, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}")
            return m

    def render_text(self) -> str:
        lines: list[str] = []
        for name in sorted(self._metrics):
            lines.extend(self._metrics[name].render())
        return "\n".join(lines) + "\n"

    def exemplars(self) -> dict:
        """Worst-observation exemplars of every histogram that recorded
        any: {metric: {label_set: {value, trace_id}}} — served on the
        debug plane so an operator can jump from a bad percentile to
        the exact trace that produced it."""
        out = {}
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if isinstance(m, Histogram):
                ex = m.exemplars()
                if ex:
                    out[name] = ex
        return out


# the process-wide registry (reference: prometheus default registerer)
REGISTRY = Registry()

# core instruments (reference monitoring/prometheus.go names, snake-cased)
BATCH_DURATION = REGISTRY.histogram(
    "weaviate_tpu_batch_durations_seconds", "batch import latency")
QUERY_DURATION = REGISTRY.histogram(
    "weaviate_tpu_query_durations_seconds", "query latency by type")
OBJECT_COUNT = REGISTRY.gauge(
    "weaviate_tpu_object_count", "live objects per collection/shard")
QUERIES_TOTAL = REGISTRY.counter(
    "weaviate_tpu_queries_total", "queries served by type")
VECTOR_INDEX_SIZE = REGISTRY.gauge(
    "weaviate_tpu_vector_index_size", "vectors per collection/shard")
ASYNC_QUEUE_SIZE = REGISTRY.gauge(
    "weaviate_tpu_vector_index_queue_size", "pending async-index vectors")
NATIVE_LIBRARY = REGISTRY.gauge(
    "weaviate_tpu_native_library",
    "1 per loaded native component by implementation in use: "
    "impl=native (C++ built on this machine) or impl=python (its twin)")
DIMENSIONS_SUM = REGISTRY.gauge(
    "weaviate_tpu_vector_dimensions_sum",
    "stored vector dimensions per collection (count x dims)")

# cluster RPC resilience instruments (retry/deadline/breaker + repair paths;
# every chaos-injected fault and every policy reaction is observable here)
RPC_RETRIES = REGISTRY.counter(
    "weaviate_tpu_rpc_retries_total",
    "transport-level retries by peer and message type")
RPC_FAILURES = REGISTRY.counter(
    "weaviate_tpu_rpc_failures_total",
    "RPC attempts that exhausted retries, by peer and failure kind")
RPC_DURATION = REGISTRY.histogram(
    "weaviate_tpu_rpc_durations_seconds",
    "cluster RPC latency by message type (includes retries/backoff)")
BREAKER_TRANSITIONS = REGISTRY.counter(
    "weaviate_tpu_breaker_transitions_total",
    "circuit-breaker state transitions by peer and target state")
DEADLINE_EXPIRED = REGISTRY.counter(
    "weaviate_tpu_deadline_expired_total",
    "operations that spent their deadline budget, by operation")
REPLICA_REPAIRS = REGISTRY.counter(
    "weaviate_tpu_replica_repairs_total",
    "objects repaired onto stale replicas, by path "
    "(read_repair/anti_entropy)")
STAGING_ABORTED = REGISTRY.counter(
    "weaviate_tpu_staging_aborted_total",
    "orphaned 2PC staging entries swept, by reason (ttl/abort)")
CHAOS_FAULTS = REGISTRY.counter(
    "weaviate_tpu_chaos_faults_total",
    "faults fired by ChaosTransport, by kind and link")

# serving QoS instruments (serving/qos.py admission controller + the
# deadline-aware coalescing dispatcher): the overload story is observable
# end to end — what was admitted, what was shed and why, how long admitted
# work queued, and what the adaptive limiter currently allows
QOS_ADMITTED = REGISTRY.counter(
    "weaviate_tpu_qos_admitted_total",
    "requests admitted past the QoS controller, by lane")
QOS_SHED = REGISTRY.counter(
    "weaviate_tpu_qos_shed_total",
    "requests rejected by the QoS controller, by lane and reason "
    "(queue_full/tenant_rate)")
QOS_EXPIRED = REGISTRY.counter(
    "weaviate_tpu_qos_expired_total",
    "requests whose deadline expired at admission or while queued, by lane")
QOS_QUEUE_DEPTH = REGISTRY.gauge(
    "weaviate_tpu_qos_queue_depth",
    "requests currently waiting in the admission queue, by lane")
QOS_QUEUE_WAIT = REGISTRY.histogram(
    "weaviate_tpu_qos_queue_wait_seconds",
    "time admitted requests spent queued before execution, by lane")
QOS_LIMIT = REGISTRY.gauge(
    "weaviate_tpu_qos_limit",
    "current AIMD concurrency ceiling of the admission controller")
QOS_INFLIGHT = REGISTRY.gauge(
    "weaviate_tpu_qos_inflight",
    "requests currently executing under the admission controller")
QOS_TENANT_THROTTLED = REGISTRY.counter(
    "weaviate_tpu_qos_tenant_throttled_total",
    "requests rejected by the per-tenant token bucket, by tenant")
DISPATCH_EXPIRED = REGISTRY.counter(
    "weaviate_tpu_dispatch_expired_total",
    "queued searches shed by the coalescing dispatcher because their "
    "deadline expired before device execution")
DISPATCH_DEVICE_ROWS = REGISTRY.counter(
    "weaviate_tpu_dispatch_device_rows_total",
    "query rows the coalescing dispatcher actually sent to device "
    "batches (expired rows never count here)")
DISPATCH_FILTERED_PLANE = REGISTRY.counter(
    "weaviate_tpu_dispatch_filtered_plane_total",
    "filtered device batches whose allow mask was a resident filter "
    "plane — coalesced by (plane_id, version), no mask digesting")
DISPATCH_FILTERED_DIGEST = REGISTRY.counter(
    "weaviate_tpu_dispatch_filtered_digest_total",
    "filtered device batches carrying an ad-hoc allow mask, coalesced "
    "by content digest + exact compare (the fallback when no resident "
    "plane serves the filter)")
DISPATCH_FILTERED_STACKED = REGISTRY.counter(
    "weaviate_tpu_dispatch_filtered_stacked_total",
    "filtered device batches whose members carried DIFFERENT allow masks "
    "and shared one scan, a mask a query row (a runner that declares "
    "per_row_masks: the flat scan); such a batch counts here and in "
    "neither of the two above")
PLANNER_PLANS = REGISTRY.counter(
    "weaviate_tpu_planner_plans_total",
    "filtered-search plans chosen by the cost-based query planner, by "
    "plan type (exact_scan / filtered_beam / overfetch_postfilter)")
FILTER_PLANE_HBM_BYTES = REGISTRY.gauge(
    "weaviate_tpu_filter_plane_hbm_bytes",
    "HBM bytes held by resident filter-plane device mirrors, by shard "
    "(charged inside the shard's tiering-ledger footprint)")
MULTITARGET_REQUESTS = REGISTRY.counter(
    "weaviate_tpu_multitarget_requests_total",
    "multi-target (named-vector) searches served, by join mode "
    "(weighted/minimum/relative); the fused path serves a whole "
    "request as ONE device dispatch (docs/multitarget.md)")
MULTITARGET_FALLBACK = REGISTRY.counter(
    "weaviate_tpu_multitarget_fallback_total",
    "multi-target searches that fell back to the host per-target "
    "walk+join oracle, by mode (transient/latched/ineligible); latched "
    "means the fused multi-target program is disabled for that "
    "target set until restart")
TARGET_PLANE_HBM_BYTES = REGISTRY.gauge(
    "weaviate_tpu_target_plane_hbm_bytes",
    "HBM bytes held per named-vector target plane, by shard and "
    "target (each target's corpus/code plane + topology mirror pays "
    "tiering-ledger rent independently)")
DEVICE_BEAM_FALLBACK = REGISTRY.counter(
    "weaviate_tpu_device_beam_fallback_total",
    "fused device-beam walks that fell back to the host per-hop path, "
    "by kind (search/construction) and mode (transient/latched); a "
    "latched fallback permanently downgrades the index to host walks")

# device rerank module tier (modules/device/ + the fused rerank stage in
# ops/device_beam.py): every rerank stage is attributed to its module and
# tier, fallbacks latch LOUDLY, and the candidate pool sizes the fused
# stage actually scored are observable per module
RERANK_REQUESTS = REGISTRY.counter(
    "weaviate_tpu_rerank_requests_total",
    "rerank stages executed, by module and tier (fused = scored inside "
    "the one-dispatch search program, host = the explicit fallback / "
    "host-module tier)")
RERANK_FALLBACK = REGISTRY.counter(
    "weaviate_tpu_rerank_fallback_total",
    "rerank requests that could not ride the fused device stage, by "
    "module and reason (warm_tier/flat_triage/host_walk/mesh_legacy/"
    "fused_error); each also lands a rerank.fallback span event — the "
    "fallback tier is never silent")
RERANK_CANDIDATES = REGISTRY.histogram(
    "weaviate_tpu_rerank_candidates",
    "candidate rows scored per reranked device batch (batch rows x "
    "fused pool width), by module",
    buckets=(8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 16384))

# hybrid search instruments (core/collection.py hybrid_search +
# query/fusion.py + ops/{fusion,sparse}.py): request mix by fusion
# algorithm, per-leg latency (the overlap story: hybrid wall time should
# track max(leg), not sum), legs shed at the deadline, and every drop out
# of the device fusion/sparse tiers — the fallback is never silent
HYBRID_REQUESTS = REGISTRY.counter(
    "weaviate_tpu_hybrid_requests_total",
    "hybrid searches served, by fusion algorithm (rankedFusion/"
    "relativeScoreFusion)")
HYBRID_LEG_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_hybrid_leg_seconds",
    "wall time of one hybrid leg, by leg (sparse = BM25, dense = vector) "
    "— the legs run CONCURRENTLY, so request wall time should track the "
    "max, not the sum")
HYBRID_LEG_SHED = REGISTRY.counter(
    "weaviate_tpu_hybrid_leg_shed_total",
    "hybrid legs abandoned at the request deadline while the other leg's "
    "results still fused, by leg")
HYBRID_FALLBACK = REGISTRY.counter(
    "weaviate_tpu_hybrid_fallback_total",
    "hybrid stages that fell off the device tier onto the host twin, by "
    "stage (fuse = query/fusion.py dict merge, sparse = WAND/host "
    "keyword scoring) and reason (disabled/device_error/unsupported); "
    "each also lands a span event — the fallback tier is never silent")

# mesh-sharded device beam instruments (ops/device_beam.py mesh kernel +
# parallel/): shard skew and accidental per-shard dispatch regressions are
# alertable — one logical index across all chips must stay ONE dispatch
MESH_SHARDS = REGISTRY.gauge(
    "weaviate_tpu_mesh_shards",
    "devices in the active shard mesh the fused beam spans (0 = mesh off)")
MESH_SHARD_ROWS = REGISTRY.gauge(
    "weaviate_tpu_mesh_shard_rows",
    "live graph rows resident on each mesh shard, by shard index — the "
    "per-shard row-count feed for skew alerts")
MESH_SHARD_IMBALANCE = REGISTRY.gauge(
    "weaviate_tpu_mesh_shard_imbalance",
    "max/mean ratio of live rows across populated mesh shards (1.0 = "
    "perfectly balanced; alert when skew concentrates the walk on one chip)")
MESH_BEAM_DISPATCH = REGISTRY.counter(
    "weaviate_tpu_mesh_beam_dispatch_total",
    "fused mesh-beam SPMD programs dispatched, by mode "
    "(search/construction); a full-mesh batch is exactly ONE dispatch — a "
    "rate jump relative to query batches means a per-shard dispatch "
    "regression")


def set_mesh_shard_gauges(counts) -> None:
    """Feed the mesh skew gauges from per-shard live-row counts — the ONE
    owner of the imbalance definition (max/mean over populated shards),
    shared by the beam mirror sync and flat-index stats."""
    import numpy as np

    counts = np.asarray(counts)
    MESH_SHARDS.set(len(counts))
    for s, c in enumerate(counts):
        MESH_SHARD_ROWS.set(float(c), shard=str(s))
    populated = counts[counts > 0]
    if len(populated):
        MESH_SHARD_IMBALANCE.set(float(populated.max() / populated.mean()))

# tiered tenant store instruments (tiering/): residency bytes per tier,
# every promotion/demotion the controller performs, cold-start behavior
# observable end to end (first-touch hits, promotion latency, and the
# 503-with-Retry-After sheds when a promotion outlives the deadline)
TIER_BYTES = REGISTRY.gauge(
    "weaviate_tpu_tier_bytes",
    "tenant-store residency bytes by tier (hbm/host/disk); hbm is the "
    "accountant ledger the budget is enforced against")
TIER_BUDGET_BYTES = REGISTRY.gauge(
    "weaviate_tpu_tier_budget_bytes",
    "configured HBM byte budget the tiering controller demotes against "
    "(0 = unlimited)")
TIER_PROMOTIONS = REGISTRY.counter(
    "weaviate_tpu_tier_promotions_total",
    "tenant promotions by source tier (warm: device re-attach; cold: "
    "shard open + replay + attach)")
TIER_DEMOTIONS = REGISTRY.counter(
    "weaviate_tpu_tier_demotions_total",
    "tenant demotions by destination tier (warm: arrays to host RAM; "
    "cold: shard closed to disk)")
TIER_COLD_HITS = REGISTRY.counter(
    "weaviate_tpu_tier_cold_hits_total",
    "requests that touched a non-hot tenant and had to wait on (or "
    "trigger) a promotion, by tier the tenant was found in")
TIER_PROMOTION_LATENCY = REGISTRY.histogram(
    "weaviate_tpu_tier_promotion_seconds",
    "wall time of one tenant promotion, by source tier (cold includes "
    "shard open + checkpoint replay)")
TIER_COLD_SHED = REGISTRY.counter(
    "weaviate_tpu_tier_cold_shed_total",
    "requests shed with 503 + Retry-After because a promotion was still "
    "in flight when the request deadline expired")
TIER_SEARCHES = REGISTRY.counter(
    "weaviate_tpu_tier_searches_total",
    "vector searches served by residency tier (device = HBM-resident "
    "arrays, host = the instrumented warm-tier exact fallback)")

# bottomless cold tier + cluster backup instruments (tiering/coldstore.py,
# backup/cluster_backup.py): every offload/hydrate/backup/restore leg and
# the retention sweep observable — the DR story's dashboards
OFFLOAD_TENANTS = REGISTRY.counter(
    "weaviate_tpu_offload_tenants_total",
    "wholesale tenant offloads to the blob tier, by outcome "
    "(ok/failed; failed leaves the local copy intact)")
OFFLOAD_BYTES = REGISTRY.counter(
    "weaviate_tpu_offload_bytes_total",
    "bytes uploaded to the blob tier by tenant offload (segments + WAL "
    "checkpoint + manifest)")
OFFLOAD_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_offload_seconds",
    "wall time of one tenant offload (upload + verify + local delete)")
HYDRATE_TENANTS = REGISTRY.counter(
    "weaviate_tpu_hydrate_tenants_total",
    "first-touch tenant hydrations from the blob tier, by outcome "
    "(ok/failed/corrupt; corrupt = digest mismatch, nothing installed)")
HYDRATE_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_hydrate_seconds",
    "wall time of one tenant hydration (download + verify + install), "
    "the cold-start tax the promotion deadline sheds against")
BACKUP_RUNS = REGISTRY.counter(
    "weaviate_tpu_backup_runs_total",
    "cluster backup runs, by terminal status (success/failed)")
BACKUP_BYTES = REGISTRY.counter(
    "weaviate_tpu_backup_bytes_total",
    "bytes uploaded by cluster backups (fenced segment sets + manifests)")
RESTORE_RUNS = REGISTRY.counter(
    "weaviate_tpu_restore_runs_total",
    "cluster restore runs, by terminal status (success/failed)")
RETENTION_DELETED = REGISTRY.counter(
    "weaviate_tpu_retention_deleted_total",
    "blobs deleted by the retention sweep, by reason (stale_generation/"
    "partial_offload/partial_backup/unreferenced)")

# end-to-end tracing instruments (monitoring/tracing.py + the coalescing
# dispatcher's batch spans): the dispatcher's queue-wait/service split is
# measurable even when sampling is off, and both histograms carry the
# trace-id exemplar of their worst observation
DISPATCH_QUEUE_WAIT = REGISTRY.histogram(
    "weaviate_tpu_dispatch_queue_wait_seconds",
    "time a coalesced search waited between enqueue and its device "
    "batch draining (per batch: the longest wait in the group)")
DISPATCH_BATCH_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_dispatch_batch_seconds",
    "service time of one coalesced device batch (dispatch through "
    "result materialization), as timed by the dispatcher leader")
DEVICE_TIME_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_device_time_seconds",
    "device-time attribution of fused beam dispatches by phase "
    "(compile = true XLA compile, cache_hit = persistent-cache disk "
    "deserialize, execute = steady state), backend, scorer and "
    "mesh mode — timed against the walk's existing result "
    "materialization, zero extra host syncs")
TRACE_SPANS = REGISTRY.counter(
    "weaviate_tpu_trace_spans_total",
    "sampled spans recorded into the bounded trace buffer, by span name")

# the interpreter's own readings (monitoring/interp.py, started by
# server.main): how long a thread that wants the interpreter lock waits for
# it, and what the collector costs (the upstream's go_gc_duration_seconds)
INTERPRETER_WAKE = REGISTRY.histogram(
    "weaviate_tpu_interpreter_wake_seconds",
    "overshoot of a 10 ms sleep on the sampler's thread: the timer's "
    "slack plus the wait for the interpreter lock that every thread "
    "pays when it comes back from a blocking call",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0))
GC_PAUSE_SECONDS = REGISTRY.counter(
    "weaviate_tpu_gc_pause_seconds_total",
    "time inside Python's cyclic collector, interpreter lock held, by "
    "generation")
GC_COLLECTIONS = REGISTRY.counter(
    "weaviate_tpu_gc_collections_total",
    "collections Python's cyclic collector ran, by generation")

# elastic scale-out instruments (cluster/rebalance.py + gossip capacity
# advertisement): every shard migration's outcome and duration, the
# in-flight count, the per-node HBM capacity view the planner places
# against, and the orphan-copy GC that reaps what failed drops leave
REBALANCE_MOVES = REGISTRY.counter(
    "weaviate_tpu_rebalance_moves_total",
    "shard migrations driven through the rebalance ledger, by outcome "
    "(completed/resumed/aborted)")
REBALANCE_MOVE_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_rebalance_move_seconds",
    "wall time of one ledger-journaled shard migration (copy through "
    "drop), by outcome",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
REBALANCE_ACTIVE = REGISTRY.gauge(
    "weaviate_tpu_rebalance_active_moves",
    "shard migrations currently executing on this coordinator")
ORPHAN_SHARDS_DROPPED = REGISTRY.counter(
    "weaviate_tpu_orphan_shards_dropped_total",
    "local shard copies absent from routing that the periodic GC dropped "
    "after an anti-entropy verify, by collection")
NODE_HBM_BUDGET = REGISTRY.gauge(
    "weaviate_tpu_node_hbm_budget_bytes",
    "per-node HBM byte budget advertised via gossip (0 = unbudgeted), "
    "by node — the capacity axis the rebalance planner places against")
NODE_HBM_USED = REGISTRY.gauge(
    "weaviate_tpu_node_hbm_used_bytes",
    "per-node HBM bytes in use as advertised via gossip (the tiering "
    "accountant ledger total), by node")

# closed-loop autoscaler instruments (cluster/autoscale.py): every
# journaled decision by direction, how close the hysteresis is to
# firing, and how long until the post-actuation cooldown releases —
# together they answer "why did/didn't the cluster just scale"
AUTOSCALE_DECISIONS = REGISTRY.counter(
    "weaviate_tpu_autoscale_decisions_total",
    "raft-journaled autoscale decisions by direction (out/in) — counted "
    "at journal time, before actuation, so an aborted scale still shows")
AUTOSCALE_BREACH_TICKS = REGISTRY.gauge(
    "weaviate_tpu_autoscale_breach_ticks",
    "consecutive evaluation ticks the pressure signal has breached in "
    "the current direction; the loop acts only at the hysteresis "
    "threshold, so this is the fuse burning down")
AUTOSCALE_COOLDOWN_REMAINING = REGISTRY.gauge(
    "weaviate_tpu_autoscale_cooldown_remaining_s",
    "seconds until the post-actuation cooldown window releases and the "
    "loop may decide again (0 = armed)")

# streaming ingest pipeline instruments (core/async_queue.py drain stage +
# storage debt-driven compaction + index/dynamic.py background cutover,
# docs/ingest.md): the WAL→device window depth, how long each drain window
# takes, the merge debt the compactor is scheduled against (also the
# backpressure signal the QoS ingest lane sheds on), and the wall time of
# a background flat→HNSW cutover
INGEST_QUEUE_DEPTH = REGISTRY.gauge(
    "weaviate_tpu_ingest_queue_depth",
    "vectors waiting in the WAL->device ingest window, by shard "
    "(delta-logged and acked; the device feed still owes them) — the "
    "same unit the ingest_shed_queue_depth backpressure knob sheds "
    "against, so the gauge IS the signal to tune that knob by")
INGEST_DRAIN_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_ingest_drain_seconds",
    "wall time of one ingest drain window (chunk-file read through the "
    "last pow2-bucketed device feed of the window)")
COMPACTION_DEBT_BYTES = REGISTRY.gauge(
    "weaviate_tpu_compaction_debt_bytes",
    "outstanding segment-merge debt across all open shards (sum over "
    "buckets of (segment_count - 1) x overlap bytes) — the score the "
    "debt-driven compaction scheduler ranks by and the QoS ingest lane "
    "sheds against")
INDEX_CUTOVER_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_index_cutover_seconds",
    "wall time of one background flat->HNSW dynamic-index cutover "
    "(snapshot build + delta replay + atomic swap), by outcome "
    "(completed/cancelled/failed)",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
             300.0))

# persistent compilation cache + shape-bucket prewarming instruments
# (utils/compile_cache.py + utils/prewarm.py): whether a restarted node
# deserialized its programs off disk instead of recompiling, and how much
# of the bucket lattice the prewarm driver covered before traffic arrived
COMPILE_CACHE_EVENTS = REGISTRY.counter(
    "weaviate_tpu_compile_cache_events_total",
    "persistent-compilation-cache traffic by event (hit = executable "
    "deserialized from disk, miss = true XLA compile that was then "
    "written back)")
COMPILE_CACHE_BYTES = REGISTRY.gauge(
    "weaviate_tpu_compile_cache_bytes",
    "on-disk size of this node's keyed persistent compilation cache "
    "directory (refreshed on /v1/debug/compile reads)")
PREWARM_PROGRAMS = REGISTRY.counter(
    "weaviate_tpu_prewarm_programs_total",
    "shape-bucket prewarm dispatches by outcome (warmed/failed/skipped) "
    "— one per (shard, target, pow2 row bucket) lattice point the "
    "driver compiled off the request path")
PREWARM_SECONDS = REGISTRY.histogram(
    "weaviate_tpu_prewarm_seconds",
    "wall time of one prewarm run (every lattice point of one trigger: "
    "boot, tenant promotion, or rebalance warming leg), by reason",
    buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
