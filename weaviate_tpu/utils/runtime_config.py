"""Runtime-mutable configuration with file-based hot reload.

Reference: ``usecases/config/runtime`` — ``DynamicValue[T]`` wraps a knob
that an operator can override at runtime via a YAML file named by
``RUNTIME_OVERRIDES_PATH``, polled every ``RUNTIME_OVERRIDES_LOAD_INTERVAL``;
consumers call ``.Get()`` on every use so changes land without restart.
Same contract here with a JSON overrides file (the image has no yaml lib):

    registry = RuntimeConfig(path="overrides.json", interval_s=5)
    ef = registry.register("query_defaults_ef", 64)   # DynamicValue
    ...
    ef.get()   # current value, overridden or default

Unknown keys in the file are reported, not fatal; a malformed file keeps
the previous values (reference behavior: refuse to crash the server over
an operator typo).
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Any, Callable, Generic, Optional, TypeVar

logger = logging.getLogger("weaviate_tpu.runtime_config")

T = TypeVar("T")


class DynamicValue(Generic[T]):
    """A named knob: default + optional runtime override."""

    __slots__ = ("name", "_default", "_override", "_cast")

    def __init__(self, name: str, default: T,
                 cast: Optional[Callable[[Any], T]] = None):
        self.name = name
        self._default = default
        self._override: Optional[T] = None
        self._cast = cast

    def get(self) -> T:
        ov = self._override
        return self._default if ov is None else ov

    def set_override(self, value: Any) -> None:
        if self._cast is not None:
            value = self._cast(value)
        elif self._default is not None:
            value = type(self._default)(value)
        self._override = value

    def clear_override(self) -> None:
        self._override = None

    @property
    def overridden(self) -> bool:
        return self._override is not None


class RuntimeConfig:
    def __init__(self, path: Optional[str] = None,
                 interval_s: float = 5.0):
        self.path = path or os.environ.get("RUNTIME_OVERRIDES_PATH", "")
        self.interval_s = float(os.environ.get(
            "RUNTIME_OVERRIDES_LOAD_INTERVAL", interval_s))
        self._values: dict[str, DynamicValue] = {}
        self._lock = threading.Lock()
        self._mtime: Optional[float] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def register(self, name: str, default: T,
                 cast: Optional[Callable[[Any], T]] = None) -> DynamicValue[T]:
        with self._lock:
            dv = self._values.get(name)
            if dv is None:
                dv = DynamicValue(name, default, cast)
                self._values[name] = dv
            return dv

    def get(self, name: str, default: Any = None) -> Any:
        dv = self._values.get(name)
        return dv.get() if dv is not None else default

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                n: {"value": dv.get(), "overridden": dv.overridden}
                for n, dv in sorted(self._values.items())
            }

    # -- file reload -------------------------------------------------------
    def load_file(self) -> bool:
        """Apply the overrides file; returns True when values changed."""
        if not self.path or not os.path.exists(self.path):
            return False
        try:
            mtime = os.path.getmtime(self.path)
            if mtime == self._mtime:
                return False
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError("overrides file must be a JSON object")
        except (OSError, ValueError) as e:
            # operator typo must not take the server down — keep old values
            logger.warning("runtime overrides not applied: %s", e)
            return False
        self._mtime = mtime
        with self._lock:
            seen = set()
            for name, value in data.items():
                dv = self._values.get(name)
                if dv is None:
                    logger.warning("unknown runtime override %r", name)
                    continue
                try:
                    dv.set_override(value)
                    seen.add(name)
                except (TypeError, ValueError) as e:
                    logger.warning("override %r rejected: %s", name, e)
            # keys removed from the file fall back to defaults
            for name, dv in self._values.items():
                if name not in seen and dv.overridden:
                    dv.clear_override()
        return True

    def start(self) -> None:
        if self.path:
            self.load_file()
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.load_file()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.ident is not None:
            self._thread.join(timeout=2)


# process-wide registry; servers start() it when RUNTIME_OVERRIDES_PATH is set
RUNTIME = RuntimeConfig()

# knobs consumed across the codebase (registered here so the overrides file
# has a stable catalogue; callers may register more)
SLOW_QUERY_THRESHOLD_S = RUNTIME.register("slow_query_threshold_s", 0.5,
                                          cast=float)
FLAT_APPROX_RECALL_DEFAULT = RUNTIME.register("flat_approx_recall_default",
                                              0.0, cast=float)
MAINTENANCE_PAUSED = RUNTIME.register("maintenance_paused", False,
                                      cast=bool)
# byte budget of the segmented index's native WAND term cache; -1 = unset
# (follow the WEAVIATE_TPU_WAND_CACHE_MB env / built-in 64 MB default)
WAND_CACHE_MB = RUNTIME.register("wand_cache_mb", -1.0, cast=float)
# serving QoS layer (serving/qos.py): "off" bypasses admission control,
# deadlines, and shedding entirely — the pre-QoS front door
SERVING_QOS = RUNTIME.register("serving_qos", "on", cast=str)
# default end-to-end request budget when the client sends none (REST
# X-Request-Timeout header / gRPC context deadline override it per call)
SERVING_DEFAULT_TIMEOUT_S = RUNTIME.register(
    "serving_default_timeout_s", 30.0, cast=float)
# per-connection socket read timeout of the bounded REST server (a slow
# client is disconnected instead of pinning a handler thread)
SERVING_REST_READ_TIMEOUT_S = RUNTIME.register(
    "serving_rest_read_timeout_s", 30.0, cast=float)
# end-to-end tracing (monitoring/tracing.py): per-TRACE sampling rate
# decided at the ingress root (children inherit the verdict). 1.0 traces
# everything (the default: the span buffer is bounded and spans are
# cheap), 0.0 disables span creation on the request path entirely —
# hot-reloadable so an operator can flip tracing on during an incident
# without a restart.
TRACING_SAMPLE_RATE = RUNTIME.register(
    "tracing_sample_rate", 1.0, cast=float)
# tiered tenant store (tiering/): HBM byte budget the controller demotes
# against; 0 = unset (follow the WEAVIATE_TPU_HBM_BUDGET_BYTES env / the
# DB constructor argument). Hot-reloadable so an operator can shrink the
# budget on a live node and watch the eviction pass drain HBM.
TIERING_HBM_BUDGET = RUNTIME.register(
    "tiering_hbm_budget_bytes", 0, cast=int)
# persistent compilation cache (utils/compile_cache.py): base directory
# for the node-local keyed cache; "" = disabled unless the
# WEAVIATE_TPU_COMPILE_CACHE_DIR env or an explicit configure() call
# names one. The server's composition root defaults it to
# <checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR, where set, wins.
COMPILE_CACHE_DIR = RUNTIME.register("compile_cache_dir", "", cast=str)
# shape-bucket prewarm driver (utils/prewarm.py): the pow2 row buckets
# compiled per (shard, target vector) at boot / tenant promotion /
# rebalance warming, and how many lattice points compile concurrently
PREWARM_BUCKETS = RUNTIME.register("prewarm_buckets", "8,16,32,64",
                                   cast=str)
PREWARM_CONCURRENCY = RUNTIME.register("prewarm_concurrency", 2, cast=int)
# 2PC finish-leg budget (cluster/node.py FINISH_BUDGET): deliberately
# generous while first-touch apply could cold-compile; with the
# persistent cache + prewarm in place an operator can tighten it — the
# workaround is a knob now, not a constant
CLUSTER_FINISH_BUDGET_S = RUNTIME.register(
    "cluster_finish_budget_s", 10.0, cast=float)
# streaming ingest pipeline (docs/ingest.md): backpressure thresholds the
# QoS ingest (batch) lane sheds against — pending vectors in the
# WAL->device window across open shards, and outstanding compaction debt.
# 0 disables that signal. Hot-reloadable: an operator can tighten them on
# a node whose WAL is outgrowing its drain rate.
INGEST_SHED_QUEUE_DEPTH = RUNTIME.register(
    "ingest_shed_queue_depth", 500_000, cast=int)
INGEST_SHED_DEBT_BYTES = RUNTIME.register(
    "ingest_shed_debt_bytes", 4 << 30, cast=int)
# debt-driven compaction scheduler (core/db.py): merge debt (bytes) past
# which the compaction cycle runs ahead of its interval backstop, and how
# many bucket merges may run concurrently per pass (native merges are
# CPU+IO bound; the cap keeps them from starving the serving threads)
COMPACTION_DEBT_TARGET_BYTES = RUNTIME.register(
    "compaction_debt_target_bytes", 64 << 20, cast=int)
COMPACTION_MAX_MERGES = RUNTIME.register(
    "compaction_max_merges", 2, cast=int)
# hybrid search (core/collection.py hybrid_search, docs/hybrid.md): each
# leg over-fetches ceil(factor * k) candidates so fusion has room beyond
# the final page — the reference fetches ~2x k per leg; the old
# hardcoded max(k, 20) silently degraded fusion quality past k≈20.
HYBRID_OVERFETCH_FACTOR = RUNTIME.register(
    "hybrid_overfetch_factor", 2.0, cast=float)
# device fusion tier (ops/fusion.py): "off" pins fusion to the host
# python twin (query/fusion.py) — the A/B lever for bench + incident
# bypass; fallbacks latch in weaviate_tpu_hybrid_fallback_total either way
HYBRID_DEVICE_FUSION = RUNTIME.register(
    "hybrid_device_fusion", "on", cast=str)
# segmented sparse scoring (ops/sparse.py): "auto" scores FILTERED hybrid
# keyword legs on device (where WAND's skipping advantage collapses),
# "on" forces every hybrid keyword leg through it, "off" keeps all
# keyword scoring on the WAND/host tier
HYBRID_SPARSE_DEVICE = RUNTIME.register(
    "hybrid_sparse_device", "auto", cast=str)
# closed-loop autoscaler (cluster/autoscale.py): the loop ships DISABLED
# — an operator (or the acceptance harness) arms it explicitly, and can
# disarm it mid-incident with one overrides-file edit while join/drain
# stay available by hand. Target p99 is the cluster-wide SLO the leader
# compares the worst advertised p99 EWMA against; cooldown is the
# mandatory quiet window after any actuation; min/max bound membership
# (scale-in additionally refuses to drop below any collection's
# replication factor).
AUTOSCALE_ENABLED = RUNTIME.register("autoscale_enabled", False,
                                     cast=bool)
AUTOSCALE_P99_TARGET_MS = RUNTIME.register(
    "autoscale_p99_target_ms", 750.0, cast=float)
AUTOSCALE_COOLDOWN_S = RUNTIME.register(
    "autoscale_cooldown_s", 60.0, cast=float)
AUTOSCALE_MIN_NODES = RUNTIME.register("autoscale_min_nodes", 1,
                                       cast=int)
AUTOSCALE_MAX_NODES = RUNTIME.register("autoscale_max_nodes", 64,
                                       cast=int)
# cold-tier blob op budget (tiering/coldstore.py): per-op deadline for
# offload/hydrate/sweep blob traffic, surfaced by the errorflow lint's
# budget pass. 0 = unset (follow the TenantColdStore constructor arg) —
# hot-reloadable so an operator can stretch it while a slow object store
# recovers instead of letting hydrations die mid-download.
COLDSTORE_OP_BUDGET_S = RUNTIME.register(
    "coldstore_op_budget_s", 0.0, cast=float)

# resident filter planes (query/planner/planes.py): an ad-hoc filter seen
# this many times auto-promotes to a device-resident bitmap plane; 0
# disables auto-promotion (declared planes still build). Max bounds the
# per-shard plane count — planes pay HBM rent through the tiering ledger.
FILTER_PLANE_PROMOTE_HITS = RUNTIME.register(
    "filter_plane_promote_hits", 3, cast=int)
FILTER_PLANE_MAX = RUNTIME.register("filter_plane_max", 8, cast=int)
