"""Shape-bucket prewarm: compile the serving lattice BEFORE traffic does.

The device search path buckets batch rows and ef to powers of two
(``index/hnsw/hnsw.py``), so a collection's serving surface is a small
LATTICE of program identities: (scorer x mesh-mode x dim x pow2 bucket).
This driver walks that lattice off the request path — synthetic queries
through each shard's REAL vector index, one per bucket — so every
program a collection's config implies is compiled (or deserialized from
the persistent cache, ``utils/compile_cache.py``) before the first user
query needs it. The measurable outcome: a restarted node whose first
device query pays zero compile seconds.

Triggers (all gated on :func:`enabled`):

- **boot** — the server's composition root prewarms every open
  collection in the background; readiness exposes a ``warming`` field so
  orchestrators can gate traffic on completion.
- **tenant promotion** — ``tiering/controller.py`` fires an async
  prewarm for the promoted tenant's shard, so tiering's cold-first-query
  SLO is compile-free.
- **rebalance warming leg** — ``cluster/rebalance.py`` asks the
  DESTINATION node to prewarm a migrating shard before the routing flip
  (``shard_prewarm`` RPC), so the first post-flip query executes.

Each lattice point runs under its own ``compile.prewarm`` trace span
with bounded concurrency (``prewarm_concurrency`` knob); outcomes land
in ``weaviate_tpu_prewarm_programs_total``.

``MANIFEST`` below is the registry of module-level jitted serving
programs this driver is responsible for. It is the source of truth the
graftlint ``unwarmed-jit-program`` rule checks ``ops/`` + ``parallel/``
entry points against: a new serving jit must either be registered here
(the driver's collection-level sweep compiles whichever of these the
index config routes through) or carry a reasoned suppression
(construction-only programs compile during builds, not serving).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Optional

logger = logging.getLogger("weaviate_tpu.prewarm")

ENV_SWITCH = "WEAVIATE_TPU_PREWARM"

# Registry of module-level jitted SERVING programs (dotted path under
# weaviate_tpu/). Checked by graftlint's unwarmed-jit-program rule; keys
# must be plain string literals (the rule reads this dict from the AST).
MANIFEST: dict[str, str] = {
    "ops.device_beam._fused_search":
        "fused greedy-descent + layer-0 beam walk, single device",
    "ops.device_beam._fused_mesh_search":
        "fused beam walk as ONE SPMD program across the shard mesh",
    "ops.device_beam._fused_multi_search":
        "fused multi-target walk + cross-scored weighted join, single "
        "device (docs/multitarget.md)",
    "ops.device_beam._fused_multi_mesh_search":
        "fused multi-target walk + join as ONE SPMD program on the mesh",
    "ops.device_beam._fused_flat_rerank":
        "fused coarse flat scan + device-module rerank (multivector "
        "MUVERA serving path, docs/modules.md)",
    "ops.distance.flat_search":
        "exact flat top-k scan (flat index + filtered-triage tier)",
    "ops.quantized.bq_search":
        "binary-quantized flat scan over packed code planes",
    "ops.quantized.sq_search":
        "scalar-quantized flat scan over SQ8 code planes",
    "ops.quantized.pq_search":
        "product-quantized flat scan via codebook LUTs",
    "ops.quantized.rq_search":
        "rotational-quantized flat scan",
    "ops.quantized.sq_gather_distance":
        "SQ candidate gather-scorer inside the fused beam / rescore",
    "ops.quantized.pq_gather_distance":
        "PQ candidate gather-scorer inside the fused beam / rescore",
    "ops.quantized.bq_gather_distance":
        "BQ candidate gather-scorer inside the fused beam / rescore",
    "ops.quantized.rq_gather_distance":
        "RQ candidate gather-scorer inside the fused beam / rescore",
    "parallel.sharded_search._sharded_flat_search_jit":
        "row-sharded exact flat scan with on-device cross-shard merge",
    "parallel.sharded_search._sharded_maxsim_jit":
        "sharded MaxSim late-interaction scorer",
    "parallel.sharded_search._sharded_gather_distance_jit":
        "sharded candidate gather-scorer (mesh rescore tier)",
    "parallel.sharded_search._sharded_take_jit":
        "sharded row gather (mesh rescore operand fetch)",
    "ops.fusion.ranked_fusion_topk":
        "hybrid reciprocal-rank fusion: per-leg scatter + fused top-k "
        "in one dispatch (docs/hybrid.md)",
    "ops.fusion.relative_score_fusion_topk":
        "hybrid min-max-normalized score fusion, one dispatch",
    "ops.sparse.sparse_score_topk":
        "segmented sparse BM25 scoring for filtered hybrid legs",
    "ops.sparse.sparse_score_topk_min_match":
        "segmented sparse BM25 with the distinct-token min-match rule",
    "parallel.sharded_search._sharded_sparse_topk_jit":
        "mesh-sharded sparse BM25: per-shard scatter-score + cross-shard "
        "top-k merge along the same axis as the dense planes",
}

_tls = threading.local()


def isolation_key() -> Optional[tuple]:
    """Non-None while the current thread is warming one lattice point.
    The HNSW search path folds it into the coalescing dispatcher's
    batch-group key, so a synthetic lattice batch can never coalesce
    with a live request (a 4-row user query dragged into a prewarm
    group would compile a 32-row bucket nobody planned) nor with a
    different bucket of a concurrent prewarm run."""
    return getattr(_tls, "token", None)


_lock = threading.Lock()
_in_flight = 0
# async runs registered BEFORE their thread starts: warming() must read
# true from the moment a trigger fires, not from when the thread gets
# scheduled — an orchestrator polling readiness right after boot would
# otherwise race through the gap
_pending = 0
_warmed: set[tuple] = set()  # (collection, shard, target, bucket)
_last_report: Optional[dict] = None


def _spawn(fn, name: str) -> None:
    global _pending
    with _lock:
        _pending += 1

    def wrapper() -> None:
        global _pending
        try:
            fn()
        finally:
            with _lock:
                _pending -= 1

    try:
        threading.Thread(target=wrapper, daemon=True, name=name).start()
    except RuntimeError:
        # can't-start-new-thread under fd/thread pressure: the pending
        # slot must not leak, or warming() reads true forever and a
        # readiness-gating orchestrator never admits this node
        with _lock:
            _pending -= 1
        logger.warning("could not start prewarm thread %s", name,
                       exc_info=True)


@dataclass
class _Spec:
    collection: str
    shard: str
    target: str
    index: object
    dims: int
    bucket: int
    k: int
    kind: str = "index"  # "index" = shard lattice; "fusion" = hybrid


# hybrid fusion programs already compiled this process, keyed on
# (algorithm, k): the kernels' identity is collection-independent
# (ops/fusion.py buckets), so one warm covers every collection
_fusion_warmed: set[tuple] = set()


@dataclass
class Report:
    reason: str
    warmed: list = field(default_factory=list)
    failed: list = field(default_factory=list)
    skipped: list = field(default_factory=list)
    seconds: float = 0.0

    def to_dict(self) -> dict:
        return {
            "reason": self.reason,
            "warmed": self.warmed,
            "failed": self.failed,
            "skipped": self.skipped,
            "seconds": round(self.seconds, 3),
            "coverage": round(
                len(self.warmed)
                / max(1, len(self.warmed) + len(self.failed)
                      + len(self.skipped)), 3),
        }


def enabled() -> bool:
    """Prewarm rides the compile-cache opt-in: on when the persistent
    cache is configured, overridable either way via the env switch.
    Unconfigured test/embedded processes pay zero extra compiles."""
    v = os.environ.get(ENV_SWITCH, "").lower()
    if v in ("off", "0", "false"):
        return False
    if v in ("on", "1", "true"):
        return True
    from weaviate_tpu.utils import compile_cache

    return compile_cache.enabled()


def buckets() -> list[int]:
    from weaviate_tpu.utils.runtime_config import PREWARM_BUCKETS

    out = []
    for part in str(PREWARM_BUCKETS.get()).split(","):
        part = part.strip()
        if not part:
            continue
        try:
            b = int(part)
        except ValueError:
            logger.warning("ignoring non-integer prewarm bucket %r", part)
            continue
        if b > 0:
            out.append(b)
    return sorted(set(out)) or [8]


def plan_for_collection(col, shards: Optional[list[str]] = None,
                        bucket_list: Optional[list[int]] = None,
                        k: int = 10,
                        skipped: Optional[list[str]] = None) -> list[_Spec]:
    """The lattice one collection's OPEN shards imply: (shard, target
    vector, pow2 row bucket). Only device-resident, populated indexes
    participate — a warm/demoted tenant serves from host and compiles
    nothing, an empty index has no programs to pin; their lattice
    points land in ``skipped`` (when given) so runs report them."""
    bucket_list = bucket_list or buckets()
    specs: list[_Spec] = []
    with col._lock:
        open_shards = dict(col._shards)
    for sname, shard in sorted(open_shards.items()):
        if shards is not None and sname not in shards:
            continue
        # snapshot under the shard lock: a concurrent first write of a
        # target vector lazily inserts into _vector_indexes, and a dict
        # mutating mid-iteration would kill the sweep thread
        with shard._lock:
            indexes = sorted(shard._vector_indexes.items())
        for target, idx in indexes:
            dims = getattr(idx, "dims", None)
            warmable = (isinstance(dims, int) and dims > 0
                        and idx.count()
                        and bool(getattr(idx, "device_resident", True)))
            # per-INDEX-OBJECT memo, not the global _warmed registry: a
            # re-promotion of the same still-open shard must not re-run
            # the lattice against live traffic (tiering thrash would
            # re-dispatch it every cycle for zero benefit), while a
            # REBUILT index (cold reopen, rebalance hydration) is a new
            # object whose programs may differ — it warms afresh
            done = getattr(idx, "_prewarmed_buckets", ())
            for b in bucket_list:
                if warmable and b not in done:
                    specs.append(_Spec(col.config.name, sname, target,
                                       idx, dims, b, k))
                elif skipped is not None:
                    skipped.append(
                        f"{col.config.name}/{sname}/{target}@{b}")
    # hybrid fusion lattice (ops/fusion.py): the fused-page program's
    # identity is (algorithm, leg bucket, union bucket, k) — derived
    # from the overfetch knob, independent of any index — so a text-
    # bearing collection warms it once per process and every hybrid
    # request (any collection) reuses the compile
    from weaviate_tpu.schema.config import DataType

    has_text = any(
        p.data_type in (DataType.TEXT, DataType.TEXT_ARRAY)
        for p in col.config.properties)
    if open_shards and has_text:
        for algo in ("rankedFusion", "relativeScoreFusion"):
            if (algo, k) not in _fusion_warmed:
                specs.append(_Spec(col.config.name, "-", algo, None, 0,
                                   0, k, kind="fusion"))
            elif skipped is not None:
                skipped.append(f"{col.config.name}/-/{algo}@0")
    return specs


def _warm_fusion(spec: _Spec) -> None:
    """Compile one hybrid-fusion program with bucket-exact synthetic
    legs: the shapes mirror exactly what a hybrid request of page size
    ``spec.k`` dispatches (two legs of ceil(overfetch·k) candidates,
    their union) — deterministic, no RNG, no index touched."""
    from weaviate_tpu.ops.fusion import bucket, fuse_topk
    from weaviate_tpu.query.fusion import hybrid_fetch

    k = spec.k
    fetch = hybrid_fetch(k)  # the SAME derivation the serving path uses
    # real legs range from fully-overlapping (union = fetch) to disjoint
    # (union = 2·fetch) — warm every distinct union bucket in that range
    # so the first hybrid request compiles nothing regardless of overlap
    for union in sorted({bucket(max(fetch, k)),
                         bucket(fetch + fetch // 2),
                         bucket(2 * fetch)}):
        legs = [list(range(fetch)),
                list(range(union - fetch, union))]
        scores = [[float(fetch - i) for i in range(fetch)] for _ in legs]
        fuse_topk(legs, scores, [0.5, 0.5], k, spec.target,
                  union_size=union)
    _fusion_warmed.add((spec.target, k))


def _warm_one(spec: _Spec, reason: str) -> None:
    import numpy as np

    from weaviate_tpu.monitoring.tracing import TRACER

    with TRACER.span("compile.prewarm", parent=None,
                     collection=spec.collection, shard=spec.shard,
                     target=spec.target, bucket=spec.bucket,
                     reason=reason) as sp:
        t0 = time.perf_counter()
        if spec.kind == "fusion":
            _warm_fusion(spec)
            sp.set(warm_ms=round((time.perf_counter() - t0) * 1000, 3))
            return
        # bucket-exact synthetic batch: the search path pads rows to the
        # same pow2 bucket a real batch of this size would land in, so
        # the program identity compiled here IS the one traffic will ask
        # for. Deterministic queries — prewarm must never depend on RNG.
        q = np.zeros((spec.bucket, spec.dims), np.float32)
        q[:, 0] = 1.0
        _tls.token = ("prewarm", spec.bucket)
        try:
            spec.index.search(q, spec.k)
            mod = getattr(spec.index, "_rerank_module", None)
            if mod is not None and not getattr(spec.index, "multi_vector",
                                               False):
                # the rerank variant is a DISTINCT program identity (the
                # module is a jit-static arg): warm it too, so a warmed
                # node's first reranked query is compile-free. The
                # multivector index needs no extra pass — its plain
                # search IS the fused scan+rerank program.
                from weaviate_tpu.modules.device import RerankRequest

                spec.index.search(q, spec.k, rerank=RerankRequest(mod))
        finally:
            _tls.token = None
        sp.set(warm_ms=round((time.perf_counter() - t0) * 1000, 3))


def _run(specs: list[_Spec], reason: str,
         concurrency: Optional[int] = None,
         skipped: Optional[list[str]] = None) -> Report:
    from weaviate_tpu.monitoring.metrics import (
        PREWARM_PROGRAMS,
        PREWARM_SECONDS,
    )
    from weaviate_tpu.utils.runtime_config import PREWARM_CONCURRENCY

    global _in_flight, _last_report
    if concurrency is None:
        concurrency = max(1, int(PREWARM_CONCURRENCY.get()))
    report = Report(reason=reason)
    for label in skipped or ():
        PREWARM_PROGRAMS.inc(outcome="skipped")
        report.skipped.append(label)
    t0 = time.perf_counter()
    # one sequential chain PER INDEX: the isolation token already keeps
    # lattice batches out of each other's (and live traffic's) dispatch
    # groups, so this is a load bound, not the correctness guarantee —
    # one compile per index at a time, concurrency across indexes only.
    chains: dict[int, list[_Spec]] = {}
    for s in specs:
        chains.setdefault(id(s.index), []).append(s)

    def _warm_chain(chain: list[_Spec]) -> None:
        for s in chain:
            key = (s.collection, s.shard, s.target, s.bucket)
            label = f"{s.collection}/{s.shard}/{s.target}@{s.bucket}"
            try:
                _warm_one(s, reason)
            except Exception as e:
                PREWARM_PROGRAMS.inc(outcome="failed")
                report.failed.append(label)
                logger.warning("prewarm of %s failed: %s", label, e)
                continue
            PREWARM_PROGRAMS.inc(outcome="warmed")
            report.warmed.append(label)
            if s.kind == "index":
                memo = getattr(s.index, "_prewarmed_buckets", None)
                if memo is None:
                    memo = s.index._prewarmed_buckets = set()
                memo.add(s.bucket)
            with _lock:
                _warmed.add(key)

    with _lock:
        _in_flight += 1
    try:
        if chains:
            with ThreadPoolExecutor(
                    max_workers=max(1, min(concurrency, len(chains))),
                    thread_name_prefix="prewarm") as pool:
                for fut in [pool.submit(_warm_chain, c)
                            for c in chains.values()]:
                    fut.result()
    finally:
        report.seconds = time.perf_counter() - t0
        PREWARM_SECONDS.observe(report.seconds, reason=reason)
        with _lock:
            _in_flight -= 1
            _last_report = report.to_dict()
    logger.info("prewarm (%s): %d warmed, %d failed in %.2fs", reason,
                len(report.warmed), len(report.failed), report.seconds)
    return report


def prewarm_collection(col, reason: str = "boot",
                       shards: Optional[list[str]] = None,
                       bucket_list: Optional[list[int]] = None,
                       k: int = 10, concurrency: Optional[int] = None,
                       block: bool = True,
                       force: bool = False) -> Optional[Report]:
    """Warm one collection's lattice. ``block=False`` runs on a
    background thread (boot / promotion — never on the request path) and
    returns None; readiness reports ``warming`` until it drains."""
    if not (force or enabled()):
        return None
    skipped: list[str] = []
    specs = plan_for_collection(col, shards=shards,
                                bucket_list=bucket_list, k=k,
                                skipped=skipped)
    if block:
        return _run(specs, reason, concurrency, skipped=skipped)
    _spawn(lambda: _run(specs, reason, concurrency, skipped=skipped),
           name=f"prewarm-{reason}")
    return None


def prewarm_db(db, reason: str = "boot", block: bool = False) -> None:
    """Boot-time sweep: every collection with open shards."""
    if not enabled():
        return

    def _sweep() -> None:
        for name in db.collections():
            try:
                col = db.get_collection(name)
            except KeyError:
                continue
            skipped: list[str] = []
            specs = plan_for_collection(col, skipped=skipped)
            if specs or skipped:
                _run(specs, reason, skipped=skipped)

    if block:
        _sweep()
    else:
        _spawn(_sweep, name=f"prewarm-{reason}")


def warming() -> bool:
    """True while any prewarm run is in flight — the readiness field
    orchestrators gate traffic on."""
    with _lock:
        return _in_flight > 0 or _pending > 0


def wait_idle(timeout: float = 30.0) -> bool:
    """Block until no prewarm run is in flight (tests, drain hooks)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not warming():
            return True
        time.sleep(0.02)
    return not warming()


def stats() -> dict:
    """The /v1/debug/compile prewarm panel."""
    with _lock:
        warmed = sorted(f"{c}/{s}/{t}@{b}" for c, s, t, b in _warmed)
        last = dict(_last_report) if _last_report else None
        busy = _in_flight > 0 or _pending > 0
    return {
        "enabled": enabled(),
        "warming": busy,
        "warmed_buckets": warmed,
        "last_run": last,
        "manifest": sorted(MANIFEST),
    }


def reset_for_tests() -> None:
    global _in_flight, _pending, _last_report
    with _lock:
        _warmed.clear()
        _fusion_warmed.clear()
        _last_report = None
        _in_flight = 0
        _pending = 0
