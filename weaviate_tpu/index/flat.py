"""Flat (brute-force) TPU index.

Reference: ``adapters/repos/db/vector/flat/index.go:49``. There, flat search is
the slow fallback (scan LSM bucket, per-vector SIMD distance). On TPU it is the
*primary* fast path: the whole corpus lives in HBM and a query batch is one
fused masked-matmul + top_k (see SURVEY.md §7 slice 0 and BASELINE.md SIFT1M
config).

Concurrent searches share scans: ``FlatIndex.search`` enqueues into a
``CoalescingDispatcher`` (``index/dispatch.py``) whose leader runs every
compatible pending request as ONE ``flat_search``. The scan is bound by
reading the corpus, so a batch costs about what one query costs. Filtered
requests share a scan whatever their filters: the members' allow masks go
up stacked, one row a query row, and the scan applies row i to row i.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.index.base import (
    SearchResult,
    VectorIndex,
    run_tier_stable,
)
from weaviate_tpu.index.dispatch import CoalescingDispatcher, one_mask
from weaviate_tpu.index.store import DeviceVectorStore
from weaviate_tpu.monitoring import tracing
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.ops.distance import MASK_DISTANCE, flat_search
from weaviate_tpu.ops.topk import masked_topk
from weaviate_tpu.schema.config import FlatIndexConfig
from weaviate_tpu.utils.prewarm import isolation_key


def make_flat(dims: int, config: Optional[FlatIndexConfig] = None,
              raw_path: Optional[str] = None) -> VectorIndex:
    """Flat-index factory: raw HBM corpus, or code planes + rescore tier when
    a quantizer is configured (reference ``flat/index.go:49`` + ``quantizer.go``).
    ``raw_path`` places a disk16 originals memmap per index instance without
    mutating the (possibly shared) config."""
    config = config or FlatIndexConfig()
    if config.quantizer is not None and config.quantizer.enabled:
        return QuantizedFlatIndex(dims, config, raw_path=raw_path)
    return FlatIndex(dims, config)


# Row counts a coalesced group is padded to (``flat_search`` is jitted on
# the query shape); the largest is the dispatcher's ``max_batch``. The scan
# is bound by reading the corpus, so padded rows cost selection only
# (~0.01 ms a row at 262,144 x 768; their zeros go up, and cosine are
# normalised, inside the same one launch) and every size is one more
# program to compile per (capacity, k). Why no more than 8 (chip runs, PERF.md PR 27):
# 8 rows a scan is already 3,200 vectors/s of scan capacity against the
# ~430 a second the host's interpreter lock lets through, while a batch
# releases all its replies at once: with 64, twenty closed-loop clients
# fell into ONE cohort after a stall and stayed there (server and clients
# then take turns: 315/s against 430); with 4 the single-flight leader
# path is the limit (310-331/s). A lone single-vector request runs the
# B = 1 program; a request wider than the largest bucket runs at its own
# width.
ROW_BUCKETS = (1, 4, 8)


def _bucket_rows(rows: int) -> int:
    for b in ROW_BUCKETS:
        if rows <= b:
            return b
    return rows


def resident_dtype(config: FlatIndexConfig):
    """Width of a flat collection's resident rows, from what its own
    configuration says the scan does with them: a ``bf16`` product of a
    matmul metric reads every row rounded to bfloat16 (``ops/distance.py
    _matmul``), so the rows are stored that way, rounded once when they
    are written, and the scan converts nothing. ``fp32`` takes the product
    from the float32 rows at full precision, and manhattan / hamming read
    the float32 rows themselves: those stay float32."""
    if config.precision == "bf16" \
            and config.distance in ("cosine", "dot", "l2-squared"):
        return jnp.bfloat16
    return jnp.float32


class FlatIndex(VectorIndex):
    def __init__(self, dims: int, config: Optional[FlatIndexConfig] = None,
                 float32_rows: bool = False):
        """``float32_rows`` (internal, no schema field): the owner needs the
        rows resident at full width whatever ``resident_dtype`` says."""
        from weaviate_tpu.parallel.runtime import default_mesh

        self.dims = dims
        self.config = config or FlatIndexConfig()
        self.metric = self.config.distance
        # Multi-chip: the corpus rows shard across the process mesh and
        # search runs as one SPMD program (reference scatter-gathers across
        # nodes instead, index.go:1928).
        self.store = DeviceVectorStore(
            dims,
            capacity=self.config.initial_capacity,
            dtype=(jnp.float32 if float32_rows
                   else resident_dtype(self.config)),
            normalized=(self.metric == "cosine"),
            mesh=default_mesh(),
        )
        # carried by every ``flat.dispatch`` span
        self._corpus_dtype = np.dtype(self.store.dtype).name
        # bumped on every demote/promote: the dispatcher keys batch
        # grouping on it, so a request enqueued against one residency
        # generation never rides a batch of another
        self._residency_epoch = 0
        # ``flat_search`` applies one allow mask a query row, so filtered
        # requests share a scan whatever their masks. The mesh program
        # (``mesh_flat_topk``) takes one mask a batch: over a mesh store
        # the capability is not declared and only mask-equal requests share
        # (every benchmark cell is one chip).
        self._dispatcher = CoalescingDispatcher(
            self._run_batch, max_batch=ROW_BUCKETS[-1], pass_tier_key=True,
            per_row_masks=self.store.mesh is None)
        # (capacity, k, filtered, approx_recall) whose every form is
        # compiled; written by the dispatcher's leader only (single-flight)
        self._warm_programs: set[tuple] = set()

    # -- VectorIndex ------------------------------------------------------
    def add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        self.store.put(doc_ids, vectors)

    def delete(self, doc_ids: np.ndarray) -> None:
        self.store.delete(doc_ids)

    def search(
        self,
        queries: np.ndarray,
        k: int,
        allow_list: Optional[np.ndarray] = None,
        approx_recall: Optional[float] = None,
        est_selectivity: Optional[float] = None,
    ) -> SearchResult:
        """Top-k scan. ``approx_recall`` overrides the config knob (range
        queries force 0.0: approx selection may drop in-range rows, which
        breaks the search_by_distance contract rather than trading recall).
        ``est_selectivity`` is accepted for signature parity with the
        planner-aware HNSW path and ignored — a flat scan IS the exact
        plan."""
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        if queries.shape[-1] != self.store.dims:
            # before the enqueue: a malformed request fails alone, never
            # the batch it would have joined
            raise ValueError(
                f"query dims {queries.shape[-1]} != index dims {self.store.dims}"
            )
        if approx_recall is None:
            approx_recall = self.config.flat_approx_recall
            if approx_recall < 0.0:
                # UNSET: follow the fleet-wide hot-reloadable default.
                # 0.0 means PINNED exact and never follows the override.
                from weaviate_tpu.utils.runtime_config import (
                    FLAT_APPROX_RECALL_DEFAULT,
                )

                approx_recall = FLAT_APPROX_RECALL_DEFAULT.get()
        # a tiering demote/promote between the residency check and the
        # array access (here or in the dispatcher's leader) surfaces as
        # ResidencyMoved: re-route, never fail. The retry re-enqueues
        # under the NEW residency epoch's tier_key.
        return run_tier_stable(
            lambda: self._search_tiered(queries, k, allow_list,
                                        approx_recall))

    def _search_tiered(self, queries: np.ndarray, k: int, allow_list,
                       approx_recall: float) -> SearchResult:
        if not self.store.device_resident:
            # WARM tier (tiering/): the corpus is demoted to host RAM —
            # serve exactly from there, never re-renting HBM per query
            ids, d = self._host_search(
                queries, k, None if allow_list is None else [allow_list])
            return SearchResult(ids=ids, dists=d)
        # everything that decides the compiled program or the arrays it
        # reads, beyond k and the mask (the dispatcher's own keys): the
        # residency epoch, approx_recall (a static argument of the scan;
        # range queries pin 0.0) and the prewarm isolation token
        tier_key = (self._residency_epoch, approx_recall, isolation_key())
        ids, d = self._dispatcher.search(queries, k, allow_list,
                                         tier_key=tier_key)
        return SearchResult(ids=ids, dists=d)

    def _run_batch(self, queries: np.ndarray, k: int, masks,
                   tier_key: tuple, rows: Optional[list[int]] = None):
        """Single-flight batch runner behind the coalescing dispatcher:
        one launch (the scan takes the group's queries up with it and,
        cosine, normalises them itself) and one copy-out for the whole
        group. ``masks`` is None or the members' allow masks in
        request order, ``rows`` their row counts. Returns (ids, dists) of
        the group's rows."""
        if rows is None:
            # a dispatcher without ``per_row_masks`` (a mesh store) hands
            # over the group's one mask
            rows = [queries.shape[0]]
            masks = None if masks is None else [masks]
        approx_recall = tier_key[1]
        if not self.store.device_resident:
            # a demotion landed while this group was queued: the leader
            # re-routes the whole batch to the warm host tier
            return self._host_search(queries, k, masks, rows)
        program = (self.store.capacity, k, masks is not None, approx_recall)
        if queries.shape[0] <= ROW_BUCKETS[-1] \
                and program not in self._warm_programs:
            self._warm_buckets(program)
        return self._scan(queries, k, masks, rows, approx_recall)

    def _host_search(self, queries: np.ndarray, k: int, masks,
                     rows: Optional[list[int]] = None):
        from weaviate_tpu.index.hnsw.backend import host_store_topk

        if masks is None or one_mask(masks) is not None:
            d, ids = host_store_topk(self.store, self.metric, queries, k,
                                     None if masks is None else masks[0])
            return ids, d
        # the warm tier's executor takes one mask a call: a member a call
        parts, at = [], 0
        for mask, n in zip(masks, rows):
            parts.append(host_store_topk(
                self.store, self.metric, queries[at:at + n], k, mask))
            at += n
        return (np.concatenate([ids for _, ids in parts]),
                np.concatenate([d for d, _ in parts]))

    def _warm_buckets(self, program: tuple) -> None:
        """Compile every form a later batch of one (capacity, k, filtered,
        approx_recall) can ask for at its first search — never under
        whichever later batch happens to be the first of its kind: every
        row bucket and, filtered, the stacked-mask form of the buckets a
        group of unequal masks can fill (it has at least two rows). Zero
        queries through ``_scan`` itself, so what is warmed is what a
        batch asks for: a host array as the query argument and, cosine,
        the program that normalises it."""
        capacity, k, filtered, approx_recall = program
        forms = [(b, [b]) for b in ROW_BUCKETS]
        if filtered and self._dispatcher.per_row_masks:
            forms += [(b, [1, b - 1]) for b in ROW_BUCKETS if b > 1]
        with TRACER.child("flat.warm", capacity=capacity, k=k,
                          buckets=list(ROW_BUCKETS)):
            # child spans of the synthetic scans would read as requests'
            token = tracing.detach()
            try:
                for b, rows in forms:
                    # one mask object a member: two members, two masks
                    masks = ([np.zeros(1, bool) for _ in rows]
                             if filtered else None)
                    self._scan(np.zeros((b, self.store.dims), np.float32),
                               k, masks, rows, approx_recall)
            finally:
                tracing.deactivate(token)
        # programs of a capacity the store has outgrown are never asked
        # for again
        self._warm_programs = {p for p in self._warm_programs
                               if p[0] == capacity} | {program}

    def _scan(self, queries: np.ndarray, k: int, masks, rows: list[int],
              approx_recall: float):
        n = queries.shape[0]
        padded = _bucket_rows(n)
        # all the host does to the queries: the pad to a row bucket. They
        # go up as an argument of the scan's own launch
        with TRACER.child("flat.prepare"):
            if padded != n:
                queries = np.pad(queries, ((0, padded - n), (0, 0)))
        with TRACER.child("flat.dispatch", capacity=self.store.capacity,
                         batch=padded, corpus_dtype=self._corpus_dtype):
            d, ids = self._dispatch(queries, k, masks, rows, approx_recall)
        # the wait for the device and the copy out (both arrays' copies
        # started before either is waited for); padded rows are dropped
        # before hand-back
        with TRACER.child("flat.result"):
            ids, d = jax.device_get((ids, d))
            return ids[:n], d[:n]

    def _dispatch(self, queries: np.ndarray, k: int, masks,
                  rows: list[int], approx_recall: float):
        """Start the scan of one padded float32 host query batch; returns
        the (distances, ids) device arrays without waiting for them. On
        one chip the batch makes ONE call into the runtime: the jitted
        scan uploads its own query argument and, cosine, normalises it."""
        shared = None if masks is None else one_mask(masks)
        if self.store.mesh is not None:
            from weaviate_tpu.ops.distance import normalize
            from weaviate_tpu.parallel.sharded_search import mesh_flat_topk

            qj = jnp.asarray(queries)
            if self.metric == "cosine":
                qj = normalize(qj)
            # one mask a batch: this index's dispatcher groups by mask
            # equality (see __init__), so ``shared`` is the group's mask
            return mesh_flat_topk(
                self.store, qj, k, self.metric, allow=shared,
                precision=self.config.precision,
                chunk_size=self.config.search_chunk_size,
                approx_recall=approx_recall,
            )
        # one consistent device-state snapshot (concurrent writers swap it)
        corpus, valid, sqnorms = self.store.snapshot()
        cap = corpus.shape[0]
        allow = None
        if shared is not None:
            # a lone request, or members carrying one and the same mask:
            # one bool a corpus row, padded to the capacity and uploaded
            with TRACER.child("flat.mask", bytes=cap):
                allow = _pad_mask(shared, cap)
        elif masks is not None:
            # members with different masks: one mask a query ROW, uploaded
            # once a batch; ``flat_search`` applies row i to row i
            with TRACER.child("flat.mask", bytes=queries.shape[0] * cap):
                allow = jnp.asarray(
                    _stack_masks(masks, rows, queries.shape[0], cap))
        chunk = self.config.search_chunk_size
        return flat_search(
            queries,
            corpus,
            k=k,
            metric=self.metric,
            valid_mask=valid,
            allow_mask=allow,
            corpus_sqnorms=sqnorms if self.metric == "l2-squared" else None,
            chunk_size=chunk if cap > chunk else 0,
            precision=self.config.precision,
            approx_recall=approx_recall,
            normalize_queries=self.metric == "cosine",
        )

    def search_by_distance(
        self,
        queries: np.ndarray,
        max_distance: float,
        allow_list: Optional[np.ndarray] = None,
        limit: int = 1024,
    ) -> SearchResult:
        k = min(limit, max(1, self.store.live_count))
        res = self.search(queries, k, allow_list, approx_recall=0.0)
        keep = res.dists <= max_distance
        ids = np.where(keep, res.ids, -1)
        dists = np.where(keep, res.dists, np.float32(MASK_DISTANCE))
        return SearchResult(ids=ids, dists=dists)

    def count(self) -> int:
        return self.store.live_count

    @property
    def capacity(self) -> int:
        return self.store.capacity

    def contains(self, doc_id: int) -> bool:
        return self.store.contains(doc_id)

    def save_vectors(self, path: str, meta: Optional[dict] = None) -> bool:
        self.store.save(path, meta)
        return True

    def load_vectors(self, path: str) -> Optional[dict]:
        return self.store.load(path)

    # -- tiered residency (docs/tiering.md) -------------------------------
    @property
    def device_resident(self) -> bool:
        return self.store.device_resident

    def hbm_bytes(self) -> int:
        return self.store.nbytes

    def host_tier_bytes(self) -> int:
        return self.store.host_bytes

    def promote_bytes(self) -> int:
        return self.store.attach_bytes

    def demote_device(self) -> int:
        freed = self.store.detach()
        if freed:
            self._residency_epoch += 1
        return freed

    def promote_device(self) -> int:
        gained = self.store.attach()
        if gained:
            self._residency_epoch += 1
        return gained

    def stats(self) -> dict:
        s = {
            "type": "flat",
            "count": self.count(),
            "capacity": self.capacity,
            "metric": self.metric,
            "device_resident": self.store.device_resident,
        }
        per_shard = self.store.per_shard_live()
        if per_shard is not None:
            # mesh mode: surface the shard layout + feed the skew gauges
            from weaviate_tpu.monitoring.metrics import set_mesh_shard_gauges

            s["mesh_shards"] = len(per_shard)
            s["mesh_shard_rows"] = [int(x) for x in per_shard]
            set_mesh_shard_gauges(per_shard)
        return s


def _stack_masks(masks: list, rows: list[int], padded: int,
                 capacity: int) -> np.ndarray:
    """[padded, capacity] bools: each member's mask in its query row(s).
    Masks may differ in length while ingest runs (each is as long as the
    doc-id space was when its filter resolved); a row past a mask's end,
    and every padded row, allows nothing. A fresh array a batch: the
    upload may alias host memory (CPU backend) and is asynchronous on the
    chip, so a reused scratch could change under a scan in flight."""
    out = np.zeros((padded, capacity), bool)
    at = 0
    for mask, n in zip(masks, rows):
        mask = np.asarray(mask, bool)[:capacity]
        out[at:at + n, :mask.shape[0]] = mask
        at += n
    return out


def _pad_mask(mask: np.ndarray, capacity: int) -> jnp.ndarray:
    mask = np.asarray(mask, bool)
    if mask.shape[0] < capacity:
        mask = np.pad(mask, (0, capacity - mask.shape[0]))
    return jnp.asarray(mask[:capacity])


def exact_rescore(
    queries: np.ndarray,
    cand_ids: np.ndarray,
    vectors: "HostVectorStore",
    metric: str,
    k: int,
) -> SearchResult:
    """Re-rank approximate candidates with exact fp32 distances on the host.

    Reference ``hnsw/search.go:184`` (shouldRescore): compressed search
    over-fetches, then the top candidates are re-scored against original
    vectors. cand_ids: [B, k'] device results (-1 = empty). The candidate
    sets are tiny (k' ~ 10-200) so host BLAS is the right tier — no HBM
    round-trip for the originals.
    """
    cand_ids = np.asarray(cand_ids)
    b, kp = cand_ids.shape
    safe = np.clip(cand_ids, 0, None)
    cand = vectors.get(safe.reshape(-1)).reshape(b, kp, -1)  # [B, k', D]
    q = np.asarray(queries, np.float32)
    if metric == "l2-squared":
        diff = q[:, None, :] - cand
        d = np.einsum("bkd,bkd->bk", diff, diff)
    elif metric in ("dot", "cosine"):
        ip = np.einsum("bd,bkd->bk", q, cand)
        d = -ip if metric == "dot" else 1.0 - ip
    elif metric == "manhattan":
        d = np.abs(q[:, None, :] - cand).sum(axis=-1)
    else:  # hamming over raw floats (reference hamming.go float variant)
        d = (q[:, None, :] != cand).sum(axis=-1).astype(np.float32)
    d = np.where(cand_ids < 0, np.float32(MASK_DISTANCE), d.astype(np.float32))
    k = min(k, kp)
    part = np.argpartition(d, k - 1, axis=1)[:, :k]
    pd = np.take_along_axis(d, part, axis=1)
    order = np.argsort(pd, axis=1, kind="stable")
    sel = np.take_along_axis(part, order, axis=1)
    out_d = np.take_along_axis(d, sel, axis=1)
    out_i = np.take_along_axis(cand_ids, sel, axis=1)
    out_i = np.where(out_d >= MASK_DISTANCE, -1, out_i)
    return SearchResult(ids=out_i, dists=out_d)


class QuantizedFlatIndex(VectorIndex):
    """Flat index over HBM-resident code planes with host-side rescore.

    Reference ``flat/index.go`` with BQ/SQ/RQ (``flat/quantizer.go``): codes
    live in the LSM 'vectors_compressed' bucket and distances are SIMD over
    codes; here codes are device arrays and distances are one MXU kernel per
    chunk (``ops/quantized.py``). Storage, fit policy, code search and the
    rescore tier all live in ``hnsw.backend.QuantizedBackend`` — this class
    is the VectorIndex adapter over it (same backend HNSW traversal uses).

    Concurrent searches share one code scan: the backend's candidate stage
    goes through a ``CoalescingDispatcher`` whose leader preps and scans
    every compatible pending request as one block, and each request
    rescores its own candidates on its own thread. The scan is bound by
    reading the codes, so a batch costs about what one query costs.
    """

    def __init__(self, dims: int, config: FlatIndexConfig,
                 raw_path: Optional[str] = None):
        from weaviate_tpu.index.hnsw.backend import QuantizedBackend

        self.config = config
        self.metric = config.distance
        self.dims = dims
        self.backend = QuantizedBackend(dims, config, raw_path=raw_path,
                                        code_stage=self._coalesced_stage)
        # bumped on every demote/promote: the dispatcher keys batch
        # grouping on it, so a request enqueued against one residency
        # generation never rides a batch of another
        self._residency_epoch = 0
        # ``quantizer.search`` applies one allow mask a batch: filtered
        # requests share a scan only when their masks are equal
        self._dispatcher = CoalescingDispatcher(
            self._run_batch, max_batch=ROW_BUCKETS[-1])
        # (code capacity, fetch) whose every row bucket is compiled;
        # written by the dispatcher's leader only (single-flight)
        self._warm_programs: set[tuple] = set()

    def _coalesced_stage(self, host: np.ndarray, k: int, allow):
        """The backend's candidate stage for this request's prepped rows,
        run by whichever request leads the batch they join."""
        ids, d = self._dispatcher.search(
            host, k, allow,
            tier_key=(self._residency_epoch, isolation_key()))
        return d, ids

    def _run_batch(self, queries: np.ndarray, k: int, allow):
        """Single-flight batch runner behind the coalescing dispatcher:
        one prep, one code scan and one copy-out for the whole group.
        Returns (candidate ids, distances) [B, fetch] of the group's
        rows; each member rescores its own."""
        backend = self.backend
        fetch = backend.flat_fetch(k)
        if not backend.device_resident:
            # a demotion landed while this group was queued: exact
            # candidates from the host originals
            d, ids = backend.host_topk(queries, fetch, allow)
            return ids, d
        program = (backend.codes.capacity, fetch)
        if queries.shape[0] <= ROW_BUCKETS[-1] \
                and program not in self._warm_programs:
            self._warm_buckets(program, k)
        d, ids = backend.code_candidates(
            queries, k, allow, pad_to=_bucket_rows(queries.shape[0]))
        return ids, d

    def _warm_buckets(self, program: tuple, k: int) -> None:
        """Compile every row bucket of one (capacity, fetch) at its first
        search, never under whichever later batch is the first of its
        size: zero queries through the batch's own stage."""
        capacity, fetch = program
        with TRACER.child("qflat.warm", capacity=capacity, fetch=fetch,
                          buckets=list(ROW_BUCKETS)):
            # child spans of the synthetic scans would read as requests'
            token = tracing.detach()
            try:
                for b in ROW_BUCKETS:
                    self.backend.code_candidates(
                        np.zeros((b, self.dims), np.float32), k, None)
            finally:
                tracing.deactivate(token)
        # programs of a capacity the codes have outgrown are never asked
        # for again
        self._warm_programs = {p for p in self._warm_programs
                               if p[0] == capacity} | {program}

    @property
    def quantizer(self):
        return self.backend.quantizer

    # -- VectorIndex ------------------------------------------------------
    def add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        self.backend.put(np.asarray(doc_ids, np.int64), vectors)

    def delete(self, doc_ids: np.ndarray) -> None:
        self.backend.delete(doc_ids)

    def search(
        self,
        queries: np.ndarray,
        k: int,
        allow_list: Optional[np.ndarray] = None,
        est_selectivity: Optional[float] = None,
    ) -> SearchResult:
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        if queries.shape[-1] != self.dims:
            raise ValueError(
                f"query dims {queries.shape[-1]} != index dims {self.dims}"
            )
        d, ids = run_tier_stable(
            lambda: self.backend.flat_topk(queries, k, allow_list))
        return SearchResult(ids=ids, dists=d)

    def search_by_distance(
        self,
        queries: np.ndarray,
        max_distance: float,
        allow_list: Optional[np.ndarray] = None,
        limit: int = 1024,
    ) -> SearchResult:
        k = min(limit, max(1, self.count()))
        res = self.search(queries, k, allow_list)
        keep = res.dists <= max_distance
        return SearchResult(
            ids=np.where(keep, res.ids, -1),
            dists=np.where(keep, res.dists, np.float32(MASK_DISTANCE)),
        )

    def count(self) -> int:
        return self.backend.originals.live_count

    @property
    def capacity(self) -> int:
        return self.backend.capacity

    def contains(self, doc_id: int) -> bool:
        return self.backend.contains(doc_id)

    # -- tiered residency (docs/tiering.md) -------------------------------
    @property
    def device_resident(self) -> bool:
        return self.backend.device_resident

    def hbm_bytes(self) -> int:
        return self.backend.hbm_bytes()

    def host_tier_bytes(self) -> int:
        return self.backend.host_tier_bytes()

    def demote_device(self) -> int:
        freed = self.backend.demote_device()
        if freed:
            self._residency_epoch += 1
        return freed

    def promote_device(self) -> int:
        gained = self.backend.promote_device()
        if gained:
            self._residency_epoch += 1
        return gained

    def stats(self) -> dict:
        return {
            "type": "flat",
            "quantizer": self.quantizer.kind,
            "fitted": self.quantizer.fitted,
            "count": self.count(),
            "capacity": self.capacity,
            "metric": self.metric,
            "device_resident": self.backend.device_resident,
        }
