"""Distance backends for HNSW traversal: raw HBM vectors or quantized codes.

The reference threads a ``CompressorDistancer`` through the HNSW hot loop when
compression is on (``compressionhelpers/compression.go:40``,
``hnsw/search.go:726``) and rescores the final candidates against original
vectors (``search.go:184``). Here the same seam is a backend object: the graph
walk is identical, only the batched distance kernels differ.

- ``RawBackend``: full-precision corpus in HBM (DeviceVectorStore).
- ``QuantizedBackend``: code planes in HBM (DeviceArraySet) + originals in
  host RAM for rescore; construction and traversal run in code space, the
  final top-k is exactly re-ranked.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.index.store import DeviceVectorStore
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.ops.distance import (
    MASK_DISTANCE,
    candidate_pairwise,
    flat_search,
    gather_distance,
    normalize,
)

_INF = np.float32(np.inf)


def host_exact_topk(q: np.ndarray, vecs: np.ndarray, live_ids: np.ndarray,
                    metric: str, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k over host rows — the WARM-tier search executor
    (tiering/): a demoted tenant's arrays live in host RAM and its
    (by definition low-rate) queries are served by one BLAS pass instead
    of re-renting HBM. ``vecs`` [L, D] are the live rows, ``live_ids``
    their doc ids. Returns (dists [B, k], ids [B, k]) ascending,
    -1/inf padded."""
    b = q.shape[0]
    if len(live_ids) == 0:
        return (np.full((b, k), _INF, np.float32),
                np.full((b, k), -1, np.int64))
    v = vecs.astype(np.float32, copy=False)
    if metric in ("l2-squared", "dot", "cosine"):
        ip = q @ v.T  # [B, L] — BLAS, never a [B, L, D] intermediate
        if metric == "l2-squared":
            sq = np.einsum("ld,ld->l", v, v)
            qsq = np.einsum("bd,bd->b", q, q)
            d = qsq[:, None] - 2.0 * ip + sq[None, :]
        elif metric == "dot":
            d = -ip
        else:
            d = 1.0 - ip
        d = d.astype(np.float32, copy=False)
    else:
        # manhattan/hamming: chunk the row axis (~64MB intermediates)
        d = np.empty((b, len(live_ids)), np.float32)
        step = max(1, (1 << 24) // max(1, b * v.shape[1]))
        for s in range(0, len(live_ids), step):
            d[:, s:s + step] = _host_metric(
                q[:, None, :], v[None, s:s + step, :], metric)
    kk = min(k, d.shape[1])
    part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
    pd = np.take_along_axis(d, part, axis=1)
    order = np.argsort(pd, axis=1, kind="stable")
    sel = np.take_along_axis(part, order, axis=1)
    out_d = np.take_along_axis(d, sel, axis=1)
    out_i = live_ids[sel].astype(np.int64)
    if kk < k:
        out_d = np.pad(out_d, ((0, 0), (0, k - kk)), constant_values=_INF)
        out_i = np.pad(out_i, ((0, 0), (0, k - kk)), constant_values=-1)
    return out_d, out_i


def _live_under_allow(valid: np.ndarray,
                      allow: Optional[np.ndarray]) -> np.ndarray:
    live = np.flatnonzero(valid)
    if allow is not None:
        al = np.asarray(allow, bool)
        live = live[live < len(al)]
        live = live[al[live]]
    return live


def host_store_topk(store: DeviceVectorStore, metric: str,
                    queries: np.ndarray, k: int,
                    allow: Optional[np.ndarray]
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Warm-tier exact search over a detached store's host corpus — the
    ONE recipe (cosine normalize, live-under-allow mask, exact top-k)
    shared by RawBackend.host_topk and FlatIndex's warm branch."""
    q = np.atleast_2d(np.asarray(queries, np.float32))
    if metric == "cosine":
        q = q / np.maximum(
            np.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    corpus, _valid, _sq = store.host_arrays
    if allow is None:
        # the unfiltered live view is immutable while detached (a
        # demoted store rejects mutations), so gather it ONCE per
        # demotion instead of copying the whole live corpus on every
        # query batch; attach()/detach() invalidate the cache
        cached = store._warm_live_cache
        if cached is None:
            live = np.flatnonzero(store.host_valid_mask)
            cached = (live, corpus[live])
            store._warm_live_cache = cached
        live, vecs = cached
        return host_exact_topk(q, vecs, live, metric, k)
    live = _live_under_allow(store.host_valid_mask, allow)
    return host_exact_topk(q, corpus[live], live, metric, k)


class RawBackend:
    """Full-precision distances over the HBM-resident corpus."""

    quantized = False

    def __init__(self, dims: int, config, store: Optional[DeviceVectorStore] = None):
        from weaviate_tpu.parallel.runtime import default_mesh

        self.config = config
        self.metric = config.distance
        self.dims = dims
        # Multi-chip: corpus rows shard across the process mesh; frontier
        # evaluation / heuristic gathers run as SPMD programs with pmin/psum
        # merges over ICI (see parallel/sharded_search.py).
        self.store = store or DeviceVectorStore(
            dims,
            capacity=config.initial_capacity,
            normalized=(self.metric == "cosine"),
            mesh=default_mesh(),
        )

    # -- storage ----------------------------------------------------------
    def put(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        self.store.put(doc_ids, vectors)

    def delete(self, doc_ids: np.ndarray) -> None:
        self.store.delete(doc_ids)

    def contains(self, doc_id: int) -> bool:
        return self.store.contains(doc_id)

    @property
    def capacity(self) -> int:
        return self.store.capacity

    @property
    def mesh(self):
        """The shard mesh the corpus rows span (None single-chip) — the
        mesh device beam shards its graph mirror by this store's layout."""
        return self.store.mesh

    def device_plane_capacity(self) -> int:
        """Capacity of the device-resident plane the beam scorer gathers
        (== row count of the sharded corpus); the mesh mirror derives its
        shard membership from this, never from the host graph's own
        capacity."""
        return self.store.capacity

    @property
    def host_valid_mask(self) -> np.ndarray:
        return self.store.host_valid_mask

    # -- tiered residency (docs/tiering.md) -------------------------------
    @property
    def device_resident(self) -> bool:
        return self.store.device_resident

    def hbm_bytes(self) -> int:
        return self.store.nbytes

    def host_tier_bytes(self) -> int:
        return self.store.host_bytes

    def demote_device(self) -> int:
        return self.store.detach()

    def promote_device(self) -> int:
        return self.store.attach()

    def host_topk(self, queries: np.ndarray, k: int,
                  allow: Optional[np.ndarray]
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Warm-tier exact search over the detached host corpus."""
        return host_store_topk(self.store, self.metric, queries, k, allow)

    # -- query prep -------------------------------------------------------
    def prep_queries(self, queries: np.ndarray):
        q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
        if self.metric == "cosine":
            q = normalize(q)
        return q

    def prep_query_ids(self, ids: np.ndarray):
        if self.store.mesh is not None:
            from weaviate_tpu.parallel.sharded_search import sharded_take

            q = sharded_take(
                self.store.corpus, jnp.asarray(np.asarray(ids, np.int32)),
                mesh=self.store.mesh)
        else:
            q = jnp.take(self.store.corpus, jnp.asarray(ids), axis=0)
        if self.metric == "cosine":
            q = normalize(q)
        return q

    @staticmethod
    def take_queries(qrep, rows: np.ndarray):
        """Row-subset of a query rep (lockstep construction sub-batching)."""
        return qrep[rows]

    # -- device beam ------------------------------------------------------
    def device_scorer(self):
        """(scorer, operands) for the fused device walk — the raw corpus
        snapshot gather-scored at full precision. None while demoted to
        the warm tier (searches belong on the host path)."""
        if not self.store.device_resident:
            return None
        from weaviate_tpu.ops.device_beam import RawScorer

        corpus, _valid, _sqnorms = self.store.snapshot()
        return RawScorer(self.metric, self.config.precision), (corpus,)

    def beam_queries(self, qrep):
        """Device query rep for the fused walk (prep_queries output is
        already a normalized device array)."""
        return qrep

    def beam_queries_for_ids(self, ids: np.ndarray):
        """Construction-side query rep GATHERED from the HBM corpus by id
        — nothing crosses the link. Rows are already metric-prepped
        (cosine rows are normalized at put)."""
        corpus, _valid, _sqnorms = self.store.snapshot()
        return jnp.take(
            corpus, jnp.asarray(np.asarray(ids, np.int32)), axis=0
        ).astype(jnp.float32)

    # -- distance kernels -------------------------------------------------
    def frontier_dists(self, qrep, cand: np.ndarray) -> np.ndarray:
        """Host-walk frontier evaluation: one device call per beam hop.
        The per-hop syncs below are the FALLBACK tier — the serving path
        is the fused one-dispatch walk (``device_scorer`` + ``ops/
        device_beam.py``); this host walk remains for mesh-sharded
        stores, latch-disabled beams, and construction's upper levels."""
        clipped = np.maximum(cand, 0)
        if self.store.mesh is not None:
            from weaviate_tpu.parallel.sharded_search import (
                sharded_gather_distance,
            )

            # graftlint: allow[host-sync-in-hot-path] reason=host-walk fallback tier; the serving path is the one-dispatch device beam
            d = np.array(
                sharded_gather_distance(
                    self.store.corpus,
                    qrep,
                    jnp.asarray(clipped.astype(np.int32)),
                    self.metric,
                    mesh=self.store.mesh,
                    precision=self.config.precision,
                )
            )
        else:
            # graftlint: allow[host-sync-in-hot-path] reason=host-walk fallback tier; the serving path is the one-dispatch device beam
            d = np.array(
                gather_distance(
                    qrep,
                    self.store.corpus,
                    jnp.asarray(clipped),
                    self.metric,
                    precision=self.config.precision,
                )
            )
        d[cand < 0] = _INF
        return d

    def pairwise(self, ids: np.ndarray) -> np.ndarray:
        """[G, C] ids (pads clipped to 0 by caller) -> [G, C, C] distances."""
        if self.store.mesh is not None:
            from weaviate_tpu.ops.distance import vectors_pairwise
            from weaviate_tpu.parallel.sharded_search import sharded_take

            v = sharded_take(
                self.store.corpus, jnp.asarray(ids.astype(np.int32)),
                mesh=self.store.mesh)
            # graftlint: allow[host-sync-in-hot-path] reason=construction-time prune matrix feeds host graph linking
            return np.array(
                vectors_pairwise(v, self.metric,
                                 precision=self.config.precision))
        # graftlint: allow[host-sync-in-hot-path] reason=construction-time prune matrix feeds host graph linking
        return np.array(
            candidate_pairwise(
                self.store.corpus,
                jnp.asarray(ids),
                self.metric,
                precision=self.config.precision,
            )
        )

    def flat_topk(
        self, queries: np.ndarray, k: int, allow: Optional[np.ndarray]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Brute-force top-k (small-filter cutoff path). Returns (dists, ids)."""
        if not self.store.device_resident:
            return self.host_topk(queries, k, allow)
        qrep = self.prep_queries(queries)
        if self.store.mesh is not None:
            from weaviate_tpu.parallel.sharded_search import mesh_flat_topk

            d, ids = mesh_flat_topk(
                self.store, qrep, k, self.metric, allow=allow,
                precision=self.config.precision,
                chunk_size=self.config.search_chunk_size,
                approx_recall=_resolved_approx_recall(self.config),
            )
            # graftlint: allow[host-sync-in-hot-path] reason=final top-k materialization
            d = np.array(d)
            # graftlint: allow[host-sync-in-hot-path] reason=final top-k materialization
            ids = np.asarray(ids, np.int64)
            d[ids < 0] = _INF
            return d, ids
        corpus, valid, sqnorms = self.store.snapshot()
        cap = corpus.shape[0]
        allow_j = None
        if allow is not None:
            al = np.asarray(allow, bool)
            if len(al) < cap:
                al = np.pad(al, (0, cap - len(al)))
            allow_j = jnp.asarray(al[:cap])
        d, ids = flat_search(
            qrep,
            corpus,
            k=k,
            metric=self.metric,
            valid_mask=valid,
            allow_mask=allow_j,
            corpus_sqnorms=sqnorms if self.metric == "l2-squared" else None,
            precision=self.config.precision,
            approx_recall=_resolved_approx_recall(self.config),
        )
        # graftlint: allow[host-sync-in-hot-path] reason=final top-k materialization
        d = np.array(d)
        # graftlint: allow[host-sync-in-hot-path] reason=final top-k materialization
        ids = np.asarray(ids, np.int64)
        d[ids < 0] = _INF
        return d, ids

    def rescore_topk(
        self, queries: np.ndarray, cand_ids: np.ndarray, cand_d: np.ndarray, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Raw distances are already exact — just truncate."""
        return cand_ids[:, :k], cand_d[:, :k]


def _resolved_approx_recall(config) -> float:
    """Same UNSET(-1) resolution FlatIndex.search applies: follow the
    hot-reloadable fleet default; 0.0 stays PINNED exact."""
    r = config.flat_approx_recall
    if r < 0.0:
        from weaviate_tpu.utils.runtime_config import (
            FLAT_APPROX_RECALL_DEFAULT,
        )

        return FLAT_APPROX_RECALL_DEFAULT.get()
    return r


class QueryRep(NamedTuple):
    """Per-search query representation: host fp32 (metric-prepped) for exact
    rescore/fallback + the quantizer's device rep (packed/rotated/cast),
    computed once and reused across every frontier hop."""

    host: np.ndarray
    code: Any  # None when the quantizer isn't fitted yet

    @property
    def shape(self) -> tuple:
        return self.host.shape


class QuantizedBackend:
    """Code-space distances + exact host rescore (HNSW+PQ/BQ/SQ/RQ)."""

    quantized = True

    def __init__(self, dims: int, config, raw_path: Optional[str] = None,
                 code_stage=None):
        """``code_stage``: what ``flat_topk`` calls for a block's
        candidates, in the signature of ``code_candidates`` (the
        default); an owner that coalesces concurrent searches into one
        scan hands in its own."""
        from weaviate_tpu.compression import (
            DeviceArraySet,
            HostVectorStore,
            build_quantizer,
        )

        self.config = config
        self.metric = config.distance
        self.dims = dims
        self.quantizer = build_quantizer(config.quantizer, dims, self.metric)
        tier = getattr(config, "raw_tier", "ram")
        if tier not in ("ram", "ram16", "disk16", "disk8"):
            raise ValueError(f"invalid raw_tier {tier!r}")
        dtype = {"ram": np.float32, "ram16": np.float16,
                 "disk16": np.float16, "disk8": np.int8}[tier]
        # raw_path param wins over config so per-shard callers can place
        # each shard's memmap under its own directory without mutating the
        # shared collection config
        path = None
        if tier.startswith("disk"):
            path = raw_path or getattr(config, "raw_path", None)
            if path is None:
                raise ValueError(f"raw_tier={tier!r} requires a raw path")
        from weaviate_tpu.parallel.runtime import default_mesh

        self.originals = HostVectorStore(
            dims, capacity=config.initial_capacity, dtype=dtype, path=path)
        # Multi-chip: the quantized code planes row-shard across the
        # process mesh exactly like the raw corpus does — the fused mesh
        # beam walks each shard's local block (docs/mesh.md).
        self.codes = DeviceArraySet(
            self.quantizer.fields(), capacity=config.initial_capacity,
            mesh=default_mesh(),
        )
        self.code_stage = code_stage

    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        v = np.asarray(vectors, np.float32)
        if self.metric == "cosine":
            v = v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-12)
        return v

    # -- storage ----------------------------------------------------------
    def put(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        v = self._prep_vectors(vectors)
        self.originals.put(doc_ids, v)
        if self.quantizer.fitted:
            self.codes.put(doc_ids, self.quantizer.encode(v))
            return
        if self.originals.live_count >= self.quantizer.min_training:
            limit = getattr(self.quantizer.config, "training_limit", 100_000)
            self.quantizer.fit(self.originals.sample(limit))
            ids, vecs = self.originals.all_live()
            self.codes.put(ids, self.quantizer.encode(vecs))

    def delete(self, doc_ids: np.ndarray) -> None:
        self.originals.delete(doc_ids)
        self.codes.delete(doc_ids)

    def contains(self, doc_id: int) -> bool:
        return doc_id < self.originals.capacity and bool(
            self.originals.valid[doc_id]
        )

    @property
    def capacity(self) -> int:
        return self.originals.capacity

    @property
    def mesh(self):
        """The shard mesh the code planes span (None single-chip)."""
        return self.codes.mesh

    def device_plane_capacity(self) -> int:
        """Row count of the sharded code planes — the mesh mirror's
        shard-membership base (the originals' host capacity can differ)."""
        return self.codes.capacity

    @property
    def host_valid_mask(self) -> np.ndarray:
        return self.originals.valid

    # -- tiered residency (docs/tiering.md) -------------------------------
    @property
    def device_resident(self) -> bool:
        return self.codes.device_resident

    def hbm_bytes(self) -> int:
        return self.codes.nbytes

    def host_tier_bytes(self) -> int:
        return self.codes.host_bytes

    def demote_device(self) -> int:
        return self.codes.detach()

    def promote_device(self) -> int:
        return self.codes.attach()

    def host_topk(self, queries: np.ndarray, k: int,
                  allow: Optional[np.ndarray]
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Warm-tier exact search over the host originals (the rescore
        tier already lives there — demotion only evicts the codes)."""
        q = self._prep_vectors(np.atleast_2d(queries))
        live = _live_under_allow(self.originals.valid, allow)
        return host_exact_topk(
            q, self.originals.get(live), live, self.metric, k)

    # -- query prep -------------------------------------------------------
    def prep_queries(self, queries: np.ndarray) -> QueryRep:
        host = self._prep_vectors(np.atleast_2d(queries))
        code = self.quantizer.prep(host) if self.quantizer.fitted else None
        return QueryRep(host=host, code=code)

    def prep_query_ids(self, ids: np.ndarray) -> QueryRep:
        return self.prep_queries(self.originals.get(ids))

    @staticmethod
    def take_queries(qrep: QueryRep, rows: np.ndarray) -> QueryRep:
        return QueryRep(
            host=qrep.host[rows],
            code=None if qrep.code is None else qrep.code[rows],
        )

    # -- device beam ------------------------------------------------------
    def device_scorer(self):
        """(scorer, operands) over the HBM code planes, or None while the
        quantizer is unfitted (pre-training corpus walks stay on host —
        that is a lifecycle stage, not a failure) or the codes are
        demoted to the warm tier."""
        if not self.quantizer.fitted or not self.codes.device_resident:
            return None
        return self.quantizer.beam_scorer(self.codes)

    def beam_queries(self, qrep: QueryRep):
        """Device query rep for the fused walk: the quantizer's code-space
        rep (packed bits / rotated bytes / fp32), None pre-fit."""
        return qrep.code

    def beam_queries_for_ids(self, ids: np.ndarray):
        """Construction-side query rep: originals gathered on host and
        prepped ONCE per chunk (one upload), not once per hop."""
        return self.prep_query_ids(ids).code

    # -- distance kernels -------------------------------------------------
    def frontier_dists(self, qrep: QueryRep, cand: np.ndarray) -> np.ndarray:
        """Host-walk frontier evaluation in code space — the FALLBACK
        tier; the serving path is the fused one-dispatch device beam."""
        if qrep.code is None:
            return self._exact_host_dists(qrep.host, cand)
        clipped = np.maximum(cand, 0)
        # graftlint: allow[host-sync-in-hot-path] reason=host-walk fallback tier; the serving path is the one-dispatch device beam
        d = np.array(
            self.quantizer.gather_distance(
                qrep.code, self.codes, jnp.asarray(clipped)
            )
        )
        d[cand < 0] = _INF
        return d

    def _exact_host_dists(self, q: np.ndarray, cand: np.ndarray) -> np.ndarray:
        clipped = np.maximum(cand, 0)
        vecs = self.originals.get(clipped.reshape(-1)).reshape(
            *cand.shape, self.dims
        )
        d = _host_metric(q[:, None, :], vecs, self.metric)
        d[cand < 0] = _INF
        return d

    def pairwise(self, ids: np.ndarray) -> np.ndarray:
        """Construction heuristic pairwise — exact over host originals,
        keeping graph quality at the uncompressed level (better than the
        reference, which builds with compressed distances once compression
        is on). BLAS-shaped for l2/dot/cosine so a large lockstep insert
        batch (C up to 4096) costs O(C^2) memory, never a [C, C, D]
        materialization; manhattan/hamming chunk the row axis."""
        vecs = self.originals.get(ids.reshape(-1)).reshape(*ids.shape, self.dims)
        if self.metric == "cosine":
            vecs = vecs / np.maximum(
                np.linalg.norm(vecs, axis=-1, keepdims=True), 1e-12
            )
        g_n, c_n, d_n = vecs.shape
        out = np.empty((g_n, c_n, c_n), np.float32)
        if self.metric in ("l2-squared", "dot", "cosine"):
            for g in range(g_n):
                v = vecs[g]
                ip = (v @ v.T).astype(np.float32)
                if self.metric == "l2-squared":
                    sq = np.einsum("cd,cd->c", v, v).astype(np.float32)
                    out[g] = sq[:, None] + sq[None, :] - 2.0 * ip
                elif self.metric == "dot":
                    out[g] = -ip
                else:
                    out[g] = 1.0 - ip
            return out
        step = max(1, (1 << 24) // max(1, c_n * d_n))  # ~64MB intermediate
        for g in range(g_n):
            v = vecs[g]
            for s in range(0, c_n, step):
                out[g, s:s + step] = _host_metric(
                    v[s:s + step, None, :], v[None, :, :], self.metric)
        return out

    def flat_topk(
        self, queries: np.ndarray, k: int, allow: Optional[np.ndarray]
    ) -> tuple[np.ndarray, np.ndarray]:
        from weaviate_tpu.index.flat import exact_rescore

        if not self.codes.device_resident:
            return self.host_topk(queries, k, allow)
        host = self._prep_vectors(np.atleast_2d(queries))
        if not self.quantizer.fitted:
            # pre-fit: exact over the (tiny) host corpus
            live = np.flatnonzero(self.originals.valid)
            if allow is not None:
                al = np.asarray(allow, bool)
                live = live[(live < len(al))]
                live = live[al[live]]
            if len(live) == 0:
                b = host.shape[0]
                return (
                    np.full((b, k), _INF, np.float32),
                    np.full((b, k), -1, np.int64),
                )
            ids = np.broadcast_to(live[None, :], (host.shape[0], len(live)))
            res = exact_rescore(
                host, ids, self.originals, self.metric, min(k, len(live))
            )
        else:
            _, ids = (self.code_stage or self.code_candidates)(host, k, allow)
            # the originals' gather and their exact distances
            with TRACER.child("qflat.rescore", quantizer=self.quantizer.kind,
                              candidates=ids.shape[1]):
                res = exact_rescore(host, ids, self.originals, self.metric, k)
        d = res.dists.astype(np.float32).copy()
        ids = res.ids.astype(np.int64)
        d[ids < 0] = _INF
        if ids.shape[1] < k:
            pad = k - ids.shape[1]
            ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
            d = np.pad(d, ((0, 0), (0, pad)), constant_values=_INF)
        return d, ids

    def flat_fetch(self, k: int) -> int:
        """Candidates a query row of ``flat_topk`` fetches from code
        space for the exact rescore."""
        rescore_limit = getattr(self.quantizer.config, "rescore_limit", 0)
        return max(4 * k, rescore_limit, k)

    def code_candidates(self, host: np.ndarray, k: int,
                        allow: Optional[np.ndarray],
                        pad_to: Optional[int] = None
                        ) -> tuple[np.ndarray, np.ndarray]:
        """``flat_topk``'s candidate stage for prepped query rows: ONE
        ``quantizer.prep`` of the whole block (zero rows added up to
        ``pad_to``, a size a batch runner compiles for), one code scan
        of the top ``flat_fetch(k)``, one copy out. Returns (code
        distances, candidate ids) [B, fetch] on the host, padded rows
        dropped."""
        kind = self.quantizer.kind
        n = host.shape[0]
        # rotate / pack, and the block's upload
        with TRACER.child("qflat.prepare", quantizer=kind):
            if pad_to is not None and pad_to > n:
                host = np.pad(host, ((0, pad_to - n), (0, 0)))
            code = self.quantizer.prep(host)
        mask = self.codes.valid_mask
        if allow is not None:
            al = np.asarray(allow, bool)
            if len(al) < self.codes.capacity:
                al = np.pad(al, (0, self.codes.capacity - len(al)))
            mask = mask & jnp.asarray(al[: self.codes.capacity])
        fetch = self.flat_fetch(k)
        chunk = self.config.search_chunk_size
        # the code scan's launch up to its candidates on the host
        with TRACER.child("qflat.scan", quantizer=kind, fetch=fetch,
                          capacity=self.codes.capacity, batch=n):
            d, ids = self.quantizer.search(
                code, self.codes, fetch, mask,
                chunk if self.codes.capacity > chunk else 0,
            )
            # the candidates cross to the host rescore tier: one copy out
            # of both arrays
            d, ids = jax.device_get((d, ids))
        return d[:n], ids[:n]

    def rescore_topk(
        self, queries: np.ndarray, cand_ids: np.ndarray, cand_d: np.ndarray, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        from weaviate_tpu.index.flat import exact_rescore

        # metric prep (cosine normalization) must match the stored originals,
        # otherwise returned distances are scaled by ||q||
        q = self._prep_vectors(np.atleast_2d(queries))
        res = exact_rescore(q, cand_ids, self.originals, self.metric, k)
        d = res.dists.astype(np.float32).copy()
        ids = res.ids.astype(np.int64)
        d[ids < 0] = _INF
        return ids, d


def _host_metric(a: np.ndarray, b: np.ndarray, metric: str) -> np.ndarray:
    """Broadcasted exact distances on host (small candidate blocks only)."""
    if metric == "l2-squared":
        diff = a - b
        return np.einsum("...d,...d->...", diff, diff).astype(np.float32)
    if metric in ("dot", "cosine"):
        ip = np.einsum("...d,...d->...", a, b).astype(np.float32)
        return -ip if metric == "dot" else 1.0 - ip
    if metric == "manhattan":
        return np.abs(a - b).sum(axis=-1).astype(np.float32)
    return (a != b).sum(axis=-1).astype(np.float32)
