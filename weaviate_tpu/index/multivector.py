"""Multi-vector (ColBERT-style) index: MUVERA FDE + exact MaxSim rescore.

Reference: ``adapters/repos/db/vector/multivector/muvera.go:26`` (fixed
dimensional encoding) + ``hnsw/search.go:927`` (late-interaction rescore).
The reference encodes per-vector in scalar Go loops. What is batched here,
and where:

- **The encode, on the host, in numpy, a BATCH of passages at a time**
  (``MuveraEncoder.encode_docs``; a query is a batch of one set without the
  fill). SimHash bucket ids: one ``[N, T, D] x [R, ksim, D]`` product and
  its sign bits. Bucket aggregation: a one-hot ``[N, R, B, T] x [N, T, D]``
  matmul (sums) over counts (means). Empty-bucket fill (docs only, as in
  MUVERA): the hamming-nearest token from a popcount table over the
  bucket ids, for the few empty buckets only. Per-repetition +-1
  projection: one broadcast ``[N, R, B, D] x [R, D, dproj]`` matmul. No
  Python loop over repetitions, buckets or tokens; an ingest batch of 100
  passages is one call (its one loop copies each set into the padded
  block).
- **The search, on the device, ONE program a request**
  (``ops/device_beam.fused_flat_rerank``): the FDE corpus lives in a normal
  ``FlatIndex`` (dot metric, HBM-resident), so the candidate scan is the
  same masked matmul + two-stage top-k as everything else; the candidates'
  token sets are gathered from the HBM token planes
  (``modules/device/store.py``: ``[capacity, T, D]`` bfloat16) and the exact
  MaxSim (Chamfer) of all of them is a single padded ``[C, Tq, Td]`` einsum,
  bfloat16 operands, float32 sums; the top-k is taken on the device too.
  Requests are not batched with each other: each is its own program.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from weaviate_tpu.index.base import SearchResult, VectorIndex
from weaviate_tpu.index.flat import FlatIndex
from weaviate_tpu.modules.device.store import TOKEN_DTYPE
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.schema.config import (
    FlatIndexConfig,
    MultiVectorIndexConfig,
    RerankModuleConfig,
)

MUVERA_SEED = 0x532C_A510


class MuveraEncoder:
    """Fixed-dimensional encoding of a token-vector set (MUVERA).

    fde_dim = repetitions * 2^ksim * dproj. Doc and query encodings differ
    exactly as in the paper: docs average + empty-fill, queries sum only.
    """

    def __init__(self, dims: int, ksim: int = 4, dproj: int = 16,
                 repetitions: int = 10):
        import jax

        self.dims = dims
        self.ksim = ksim
        self.dproj = min(dproj, dims)
        self.repetitions = repetitions
        self.buckets = 1 << ksim
        key = jax.random.PRNGKey(MUVERA_SEED)
        kg, kp = jax.random.split(key)
        # host copies: encoding happens in jitted fns that close over these
        # graftlint: allow[host-sync-in-hot-path] reason=one-shot init; jitted encoders close over host copies
        self.gaussians = np.asarray(
            jax.random.normal(kg, (repetitions, ksim, dims)), np.float32)
        # graftlint: allow[host-sync-in-hot-path] reason=one-shot init; jitted encoders close over host copies
        self.proj = np.asarray(
            jax.random.rademacher(kp, (repetitions, dims, self.dproj)),
            np.float32) / np.float32(np.sqrt(self.dproj))
        self.fde_dim = repetitions * self.buckets * self.dproj
        self._bit_weights = (1 << np.arange(ksim)).astype(np.int32)

        # popcount of the xor of two bucket ids: their hamming distance
        grid = np.arange(self.buckets)
        self._hamming = np.array(
            [[bin(x ^ y).count("1") for y in grid] for x in grid], np.int32)

    # -- host-side (numpy), a padded batch of token sets at a time ----------
    def _aggregate(self, tokens: np.ndarray, mask: np.ndarray):
        """tokens [N, T, D] zero-padded, mask [N, T] -> (bucket ids
        [N, R, T], per-bucket token sums [N, R, B, D], counts [N, R, B])."""
        # sign bits of the gaussian projections -> a bucket id a token
        dots = np.einsum("ntd,rkd->nrkt", tokens, self.gaussians,
                         optimize=True)
        ids = np.einsum("nrkt,k->nrt", (dots < 0).astype(np.int32),
                        self._bit_weights)
        onehot = (ids[:, :, None, :] == np.arange(self.buckets)[:, None]) \
            & mask[:, None, None, :]                       # [N, R, B, T]
        sums = np.matmul(onehot.astype(np.float32), tokens[:, None])
        return ids, sums, onehot.sum(axis=3)

    def encode_docs(self, token_sets: list[np.ndarray]) -> np.ndarray:
        """N token sets ([T_i, D] each) -> [N, fde_dim]. Per bucket: MEAN of
        assigned tokens; empty buckets take the hamming-nearest token, the
        first of several equally near (MUVERA fill)."""
        n = len(token_sets)
        width = max(t.shape[0] for t in token_sets)
        tokens = np.zeros((n, width, self.dims), np.float32)
        mask = np.zeros((n, width), bool)
        for i, t in enumerate(token_sets):
            tokens[i, : t.shape[0]] = t
            mask[i, : t.shape[0]] = True
        ids, out, counts = self._aggregate(tokens, mask)
        out /= np.maximum(counts, 1)[..., None]
        # the few empty buckets, one row each: hamming distance of the
        # bucket's id to every token's, padding beyond any
        en, er, eb = np.nonzero(counts == 0)
        if len(en):
            ham = np.where(mask[en], self._hamming[eb[:, None], ids[en, er]],
                           self.ksim + 1)                  # [E, T]
            out[en, er, eb] = tokens[en, ham.argmin(axis=1)]
        # per-repetition +-1 projection: [N, R, B, D] x [R, D, dproj]
        return np.matmul(out, self.proj).reshape(n, -1)

    def encode_doc(self, tokens: np.ndarray) -> np.ndarray:
        """[T, D] -> [fde_dim]: a batch of one."""
        return self.encode_docs([np.asarray(tokens, np.float32)])[0]

    def encode_query(self, tokens: np.ndarray) -> np.ndarray:
        """[Tq, D] -> [fde_dim]. SUM per bucket, no fill (paper asymmetry)."""
        tokens = np.asarray(tokens, np.float32)[None]
        _, sums, _ = self._aggregate(
            tokens, np.ones(tokens.shape[:2], bool))
        return np.matmul(sums[0], self.proj).reshape(-1)


def maxsim_scores(query: np.ndarray, cand_tokens: np.ndarray,
                  cand_mask: np.ndarray) -> np.ndarray:
    """Exact late-interaction (Chamfer/MaxSim) on device.

    query [Tq, D]; cand_tokens [C, Tmax, D] zero-padded; cand_mask [C, Tmax].
    Returns [C] scores = sum over query tokens of max over doc tokens of the
    dot product (reference hnsw/search.go:927 rescore loop -> one einsum).
    With an active device mesh the candidate axis shards across it
    (``parallel.sharded_maxsim``) — the rescore tier's sequence-parallel
    analogue for long token sets.
    """
    import jax.numpy as jnp

    from weaviate_tpu.parallel.runtime import default_mesh

    mesh = default_mesh()
    if mesh is not None and cand_tokens.shape[0] >= 2 * mesh.size:
        from weaviate_tpu.parallel.sharded_search import sharded_maxsim
        from jax.sharding import NamedSharding, PartitionSpec as P
        from weaviate_tpu.parallel.mesh import SHARD_AXIS

        c = cand_tokens.shape[0]
        pad = (-c) % mesh.size
        if pad:
            cand_tokens = np.concatenate(
                [cand_tokens, np.zeros((pad, *cand_tokens.shape[1:]),
                                       cand_tokens.dtype)])
            cand_mask = np.concatenate(
                [cand_mask, np.zeros((pad, cand_mask.shape[1]), bool)])
        import jax

        toks = jax.device_put(
            cand_tokens.astype(np.float32),
            NamedSharding(mesh, P(SHARD_AXIS, None, None)))
        mask = jax.device_put(cand_mask,
                              NamedSharding(mesh, P(SHARD_AXIS, None)))
        # replication of the query rides sharded_maxsim's identity-keyed
        # cache (one upload per query batch, not per invocation)
        q = np.asarray(query, np.float32)
        # graftlint: allow[host-sync-in-hot-path] reason=final [C] score materialization for host rerank
        return np.asarray(sharded_maxsim(q, toks, mask, mesh=mesh))[:c]

    # operands in the candidates' dtype (bfloat16 from the token planes:
    # the query's tokens are rounded to it), float32 sums
    c = jnp.asarray(cand_tokens)
    q = jnp.asarray(query, c.dtype)
    m = jnp.asarray(cand_mask, bool)
    sims = jnp.einsum("qd,ctd->cqt", q, c, preferred_element_type=jnp.float32)
    sims = jnp.where(m[:, None, :], sims, -jnp.inf)
    best = jnp.max(sims, axis=2)  # [C, Tq]
    best = jnp.where(jnp.isfinite(best), best, 0.0)
    # graftlint: allow[host-sync-in-hot-path] reason=final [C] score materialization for host rerank
    return np.asarray(jnp.sum(best, axis=1))


class MultiVectorIndex(VectorIndex):
    """FDE candidate index + token store + exact MaxSim rescore tier."""

    def __init__(self, dims: int, config: Optional[MultiVectorIndexConfig] = None):
        self.config = config or MultiVectorIndexConfig()
        self.dims = dims
        self.metric = "dot"  # FDE similarity is inner product
        self.encoder = MuveraEncoder(
            dims, ksim=self.config.ksim, dproj=self.config.dproj,
            repetitions=self.config.repetitions)
        inner_cfg = FlatIndexConfig(
            distance="dot",
            initial_capacity=self.config.initial_capacity,
            precision=self.config.precision,
            flat_approx_recall=self.config.flat_approx_recall,
        )
        # float32 rows: the configuration states a float32 FDE plane, and
        # the fused scan + rerank reads it as such (ROADMAP S14 halves it)
        self.inner = FlatIndex(self.encoder.fde_dim, inner_cfg,
                               float32_rows=True)
        # device rerank tier (modules/device/): the exact MaxSim rescore
        # IS a rerank module here, fused with the FDE candidate scan into
        # ONE dispatch (ops/device_beam.fused_flat_rerank) — candidates
        # never round-trip to the host. config.rerank swaps the module.
        # The token store's host planes are the ONE host copy of the
        # token sets (rescore fallback + checkpoint both read them).
        from weaviate_tpu.modules.device import (
            CandidateTokenStore,
            build_device_reranker,
        )

        rr_cfg = getattr(self.config, "rerank", None)
        # explicit config vs the built-in default matters for the
        # fallback COUNTER only: an operator alerting on rerank
        # fallbacks must not see every unconfigured multivector
        # collection's normal host rescore firing the alert
        self._rerank_explicit = rr_cfg is not None and rr_cfg.enabled
        if self._rerank_explicit:
            self._rerank_module = build_device_reranker(
                rr_cfg.module, rr_cfg.params)
            tmax = rr_cfg.max_tokens
        else:
            self._rerank_module = build_device_reranker("rerank-maxsim")
            tmax = RerankModuleConfig.max_tokens
        # the planes are as wide as the longest token set the collection
        # expects (``rerank.max_tokens``: ColBERT's doc_maxlen); a longer
        # one widens them, at the price of a re-feed and a recompile
        self._token_store = CandidateTokenStore(
            dims, max_tokens=tmax,
            cap_fn=lambda: self.inner.store.capacity,
            mesh=self.inner.store.mesh)

    multi_vector = True

    # -- writes -------------------------------------------------------------
    def add_batch_multi(self, doc_ids: np.ndarray,
                        token_sets: list[np.ndarray]) -> None:
        if len(doc_ids) == 0:
            return
        token_sets = [np.atleast_2d(np.asarray(t, np.float32))
                      for t in token_sets]
        # tokens BEFORE the candidate index: a racing search that sees the
        # new id in the FDE corpus must find its rescore tokens
        self._token_store.put(np.asarray(doc_ids, np.int64), token_sets)
        with TRACER.child("mv.encode_docs", docs=len(token_sets),
                          tokens=sum(t.shape[0] for t in token_sets)):
            fdes = self.encoder.encode_docs(token_sets)
        self.inner.add_batch(np.asarray(doc_ids, np.int64), fdes)

    def _host_token_set(self, doc_id: int) -> Optional[np.ndarray]:
        """The exact (unpadded) token set for one doc from the host
        planes, or None when absent/deleted (mask rows are prefix-True,
        so the mask slice reconstructs the original shape)."""
        toks, mask = self._token_store.host_planes()
        if doc_id >= toks.shape[0]:
            return None
        m = mask[doc_id]
        if not m.any():
            return None
        return toks[doc_id][m]

    def add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        """Single-vector adds are degenerate token sets of size 1."""
        self.add_batch_multi(doc_ids, [v[None, :] if v.ndim == 1 else v
                                       for v in vectors])

    def delete(self, doc_ids: np.ndarray) -> None:
        self.inner.delete(doc_ids)
        self._token_store.delete(np.asarray(doc_ids).reshape(-1))

    # -- search ---------------------------------------------------------------
    def search_multi(self, query_tokens: np.ndarray, k: int,
                     allow_list: Optional[np.ndarray] = None) -> SearchResult:
        """query_tokens [Tq, D] -> top-k by the rerank module (exact
        MaxSim by default) over the FDE candidates (rescore_limit-wide).
        Device-resident single-chip stores run FDE scan + module score +
        top-k as ONE fused dispatch — candidates never visit the host;
        the legacy host rescore remains the (loud) fallback tier."""
        query_tokens = np.atleast_2d(np.asarray(query_tokens, np.float32))
        if query_tokens.shape[-1] != self.dims:
            raise ValueError(
                f"query token dims {query_tokens.shape[-1]} != {self.dims}")
        with TRACER.child("mv.encode_query", tokens=len(query_tokens),
                          fde_dim=self.encoder.fde_dim):
            fde = self.encoder.encode_query(query_tokens)[None, :]
        cand_k = max(k, self.config.rescore_limit or 4 * k)
        cand_k = min(cand_k, max(1, self.inner.count()))
        if self.inner.store.device_resident and self.inner.store.mesh is None:
            res = self._search_multi_fused(query_tokens, fde, cand_k, k,
                                           allow_list)
            if res is not None:
                return res
        elif self._rerank_explicit:
            from weaviate_tpu.monitoring.metrics import RERANK_FALLBACK

            RERANK_FALLBACK.inc(
                module=self._rerank_module.name,
                reason="mesh_legacy" if self.inner.store.mesh is not None
                else "warm_tier")
        with TRACER.child("mv.search", candidates=cand_k, k=k,
                          tokens=len(query_tokens), tier="host"):
            return self._search_multi_host(query_tokens, fde, cand_k, k,
                                           allow_list)

    def _search_multi_host(self, query_tokens: np.ndarray, fde: np.ndarray,
                           cand_k: int, k: int,
                           allow_list: Optional[np.ndarray]) -> SearchResult:
        """The fallback tier: FDE search, then the candidates' token sets
        from the host planes through the module's scorer."""
        res = self.inner.search(fde, cand_k, allow_list)
        cand = res.ids[0]
        cand = cand[cand >= 0]
        if len(cand) == 0:
            return SearchResult(ids=np.full((1, k), -1, np.int64),
                                dists=np.full((1, k), np.inf, np.float32))
        # a candidate may have been deleted between the FDE search and here
        sets = []
        kept = []
        for d in cand:
            t = self._host_token_set(int(d))
            if t is not None:
                sets.append(t)
                kept.append(int(d))
        cand = np.asarray(kept, np.int64)
        if len(cand) == 0:
            return SearchResult(ids=np.full((1, k), -1, np.int64),
                                dists=np.full((1, k), np.inf, np.float32))
        tmax = max(s.shape[0] for s in sets)
        # in the planes' dtype, so that both tiers round alike
        toks = np.zeros((len(sets), tmax, self.dims), sets[0].dtype)
        mask = np.zeros((len(sets), tmax), bool)
        for i, s in enumerate(sets):
            toks[i, : s.shape[0]] = s
            mask[i, : s.shape[0]] = True
        if self._rerank_module.name == "rerank-maxsim":
            # the default module IS this scorer — keep the (possibly
            # mesh-sharded, device-accelerated) implementation
            scores = maxsim_scores(query_tokens, toks, mask)
        else:
            # a configured non-default module must rank the fallback
            # tier too, or demotion would silently change the ordering
            # (docs/modules.md: the fallback runs the host_score twin)
            qm = np.ones((1, query_tokens.shape[0]), bool)
            scores = self._rerank_module.host_score(
                query_tokens[None], qm, toks[None], mask[None])[0]
        order = np.argsort(-scores, kind="stable")[:k]
        ids = np.full((1, k), -1, np.int64)
        d = np.full((1, k), np.inf, np.float32)
        ids[0, : len(order)] = cand[order]
        # present as a distance: negated MaxSim (lower = better)
        d[0, : len(order)] = -scores[order]
        return SearchResult(ids=ids, dists=d)

    def _search_multi_fused(self, query_tokens: np.ndarray,
                            fde: np.ndarray, cand_k: int, k: int,
                            allow_list: Optional[np.ndarray]
                            ) -> Optional[SearchResult]:
        """ONE dispatch: FDE scan → gather candidate token planes →
        module score → on-device top-k (``ops/device_beam.
        fused_flat_rerank``). Returns None to use the host path (the
        caller latches the fallback counter)."""
        from weaviate_tpu.monitoring import tracing
        from weaviate_tpu.monitoring.metrics import (
            RERANK_CANDIDATES,
            RERANK_FALLBACK,
            RERANK_REQUESTS,
        )
        from weaviate_tpu.ops.device_beam import fused_flat_rerank

        name = self._rerank_module.name
        corpus, valid, _sqnorms = self.inner.store.snapshot()
        cap = int(corpus.shape[0])
        tq = query_tokens.shape[0]
        tq_pad = 1 << max(0, (tq - 1).bit_length())
        qt = np.zeros((1, tq_pad, self.dims), np.float32)
        qt[0, :tq] = query_tokens
        qm = np.zeros((1, tq_pad), bool)
        qm[0, :tq] = True
        allow = None
        if allow_list is not None:
            allow = np.asarray(allow_list, bool)
            if len(allow) < cap:
                allow = np.pad(allow, (0, cap - len(allow)))
            allow = allow[:cap]
        # pow2 buckets so steady traffic shares a handful of compiles
        fetch = 1 << max(3, (int(cand_k) - 1).bit_length())
        out_k = min(1 << max(3, (int(k) - 1).bit_length()), fetch)
        try:
            # the request's three arrays go up with the call itself (cheaper
            # in interpreter time than a device_put of their own: 315
            # against 281 requests/s, PERF.md PR 35); the planes are held,
            # with the other requests in flight, until the program is
            # enqueued, since a later feed donates them
            t0 = time.perf_counter()
            with TRACER.child("mv.search", candidates=fetch, k=out_k,
                              tokens=tq, tier="fused") as span, \
                    self._token_store.planes(min_rows=cap) as (toks, tmask):
                # until the planes are this request's to read: the turn at
                # the store's lock and, after a write, the feed
                span.set(planes_wait_ms=(time.perf_counter() - t0) * 1e3)
                ids_j, d_j = fused_flat_rerank(
                    self._rerank_module, fde, corpus, valid, qt, qm, toks,
                    tmask, fetch=fetch, k=out_k, allow=allow, metric="dot",
                    precision=self.config.precision)
            with TRACER.child("mv.result"):
                # graftlint: allow[host-sync-in-hot-path] reason=final reranked top-k materialization
                ids = np.asarray(ids_j)[0].astype(np.int64)
                # graftlint: allow[host-sync-in-hot-path] reason=final reranked top-k materialization
                d = np.asarray(d_j)[0].astype(np.float32)
        except Exception as e:
            import logging

            RERANK_FALLBACK.inc(module=name, reason="fused_error")
            logging.getLogger("weaviate_tpu.multivector").warning(
                "fused multivector rerank failed (host path serves this "
                "query): %s", e)
            return None
        RERANK_REQUESTS.inc(module=name, tier="fused")
        RERANK_CANDIDATES.observe(float(fetch), module=name)
        tracing.add_event("rerank.score", module=name,
                          candidates=int(fetch), rows=1)
        out_ids = np.full((1, k), -1, np.int64)
        out_d = np.full((1, k), np.inf, np.float32)
        n_out = min(k, len(ids))
        out_ids[0, :n_out] = ids[:n_out]
        out_d[0, :n_out] = d[:n_out]
        out_ids[0][~np.isfinite(out_d[0])] = -1
        return SearchResult(ids=out_ids, dists=out_d)

    def search(self, queries: np.ndarray, k: int,
               allow_list: Optional[np.ndarray] = None,
               est_selectivity: Optional[float] = None) -> SearchResult:
        """[B, D] single-vector queries (each = a 1-token set) or a single
        [Tq, D] token matrix via search_multi. ``est_selectivity`` is
        accepted for interface parity (planes resolve to host masks here)."""
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        outs = [self.search_multi(q[None, :], k, allow_list) for q in queries]
        return SearchResult(
            ids=np.concatenate([o.ids for o in outs]),
            dists=np.concatenate([o.dists for o in outs]),
        )

    def search_by_distance(self, queries, max_distance, allow_list=None,
                           limit: int = 1024):
        res = self.search(queries, min(limit, max(1, self.count())), allow_list)
        keep = res.dists <= max_distance
        return SearchResult(ids=np.where(keep, res.ids, -1),
                            dists=np.where(keep, res.dists, np.inf))

    # -- checkpoint ----------------------------------------------------------
    def save_vectors(self, path: str, meta: Optional[dict] = None) -> bool:
        """FDE corpus via the inner store + one token file (written from
        the token-store host planes — the one host copy) — boot becomes
        O(bytes) instead of an O(corpus) re-encode through the FDE loop."""
        import os

        import msgpack

        self.inner.store.save(path, meta)
        toks, mask = self._token_store.host_planes()
        live = np.flatnonzero(mask.any(axis=1))
        tmp = path + ".tokens.tmp"
        with open(tmp, "wb") as f:
            f.write(msgpack.packb({
                "version": 2,   # tokens as the planes hold them: bfloat16
                "docs": [
                    {"d": int(d),
                     "shape": [int(mask[d].sum()), self.dims],
                     "data": toks[d][mask[d]].tobytes()}
                    for d in live
                ],
            }, use_bin_type=True))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path + ".tokens")
        return True

    def load_vectors(self, path: str) -> Optional[dict]:
        import os

        import msgpack

        meta = self.inner.store.load(path)
        if meta is None:
            return None
        tok_path = path + ".tokens"
        if not os.path.exists(tok_path):
            return None  # half a checkpoint is no checkpoint
        try:
            with open(tok_path, "rb") as f:
                d = msgpack.unpackb(f.read(), raw=False)
            if d.get("version") != 2:
                return None     # an older layout: rebuild from source
            ids = [rec["d"] for rec in d["docs"]]
            sets = [
                np.frombuffer(rec["data"], TOKEN_DTYPE).reshape(rec["shape"])
                for rec in d["docs"]
            ]
        except (OSError, ValueError, KeyError, TypeError, AttributeError):
            # torn/corrupt token sidecar: contract is "rebuild from source"
            return None
        if ids:
            # a recovered index must rerank against the SAME token sets
            # it checkpointed, not empty masks
            self._token_store.put(np.asarray(ids, np.int64), sets)
        return meta

    # -- bookkeeping ---------------------------------------------------------
    def count(self) -> int:
        return self.inner.count()

    @property
    def capacity(self) -> int:
        return self.inner.capacity

    def contains(self, doc_id: int) -> bool:
        return self.inner.contains(doc_id)

    # -- tiered residency (docs/tiering.md): the FDE corpus is the inner
    # FlatIndex, whose warm tier serves demoted searches exactly; the
    # token store for the rescore tier is host-side already. Pure
    # delegation keeps the budget ledger seeing the real HBM rent.
    @property
    def device_resident(self) -> bool:
        return self.inner.device_resident

    def hbm_bytes(self) -> int:
        return self.inner.hbm_bytes() + self._token_store.nbytes

    def host_tier_bytes(self) -> int:
        return self.inner.host_tier_bytes() + self._token_store.host_bytes

    def demote_device(self) -> int:
        # the fused rerank's token planes are HBM rent exactly like the
        # FDE corpus — demotion drops both (host copies stay exact)
        return self.inner.demote_device() + self._token_store.drop_device()

    def promote_device(self) -> int:
        gained = self.inner.promote_device()
        if gained and self.inner.store.mesh is None:
            # the fused scan+rerank path is single-chip only; mesh mode
            # serves the rescore tier from host planes — uploading the
            # token planes there would be pure HBM rent for arrays no
            # program reads
            with self._token_store.planes():
                gained += self._token_store.nbytes
        return gained

    def stats(self) -> dict:
        return {
            "type": "multivector",
            "count": self.count(),
            "fde_dim": self.encoder.fde_dim,
            "token_dims": self.dims,
            "rerank_module": self._rerank_module.name,
            "rerank_hbm_bytes": self._token_store.nbytes,
        }
