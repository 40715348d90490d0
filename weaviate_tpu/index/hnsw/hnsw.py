"""HNSW with batched TPU distance evaluation.

Reference: ``adapters/repos/db/vector/hnsw`` (``index.go:43``,
``insert.go:107`` AddBatch, ``search.go:78`` SearchByVector, ``:726`` hot
loop, ``heuristic.go:23`` neighbor selection, ``delete.go`` tombstones).

TPU-first redesign (SURVEY.md §7 slice 2): the graph and beam control flow
stay on host, but **every distance evaluation is a batched device call** —
a whole batch of queries advances through the graph in lockstep, and each
beam iteration evaluates all queries' neighbor frontiers as one gathered
``[B, width]`` distance computation (``ops.gather_distance``). The reference
instead calls a SIMD ``Distance(a, b)`` per candidate inside a scalar loop.

Construction is batched the same way: a sub-batch of inserts runs its
ef_construction searches in lockstep; the selection heuristic runs for all
nodes of a level at once — candidate-to-candidate distances come from one
padded ``[G, C, C]`` einsum (``ops.candidate_pairwise``) and the greedy
accept loop is vectorized across the G nodes. Intra-batch visibility is
restored via the batch's own pairwise block; backlink overflow pruning is
batched per level the same way.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Optional

import numpy as np

from weaviate_tpu.index.base import SearchResult, VectorIndex
from weaviate_tpu.index.hnsw.backend import QuantizedBackend, RawBackend
from weaviate_tpu.index.hnsw.graph import NO_NODE, HostGraph
from weaviate_tpu.index.store import DeviceVectorStore
from weaviate_tpu.schema.config import HNSWIndexConfig

_INF = np.float32(np.inf)

# cap on the [B, capacity] visited scratch (bool bytes)
_VISITED_BUDGET = 256 << 20


def _pow2_pad(n: int) -> int:
    return 1 << max(3, (n - 1).bit_length())


class HNSWIndex(VectorIndex):
    supports_filter_planes = True

    def __init__(
        self,
        dims: int,
        config: Optional[HNSWIndexConfig] = None,
        path: Optional[str] = None,
        store: Optional[DeviceVectorStore] = None,
    ):
        self.config = config or HNSWIndexConfig()
        self.metric = self.config.distance
        self.path = path
        # an existing store may be handed over (dynamic-index upgrade keeps
        # the corpus in HBM and only rebuilds the graph); a configured
        # quantizer swaps the whole distance tier to code space
        quant = self.config.quantizer
        if store is None and quant is not None and quant.enabled:
            raw_path = None
            tier = getattr(self.config, "raw_tier", "ram")
            if tier.startswith("disk") \
                    and getattr(self.config, "raw_path", None) is None \
                    and path:
                raw_path = os.path.join(path, f"raw{tier[4:]}.bin")
            self.backend = QuantizedBackend(dims, self.config,
                                            raw_path=raw_path)
            self.store = None
        else:
            self.backend = RawBackend(dims, self.config, store=store)
            self.store = self.backend.store
        self.dims = dims
        self.graph = HostGraph(m=self.config.max_connections)
        self._ml = 1.0 / math.log(max(2, self.config.max_connections))
        self._level_rng = np.random.default_rng(0x5EED)
        self._insert_batch = self.config.insert_batch
        self._visited: Optional[np.ndarray] = None  # [B, cap] scratch
        # Batching, not thread fan-out, is this index's throughput
        # mechanism: concurrent searches COALESCE into one lockstep walk
        # (dispatch.py); the scratch lock is the search/construction
        # exclusion point (_search_level).
        import threading

        from weaviate_tpu.index.dispatch import CoalescingDispatcher

        self._scratch_lock = threading.Lock()
        # residency epoch: bumped on every demote/promote; the dispatcher
        # keys batch grouping on it so a request enqueued against one
        # residency generation never coalesces into a batch of another
        # (a cold/warm tenant must not ride a hot tenant's device batch)
        self._residency_epoch = 0
        self._dispatch = CoalescingDispatcher(self._run_search_batch)
        if path and os.path.exists(self._snapshot_path()):
            self._load_snapshot()
        if path:
            # incremental op log: graph edits since the last condensed
            # snapshot replay on open (reference commit_logger.go +
            # startup.go); condensing == flush() + truncate
            from weaviate_tpu.index.hnsw.commitlog import HNSWCommitLog

            self._commitlog = HNSWCommitLog(
                os.path.join(path, "commitlog"))
            self._commitlog.replay_into(self.graph)
            self.graph.log = self._commitlog
        else:
            self._commitlog = None
        # device-resident graph walk (ops/device_beam.py): upper-layer
        # greedy descent + layer-0 beam fused into ONE dispatch per batch
        # instead of one per hop, filtered or not (filtered walks track
        # best-allowed-seen on device). Works for EVERY backend: the raw
        # corpus gather-scores at full precision; SQ/PQ/BQ/RQ walks
        # gather-score their HBM code planes through the same pluggable
        # scorer. Opt-in (config flag or WEAVIATE_TPU_DEVICE_BEAM=on).
        # Created AFTER snapshot load/replay: those swap self.graph, and
        # the mirror must bind the final graph object.
        self._device_beam = None
        # env > per-index config > off: on/1/true enable, any other
        # non-empty value disables (an operator who set something is not
        # overridden by the config)
        _beam_env = os.environ.get("WEAVIATE_TPU_DEVICE_BEAM", "")
        if _beam_env:
            _beam_on = _beam_env.lower() in ("on", "1", "true")
        else:
            _beam_on = bool(getattr(self.config, "device_beam", False))
        # Mesh mode: with the backend's planes row-sharded across a
        # device mesh, the fused walk runs as ONE SPMD dispatch spanning
        # every chip — per-shard subgraph walks + on-device cross-shard
        # top-k merge (docs/mesh.md). The graph is then PARTITIONED
        # (edges intra-shard only), so the mirror is the mesh variant
        # and construction routes through _insert_subbatch_mesh.
        self._mesh_partitioned = False
        if _beam_on:
            from weaviate_tpu.ops.device_beam import (
                DeviceAdjacency,
                MeshDeviceAdjacency,
            )

            mesh = getattr(self.backend, "mesh", None)
            if mesh is not None:
                if self._graph_intra_shard(mesh):
                    self._device_beam = MeshDeviceAdjacency(
                        self.graph, mesh,
                        self.backend.device_plane_capacity)
                    if self.graph.node_count:
                        # restored shard-consistent graph: elect per-shard
                        # seeds and serve it through the mesh walk
                        self._device_beam.refresh_seeds()
                        self._mesh_partitioned = True
                else:
                    # legacy GLOBAL graph under a mesh (e.g. a snapshot
                    # from a single-chip build): its edges cross shards,
                    # so the mesh walk cannot own it — keep the pre-mesh
                    # host-walk path (sharded gather kernels) instead
                    import logging

                    logging.getLogger("weaviate_tpu.hnsw").warning(
                        "graph edges cross mesh shards (single-chip "
                        "build?); mesh device beam disabled, host walk "
                        "serves this index")
                    self._device_beam = None
            else:
                self._device_beam = DeviceAdjacency(self.graph)
            if self._device_beam is not None:
                self.graph.dirty_hook = self._device_beam.mark_dirty
        # fused device rerank tier (modules/device/, docs/modules.md):
        # a frozen module scores the walk's candidates INSIDE the fused
        # dispatch against HBM-resident candidate token planes. Token
        # sets default to each vector as a 1-token set (set_tokens
        # registers real late-interaction sets); the planes pay HBM rent
        # through this index's tiering ledger like code planes do.
        self._rerank_module = None
        self._token_store = None
        rr_cfg = getattr(self.config, "rerank", None)
        if rr_cfg is not None and rr_cfg.enabled:
            from weaviate_tpu.modules.device import (
                CandidateTokenStore,
                build_device_reranker,
            )

            self._rerank_module = build_device_reranker(
                rr_cfg.module, rr_cfg.params)
            self._token_store = CandidateTokenStore(
                dims, max_tokens=rr_cfg.max_tokens,
                cap_fn=self.backend.device_plane_capacity,
                mesh=getattr(self.backend, "mesh", None))

    # ------------------------------------------------------------------
    # persistence: condensed-graph snapshot (reference commit_logger.go
    # writes op deltas + condensor.go compacts; we persist the condensed
    # form directly — vectors themselves are durable in the object store)
    # ------------------------------------------------------------------
    def _snapshot_path(self) -> str:
        return os.path.join(self.path, "graph.npz")

    def _quantizer_path(self) -> str:
        return os.path.join(self.path, "quantizer.msgpack")

    def flush(self) -> None:
        if not self.path:
            return
        os.makedirs(self.path, exist_ok=True)
        tmp = self._snapshot_path() + ".tmp.npz"
        np.savez_compressed(tmp, **self.graph.to_arrays())
        os.replace(tmp, self._snapshot_path())
        if self._commitlog is not None:
            # the snapshot condenses everything logged so far
            self._commitlog.truncate_after_snapshot()
        if self.backend.quantized and self.backend.quantizer.fitted:
            # persist trained quantizer state (codebooks/rotation/scales) so
            # recovery re-encodes with identical codes (reference persists
            # PQData/SQData/... in the commit log)
            import msgpack

            tmp = self._quantizer_path() + ".tmp"
            with open(tmp, "wb") as f:
                f.write(
                    msgpack.packb(
                        self.backend.quantizer.state_dict(), use_bin_type=True
                    )
                )
            os.replace(tmp, self._quantizer_path())

    def close(self) -> None:
        """Condense + release the commit log (crash after this point
        replays nothing)."""
        self.flush()
        if self._commitlog is not None:
            self._commitlog.close()
            self._commitlog = None
            self.graph.log = None

    def _load_snapshot(self) -> None:
        with np.load(self._snapshot_path()) as z:
            self.graph = HostGraph.from_arrays({k: z[k] for k in z.files})
        if self.backend.quantized and os.path.exists(self._quantizer_path()):
            import msgpack

            with open(self._quantizer_path(), "rb") as f:
                self.backend.quantizer.load_state_dict(
                    msgpack.unpackb(f.read(), raw=False)
                )

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _qdev(self, queries: np.ndarray):
        return self.backend.prep_queries(queries)

    def _frontier_dists(self, qdev, cand: np.ndarray) -> np.ndarray:
        """[B, C] candidate ids (-1 pad) -> [B, C] distances (inf for pads)."""
        return self.backend.frontier_dists(qdev, cand)

    def _node_dists(self, node_ids: np.ndarray, cand: np.ndarray) -> np.ndarray:
        """Distances from each node's own vector to its candidates [G, C]."""
        return self.backend.frontier_dists(
            self.backend.prep_query_ids(node_ids), cand
        )

    def _level_for_new(self, n: int) -> np.ndarray:
        u = self._level_rng.random(n)
        return np.minimum(
            (-np.log(np.maximum(u, 1e-12)) * self._ml).astype(np.int16), 30
        )

    def _mesh_mirror(self):
        """The MeshDeviceAdjacency mirror when mesh beam mode is active,
        else None."""
        from weaviate_tpu.ops.device_beam import MeshDeviceAdjacency

        beam = self._device_beam
        return beam if isinstance(beam, MeshDeviceAdjacency) else None

    def _graph_intra_shard(self, mesh) -> bool:
        """Whether every existing edge stays within one block shard of
        the backend's plane layout — the invariant the mesh walk owns.
        A restored single-chip graph fails this and keeps the host-walk
        path instead (a wrong local-index walk must be impossible)."""
        from weaviate_tpu.parallel.mesh import mesh_size, shard_of

        g = self.graph
        if g.node_count == 0:
            return True
        cap = self.backend.device_plane_capacity()
        n = mesh_size(mesh)
        gc = min(g.capacity, cap)
        src = g.layer0[:gc]
        row_shard = shard_of(np.arange(gc), cap, n)[:, None]
        if not np.all((src < 0) | (shard_of(src, cap, n) == row_shard)):
            return False
        for layer in g.upper.values():
            for node, nbrs in layer.items():
                if len(nbrs) and not np.all(
                        shard_of(np.asarray(nbrs), cap, n)
                        == shard_of(node, cap, n)):
                    return False
        return True

    # ------------------------------------------------------------------
    # batched greedy descent (upper layers, ef=1) — reference search.go:760
    # ------------------------------------------------------------------
    def _greedy_step_until_stable(self, qdev, eps: np.ndarray, level: int,
                                  active: np.ndarray) -> np.ndarray:
        cur = eps.copy()
        cur_d = self._frontier_dists(qdev, cur[:, None])[:, 0]
        live = active.copy()
        while live.any():
            nbrs = self.graph.neighbors_batch(level, cur)
            nbrs[~live] = NO_NODE
            d = self._frontier_dists(qdev, nbrs)
            j = np.argmin(d, axis=1)
            bd = d[np.arange(len(cur)), j]
            better = bd < cur_d
            upd = live & better
            cur[upd] = nbrs[np.arange(len(cur)), j][upd]
            cur_d[upd] = bd[upd]
            live = upd
        return cur

    # ------------------------------------------------------------------
    # batched beam search at one level — reference searchLayerByVector
    # (search.go:215); one device call per beam iteration for all queries
    # ------------------------------------------------------------------
    def _get_visited(self, b: int) -> np.ndarray:
        cap = self.graph.capacity
        if (
            self._visited is None
            or self._visited.shape[0] < b
            or self._visited.shape[1] < cap
        ):
            self._visited = np.zeros((b, cap), bool)
        return self._visited

    def _search_level(
        self,
        qdev,
        eps: np.ndarray,
        ef: int,
        level: int,
        keep_mask: Optional[np.ndarray] = None,
        keep_k: int = 0,
        expand: int = 0,
    ):
        """Returns (res_ids [B, ef], res_d [B, ef]) ascending, and — when
        ``keep_mask`` is given (sweeping filter strategy, search.go:36-41) —
        (kept_ids [B, keep_k], kept_d [B, keep_k]) best *allowed* nodes seen.

        The visited scratch is shared between searches (single-flight via
        the coalescing dispatcher) and construction beams — this lock
        serializes SCRATCH use only. Graph structure itself is read without
        a lock (torn-read semantics, as in the reference's lock-free reads):
        nodes linked mid-search are skipped via the scratch-width clamp in
        the expansion loop.
        """
        with self._scratch_lock:
            # graftlint: allow[blocking-under-lock] reason=scratch buffers are the shared state the walk mutates per hop; serving uses the device beam, this host walk is the annotated fallback tier
            return self._search_level_impl(qdev, eps, ef, level, keep_mask,
                                           keep_k, expand)

    def _search_level_impl(self, qdev, eps, ef, level, keep_mask=None,
                           keep_k=0, expand=0):
        b = qdev.shape[0]
        rows = np.arange(b)
        # reusable visited scratch, cleared lazily via the touched log so a
        # search costs O(touched), not O(capacity) (review finding)
        visited = self._get_visited(b)
        touched: list[tuple[np.ndarray, np.ndarray]] = []

        res_ids = np.full((b, ef), NO_NODE, np.int64)
        res_d = np.full((b, ef), _INF, np.float32)
        expanded = np.zeros((b, ef), bool)

        d0 = self._frontier_dists(qdev, eps[:, None])[:, 0]
        res_ids[:, 0] = eps
        res_d[:, 0] = d0
        visited[rows, eps] = True
        touched.append((rows.copy(), eps.astype(np.int64)))

        track_kept = keep_mask is not None and keep_k > 0
        if track_kept:
            kept_ids = np.full((b, keep_k), NO_NODE, np.int64)
            kept_d = np.full((b, keep_k), _INF, np.float32)
            seed_ok = keep_mask[eps]
            kept_ids[seed_ok, 0] = eps[seed_ok]
            kept_d[seed_ok, 0] = d0[seed_ok]

        max_iters = 4 * ef + 64  # safety bound; beam converges well before
        for _ in range(max_iters):
            cand_d = np.where(expanded | (res_ids < 0), _INF, res_d)
            j = np.argmin(cand_d, axis=1)
            cd = cand_d[rows, j]
            # stop per query when closest unexpanded is worse than the
            # current ef-th best (res_d sorted ascending, inf-padded)
            active = np.isfinite(cd) & (cd <= res_d[:, -1])
            if not active.any():
                break
            expanded[rows[active], j[active]] = True
            cur = res_ids[rows, j].astype(np.int64)
            nbrs = self.graph.neighbors_batch(level, cur).astype(np.int64)
            nbrs[~active] = NO_NODE
            # a concurrent insert may have linked nodes past this scratch's
            # width (graph reads are torn-read-tolerant); skip them — they
            # were not visible when this search started
            nbrs[nbrs >= visited.shape[1]] = NO_NODE
            rr = np.repeat(rows, nbrs.shape[1]).reshape(nbrs.shape)
            fresh = nbrs >= 0
            fresh[fresh] = ~visited[rr[fresh], nbrs[fresh]]
            nbrs = np.where(fresh, nbrs, NO_NODE)
            sel = nbrs >= 0
            if sel.any():
                visited[rr[sel], nbrs[sel]] = True
                touched.append((rr[sel], nbrs[sel]))
            nd = self._frontier_dists(qdev, nbrs)

            if track_kept and expand > 0:
                # ACORN two-hop widening — the parity oracle of the device
                # kernel's _two_hop_widen: the `expand` closest BLOCKED
                # neighbors expand through to their own adjacency rows in
                # the same step, with in-row first-occurrence dedup
                blocked_d = np.where(
                    (nbrs >= 0) & ~keep_mask[np.maximum(nbrs, 0)],
                    nd, _INF)
                psel = np.argsort(blocked_d, axis=1,
                                  kind="stable")[:, :expand]
                parents = np.take_along_axis(nbrs, psel, 1)
                pvalid = np.take_along_axis(blocked_d, psel, 1) < _INF
                hop2 = self.graph.neighbors_batch(
                    level, np.maximum(parents, 0).reshape(-1)
                ).astype(np.int64).reshape(b, parents.shape[1], -1)
                hop2[~pvalid] = NO_NODE
                hop2 = hop2.reshape(b, -1)
                eq = hop2[:, :, None] == hop2[:, None, :]
                first = (np.argmax(eq, axis=2)
                         == np.arange(hop2.shape[1])[None, :])
                hop2[~first] = NO_NODE
                hop2[hop2 >= visited.shape[1]] = NO_NODE
                rr2 = np.repeat(rows, hop2.shape[1]).reshape(hop2.shape)
                fresh2 = hop2 >= 0
                fresh2[fresh2] = ~visited[rr2[fresh2], hop2[fresh2]]
                hop2 = np.where(fresh2, hop2, NO_NODE)
                sel2 = hop2 >= 0
                if sel2.any():
                    visited[rr2[sel2], hop2[sel2]] = True
                    touched.append((rr2[sel2], hop2[sel2]))
                nd2 = self._frontier_dists(qdev, hop2)
                nbrs = np.concatenate([nbrs, hop2], axis=1)
                nd = np.concatenate([nd, nd2], axis=1)

            all_ids = np.concatenate([res_ids, nbrs], axis=1)
            all_d = np.concatenate([res_d, nd], axis=1)
            all_exp = np.concatenate(
                [expanded, np.zeros_like(nbrs, bool)], axis=1
            )
            order = np.argsort(all_d, axis=1, kind="stable")[:, :ef]
            res_ids = np.take_along_axis(all_ids, order, 1)
            res_d = np.take_along_axis(all_d, order, 1)
            expanded = np.take_along_axis(all_exp, order, 1)

            if track_kept:
                ok = (nbrs >= 0) & keep_mask[np.maximum(nbrs, 0)]
                nd_k = np.where(ok, nd, _INF)
                ka = np.concatenate([kept_ids, nbrs], axis=1)
                kd = np.concatenate([kept_d, nd_k], axis=1)
                korder = np.argsort(kd, axis=1, kind="stable")[:, :keep_k]
                kept_ids = np.take_along_axis(ka, korder, 1)
                kept_d = np.take_along_axis(kd, korder, 1)

        for r, n in touched:
            visited[r, n] = False

        if track_kept:
            kept_ids[~np.isfinite(kept_d)] = NO_NODE
            return res_ids, res_d, kept_ids, kept_d
        return res_ids, res_d

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    def add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        doc_ids = np.asarray(doc_ids, np.int64)
        vectors = np.asarray(vectors, np.float32)
        if len(doc_ids) == 0:
            return
        self.backend.put(doc_ids, vectors)
        if self._token_store is not None:
            # default token sets: the vector itself (1-token), written
            # as one [m, 1, D] block so the store takes its vectorized
            # path; callers with real late-interaction sets override
            # via set_tokens
            self._token_store.put(doc_ids, vectors[:, None, :])
        self.graph.ensure_capacity(int(doc_ids.max()) + 1)
        # a re-added tombstoned id is a fresh vector at an old id: drop the
        # stale node so it re-inserts with edges for the new vector
        revived = [int(d) for d in doc_ids if int(d) in self.graph.tombstones]
        for d in revived:
            self.graph.remove_node_hard(d)
        # skip ids already present (idempotent rebuild/recovery path)
        doc_ids = doc_ids[self.graph.levels[doc_ids] < 0]
        for start in range(0, len(doc_ids), self._insert_batch):
            self._insert_subbatch(doc_ids[start : start + self._insert_batch])
        if self._commitlog is not None:
            self._commitlog.flush_soft()
            # condense once the op window outgrows the snapshot cost
            if self._commitlog.pending_bytes > (64 << 20):
                self.flush()

    def index_existing(self) -> None:
        """Build the graph over the store's live vectors without touching the
        corpus (dynamic upgrade path — vectors never leave HBM)."""
        live = np.nonzero(self.backend.host_valid_mask)[0].astype(np.int64)
        if len(live) == 0:
            return
        self.graph.ensure_capacity(int(live.max()) + 1)
        live = live[self.graph.levels[live] < 0]
        for start in range(0, len(live), self._insert_batch):
            self._insert_subbatch(live[start : start + self._insert_batch])

    def _construction_beam_level0(self, node_ids: np.ndarray,
                                  eps: np.ndarray, efc: int):
        """Layer-0 ef_construction walks fully on device (VERDICT r3 #5):
        one dispatch per chunk instead of one per hop — the construction
        analogue of ``_device_beam_search``, for EVERY backend. Raw
        query vectors are GATHERED from the HBM corpus by id (nothing
        crosses the link per hop); quantized backends upload the chunk's
        code-space query rep once and walk the HBM code planes with the
        same pluggable scorer the search path uses. Returns (res_ids,
        res_d) ascending, or None to use the host walk (no device beam
        configured / quantizer unfitted / lowering failed — same latch
        semantics as the search path)."""
        if self._device_beam is None:
            return None
        scorer_pack = self.backend.device_scorer()
        if scorer_pack is None:
            return None  # quantizer unfitted: lifecycle, not a failure
        scorer, operands = scorer_pack
        import jax.numpy as jnp

        from weaviate_tpu.monitoring.metrics import DEVICE_BEAM_FALLBACK
        from weaviate_tpu.ops.device_beam import device_search

        mesh_mirror = self._mesh_mirror()
        try:
            adj, present = self._device_beam.sync()
            ef_pad = 1 << max(4, (int(efc) - 1).bit_length())
            outs_i, outs_d = [], []
            chunk = 256  # bounds the [chunk, capacity] visited scratch
            for s in range(0, len(node_ids), chunk):
                sub = node_ids[s:s + chunk].astype(np.int64)
                q = self.backend.beam_queries_for_ids(sub)
                sub_eps = eps[s:s + chunk].astype(np.int32)
                if len(sub) < chunk:
                    # pad the tail to the fixed chunk shape so every
                    # sub-batch reuses ONE compiled program (row 0
                    # repeats; its results are sliced off below)
                    pad = chunk - len(sub)
                    q = jnp.concatenate(
                        [q, jnp.repeat(q[:1], pad, axis=0)], axis=0)
                    sub_eps = np.concatenate(
                        [sub_eps, np.repeat(sub_eps[:1], pad)])
                if mesh_mirror is not None:
                    # ONE SPMD dispatch for the whole chunk: every shard
                    # walks all rows, but a row's entrypoint is local to
                    # exactly one shard — the others see seed -1 and
                    # exit immediately. merge=False returns the stacked
                    # per-shard results; each node takes its OWN shard's
                    # candidates (links are intra-shard by definition).
                    from weaviate_tpu.ops.device_beam import (
                        device_search_mesh,
                    )

                    ids_j, d_j = device_search_mesh(
                        scorer, q, operands, adj, present,
                        mesh_mirror.mesh, ef=ef_pad,
                        max_steps=int(4 * ef_pad + 64), fetch=ef_pad,
                        qeps=jnp.asarray(sub_eps), merge=False)
                    own = mesh_mirror.shard_of(sub)
                    # graftlint: allow[host-sync-in-hot-path] reason=per-batch beam results feed host graph linking
                    oi = np.asarray(ids_j)
                    # graftlint: allow[host-sync-in-hot-path] reason=per-batch beam results feed host graph linking
                    od = np.asarray(d_j)
                    sel = np.arange(len(sub))
                    outs_i.append(oi[own, sel].astype(np.int64))
                    outs_d.append(od[own, sel])
                else:
                    ids_j, d_j = device_search(
                        scorer, q, operands, adj, present, sub_eps,
                        ef=ef_pad, max_steps=int(4 * ef_pad + 64))
                    # graftlint: allow[host-sync-in-hot-path] reason=per-batch beam results feed host graph linking
                    oi = np.asarray(ids_j)[:len(sub)].astype(np.int64)
                    outs_i.append(oi)
                    # graftlint: allow[host-sync-in-hot-path] reason=per-batch beam results feed host graph linking
                    outs_d.append(np.asarray(d_j)[:len(sub)])
            res_ids = np.concatenate(outs_i)[:, :efc]
            res_d = np.concatenate(outs_d)[:, :efc]
            self._beam_proven = True
            return res_ids, res_d
        except Exception as e:
            import logging

            if getattr(self, "_beam_proven", False):
                DEVICE_BEAM_FALLBACK.inc(kind="construction",
                                         mode="transient")
                logging.getLogger("weaviate_tpu.hnsw").warning(
                    "construction device beam failed (transient, host "
                    "walk for this sub-batch): %s", e)
            else:
                DEVICE_BEAM_FALLBACK.inc(kind="construction", mode="latched")
                logging.getLogger("weaviate_tpu.hnsw").warning(
                    "device beam disabled after construction failure: %s", e)
                self.graph.dirty_hook = None
                self._device_beam = None
            return None

    def _insert_subbatch(self, ids: np.ndarray) -> None:
        if len(ids) == 0:
            return
        if self._mesh_mirror() is not None:
            return self._insert_subbatch_mesh(ids)
        levels = self._level_for_new(len(ids))
        if self.graph.entrypoint == NO_NODE:
            self.graph.add_node(int(ids[0]), int(levels[0]))
            ids, levels = ids[1:], levels[1:]
            if len(ids) == 0:
                return
        b = len(ids)
        qdev = self.backend.prep_query_ids(ids)
        eps = np.full(b, self.graph.entrypoint, np.int64)
        efc = self.config.ef_construction
        old_max = self.graph.max_level
        batch_max = max(old_max, int(levels.max()))

        # lockstep layer walk: greedy descent while level > node level,
        # ef_construction search at levels <= node level. Levels above the
        # pre-batch max have no existing nodes — link_plan still gets an
        # entry so same-batch peers connect there (review finding).
        link_plan: list[tuple[int, np.ndarray, np.ndarray, np.ndarray]] = []
        for level in range(batch_max, -1, -1):
            search = levels >= level
            if level <= old_max:
                descend = ~search
                if descend.any():
                    eps[descend] = self._greedy_step_until_stable(
                        qdev, eps, level, descend
                    )[descend]
                if search.any():
                    sub = np.nonzero(search)[0]
                    res = (self._construction_beam_level0(
                        ids[sub], eps[sub], efc) if level == 0 else None)
                    if res is None:
                        res = self._search_level(
                            self.backend.take_queries(qdev, sub), eps[sub],
                            efc, level)
                    res_ids, res_d = res
                    eps[sub] = res_ids[:, 0]
                    link_plan.append((level, sub, res_ids, res_d))
            elif search.any():
                sub = np.nonzero(search)[0]
                empty = np.empty((len(sub), 0))
                link_plan.append(
                    (level, sub, empty.astype(np.int64), empty.astype(np.float32))
                )

        # register nodes (marks them visible; edges come next)
        for i, node in enumerate(ids):
            self.graph.add_node(int(node), int(levels[i]))

        # intra-batch candidates: batch-to-batch pairwise distances restore
        # visibility between nodes inserted in the same lockstep sub-batch
        bb = self.backend.pairwise(ids[None, :])[0]

        for level, sub, res_ids, res_d in link_plan:
            self._link_level(level, ids, levels, sub, res_ids, res_d, bb)

    def _insert_subbatch_mesh(self, ids: np.ndarray) -> None:
        """Lockstep insert for the PARTITIONED (mesh) graph: every node
        links only within its block shard, seeded at its shard's
        entrypoints, so each shard grows an independent subgraph the
        SPMD walk can traverse in pure local index space. The layer-0
        ef_construction walks still run as ONE mesh dispatch per chunk
        (``_construction_beam_level0``) — per-shard host loops are
        exactly the anti-pattern graftlint's host-loop-over-mesh bans."""
        mirror = self._device_beam
        levels = self._level_for_new(len(ids))
        shard = mirror.shard_of(np.asarray(ids, np.int64))
        # bootstrap: the first node of a seedless shard becomes its seed
        boot = []
        for i, node in enumerate(ids):
            if not mirror.has_seed(int(shard[i])):
                self.graph.add_node(int(node), int(levels[i]))
                mirror.add_seed(int(node))
                boot.append(i)
        if boot:
            keep = np.setdiff1d(np.arange(len(ids)), np.asarray(boot))
            ids, levels, shard = ids[keep], levels[keep], shard[keep]
        self._mesh_partitioned = True
        if len(ids) == 0:
            return
        b = len(ids)
        qdev = self.backend.prep_query_ids(ids)
        eps = np.empty(b, np.int64)
        shard_max = np.empty(b, np.int64)
        for i in range(b):
            sd = mirror.primary_seed(int(shard[i]))
            eps[i] = sd
            shard_max[i] = int(self.graph.levels[sd]) if sd >= 0 else -1
        efc = self.config.ef_construction
        batch_max = int(max(int(levels.max()), int(shard_max.max())))

        link_plan: list[tuple[int, np.ndarray, np.ndarray, np.ndarray]] = []
        for level in range(batch_max, -1, -1):
            # a shard's seed is its highest-level node, so it exists at
            # every level the shard has — descent/search never step onto
            # a level the shard's subgraph lacks
            exists = shard_max >= level
            search = levels >= level
            descend = exists & ~search
            if descend.any():
                eps[descend] = self._greedy_step_until_stable(
                    qdev, eps, level, descend)[descend]
            active = search & exists
            if active.any():
                sub = np.nonzero(active)[0]
                res = (self._construction_beam_level0(
                    ids[sub], eps[sub], efc) if level == 0 else None)
                if res is None:
                    res = self._search_level(
                        self.backend.take_queries(qdev, sub), eps[sub],
                        efc, level)
                res_ids, res_d = res
                eps[sub] = np.where(res_ids[:, 0] >= 0, res_ids[:, 0],
                                    eps[sub])
                link_plan.append((level, sub, res_ids, res_d))
            lonely = search & ~exists
            if lonely.any():
                # levels above the shard's current max: same-shard batch
                # peers are the only candidates
                sub = np.nonzero(lonely)[0]
                empty = np.empty((len(sub), 0))
                link_plan.append(
                    (level, sub, empty.astype(np.int64),
                     empty.astype(np.float32)))

        for i, node in enumerate(ids):
            self.graph.add_node(int(node), int(levels[i]))
            if int(levels[i]) > int(shard_max[i]):
                # new shard-top node: future descents start here
                mirror.add_seed(int(node))

        bb = self.backend.pairwise(ids[None, :])[0]
        for level, sub, res_ids, res_d in link_plan:
            self._link_level(level, ids, levels, sub, res_ids, res_d, bb,
                             peer_shard=shard)

    def _link_level(self, level, ids, levels, sub, res_ids, res_d, bb,
                    peer_shard=None) -> None:
        width = self.graph.width(level)
        b = len(ids)
        g = len(sub)
        peer_ok = levels >= level

        # candidate matrix: search results + same-batch peers at this level
        cmax = res_ids.shape[1] + b
        cand = np.full((g, cmax), NO_NODE, np.int64)
        cd = np.full((g, cmax), _INF, np.float32)
        cand[:, : res_ids.shape[1]] = res_ids
        cd[:, : res_d.shape[1]] = res_d
        for row, i in enumerate(sub):
            ok = peer_ok & (np.arange(b) != i)
            if peer_shard is not None:
                # partitioned graph: only same-shard peers may link
                ok &= peer_shard == peer_shard[i]
            peers = np.nonzero(ok)[0]
            if len(peers):
                cand[row, res_ids.shape[1] : res_ids.shape[1] + len(peers)] = ids[peers]
                cd[row, res_ids.shape[1] : res_ids.shape[1] + len(peers)] = bb[i, peers]

        sels = self._select_heuristic_batch(cand, cd, width)
        backlinks: dict[int, list[int]] = {}
        for row, i in enumerate(sub):
            node = int(ids[i])
            self.graph.set_neighbors(level, node, sels[row])
            for nbr in sels[row]:
                backlinks.setdefault(int(nbr), []).append(node)

        # apply backlinks; batch-prune overflowing nodes with the heuristic
        over_nodes: list[int] = []
        over_cands: list[np.ndarray] = []
        for nbr, new in backlinks.items():
            cur = self.graph.get_neighbors(level, nbr)
            cur_set = set(int(c) for c in cur)
            new = [x for x in dict.fromkeys(new) if x not in cur_set]
            if not new:
                continue
            if len(cur) + len(new) <= width:
                for x in new:
                    self.graph.append_neighbor(level, nbr, x)
            else:
                over_nodes.append(nbr)
                over_cands.append(
                    np.unique(np.concatenate([cur, np.asarray(new, np.int32)]))
                )
        if over_nodes:
            go = len(over_nodes)
            cmax2 = max(len(c) for c in over_cands)
            cand2 = np.full((go, cmax2), NO_NODE, np.int64)
            for r, c in enumerate(over_cands):
                cand2[r, : len(c)] = c
            cd2 = self._node_dists(np.asarray(over_nodes, np.int64), cand2)
            sels2 = self._select_heuristic_batch(cand2, cd2, width)
            for r, node in enumerate(over_nodes):
                self.graph.set_neighbors(level, node, sels2[r])

    def _select_heuristic_batch(
        self, cand_ids: np.ndarray, cand_d: np.ndarray, m: int
    ) -> list[np.ndarray]:
        """Vectorized greedy diversity heuristic (reference heuristic.go:23):
        iterate candidates by ascending distance; keep c iff
        dist(c, q) < dist(c, s) for every already-selected s. One padded
        [G, C, C] einsum provides all candidate-to-candidate distances.
        """
        g, c_in = cand_ids.shape
        if g == 0 or c_in == 0:
            return [np.empty(0, np.int32) for _ in range(g)]
        # sort by distance, cap candidate width (nearest candidates dominate
        # heuristic selections), pad rows to pow2 to bound jit shape count
        c_cap = min(c_in, max(3 * m, 96))
        order = np.argsort(cand_d, axis=1, kind="stable")[:, :c_cap]
        ids_s = np.take_along_axis(cand_ids, order, 1)
        d_s = np.take_along_axis(cand_d, order, 1)
        c_pad = _pow2_pad(c_cap)
        g_pad = _pow2_pad(g)
        ids_p = np.full((g_pad, c_pad), 0, np.int64)  # clipped pads
        d_p = np.full((g_pad, c_pad), _INF, np.float32)
        ids_p[:g, :c_cap] = np.maximum(ids_s, 0)
        d_p[:g, :c_cap] = np.where(ids_s >= 0, d_s, _INF)

        pair = self.backend.pairwise(ids_p)
        rows = np.arange(g_pad)
        chosen = np.zeros((g_pad, c_pad), bool)
        min_to_sel = np.full((g_pad, c_pad), _INF, np.float32)
        for _ in range(m):
            elig = (d_p < min_to_sel) & ~chosen & np.isfinite(d_p)
            pick = np.argmin(np.where(elig, d_p, _INF), axis=1)
            ok = elig[rows, pick]
            if not ok.any():
                break
            okr = rows[ok]
            chosen[okr, pick[ok]] = True
            upd = pair[okr, :, pick[ok]]  # dist of every cand to the new pick
            min_to_sel[okr] = np.minimum(min_to_sel[okr], upd)
        out = []
        for r in range(g):
            sel_cols = np.nonzero(chosen[r])[0]
            out.append(ids_s[r][sel_cols[sel_cols < c_cap]].astype(np.int32))
        return out

    # ------------------------------------------------------------------
    # deletes — tombstone semantics (reference delete.go): deleted nodes
    # stay traversable (their edges keep the graph connected) but are
    # excluded from results; cleanup_tombstones() rewires + drops them
    # (reference tombstone cleanup cycle, maintenance.go)
    # ------------------------------------------------------------------
    def delete(self, doc_ids: np.ndarray) -> None:
        doc_ids = np.asarray(doc_ids, np.int64)
        self.backend.delete(doc_ids)
        if self._token_store is not None:
            self._token_store.delete(doc_ids)
        for d in doc_ids:
            self.graph.add_tombstone(int(d))
        if self._commitlog is not None:
            self._commitlog.flush_soft()

    def set_tokens(self, doc_ids: np.ndarray, token_sets: list) -> None:
        """Register late-interaction token sets for the rerank tier
        (overrides the 1-token default add_batch stores). Requires a
        configured rerank module."""
        if self._token_store is None:
            raise ValueError(
                "set_tokens requires a rerank module configured on this "
                "index (HNSWIndexConfig.rerank)")
        self._token_store.put(np.asarray(doc_ids, np.int64), token_sets)

    def cleanup_tombstones(self) -> int:
        """Rewire edges around tombstoned nodes, then drop them.

        For every live node with a dead neighbor, the dead neighbor is
        replaced by bridging to the dead node's own live neighbors, with the
        diversity heuristic re-selecting when over width.
        Returns the number of nodes removed.
        """
        dead = self.graph.tombstones
        if not dead:
            return 0
        for level in range(self.graph.max_level, -1, -1):
            if level == 0:
                nodes = np.nonzero(self.graph.levels >= 0)[0]
            else:
                nodes = np.asarray(list(self.graph.upper.get(level, {})), np.int64)
            width = self.graph.width(level)
            rewire_nodes: list[int] = []
            rewire_cands: list[np.ndarray] = []
            for node in nodes:
                node = int(node)
                if node in dead:
                    continue
                nbrs = self.graph.get_neighbors(level, node)
                dead_mask = np.asarray([int(n) in dead for n in nbrs])
                if not dead_mask.any():
                    continue
                keep = [int(n) for n in nbrs[~dead_mask]]
                bridge: set[int] = set()
                for dn in nbrs[dead_mask]:
                    for x in self.graph.get_neighbors(level, int(dn)):
                        x = int(x)
                        if x not in dead and x != node:
                            bridge.add(x)
                cand = np.asarray(sorted(set(keep) | bridge), np.int64)
                if len(cand) <= width:
                    self.graph.set_neighbors(level, node, cand)
                else:
                    rewire_nodes.append(node)
                    rewire_cands.append(cand)
            if rewire_nodes:
                cmax = max(len(c) for c in rewire_cands)
                cm = np.full((len(rewire_nodes), cmax), -1, np.int64)
                for r, c in enumerate(rewire_cands):
                    cm[r, : len(c)] = c
                cd = self._node_dists(np.asarray(rewire_nodes, np.int64), cm)
                sels = self._select_heuristic_batch(cm, cd, width)
                for r, node in enumerate(rewire_nodes):
                    self.graph.set_neighbors(level, node, sels[r])
        removed = len(dead)
        for dn in sorted(dead):
            self.graph.remove_node_hard(dn)
        mirror = self._mesh_mirror()
        if mirror is not None:
            # a hard-removed node may have been a shard seed: drop it and
            # re-elect so every populated shard stays walkable
            mirror.refresh_seeds()
        return removed

    # ------------------------------------------------------------------
    # search
    # ------------------------------------------------------------------
    def _dynamic_ef(self, k: int) -> int:
        ef = self.config.ef
        if ef > 0:
            return max(ef, k)
        ef = k * self.config.dynamic_ef_factor
        ef = min(max(ef, self.config.dynamic_ef_min), self.config.dynamic_ef_max)
        return max(ef, k)

    def search(
        self,
        queries: np.ndarray,
        k: int,
        allow_list: Optional[np.ndarray] = None,
        rerank=None,
        est_selectivity: Optional[float] = None,
    ) -> SearchResult:
        # a tiering demote/promote between the residency check and the
        # array access (here, in the dispatcher's leader, or in the host
        # tier) surfaces as ResidencyMoved: re-route, never fail — the
        # retry re-enqueues under the NEW residency epoch's tier_key.
        # ``allow_list`` is an ndarray mask OR a resident FilterPlane
        # (query/planner/planes.py); ``est_selectivity`` is the inverted
        # index's sketch estimate, surfaced on the plan's trace span.
        from weaviate_tpu.index.base import run_tier_stable

        if rerank is not None and self._token_store is None:
            raise ValueError(
                "rerank requested but no rerank module is configured on "
                "this index (HNSWIndexConfig.rerank)")
        return run_tier_stable(
            lambda: self._search_tiered(queries, k, allow_list, rerank,
                                        est_selectivity))

    def _allow_host(self, allow_list):
        """Resolve a resident FilterPlane to its host bitmap; ad-hoc
        ndarray masks (and None) pass through untouched."""
        if allow_list is not None \
                and getattr(allow_list, "plane_id", None) is not None:
            return allow_list.mask(self.graph.capacity)
        return allow_list

    def _allow_popcount(self, allow_list) -> int:
        """Allowed count over PRESENT rows only: a capacity-sized mask's
        padding tail must not count, or selectivity inflates past 1.0
        and the planner mistakes a real filter for a no-op."""
        if getattr(allow_list, "plane_id", None) is not None:
            return allow_list.count()
        a = np.asarray(allow_list, bool)
        m = min(len(a), len(self.graph.levels))
        return int(np.count_nonzero(a[:m] & (self.graph.levels[:m] >= 0)))

    def _fetch_width(self, k: int, ef: int) -> int:
        """THE over-fetch policy (reference hnsw/search.go:184
        shouldRescore): the candidate pool width the rescore tier AND
        the rerank stage promote from — one owner, so the device walk,
        host-walk fallback, and rerank pools can never silently
        diverge."""
        fetch = max(k, min(ef, 2 * k))
        if self.backend.quantized:
            rl = getattr(self.backend.quantizer.config, "rescore_limit", 0)
            fetch = min(ef, max(fetch, rl, 2 * k))
        return fetch

    def _host_rerank_topk(self, rerank_batch, cand_ids: np.ndarray,
                          k: int, reason: str
                          ) -> tuple[np.ndarray, np.ndarray]:
        """Host fallback tier for the rerank stage: score the candidate
        pool against the token store's HOST planes with the module's
        numpy twin. Latches LOUDLY — counter + span event — never
        silently (acceptance contract, docs/modules.md)."""
        from weaviate_tpu.monitoring import tracing
        from weaviate_tpu.monitoring.metrics import (
            RERANK_FALLBACK,
            RERANK_REQUESTS,
        )

        module, rq, rqm = rerank_batch
        name = getattr(module, "name", type(module).__name__)
        RERANK_REQUESTS.inc(module=name, tier="host")
        RERANK_FALLBACK.inc(module=name, reason=reason)
        tracing.add_event("rerank.fallback", module=name, reason=reason)
        toks, mask = self._token_store.host_planes()
        cand_ids = np.asarray(cand_ids, np.int64)
        inside = (cand_ids >= 0) & (cand_ids < toks.shape[0])
        safe = np.clip(cand_ids, 0, toks.shape[0] - 1)
        ct = toks[safe]
        cm = mask[safe] & inside[:, :, None]
        scores = module.host_score(rq, rqm, ct, cm)
        scores = np.where(inside, scores, -np.inf)
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        ids = np.take_along_axis(cand_ids, order, axis=1)
        s = np.take_along_axis(scores, order, axis=1)
        ids = np.where(np.isfinite(s), ids, -1)
        d = np.where(np.isfinite(s), -s, _INF).astype(np.float32)
        if ids.shape[1] < k:
            pad = k - ids.shape[1]
            ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
            d = np.pad(d, ((0, 0), (0, pad)), constant_values=_INF)
        return ids.astype(np.int64), d

    def _search_tiered(
        self,
        queries: np.ndarray,
        k: int,
        allow_list: Optional[np.ndarray] = None,
        rerank=None,
        est_selectivity: Optional[float] = None,
    ) -> SearchResult:
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        if queries.shape[-1] != self.backend.dims:
            raise ValueError(
                f"query dims {queries.shape[-1]} != index dims {self.backend.dims}"
            )
        b = queries.shape[0]
        if self.graph.entrypoint == NO_NODE:
            return SearchResult(
                ids=np.full((b, k), -1, np.int64),
                dists=np.full((b, k), _INF, np.float32),
            )

        if not self.backend.device_resident:
            # WARM tier (tiering/): arrays are demoted to host RAM — the
            # exact host pass serves the query without entering the
            # device dispatcher, so a demoted tenant can never occupy a
            # hot tenant's batch slot (or re-rent HBM per query). The
            # span makes the tier visible per-request: a latency cliff
            # that is "tenant went warm" reads directly off the trace
            from weaviate_tpu.monitoring.tracing import TRACER

            with TRACER.span("tiering.host_search", rows=b, k=k):
                allow_host = self._allow_host(allow_list)
                if rerank is not None:
                    fetch = self._fetch_width(k, self._dynamic_ef(k))
                    _, ids = self.backend.host_topk(
                        queries, fetch, allow_host)
                    ids, d = self._host_rerank_topk(
                        rerank.batch_for(queries), ids, k, "warm_tier")
                else:
                    d, ids = self.backend.host_topk(queries, k, allow_host)
            return SearchResult(ids=ids, dists=d)

        # batch-group key: residency epoch PLUS the mesh mirror's
        # membership epoch — a request enqueued before an integer-factor
        # growth re-sharded the planes must never coalesce into a batch
        # whose local-index layout belongs to the new generation — PLUS
        # the prewarm isolation token (None for live traffic): a
        # synthetic lattice batch coalescing with a user query would
        # compile a bigger bucket nobody planned and drag that query's
        # latency through it (utils/prewarm.py)
        from weaviate_tpu.utils.prewarm import isolation_key

        tier_key = (self._residency_epoch,
                    getattr(self._device_beam, "epoch", 0),
                    isolation_key())

        # Filtered-search triage is the COST-BASED PLANNER's call
        # (query/planner/cost.py): pure ``plan()`` races the exact
        # masked flat scan (reference SWEEPING + flat cutoff,
        # flat_search.go:28) against the filter-aware beam (ACORN-style
        # two-hop expansion through blocked neighbors) and the
        # over-fetch-post-filter route, from the allowlist popcount —
        # exact here; the inverted index's sketch estimate rides along
        # as a trace attribute. The legacy cutoff knobs remain hard
        # guards INSIDE the planner, so sub-cutoff filters take the
        # one-dispatch masked-matmul exactly as before.
        if allow_list is not None:
            from weaviate_tpu.monitoring import tracing
            from weaviate_tpu.monitoring.metrics import PLANNER_PLANS
            from weaviate_tpu.query.planner import (
                PLAN_EXACT,
                PLAN_OVERFETCH,
                PlanStats,
                plan,
            )

            plane = (allow_list if getattr(allow_list, "plane_id", None)
                     is not None else None)
            n_allowed = self._allow_popcount(allow_list)
            live = max(1, self.count())
            stats = PlanStats(
                live=live, k=k, ef=self._dynamic_ef(k),
                selectivity=n_allowed / live, exact_count=True,
                plane_resident=plane is not None,
                flat_cutoff=self.config.flat_search_cutoff,
                flat_selectivity=self.config.filter_flat_selectivity,
                graph_degree=self.config.max_connections,
                mesh=self._mesh_partitioned)
            chosen = plan(stats)
            PLANNER_PLANS.inc(plan=chosen.plan_type)
            attrs = chosen.trace_attrs()
            if est_selectivity is not None:
                attrs["planner.sketch_selectivity"] = round(
                    float(est_selectivity), 6)
            if plane is not None:
                attrs["planner.plane"] = plane.plane_id
            tracing.annotate(**attrs)
            if chosen.plan_type == PLAN_EXACT:
                allow_host = self._allow_host(allow_list)
                if rerank is not None:
                    fetch = self._fetch_width(k, self._dynamic_ef(k))
                    _, ids = self.backend.flat_topk(
                        queries, fetch, allow_host)
                    ids, d = self._host_rerank_topk(
                        rerank.batch_for(queries), ids, k, "flat_triage")
                    return SearchResult(ids=ids, dists=d)
                return self._flat_filtered(queries, k, allow_host)
            if chosen.plan_type == PLAN_OVERFETCH and rerank is None:
                # over-fetch the UNFILTERED walk — it coalesces with
                # plain traffic at fetch_k — then post-filter on host;
                # the planner only picks this when selectivity is mild
                # enough that fetch_k stays bounded
                ids, d = self._dispatch.search(
                    queries, chosen.fetch_k, None, tier_key=tier_key)
                al = np.asarray(self._allow_host(allow_list), bool)
                ok = ((ids >= 0) & (ids < len(al))
                      & al[np.clip(ids, 0, len(al) - 1)])
                d = np.where(ok, d, _INF)
                ids = np.where(ok, ids, -1)
                order = np.argsort(d, axis=1, kind="stable")[:, :k]
                return SearchResult(
                    ids=np.take_along_axis(ids, order, axis=1),
                    dists=np.take_along_axis(d, order, axis=1))
            # PLAN_BEAM (and over-fetch under rerank, which degenerates
            # to the filtered beam — the fused rerank stage needs the
            # mask on device): the plane/mask rides the dispatch below;
            # the batch leader re-derives the expansion budget from the
            # same popcount, so every coalesced member agrees with the
            # plan made here

        ids, d = self._dispatch.search(
            queries, k, allow_list, tier_key=tier_key, rerank=rerank)
        return SearchResult(ids=ids, dists=d)

    def _run_search_batch(self, queries: np.ndarray, k: int, allow_list,
                          rerank=None):
        """Single-flight batch runner behind the coalescing dispatcher.
        ``rerank``: (module, q_tokens [B, Tq, D], q_mask) concatenated by
        the leader across the coalesced group, or None."""
        if not self.backend.device_resident:
            # a demotion landed while this group was queued: the leader
            # re-routes the whole batch to the warm host tier instead of
            # touching (now-detached) device arrays
            allow_host = self._allow_host(allow_list)
            if rerank is not None:
                fetch = self._fetch_width(k, self._dynamic_ef(k))
                _, ids = self.backend.host_topk(queries, fetch, allow_host)
                return self._host_rerank_topk(rerank, ids, k, "warm_tier")
            d, ids = self.backend.host_topk(queries, k, allow_host)
            return ids, d
        b = queries.shape[0]
        # visited scratch is [B, capacity]; bound its footprint
        sub_b = max(8, min(64, _VISITED_BUDGET // max(1, self.graph.capacity)))
        out_ids = np.full((b, k), -1, np.int64)
        out_d = np.full((b, k), _INF, np.float32)
        for s in range(0, b, sub_b):
            e = min(b, s + sub_b)
            sub_rr = rerank
            if rerank is not None and (s or e < b):
                sub_rr = (rerank[0], rerank[1][s:e], rerank[2][s:e])
            ids, d = self._search_one_batch(queries[s:e], k, allow_list,
                                            rerank=sub_rr)
            out_ids[s:e], out_d[s:e] = ids, d
        return out_ids, out_d

    def _keep_mask(self, allow_list: Optional[np.ndarray]) -> np.ndarray:
        cap = self.graph.capacity
        valid = self.backend.host_valid_mask
        if len(valid) < cap:
            valid = np.pad(valid, (0, cap - len(valid)))
        keep = valid[:cap] & (self.graph.levels >= 0)
        allow_list = self._allow_host(allow_list)
        if allow_list is not None:
            al = np.asarray(allow_list, bool)
            if len(al) < cap:
                al = np.pad(al, (0, cap - len(al)))
            keep &= al[:cap]
        return keep

    def _search_one_batch(self, queries, k, allow_list, rerank=None):
        b = queries.shape[0]
        qdev = self._qdev(queries)
        ef = self._dynamic_ef(k)
        # the leader re-derives the filtered beam's two-hop expansion
        # budget from the group's mask (deterministic in the popcount,
        # so it matches the plan each member was routed under — a plane
        # coalesces only with itself, an ad-hoc mask only with byte-
        # equal masks, hence ONE budget per batch)
        expand = 0
        if allow_list is not None:
            from weaviate_tpu.query.planner import expansion_budget

            n_allowed = self._allow_popcount(allow_list)
            expand = expansion_budget(n_allowed / max(1, self.count()))
        if self._device_beam is not None:
            # fused walk: greedy descent + layer-0 beam in ONE dispatch
            # (the host per-level loop below is the fallback tier)
            out = self._device_beam_search(queries, qdev, ef, k, allow_list,
                                           rerank=rerank, expand=expand)
            if out is not None:
                return out
        if self._mesh_partitioned:
            # a PARTITIONED graph has no global walk: the host beam from
            # one entrypoint would explore a single shard's subgraph and
            # silently drop 7/8ths of the corpus. The correct fallback
            # (mesh kernel unavailable / unfitted quantizer / latched)
            # is the exact sharded flat scan — still one dispatch.
            if rerank is not None:
                fetch = self._fetch_width(k, ef)
                _, ids = self.backend.flat_topk(
                    queries, fetch, self._allow_host(allow_list))
                return self._host_rerank_topk(rerank, ids, k, "host_walk")
            d, ids = self.backend.flat_topk(
                queries, k, self._allow_host(allow_list))
            return ids, d
        eps = np.full(b, self.graph.entrypoint, np.int64)
        all_active = np.ones(b, bool)
        for level in range(self.graph.max_level, 0, -1):
            eps = self._greedy_step_until_stable(qdev, eps, level, all_active)
        keep = self._keep_mask(allow_list)
        # over-fetch so the exact rescore tier has candidates to promote
        # (reference hnsw/search.go:184 shouldRescore); ONE owner of the
        # policy — the device walk and rerank pool use the same width
        keep_k = self._fetch_width(k, ef)
        _, _, kept_ids, kept_d = self._search_level(
            qdev, eps, ef, 0, keep_mask=keep, keep_k=keep_k, expand=expand
        )
        if rerank is not None:
            # host-walk fallback: the kept candidates feed the module's
            # numpy twin instead of the fused stage
            return self._host_rerank_topk(rerank, kept_ids, k, "host_walk")
        return self.backend.rescore_topk(queries, kept_ids, kept_d, k)

    def _device_beam_search(self, queries, qdev, ef, k, allow_list=None,
                            rerank=None, expand: int = 0):
        """Full entrypoint→layer-0 walk in ONE device dispatch: the fused
        kernel runs the upper-layer greedy descent AND the layer-0 beam
        (``ops/device_beam.py``), gather-scoring the backend's HBM arrays
        — raw corpus or SQ/PQ/BQ/RQ code planes — through its pluggable
        scorer. The host then filters tombstoned/deleted ids out of the
        returned beam (sweeping semantics) and runs the backend's rescore
        tier (identity for raw; exact over originals for quantized). With
        a filter, the device additionally tracks the best ALLOWED nodes
        seen along the unchanged walk (ACORN-style connectivity through
        disallowed nodes; still a single dispatch)."""
        from weaviate_tpu.monitoring.metrics import DEVICE_BEAM_FALLBACK
        from weaviate_tpu.ops.device_beam import device_search

        scorer_pack = self.backend.device_scorer()
        if scorer_pack is None:
            return None  # quantizer unfitted: lifecycle, not a failure
        scorer, operands = scorer_pack
        q = self.backend.beam_queries(qdev)
        if q is None:
            return None
        # over-fetch width for the rescore tier (reference
        # hnsw/search.go:184 shouldRescore): raw distances are exact so
        # k suffices; code-space walks promote from a wider candidate
        # set — same policy owner as the host walk and rerank pool
        fetch = self._fetch_width(k, ef)
        mesh_mirror = self._mesh_mirror()
        rr_name = ""  # set for real below; the except path may read it
        planes_held = contextlib.ExitStack()   # the rerank token planes
        try:
            import jax.numpy as jnp

            adj, present = self._device_beam.sync()
            upper_adj, upper_slots = self._device_beam.sync_upper()
            b = q.shape[0]
            # bucket ef AND the batch to powers of two so a workload
            # mixing k values / batch sizes shares a handful of
            # while_loop compiles instead of one per distinct shape
            # (the beam tolerates extra -1/MASK width; padded rows
            # repeat row 0 and are sliced off after the fetch)
            ef_pad = 1 << max(4, (int(ef) - 1).bit_length())
            b_pad = 1 << max(3, (b - 1).bit_length())  # b: python int shape
            if b_pad != b:
                q = jnp.concatenate(
                    [q, jnp.repeat(q[:1], b_pad - b, axis=0)], axis=0)
            cap = int(adj.shape[0])
            al_pad = None
            plane = (allow_list if getattr(allow_list, "plane_id", None)
                     is not None else None)
            if allow_list is not None:
                al = (plane.mask(cap) if plane is not None
                      else np.asarray(allow_list, bool))
                if len(al) < cap:
                    al = np.pad(al, (0, cap - len(al)))
                al_pad = al[:cap]
            fetch_pad = min(ef_pad, 1 << max(3, (int(fetch) - 1).bit_length()))
            rr_args: dict = {}
            rr_name = ""
            if rerank is not None:
                # fused rerank stage: candidate token planes ride the
                # same dispatch; query token sets pad like the queries
                module, rq, rqm = rerank
                rr_name = getattr(module, "name", type(module).__name__)
                # held until the walk is enqueued: a later feed of the
                # planes donates them (modules/device/store.py)
                toks, tmask = planes_held.enter_context(
                    self._token_store.planes(min_rows=cap))
                if b_pad != b:
                    rq = np.concatenate(
                        [rq, np.repeat(rq[:1], b_pad - b, axis=0)])
                    rqm = np.concatenate(
                        [rqm, np.repeat(rqm[:1], b_pad - b, axis=0)])
                rr_args = dict(rerank=module, rerank_k=fetch_pad,
                               rerank_q=jnp.asarray(rq),
                               rerank_qmask=jnp.asarray(rqm),
                               rerank_tokens=toks, rerank_tmask=tmask)
            import time as _time

            t_dev = _time.perf_counter()
            if mesh_mirror is not None:
                # ONE SPMD dispatch spanning the whole mesh: per-shard
                # walk from the shard's seed table + on-device
                # cross-shard top-k merge (docs/mesh.md)
                import jax

                from jax.sharding import NamedSharding, PartitionSpec as P

                from weaviate_tpu.ops.device_beam import device_search_mesh
                from weaviate_tpu.parallel.mesh import SHARD_AXIS

                seeds = mesh_mirror.sync_seeds()
                if al_pad is not None:
                    # a resident plane's device mirror is cached inside
                    # the plane (keyed by version + mutation counter +
                    # sharding), so repeat queries through a hot
                    # predicate re-upload NOTHING; ad-hoc masks pay the
                    # device_put per miss as before
                    shard_spec = NamedSharding(mesh_mirror.mesh,
                                               P(SHARD_AXIS))
                    if plane is not None:
                        allow_j = plane.device_mask(cap, shard_spec)
                    else:
                        allow_j = jax.device_put(al_pad, shard_spec)
                    out = device_search_mesh(
                        scorer, q, operands, adj, present,
                        mesh_mirror.mesh, ef=ef_pad,
                        max_steps=int(4 * ef_pad + 64), fetch=fetch_pad,
                        seeds=seeds, upper_adj=upper_adj,
                        upper_slots=upper_slots, allow=allow_j,
                        keep_k=fetch_pad, expand=expand, **rr_args)
                    # with rerank the mesh merge ranks by module score
                    # and returns just (ids, neg_scores); unfused
                    # filtered walks return the 4-tuple kept track
                    ids, d = out if len(out) == 2 else out[2:]
                else:
                    ids, d = device_search_mesh(
                        scorer, q, operands, adj, present,
                        mesh_mirror.mesh, ef=ef_pad,
                        max_steps=int(4 * ef_pad + 64), fetch=fetch_pad,
                        seeds=seeds, upper_adj=upper_adj,
                        upper_slots=upper_slots, **rr_args)
            elif al_pad is not None:
                eps = np.full(b_pad, self.graph.entrypoint, np.int32)
                allow_j = (plane.device_mask(cap) if plane is not None
                           else jnp.asarray(al_pad))
                out = device_search(
                    scorer, q, operands, adj, present, eps,
                    ef=ef_pad, max_steps=int(4 * ef_pad + 64),
                    upper_adj=upper_adj, upper_slots=upper_slots,
                    allow=allow_j, keep_k=fetch_pad, expand=expand,
                    **rr_args,
                )
                ids, d = out[2:]
            else:
                eps = np.full(b_pad, self.graph.entrypoint, np.int32)
                out = device_search(
                    scorer, q, operands, adj, present, eps,
                    ef=ef_pad, max_steps=int(4 * ef_pad + 64),
                    upper_adj=upper_adj, upper_slots=upper_slots,
                    **rr_args,
                )
                ids, d = out if len(out) == 2 else out[2:]
            planes_held.close()
            # graftlint: allow[host-sync-in-hot-path] reason=final beam materialization
            ids = np.asarray(ids)[:b].astype(np.int64)
            # graftlint: allow[host-sync-in-hot-path] reason=final beam materialization
            d = np.asarray(d)[:b]
            # device-time attribution (monitoring/devtime.py): the
            # np.asarray above IS the completion sync, so bracketing it
            # costs two perf_counter reads and ZERO extra host syncs.
            # First sighting of a (backend, scorer, mesh, shape-bucket)
            # identity = the dispatch that paid program acquisition —
            # classified compile (true XLA) vs cache_hit (persistent-
            # cache deserialize, utils/compile_cache.py) from the
            # cache's hit/miss counters across this bracket.
            from weaviate_tpu.monitoring import devtime, tracing

            dt_dev = _time.perf_counter() - t_dev
            mesh_mode = "mesh" if mesh_mirror is not None else "single"
            phase = devtime.record(
                backend=type(self.backend).__name__,
                scorer=type(scorer).__name__, mesh=mesh_mode,
                # the rerank module is a jit-static arg: its variant is
                # a DISTINCT program identity whose first dispatch pays
                # its own compile — it must not masquerade as a warm
                # execute of the plain walk
                shape_key=(b_pad, ef_pad, al_pad is not None, expand,
                           rr_name),
                seconds=dt_dev)
            tracing.annotate(
                device_execute_ms=round(dt_dev * 1000, 3),
                device_phase=phase, scorer=type(scorer).__name__,
                mesh_mode=mesh_mode)
            self._beam_proven = True
        except Exception as e:
            import logging

            if getattr(self, "_beam_proven", False):
                # worked before: treat as transient (device busy, batch
                # OOM) — fall back for THIS query only
                DEVICE_BEAM_FALLBACK.inc(kind="search", mode="transient")
                logging.getLogger("weaviate_tpu.hnsw").warning(
                    "device beam failed (transient, falling back): %s", e)
            elif rerank is not None:
                # a rerank-STAGE failure (token-plane sync, query-token
                # dims mismatch in the fused einsum) says nothing about
                # the plain walk — never latch the whole beam off for
                # it; this query serves from the host rerank tier
                from weaviate_tpu.monitoring.metrics import RERANK_FALLBACK

                DEVICE_BEAM_FALLBACK.inc(kind="search", mode="transient")
                RERANK_FALLBACK.inc(module=rr_name or "unknown",
                                    reason="fused_error")
                logging.getLogger("weaviate_tpu.hnsw").warning(
                    "fused rerank stage failed (host tier serves this "
                    "query): %s", e)
            else:
                # never lowered successfully on this backend: latch off
                DEVICE_BEAM_FALLBACK.inc(kind="search", mode="latched")
                logging.getLogger("weaviate_tpu.hnsw").warning(
                    "device beam disabled after failure: %s", e)
                self.graph.dirty_hook = None
                self._device_beam = None
            return None
        finally:
            planes_held.close()
        keep = self._keep_mask(allow_list)
        ok = (ids >= 0) & keep[np.clip(ids, 0, len(keep) - 1)]
        d = np.where(ok, d, _INF)
        ids = np.where(ok, ids, -1)
        order = np.argsort(d, axis=1, kind="stable")[:, :fetch]
        d = np.take_along_axis(d, order, axis=1)
        ids = np.take_along_axis(ids, order, axis=1)
        if rerank is not None:
            # the module score IS the final ordering (d = negated score;
            # the stable sort above only re-packed keep-filtered slots) —
            # no second rescore tier. Observability: the batch span (the
            # active span here — the dispatcher leader runs this inside
            # it) gains the rerank.score child event, and the instruments
            # make fused-vs-fallback traffic alertable per module.
            from weaviate_tpu.monitoring import tracing
            from weaviate_tpu.monitoring.metrics import (
                RERANK_CANDIDATES,
                RERANK_REQUESTS,
            )

            RERANK_REQUESTS.inc(module=rr_name, tier="fused")
            # b and fetch_pad are python ints (shape metadata, no sync)
            n_scored = b * fetch_pad
            RERANK_CANDIDATES.observe(n_scored, module=rr_name)
            tracing.add_event("rerank.score", module=rr_name,
                              candidates=fetch_pad, rows=b)
            ids = ids[:, :k].astype(np.int64)
            d = d[:, :k].astype(np.float32)
        else:
            # rescore tier: exact promotion for quantized walks,
            # truncation for raw ones (distances already exact)
            ids, d = self.backend.rescore_topk(queries, ids, d, k)
            ids = ids.astype(np.int64)
        if d.shape[1] < k:
            pad = k - d.shape[1]
            d = np.pad(d, ((0, 0), (0, pad)), constant_values=_INF)
            ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        return ids, d

    def multi_walk_inputs(self, queries, k: int, b_pad: int,
                          allow_list=None, expand: int = 0):
        """One WALK LEG of the fused multi-target program: everything
        ``_device_beam_search`` would hand the single-target kernel —
        scorer + HBM operands, padded device queries, synced adjacency
        mirror, entrypoints/seed table, pow2-bucketed widths, device
        allow mask — extracted so the shard's multi-target dispatcher
        (``core/shard.py``) can assemble N legs into ONE
        ``device_multi_search[_mesh]`` dispatch. Returns None when this
        index cannot serve a device walk right now (mirror dropped /
        demoted / unfitted quantizer); the caller then falls back to
        the host per-target-walk+join oracle for the whole request."""
        if self._device_beam is None or not self.device_resident:
            return None
        scorer_pack = self.backend.device_scorer()
        if scorer_pack is None:
            return None  # quantizer unfitted: lifecycle, not a failure
        scorer, operands = scorer_pack
        qdev = self._qdev(queries)
        q = self.backend.beam_queries(qdev)
        if q is None:
            return None
        import jax.numpy as jnp

        ef = self._dynamic_ef(k)
        fetch = self._fetch_width(k, ef)
        ef_pad = 1 << max(4, (int(ef) - 1).bit_length())
        fetch_pad = min(ef_pad, 1 << max(3, (int(fetch) - 1).bit_length()))
        b = q.shape[0]
        if b_pad != b:
            q = jnp.concatenate(
                [q, jnp.repeat(q[:1], b_pad - b, axis=0)], axis=0)
        adj, present = self._device_beam.sync()
        upper_adj, upper_slots = self._device_beam.sync_upper()
        cap = int(adj.shape[0])
        mesh_mirror = self._mesh_mirror()
        leg = dict(
            scorer=scorer, operands=operands, q=q, adj=adj,
            present=present, upper_adj=upper_adj,
            upper_slots=upper_slots, ef_pad=ef_pad, fetch_pad=fetch_pad,
            cap=cap, allow=None, keep_k=0, expand=0,
            mesh_mirror=mesh_mirror,
        )
        if mesh_mirror is not None:
            leg["seeds"] = mesh_mirror.sync_seeds()
        else:
            leg["eps"] = np.full(b_pad, self.graph.entrypoint, np.int32)
        if allow_list is not None:
            plane = (allow_list if getattr(allow_list, "plane_id", None)
                     is not None else None)
            al = (plane.mask(cap) if plane is not None
                  else np.asarray(allow_list, bool))
            if len(al) < cap:
                al = np.pad(al, (0, cap - len(al)))
            al_pad = al[:cap]
            if mesh_mirror is not None:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                from weaviate_tpu.parallel.mesh import SHARD_AXIS

                shard_spec = NamedSharding(mesh_mirror.mesh, P(SHARD_AXIS))
                leg["allow"] = (plane.device_mask(cap, shard_spec)
                                if plane is not None
                                else jax.device_put(al_pad, shard_spec))
            else:
                leg["allow"] = (plane.device_mask(cap) if plane is not None
                                else jnp.asarray(al_pad))
            leg["keep_k"] = fetch_pad
            leg["expand"] = expand
        return leg

    def beam_proven(self) -> None:
        """Mark the fused walk proven on this backend — called by the
        multi-target dispatcher after a leg of its joint program ran,
        so a later single-target failure is classified transient."""
        self._beam_proven = True

    def _flat_filtered(self, queries, k, allow_list):
        d, ids = self.backend.flat_topk(queries, k, allow_list)
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
            dists=np.where(keep, res.dists, _INF),
        )

    # ------------------------------------------------------------------
    def save_vectors(self, path: str, meta: Optional[dict] = None) -> bool:
        if self.store is None:  # quantized backend: codes rebuild from source
            return False
        self.store.save(path, meta)
        if self._token_store is not None:
            # the rerank tier's token planes checkpoint alongside the
            # corpus — a restored index reranking against empty masks
            # would be silently wrong ordering
            self._token_store.save(path)
        return True

    def load_vectors(self, path: str) -> Optional[dict]:
        if self.store is None:
            return None
        meta = self.store.load(path)
        if meta is None:
            return None
        if self._token_store is not None \
                and not self._token_store.load(path):
            # corpus without its token sidecar (older checkpoint / torn
            # write): half a checkpoint is no checkpoint — the caller's
            # rebuild path re-adds vectors and repopulates the planes
            return None
        return meta

    def count(self) -> int:
        return self.graph.node_count

    @property
    def capacity(self) -> int:
        return self.backend.capacity

    def contains(self, doc_id: int) -> bool:
        return self.graph.contains(doc_id) and self.backend.contains(doc_id)

    # -- tiered residency (docs/tiering.md) -------------------------------
    @property
    def device_resident(self) -> bool:
        return self.backend.device_resident

    def hbm_bytes(self) -> int:
        n = self.backend.hbm_bytes()
        if self._device_beam is not None:
            n += self._device_beam.nbytes
        if self._token_store is not None:
            # the rerank tier's candidate token planes pay HBM rent
            # through the same ledger as code planes (docs/modules.md)
            n += self._token_store.nbytes
        return n

    def host_tier_bytes(self) -> int:
        n = self.backend.host_tier_bytes()
        if self._token_store is not None:
            n += self._token_store.host_bytes
        return n

    def demote_device(self) -> int:
        """Warm demotion: corpus/codes to host RAM + the beam's mirrored
        tables released. The DeviceAdjacency OBJECT survives (it re-syncs
        wholesale on the next hot search at identical shapes), so the
        fused walk is never latched off by tiering."""
        freed = self.backend.demote_device()
        if self._device_beam is not None:
            freed += self._device_beam.drop_device()
        if self._token_store is not None:
            freed += self._token_store.drop_device()
        if freed:
            self._residency_epoch += 1
        return freed

    def promote_device(self) -> int:
        """Re-attach the demoted arrays; the beam tables re-upload lazily
        on the next search's sync (counted by the footprint refresh)."""
        gained = self.backend.promote_device()
        if gained:
            self._residency_epoch += 1
        return gained

    def stats(self) -> dict:
        s = {
            "type": "hnsw",
            "count": self.count(),
            "capacity": self.capacity,
            "metric": self.metric,
            "max_level": self.graph.max_level,
            "entrypoint": self.graph.entrypoint,
        }
        s["device_resident"] = self.backend.device_resident
        if not self.backend.device_resident:
            s["host_tier_bytes"] = self.backend.host_tier_bytes()
        if self.backend.quantized:
            s["quantizer"] = self.backend.quantizer.kind
            s["fitted"] = self.backend.quantizer.fitted
            s["codes_hbm_bytes"] = self.backend.codes.nbytes
        else:
            s["corpus_hbm_bytes"] = self.backend.store.nbytes
        if self._device_beam is not None:
            # the fused walk's extra HBM rent: mirrored layer-0 rows,
            # presence mask, and compact upper-layer tables
            s["device_beam"] = True
            s["device_beam_hbm_bytes"] = self._device_beam.nbytes
        if self._rerank_module is not None:
            s["rerank_module"] = self._rerank_module.name
            s["rerank_hbm_bytes"] = self._token_store.nbytes
            s["rerank_host_bytes"] = self._token_store.host_bytes
        mirror = self._mesh_mirror()
        if mirror is not None:
            s["mesh_shards"] = mirror.n
            s["mesh_rows_per_shard"] = mirror.rows_per_shard()
        return s
