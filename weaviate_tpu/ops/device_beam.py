"""Device-resident HNSW search: ONE dispatch per batch, any backend.

Reference hot loop: ``hnsw/search.go:726`` expands one candidate at a
time with per-candidate SIMD distance calls. The host-side TPU redesign
(``index/hnsw/hnsw.py _search_level``) batches each beam ITERATION into
one device call — but still pays a host↔device round-trip per hop, which
dominates wall time on a high-latency host-device link and adds
dispatch overhead everywhere else.

This kernel moves the WHOLE walk — upper-layer greedy descent from the
entrypoint plus the layer-0 beam — into one jitted program: the
adjacency lives in HBM as a device array (``DeviceAdjacency`` — an
incrementally synced mirror of the host graph, including compact
slot-addressed upper-layer tables), the beam/visited state stays on
device, and the host gets exactly one dispatch + one fetch per search
batch.

Distance evaluation is PLUGGABLE: a :class:`Scorer` is a frozen (and
therefore hashable — it keys the jit cache) dataclass whose ``__call__``
maps ``(queries, candidate_ids, operands) -> [B, C]`` distances, where
``operands`` is the backend's tuple of HBM-resident arrays. ``RawScorer``
gather-scores the fp32 corpus; ``SQScorer``/``PQScorer``/``BQScorer``/
``RQScorer`` gather-score quantized code planes via the kernels in
``ops/quantized.py`` — so PQ/SQ/BQ/RQ graph walks are exactly as
device-resident as the raw ones, with only the codes (4–32x smaller)
living in HBM.

Semantics mirror the host implementation (lockstep best-first expansion,
ef-bounded beam, stop when the beam holds no unexpanded candidates —
every entry that survives the ef cut gets expanded once). Tombstoned
nodes remain traversable; result filtering happens after the walk
(sweeping strategy). Filtered searches pass ``allow``/``keep_k``: the
walk itself is UNCHANGED (traversal through disallowed nodes preserves
graph connectivity — the device analogue of the reference's ACORN
traversal, ``hnsw/search.go:36-41``) while a second on-device top-k
tracks the best ALLOWED nodes seen, exactly like the host sweep's
``keep_mask`` track — so a filtered batch still costs one dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.ops.distance import MASK_DISTANCE

# numpy scalars, not jnp: a device constant at module level would
# initialize the default backend at import (see ops/distance.py)
_INF = np.float32(MASK_DISTANCE)

# Test/ops hook: fused-walk programs dispatched by this process. The
# acceptance contract "one dispatch per batch for the whole
# entrypoint→layer-0 walk" is asserted against this counter.
_dispatch_count = 0


def dispatch_count() -> int:
    return _dispatch_count


# ---------------------------------------------------------------------------
# scorers: static (hashable) per-backend distance evaluators
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RawScorer:
    """Full-precision gather-score. operands = (corpus [N, D],)."""

    metric: str
    precision: str

    def __call__(self, q, ids, operands):
        from weaviate_tpu.ops.distance import gather_distance

        (corpus,) = operands
        return gather_distance(q, corpus, ids, self.metric,
                               precision=self.precision)


@dataclasses.dataclass(frozen=True)
class SQScorer:
    """operands = (codes [N, D] u8, dec_sqnorms [N], a, s)."""

    metric: str

    def __call__(self, q, ids, operands):
        from weaviate_tpu.ops import quantized as qops

        codes, dsq, a, s = operands
        return qops.sq_gather_distance(q, codes, ids, dsq, a, s, self.metric)


@dataclasses.dataclass(frozen=True)
class PQScorer:
    """operands = (codes [N, M] u8, codebooks [M, C, dsub], dec_sqnorms)."""

    metric: str

    def __call__(self, q, ids, operands):
        from weaviate_tpu.ops import quantized as qops

        codes, codebooks, dsq = operands
        return qops.pq_gather_distance(q, codes, codebooks, ids, dsq,
                                       self.metric)


@dataclasses.dataclass(frozen=True)
class BQScorer:
    """operands = (packed [N, W] u32, popcounts [N]); q is packed bits."""

    dims: int

    def __call__(self, q, ids, operands):
        from weaviate_tpu.ops import quantized as qops

        packed, popcounts = operands
        return qops.bq_gather_distance(q, packed, ids, popcounts, self.dims)


@dataclasses.dataclass(frozen=True)
class RQScorer:
    """operands = (codes [N, D'] u8, lower [N], step [N], dec_sqnorms)."""

    metric: str

    def __call__(self, q, ids, operands):
        from weaviate_tpu.ops import quantized as qops

        codes, lower, step, dsq = operands
        return qops.rq_gather_distance(q, codes, ids, lower, step, dsq,
                                       self.metric)


def _masked_scores(scorer, q, ids, operands):
    """[B, C] distances for candidate ids (-1 → MASK) via the scorer."""
    d = scorer(q, jnp.maximum(ids, 0), operands)
    return jnp.where(ids >= 0, d, _INF)


_NEG_INF = np.float32(-np.inf)


def _rerank_module_scores(rerank, cand, tokens, tmask, rq, rqmask):
    """The fused rerank core (traced INSIDE the search program): gather
    the candidate token planes for a candidate pool and score it
    through the device module hook (``modules/device/``). ``cand``
    [B, C] pool ids (-1 pad). Returns (valid [B, C], scores [B, C],
    higher = better; invalid slots carry garbage — every caller masks
    with its own sentinel)."""
    valid = cand >= 0
    safe = jnp.maximum(cand, 0)
    toks = jnp.take(tokens, safe, axis=0)               # [B, C, T, D]
    tm = jnp.take(tmask, safe, axis=0) & valid[:, :, None]
    return valid, rerank(rq, rqmask, toks, tm)


def _rerank_stage(rerank, out_k, cand, tokens, tmask, rq, rqmask):
    """Single-program rerank tail: module scores + on-device top-k.
    Returns (ids [B, out_k], neg_scores [B, out_k]) — negated scores,
    so lower is better and the host plumbing treats them exactly like
    distances."""
    valid, scores = _rerank_module_scores(rerank, cand, tokens, tmask,
                                          rq, rqmask)
    scores = jnp.where(valid, scores, _NEG_INF)
    s, sel = jax.lax.top_k(scores, out_k)
    r_ids = jnp.take_along_axis(cand, sel, axis=1)
    ok = jnp.isfinite(s)
    return jnp.where(ok, r_ids, -1), jnp.where(ok, -s, _INF)


# ---------------------------------------------------------------------------
# fused kernel: greedy descent over upper layers + layer-0 beam, one jit
# ---------------------------------------------------------------------------


def _two_hop_widen(adjacency, present, allow, queries, operands, scorer,
                   nbrs, nd, visited, rows, expand: int):
    """ACORN-style two-hop widening: instead of letting blocked neighbors
    dead-end the kept track, the ``expand`` CLOSEST blocked one-hop
    neighbors expand through to their own adjacency rows in the same
    step. Returns (nbrs, nd, visited) with the second-hop frontier
    concatenated — both the beam merge and the kept-track merge then
    consume the widened frontier, so traversal reach grows under
    selective filters without extra dispatches.

    Second-hop rows from different parents can collide; an in-row
    first-occurrence dedup keeps one copy (duplicate ids would otherwise
    occupy two beam/kept slots and surface duplicate results). Collisions
    with this step's one-hop frontier are screened by ``visited``, which
    the caller already updated for the one-hop row."""
    b, m0 = nbrs.shape[0], adjacency.shape[1]
    # closest blocked one-hop neighbors become expansion parents
    blocked_d = jnp.where(
        (nbrs >= 0) & ~jnp.take(allow, jnp.maximum(nbrs, 0)), nd, _INF)
    _, psel = jax.lax.top_k(-blocked_d, expand)            # [B, expand]
    parents = jnp.take_along_axis(nbrs, psel, axis=1)
    pvalid = jnp.take_along_axis(blocked_d, psel, axis=1) < _INF
    parents = jnp.where(pvalid, parents, -1)
    hop2 = jnp.take(adjacency, jnp.maximum(parents, 0), axis=0)
    hop2 = jnp.where(pvalid[:, :, None], hop2, -1).reshape(b, expand * m0)
    # in-row first-occurrence dedup across parent rows
    eq = hop2[:, :, None] == hop2[:, None, :]
    first = jnp.argmax(eq, axis=2) == jnp.arange(expand * m0)[None, :]
    safe2 = jnp.maximum(hop2, 0)
    seen2 = jnp.take_along_axis(visited, safe2, axis=1) > 0
    ok2 = (hop2 >= 0) & first & ~seen2 & jnp.take(present, safe2)
    hop2 = jnp.where(ok2, hop2, -1)
    visited = visited.at[rows[:, None], safe2].max(ok2.astype(jnp.uint8))
    nd2 = _masked_scores(scorer, queries, hop2, operands)
    return (jnp.concatenate([nbrs, hop2], axis=1),
            jnp.concatenate([nd, nd2], axis=1), visited)


@functools.partial(
    jax.jit,
    static_argnames=("scorer", "ef", "max_steps", "keep_k", "rerank",
                     "rerank_k", "expand"))
def _fused_search(
    scorer,                      # static Scorer (hashable dataclass)
    queries: jnp.ndarray,        # [B, ...] backend query rep
    operands: tuple,             # backend HBM arrays (corpus or code planes)
    adjacency: jnp.ndarray,      # [N, M0] int32, -1 padded (layer 0)
    present: jnp.ndarray,        # [N] bool — node exists (incl. tombstoned)
    eps: jnp.ndarray,            # [B] int32 entrypoints
    upper_adj: jnp.ndarray,      # [L, S, M] int32 slot-compacted, top first
    upper_slots: jnp.ndarray,    # [L, N] int32 node -> slot (-1 absent)
    ef: int,
    max_steps: int,
    allow: Optional[jnp.ndarray] = None,  # [N] bool filter allowlist
    keep_k: int = 0,
    expand: int = 0,             # static two-hop widening budget (ACORN)
    rerank=None,                 # static DeviceRerankModule (hashable)
    rerank_k: int = 0,
    rerank_q: Optional[jnp.ndarray] = None,       # [B, Tq, D]
    rerank_qmask: Optional[jnp.ndarray] = None,   # [B, Tq] bool
    rerank_tokens: Optional[jnp.ndarray] = None,  # [N, T, D] HBM plane
    rerank_tmask: Optional[jnp.ndarray] = None,   # [N, T] bool
):
    """→ (ids [B, ef], dists [B, ef]) ascending; -1/MASK padded. With
    ``allow`` + ``keep_k`` also returns (kept_ids [B, keep_k], kept_d) —
    the best ALLOWED nodes seen anywhere along the walk (the device
    analogue of the host sweep's keep_mask track). With a ``rerank``
    module the walk's top candidates (the kept track when filtered, the
    beam otherwise) feed the fused rerank stage — gather candidate token
    planes, module score, on-device top-k — and the returns become
    (beam_ids, beam_d, rerank_ids [B, rerank_k], neg_scores); still ONE
    dispatch for walk + rerank."""
    b = queries.shape[0]
    n, m0 = adjacency.shape
    rows = jnp.arange(b)
    track = allow is not None and keep_k > 0

    eps = eps.astype(jnp.int32)
    d0 = _masked_scores(scorer, queries, eps[:, None], operands)[:, 0]

    # -- upper-layer greedy descent (reference search.go:760) ------------
    # One fori_loop over levels (index 0 = TOP level), nested while_loop
    # per level; a node absent at a level (slot -1) simply never moves.
    n_upper = upper_adj.shape[0]
    if n_upper:  # static — L=0 graphs skip the descent entirely
        def level_body(li, carry):
            cur, cur_d = carry
            adj_l = jax.lax.dynamic_index_in_dim(
                upper_adj, li, 0, keepdims=False)      # [S, M]
            slot_l = jax.lax.dynamic_index_in_dim(
                upper_slots, li, 0, keepdims=False)    # [N]

            def cond(st):
                step, _, _, live = st
                return (step < max_steps) & live.any()

            def body(st):
                step, cur, cur_d, live = st
                slot = jnp.take(slot_l, cur)                      # [B]
                nbrs = jnp.take(adj_l, jnp.maximum(slot, 0), axis=0)
                ok = ((slot >= 0) & live)[:, None] & (nbrs >= 0)
                ok &= jnp.take(present, jnp.maximum(nbrs, 0))
                nbrs = jnp.where(ok, nbrs, -1)
                d = _masked_scores(scorer, queries, nbrs, operands)
                j = jnp.argmin(d, axis=1)
                bd = d[rows, j]
                upd = live & (bd < cur_d)
                cur = jnp.where(upd, nbrs[rows, j], cur)
                cur_d = jnp.where(upd, bd, cur_d)
                return step + 1, cur, cur_d, upd

            _, cur, cur_d, _ = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), cur, cur_d, jnp.ones((b,), bool)))
            return cur, cur_d

        eps, d0 = jax.lax.fori_loop(0, n_upper, level_body, (eps, d0))

    # -- layer-0 best-first beam -----------------------------------------
    beam_ids = jnp.full((b, ef), -1, jnp.int32).at[:, 0].set(eps)
    beam_d = jnp.full((b, ef), _INF, jnp.float32).at[:, 0].set(d0)
    expanded = jnp.zeros((b, ef), bool)
    visited = jnp.zeros((b, n), jnp.uint8).at[rows, eps].set(1)
    if track:
        seed_ok = jnp.take(allow, eps)
        kept_ids = jnp.full((b, keep_k), -1, jnp.int32).at[:, 0].set(
            jnp.where(seed_ok, eps, -1))
        kept_d = jnp.full((b, keep_k), _INF, jnp.float32).at[:, 0].set(
            jnp.where(seed_ok, d0, _INF))
    else:
        # zero-width placeholders keep the while_loop carry structure
        # identical across the two variants
        kept_ids = jnp.zeros((b, 0), jnp.int32)
        kept_d = jnp.zeros((b, 0), jnp.float32)

    def cond(st):
        step, _, _, _, _, _, _, alive = st
        return (step < max_steps) & alive

    def body(st):
        step, beam_ids, beam_d, expanded, visited, kept_ids, kept_d, _ = st
        cand_d = jnp.where(expanded | (beam_ids < 0), _INF, beam_d)
        j = jnp.argmin(cand_d, axis=1)
        cd = cand_d[rows, j]
        # termination is beam exhaustion: every beam entry (all within the
        # ef best seen) gets expanded exactly once — cd is drawn FROM the
        # beam, so a "worse than ef-th best" test would be vacuous here
        active = cd < _INF
        expanded = expanded.at[rows, j].set(expanded[rows, j] | active)
        cur = jnp.where(active, beam_ids[rows, j], 0)
        nbrs = jnp.take(adjacency, jnp.maximum(cur, 0), axis=0)  # [B, M0]
        nbrs = jnp.where(active[:, None], nbrs, -1)
        safe = jnp.maximum(nbrs, 0)
        seen = jnp.take_along_axis(visited, safe, axis=1) > 0
        ok = (nbrs >= 0) & ~seen & jnp.take(present, safe)
        nbrs = jnp.where(ok, nbrs, -1)
        visited = visited.at[rows[:, None], safe].max(
            ok.astype(jnp.uint8))
        nd = _masked_scores(scorer, queries, nbrs, operands)
        if track and expand > 0:
            nbrs, nd, visited = _two_hop_widen(
                adjacency, present, allow, queries, operands, scorer,
                nbrs, nd, visited, rows, expand)
        all_ids = jnp.concatenate([beam_ids, nbrs], axis=1)
        all_d = jnp.concatenate([beam_d, nd], axis=1)
        all_exp = jnp.concatenate(
            [expanded, jnp.zeros_like(nbrs, bool)], axis=1)
        order = jnp.argsort(all_d, axis=1, stable=True)[:, :ef]
        beam_ids = jnp.take_along_axis(all_ids, order, axis=1)
        beam_d = jnp.take_along_axis(all_d, order, axis=1)
        expanded = jnp.take_along_axis(all_exp, order, axis=1)
        if track:
            # merge this hop's ALLOWED neighbors into the kept track; the
            # walk itself stays unfiltered (connectivity through
            # disallowed nodes is the point)
            nd_k = jnp.where(
                (nbrs >= 0) & jnp.take(allow, jnp.maximum(nbrs, 0)),
                nd, _INF)
            ka = jnp.concatenate([kept_ids, nbrs], axis=1)
            kd = jnp.concatenate([kept_d, nd_k], axis=1)
            korder = jnp.argsort(kd, axis=1, stable=True)[:, :keep_k]
            kept_ids = jnp.take_along_axis(ka, korder, axis=1)
            kept_d = jnp.take_along_axis(kd, korder, axis=1)
        return (step + 1, beam_ids, beam_d, expanded, visited,
                kept_ids, kept_d, active.any())

    _, beam_ids, beam_d, _, _, kept_ids, kept_d, _ = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), beam_ids, beam_d, expanded, visited,
         kept_ids, kept_d, jnp.bool_(True)))
    if track:
        kept_ids = jnp.where(kept_d >= _INF, -1, kept_ids)
    if rerank is not None and rerank_k > 0:
        r_ids, r_d = _rerank_stage(
            rerank, rerank_k,
            (kept_ids if track else beam_ids)[:, :rerank_k],
            rerank_tokens, rerank_tmask, rerank_q, rerank_qmask)
        return beam_ids, beam_d, r_ids, r_d
    if track:
        return beam_ids, beam_d, kept_ids, kept_d
    return beam_ids, beam_d


# ---------------------------------------------------------------------------
# mesh-sharded fused walk: ONE SPMD dispatch across every chip
# ---------------------------------------------------------------------------
#
# The reference scales reads by per-shard goroutine fan-out with a
# coordinator merge (index.go:1928); the jax-native analogue is the same
# fused walk run under shard_map: queries replicate, every device walks
# its OWN shard-local subgraph over its LOCAL block of the scored planes
# (raw corpus or SQ/PQ/BQ/RQ codes, row-block-sharded), each shard
# over-fetches its rescore-tier candidates, and a tiled all_gather +
# top_k merges across shards ON DEVICE (ops/topk.merge_across_shards) —
# no per-shard candidate list ever round-trips to the host, and the
# whole thing is still exactly one dispatch per batch.
#
# Shard-local subgraphs: mesh construction (index/hnsw/hnsw.py) links
# every node only within its block shard (shard(id) = id // L, L =
# plane capacity / mesh size), so the mirrored adjacency can store
# LOCAL neighbor indices and each device's block is self-contained —
# the device walk never needs a cross-shard gather per hop.


def _op_partition_spec(arr, cap: int, axis: str):
    """Row-sharded for plane arrays (leading dim == capacity), replicated
    for everything else (PQ codebooks, SQ affine scalars)."""
    from jax.sharding import PartitionSpec as P

    nd = np.ndim(arr)
    if nd >= 1 and arr.shape[0] == cap:
        return P(axis, *([None] * (nd - 1)))
    return P(*([None] * nd))


@functools.partial(
    jax.jit,
    static_argnames=("scorer", "ef", "max_steps", "fetch", "keep_k",
                     "mesh", "axis", "merge", "rerank", "rerank_k",
                     "expand"))
def _fused_mesh_search(
    scorer,
    queries,
    operands: tuple,
    adjacency,           # [cap, M0] int32 row-sharded, content LOCAL ids
    present,             # [cap] bool row-sharded
    upper_adj,           # [n, Lv, S, M] int32 sharded on 0, content LOCAL
    upper_slots,         # [Lv, cap] int32 sharded on dim 1
    ef: int,
    max_steps: int,
    fetch: int,
    mesh=None,
    axis: str = "shard",
    merge: bool = True,
    seeds=None,          # [n, E] int32 sharded on 0, LOCAL ids (serving)
    qeps=None,           # [B] int32 replicated GLOBAL ids (construction)
    allow=None,          # [cap] bool row-sharded
    keep_k: int = 0,
    expand: int = 0,     # static two-hop widening budget (ACORN)
    rerank=None,         # static DeviceRerankModule (hashable)
    rerank_k: int = 0,
    rerank_q=None,       # [B, Tq, D] replicated
    rerank_qmask=None,   # [B, Tq] replicated
    rerank_tokens=None,  # [cap, T, D] row-sharded token plane
    rerank_tmask=None,   # [cap, T] row-sharded
):
    """The whole mesh as one program: per-shard descent + layer-0 beam
    in local index space, then the cross-shard top-k merge. Returns
    replicated (ids [B, fetch] GLOBAL, dists) — plus (kept_ids [B,
    keep_k], kept_d) when filtered — or, with ``merge=False``
    (construction), the UNMERGED per-shard results stacked [n, B,
    fetch] so the host can take each node's own-shard candidates. With
    a ``rerank`` module every shard runs the fused rerank stage over
    its LOCAL candidates (token planes row-shard like every other HBM
    plane) and the cross-shard merge ranks by module score — returns
    replicated (ids [B, rerank_k], neg_scores); still ONE dispatch."""
    from jax.sharding import PartitionSpec as P

    from weaviate_tpu.parallel.sharded_search import _shard_map

    cap = adjacency.shape[0]
    track = allow is not None and keep_k > 0
    rerank_on = rerank is not None and rerank_k > 0 and merge

    def local(q, ops_l, adj_l, pres_l, uadj_l, uslots_l, *rest):
        rest = list(rest)
        seeds_l = rest.pop(0) if seeds is not None else None
        qeps_r = rest.pop(0) if qeps is not None else None
        allow_l = rest.pop(0) if allow is not None else None
        if rerank_on:
            tok_l = rest.pop(0)
            tmask_l = rest.pop(0)
            rq_r = rest.pop(0)
            rqm_r = rest.pop(0)
        n_local = adj_l.shape[0]
        b = q.shape[0]
        rows = jnp.arange(b)
        base = jax.lax.axis_index(axis) * n_local

        if seeds_l is not None:
            sds = seeds_l[0]                                   # [E] local
            cur = jnp.broadcast_to(sds[None, :], (b, sds.shape[0]))
        else:
            # construction: per-query global entrypoints — only the
            # owning shard walks each query, the rest see seed -1 and
            # exit their beam immediately (per-shard parallelism)
            ok = (qeps_r >= base) & (qeps_r < base + n_local)
            cur = jnp.where(ok, qeps_r - base, -1)[:, None]
        e_w = cur.shape[1]
        d0 = _masked_scores(scorer, q, cur, ops_l)             # [B, E]

        # -- per-shard upper-layer greedy descent (one seed lane each) --
        n_upper = uadj_l.shape[1]
        if n_upper:
            def level_body(li, carry):
                cur, cur_d = carry
                adj_lv = jax.lax.dynamic_index_in_dim(
                    uadj_l[0], li, 0, keepdims=False)          # [S, M]
                slot_lv = jax.lax.dynamic_index_in_dim(
                    uslots_l, li, 0, keepdims=False)           # [L]

                def cond(st):
                    step, _, _, live = st
                    return (step < max_steps) & live.any()

                def body(st):
                    step, cur, cur_d, live = st
                    slot = jnp.where(
                        cur >= 0, jnp.take(slot_lv, jnp.maximum(cur, 0)), -1)
                    nbrs = jnp.take(adj_lv, jnp.maximum(slot, 0), axis=0)
                    okm = ((slot >= 0) & live)[..., None] & (nbrs >= 0)
                    okm &= jnp.take(pres_l, jnp.maximum(nbrs, 0))
                    nbrs = jnp.where(okm, nbrs, -1)
                    d = _masked_scores(
                        scorer, q, nbrs.reshape(b, -1), ops_l
                    ).reshape(nbrs.shape)
                    j = jnp.argmin(d, axis=2)
                    bd = jnp.take_along_axis(d, j[..., None], 2)[..., 0]
                    upd = live & (bd < cur_d)
                    cur = jnp.where(
                        upd,
                        jnp.take_along_axis(nbrs, j[..., None], 2)[..., 0],
                        cur)
                    cur_d = jnp.where(upd, bd, cur_d)
                    return step + 1, cur, cur_d, upd

                _, cur, cur_d, _ = jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), cur, cur_d, jnp.ones(cur.shape, bool)))
                return cur, cur_d

            cur, d0 = jax.lax.fori_loop(0, n_upper, level_body, (cur, d0))

        if e_w > 1:
            # seed lanes that converged to the same node would occupy two
            # beam slots and surface DUPLICATE result ids — keep the first
            same = (cur[:, :, None] == cur[:, None, :]) & (cur[:, None, :] >= 0)
            earlier = jnp.tril(jnp.ones((e_w, e_w), bool), -1)
            dup = (same & earlier[None]).any(axis=2) & (cur >= 0)
            cur = jnp.where(dup, -1, cur)
            d0 = jnp.where(dup, _INF, d0)

        # -- layer-0 best-first beam over the local block ---------------
        beam_ids = jnp.full((b, ef), -1, jnp.int32).at[:, :e_w].set(cur)
        beam_d = jnp.full((b, ef), _INF, jnp.float32).at[:, :e_w].set(
            jnp.where(cur >= 0, d0, _INF))
        expanded = jnp.zeros((b, ef), bool)
        visited = jnp.zeros((b, n_local), jnp.uint8).at[
            rows[:, None], jnp.maximum(cur, 0)].max(
                (cur >= 0).astype(jnp.uint8))
        if track:
            pad_w = max(e_w, keep_k)
            ka0 = jnp.full((b, pad_w), -1, jnp.int32).at[:, :e_w].set(cur)
            al_ok = (cur >= 0) & jnp.take(allow_l, jnp.maximum(cur, 0))
            kd0 = jnp.full((b, pad_w), _INF, jnp.float32).at[:, :e_w].set(
                jnp.where(al_ok, d0, _INF))
            korder0 = jnp.argsort(kd0, axis=1, stable=True)[:, :keep_k]
            kept_ids = jnp.take_along_axis(ka0, korder0, axis=1)
            kept_d = jnp.take_along_axis(kd0, korder0, axis=1)
        else:
            kept_ids = jnp.zeros((b, 0), jnp.int32)
            kept_d = jnp.zeros((b, 0), jnp.float32)

        def cond(st):
            step, _, _, _, _, _, _, alive = st
            return (step < max_steps) & alive

        def body(st):
            step, beam_ids, beam_d, expanded, visited, kept_ids, kept_d, _ = st
            cand_d = jnp.where(expanded | (beam_ids < 0), _INF, beam_d)
            j = jnp.argmin(cand_d, axis=1)
            cd = cand_d[rows, j]
            active = cd < _INF
            expanded = expanded.at[rows, j].set(expanded[rows, j] | active)
            cur = jnp.where(active, beam_ids[rows, j], 0)
            nbrs = jnp.take(adj_l, jnp.maximum(cur, 0), axis=0)
            nbrs = jnp.where(active[:, None], nbrs, -1)
            safe = jnp.maximum(nbrs, 0)
            seen = jnp.take_along_axis(visited, safe, axis=1) > 0
            ok = (nbrs >= 0) & ~seen & jnp.take(pres_l, safe)
            nbrs = jnp.where(ok, nbrs, -1)
            visited = visited.at[rows[:, None], safe].max(
                ok.astype(jnp.uint8))
            nd = _masked_scores(scorer, q, nbrs, ops_l)
            if track and expand > 0:
                # same ACORN widening as the single-chip kernel, over the
                # shard-LOCAL subgraph (local adjacency + local allow)
                nbrs, nd, visited = _two_hop_widen(
                    adj_l, pres_l, allow_l, q, ops_l, scorer,
                    nbrs, nd, visited, rows, expand)
            all_ids = jnp.concatenate([beam_ids, nbrs], axis=1)
            all_d = jnp.concatenate([beam_d, nd], axis=1)
            all_exp = jnp.concatenate(
                [expanded, jnp.zeros_like(nbrs, bool)], axis=1)
            order = jnp.argsort(all_d, axis=1, stable=True)[:, :ef]
            beam_ids = jnp.take_along_axis(all_ids, order, axis=1)
            beam_d = jnp.take_along_axis(all_d, order, axis=1)
            expanded = jnp.take_along_axis(all_exp, order, axis=1)
            if track:
                nd_k = jnp.where(
                    (nbrs >= 0) & jnp.take(allow_l, jnp.maximum(nbrs, 0)),
                    nd, _INF)
                ka = jnp.concatenate([kept_ids, nbrs], axis=1)
                kd = jnp.concatenate([kept_d, nd_k], axis=1)
                korder = jnp.argsort(kd, axis=1, stable=True)[:, :keep_k]
                kept_ids = jnp.take_along_axis(ka, korder, axis=1)
                kept_d = jnp.take_along_axis(kd, korder, axis=1)
            return (step + 1, beam_ids, beam_d, expanded, visited,
                    kept_ids, kept_d, active.any())

        _, beam_ids, beam_d, _, _, kept_ids, kept_d, _ = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), beam_ids, beam_d, expanded, visited,
             kept_ids, kept_d, jnp.bool_(True)))

        if rerank_on:
            # fused rerank over this shard's LOCAL candidates: gather
            # the local token block, score, and let the cross-shard
            # merge rank by (negated) module score — the rerank is part
            # of the same SPMD program, no extra dispatch
            from weaviate_tpu.ops.topk import merge_across_shards

            if track:
                # the kept track's filler slots hold real-but-DISALLOWED
                # ids at kd=_INF (the unfiltered merge keeps them for
                # shape); mask them out BEFORE scoring or they would
                # earn genuine module scores and displace allowed
                # candidates in the cross-shard merge (the single-chip
                # path applies the same mask in _fused_search)
                cand = jnp.where(kept_d[:, :rerank_k] >= _INF, -1,
                                 kept_ids[:, :rerank_k])
            else:
                cand = beam_ids[:, :rerank_k]
            rvalid, scores = _rerank_module_scores(
                rerank, cand, tok_l, tmask_l, rq_r, rqm_r)
            neg = jnp.where(rvalid, -scores, _INF)
            rgids = jnp.where(rvalid, cand + base, -1)
            rmd, rmi = merge_across_shards(neg, rgids, rerank_k, axis)
            return rmi, rmd

        out_ids = beam_ids[:, :fetch]
        out_d = beam_d[:, :fetch]
        gids = jnp.where(out_ids >= 0, out_ids + base, -1)
        if not merge:
            return gids[None], out_d[None]       # [1, B, fetch] per shard
        from weaviate_tpu.ops.topk import merge_across_shards

        md, mi = merge_across_shards(out_d, gids, fetch, axis)
        if track:
            kg = jnp.where(kept_ids >= 0, kept_ids + base, -1)
            kept_ids = jnp.where(kg >= 0, kg, -1)
            kmd, kmi = merge_across_shards(kept_d, kept_ids, keep_k, axis)
            return mi, md, kmi, kmd
        return mi, md

    q_spec = P(*([None] * np.ndim(queries)))
    op_specs = tuple(_op_partition_spec(a, cap, axis) for a in operands)
    in_specs = [q_spec, op_specs, P(axis, None), P(axis),
                P(axis, None, None, None), P(None, axis)]
    args = [queries, operands, adjacency, present, upper_adj, upper_slots]
    if seeds is not None:
        in_specs.append(P(axis, None))
        args.append(seeds)
    if qeps is not None:
        in_specs.append(P(None))
        args.append(qeps)
    if allow is not None:
        in_specs.append(P(axis))
        args.append(allow)
    if rerank_on:
        in_specs += [P(axis, None, None), P(axis, None),
                     P(None, None, None), P(None, None)]
        args += [rerank_tokens, rerank_tmask, rerank_q, rerank_qmask]
    if not merge:
        out_specs = (P(axis, None, None), P(axis, None, None))
    elif rerank_on:
        out_specs = (P(None, None), P(None, None))
    elif track:
        out_specs = (P(None, None),) * 4
    else:
        out_specs = (P(None, None), P(None, None))
    fn = _shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=out_specs)
    return fn(*args)


# jit-cache-stable empty per-shard upper tables ([n, 0, 1, 1] + [0, cap])
# for layer-0-only mesh walks; cached per (mesh, cap) so construction
# never re-places them per dispatch
_mesh_empty_upper_cache: dict = {}


def _mesh_empty_upper(mesh, cap: int, axis: str = "shard"):
    key = (mesh, cap)
    out = _mesh_empty_upper_cache.get(key)
    if out is None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = int(mesh.devices.size)
        out = (
            jax.device_put(
                np.zeros((n, 0, 1, 1), np.int32),
                NamedSharding(mesh, P(axis, None, None, None))),
            jax.device_put(
                np.zeros((0, cap), np.int32),
                NamedSharding(mesh, P(None, axis))),
        )
        _mesh_empty_upper_cache[key] = out
    return out


def device_search_mesh(
    scorer,
    queries,
    operands,
    adjacency,
    present,
    mesh,
    ef: int,
    max_steps: int,
    fetch: int,
    seeds=None,
    qeps=None,
    upper_adj=None,
    upper_slots=None,
    allow=None,
    keep_k: int = 0,
    expand: int = 0,
    merge: bool = True,
    axis: str = "shard",
    rerank=None,
    rerank_k: int = 0,
    rerank_q=None,
    rerank_qmask=None,
    rerank_tokens=None,
    rerank_tmask=None,
):
    """Dispatch ONE fused SPMD walk spanning every mesh shard (per-shard
    descent + beam + on-device cross-shard merge). Exactly one of
    ``seeds`` (serving: per-shard entrypoint table) / ``qeps``
    (construction: per-query global entrypoints, unmerged output) must
    be given. Increments the module dispatch counter — the same hook
    behind the single-chip one-dispatch-per-batch contract."""
    global _dispatch_count
    if (seeds is None) == (qeps is None):
        raise ValueError("exactly one of seeds/qeps must be provided")
    if upper_adj is None or upper_adj.shape[1] == 0:
        upper_adj, upper_slots = _mesh_empty_upper(
            mesh, adjacency.shape[0], axis)
    if rerank is not None:
        rerank_k = min(rerank_k, keep_k if (allow is not None
                                            and keep_k > 0) else ef)
    _dispatch_count += 1
    from weaviate_tpu.monitoring.metrics import MESH_BEAM_DISPATCH

    MESH_BEAM_DISPATCH.inc(mode="search" if merge else "construction")
    if merge:
        # the cross-shard merge is a collective: dispatches must enqueue
        # on every device in one total order or two concurrent programs
        # deadlock at the all_gather rendezvous (see
        # parallel.sharded_search.mesh_dispatch_lock)
        from weaviate_tpu.parallel.sharded_search import mesh_dispatch_lock

        with mesh_dispatch_lock():
            return _fused_mesh_search(
                scorer, queries, operands, adjacency, present, upper_adj,
                upper_slots, ef=ef, max_steps=max_steps, fetch=fetch,
                mesh=mesh, axis=axis, merge=merge, seeds=seeds, qeps=qeps,
                allow=allow, keep_k=keep_k, expand=expand, rerank=rerank,
                rerank_k=rerank_k, rerank_q=rerank_q,
                rerank_qmask=rerank_qmask, rerank_tokens=rerank_tokens,
                rerank_tmask=rerank_tmask)
    # merge=False (construction) has no cross-device rendezvous — the
    # per-shard walks are independent programs and cannot invert
    # graftlint: allow[unlocked-collective-dispatch] reason=merge=False traces no all_gather; independent per-shard programs cannot invert
    return _fused_mesh_search(
        scorer, queries, operands, adjacency, present, upper_adj,
        upper_slots, ef=ef, max_steps=max_steps, fetch=fetch, mesh=mesh,
        axis=axis, merge=merge, seeds=seeds, qeps=qeps, allow=allow,
        keep_k=keep_k, expand=expand)


# jit-cache-stable empty upper tables for layer-0-only walks (the shapes
# participate in the compile key, so they must never vary)
_NO_UPPER_ADJ = None
_NO_UPPER_SLOTS = None


def _empty_upper():
    global _NO_UPPER_ADJ, _NO_UPPER_SLOTS
    if _NO_UPPER_ADJ is None:
        _NO_UPPER_ADJ = jnp.zeros((0, 1, 1), jnp.int32)
        _NO_UPPER_SLOTS = jnp.zeros((0, 1), jnp.int32)
    return _NO_UPPER_ADJ, _NO_UPPER_SLOTS


def device_search(
    scorer,
    queries,
    operands,
    adjacency,
    present,
    eps,
    ef: int,
    max_steps: int,
    upper_adj=None,
    upper_slots=None,
    allow=None,
    keep_k: int = 0,
    expand: int = 0,
    rerank=None,
    rerank_k: int = 0,
    rerank_q=None,
    rerank_qmask=None,
    rerank_tokens=None,
    rerank_tmask=None,
):
    """Dispatch ONE fused walk program (descent + layer-0 beam). Without
    upper tables the walk starts at layer 0 (construction / flat graphs).
    With a ``rerank`` module the same single program also runs the fused
    rerank stage over its top candidates (see ``_fused_search``).
    Increments the module dispatch counter — the test hook behind the
    one-dispatch-per-batch contract."""
    global _dispatch_count
    if upper_adj is None or upper_adj.shape[0] == 0:
        upper_adj, upper_slots = _empty_upper()
    if rerank is not None:
        # the rerank pool is drawn from the kept track when filtered,
        # the beam otherwise — never wider than its source
        rerank_k = min(rerank_k, keep_k if (allow is not None
                                            and keep_k > 0) else ef)
    _dispatch_count += 1
    return _fused_search(
        scorer, queries, operands, adjacency, present,
        jnp.asarray(eps, jnp.int32), upper_adj, upper_slots,
        ef=ef, max_steps=max_steps, allow=allow, keep_k=keep_k,
        expand=expand, rerank=rerank, rerank_k=rerank_k, rerank_q=rerank_q,
        rerank_qmask=rerank_qmask, rerank_tokens=rerank_tokens,
        rerank_tmask=rerank_tmask)


def beam_search_layer0(
    queries: jnp.ndarray,
    corpus: jnp.ndarray,
    adjacency: jnp.ndarray,
    present: jnp.ndarray,
    eps: jnp.ndarray,
    ef: int,
    max_steps: int,
    metric: str = "l2-squared",
    precision: str = "bf16",
    allow: Optional[jnp.ndarray] = None,
    keep_k: int = 0,
):
    """Layer-0-only raw-corpus walk (compat wrapper over the pluggable
    kernel; the scorer-generic ``device_search`` is the primary entry)."""
    return device_search(
        RawScorer(metric, precision), queries, (corpus,), adjacency,
        present, eps, ef=ef, max_steps=max_steps, allow=allow,
        keep_k=keep_k)


# ---------------------------------------------------------------------------
# fused flat scan + rerank: the multivector (MUVERA) serving program
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("module", "fetch", "k", "metric", "precision"))
def _fused_flat_rerank(
    module,                   # static DeviceRerankModule (hashable)
    queries: jnp.ndarray,     # [B, F] coarse-space queries (e.g. FDE)
    corpus: jnp.ndarray,      # [N, F] coarse corpus (HBM)
    valid: jnp.ndarray,       # [N] bool
    q_tokens: jnp.ndarray,    # [B, Tq, D] rerank query token sets
    q_mask: jnp.ndarray,      # [B, Tq] bool
    tokens: jnp.ndarray,      # [N, T, D] candidate token plane (HBM)
    tmask: jnp.ndarray,       # [N, T] bool
    fetch: int,
    k: int,
    allow: Optional[jnp.ndarray] = None,
    metric: str = "dot",
    precision: str = "bf16",
):
    """Coarse flat scan → gather candidate token planes → module score →
    on-device top-k, ONE program. This is ``MultiVectorIndex``'s serving
    path: the MUVERA FDE scan produces ``fetch`` candidates and the
    exact MaxSim (or any device module) reranks them WITHOUT the
    candidate ids ever round-tripping to the host — the fix for the
    coarse-search→host→rescore pattern the pre-rerank code paid."""
    from weaviate_tpu.ops.distance import flat_search

    d, ids = flat_search(queries, corpus, k=fetch, metric=metric,
                         valid_mask=valid, allow_mask=allow,
                         precision=precision)
    return _rerank_stage(module, k, ids.astype(jnp.int32)[:, :fetch],
                         tokens, tmask, q_tokens, q_mask)


def fused_flat_rerank(module, queries, corpus, valid, q_tokens, q_mask,
                      tokens, tmask, fetch: int, k: int, allow=None,
                      metric: str = "dot", precision: str = "bf16"):
    """Dispatch ONE fused coarse-scan + rerank program. Increments the
    module dispatch counter (same hook as the beam's one-dispatch
    contract). ``k`` is clamped to ``fetch`` — the rerank pool."""
    global _dispatch_count
    _dispatch_count += 1
    return _fused_flat_rerank(
        module, queries, corpus, valid, q_tokens, q_mask, tokens, tmask,
        fetch=fetch, k=min(k, fetch), allow=allow, metric=metric,
        precision=precision)


# ---------------------------------------------------------------------------
# multi-target fused search: N named-vector walks + weighted join, ONE jit
# ---------------------------------------------------------------------------
#
# The reference fans out one goroutine per target vector and joins the
# candidate lists on the host (traverser multi-target path; PAPER.md
# §2.9 intra-query parallelism). The jax-native analogue inlines each
# target's ALREADY-JITTED fused walk (`_fused_search` /
# `_fused_mesh_search`) into one outer program — per-target descent +
# beam over that target's own HBM planes, then a generalized fusion
# stage (the hybrid-search join with target weights as a TRACED input,
# so sum / average / manualWeights requests share one compiled program)
# and one on-device top-k. N targets still cost exactly one dispatch.
#
# Join semantics (host oracle: query/multi_target.combine_multi_target):
#   "weighted"  — Σ_t w_t · d_t   (sum: w=1; average: w=1/T;
#                 manualWeights: caller weights)
#   "minimum"   — min_t d_t
#   "relative"  — per-target min-max normalize over the candidate pool,
#                 then Σ_t w_t · norm_t (relativeScore)
# A candidate missing ANY target's vector is masked to _INF — exactly
# the host oracle's drop-if-missing semantics.

_MT_JOINS = ("weighted", "minimum", "relative")


def _mt_dedup(cand):
    """In-row dedup of the cross-target candidate union: ascending sort
    clusters duplicates (and -1 pads, which sort first), adjacent equals
    collapse to -1. Order is irrelevant — the join re-ranks the pool."""
    cand = jnp.sort(cand, axis=1)
    dup = (cand[:, 1:] == cand[:, :-1]) & (cand[:, 1:] >= 0)
    return jnp.concatenate(
        [cand[:, :1], jnp.where(dup, -1, cand[:, 1:])], axis=1)


def _mt_join(join, weights, stack, valid_all):
    """[B, C, T] per-target distances + [B, C] validity → [B, C]
    combined distance (invalid slots at _INF). ``weights`` [B, T] is
    traced — per-REQUEST weights ride the batch, so differently-weighted
    requests over the same target set share one compiled program."""
    if join == "minimum":
        combined = jnp.min(stack, axis=-1)
    elif join == "relative":
        # min-max normalize each target over the VALID candidate pool
        # (the host oracle normalizes over its own top-k pool; the pools
        # coincide up to walk recall)
        vmask = valid_all[:, :, None]
        lo = jnp.min(jnp.where(vmask, stack, _INF), axis=1, keepdims=True)
        hi = jnp.max(jnp.where(vmask, stack, _NEG_INF), axis=1,
                     keepdims=True)
        span = hi - lo
        span = jnp.where(span > 0, span, jnp.float32(1.0))
        combined = jnp.sum(((stack - lo) / span) * weights[:, None, :],
                           axis=-1)
    else:
        combined = jnp.sum(stack * weights[:, None, :], axis=-1)
    return jnp.where(valid_all, combined, _INF)


def _mt_topk(cand, combined, fetch):
    neg, sel = jax.lax.top_k(-combined, fetch)
    ids = jnp.take_along_axis(cand, sel, axis=1)
    d_out = -neg
    ok = d_out < _INF
    return jnp.where(ok, ids, -1), jnp.where(ok, d_out, _INF)


@functools.partial(
    jax.jit,
    static_argnames=("scorers", "efs", "max_steps", "fetch", "join",
                     "keep_ks", "expands"))
def _fused_multi_search(
    scorers,        # static tuple of per-target Scorers
    weights,        # [B, T] traced join weights (rows = requests)
    queries,        # tuple of per-target query reps [B, ...]
    operands,       # tuple of per-target HBM operand tuples
    adjacency,      # tuple of [N_t, M0_t] int32 layer-0 adjacencies
    present,        # tuple of [N_t] bool node-exists masks
    eps,            # tuple of [B] int32 per-target entrypoints
    upper_adj,      # tuple of [L_t, S_t, M_t] slot-compacted tables
    upper_slots,    # tuple of [L_t, N_t] node -> slot maps
    efs,            # static tuple: per-target beam width
    max_steps: int,
    fetch: int,     # static: per-target pool width AND output width
    join: str,      # static: "weighted" | "minimum" | "relative"
    allows=None,    # tuple of Optional [N_t] bool (shared docid space)
    keep_ks=None,   # static tuple: per-target kept-track width
    expands=None,   # static tuple: per-target two-hop widening budget
):
    """→ (ids [B, fetch], combined [B, fetch]) ascending by joined
    distance; -1/_INF padded. One program: T inlined fused walks (each
    over its own planes/graph/scorer), candidate-union dedup, per-target
    cross-scoring of the union (a candidate surfaced by target A's walk
    gets its exact target-B distance from B's scorer — the device
    analogue of the host oracle's gap-fill recompute), weighted join,
    one top-k. Node ids are shard docids, shared across every target's
    graph, which is what makes cross-target scoring well-defined."""
    t_count = len(scorers)
    cands = []
    for t in range(t_count):
        out = _fused_search(
            scorers[t], queries[t], operands[t], adjacency[t], present[t],
            eps[t], upper_adj[t], upper_slots[t], ef=efs[t],
            max_steps=max_steps, allow=allows[t], keep_k=keep_ks[t],
            expand=expands[t])
        pool = out[2] if (allows[t] is not None and keep_ks[t] > 0) \
            else out[0]
        cands.append(pool[:, :fetch])
    cand = _mt_dedup(jnp.concatenate(cands, axis=1))

    per_d = []
    valid_all = cand >= 0
    for t in range(t_count):
        cap_t = present[t].shape[0]
        safe = jnp.clip(cand, 0, cap_t - 1)
        # a docid can exceed target t's capacity (planes grow
        # independently) or lack a t-vector (present False) — both mean
        # "missing this target", which invalidates the candidate
        ok_t = (cand >= 0) & (cand < cap_t) & jnp.take(present[t], safe)
        d_t = _masked_scores(scorers[t], queries[t],
                             jnp.where(ok_t, cand, -1), operands[t])
        per_d.append(d_t)
        valid_all &= ok_t
    combined = _mt_join(join, weights, jnp.stack(per_d, axis=-1),
                        valid_all)
    return _mt_topk(cand, combined, fetch)


@functools.partial(
    jax.jit,
    static_argnames=("scorers", "efs", "max_steps", "fetch", "join",
                     "keep_ks", "expands", "mesh", "axis"))
def _fused_multi_mesh_search(
    scorers,
    weights,        # [B, T] replicated
    queries,        # tuple of per-target [B, ...] replicated
    operands,       # tuple of per-target operand tuples (row-sharded)
    adjacency,      # tuple of [cap_t, M0] row-sharded, LOCAL ids
    present,        # tuple of [cap_t] bool row-sharded
    seeds,          # tuple of [n, E] int32 sharded on 0, LOCAL ids
    upper_adj,      # tuple of [n, Lv, S, M] sharded on 0
    upper_slots,    # tuple of [Lv, cap_t] sharded on dim 1
    efs,
    max_steps: int,
    fetch: int,
    join: str,
    mesh=None,
    axis: str = "shard",
    allows=None,
    keep_ks=None,
    expands=None,
):
    """Mesh twin: T inlined SPMD walks (each already merging across
    shards on device) feed one replicated candidate union; a second
    shard_map cross-scores the union against every target's row-sharded
    planes — each shard scores the docids IT owns (per-target
    capacities, hence shard boundaries, may differ; global docid = shard
    base + local row reconstructs identically for every target) and
    ``pmin``/``pmax`` resolve ownership — then the join + top-k run
    replicated. Still exactly ONE dispatch for the whole mesh."""
    from jax.sharding import PartitionSpec as P

    from weaviate_tpu.parallel.sharded_search import _shard_map

    t_count = len(scorers)
    cands = []
    for t in range(t_count):
        out = _fused_mesh_search(
            scorers[t], queries[t], operands[t], adjacency[t], present[t],
            upper_adj[t], upper_slots[t], ef=efs[t], max_steps=max_steps,
            fetch=fetch, mesh=mesh, axis=axis, merge=True, seeds=seeds[t],
            allow=allows[t], keep_k=keep_ks[t], expand=expands[t])
        pool = out[2] if (allows[t] is not None and keep_ks[t] > 0) \
            else out[0]
        cands.append(pool[:, :fetch])
    cand = _mt_dedup(jnp.concatenate(cands, axis=1))

    def xscore(cand_r, *rest):
        rest = list(rest)
        per_d = []
        ok_all = cand_r >= 0
        for t in range(t_count):
            q_t = rest.pop(0)
            ops_t = rest.pop(0)
            pres_t = rest.pop(0)
            n_local = pres_t.shape[0]
            base = jax.lax.axis_index(axis) * n_local
            loc = cand_r - base
            inr = (cand_r >= 0) & (loc >= 0) & (loc < n_local)
            safe = jnp.clip(loc, 0, n_local - 1)
            ok = inr & jnp.take(pres_t, safe)
            d = _masked_scores(scorers[t], q_t,
                               jnp.where(ok, loc, -1), ops_t)
            # exactly one shard owns each docid for target t; the
            # non-owners hold _INF / False, so pmin/pmax ARE the
            # ownership resolution (and leave the result replicated)
            d = jax.lax.pmin(d, axis)
            okg = jax.lax.pmax(ok.astype(jnp.int32), axis) > 0
            per_d.append(jnp.where(okg, d, _INF))
            ok_all &= okg
        return jnp.stack(per_d, axis=-1), ok_all

    in_specs = [P(None, None)]
    args = [cand]
    for t in range(t_count):
        cap_t = present[t].shape[0]
        in_specs += [
            P(*([None] * np.ndim(queries[t]))),
            tuple(_op_partition_spec(a, cap_t, axis)
                  for a in operands[t]),
            P(axis),
        ]
        args += [queries[t], operands[t], present[t]]
    fn = _shard_map(xscore, mesh=mesh, in_specs=tuple(in_specs),
                    out_specs=(P(None, None, None), P(None, None)))
    stack, valid_all = fn(*args)
    combined = _mt_join(join, weights, stack, valid_all)
    return _mt_topk(cand, combined, fetch)


def _mt_norm_static(t_count, allows, keep_ks, expands):
    allows = tuple(allows) if allows is not None else (None,) * t_count
    keep_ks = tuple(keep_ks) if keep_ks is not None else (0,) * t_count
    expands = tuple(expands) if expands is not None else (0,) * t_count
    return allows, keep_ks, expands


def device_multi_search(
    scorers,
    weights,
    queries,
    operands,
    adjacency,
    present,
    eps,
    upper_adjs,
    upper_slots,
    efs,
    max_steps: int,
    fetch: int,
    join: str,
    allows=None,
    keep_ks=None,
    expands=None,
):
    """Dispatch ONE fused multi-target program: per-target walks +
    cross-scored weighted join + top-k. Increments the module dispatch
    counter once — the test hook behind 'N targets, one dispatch'."""
    global _dispatch_count
    t_count = len(scorers)
    if join not in _MT_JOINS:
        raise ValueError(f"unknown multi-target join {join!r}")
    allows, keep_ks, expands = _mt_norm_static(
        t_count, allows, keep_ks, expands)
    ua, us = [], []
    for t in range(t_count):
        a, s = upper_adjs[t], upper_slots[t]
        if a is None or a.shape[0] == 0:
            a, s = _empty_upper()
        ua.append(a)
        us.append(s)
    _dispatch_count += 1
    return _fused_multi_search(
        tuple(scorers), weights, tuple(queries), tuple(operands),
        tuple(adjacency), tuple(present),
        tuple(jnp.asarray(e, jnp.int32) for e in eps),
        tuple(ua), tuple(us), efs=tuple(efs), max_steps=max_steps,
        fetch=fetch, join=join, allows=allows, keep_ks=keep_ks,
        expands=expands)


def device_multi_search_mesh(
    scorers,
    weights,
    queries,
    operands,
    adjacency,
    present,
    seeds,
    mesh,
    efs,
    max_steps: int,
    fetch: int,
    join: str,
    upper_adjs=None,
    upper_slots=None,
    allows=None,
    keep_ks=None,
    expands=None,
    axis: str = "shard",
):
    """Mesh twin of :func:`device_multi_search`: one SPMD program spans
    every chip AND every target. Serialized on the collective-dispatch
    lock like every merged mesh walk."""
    global _dispatch_count
    t_count = len(scorers)
    if join not in _MT_JOINS:
        raise ValueError(f"unknown multi-target join {join!r}")
    allows, keep_ks, expands = _mt_norm_static(
        t_count, allows, keep_ks, expands)
    ua, us = [], []
    for t in range(t_count):
        a = None if upper_adjs is None else upper_adjs[t]
        s = None if upper_slots is None else upper_slots[t]
        if a is None or a.shape[1] == 0:
            a, s = _mesh_empty_upper(mesh, adjacency[t].shape[0], axis)
        ua.append(a)
        us.append(s)
    _dispatch_count += 1
    from weaviate_tpu.monitoring.metrics import MESH_BEAM_DISPATCH

    MESH_BEAM_DISPATCH.inc(mode="search")
    from weaviate_tpu.parallel.sharded_search import mesh_dispatch_lock

    with mesh_dispatch_lock():
        return _fused_multi_mesh_search(
            tuple(scorers), weights, tuple(queries), tuple(operands),
            tuple(adjacency), tuple(present), tuple(seeds),
            tuple(ua), tuple(us), efs=tuple(efs), max_steps=max_steps,
            fetch=fetch, join=join, mesh=mesh, axis=axis, allows=allows,
            keep_ks=keep_ks, expands=expands)


class DeviceAdjacency:
    """Incrementally synced device mirror of the host graph topology.

    Layer 0: the host graph mutates rows during inserts/deletes
    (set_neighbors / append_neighbor / rewires); uploading the full
    [N, 2M] array per search would swamp the link, so the mirror tracks
    dirty rows and scatters ONLY those before a search (one device
    call). Capacity growth re-uploads wholesale (rare: doubling).

    Upper layers: compact slot-addressed tables ([L, S, M] adjacency +
    [L, N] node→slot maps, top level first) consumed by the fused
    kernel's greedy descent. They hold ~N/(M-1) rows total, so a version
    bump on the host graph (``HostGraph.upper_version``) rebuilds them
    wholesale — cheap, and only when construction actually touched a
    level ≥ 1."""

    def __init__(self, graph):
        self.graph = graph
        self._adj = None        # device [cap, M0] int32
        self._present = None    # device [cap] bool
        self._synced_cap = 0
        self._dirty: set[int] = set()
        self._upper = None      # (upper_adj [L, S, M], upper_slots [L, cap])
        self._upper_version = -1
        self._upper_cap = 0
        # monkeypatch-free hook: HostGraph calls log ops; we piggyback on
        # set_neighbors/append/remove via mark_dirty from the index layer

    def mark_dirty(self, *node_ids) -> None:
        self._dirty.update(int(x) for x in node_ids)

    def drop_device(self) -> int:
        """Release the mirrored tables from HBM (tiering warm tier).
        Returns bytes released. The next ``sync`` re-uploads wholesale at
        the same shapes, so compiled beam programs keep hitting their
        cache — dropping never latches the beam off."""
        freed = self.nbytes
        self._adj = None
        self._present = None
        self._synced_cap = 0
        self._dirty.clear()
        self._upper = None
        self._upper_version = -1
        return freed

    @property
    def nbytes(self) -> int:
        """HBM footprint of the mirrored topology (layer 0 + upper)."""
        total = 0
        for a in (self._adj, self._present):
            if a is not None:
                total += a.nbytes
        if self._upper is not None:
            total += sum(a.nbytes for a in self._upper)
        return total

    def sync(self):
        """→ (adjacency, present) device arrays, up to date."""
        g = self.graph
        cap = g.capacity
        if self._adj is None or self._synced_cap != cap:
            self._adj = jnp.asarray(g.layer0, jnp.int32)
            pres = g.levels >= 0
            self._present = jnp.asarray(pres)
            self._synced_cap = cap
            self._dirty.clear()
            return self._adj, self._present
        if self._dirty:
            # atomic swap: construction threads keep calling mark_dirty
            # concurrently — iterating the live set would race (and a
            # dropped id would leave a device row stale forever)
            dirty, self._dirty = self._dirty, set()
            idx = np.fromiter((i for i in dirty if i < cap), np.int32)
            if len(idx):
                rows = jnp.asarray(g.layer0[idx], jnp.int32)
                self._adj = self._adj.at[jnp.asarray(idx)].set(rows)
                self._present = self._present.at[jnp.asarray(idx)].set(
                    jnp.asarray(g.levels[idx] >= 0))
        return self._adj, self._present

    def sync_upper(self):
        """→ (upper_adj, upper_slots) device tables for the fused
        descent; rebuilt only when the host graph's upper_version (or
        capacity) moved."""
        g = self.graph
        ver = getattr(g, "upper_version", 0)
        cap = g.capacity
        if (self._upper is not None and self._upper_version == ver
                and self._upper_cap == cap):
            return self._upper
        levels = max(0, int(g.max_level))
        if levels == 0:
            self._upper = _empty_upper()
        else:
            # searches read the level dicts lock-free while inserts grow
            # them (same torn-read contract as the host walk); _snap_upper
            # owns the retry — a transient resize MUST NOT propagate, or
            # the caller's blanket fallback would latch the beam off.
            # Index 0 = TOP level (the descent order).
            snap = _snap_upper(g, levels)
            if snap is None:
                # pathological churn: serve the previous tables (stale
                # topology is valid — the walk just sees older edges) or
                # start at layer 0; leave version unmoved so the next
                # search retries the rebuild
                return self._upper if self._upper is not None \
                    else _empty_upper()
            sizes = [len(items) for items in snap]
            # pow2-pad the slot axis so steady growth reuses compiles
            s_pad = 1 << max(3, (max(1, max(sizes)) - 1).bit_length())
            adj = np.full((levels, s_pad, g.m), -1, np.int32)
            slots = np.full((levels, cap), -1, np.int32)
            for li, items in enumerate(snap):
                for slot, (node, nbrs) in enumerate(items):
                    if node >= cap:
                        continue  # torn read mid-grow; next sync catches up
                    slots[li, node] = slot
                    nb = nbrs[:g.m]
                    if len(nb):
                        adj[li, slot, :len(nb)] = nb
            self._upper = (jnp.asarray(adj), jnp.asarray(slots))
        self._upper_version = ver
        self._upper_cap = cap
        return self._upper


def _snap_upper(g, levels: int):
    """Lock-free snapshot of the upper-level dicts, top level first, with
    the same short RuntimeError retry the single-chip mirror uses (a
    dict resizing under a concurrent insert MUST NOT latch the beam
    off). None = pathological churn; caller serves stale tables."""
    for _ in range(8):
        try:
            return [list(g.upper.get(lv, {}).items())
                    for lv in range(levels, 0, -1)]
        except RuntimeError:  # resized under us; re-read
            continue
    return None


# per-mesh jitted mirror scatters with pinned out-shardings (dirty-row
# sync must stay distributed, never gather the adjacency to one device)
_mesh_adj_fns_cache: dict = {}


def _mesh_adj_fns(mesh):
    fns = _mesh_adj_fns_cache.get(mesh)
    if fns is None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from weaviate_tpu.parallel.mesh import SHARD_AXIS

        row = NamedSharding(mesh, P(SHARD_AXIS, None))
        flat = NamedSharding(mesh, P(SHARD_AXIS))
        fns = (
            row, flat,
            # graftlint: allow[jit-in-loop] reason=compiled once per mesh via _mesh_adj_fns_cache
            jax.jit(lambda a, i, r: a.at[i].set(r), out_shardings=row),
            # graftlint: allow[jit-in-loop] reason=compiled once per mesh via _mesh_adj_fns_cache
            jax.jit(lambda a, i, v: a.at[i].set(v), out_shardings=flat),
        )
        _mesh_adj_fns_cache[mesh] = fns
    return fns


class MeshDeviceAdjacency:
    """Mesh twin of :class:`DeviceAdjacency`: the shard-local subgraph
    topology mirrored across the mesh, plus the per-shard entrypoint
    seed table the fused SPMD walk starts from.

    Membership is the store's row-block layout: ``shard(id) = id // L``
    with ``L = plane_capacity / n_shards`` (``cap_fn`` reports the
    backend's device-plane capacity — the raw corpus or the quantized
    code planes — so adjacency rows shard EXACTLY like the arrays the
    scorer gathers). Mesh construction links nodes only within their
    shard, so adjacency content is stored as LOCAL indices and each
    device's block is self-contained. Growth multiplies capacity by an
    integer factor (store contract), which only COARSENS membership —
    on a capacity move the mirror rebuilds wholesale, regroups the seed
    lists (previously separate shards merge, leaving multiple seed
    components per shard — all of them stay seeds), and bumps ``epoch``
    so the dispatcher never coalesces requests across the move."""

    MAX_SEEDS = 8

    def __init__(self, graph, mesh, cap_fn):
        from weaviate_tpu.parallel.mesh import mesh_size

        self.graph = graph
        self.mesh = mesh
        self.n = mesh_size(mesh)
        self.cap_fn = cap_fn
        self.epoch = 0
        self._adj = None
        self._present = None
        self._synced_cap = 0
        self._dirty: set[int] = set()
        self._upper = None
        self._upper_version = -1
        self._upper_cap = 0
        self._seed_lists: list[list[int]] = [[] for _ in range(self.n)]
        self._seeds_dev = None
        self._seeds_key = None
        self._seeds_version = 0

    # -- membership -------------------------------------------------------
    def capacity(self) -> int:
        return int(self.cap_fn())

    def rows_per_shard(self) -> int:
        return self.capacity() // self.n

    def shard_of(self, ids):
        from weaviate_tpu.parallel.mesh import shard_of

        return shard_of(ids, self.capacity(), self.n)

    # -- seeds ------------------------------------------------------------
    def add_seed(self, node: int) -> None:
        lst = self._seed_lists[int(node) // self.rows_per_shard()]
        if node not in lst:
            lst.append(int(node))
            del lst[self.MAX_SEEDS:]
            self._seeds_version += 1

    def has_seed(self, shard: int) -> bool:
        return bool(self._seed_lists[shard])

    def primary_seed(self, shard: int) -> int:
        """The shard's highest-level present seed (construction descends
        from it; its level IS the shard's max walkable level), -1 when
        the shard is empty."""
        g = self.graph
        best, best_lv = -1, -1
        for x in self._seed_lists[shard]:
            if x < g.capacity and g.levels[x] >= 0:
                lv = int(g.levels[x])
                if lv > best_lv:
                    best, best_lv = x, lv
        return best

    def _regroup_seeds(self, rows_per_shard: int) -> None:
        flat = [x for lst in self._seed_lists for x in lst]
        self._seed_lists = [[] for _ in range(self.n)]
        for x in flat:
            lst = self._seed_lists[x // rows_per_shard]
            if x not in lst:
                lst.append(x)
        for lst in self._seed_lists:
            del lst[self.MAX_SEEDS:]
        self._seeds_version += 1

    def refresh_seeds(self) -> None:
        """Drop hard-removed seeds and re-elect for shards left seedless
        (tombstone cleanup can physically remove a seed node)."""
        g = self.graph
        cap = self.capacity()
        rows = self.rows_per_shard()
        gc = min(g.capacity, cap)
        changed = False
        for s, lst in enumerate(self._seed_lists):
            keep = [x for x in lst if x < g.capacity and g.levels[x] >= 0]
            if len(keep) != len(lst):
                self._seed_lists[s] = keep
                changed = True
        present = np.nonzero(g.levels[:gc] >= 0)[0]
        if len(present):
            by_shard = present // rows
            for s in np.unique(by_shard):
                if not self._seed_lists[int(s)]:
                    members = present[by_shard == s]
                    top = members[np.argmax(g.levels[members])]
                    self._seed_lists[int(s)].append(int(top))
                    changed = True
        if changed:
            self._seeds_version += 1

    def sync_seeds(self):
        """→ [n, E] int32 device table (sharded on the shard axis) of
        LOCAL seed indices, -1 padded; E pow2-padded so seed-list growth
        reuses compiles."""
        cap = self._synced_cap or self.capacity()
        rows = cap // self.n
        key = (self._seeds_version, cap)
        if self._seeds_dev is not None and self._seeds_key == key:
            return self._seeds_dev
        longest = max(1, max(len(lst) for lst in self._seed_lists))
        e_pad = 1 << (longest - 1).bit_length()
        arr = np.full((self.n, e_pad), -1, np.int32)
        for s, lst in enumerate(self._seed_lists):
            vals = [x % rows for x in lst if x < cap]
            arr[s, :len(vals)] = vals
        row_sh, _flat, _sr, _sf = _mesh_adj_fns(self.mesh)
        self._seeds_dev = jax.device_put(arr, row_sh)
        self._seeds_key = key
        return self._seeds_dev

    # -- residency (tiering warm tier) ------------------------------------
    def mark_dirty(self, *node_ids) -> None:
        self._dirty.update(int(x) for x in node_ids)

    def drop_device(self) -> int:
        """Release every shard's mirrored slice from HBM; the next sync
        re-uploads wholesale at identical shapes (promotion costs one
        sharded upload, zero recompiles)."""
        freed = self.nbytes
        self._adj = None
        self._present = None
        self._synced_cap = 0
        self._dirty.clear()
        self._upper = None
        self._upper_version = -1
        self._seeds_dev = None
        self._seeds_key = None
        return freed

    @property
    def nbytes(self) -> int:
        total = 0
        for a in (self._adj, self._present, self._seeds_dev):
            if a is not None:
                total += a.nbytes
        if self._upper is not None:
            total += sum(a.nbytes for a in self._upper)
        return total

    # -- sync -------------------------------------------------------------
    def sync(self):
        """→ (adjacency, present) sharded device arrays, up to date.
        Content is LOCAL neighbor indices (edges are intra-shard by
        construction, so ``global % L`` is exact)."""
        g = self.graph
        cap = self.capacity()
        rows = cap // self.n
        row_sh, flat_sh, scatter_rows, scatter_flat = _mesh_adj_fns(self.mesh)
        if self._adj is None or self._synced_cap != cap:
            if self._synced_cap and self._synced_cap != cap:
                # membership coarsened (integer-factor growth): regroup
                # the seed lists and fence the dispatcher epoch
                self._regroup_seeds(rows)
                self.epoch += 1
            gc = min(g.capacity, cap)
            adj = np.full((cap, g.m0), -1, np.int32)
            src = g.layer0[:gc]
            adj[:gc] = np.where(src >= 0, src % rows, -1)
            pres = np.zeros(cap, bool)
            pres[:gc] = g.levels[:gc] >= 0
            self._adj = jax.device_put(adj, row_sh)
            self._present = jax.device_put(pres, flat_sh)
            self._synced_cap = cap
            self._dirty.clear()
            self._update_shard_gauges(pres, rows)
            return self._adj, self._present
        if self._dirty:
            dirty, self._dirty = self._dirty, set()
            idx = np.fromiter(
                (i for i in dirty if i < min(cap, g.capacity)), np.int32)
            if len(idx):
                src = g.layer0[idx]
                local = np.where(src >= 0, src % rows, -1).astype(np.int32)
                jidx = jnp.asarray(idx)
                self._adj = scatter_rows(self._adj, jidx, jnp.asarray(local))
                self._present = scatter_flat(
                    self._present, jidx, jnp.asarray(g.levels[idx] >= 0))
        return self._adj, self._present

    def sync_upper(self):
        """→ per-shard compact upper tables: ([n, Lv, S, M] adjacency
        sharded on the shard axis, content LOCAL; [Lv, cap] node→slot
        sharded on the node axis). Rebuilt wholesale when the host
        graph's upper_version (or capacity) moves."""
        g = self.graph
        ver = getattr(g, "upper_version", 0)
        cap = self._synced_cap or self.capacity()
        if (self._upper is not None and self._upper_version == ver
                and self._upper_cap == cap):
            return self._upper
        rows = cap // self.n
        levels = max(0, int(g.max_level))
        if levels == 0:
            self._upper = _mesh_empty_upper(self.mesh, cap)
        else:
            snap = _snap_upper(g, levels)
            if snap is None:
                # pathological churn: serve the previous tables (stale
                # topology is valid) or start at layer 0; version stays
                # unmoved so the next search retries the rebuild
                return self._upper if self._upper is not None \
                    else _mesh_empty_upper(self.mesh, cap)
            per: list[list[list]] = [
                [[] for _ in range(self.n)] for _ in range(levels)]
            for li, items in enumerate(snap):
                for node, nbrs in items:
                    if node >= cap:
                        continue  # torn read mid-grow; next sync catches up
                    per[li][node // rows].append((node, nbrs))
            smax = max(
                (len(pl) for lvl in per for pl in lvl), default=1)
            s_pad = 1 << max(3, (max(1, smax) - 1).bit_length())
            adj = np.full((self.n, levels, s_pad, g.m), -1, np.int32)
            slots = np.full((levels, cap), -1, np.int32)
            for li in range(levels):
                for s in range(self.n):
                    for slot, (node, nbrs) in enumerate(per[li][s]):
                        slots[li, node] = slot
                        nb = np.asarray(nbrs[:g.m], np.int64)
                        if len(nb):
                            adj[s, li, slot, :len(nb)] = nb % rows
            from jax.sharding import NamedSharding, PartitionSpec as P

            from weaviate_tpu.parallel.mesh import SHARD_AXIS

            self._upper = (
                jax.device_put(adj, NamedSharding(
                    self.mesh, P(SHARD_AXIS, None, None, None))),
                jax.device_put(slots, NamedSharding(
                    self.mesh, P(None, SHARD_AXIS))),
            )
        self._upper_version = ver
        self._upper_cap = cap
        return self._upper

    def _update_shard_gauges(self, present: np.ndarray, rows: int) -> None:
        from weaviate_tpu.monitoring.metrics import set_mesh_shard_gauges

        set_mesh_shard_gauges(present.reshape(self.n, rows).sum(axis=1))
