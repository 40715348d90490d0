"""Batched distance computation on TPU.

Replaces the reference's SIMD distancers (``hnsw/distancer/l2.go:31``,
``dot_product.go``, ``cosine_dist.go``, ``hamming.go``, ``manhattan.go`` and
their C/asm variants). Distance semantics match the reference exactly:

- ``l2-squared``: sum((a-b)^2)  (no sqrt, as in ``l2.go``)
- ``dot``:        -dot(a, b)    (negative inner product, ``dot_product.go:53``)
- ``cosine``:     1 - dot(a, b) on pre-normalized vectors
                  (``cosine_dist.go`` normalizes at insert/query time)
- ``manhattan``:  sum(|a-b|)
- ``hamming``:    count of differing dimensions (float variant, ``hamming.go``)

All functions operate on batches and are jit-friendly (static shapes, no
data-dependent control flow). Lower distance is always better; top-k selection
negates internally.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

METRICS = ("l2-squared", "dot", "cosine", "manhattan", "hamming")

# Large-but-finite sentinel used for masked-out candidates. float32 max is
# ~3.4e38; we stay well below so arithmetic on sentinels can't overflow to inf
# (inf - inf = nan would poison top-k merges). A plain Python float, NOT a
# jnp scalar: a device constant here would initialize the default backend at
# import time, and whichever process imports the package would take the chip
# (tests/test_import_no_backend.py).
MASK_DISTANCE = 1e30


def normalize(v: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    """L2-normalize along the last axis (cosine pre-processing)."""
    n = jnp.linalg.norm(v, axis=-1, keepdims=True)
    return v / jnp.maximum(n, eps)


def _matmul(q: jnp.ndarray, c: jnp.ndarray, precision: str) -> jnp.ndarray:
    """[B, D] x [N, D] -> [B, N] inner products on the MXU.

    ``precision='bf16'`` casts operands to bfloat16 with float32 accumulation —
    the MXU-native mode (2x flops vs fp32 inputs). Over a corpus that is
    already bfloat16 (a flat collection's resident rows) the cast is the
    identity; over float32 rows XLA hoists it out of the chunk loop as one
    pass over the whole corpus, every call.
    """
    if precision == "bf16":
        q = q.astype(jnp.bfloat16)
        c = c.astype(jnp.bfloat16)
    return jax.lax.dot_general(
        q,
        c,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=None if precision == "bf16" else jax.lax.Precision.HIGHEST,
    )


def pairwise_distance(
    queries: jnp.ndarray,
    corpus: jnp.ndarray,
    metric: str,
    corpus_sqnorms: Optional[jnp.ndarray] = None,
    precision: str = "fp32",
) -> jnp.ndarray:
    """All-pairs distances ``[B, N]`` between queries ``[B, D]`` and corpus ``[N, D]``.

    For l2-squared the expansion ||q||^2 - 2 q.c + ||c||^2 keeps the hot op a
    single MXU matmul; ``corpus_sqnorms`` ([N]) may be precomputed once per
    corpus block and reused across queries.
    """
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; want one of {METRICS}")
    if metric == "l2-squared":
        ip = _matmul(queries, corpus, precision)
        if corpus_sqnorms is None:
            corpus_sqnorms = jnp.sum(
                corpus.astype(jnp.float32) * corpus.astype(jnp.float32), axis=-1
            )
        q_sq = jnp.sum(queries.astype(jnp.float32) * queries.astype(jnp.float32), axis=-1)
        d = q_sq[:, None] - 2.0 * ip + corpus_sqnorms[None, :]
        return jnp.maximum(d, 0.0)
    if metric == "dot":
        return -_matmul(queries, corpus, precision)
    if metric == "cosine":
        # Vectors are stored normalized (see FlatIndex/HNSW insert paths), so
        # cosine distance is 1 - ip.
        return 1.0 - _matmul(queries, corpus, precision)
    if metric == "manhattan":
        # VPU path: no matmul formulation; broadcast in the chunked driver.
        return jnp.sum(
            jnp.abs(queries[:, None, :].astype(jnp.float32) - corpus[None, :, :].astype(jnp.float32)),
            axis=-1,
        )
    # hamming (float variant): count of differing dims.
    return jnp.sum(
        (queries[:, None, :] != corpus[None, :, :]).astype(jnp.float32), axis=-1
    )


def gather_distance(
    queries: jnp.ndarray,
    corpus: jnp.ndarray,
    candidate_ids: jnp.ndarray,
    metric: str,
    precision: str = "fp32",
) -> jnp.ndarray:
    """Distances between each query and its own candidate set.

    ``queries``: [B, D]; ``candidate_ids``: [B, C] int32 indices into corpus
    [N, D]. Returns [B, C]. This is the HNSW frontier-evaluation primitive: the
    host streams neighbor-frontier IDs, the device gathers + evaluates them in
    one fused step (reference hot loop ``hnsw/search.go:726``).
    """
    cand = jnp.take(corpus, candidate_ids, axis=0)  # [B, C, D]
    q = queries[:, None, :]
    if metric == "l2-squared":
        diff = q.astype(jnp.float32) - cand.astype(jnp.float32)
        return jnp.sum(diff * diff, axis=-1)
    if metric in ("dot", "cosine"):
        if precision == "bf16":
            q = q.astype(jnp.bfloat16)
            cand = cand.astype(jnp.bfloat16)
        ip = jnp.einsum(
            "bqd,bcd->bc",
            q,
            cand,
            preferred_element_type=jnp.float32,
        )
        return -ip if metric == "dot" else 1.0 - ip
    if metric == "manhattan":
        return jnp.sum(jnp.abs(q.astype(jnp.float32) - cand.astype(jnp.float32)), axis=-1)
    if metric == "hamming":
        return jnp.sum((q != cand).astype(jnp.float32), axis=-1)
    raise ValueError(f"unknown metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("metric", "precision"))
# graftlint: allow[unwarmed-jit-program] reason=construction-only neighbor-selection program; compiles during index builds, never on the serving path
def candidate_pairwise(
    corpus: jnp.ndarray,
    candidate_ids: jnp.ndarray,
    metric: str,
    precision: str = "fp32",
) -> jnp.ndarray:
    """Pairwise distances within each candidate set: [B, C] ids -> [B, C, C].

    Drives the batched HNSW neighbor-selection heuristic
    (reference ``hnsw/heuristic.go:23``): the greedy accept test needs
    candidate-to-candidate distances, which here are one batched einsum.
    """
    v = jnp.take(corpus, candidate_ids, axis=0)  # [B, C, D]
    return vectors_pairwise(v, metric, precision)


@functools.partial(jax.jit, static_argnames=("metric", "precision"))
# graftlint: allow[unwarmed-jit-program] reason=construction-only neighbor-selection program; compiles during index builds, never on the serving path
def vectors_pairwise(
    v: jnp.ndarray,
    metric: str,
    precision: str = "fp32",
) -> jnp.ndarray:
    """Pairwise distances over already-gathered candidate vectors [B, C, D]
    -> [B, C, C] (mesh-sharded corpora gather first via ``sharded_take``)."""
    vf = v.astype(jnp.bfloat16 if precision == "bf16" else jnp.float32)
    ip = jnp.einsum("bcd,bed->bce", vf, vf, preferred_element_type=jnp.float32)
    if metric == "l2-squared":
        sq = jnp.sum(v.astype(jnp.float32) ** 2, axis=-1)
        d = sq[:, :, None] - 2.0 * ip + sq[:, None, :]
        return jnp.maximum(d, 0.0)
    if metric == "dot":
        return -ip
    if metric == "cosine":
        return 1.0 - ip
    # manhattan / hamming: no matmul form; direct broadcast
    diff = v[:, :, None, :].astype(jnp.float32) - v[:, None, :, :].astype(jnp.float32)
    if metric == "manhattan":
        return jnp.sum(jnp.abs(diff), axis=-1)
    return jnp.sum((diff != 0).astype(jnp.float32), axis=-1)


def select_topk(
    d: jnp.ndarray, k: int, approx_recall: float = 0.0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Smallest-k selection over the last axis: exact ``top_k`` or, when
    ``0 < approx_recall < 1``, TPU-native two-stage selection via
    ``lax.approx_min_k`` (PartialReduce bins + aggregate) — ~4-5x faster at
    1M rows for a bounded, reported recall loss. On CPU approx lowers to an
    exact sort, so virtual-mesh tests see exact results either way.
    """
    if 0.0 < approx_recall < 1.0 and k < d.shape[-1]:
        return jax.lax.approx_min_k(d, k, recall_target=approx_recall)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


@functools.partial(
    jax.jit,
    static_argnames=("metric", "k", "chunk_size", "precision", "approx_recall",
                     "normalize_queries"),
)
def flat_search(
    queries: jnp.ndarray,
    corpus: jnp.ndarray,
    k: int,
    metric: str = "l2-squared",
    valid_mask: Optional[jnp.ndarray] = None,
    allow_mask: Optional[jnp.ndarray] = None,
    corpus_sqnorms: Optional[jnp.ndarray] = None,
    chunk_size: int = 0,
    precision: str = "fp32",
    approx_recall: float = 0.0,
    normalize_queries: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Brute-force top-k: the TPU-native flat index (reference ``flat/index.go:49``).

    queries      [B, D] float; a numpy array goes up with this launch
    corpus       [N, D] float32 or bfloat16 (padded to capacity; see valid_mask)
    valid_mask   [N] bool — False for pad slots / tombstoned ids
    allow_mask   [N] bool — optional filter allowlist (reference AllowList),
                 or [B, N]: row i of the mask filters row i of the queries
                 (one scan for B requests whose filters differ)
    chunk_size   evaluate corpus in chunks of this many rows to bound the
                 [B, chunk] score materialization (0 = single shot). Must
                 divide into N by padding; non-multiple tail is handled.
    approx_recall  0 = exact selection; in (0, 1) = per-chunk
                 ``lax.approx_min_k`` with this recall target (see
                 ``select_topk``); candidates are collected via ``scan``
                 and merged ONCE — two-stage selection, no per-chunk sort.
    normalize_queries  the queries arrive raw and this program L2-normalises
                 them itself (``normalize``, float32, before ``_matmul``'s
                 cast): a cosine caller's whole batch is then ONE launch.

    Returns (distances [B, k], ids [B, k]); masked/empty slots have distance
    MASK_DISTANCE and id -1.
    """
    if normalize_queries:
        queries = normalize(queries)
    n = corpus.shape[0]
    b = queries.shape[0]
    mask = None
    if valid_mask is not None:
        mask = valid_mask
    if allow_mask is not None:
        if mask is not None and allow_mask.ndim == 2:
            mask = mask[None, :]
        mask = allow_mask if mask is None else (mask & allow_mask)
    # corpus rows lie along the mask's LAST axis, whichever form it has
    mask_axis = 0 if mask is None else mask.ndim - 1

    def score_block(c_block, norms_block, mask_block, base):
        d = pairwise_distance(
            queries, c_block, metric, corpus_sqnorms=norms_block, precision=precision
        )
        if mask_block is not None:
            if mask_block.ndim == 1:
                mask_block = mask_block[None, :]
            d = jnp.where(mask_block, d, MASK_DISTANCE)
        kk = min(k, c_block.shape[0])
        vals, idx = select_topk(d, kk, approx_recall)
        ids = idx.astype(jnp.int32) + base
        if kk < k:
            pad = k - kk
            vals = jnp.concatenate(
                [vals, jnp.full((b, pad), MASK_DISTANCE, vals.dtype)], axis=1
            )
            ids = jnp.concatenate([ids, jnp.full((b, pad), -1, ids.dtype)], axis=1)
        return vals, ids

    if chunk_size <= 0 or chunk_size >= n:
        vals, ids = score_block(corpus, corpus_sqnorms, mask, 0)
    else:
        from weaviate_tpu.ops.topk import merge_candidate_stack, merge_topk

        n_full = (n // chunk_size) * chunk_size

        def body(carry, i):
            start = i * chunk_size
            c_block = jax.lax.dynamic_slice_in_dim(corpus, start, chunk_size, 0)
            norms_block = (
                jax.lax.dynamic_slice_in_dim(corpus_sqnorms, start, chunk_size, 0)
                if corpus_sqnorms is not None
                else None
            )
            mask_block = (
                jax.lax.dynamic_slice_in_dim(mask, start, chunk_size,
                                             mask_axis)
                if mask is not None
                else None
            )
            return carry, score_block(c_block, norms_block, mask_block, start)

        # Collect every chunk's [B, k] candidates (scan stacks them) and pay
        # for exactly ONE [B, chunks*k] merge at the end — not a sort per
        # chunk (the round-1 fori_loop merged after every chunk).
        _, (vs, is_) = jax.lax.scan(
            body, 0, jnp.arange(n_full // chunk_size, dtype=jnp.int32)
        )
        vals, ids = merge_candidate_stack(vs, is_, k)
        if n_full < n:
            tail_c = corpus[n_full:]
            tail_norms = corpus_sqnorms[n_full:] if corpus_sqnorms is not None else None
            tail_mask = mask[..., n_full:] if mask is not None else None
            v, idx = score_block(tail_c, tail_norms, tail_mask, n_full)
            vals, ids = merge_topk(vals, ids, v, idx, k)

    # Mark slots that only contain sentinel as id -1.
    ids = jnp.where(vals >= MASK_DISTANCE, -1, ids)
    return vals, ids
