"""Pallas fused flat-search kernel: masked distance + per-chunk top-k.

Reference counterpart: the SIMD distancer tier (``hnsw/distancer/asm``) —
here ONE TPU kernel per corpus chunk computes the [B, CHUNK] distance
block on the MXU and reduces it to [B, K] candidates on the VPU without
ever writing the full score matrix back to HBM. The XLA two-stage path
(``ops.distance.flat_search``) materializes [B, chunk] scores between the
matmul and ``approx_min_k``; fusing the select into the same VMEM
residency removes that HBM round-trip, which is the flat scan's
bandwidth ceiling at large B.

Gated OFF by default (``WEAVIATE_TPU_PALLAS_FLAT=on`` to enable in the
serving path): semantics are validated in interpret mode on CPU, but the
compiled kernel must prove itself against ``approx_min_k`` on real
hardware before it takes over the hot path. An ENABLED kernel that
fails to lower or run raises into the request — it never gives way to
the XLA path in silence.

Selection inside the kernel is bucketed, the same shape as
``approx_min_k``'s PartialReduce: the [B, C] block folds into C/FOLD
STRIDED buckets (bucket j = block rows {j, j+C/FOLD, j+2·C/FOLD, ...};
strided so the reduction keeps full lane width — see ``_kernel``) as
per-bucket (min, argmin) pairs — two passes over the block — and the k
unrolled extract-min rounds then run on the [B, C/FOLD] bucket minima
only (a bucket is retired whole once its min is taken, so
each bucket contributes at most one candidate — exactly ``approx_min_k``
semantics, and the serving path only routes here when approximate
selection is permitted). This keeps the VPU selection cost ~FOLD× below
full-width extraction, leaving the kernel HBM-bound on the corpus read.

The corpus is tiled into VMEM-sized blocks of ``_BLOCK_LADDER`` rows,
capped at ``_BLOCK_BYTES`` per buffer at the corpus's OWN dtype (2048
rows of 768-d bf16, 1024 of the fp32 the serving store holds): the
block is double-buffered and cast in-kernel, and a 2048x768 fp32 block
asks for 17.35 MB of the 16 MB scoped VMEM. Interpret mode on CPU never
sees VMEM; ``tests/test_chip_compile.py`` compiles both dtypes for a
described v5e.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from weaviate_tpu.ops.distance import MASK_DISTANCE


def enabled() -> bool:
    # env wins; else the MEASURED verdict from the last bench A/B on
    # THIS platform (utils/perf_flags.py): the kernel flips on only
    # after beating the XLA path within 0.005 of its recall and above
    # the 0.95 gate. Called from the flat search hot path, so the
    # backend is already initialized — default_backend() is safe.
    from weaviate_tpu.utils import perf_flags

    return perf_flags.resolve(
        "pallas_flat", os.environ.get("WEAVIATE_TPU_PALLAS_FLAT", ""),
        platform=jax.default_backend())


def bucket_live(live: int) -> int:
    """Coarse power-of-4 bucket of a live-row count. Fold sizing only
    needs the order of magnitude of the candidate population, and the
    bucket is a static (compile-time) argument — bucketing means a
    recompile happens when the live set crosses a 4x boundary, not on
    every insert/delete."""
    b = 1
    while b * 4 <= max(1, live):
        b *= 4
    return b


def _kernel(q_ref, c_ref, norms_ref, mask_ref, vals_ref, ids_ref, *,
            k, fold):
    """One grid step: queries [B, D] x corpus block [C, D] -> top-k per
    query within the block. mask is float32 (1 = allowed)."""
    q = q_ref[:].astype(jnp.bfloat16)
    c = c_ref[:].astype(jnp.bfloat16)
    # [B, C] inner products on the MXU, fp32 accumulation
    ip = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    qf = q_ref[:].astype(jnp.float32)
    q_sq = jnp.sum(qf * qf, axis=1, keepdims=True)          # [B, 1]
    d = q_sq - 2.0 * ip + norms_ref[:][None, :]             # [B, C]
    d = jnp.maximum(d, 0.0)
    d = jnp.where(mask_ref[:][None, :] > 0.5, d, MASK_DISTANCE)

    b, cwidth = d.shape
    folds = cwidth // fold
    # STRIDED fold: bucket j holds columns {j, j+folds, ...} so the
    # reduction runs over the sublane-direction axis of a [B, fold,
    # folds] view and the surviving [B, folds] minima keep the full
    # lane width — no narrow-lane relayouts for Mosaic to fight
    dr = d.reshape(b, fold, folds)
    loc3 = jax.lax.broadcasted_iota(jnp.int32, (b, fold, folds), 1)
    fmin = jnp.min(dr, axis=1)                               # [B, F]
    floc = jnp.min(
        jnp.where(dr == fmin[:, None, :], loc3, fold), axis=1)  # [B, F]

    fcol = jax.lax.broadcasted_iota(jnp.int32, (b, folds), 1)
    # k extract-min rounds over the bucket minima only; an extracted
    # bucket retires whole (<=1 candidate per bucket)
    vs, gs = [], []
    for i in range(k):
        row_min = jnp.min(fmin, axis=1)                      # [B]
        is_min = fmin == row_min[:, None]
        j = jnp.min(jnp.where(is_min, fcol, folds), axis=1)  # [B]
        jc = jnp.minimum(j, folds - 1)[:, None]
        loc = jnp.min(jnp.where(fcol == jc, floc, fold), axis=1)  # [B]
        vs.append(row_min)
        gs.append(jnp.minimum(loc, fold - 1) * folds
                  + jnp.minimum(j, folds - 1))
        fmin = jnp.where(fcol == jc, MASK_DISTANCE, fmin)
    vals_ref[0] = jnp.stack(vs, axis=1)
    ids_ref[0] = jnp.stack(gs, axis=1)


# VMEM block rows, largest-first; the ladder walks down for small or
# oddly-sized (test-scale) corpora and for wide rows: one corpus buffer
# holds at most _BLOCK_BYTES (2048x768 bf16) — double-buffered, plus the
# in-kernel bf16 copy and the [B, block] score temporaries at B=256,
# that stays inside the 16 MiB of scoped VMEM
_BLOCK_LADDER = (2048, 1024, 512, 256, 128)
_BLOCK_BYTES = 2048 * 768 * 2


def _pick_block(n: int, chunk_size: int, row_bytes: int) -> int:
    for blk in _BLOCK_LADDER:
        if (blk <= chunk_size and n % blk == 0
                and blk * row_bytes <= _BLOCK_BYTES):
            return blk
    raise ValueError(
        f"corpus rows {n} x {row_bytes} B have no VMEM block divisor "
        f"<= chunk {chunk_size}")


def fits(n: int, chunk_size: int, row_bytes: int) -> bool:
    """Whether a corpus of ``n`` rows of ``row_bytes`` each satisfies the
    kernel's shape contract — the serving-path gate (``index/flat.py``)
    must ask THIS, not the pre-rewrite ``n % chunk_size == 0`` rule."""
    try:
        _pick_block(n, chunk_size, row_bytes)
        return True
    except ValueError:
        return False


@functools.partial(
    jax.jit,
    static_argnames=("k", "chunk_size", "interpret", "live_rows"))
def pallas_flat_topk(
    queries: jnp.ndarray,
    corpus: jnp.ndarray,
    corpus_sqnorms: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    chunk_size: int = 131072,
    interpret: bool = False,
    live_rows: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """L2 top-k over the corpus. queries [B, D] fp32; corpus [N, D] (any
    float dtype; cast to bf16 in-kernel); corpus_sqnorms [N] fp32 (exact,
    fp32-computed); mask [N] float32 1/0. N must be a multiple of a
    ladder block <= chunk_size (pad with mask=0 rows). Selection is
    bucketed (see module docstring) — approximate in exactly the way
    ``approx_min_k`` is. ``live_rows`` (static; pass through
    ``bucket_live``) is the unmasked candidate population — fold sizing
    must bound collision loss against the LIVE rows, not the padded
    corpus, or a heavily padded/filtered corpus gets ~fold x the
    advertised loss. Returns ([B, k], [B, k])."""
    from jax.experimental import pallas as pl

    n, d_dim = corpus.shape
    b = queries.shape[0]
    block = _pick_block(n, chunk_size, d_dim * corpus.dtype.itemsize)
    grid = n // block
    # fold width scales with the live candidate count so the
    # bucket-collision loss is bounded: expected missed candidates
    # ~ C(k,2)*(fold-1)/live, so capping fold at live/(64*k^2) keeps the
    # loss under ~1% at any scale — tiny (test-sized) or heavily masked
    # corpora degrade to fold=1, i.e. exact full-width extraction;
    # 1M x k=10 serving gets the full 16x VPU saving at a 2048-row
    # block. The [B, fold, block // fold] view must keep whole 128-lane
    # rows (Mosaic refuses the reshape below that: "unsupported shape
    # cast"), so the 1024-row block of an fp32 corpus folds at most 8x
    live = live_rows if live_rows else n
    fold = 16
    while fold > 1 and (block // fold < max(k, 128)
                        or fold * 64 * k * k > live):
        fold //= 2
    if block // fold < k:
        raise ValueError(f"k={k} exceeds block {block} bucket count")

    vals, ids = pl.pallas_call(
        functools.partial(_kernel, k=k, fold=fold),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((b, d_dim), lambda i: (0, 0)),
            pl.BlockSpec((block, d_dim), lambda i: (i, 0)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((1, b, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid, b, k), jnp.float32),
            jax.ShapeDtypeStruct((grid, b, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries.astype(jnp.float32), corpus,
      corpus_sqnorms.astype(jnp.float32), mask.astype(jnp.float32))

    # global merge of the per-block candidates ([B, grid*k]; at 1M rows
    # and block 2048 that is [B, 5120] — one small device top_k)
    base = (jnp.arange(grid, dtype=jnp.int32) * block)[:, None, None]
    gids = ids + base
    flat_v = jnp.transpose(vals, (1, 0, 2)).reshape(b, grid * k)
    flat_i = jnp.transpose(gids, (1, 0, 2)).reshape(b, grid * k)
    sel_v, sel_pos = jax.lax.top_k(-flat_v, k)
    out_v = -sel_v
    out_i = jnp.take_along_axis(flat_i, sel_pos, axis=1)
    out_i = jnp.where(out_v >= MASK_DISTANCE, -1, out_i)
    return out_v, out_i
