"""Device hybrid-fusion kernels: rankedFusion / relativeScoreFusion top-k.

Reference: ``usecases/traverser/hybrid/hybrid_fusion.go`` — the same two
algorithms ``query/fusion.py`` implements on host with Python dicts. Here
each leg's candidates arrive as dense arrays (union-slot ids + raw scores),
the fused score materializes via one scatter-add per leg matrix, and one
``top_k`` yields the fused page — the whole fusion is ONE jitted dispatch
per hybrid request instead of a host dict merge on the request path.

Slot assignment (``query/fusion.py:assemble_slots``) preserves the host
twin's dict-insertion order, and ``lax.top_k`` prefers the lower index on
ties exactly like the host's stable sort prefers earlier insertion — so
the device page ORDER matches the host page bit-for-bit, with scores equal
up to float32 rounding.

Shapes bucket to powers of two (legs x leg-length, union size) so a steady
hybrid workload reuses a small lattice of compiled programs; the bucket
helpers are shared with the prewarm driver, which walks the same lattice
at boot (utils/prewarm.py MANIFEST).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

# the classic RRF constant used by the reference (query/fusion.py twin)
RANKED_FUSION_OFFSET = 60.0

# Test/ops hook, mirroring ops.device_beam.dispatch_count: fused-fusion
# programs dispatched by this process. The acceptance contract "hybrid
# fusion is ONE device dispatch per request" is asserted against this.
_dispatch_count = 0


def dispatch_count() -> int:
    return _dispatch_count


def bucket(n: int, floor: int = 8) -> int:
    """pow2 shape bucket (same discipline as the beam's row bucketing)."""
    return max(floor, 1 << max(0, int(n - 1).bit_length()))


def _scatter_fused(slots, contrib, union):
    """Scatter per-entry fused contributions into the union accumulator.

    slots [S, L] int32 (-1 = pad), contrib [S, L] f32 (already zeroed on
    pads). Returns (acc [union], present [union]) — ``present`` guards
    slots no leg ever touched (padded union tail).
    """
    ok = slots >= 0
    rows = jnp.where(ok, slots, 0).reshape(-1)
    flat = jnp.where(ok, contrib, jnp.float32(0.0)).reshape(-1)
    acc = jnp.zeros(union, jnp.float32).at[rows].add(flat, mode="drop")
    hits = jnp.zeros(union, jnp.float32).at[rows].add(
        ok.astype(jnp.float32).reshape(-1), mode="drop")
    return acc, hits > 0


def _present_topk(acc, present, k):
    """Top-k of the fused accumulator; absent slots come back id -1."""
    neg_inf = jnp.float32(-jnp.inf)
    scored = jnp.where(present, acc, neg_inf)
    vals, ids = jax.lax.top_k(scored, k)
    live = jnp.isfinite(vals)
    return (jnp.where(live, vals, jnp.float32(0.0)),
            jnp.where(live, ids.astype(jnp.int32), -1))


@functools.partial(jax.jit, static_argnames=("k", "union"))
def ranked_fusion_topk(slots, weights, k: int, union: int):
    """Reciprocal-rank fusion: score = Σ_leg weight / (60 + rank).

    slots: [S, L] int32 union-slot per leg entry in rank order (-1 pad);
    weights: [S] f32. Returns (fused scores [k], slot ids [k]).
    """
    l = slots.shape[1]
    ranks = jnp.arange(l, dtype=jnp.float32)
    contrib = weights[:, None] / (
        jnp.float32(RANKED_FUSION_OFFSET) + ranks)[None, :]
    acc, present = _scatter_fused(slots, contrib, union)
    return _present_topk(acc, present, k)


@functools.partial(jax.jit, static_argnames=("k", "union"))
def relative_score_fusion_topk(slots, scores, weights, k: int, union: int):
    """Min-max normalize each leg's scores to [0,1], then weighted sum.

    Matches the host twin exactly: a leg with a single distinct score (or
    one entry) normalizes to 1.0; scores must already be "higher is
    better" in every leg (vector distances negated by the caller).
    """
    ok = slots >= 0
    big = jnp.float32(np.finfo(np.float32).max)
    lo = jnp.min(jnp.where(ok, scores, big), axis=1, keepdims=True)
    hi = jnp.max(jnp.where(ok, scores, -big), axis=1, keepdims=True)
    span = hi - lo
    norm = jnp.where(span > jnp.float32(0.0),
                     (scores - lo) / jnp.maximum(span, jnp.float32(1e-30)),
                     jnp.float32(1.0))
    acc, present = _scatter_fused(slots, weights[:, None] * norm, union)
    return _present_topk(acc, present, k)


def fuse_topk(slot_sets, score_sets, weights, k: int, algorithm: str,
              union_size: int, stats=None):
    """Host-callable entry: pad each leg to one pow2 (legs x length)
    bucket, run the requested fusion as ONE jitted dispatch, and hand
    back (slot ids [<=k] int32 np, fused scores [<=k] f32 np) with the
    absent tail trimmed.

    slot_sets / score_sets: one int/float sequence per leg (rank order);
    union_size: distinct keys across all legs (slot ids are < this);
    ``stats``, where given, receives ``sync_ms``: the wait for the two
    results.
    """
    global _dispatch_count
    n_sets = max(1, len(slot_sets))
    l_max = bucket(max([1] + [len(s) for s in slot_sets]))
    union = bucket(max(union_size, k))
    slots = np.full((n_sets, l_max), -1, np.int32)
    scores = np.zeros((n_sets, l_max), np.float32)
    for i, ss in enumerate(slot_sets):
        slots[i, :len(ss)] = ss
        scores[i, :len(ss)] = score_sets[i]
    w = np.zeros(n_sets, np.float32)
    w[:len(weights)] = weights
    kk = min(k, union)
    if algorithm == "rankedFusion":
        vals, ids = ranked_fusion_topk(slots, w, kk, union)
    elif algorithm == "relativeScoreFusion":
        vals, ids = relative_score_fusion_topk(slots, scores, w, kk, union)
    else:
        raise ValueError(f"unknown fusion algorithm {algorithm!r}")
    _dispatch_count += 1
    # result materialization: the one host sync of the fusion stage
    t0 = time.perf_counter()
    out_ids = np.asarray(ids)
    out_vals = np.asarray(vals)
    if stats is not None:
        stats["sync_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    live = out_ids >= 0
    return out_ids[live], out_vals[live]
