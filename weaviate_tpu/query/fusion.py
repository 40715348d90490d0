"""Hybrid fusion algorithms.

Reference: ``usecases/traverser/hybrid/hybrid_fusion.go`` — rankedFusion
(``:22``, reciprocal-rank with a 60 offset) and relativeScoreFusion (``:93``,
min-max normalize each branch then weighted sum). Keys are object UUIDs so
fusion works across shards — and across NODES: the coordinator fuses the
globally merged per-leg candidate sets, so relativeScoreFusion's min-max
normalization spans the whole corpus, never one shard's skewed slice.

Two tiers serve the same semantics. ``fuse_result_sets`` routes to the
device kernels (``ops/fusion.py``: one jitted scatter + top_k per hybrid
request) and keeps the pure-python functions below as the exact twin —
the parity oracle for tests AND the fallback tier, which latches LOUDLY
(``weaviate_tpu_hybrid_fallback_total`` + a span event) the way the
rerank tier's host fallback does.
"""

from __future__ import annotations

import logging
from typing import Any, Hashable, Optional

logger = logging.getLogger("weaviate_tpu.query.fusion")

# the classic RRF constant used by the reference
RANKED_FUSION_OFFSET = 60.0


def ranked_fusion(
    result_sets: list[list[tuple[Hashable, float]]],
    weights: list[float],
    k: int,
) -> list[tuple[Hashable, float]]:
    """Reciprocal-rank fusion: score = Σ_set weight / (60 + rank).

    Each result set is [(key, score)] sorted best-first; scores themselves
    are ignored, only ranks matter.
    """
    fused: dict[Hashable, float] = {}
    for rs, w in zip(result_sets, weights):
        for rank, (key, _score) in enumerate(rs):
            fused[key] = fused.get(key, 0.0) + w / (RANKED_FUSION_OFFSET + rank)
    out = sorted(fused.items(), key=lambda t: -t[1])
    return out[:k]


def relative_score_fusion(
    result_sets: list[list[tuple[Hashable, float]]],
    weights: list[float],
    k: int,
) -> list[tuple[Hashable, float]]:
    """Min-max normalize each branch's scores to [0,1], then weighted sum.

    Scores must be "higher is better" in every set (invert distances before
    calling). Matches the reference's relativeScoreFusion (:93): a set with
    a single distinct score normalizes to 1.0.
    """
    fused: dict[Hashable, float] = {}
    for rs, w in zip(result_sets, weights):
        if not rs:
            continue
        scores = [s for _, s in rs]
        lo, hi = min(scores), max(scores)
        span = hi - lo
        for key, s in rs:
            norm = 1.0 if span <= 0 else (s - lo) / span
            fused[key] = fused.get(key, 0.0) + w * norm
    out = sorted(fused.items(), key=lambda t: -t[1])
    return out[:k]


FUSION_ALGORITHMS = {
    "rankedFusion": ranked_fusion,
    "relativeScoreFusion": relative_score_fusion,
}


def hybrid_fetch(k: int) -> int:
    """Per-leg over-fetch: ceil(hybrid_overfetch_factor · k), never below
    k. THE one definition — the collection path, the cluster
    coordinator, and the prewarm fusion lattice must all derive the same
    fetch or prewarm compiles shapes traffic never dispatches."""
    import math

    from weaviate_tpu.utils.runtime_config import HYBRID_OVERFETCH_FACTOR

    factor = max(1.0, float(HYBRID_OVERFETCH_FACTOR.get()))
    return max(k, int(math.ceil(k * factor)))


def validate_fusion(name: str) -> None:
    """Reject unknown fusion names with a clean ValueError — mapped to
    400 / INVALID_ARGUMENT at every API surface, never a 500."""
    if name not in FUSION_ALGORITHMS:
        raise ValueError(
            f"unknown fusion algorithm {name!r} (expected one of "
            f"{sorted(FUSION_ALGORITHMS)})")


def assemble_slots(
    result_sets: list[list[tuple[Hashable, float]]],
) -> tuple[list[Hashable], list[list[int]], list[list[float]]]:
    """Dense union-slot encoding of the legs' (key, score) lists.

    Slot ids are assigned in the host twin's dict-insertion order (leg 0
    in rank order, then each later leg's NEW keys in rank order), so the
    device kernel's lower-index-wins tie-break reproduces the host's
    stable-sort order exactly. Returns (keys by slot, per-leg slot
    lists, per-leg score lists).
    """
    slot_of: dict[Hashable, int] = {}
    keys: list[Hashable] = []
    slot_sets: list[list[int]] = []
    score_sets: list[list[float]] = []
    for rs in result_sets:
        slots = []
        scores = []
        for key, score in rs:
            idx = slot_of.get(key)
            if idx is None:
                idx = slot_of[key] = len(keys)
                keys.append(key)
            slots.append(idx)
            scores.append(float(score))
        slot_sets.append(slots)
        score_sets.append(scores)
    return keys, slot_sets, score_sets


def _latch_fallback(reason: str, exc: Optional[BaseException]) -> None:
    """The fallback tier is never silent: counter + span event + log."""
    from weaviate_tpu.monitoring import tracing
    from weaviate_tpu.monitoring.metrics import HYBRID_FALLBACK

    HYBRID_FALLBACK.inc(stage="fuse", reason=reason)
    span = tracing.current_span()
    if span is not None:
        span.add_event("hybrid.fuse.fallback", reason=reason)
    if exc is not None:
        logger.warning("device hybrid fusion fell back to host (%s): %s",
                       reason, exc)


def device_fusion_enabled() -> bool:
    from weaviate_tpu.utils.runtime_config import HYBRID_DEVICE_FUSION

    return str(HYBRID_DEVICE_FUSION.get()).lower() not in (
        "off", "0", "false")


def fuse_result_sets(
    result_sets: list[list[tuple[Hashable, float]]],
    weights: list[float],
    k: int,
    algorithm: str,
    stats: Optional[dict] = None,
) -> list[tuple[Hashable, float]]:
    """Fuse the legs on device (one jitted dispatch), falling back to
    the exact host twin — loudly — when the device tier is disabled or
    errors. Same contract as the host functions: [(key, fused score)]
    best-first, at most ``k`` entries. ``stats``, where given, receives
    what the ``hybrid.fuse`` span reports: ``tier`` (``device`` /
    ``host``), ``union`` (distinct keys over the legs) and, on the device
    tier, ``sync_ms`` (the wait for the program's two results)."""
    validate_fusion(algorithm)
    if stats is None:
        stats = {}
    if not any(result_sets):
        return []
    stats["tier"] = "host"
    if not device_fusion_enabled():
        _latch_fallback("disabled", None)
        stats["union"] = len({key for rs in result_sets for key, _ in rs})
        return FUSION_ALGORITHMS[algorithm](result_sets, weights, k)
    keys, slot_sets, score_sets = assemble_slots(result_sets)
    stats["union"] = len(keys)
    try:
        from weaviate_tpu.ops.fusion import fuse_topk

        ids, vals = fuse_topk(slot_sets, score_sets, weights, k,
                              algorithm, len(keys), stats)
    except Exception as e:  # device tier down: serve host, latch loudly
        _latch_fallback("device_error", e)
        return FUSION_ALGORITHMS[algorithm](result_sets, weights, k)
    stats["tier"] = "device"
    return [(keys[int(i)], float(v)) for i, v in zip(ids, vals)]
