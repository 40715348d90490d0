"""The vector-index interface every backend implements.

Mirrors the reference's ``adapters/repos/db/vector_index.go:25`` (VectorIndex:
Add/AddBatch/Delete/SearchByVector/SearchByVectorDistance/Flush/Drop/
PostStartup/...), with one deliberate TPU-first change: **every method is
batched**. The reference's per-vector ``Add(id, vec)`` / per-candidate
``Distance`` calls would serialize the device; here the unit of work is a
batch of ids/vectors/queries.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class SearchResult:
    """Top-k result for a batch of queries: ids[b, k] (-1 = empty), dists[b, k]."""

    ids: np.ndarray
    dists: np.ndarray


class VectorIndex(abc.ABC):
    """Batched ANN index over internal doc ids (uint64 monotonic per shard)."""

    multi_vector: bool = False
    # whether search() accepts a resident FilterPlane as ``allow_list``
    # (query/planner/planes.py); callers resolve the plane's host bitmap
    # for indexes that don't
    supports_filter_planes: bool = False

    @abc.abstractmethod
    def add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        """Insert/overwrite vectors for the given internal doc ids."""

    @abc.abstractmethod
    def delete(self, doc_ids: np.ndarray) -> None:
        """Remove ids (tombstone semantics — slots masked, space reclaimed later)."""

    @abc.abstractmethod
    def search(
        self,
        queries: np.ndarray,
        k: int,
        allow_list: Optional[np.ndarray] = None,
        est_selectivity: Optional[float] = None,
    ) -> SearchResult:
        """Batched top-k by vector. ``allow_list``: bool mask over doc ids
        (or a resident FilterPlane where the index supports them).
        ``est_selectivity``: the inverted index's sketch estimate for the
        filter — explainability payload for planner-routed indexes, ignored
        by the rest."""

    @abc.abstractmethod
    def search_by_distance(
        self,
        queries: np.ndarray,
        max_distance: float,
        allow_list: Optional[np.ndarray] = None,
        limit: int = 1024,
    ) -> SearchResult:
        """All results within max_distance (reference SearchByVectorDistance)."""

    @abc.abstractmethod
    def count(self) -> int:
        """Live (non-deleted) vector count."""

    @property
    @abc.abstractmethod
    def capacity(self) -> int:
        """Current padded device capacity (doc-id space size)."""

    def contains(self, doc_id: int) -> bool:
        raise NotImplementedError

    def flush(self) -> None:  # durability hook; storage owns real persistence
        pass

    # -- device-state checkpoint (shard boot = load + delta replay, not a
    # full object-store rebuild; reference hnsw/startup.go commit-log role)
    def save_vectors(self, path: str, meta: Optional[dict] = None) -> bool:
        """Persist the raw vector tier; False = unsupported by this index."""
        return False

    def load_vectors(self, path: str) -> Optional[dict]:
        """Restore the raw vector tier; returns saved meta, None = no/bad
        checkpoint (or unsupported) — caller falls back to full rebuild."""
        return None

    def drop(self) -> None:
        pass

    # -- tiered residency (tiering/ warm tier; docs/tiering.md) -----------
    # Default: an index type with no device arrays (or one that cannot
    # demote them) reports zero HBM rent and stays "resident" — the
    # controller then only ever cold-releases its whole shard.
    @property
    def device_resident(self) -> bool:
        """False while this index's device arrays are demoted to host."""
        return True

    def hbm_bytes(self) -> int:
        """Current HBM rent (0 while demoted / for host-only indexes)."""
        return 0

    def host_tier_bytes(self) -> int:
        """Host-RAM rent of demoted device arrays (warm tier)."""
        return 0

    def promote_bytes(self) -> int:
        """HBM ``promote_device`` would charge (0 while resident). The
        host mirror's own size unless an index keeps it at another width
        than its device arrays."""
        return self.host_tier_bytes()

    def demote_device(self) -> int:
        """Move device arrays to host RAM (warm tier); returns HBM bytes
        released. Callers MUST feed the returned delta to the tiering
        accountant (graftlint rule ``device-array-leak``)."""
        return 0

    def promote_device(self) -> int:
        """Re-upload demoted arrays; returns HBM bytes charged. Same
        accountant contract as :meth:`demote_device`."""
        return 0

    def stats(self) -> dict:
        return {"count": self.count(), "capacity": self.capacity}


def run_tier_stable(fn):
    """Run a search closure, retrying when a residency flip lands between
    its tier check and the array access (``ResidencyMoved``). Either tier
    can serve any query, so a concurrent demote/promote must re-route the
    request, never fail it. Two retries bound the pathological case of a
    flip landing on every attempt."""
    from weaviate_tpu.compression.store import ResidencyMoved

    for _ in range(2):
        try:
            return fn()
        except ResidencyMoved:
            continue
    return fn()
