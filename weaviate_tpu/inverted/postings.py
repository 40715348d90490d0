"""Array-backed posting lists + doc-length columns.

Reference: the reference's postings live in LSMKV ``map``/``inverted``
buckets and are merged on read (``bm25_searcher.go``); round 1 held plain
Python dicts, which made snapshot load O(corpus) dict-building. These
structures keep the SNAPSHOT-LOADED base as numpy arrays (zero-copy from the
snapshot file) with a small mutation overlay on top — boot cost is
O(bytes read), not O(entries), and the dense scoring path consumes the
arrays directly.
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

_EMPTY_I64 = np.empty(0, np.int64)
_EMPTY_U32 = np.empty(0, np.uint32)


class PostingList:
    """doc -> tf map: immutable base arrays + dict overlay + dead set.

    Base arrays are doc-id-sorted (snapshot order). Mutations go to the
    overlay (`_over`) / tombstones (`_dead`); `arrays()` materializes the
    merged view lazily and caches it until the next mutation.
    """

    __slots__ = ("_ids", "_tfs", "_over", "_dead", "_len", "_cache")

    def __init__(self, ids: Optional[np.ndarray] = None,
                 tfs: Optional[np.ndarray] = None):
        self._ids = ids if ids is not None else _EMPTY_I64
        self._tfs = tfs if tfs is not None else _EMPTY_U32
        self._over: dict[int, int] = {}
        self._dead: Optional[set[int]] = None
        self._len = len(self._ids)
        self._cache: Optional[tuple[np.ndarray, np.ndarray]] = None

    # -- membership helpers ----------------------------------------------
    def _in_base(self, doc: int) -> int:
        """Index into base arrays or -1."""
        i = int(np.searchsorted(self._ids, doc))
        if i < len(self._ids) and self._ids[i] == doc:
            return i
        return -1

    def get(self, doc: int, default: int = 0) -> int:
        if self._over and doc in self._over:
            return self._over[doc]
        if self._dead and doc in self._dead:
            return default
        i = self._in_base(doc)
        return int(self._tfs[i]) if i >= 0 else default

    def __contains__(self, doc: int) -> bool:
        if self._over and doc in self._over:
            return True
        if self._dead and doc in self._dead:
            return False
        return self._in_base(doc) >= 0

    def __len__(self) -> int:
        return self._len

    # -- mutation ---------------------------------------------------------
    def set(self, doc: int, tf: int) -> None:
        existed = doc in self
        self._over[doc] = tf
        if self._dead:
            self._dead.discard(doc)
        if not existed:
            self._len += 1
        self._cache = None

    __setitem__ = set

    def add_new(self, doc: int, tf: int) -> None:
        """``set`` for a doc id KNOWN to be absent (fresh ingest: doc
        ids are monotonic and updates tombstone the old id, so the
        write path never re-adds a live doc). Skips the two
        membership probes — base-array searchsorted per (term, doc)
        was the ingest profile's top cost."""
        self._over[doc] = tf
        self._len += 1
        self._cache = None

    def pop(self, doc: int, default=None):
        prev = self.get(doc, -1)
        if prev == -1:
            return default
        self._over.pop(doc, None)
        if self._in_base(doc) >= 0:
            if self._dead is None:
                self._dead = set()
            self._dead.add(doc)
        self._len -= 1
        self._cache = None
        return prev

    # -- bulk views -------------------------------------------------------
    def arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """Merged (doc_ids int64, tfs uint32), doc-sorted. Cached."""
        if self._cache is not None:
            return self._cache
        ids, tfs = self._ids, self._tfs
        if self._dead:
            keep = ~np.isin(ids, np.fromiter(self._dead, np.int64,
                                             len(self._dead)))
            ids, tfs = ids[keep], tfs[keep]
        if self._over:
            o_ids = np.fromiter(self._over.keys(), np.int64, len(self._over))
            o_tfs = np.fromiter(self._over.values(), np.uint32,
                                len(self._over))
            keep = ~np.isin(ids, o_ids)
            ids = np.concatenate([ids[keep], o_ids])
            tfs = np.concatenate([tfs[keep], o_tfs])
            order = np.argsort(ids, kind="stable")
            ids, tfs = ids[order], tfs[order]
        self._cache = (ids, tfs)
        return self._cache

    def items(self) -> Iterator[tuple[int, int]]:
        ids, tfs = self.arrays()
        return zip(ids.tolist(), tfs.tolist())

    def keys(self) -> np.ndarray:
        return self.arrays()[0]

    def __iter__(self) -> Iterator[int]:
        return iter(self.arrays()[0].tolist())

    def values(self) -> np.ndarray:
        return self.arrays()[1]


class DocLengths:
    """Doc-id-aligned uint32 length column + live count.

    Replaces per-prop ``{doc: n_tokens}`` dicts: get/set are array ops, the
    dense BM25 path gathers lengths for a whole candidate set with one
    fancy-index, and snapshots are a single buffer write. The array stores
    ``length + 1`` (0 = absent) so zero-token docs stay representable.
    """

    __slots__ = ("_arr", "_count")

    def __init__(self, arr: Optional[np.ndarray] = None, count: int = 0):
        self._arr = arr if arr is not None else np.zeros(64, np.uint32)
        self._count = count

    def _ensure(self, doc: int) -> None:
        if doc >= len(self._arr):
            n = len(self._arr)
            while n <= doc:
                n *= 2
            grown = np.zeros(n, np.uint32)
            grown[: len(self._arr)] = self._arr
            self._arr = grown

    def get(self, doc: int, default: int = 0) -> int:
        if 0 <= doc < len(self._arr):
            v = int(self._arr[doc])
            return v - 1 if v else default
        return default

    def set(self, doc: int, length: int) -> Optional[int]:
        """Set and return the previous length (None if absent)."""
        self._ensure(doc)
        prev = int(self._arr[doc])
        self._arr[doc] = length + 1
        if prev == 0:
            self._count += 1
            return None
        return prev - 1

    def set_many(self, docs, lengths) -> int:
        """``set`` for each (doc, length) of a write batch (distinct docs,
        non-empty); returns the sum of the lengths it replaced."""
        ids = np.asarray(docs, np.int64)
        self._ensure(int(ids.max()))
        prev = self._arr[ids]  # length + 1 where the doc had one, else 0
        had = int(np.count_nonzero(prev))
        self._arr[ids] = np.asarray(lengths, np.uint32) + 1
        self._count += len(ids) - had
        return int(prev.sum()) - had

    def pop(self, doc: int, default=None):
        if 0 <= doc < len(self._arr) and self._arr[doc]:
            prev = int(self._arr[doc])
            self._arr[doc] = 0
            self._count -= 1
            return prev - 1
        return default

    def gather(self, doc_ids: np.ndarray) -> np.ndarray:
        """Lengths for a candidate array (out-of-range/absent -> 0)."""
        out = np.zeros(len(doc_ids), np.float32)
        ok = (doc_ids >= 0) & (doc_ids < len(self._arr))
        v = self._arr[doc_ids[ok]].astype(np.float32)
        out[ok] = np.maximum(v - 1.0, 0.0)
        return out

    def __len__(self) -> int:
        return self._count

    @property
    def raw(self) -> np.ndarray:
        return self._arr

    @property
    def count(self) -> int:
        return self._count
