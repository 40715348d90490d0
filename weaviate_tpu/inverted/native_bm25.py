"""ctypes wrapper over the C++ BlockMax-WAND BM25 engine.

Reference: ``inverted/bm25_searcher_block.go`` (BlockMax-WAND). The Python
``InvertedIndex`` keeps its dict postings as source of truth (filters,
deletes, aggregations read them); this engine mirrors writes into native
posting lists and serves the scoring hot path. Scores match the Python
dense path bit-for-bit up to float32 rounding: idf and avgdl are computed
Python-side and passed per query term.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import threading
from typing import Optional

import numpy as np

from weaviate_tpu.native import NativeUnavailable, load

_U64 = ctypes.POINTER(ctypes.c_uint64)
_U32 = ctypes.POINTER(ctypes.c_uint32)
_I64 = ctypes.POINTER(ctypes.c_int64)
_F32 = ctypes.POINTER(ctypes.c_float)


def _bind():
    lib = load("bm25_wand")
    lib.bm25_new.restype = ctypes.c_void_p
    lib.bm25_new.argtypes = [ctypes.c_float, ctypes.c_float]
    lib.bm25_free.argtypes = [ctypes.c_void_p]
    # the five arrays as plain addresses: a typed cast costs more than
    # the call at the batch lengths the write path hands over
    lib.bm25_add_docs.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64] + [ctypes.c_void_p] * 5
    lib.bm25_add_term.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, _I64, _U32, _U32, ctypes.c_uint64]
    lib.bm25_set_params.argtypes = [
        ctypes.c_void_p, ctypes.c_float, ctypes.c_float]
    lib.bm25_remove_doc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.bm25_drop_term.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.bm25_compact.argtypes = [ctypes.c_void_p]
    lib.bm25_posting_len.restype = ctypes.c_uint64
    lib.bm25_posting_len.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.bm25_search.restype = ctypes.c_uint32
    lib.bm25_search.argtypes = [
        ctypes.c_void_p, _U64, _F32, _F32, ctypes.c_uint32, ctypes.c_uint32,
        _I64, _F32]
    lib.bm25_search_filtered.restype = ctypes.c_uint32
    lib.bm25_search_filtered.argtypes = [
        ctypes.c_void_p, _U64, _F32, _F32, ctypes.c_uint32, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64, _I64, _F32]
    lib.bm25_search_min_match.restype = ctypes.c_uint32
    lib.bm25_search_min_match.argtypes = [
        ctypes.c_void_p, _U64, _F32, _F32, _U32, ctypes.c_uint32,
        ctypes.c_uint32, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64, _I64, _F32]
    lib.bm25_score_docs.argtypes = [
        ctypes.c_void_p, _U64, _F32, _F32, ctypes.c_uint32,
        _I64, ctypes.c_uint32, _F32]
    return lib


def bm25_idf(n_docs: int, df: int) -> float:
    """The one BM25 idf definition every scoring tier shares — the
    native WAND engine, the dense python path, and the segmented device
    kernels (``ops/sparse.py``) all weight terms with exactly this, so
    their scores agree up to float32 rounding."""
    import math

    return math.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))


@functools.lru_cache(maxsize=262_144)
def term_id(prop: str, term: str) -> int:
    """64-bit id for a (property, term) pair — the native engine's key.
    Cached: term distributions are Zipf, so ingest hits the same few
    thousand hot terms constantly and the blake2b per (term, doc) was
    a measurable slice of the write path; the LRU bound keeps a
    pathological vocab from pinning memory."""
    h = hashlib.blake2b(f"{prop}\x00{term}".encode(), digest_size=8)
    return int.from_bytes(h.digest(), "big")


class NativeBM25:
    """One engine per shard; posting lists keyed by (property, term)."""

    COMPACT_EVERY = 4096  # removals between full tombstone purges

    def __init__(self, k1: float, b: float):
        self._lib = _bind()  # raises NativeUnavailable when no toolchain
        self._h = ctypes.c_void_p(self._lib.bm25_new(k1, b))
        self._lock = threading.Lock()
        self._removals = 0

    def set_params(self, k1: float, b: float) -> None:
        """Live scoring-param update (schema PUT applies without rebuild)."""
        with self._lock:
            self._lib.bm25_set_params(self._h, k1, b)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.bm25_free(h)
            self._h = None

    def add_docs(self, prop: str, doc_ids: list[int],
                 term_freqs: list[dict[str, int]],
                 doc_lens: list[int]) -> None:
        """One property's documents of a write batch (``term_freqs[i]``,
        never empty, and ``doc_lens[i]`` are doc ``doc_ids[i]``'s): one
        take of the lock and one hand-over of the flattened arrays."""
        if not doc_ids:
            return
        offsets = np.zeros(len(doc_ids) + 1, np.uint64)
        np.cumsum([len(tf) for tf in term_freqs], dtype=np.uint64,
                  out=offsets[1:])
        ids = np.array([term_id(prop, t) for tf in term_freqs for t in tf],
                       np.uint64)
        tfs = np.array([n for tf in term_freqs for n in tf.values()],
                       np.uint32)
        docs = np.array(doc_ids, np.int64)
        lens = np.array(doc_lens, np.uint32)
        with self._lock:
            self._lib.bm25_add_docs(
                self._h, len(docs), docs.ctypes.data, lens.ctypes.data,
                offsets.ctypes.data, ids.ctypes.data, tfs.ctypes.data)

    def add_term(self, prop: str, term: str, doc_ids: np.ndarray,
                 tfs: np.ndarray, doc_lens: np.ndarray) -> None:
        """Bulk-append one (prop, term) posting list — the snapshot-load
        path: one C call per term instead of one per doc."""
        n = len(doc_ids)
        if n == 0:
            return
        docs = np.ascontiguousarray(doc_ids, np.int64)
        tf = np.ascontiguousarray(tfs, np.uint32)
        dl = np.ascontiguousarray(doc_lens, np.uint32)
        with self._lock:
            self._lib.bm25_add_term(
                self._h, term_id(prop, term),
                docs.ctypes.data_as(_I64), tf.ctypes.data_as(_U32),
                dl.ctypes.data_as(_U32), n)

    def remove_doc(self, doc_id: int) -> None:
        with self._lock:
            self._lib.bm25_remove_doc(self._h, doc_id)
            self._removals += 1
            if self._removals >= self.COMPACT_EVERY:
                self._lib.bm25_compact(self._h)
                self._removals = 0

    def posting_len(self, prop: str, term: str) -> int:
        with self._lock:
            return self._lib.bm25_posting_len(self._h, term_id(prop, term))

    def drop_term(self, prop: str, term: str) -> None:
        """Evict one (prop, term) posting list — cache-tier eviction and
        write invalidation for the segment-resident index."""
        with self._lock:
            self._lib.bm25_drop_term(self._h, term_id(prop, term))

    def search(self, query_terms: list[tuple[str, str, float, float]],
               k: int, allow: Optional[np.ndarray] = None,
               groups: Optional[list[int]] = None, min_match: int = 1,
               ) -> tuple[np.ndarray, np.ndarray]:
        """query_terms: [(prop, term, weight=boost*idf, avgdl)]; allow:
        optional byte-per-doc mask (the filter engine's output) — WAND
        skipping stays active, disallowed docs are just never scored.
        ``groups``/``min_match``: distinct-token group per term and the
        minimum distinct tokens a doc must match (reference
        minimumOrTokensMatch / operator AND — one token fans out across
        properties in BM25F and must count once).
        Returns (doc_ids, scores) descending."""
        n = len(query_terms)
        if n == 0 or k == 0:
            return np.empty(0, np.int64), np.empty(0, np.float32)
        ids = (ctypes.c_uint64 * n)(
            *(term_id(p, t) for p, t, _, _ in query_terms))
        ws = (ctypes.c_float * n)(*(w for _, _, w, _ in query_terms))
        ads = (ctypes.c_float * n)(*(a for _, _, _, a in query_terms))
        out_docs = (ctypes.c_int64 * k)()
        out_scores = (ctypes.c_float * k)()
        ptr, alen = None, 0
        if allow is not None:
            if isinstance(allow, np.ndarray) and allow.flags.c_contiguous \
                    and allow.dtype in (np.uint8, np.bool_):
                # bool is 1 byte: view, don't copy — at 1M docs the two
                # dtype passes the generic path pays per query cost more
                # than the WAND search itself
                ab = allow.view(np.uint8)
            else:
                ab = np.ascontiguousarray(np.asarray(allow, bool), np.uint8)
            ptr = ab.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            alen = len(ab)
        if min_match > 1:
            garr = (ctypes.c_uint32 * n)(
                *(groups if groups is not None else range(n)))
            with self._lock:
                m = self._lib.bm25_search_min_match(
                    self._h, ids, ws, ads, garr, int(min_match), n, k,
                    ptr, alen, out_docs, out_scores)
        elif allow is None:
            with self._lock:
                m = self._lib.bm25_search(self._h, ids, ws, ads, n, k,
                                          out_docs, out_scores)
        else:
            with self._lock:
                m = self._lib.bm25_search_filtered(
                    self._h, ids, ws, ads, n, k, ptr, alen,
                    out_docs, out_scores)
        return (np.ctypeslib.as_array(out_docs)[:m].astype(np.int64),
                np.ctypeslib.as_array(out_scores)[:m].astype(np.float32))

    def score_docs(self, query_terms: list[tuple[str, str, float, float]],
                   doc_ids: np.ndarray) -> np.ndarray:
        n = len(query_terms)
        nd = len(doc_ids)
        out = (ctypes.c_float * nd)()
        if n == 0 or nd == 0:
            return np.zeros(nd, np.float32)
        ids = (ctypes.c_uint64 * n)(
            *(term_id(p, t) for p, t, _, _ in query_terms))
        ws = (ctypes.c_float * n)(*(w for _, _, w, _ in query_terms))
        ads = (ctypes.c_float * n)(*(a for _, _, _, a in query_terms))
        docs = (ctypes.c_int64 * nd)(*[int(d) for d in doc_ids])
        with self._lock:
            self._lib.bm25_score_docs(self._h, ids, ws, ads, n, docs, nd, out)
        return np.ctypeslib.as_array(out).astype(np.float32).copy()


def try_native_bm25(k1: float, b: float) -> Optional[NativeBM25]:
    """The native engine, or None where the loader serves from the Python
    twin (it warns once and says so in ``weaviate_tpu_native_library``:
    the dense path is a ~20x keyword-search slowdown)."""
    try:
        return NativeBM25(k1, b)
    except NativeUnavailable:
        return None
