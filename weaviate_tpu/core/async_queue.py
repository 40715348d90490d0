"""Disk-backed vector feed queue: the WAL→device stage of the ingest
pipeline (docs/ingest.md).

Reference: ``adapters/repos/db/queue/`` (scheduler + disk chunks) and
``indexcheckpoint/`` — the objectsBatcher decouples durability from
indexing: vectors enqueue to disk chunks inside the writer's durability
section, and the device feed happens in DRAIN windows outside the shard
lock, coalescing many writers' chunks into few large device batches.

Two modes (core/shard.py wires them):

- **inline (default)**: ``put_batch`` pushes under the shard lock, then
  calls :meth:`ensure_drained` after RELEASING it — read-your-writes is
  preserved, but concurrent readers and writers never queue behind one
  writer's device build (the old in-lock ``_feed_index`` convoy).
- **background** (``async_indexing`` / ``ASYNC_INDEXING=true``): the
  legacy fully-async mode — a scheduler thread drains on an interval and
  writes return before indexing.

The drain feeds each target's rows in **pow2 buckets** (binary
decomposition of the row count, largest-first, capped) so the device sees
a small closed set of batch shapes — every bucket reuses a compiled
program — and wraps the feed in ``dispatch_group(("ingest",))`` so any
dispatcher-mediated device work under the build coalesces with other
ingest work but never with a live search batch.

Durability: a chunk file is fully written before push returns; on restart
the shard's recovery rebuild re-feeds vectors from the object store
(add_batch is idempotent), so leftover chunks are simply discarded — a
SIGKILL mid-drain costs re-feeding, never wrong rows.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional

import msgpack
import numpy as np

from weaviate_tpu.monitoring.metrics import (
    ASYNC_QUEUE_SIZE,
    INGEST_DRAIN_SECONDS,
    INGEST_QUEUE_DEPTH,
)

# Largest pow2 feed bucket: bounds both the compile-shape set and the
# [rows, capacity] construction scratch one add_batch may allocate.
MAX_FEED_BUCKET = 2048


def pow2_buckets(n: int, cap: int = MAX_FEED_BUCKET) -> list[tuple[int, int]]:
    """Binary decomposition of ``n`` rows into (offset, size) pow2 buckets,
    largest-first, each size a power of two ≤ cap (300 → 256, 32, 8, 4).
    The drained feed issues ONE add_batch per bucket."""
    out: list[tuple[int, int]] = []
    off = 0
    while n > 0:
        b = min(cap, 1 << (n.bit_length() - 1))
        out.append((off, b))
        off += b
        n -= b
    return out


class AsyncVectorQueue:
    def __init__(
        self,
        dirpath: str,
        index_for: Callable[[str, int], object],
        is_live: Callable[[int], bool],
        shard_label: str = "",
        interval: float = 0.25,
        max_files_per_drain: int = 64,
    ):
        self.dir = dirpath
        self.index_for = index_for
        self.is_live = is_live
        self.label = shard_label
        self.interval = interval
        self.max_files_per_drain = max_files_per_drain
        os.makedirs(dirpath, exist_ok=True)
        self._lock = threading.Lock()
        self._drain_lock = threading.Lock()  # one drainer at a time
        self._seq = 0
        self._pending_vectors = 0
        self._pending_files = 0
        self._feed_dispatches = 0  # test hook: one per pow2 bucket fed
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # discard leftover chunks: recovery re-fed the index from the store
        for fn in os.listdir(dirpath):
            if fn.startswith("q-"):
                os.unlink(os.path.join(dirpath, fn))

    # -- enqueue -----------------------------------------------------------
    def push(self, target: str, doc_ids: np.ndarray,
             vectors: np.ndarray) -> str:
        """Write one durable chunk file; returns its filename (the handle
        :meth:`ensure_drained` waits on). Called inside the writer's
        durability section — this is a disk write, never device work."""
        frame = msgpack.packb({
            "target": target,
            "ids": np.asarray(doc_ids, np.int64).tobytes(),
            "vecs": np.asarray(vectors, np.float32).tobytes(),
            "n": int(len(doc_ids)),
            "d": int(vectors.shape[-1]),
        }, use_bin_type=True)
        with self._lock:
            fn = f"q-{self._seq:012d}.bin"
            path = os.path.join(self.dir, fn)
            self._seq += 1
            with open(path + ".tmp", "wb") as f:
                f.write(frame)
            os.replace(path + ".tmp", path)
            self._pending_vectors += len(doc_ids)
            self._pending_files += 1
        ASYNC_QUEUE_SIZE.set(self._pending_vectors, shard=self.label)
        INGEST_QUEUE_DEPTH.set(self._pending_vectors, shard=self.label)
        return fn

    def size(self) -> int:
        return self._pending_vectors

    def has_pending_files(self) -> bool:
        return bool(self._chunk_files())

    def feed_dispatch_count(self) -> int:
        """Test hook: add_batch calls issued by drains — one per pow2
        bucket (the acceptance pin of docs/ingest.md)."""
        return self._feed_dispatches

    def apply_barrier(self):
        """Serialization point for index mutations that must order against
        the drain's apply phase (deferred deletes in core/shard.py): a doc
        marked dead BEFORE acquiring this barrier can never resurrect —
        any in-flight drain that liveness-checked it finishes first, and
        later drains see it dead."""
        return self._drain_lock

    # -- drain -------------------------------------------------------------
    def _chunk_files(self) -> list[str]:
        return sorted(
            fn for fn in os.listdir(self.dir)
            if fn.startswith("q-") and fn.endswith(".bin"))

    def drain_once(self) -> int:
        """Apply up to max_files_per_drain chunks; returns vectors indexed."""
        with self._drain_lock:
            return self._drain_locked()

    def ensure_drained(self, files: list[str]) -> None:
        """Inline mode's read-your-writes tail: drain until every named
        chunk has been applied (file unlinked ⇒ its add_batch completed).
        Another drainer may consume our chunks for us — that is the
        coalescing win, not a race."""
        while any(os.path.exists(os.path.join(self.dir, fn))
                  for fn in files):
            self.drain_once()

    def _drain_locked(self) -> int:
        files = self._chunk_files()[: self.max_files_per_drain]
        if not files:
            return 0
        from weaviate_tpu.index.dispatch import dispatch_group
        from weaviate_tpu.monitoring import tracing

        t0 = time.perf_counter()
        by_target: dict[str, tuple[list, list]] = {}
        for fn in files:
            with open(os.path.join(self.dir, fn), "rb") as f:
                d = msgpack.unpackb(f.read(), raw=False)
            ids = np.frombuffer(d["ids"], np.int64)
            vecs = np.frombuffer(d["vecs"], np.float32).reshape(
                d["n"], d["d"])
            b = by_target.setdefault(d["target"], ([], []))
            b[0].append(ids)
            b[1].append(vecs)
        applied = 0
        buckets_fed = 0
        rows = sum(len(a) for arrs, _ in by_target.values() for a in arrs)
        with tracing.TRACER.span("ingest.drain", shard=self.label,
                                 files=len(files), rows=rows) as span:
            for target, (id_arrs, vec_arrs) in by_target.items():
                ids = np.concatenate(id_arrs)
                vecs = np.concatenate(vec_arrs)
                # docs deleted while queued must not resurrect in the index
                live = np.asarray(
                    [self.is_live(int(i)) for i in ids], bool)
                if not live.any():
                    continue
                ids, vecs = ids[live], vecs[live]
                idx = self.index_for(target, vecs.shape[-1])
                # pow2-bucketed feed under the ingest batch-group token:
                # builds coalesce with each other, never with a live
                # search batch (acceptance pin, docs/ingest.md)
                with dispatch_group(("ingest",)):
                    for off, size in pow2_buckets(len(ids)):
                        # grew: set by DeviceVectorStore.ensure_capacity
                        with tracing.TRACER.child("index.add_batch",
                                                 rows=size, grew=False):
                            # graftlint: allow[device-feed-under-lock] reason=_drain_lock is the single-drainer apply guard, not a shard lock; writers and readers never contend on it
                            idx.add_batch(ids[off:off + size],
                                          vecs[off:off + size])
                        buckets_fed += 1
                applied += len(ids)
            with self._lock:
                self._feed_dispatches += buckets_fed
            span.set(buckets=buckets_fed, applied=applied)
        for fn in files:
            os.unlink(os.path.join(self.dir, fn))
        drained = sum(len(a) for arrs, _ in by_target.values() for a in arrs)
        with self._lock:
            self._pending_vectors = max(0, self._pending_vectors - drained)
            self._pending_files = max(0, self._pending_files - len(files))
        ASYNC_QUEUE_SIZE.set(self._pending_vectors, shard=self.label)
        INGEST_QUEUE_DEPTH.set(self._pending_vectors, shard=self.label)
        INGEST_DRAIN_SECONDS.observe(time.perf_counter() - t0)
        return applied

    def flush(self) -> None:
        """Drain everything synchronously (shard flush/close path)."""
        while self._chunk_files():
            self.drain_once()

    def drain_until_empty(self) -> None:
        """Drain every pending chunk in ONE barrier hold. The shard's
        checkpoint needs "the index covers every pushed chunk" as a
        point-in-time truth; per-window :meth:`drain_once` can't give it
        while other pushers race between windows. The caller prevents new
        pushes for the duration (the shard checkpoint holds the shard
        lock, which every push runs under), so the loop terminates."""
        with self._drain_lock:
            while self._chunk_files():
                self._drain_locked()

    # -- scheduler ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"vindex-queue-{self.label}")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.drain_once()
            except Exception:  # noqa: BLE001 — background drain must survive
                import logging

                logging.getLogger("weaviate_tpu.queue").exception(
                    "async drain failed")
