"""LSM-style buckets: memtable + WAL + immutable sorted segments.

Reference: ``adapters/repos/db/lsmkv`` (``store.go:41``, ``bucket.go:74``,
``strategies.go:21-27``). A Store is a directory of named Buckets per shard;
each Bucket has an active memtable guarded by a WAL, and a list of immutable
segment files compacted in the background.

Strategies implemented:
- ``replace`` — last write wins (object CRUD), tombstones via None
- ``set``    — value is a set of byte-strings, merged by union across
               segments with per-entry add/remove (roaringset analogue)
- ``map``    — value is a key->bytes mapping merged newest-wins per map-key
               (postings with payloads)

Segments are disk-resident (``storage/segment.py``): record reads via mmap,
iteration/compaction as streaming k-way merges. A segment whose keys all
have one width (``objects``, ``ids``) keeps an exact key -> offset index in
RAM (width + 8 bytes a record, built at write / open); one of mixed widths
the sparse index + bloom filter (reference ``segment_bloom_filters.go``,
``segmentindex/``).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Iterator, Optional

import msgpack

from weaviate_tpu.storage.segment import (
    MISSING as _MISSING,
    DiskSegment as Segment,
    merge_streams,
    native_merge,
)
from weaviate_tpu.storage.wal import WAL

STRATEGIES = ("replace", "set", "map",
              # bitmap + postings strategies (reference strategies.go:21-27)
              "roaringset", "roaringsetrange", "inverted")


class ShardClosed(RuntimeError):
    """A read/write raced a shard shutdown (tenant freeze, drop): the
    mmap'd segments are gone. Clean and retriable — the reference cancels
    in-flight readers' contexts on shard shutdown the same way."""


class Bucket:
    def __init__(self, dirpath: str, strategy: str = "replace", sync: bool = False,
                 memtable_max_entries: int = 100_000, group: bool = False):
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {strategy!r}")
        self.dir = dirpath
        self.strategy = strategy
        self.memtable_max_entries = memtable_max_entries
        self.group = group  # group-commit WAL (one fsync per sync_window)
        os.makedirs(dirpath, exist_ok=True)
        self._lock = threading.RLock()
        self._mem: dict[bytes, Any] = {}
        self._segments: list[Segment] = []
        self._seg_seq = 0
        self._paused = 0  # maintenance (flush/compact) pause counter
        self._closed = False
        self.compaction_bytes_written = 0  # write-amplification diagnostic
        self._wal_writes = 0  # write() calls of this bucket's rotated WALs
        self._open(sync)

    def _open(self, sync: bool) -> None:
        segs = sorted(
            f for f in os.listdir(self.dir) if f.startswith("segment-") and f.endswith(".db")
        )
        for s in segs:
            path = os.path.join(self.dir, s)
            # seq advances even over quarantined files so a fresh segment
            # never reuses a number that would re-order the LSM stack
            self._seg_seq = max(self._seg_seq, int(s[len("segment-"):-3]) + 1)
            try:
                self._segments.append(Segment(path))
            except (ValueError, OSError):
                # unreadable/foreign-format segment: quarantine instead of
                # failing the whole shard open (reference has dedicated
                # corruption fixers; data re-enters via rebuild paths)
                os.replace(path, path + ".corrupt")
        wal_path = os.path.join(self.dir, "wal.log")
        for rec in WAL.replay(wal_path):
            op = msgpack.unpackb(rec, raw=True)
            self._apply_mem(op[b"k"], op[b"v"])
        self._wal = WAL(wal_path, sync=sync, group=self.group)

    # -- strategy-aware memtable application ------------------------------
    def _apply_mem(self, key: bytes, val) -> None:
        if self.strategy == "replace":
            self._mem[key] = val  # None == tombstone
        elif self.strategy == "set":
            cur = self._mem.setdefault(key, {})
            cur.update(val)  # val: {member: True/False}
        elif self.strategy in ("roaringset", "roaringsetrange"):
            # val: WAL delta {b"a": uint64-array bytes, b"d": ...}
            import numpy as _np

            from weaviate_tpu.storage.bitmaps import BitmapLayer

            layer = self._mem.get(key)
            if not isinstance(layer, BitmapLayer):
                layer = BitmapLayer()
                self._mem[key] = layer
            adds = _np.frombuffer(val.get(b"a", b""), _np.uint64)
            dels = _np.frombuffer(val.get(b"d", b""), _np.uint64)
            if len(adds):
                layer.adds.add_many(adds)
                layer.dels.remove_many(adds)
            if len(dels):
                layer.dels.add_many(dels)
                layer.adds.remove_many(dels)
        else:  # map / inverted (postings: docid-key -> packed payload)
            cur = self._mem.setdefault(key, {})
            cur.update(val)  # val: {mapkey: bytes|None}

    def _log(self, key: bytes, val) -> None:
        self._wal.append(msgpack.packb({"k": key, "v": val}, use_bin_type=True))

    # -- public API -------------------------------------------------------
    def put(self, key: bytes, value: bytes) -> None:
        self.put_many((key,), (value,))

    def put_many(self, keys, values) -> None:
        """``put`` for each pair of the two sequences, in order, under ONE
        take of the bucket lock: the records ``put`` would log, concatenated
        into one WAL ``write()`` (the file comes out byte-identical; a sync
        WAL is fsynced once, after it and before this returns), then the
        memtable (a key given twice keeps its last value), then the
        memtable-to-segment check once."""
        if self.strategy != "replace":
            raise ValueError("put()/put_many() require replace strategy")
        recs = [msgpack.packb({"k": k, "v": v}, use_bin_type=True)
                for k, v in zip(keys, values)]
        with self._lock:
            self._wal.append_many(recs)
            self._mem.update(zip(keys, values))  # replace: last write wins
            self._maybe_flush()

    def delete(self, key: bytes) -> None:
        if self.strategy != "replace":
            raise ValueError("delete() requires replace strategy")
        with self._lock:
            self._log(key, None)
            self._apply_mem(key, None)

    def get(self, key: bytes) -> Optional[bytes]:
        if self.strategy in ("roaringset", "roaringsetrange"):
            return self.roaring_get(key)
        if self.strategy == "replace":
            return self.get_many([key])[0]
        try:
            return self._get_merged(key)
        except ValueError as e:
            self._guard_closed(e)

    def get_many(self, keys: list[bytes],
                 stats: Optional[dict] = None) -> list[Optional[bytes]]:
        """Newest value of each key (``None``: absent or deleted), in the
        order asked, under ONE take of the bucket lock: the memtable first,
        then the segments newest to oldest for the keys still open; a
        tombstone ends a key's search. Keys in any order, duplicates
        allowed. ``stats`` (a request's counters, see ``docs/tracing.md``
        ``objects.fetch``) gains ``lock_takes``, ``mem_hits`` and
        ``records_read``."""
        if self.strategy != "replace":
            raise ValueError("get_many() requires replace strategy")
        out: list[Optional[bytes]] = [None] * len(keys)
        records = 0
        try:
            with self._lock:
                mem = self._mem
                open_at = []
                for i, key in enumerate(keys):
                    v = mem.get(key, _MISSING)
                    if v is _MISSING:
                        open_at.append(i)
                    else:
                        out[i] = v
                mem_hits = len(keys) - len(open_at)
                for seg in reversed(self._segments):
                    if not open_at:
                        break
                    vals, n = seg.get_many([keys[i] for i in open_at])
                    records += n
                    still = []
                    for i, v in zip(open_at, vals):
                        if v is _MISSING:
                            still.append(i)
                        else:
                            out[i] = v
                    open_at = still
        except ValueError as e:
            self._guard_closed(e)
        if stats is not None:
            stats["lock_takes"] = stats.get("lock_takes", 0) + 1
            stats["mem_hits"] = stats.get("mem_hits", 0) + mem_hits
            stats["records_read"] = stats.get("records_read", 0) + records
        return out

    def _get_merged(self, key: bytes) -> dict:
        """set/map/inverted: merged dict view, oldest segment first."""
        with self._lock:
            merged: dict = {}
            for seg in self._segments:
                v = seg.get(key)
                if v is not _MISSING and v is not None:
                    merged.update(v)
            if key in self._mem:
                merged.update(self._mem[key])
            return merged

    def set_add(self, key: bytes, members: list[bytes]) -> None:
        if self.strategy != "set":
            raise ValueError("set_add() requires set strategy")
        val = {m: True for m in members}
        with self._lock:
            self._log(key, val)
            self._apply_mem(key, val)
            self._maybe_flush()

    def set_remove(self, key: bytes, members: list[bytes]) -> None:
        val = {m: False for m in members}
        with self._lock:
            self._log(key, val)
            self._apply_mem(key, val)

    def set_members(self, key: bytes) -> set[bytes]:
        merged = self.get(key)
        return {m for m, present in merged.items() if present}

    def map_put(self, key: bytes, mapkey: bytes, value: bytes) -> None:
        if self.strategy != "map":
            raise ValueError("map_put() requires map strategy")
        with self._lock:
            self._log(key, {mapkey: value})
            self._apply_mem(key, {mapkey: value})
            self._maybe_flush()

    def map_delete(self, key: bytes, mapkey: bytes) -> None:
        with self._lock:
            self._log(key, {mapkey: None})
            self._apply_mem(key, {mapkey: None})

    def map_items(self, key: bytes) -> dict[bytes, bytes]:
        merged = self.get(key)
        return {k: v for k, v in merged.items() if v is not None}

    # -- roaringset(+range) API (reference roaringset/ bitmap layers) ------
    def roaring_add(self, key: bytes, ids) -> None:
        if self.strategy not in ("roaringset", "roaringsetrange"):
            raise ValueError("roaring_add() requires a roaring strategy")
        import numpy as _np

        arr = _np.asarray(ids, _np.uint64)
        if not len(arr):
            return
        val = {b"a": arr.tobytes()}
        with self._lock:
            self._log(key, val)
            self._apply_mem(key, val)
            self._maybe_flush()

    def roaring_remove(self, key: bytes, ids) -> None:
        if self.strategy not in ("roaringset", "roaringsetrange"):
            raise ValueError("roaring_remove() requires a roaring strategy")
        import numpy as _np

        arr = _np.asarray(ids, _np.uint64)
        if not len(arr):
            return
        val = {b"d": arr.tobytes()}
        with self._lock:
            self._log(key, val)
            self._apply_mem(key, val)

    def roaring_get(self, key: bytes):
        """Merged bitmap: fold segment layers oldest→newest, then the
        memtable layer (reference roaringset BitmapLayers.Flatten)."""
        from weaviate_tpu.storage.bitmaps import Bitmap, BitmapLayer

        if self.strategy not in ("roaringset", "roaringsetrange"):
            raise ValueError("roaring_get() requires a roaring strategy")
        with self._lock:
            try:
                acc = Bitmap()
                for seg in self._segments:
                    v = seg.get(key)
                    if v is not _MISSING and v is not None:
                        acc = _as_layer(v).apply_over(acc)
            except ValueError as e:
                self._guard_closed(e)
            mem = self._mem.get(key)
            if isinstance(mem, BitmapLayer):
                acc = mem.apply_over(acc)
            return acc

    # -- inverted (postings) API (reference StrategyInverted blocks) -------
    def postings_put(self, term: bytes, doc_ids, tfs, doc_lens) -> None:
        if self.strategy != "inverted":
            raise ValueError("postings_put() requires inverted strategy")
        import struct as _struct

        val = {int(d).to_bytes(8, "big"): _struct.pack("<II", int(t), int(l))
               for d, t, l in zip(doc_ids, tfs, doc_lens)}
        with self._lock:
            self._log(term, val)
            self._apply_mem(term, val)
            self._maybe_flush()

    def postings_remove(self, term: bytes, doc_ids) -> None:
        if self.strategy != "inverted":
            raise ValueError("postings_remove() requires inverted strategy")
        val = {int(d).to_bytes(8, "big"): None for d in doc_ids}
        with self._lock:
            self._log(term, val)
            self._apply_mem(term, val)

    def postings_get(self, term: bytes):
        """→ (doc_ids int64[], tfs uint32[], doc_lens uint32[]) sorted by
        doc id; the shape BlockMax-WAND block loads consume."""
        import struct as _struct

        import numpy as _np

        merged = self.get(term)
        live = sorted((k, v) for k, v in merged.items() if v is not None)
        ids = _np.fromiter((int.from_bytes(k, "big") for k, _ in live),
                           _np.int64, count=len(live))
        tfs = _np.empty(len(live), _np.uint32)
        dls = _np.empty(len(live), _np.uint32)
        for i, (_, v) in enumerate(live):
            tfs[i], dls[i] = _struct.unpack("<II", v)
        return ids, tfs, dls

    def items(self, start: bytes | None = None) -> Iterator[tuple[bytes, Any]]:
        """Live (key, merged-value) pairs in key order — one streaming k-way
        merge over segments + a memtable snapshot; nothing is materialized.
        ``start`` seeks every stream to the first key >= start (cursor
        pagination)."""
        with self._lock:
            streams = [seg.items(start) for seg in self._segments]
            mem = (sorted(self._mem.items()) if start is None else
                   sorted(kv for kv in self._mem.items()
                          if kv[0] >= start))
            streams.append(iter(mem))
        try:
            yield from merge_streams(streams, self.strategy,
                                     drop_tombstones=True)
        except ValueError as e:
            self._guard_closed(e)

    def keys(self) -> Iterator[bytes]:
        """All live keys, merged across memtable + segments, in key order."""
        for k, _ in self.items():
            yield k

    def __len__(self) -> int:
        return sum(1 for _ in self.keys())

    # -- flush / compaction ----------------------------------------------
    def pause_maintenance(self) -> None:
        """Stop segment-set mutations (flush + compaction) so a backup can
        copy a stable file set while WRITES keep landing in WAL+memtable —
        reference ``bucket_pauses.go`` PauseCompaction/FlushMemtable
        ordering. Re-entrant via a counter."""
        with self._lock:
            self._paused += 1

    def resume_maintenance(self) -> None:
        with self._lock:
            self._paused = max(0, self._paused - 1)

    def _maybe_flush(self) -> None:
        if self._paused:
            return  # deferred until resume; WAL holds the overflow
        if len(self._mem) >= self.memtable_max_entries:
            self.flush_memtable()

    def flush_memtable(self) -> None:
        with self._lock:
            if self._paused or not self._mem:
                return
            path = os.path.join(self.dir, f"segment-{self._seg_seq:06d}.db")
            self._seg_seq += 1
            self._segments.append(
                Segment.write(
                    path,
                    ((k, _encode_value(v)) for k, v in
                     sorted(self._mem.items()))
                )
            )
            self._mem = {}
            self._wal_writes += self._wal.writes
            self._wal.close()
            WAL.delete(self._wal.path)
            self._wal = WAL(self._wal.path, sync=self._wal.sync,
                            group=self._wal.group)

    def _merge_to(self, path: str, old: list, drop_tombstones: bool):
        """Merge ``old`` (oldest first) into a new segment at ``path``.
        The replace/map/inverted/set strategies route through the
        native C++ merge; byte-identical output is parity-tested, and
        any native failure falls back to the streaming Python merge
        (roaring strategies always take the Python path — their layer
        fold lives in ``storage/bitmaps.py``)."""
        if self.strategy in ("replace", "map", "inverted", "set"):
            tmp = path + ".tmp"
            n = native_merge([s.path for s in old], tmp, self.strategy,
                             drop_tombstones)
            if n is not None:
                os.replace(tmp, path)
                return Segment(path)
        return Segment.write(
            path,
            merge_streams([s.items() for s in old], self.strategy,
                          drop_tombstones=drop_tombstones),
        )

    def compact(self) -> None:
        """Streaming full-merge of all segments (newest wins / set-union /
        map-merge), dropping tombstones — reference
        ``segment_group_compaction.go``. Memory stays O(1) per record: the
        k-way merge reads each segment sequentially and the new segment is
        written as the merge drains. This is the EXPLICIT full compaction;
        the background cycle uses ``compact_tiered`` (pairwise, bounded
        write amplification)."""
        with self._lock:
            if self._paused or len(self._segments) <= 1:
                return
            old = self._segments
            path = os.path.join(self.dir, f"segment-{self._seg_seq:06d}.db")
            self._seg_seq += 1
            new_seg = self._merge_to(path, old, drop_tombstones=True)
            self.compaction_bytes_written += os.path.getsize(path)
            self._segments = [new_seg]
            for seg in old:
                # unlink only: a concurrent items() iterator may still hold
                # the mmap (Linux keeps the inode until the map drops)
                os.remove(seg.path)

    def compact_once(self) -> bool:
        """ONE pairwise merge of the adjacent pair with the smallest
        combined file size (reference ``segment_group_compaction.go``
        pairwise/leveled compaction). O(pair bytes), never O(total): a
        large cold segment is not rewritten to absorb a few fresh small
        ones — small neighbors merge together until their tier grows
        comparable. Tombstones drop only when the pair includes the OLDEST
        segment (an older segment could otherwise still hold the key).
        Returns True if a merge happened."""
        with self._lock:
            if self._paused or len(self._segments) <= 1:
                return False
            sizes = [os.path.getsize(s.path) for s in self._segments]
            i = min(range(len(sizes) - 1),
                    key=lambda j: sizes[j] + sizes[j + 1])
            old = self._segments[i:i + 2]
            # The merged segment adopts the OLDER filename — the only
            # crash-safe choice: a crash between the replace and the remove
            # leaves merged@old[0].path + old[1] on disk, and replaying
            # old[1] OVER the merged file is idempotent for every strategy
            # (newest-wins re-wins, unions re-union, roaring layers re-fold,
            # and a tombstone dropped from the i==0 merge still exists in
            # old[1]). Adopting the NEWER name instead would make a dropped
            # tombstone resurrect old[0]'s value after a crash.
            final_path = old[0].path
            tmp = final_path + ".compacting"
            new_seg = self._merge_to(tmp, old, drop_tombstones=(i == 0))
            os.replace(tmp, final_path)
            new_seg.path = final_path
            self.compaction_bytes_written += os.path.getsize(final_path)
            self._segments[i:i + 2] = [new_seg]
            os.remove(old[1].path)
            return True

    def compact_tiered(self, max_segments: int = 4) -> None:
        """Pairwise-merge until at most ``max_segments`` remain (or
        maintenance pauses). The background-cycle entry point."""
        while len(self._segments) > max(1, max_segments):
            if not self.compact_once():
                return

    def compaction_debt(self) -> int:
        """Outstanding merge work this bucket owes, in bytes — the
        leveled-policy debt score (docs/ingest.md): ``(segment_count - 1)
        × overlap bytes``, where overlap is the bytes that must be
        rewritten to collapse the stack to one segment (total minus the
        largest segment — LSM segments overlap the full key range). A
        single-segment or paused bucket owes nothing. The debt-driven
        scheduler ranks buckets by this score instead of sweeping every
        bucket on a fixed clock."""
        with self._lock:
            if self._paused or len(self._segments) <= 1:
                return 0
            try:
                sizes = [os.path.getsize(s.path) for s in self._segments]
            except OSError:
                return 0  # a racing compaction swapped files; next pass
            overlap = sum(sizes) - max(sizes)
            return max(0, (len(sizes) - 1) * overlap)

    def sync_window(self) -> None:
        """Group-commit barrier for this bucket's WAL, safe against a
        concurrent memtable-flush rotation: the WAL reference is captured
        under the bucket lock, and a barrier that loses the race to the
        rotation (closed file) is satisfied vacuously — flush_memtable
        wrote every one of that WAL's records into a segment before
        closing it."""
        with self._lock:
            wal = self._wal
        try:
            wal.sync_window()
        except ValueError:
            if not wal.closed:
                raise

    def wal_writes(self) -> int:
        """``write()`` calls handed to this bucket's WAL files since it was
        opened (``shard.durable`` reports the difference over a batch)."""
        with self._lock:
            return self._wal_writes + self._wal.writes

    def flush(self) -> None:
        self._wal.flush()

    def close(self) -> None:
        self._closed = True
        self.flush_memtable()
        self._wal.close()
        for seg in self._segments:
            seg.close()

    def _guard_closed(self, e: Exception):
        """mmap access after close raises ValueError; surface the race as
        ShardClosed instead of a confusing mmap error."""
        if self._closed:
            raise ShardClosed(
                f"bucket {self.dir!r} closed mid-operation") from e
        raise e

    def count(self) -> int:
        return len(self)


def _encode_value(v):
    """Memtable value → msgpack-able segment value (roaring layers carry
    their serialized form; everything else passes through)."""
    from weaviate_tpu.storage.bitmaps import BitmapLayer

    if isinstance(v, BitmapLayer):
        return {b"a": v.adds.to_bytes(), b"d": v.dels.to_bytes()}
    return v


def _as_layer(v):
    """Segment/memtable roaring value → BitmapLayer."""
    from weaviate_tpu.storage.bitmaps import Bitmap, BitmapLayer

    if isinstance(v, BitmapLayer):
        return v
    return BitmapLayer(
        Bitmap.from_bytes(v[b"a"]) if v.get(b"a") else None,
        Bitmap.from_bytes(v[b"d"]) if v.get(b"d") else None,
    )


class Store:
    """Named buckets rooted at a shard directory (reference ``store.go:41``)."""

    def __init__(self, dirpath: str, sync: bool = False, group: bool = False):
        self.dir = dirpath
        self.sync = sync
        self.group = group  # bucket WALs group-commit; ack via sync_all()
        os.makedirs(dirpath, exist_ok=True)
        self._buckets: dict[str, Bucket] = {}
        self._lock = threading.Lock()

    def bucket(self, name: str, strategy: str = "replace", **kw) -> Bucket:
        with self._lock:
            b = self._buckets.get(name)
            if b is None:
                b = Bucket(os.path.join(self.dir, name), strategy,
                           sync=self.sync, group=self.group, **kw)
                self._buckets[name] = b
            elif b.strategy != strategy:
                raise ValueError(
                    f"bucket {name!r} exists with strategy {b.strategy!r}"
                )
            return b

    def close(self) -> None:
        with self._lock:
            for b in self._buckets.values():
                b.close()
            self._buckets = {}

    def drop_bucket(self, name: str) -> None:
        """Close and delete a bucket's files (reindex truncation path)."""
        import shutil

        with self._lock:
            b = self._buckets.pop(name, None)
            if b is not None:
                b.close()
            shutil.rmtree(os.path.join(self.dir, name), ignore_errors=True)

    def bucket_names(self) -> list[str]:
        with self._lock:
            return list(self._buckets)

    def flush_all(self) -> None:
        with self._lock:
            for b in self._buckets.values():
                b.flush_memtable()

    def pause_maintenance(self) -> None:
        """Backup snapshot isolation (reference ``store_snapshot.go`` +
        ``bucket_pauses.go``): freeze every bucket's segment set."""
        with self._lock:
            for b in self._buckets.values():
                b.pause_maintenance()

    def resume_maintenance(self) -> None:
        with self._lock:
            for b in self._buckets.values():
                b.resume_maintenance()

    def sync_all(self) -> None:
        """Group-commit barrier across every bucket: one fsync per bucket
        WAL covering all records appended before the call (the per-batch
        durability ack of the ingest pipeline, docs/ingest.md). A no-op
        for non-group stores (every append already synced or soft)."""
        with self._lock:
            buckets = list(self._buckets.values())
        for b in buckets:
            b.sync_window()

    def wal_writes(self) -> int:
        """Sum of :meth:`Bucket.wal_writes` over the store's buckets."""
        with self._lock:
            buckets = list(self._buckets.values())
        return sum(b.wal_writes() for b in buckets)

    def compaction_debt(self) -> int:
        """Total merge debt across buckets (see Bucket.compaction_debt)."""
        with self._lock:
            buckets = list(self._buckets.values())
        return sum(b.compaction_debt() for b in buckets)

    def debt_ranked_buckets(self) -> list[tuple[int, "Bucket"]]:
        """(debt, bucket) pairs with positive debt, highest first — the
        debt-driven compaction scheduler's work queue."""
        with self._lock:
            buckets = list(self._buckets.values())
        ranked = [(b.compaction_debt(), b) for b in buckets]
        return sorted(((d, b) for d, b in ranked if d > 0),
                      key=lambda t: -t[0])

    def compact_all(self, min_segments: int = 4) -> None:
        """Background compaction entry (reference cyclemanager-driven
        ``segment_group_compaction.go``): size-tiered pairwise merges for
        any bucket whose segment stack is at least ``min_segments`` deep —
        each merge O(pair bytes), so a deep stack of fresh small segments
        never forces a rewrite of the large cold ones."""
        with self._lock:
            buckets = list(self._buckets.values())
        for b in buckets:
            if len(b._segments) >= min_segments:
                b.compact_tiered(min_segments - 1)
