"""Write-ahead log: length+CRC framed append-only records.

Reference: ``adapters/repos/db/lsmkv/commitlogger.go`` (per-memtable commit
log) and ``bucket_recover_from_wal.go`` (replay on startup, tolerate a torn
tail). Records are ``[u32 little-endian length][u32 crc32][payload]``; replay
stops cleanly at the first truncated or corrupt record, truncating the file
there — exactly the reference's recovery semantics.

Group commit (docs/ingest.md): with ``sync=True, group=True`` the fsync is
decoupled from ``append`` — records buffer to the OS and durability is
claimed at an explicit :meth:`sync_window` barrier, ONE fsync covering every
record appended before the call. Concurrent committers share the in-flight
fsync (leader/follower on a condition variable), so a burst of writers pays
one disk flush per append window instead of one per record — the
objectsBatcher's decouple-durability-from-indexing move, applied to the
fsync itself.
"""

from __future__ import annotations

import os
import struct
import threading
import zlib
from typing import Iterator, Optional

_HDR = struct.Struct("<II")


class WAL:
    def __init__(self, path: str, sync: bool = False, group: bool = False):
        self.path = path
        self.sync = sync
        # group commit: append() never fsyncs; callers claim durability at
        # sync_window(). Meaningful only with sync=True (sync=False never
        # fsyncs on append anyway, and sync_window degrades to flush_soft).
        self.group = group
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "ab")
        self.writes = 0  # write() calls handed to the file (tracing)
        # group-commit barrier state: a monotonic append counter, the
        # highest counter an fsync has covered, and whether a leader's
        # fsync is in flight (followers wait instead of stacking fsyncs)
        self._sync_cv = threading.Condition()
        self._appended = 0
        self._synced = 0
        self._syncing = False

    def append(self, payload: bytes) -> None:
        self.append_many((payload,))

    def append_many(self, payloads) -> None:
        """The records ``append`` would write one by one, framed the same
        and in the same order, handed to the file in ONE ``write()``; in
        sync mode one flush + fsync after it, before returning (group mode:
        every record counts toward the next :meth:`sync_window`)."""
        recs = [_HDR.pack(len(p), zlib.crc32(p)) + p for p in payloads]
        if not recs:
            return
        self._f.write(b"".join(recs))
        self.writes += 1
        if self.group:
            with self._sync_cv:
                self._appended += len(recs)
            return
        if self.sync:
            self._f.flush()
            os.fsync(self._f.fileno())

    def sync_window(self) -> None:
        """Group-commit barrier: returns once every record appended BEFORE
        this call is fsync-durable. One leader fsyncs for every waiter
        whose records the flush covers; late arrivals whose appends raced
        past an in-flight fsync elect the next leader."""
        if not self.sync:
            self._f.flush()  # soft mode: OS-buffer durability only
            return
        if not self.group:
            return  # every append already fsynced
        with self._sync_cv:
            target = self._appended
            while self._synced < target:
                if self._syncing:
                    self._sync_cv.wait(timeout=1.0)
                    continue
                self._syncing = True
                upto = self._appended
                break
            else:
                return
        try:
            self._f.flush()
            os.fsync(self._f.fileno())
        except BaseException:
            # a failed fsync (ENOSPC/EIO/rotated file) must not advance
            # _synced: followers waiting on this window would otherwise
            # ack durability for records that never hit disk. Hand the
            # leader role back so the next waiter retries (and surfaces
            # the same error to its own caller).
            with self._sync_cv:
                self._syncing = False
                self._sync_cv.notify_all()
            raise
        with self._sync_cv:
            self._syncing = False
            self._synced = max(self._synced, upto)
            self._sync_cv.notify_all()

    def flush(self) -> None:
        if self.group:
            # snapshot BEFORE the fsync: an append racing past the flush
            # must not be credited as durable by it
            with self._sync_cv:
                upto = self._appended
            self._f.flush()
            os.fsync(self._f.fileno())
            with self._sync_cv:
                self._synced = max(self._synced, upto)
                self._sync_cv.notify_all()
            return
        self._f.flush()
        os.fsync(self._f.fileno())

    def flush_soft(self) -> None:
        """Drain the userspace buffer to the OS (no fsync): survives process
        kill, keeps write-ordering against other files' fsyncs."""
        self._f.flush()

    @property
    def closed(self) -> bool:
        return self._f.closed

    def close(self) -> None:
        if not self._f.closed:
            self._f.flush()
            self._f.close()

    def size(self) -> int:
        self._f.flush()
        return os.path.getsize(self.path)

    @staticmethod
    def replay(path: str, truncate_corrupt: bool = True) -> Iterator[bytes]:
        """Yield intact records; on torn/corrupt tail, truncate and stop.

        The truncate re-checks the file size first: a writer that appended
        AFTER the replay snapshot (flush_soft racing a background replay)
        must not have its fresh records chopped off — a grown file is an
        active log, and recovery truncation applies only to quiescent ones
        (the post-corruption bytes are unreachable by framing either way)."""
        if not os.path.exists(path):
            return
        good_end = 0
        with open(path, "rb") as f:
            data = f.read()
        off = 0
        n = len(data)
        while off + _HDR.size <= n:
            length, crc = _HDR.unpack_from(data, off)
            start = off + _HDR.size
            end = start + length
            if end > n:
                break
            payload = data[start:end]
            if zlib.crc32(payload) != crc:
                break
            yield payload
            off = end
            good_end = end
        if truncate_corrupt and good_end < n:
            try:
                if os.path.getsize(path) != n:
                    return  # the log grew since the snapshot: writer active
            except OSError:
                return
            with open(path, "r+b") as f:
                f.truncate(good_end)

    @staticmethod
    def delete(path: str) -> None:
        if os.path.exists(path):
            os.remove(path)
