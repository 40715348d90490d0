"""Disk-resident immutable segments: sparse index + bloom filter + mmap reads.

Reference: ``adapters/repos/db/lsmkv/segment.go`` + ``segment_bloom_filters.go``
+ ``segmentindex/`` (disk b-tree). Round-1 segments loaded every record into a
RAM dict on open — O(corpus) memory and boot time. This format keeps data on
disk and loads only a sparse index (every SPARSE-th key) plus a bloom filter:

    [magic "WVTSEG01"]
    data:   repeated [u32 klen][u32 vlen][key][msgpack(value)]   (key-sorted)
    index:  msgpack [[key, offset] every SPARSE-th record, ..., [last, off]]
    bloom:  [u64 nbits][u32 nhashes][bit bytes]
    footer: [u64 index_off][u64 bloom_off][u64 count][magic]

Point reads take one of two paths, chosen by the key widths the segment
holds (no option):

- **Exact index** — every key has one width (the ``objects`` bucket: 8-byte
  big-endian doc ids; ``ids``: uuid bytes). The segment keeps every key and
  its record offset in RAM: a numpy ``S<width>`` array plus ``uint64``
  offsets, ``width + 8`` bytes a record (16 B for ``objects``: 4 MB for
  250,000 rows). ``get_many`` is one ``np.searchsorted`` for all of a
  request's keys and one record read a hit; the bloom filter is not loaded.
  The index is built when the segment is written (``write`` has every key
  and offset in hand) or opened (one pass over the record headers: restart,
  ``native_merge``'s output) — never filled by reads.
- **Sparse index** — keys of mixed width (the inverted buckets): bloom probe
  -> bisect the sparse index -> scan <= SPARSE records via mmap.

The file format is the same for both: the exact index is derived state, and
a file written before it existed opens and answers the same. Iteration
streams records in key order (compaction never materializes a segment in
RAM). Tombstones are msgpack ``nil`` payloads, kept until compaction drops
them.
"""

from __future__ import annotations

import bisect
import hashlib
import mmap
import os
import struct
from typing import Any, Iterator

import msgpack
import numpy as np

MAGIC = b"WVTSEG01"
SPARSE = 32  # one index entry per this many records
_REC = struct.Struct("<II")
_FOOTER = struct.Struct("<QQQ")
_BLOOM_HDR = struct.Struct("<QI")
_BLOOM_BITS_PER_KEY = 10
_BLOOM_HASHES = 7


class _Missing:
    __slots__ = ()


MISSING = _Missing()


def _bloom_hashes(key: bytes) -> tuple[int, int]:
    d = hashlib.blake2b(key, digest_size=16).digest()
    return int.from_bytes(d[:8], "little"), int.from_bytes(d[8:], "little")


class BloomFilter:
    """Double-hashing bloom: h_i = h1 + i*h2 (Kirsch-Mitzenmacher)."""

    def __init__(self, nbits: int, nhashes: int, bits: bytearray):
        self.nbits = nbits
        self.nhashes = nhashes
        self.bits = bits

    @classmethod
    def build(cls, keys, count: int) -> "BloomFilter":
        nbits = max(64, count * _BLOOM_BITS_PER_KEY)
        bf = cls(nbits, _BLOOM_HASHES, bytearray((nbits + 7) // 8))
        for k in keys:
            bf.add(k)
        return bf

    def add(self, key: bytes) -> None:
        h1, h2 = _bloom_hashes(key)
        for i in range(self.nhashes):
            b = (h1 + i * h2) % self.nbits
            self.bits[b >> 3] |= 1 << (b & 7)

    def __contains__(self, key: bytes) -> bool:
        h1, h2 = _bloom_hashes(key)
        for i in range(self.nhashes):
            b = (h1 + i * h2) % self.nbits
            if not (self.bits[b >> 3] >> (b & 7)) & 1:
                return False
        return True

    def to_bytes(self) -> bytes:
        return _BLOOM_HDR.pack(self.nbits, self.nhashes) + bytes(self.bits)

    @classmethod
    def from_bytes(cls, raw: bytes) -> "BloomFilter":
        nbits, nhashes = _BLOOM_HDR.unpack_from(raw)
        return cls(nbits, nhashes, bytearray(raw[_BLOOM_HDR.size:]))


class DiskSegment:
    """Immutable on-disk sorted segment; RAM cost is the exact index
    (uniform key width) or the sparse index + bloom (mixed widths)."""

    def __init__(self, path: str, exact=None):
        """``exact``: (keys, offsets) from :meth:`write`, which has them in
        hand; a segment opened from a file reads them off the headers."""
        self.path = path
        self._f = open(path, "rb")
        size = os.fstat(self._f.fileno()).st_size
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        foot_at = size - _FOOTER.size - len(MAGIC)
        if self._mm[foot_at + _FOOTER.size:size] != MAGIC or self._mm[:8] != MAGIC:
            raise ValueError(f"corrupt segment {path!r} (bad magic)")
        index_off, bloom_off, self.count = _FOOTER.unpack_from(self._mm, foot_at)
        self._data_end = index_off
        idx = msgpack.unpackb(bytes(self._mm[index_off:bloom_off]), raw=True)
        self._idx_keys: list[bytes] = [e[0] for e in idx]
        self._idx_offs: list[int] = [e[1] for e in idx]
        self._keys, self._offs = \
            self._scan_exact() if exact is None else exact
        # a segment with an exact index answers absence itself
        self.bloom = None if self._keys is not None else \
            BloomFilter.from_bytes(bytes(self._mm[bloom_off:foot_at]))

    def _scan_exact(self):
        """(keys, offsets) off one pass over the record headers, or
        (None, None) at the first key of another width."""
        mm, off, end = self._mm, len(MAGIC), self._data_end
        if off >= end:
            return None, None
        width = _REC.unpack_from(mm, off)[0]
        keys = bytearray(width * self.count)
        offs = np.empty(self.count, np.uint64)
        i = 0
        while off < end and i < self.count:
            klen, vlen = _REC.unpack_from(mm, off)
            if klen != width:
                return None, None
            offs[i] = off
            keys[i * width:(i + 1) * width] = mm[off + 8:off + 8 + width]
            off += _REC.size + klen + vlen
            i += 1
        if i != self.count or off != end:
            raise ValueError(
                f"corrupt segment {self.path!r} (record count)")
        return _exact_index(bytes(keys), width, offs)

    # -- reads ------------------------------------------------------------
    def _value(self, off: int, vlen: int):
        """Decode the msgpack payload at ``off``. A ``bin`` payload (the
        replace buckets' opaque bytes) is sliced past its header: one copy
        out of the mmap, not two."""
        mm = self._mm
        hdr = _BIN_HEADER.get(mm[off])
        if hdr is not None:
            return mm[off + hdr:off + vlen]
        return msgpack.unpackb(mm[off:off + vlen], raw=True)

    def get(self, key: bytes):
        """Value for key, None for a tombstone, MISSING when absent."""
        return self.get_many([key])[0][0]

    def get_many(self, keys: list[bytes]):
        """([value | None (tombstone) | MISSING per key], records read).
        Keys in any order, duplicates allowed."""
        if self._keys is None:
            read = 0
            out = []
            for key in keys:
                v, n = self._get_sparse(key)
                out.append(v)
                read += n
            return out, read
        out = [MISSING] * len(keys)
        width = self._keys.dtype.itemsize
        # a key of another width cannot be here (and must not reach the
        # S-dtype compare, which pads with NULs)
        at = [i for i, k in enumerate(keys) if len(k) == width]
        if not at:
            return out, 0
        q = np.frombuffer(b"".join([keys[i] for i in at]), self._keys.dtype)
        pos = np.minimum(self._keys.searchsorted(q), self.count - 1)
        found = (self._keys[pos] == q).tolist()
        mm = self._mm
        read = 0
        for i, hit, off in zip(at, found, self._offs[pos].tolist()):
            if hit:
                klen, vlen = _REC.unpack_from(mm, off)
                out[i] = self._value(off + _REC.size + klen, vlen)
                read += 1
        return out, read

    def _get_sparse(self, key: bytes):
        """(value, records stepped) by bloom probe -> bisect -> scan."""
        if not self._idx_keys or key not in self.bloom:
            return MISSING, 0
        # rightmost sparse entry with idx_key <= key
        i = bisect.bisect_right(self._idx_keys, key) - 1
        if i < 0:
            return MISSING, 0
        off = self._idx_offs[i]
        stop = (
            self._idx_offs[i + 1]
            if i + 1 < len(self._idx_offs)
            else self._data_end
        )
        mm = self._mm
        read = 0
        while off <= stop and off < self._data_end:
            klen, vlen = _REC.unpack_from(mm, off)
            off += _REC.size
            k = mm[off:off + klen]
            off += klen
            read += 1
            if k == key:
                return self._value(off, vlen), read
            if k > key:
                return MISSING, read
            off += vlen
        return MISSING, read

    def __contains__(self, key: bytes) -> bool:
        return self.get(key) is not MISSING

    def items(self, start: bytes | None = None) -> Iterator[tuple[bytes, Any]]:
        """Stream (key, value) in key order; tombstones yield value None.
        ``start`` seeks to the first key >= start via the sparse index —
        the cursor-pagination path (reference ``filters.Cursor``) pays
        O(SPARSE) records of skip, not O(position)."""
        mm = self._mm
        off = len(MAGIC)
        end = self._data_end
        if start is not None and self._idx_keys:
            # rightmost sparse entry <= start bounds the scan-in point
            i = bisect.bisect_right(self._idx_keys, start) - 1
            if i >= 0:
                off = self._idx_offs[i]
        while off < end:
            klen, vlen = _REC.unpack_from(mm, off)
            off += _REC.size
            k = mm[off:off + klen]
            off += klen
            if start is not None and k < start:
                off += vlen  # inside the sparse gap, before the cursor
                continue
            v = self._value(off, vlen)
            off += vlen
            yield k, v

    def keys(self) -> Iterator[bytes]:
        for k, _ in self.items():
            yield k

    def __len__(self) -> int:
        return self.count

    def close(self) -> None:
        try:
            self._mm.close()
            self._f.close()
        except (OSError, ValueError):
            pass  # double-close during compaction teardown is harmless

    # -- writes -----------------------------------------------------------
    @staticmethod
    def write(path: str, items) -> "DiskSegment":
        """Write a segment from (key, value) pairs in SORTED key order.

        ``items`` may be any iterable (list or generator — compaction streams
        a k-way merge through here without materializing).
        """
        tmp = path + ".tmp"
        sparse: list[tuple[bytes, int]] = []
        keys: list[bytes] = []
        offs: list[int] = []
        widths: set[int] = set()
        count = 0
        last: tuple[bytes, int] | None = None
        with open(tmp, "wb") as f:
            f.write(MAGIC)
            off = len(MAGIC)
            for key, val in items:
                payload = msgpack.packb(val, use_bin_type=True)
                if count % SPARSE == 0:
                    sparse.append((key, off))
                last = (key, off)
                keys.append(key)
                offs.append(off)
                widths.add(len(key))
                f.write(_REC.pack(len(key), len(payload)))
                f.write(key)
                f.write(payload)
                off += _REC.size + len(key) + len(payload)
                count += 1
            if last is not None and (count - 1) % SPARSE != 0:
                sparse.append(last)  # bound the final scan range
            index_off = off
            f.write(msgpack.packb([[k, o] for k, o in sparse], use_bin_type=True))
            bloom_off = f.tell()
            f.write(BloomFilter.build(keys, count).to_bytes())
            f.write(_FOOTER.pack(index_off, bloom_off, count))
            f.write(MAGIC)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        if len(widths) != 1:
            return DiskSegment(path, exact=(None, None))
        return DiskSegment(path, exact=_exact_index(
            b"".join(keys), widths.pop(), np.array(offs, np.uint64)))


# msgpack ``bin`` type byte -> header length (bin8 / bin16 / bin32)
_BIN_HEADER = {0xC4: 2, 0xC5: 3, 0xC6: 5}


def _exact_index(keys: bytes, width: int, offs: np.ndarray):
    """(sorted ``S<width>`` key array over ``keys``, record offsets); no
    index for zero-width keys (numpy has no ``S0`` ordering)."""
    if not width:
        return None, None
    return np.frombuffer(keys, f"S{width}"), offs


def native_merge(in_paths: list[str], out_path: str, strategy: str,
                 drop_tombstones: bool):
    """C++ k-way merge for the non-bitmap strategies. *replace*:
    payloads are opaque (newest wins, tombstone = msgpack nil).
    *map*/*inverted*/*set*: member maps union oldest -> newest with
    newest-wins per member and Python-dict insertion order; map/
    inverted drop nil members, set drops falsy ones. Output is
    byte-identical to :meth:`DiskSegment.write` over ``merge_streams``
    (parity-tested on the store's real payload shapes). Returns the
    record count, or ``None`` when the native tier is unavailable or
    the merge fails — callers fall back to the streaming Python merge.
    ``in_paths`` oldest -> newest, like ``merge_streams``."""
    import ctypes

    from weaviate_tpu import native

    if strategy not in ("replace", "map", "inverted", "set"):
        return None
    try:
        lib = native.load("segment_merge")
    except native.NativeUnavailable:
        return None
    arr = (ctypes.c_char_p * len(in_paths))(
        *[p.encode() for p in in_paths])
    # getattr: a stale cached .so predating a symbol must degrade to
    # the Python merge, not AttributeError out of compaction
    if strategy == "replace":
        fn = getattr(lib, "merge_replace_segments", None)
        if fn is None:
            return None
        fn.restype = ctypes.c_longlong
        fn.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                       ctypes.c_char_p, ctypes.c_int]
        rc = fn(arr, len(in_paths), out_path.encode(),
                1 if drop_tombstones else 0)
    else:
        fn = getattr(lib, "merge_map_segments", None)
        if fn is None:
            return None
        fn.restype = ctypes.c_longlong
        fn.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                       ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
        rc = fn(arr, len(in_paths), out_path.encode(),
                1 if drop_tombstones else 0,
                1 if strategy == "set" else 0)
    if rc < 0:
        try:  # never leave a half-written output behind
            os.remove(out_path)
        except OSError:
            pass
        return None
    return int(rc)


def native_merge_replace(in_paths: list[str], out_path: str,
                         drop_tombstones: bool):
    """Back-compat shim over :func:`native_merge` (replace strategy)."""
    return native_merge(in_paths, out_path, "replace", drop_tombstones)


def merge_streams(streams: list[Iterator[tuple[bytes, Any]]], strategy: str,
                  drop_tombstones: bool) -> Iterator[tuple[bytes, Any]]:
    """K-way merge of key-sorted streams, oldest stream first in ``streams``.

    Equal keys combine by strategy: replace -> newest wins; set/map -> dict
    union with newest-wins per member, dropping removed members when
    ``drop_tombstones`` (full compaction semantics, reference
    ``segment_group_compaction.go``).
    """
    import heapq

    iters = [iter(s) for s in streams]
    heap: list[tuple[bytes, int]] = []
    heads: list[Any] = [None] * len(iters)
    for i, it in enumerate(iters):
        try:
            k, v = next(it)
            heads[i] = v
            heapq.heappush(heap, (k, i))
        except StopIteration:
            pass

    def advance(i):
        try:
            k, v = next(iters[i])
            heads[i] = v
            heapq.heappush(heap, (k, i))
        except StopIteration:
            heads[i] = None

    while heap:
        key, i = heapq.heappop(heap)
        vals = [(i, heads[i])]
        advance(i)
        while heap and heap[0][0] == key:
            _, j = heapq.heappop(heap)
            vals.append((j, heads[j]))
            advance(j)
        vals.sort(key=lambda t: t[0])  # oldest -> newest
        if strategy == "replace":
            merged = vals[-1][1]
            if merged is None and drop_tombstones:
                continue
            yield key, merged
        elif strategy in ("roaringset", "roaringsetrange"):
            # fold bitmap layers oldest->newest (reference roaringset
            # compactor); a full compaction flattens deletions away
            from weaviate_tpu.storage.bitmaps import BitmapLayer
            from weaviate_tpu.storage.store import _as_layer, _encode_value

            layer = BitmapLayer()
            for _, v in vals:
                if v is not None:
                    layer = BitmapLayer.merged(layer, _as_layer(v))
            if drop_tombstones:
                layer.dels = type(layer.dels)()
                if not len(layer.adds):
                    continue
            yield key, _encode_value(layer)
        else:
            acc: dict = {}
            for _, v in vals:
                if v:
                    acc.update(v)
            if drop_tombstones:
                if strategy == "set":
                    acc = {m: p for m, p in acc.items() if p}
                else:
                    acc = {m: p for m, p in acc.items() if p is not None}
            if acc or not drop_tombstones:
                yield key, acc
