"""Storage primitives: WAL recovery, bucket strategies, compaction.

Mirrors reference tests ``lsmkv/bucket_recover_test.go``,
``lsmkv/compaction_integration_test.go``, ``commitlogger_parser_test.go``,
``segment_group_compaction.go`` (pairwise/tiered).
"""

import hashlib
import os
import random
import struct
import sys
import threading

import msgpack
import pytest

from weaviate_tpu import native
from weaviate_tpu.storage import segment as segmod
from weaviate_tpu.storage.segment import MISSING, DiskSegment, native_merge
from weaviate_tpu.storage.wal import WAL
from weaviate_tpu.storage.store import Bucket, Store


def test_tiered_compaction_is_pairwise_and_bounded(tmp_path):
    """The background cycle must NOT rewrite a large cold segment to absorb
    a few fresh small ones (VERDICT r2 missing #6: all-to-one compact was
    O(total bytes) per cycle)."""
    b = Bucket(str(tmp_path / "b"), memtable_max_entries=100_000)
    for i in range(2000):
        b.put(f"big{i:05d}".encode(), b"x" * 50)
    b.flush_memtable()
    big_path = b._segments[0].path
    big_ino = os.stat(big_path).st_ino
    for s in range(4):
        for i in range(20):
            b.put(f"s{s}k{i:02d}".encode(), b"y")
        b.flush_memtable()
    assert len(b._segments) == 5
    b.compact_tiered(max_segments=2)
    assert len(b._segments) == 2
    # the big cold segment kept its file (inode) — never rewritten
    assert b._segments[0].path == big_path
    assert os.stat(big_path).st_ino == big_ino
    assert b.compaction_bytes_written < os.path.getsize(big_path)
    # all data still readable after reopen (on-disk order preserved)
    b.close()
    b2 = Bucket(str(tmp_path / "b"))
    assert b2.get(b"big00000") == b"x" * 50
    assert b2.get(b"s3k19") == b"y"
    assert b2.get(b"s0k00") == b"y"
    b2.close()


def test_pairwise_merge_keeps_tombstones_until_oldest(tmp_path):
    """A tombstone may only be dropped when its merge includes the oldest
    segment — an older segment could still hold the key (reference
    compactor ``keepTombstones`` rule)."""
    b = Bucket(str(tmp_path / "b"))
    for i in range(500):  # big oldest segment holding k
        b.put(f"pad{i:04d}".encode(), b"p" * 40)
    b.put(b"k", b"v1")
    b.flush_memtable()
    b.delete(b"k")
    b.flush_memtable()   # tiny segment: tombstone only
    b.put(b"other", b"x")
    b.flush_memtable()   # tiny segment
    assert len(b._segments) == 3
    # min-combined pair is the two tiny ones -> merged WITHOUT the oldest
    assert b.compact_once()
    assert len(b._segments) == 2
    assert b.get(b"k") is None          # tombstone still effective...
    assert b._segments[1].get(b"k") is None  # ...and physically retained
    b.compact()  # full merge includes the oldest: tombstone GC
    assert len(b._segments) == 1
    assert b.get(b"k") is None
    assert all(k != b"k" for k in b._segments[0].keys())
    b.close()


def test_wal_roundtrip_and_torn_tail(tmp_path):
    p = str(tmp_path / "wal.log")
    w = WAL(p)
    w.append(b"one")
    w.append(b"two")
    w.append(b"three")
    w.close()
    # corrupt: append garbage partial record
    with open(p, "ab") as f:
        f.write(b"\xff\xff\xff\xff partial")
    recs = list(WAL.replay(p))
    assert recs == [b"one", b"two", b"three"]
    # file was truncated to last good record; replay again is clean
    assert list(WAL.replay(p)) == [b"one", b"two", b"three"]


def test_bucket_replace_crud_and_recovery(tmp_path):
    d = str(tmp_path / "b")
    b = Bucket(d)
    b.put(b"k1", b"v1")
    b.put(b"k2", b"v2")
    b.put(b"k1", b"v1b")
    b.delete(b"k2")
    assert b.get(b"k1") == b"v1b"
    assert b.get(b"k2") is None
    b._wal.flush()
    # reopen WITHOUT closing (crash): WAL replay restores memtable
    b2 = Bucket(d)
    assert b2.get(b"k1") == b"v1b"
    assert b2.get(b"k2") is None
    b2.close()


def test_bucket_flush_segments_and_compaction(tmp_path):
    d = str(tmp_path / "b")
    b = Bucket(d)
    for i in range(10):
        b.put(f"k{i}".encode(), f"v{i}".encode())
    b.flush_memtable()
    for i in range(5):
        b.put(f"k{i}".encode(), f"v{i}x".encode())
    b.delete(b"k9")
    b.flush_memtable()
    assert len(b._segments) == 2
    assert b.get(b"k3") == b"v3x"
    assert b.get(b"k7") == b"v7"
    assert b.get(b"k9") is None
    b.compact()
    assert len(b._segments) == 1
    assert b.get(b"k3") == b"v3x"
    assert b.get(b"k9") is None
    assert len(b) == 9
    b.close()
    # reopen from segments only
    b2 = Bucket(d)
    assert b2.get(b"k0") == b"v0x"
    b2.close()


def test_set_strategy(tmp_path):
    b = Bucket(str(tmp_path / "s"), strategy="set")
    b.set_add(b"key", [b"a", b"b"])
    b.flush_memtable()
    b.set_add(b"key", [b"c"])
    b.set_remove(b"key", [b"a"])
    assert b.set_members(b"key") == {b"b", b"c"}
    b.compact()
    assert b.set_members(b"key") == {b"b", b"c"}
    b.close()


def test_map_strategy(tmp_path):
    b = Bucket(str(tmp_path / "m"), strategy="map")
    b.map_put(b"doc", b"f1", b"x")
    b.flush_memtable()
    b.map_put(b"doc", b"f2", b"y")
    b.map_put(b"doc", b"f1", b"z")
    b.map_delete(b"doc", b"f2")
    assert b.map_items(b"doc") == {b"f1": b"z"}
    b.close()
    b2 = Bucket(str(tmp_path / "m"), strategy="map")
    assert b2.map_items(b"doc") == {b"f1": b"z"}
    b2.close()


def test_store_buckets(tmp_path):
    s = Store(str(tmp_path / "st"))
    b1 = s.bucket("objects")
    b2 = s.bucket("postings", strategy="map")
    assert s.bucket("objects") is b1
    b1.put(b"a", b"1")
    b2.map_put(b"t", b"d", b"2")
    s.close()
    s2 = Store(str(tmp_path / "st"))
    assert s2.bucket("objects").get(b"a") == b"1"
    s2.close()


def test_memtable_auto_flush(tmp_path):
    b = Bucket(str(tmp_path / "af"), memtable_max_entries=10)
    for i in range(25):
        b.put(f"k{i:03d}".encode(), b"v")
    assert len(b._segments) >= 2
    assert len(b) == 25
    b.close()


def test_write_heavy_soak_bounded_write_amplification(tmp_path):
    """Sustained writes with periodic background compaction: total
    compaction bytes stay a small multiple of ingested bytes (the
    all-to-one compactor rewrote O(total) per cycle — VERDICT r2 #6)."""
    b = Bucket(str(tmp_path / "b"), memtable_max_entries=500)
    ingested = 0
    for i in range(8000):
        payload = (f"v{i}".encode() * 8)
        b.put(f"k{i % 4000:05d}".encode(), payload)
        ingested += len(payload) + 6
        if i % 2000 == 1999:
            b.compact_tiered(max_segments=4)
    b.flush_memtable()
    b.compact_tiered(max_segments=4)
    assert len(b._segments) <= 4
    amp = b.compaction_bytes_written / max(ingested, 1)
    # tiered pairwise keeps amplification low; all-to-one on this write
    # pattern measures >4x
    assert amp < 3.0, f"write amplification {amp:.2f}"
    # data correct after all that churn
    assert b.get(b"k00123") is not None
    b.close()


# -- multi-get and the exact index (PR 32) ----------------------------------

_K = struct.Struct(">q")


def _layered_bucket(tmp_path):
    """Three segments and a memtable over one key space, with newer values
    and tombstones shadowing older ones; -> (bucket, {key: expected})."""
    b = Bucket(str(tmp_path / "b"), memtable_max_entries=10_000)
    want: dict[bytes, bytes | None] = {}
    rng = random.Random(5)
    for layer in range(4):  # 0..2 flushed, 3 stays in the memtable
        for i in rng.sample(range(300), 120):
            key = _K.pack(i)
            if layer and rng.random() < 0.2:
                b.delete(key)
                want[key] = None
            else:
                val = f"L{layer}-{i}-".encode() + b"v" * (i % 300)
                b.put(key, val)
                want[key] = val
        if layer < 3:
            b.flush_memtable()
    assert len(b._segments) == 3 and b._mem
    return b, want


_GET_MANY_CASES = {
    "memtable_hits": lambda b, want: list(b._mem),
    "oldest_segment": lambda b, want: list(b._segments[0].keys()),
    "middle_segment": lambda b, want: list(b._segments[1].keys()),
    "newest_segment": lambda b, want: list(b._segments[2].keys()),
    "misses": lambda b, want: [_K.pack(i) for i in range(300, 330)]
    + [b"", b"short", b"x" * 9],
    "tombstones_over_older_values": lambda b, want: [
        k for k, v in want.items() if v is None],
    "duplicate_keys": lambda b, want: [_K.pack(7), _K.pack(7), _K.pack(299),
                                       _K.pack(7), _K.pack(400)] * 3,
    "any_order": lambda b, want: random.Random(9).sample(
        [_K.pack(i) for i in range(-5, 320)], 325),
    "empty": lambda b, want: [],
}


@pytest.mark.parametrize("case", sorted(_GET_MANY_CASES))
def test_get_many_equals_loop_of_get(tmp_path, case):
    b, want = _layered_bucket(tmp_path)
    keys = _GET_MANY_CASES[case](b, want)
    stats: dict = {}
    got = b.get_many(keys, stats)
    assert got == [b.get(k) for k in keys]
    assert got == [want.get(k) for k in keys]
    # one take of the lock whatever the keys; every key is answered by the
    # memtable, by one record of one segment, or by nothing
    assert stats["lock_takes"] == 1
    assert stats["mem_hits"] == sum(k in b._mem for k in keys)
    assert stats["records_read"] <= len(keys) - stats["mem_hits"]
    # the same after a restart (segments opened from their files) and after
    # the merges have folded the stack
    b.close()
    b2 = Bucket(str(tmp_path / "b"), memtable_max_entries=10_000)
    assert b2.get_many(keys) == got
    b2.compact()
    assert b2.get_many(keys) == got
    b2.close()


def test_get_many_requires_replace(tmp_path):
    b = Bucket(str(tmp_path / "s"), strategy="set")
    with pytest.raises(ValueError):
        b.get_many([b"k"])
    b.close()


_WAL_MODES = {"soft": {}, "sync": {"sync": True},
              "group": {"sync": True, "group": True}}


def _put_many_pairs():
    """Pairs in no key order, with a key given three times, an empty value
    and one past the file object's buffer."""
    rng = random.Random(2)
    pairs = [(_K.pack(rng.randrange(10_000)), rng.randbytes(rng.randrange(400)))
             for _ in range(60)]
    pairs[10] = (pairs[3][0], b"second")
    pairs[40] = (pairs[3][0], b"third and last")
    pairs[20] = (pairs[20][0], b"")
    pairs[30] = (pairs[30][0], rng.randbytes(70_000))
    return pairs


def _wal_bytes(b: Bucket) -> bytes:
    b._wal.flush_soft()
    with open(b._wal.path, "rb") as f:
        return f.read()


@pytest.mark.parametrize("mode", sorted(_WAL_MODES))
def test_put_many_writes_the_bytes_of_n_puts(tmp_path, monkeypatch, mode):
    pairs = _put_many_pairs()
    keys, values = [k for k, _ in pairs], [v for _, v in pairs]
    fsyncs = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync",
                        lambda fd: (fsyncs.append(fd), real_fsync(fd))[1])
    one = Bucket(str(tmp_path / "one"), **_WAL_MODES[mode])
    many = Bucket(str(tmp_path / "many"), **_WAL_MODES[mode])
    many.put(b"before", b"x")  # the batch is appended, not a file of its own
    one.put(b"before", b"x")
    fsyncs.clear()
    for k, v in pairs:
        one.put(k, v)
    puts_fsyncs = len(fsyncs)
    fsyncs.clear()
    writes = many.wal_writes()
    many.put_many(keys, values)
    # one write() for the batch; where every append is fsynced, one fsync
    # after it and before put_many returns (a put each: one a record)
    assert many.wal_writes() - writes == 1
    assert one.wal_writes() == 1 + len(pairs)
    assert (len(fsyncs), puts_fsyncs) == \
        {"soft": (0, 0), "sync": (1, len(pairs)), "group": (0, 0)}[mode]
    assert _wal_bytes(many) == _wal_bytes(one)
    assert many._mem == one._mem
    assert many.get(pairs[3][0]) == b"third and last"  # the last of three
    # the group-commit barrier sees every record of the batch
    state = lambda b: (b._wal._appended, b._wal._synced)  # noqa: E731
    assert state(many) == state(one)
    many.sync_window()
    one.sync_window()
    assert state(many) == state(one)
    assert state(many) == ((61, 61) if mode == "group" else (0, 0))
    # replay: a bucket opened on either file holds the same
    for b in (one, many):
        b._wal.close()
    again = Bucket(str(tmp_path / "many"), **_WAL_MODES[mode])
    assert again._mem == one._mem
    again.close()


@pytest.mark.parametrize("where", ["last_byte", "last_payload",
                                   "last_header", "previous_record"])
def test_put_many_torn_tail_replays_the_whole_records_before_it(tmp_path,
                                                                where):
    """A crash inside the batch's one write() leaves a prefix of it: replay
    recovers exactly the records that are whole, as it does after a torn
    put, and truncates the file there."""
    pairs = [(_K.pack(i), b"v%d-" % i + b"x" * (i * 3)) for i in range(12)]
    b = Bucket(str(tmp_path / "b"))
    b.put_many([k for k, _ in pairs[:4]], [v for _, v in pairs[:4]])
    b.put_many([k for k, _ in pairs[4:]], [v for _, v in pairs[4:]])
    data = _wal_bytes(b)
    b._wal.close()
    ends, off = [], 0  # where each framed record ends
    while off < len(data):
        off += 8 + struct.unpack_from("<I", data, off)[0]
        ends.append(off)
    assert len(ends) == 12 and ends[-1] == len(data)
    last = ends[-1] - ends[-2]
    cut = {"last_byte": 1, "last_payload": last // 2, "last_header": last - 3,
           "previous_record": last + 5}[where]
    whole = 10 if where == "previous_record" else 11
    os.makedirs(tmp_path / "torn")
    with open(tmp_path / "torn" / "wal.log", "wb") as f:
        f.write(data[:-cut])
    torn = Bucket(str(tmp_path / "torn"))
    assert torn._mem == dict(pairs[:whole])
    assert torn.get(pairs[whole][0]) is None
    assert os.path.getsize(tmp_path / "torn" / "wal.log") == ends[whole - 1]
    torn.put(pairs[11][0], b"after")  # and the log goes on from there
    torn._wal.close()
    reopened = Bucket(str(tmp_path / "torn"))
    assert reopened._mem == {**dict(pairs[:whole]), pairs[11][0]: b"after"}
    reopened.close()


def test_put_many_empty_and_strategy(tmp_path):
    b = Bucket(str(tmp_path / "b"))
    b.put_many([], [])
    assert b.wal_writes() == 0 and not b._mem
    b.close()
    s = Bucket(str(tmp_path / "s"), strategy="set")
    with pytest.raises(ValueError):
        s.put_many([b"k"], [b"v"])
    s.close()


def test_put_many_flushes_the_memtable_once_after_the_batch(tmp_path):
    b = Bucket(str(tmp_path / "b"), memtable_max_entries=10)
    b.put_many([_K.pack(i) for i in range(25)], [b"v"] * 25)
    # not a durability point: one segment for the batch, the WAL restarted,
    # the counter of write() calls carried over the rotation
    assert len(b._segments) == 1 and not b._mem
    assert b.wal_writes() == 1
    assert b.get_many([_K.pack(0), _K.pack(24)]) == [b"v", b"v"]
    b.close()


def _parent_write(path: str, items) -> None:
    """The segment writer as it stood before the exact index (PR 31's
    ``DiskSegment.write``, less the reopen): what files in the field hold."""
    sparse, keys, count, last = [], [], 0, None
    with open(path, "wb") as f:
        f.write(segmod.MAGIC)
        off = len(segmod.MAGIC)
        for key, val in items:
            payload = msgpack.packb(val, use_bin_type=True)
            if count % segmod.SPARSE == 0:
                sparse.append((key, off))
            last = (key, off)
            keys.append(key)
            f.write(struct.pack("<II", len(key), len(payload)))
            f.write(key)
            f.write(payload)
            off += 8 + len(key) + len(payload)
            count += 1
        if last is not None and (count - 1) % segmod.SPARSE != 0:
            sparse.append(last)
        index_off = off
        f.write(msgpack.packb([[k, o] for k, o in sparse], use_bin_type=True))
        bloom_off = f.tell()
        f.write(segmod.BloomFilter.build(keys, count).to_bytes())
        f.write(struct.pack("<QQQ", index_off, bloom_off, count))
        f.write(segmod.MAGIC)


def _fixed_items(n=500):
    return [(_K.pack(3 * i), None if i % 11 == 0
             else bytes([i % 251]) * (1 + i * 7 % 400)) for i in range(n)]


def _probe_keys(n=500):
    return [_K.pack(i) for i in range(-2, 3 * n + 2)] + [b"", b"abc"]


def test_segment_file_format_unchanged(tmp_path):
    """Today's writer produces the parent's bytes (digest pinned from the
    parent's tree), and a parent-written file opens and answers the same:
    the exact index is derived state, built from the record headers."""
    items = _fixed_items()
    old, new = str(tmp_path / "old.db"), str(tmp_path / "new.db")
    _parent_write(old, items)
    written = DiskSegment.write(new, items)
    raw = open(new, "rb").read()
    assert raw == open(old, "rb").read()
    assert hashlib.sha256(raw).hexdigest() == _PARENT_SEGMENT_SHA256
    opened = DiskSegment(old)
    for seg in (written, opened):
        assert seg._keys is not None and seg.bloom is None
        assert len(seg._keys) == len(seg._offs) == len(items)
    expect = dict(items)
    keys = _probe_keys()
    vals, read = opened.get_many(keys)
    assert vals == [expect.get(k, MISSING) for k in keys]
    assert read == len(items)  # one record a hit, none for a miss
    assert written.get_many(keys) == (vals, read)
    assert list(opened.items()) == items
    assert list(opened.items(start=_K.pack(700))) == \
        [kv for kv in items if kv[0] >= _K.pack(700)]
    written.close()
    opened.close()


# sha256 of DiskSegment.write(_fixed_items()) at commit 15267b3 (PR 31)
_PARENT_SEGMENT_SHA256 = \
    "75917fa1473893c9edcecc42d4e1bff0ad4dd39f4074c07cdc9a13b46bfbc2a2"


@pytest.mark.skipif(not native.available("segment_merge"),
                    reason="native toolchain unavailable")
def test_native_merge_output_gets_the_exact_index(tmp_path):
    a = [(k, v) for k, v in _fixed_items() if k[-1] % 2]
    bb = [(k, b"newer" if v is not None else b"back") for k, v
          in _fixed_items()[::3]]
    pa, pb, out = (str(tmp_path / n) for n in ("a.db", "b.db", "out.db"))
    _parent_write(pa, a)
    _parent_write(pb, bb)
    assert native_merge([pa, pb], out, "replace", False) is not None
    seg = DiskSegment(out)
    assert seg._keys is not None and seg.bloom is None
    expect = {**dict(a), **dict(bb)}
    keys = _probe_keys()
    vals, read = seg.get_many(keys)
    assert vals == [expect.get(k, MISSING) for k in keys]
    assert read == len(expect)
    seg.close()


def test_mixed_width_segment_takes_the_sparse_path(tmp_path):
    items = sorted({f"t{i}".encode(): {b"m": bytes([i % 256])}
                    for i in range(0, 2000, 3)}.items())
    seg = DiskSegment.write(str(tmp_path / "m.db"), items)
    reopened = DiskSegment(seg.path)
    expect = dict(items)
    keys = [f"t{i}".encode() for i in range(0, 400)] + [b"", b"zz"]
    for s in (seg, reopened):
        assert s._keys is None and s.bloom is not None
        vals, read = s.get_many(keys)
        assert vals == [expect.get(k, MISSING) for k in keys]
        assert [s.get(k) for k in keys] == vals
        assert read > len([v for v in vals if v is not MISSING])  # it steps
        s.close()


def test_index_is_built_at_write_and_open_never_by_reads(tmp_path):
    """Rule (b) of the exact index: complete before the first read, and no
    read changes it (a cache that filled with the traffic would read fast
    for repeated queries only)."""
    items = _fixed_items(200)
    seg = DiskSegment.write(str(tmp_path / "w.db"), items)
    for s in (seg, DiskSegment(seg.path)):
        keys0, offs0, attrs = s._keys, s._offs, set(vars(s))
        assert len(keys0) == 200
        before = (keys0.tobytes(), offs0.tobytes())
        for _ in range(3):
            s.get_many(_probe_keys(200))
        assert s._keys is keys0 and s._offs is offs0
        assert (s._keys.tobytes(), s._offs.tobytes()) == before
        assert set(vars(s)) == attrs    # and nothing was put beside it
        s.close()


def test_truncated_record_area_is_refused(tmp_path):
    """A file whose footer count disagrees with its records is corrupt, and
    the bucket quarantines it like any unreadable segment."""
    d = tmp_path / "b"
    b = Bucket(str(d))
    for i in range(50):
        b.put(_K.pack(i), b"v")
    b.flush_memtable()
    path = b._segments[0].path
    b.close()
    raw = bytearray(open(path, "rb").read())
    foot = len(raw) - 24 - 8
    index_off, bloom_off, count = struct.unpack_from("<QQQ", raw, foot)
    struct.pack_into("<QQQ", raw, foot, index_off, bloom_off, count + 1)
    open(path, "wb").write(raw)
    with pytest.raises(ValueError):
        DiskSegment(path)
    b2 = Bucket(str(d))
    assert not b2._segments and os.path.exists(path + ".corrupt")
    b2.close()


@pytest.mark.timeout(120)
def test_flush_and_compaction_race_multi_get_readers(tmp_path):
    """Readers multi-get while a writer overwrites, flushes and merges: every
    key's answer is one of the values written for it, never torn, never
    lost (count-based: no wall clock)."""
    b = Bucket(str(tmp_path / "race"), memtable_max_entries=10_000)
    nkeys, rounds = 120, 12
    keys = [_K.pack(i) for i in range(nkeys)]
    for k in keys:
        b.put(k, b"r0:" + k)
    errors: list = []
    done = threading.Event()
    reads = [0] * 4

    def reader(slot):
        rng = random.Random(slot)
        while not done.is_set():
            ks = rng.sample(keys, 10) + [_K.pack(10_000)]
            try:
                for k, v in zip(ks, b.get_many(ks)):
                    if k == _K.pack(10_000):
                        ok = v is None
                    else:
                        ok = v is not None and v[v.index(b":") + 1:] == k \
                            and v.startswith(b"r")
                    if not ok:
                        errors.append((k, v))
                reads[slot] += 1
            except Exception as e:  # noqa: BLE001 — surface in main thread
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=reader, args=(s,)) for s in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # many more hand-overs inside the lock
    for th in threads:
        th.start()
    try:
        for r in range(1, rounds + 1):
            for k in keys[r % 3::3]:
                b.put(k, f"r{r}:".encode() + k)
            b.flush_memtable()
            if r % 2 == 0:
                b.compact_once()
            # each round waits for every reader to have read through it
            mark = list(reads)
            while not errors and any(
                    reads[s] <= mark[s] for s in range(4)):
                threading.Event().wait(0.001)
    finally:
        done.set()
        for th in threads:
            th.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert not errors, errors[:5]
    b.close()


@pytest.mark.timeout(120)
def test_multi_get_fetches_see_acknowledged_and_undeleted_objects(tmp_path):
    """8 threads x 10-hit ``objects_by_docids`` against a writer that puts,
    updates, deletes, flushes and merges. By count, no wall clock: an
    object comes back only if its write was acknowledged and its delete
    had not returned when the fetch started; an acknowledged doc id whose
    delete had not begun when the fetch ended always comes back."""
    from weaviate_tpu.core.db import DB
    from weaviate_tpu.schema.config import CollectionConfig
    from weaviate_tpu.storage.objects import StorageObject

    db = DB(str(tmp_path / "db"))
    col = db.create_collection(CollectionConfig(name="Doc"))
    (shard,) = col._search_shards()
    uid = "99000000-0000-0000-0000-{:012d}".format
    table = threading.Lock()
    acked: dict[int, str] = {}      # doc id -> uuid, once put_batch returned
    deleting: set[int] = set()      # doc ids whose delete has begun
    deleted: set[int] = set()       # ... and has returned
    errors: list = []
    done = threading.Event()
    readers, rounds = 8, 10
    fetches = [0] * readers

    def put(numbers):
        objs = [StorageObject(uuid=uid(n), collection="Doc",
                              properties={"n": n}) for n in numbers]
        col.put_batch(objs)
        with table:
            acked.update({o.doc_id: o.uuid for o in objs})

    def reader(slot):
        rng = random.Random(slot)
        while not done.is_set():
            with table:
                ids = rng.choices(list(acked), k=10)
                gone_before = set(deleted)
            try:
                objs = shard.objects_by_docids(ids)
            except Exception as e:  # noqa: BLE001 — surface in main thread
                errors.append(repr(e))
                return
            with table:
                begun_after = set(deleting)
            for d, o in zip(ids, objs):
                if o is None:
                    if d not in begun_after:
                        errors.append(f"acknowledged {d} not returned")
                elif (o.doc_id, o.uuid) != (d, acked[d]) or d in gone_before:
                    errors.append(f"{d}: got {o.doc_id} {o.uuid}")
            fetches[slot] += 1

    put(range(60))
    threads = [threading.Thread(target=reader, args=(s,))
               for s in range(readers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # many more hand-overs inside the lock
    for th in threads:
        th.start()
    try:
        for r in range(rounds):
            put(range(60 + 20 * r, 80 + 20 * r))
            by_uuid = {u: d for d, u in acked.items() if d not in deleting}
            drop = [uid(6 * r + j) for j in range(3)]
            again = [6 * r + 3 + j for j in range(3)]
            with table:
                deleting.update(by_uuid[u] for u in drop)
            assert col.delete(drop) == 3
            with table:
                deleted.update(by_uuid[u] for u in drop)
                # an update writes a new doc id and tombstones the old
                deleting.update(by_uuid[uid(n)] for n in again)
            put(again)
            with table:
                deleted.update(by_uuid[uid(n)] for n in again)
            shard.objects.flush_memtable()
            if r % 3 == 2:
                shard.objects.compact_once()
            mark = list(fetches)
            while not errors and any(
                    fetches[s] <= mark[s] for s in range(readers)):
                threading.Event().wait(0.001)
    finally:
        done.set()
        for th in threads:
            th.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert not errors, errors[:5]
    assert len(shard.objects._segments) >= 2 and min(fetches) >= rounds
    db.close()
