"""A write batch is indexed as a batch: ``InvertedIndex.add_objects`` and
``Shard.put_batch`` leave what one object at a time leaves.

The reference here is the per-object loop as it stood before the batch
form (``_add_object_loop``: every property of an object before the next
object, ``Counter`` term frequencies, one native hand-over a document),
kept so that the column-wise form is held to it and not only to itself
at batch length one.
"""

import os
import random

import numpy as np
import pytest

from weaviate_tpu.core.shard import Shard
from weaviate_tpu.inverted.analyzer import term_frequencies
from weaviate_tpu.inverted.filters import Where
from weaviate_tpu.inverted.index import InvertedIndex
from weaviate_tpu.inverted.native_bm25 import try_native_bm25
from weaviate_tpu.schema.config import (
    CollectionConfig,
    DataType,
    Property,
    Tokenization,
)
from weaviate_tpu.storage.objects import StorageObject
from weaviate_tpu.storage.store import Store

WORDS = ("the quick brown fox and a lazy dog are not in this to be or "
         "vector index shard batch column object search filter").split()


def _add_object_loop(ix: InvertedIndex, obj: StorageObject) -> None:
    """``InvertedIndex.add_object`` of the parent commit, on the index's
    single-row primitives."""
    doc_id = obj.doc_id
    ix.doc_count += 1
    ix.columnar.add(doc_id, {p: v for p, v in obj.properties.items()
                             if v is not None and ix._filterable(p)})
    for prop, val in obj.properties.items():
        if val is None:
            continue
        if ix._filterable(prop):
            ix.values[prop][doc_id] = val
            ix.sketches.add(prop, val)
        if ix._range_indexed(prop) and ix._range_eligible(val):
            if ix._range_counts.get(prop) is not None:
                ix._range_counts[prop] += 1
            ix._range_bucket(prop).put_many([doc_id], [val])
        if isinstance(val, str) or (
                isinstance(val, list) and val and isinstance(val[0], str)):
            if ix._searchable(prop) or ix._prop_schema(prop) is None:
                total = 0
                combined: dict[str, int] = {}
                for t in (val if isinstance(val, list) else [val]):
                    tf = term_frequencies(t, ix._tokenization(prop),
                                          ix.stopwords)
                    total += sum(tf.values())
                    for term, n in tf.items():
                        combined[term] = combined.get(term, 0) + n
                for term, n in combined.items():
                    ix.postings[prop][term].add_new(doc_id, n)
                prev = ix.doc_lengths[prop].set(doc_id, total)
                if prev is not None:
                    ix.len_totals[prop] -= prev
                ix.len_totals[prop] += total
                if ix.native is not None and combined:
                    ix.native.add_docs(prop, [doc_id], [combined], [total])


def _props(*extra):
    return [Property(name="tag", data_type=DataType.TEXT),
            Property(name="tags", data_type=DataType.TEXT_ARRAY),
            Property(name="text", data_type=DataType.TEXT),
            Property(name="code", data_type=DataType.TEXT,
                     tokenization=Tokenization.FIELD, index_searchable=False),
            Property(name="views", data_type=DataType.INT),
            Property(name="price", data_type=DataType.NUMBER),
            Property(name="flag", data_type=DataType.BOOL),
            Property(name="hidden", data_type=DataType.TEXT,
                     index_filterable=False), *extra]


def _passage(rng, n):
    return " ".join(rng.choice(WORDS).capitalize() if rng.random() < 0.2
                    else rng.choice(WORDS) for _ in range(n))


# each case: rng -> the properties of one object
_CASES = {
    # the cells' ingest shapes
    "one_tag_text": lambda rng, i: {"tag": f"r{i}"},
    "text_array_bag": lambda rng, i: {
        "tags": [f"t{rng.randrange(40)}" for _ in range(11)]},
    "word_passage_with_stopwords": lambda rng, i: {
        "text": _passage(rng, rng.randint(5, 60))},
    # the other value shapes
    "numbers": lambda rng, i: {"views": rng.randrange(50),
                               "price": rng.random() * 10},
    "number_lists": lambda rng, i: {
        "views": [rng.randrange(9) for _ in range(rng.randint(1, 3))]},
    "bools": lambda rng, i: {"flag": rng.random() < 0.5},
    "none_values": lambda rng, i: {
        "tag": None if i % 3 else f"r{i}", "views": None if i % 2 else i,
        "text": None},
    "list_of_one": lambda rng, i: {"tags": [f"t{rng.randrange(5)}"]},
    "empty_string_and_empty_list": lambda rng, i: {
        "tag": "" if i % 2 else "x", "tags": [] if i % 3 else ["a", "b"]},
    "unfilterable_and_unsearchable": lambda rng, i: {
        "hidden": _passage(rng, 6), "code": f"AB-{i % 7}"},
    "schema_less_properties": lambda rng, i: {
        "note": _passage(rng, 4), "rank": i % 5, "labels": ["p", f"q{i % 3}"]},
    "differing_property_sets": lambda rng, i: [
        {"tag": f"r{i}", "views": i}, {"text": _passage(rng, 9)},
        {"tags": ["a", f"t{i % 4}"], "flag": True}, {}][i % 4],
    "geo_points": lambda rng, i: {
        "where": {"latitude": 50 + rng.random(), "longitude": 8.0},
        "tag": f"r{i % 9}"},
}


def _objects(case: str, n: int, seed: int = 11) -> list[StorageObject]:
    rng = random.Random(seed)
    return [StorageObject(uuid=f"u{i}", collection="C",
                          properties=_CASES[case](rng, i), doc_id=3 + 2 * i,
                          creation_time_ms=1, update_time_ms=1)
            for i in range(n)]


def _index(engine: str, store=None, extra=()) -> InvertedIndex:
    cfg = CollectionConfig(name="C", properties=_props(*extra))
    if engine == "native":
        ix = InvertedIndex(cfg, store)
        assert ix.native is not None
        return ix
    os.environ["WEAVIATE_TPU_NATIVE_BM25"] = "off"
    try:
        ix = InvertedIndex(cfg, store)
    finally:
        os.environ.pop("WEAVIATE_TPU_NATIVE_BM25")
    assert ix.native is None
    return ix


def _filters(objs) -> list:
    """Equal / ContainsAll / IsNull filters over what the objects hold."""
    out = []
    seen = set()
    for obj in objs[:12]:
        for prop, val in obj.properties.items():
            if prop == "where" or val is None:
                continue
            vals = val if isinstance(val, list) else [val]
            for v in vals[:2]:
                if (prop, repr(v)) not in seen:
                    seen.add((prop, repr(v)))
                    out.append(Where.eq(prop, v))
            if isinstance(val, list) and val:
                out.append(Where.contains_all(prop, vals[:2]))
    for prop in ("tag", "tags", "text", "views", "flag", "note", "hidden"):
        out += [Where.is_null(prop, True), Where.is_null(prop, False)]
    return out


def _queries(objs) -> list[str]:
    texts = []
    for obj in objs[:8]:
        for val in obj.properties.values():
            for v in (val if isinstance(val, list) else [val]):
                if isinstance(v, str) and v:
                    texts.append(v)
    return texts[:8] + ["quick fox", "the and", "r1 r3 t2 a", "nothing-here"]


def _state(ix: InvertedIndex, objs, space: int) -> dict:
    """Everything the write path leaves that a reader can see."""
    return {
        "doc_count": ix.doc_count,
        "values": {p: dict(v) for p, v in ix.values.items()},
        "doc_lengths": {p: (dl.raw[:space].tolist() if len(dl.raw) >= space
                            else dl.raw.tolist() + [0] * (space - len(dl.raw)),
                            len(dl))
                        for p, dl in ix.doc_lengths.items()},
        "len_totals": dict(ix.len_totals),
        "sketches": ix.sketches.to_dict(),
        "sketch_summary": ix.sketches.summary(),
        "postings": {p: {t: tuple(a.tolist() for a in pl.arrays())
                         for t, pl in terms.items()}
                     for p, terms in ix.postings.items()},
        "live": ix.columnar.live_mask(space).tolist(),
        "watermark": ix.columnar._watermark,
        "allow": [ix.allow_list(f, space).tolist() for f in _filters(objs)],
        "range_counts": dict(ix._range_counts),
    }


def _assert_same_answers(a: InvertedIndex, b: InvertedIndex, objs,
                         space: int) -> None:
    sa, sb = _state(a, objs, space), _state(b, objs, space)
    for key in sa:
        assert sa[key] == sb[key], key
    for q in _queries(objs):
        for k in (1, 5, 40):
            ids_a, scores_a = a.bm25_search(q, k)
            ids_b, scores_b = b.bm25_search(q, k)
            # the same postings, lengths and totals in the same order: not
            # close, equal
            assert ids_a.tolist() == ids_b.tolist(), (q, k)
            assert scores_a.tolist() == scores_b.tolist(), (q, k)


ENGINES = [
    pytest.param("native", marks=pytest.mark.skipif(
        try_native_bm25(1.2, 0.75) is None,
        reason="native toolchain unavailable")),
    "python",
]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("case", sorted(_CASES))
def test_add_objects_equals_an_object_at_a_time(case, engine):
    objs = _objects(case, 90)
    space = 3 + 2 * len(objs) + 5
    loop, single, batch, chunks = (_index(engine) for _ in range(4))
    for obj in objs:
        _add_object_loop(loop, obj)
        single.add_object(obj)
    batch.add_objects(objs)
    for i in range(0, len(objs), 32):  # 32, 32, 26: columns grow in between
        chunks.add_objects(objs[i:i + 32])
    for ix in (single, batch, chunks):
        _assert_same_answers(loop, ix, objs, space)
    # the same after a third of the batch is deleted
    for ix in (loop, single, batch, chunks):
        for obj in objs[::3]:
            ix.delete_object(obj)
    for ix in (single, batch, chunks):
        _assert_same_answers(loop, ix, objs, space)


@pytest.mark.parametrize("engine", ENGINES)
def test_batch_of_one_and_empty_batch(engine):
    (obj,) = _objects("differing_property_sets", 1)
    loop, batch = _index(engine), _index(engine)
    _add_object_loop(loop, obj)
    batch.add_objects([])
    assert batch.doc_count == 0 and not batch.columnar.props
    batch.add_objects([obj])
    _assert_same_answers(loop, batch, [obj], 16)


def test_range_indexed_column_goes_to_the_bucket_once_a_batch(tmp_path):
    extra = (Property(name="year", data_type=DataType.INT,
                      index_range_filters=True),)
    rng = random.Random(3)
    objs = [StorageObject(uuid=f"u{i}", collection="C", doc_id=i,
                          properties={"year": True if i == 7 else
                                      None if i % 5 == 0 else
                                      1990 + rng.randrange(30)})
            for i in range(60)]
    loop = _index("python", Store(str(tmp_path / "a")), extra)
    batch = _index("python", Store(str(tmp_path / "b")), extra)
    for obj in objs:
        _add_object_loop(loop, obj)
    with batch.batched_writes():
        batch.add_objects(objs)
    # one put_many of the bit-sliced bucket for the column, not one a doc
    assert 0 < batch.store.wal_writes() * 10 < loop.store.wal_writes()
    for flt in (Where.gte("year", 2005), Where.lt("year", 1995),
                Where.eq("year", 2001), Where.is_null("year")):
        assert batch.allow_list(flt, 64).tolist() == \
            loop.allow_list(flt, 64).tolist()
    _assert_same_answers(loop, batch, objs, 64)
    loop.store.close()
    batch.store.close()


def test_segment_tier_add_objects_is_its_own_loop(tmp_path):
    from weaviate_tpu.inverted.segmented import SegmentedInvertedIndex

    cfg = CollectionConfig(name="C", properties=_props())
    objs = _objects("differing_property_sets", 40)
    one = SegmentedInvertedIndex(cfg, Store(str(tmp_path / "a")))
    many = SegmentedInvertedIndex(cfg, Store(str(tmp_path / "b")))
    for obj in objs:
        one.add_object(obj)
    many.add_objects(objs)
    assert one.doc_count == many.doc_count == len(objs)
    for flt in _filters(objs):
        assert one.allow_list(flt, 96).tolist() == \
            many.allow_list(flt, 96).tolist()
    for q in _queries(objs):
        ids_a, scores_a = one.bm25_search(q, 10)
        ids_b, scores_b = many.bm25_search(q, 10)
        assert ids_a.tolist() == ids_b.tolist()
        np.testing.assert_allclose(scores_a, scores_b, rtol=1e-6)
    one.store.close()
    many.store.close()


# -- the shard: a batch against an object at a time --------------------------

def _shard_objects(rng):
    def make(i, version=0):
        return StorageObject(
            uuid=f"00000000-0000-0000-0000-{i:012d}", collection="C",
            properties={"tag": f"r{i}v{version}",
                        "tags": [f"t{(i + version) % 6}", f"t{i % 4}"],
                        "text": _passage(random.Random(i + 100 * version), 12),
                        "views": i + version},
            vector=rng.standard_normal(8).astype(np.float32),
            creation_time_ms=1000 + i, update_time_ms=2000 + version)

    first = [make(i) for i in range(30)]
    # an update of uuids 3 and 4, uuid 40 twice (the later one wins), uuid
    # 5 updated twice in the batch
    second = [make(3, 1), make(40, 0), make(31), make(5, 1), make(40, 1),
              make(4, 1), make(5, 2), make(32)]
    return first, second


def _shard_answers(shard: Shard) -> dict:
    space = shard._next_doc_id
    by_uuid = {}
    for i in list(range(34)) + [40]:
        obj = shard.get_by_uuid(f"00000000-0000-0000-0000-{i:012d}")
        by_uuid[i] = None if obj is None else obj.to_bytes()
    flts = [Where.eq("tag", "r3v1"), Where.eq("tag", "r3v0"),
            Where.eq("tag", "r40v0"), Where.eq("tag", "r40v1"),
            Where.contains_all("tags", ["t1", "t3"]), Where.gte("views", 20),
            Where.is_null("text", False)]
    return {
        "count": shard.count(),
        "objects": by_uuid,
        "allow": [shard.allow_list(f, space).tolist() for f in flts],
        "bm25": [tuple(a.tolist() for a in
                       shard.inverted.bm25_search(q, 10, doc_space=space))
                 for q in ("quick fox", "r5v2", "r5v1 r40v1", "vector index")],
    }


def test_shard_batch_equals_an_object_at_a_time(tmp_path):
    cfg = CollectionConfig(name="C", properties=_props())
    first, second = _shard_objects(np.random.default_rng(0))
    batched = Shard(str(tmp_path / "batched"), cfg)
    batched.put_batch(first)
    ids = batched.put_batch(second)
    # the occurrences of a repeated uuid report the winner's doc id, and
    # the counter moved by the winners only (8 objects, 6 uuids), in the
    # order the uuids first appear
    assert ids == [30, 31, 32, 33, 31, 34, 33, 35]
    assert batched._next_doc_id == 36
    # an object at a time, a repeated uuid's earlier occurrences would be
    # real writes: write the winners, in the batch's order
    one = Shard(str(tmp_path / "one"), cfg)
    first, second = _shard_objects(np.random.default_rng(0))
    for obj in first + [second[i] for i in (0, 4, 2, 6, 5, 7)]:
        one.put_batch([obj])
    want = _shard_answers(batched)
    assert want["count"] == 33
    assert b"r40v1" in want["objects"][40] and b"r5v2" in want["objects"][5]
    assert sum(want["allow"][0]) == 1 and sum(want["allow"][1]) == 0
    assert sum(want["allow"][2]) == 0 and sum(want["allow"][3]) == 1
    assert _shard_answers(one) == want
    # close and reopen, without and with a checkpoint: the buckets' records
    # replay from their WALs, the index from the delta log or the snapshot
    batched.close()
    one._delta.flush()
    one.store.close()  # no checkpoint: as a killed process leaves it
    for name in ("batched", "one"):
        reopened = Shard(str(tmp_path / name), cfg)
        assert _shard_answers(reopened) == want, name
        reopened.close()
