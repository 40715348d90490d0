"""Filtered nearest-vector search where every query carries its own filter,
held against the benchmark's plain filtered reference
(``benchmark/reference_filtered.py``, nothing of the program) on seeded data
at a small size: the ``yfcc-192-filtered`` deployment's rows, bags of tags
and queries, dims and rows cut. Through ``Collection`` and through the
served gRPC plane; one and two tags; answers shorter than ``k``; a filter
promoted to a plane; concurrent requests with different and with equal
masks, by count and structure (never by wall clock)."""

import json
import threading
import time

import numpy as np
import pytest

from benchmark import harness, reference, reference_filtered
from weaviate_tpu.api.grpc_server import GrpcAPI, GrpcClient
from weaviate_tpu.api.proto import pb
from weaviate_tpu.core.db import DB
from weaviate_tpu.core.shard import DEFAULT_VECTOR
from weaviate_tpu.inverted.filters import Where
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.schema.config import (
    CollectionConfig,
    DataType,
    FlatIndexConfig,
    Property,
    Tokenization,
)
from weaviate_tpu.storage.objects import StorageObject

ROWS, DIMS, VOCABULARY, K, SEED = 4000, 24, 80, 10, 2147483659
DATA = {"kind": "uniform_int", "low": 0, "high": 255, "query_noise": 2,
        "tags": {"draws": 11, "zipf_exponent": 0.75}}
FEW, OWN = VOCABULARY, VOCABULARY + 1   # two tags outside the Zipf law's
FEW_ROWS, OWN_ROW = (5, 17, 3001), 42   # on three rows; on one row
JOIN_S = 30


class Deployment:
    """Seeded rows, bags and queries, and the reference over them."""

    def __init__(self):
        self.corpus = reference.make_rows(DATA, DIMS, ROWS, SEED)
        bags = np.pad(reference_filtered.make_bags(
            DATA["tags"], VOCABULARY, ROWS, SEED), ((0, 0), (0, 1)),
            constant_values=-1)
        bags[list(FEW_ROWS), -1] = FEW
        bags[OWN_ROW, -1] = OWN
        self.bag_rows = bags
        self.bags = reference_filtered.Bags(bags)
        self.queries, self.made_from = reference.make_queries(
            DATA, self.corpus, 64, SEED)
        self.scan = reference.Scan("l2-squared", self.corpus)

    def filters(self, per_query):
        return reference_filtered.make_filters(
            per_query, self.bag_rows, self.made_from, SEED)

    def objects(self, lo=0, hi=ROWS):
        return [StorageObject(
            uuid=harness.row_uuid(i), collection="Yfcc",
            properties={"tags": reference_filtered.bag_texts(
                self.bag_rows[i])},
            vector=self.corpus[i]) for i in range(lo, hi)]

    def numbers(self, filters, answers):
        """``answers``: (query index, [(object, distance), ...])."""
        served = [(qi, np.array([harness.uuid_row(o.uuid) for o, _ in hits],
                                np.int64),
                   np.array([d for _, d in hits], np.float32))
                  for qi, hits in answers]
        return reference_filtered.compare_answers(
            self.scan, self.bags, self.queries, filters, K, served)


def where(tags):
    return Where.contains_all(
        "tags", [reference_filtered.tag_text(t) for t in tags])


COMPARED = ("bad_hits", "filter_violations", "short_answers", "rank_gap",
            "dist_err")


def assert_sound(numbers):
    """Integer data: every product and sum is exact, so sound reads 0."""
    assert [numbers[n] for n in COMPARED] == [0] * len(COMPARED), numbers


@pytest.fixture(scope="module")
def deployment():
    return Deployment()


@pytest.fixture
def served(deployment, tmp_dbdir):
    db = DB(tmp_dbdir)
    col = db.create_collection(CollectionConfig(
        name="Yfcc",
        properties=[Property("tags", DataType.TEXT_ARRAY,
                             tokenization=Tokenization.FIELD)],
        vector_config=FlatIndexConfig(distance="l2-squared")))
    col.put_batch(deployment.objects())
    yield db, col
    db.close()


def test_grouped_bags_are_the_definition(deployment):
    """``Bags.allowed`` (one sort) against "every tag of the query is in
    the row's bag", spelled out."""
    d = deployment
    for tags in d.filters([1]) + d.filters([2]) + [(FEW,), (OWN,), (0, FEW)]:
        spelled = np.flatnonzero(np.all(
            [(d.bag_rows == t).any(axis=1) for t in tags], axis=0))
        assert d.bags.allowed(tags).tolist() == spelled.tolist()
    assert d.bags.allowed((VOCABULARY + 7,)).tolist() == []


@pytest.mark.parametrize("per_query", [[1], [2]], ids=["one_tag", "two_tags"])
def test_collection_answers_as_the_filtered_reference(deployment, served,
                                                      per_query):
    _, col = served
    filters = deployment.filters(per_query)
    assert {len(f) for f in filters} == set(per_query)
    answers = [(qi, col.vector_search(deployment.queries[qi], K,
                                      flt=where(filters[qi])))
               for qi in range(len(filters))]
    numbers = deployment.numbers(filters, answers)
    assert numbers["answers"] == numbers["distinct_queries"] == 64
    assert_sound(numbers)


@pytest.mark.parametrize("tag,rows", [(FEW, FEW_ROWS), (OWN, (OWN_ROW,))],
                         ids=["fewer_than_k", "own_row_only"])
def test_a_filter_that_allows_fewer_than_k_rows(deployment, served, tag,
                                                rows):
    _, col = served
    query = deployment.corpus[rows[0]]
    hits = col.vector_search(query, K, flt=where((tag,)))
    assert sorted(harness.uuid_row(o.uuid) for o, _ in hits) == sorted(rows)
    assert hits[0][0].uuid == harness.row_uuid(rows[0]) and hits[0][1] == 0
    filters = [(tag,)] * len(deployment.queries)
    assert_sound(deployment.numbers(filters, [(0, col.vector_search(
        deployment.queries[0], K, flt=where((tag,))))]))
    # the reference counts what a server gets wrong here: a hit the filter
    # forbids, and an answer cut short
    plain = col.vector_search(deployment.queries[0], K)
    wrong = deployment.numbers(filters, [(0, plain)])
    assert wrong["filter_violations"] >= K - len(rows)
    assert deployment.numbers(filters, [(0, hits[:-1])])["short_answers"] == 1


def test_a_promoted_plane_answers_as_the_inverted_index_did(deployment,
                                                            served):
    _, col = served
    (shard,) = col._search_shards()
    tags = deployment.filters([2])[3]
    TRACER.clear()
    with TRACER.span("client", parent=None):
        answers = [col.vector_search(deployment.queries[3], K,
                                     flt=where(tags)) for _ in range(4)]
    resolved = [s["attributes"] for s in TRACER.recent(limit=200)
                if s["name"] == "filter.resolve"]
    # promoted at its third hit (filter_plane_promote_hits)
    assert [a["source"] for a in resolved] == [
        "inverted", "inverted", "plane", "plane"]
    allowed = len(deployment.bags.allowed(tags))
    assert [(a["allowed"], a["tags"]) for a in resolved] == [(allowed, 2)] * 4
    assert len(shard.filter_planes._planes) == 1
    ids = [[o.uuid for o, _ in hits] for hits in answers]
    assert ids[0] == ids[1] == ids[2] == ids[3]
    assert_sound(deployment.numbers(
        [tags] * 4, [(3, hits) for hits in answers]))


class _Gate:
    """Holds the index's FIRST batch until the test opens the gate, so what
    queues behind it is decided by the test; records each batch's rows and
    how many masks it was handed."""

    def __init__(self, col):
        (shard,) = col._search_shards()
        self.dispatcher = shard._vector_indexes[DEFAULT_VECTOR]._dispatcher
        self.open, self.rows, self.masks = threading.Event(), [], []
        self.real = self.dispatcher.run_batch
        self.dispatcher.run_batch = self

    def __call__(self, q, k, masks, tier_key, rows):
        assert self.open.wait(JOIN_S)
        assert masks is not None and len(masks) == len(rows)
        self.rows.append(q.shape[0])
        self.masks.append(len({id(m) for m in masks}))
        return self.real(q, k, masks, tier_key=tier_key, rows=rows)

    def wait(self, what):
        deadline = time.monotonic() + JOIN_S
        while not what(self.dispatcher):
            assert time.monotonic() < deadline
            time.sleep(0.001)


@pytest.mark.parametrize("same_mask", [False, True],
                         ids=["eight_masks", "one_mask"])
def test_concurrent_requests_share_a_scan_whatever_their_masks(
        deployment, served, same_mask):
    """Eight requests queued behind a held batch share ONE scan, a mask a
    row, and each gets its own mask's answer: with eight different filters
    as with one (equal content, an array a request)."""
    _, col = served
    filters = deployment.filters([1])
    if same_mask:
        chosen = [0] * 9
    else:
        chosen, seen = [], set()
        for qi, f in enumerate(filters):     # nine different filters
            if f not in seen:
                seen.add(f)
                chosen.append(qi)
        chosen = chosen[:9]
        assert len(chosen) == 9
    col.vector_search(deployment.queries[0], K, flt=where((FEW,)))  # compile
    gate = _Gate(col)
    got, errs = {}, []

    def client(slot):
        try:
            qi = chosen[slot]
            got[slot] = col.vector_search(
                deployment.queries[slot], K, flt=where(filters[qi]))
        except BaseException as e:  # re-raised below
            errs.append(e)

    threads = [threading.Thread(target=client, args=(0,))]
    threads[0].start()
    gate.wait(lambda d: d._draining)
    for slot in range(1, 9):
        threads.append(threading.Thread(target=client, args=(slot,)))
        threads[-1].start()
    gate.wait(lambda d: len(d._pending) >= 8)
    gate.open.set()
    for t in threads:
        t.join(JOIN_S)
        assert not t.is_alive()
    assert not errs, errs
    assert gate.rows == [1, 8]
    # one filter sent nine times is promoted to a plane on the way: some
    # members then carry the plane's one bitmap, the others an array each
    assert gate.masks == [1, 8] or (same_mask and 1 <= gate.masks[1] <= 8)
    # slot s sent query s under filter chosen[s]: held to exactly that pair
    pair_filters = [filters[chosen[s]] if s < 9 else ()
                    for s in range(len(deployment.queries))]
    assert_sound(deployment.numbers(
        pair_filters, [(s, got[s]) for s in range(9)]))


def test_the_served_plane_answers_as_the_filtered_reference(deployment,
                                                            served):
    """gRPC ``BatchObjects`` with ``tags`` and ``Search`` with
    ``where_json``, as the benchmark's cell sends them."""
    db, _ = served
    api = GrpcAPI(db)
    client = GrpcClient(f"127.0.0.1:{api.serve(port=0)}")
    try:
        filters = deployment.filters([1, 2])
        filters[0], filters[1] = (FEW,), (OWN,)
        served_answers = []
        for qi, tags in enumerate(filters):
            reply = client.search(pb.SearchRequest(
                collection="Yfcc", limit=K,
                near_vectors=[pb.Vector(
                    values=deployment.queries[qi].tolist())],
                where_json=json.dumps({
                    "operator": "ContainsAll", "path": ["tags"],
                    "valueText": [reference_filtered.tag_text(t)
                                  for t in tags]})))
            (ids, dists), = harness.parse_search_reply(reply)
            served_answers.append((qi, ids, dists))
        numbers = reference_filtered.compare_answers(
            deployment.scan, deployment.bags, deployment.queries, filters, K,
            served_answers)
        assert_sound(numbers)
        assert [len(a[1]) for a in served_answers[:2]] == [3, 1]
        assert {len(f) for f in filters} == {1, 2}
    finally:
        client.close()
        api.shutdown()
