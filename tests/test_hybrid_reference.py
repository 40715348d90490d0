"""The served hybrid path against the plain reference of the benchmark
(``benchmark/reference_hybrid.py``, imported, not copied): a hybrid
``Search`` over gRPC through ``GrpcAPI`` on 2,000 seeded passages x 64-d is
held to the reference's fused scores for ``alpha`` 0.75 and its two ends, for
both fusion names, with ties at the sparse leg's cut-off present in the data;
the reference with its dense leg in int8, and a dense leg fused alone, both
fail the cell's limits; and the reference's BM25 is the published formula,
term by term."""

import json
import math
import os

import numpy as np
import pytest

from benchmark import reference, reference_hybrid
from weaviate_tpu.api.grpc_server import GrpcAPI, GrpcClient
from weaviate_tpu.api.proto import pb
from weaviate_tpu.core.db import DB
from weaviate_tpu.schema.config import (
    CollectionConfig,
    DataType,
    FlatIndexConfig,
    Property,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, DIMS, QUERIES, K, DEPTH, SEED = 2000, 64, 200, 10, 20, 2147484001
DATA = {"kind": "normal", "query_noise": 0.1}
TEXT = {"vocabulary": 20000, "zipf_exponent": 1.0, "query_words": [3, 9],
        "length": {"mean": 56, "sigma": 0.4, "min": 20, "max": 150}}
# rankedFusion's scores are ranks: exact but for a swap of two near-equal
# leg scores, which the reference counts as a tie (TIE_EPS)
RANKED_LIMIT = 1e-6


def _limits() -> dict:
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "msmarco768.hybrid_c20.json")) as f:
        return {n: v for n, v in json.load(f)["limits"].items()
                if n != "unanswered"}


def _uuid(i: int) -> str:
    return f"{i:08x}-0000-4000-8000-{i:012x}"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    corpus = reference.make_rows(DATA, DIMS, ROWS, SEED)
    passages = reference_hybrid.make_passages(TEXT, ROWS, SEED)
    texts = reference_hybrid.passage_texts(TEXT, passages)
    queries, made_from = reference.make_queries(DATA, corpus, QUERIES, SEED)
    query_texts = reference_hybrid.make_query_texts(
        TEXT, passages, made_from, SEED)
    db = DB(str(tmp_path_factory.mktemp("hybrid_reference")))
    db.create_collection(CollectionConfig(
        name="Passages",
        properties=[Property(name="passage", data_type=DataType.TEXT)],
        vector_config=FlatIndexConfig(distance="cosine")))
    api = GrpcAPI(db)
    client = GrpcClient(f"127.0.0.1:{api.serve(port=0)}")
    for lo in range(0, ROWS, 500):
        reply = client.batch_objects(pb.BatchObjectsRequest(objects=[
            pb.BatchObject(
                uuid=_uuid(i), collection="Passages",
                properties_json=json.dumps({"passage": texts[i]}),
                vector=pb.Vector(values=corpus[i].tolist()))
            for i in range(lo, lo + 500)]))
        assert not reply.errors
    yield {"client": client, "corpus": corpus, "texts": texts,
           "queries": queries, "query_texts": query_texts,
           "bm25": reference_hybrid.Bm25(texts, 1.2, 0.75),
           "scan": reference.Scan("cosine", corpus)}
    client.close()
    api.shutdown()
    db.close()


def _answers(s, alpha, fusion):
    out = []
    for qi in range(QUERIES):
        req = pb.SearchRequest(
            collection="Passages", limit=K, use_hybrid=True,
            bm25_query=s["query_texts"][qi],
            near_vectors=[pb.Vector(values=s["queries"][qi].tolist())])
        if (alpha, fusion) != (0.75, "relativeScoreFusion"):
            req.alpha, req.fusion = alpha, fusion   # else: the defaults
        (result,) = s["client"].search(req).results
        out.append((qi, np.array([int(h.uuid[:8], 16) for h in result.hits]),
                    np.array([h.score for h in result.hits], np.float32)))
    return out


def _hybrid(s, alpha, fusion):
    return reference_hybrid.Hybrid(s["scan"], s["bm25"], alpha, fusion, DEPTH)


@pytest.mark.parametrize("fusion", ["relativeScoreFusion", "rankedFusion"])
@pytest.mark.parametrize("alpha", [0.75, 0.0, 1.0])
def test_served_hybrid_answers_agree_with_the_plain_reference(
        served, alpha, fusion):
    hybrid = _hybrid(served, alpha, fusion)
    numbers = reference_hybrid.compare_answers(
        hybrid, served["queries"], served["query_texts"], K,
        _answers(served, alpha, fusion))
    limits = _limits()
    if fusion == "rankedFusion":
        limits.update(score_gap=RANKED_LIMIT, score_err=RANKED_LIMIT)
    ok, compared = reference.verdict(numbers, limits)
    assert ok, compared
    assert numbers["answers"] == numbers["distinct_queries"] == QUERIES
    if alpha < 1.0:     # passages beyond the leg score as its last does
        assert numbers["cutoff_ties"] > 0


@pytest.mark.parametrize("control,number", [("int8", "score_err"),
                                            ("dense_only", "score_err")])
def test_a_lower_precision_and_a_shed_leg_fail_the_cells_limits(
        served, control, number):
    hybrid = _hybrid(served, 0.75, "relativeScoreFusion")
    answers = reference_hybrid.control_answers(
        hybrid, served["corpus"], served["queries"], served["query_texts"],
        K, list(range(QUERIES)), control)
    numbers = reference_hybrid.compare_answers(
        hybrid, served["queries"], served["query_texts"], K, answers)
    ok, compared = reference.verdict(numbers, _limits())
    assert not ok, compared
    assert compared[number]["value"] > compared[number]["limit"]
    assert numbers["bad_hits"] == numbers["short_answers"] == 0


def test_the_reference_put_in_its_own_place_reads_zero(served):
    hybrid = _hybrid(served, 0.75, "relativeScoreFusion")
    used = list(range(QUERIES))
    dense = hybrid.dense_legs(served["queries"])
    answers = [(qi, *reference_hybrid.Query(
        hybrid, served["queries"][qi], served["query_texts"][qi],
        dense[qi]).serve(K)) for qi in used]
    numbers = reference_hybrid.compare_answers(
        hybrid, served["queries"], served["query_texts"], K, answers)
    assert numbers["bad_hits"] == numbers["short_answers"] == 0
    assert numbers["score_gap"] < 1e-9 and numbers["score_err"] < 1e-6


def test_the_references_bm25_is_the_published_formula(served):
    bm25, texts = served["bm25"], served["texts"]
    docs = [reference_hybrid.tokenize(t) for t in texts]
    avgdl = sum(map(len, docs)) / len(docs)
    for qi in (0, 7, 42):
        want = np.zeros(len(docs))
        for term in set(reference_hybrid.tokenize(served["query_texts"][qi])):
            n = sum(1 for d in docs if term in d)
            if not n:
                continue
            idf = math.log(1 + (len(docs) - n + 0.5) / (n + 0.5))
            for i, d in enumerate(docs):
                tf = d.count(term)
                want[i] += idf * tf * 2.2 / (
                    tf + 1.2 * (0.25 + 0.75 * len(d) / avgdl))
        np.testing.assert_allclose(
            bm25.scores(served["query_texts"][qi]), want, rtol=1e-12)
    assert "the" not in bm25.term_ids and len(
        reference_hybrid.STOPWORDS_EN) == 33
