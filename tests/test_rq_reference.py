"""A flat collection under 8-bit rotational quantization, held to the plain
reference of the benchmark (``benchmark/reference_rq.py``, imported, not
copied) at a small size on the CPU: 4,096 x 768 of the ``cohere-768-rq``
configuration's clustered rows (clusters of its ``cluster_rows``, so the
ten best of a query lie as close together as in the cell), k = 10.

``QuantizedFlatIndex`` driven in-process, and the same collection created
over REST with the configuration's own ``collection`` block, loaded by gRPC
``BatchObjects`` and searched by gRPC ``Search``, both return the plain
reference's ten ids (the 40 best in code space, rescored exactly), each with
its exact float32 distance. The two faulty forms of the cell's controls
(``benchmark/tests/faulty_rq_serve.py``: no rescore; k candidates) fail the
cell's limits, and a traced search leaves the tier's spans."""

import json
import os
import urllib.request

import numpy as np
import pytest

from benchmark import reference, reference_rq
from benchmark.tests import faulty_rq_serve
from weaviate_tpu.api.grpc_server import GrpcAPI, GrpcClient
from weaviate_tpu.api.proto import pb
from weaviate_tpu.api.rest import RestAPI
from weaviate_tpu.core.db import DB
from weaviate_tpu.index.flat import QuantizedFlatIndex, make_flat
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.schema.config import FlatIndexConfig, RQConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, QUERIES, K, SEED = 4096, 64, 10, 2147484007


def _json(*parts: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CFG = _json("configs", "cohere-768-rq.json")
LIMITS = _json("workloads", "cohere768.rq_c20.json")["limits"]
DIMS, CLASS = CFG["dims"], CFG["collection"]["class"]


def _uuid(i: int) -> str:
    return f"{i:08x}-0000-4000-8000-{i:012x}"


def _post(base: str, path: str, body: dict):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read() or b"null")


def _held(ref, queries, answers) -> dict:
    """The cell's numbers for ``answers`` of every query, all sampled."""
    numbers = reference_rq.compare_answers(
        ref, queries, K, answers, list(range(len(queries))))
    numbers["unanswered"] = 0
    return numbers


@pytest.fixture(scope="module")
def data():
    corpus = reference_rq.make_rows(CFG["data"], DIMS, ROWS, SEED)
    queries, _ = reference_rq.make_queries(CFG["data"], corpus, QUERIES,
                                           SEED)
    ref = reference_rq.RQ(corpus, reference_rq.layout(CFG))
    return {"corpus": corpus, "queries": queries, "ref": ref,
            "top": ref.answer(queries, K, reference_rq.fetch_count(
                ref.layout, K))}


@pytest.fixture(scope="module")
def index(data):
    rq = CFG["collection"]["vectorIndexConfig"]["rq"]
    idx = make_flat(DIMS, FlatIndexConfig(
        distance="cosine", quantizer=RQConfig(
            bits=rq["bits"], rescore_limit=rq["rescoreLimit"])))
    assert isinstance(idx, QuantizedFlatIndex)
    idx.add_batch(np.arange(ROWS), data["corpus"])
    return idx


@pytest.fixture(scope="module")
def served(tmp_path_factory, data):
    db = DB(str(tmp_path_factory.mktemp("rq_served")))
    rest = RestAPI(db)
    srv = rest.serve(host="127.0.0.1", port=0, background=True)
    col = CFG["collection"]
    _post(f"http://127.0.0.1:{srv.server_port}", "/v1/schema", {
        key: col[key] for key in ("class", "vectorizer", "vectorIndexType",
                                  "vectorIndexConfig", "properties")})
    api = GrpcAPI(db)
    client = GrpcClient(f"127.0.0.1:{api.serve(port=0)}")
    for lo in range(0, ROWS, 1024):
        reply = client.batch_objects(pb.BatchObjectsRequest(objects=[
            pb.BatchObject(uuid=_uuid(i), collection=CLASS,
                           properties_json=json.dumps({"tag": f"r{i}"}),
                           vector=pb.Vector(values=data["corpus"][i]))
            for i in range(lo, lo + 1024)]))
        assert not reply.errors and len(reply.uuids) == 1024
    yield {"db": db, "client": client}
    client.close()
    api.shutdown()
    rest.shutdown()
    db.close()


def _search(client, q: np.ndarray):
    (result,) = client.search(pb.SearchRequest(
        collection=CLASS, limit=K, near_vectors=[pb.Vector(values=q)])).results
    return (np.array([int(h.uuid[:8], 16) for h in result.hits]),
            np.array([h.distance for h in result.hits], np.float32))


def _assert_reference_answers(data, answers):
    top_d, top_i = data["top"]
    for qi, ids, dists in answers:
        assert ids.tolist() == top_i[qi].tolist(), qi
        assert np.abs(dists - top_d[qi]).max() <= 1e-6, qi
    ok, compared = reference.verdict(
        _held(data["ref"], data["queries"], answers), LIMITS)
    assert ok, compared
    assert compared["recall_miss"]["value"] == 0.0
    assert compared["dist_err"]["value"] <= 1e-6


def test_the_index_returns_the_plain_references_ten(data, index):
    res = index.search(data["queries"], K)
    _assert_reference_answers(data, [
        (qi, res.ids[qi], res.dists[qi]) for qi in range(QUERIES)])
    assert index.stats()["quantizer"] == "rq"


def test_the_served_path_returns_the_plain_references_ten(data, served):
    cfg = served["db"].get_collection(CLASS).config.vector_config
    rq = CFG["collection"]["vectorIndexConfig"]["rq"]
    assert (cfg.index_type, cfg.distance, cfg.quantizer.kind,
            cfg.quantizer.bits, cfg.quantizer.rescore_limit) == (
        "flat", "cosine", "rq", rq["bits"], rq["rescoreLimit"])
    _assert_reference_answers(data, [
        (qi, *_search(served["client"], q))
        for qi, q in enumerate(data["queries"])])


@pytest.mark.parametrize("fault,number", [
    ("skip_rescore", "dist_err"), ("few_candidates", "exact_recall_miss")])
def test_the_faulty_forms_fail_the_cells_limits(data, index, fault, number):
    dists, ids = faulty_rq_serve.FAULTS[fault](
        index.backend, data["queries"], K, None)
    answers = [(qi, ids[qi], dists[qi]) for qi in range(QUERIES)]
    ok, compared = reference.verdict(
        _held(data["ref"], data["queries"], answers), LIMITS)
    assert not ok
    assert compared[number]["value"] > 2 * compared[number]["limit"]
    assert compared["bad_hits"]["value"] == 0


def test_a_lower_precision_rescore_fails_the_cells_limits(data):
    used = list(range(QUERIES))
    for arithmetic in ("bf16", "int8"):
        control = reference_rq.control_answers(
            data["ref"], data["queries"], K, used, arithmetic)
        ok, compared = reference.verdict(
            _held(data["ref"], data["queries"], control), LIMITS)
        assert not ok and compared["dist_err"]["value"] > 5 * 1e-5


def test_a_traced_search_leaves_the_tiers_spans(data, served):
    TRACER.clear()
    _search(served["client"], data["queries"][0])
    (trace,) = [t for t in TRACER.traces(50) if t["root"] == "grpc.Search"
                and any(s["name"] == "qflat.scan" for s in t["spans"])]
    spans = {s["name"]: s for s in trace["spans"]}
    assert {"index.search", "qflat.prepare", "qflat.scan", "qflat.rescore",
            "objects.fetch"} <= set(spans)
    assert all(spans[n]["attributes"]["quantizer"] == "rq"
               for n in ("qflat.prepare", "qflat.scan", "qflat.rescore"))
    assert spans["qflat.scan"]["attributes"]["fetch"] == 4 * K
    assert spans["qflat.scan"]["attributes"]["capacity"] == ROWS
    assert spans["qflat.rescore"]["attributes"]["candidates"] == 4 * K


def test_the_scan_cost_is_the_stated_layout():
    from benchmark import costs, costs_rq, peaks

    cap = CFG["rq_layout"]["capacity"]
    assert costs.store_capacity(CFG["rows"]) == cap == 262144
    flops, nbytes = costs_rq.scan(3, 1, cap, DIMS, resident_bytes=0)
    # codes (1 B a dimension) + lower + step (4 B each) a row, each scan
    assert nbytes == 3 * 262144 * (768 + 8) == 3 * 203_423_744
    assert flops == 3 * 2.0 * 262144 * 768
    least, binds = costs.least_seconds(
        flops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert binds == "bytes" and abs(least - 3 * 203_423_744 / 819e9) < 1e-12
