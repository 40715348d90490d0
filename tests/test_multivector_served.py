"""Late-interaction search as it is served, at a small size on the CPU: a
multi-vector collection created over REST with the block the benchmark's
configuration posts, 300 seeded passages of 8-24 tokens x 16-d imported as
token sets by gRPC ``BatchObjects``, queried by gRPC ``Search`` with one
token set a request, and held to the plain reference of the benchmark
(``benchmark/reference_multivector.py``, imported, not copied). With
``rescoreLimit`` over the row count the promise "the 10 best by exact MaxSim
among the candidates" is the exact top 10, so the answer must equal the
reference's and the scores agree within the bf16 arithmetic; the same MaxSim
in int8 fails the cell's limits. Also: a token set survives a restart bit
for bit, ragged batches pass ``Shard.put_batch``, the batched FDE encode is
the per-passage one, and the token planes are fed by row."""

import json
import os
import urllib.request

import numpy as np
import pytest

from benchmark import reference, reference_multivector
from weaviate_tpu.api.grpc_server import GrpcAPI, GrpcClient
from weaviate_tpu.api.proto import pb
from weaviate_tpu.api.rest import RestAPI
from weaviate_tpu.api.schema_translate import class_from_rest, class_to_rest
from weaviate_tpu.core.db import DB
from weaviate_tpu.index.multivector import MuveraEncoder
from weaviate_tpu.modules.device.store import (
    TOKEN_DTYPE,
    CandidateTokenStore,
)
from weaviate_tpu.monitoring.tracing import TRACER
from weaviate_tpu.storage.objects import StorageObject

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, DIMS, QUERIES, TQ, K, SEED = 300, 16, 40, 8, 10, 2147484001
CLASS = {
    "class": "Passages", "vectorizer": "none",
    "vectorIndexType": "multivector",
    "vectorIndexConfig": {
        "distance": "dot",
        "multivector": {"enabled": True, "muvera": {
            "enabled": True, "ksim": 3, "dprojections": 8,
            "repetitions": 6}},
        "rescoreLimit": 512,
        "rerank": {"module": "rerank-maxsim", "max_tokens": 24}},
    "properties": [{"name": "passage", "dataType": ["text"]}],
}


def _limits() -> dict:
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "msmarco128.multivector_c20.json")) as f:
        limits = json.load(f)["limits"]
    # every passage is a candidate here: the selection is exact
    return {**limits, "rank_gap": 1e-5, "recall_miss": 0.0}


def _uuid(i: int) -> str:
    return f"{i:08x}-0000-4000-8000-{i:012x}"


def _token_sets(rng, rows: int):
    """Unit tokens around one centre a passage, so that a query made from a
    passage has neighbours; (flat tokens, offsets)."""
    counts = rng.integers(8, 25, rows)
    centres = rng.standard_normal((rows, DIMS)).astype(np.float32)
    flat = reference.unit(
        np.repeat(centres, counts, axis=0) + 0.8 * rng.standard_normal(
            (int(counts.sum()), DIMS)).astype(np.float32))
    return flat, np.concatenate(([0], np.cumsum(counts)))


def _pb_set(tokens: np.ndarray) -> pb.Vector:
    return pb.Vector(token_bytes=tokens.astype("<f4").tobytes(),
                     token_dims=tokens.shape[1])


def _post(base: str, path: str, body: dict):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read() or b"null")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    rng = np.random.default_rng(SEED)
    tokens, offsets = _token_sets(rng, ROWS)
    source = rng.choice(ROWS, QUERIES, replace=False)
    queries = np.stack([reference.unit(
        tokens[offsets[r]:offsets[r] + TQ] + 0.2 * rng.standard_normal(
            (TQ, DIMS)).astype(np.float32)) for r in source])
    path = str(tmp_path_factory.mktemp("multivector_served"))
    db = DB(path)
    rest = RestAPI(db)
    srv = rest.serve(host="127.0.0.1", port=0, background=True)
    _post(f"http://127.0.0.1:{srv.server_port}", "/v1/schema", CLASS)
    api = GrpcAPI(db)
    client = GrpcClient(f"127.0.0.1:{api.serve(port=0)}")
    for lo in range(0, ROWS, 100):
        reply = client.batch_objects(pb.BatchObjectsRequest(objects=[
            pb.BatchObject(
                uuid=_uuid(i), collection="Passages",
                properties_json=json.dumps({"passage": f"passage {i}"}),
                vector=_pb_set(tokens[offsets[i]:offsets[i + 1]]))
            for i in range(lo, lo + 100)]))
        assert not reply.errors and len(reply.uuids) == 100
    state = {"db": db, "path": path, "client": client, "tokens": tokens,
             "offsets": offsets, "queries": queries,
             "reference": reference_multivector.MaxSim(tokens, offsets)}
    yield state
    client.close()
    api.shutdown()
    rest.shutdown()
    state["db"].close()


def _answers(s, include_vector: bool = False):
    out = []
    for qi, q in enumerate(s["queries"]):
        (result,) = s["client"].search(pb.SearchRequest(
            collection="Passages", limit=K, near_vectors=[_pb_set(q)],
            include_vector=include_vector)).results
        out.append((qi, np.array([int(h.uuid[:8], 16) for h in result.hits]),
                    -np.array([h.distance for h in result.hits], np.float32)))
    return out


def test_the_rest_block_reaches_the_index(served):
    cfg = served["db"].get_collection("Passages").config.vector_config
    assert (cfg.index_type, cfg.ksim, cfg.dproj, cfg.repetitions,
            cfg.rescore_limit) == ("multivector", 3, 8, 6, 512)
    assert (cfg.rerank.module, cfg.rerank.max_tokens) == ("rerank-maxsim", 24)
    back = class_to_rest(served["db"].get_collection("Passages").config)
    again = class_from_rest(back).vector_config
    assert again.to_dict() == cfg.to_dict()


def test_served_answers_are_the_exact_top_ten(served):
    answers = _answers(served)
    numbers = reference_multivector.compare_answers(
        served["reference"], served["queries"], K, answers,
        list(range(QUERIES)))
    numbers["unanswered"] = numbers["rerank_fallbacks"] = 0
    ok, compared = reference.verdict(numbers, _limits())
    assert ok, compared
    assert numbers["recall_at_10"] == 1.0
    assert numbers["answers"] == numbers["sampled_queries"] == QUERIES
    _, top_i = served["reference"].topk(served["queries"], K)
    assert all((ids == top_i[qi]).all() for qi, ids, _ in answers)


def test_the_same_maxsim_in_int8_fails_the_cells_limits(served):
    sampled = list(range(QUERIES))
    control = reference_multivector.control_answers(
        served["tokens"], served["offsets"], served["queries"], K, sampled)
    numbers = reference_multivector.compare_answers(
        served["reference"], served["queries"], K, control, sampled)
    numbers["unanswered"] = numbers["rerank_fallbacks"] = 0
    ok, compared = reference.verdict(numbers, _limits())
    assert not ok
    assert compared["score_err"]["value"] > 10 * compared["score_err"]["limit"]
    assert numbers["bad_hits"] == numbers["short_answers"] == 0


def test_a_fault_in_the_served_list_shows_in_its_own_number(served):
    ref, queries = served["reference"], served["queries"]
    (qi, ids, scores), = _answers({**served, "queries": queries[:1]})
    swapped = (qi, ids[::-1].copy(), scores[::-1].copy())
    worst = np.argsort(ref.scores(queries[:1])[0])[:K]
    far = (qi, worst, ref.pair_scores(queries[0], worst))
    short = (qi, ids[:4], scores[:4])
    twice = (qi, np.r_[ids[:9], ids[0]], np.r_[scores[:9], scores[0]])
    read = {name: reference_multivector.compare_answers(
        ref, queries, K, [answer], [0])
        for name, answer in (("swapped", swapped), ("far", far),
                             ("short", short), ("twice", twice))}
    assert read["swapped"]["order_gap"] > 0.01
    assert read["far"]["rank_gap"] > 0.1 and read["far"]["recall_miss"] == 1
    assert read["short"]["short_answers"] == 1
    assert read["twice"]["bad_hits"] == 1


def test_a_hit_carries_its_token_set_back(served):
    (result,) = served["client"].search(pb.SearchRequest(
        collection="Passages", limit=1, include_vector=True,
        near_vectors=[_pb_set(served["queries"][0])])).results
    hit = result.hits[0]
    row = int(hit.uuid[:8], 16)
    got = np.frombuffer(hit.vector.token_bytes, "<f4").reshape(
        -1, hit.vector.token_dims)
    want = served["tokens"][served["offsets"][row]:served["offsets"][row + 1]]
    assert hit.vector.token_dims == DIMS and (got == want).all()


def test_a_search_leaves_the_multi_vector_spans(served):
    TRACER.clear()
    served["client"].search(pb.SearchRequest(
        collection="Passages", limit=K,
        near_vectors=[_pb_set(served["queries"][0])]))
    (trace,) = [t for t in TRACER.traces(50) if t["root"] == "grpc.Search"]
    spans = {s["name"]: s for s in trace["spans"]}
    assert spans["grpc.Search"]["attributes"]["query_tokens"] == TQ
    enc = spans["mv.encode_query"]["attributes"]
    assert (enc["tokens"], enc["fde_dim"]) == (TQ, 6 * 8 * 8)
    search = spans["mv.search"]["attributes"]
    assert search["tier"] == "fused" and search["tokens"] == TQ
    assert search["candidates"] == 512 and search["k"] == 16
    assert "mv.result" in spans and "objects.fetch" in spans


def test_two_token_sets_in_one_request_are_refused(served):
    import grpc

    with pytest.raises(grpc.RpcError) as e:
        served["client"].search(pb.SearchRequest(
            collection="Passages", limit=K,
            near_vectors=[_pb_set(q) for q in served["queries"][:2]]))
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    with pytest.raises(grpc.RpcError) as e:
        served["client"].search(pb.SearchRequest(
            collection="Passages", limit=K, near_vectors=[pb.Vector(
                token_bytes=b"\0" * 36, token_dims=DIMS)]))
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_a_token_set_survives_a_restart_bit_for_bit(served):
    served["db"].close()
    served["db"] = db = DB(served["path"])
    # the running servers go on with the reopened database
    col = db.get_collection("Passages")
    for row in (0, 137, ROWS - 1):
        obj = col.get(_uuid(row))
        want = served["tokens"][
            served["offsets"][row]:served["offsets"][row + 1]]
        assert obj.vector.dtype == np.float32
        assert obj.vector.shape == want.shape and (obj.vector == want).all()
    q = served["queries"][3]
    hits = col.vector_search(q, k=K)
    want_s, want_i = served["reference"].topk(q[None], K)
    assert [int(o.uuid[:8], 16) for o, _ in hits] == want_i[0].tolist()
    assert np.allclose([-d for _, d in hits], want_s[0], atol=1e-4)


def test_ragged_batches_pass_put_batch(tmp_path):
    db = DB(str(tmp_path))
    col = db.create_collection(class_from_rest(CLASS))
    rng = np.random.default_rng(3)
    tokens, offsets = _token_sets(rng, 40)
    sets = [tokens[offsets[i]:offsets[i + 1]] for i in range(40)]
    for lo in (0, 20):      # 8..24 tokens, no two batches alike
        col.put_batch([StorageObject(
            uuid=_uuid(i), collection="Passages", properties={},
            vector=sets[i]) for i in range(lo, lo + 20)])
    with pytest.raises(ValueError, match="dims"):
        col.put_batch([StorageObject(
            uuid=_uuid(99), collection="Passages", properties={},
            vector=np.zeros((3, DIMS + 1), np.float32))])
    ref = reference_multivector.MaxSim(tokens, offsets)
    for row in (5, 31):
        hits = col.vector_search(sets[row], k=3)
        _, want = ref.topk(sets[row][None], 3)
        assert [int(o.uuid[:8], 16) for o, _ in hits] == want[0].tolist()
    db.close()


def _encode_doc_by_the_book(enc: MuveraEncoder, tokens: np.ndarray):
    """MUVERA's document encoding a passage, a repetition and a bucket at a
    time: what ``encode_docs`` batches."""
    out = np.zeros((enc.repetitions, enc.buckets, enc.dims), np.float32)
    for r in range(enc.repetitions):
        bits = (enc.gaussians[r] @ tokens.T) < 0            # [ksim, T]
        ids = (bits * (1 << np.arange(enc.ksim))[:, None]).sum(axis=0)
        for b in range(enc.buckets):
            mine = tokens[ids == b]
            if len(mine):
                out[r, b] = mine.mean(axis=0)
            else:
                ham = [bin(b ^ int(i)).count("1") for i in ids]
                out[r, b] = tokens[int(np.argmin(ham))]
    return np.einsum("rbd,rdp->rbp", out, enc.proj).reshape(-1)


@pytest.mark.parametrize("tokens_low,tokens_high", [(1, 4), (5, 40), (60, 180)],
                         ids=["mostly_empty_buckets", "some_empty", "full"])
def test_the_batched_encode_is_the_per_passage_encode(tokens_low, tokens_high):
    enc = MuveraEncoder(32, ksim=4, dproj=8, repetitions=5)
    rng = np.random.default_rng(tokens_high)
    sets = [rng.standard_normal((int(t), 32)).astype(np.float32)
            for t in rng.integers(tokens_low, tokens_high + 1, 12)]
    batched = enc.encode_docs(sets)
    assert batched.shape == (12, enc.fde_dim) and batched.dtype == np.float32
    for got, tokens in zip(batched, sets):
        assert np.allclose(got, _encode_doc_by_the_book(enc, tokens),
                           atol=1e-5)
        assert np.allclose(got, enc.encode_doc(tokens), atol=1e-6)


def test_the_query_encode_sums_and_does_not_fill():
    enc = MuveraEncoder(32, ksim=4, dproj=8, repetitions=5)
    q = np.random.default_rng(1).standard_normal((3, 32)).astype(np.float32)
    out = np.zeros((enc.repetitions, enc.buckets, 32), np.float32)
    for r in range(enc.repetitions):
        ids = (((enc.gaussians[r] @ q.T) < 0)
               * (1 << np.arange(4))[:, None]).sum(axis=0)
        np.add.at(out[r], ids, q)
    want = np.einsum("rbd,rdp->rbp", out, enc.proj).reshape(-1)
    assert np.allclose(enc.encode_query(q), want, atol=1e-5)
    assert (enc.encode_query(q).reshape(5, 16, 8) == 0).all(axis=2).sum() \
        >= 5 * (16 - 3)     # an empty bucket stays empty


def test_the_token_planes_are_bf16_and_fed_by_row():
    store = CandidateTokenStore(16, max_tokens=180)
    assert store.tmax == 192 and store.host_planes()[0].dtype == TOKEN_DTYPE
    rng = np.random.default_rng(0)
    first = [rng.standard_normal((int(t), 16)).astype(np.float32)
             for t in rng.integers(1, 181, 50)]
    store.put(np.arange(50), first)
    with store.planes(min_rows=2048) as (tokens, mask):
        assert tokens.shape == (2048, 192, 16) and tokens.dtype == TOKEN_DTYPE
        before = tokens
    assert not store._dirty.any()
    store.put(np.array([7, 60]), [first[0], first[1]])
    store.delete(np.array([3]))
    with store.planes(min_rows=2048) as (tokens, mask):
        host_t, host_m = store.host_planes()
        assert (np.asarray(tokens) == host_t).all()
        assert (np.asarray(mask) == host_m).all()
        assert np.asarray(mask)[7].sum() == len(first[0])
        assert not np.asarray(mask)[3].any()
    # the feed gave its planes away: nothing was copied whole
    assert before.is_deleted()
    want = np.zeros((192, 16), TOKEN_DTYPE)
    want[:len(first[1])] = first[1]
    assert (np.asarray(tokens)[60] == want).all()
    # a longer set widens the planes (a multiple of 16), a grown backend
    # lengthens them; the mirror is made anew and fed the live rows
    store.put(np.array([61]), [np.ones((200, 16), np.float32)])
    with store.planes(min_rows=4096) as (tokens, mask):
        assert tokens.shape == (4096, 208, 16)
        assert (np.asarray(mask).sum(axis=1)[[7, 60, 61]]
                == [len(first[0]), len(first[1]), 200]).all()


def test_readers_share_the_planes_and_a_feed_waits_for_them():
    import threading

    store = CandidateTokenStore(16, max_tokens=16)
    store.put(np.arange(4), np.ones((4, 3, 16), np.float32))
    inside, leave, fed = threading.Barrier(3), threading.Event(), []

    def read():
        with store.planes() as (tokens, _):
            inside.wait(timeout=30)     # both readers are in at once
            leave.wait(timeout=30)
            assert not tokens.is_deleted()

    def feed():
        store.put(np.array([5]), np.ones((1, 2, 16), np.float32))
        with store.planes() as (_, mask):
            fed.append(int(np.asarray(mask)[5].sum()))

    readers = [threading.Thread(target=read) for _ in range(2)]
    for t in readers:
        t.start()
    inside.wait(timeout=30)
    feeder = threading.Thread(target=feed)
    feeder.start()
    feeder.join(timeout=0.5)
    # the feed donates the planes the readers hold: it waits for them
    assert feeder.is_alive() and not fed and store._readers == 2
    leave.set()
    for t in readers + [feeder]:
        t.join(timeout=30)
    assert fed == [2] and store._readers == 0


@pytest.mark.parametrize("saved_width", [16, 192, 208])
def test_a_sidecar_of_another_width_loads(tmp_path, saved_width):
    rng = np.random.default_rng(3)
    sets = [rng.standard_normal((int(t), 16)).astype(np.float32)
            for t in rng.integers(1, saved_width + 1, 20)]
    sets[0] = sets[0][:1].repeat(saved_width, axis=0)   # the widest set
    old = CandidateTokenStore(16, max_tokens=saved_width)
    old.put(np.arange(20), sets)
    path = str(tmp_path / "ckpt")
    old.save(path)
    new = CandidateTokenStore(16, max_tokens=180)
    assert new.load(path)
    tokens, mask = new.host_planes()
    assert tokens.shape[1] == max(192, saved_width)
    assert (mask[:20].sum(axis=1) == [len(s) for s in sets]).all()
    assert not mask[20:].any()
    for i, s in enumerate(sets):
        assert (tokens[i, :len(s)] == s.astype(TOKEN_DTYPE)).all()
