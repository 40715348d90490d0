"""Traffic kind ``search_hybrid_closed``: closed-loop gRPC ``Search``
clients whose every request is one hybrid query.

The configuration's rows each carry a passage (``data.text``: seeded words
from a Zipf law, ``benchmark/reference_hybrid.py``) under the collection's one
searchable ``text`` property; a request is ``use_hybrid`` with one query
vector, one text of a few words and ``limit`` k, and leaves ``alpha`` and
``fusion`` unset, so the server's defaults are what is measured (the
configuration's ``hybrid`` block states them for the reference).

Parameters (the workload file's ``traffic``): ``clients``; ``processes``;
``vectors_per_request`` (1); ``query_pool`` distinct (vector, text) pairs a
seed, each client in its own seeded order; ``trace_seconds``. The clients
and the end-to-end metrics are ``search_closed``'s; the load is
``search_filtered_closed``'s with the passage in the bag's place, and set-up
ends, as there, by waiting out the merges the load left the object store.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import harness, reference, reference_hybrid
from benchmark.traffic import search_closed, search_filtered_closed
from weaviate_tpu.api.proto import pb

end_to_end = search_closed.end_to_end
trace_costs = search_closed.trace_costs

COUNTERS = ("weaviate_tpu_hybrid_requests_total",
            "weaviate_tpu_hybrid_leg_shed_total",
            "weaviate_tpu_hybrid_fallback_total")


def setup(ctx) -> dict:
    cfg, spec = ctx.cfg, ctx.spec
    text = cfg["data"]["text"]
    prop = cfg["collection"]["properties"][0]["name"]
    corpus = reference.make_rows(cfg["data"], cfg["dims"], ctx.rows, ctx.seed)
    passages = reference_hybrid.make_passages(text, ctx.rows, ctx.seed)
    texts = reference_hybrid.passage_texts(text, passages)
    pool = min(spec["query_pool"], ctx.rows)
    queries, made_from = reference.make_queries(
        cfg["data"], corpus, pool, ctx.seed)
    query_texts = reference_hybrid.make_query_texts(
        text, passages, made_from, ctx.seed)
    requests = [_search_request(ctx.collection, ctx.k, q, t)
                for q, t in zip(queries.tolist(), query_texts)]
    orders = {c: np.random.default_rng([ctx.seed, 100 + c])
              .permutation(pool) for c in range(spec["clients"])}
    secs = _load(ctx.server, ctx.collection, corpus, prop, texts)
    loaded_at = time.monotonic()
    harness.say(phase="load", rows=ctx.rows, seconds=secs,
                docs_per_s=ctx.rows / secs,
                mean_words=float(np.mean([len(p) for p in passages])))
    counted = ctx.server.object_count()
    if counted != ctx.rows:
        raise RuntimeError(f"/v1/nodes counts {counted}, loaded {ctx.rows}")
    state = {"corpus": corpus, "texts": texts, "queries": queries,
             "query_texts": query_texts, "requests": requests,
             "orders": orders, "vpr": 1, "cursor": 0}
    _warm_up(ctx, state, passages, made_from)
    search_filtered_closed._settle(ctx.server, loaded_at)
    return state


def _search_request(collection: str, k: int, vector: list[float],
                    text: str) -> bytes:
    return pb.SearchRequest(
        collection=collection, limit=k, use_hybrid=True, bm25_query=text,
        near_vectors=[pb.Vector(values=vector)]).SerializeToString()


def _batch_request(collection: str, first_row: int, vectors: np.ndarray,
                   prop: str, texts: list[str]) -> bytes:
    return pb.BatchObjectsRequest(objects=[
        pb.BatchObject(
            uuid=harness.row_uuid(first_row + j), collection=collection,
            properties_json=json.dumps({prop: text}),
            vector=pb.Vector(values=values))
        for j, (values, text) in enumerate(zip(vectors.tolist(), texts))
    ]).SerializeToString()


def _load(server, collection: str, corpus: np.ndarray, prop: str,
          texts: list[str]) -> float:
    """``harness.load`` with each row's passage as its text property: rows
    0..len(corpus) over gRPC, 4 threads x 1000-object batches, every reply
    checked. Returns the seconds it took."""
    local, clients = threading.local(), []

    def send(lo: int) -> None:
        if not hasattr(local, "client"):
            local.client = harness.Grpc(server.address)
            clients.append(local.client)
        hi = lo + harness.LOAD_BATCH
        reply = local.client.batch_objects(
            _batch_request(collection, lo, corpus[lo:hi], prop, texts[lo:hi]),
            timeout=300)
        err = harness.check_batch_reply(reply, lo, len(corpus[lo:hi]))
        if err:
            raise RuntimeError("BatchObjects at " + err)

    t0 = time.monotonic()
    try:
        with ThreadPoolExecutor(harness.LOAD_THREADS) as pool:
            for _ in pool.map(send, range(0, len(corpus), harness.LOAD_BATCH)):
                pass    # a failed batch raises here
    finally:
        for client in clients:
            client.close()
    return time.monotonic() - t0


def _warm_up(ctx, state, passages, made_from) -> None:
    """``search_closed``'s warm-up (the cell's own request until two answers
    in a row take under a second, then two seconds at the cell's
    concurrency), and before the concurrent part one request whose text is a
    single word, the rarest of its passage: a sparse leg of a few hits, so
    the fusion program of the smaller union is compiled too, whatever the
    pool's first two seconds happen to draw."""
    row = int(made_from[0])
    rare = int(passages[row].max())
    text = reference_hybrid.words(ctx.cfg["data"]["text"]["vocabulary"])[rare]
    client = harness.Grpc(ctx.server.address)
    try:
        search_closed._warm_up(ctx, state)
        client.search(_search_request(
            ctx.collection, ctx.k, state["queries"][0].tolist(), text),
            timeout=300)
    finally:
        client.close()


def _parse(reply) -> list[tuple[np.ndarray, np.ndarray]]:
    """(row ids, fused scores) of the one result of a hybrid reply."""
    return [(np.array([harness.uuid_row(h.uuid) for h in r.hits], np.int64),
             np.array([h.score for h in r.hits], np.float32))
            for r in reply.results]


def window(ctx, state, seconds: float) -> list[dict]:
    clients = list(range(ctx.spec["clients"]))
    requests, orders, cursor = \
        state["requests"], state["orders"], state["cursor"]
    state["cursor"] += int(seconds * 2000)   # as search_closed.window
    pos = {c: cursor for c in clients}

    def next_request(c):
        i = int(orders[c][pos[c] % len(orders[c])])
        pos[c] += 1
        return i, requests[i]

    return harness.closed_loop(ctx.server.address, clients, seconds,
                               next_request, "Search", _parse)


def after_window(ctx, state, records) -> None:
    """The answers are in the records; what the program counted of its
    hybrid requests (legs shed, tiers fallen back) goes on record."""
    with urllib.request.urlopen(ctx.server.base + "/metrics",
                                timeout=60) as r:
        lines = r.read().decode().splitlines()
    counted = {name: sum(float(line.split()[-1]) for line in lines
                         if line.startswith(name))
               for name in COUNTERS}
    harness.say(phase="hybrid", **{
        name[len("weaviate_tpu_hybrid_"):-len("_total")]: value
        for name, value in counted.items()})


def _hybrid(ctx, state) -> reference_hybrid.Hybrid:
    cfg = ctx.cfg
    bm25 = reference_hybrid.Bm25(
        state["texts"], cfg["collection"]["bm25"]["k1"],
        cfg["collection"]["bm25"]["b"])
    return reference_hybrid.Hybrid(
        reference.Scan(cfg["distance"], state["corpus"]), bm25,
        cfg["hybrid"]["alpha"], cfg["hybrid"]["fusion"],
        cfg["hybrid"]["leg_depth"])


def check(ctx, state, records, control: str = "") -> dict:
    """Every answer of the window against the plain hybrid reference.
    ``control`` names a lower precision: the reference with its dense leg at
    that precision is put in the program's place and must come out not
    correct."""
    if "hybrid" not in state:
        t0 = time.monotonic()
        state["hybrid"] = _hybrid(ctx, state)
        bm25 = state["hybrid"].bm25
        harness.say(phase="corpus", rows=bm25.rows,
                    distinct_terms=len(bm25.term_ids),
                    postings=bm25.postings, mean_length=bm25.avgdl,
                    seconds=time.monotonic() - t0)
    hybrid = state["hybrid"]
    none = (np.empty(0, np.int64), np.empty(0, np.float32))
    answers = [(int(r["tag"]), *(r["answer"][0] if r["answer"] else none))
               for r in records if not r["error"]]
    if control:
        answers = reference_hybrid.control_answers(
            hybrid, state["corpus"], state["queries"], state["query_texts"],
            ctx.k, sorted({qi for qi, _, _ in answers}), control)
    numbers = reference_hybrid.compare_answers(
        hybrid, state["queries"], state["query_texts"], ctx.k, answers)
    numbers["unanswered"] = sum(1 for r in records if r["error"])
    return numbers
