"""Traffic kind ``search_filtered_closed``: closed-loop gRPC ``Search``
clients whose every request carries its own filter.

The configuration's rows each hold a bag of tags (``data.tags``, vocabulary
``vocabulary``, cut with the rows where a rehearsal cuts those); a request is
one query vector, ``limit`` k and ``where_json`` = ``ContainsAll`` on
``tags`` with the query's 1 or 2 tags, which a hit must all have.

Parameters (the workload file's ``traffic``): ``clients``; ``processes``;
``vectors_per_request`` (1); ``query_pool`` distinct (vector, filter) pairs a
seed, each client in its own seeded order; ``tags_per_query`` the numbers of
tags a filter may have, each as likely; ``trace_seconds``. The clients, the
warm-up and the end-to-end metrics are ``search_closed``'s; set-up ends by
waiting out the merges the bulk load left the object store (``_settle``).
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import harness, reference, reference_filtered
from benchmark.traffic import search_closed
from weaviate_tpu.api.proto import pb

window = search_closed.window
end_to_end = search_closed.end_to_end
trace_costs = search_closed.trace_costs


def setup(ctx) -> dict:
    cfg, spec = ctx.cfg, ctx.spec
    tags = cfg["data"]["tags"]
    vocabulary = max(tags["draws"],
                     round(cfg["vocabulary"] * ctx.rows / cfg["rows"]))
    corpus = reference.make_rows(cfg["data"], cfg["dims"], ctx.rows, ctx.seed)
    bags = reference_filtered.make_bags(tags, vocabulary, ctx.rows, ctx.seed)
    pool = min(spec["query_pool"], ctx.rows)
    queries, made_from = reference.make_queries(
        cfg["data"], corpus, pool, ctx.seed)
    filters = reference_filtered.make_filters(
        spec["tags_per_query"], bags, made_from, ctx.seed)
    requests = [_search_request(ctx.collection, ctx.k, q, f)
                for q, f in zip(queries.tolist(), filters)]
    orders = {c: np.random.default_rng([ctx.seed, 100 + c])
              .permutation(pool) for c in range(spec["clients"])}
    secs = _load(ctx.server, ctx.collection, corpus, bags)
    loaded_at = time.monotonic()
    harness.say(phase="load", rows=ctx.rows, vocabulary=vocabulary,
                mean_bag=float((bags >= 0).sum(axis=1).mean()),
                seconds=secs, docs_per_s=ctx.rows / secs)
    counted = ctx.server.object_count()
    if counted != ctx.rows:
        raise RuntimeError(f"/v1/nodes counts {counted}, loaded {ctx.rows}")
    state = {"corpus": corpus, "bags": reference_filtered.Bags(bags),
             "queries": queries, "filters": filters, "requests": requests,
             "orders": orders, "vpr": 1, "cursor": 0}
    search_closed._warm_up(ctx, state)  # the same clients, the same warm-up
    _settle(ctx.server, loaded_at)
    return state


def _search_request(collection: str, k: int, vector: list[float],
                    tags: tuple[int, ...]) -> bytes:
    where = {"operator": "ContainsAll", "path": ["tags"],
             "valueText": [reference_filtered.tag_text(t) for t in tags]}
    return pb.SearchRequest(
        collection=collection, limit=k,
        near_vectors=[pb.Vector(values=vector)],
        where_json=json.dumps(where)).SerializeToString()


def _batch_request(collection: str, first_row: int, vectors: np.ndarray,
                   bags: np.ndarray) -> bytes:
    return pb.BatchObjectsRequest(objects=[
        pb.BatchObject(
            uuid=harness.row_uuid(first_row + j), collection=collection,
            properties_json=json.dumps(
                {"tags": reference_filtered.bag_texts(bag)}),
            vector=pb.Vector(values=values))
        for j, (values, bag) in enumerate(zip(vectors.tolist(), bags))
    ]).SerializeToString()


def _load(server, collection: str, corpus: np.ndarray,
          bags: np.ndarray) -> float:
    """``harness.load`` with each row's bag as its ``tags``: rows
    0..len(corpus) over gRPC, 4 threads x 1000-object batches, every reply
    checked. Returns the seconds it took."""
    local, clients = threading.local(), []

    def send(lo: int) -> None:
        if not hasattr(local, "client"):
            local.client = harness.Grpc(server.address)
            clients.append(local.client)
        hi = lo + harness.LOAD_BATCH
        reply = local.client.batch_objects(
            _batch_request(collection, lo, corpus[lo:hi], bags[lo:hi]),
            timeout=120)
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


def _settle(server, loaded_at: float, tick_s: float = 5.5,
            deadline_s: float = 30.0) -> None:
    """Wait out the merges the bulk load left the object store, as the
    harness's ``os.sync()`` waits out its dirty pages. While its merge debt
    is over the program's own target the store merges two buckets a 5 s
    tick, each under that bucket's lock: the last ``objects`` merge (67 + 33
    -> 101 MB) held every search for ~2 s and fell into the first seconds of
    3 windows in 7 (chip runs, PR 28: 180 queries/s against 203). The gauge
    is refreshed at a tick and after a merge, so it is first read a whole
    tick after the last write; past the deadline the run goes on and the
    line says so."""
    target = server.get("/v1/debug/config")["values"][
        "compaction_debt_target_bytes"]["value"]
    time.sleep(max(0.0, loaded_at + tick_s - time.monotonic()))
    t0 = time.monotonic()
    while True:
        with urllib.request.urlopen(server.base + "/metrics",
                                    timeout=60) as r:
            debt = next(float(line.split()[-1])
                        for line in r.read().decode().splitlines()
                        if line.startswith(
                            "weaviate_tpu_compaction_debt_bytes"))
        waited = time.monotonic() - t0
        if debt < target or waited > deadline_s:
            break
        time.sleep(0.5)
    harness.say(phase="settled", under_target=debt < target,
                debt_bytes=debt, target_bytes=target, waited_s=waited)


def after_window(ctx, state, records) -> None:
    """Nothing to read back (the answers are in the records); what the
    program's filter planes made of this traffic goes on record."""
    shards = ctx.server.get("/v1/debug/planner?collection=" + ctx.collection)[
        "collections"][ctx.collection]["shards"]
    for planes in (s["filter_planes"] for s in shards.values()):
        harness.say(phase="planes", planes=len(planes["planes"]),
                    hits=[p["hits"] for p in planes["planes"]],
                    allowed=[p["count"] for p in planes["planes"]],
                    hbm_bytes=planes["hbm_bytes"],
                    host_bytes=planes["host_bytes"],
                    filters_counting=len(planes["pending"]))


def check(ctx, state, records, control: str = "") -> dict:
    """Every answer of the window against the plain filtered reference.
    ``control`` names a lower precision: the reference at that precision,
    under the same filters, is put in the program's place and must come out
    not correct."""
    scan = reference.Scan(ctx.cfg["distance"], state["corpus"])
    none = (np.empty(0, np.int64), np.empty(0, np.float32))
    answers = [(int(r["tag"]), *(r["answer"][0] if r["answer"] else none))
               for r in records if not r["error"]]
    if control:
        answers = reference_filtered.control_answers(
            ctx.cfg["distance"], state["corpus"], state["bags"],
            state["queries"], state["filters"], ctx.k,
            sorted({qi for qi, _, _ in answers}))
    numbers = reference_filtered.compare_answers(
        scan, state["bags"], state["queries"], state["filters"], ctx.k,
        answers)
    numbers["unanswered"] = sum(1 for r in records if r["error"])
    selectivity = numbers.pop("allowed_rows")
    if not control:
        harness.say(phase="filters", queries=numbers["distinct_queries"],
                    **selectivity)
    return numbers
