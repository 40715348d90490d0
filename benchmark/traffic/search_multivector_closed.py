"""Traffic kind ``search_multivector_closed``: closed-loop gRPC ``Search``
clients whose every request is one late-interaction query, a ``[query_tokens,
dims]`` token set (``Vector.token_bytes``), against a collection whose every
object is one passage's token set.

The collection is made from the configuration's whole ``collection`` block
(``vectorIndexConfig``: MUVERA's parameters, ``rescoreLimit``, the rerank
module and its token width): ``harness.create_collection`` passes the distance
alone, so ``setup`` drops the class it made and posts the block as it stands.
Passages are imported by ``BatchObjects`` of ``load_batch`` objects (100: ~4 MB
a message), each with its ``passage`` text; the text generator and the corpus
are ``msmarco-768-hybrid``'s, the token sets ``reference_multivector``'s.

Parameters (the workload file's ``traffic``): ``clients``; ``processes`` (1);
``vectors_per_request`` (1: one token set); ``query_pool`` distinct queries a
seed, each client in its own seeded order; ``load_batch``;
``reference_queries``, the seeded sample of the window's distinct queries that
the reference scans every passage for (``rank_gap``, ``recall_miss``; every
other number is read on every answer); ``trace_seconds``. The clients, the
warm-up and the end-to-end metrics are ``search_closed``'s; set-up ends, as the
hybrid kind's, by waiting out the merges the load left the object store.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import harness, reference_hybrid, reference_multivector
from benchmark.traffic import search_closed, search_filtered_closed
from weaviate_tpu.api.proto import pb

end_to_end = search_closed.end_to_end
trace_costs = search_closed.trace_costs

COUNTERS = ("weaviate_tpu_rerank_requests_total",
            "weaviate_tpu_rerank_fallback_total")
TICK_S, STALL_S, PROBE_S = 0.1, 1.0, 0.5


def setup(ctx) -> dict:
    cfg, spec = ctx.cfg, ctx.spec
    data, col = cfg["data"], cfg["collection"]
    # a program whose plane cannot carry a token set fails here, at once
    _token_set(np.zeros((1, cfg["dims"]), np.float32))
    _recreate(ctx.server, col)
    prop = col["properties"][0]["name"]
    t0 = time.monotonic()
    passages = reference_hybrid.make_passages(data["text"], ctx.rows, ctx.seed)
    texts = reference_hybrid.passage_texts(data["text"], passages)
    tokens, offsets = reference_multivector.make_token_sets(
        data, cfg["dims"], passages, ctx.seed)
    pool = min(spec["query_pool"], ctx.rows)
    queries, _ = reference_multivector.make_queries(
        data, cfg["dims"], passages, pool, ctx.seed)
    requests = [_search_request(ctx.collection, ctx.k, q) for q in queries]
    orders = {c: np.random.default_rng([ctx.seed, 100 + c])
              .permutation(pool) for c in range(spec["clients"])}
    harness.say(phase="data", rows=ctx.rows, tokens=len(tokens),
                mean_tokens=len(tokens) / ctx.rows,
                longest=int(np.diff(offsets).max()),
                seconds=time.monotonic() - t0)
    secs = _load(ctx.server, ctx.collection, tokens, offsets, prop, texts,
                 spec["load_batch"])
    loaded_at = time.monotonic()
    harness.say(phase="load", rows=ctx.rows, seconds=secs,
                docs_per_s=ctx.rows / secs,
                megabytes_per_s=tokens.nbytes / secs / 1e6)
    counted = ctx.server.object_count()
    if counted != ctx.rows:
        raise RuntimeError(f"/v1/nodes counts {counted}, loaded {ctx.rows}")
    state = {"tokens": tokens, "offsets": offsets, "queries": queries,
             "requests": requests, "orders": orders, "vpr": 1, "cursor": 0}
    search_closed._warm_up(ctx, state)  # the same clients, the same warm-up
    search_filtered_closed._settle(ctx.server, loaded_at)
    return state


def _recreate(server, col: dict) -> None:
    req = urllib.request.Request(
        f"{server.base}/v1/schema/{col['class']}", method="DELETE")
    with urllib.request.urlopen(req, timeout=60):
        pass
    server.post("/v1/schema", {key: col[key] for key in (
        "class", "vectorizer", "vectorIndexType", "vectorIndexConfig",
        "properties")})


def _token_set(tokens: np.ndarray) -> pb.Vector:
    return pb.Vector(token_bytes=tokens.astype("<f4", copy=False).tobytes(),
                     token_dims=tokens.shape[1])


def _search_request(collection: str, k: int, query: np.ndarray) -> bytes:
    return pb.SearchRequest(
        collection=collection, limit=k,
        near_vectors=[_token_set(query)]).SerializeToString()


def _batch_request(collection: str, lo: int, hi: int, tokens: np.ndarray,
                   offsets: np.ndarray, prop: str, texts: list[str]) -> bytes:
    return pb.BatchObjectsRequest(objects=[
        pb.BatchObject(
            uuid=harness.row_uuid(row), collection=collection,
            properties_json=json.dumps({prop: texts[row]}),
            vector=_token_set(tokens[offsets[row]:offsets[row + 1]]))
        for row in range(lo, hi)]).SerializeToString()


def _load(server, collection: str, tokens: np.ndarray, offsets: np.ndarray,
          prop: str, texts: list[str], batch: int) -> float:
    """Rows 0..len(texts) over gRPC: 4 threads x ``batch``-object
    ``BatchObjects``, every reply checked. Returns the seconds it took."""
    local, clients = threading.local(), []
    rows = len(texts)

    def send(lo: int) -> None:
        if not hasattr(local, "client"):
            local.client = harness.Grpc(server.address)
            clients.append(local.client)
        hi = min(lo + batch, rows)
        reply = local.client.batch_objects(
            _batch_request(collection, lo, hi, tokens, offsets, prop, texts),
            timeout=300)
        err = harness.check_batch_reply(reply, lo, hi - lo)
        if err:
            raise RuntimeError("BatchObjects at " + err)

    t0 = time.monotonic()
    try:
        with ThreadPoolExecutor(harness.LOAD_THREADS) as pool:
            for _ in pool.map(send, range(0, rows, batch)):
                pass    # a failed batch raises here
    finally:
        for client in clients:
            client.close()
    return time.monotonic() - t0


def _parse(reply) -> list[tuple[np.ndarray, np.ndarray]]:
    """(row ids, MaxSim scores) of the one result of a reply: a hit's
    ``distance`` is its negated score."""
    return [(np.array([harness.uuid_row(h.uuid) for h in r.hits], np.int64),
             -np.array([h.distance for h in r.hits], np.float32))
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

    watch = _StallWatch(ctx.server, lambda: sum(pos.values()))
    watch.start()
    try:
        return harness.closed_loop(ctx.server.address, clients, seconds,
                                   next_request, "Search", _parse)
    finally:
        watch.done.set()
        watch.join()
        harness.say(phase="stall_watch", **watch.seen)


class _StallWatch(threading.Thread):
    """One run in a dozen of this cell answered nothing for 2-4 s of its
    window (PERF.md, PR 35). Which side stood still is read here, while it
    lasts: a thread that ticks every ``TICK_S`` and, once no client has sent
    a request for ``STALL_S`` (every one of them waits for a reply), asks the
    server's own stack sampler (``/debug/pprof/profile``, ``PROBE_S``) where
    its threads stand. On record: how long the stall lasted, how long the
    probe took to come back (a server that cannot even answer that is
    stopped as a whole), the server's CPU seconds meanwhile, the stacks, and
    the longest gap between this thread's own ticks (a gap there means the
    generator stood still too: the machine, not the program). Costs ten
    wake-ups a second and, in a window without a stall, nothing else."""

    def __init__(self, server, sent):
        super().__init__(daemon=True)
        self.server, self.sent, self.done = server, sent, threading.Event()
        self.seen = {"ticks": 0, "longest_tick_gap_s": 0.0, "stalls": []}

    def _server_cpu_s(self) -> float:
        with open(f"/proc/{self.server.proc.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / 100.0

    def _probe(self) -> dict:
        t0, cpu0 = time.monotonic(), self._server_cpu_s()
        try:
            with urllib.request.urlopen(
                    f"{self.server.base}/debug/pprof/profile"
                    f"?seconds={PROBE_S}", timeout=30) as r:
                stacks = r.read().decode()[:6000]
        except OSError as e:
            stacks = f"no answer: {e}"
        return {"probe_s": time.monotonic() - t0,
                "server_cpu_s": self._server_cpu_s() - cpu0,
                "stacks": stacks}

    def run(self) -> None:
        seen, last_tick = self.seen, time.monotonic()
        count, moved_at, open_stall = self.sent(), last_tick, None
        while not self.done.wait(TICK_S):
            now = time.monotonic()
            seen["ticks"] += 1
            seen["longest_tick_gap_s"] = max(
                seen["longest_tick_gap_s"], now - last_tick)
            last_tick = now
            if self.sent() != count:
                count, moved_at = self.sent(), now
                if open_stall is not None:
                    open_stall["lasted_s"] = now - open_stall.pop("since")
                    open_stall = None
            elif open_stall is None and now - moved_at >= STALL_S:
                open_stall = {"since": moved_at, **self._probe()}
                seen["stalls"].append(open_stall)
                last_tick = time.monotonic()    # the probe is no gap
        if open_stall is not None:
            open_stall["lasted_s"] = time.monotonic() - open_stall.pop("since")


def after_window(ctx, state, records) -> None:
    """The answers are in the records; what the program counted of its
    rerank tiers goes on record, and the fallbacks into the comparison."""
    with urllib.request.urlopen(ctx.server.base + "/metrics",
                                timeout=60) as r:
        lines = r.read().decode().splitlines()
    counted = {name: {line.split()[0][len(name):]: float(line.split()[-1])
                      for line in lines if line.startswith(name)}
               for name in COUNTERS}
    state["rerank_fallbacks"] = sum(counted[COUNTERS[1]].values())
    harness.say(phase="rerank", requests=counted[COUNTERS[0]],
                fallbacks=counted[COUNTERS[1]])


def check(ctx, state, records, control: str = "") -> dict:
    """Every answer of the window against the plain reference. ``control``
    names a lower precision: the reference's MaxSim at that precision is put
    in the program's place (the sampled queries), and must come out not
    correct."""
    if "reference" not in state:
        t0 = time.monotonic()
        state["reference"] = reference_multivector.MaxSim(
            state["tokens"], state["offsets"])
        harness.say(phase="corpus", rows=state["reference"].rows,
                    token_counts=len(state["reference"].groups),
                    seconds=time.monotonic() - t0)
    none = (np.empty(0, np.int64), np.empty(0, np.float32))
    answers = [(int(r["tag"]), *(r["answer"][0] if r["answer"] else none))
               for r in records if not r["error"]]
    sampled = reference_multivector.sample_queries(
        sorted({qi for qi, _, _ in answers}), ctx.spec["reference_queries"],
        ctx.seed)
    if control:
        answers = reference_multivector.control_answers(
            state["tokens"], state["offsets"], state["queries"], ctx.k,
            sampled, control)
    numbers = reference_multivector.compare_answers(
        state["reference"], state["queries"], ctx.k, answers, sampled)
    numbers["unanswered"] = sum(1 for r in records if r["error"])
    numbers["rerank_fallbacks"] = 0.0 if control \
        else state["rerank_fallbacks"]
    return numbers
