"""Traffic kind ``search_closed``: closed-loop gRPC ``Search`` clients.

Parameters (the workload file's ``traffic``): ``clients``; ``processes``
the generator is split over (1: threads of the parent); ``vectors_per_request``
(1: one query vector per request; more: each request carries that many of the
configuration's query pool, ``distinct_requests`` different ones per seed);
``trace_seconds``. Every seed sends the same number of distinct requests of
the same sizes, each client in its own seeded order.
"""

from __future__ import annotations

import multiprocessing
import time

import numpy as np

from benchmark import harness, reference

WARM_TRIES = 40


def setup(ctx) -> dict:
    cfg, spec = ctx.cfg, ctx.spec
    corpus = reference.make_rows(cfg["data"], cfg["dims"], ctx.rows, ctx.seed)
    pool = min(cfg["data"]["queries"], ctx.rows)
    queries, _ = reference.make_queries(cfg["data"], corpus, pool, ctx.seed)
    vpr = min(spec["vectors_per_request"], pool)
    rng = np.random.default_rng([ctx.seed, 11])
    if vpr == 1:
        members = [np.array([i]) for i in range(pool)]
    else:
        members = [rng.choice(pool, size=vpr, replace=False)
                   for _ in range(spec.get("distinct_requests", 16))]
    requests = [harness.search_request(ctx.collection, ctx.k, queries[m])
                for m in members]
    orders = {c: np.random.default_rng([ctx.seed, 100 + c])
              .permutation(len(requests)) for c in range(spec["clients"])}
    secs = harness.load(ctx.server, ctx.collection, corpus)
    harness.say(phase="load", rows=ctx.rows, seconds=secs,
                docs_per_s=ctx.rows / secs)
    counted = ctx.server.object_count()
    if counted != ctx.rows:
        raise RuntimeError(f"/v1/nodes counts {counted}, loaded {ctx.rows}")
    state = {"corpus": corpus, "queries": queries, "members": members,
             "requests": requests, "orders": orders, "vpr": vpr,
             "cursor": 0}
    _warm_up(ctx, state)
    return state


def _warm_up(ctx, state) -> None:
    """The cell's own request shape until two answers in a row take under a
    second, then two seconds at the cell's concurrency."""
    client = harness.Grpc(ctx.server.address)
    took = []
    try:
        for _ in range(WARM_TRIES):
            t0 = time.monotonic()
            client.search(state["requests"][0], timeout=300)
            took.append(time.monotonic() - t0)
            if len(took) >= 2 and max(took[-2:]) < 1.0:
                break
        else:
            raise RuntimeError(f"warm-up never settled: {took}")
    finally:
        client.close()
    window(ctx, state, 2.0)
    harness.say(phase="warm_up", first_answer_s=took[0], answers=len(took),
                last_answer_s=took[-1])


def _drive(address, clients, seconds, requests, orders, cursor, start_at):
    pos = {c: cursor for c in clients}

    def next_request(c):
        i = int(orders[c][pos[c] % len(orders[c])])
        pos[c] += 1
        return i, requests[i]

    return harness.closed_loop(address, clients, seconds, next_request,
                               "Search", harness.parse_search_reply, start_at)


def window(ctx, state, seconds: float) -> list[dict]:
    clients = list(range(ctx.spec["clients"]))
    n_proc = ctx.spec.get("processes", 1)
    args = (state["requests"], state["orders"], state["cursor"])
    # a later window goes on in each client's order where this one stopped
    # (an upper estimate of the requests one client can finish)
    state["cursor"] += int(seconds * 2000)
    if n_proc == 1:
        return _drive(ctx.server.address, clients, seconds, *args, None)
    start_at = time.monotonic() + 3.0   # spawned generators import first
    shares = [clients[p::n_proc] for p in range(n_proc)]
    with multiprocessing.get_context("spawn").Pool(n_proc) as pool:
        parts = pool.starmap(_drive, [
            (ctx.server.address, share, seconds, *args, start_at)
            for share in shares])
    return [r for part in parts for r in part]


def end_to_end(ctx, state, records, seconds: float) -> dict:
    done = [r for r in records
            if not r["error"] and r["sent"] + r["latency"] <= seconds]
    lat = [r["latency"] * 1e3 if not r["error"] else np.inf for r in records]
    return {"search_qps": len(done) * state["vpr"] / seconds,
            "search_p95_ms": harness.percentile(lat, 0.95)}


def after_window(ctx, state, records) -> None:
    """Nothing to read back: the answers are in the records."""


def _answers(state, records):
    out = []
    for r in records:
        if r["error"]:
            continue
        qis = state["members"][r["tag"]]
        got = r["answer"]
        for j, qi in enumerate(qis):
            ids, dists = got[j] if j < len(got) else (
                np.empty(0, np.int64), np.empty(0, np.float32))
            out.append((int(qi), ids, dists))
    return out


def check(ctx, state, records, control: str = "") -> dict:
    """Every answer of the window against the plain reference. ``control``
    names a lower precision: the reference at that precision is put in the
    program's place (same queries), and must come out not correct."""
    scan = reference.Scan(ctx.cfg["distance"], state["corpus"])
    answers = _answers(state, records)
    if control:
        used = sorted({qi for qi, _, _ in answers})
        answers = reference.control_answers(
            ctx.cfg["distance"], state["corpus"], state["queries"], ctx.k,
            used)
    numbers = reference.compare_answers(
        scan, state["queries"], ctx.k, answers)
    numbers["unanswered"] = sum(1 for r in records if r["error"])
    return numbers


def trace_costs(ctx, state) -> dict:
    return {"vectors_per_execution": state["vpr"]}
