"""Traffic kind ``search_rq_closed``: ``search_closed``'s closed-loop gRPC
``Search`` clients, one query vector a request, against a flat collection
whose vectors are kept as 8-bit rotational-quantization codes on the device
and float32 originals on the host.

The collection is made from the configuration's whole ``collection`` block
(its ``vectorIndexConfig`` carries the ``rq`` block): ``harness.create_collection``
passes the distance alone, so ``setup`` drops the class it made and posts
the block as it stands, as ``search_multivector_closed`` does. Rows are
``reference_rq``'s clustered ones, loaded by the harness's ``BatchObjects``;
``/v1/nodes`` has to count them all.

Parameters (the workload file's ``traffic``): ``clients``; ``processes``
(1); ``vectors_per_request`` (1); ``reference_queries``, the seeded sample
of the window's distinct queries whose answers are also held to the plain
reference's top 10 and to the exact top 10 (every other number is read on
every answer); ``trace_seconds``. The window, the warm-up and the end-to-end
metrics are ``search_closed``'s.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness, reference_multivector, reference_rq
from benchmark.traffic import search_closed, search_multivector_closed

window = search_closed.window
end_to_end = search_closed.end_to_end
after_window = search_closed.after_window
trace_costs = search_closed.trace_costs


def setup(ctx) -> dict:
    cfg, spec = ctx.cfg, ctx.spec
    search_multivector_closed._recreate(ctx.server, cfg["collection"])
    t0 = time.monotonic()
    corpus = reference_rq.make_rows(cfg["data"], cfg["dims"], ctx.rows,
                                    ctx.seed)
    pool = min(cfg["data"]["queries"], ctx.rows)
    queries, _ = reference_rq.make_queries(cfg["data"], corpus, pool,
                                           ctx.seed)
    requests = [harness.search_request(ctx.collection, ctx.k, q[None])
                for q in queries]
    orders = {c: np.random.default_rng([ctx.seed, 100 + c])
              .permutation(pool) for c in range(spec["clients"])}
    harness.say(phase="data", rows=ctx.rows, queries=pool,
                seconds=time.monotonic() - t0)
    secs = harness.load(ctx.server, ctx.collection, corpus)
    harness.say(phase="load", rows=ctx.rows, seconds=secs,
                docs_per_s=ctx.rows / secs)
    counted = ctx.server.object_count()
    if counted != ctx.rows:
        raise RuntimeError(f"/v1/nodes counts {counted}, loaded {ctx.rows}")
    state = {"corpus": corpus, "queries": queries,
             "members": [np.array([i]) for i in range(pool)],
             "requests": requests, "orders": orders, "vpr": 1, "cursor": 0}
    search_closed._warm_up(ctx, state)  # the same clients, the same warm-up
    return state


def check(ctx, state, records, control: str = "") -> dict:
    """Every answer of the window against the plain reference. ``control``
    names a lower precision: the reference, its rescore at that precision,
    is put in the program's place (the sampled queries), and must come out
    not correct."""
    if "reference" not in state:
        t0 = time.monotonic()
        state["reference"] = reference_rq.RQ(
            state["corpus"], reference_rq.layout(ctx.cfg))
        harness.say(phase="codes", rows=len(state["corpus"]),
                    seconds=time.monotonic() - t0)
    ref, queries = state["reference"], state["queries"]
    answers = search_closed._answers(state, records)
    sampled = reference_multivector.sample_queries(
        sorted({qi for qi, _, _ in answers}), ctx.spec["reference_queries"],
        ctx.seed)
    if control:
        answers = reference_rq.control_answers(
            ref, queries, ctx.k, sampled, control)
    numbers = reference_rq.compare_answers(ref, queries, ctx.k, answers,
                                           sampled)
    numbers["unanswered"] = sum(1 for r in records if r["error"])
    return numbers
