"""Traffic kind ``import_closed``: closed-loop gRPC ``BatchObjects`` clients
adding fresh objects to a collection that set-up preloaded.

Parameters: ``clients``; ``batch`` objects per request; ``preload_rows``
loaded in bulk by set-up; ``max_rows`` the collection may reach (kept under
the store's next doubling, so no grow and no new program shape falls inside
the window — running out of fresh rows is an error, not a grow);
``readback_sample`` acknowledged objects fetched back by id and
``search_sample`` vector searches for imported rows, both after the window;
``trace_seconds``.
"""

from __future__ import annotations

import threading
import time
import urllib.error

import numpy as np

from benchmark import harness, reference

WARM_TRIES = 40


def setup(ctx) -> dict:
    cfg, spec = ctx.cfg, ctx.spec
    scale = ctx.rows / cfg["rows"]          # 1 unless a rehearsal cut rows
    preload = max(spec["batch"], int(spec["preload_rows"] * scale))
    if scale != 1:
        preload = min(preload, 4000)
    # a rehearsal's small collection may grow: it measures nothing
    max_rows = spec["max_rows"] if scale == 1 else preload + 60000
    rows = reference.make_rows(cfg["data"], cfg["dims"], max_rows, ctx.seed)
    secs = harness.load(ctx.server, ctx.collection, rows[:preload])
    harness.say(phase="load", rows=preload, seconds=secs,
                docs_per_s=preload / secs)
    state = {"rows": rows, "preload": preload, "next_row": preload,
             "lock": threading.Lock(), "acked": 0, "window_first": None}
    _warm_up(ctx, state)
    return state


def _take_block(ctx, state) -> int:
    n = ctx.spec["batch"]
    with state["lock"]:
        first = state["next_row"]
        if first + n > len(state["rows"]):
            raise RuntimeError(
                f"out of fresh rows at {first}: the window imports faster "
                "than max_rows allows, a grow would fall inside it")
        state["next_row"] = first + n
    return first


def _drive(ctx, state, seconds: float) -> list[dict]:
    n = ctx.spec["batch"]

    def next_request(c):
        first = _take_block(ctx, state)
        return first, harness.batch_request(
            ctx.collection, first, state["rows"][first:first + n])

    records = harness.closed_loop(
        ctx.server.address, list(range(ctx.spec["clients"])), seconds,
        next_request, "BatchObjects", lambda reply: reply)
    for r in records:
        reply = r.pop("answer")
        if not r["error"]:
            r["error"] = harness.check_batch_reply(reply, r["tag"], n)
        if not r["error"]:
            state["acked"] += n
    return records


def _warm_up(ctx, state) -> None:
    client = harness.Grpc(ctx.server.address)
    n, took = ctx.spec["batch"], []
    try:
        for _ in range(WARM_TRIES):
            first = _take_block(ctx, state)
            t0 = time.monotonic()
            reply = client.batch_objects(harness.batch_request(
                ctx.collection, first, state["rows"][first:first + n]),
                timeout=300)
            took.append(time.monotonic() - t0)
            err = harness.check_batch_reply(reply, first, n)
            if err:
                raise RuntimeError("warm-up BatchObjects at " + err)
            state["acked"] += n
            if len(took) >= 2 and max(took[-2:]) < 1.0:
                break
        else:
            raise RuntimeError(f"warm-up never settled: {took}")
    finally:
        client.close()
    bad = [r for r in _drive(ctx, state, 2.0) if r["error"]]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]['error']}")
    harness.say(phase="warm_up", first_answer_s=took[0], answers=len(took),
                last_answer_s=took[-1])


def window(ctx, state, seconds: float) -> list[dict]:
    if state["window_first"] is None:
        state["window_first"] = state["next_row"]
    return _drive(ctx, state, seconds)


def end_to_end(ctx, state, records, seconds: float) -> dict:
    done = [r for r in records
            if not r["error"] and r["sent"] + r["latency"] <= seconds]
    lat = [r["latency"] * 1e3 if not r["error"] else np.inf for r in records]
    return {"import_rate": len(done) * ctx.spec["batch"] / seconds,
            "import_p95_ms": harness.percentile(lat, 0.95)}


def after_window(ctx, state, records) -> None:
    """With the server still up: the node's count, acknowledged objects
    fetched back by id, and vector searches for imported rows."""
    spec, n = ctx.spec, ctx.spec["batch"]
    state["high_water"] = state["next_row"]
    state["count"] = ctx.server.object_count()
    rng = np.random.default_rng([ctx.seed, 13])
    acked = [r for r in records if not r["error"]]
    imported = np.concatenate(
        [np.arange(r["tag"], r["tag"] + n) for r in acked]) if acked \
        else np.arange(state["preload"], state["high_water"])
    # the last acknowledged object of each client, and a seeded sample
    last = {}
    for r in acked:
        if r["client"] not in last or r["sent"] > last[r["client"]]["sent"]:
            last[r["client"]] = r
    probe = [r["tag"] + n - 1 for r in last.values()] + rng.choice(
        imported, size=min(spec["readback_sample"], len(imported)),
        replace=False).tolist()
    bad = 0
    cls = ctx.cfg["collection"]["class"]
    for row in probe:
        try:
            obj = ctx.server.get(f"/v1/objects/{cls}/{harness.row_uuid(row)}")
        except urllib.error.HTTPError:
            bad += 1
            continue
        vec = np.asarray(obj.get("vector", []), np.float32)
        same = vec.shape == state["rows"][row].shape and np.array_equal(
            vec.view(np.uint32), state["rows"][row].view(np.uint32))
        if not same or obj.get("properties", {}).get("tag") != f"r{row}":
            bad += 1
    state["readback"] = (len(probe), bad)
    targets = rng.choice(imported, size=min(spec["search_sample"],
                                            len(imported)), replace=False)
    queries, _ = reference.make_queries(
        ctx.cfg["data"], state["rows"], len(targets), ctx.seed, rows=targets)
    client = harness.Grpc(ctx.server.address)
    answers = []
    try:
        for qi, q in enumerate(queries):
            reply = client.search(harness.search_request(
                ctx.collection, ctx.k, q[None, :]), timeout=300)
            ids, dists = harness.parse_search_reply(reply)[0]
            answers.append((qi, ids, dists))
    finally:
        client.close()
    state["queries"], state["answers"] = queries, answers


def check(ctx, state, records, control: str = "") -> dict:
    live = state["high_water"]
    scan = reference.Scan(ctx.cfg["distance"], state["rows"][:live])
    answers = state["answers"]
    if control:
        answers = reference.control_answers(
            ctx.cfg["distance"], state["rows"][:live], state["queries"],
            ctx.k, list(range(len(state["queries"]))))
    numbers = reference.compare_answers(scan, state["queries"], ctx.k,
                                        answers)
    numbers["count_diff"] = abs(state["count"]
                                - (state["preload"] + state["acked"]))
    numbers["readback_checked"], numbers["readback_bad"] = state["readback"]
    numbers["unanswered"] = sum(1 for r in records if r["error"])
    return numbers


def trace_costs(ctx, state) -> dict:
    return {}
