"""The spans of the served gRPC path, by count and structure (never by
wall clock): a ``Search`` and a ``BatchObjects`` through ``GrpcAPI`` over a
real channel each give ONE trace whose root is still ``grpc.<rpc>`` with the
children ``docs/tracing.md`` lists, inside the span budget; an unsampled
request opens nothing; the same names land in a ``jax.profiler`` trace's
host plane (the shared clock ``benchmark/xplane.py`` reads gaps from)."""

import json
import time

import numpy as np
import pytest

from weaviate_tpu.api.grpc_server import GrpcAPI, GrpcClient
from weaviate_tpu.api.proto import pb
from weaviate_tpu.core.db import DB
from weaviate_tpu.monitoring import tracing
from weaviate_tpu.monitoring.tracing import TRACER, Tracer
from weaviate_tpu.schema.config import CollectionConfig, FlatIndexConfig

D = 16

# span -> parent, as docs/tracing.md's table has them
SEARCH_TREE = {
    "grpc.Search": None,
    "qos.queue": "grpc.Search",
    "index.search": "grpc.Search",
    # the coalescing dispatcher's batch, in the trace of the request whose
    # thread led it; the scan's spans are opened once a batch, under it
    "dispatch.batch": "index.search",
    "flat.warm": "dispatch.batch",        # only at a program's first search
    "flat.prepare": "dispatch.batch",
    "flat.dispatch": "dispatch.batch",
    "flat.result": "dispatch.batch",
    "objects.fetch": "grpc.Search",
    "grpc.encode": "grpc.Search",
    "grpc.send": "grpc.Search",
}
BATCH_TREE = {
    "grpc.BatchObjects": None,
    "qos.queue": "grpc.BatchObjects",
    "batch.build": "grpc.BatchObjects",
    "schema.ensure": "grpc.BatchObjects",
    "shard.put_batch": "grpc.BatchObjects",
    "shard.durable": "shard.put_batch",
    "shard.sync": "shard.put_batch",      # only with group commit
    "shard.drain_wait": "shard.put_batch",
    "ingest.drain": "shard.drain_wait",
    "index.add_batch": "ingest.drain",
    "grpc.encode": "grpc.BatchObjects",
    "grpc.send": "grpc.BatchObjects",
}
# what a filter adds: its resolution to an allow mask, on the request's own
# thread, and the mask's way to the device, once a batch
FILTERED_TREE = {**SEARCH_TREE, "filter.resolve": "grpc.Search",
                 "flat.mask": "flat.dispatch"}
# what a hybrid Search adds: the sparse leg on a pool thread (its engine and
# its object reads beneath it), the dense leg's span around the flat path
# (index.search re-enters the request's scope, so it stays the root's child;
# the leg's own objects.fetch hangs under the leg), and the fusion
HYBRID_TREE = {**SEARCH_TREE, "hybrid.sparse": "grpc.Search",
               "bm25.search": "hybrid.sparse", "bm25.fetch": "hybrid.sparse",
               "hybrid.dense": "grpc.Search", "objects.fetch": "hybrid.dense",
               "hybrid.fuse": "grpc.Search"}
# 10 before the flat path passed the dispatcher: + dispatch.batch
SEARCH_BUDGET, BATCH_BUDGET, FILTERED_BUDGET = 11, 16, 13
HYBRID_BUDGET = SEARCH_BUDGET + 5


def _serve(tmp_dbdir, sync_writes=False, max_workers=None):
    db = DB(tmp_dbdir, sync_writes=sync_writes)
    db.create_collection(CollectionConfig(
        name="Article",
        vector_config=FlatIndexConfig(distance="cosine")))
    api = GrpcAPI(db, max_workers=max_workers)
    client = GrpcClient(f"127.0.0.1:{api.serve(port=0)}")
    return db, api, client


@pytest.fixture(params=[False, True], ids=["soft", "group_commit"])
def served(request, tmp_dbdir):
    db, api, client = _serve(tmp_dbdir, sync_writes=request.param)
    yield client, request.param
    client.close()
    api.shutdown()
    db.close()


def _batch(n, start=0):
    rng = np.random.default_rng(start)
    req = pb.BatchObjectsRequest()
    for i in range(start, start + n):
        o = req.objects.add()
        o.uuid = f"00000000-0000-0000-0000-{i:012d}"
        o.collection = "Article"
        o.properties_json = json.dumps({"title": f"article {i}"})
        o.vector.values.extend(rng.standard_normal(D).tolist())
    return req


def _search(vectors=1, limit=10):
    rng = np.random.default_rng(99)
    req = pb.SearchRequest(collection="Article", limit=limit)
    for _ in range(vectors):
        req.near_vectors.add().values.extend(rng.standard_normal(D).tolist())
    return req


def _clear() -> None:
    """Empty the buffer once every call made so far is retired: a call's
    ``grpc.send`` is recorded AFTER the client has its reply, and one that
    arrived after the buffer was emptied would stand there without its
    root."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        spans = TRACER.recent(limit=TRACER.max_spans)
        roots = {s["spanId"] for s in spans if s["parentSpanId"] is None
                 and s["name"].startswith("grpc.")}
        if roots <= {s["parentSpanId"] for s in spans
                     if s["name"] == "grpc.send"}:
            break
        time.sleep(0.005)
    TRACER.clear()


def _one_trace(root: str) -> list[dict]:
    """The spans of the only trace in the buffer, once its late
    ``grpc.send`` (recorded after the client has its reply) is in."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        traces = TRACER.traces(limit=10)
        if len(traces) == 1 and any(
                s["name"] == "grpc.send" for s in traces[0]["spans"]):
            break
        time.sleep(0.01)
    assert len(traces) == 1, [t["root"] for t in traces]
    assert traces[0]["root"] == root
    assert not traces[0]["truncated"] and not traces[0]["inFlight"]
    return traces[0]["spans"]


def _check_tree(spans: list[dict], tree: dict, root: str) -> dict:
    """Every span has the parent the table gives it and lies inside that
    parent's interval, except the documented late ``grpc.send``, which
    starts where the root closed and ends when gRPC retired the call: its
    ``resident_ms`` (arrival at the pool -> retirement) holds the pool wait
    and the whole root. Returns spans by name (lists)."""
    by_id = {s["spanId"]: s for s in spans}
    by_name: dict[str, list[dict]] = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
        assert s["name"] in tree, f"a span the table does not have: {s}"
        assert s["status"] == "OK"
        if s["name"] == "grpc.send":    # recorded after the fact
            assert "cpu_ms" not in s["attributes"]
        elif tracing.THREAD_CLOCK:
            assert s["attributes"]["cpu_ms"] >= 0
        if tree[s["name"]] is None:
            assert s["parentSpanId"] is None
            continue
        parent = by_id[s["parentSpanId"]]
        assert parent["name"] == tree[s["name"]], (s["name"], parent["name"])
        if s["name"] == "grpc.send":
            assert s["startTimeUnixNano"] == parent["endTimeUnixNano"]
            assert s["endTimeUnixNano"] >= s["startTimeUnixNano"]
            assert s["attributes"]["resident_ms"] >= (
                parent["attributes"]["pool_wait_ms"] + parent["durationMs"])
            assert s["attributes"]["serialize_ms"] >= 0
        else:
            assert parent["startTimeUnixNano"] <= s["startTimeUnixNano"]
            assert s["endTimeUnixNano"] <= parent["endTimeUnixNano"]
    (root_span,) = by_name[root]
    attrs = root_span["attributes"]
    assert attrs["pool_wait_ms"] >= 0 and attrs["decode_ms"] >= 0
    assert attrs["pool_wait_ms"] >= attrs["decode_ms"]
    assert attrs["request_bytes"] > 0
    return by_name


@pytest.mark.parametrize("vectors", [1, 3])
def test_search_gives_one_trace_with_the_tables_children(served, vectors):
    client, _ = served
    assert not client.batch_objects(_batch(100)).errors
    # the first search at a capacity compiles every row bucket under one
    # more span, flat.warm, whose synthetic scans open no span of their own
    _clear()
    client.search(_search(vectors))
    first = _check_tree(_one_trace("grpc.Search"), SEARCH_TREE, "grpc.Search")
    assert {n: len(v) for n, v in first.items()} == dict.fromkeys(
        SEARCH_TREE, 1)
    assert first["flat.warm"][0]["attributes"]["buckets"] == [1, 4, 8]
    assert first["flat.warm"][0]["endTimeUnixNano"] <= \
        first["flat.prepare"][0]["startTimeUnixNano"]
    _clear()
    reply = client.search(_search(vectors))
    assert [len(r.hits) for r in reply.results] == [10] * vectors
    spans = _one_trace("grpc.Search")
    by_name = _check_tree(spans, SEARCH_TREE, "grpc.Search")
    # exactly the table's names, one span each: nothing per hit or per row
    steady = {n: 1 for n in SEARCH_TREE if n != "flat.warm"}
    assert {n: len(v) for n, v in by_name.items()} == steady
    assert len(spans) <= SEARCH_BUDGET
    (index,) = by_name["index.search"]
    assert index["attributes"]["index_type"] == "FlatIndex"
    assert index["attributes"]["rows"] == 100
    assert index["attributes"]["k"] == 10
    # a lone request is its own batch; its rows are padded to a bucket
    (batch,) = by_name["dispatch.batch"]
    assert batch["attributes"]["batch_size"] == 1
    assert batch["attributes"]["rows"] == vectors
    assert batch["attributes"]["queue_ms"] >= 0
    assert batch["attributes"]["device_ms"] >= 0
    assert by_name["flat.dispatch"][0]["attributes"]["batch"] == \
        {1: 1, 3: 4}[vectors]
    assert by_name["flat.dispatch"][0]["attributes"]["capacity"] >= 100
    # a bf16 cosine product: the rows are resident as the scan reads them
    assert by_name["flat.dispatch"][0]["attributes"]["corpus_dtype"] == \
        "bfloat16"
    fetch = by_name["objects.fetch"][0]["attributes"]
    assert fetch["objects"] == 10 * vectors
    # 100 rows never flushed: the memtable answers every key of the
    # request's one multi-get, and no segment record is read
    assert (fetch["lock_takes"], fetch["mem_hits"], fetch["records_read"]) \
        == (1, 10 * vectors, 0)
    assert by_name["grpc.encode"][0]["attributes"]["hits"] == 10 * vectors
    assert by_name["grpc.send"][0]["attributes"]["reply_bytes"] > 0
    # the three scan spans follow each other on the leader's thread
    order = [by_name[n][0] for n in
             ("flat.prepare", "flat.dispatch", "flat.result")]
    for a, b in zip(order, order[1:]):
        assert a["endTimeUnixNano"] <= b["startTimeUnixNano"]


@pytest.mark.parametrize("vectors", [1, 3])
def test_hits_over_flushed_segments_cost_one_record_and_one_lock_take(
        tmp_dbdir, vectors):
    """The reply's hits come from ONE multi-get a shard: one take of the
    ``objects`` bucket's lock and one record read a hit, with the rows in
    three segments and the memtable; span counts as in the memtable case."""
    db, api, client = _serve(tmp_dbdir)
    try:
        (shard,) = db.get_collection("Article")._shards.values()
        for start in range(0, 400, 100):
            assert not client.batch_objects(_batch(100, start=start)).errors
            if start < 300:
                shard.objects.flush_memtable()
        assert len(shard.objects._segments) == 3
        client.search(_search(vectors))      # compiles (flat.warm)
        _clear()
        reply = client.search(_search(vectors))
        assert [len(r.hits) for r in reply.results] == [10] * vectors
        spans = _one_trace("grpc.Search")
        by_name = _check_tree(spans, SEARCH_TREE, "grpc.Search")
        assert {n: len(v) for n, v in by_name.items()} == {
            n: 1 for n in SEARCH_TREE if n != "flat.warm"}
        assert len(spans) <= SEARCH_BUDGET
        fetch = by_name["objects.fetch"][0]["attributes"]
        assert fetch["objects"] == 10 * vectors
        assert fetch["lock_takes"] == 1
        assert fetch["records_read"] + fetch["mem_hits"] == 10 * vectors
        assert 0 < fetch["records_read"] <= 10 * vectors
    finally:
        client.close()
        api.shutdown()
        db.close()


def test_a_filtered_search_adds_two_spans_inside_its_budget(served):
    client, _ = served
    batch = _batch(100)
    for i, o in enumerate(batch.objects):
        o.properties_json = json.dumps({"tags": [f"t{i % 4}", "all"]})
    assert not client.batch_objects(batch).errors
    req = _search()
    req.where_json = json.dumps({"operator": "ContainsAll", "path": ["tags"],
                                 "valueText": ["t1", "all"]})
    # the first filtered search compiles the masked programs (flat.warm)
    _clear()
    client.search(req)
    first = _one_trace("grpc.Search")
    assert {n: len(v) for n, v in _check_tree(
        first, FILTERED_TREE, "grpc.Search").items()} == dict.fromkeys(
            FILTERED_TREE, 1)
    assert len(first) <= FILTERED_BUDGET
    _clear()
    reply = client.search(req)
    assert all("t1" in json.loads(h.properties_json)["tags"]
               for h in reply.results[0].hits)
    spans = _one_trace("grpc.Search")
    by_name = _check_tree(spans, FILTERED_TREE, "grpc.Search")
    assert {n: len(v) for n, v in by_name.items()} == {
        n: 1 for n in FILTERED_TREE if n != "flat.warm"}
    assert len(spans) < FILTERED_BUDGET
    resolved = by_name["filter.resolve"][0]["attributes"]
    assert (resolved["source"], resolved["allowed"], resolved["tags"]) == (
        "inverted", 25, 2)
    assert by_name["flat.mask"][0]["attributes"]["bytes"] == \
        by_name["flat.dispatch"][0]["attributes"]["capacity"]
    assert by_name["dispatch.batch"][0]["attributes"]["filtered"] is True
    # resolved before the index is asked, uploaded before the scan starts
    assert by_name["filter.resolve"][0]["endTimeUnixNano"] <= \
        by_name["index.search"][0]["startTimeUnixNano"]
    assert by_name["flat.prepare"][0]["endTimeUnixNano"] <= \
        by_name["flat.mask"][0]["startTimeUnixNano"]


@pytest.mark.parametrize("fusion", ["relativeScoreFusion", "rankedFusion"])
def test_a_hybrid_search_adds_its_legs_and_fusion_under_the_root(
        served, fusion):
    """The spans and attributes the hybrid cell's per-layer metrics read
    (``benchmark/metrics/hybrid_*.json``, ``bm25_*.json``)."""
    client, _ = served
    batch = _batch(100)
    for i, o in enumerate(batch.objects):
        o.properties_json = json.dumps(
            {"title": f"article {i} about topic{i % 7} and the rest"})
    assert not client.batch_objects(batch).errors
    req = _search()
    req.use_hybrid, req.bm25_query = True, "the article on topic3"
    if fusion != "relativeScoreFusion":     # else: the server's default
        req.fusion = fusion
    client.search(req)      # compiles the k = 20 scans and the fusion
    _clear()
    (result,) = client.search(req).results
    assert len(result.hits) == 10
    spans = _one_trace("grpc.Search")
    by_name = _check_tree(spans, HYBRID_TREE, "grpc.Search")
    assert {n: len(v) for n, v in by_name.items()} == {
        n: 1 for n in HYBRID_TREE if n != "flat.warm"}
    assert len(spans) <= HYBRID_BUDGET
    assert by_name["grpc.Search"][0]["attributes"]["legs_shed"] == 0
    sparse = by_name["hybrid.sparse"][0]["attributes"]
    # each leg is ceil(hybrid_overfetch_factor x limit) deep
    assert (sparse["k"], sparse["hits"]) == (20, 20)
    assert sparse["pool_wait_ms"] >= 0
    engine = by_name["bm25.search"][0]["attributes"]
    # "the" and "on" are stopwords; "article" is in all 100 rows and
    # "topic3" in 14 of them (the native engine may be the Python tier)
    assert engine["engine"] in ("wand", "python")
    assert (engine["terms"], engine["postings"], engine["hits"]) == (
        2, 114, 20)
    fetch = by_name["bm25.fetch"][0]["attributes"]
    # the leg's 20 hits by one multi-get, as the dense leg's objects.fetch
    assert (fetch["objects"], fetch["lock_takes"], fetch["mem_hits"]) == (
        20, 1, 20)
    assert by_name["hybrid.dense"][0]["attributes"]["k"] == 20
    assert by_name["index.search"][0]["attributes"]["k"] == 20
    assert by_name["objects.fetch"][0]["attributes"]["objects"] == 20
    assert by_name["dispatch.batch"][0]["attributes"]["group"] == str(
        ("hybrid", fusion))
    fuse = by_name["hybrid.fuse"][0]["attributes"]
    assert (fuse["fusion"], fuse["legs"], fuse["tier"]) == (
        fusion, 2, "device")
    assert 20 <= fuse["union"] <= 40 and fuse["sync_ms"] >= 0
    # the legs overlap; the fusion starts when both have ended
    for leg in ("hybrid.sparse", "hybrid.dense"):
        assert by_name[leg][0]["endTimeUnixNano"] <= \
            by_name["hybrid.fuse"][0]["startTimeUnixNano"]


def test_batch_objects_gives_one_trace_with_the_tables_children(served):
    client, group_commit = served
    assert not client.batch_objects(_batch(100)).errors   # schema, dims
    _clear()
    reply = client.batch_objects(_batch(100, start=100))
    assert not reply.errors and len(reply.uuids) == 100
    spans = _one_trace("grpc.BatchObjects")
    by_name = _check_tree(spans, BATCH_TREE, "grpc.BatchObjects")
    want = dict.fromkeys(BATCH_TREE, 1)
    # a single writer's 100 rows feed the device as 64 + 32 + 4
    want["index.add_batch"] = 3
    if not group_commit:
        del want["shard.sync"]
    assert {n: len(v) for n, v in by_name.items()} == want
    assert len(spans) <= BATCH_BUDGET
    assert sorted(s["attributes"]["rows"]
                  for s in by_name["index.add_batch"]) == [4, 32, 64]
    assert all(s["attributes"]["grew"] is False
               for s in by_name["index.add_batch"])
    assert by_name["batch.build"][0]["attributes"]["objects"] == 100
    (put,) = by_name["shard.put_batch"]
    assert put["attributes"]["objects"] == 100
    assert put["attributes"]["lock_wait_ms"] >= 0
    (durable,) = by_name["shard.durable"]
    attrs = durable["attributes"]
    assert attrs["wal_ms"] >= 0 and attrs["push_ms"] >= 0
    assert attrs["store_ms"] > 0 and attrs["inverted_ms"] > 0
    assert attrs["wal_ms"] + attrs["store_ms"] + attrs["inverted_ms"] \
        + attrs["push_ms"] <= durable["durationMs"] + 0.01
    # a batch is written as a batch: the delta log, the id map and the
    # objects each get ONE write() for the hundred (was 1 + 2 x 100)
    assert (attrs["objects"], attrs["wal_writes"]) == (100, 3)
    (drain,) = by_name["ingest.drain"]
    assert drain["attributes"]["rows"] == 100
    assert drain["attributes"]["buckets"] == 3
    assert by_name["grpc.encode"][0]["attributes"]["objects"] == 100


def test_a_grow_is_named_on_the_feed_that_paid_for_it(served):
    """``grew`` comes from ``DeviceVectorStore.ensure_capacity``: the first
    rows past the store's capacity (4,096 rows a page) say so."""
    client, _ = served
    _clear()
    for start in range(0, 4200, 700):
        assert not client.batch_objects(_batch(700, start=start)).errors
    feeds = [s for s in TRACER.recent(limit=TRACER.max_spans)
             if s["name"] == "index.add_batch"]
    assert sum(s["attributes"]["rows"] for s in feeds) == 4200
    assert sum(1 for s in feeds if s["attributes"]["grew"]) == 1


def test_an_aborted_call_leaves_no_root_for_the_next_serializer(
        tmp_dbdir, monkeypatch):
    """One worker thread: the ``grpc.send`` of a reply hangs under its own
    call's root and carries its own reply's bytes; the aborted call before
    it gets a ``grpc.send`` of its own with no reply in it. Both are
    recorded from gRPC's polling thread, when it retires the call."""
    import threading

    import grpc

    recorders = []
    record = TRACER.record

    def recording(name, *args, **kwargs):
        recorders.append((name, threading.current_thread().name))
        return record(name, *args, **kwargs)

    monkeypatch.setattr(TRACER, "record", recording)
    db, api, client = _serve(tmp_dbdir, max_workers=1)
    try:
        assert not client.batch_objects(_batch(20)).errors
        _clear()
        recorders.clear()
        bad = _search()
        bad.collection = "Nowhere"
        with pytest.raises(grpc.RpcError):
            client.search(bad)
        client.search(_search(limit=5))
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and sum(
                s["name"] == "grpc.send"
                for s in TRACER.recent(limit=100)) < 2:
            time.sleep(0.01)
        spans = TRACER.recent(limit=100)
        roots = [s for s in spans if s["name"] == "grpc.Search"]
        assert [r["status"] for r in roots] == ["ERROR", "OK"]
        sends = {s["parentSpanId"]: s for s in spans
                 if s["name"] == "grpc.send"}
        assert set(sends) == {r["spanId"] for r in roots}
        for root in roots:
            send = sends[root["spanId"]]
            assert send["traceId"] == root["traceId"]
            assert send["startTimeUnixNano"] == root["endTimeUnixNano"]
            assert send["attributes"]["resident_ms"] >= (
                root["attributes"]["pool_wait_ms"] + root["durationMs"])
        aborted, answered = (sends[r["spanId"]]["attributes"] for r in roots)
        assert "reply_bytes" not in aborted and "serialize_ms" not in aborted
        assert answered["reply_bytes"] > 0 and answered["serialize_ms"] >= 0
        assert [n for n, _ in recorders] == ["grpc.send"] * 2
        assert all("_serve" in thread and "ThreadPoolExecutor" not in thread
                   for _, thread in recorders), recorders
    finally:
        client.close()
        api.shutdown()
        db.close()


def test_v1_compat_plane_shares_the_ingress_attributes(tmp_dbdir):
    """``weaviate.v1`` calls go through the same ``traced_unary_handler``."""
    from weaviate_tpu.api.grpc_v1_compat import SERVICE_V1
    from weaviate_tpu.api.proto import weaviate_v1_compat_pb2 as wv

    db, api, client = _serve(tmp_dbdir)
    try:
        assert not client.batch_objects(_batch(20)).errors
        _clear()
        call = client.channel.unary_unary(
            f"/{SERVICE_V1}/Search",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=wv.SearchReply.FromString)
        req = wv.SearchRequest(collection="Article", limit=3)
        req.near_vector.vector_bytes = np.random.default_rng(
            5).standard_normal(D).astype(np.float32).tobytes()
        assert len(call(req).results) == 3
        spans = _one_trace("grpc.Search")
        (root,) = [s for s in spans if s["name"] == "grpc.Search"]
        assert root["attributes"]["plane"] == "v1_compat"
        assert root["attributes"]["pool_wait_ms"] >= 0
        assert root["attributes"]["request_bytes"] > 0
        (send,) = [s for s in spans if s["name"] == "grpc.send"]
        assert send["parentSpanId"] == root["spanId"]
        assert send["attributes"]["reply_bytes"] > 0
    finally:
        client.close()
        api.shutdown()
        db.close()


@pytest.fixture
def counting(monkeypatch):
    """Counts the annotations the tracer opens and its reads of the
    thread's CPU clock."""
    import types

    c = types.SimpleNamespace(opened=[], clock_reads=0)
    real = tracing.TraceAnnotation

    def annotation(name):
        c.opened.append(name)
        return real(name)

    def thread_time_ns():
        c.clock_reads += 1
        return time.thread_time_ns()

    monkeypatch.setattr(tracing, "TraceAnnotation", annotation)
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        time_ns=time.time_ns, thread_time_ns=thread_time_ns))
    return c


def test_unsampled_requests_open_no_annotation_and_read_no_thread_clock(
        tmp_dbdir, counting):
    from weaviate_tpu.utils.runtime_config import TRACING_SAMPLE_RATE

    db, api, client = _serve(tmp_dbdir)
    try:
        assert not client.batch_objects(_batch(50)).errors
        client.search(_search())
        sampled = len(counting.opened)
        reads = (2 if tracing.THREAD_CLOCK else 0) * sampled
        assert sampled >= 2 and counting.clock_reads == reads
        assert "grpc.Search" in counting.opened
        TRACING_SAMPLE_RATE.set_override(0.0)
        try:
            _clear()
            assert not client.batch_objects(_batch(50, start=50)).errors
            assert len(client.search(_search()).results[0].hits) == 10
            api.shutdown()      # the workers are done, serializers too
            assert len(counting.opened) == sampled
            assert counting.clock_reads == reads
            assert TRACER.recent(limit=TRACER.max_spans) == []
        finally:
            TRACING_SAMPLE_RATE.clear_override()
    finally:
        client.close()
        api.shutdown()
        db.close()


def test_span_names_land_on_a_host_line_of_a_profiler_trace(tmp_path,
                                                            tmp_dbdir):
    """The shared clock, rehearsed on the CPU backend: with a profile
    session open (the options ``benchmark/serve.py`` sets), a ``Search``'s
    spans are events of ONE thread line of the host plane, nested there as
    in the program's own trace."""
    import jax
    from jax.profiler import ProfileData

    from benchmark import xplane

    db, api, client = _serve(tmp_dbdir)
    try:
        assert not client.batch_objects(_batch(50)).errors
        client.search(_search())            # compile outside the session
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            client.search(_search())
            api.shutdown()
        finally:
            jax.profiler.stop_trace()
    finally:
        client.close()
        api.shutdown()
        db.close()
    path = xplane.find_trace(str(tmp_path))
    assert path is not None
    # flat.warm compiled outside; grpc.send is recorded after the fact and
    # so is no annotation: it stands on the host's clock alone
    want = set(SEARCH_TREE) - {"flat.warm", "grpc.send"}
    lines = [
        {ev.name: (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns))
         for ev in line.events}
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU" for line in plane.lines]
    held = [events for events in lines if want <= set(events)]
    assert len(held) == 1, [sorted(set(e) & want) for e in lines]
    assert not any("grpc.send" in events for events in lines)
    events = held[0]
    for name, parent in SEARCH_TREE.items():
        if parent is None or name not in want:
            continue
        assert events[parent][0] <= events[name][0]
        assert events[name][1] <= events[parent][1]
    # a device call made inside flat.prepare / flat.dispatch lies inside it
    # on that line: "the innermost host event that covers the gap" is then
    # the jax call where there is one and the program's span where not
    jax_calls = [n for n in events if n.startswith("PjitFunction(")]
    assert jax_calls, sorted(events)
    for n in jax_calls:
        assert events["index.search"][0] <= events[n][0]
        assert events[n][1] <= events["index.search"][1]


def test_the_buffer_keeps_a_cells_traced_segment():
    """~2,400 requests x 9 spans and ~430 ticks in the busiest search
    cell's traced ~4.3 s."""
    assert TRACER.max_spans >= 16384
    tr = Tracer()
    n = tr.max_spans + 100
    for _ in range(n):
        with tr.span("s"):
            pass
    assert len(tr.recent(limit=n)) == tr.max_spans >= 16384
