"""Tracing, telemetry, runtime config hot-reload, reindexer, CJK tokens.

Reference test models: ``usecases/config/runtime`` tests, telemetry
payload tests, ``inverted_reindexer`` tests, entities/tokenizer tests.
"""

import json
import shutil
import tempfile
import urllib.request

import numpy as np
import pytest

from weaviate_tpu.inverted.analyzer import tokenize
from weaviate_tpu.monitoring.tracing import TRACER, Tracer
from weaviate_tpu.utils.runtime_config import RuntimeConfig


# -- tracing -----------------------------------------------------------------

def test_span_nesting_and_retention():
    tr = Tracer(max_spans=8)
    with tr.span("root", kind="test") as root:
        with tr.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    spans = tr.recent()
    assert [s["name"] for s in spans] == ["child", "root"]  # finish order
    assert spans[1]["parentSpanId"] is None
    trees = tr.traces()
    assert trees[0]["root"] == "root" and len(trees[0]["spans"]) == 2


def test_span_error_status():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    assert tr.recent()[-1]["status"] == "ERROR"


def test_tracer_bounds_memory():
    tr = Tracer(max_spans=10)
    for i in range(50):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.recent(limit=100)) == 10


class _FakeAnnotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, exc_type, exc, tb):
        self.log.append(("exit", self.name, exc_type))


@pytest.mark.parametrize("rate,raises", [(1.0, False), (1.0, True),
                                         (0.0, False)],
                         ids=["sampled", "sampled_error", "unsampled"])
def test_span_mirrors_itself_onto_the_profilers_clock(monkeypatch, rate,
                                                      raises):
    """A sampled span entered with ``with`` opens a TraceAnnotation of its
    own name on its thread and closes it on exit (an exception included);
    an unsampled one opens nothing and carries no ``cpu_ms``."""
    from weaviate_tpu.monitoring import tracing

    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(tracing, "TraceAnnotation", _FakeAnnotation)
    tr = Tracer(sample_rate=rate)
    created = tr.span("never.entered")      # no ``with``: nothing opens
    try:
        with tr.span("outer"):
            with tr.span("inner"):
                if raises:
                    raise KeyError("x")
    except KeyError:
        pass
    assert created.end_ns is None
    if rate == 0.0:
        assert _FakeAnnotation.log == [] and tr.recent() == []
        return
    exc = KeyError if raises else None
    assert _FakeAnnotation.log == [
        ("enter", "outer"), ("enter", "inner"),
        ("exit", "inner", exc), ("exit", "outer", exc)]
    spans = tr.recent()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    assert tr.open_span_ids() == set()


@pytest.mark.parametrize("cheap", [True, False],
                         ids=["cheap_clock", "dear_clock"])
def test_span_cpu_ms_counts_the_threads_own_work(monkeypatch, cheap):
    """``cpu_ms`` where the thread's CPU clock is cheap; where it is not
    (the sealed machines that hold the chip: 5.9 us a call, 10 ms steps)
    the clock is never read and the attribute is absent."""
    import time
    import types

    from weaviate_tpu.monitoring import tracing

    reads = []

    def thread_time_ns():
        reads.append(1)
        return time.thread_time_ns()

    monkeypatch.setattr(tracing, "THREAD_CLOCK", cheap)
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        time_ns=time.time_ns, thread_time_ns=thread_time_ns))
    tr = Tracer()
    with tr.span("idle"):
        pass
    with tr.span("busy"):
        sum(i * i for i in range(200_000))
    idle, busy = tr.recent()
    if cheap:
        assert len(reads) == 4
        assert busy["attributes"]["cpu_ms"] > \
            idle["attributes"]["cpu_ms"] >= 0
    else:
        assert reads == []
        assert "cpu_ms" not in idle["attributes"]
        assert "cpu_ms" not in busy["attributes"]


@pytest.mark.parametrize("costs_ns,cheap", [
    ([300] * 50, True),                     # a plain Linux host
    ([5900] * 50, False),                   # the chip's sealed machine
    ([300] * 10 + [90_000] * 30 + [300] * 10, True),    # preempted rounds
], ids=["cheap", "dear", "preempted"])
def test_thread_clock_probe_goes_by_its_cheapest_round(monkeypatch,
                                                       costs_ns, cheap):
    import types

    from weaviate_tpu.monitoring import tracing

    clock = {"now": 0, "calls": 0}

    def thread_time_ns():
        clock["now"] += costs_ns[clock["calls"]]
        clock["calls"] += 1
        return 0

    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: clock["now"],
        thread_time_ns=thread_time_ns))
    assert tracing._thread_clock_is_cheap() is cheap
    assert clock["calls"] == 50


def test_child_joins_a_trace_but_never_starts_one():
    """``Tracer.child``: the deep layer boundaries. Under an active span a
    real child; with none, nothing is recorded, attributes are taken, the
    thread's current span stays None and a ``span`` nested inside mints its
    own root as it always did."""
    from weaviate_tpu.monitoring.tracing import current_span

    tr = Tracer()
    with tr.child("index.search", k=3) as orphan:
        orphan.set(rows=1)
        assert not orphan.sampled and current_span() is None
        with tr.span("ingest.drain"):
            pass
    assert [s["name"] for s in tr.recent()] == ["ingest.drain"]
    assert tr.recent()[0]["parentSpanId"] is None
    with tr.span("grpc.Search") as root:
        with tr.child("index.search", k=3) as kid:
            assert kid.sampled and kid.parent_id == root.span_id
    with Tracer(sample_rate=0.0).span("grpc.Search"):
        with tr.child("index.search") as kid:
            assert not kid.sampled
    assert [s["name"] for s in tr.recent()] == [
        "ingest.drain", "index.search", "grpc.Search"]


def test_lockless_span_path_loses_nothing_under_contention():
    """The span path takes no lock: finished spans land by ``deque.append``,
    open ids by ``set.add`` / ``discard``, readers snapshot with
    ``list(deque)``. More writers than cores, a switch interval of
    microseconds and a reader hammering every read path: no span lost,
    none twice, no id left open, no reader error."""
    import sys
    import threading

    from weaviate_tpu.monitoring.metrics import TRACE_SPANS

    writers, each = 24, 400
    tr = Tracer(max_spans=writers * each * 2)
    before = TRACE_SPANS.value(name="stress.child")
    errors, stop = [], threading.Event()

    def write():
        try:
            for _ in range(each):
                with tr.span("stress.root", parent=None):
                    with tr.span("stress.child") as c:
                        c.set(a=1)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    def read():
        try:
            while not stop.is_set():
                tr.traces(limit=50)
                tr.recent(limit=50)
                tr.open_span_ids()
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        reader = threading.Thread(target=read)
        threads = [threading.Thread(target=write) for _ in range(writers)]
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        reader.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not reader.is_alive()
    assert errors == []
    spans = tr.recent(limit=tr.max_spans)
    assert len(spans) == 2 * writers * each
    assert len({s["spanId"] for s in spans}) == len(spans)
    assert all(s["endTimeUnixNano"] for s in spans)
    assert tr.open_span_ids() == set()
    assert TRACE_SPANS.value(name="stress.child") - before == writers * each


def test_span_and_trace_ids_keep_the_w3c_shape():
    from weaviate_tpu.monitoring.tracing import parse_traceparent

    tr = Tracer()
    seen_spans, seen_traces = set(), set()
    for _ in range(2000):
        with tr.span("root", parent=None) as s:
            pass
        assert len(s.span_id) == 16 and len(s.trace_id) == 32
        int(s.span_id, 16), int(s.trace_id, 16)
        assert parse_traceparent(s.traceparent) == s.context
        seen_spans.add(s.span_id)
        seen_traces.add(s.trace_id)
    assert len(seen_spans) == len(seen_traces) == 2000


def test_default_retention_holds_a_traced_segment():
    """No knob: the constant keeps the ~11,000 spans of the search cell's
    traced seconds whole (benchmark/run.py read_spans)."""
    from weaviate_tpu.monitoring.tracing import MAX_SPANS

    assert MAX_SPANS >= 16384
    assert Tracer().max_spans == TRACER.max_spans == MAX_SPANS


def test_traceparent_roundtrip():
    from weaviate_tpu.monitoring.tracing import parse_traceparent

    tr = Tracer()
    with tr.span("root") as s:
        tp = s.traceparent
    ctx = parse_traceparent(tp)
    assert ctx.trace_id == s.trace_id and ctx.span_id == s.span_id
    assert ctx.sampled
    # malformed headers never fail the request: they parse to None
    for bad in ("", "junk", "00-short-short-01", "00-" + "zz" * 16
                + "-" + "cd" * 8 + "-01"):
        assert parse_traceparent(bad) is None
    # unsampled flag is honored
    assert parse_traceparent(
        "00-" + "ab" * 16 + "-" + "cd" * 8 + "-00").sampled is False


def test_remote_parent_and_links_and_events():
    from weaviate_tpu.monitoring.tracing import SpanContext

    tr = Tracer()
    remote = SpanContext("ab" * 16, "cd" * 8, True)
    other = SpanContext("ef" * 16, "12" * 8, True)
    with tr.span("server", parent=remote, links=[other]) as s:
        s.add_event("retry", attempt=1)
    d = tr.recent()[-1]
    assert d["traceId"] == "ab" * 16 and d["parentSpanId"] == "cd" * 8
    assert d["links"][0]["traceId"] == "ef" * 16
    assert d["events"][0]["name"] == "retry"
    assert d["events"][0]["attributes"]["attempt"] == 1


def test_sampling_rate_zero_and_inheritance():
    tr = Tracer(sample_rate=0.0)
    with tr.span("root") as root:
        assert not root.sampled
        with tr.span("child") as child:
            # the verdict is decided ONCE at the root and inherited
            assert not child.sampled and child.span_id == ""
    assert tr.recent() == []
    # an explicitly sampled remote parent overrides the local rate:
    # the caller already decided to trace this request
    from weaviate_tpu.monitoring.tracing import SpanContext

    with tr.span("server", parent=SpanContext("ab" * 16, "cd" * 8, True)):
        pass
    assert [s["name"] for s in tr.recent()] == ["server"]


def test_truncated_trace_synthesizes_placeholder_root():
    """Satellite: when the bounded buffer evicted a trace's root, the
    orphaned children must not be misattributed to group[0] as the root,
    and the duration must be the span extent — the trace is rendered
    under a synthesized placeholder and marked truncated."""
    tr = Tracer(max_spans=3)
    with tr.span("root2") as root:
        ctx = root.context
    # LOCAL children (parent passed as the Span, not a remote
    # SpanContext) finishing after the root pushed it out of maxlen=3
    with tr.span("c1", parent=root):
        pass
    with tr.span("c2", parent=root):
        pass
    with tr.span("c3", parent=root):
        pass
    # buffer holds c1..c3; root2 was evicted
    (trace,) = [t for t in tr.traces() if t["traceId"] == ctx.trace_id]
    assert trace["truncated"] is True
    assert trace["root"] == "(root evicted)"
    tree = tr.trace_tree(ctx.trace_id)
    assert tree["truncated"] and tree["tree"]["synthesized"]
    assert {c["name"] for c in tree["tree"]["children"]} == \
        {"c1", "c2", "c3"}
    # durationMs is the extent over the surviving spans, not a max over
    # disconnected subtree durations
    spans = tr.recent(limit=10, trace_id=ctx.trace_id)
    extent = (max(s["endTimeUnixNano"] for s in spans)
              - min(s["startTimeUnixNano"] for s in spans)) / 1e6
    assert abs(trace["durationMs"] - extent) < 0.01


def test_in_flight_trace_is_not_reported_truncated():
    """A trace whose root is still OPEN (finished children only in the
    buffer) is IN FLIGHT — exactly the slow request an operator queries
    mid-execution — and must not be misreported as '(root evicted)'."""
    tr = Tracer()
    root = tr.span("slow_request")
    root.__enter__()
    try:
        with tr.span("child"):
            pass
        (trace,) = [t for t in tr.traces()
                    if t["traceId"] == root.trace_id]
        assert trace["truncated"] is False and trace["inFlight"] is True
        assert trace["root"] == "(in flight)"
        tree = tr.trace_tree(root.trace_id)
        assert tree["tree"]["name"] == "(in flight)"
    finally:
        root.__exit__(None, None, None)
    # once the root finishes, the trace assembles normally
    tree = tr.trace_tree(root.trace_id)
    assert tree["root"] == "slow_request" and not tree["inFlight"]


def test_remote_parented_span_is_a_local_root_not_truncation():
    """A span continued from an incoming traceparent (or transport
    envelope) has a parent that lives in ANOTHER process — it must
    render as this process's legitimate root, never as '(root evicted)'
    with a truncated flag."""
    from weaviate_tpu.monitoring.tracing import SpanContext

    tr = Tracer()
    remote = SpanContext("ab" * 16, "cd" * 8, True)
    with tr.span("server", parent=remote):
        with tr.span("inner"):
            pass
    (trace,) = [t for t in tr.traces() if t["traceId"] == "ab" * 16]
    assert trace["truncated"] is False
    assert trace["root"] == "server"
    tree = tr.trace_tree("ab" * 16)
    assert tree["tree"]["name"] == "server"
    assert [c["name"] for c in tree["tree"]["children"]] == ["inner"]


def test_trace_tree_nests_children():
    tr = Tracer()
    with tr.span("root") as r:
        with tr.span("a"):
            with tr.span("a1"):
                pass
        with tr.span("b"):
            pass
    tree = tr.trace_tree(r.trace_id)
    assert not tree["truncated"]
    node = tree["tree"]
    assert node["name"] == "root"
    assert [c["name"] for c in node["children"]] == ["a", "b"]
    assert [c["name"] for c in node["children"][0]["children"]] == ["a1"]


def test_otlp_jsonl_export_shape():
    tr = Tracer()
    with tr.span("root", kind="test") as r:
        pass
    lines = tr.export_otlp_jsonl(r.trace_id).splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    span = rec["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
    assert span["name"] == "root" and span["traceId"] == r.trace_id
    assert {"key": "kind", "value": {"stringValue": "test"}} \
        in span["attributes"]
    res_attrs = rec["resourceSpans"][0]["resource"]["attributes"]
    assert {"key": "service.name",
            "value": {"stringValue": "weaviate_tpu"}} in res_attrs


def test_histogram_exemplar_tracks_worst():
    from weaviate_tpu.monitoring.metrics import Histogram

    h = Histogram("test_exemplar_seconds")
    h.observe(0.1, exemplar="t1", lane="x")
    h.observe(0.5, exemplar="t2", lane="x")
    h.observe(0.2, exemplar="t3", lane="x")
    h.observe(0.9, lane="x")  # no trace id: never displaces an exemplar
    assert h.exemplar(lane="x") == (0.5, "t2")
    ex = h.exemplars()
    assert ex['{lane="x"}'] == {"value": 0.5, "trace_id": "t2"}


def test_devtime_compile_vs_execute():
    from weaviate_tpu.monitoring import devtime
    from weaviate_tpu.monitoring.metrics import DEVICE_TIME_SECONDS

    devtime.reset()
    base = DEVICE_TIME_SECONDS.count(phase="compile", backend="B",
                                     scorer="S", mesh="single")
    assert devtime.record("B", "S", "single", (8, 16), 1.5) == "compile"
    assert devtime.record("B", "S", "single", (8, 16), 0.01) == "execute"
    # a new shape bucket recompiles
    assert devtime.record("B", "S", "single", (16, 16), 1.0) == "compile"
    assert DEVICE_TIME_SECONDS.count(
        phase="compile", backend="B", scorer="S", mesh="single") \
        == base + 2


def test_devtime_three_way_classification():
    """compile vs cache_hit vs execute: a first sighting whose bracket
    saw only persistent-cache HITS deserialized off disk (cache_hit);
    any miss — or no cache traffic at all — is a true compile."""
    from weaviate_tpu.monitoring import devtime
    from weaviate_tpu.monitoring.metrics import DEVICE_TIME_SECONDS
    from weaviate_tpu.utils import compile_cache

    devtime.reset()
    hit = "/jax/compilation_cache/cache_hits"
    miss = "/jax/compilation_cache/cache_misses"
    base_hit = DEVICE_TIME_SECONDS.count(phase="cache_hit", backend="B",
                                         scorer="S", mesh="single")
    # no cache events: conservative compile (cache disabled looks
    # exactly like this)
    assert devtime.record("B", "S", "single", (8, 8), 1.0) == "compile"
    # hits only across the bracket: disk deserialize, not a compile
    compile_cache._note_event(hit)
    compile_cache._note_event(hit)
    assert devtime.record("B", "S", "single", (16, 8), 0.05) \
        == "cache_hit"
    # the SAME identity after: steady state, whatever the cache did
    compile_cache._note_event(hit)
    assert devtime.record("B", "S", "single", (16, 8), 0.01) == "execute"
    # a miss anywhere in the bracket means XLA really compiled
    compile_cache._note_event(hit)
    compile_cache._note_event(miss)
    assert devtime.record("B", "S", "single", (32, 8), 0.8) == "compile"
    assert DEVICE_TIME_SECONDS.count(
        phase="cache_hit", backend="B", scorer="S", mesh="single") \
        == base_hit + 1
    # the debug surface sees first-sighting phases and running counts
    snap = devtime.snapshot()
    assert snap["B/S/single/(16, 8)"] == "cache_hit"
    assert snap["B/S/single/(32, 8)"] == "compile"
    counts = devtime.phase_counts()
    assert counts == {"compile": 2, "cache_hit": 1, "execute": 1}


def test_devtime_reset_reanchors_cache_mark():
    """Events fired before a reset must not classify the next fresh
    identity: reset re-anchors the delta mark at the current counters."""
    from weaviate_tpu.monitoring import devtime
    from weaviate_tpu.utils import compile_cache

    compile_cache._note_event("/jax/compilation_cache/cache_hits")
    devtime.reset()
    assert devtime.record("B2", "S", "single", (8, 8), 0.5) == "compile"


# -- runtime config ----------------------------------------------------------

def test_runtime_overrides_file_roundtrip(tmp_path):
    path = tmp_path / "overrides.json"
    rc = RuntimeConfig(path=str(path))
    knob = rc.register("ef_default", 64)
    assert knob.get() == 64
    path.write_text(json.dumps({"ef_default": 128, "unknown_key": 1}))
    assert rc.load_file() is True
    assert knob.get() == 128 and knob.overridden
    # removing the key falls back to the default
    path.write_text(json.dumps({}))
    rc._mtime = None  # force re-read despite fast mtime granularity
    rc.load_file()
    assert knob.get() == 64 and not knob.overridden


def test_runtime_overrides_malformed_file_keeps_values(tmp_path):
    path = tmp_path / "overrides.json"
    rc = RuntimeConfig(path=str(path))
    knob = rc.register("x", 1)
    path.write_text(json.dumps({"x": 5}))
    rc.load_file()
    assert knob.get() == 5
    path.write_text("{not json")
    rc._mtime = None
    assert rc.load_file() is False
    assert knob.get() == 5  # previous override retained


# -- CJK tokenization --------------------------------------------------------

def test_cjk_bigram_tokenization():
    assert tokenize("今日は良い天気", "gse") == [
        "今日", "日は", "は良", "良い", "い天", "天気"]
    # mixed CJK + latin: latin runs tokenize as words, order of appearance
    assert tokenize("GPU架构设计 rocks", "kagome_ja") == [
        "gpu", "架构", "构设", "设计", "rocks"]
    assert tokenize("中", "gse") == ["中"]
    assert tokenize("hello world", "gse") == ["hello", "world"]
    # halfwidth katakana indexes as CJK; fullwidth ASCII normalizes
    assert tokenize("ﾃｽﾄです", "kagome_ja") == ["ﾃｽ", "ｽﾄ", "ﾄで", "です"]
    assert tokenize("ＧＰＵ２ rocks", "gse") == ["gpu2", "rocks"]


def test_cjk_bm25_end_to_end(tmp_path):
    from weaviate_tpu.core.shard import Shard
    from weaviate_tpu.schema.config import (
        CollectionConfig, DataType, Property, Tokenization,
    )
    from weaviate_tpu.storage.objects import StorageObject

    cfg = CollectionConfig(
        name="Docs",
        properties=[Property(name="body", data_type=DataType.TEXT,
                             tokenization=Tokenization.GSE)],
    )
    s = Shard(str(tmp_path), cfg)
    s.put_batch([
        StorageObject(uuid=f"00000000-0000-0000-0000-{i:012d}",
                      collection="Docs", properties={"body": b})
        for i, b in enumerate(["今日は良い天気です", "機械学習の話", "良い本"])
    ])
    ids, scores = s.inverted.bm25_search("良い天気", k=3)
    assert len(ids) >= 1 and ids[0] == 0  # best match: the weather doc
    s.close()


def test_cjk_tokenizer_env_gate(tmp_path, monkeypatch, caplog):
    """gse/kagome_* schemes are rejected at schema validation unless the
    reference's enable flags are set (``entities/tokenizer/tokenizer.go``
    USE_GSE / ENABLE_TOKENIZER_*; ``usecases/schema/class.go:832``), and
    enabling them logs the bigram-approximation warning once."""
    import logging

    from weaviate_tpu.schema import config as cfgmod
    from weaviate_tpu.schema.config import (
        CollectionConfig, DataType, Property, Tokenization,
    )

    def cjk_cfg(name):
        return CollectionConfig(
            name=name,
            properties=[Property(name="body", data_type=DataType.TEXT,
                                 tokenization=Tokenization.GSE)])

    monkeypatch.delenv("ENABLE_TOKENIZER_GSE", raising=False)
    monkeypatch.delenv("USE_GSE", raising=False)
    with pytest.raises(ValueError, match="ENABLE_TOKENIZER_GSE"):
        cjk_cfg("Cjk").validate()
    # enabled: validates, and warns (once) that this is an approximation
    monkeypatch.setenv("ENABLE_TOKENIZER_GSE", "true")
    monkeypatch.setattr(cfgmod, "_CJK_WARNED", set())
    with caplog.at_level(logging.WARNING, logger="weaviate_tpu.schema"):
        cjk_cfg("Cjk").validate()
        cjk_cfg("Cjk2").validate()
    warns = [r for r in caplog.records if "bigrams" in r.getMessage()]
    assert len(warns) == 1  # once per scheme, not per class


# -- reindexer ---------------------------------------------------------------

def test_reindex_inverted_rebuilds_postings(tmp_path):
    from weaviate_tpu.core.shard import Shard
    from weaviate_tpu.schema.config import (
        CollectionConfig, DataType, Property,
    )
    from weaviate_tpu.storage.objects import StorageObject

    cfg = CollectionConfig(
        name="Docs",
        properties=[Property(name="body", data_type=DataType.TEXT)],
    )
    s = Shard(str(tmp_path), cfg)
    objs = [StorageObject(uuid=f"00000000-0000-0000-0000-{i:012d}",
                          collection="Docs",
                          properties={"body": f"alpha beta doc{i}"})
            for i in range(10)]
    s.put_batch(objs)
    s.delete([objs[3].uuid])
    n = s.reindex_inverted()
    assert n == 9  # deleted doc not reindexed
    ids, _ = s.inverted.bm25_search("alpha", k=20)
    assert len(ids) == 9 and objs[3].doc_id not in set(ids.tolist())
    ids, _ = s.inverted.bm25_search("doc5", k=5)
    assert ids[0] == objs[5].doc_id
    s.close()


# -- REST debug plane --------------------------------------------------------

def test_rest_debug_endpoints():
    from weaviate_tpu.api.rest import RestAPI
    from weaviate_tpu.core.db import DB
    from weaviate_tpu.monitoring.telemetry import Telemeter

    tmp = tempfile.mkdtemp()
    try:
        db = DB(tmp)
        api = RestAPI(db)
        api.telemeter = Telemeter(db, enabled=False)
        srv = api.serve(host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{srv.server_port}/v1"

        def get(path):
            with urllib.request.urlopen(base + path) as r:
                return json.loads(r.read())

        get("/schema")  # generates at least one span
        traces = get("/debug/traces")
        assert any(t["root"].startswith("rest.") for t in traces["traces"])
        cfgv = get("/debug/config")
        assert "slow_query_threshold_s" in cfgv["values"]
        tel = get("/debug/telemetry")
        assert tel["payload"]["num_collections"] == 0
        assert tel["payload"]["machine_id"]
        api.shutdown()
        db.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_trace_demo_smoke():
    """`make trace-demo` end to end against the in-proc server: the
    demo must boot, burst, and render a rest.graphql trace tree that
    reaches the dispatcher's batch span."""
    from tools.trace_demo import run

    lines: list[str] = []
    tree = run(out=lines.append)
    assert tree["root"] == "rest.graphql"
    joined = "\n".join(lines)
    assert "rest.graphql" in joined
    assert "qos.queue" in joined
    assert "dispatch.batch" in joined
    assert "└─" in joined  # the tree actually rendered as a tree


def test_telemetry_payload_counts():
    from weaviate_tpu.core.db import DB
    from weaviate_tpu.monitoring.telemetry import Telemeter
    from weaviate_tpu.schema.config import CollectionConfig
    from weaviate_tpu.storage.objects import StorageObject

    tmp = tempfile.mkdtemp()
    try:
        db = DB(tmp)
        col = db.create_collection(CollectionConfig(name="T"))
        col.put_batch([
            StorageObject(uuid=f"00000000-0000-0000-0000-{i:012d}",
                          collection="T", properties={},
                          vector=np.zeros(4, np.float32))
            for i in range(7)
        ])
        t = Telemeter(db, enabled=False)
        p = t.build_payload("INIT")
        assert p["num_collections"] == 1 and p["num_objects"] == 7
        assert p["type"] == "INIT" and p["version"]
        db.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_pprof_endpoints(tmp_path):
    import threading
    import urllib.request

    from weaviate_tpu.api.rest import RestAPI
    from weaviate_tpu.core.db import DB

    db = DB(str(tmp_path))
    api = RestAPI(db)
    srv = api.serve(host="127.0.0.1", port=0)
    base = f"http://127.0.0.1:{srv.server_port}"
    stop = threading.Event()

    def busy():  # give the sampler something to see
        while not stop.is_set():
            sum(i * i for i in range(1000))

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
                base + "/debug/pprof/profile?seconds=0.3", timeout=30) as r:
            body = r.read().decode()
        assert "stack samples" in body and "busy" in body
        with urllib.request.urlopen(
                base + "/debug/pprof/heap", timeout=10) as r:
            assert b"tracemalloc started" in r.read()
        with urllib.request.urlopen(
                base + "/debug/pprof/heap", timeout=10) as r:
            assert b"blocks" in r.read()
    finally:
        stop.set()
    api.shutdown()
    db.close()
