"""The interpreter's own readings (``monitoring/interp.py``) and the
tracer's after-the-fact ``record``: by count, structure and ratio, never by
an absolute wall-clock bound."""

import gc
import statistics
import sys
import threading
import time

import pytest

from weaviate_tpu.monitoring import tracing
from weaviate_tpu.monitoring.interp import SAMPLER, InterpreterSampler
from weaviate_tpu.monitoring.metrics import (
    GC_COLLECTIONS,
    GC_PAUSE_SECONDS,
    INTERPRETER_WAKE,
    TRACE_SPANS,
)
from weaviate_tpu.monitoring.tracing import Tracer


@pytest.fixture
def annotations(monkeypatch):
    opened = []
    real = tracing.TraceAnnotation

    def annotation(name):
        opened.append(name)
        return real(name)

    monkeypatch.setattr(tracing, "TraceAnnotation", annotation)
    return opened


@pytest.fixture
def sampler():
    tracer = Tracer(sample_rate=1.0)
    s = InterpreterSampler(tracer)
    s.start()
    yield s, tracer
    s.stop()


def _ticks(tracer, at_least: int, since: int = 0,
           enough=lambda ticks: True) -> list[dict]:
    """The tracer's ``interp.tick`` spans past the first ``since``, once
    there are ``at_least`` of them and they are ``enough``. The deadline
    only keeps a broken sampler from hanging the suite."""
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        ticks = [s for s in tracer.recent(limit=tracer.max_spans)
                 if s["name"] == "interp.tick"][since:]
        if len(ticks) >= at_least and enough(ticks):
            return ticks
        time.sleep(0.01)
    raise AssertionError(f"{len(ticks)} ticks past {since}: {ticks[-3:]}")


def _sampler_threads() -> int:
    return sum(t.name == "interp-tick" for t in threading.enumerate())


@pytest.mark.parametrize("case", ["root", "child", "unsampled_root",
                                  "unsampled_parent", "disabled"])
def test_record_writes_a_span_with_given_ends(case, annotations):
    tracer = Tracer(sample_rate=0.0 if case == "unsampled_root" else 1.0,
                    enabled=case != "disabled")
    parent = None
    if case == "child":
        with tracer.span("request") as parent:
            pass
    elif case == "unsampled_parent":
        tracer.sample_rate = 0.0
        with tracer.span("request") as parent:
            pass
        tracer.sample_rate = 1.0
    counted = TRACE_SPANS.value(name="late.span")
    opened = len(annotations)
    stack = tracing.current_span()
    tracer.record("late.span", 1_000, 3_500_000, parent=parent, bytes=7)
    assert len(annotations) == opened           # nothing to backdate
    assert tracing.current_span() is stack      # the context stack is left
    spans = [s for s in tracer.recent() if s["name"] == "late.span"]
    if case in ("root", "child"):
        (span,) = spans
        assert TRACE_SPANS.value(name="late.span") == counted + 1
        assert (span["startTimeUnixNano"], span["endTimeUnixNano"]) == \
            (1_000, 3_500_000)
        assert span["durationMs"] == 3.499
        assert span["attributes"] == {"bytes": 7}   # and no cpu_ms
        assert span["status"] == "OK"
        if case == "child":
            assert span["parentSpanId"] == parent.span_id
            assert span["traceId"] == parent.trace_id
            (trace,) = tracer.traces()
            assert trace["root"] == "request" and not trace["truncated"]
            assert len(trace["spans"]) == 2
        else:
            assert span["parentSpanId"] is None
            assert len(span["traceId"]) == 32
            (trace,) = tracer.traces()
            assert trace["root"] == "late.span"
    else:
        assert spans == []
        assert TRACE_SPANS.value(name="late.span") == counted
        assert tracer.open_span_ids() == set()


def test_a_tick_beside_threads_that_hold_the_lock_reads_the_wait(sampler):
    """Threads in pure Python give the lock up only when asked and only
    after the switch interval, so with that raised to 50 ms a tick waits
    tens of milliseconds where an idle one reads the timer's slack."""
    _, tracer = sampler
    observed = INTERPRETER_WAKE.count()
    idle = [t["durationMs"] for t in _ticks(tracer, 20)]
    stop = False

    def spin():
        x = 0
        while not stop:
            x += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.05)
    spinners = [threading.Thread(target=spin, daemon=True) for _ in range(2)]
    try:
        for t in spinners:
            t.start()
        busy_ticks = _ticks(tracer, 8, since=len(idle) + 1)
    finally:
        stop = True
        sys.setswitchinterval(interval)
        for t in spinners:
            t.join()
    busy = [t["durationMs"] for t in busy_ticks]
    assert statistics.median(busy) > 5 * statistics.median(idle), (idle, busy)
    # a wait longer than the period is ONE tick that says what it missed
    late = [t["attributes"]["late_ticks"] for t in busy_ticks]
    assert statistics.median(late) >= 1, late
    assert all(t["attributes"]["late_ticks"] == (
        t["endTimeUnixNano"] - t["startTimeUnixNano"]) // 10_000_000
        for t in busy_ticks)
    for t in busy_ticks:
        assert t["parentSpanId"] is None and "cpu_ms" not in t["attributes"]
    assert INTERPRETER_WAKE.count() >= observed + len(idle) + len(busy)
    # every tick is the root of its own one-span trace
    roots = {t["traceId"] for t in busy_ticks}
    assert len(roots) == len(busy_ticks)


def test_a_full_collection_is_a_root_span_linked_to_the_trace_it_met(sampler):
    _, tracer = sampler
    was_enabled = gc.isenabled()
    gc.disable()        # only the collection asked for below
    try:
        runs = GC_COLLECTIONS.value(generation="2")
        pause = GC_PAUSE_SECONDS.value(generation="2")
        seen = len(_ticks(tracer, 1))
        with tracer.span("request") as root:
            with tracer.span("layer") as layer:
                gc.collect()
                # the thread's current span is the request's own again
                assert tracing.current_span() is layer
            gc.collect(0)       # a young collection gets no span
        spans = tracer.recent(limit=tracer.max_spans)
        request = [s for s in spans if s["traceId"] == root.trace_id]
        assert sorted(s["name"] for s in request) == ["layer", "request"]
        (full,) = [s for s in spans if s["name"] == "gc.collect"]
        assert full["parentSpanId"] is None
        assert full["traceId"] != root.trace_id
        assert full["attributes"]["generation"] == 2
        assert full["attributes"]["collected"] >= 0
        assert [(ln["traceId"], ln["spanId"]) for ln in full["links"]] == \
            [(root.trace_id, layer.span_id)]
        assert layer.start_ns <= full["startTimeUnixNano"]
        assert full["endTimeUnixNano"] <= layer.end_ns
        # the tick after it carries the pause, and publishes the totals
        after = _ticks(tracer, 1, since=seen, enough=lambda ticks: sum(
            t["attributes"]["gc_runs"] for t in ticks) >= 2)
        assert sum(t["attributes"]["gc_runs"] for t in after) == 2
        assert sum(t["attributes"]["gc_ms"] for t in after) > 0
        assert GC_COLLECTIONS.value(generation="2") == runs + 1
        assert GC_PAUSE_SECONDS.value(generation="2") > pause
    finally:
        if was_enabled:
            gc.enable()


def test_a_collection_with_no_trace_under_way_has_no_link(sampler):
    _, tracer = sampler
    gc.collect()
    full = [s for s in tracer.recent(limit=tracer.max_spans)
            if s["name"] == "gc.collect"]
    assert full and all("links" not in s for s in full)


def test_start_and_stop_are_idempotent_and_leave_gc_callbacks_as_found():
    found = list(gc.callbacks)
    threads = _sampler_threads()
    s = InterpreterSampler(Tracer(sample_rate=1.0))
    s.stop()                                    # never started: nothing
    assert gc.callbacks == found
    s.start()
    s.start()
    assert len(gc.callbacks) == len(found) + 1
    assert _sampler_threads() == threads + 1
    s.stop()
    s.stop()
    assert gc.callbacks == found
    assert _sampler_threads() == threads
    s.start()                                   # and it starts again
    assert len(gc.callbacks) == len(found) + 1
    s.stop()
    assert gc.callbacks == found


def test_importing_the_package_and_opening_a_db_start_no_sampler(tmp_dbdir):
    import weaviate_tpu.server  # noqa: F401  (only main() starts it)
    from weaviate_tpu.core.db import DB

    db = DB(tmp_dbdir)
    try:
        assert SAMPLER._thread is None
        assert _sampler_threads() == 0
        assert not any(getattr(cb, "__self__", None) is SAMPLER
                       for cb in gc.callbacks)
    finally:
        db.close()


def test_import_alone_starts_no_thread_and_no_gc_callback():
    import os
    import subprocess
    from pathlib import Path

    child = ("import gc, threading\n"
             "import jax  # brings a callback of its own\n"
             "found = list(gc.callbacks)\n"
             "import weaviate_tpu, weaviate_tpu.monitoring.interp\n"
             "assert gc.callbacks == found, gc.callbacks\n"
             "assert [t.name for t in threading.enumerate()] == "
             "['MainThread'], threading.enumerate()\n"
             "print('NOTHING_STARTED')\n")
    repo = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=str(repo)))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "NOTHING_STARTED" in out.stdout
