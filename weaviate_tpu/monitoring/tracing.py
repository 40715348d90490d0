"""Request tracing: span trees with timings, links, events and propagation.

Reference: the reference wires OpenTelemetry tracing through its whole
handler chain (``adapters/handlers/rest/middlewares``) and exposes pprof
profiles (``adapters/handlers/debug``). Zero-egress equivalent: an
in-process tracer with bounded retention, OTLP-shaped JSON export, and a
``/v1/debug/traces`` endpoint. Spans nest via a context-local stack, so
instrumented layers (REST -> QoS -> Collection -> dispatcher -> kernel)
compose without passing handles around; layers that hop threads
(collection scatter pools, the cluster replica fan-out) re-activate the
request's span explicitly (``use_span`` / ``serving.context``).

Cross-process propagation follows the W3C trace-context shape: a
``traceparent`` header (``00-<trace_id>-<span_id>-<flags>``) travels in
and out of REST/gRPC ingress and rides the cluster transport's msgpack
envelope (``_trace`` key), so a replica RPC handled on another node
continues the ingress trace.

Sampling: the ``tracing_sample_rate`` runtime knob (default 1.0) decides
per-TRACE at the root; children inherit the verdict. An unsampled span
is a real object (so nesting and inheritance stay uniform) but skips id
generation, attribute work, and retention — near-zero overhead. Hot
paths that must add literally nothing (the coalescing dispatcher) check
``span.sampled``/``current_span()`` before creating anything.

Two clocks. A sampled span entered with ``with`` also opens a
``jax.profiler.TraceAnnotation`` of the same name on the same thread, so
while a profile session runs the span appears in the trace's host plane,
on the profiler's clock, beside the device's operations (``benchmark/
xplane.py`` names a device's idle gaps from those lines). With no session
the annotation is a level check and a return. Where the thread's CPU clock
is cheap (``THREAD_CLOCK``), the span also records the thread's CPU time
between enter and exit as ``cpu_ms``: wall minus CPU is what the layer
spent waiting (interpreter lock, device, disk). A span whose ends lie on
different threads, or before the recording thread ran, is written after
the fact with ``Tracer.record`` (``grpc.send``, ``interp.tick``): it has
the host's clock only — an annotation cannot be backdated — and no
``cpu_ms``.
"""

from __future__ import annotations

import contextvars
import json
import random
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Iterator, NamedTuple, Optional

# importing jax.profiler initialises no backend (tests/test_import_no_backend)
from jax.profiler import TraceAnnotation

from weaviate_tpu.monitoring.metrics import TRACE_SPANS

_current_span: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("wv_current_span", default=None)

_UNSET = object()

# the traced segment of a benchmark cell has to fit whole: ~2,400 search
# requests x 9 spans and ~430 ``interp.tick`` in the ~4.3 s of the busiest
# cell today, ~22,000 in all (PERF.md section 3)
MAX_SPANS = 32768


def _thread_clock_is_cheap() -> bool:
    """Whether ``time.thread_time_ns`` is worth two reads a span. On a
    plain Linux host it is ~0.3 us a call at nanosecond steps; on the
    sealed machines that hold the benchmark's chip it is a 5.9 us call
    that ticks in 10 ms steps (probe on the chip, PR 26), which cost
    ``cohere768.search_c20`` 8% of its queries per second and told nothing.
    The cheapest of five rounds decides, so a preempted round cannot."""
    cheapest = float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(10):
            time.thread_time_ns()
        cheapest = min(cheapest, (time.perf_counter_ns() - t0) / 10)
    return cheapest < 2000


THREAD_CLOCK = _thread_clock_is_cheap()


def _span_id() -> str:
    return "%016x" % random.getrandbits(64)


def _trace_id() -> str:
    return "%032x" % random.getrandbits(128)


class SpanContext(NamedTuple):
    """The portable identity of a span: enough to parent or link a child
    across threads and processes."""

    trace_id: str
    span_id: str
    sampled: bool = True

    @property
    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id, self.sampled)


def format_traceparent(trace_id: str, span_id: str, sampled: bool) -> str:
    """W3C trace-context header: version 00, 32-hex trace id, 16-hex
    parent span id, flags (01 = sampled)."""
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def parse_traceparent(header: str) -> Optional[SpanContext]:
    """Parse a ``traceparent`` header; None when absent or malformed (a
    bad header starts a fresh trace, it never fails the request)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    _ver, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
        sampled = bool(int(flags, 16) & 0x01)
    except ValueError:
        return None
    return SpanContext(trace_id, span_id, sampled)


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start_ns",
                 "end_ns", "attributes", "status", "sampled", "links",
                 "events", "remote_parent", "_token", "_tracer",
                 "_annotation", "_cpu_ns")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: Optional[str], sampled: bool = True,
                 remote_parent: bool = False):
        # remote_parent: the parent span lives in ANOTHER process (an
        # incoming traceparent / transport envelope) — this span is a
        # legitimate local root, not an eviction orphan
        self.remote_parent = remote_parent
        self._tracer = tracer
        self.name = name
        self.sampled = sampled
        self.trace_id = trace_id
        # unsampled spans exist only to propagate the verdict down the
        # context stack: no ids, no retention, (almost) no work
        self.span_id = _span_id() if sampled else ""
        self.parent_id = parent_id
        self.start_ns = time.time_ns() if sampled else 0
        self.end_ns: Optional[int] = None
        self.attributes: dict[str, Any] = {}
        self.links: Optional[list[dict]] = None
        self.events: Optional[list[dict]] = None
        self.status = "OK"
        self._token = None
        self._annotation = None
        # None: recorded after the fact, no CPU reading (Tracer.record)
        self._cpu_ns: Optional[int] = 0

    def set(self, **attrs) -> "Span":
        if self.sampled:
            self.attributes.update(attrs)
        return self

    def add_event(self, name: str, **attrs) -> "Span":
        """Timestamped point-in-time annotation (retry attempts, breaker
        skips, dispatcher sheds)."""
        if self.sampled:
            if self.events is None:
                self.events = []
            self.events.append({
                "name": name,
                "timeUnixNano": time.time_ns(),
                "attributes": attrs,
            })
        return self

    def add_link(self, ctx: Optional[SpanContext], **attrs) -> "Span":
        """Link another trace's span (the N:1 batch<-requests relation)."""
        if self.sampled and ctx is not None:
            if self.links is None:
                self.links = []
            self.links.append({
                "traceId": ctx.trace_id,
                "spanId": ctx.span_id,
                "attributes": attrs,
            })
        return self

    @property
    def context(self) -> Optional[SpanContext]:
        if not self.sampled:
            return None
        return SpanContext(self.trace_id, self.span_id, True)

    @property
    def traceparent(self) -> str:
        return format_traceparent(self.trace_id or "0" * 32,
                                  self.span_id or "0" * 16, self.sampled)

    def __enter__(self) -> "Span":
        self._token = _current_span.set(self)
        if self.sampled:
            # open-span registry: lets the assembler tell "parent still
            # executing" apart from "parent evicted from the buffer"
            self._tracer._open.add(self.span_id)
            self._annotation = TraceAnnotation(self.name)
            self._annotation.__enter__()
            if THREAD_CLOCK:
                self._cpu_ns = time.thread_time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.sampled:
            self.status = "ERROR"
            self.attributes["error"] = repr(exc)
        if self.sampled:
            if THREAD_CLOCK:
                self._cpu_ns = time.thread_time_ns() - self._cpu_ns
            self.end_ns = time.time_ns()
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self._token is not None:
            _current_span.reset(self._token)
            self._token = None
        self._tracer._finish(self)

    @property
    def duration_ms(self) -> float:
        end = self.end_ns or time.time_ns()
        return (end - self.start_ns) / 1e6

    def to_dict(self) -> dict:
        if (THREAD_CLOCK and self.end_ns is not None
                and self._cpu_ns is not None):
            self.attributes["cpu_ms"] = round(self._cpu_ns / 1e6, 3)
        out = {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentSpanId": self.parent_id,
            "name": self.name,
            "startTimeUnixNano": self.start_ns,
            "endTimeUnixNano": self.end_ns,
            "durationMs": round(self.duration_ms, 3),
            "attributes": self.attributes,
            "status": self.status,
        }
        if self.remote_parent:
            out["remoteParent"] = True
        if self.links:
            out["links"] = self.links
        if self.events:
            out["events"] = self.events
        return out


class _NoSpan:
    """What ``Tracer.child`` hands out where no trace is under way: takes
    attributes, records nothing and leaves the thread's current span alone
    (a ``Tracer.span`` nested inside still mints its own root, as before)."""

    sampled = False

    def set(self, **attrs) -> "_NoSpan":
        return self

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NO_SPAN = _NoSpan()


class Tracer:
    """Bounded-retention tracer; disabled/unsampled = near-zero overhead."""

    def __init__(self, max_spans: int = MAX_SPANS, enabled: bool = True,
                 sample_rate: Optional[float] = None):
        self.enabled = enabled
        self.max_spans = max_spans
        # None = follow the tracing_sample_rate runtime knob; a float
        # pins it (unit tests, the bench harness)
        self.sample_rate = sample_rate
        self._rng = random.Random()
        # No lock on the span path. Twenty request threads finishing spans
        # under one tracer lock convoyed on it (a holder that loses the
        # interpreter lock stalls every other finisher): 8 us a span
        # became 40-50 (host count, PR 26). A finished Span is appended as
        # it is — deque.append with maxlen evicts in O(1) and, like set.add
        # / discard and list(deque), is one atomic step under the
        # interpreter lock — and turned into a dict when somebody reads.
        self._spans: deque[Span] = deque(maxlen=max_spans)
        # span ids currently OPEN (entered, not finished): finished
        # children whose parent is here belong to an in-flight trace,
        # not a truncated one
        self._open: set[str] = set()

    def open_span_ids(self) -> set:
        return set(self._open)

    # -- sampling ----------------------------------------------------------
    def _rate(self) -> float:
        if self.sample_rate is not None:
            return self.sample_rate
        from weaviate_tpu.utils.runtime_config import TRACING_SAMPLE_RATE

        return float(TRACING_SAMPLE_RATE.get())

    def _sample(self) -> bool:
        rate = self._rate()
        if rate >= 1.0:
            return True
        if rate <= 0.0:
            return False
        return self._rng.random() < rate

    # -- span creation -----------------------------------------------------
    def span(self, name: str, parent=_UNSET,
             links: Optional[list] = None, **attrs) -> Span:
        """Child of ``parent`` (default: the context-active span), or a
        new root — which draws the sampling verdict for its whole trace.
        ``parent`` may be a Span, a SpanContext (remote parent), or
        None (force a new root)."""
        if parent is _UNSET:
            parent = _current_span.get()
        if isinstance(parent, Span):
            s = Span(self, name, parent.trace_id, parent.span_id or None,
                     sampled=parent.sampled)
        elif isinstance(parent, SpanContext):
            s = Span(self, name, parent.trace_id, parent.span_id,
                     sampled=parent.sampled, remote_parent=True)
        else:
            sampled = self._sample()
            s = Span(self, name,
                     _trace_id() if sampled else "", None,
                     sampled=sampled)
        if s.sampled:
            if attrs:
                s.attributes.update(attrs)
            if links:
                for ctx in links:
                    s.add_link(ctx)
        return s

    def child(self, name: str, **attrs):
        """A span only as part of a trace that is under way on this thread:
        the layer boundaries deep in the served path (``index.search``,
        ``shard.durable``, ...). With no active span — direct library use, a
        background thread — they must not each mint a one-span trace of
        their own (4,000 of them pushed a test's real traces out of the
        buffer), so the caller gets a span that does nothing."""
        parent = _current_span.get()
        if parent is None:
            return _NO_SPAN
        return self.span(name, parent=parent, **attrs)

    def ingress(self, name: str, traceparent: str = "", **attrs) -> Span:
        """Root-of-request span minted at REST/gRPC ingress: continues an
        incoming ``traceparent`` (honoring its sampled flag) or starts a
        fresh trace under the sampling knob."""
        remote = parse_traceparent(traceparent)
        if remote is not None:
            return self.span(name, parent=remote, **attrs)
        return self.span(name, parent=None, **attrs)

    def _finish(self, span: Span) -> None:
        if not span.sampled:
            return
        self._open.discard(span.span_id)
        if self.enabled:
            TRACE_SPANS.inc(name=span.name)
            self._spans.append(span)

    def record(self, name: str, start_ns: int, end_ns: int,
               parent: Optional[Span] = None, **attrs) -> None:
        """A span with given ends (unix ns), recorded after the fact: for
        an interval whose ends lie on different threads or before the
        recording thread ran. A child of ``parent`` (open or closed) under
        the parent's sampling verdict, or with none the root of a one-span
        trace under a verdict of its own. Counted and retained as
        ``_finish`` does; no ``TraceAnnotation`` and no ``cpu_ms`` (neither
        can be backdated), and the context stack is left alone."""
        if not self.enabled:
            return
        if parent is None:
            if not self._sample():
                return
            span = Span(self, name, _trace_id(), None)
        elif parent.sampled:
            span = Span(self, name, parent.trace_id, parent.span_id)
        else:
            return
        span.start_ns, span.end_ns, span._cpu_ns = start_ns, end_ns, None
        span.attributes = attrs
        TRACE_SPANS.inc(name=name)
        self._spans.append(span)

    # -- export ------------------------------------------------------------
    def recent(self, limit: int = 100,
               trace_id: Optional[str] = None) -> list[dict]:
        spans = list(self._spans)
        if trace_id:
            spans = [s for s in spans if s.trace_id == trace_id]
        return [s.to_dict() for s in spans[-limit:]]

    @staticmethod
    def _assemble(group: list[dict], open_ids: set) -> dict:
        """Root + duration + truncation verdict for one trace's spans.
        A root is a span with no parent OR whose parent was evicted from
        the bounded buffer; with no true root left the trace is rendered
        under a synthesized placeholder and marked ``truncated`` —
        orphans must never masquerade as the request root, and the
        duration is the span EXTENT (min start .. max end), not a max
        over disconnected subtree durations. A missing parent that is
        still OPEN (``open_ids``) means the trace is IN FLIGHT — a slow
        request queried mid-execution — not evicted."""
        ids = {s["spanId"] for s in group}
        # a span whose parent lives in ANOTHER process (remoteParent:
        # incoming traceparent, transport envelope) is a legitimate
        # LOCAL root when that parent was never recorded here — only a
        # local parent missing from the buffer means eviction
        true_roots = [s for s in group
                      if s["parentSpanId"] is None
                      or (s.get("remoteParent")
                          and s["parentSpanId"] not in ids)]
        orphans = [s for s in group
                   if s["parentSpanId"] is not None
                   and s["parentSpanId"] not in ids
                   and not s.get("remoteParent")]
        pending = [s for s in orphans if s["parentSpanId"] in open_ids]
        evicted = [s for s in orphans
                   if s["parentSpanId"] not in open_ids]
        start = min(s["startTimeUnixNano"] for s in group)
        end = max(s["endTimeUnixNano"] or s["startTimeUnixNano"]
                  for s in group)
        if true_roots:
            root_name = true_roots[0]["name"]
        elif pending and not evicted:
            root_name = "(in flight)"
        else:
            root_name = "(root evicted)"
        return {
            "root": root_name,
            # an EVICTED subtree means the buffer dropped part of this
            # trace — the duration/shape below is a lower bound, say so;
            # an in-flight parent is normal operation, not truncation
            "truncated": bool(evicted),
            "in_flight": bool(pending),
            "durationMs": round((end - start) / 1e6, 3),
            "true_roots": true_roots,
            "orphans": orphans,
        }

    def traces(self, limit: int = 20) -> list[dict]:
        """Assembled span trees, newest first (root span + children)."""
        # list(deque) first: one atomic snapshot, then the dicts
        spans = [s.to_dict() for s in list(self._spans)]
        by_trace: dict[str, list[dict]] = {}
        order: list[str] = []
        for s in spans:
            if s["traceId"] not in by_trace:
                order.append(s["traceId"])
            by_trace.setdefault(s["traceId"], []).append(s)
        open_ids = self.open_span_ids()
        out = []
        for tid in reversed(order[-limit:]):
            group = by_trace[tid]
            meta = self._assemble(group, open_ids)
            out.append({
                "traceId": tid,
                "root": meta["root"],
                "truncated": meta["truncated"],
                "inFlight": meta["in_flight"],
                "durationMs": meta["durationMs"],
                "spans": group,
            })
        return out

    def trace_tree(self, trace_id: str) -> Optional[dict]:
        """One trace rendered as a nested tree (children under parents,
        ordered by start time). Evicted ancestors are represented by a
        synthesized ``(root evicted)`` placeholder so orphaned subtrees
        stay visible and correctly grouped."""
        group = self.recent(limit=self.max_spans, trace_id=trace_id)
        if not group:
            return None
        meta = self._assemble(group, self.open_span_ids())
        children: dict[Optional[str], list[dict]] = {}
        ids = {s["spanId"] for s in group}
        root_ids = {s["spanId"] for s in meta["true_roots"]}
        for s in group:
            if s["spanId"] in root_ids:
                continue  # roots (incl. remote-parented) render top-level
            pid = s["parentSpanId"]
            if pid is not None and pid not in ids:
                pid = "(evicted)"
            children.setdefault(pid, []).append(s)

        def build(span: dict) -> dict:
            node = dict(span)
            kids = children.get(span["spanId"], [])
            node["children"] = [build(k)
                                for k in sorted(
                                    kids,
                                    key=lambda s: s["startTimeUnixNano"])]
            return node

        def placeholder(kids: list[dict], label: str) -> dict:
            return {
                "name": label,
                "traceId": trace_id,
                "spanId": "(evicted)",
                "synthesized": True,
                "durationMs": meta["durationMs"],
                "children": [build(k) for k in sorted(
                    kids, key=lambda s: s["startTimeUnixNano"])],
            }

        true_roots = sorted(meta["true_roots"],
                            key=lambda s: s["startTimeUnixNano"])
        if not true_roots:
            # the real root is missing: still OPEN (in-flight trace,
            # finished children only) or evicted from the bounded
            # buffer — orphaned subtrees render under a synthesized
            # placeholder either way, labeled accordingly
            tree = placeholder(meta["orphans"], meta["root"])
        else:
            tree = build(true_roots[0])
            for extra in true_roots[1:]:  # multi-root trace: siblings
                tree.setdefault("siblings", []).append(build(extra))
            if meta["orphans"]:
                # a MIDDLE ancestor is missing: keep its subtrees
                # visible instead of silently dropping them
                tree.setdefault("siblings", []).append(placeholder(
                    meta["orphans"],
                    "(root evicted)" if meta["truncated"]
                    else "(in flight)"))
        return {
            "traceId": trace_id,
            "root": meta["root"],
            "truncated": meta["truncated"],
            "inFlight": meta["in_flight"],
            "durationMs": meta["durationMs"],
            "spanCount": len(group),
            "tree": tree,
        }

    # OTLP-shaped export: the ResourceSpans JSON shape OTLP/HTTP uses,
    # one line per span batch, importable by any OTLP-tolerant tool.
    def _otlp_record(self, spans: list[dict]) -> dict:
        def enc_attrs(attrs: dict) -> list[dict]:
            return [{"key": k, "value": {"stringValue": str(v)}}
                    for k, v in attrs.items()]

        otlp_spans = []
        for s in spans:
            rec = {
                "traceId": s["traceId"],
                "spanId": s["spanId"],
                "name": s["name"],
                "startTimeUnixNano": str(s["startTimeUnixNano"]),
                "endTimeUnixNano": str(s["endTimeUnixNano"] or 0),
                "kind": "SPAN_KIND_INTERNAL",
                "attributes": enc_attrs(s.get("attributes", {})),
                "status": {"code": "STATUS_CODE_ERROR"
                           if s["status"] == "ERROR" else "STATUS_CODE_OK"},
            }
            if s["parentSpanId"]:
                rec["parentSpanId"] = s["parentSpanId"]
            if s.get("links"):
                rec["links"] = [{
                    "traceId": ln["traceId"], "spanId": ln["spanId"],
                    "attributes": enc_attrs(ln.get("attributes", {})),
                } for ln in s["links"]]
            if s.get("events"):
                rec["events"] = [{
                    "name": ev["name"],
                    "timeUnixNano": str(ev["timeUnixNano"]),
                    "attributes": enc_attrs(ev.get("attributes", {})),
                } for ev in s["events"]]
            otlp_spans.append(rec)
        return {
            "resourceSpans": [{
                "resource": {"attributes": enc_attrs(
                    {"service.name": "weaviate_tpu"})},
                "scopeSpans": [{
                    "scope": {"name": "weaviate_tpu.monitoring.tracing"},
                    "spans": otlp_spans,
                }],
            }],
        }

    def export_otlp_jsonl(self, trace_id: str) -> str:
        """One trace as OTLP-shaped JSONL: one ResourceSpans line per
        span (streaming-friendly; ``cat | jq`` works line by line)."""
        spans = self.recent(limit=self.max_spans, trace_id=trace_id)
        return "".join(json.dumps(self._otlp_record([s])) + "\n"
                       for s in spans)

    def clear(self) -> None:
        self._spans.clear()


# -- context helpers (the thread-hop API layers use) ------------------------

def current_span() -> Optional[Span]:
    return _current_span.get()


def current_context() -> Optional[SpanContext]:
    s = _current_span.get()
    return s.context if s is not None else None


def current_trace_id() -> str:
    """Trace id of the active sampled span, "" otherwise — the exemplar
    feed for histograms and slow-query logs."""
    s = _current_span.get()
    return s.trace_id if s is not None and s.sampled else ""


def current_traceparent() -> str:
    s = _current_span.get()
    return s.traceparent if s is not None and s.sampled else ""


def annotate(**attrs) -> None:
    """Set attributes on the active span; no-op when unsampled/absent."""
    s = _current_span.get()
    if s is not None and s.sampled:
        s.attributes.update(attrs)


def add_event(name: str, **attrs) -> None:
    s = _current_span.get()
    if s is not None and s.sampled:
        s.add_event(name, **attrs)


def activate(span: Optional[Span]):
    """Install an ALREADY-OPEN span as this thread's current span (the
    pool-thread re-entry path); returns a token for ``deactivate``."""
    if span is None:
        return None
    return _current_span.set(span)


def detach():
    """Clear this thread's current span (returns a token for
    ``deactivate``): for code that runs on the caller's thread but does
    work the caller's span must NOT absorb — e.g. a dispatcher leader
    draining a batch that belongs to OTHER requests."""
    return _current_span.set(None)


def deactivate(token) -> None:
    if token is not None:
        _current_span.reset(token)


@contextmanager
def use_span(span: Optional[Span]) -> Iterator[Optional[Span]]:
    """Re-activate a span captured in another thread without finishing
    it — the worker-pool analogue of ``with span:``."""
    token = activate(span)
    try:
        yield span
    finally:
        deactivate(token)


# process-wide default tracer (REST wires its endpoints to this)
TRACER = Tracer()
