"""Readings of the interpreter itself: the wait for its lock and the
collector's pauses (reference: the Go runtime's scheduler latency and
``go_gc_duration_seconds``, which every upstream node exports).

A daemon thread sleeps to a due instant every 10 ms and notes when it next
runs: the overshoot is what any thread pays to get the interpreter lock
back after a blocking call (a device result, a condition, a socket), plus
the timer's slack. Each tick is one ``interp.tick`` span, the root of its
own trace, from the due instant to the wake-up, and one observation of
``weaviate_tpu_interpreter_wake_seconds``. ``gc.callbacks`` times every
collection; a full one (generation 2) is also a ``gc.collect`` span on the
collecting thread. Nothing starts at import: ``server.main()`` starts
``SAMPLER`` once the listeners are up (docs/tracing.md).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional

from weaviate_tpu.monitoring.metrics import (
    GC_COLLECTIONS,
    GC_PAUSE_SECONDS,
    INTERPRETER_WAKE,
)
from weaviate_tpu.monitoring.tracing import (
    TRACER,
    Span,
    Tracer,
    current_context,
)

PERIOD_NS = 10_000_000


class InterpreterSampler:
    def __init__(self, tracer: Tracer = TRACER):
        self._tracer = tracer
        self._thread: Optional[threading.Thread] = None
        self._stopping = False
        # the collector's pause ns by generation, then its runs: written by
        # its callback alone (collections never nest) and under no lock,
        # since a collection can start between two bytecodes of a thread
        # that holds one. The tick publishes them.
        self._gc = [0] * 6
        self._gc_started_ns = 0
        self._gc_span: Optional[Span] = None

    def start(self) -> None:
        if self._thread is None:
            self._stopping = False
            gc.callbacks.append(self._on_gc)
            self._thread = threading.Thread(
                target=self._run, name="interp-tick", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        thread, self._thread = self._thread, None
        if thread is not None:
            self._stopping = True
            thread.join()
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if gen == 2:
                # a root with a link, never a child: a request's span count
                # must not depend on whether the collector ran inside it
                span = self._tracer.span("gc.collect", parent=None,
                                         generation=gen)
                self._gc_span = span.add_link(current_context()).__enter__()
            self._gc_started_ns = time.perf_counter_ns()
            return
        started, self._gc_started_ns = self._gc_started_ns, 0
        if started:     # else installed while a collection was under way
            self._gc[gen] += time.perf_counter_ns() - started
            self._gc[3 + gen] += 1
        span, self._gc_span = self._gc_span, None
        if span is not None:
            span.set(collected=info["collected"]).__exit__(None, None, None)

    def _run(self) -> None:
        seen = list(self._gc)
        due = time.perf_counter_ns() + PERIOD_NS
        while not self._stopping:
            time.sleep(max(0, due - time.perf_counter_ns()) / 1e9)
            wait = max(0, time.perf_counter_ns() - due)
            woke_unix = time.time_ns()
            now = list(self._gc)
            late = wait // PERIOD_NS
            self._tracer.record(
                "interp.tick", woke_unix - wait, woke_unix,
                gc_ms=round((sum(now[:3]) - sum(seen[:3])) / 1e6, 3),
                gc_runs=sum(now[3:]) - sum(seen[3:]), late_ticks=late)
            INTERPRETER_WAKE.observe(wait / 1e9)
            for gen in range(3):
                if (now[gen], now[3 + gen]) != (seen[gen], seen[3 + gen]):
                    GC_PAUSE_SECONDS.inc((now[gen] - seen[gen]) / 1e9,
                                         generation=str(gen))
                    GC_COLLECTIONS.inc(now[3 + gen] - seen[3 + gen],
                                       generation=str(gen))
            seen = now
            # the grid stands: a wait of 35 ms is one tick of 35 ms with
            # three periods missed, not four ticks
            due += PERIOD_NS * (1 + late)


SAMPLER = InterpreterSampler()
