"""The seven metrics that read the interpreter's spans (``interp.tick``,
``grpc.send``): the ``span_sum`` reader on hand-made evidence, the data
files, and a traced rehearsal of one search cell and the import cell in
which each of them finds something to read. Run with ``python3 -m pytest
benchmark/tests -q``; not part of the tier-1 suite.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import selfcheck
from benchmark.readers import span_sum, spans
from benchmark.run import ROOT, cell_metrics, load_json

NEW = {
    "search": ["interp_wake_ms.search", "interp_wake_p95_ms.search",
               "server_resident_ms.search", "gc_pause_share.search"],
    "import": ["interp_wake_ms.import", "server_resident_ms.import",
               "gc_pause_share.import"],
}
GC = {"span": "interp.tick", "root": "interp.tick",
      "field": "attributes.gc_ms"}


def tick(gc_ms, wait_ms=0.2):
    return {"name": "interp.tick", "root": "interp.tick",
            "durationMs": wait_ms,
            "attributes": {"gc_ms": gc_ms, "gc_runs": int(gc_ms > 0),
                           "late_ticks": 0}}


def test_span_sum_is_the_fields_share_of_the_window():
    other = {"name": "grpc.send", "root": "grpc.Search", "durationMs": 0.3,
             "attributes": {"resident_ms": 30.0, "gc_ms": 1e6}}
    evidence = {"trace_info": {"window_s": 4.0},
                "spans": [tick(0.0), tick(30.0), other, tick(0.0),
                          tick(90.0)]}
    assert span_sum.read(GC, evidence) == pytest.approx(3.0)    # 120 / 4,000
    evidence["spans"] = [tick(0.0), other, tick(0.0)]
    assert span_sum.read(GC, evidence) == 0.0       # ticks, no collection
    evidence["spans"] = [other]
    assert span_sum.read(GC, evidence) is None      # the parent: no tick
    assert span_sum.read(GC, {"spans": [tick(5.0)]}) is None    # no window
    assert span_sum.read(GC, {}) is None


def test_the_spans_reader_reads_a_ticks_wait_and_a_calls_residency():
    evidence = {"spans": [tick(0.0, 0.2), tick(0.0, 0.4), tick(0.0, 9.0), {
        "name": "grpc.send", "root": "grpc.Search", "durationMs": 0.3,
        "attributes": {"resident_ms": 30.0}}]}
    wake = load_json("benchmark", "metrics", "interp_wake_ms.search.json")
    assert spans.read(wake["params"], evidence) == 0.4
    resident = load_json("benchmark", "metrics",
                         "server_resident_ms.search.json")
    assert spans.read(resident["params"], evidence) == 30.0
    batch = load_json("benchmark", "metrics",
                      "server_resident_ms.import.json")
    assert spans.read(batch["params"], evidence) is None


@pytest.mark.parametrize("name", NEW["search"] + NEW["import"])
def test_a_new_metric_file_names_a_reader_and_its_cells(name):
    bench = load_json("BENCHMARK.json")
    spec = load_json("benchmark", "metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    kind = name.rsplit(".", 1)[1]
    e2e = {"search": "search_p95_ms", "import": "import_p95_ms"}[kind]
    assert entry["moves"] == spec["moves"] == e2e
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == e2e]
    assert entry["workloads"] == moved["workloads"]
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in cell_metrics(bench, cell)[1]}


def test_selfcheck_finds_every_file_by_name():
    selfcheck.check_files()


@pytest.mark.parametrize("workload,kind", [
    ("cohere768.search_c1", "search"), ("sift128.import_c4", "import")])
def test_a_traced_rehearsal_reports_the_new_metrics(workload, kind):
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", "2147487011", "--seconds", "2", "--trace", "1",
         "--rehearse", "--rows", "3000"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed"
    # a metric is listed only where its reader found a number
    assert set(NEW[kind]) <= set(line["reported"]), line["reported"]
    other = NEW["import" if kind == "search" else "search"]
    assert not set(other) & set(line["reported"])
