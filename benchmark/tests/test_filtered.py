"""The filtered cell's comparison has to tell a wrong server from a sound
one: a whole rehearsed run whose server ignores the filter ends not correct
by ``filter_violations``, one that drops the last hit by ``short_answers``;
the filtered reference in int8 put in the program's place fails the cell's
limits and at the stated precision passes them (pure numpy); and a rehearsed
run with ``--control int8`` says so of the cell itself. Run by hand or with
``python3 -m pytest benchmark/tests -q``; not part of the tier-1 suite.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmark import reference, reference_filtered
from benchmark.tests.test_faults import ROOT

CELL = "yfcc192.filtered_c20"


def rehearse(workload: str, *extra: str, fault: str = ""):
    """(exit code, the run's JSON lines)."""
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", workload,
           "--seed", "2147484001", "--seconds", "2", "--trace", "0",
           "--rehearse", "--rows", "3000", *extra]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if fault:
        cmd += ["--serve-module", "benchmark.tests.faulty_filtered_serve"]
        env["BENCH_FAULT"] = fault
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    return p.returncode, [json.loads(line)
                          for line in p.stdout.strip().splitlines()]


@pytest.mark.parametrize("fault,number", [
    ("ignore_filter", "filter_violations"),
    ("drop_last_hit", "short_answers"),
])
def test_filter_fault_reads_not_correct(fault, number):
    rc, lines = rehearse(CELL, fault=fault)
    assert rc == 1 and lines[-1]["rehearsal"] == "failed"
    got = lines[-1]["compared"][number]
    assert got["value"] > got["limit"]


def test_sound_run_reads_correct_and_its_int8_control_does_not():
    rc, lines = rehearse(CELL, "--control", "int8")
    assert rc == 0 and lines[-1]["rehearsal"] == "passed"
    (control,) = [line for line in lines if line.get("phase") == "control"]
    assert control["arithmetic"] == "int8" and control["correct"] is False
    exact = ("bad_hits", "filter_violations", "short_answers", "unanswered")
    assert all(c["value"] <= c["limit"]
               for n, c in control["compared"].items() if n in exact)


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_filtered_int8_control_fails_and_bf16_passes(seed):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        work = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           work["config"] + ".json")) as f:
        cfg = json.load(f)
    rows, k = 20000, cfg["k"]
    corpus = reference.make_rows(cfg["data"], cfg["dims"], rows, seed)
    bag_rows = reference_filtered.make_bags(
        cfg["data"]["tags"], cfg["vocabulary"] * rows // cfg["rows"], rows,
        seed)
    bags = reference_filtered.Bags(bag_rows)
    queries, made_from = reference.make_queries(
        cfg["data"], corpus, 128, seed)
    filters = reference_filtered.make_filters(
        work["traffic"]["tags_per_query"], bag_rows, made_from, seed)
    used = list(range(len(queries)))
    scan = reference.Scan(cfg["distance"], corpus)
    limits = {n: v for n, v in work["limits"].items() if n != "unanswered"}
    d, i = reference_filtered.topk_allowed(scan, bags, queries, filters, k)
    sound = reference_filtered.compare_answers(
        scan, bags, queries, filters, k,
        [(q, i[q][i[q] >= 0], d[q][i[q] >= 0]) for q in used])
    ok, compared = reference.verdict(sound, limits)
    assert ok, compared
    assert sound["allowed_rows"]["share_at_most_k"] > 0     # short answers
    control = reference_filtered.compare_answers(
        scan, bags, queries, filters, k, reference_filtered.control_answers(
            cfg["distance"], corpus, bags, queries, filters, k, used))
    ok, compared = reference.verdict(control, limits)
    assert not ok, compared
    assert all(compared[n]["value"] == 0 for n in (
        "bad_hits", "filter_violations", "short_answers"))
