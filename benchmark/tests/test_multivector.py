"""The multi-vector cell's comparison has to tell a wrong server from a sound
one: a whole rehearsed run ends in a rehearsal line, every request on the
fused tier, and its ``--control int8`` (the reference's MaxSim in int8, put in
the program's place) comes out not correct by the arithmetic alone
(``score_err``); a rehearsed run whose server serves the FDE order without the
rescore (``faulty_multivector_serve``) ends not correct by ``score_err`` and
``rank_gap``; and one whose server rescores a quarter of the candidates serves
exact scores in sorted order and still ends not correct, by what the
candidates cost (``recall_miss``). (The third fault, a fifth of the
repetitions, shows only at the cell's rows: at 2,000 passages two repetitions
still find a topic whole; its readings at 50,000 are in the configuration's
``what_a_breach_reads`` and in PERF.md.) Run by hand or with ``python3 -m pytest benchmark/tests -q``;
not part of the tier-1 suite (``tests/test_multivector_served.py`` is).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark.tests.test_faults import ROOT

CELL = "msmarco128.multivector_c20"


def rehearse(*extra: str, **env: str):
    """(exit code, the run's JSON lines)."""
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", CELL,
           "--seed", "2147484001", "--seconds", "2", "--trace", "0",
           "--rehearse", "--rows", "2000", *extra]
    p = subprocess.run(cmd, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
                       capture_output=True, text=True, timeout=600)
    return p.returncode, [json.loads(line)
                          for line in p.stdout.strip().splitlines()]


def test_sound_run_reads_correct_and_its_int8_control_does_not():
    rc, lines = rehearse("--control", "int8")
    assert rc == 0 and lines[-1]["rehearsal"] == "passed"
    (counted,) = [line for line in lines if line.get("phase") == "rerank"]
    assert list(counted["requests"]) == [
        '{module="rerank-maxsim",tier="fused"}']
    assert counted["fallbacks"] == {}
    (control,) = [line for line in lines if line.get("phase") == "control"]
    assert control["arithmetic"] == "int8" and control["correct"] is False
    got = control["compared"]["score_err"]
    assert got["value"] > 10 * got["limit"]
    exact = ("bad_hits", "short_answers", "unanswered", "order_gap")
    assert all(c["value"] <= c["limit"]
               for n, c in control["compared"].items() if n in exact)


def test_a_server_that_skips_the_rescore_reads_not_correct():
    rc, lines = rehearse("--serve-module",
                         "benchmark.tests.faulty_multivector_serve")
    assert rc == 1 and lines[-1]["rehearsal"] == "failed"
    compared = lines[-1]["compared"]
    assert compared["score_err"]["value"] > 0.1 > compared["score_err"]["limit"]
    assert compared["rank_gap"]["value"] > compared["rank_gap"]["limit"]
    assert compared["bad_hits"]["value"] == 0


def test_a_server_that_rescores_too_few_candidates_reads_not_correct():
    rc, lines = rehearse("--serve-module",
                         "benchmark.tests.faulty_multivector_serve",
                         BENCH_FAULT="few_candidates")
    assert rc == 1 and lines[-1]["rehearsal"] == "failed"
    compared = lines[-1]["compared"]
    # every score it serves is the exact MaxSim, in order: only the
    # candidates' quality gives it away
    exact = ("bad_hits", "short_answers", "unanswered", "rerank_fallbacks",
             "order_gap", "score_err")
    assert all(compared[n]["value"] <= compared[n]["limit"] for n in exact)
    assert compared["recall_miss"]["value"] > compared["recall_miss"]["limit"]
