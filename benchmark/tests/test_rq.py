"""The compressed flat cell's comparison has to tell a wrong server from a
sound one: a whole rehearsed run ends in a rehearsal line, and its
``--control int8`` (the plain reference, its rescore in int8, put in the
program's place) comes out not correct by the arithmetic (``dist_err``); a
rehearsed run whose server serves code-space distances without the rescore
(``faulty_rq_serve``) ends not correct by ``dist_err``; and one whose server
rescores k candidates serves exact distances in order and still ends not
correct, by what the candidates cost (``exact_recall_miss``). Run by hand or
with ``python3 -m pytest benchmark/tests -q``; not part of the tier-1 suite
(``tests/test_rq_reference.py`` is).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmark.tests.test_faults import ROOT

CELL = "cohere768.rq_c20"


def rehearse(*extra: str, **env: str):
    """(exit code, the run's JSON lines): 4,000 rows, four clusters."""
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", CELL,
           "--seed", "2147484001", "--seconds", "2", "--trace", "0",
           "--rehearse", "--rows", "4000", *extra]
    p = subprocess.run(cmd, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
                       capture_output=True, text=True, timeout=600)
    return p.returncode, [json.loads(line)
                          for line in p.stdout.strip().splitlines()]


def test_sound_run_reads_correct_and_its_int8_control_does_not():
    rc, lines = rehearse("--control", "int8")
    assert rc == 0 and lines[-1]["rehearsal"] == "passed"
    assert lines[-1]["compared"]["recall_miss"]["value"] == 0.0
    (control,) = [line for line in lines if line.get("phase") == "control"]
    assert control["arithmetic"] == "int8" and control["correct"] is False
    got = control["compared"]["dist_err"]
    assert got["value"] > 10 * got["limit"]
    assert all(control["compared"][n]["value"] == 0
               for n in ("bad_hits", "unanswered"))


@pytest.mark.parametrize("fault,number,exact", [
    ("skip_rescore", "dist_err", ("bad_hits", "unanswered")),
    ("few_candidates", "exact_recall_miss",
     ("bad_hits", "unanswered", "dist_err")),
])
def test_a_faulty_server_reads_not_correct(fault, number, exact):
    rc, lines = rehearse("--serve-module", "benchmark.tests.faulty_rq_serve",
                         BENCH_FAULT=fault)
    assert rc == 1 and lines[-1]["rehearsal"] == "failed"
    compared = lines[-1]["compared"]
    assert compared[number]["value"] > compared[number]["limit"]
    assert all(compared[n]["value"] <= compared[n]["limit"] for n in exact)
