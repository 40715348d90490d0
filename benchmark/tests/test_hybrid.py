"""The hybrid cell's comparison has to tell a wrong server from a sound one:
a whole rehearsed run ends in a rehearsal line and its ``--control int8``
(the reference with its dense leg in int8, put in the program's place) comes
out not correct by the arithmetic alone; a rehearsed run whose server sheds
the sparse leg of every request (``faulty_hybrid_serve``) ends not correct by
``score_err``. Run by hand or with ``python3 -m pytest benchmark/tests -q``;
not part of the tier-1 suite (``tests/test_hybrid_reference.py`` is).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark.tests.test_faults import ROOT

CELL = "msmarco768.hybrid_c20"


def rehearse(*extra: str):
    """(exit code, the run's JSON lines)."""
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", CELL,
           "--seed", "2147484001", "--seconds", "2", "--trace", "0",
           "--rehearse", "--rows", "3000", *extra]
    p = subprocess.run(cmd, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    return p.returncode, [json.loads(line)
                          for line in p.stdout.strip().splitlines()]


def test_sound_run_reads_correct_and_its_int8_control_does_not():
    rc, lines = rehearse("--control", "int8")
    assert rc == 0 and lines[-1]["rehearsal"] == "passed"
    (counted,) = [line for line in lines if line.get("phase") == "hybrid"]
    assert counted["requests"] > 0
    assert counted["leg_shed"] == counted["fallback"] == 0
    (control,) = [line for line in lines if line.get("phase") == "control"]
    assert control["arithmetic"] == "int8" and control["correct"] is False
    exact = ("bad_hits", "short_answers", "unanswered")
    assert all(c["value"] <= c["limit"]
               for n, c in control["compared"].items() if n in exact)


def test_a_server_that_sheds_the_sparse_leg_reads_not_correct():
    rc, lines = rehearse("--serve-module",
                         "benchmark.tests.faulty_hybrid_serve")
    assert rc == 1 and lines[-1]["rehearsal"] == "failed"
    got = lines[-1]["compared"]["score_err"]
    assert got["value"] > 0.1 > got["limit"]
    assert lines[-1]["compared"]["bad_hits"]["value"] == 0
