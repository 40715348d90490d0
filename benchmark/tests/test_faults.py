"""A whole run with the timed path broken underneath has to end with
``correct`` false. Skips the harness's look for a chip (``--rehearse``: CPU
backend, small collection) and drives the rest of a run. Run by hand or with
``python3 -m pytest benchmark/tests -q``; not part of the tier-1 suite.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def rehearse(workload: str, fault: str = "") -> tuple[int, dict]:
    cmd = [sys.executable, "-m", "benchmark.run", "--workload", workload,
           "--seed", "2147484001", "--seconds", "2", "--trace", "0",
           "--rehearse", "--rows", "3000"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if fault:
        cmd += ["--serve-module", "benchmark.tests.faulty_serve"]
        env["BENCH_FAULT"] = fault
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    last = p.stdout.strip().splitlines()[-1]
    return p.returncode, json.loads(last)


@pytest.mark.parametrize("workload,fault,number", [
    ("cohere768.search_c20", "alter_answer", "rank_gap"),
    ("sift128.import_c4", "alter_answer", "rank_gap"),
    ("sift128.import_c4", "drop_half_batch", "readback_bad"),
])
def test_fault_reads_not_correct(workload, fault, number):
    rc, line = rehearse(workload, fault)
    assert rc == 1 and line["rehearsal"] == "failed"
    got = line["compared"][number]
    assert got["value"] > got["limit"]


@pytest.mark.parametrize("workload", [
    "cohere768.search_c20", "sift128.import_c4"])
def test_sound_run_reads_correct(workload):
    rc, line = rehearse(workload)
    assert rc == 0 and line["rehearsal"] == "passed"
    assert "metrics" not in line and "correct" not in line
