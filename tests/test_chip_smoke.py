"""``chip_smoke.py`` rehearsed on the CPU backend, and refused without a chip.

The rehearsal drives the real thing end to end — server child through
``python -m weaviate_tpu.server``, REST schema, gRPC load, gRPC + GraphQL
queries, numpy reference, /v1/nodes device block, /metrics, SIGTERM — at a
size that takes seconds. It can never print ``"ok": true``: that line is for
a TPU. D stays 768; only N is small.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _smoke(*argv, tmp_path):
    env = dict(os.environ, TMPDIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *argv], env=env,
        cwd=tmp_path, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("chips,n", [(1, 3000), (4, 4000)])
def test_rehearsal_passes_on_cpu_devices(tmp_path, chips, n):
    out = _smoke("--rehearse", "--chips", str(chips), "--n", str(n),
                 tmp_path=tmp_path)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    assert lines[-1] == {"rehearsal": "passed", "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    assert '"ok"' not in out.stdout
    by_phase = {rec["phase"]: rec for rec in lines if "phase" in rec}
    assert by_phase["load"]["n"] == n
    assert by_phase["grpc_search"]["queries"] == 256
    assert by_phase["rest_graphql"]["queries"] == 8
    assert len(by_phase["device"]["bytes_in_use"]) == chips
    assert by_phase["server_stopped"]["exit_code"] == 0
    assert {"reduced": {"n": n, "why": "--n given on the command line"}} \
        in lines
    assert not list(tmp_path.glob("chip_smoke_*"))  # cleaned up after itself


def test_no_chip_means_no_ok(tmp_path):
    """As the driver runs it in a sandbox: JAX_PLATFORMS=tpu in the child,
    no chip, so the server dies at start and the smoke fails without a
    result line."""
    out = _smoke("--n", "2000", tmp_path=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "rehearsal" not in out.stdout
    assert "server exited with code" in out.stderr
