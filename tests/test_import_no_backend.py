"""Importing the package never initialises a JAX backend.

A chip belongs to one process. ``chip_smoke.py``'s parent imports the client
and the gRPC stubs while its server CHILD holds the chip; a module-level
``jnp`` constant anywhere under ``weaviate_tpu/`` would make whichever process
imports it first take the chip (``ops/distance.py`` MASK_DISTANCE note).
"""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_CHILD = r"""
import importlib, pkgutil, sys
import weaviate_tpu
failed = []
for m in pkgutil.walk_packages(weaviate_tpu.__path__, "weaviate_tpu."):
    if m.name.endswith("__main__"):
        continue
    try:
        importlib.import_module(m.name)
    except Exception as e:
        failed.append(f"{m.name}: {type(e).__name__}: {e}")
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        print("BACKEND_UP_AFTER", m.name)
        sys.exit(3)
if failed:
    print("IMPORT_FAILED", *failed, sep="\n")
    sys.exit(4)
print("IMPORTED_WITHOUT_BACKEND")
"""


def test_no_module_import_initialises_a_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "IMPORTED_WITHOUT_BACKEND" in out.stdout
