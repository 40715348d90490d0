"""Test harness: force an 8-device virtual CPU platform BEFORE jax import.

Mirrors the reference's in-process multi-node tests
(``adapters/repos/db/clusterintegrationtest/``): instead of spinning real TPU
pods we validate sharding/collectives on a virtual 8-device CPU mesh.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Belt and braces with the env var above: a plugin that imported jax before
# conftest ran would have read JAX_PLATFORMS already; the config update still
# takes effect because backends initialize lazily.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Auto-mesh stays OFF for the bulk of the suite: with 8 virtual devices,
# every Collection search would otherwise compile an 8-way SPMD program per
# new shape — minutes of XLA time across the suite's hundreds of shapes.
# Sharding/collectives are still validated by the dedicated mesh tests
# (test_parallel.py builds meshes directly; test_mesh_serving.py opts back
# in via runtime.set_mesh).
os.environ.setdefault("WEAVIATE_TPU_MESH", "off")

# Lock-order witness (docs/lint.md "Concurrency contracts"): instrument
# every lock weaviate_tpu creates so the whole tier-1 run doubles as a
# dynamic validation of graftlint's static lock-order graph. The module
# is boot-loaded by file path BEFORE any weaviate_tpu import so the
# threading.Lock/RLock factories are already patched when module-level
# locks (mesh _DISPATCH_LOCK, native._LOCK, ...) are born; registering
# it in sys.modules keeps it the one shared instance for later package
# imports. Knob: WEAVIATE_TPU_LOCK_WITNESS=off|record|strict (default
# record — inversions fail the session at exit, see pytest_sessionfinish).
import sys  # noqa: E402

_WITNESS_MODE = os.environ.get("WEAVIATE_TPU_LOCK_WITNESS", "record")
if _WITNESS_MODE not in ("off", "0", ""):
    import importlib.util

    _lw_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "weaviate_tpu", "utils", "lockwitness.py")
    _spec = importlib.util.spec_from_file_location(
        "weaviate_tpu.utils.lockwitness", os.path.abspath(_lw_path))
    lockwitness = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(lockwitness)
    sys.modules["weaviate_tpu.utils.lockwitness"] = lockwitness
    lockwitness.install(strict=(_WITNESS_MODE == "strict"))

# Deadline witness (docs/lint.md "Error-path contracts"): the runtime
# counterpart of the errorflow budget pass. Boot-loaded by file path the
# same way so the conftest-installed instance is THE one the inline
# transport/resilience hooks see. Knob:
# WEAVIATE_TPU_DEADLINE_WITNESS=off|record|strict (default record —
# a serving-scope RPC with no live deadline fails the session at exit).
_DW_MODE = os.environ.get("WEAVIATE_TPU_DEADLINE_WITNESS", "record")
if _DW_MODE not in ("off", "0", ""):
    import importlib.util

    _dw_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "weaviate_tpu", "utils", "deadlinewitness.py")
    _dw_spec = importlib.util.spec_from_file_location(
        "weaviate_tpu.utils.deadlinewitness", os.path.abspath(_dw_path))
    deadlinewitness = importlib.util.module_from_spec(_dw_spec)
    _dw_spec.loader.exec_module(deadlinewitness)
    sys.modules["weaviate_tpu.utils.deadlinewitness"] = deadlinewitness
    deadlinewitness.install(strict=(_DW_MODE == "strict"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    """Zero observed lock-order inversions AND zero unbudgeted
    serving-scope RPCs are tier-1 invariants: the chaos, tiering, and
    mesh suites all ran with both witnesses on."""
    lw = sys.modules.get("weaviate_tpu.utils.lockwitness")
    if lw is not None and lw.installed():
        w = lw.current()
        print("\n" + w.report())
        if w.inversions and exitstatus == 0:
            session.exitstatus = 1
    dw = sys.modules.get("weaviate_tpu.utils.deadlinewitness")
    if dw is not None and dw.installed():
        w = dw.current()
        print(w.report())
        if w.violations and exitstatus == 0:
            session.exitstatus = 1


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def tmp_dbdir(tmp_path):
    d = tmp_path / "db"
    d.mkdir()
    return str(d)
