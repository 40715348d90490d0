"""``tests/test_control.py`` takes every file of ``benchmark/workloads`` for a
cell whose limits are ``rank_gap`` and ``dist_err`` of a plain vector search.
The multi-vector cell's numbers are MaxSim scores (``score_err``,
``recall_miss``, ...) and its controls are in ``tests/test_multivector.py``,
so that case is taken out of the collection here, as ``tests/conftest.py``
takes out the hybrid cell's: a new file, since no file of the benchmark is
edited."""

from __future__ import annotations

OWN_CONTROL = ("msmarco128.multivector_c20.json",)


def pytest_collection_modifyitems(config, items):
    items[:] = [
        item for item in items
        if not (item.name.startswith("test_int8_control_fails_and_bf16_passes")
                and any(cell in item.name for cell in OWN_CONTROL))]
