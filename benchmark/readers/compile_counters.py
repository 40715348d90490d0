"""Reader ``compile_counters``: a counter of ``/v1/debug/compile``'s cache
panel after the traced window minus before the timed window.

params: ``counter`` (``misses`` or ``hits``). Nothing to read -> None.
"""

from __future__ import annotations


def read(params: dict, evidence: dict):
    before, after = evidence.get("compile_before"), evidence.get(
        "compile_after")
    if not before or not after or params["counter"] not in after:
        return None
    return after[params["counter"]] - before[params["counter"]]
