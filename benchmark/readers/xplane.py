"""Reader ``xplane``: numbers of the profiler trace of the server process.

params: ``what`` is ``idle_share`` (1 - union of device-operation intervals
/ traced window, in %), ``program_ms`` (device time of ``program``'s
executions / their number, ms) or ``program_roofline`` (least time / measured
time of the same executions in %, the least time from the function ``cost``
names as ``module:function`` and the peaks of the device kind). Nothing to read -> None: a share
is never reported as 0 for want of a trace.
"""

from __future__ import annotations

import importlib

from benchmark import costs, peaks


def read(params: dict, evidence: dict):
    trace = evidence.get("trace")
    if not trace:
        return None
    if params["what"] == "idle_share":
        return 100.0 * trace["idle_share"]
    program = trace["programs"].get(params["program"])
    if not program or not program["executions"] or not program["seconds"]:
        return None
    if params["what"] == "program_ms":
        return 1e3 * program["seconds"] / program["executions"]
    if params["what"] == "program_roofline":
        shape = evidence["shape"]
        module, _, function = params["cost"].partition(":")
        flops, nbytes = getattr(importlib.import_module(module), function)(
            program["executions"], shape["vectors_per_execution"],
            shape["capacity"], shape["dims"], shape["resident_bytes"])
        least, binds = costs.least_seconds(
            flops, nbytes, peaks.peaks_for(evidence["device"]["kind"]))
        evidence.setdefault("notes", {})[
            f"{params['program']}.roofline_bound"] = binds
        return 100.0 * least / program["seconds"]
    raise ValueError(f"unknown xplane reading {params['what']!r}")
