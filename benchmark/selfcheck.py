"""Checks of the yardstick itself, by hand and in the rehearsal:
``python3 -m benchmark.selfcheck``. Needs no chip and no server.

1. The trace reduction gives known busy/idle, per-program time and roofline
   numbers on ``testdata/flat_scan_tpu.xplane.pb`` (recorded on one TPU v5e by
   ``testdata/record.py``: 5 executions of ``jit_flat_search`` over 8,192 x
   128 rows; expected values in ``testdata/flat_scan_tpu.expected.json``,
   worked out once from the trace's own events and looked at by hand).
2. The FLOP/byte functions against hand-worked values for 262,144 x 768.
3. An unknown ``device_kind`` raises.
4. ``BENCHMARK.json`` and the data files agree: every cell, configuration,
   traffic kind, metric file and reader is found by name.
"""

from __future__ import annotations

import importlib
import json
import math
import os

from benchmark import costs, peaks, xplane
from benchmark.readers import xplane as xplane_reader
from benchmark.run import ROOT, cell_metrics, load_json

HERE = os.path.dirname(os.path.abspath(__file__))


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-15)


def check_union() -> None:
    covered, gaps = xplane.union_seconds(
        [(0, 10), (5, 20), (30, 40), (32, 35), (40, 41)])
    assert covered == 31 and gaps == [(20, 30)], (covered, gaps)


def check_trace() -> None:
    with open(os.path.join(HERE, "testdata",
                           "flat_scan_tpu.expected.json")) as f:
        want = json.load(f)
    reduced = xplane.reduce_trace(
        os.path.join(HERE, "testdata", "flat_scan_tpu.xplane.pb"))
    assert reduced and len(reduced["planes"]) == 1, "one TPU plane expected"
    s = xplane.summarize(reduced, want["window_s"])
    prog = s["programs"]["jit_flat_search"]
    assert prog["executions"] == want["executions"] == 5
    assert close(prog["seconds"], want["program_seconds"])
    assert close(s["busy_s"], want["busy_s"])
    assert close(s["idle_share"], 1 - want["busy_s"] / want["window_s"])
    plane = reduced["planes"][0]
    assert close((plane["last_ns"] - plane["first_ns"]) / 1e9,
                 want["window_s"])
    lo, hi = plane["gaps"][0]
    assert close((hi - lo) / 1e9, want["longest_gap_s"])
    evidence = {"trace": s, "device": {"kind": "TPU v5 lite"},
                "shape": {"capacity": 8192, "dims": 128,
                          "vectors_per_execution": 1,
                          "resident_bytes": 8192 * 128 * 4 + 4096}}
    ms = xplane_reader.read(
        {"what": "program_ms", "program": "jit_flat_search"}, evidence)
    assert close(ms, 1e3 * want["program_seconds"] / 5)
    roof = xplane_reader.read(
        {"what": "program_roofline", "program": "jit_flat_search",
         "cost": "benchmark.costs:flat_scan"}, evidence)
    # least time: 5 x 8192 x 128 x 4 B / 819e9 B/s = 25.6 us (bytes bind:
    # the FLOPs, 5 x 2 x 8192 x 128 / 197e12, are 0.05 us)
    least = 5 * 8192 * 128 * 4 / 819e9
    assert close(roof, 100 * least / want["program_seconds"])
    assert 0 < roof < 100, roof
    assert evidence["notes"]["jit_flat_search.roofline_bound"] == "bytes"
    assert xplane_reader.read({"what": "idle_share"}, {}) is None
    assert xplane_reader.read(
        {"what": "program_ms", "program": "jit_absent"}, evidence) is None
    b = xplane.breakdown(reduced)
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert b["device_ops"][0][0].startswith("%multiply_reduce_fusion")
    assert all(len(name) <= 160 for name, _ in b["device_ops"])
    # record.py sleeps 10 ms between scans: no jax call covers those gaps
    assert 4 <= len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == xplane.UNATTRIBUTED


def check_costs() -> None:
    cap, d = 262144, 768
    assert costs.store_capacity(250000) == cap
    assert costs.store_capacity(140000) == cap
    assert costs.store_capacity(1024) == 1024
    resident = 805_306_368 + 3_000_000     # rows + masks, norms, slack
    flops, nbytes = costs.flat_scan(1, 1, cap, d, resident)
    assert flops == 402_653_184.0           # 2 x 262,144 x 768
    assert nbytes == 805_306_368.0          # 262,144 x 768 x 4 B
    p = peaks.peaks_for("TPU v5 lite")
    least, binds = costs.least_seconds(flops, nbytes, p)
    assert binds == "bytes" and close(least, 805_306_368 / 819e9)  # 0.983 ms
    flops, nbytes = costs.flat_scan(3, 256, cap, d, resident)
    assert flops == 3 * 256 * 402_653_184.0
    assert nbytes == 3 * 805_306_368.0
    least, binds = costs.least_seconds(flops, nbytes, p)
    # 256 vectors: 0.523 ms of FLOPs against 0.983 ms of bytes, an execution
    assert binds == "bytes" and close(least, 3 * 805_306_368 / 819e9)
    # a bf16-resident corpus halves the bytes with what /v1/nodes reports
    assert costs.stored_itemsize(cap * d * 2 + 5_000_000, cap, d) == 2
    try:
        costs.stored_itemsize(1000, cap, d)
    except ValueError:
        pass
    else:
        raise AssertionError("too few resident bytes must raise")


def check_peaks() -> None:
    try:
        peaks.peaks_for("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must raise")
    for bad in ("source", "cpu"):
        try:
            peaks.peaks_for(bad)
        except KeyError:
            continue
        raise AssertionError(f"{bad!r} is no device kind")


def check_files() -> None:
    bench = load_json("BENCHMARK.json")
    for cfg in bench["configs"]:
        body = load_json(cfg["file"])
        assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
        assert body["reduced"] == cfg["reduced"]
        for key in ("assumed", "guarantees"):
            assert body[key], (cfg["name"], key)
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        work = load_json("benchmark", "workloads", cell["name"] + ".json")
        assert work["config"] == cell["config"]
        assert cell["name"].endswith("." + cell["traffic"])
        importlib.import_module("benchmark.traffic." + work["traffic"]["kind"])
        e2e, layer = cell_metrics(bench, cell["name"])
        assert len(e2e) >= 2 and layer, cell["name"]
    for m in bench["per_layer"]:
        spec = load_json("benchmark", "metrics", m["name"] + ".json")
        assert m["moves"] in e2e_names
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        importlib.import_module("benchmark.readers." + spec["reader"])
    assert os.path.isdir(os.path.join(ROOT, "benchmark"))


def main() -> int:
    for check in (check_union, check_costs, check_peaks, check_files,
                  check_trace):
        check()
        print("ok", check.__name__)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
