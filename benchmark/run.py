"""One run of one cell: ``python3 -m benchmark.run --workload W --seed N
--seconds S --trace 0|1``, from the root of the checkout.

Server child up -> collection -> load and warm-up (the traffic kind's
``setup``) -> ``setup_s`` ends -> the window of ``--seconds`` -> with
``--trace 1`` a few traced seconds of the same traffic -> read-backs ->
SIGTERM (exit code 0 required) -> the plain reference decides ``correct``
-> the result line. The cell, its configuration, its traffic kind and its
per-layer metrics are found by name: ``BENCHMARK.json``,
``benchmark/workloads/``, ``benchmark/configs/``, ``benchmark/traffic/``,
``benchmark/metrics/`` and ``benchmark/readers/`` (see README.md).

Knobs of the harness, never passed by the driver: ``--rehearse`` (CPU
backend, rows cut; ends with a ``{"rehearsal": ...}`` line and no result),
``--rows``, ``--control int8`` (also reads the control's numbers),
``--serve-module`` (a test's broken server), ``--keep-trace DIR``,
``--processes N`` (split the generator's clients over N processes).
"""

from __future__ import annotations

import time

T0 = time.monotonic()   # process start, as near as Python lets us see it

import argparse
import importlib
import json
import os
import shutil
import signal
import sys
import tempfile
import types

from benchmark import costs, harness, hostprobe, peaks, reference, xplane

ROOT = harness.REPO


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_metrics(bench: dict, name: str):
    """(end-to-end entries, per-layer entries) this cell reports: an entry
    without ``workloads`` belongs to every cell that reports the metric it
    needs (``setup_s``: all; a per-layer metric: those with its ``moves``)."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in names]
    return e2e, layer


def read_spans(server, start_unix_ns: int, stop_unix_ns: int) -> list[dict]:
    """The server's spans that ended inside the traced window, each with the
    name of its trace's root."""
    out = []
    for trace in server.get("/v1/debug/traces?limit=5000")["traces"]:
        for s in trace["spans"]:
            end = s["endTimeUnixNano"] or 0
            if start_unix_ns <= end <= stop_unix_ns:
                out.append({**s, "root": trace["root"]})
    return out


def traced_segment(ctx, traffic, state, evidence: dict) -> None:
    server = ctx.server
    started = server.signal_and_wait(signal.SIGUSR1, "trace_started.json")
    traffic.window(ctx, state, float(ctx.spec.get("trace_seconds", 4)))
    stopped = server.signal_and_wait(signal.SIGUSR2, "trace_stopped.json")
    evidence["trace_info"] = stopped
    evidence["spans"] = read_spans(
        server, started["unix_ns"], stopped["stop_unix_ns"])


def read_layer_metrics(layer: list[dict], evidence: dict) -> dict:
    metrics = {}
    for entry in layer:
        spec = load_json("benchmark", "metrics", entry["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(spec["params"], evidence)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return metrics


def run(args) -> tuple[dict, bool]:
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(config["file"])
    work = load_json("benchmark", "workloads", cell["name"] + ".json")
    traffic = importlib.import_module(
        "benchmark.traffic." + work["traffic"]["kind"])
    if args.processes:
        work["traffic"]["processes"] = args.processes
    e2e_entries, layer_entries = cell_metrics(bench, cell["name"])
    rows = args.rows or (max(2000, cfg["rows"] // 50) if args.rehearse
                         else cfg["rows"])
    harness.say(phase="start", workload=cell["name"], seed=args.seed,
                seconds=args.seconds, trace=args.trace, rows=rows,
                rehearse=args.rehearse,
                JAX_COMPILATION_CACHE_DIR=os.environ.get(
                    "JAX_COMPILATION_CACHE_DIR"))

    workdir = tempfile.mkdtemp(prefix="benchmark_")
    server = harness.Server(workdir, "cpu" if args.rehearse else "tpu",
                            args.serve_module)
    try:
        harness.say(phase="server_ready", seconds=server.wait_ready())
        device = server.device()
        want = "cpu" if args.rehearse else "tpu"
        if device["platform"] != want or (
                not args.rehearse and device["count"] != cell["chips"]):
            raise RuntimeError(
                f"server runs on {device['count']} x {device['platform']}, "
                f"the cell asks for {cell['chips']} x {want}")
        if not args.rehearse:
            peaks.peaks_for(device["kind"])     # unknown kind: an error
        harness.create_collection(server, cfg)
        ctx = types.SimpleNamespace(
            cfg=cfg, spec=work["traffic"], seed=args.seed, server=server,
            rows=rows, k=cfg["k"], collection=cfg["collection"]["class"])
        state = traffic.setup(ctx)
        # the load left a gigabyte of dirty pages: have the kernel write
        # them back now, in set-up, and not at its leisure in the window
        os.sync()
        resident = server.device()["bytes_in_use"]
        capacity = costs.store_capacity(rows)
        harness.say(phase="resident", bytes_in_use=resident,
                    capacity_rows=capacity, dims=cfg["dims"])
        evidence = {
            "device": device, "compile_before": server.compile_counters(),
            "shape": {"capacity": capacity, "dims": cfg["dims"],
                      "resident_bytes": max(resident),
                      **traffic.trace_costs(ctx, state)}}

        data_dir = os.path.join(workdir, "data")
        probe = hostprobe.snapshot(server.proc.pid, data_dir)
        setup_s = time.monotonic() - T0
        records = traffic.window(ctx, state, float(args.seconds))
        probe = hostprobe.difference(
            probe, hostprobe.snapshot(server.proc.pid, data_dir))
        values = traffic.end_to_end(ctx, state, records, float(args.seconds))
        values["setup_s"] = setup_s
        harness.say(phase="window", requests=len(records),
                    failed=sum(1 for r in records if r["error"]),
                    first_error=next(
                        (r["error"] for r in records if r["error"]), ""))
        if args.trace:
            traced_segment(ctx, traffic, state, evidence)
        evidence["compile_after"] = server.compile_counters()
        harness.say(
            phase="window_host", **probe,
            compile_misses=evidence["compile_after"]["misses"]
            - evidence["compile_before"]["misses"],
            per_second=hostprobe.per_second(records, float(args.seconds)))
        traffic.after_window(ctx, state, records)

        rc = server.stop()
        if rc != 0:
            raise RuntimeError(f"server exit code {rc} after SIGTERM")
        stderr = server.stderr_text()
        if "Traceback (most recent call last)" in stderr:
            raise RuntimeError("server stderr has a traceback:\n"
                               + stderr[-4000:])
        with open(os.path.join(server.out, "device_exit.json")) as f:
            peak = [p for p in json.load(f)["peak_bytes_in_use"]
                    if p is not None]
        dev = {"platform": device["platform"], "kind": device["kind"],
               "count": device["count"],
               "memory_peak_bytes": max(peak) if peak else None}

        result_extra = {}
        if args.trace:
            metrics = traced_metrics(
                server, evidence, layer_entries, dev, result_extra, args)
        else:
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]} for m in e2e_entries}

        # the program's state is freed; now the plain reference
        t_ref = time.monotonic()
        numbers = traffic.check(ctx, state, records)
        correct, compared = reference.verdict(numbers, work["limits"])
        harness.say(phase="reference", seconds=time.monotonic() - t_ref,
                    numbers=numbers)
        if args.control:
            ctl = traffic.check(ctx, state, records, control=args.control)
            ctl_correct, ctl_compared = reference.verdict(
                ctl, work["limits"])
            harness.say(phase="control", arithmetic=args.control,
                        correct=ctl_correct, compared=ctl_compared)
    except BaseException:
        sys.stderr.write("---- server stderr (tail) ----\n"
                         + server.stderr_text()[-6000:] + "\n")
        raise
    finally:
        server.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    result = {
        "correct": correct, "attempted": len(records),
        "failed": sum(1 for r in records if r["error"]),
        "metrics": metrics, "device": dev, **result_extra,
        "compared": compared}
    return result, correct


def traced_metrics(server, evidence, layer_entries, dev, result_extra,
                   args) -> dict:
    trace_dir = os.path.join(server.out, "trace")
    path = xplane.find_trace(trace_dir)
    reduced = xplane.reduce_trace(path) if path else None
    if args.keep_trace and path:
        # a description always; the file itself only where it is small
        os.makedirs(args.keep_trace, exist_ok=True)
        with open(os.path.join(args.keep_trace, "describe.json"), "w") as f:
            json.dump(xplane.describe(path), f, indent=1)
        if os.path.getsize(path) < 16 << 20:
            shutil.copy(path, args.keep_trace)
    if reduced:
        evidence["trace"] = xplane.summarize(
            reduced, evidence["trace_info"]["window_s"])
        dev["busy_s"] = evidence["trace"]["busy_s"]
        dev["window_s"] = evidence["trace"]["window_s"]
        result_extra["breakdown"] = xplane.breakdown(reduced)
    metrics = read_layer_metrics(layer_entries, evidence)
    harness.say(phase="trace", file=os.path.basename(path or ""),
                spans=len(evidence["spans"]),
                programs=(evidence.get("trace") or {}).get("programs"),
                notes=evidence.get("notes"))
    return metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--control", choices=("int8",), default="")
    ap.add_argument("--serve-module", default="benchmark.serve")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--processes", type=int, default=0)
    args = ap.parse_args()
    result, correct = run(args)
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError("the parent initialised a JAX backend")
    # each number compared beside its limit: last on standard error, and
    # last in the result's line
    sys.stderr.write("compared: " + json.dumps(result["compared"]) + "\n")
    sys.stderr.flush()
    if args.rehearse:
        print(json.dumps({
            "rehearsal": "passed" if correct else "failed",
            "reported": sorted(result["metrics"]),
            "device": {k: result["device"][k]
                       for k in ("platform", "kind", "count")},
            "compared": result["compared"]}))
        return 0 if correct else 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
