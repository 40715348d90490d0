"""The reduction from a profiler trace (``.xplane.pb``) to device busy time,
per-program time, the operations that took most time and the longest idle
gaps. Reads the file with ``jax.profiler.ProfileData`` (parsing only: no
backend is initialised).

A TPU's plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line has one
event per device operation and its ``XLA Modules`` line one per execution of
a compiled program (``jit_<function>(<fingerprint>)``).
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_trace(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_seconds(intervals: list[tuple[int, int]]):
    """(covered ns, gaps [(start, end)]) of (start, end) intervals in ns."""
    covered, gaps, end = 0, [], None
    for lo, hi in sorted(intervals):
        if end is None or lo > end:
            if end is not None:
                gaps.append((end, lo))
            covered += hi - lo
            end = hi
        elif hi > end:
            covered += hi - end
            end = hi
    return covered, gaps


def program_name(event_name: str) -> str:
    """``jit_flat_search(1234)`` -> ``jit_flat_search``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce_trace(path: str) -> dict | None:
    """Per device plane: busy seconds (union of the operations' intervals),
    per-program executions and seconds, the top operations, the longest
    gaps; and the jax events of the server's Python threads. None when the
    trace has no device plane with operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, host_events = [], []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host_events += [
                        (int(ev.start_ns),
                         int(ev.start_ns) + int(ev.duration_ns), ev.name)
                        for ev in line.events]
        if not plane.name.startswith("/device:"):
            continue
        ops, programs, intervals = {}, {}, []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    lo = int(ev.start_ns)
                    hi = lo + int(ev.duration_ns)
                    intervals.append((lo, hi))
                    ops[ev.name] = ops.get(ev.name, 0) + (hi - lo)
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    p = programs.setdefault(program_name(ev.name), [0, 0])
                    p[0] += 1
                    p[1] += int(ev.duration_ns)
        if not intervals:
            continue
        busy, gaps = union_seconds(intervals)
        planes.append({
            "plane": plane.name, "busy_s": busy / 1e9,
            "first_ns": min(lo for lo, _ in intervals),
            "last_ns": max(hi for _, hi in intervals),
            "programs": {n: {"executions": c, "seconds": ns / 1e9}
                         for n, (c, ns) in programs.items()},
            "ops": sorted(((n, ns / 1e9) for n, ns in ops.items()),
                          key=lambda x: -x[1]),
            "gaps": sorted(gaps, key=lambda g: g[0] - g[1])[:10],
        })
    return {"planes": planes, "host_events": host_events} if planes \
        else None


def summarize(reduced: dict, window_s: float) -> dict:
    """Averages over the chips used: busy seconds, idle share of the traced
    window, and per-program time summed over chips."""
    planes = reduced["planes"]
    busy = sum(p["busy_s"] for p in planes) / len(planes)
    programs: dict[str, dict] = {}
    for p in planes:
        for name, v in p["programs"].items():
            agg = programs.setdefault(name, {"executions": 0, "seconds": 0.0})
            agg["executions"] += v["executions"]
            agg["seconds"] += v["seconds"]
    return {"busy_s": busy, "window_s": window_s,
            "idle_share": 1.0 - busy / window_s, "programs": programs,
            "chips": len(planes)}


UNATTRIBUTED = "unattributed (no jax call on a server thread)"


def short_name(op: str) -> str:
    """``%convert.9 = bf16[...] convert(f32[...] %corpus.1)`` keeps its name
    and the start of its definition: at most 160 characters."""
    head, _, rest = op.partition(" = ")
    return (head + ": " + rest)[:160] if rest else op[:160]


def breakdown(reduced: dict) -> dict:
    """The device operations that took most time, and the longest idle gaps
    of the first chip, each named by what a host thread of the server was
    doing in jax at the gap's middle (the ``python3`` lines of the trace's
    host plane are on the device's clock). The program's own spans are on
    the unix clock and cannot be laid over the trace; a gap that no jax call
    covers reads ``unattributed``."""
    plane = reduced["planes"][0]
    gaps = []
    for lo, hi in plane["gaps"]:
        mid = (lo + hi) // 2
        cover = [(e - s, name) for s, e, name in reduced["host_events"]
                 if s <= mid <= e]
        # the innermost host event that covers the middle of the gap
        gaps.append([min(cover)[1] if cover else UNATTRIBUTED,
                     (hi - lo) / 1e9])
    return {"device_ops": [[short_name(n), s] for n, s in plane["ops"][:10]],
            "idle_gaps": gaps}


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines, event counts and the names that took most time: what
    to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names, n, first = {}, 0, None
            for ev in line.events:
                n += 1
                first = ev.start_ns if first is None else min(first,
                                                              ev.start_ns)
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            lines[f"{line.name}#{len(lines)}"] = {
                "events": n, "first_start_ns": first,
                "top": sorted(((k, v / 1e9) for k, v in names.items()),
                              key=lambda x: -x[1])[:top]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))
