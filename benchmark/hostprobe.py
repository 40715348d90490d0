"""What the host did during the window, read from ``/proc`` and the server's
data directory before and after it: never from the server itself, so that
the reading costs the window nothing. It goes on an earlier line of every
run (``phase: window_host``), not into ``metrics``: when a run reads far off,
the line says whether the server burnt more CPU for the same answers, which
files it wrote while it was only asked to search, and in which seconds the
answers stopped coming. (``/proc/stat`` reads all zeros on the machines with
the chip, so the host's own counters, steal among them, are not read.)
"""

from __future__ import annotations

import os
import time

_TICK = os.sysconf("SC_CLK_TCK")


def _proc_cpu_s(pid: int) -> float:
    """utime + stime of one process, all its threads, in seconds."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            rest = f.read().rsplit(")", 1)[1].split()
        return (int(rest[11]) + int(rest[12])) / _TICK
    except (OSError, IndexError, ValueError):
        return float("nan")


def _files(root: str) -> dict:
    out = {}
    for base, _, names in os.walk(root):
        for n in names:
            p = os.path.join(base, n)
            try:
                st = os.stat(p)
            except OSError:
                continue
            out[os.path.relpath(p, root)] = (st.st_size, st.st_mtime_ns)
    return out


def snapshot(server_pid: int, data_dir: str) -> dict:
    return {"t": time.monotonic(),
            "server_cpu_s": _proc_cpu_s(server_pid),
            "generator_cpu_s": _proc_cpu_s(os.getpid()),
            "files": _files(data_dir)}


def difference(before: dict, after: dict) -> dict:
    took = after["t"] - before["t"]
    touched = {}
    for path, (size, mtime) in after["files"].items():
        old = before["files"].get(path)
        if old is None or old != (size, mtime):
            touched[path] = size - (old[0] if old else 0)
    gone = [p for p in before["files"] if p not in after["files"]]
    top = sorted(touched.items(), key=lambda kv: -abs(kv[1]))[:8]
    return {"seconds": round(took, 3), "cores": os.cpu_count(),
            "server_cpu_s": round(
                after["server_cpu_s"] - before["server_cpu_s"], 2),
            "generator_cpu_s": round(
                after["generator_cpu_s"] - before["generator_cpu_s"], 2),
            "files_written": len(touched), "files_removed": len(gone),
            "bytes_written": sum(v for v in touched.values() if v > 0),
            "largest_written": top}


def per_second(records: list[dict], seconds: float) -> list[int]:
    """Requests completed in each whole second of the window."""
    counts = [0] * int(seconds)
    for r in records:
        if r["error"]:
            continue
        i = int(r["sent"] + r["latency"])
        if 0 <= i < len(counts):
            counts[i] += 1
    return counts
