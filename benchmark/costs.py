"""Operations and bytes a device program needs for its work, from shapes.
The yardstick's own arithmetic: a roofline share divides the least time
these give by the time the trace measured."""

from __future__ import annotations

CAPACITY_START = 1024   # DeviceVectorStore doubles from here


def store_capacity(rows: int) -> int:
    """Rows the device store holds (and the scan covers) for ``rows`` live
    rows: the next doubling of 1024."""
    cap = CAPACITY_START
    while cap < rows:
        cap *= 2
    return cap


def stored_itemsize(resident_bytes: int, capacity: int, dims: int) -> int:
    """Bytes per stored element, from what ``/v1/nodes`` reported resident
    after the load: the widest of 4, 2, 1 that the resident bytes can hold.
    So a later change of the stored dtype moves the byte count with it, and
    the count is never more than what is resident."""
    for size in (4, 2, 1):
        if capacity * dims * size <= resident_bytes:
            return size
    raise ValueError(
        f"{resident_bytes} resident bytes cannot hold {capacity} x {dims}")


def flat_scan(executions: int, vectors_per_execution: int, capacity: int,
              dims: int, resident_bytes: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``executions`` flat scans: one product of the query
    vectors with every stored row, and one read of the stored rows each."""
    flops = 2.0 * executions * vectors_per_execution * capacity * dims
    size = stored_itemsize(resident_bytes, capacity, dims)
    return flops, float(executions) * capacity * dims * size


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """(least time, which bound binds)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")

