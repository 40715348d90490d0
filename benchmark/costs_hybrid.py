"""Operations and bytes of the hybrid fusion program, from shapes: the
yardstick's own arithmetic for ``hybrid_fuse_roofline``, as ``costs.py`` is
for the flat scan. Counted from the configuration's shapes alone (two legs
of ``leg_depth``, ``k`` served), never from what tier the program fuses on,
so that a later change of tier leaves the yardstick where it is.
"""

from __future__ import annotations

import json
import os

LEGS = 2
HERE = os.path.dirname(os.path.abspath(__file__))


def bucket(n: int, floor: int = 8) -> int:
    """The next power of two, at least ``floor``: how the program pads a
    leg's length and the union of the legs' ids."""
    size = floor
    while size < n:
        size *= 2
    return size


def fuse(executions: int, vectors_per_execution: int, capacity: int,
         dims: int, resident_bytes: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``executions`` fusions of one request each. The
    arguments after the first are the flat scan's and say nothing of this
    program; its shapes are the configuration's. Bytes: the padded slot
    (int32) and score (float32) arrays of both legs and their weights read,
    the union's accumulator and presence planes written and read once, k
    scores and k ids written. FLOPs: a leg entry's normalisation (min, max,
    subtract, divide, weigh) and its two scatter-adds, and one comparison a
    union slot and served rank for the selection."""
    with open(os.path.join(HERE, "configs", "msmarco-768-hybrid.json")) as f:
        cfg = json.load(f)
    k, depth = cfg["k"], cfg["hybrid"]["leg_depth"]
    entries = LEGS * bucket(depth)
    union = bucket(LEGS * depth)        # the legs' ids, none shared
    nbytes = entries * (4 + 4) + LEGS * 4 + 2 * 2 * union * 4 + k * (4 + 4)
    flops = entries * 7 + union * k
    return float(executions) * flops, float(executions) * nbytes
