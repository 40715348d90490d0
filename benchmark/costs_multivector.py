"""Operations and bytes of the fused multi-vector search program (FDE scan,
gather of the candidates' token sets, MaxSim, top-k), from shapes: the
yardstick's own arithmetic for ``mv_fused_roofline``, as ``costs.py`` is for
the flat scan. Counted from the configuration's shapes alone (its
``device_planes`` block states the stored itemsizes and the padded token
width), never from what the program happens to do, so that a later change of
the program leaves the yardstick where it is.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def fused(executions: int, vectors_per_execution: int, capacity: int,
          dims: int, resident_bytes: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``executions`` requests of one query each.
    ``capacity`` (the rows the scan covers) and ``dims`` (a token's width)
    are the run's; the rest is the configuration's. Bytes: the FDE plane
    read once at its stored itemsize, and every candidate's padded token
    set read once at the token itemsize. FLOPs: the product of the query's
    FDE with every stored row, and of the query's tokens with every token
    slot of every candidate. The selection, the mask and the query itself
    are not counted."""
    with open(os.path.join(
            HERE, "configs", "msmarco-128-multivector.json")) as f:
        cfg = json.load(f)
    planes = cfg["device_planes"]
    candidates = cfg["collection"]["vectorIndexConfig"]["rescoreLimit"]
    slots = candidates * planes["padded_tokens"]
    nbytes = capacity * cfg["fde_dim"] * planes["fde_itemsize"] \
        + slots * dims * planes["token_itemsize"]
    flops = 2.0 * capacity * cfg["fde_dim"] \
        + 2.0 * cfg["query_tokens"] * slots * dims
    return float(executions) * flops, float(executions) * nbytes
