"""Operations and bytes of the code-space scan under rotational quantization
(``jit_rq_search``), from the configuration's stated layout: the yardstick's
own arithmetic for ``rq_scan_roofline``, as ``costs.py`` is for the flat
scan. Never from what the program happens to hold or do, so that a later
change of the program leaves the yardstick where it is.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
AFFINE_BYTES = 8        # lower + step, float32 each, read for every row


def scan(executions: int, vectors_per_execution: int, capacity: int,
         dims: int, resident_bytes: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``executions`` scans of ``vectors_per_execution``
    queries each. ``capacity`` (the rows the scan covers) is the run's; the
    rotated width and the code width are the configuration's. Bytes: every
    row's codes at ``bits`` / 8 bytes a dimension and its ``lower`` and
    ``step`` (``dec_sqnorm`` is read by l2-squared alone, and this
    configuration is cosine). FLOPs: the product of the rotated queries with
    every row's codes. The selection, the mask and the queries themselves
    are not counted."""
    with open(os.path.join(HERE, "configs", "cohere-768-rq.json")) as f:
        cfg = json.load(f)
    rdims = cfg["rq_layout"]["rdims"]
    code_bytes = cfg["collection"]["vectorIndexConfig"]["rq"]["bits"] / 8
    nbytes = capacity * (rdims * code_bytes + AFFINE_BYTES)
    flops = 2.0 * vectors_per_execution * capacity * rdims
    return float(executions) * flops, float(executions) * nbytes
