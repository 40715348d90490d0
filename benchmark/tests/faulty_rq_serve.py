"""``benchmark.serve`` with the compressed flat path broken underneath, for
``test_rq.py``, ``tests/test_rq_reference.py`` and for the cell's controls
on the chip: ``BENCH_FAULT`` names the fault planted in the program before
its normal entry point runs (``skip_rescore`` where it is unset).

``skip_rescore``    every search of a quantized flat index is served from the
                    code scan alone: the k best in code space, their
                    code-space distances as the distances, no original read.
                    What "every served distance is the exact float32
                    distance of that id" forbids.
``few_candidates``  the exact rescore of the k best in code space, where the
                    configuration fetches max(4k, rescoreLimit, k): every
                    distance is still exact and the list sorted, and a
                    quarter of the rescore is paid. What "the candidate stage
                    is approximate as configured, and never less" forbids.
"""

from __future__ import annotations

import os
import sys

import numpy as np


def _code_topk(backend, queries, fetch):
    """The quantizer's own scan of every live row: (distances, ids)."""
    qrep = backend.prep_queries(queries)
    chunk = backend.config.search_chunk_size
    d, ids = backend.quantizer.search(
        qrep.code, backend.codes, fetch, backend.codes.valid_mask,
        chunk if backend.codes.capacity > chunk else 0)
    return qrep, np.asarray(d, np.float32), np.asarray(ids, np.int64)


def skip_rescore(self, queries, k, allow):
    _, d, ids = _code_topk(self, queries, k)
    return d, ids


def few_candidates(self, queries, k, allow):
    from weaviate_tpu.index.flat import exact_rescore

    qrep, _, ids = _code_topk(self, queries, k)
    res = exact_rescore(qrep.host, ids, self.originals, self.metric, k)
    return res.dists.astype(np.float32), res.ids.astype(np.int64)


FAULTS = {"skip_rescore": skip_rescore, "few_candidates": few_candidates}


def plant(fault: str) -> None:
    """Every ``QuantizedBackend`` searches flat by the faulty form (the
    cell sends no filter, so ``allow`` is not read)."""
    from weaviate_tpu.index.hnsw.backend import QuantizedBackend

    QuantizedBackend.flat_topk = FAULTS[fault]


if __name__ == "__main__":
    plant(os.environ.get("BENCH_FAULT", "skip_rescore"))
    from benchmark.serve import main

    sys.exit(main())
