"""``benchmark.serve`` with the timed path broken underneath, for
``test_faults.py``: ``BENCH_FAULT`` names the fault planted in the program
before its normal entry point runs.

``alter_answer``     the flat index's scan returns a wrong row at rank 2 of
                     every answer (an answer altered where it is produced).
``drop_half_batch``  ``BatchObjects`` stores the first half of each batch
                     and acknowledges all of it (half of the batch left out).
"""

from __future__ import annotations

import os
import sys


def plant(fault: str) -> None:
    if fault == "alter_answer":
        import weaviate_tpu.index.flat as flat

        real = flat.flat_search

        def altered(*a, **kw):
            d, ids = real(*a, **kw)
            return d, ids.at[:, 2].set((ids[:, 2] + 7) % 1000)

        flat.flat_search = altered
    elif fault == "drop_half_batch":
        import weaviate_tpu.api.grpc_server as plane

        real = plane.insert_grouped

        def dropped(db, items):
            items = list(items)
            return real(db, items[:max(1, len(items) // 2)])

        plane.insert_grouped = dropped
    else:
        raise ValueError(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ["BENCH_FAULT"])
    from benchmark.serve import main

    sys.exit(main())
