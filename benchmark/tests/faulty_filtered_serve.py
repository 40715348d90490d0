"""``benchmark.serve`` with the filtered path broken underneath, for
``test_filtered.py``: ``BENCH_FAULT`` names the fault planted in the program
before its normal entry point runs.

``ignore_filter``  the gRPC plane drops every request's ``where_json``: the
                   answers are the unfiltered nearest rows.
``drop_last_hit``  every vector search loses its last hit: an answer one
                   shorter than the filter allows.
"""

from __future__ import annotations

import os
import sys


def plant(fault: str) -> None:
    if fault == "ignore_filter":
        import weaviate_tpu.api.grpc_server as plane

        plane.where_to_filter = lambda where: None
    elif fault == "drop_last_hit":
        from weaviate_tpu.core.collection import Collection

        real = Collection.vector_search

        def shortened(self, *a, **kw):
            return real(self, *a, **kw)[:-1]

        Collection.vector_search = shortened
    else:
        raise ValueError(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ["BENCH_FAULT"])
    from benchmark.serve import main

    sys.exit(main())
