"""``benchmark.serve`` with the hybrid path broken underneath, for
``test_hybrid.py`` and for the cell's control on the chip: ``BENCH_FAULT``
names the fault planted in the program before its normal entry point runs
(``shed_sparse`` where it is unset).

``shed_sparse``  every hybrid request is served as if its sparse leg had
                 outlived the deadline: the dense leg alone is fused, under
                 its own weight. What the configuration's guarantee ("both
                 whole legs") forbids.
"""

from __future__ import annotations

import os
import sys


def plant(fault: str) -> None:
    if fault == "shed_sparse":
        from weaviate_tpu.core.collection import Collection

        real = Collection.hybrid_search

        def dense_only(self, query=None, *a, **kw):
            return real(self, None, *a, **kw)

        Collection.hybrid_search = dense_only
    else:
        raise ValueError(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ.get("BENCH_FAULT", "shed_sparse"))
    from benchmark.serve import main

    sys.exit(main())
