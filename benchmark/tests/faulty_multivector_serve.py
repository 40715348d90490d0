"""``benchmark.serve`` with the multi-vector path broken underneath, for
``test_multivector.py`` and for the cell's control on the chip:
``BENCH_FAULT`` names the fault planted in the program before its normal
entry point runs (``skip_rescore`` where it is unset).

``skip_rescore``     every multi-vector request is served from the
                     candidate scan alone: the k best by FDE product, that
                     product as their score, no token set gathered and no
                     MaxSim computed. What the configuration's guarantee
                     ("every served score is the exact MaxSim of that
                     passage") forbids.
``few_candidates``   the exact rescore of the 64 best by FDE product, where
                     the collection was created with ``rescoreLimit`` 256:
                     every score is still the exact MaxSim, the list is
                     sorted, and a quarter of the gather and of the MaxSim is
                     paid. What "the ten best among the ``rescore_limit``
                     best passages by FDE product" forbids.
``few_repetitions``  every index is built with 2 of MUVERA's repetitions,
                     whatever the collection asked for (a 512-d FDE plane in
                     place of the 2,560-d one: a fifth of the scan's bytes).
                     What "fewer repetitions or a narrower FDE is a different
                     result" forbids. Only the candidates' quality shows it:
                     ``recall_miss``, ``rank_gap``.
"""

from __future__ import annotations

import os
import sys


SHRUNK = {"few_candidates": {"rescore_limit": 64},
          "few_repetitions": {"repetitions": 2}}


def plant(fault: str) -> None:
    if fault == "skip_rescore":
        from weaviate_tpu.index.multivector import MultiVectorIndex

        def fde_only(self, query_tokens, fde, cand_k, k, allow_list):
            return self.inner.search(fde, k, allow_list)

        MultiVectorIndex._search_multi_fused = fde_only
    elif fault in SHRUNK:
        import dataclasses

        from weaviate_tpu.index.multivector import MultiVectorIndex

        made = MultiVectorIndex.__init__

        def shrunk(self, dims, config=None):
            made(self, dims, dataclasses.replace(config, **SHRUNK[fault]))

        MultiVectorIndex.__init__ = shrunk
    else:
        raise ValueError(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ.get("BENCH_FAULT", "skip_rescore"))
    from benchmark.serve import main

    sys.exit(main())
