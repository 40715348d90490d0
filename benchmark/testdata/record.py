"""Records the small trace ``flat_scan_tpu.xplane.pb`` that ``selfcheck.py``
reads: 5 executions of the program's ``flat_search`` (1 x 128 query, 8,192 x
128 corpus, k=10, exact selection) on one chip under the same profiler options
as ``benchmark/serve.py``. Run on the chip, by hand:
``python3 benchmark/testdata/record.py <out_dir>``. Holds the chip itself.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from weaviate_tpu.ops.distance import flat_search

    rng = np.random.default_rng(0)
    corpus = jnp.asarray(rng.standard_normal((8192, 128), dtype=np.float32))
    valid = jnp.ones((8192,), bool)
    q = jnp.asarray(rng.standard_normal((1, 128), dtype=np.float32))

    def scan():
        d, i = flat_search(q, corpus, k=10, metric="cosine", valid_mask=valid,
                           precision="bf16")
        return np.asarray(i)

    scan()                                  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    trace_dir = os.path.join(out_dir, "record_trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(5):
        scan()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "flat_scan_tpu.xplane.pb"))
    shutil.rmtree(trace_dir)
    print(jax.devices()[0].device_kind, os.path.getsize(
        os.path.join(out_dir, "flat_scan_tpu.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
