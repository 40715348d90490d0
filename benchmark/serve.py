"""The server child: ``weaviate_tpu.server.main()`` — the program's normal
entry point — with the profiler on two signals, because only the process
that holds the chip can trace it.

SIGUSR1 starts ``jax.profiler`` into ``$BENCH_SERVE_OUT/trace``, SIGUSR2 stops
it; each writes a small JSON file when done (``trace_started.json``,
``trace_stopped.json``) that the parent waits for. After ``main()`` returns
(SIGTERM), the peak device memory goes to ``$BENCH_SERVE_OUT/device_exit.json``.
A ``--trace 0`` run starts the same child and never signals it.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time


def _write(out: str, name: str, payload: dict) -> None:
    tmp = os.path.join(out, name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, os.path.join(out, name))


def main() -> int:
    out = os.environ["BENCH_SERVE_OUT"]
    state = {}

    def start(*_):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # device ops and XLA host events only
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(os.path.join(out, "trace"),
                                 profiler_options=opts)
        state["t0"], state["unix0"] = time.perf_counter(), time.time_ns()
        _write(out, "trace_started.json", {"unix_ns": state["unix0"]})

    def stop(*_):
        import jax

        t1, unix1 = time.perf_counter(), time.time_ns()
        jax.profiler.stop_trace()
        _write(out, "trace_stopped.json", {
            "window_s": t1 - state["t0"], "start_unix_ns": state["unix0"],
            "stop_unix_ns": unix1})

    signal.signal(signal.SIGUSR1, start)
    signal.signal(signal.SIGUSR2, stop)

    from weaviate_tpu.server import main as server_main

    rc = server_main()
    import jax

    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use"))
    _write(out, "device_exit.json", {"peak_bytes_in_use": peaks})
    return rc


if __name__ == "__main__":
    sys.exit(main())
