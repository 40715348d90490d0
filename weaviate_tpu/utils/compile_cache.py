"""Persistent XLA compilation cache: compiled programs survive restarts.

First-touch XLA compilation is the dominant cold-path tail everywhere the
system restarts, autoscales, or promotes a tenant (ROADMAP item 3): the
2PC commit leg carries a generous finish budget because a replica's
first-touch apply can cold-compile, and tiering cold-start SLOs absorb
recompiles whenever shapes drift. This module wires JAX's persistent
compilation cache to a node-local directory so a restarted process
DESERIALIZES yesterday's executables off disk instead of re-lowering and
re-optimizing them — seconds of XLA time become a disk read.

Where the cache lives. ``JAX_COMPILATION_CACHE_DIR`` is JAX's own
setting and wins outright: when it is set this module leaves
``jax_compilation_cache_dir`` exactly as JAX read it — no override, no
sub-directory — so an operator (or a harness that shares one cache
between runs) places the cache from outside. Otherwise the base
directory resolves as explicit ``configure()`` arg >
``WEAVIATE_TPU_COMPILE_CACHE_DIR`` env > the ``compile_cache_dir``
runtime knob > disabled, and the cache goes to a sub-directory of it
keyed on (jax version, jaxlib version, backend platform, device count),
so an image upgrade or a topology change (v5e-4 -> v5e-8 reslice) lands
in a fresh keyspace and invalidation is directory removal. The server
passes ``<checkout>/.jax_cache`` as its base (``server.py``): a fixed
path, because the path is part of what makes a later run hit.
``WEAVIATE_TPU_COMPILE_CACHE=off`` is the kill switch regardless. Absent
all of these the layer is inert — test processes and embedded uses pay
zero behavior change.

Observability: a jax monitoring listener counts cache hits (disk
deserialize) and misses (true compile) into
``weaviate_tpu_compile_cache_events_total``; the same counters feed
``monitoring/devtime.py``'s three-way phase classification (``compile``
vs ``cache_hit`` vs ``execute``) so the win is attributable per program
identity, not assumed. See docs/compile_cache.md.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

logger = logging.getLogger("weaviate_tpu.compile_cache")

ENV_DIR = "WEAVIATE_TPU_COMPILE_CACHE_DIR"
ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_SWITCH = "WEAVIATE_TPU_COMPILE_CACHE"

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_dir: Optional[str] = None  # the directory in use once configured
_hits = 0
_misses = 0
_listener_installed = False


def _switched_off() -> bool:
    return os.environ.get(ENV_SWITCH, "").lower() in ("off", "0", "false")


def resolve_base_dir(cache_dir: Optional[str] = None) -> Optional[str]:
    """The configured BASE directory (pre-keying), or None = disabled."""
    if _switched_off():
        return None
    if cache_dir:
        return cache_dir
    env = os.environ.get(ENV_DIR, "")
    if env:
        return env
    from weaviate_tpu.utils.runtime_config import COMPILE_CACHE_DIR

    knob = str(COMPILE_CACHE_DIR.get() or "")
    return knob or None


def keyed_dir(base: str) -> str:
    """``base`` narrowed to this process's program keyspace: (jax,
    jaxlib, backend platform, visible device count)."""
    import jax
    import jaxlib

    backend = jax.default_backend()
    ndev = jax.device_count()
    return os.path.join(
        base, f"jax{jax.__version__}-jaxlib{jaxlib.__version__}"
              f"-{backend}-d{ndev}")


def _note_event(event: str, **_kw) -> None:
    """jax monitoring listener (also the unit-test injection point for
    simulated cache traffic)."""
    global _hits, _misses
    if event == _HIT_EVENT:
        kind = "hit"
    elif event == _MISS_EVENT:
        kind = "miss"
    else:
        return
    from weaviate_tpu.monitoring.metrics import COMPILE_CACHE_EVENTS

    with _lock:
        if kind == "hit":
            _hits += 1
        else:
            _misses += 1
    COMPILE_CACHE_EVENTS.inc(event=kind)


def _unlatch_jax_cache() -> None:
    """jax initializes its persistent cache AT MOST ONCE per process
    (``_cache``/``_cache_checked`` latch on the first compile), so a
    config update alone is a no-op once anything has compiled — the
    latch must be reset for (re)configuration to take effect."""
    from jax._src.compilation_cache import reset_cache

    reset_cache()


def configure(cache_dir: Optional[str] = None) -> Optional[str]:
    """Wire the persistent cache; returns the directory in use, or None
    when the layer stays disabled. Idempotent; a second call with a
    different base re-points the cache (tests, operator re-config)."""
    global _dir, _listener_installed
    if _switched_off():
        return None
    import jax

    if os.environ.get(ENV_JAX_DIR):
        # placed from outside: jax read the variable itself and this
        # module sets no other directory
        path = jax.config.jax_compilation_cache_dir
    else:
        base = resolve_base_dir(cache_dir)
        if base is None:
            return None
        path = keyed_dir(base)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        _unlatch_jax_cache()
    # cache EVERYTHING: the defaults skip sub-second compiles, but the
    # restart proof needs every program in a dispatch to hit (one missed
    # helper jit would classify the whole bracket as a compile)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _lock:
        _dir = path
        if not _listener_installed:
            from jax._src import monitoring

            monitoring.register_event_listener(_note_event)
            _listener_installed = True
    logger.info("persistent compilation cache at %s", path)
    return path


def enabled() -> bool:
    return _dir is not None and not _switched_off()


def counters() -> tuple[int, int]:
    """(hits, misses) observed by this process so far — the feed for
    devtime's compile vs cache_hit classification."""
    with _lock:
        return _hits, _misses


def dir_bytes() -> int:
    if _dir is None:
        return 0
    total = 0
    try:
        with os.scandir(_dir) as it:
            for entry in it:
                try:
                    total += entry.stat().st_size
                except OSError:
                    continue
    except OSError:
        return 0
    return total


def stats() -> dict:
    """The /v1/debug/compile cache panel; refreshes the bytes gauge."""
    from weaviate_tpu.monitoring.metrics import COMPILE_CACHE_BYTES

    nbytes = dir_bytes()
    COMPILE_CACHE_BYTES.set(nbytes)
    hits, misses = counters()
    entries = 0
    if _dir is not None:
        try:
            entries = sum(1 for n in os.listdir(_dir)
                          if n.endswith("-cache"))
        except OSError:
            entries = 0
    return {
        "enabled": enabled(),
        "dir": _dir,
        "hits": hits,
        "misses": misses,
        "bytes": nbytes,
        "entries": entries,
    }


def reset_for_tests() -> None:
    """Forget configuration and counters, and detach jax from the (very
    possibly deleted-tmpdir) cache directory — later tests in the same
    process must compile exactly as an unconfigured process would."""
    global _dir, _hits, _misses
    with _lock:
        was = _dir
        _dir = None
        _hits = 0
        _misses = 0
    if was is not None and not os.environ.get(ENV_JAX_DIR):
        import jax

        jax.config.update("jax_compilation_cache_dir", None)
        _unlatch_jax_cache()
