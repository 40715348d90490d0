"""Native (C++) components, compiled on demand with the system toolchain.

The reference ships pre-generated assembly kernels linked by the Go
toolchain (SURVEY.md §2.8); here the native tier is C++ compiled at first
use (g++ -O3 -march=native) and cached next to the sources under a file
name keyed on (source bytes, compiler flags, this machine's CPU): a
library built from other sources, with other flags or on another CPU —
one that travelled with a copy of the tree — has another name and is
never loaded. Every native component has a pure-Python twin; a failed
build or load serves from it, with one WARNING per library and
``weaviate_tpu_native_library{name,impl}`` saying which is in use.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional

from weaviate_tpu.monitoring.metrics import NATIVE_LIBRARY

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIBS: dict[str, Optional[ctypes.CDLL]] = {}
_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-march=native")

logger = logging.getLogger("weaviate_tpu.native")


class NativeUnavailable(RuntimeError):
    pass


def _cpu_identity() -> str:
    """What ``-march=native`` resolves against: the architecture plus the
    first CPU's model and feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            first_cpu = f.read().split("\n\n", 1)[0].splitlines()
    except OSError:
        first_cpu = [f"model name: {platform.processor()}"]
    return "\n".join([platform.machine()] + [
        line for line in first_cpu
        if line.split(":")[0].strip() in ("model name", "flags", "Features")])


def _lib_path(name: str) -> str:
    with open(os.path.join(_DIR, f"{name}.cpp"), "rb") as f:
        h = hashlib.sha256(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(_DIR, f"lib{name}.{h.hexdigest()[:16]}.so")


def _build(name: str) -> str:
    out = _lib_path(name)
    if os.path.exists(out):
        return out
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, "-o", tmp, os.path.join(_DIR, f"{name}.cpp")]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        raise NativeUnavailable(
            f"building {name}: {detail[:2000]}") from e
    os.replace(tmp, out)
    for stale in glob.glob(os.path.join(_DIR, f"lib{name}.*so")):
        if stale != out:
            try:
                os.remove(stale)
            except OSError:
                pass  # another process's; it loses nothing by keeping it
    return out


def _in_use(name: str, impl: str) -> None:
    for other in ("native", "python"):
        NATIVE_LIBRARY.set(1 if other == impl else 0, name=name, impl=other)


def load(name: str) -> ctypes.CDLL:
    """Load (building if needed) a native library by basename."""
    with _LOCK:
        if name in _LIBS:
            lib = _LIBS[name]
            if lib is None:
                raise NativeUnavailable(f"{name} previously failed to build")
            return lib
        try:
            lib = ctypes.CDLL(_build(name))
        except (NativeUnavailable, OSError) as e:
            _LIBS[name] = None
            _in_use(name, "python")
            logger.warning(
                "native library %s unavailable (%s): serving from its "
                "Python twin", name, e)
            raise NativeUnavailable(str(e)) from e
        _LIBS[name] = lib
        _in_use(name, "native")
        return lib


def available(name: str) -> bool:
    try:
        load(name)
        return True
    except NativeUnavailable:
        return False
