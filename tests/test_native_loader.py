"""The native loader only loads what this machine built from the committed
source, and says so when it serves from a Python twin instead."""

import glob
import logging
import os
import shutil

import pytest

from weaviate_tpu import native
from weaviate_tpu.monitoring.metrics import NATIVE_LIBRARY


@pytest.fixture()
def sandbox(tmp_path, monkeypatch):
    """A private copy of the native sources with a fresh loader state."""
    for src in glob.glob(os.path.join(native._DIR, "*.cpp")):
        shutil.copy(src, tmp_path)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_LIBS", {})
    return tmp_path


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_foreign_library_is_never_loaded(sandbox):
    """A lib*.so that travelled with a copy of the tree (other machine,
    other source) has another name: it is ignored, rebuilt, and removed."""
    foreign = sandbox / "libsegment_merge.so"
    foreign.write_bytes(b"\x7fELF built somewhere else")
    other_key = sandbox / "libsegment_merge.0123456789abcdef.so"
    other_key.write_bytes(b"\x7fELF other cpu")
    lib = native.load("segment_merge")
    assert hasattr(lib, "merge_replace_segments")
    built = os.path.basename(native._lib_path("segment_merge"))
    assert sorted(p.name for p in sandbox.glob("*.so")) == [built]
    assert NATIVE_LIBRARY.value(name="segment_merge", impl="native") == 1
    # the key moves with the source: an edited .cpp never meets this file
    with open(sandbox / "segment_merge.cpp", "a") as f:
        f.write("\n// edited\n")
    assert os.path.basename(native._lib_path("segment_merge")) != built


def test_python_twin_is_announced_once(sandbox, monkeypatch, caplog):
    def no_compiler(*a, **kw):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(native.subprocess, "run", no_compiler)
    with caplog.at_level(logging.WARNING, logger="weaviate_tpu.native"):
        for _ in range(3):
            with pytest.raises(native.NativeUnavailable):
                native.load("bm25_wand")
        assert not native.available("bm25_wand")
    said = [r for r in caplog.records if "Python twin" in r.getMessage()]
    assert len(said) == 1 and said[0].levelno == logging.WARNING
    assert NATIVE_LIBRARY.value(name="bm25_wand", impl="python") == 1
