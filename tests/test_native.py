"""Native-extension build robustness (kubernetes_tpu.native).

The `.so` files are built on demand next to their `.cpp` source and are
never committed. Staleness is decided by a hash of the source stored
beside the artifact (a copied tree has arbitrary mtimes); an artifact whose
hash matches but which will not import (built by a different Python) is
rebuilt once; and when the toolchain is absent load() degrades to None
(every consumer's pure-Python twin) while load_error() says why.
"""
import os
import shutil
import subprocess
import time

import pytest

import kubernetes_tpu.native as native


@pytest.fixture
def sandbox(tmp_path, monkeypatch):
    """A throwaway build dir holding a copy of heapcore.cpp plus a corrupt
    .so whose stored digest MATCHES the source, so it looks up to date and
    tests never clobber the real artifact."""
    src = os.path.join(os.path.dirname(native.__file__), "heapcore.cpp")
    shutil.copy(src, tmp_path / "heapcore.cpp")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_cache", {})
    monkeypatch.setattr(native, "_errors", {})
    so = native._so_path("heapcore")
    with open(so, "wb") as f:
        f.write(b"\x7fELFnot-actually-loadable")
    with open(so + ".sha256", "w") as f:
        f.write(native._source_digest(str(tmp_path / "heapcore.cpp")))
    return so


needs_gxx = pytest.mark.skipif(shutil.which("g++") is None,
                               reason="g++ not available")


@needs_gxx
def test_rebuilds_when_cached_so_fails_to_import(sandbox):
    mod = native.load("heapcore")
    assert mod is not None, "import failure must force a rebuild"
    h = mod.HeapCore()
    h.add("k", 1.0, 2.0, 3.0, {"payload": True})
    assert h.peek() == {"payload": True}
    # the corrupt artifact was replaced by a real build
    assert os.path.getsize(sandbox) > 1024
    assert native.load_error("heapcore") is None


def test_falls_back_to_none_without_toolchain(sandbox, monkeypatch):
    def no_gxx(*a, **kw):
        raise FileNotFoundError("g++ not found")

    monkeypatch.setattr(subprocess, "run", no_gxx)
    assert native.load("heapcore") is None
    # the verdict is cached: consumers see one consistent answer
    assert native._cache["heapcore"] is None
    # ...and it is not silent
    assert "g++ not found" in native.load_error("heapcore")


@needs_gxx
def test_staleness_is_the_source_hash_not_mtime(sandbox):
    """A copied tree has arbitrary mtimes. An artifact NEWER than its
    source but built from different source must rebuild; one OLDER than
    its source with a matching digest must not."""
    future = time.time() + 3600
    os.utime(sandbox, (future, future))
    with open(sandbox + ".sha256", "w") as f:
        f.write("0" * 64)                   # built from some other source
    assert native._build("heapcore") == sandbox
    assert os.path.getsize(sandbox) > 1024  # rebuilt despite the mtime
    built = os.path.getmtime(sandbox)
    past = time.time() - 3600
    os.utime(sandbox, (past, past))         # now it LOOKS stale by mtime
    assert native._build("heapcore") == sandbox
    assert os.path.getmtime(sandbox) == pytest.approx(past, abs=1.0), \
        "matching digest must not rebuild"
    assert built > past


@needs_gxx
def test_compiler_stderr_is_reported(sandbox, tmp_path):
    """A source that does not compile: load() still degrades to None for
    the library's consumers, and load_error() carries g++'s own words so
    a caller that must not run on the twin can fail with them."""
    with open(tmp_path / "heapcore.cpp", "a") as f:
        f.write("\nthis is not c++;\n")
    assert native.load("heapcore") is None
    msg = native.load_error("heapcore")
    assert "CalledProcessError" in msg and "error" in msg.lower()
    assert "heapcore.cpp" in msg            # the compiler's diagnostic


def test_heap_twin_equivalence_after_fallback(sandbox, monkeypatch):
    """The consumer-visible contract: with the native core unavailable the
    queue heap still works, via the pure-Python twin."""
    monkeypatch.setattr(subprocess, "run",
                        lambda *a, **kw: (_ for _ in ()).throw(OSError()))
    assert native.load("heapcore") is None
    from kubernetes_tpu.utils.heap import NumericKeyedHeap
    h = NumericKeyedHeap(lambda it: it[0], lambda it: it[1])
    h.add(("b", (2.0, 0.0, 0.0)))
    h.add(("a", (1.0, 0.0, 0.0)))
    assert h.pop()[0] == "a"
    assert h.pop()[0] == "b"
