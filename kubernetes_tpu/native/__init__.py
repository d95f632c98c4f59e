"""Native (C++) runtime components, built on demand with the system g++.

The compute path is JAX/XLA; the runtime around it follows the reference's
stance of natively-compiled infrastructure (the reference is Go throughout).
Components live here as single-file CPython extensions compiled lazily into
this directory (no pip, no network): `load(name)` rebuilds when the hash of
the source differs from the one stored beside the cached .so (mtimes say
nothing in a copied tree) and returns None when the build or the import
fails — every consumer keeps a pure-Python twin with identical semantics,
so a missing toolchain degrades performance, never behavior. The failure
is never silent: `load_error(name)` returns what went wrong, compiler
stderr included, for callers that must not run on the twin.
"""
from __future__ import annotations

import hashlib
import importlib.machinery
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache: dict[str, object] = {}
_errors: dict[str, str] = {}


def _asan() -> bool:
    """ASan build mode (KTPU_NATIVE_ASAN=1): compile the extensions with
    AddressSanitizer so native bugs surface as aborts-with-reports in a
    dedicated test run, not as silent heap corruption. The instrumented
    artifact gets its own cache name (never clobbers the fast build) and
    only imports when the ASan runtime is preloaded (tests/test_native.py
    runs a subprocess with LD_PRELOAD=libasan); anywhere else the import
    fails and consumers degrade to their twins as usual."""
    return os.environ.get("KTPU_NATIVE_ASAN") == "1"


def _so_path(name: str) -> str:
    tag = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    variant = "_asan" if _asan() else ""
    return os.path.join(_DIR, f"_{name}{variant}{tag}")


def _source_digest(src: str) -> str:
    with open(src, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build(name: str, force: bool = False) -> str:
    """Compile `name`.cpp to its .so unless the digest stored beside the
    cached artifact matches the source (or unconditionally with `force`,
    for a cached .so that matches but won't import — ABI-mismatched on
    this machine, e.g. built by a different Python)."""
    src = os.path.join(_DIR, f"{name}.cpp")
    out = _so_path(name)
    stamp = out + ".sha256"
    digest = _source_digest(src)
    if not force and os.path.exists(out) and os.path.exists(stamp):
        with open(stamp) as f:
            if f.read().strip() == digest:
                return out
    include = sysconfig.get_paths()["include"]
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
           f"-I{include}", src, "-o", tmp]
    if _asan():
        cmd[1:1] = ["-fsanitize=address", "-fno-omit-frame-pointer", "-g"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)     # a concurrent process never sees half a .so
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    with open(stamp, "w") as f:
        f.write(digest + "\n")
    return out


def _import_so(name: str, path: str):
    loader = importlib.machinery.ExtensionFileLoader(f"_{name}", path)
    spec = importlib.util.spec_from_file_location(
        f"_{name}", path, loader=loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


_LOAD_FAILURES = (OSError, subprocess.SubprocessError, ImportError)


def _describe(exc: BaseException) -> str:
    text = f"{type(exc).__name__}: {exc}"
    stderr = getattr(exc, "stderr", None)
    if stderr:
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        text += "\n" + stderr.strip()
    return text


def load(name: str):
    """Import native module `_name`, building it first if needed. An
    import failure of an up-to-date-looking .so forces one rebuild from
    source and retries (the digest can't see ABI mismatches). Returns the
    module, or None when building/loading fails — g++ absence included —
    so every consumer degrades to its pure-Python twin; `load_error` says
    why."""
    with _lock:
        if name in _cache:
            return _cache[name]
        mod = None
        try:
            mod = _import_so(name, _build(name))
        except _LOAD_FAILURES:
            try:
                mod = _import_so(name, _build(name, force=True))
            except _LOAD_FAILURES as e:
                _errors[name] = _describe(e)
        _cache[name] = mod
        return mod


def load_error(name: str):
    """Why `load(name)` returned None (exception text plus the compiler's
    stderr), or None when the module loaded or was never requested."""
    with _lock:
        return _errors.get(name)
