"""Span tracing — context-propagated spans in a bounded ring buffer,
exportable as Chrome trace-event JSON (load in Perfetto / chrome://tracing).

Promotes utils/tracing.Trace from a log-only step timer to a real tracing
layer: `with span("burst.encode"): ...` records a complete ("X") event;
nesting is carried through a contextvar so child spans know their parent
even across the scheduler's bind threads. The buffer is a deque with a
fixed capacity — tracing is always on, costs one append per span, and old
spans fall off the back instead of growing memory.

Device-cost accounting: dispatch is asynchronous, so a span around the
launch measures the enqueue only. The TPU pipeline records cat="device"
spans around the packed-array readback (`np.asarray` / `jax.device_get`),
which waits for the result, and cat="host" spans around encode — host
encode vs device execution+readback separate in the trace viewer. These
are host-clock spans; device busy/idle share needs a profiler trace.

Consumers: `GET /debug/traces` on the apiserver, `bench.py --trace out.json`.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

DEFAULT_CAPACITY = 65536

# perf_counter anchor: Chrome wants microsecond timestamps on one clock
_ORIGIN = time.perf_counter()

_buf: deque = deque(maxlen=DEFAULT_CAPACITY)
_current: contextvars.ContextVar = contextvars.ContextVar(
    "obs_span", default=None)
_lock = threading.Lock()


def set_capacity(n: int) -> None:
    """Resize the ring (drops recorded spans)."""
    global _buf
    with _lock:
        _buf = deque(maxlen=max(int(n), 1))


def clear() -> None:
    _buf.clear()


def now() -> float:
    return time.perf_counter()


def _note_dropped(n: int = 1) -> None:
    """Book spans the ring overflowed away (the deque drops them silently;
    this is the observable tripwire). Lazy import: obs/__init__ imports
    this module, so the counter can only be fetched after init — drops are
    rare, and the registry's get-or-create makes the repeat lookup cheap."""
    try:
        from kubernetes_tpu import obs
        obs.counter(
            "obs_trace_dropped_total",
            "Spans dropped from the trace ring buffer on overflow (the "
            "ring keeps the newest spans; resize with "
            "obs.trace.set_capacity).").inc(n)
    except Exception:
        pass   # never let observability bookkeeping break a hot path


def add_span(name: str, t0: float, t1: float, cat: str = "host",
             args: Optional[dict] = None) -> None:
    """Record one complete span from explicit perf_counter timestamps —
    the hot-path API (no context manager overhead). `args` values must be
    JSON-serializable."""
    ev = {"name": name, "cat": cat, "ph": "X",
          "ts": (t0 - _ORIGIN) * 1e6, "dur": (t1 - t0) * 1e6,
          "pid": os.getpid(), "tid": threading.get_ident()}
    parent = _current.get()
    if args or parent:
        a = dict(args) if args else {}
        if parent:
            a.setdefault("parent", parent)
        ev["args"] = a
    buf = _buf
    if buf.maxlen is not None and len(buf) >= buf.maxlen:
        _note_dropped()
    buf.append(ev)


@contextmanager
def span(name: str, cat: str = "host", **args):
    """Context-manager span; nests via a contextvar so children record
    their parent chain (propagates across threads started with
    contextvars-aware APIs; explicit `parent=` beats inference)."""
    t0 = time.perf_counter()
    token = _current.set(name)
    try:
        yield
    finally:
        _current.reset(token)
        add_span(name, t0, time.perf_counter(), cat=cat,
                 args=args or None)


def events(limit: Optional[int] = None,
           cat: Optional[str] = None) -> list[dict]:
    """Snapshot of the recorded spans, oldest first. `cat` filters by span
    category (e.g. "device" vs "host"); `limit` keeps only the NEWEST N
    spans after filtering — the /debug/traces query knobs."""
    evs = list(_buf)
    if cat is not None:
        evs = [e for e in evs if e.get("cat") == cat]
    if limit is not None and limit >= 0:
        evs = evs[-limit:] if limit else []
    return evs


def to_chrome(limit: Optional[int] = None,
              cat: Optional[str] = None) -> dict:
    """Chrome trace-event JSON object — Perfetto and chrome://tracing both
    load it directly."""
    return {"traceEvents": events(limit=limit, cat=cat),
            "displayTimeUnit": "ms"}


def export(path: str) -> int:
    """Write the Chrome trace JSON to `path`; returns the span count."""
    evs = to_chrome()
    with open(path, "w") as f:
        json.dump(evs, f)
    return len(evs["traceEvents"])
