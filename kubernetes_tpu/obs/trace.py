"""Span tracing — context-propagated spans in a bounded ring buffer,
exportable as Chrome trace-event JSON (load in Perfetto / chrome://tracing).

Promotes utils/tracing.Trace from a log-only step timer to a real tracing
layer: `with span("burst.encode"): ...` records a complete ("X") event;
nesting is carried through a contextvar so child spans know their parent
even across the scheduler's bind threads. The buffer is a deque with a
fixed capacity — tracing is always on, costs one append per span, and old
spans fall off the back instead of growing memory.

One span, two places. A span opened through `span()` / `begin()` is
recorded once and lands in the ring and, whenever a `jax.profiler` session
is running, in the profiler's own trace as a `TraceAnnotation` of the same
name — on the profiler's clock, beside the device's lines, so an idle gap
of the chip can be named by the program span that covers it. With no
session running the annotation is one `is_enabled()` check. Spans of one
launch window share its sequence number (`next_window()`): `args.window`
in the ring, `window` metadata on the annotation. The budget on the bind
path is a span per pump, per window and per commit wave, and per poll
batch and handler run inside a pump — never per pod, per node or per event.

Device-cost accounting: dispatch is asynchronous, so a span around the
launch measures the enqueue only. The TPU pipeline records cat="device"
spans around the packed-array readback (`np.asarray` / `jax.device_get`),
which waits for the result, and cat="host" spans around encode — host
encode vs device execution+readback separate in the trace viewer. These
are host-clock spans; device busy/idle share needs a profiler trace.

Consumers: `GET /debug/traces` on the apiserver, `benchmark/tools/stalls.py`.
"""
from __future__ import annotations

import contextvars
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

DEFAULT_CAPACITY = 65536

# perf_counter anchor: Chrome wants microsecond timestamps on one clock
_ORIGIN = time.perf_counter()

_buf: deque = deque(maxlen=DEFAULT_CAPACITY)
_current: contextvars.ContextVar = contextvars.ContextVar(
    "obs_span", default=None)
_lock = threading.Lock()
# the launch window the calling thread is in (0 = none yet): a contextvar,
# so two schedulers on two threads keep their own
_window: contextvars.ContextVar = contextvars.ContextVar(
    "obs_window", default=0)
_window_seq = itertools.count(1)
# jax.profiler.TraceAnnotation, looked up once jax is in the process (this
# module must not be the one that imports jax: conftest and the CLIs set
# the platform first, and the apiserver never needs it)
_annotation = None


def next_window() -> int:
    """Open the next launch window on this thread: every span recorded
    from here until the next call carries the returned sequence number. A
    pump carries the number of the window whose binds it digests."""
    w = next(_window_seq)
    _window.set(w)
    return w


def set_capacity(n: int) -> None:
    """Resize the ring (drops recorded spans)."""
    global _buf
    with _lock:
        _buf = deque(maxlen=max(int(n), 1))


def clear() -> None:
    _buf.clear()


def now() -> float:
    return time.perf_counter()


_dropped = None     # the obs_trace_dropped_total counter, once fetched


def _note_dropped(n: int = 1) -> None:
    """Book spans the ring overflowed away (the deque drops them silently;
    this is the observable tripwire). Lazy import: obs/__init__ imports
    this module, so the counter can only be fetched after init; a server
    that has run for a while drops one span per span recorded, so the
    counter is fetched once."""
    global _dropped
    try:
        if _dropped is None:
            from kubernetes_tpu import obs
            _dropped = obs.counter(
                "obs_trace_dropped_total",
                "Spans dropped from the trace ring buffer on overflow (the "
                "ring keeps the newest spans; resize with "
                "obs.trace.set_capacity).")
        _dropped.inc(n)
    except Exception:
        pass   # never let observability bookkeeping break a hot path


def add_span(name: str, t0: float, t1: float, cat: str = "host",
             args: Optional[dict] = None) -> None:
    """Record one complete span in the ring from explicit perf_counter
    timestamps, after the fact: for an interval that is no scoped region
    of one thread (a launch in flight across other work, a step timeline
    folded in once it proved slow). It cannot reach the profiler's trace —
    an annotation is opened and closed, not written afterwards — so host
    work on the bind path uses `span()` / `begin()`. `args` values must be
    JSON-serializable."""
    _record(name, t0, t1, cat, args, _current.get(), _window.get())


def _record(name: str, t0: float, t1: float, cat: str,
            args: Optional[dict], parent, window: int) -> None:
    # the ring holds flat tuples (no per-span dict for the collector to
    # walk); `events()` gives them their Chrome form
    buf = _buf
    if buf.maxlen is not None and len(buf) >= buf.maxlen:
        _note_dropped()
    buf.append((name, cat, t0, t1, threading.get_ident(), args, parent,
                window))


def _chrome(rec: tuple, pid: int) -> dict:
    name, cat, t0, t1, tid, args, parent, window = rec
    ev = {"name": name, "cat": cat, "ph": "X",
          "ts": (t0 - _ORIGIN) * 1e6, "dur": (t1 - t0) * 1e6,
          "pid": pid, "tid": tid}
    if args or parent or window:
        a = dict(args) if args else {}
        if parent:
            a.setdefault("parent", parent)
        if window:
            a.setdefault("window", window)
        ev["args"] = a
    return ev


def _profiler_annotation(sp: "Span"):
    """An entered TraceAnnotation when a profiler session is running,
    else None. The session check is the whole cost when none is."""
    global _annotation
    cls = _annotation
    if cls is None:
        if "jax" not in sys.modules:
            return None     # no jax in the process: no session either
        from jax.profiler import TraceAnnotation
        cls = _annotation = TraceAnnotation
    if not cls.is_enabled():
        return None
    meta = dict(sp.args) if sp.args else {}
    if sp._window:
        meta.setdefault("window", sp._window)
    ann = cls(sp.name, **meta)
    ann.__enter__()
    return ann


class Span:
    """A scoped region: `with span(name): ...`, or `sp = begin(name)` ...
    `sp.end()` where the region's two ends are not one block. Nests via a
    contextvar so children record their parent (propagates across threads
    started with contextvars-aware APIs; explicit `parent=` beats
    inference)."""

    __slots__ = ("name", "cat", "args", "t0", "t1", "_parent", "_window",
                 "_token", "_ann")

    def __init__(self, name: str, cat: str = "host",
                 args: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Span":
        self._window = _window.get()
        self._ann = _profiler_annotation(self)
        self._parent = _current.get()
        self._token = _current.set(self.name)
        self.t0 = time.perf_counter()
        return self

    def end(self, **more) -> float:
        """Close the region; returns its end (perf_counter). `more` is
        added to the span's args: what only the end knows (a count)."""
        self.t1 = t1 = time.perf_counter()
        _current.reset(self._token)
        args = self.args
        if more:
            args = {**args, **more} if args else more
        if self._ann is not None:
            if more:
                self._ann.set_metadata(**more)
            self._ann.__exit__(None, None, None)
        _record(self.name, self.t0, t1, self.cat, args, self._parent,
                self._window)
        return t1

    def cancel(self) -> None:
        """Close the region and record nothing in the ring: it turned out
        empty (a drain that popped no pod). An annotation already open in
        the profiler's trace cannot be taken back: it closes, a few
        microseconds long, marked `empty`."""
        _current.reset(self._token)
        if self._ann is not None:
            self._ann.set_metadata(empty=1)
            self._ann.__exit__(None, None, None)

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


def span(name: str, cat: str = "host", **args) -> Span:
    """`with span("burst.plan"): ...` — one region, recorded once, in the
    ring and (when a profiler session runs) in the profiler's trace."""
    return Span(name, cat, args or None)


def begin(name: str, cat: str = "host", **args) -> Span:
    """`span()` already entered: close it with `.end()`."""
    return Span(name, cat, args or None).__enter__()


def events(limit: Optional[int] = None,
           cat: Optional[str] = None) -> list[dict]:
    """Snapshot of the recorded spans, oldest first. `cat` filters by span
    category (e.g. "device" vs "host"); `limit` keeps only the NEWEST N
    spans after filtering — the /debug/traces query knobs."""
    recs = list(_buf)
    if cat is not None:
        recs = [r for r in recs if r[1] == cat]
    if limit is not None and limit >= 0:
        recs = recs[-limit:] if limit else []
    pid = os.getpid()
    return [_chrome(r, pid) for r in recs]


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
