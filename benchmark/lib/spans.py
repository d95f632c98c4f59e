"""The program's own spans and the kernels' named scopes in a profiler trace.

`lib/trace.reduce_xplane` keeps the benchmark's six span names and the ten
largest gaps. The program now opens spans of its own through
`obs.trace.span` (one region, recorded once, in the program's ring and in
the profiler's trace as a `TraceAnnotation`), and names its kernel stages
with `jax.named_scope`. This module reads both from the same `.xplane.pb`:

- every host span whose name has one of `SPAN_PREFIXES`, flattened per
  thread into disjoint segments that each carry the *innermost* span's name:
  a span's self time is the length of its segments, and an idle gap of the
  chip goes to the innermost span over it (`lib.trace.name_gaps` on the
  segments);
- the device's leaf operations (`XLA Ops`; an enclosing `while` is not
  counted again) with the named scope of each, looked up by instruction name
  in the HLO the profiler keeps for every program it saw (plane
  `/host:metadata`, stat `Hlo Proto`; the TPU's events themselves carry the
  HLO text of the instruction and no scope). `jax.profiler.ProfileData` does
  not reach that plane's metadata, so those few fields are read from the
  protobuf's wire format directly.

A run's trace is parsed once (`load`, cached by path). A program without
such spans or scopes (an older commit) gives empty results, never an error.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os

from lib import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the benchmark's own spans (client.*, sched.*, loop.*) and the program's
SPAN_PREFIXES = ("client.", "sched.", "loop.", "pump.", "burst.", "store.",
                 "cycle.", "preempt.", "pressure.")
WRAPPERS = ("sched.", "loop.")    # the benchmark's spans around the program
SCOPES = ("filter", "score", "pick", "fold")   # ops/kernels.py SCOPES


def find_xplane(root: str = ROOT) -> str | None:
    """The trace of the run in progress: `run.py` writes it under
    `.bench_trace/<cell>-<seed>/` and removes it after the readers ran."""
    found = [tr.newest_xplane(d)
             for d in glob.glob(os.path.join(root, ".bench_trace", "*"))]
    found = [p for p in found if p]
    return max(found, key=os.path.getmtime) if found else None


# -- host spans --------------------------------------------------------------
def flatten(spans: list) -> list:
    """Disjoint (start, end, name) segments of one thread's nested spans,
    each named by the innermost span over it, in time order. A span that
    only partly overlaps its predecessor is cut to fit inside it."""
    out: list = []
    stack: list = []
    pos = 0

    def close_until(t) -> None:
        nonlocal pos
        while stack and stack[-1][1] <= t:
            _s, e, name = stack.pop()
            if e > pos:
                out.append((pos, e, name))
                pos = e

    for s, e, name in sorted(spans, key=lambda r: (r[0], -r[1])):
        close_until(s)
        if stack:
            e = min(e, stack[-1][1])
        if e <= s:
            continue
        if stack and s > pos:
            out.append((pos, s, stack[-1][2]))
        pos = max(pos, s)
        stack.append((s, e, name))
    close_until(float("inf"))
    return out


def matches(name: str, wanted: list) -> bool:
    """`wanted` holds span names; one that ends in `.` is a prefix."""
    return any(name == w or (w.endswith(".") and name.startswith(w))
               for w in wanted)


# -- the HLO the profiler kept: instruction name -> named scope --------------
def _varint(b: bytes, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            val = b[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = b[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire}")
        yield num, wire, val


def _sub(b: bytes, num: int) -> list:
    return [v for f, w, v in _fields(b) if f == num and w == 2]


def scope_of(op_name: str) -> str | None:
    """The innermost named scope in an op's name stack."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def program_scopes(raw: bytes) -> dict:
    """{program name as the `XLA Modules` line has it: {instruction name:
    scope}} for every instruction under a named scope. A fusion takes its
    own name stack's scope; one without any takes the commonest scope of
    the instructions it fused. Field numbers: xplane.proto (XSpace.planes 1;
    XPlane.name 2, event_metadata 4; XEventMetadata.name 2, stats 5;
    XStat.bytes_value 6), hlo.proto (HloProto.hlo_module 1; module
    .computations 3; computation.name 1, .instructions 2, .id 5; instruction
    .name 1, .metadata 7, .called_computation_ids 38; OpMetadata.op_name 2)."""
    out: dict = {}
    for plane in _sub(raw, 1):
        if _sub(plane, 2)[:1] != [b"/host:metadata"]:
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                program = b"".join(_sub(meta, 2)[:1]).decode()
                for stat in _sub(meta, 5):
                    for hlo in _sub(stat, 6):
                        for module in _sub(hlo, 1):
                            found = _module_scopes(module)
                            if found:
                                out.setdefault(program, {}).update(found)
    return out


def _module_scopes(module: bytes) -> dict:
    own: dict = {}        # instruction name -> scope of its own name stack
    calls: dict = {}      # instruction name -> called computation ids
    inside: dict = {}     # computation id -> scopes of its instructions
    for comp in _sub(module, 3):
        comp_id = next((v for f, w, v in _fields(comp)
                        if f == 5 and w == 0), None)
        scopes = inside.setdefault(comp_id, [])
        for ins in _sub(comp, 2):
            name = op_name = ""
            called = []
            for f, w, v in _fields(ins):        # one pass per instruction
                if f == 1 and w == 2:
                    name = v.decode()
                elif f == 7 and w == 2:
                    op_name = b"".join(_sub(v, 2)[:1]).decode()
                elif f == 38 and w == 0:
                    called.append(v)
                elif f == 38:                   # packed
                    i = 0
                    while i < len(v):
                        x, i = _varint(v, i)
                        called.append(x)
            sc = scope_of(op_name)
            if sc:
                own[name] = sc
                scopes.append(sc)
            if called:
                calls[name] = called
    for name, called in calls.items():
        if name not in own:
            fused = [s for c in called for s in inside.get(c, [])]
            if fused:
                own[name] = max(SCOPES, key=fused.count)
    return own


# -- one parse per run ---------------------------------------------------------
@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """What the two readers take from one trace:

    `segments`   disjoint (start_ns, end_ns, innermost span name), sorted
    `busy0`      chip 0's merged busy intervals
    `scope_ns`   {scope: device nanoseconds of the leaf operations under it,
                 averaged over the chips}
    `spans`      how many spans were read, by name"""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    try:
        scopes = program_scopes(raw)
    except (ValueError, IndexError, UnicodeDecodeError):
        scopes = {}     # a wire format this reader does not know: no scopes
    pd = ProfileData.from_file(path)
    segments: list = []
    counts: dict = {}
    devices: dict = {}
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            ops = modules = ()
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == tr.MODULES_LINE:
                    modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events]
            devices[int(m.group(1))] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                # (a drain pass that popped nothing closes marked `empty`)
                mine = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIXES)
                        and not any(k == "empty" for k, _v in e.stats)]
                for _s, _e, name in mine:
                    counts[name] = counts.get(name, 0) + 1
                segments.extend(flatten(mine))
    segments.sort()
    scope_ns = {s: 0.0 for s in SCOPES}
    busy0: list = []
    for dev, (ops, modules) in sorted(devices.items()):
        if dev == min(devices):
            busy0 = tr.merge((s, e) for s, e, _n in (ops or modules))
        if not scopes:
            continue
        modules = sorted(modules)
        starts = [m[0] for m in modules]
        ops = sorted(ops, key=lambda r: (r[0], -r[1]))
        for k, (s, e, text) in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1][0] < e:
                continue        # it encloses the next one: not a leaf
            j = bisect.bisect_right(starts, s) - 1
            if j < 0 or modules[j][1] < s:
                continue
            sc = scopes.get(modules[j][2], {}).get(tr.clean(text))
            if sc:
                scope_ns[sc] += e - s
    n = max(1, len(devices))
    return {"segments": segments, "busy0": busy0, "spans": counts,
            "scope_ns": {s: v / n for s, v in scope_ns.items()},
            "scoped_programs": sorted(scopes)}
