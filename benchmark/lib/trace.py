"""Reduction of a profiler trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData`, nothing else. A device plane is one
named `/device:TPU:<n>`; its operations are the events of the line named
`XLA Ops` and its programs those of `XLA Modules`. Busy time is the union of
the operations' intervals, so overlapping operations count once. Host spans
are the `TraceAnnotation`s the benchmark puts around its own calls; they are
on the same clock as the device lines, which is what lets an idle gap be
named by what the host was doing.
"""
from __future__ import annotations

import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"      # copies and collectives that overlap compute
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast", re.I)
HOST_SPANS = ("client.create", "client.watch_drain", "client.reap",
              "sched.pump", "sched.schedule_burst", "loop.step")
NO_SPAN = "(no benchmark span)"


def newest_xplane(log_dir: str) -> str | None:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def merge(intervals) -> list:
    """Sorted, disjoint [start, end] intervals covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@functools.lru_cache(maxsize=1 << 16)     # a trace repeats a few hundred names
def clean(name: str) -> str:
    """A short, stable name. The TPU's operation events carry the whole HLO
    instruction as their name (`%while.62 = (u32[5,16385]{...}, ...) while(...)`):
    keep the instruction's own name, `while.62`. A program's name loses its
    fingerprint: `jit__scatter_rows(1713...)` -> `jit__scatter_rows`."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def name_gaps(busy: list, host: list) -> dict:
    """Idle nanoseconds between consecutive busy intervals, and from the
    first host span's start to the first busy interval and from the last to
    the last host span's end, by the host span that covers them (`NO_SPAN`
    for what no span covers). `host` is sorted (start, end, name)."""
    gaps: dict[str, float] = {}
    hi = 0
    edges = [[e0, s1] for (_s0, e0), (s1, _e1) in zip(busy, busy[1:])]
    if host and busy:
        edges.insert(0, [min(h[0] for h in host), busy[0][0]])
        edges.append([busy[-1][1], max(h[1] for h in host)])
    for gs, ge in edges:
        if ge <= gs:
            continue
        while hi < len(host) and host[hi][1] <= gs:
            hi += 1
        covered = 0.0
        k = hi
        while k < len(host) and host[k][0] < ge:
            hs, he, name = host[k]
            part = min(he, ge) - max(hs, gs)
            if part > 0:
                gaps[name] = gaps.get(name, 0.0) + part
                covered += part
            k += 1
        rest = (ge - gs) - covered
        if rest > 0:
            gaps[NO_SPAN] = gaps.get(NO_SPAN, 0.0) + rest
    return gaps


def top(by_name: dict, scale: float, k: int = 10) -> list:
    return [[name, v * scale] for name, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:k]]


def reduce_xplane(path: str, host_spans=HOST_SPANS) -> dict | None:
    """Everything the readers take from a trace; None when the trace holds
    no device plane (a CPU rehearsal)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = []
    host = []          # (start_ns, end_ns, name) of the benchmark's spans
    line_names = set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            by_line = {OPS_LINE: [], ASYNC_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                line_names.add(line.name)
                if line.name in by_line:
                    by_line[line.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, clean(e.name))
                        for e in line.events]
            devices.append({"id": int(m.group(1)), "ops": by_line[OPS_LINE],
                            "async": by_line[ASYNC_LINE],
                            "modules": by_line[MODULES_LINE]})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_spans:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    if not devices:
        return None
    devices.sort(key=lambda d: d["id"])
    n = len(devices)
    busy_ns = []
    op_ns: dict[str, float] = {}
    coll_ns = 0.0
    mod_ns: dict[str, list] = {}
    for d in devices:
        src = d["ops"] or d["modules"]
        d["busy"] = merge((s, e) for s, e, _n in src)
        busy_ns.append(sum(e - s for s, e in d["busy"]))
        for s, e, name in d["ops"]:
            op_ns[name] = op_ns.get(name, 0.0) + (e - s)
        # a collective is told by the instruction's own name, on either line
        coll_ns += sum(e - s for s, e in merge(
            (s, e) for s, e, name in d["ops"] + d["async"]
            if COLLECTIVE.search(name)))
        for s, e, name in d["modules"]:
            rec = mod_ns.setdefault(name, [0.0, 0])
            rec[0] += e - s
            rec[1] += 1
    host.sort()
    gaps = name_gaps(devices[0]["busy"], host)
    return {
        "devices": n,
        "busy_s": sum(busy_ns) / n / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy_ns],
        "collective_s": coll_ns / n / 1e9,
        "device_ops": top(op_ns, 1.0 / n / 1e9),
        "modules": {k: {"seconds": v[0] / n / 1e9, "launches": v[1] / n}
                    for k, v in mod_ns.items()},
        "idle_gaps": top(gaps, 1e-9),
        "line_names": sorted(line_names),
        "host_spans": len(host),
    }
