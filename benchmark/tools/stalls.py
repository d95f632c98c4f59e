#!/usr/bin/env python3
"""Name what stalled an untraced run, from the program's own span ring.

    python3 benchmark/tools/stalls.py <cell> <seed> <seconds> [key=value ...]

One run of the cell through `run.execute` with the profiler off, as the
driver measures it. The program's ring (`obs.trace`, always on) then holds
every span of the window; the client's own calls are put there too, for this
tool only. Printed, for the window:

- every leaf span (one with no span inside it) longer than `factor` (10)
  times the median of its name, with its parent chain, its window number and
  its args;
- every stretch longer than `gap_ms` (50) that no span covers;
- per name: count, median, maximum and total seconds, and the program's spans
  per window (`burst.wave.device`, written after the fact, left out).

keys: factor=<n> gap_ms=<n>, and for rehearsals on the CPU backend
rehearse=1 nodes=<n> backlog=<n> rate=<n>. The last line is one JSON object;
the whole listing goes to `chiprun_out/stalls-<cell>-<seed>.json`."""
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402
from lib import trace as tr  # noqa: E402

RING = 1 << 20      # spans the ring keeps for this run (the default is 64Ki)


def nest(events: list) -> list:
    """Each event with `chain` (its parents, outermost first) and `leaf`,
    by containment among the spans of its thread."""
    out = []
    by_tid: dict = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list = []
        for e in evs:
            while stack and stack[-1]["ts"] + stack[-1]["dur"] <= e["ts"]:
                stack.pop()
            for p in stack:
                p["leaf"] = False
            rec = dict(e, chain=[p["name"] for p in stack], leaf=True)
            stack.append(rec)
            out.append(rec)
    return out


def uncovered(events: list, t0: float, t1: float, least_us: float) -> list:
    """[start, length] in microseconds of the stretches of [t0, t1] longer
    than `least_us` that no span covers."""
    out = []
    pos = t0
    for s, e in tr.merge((e["ts"], e["ts"] + e["dur"]) for e in events):
        if s - pos > least_us:
            out.append([pos, s - pos])
        pos = max(pos, e)
    if t1 - pos > least_us:
        out.append([pos, t1 - pos])
    return out


def analyse(events: list, window_s: float, factor: float,
            gap_ms: float) -> dict:
    """`events` are the ring's (Chrome form, microseconds); the window is
    the last `window_s` seconds before the last span's end."""
    # a launch in flight overlaps its neighbours: no scoped region, so it
    # has no place in the nesting
    events = [e for e in events if e["name"] != "burst.wave.device"]
    if not events:
        return {"spans": 0}
    t1 = max(e["ts"] + e["dur"] for e in events)
    t0 = t1 - window_s * 1e6
    inside = [e for e in nest(events) if e["ts"] >= t0]
    by_name: dict = {}
    for e in inside:
        by_name.setdefault(e["name"], []).append(e["dur"])
    median = {n: statistics.median(d) for n, d in by_name.items()}
    slow = [{"name": e["name"], "ms": e["dur"] / 1e3,
             "median_ms": median[e["name"]] / 1e3,
             "at_s": (e["ts"] - t0) / 1e6, "chain": e["chain"],
             "window": (e.get("args") or {}).get("window"),
             "args": {k: v for k, v in (e.get("args") or {}).items()
                      if k not in ("parent", "window")}}
            for e in inside
            if e["leaf"] and e["dur"] > factor * median[e["name"]]
            and len(by_name[e["name"]]) >= 5]
    slow.sort(key=lambda r: -r["ms"])
    gaps = [{"at_s": (s - t0) / 1e6, "ms": d / 1e3}
            for s, d in uncovered(inside, t0, t1, gap_ms * 1e3)]
    windows = {(e.get("args") or {}).get("window") for e in inside
               if e["name"] == "burst.plan"}
    return {
        "spans": len(inside), "windows": len(windows),
        # the program's own: not the client's, not the benchmark's wrappers
        "spans_per_window": (len([e for e in inside if not e["name"].startswith(
            ("client.", "sched.", "loop."))]) / len(windows)
            if windows else None),
        "by_name": {n: {"count": len(d), "median_ms": median[n] / 1e3,
                        "max_ms": max(d) / 1e3,
                        "total_s": sum(d) / 1e6}
                    for n, d in sorted(by_name.items())},
        "slow_leaf_spans": slow, "uncovered": gaps,
    }


def main(argv) -> int:
    cell, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    opts = dict(a.split("=", 1) for a in argv[3:])
    from kubernetes_tpu import obs
    from lib import client

    # the client's calls go to the ring for this run (in a traced run they
    # go to the profiler's trace alone): a stretch that no span covers is
    # then neither the program's nor the client's
    client.Client.span = lambda self, name: obs.trace.span(name)
    overrides = {}
    if "nodes" in opts:
        overrides["config"] = {"nodes": {"count": int(opts["nodes"])},
                               "check": {"first_binds": 200,
                                         "sampled_binds": 60}}
    if "backlog" in opts:
        overrides["traffic"] = {"warm_binds": 0,
                                "backlog": int(opts["backlog"])}
    if "rate" in opts:
        overrides["traffic"] = {"warm_binds": 0, "lifetime_s": 0.4,
                                "arrival": {"rate_per_s": float(opts["rate"])},
                                "serve": {"window_size": 64}}
    out = run.execute(
        cell, seed, seconds, False, rehearse=opts.get("rehearse") == "1",
        overrides=overrides,
        hook=lambda sched, store: obs.trace.set_capacity(RING))
    rep = out["report"]
    window_s = rep["window_s"] + (rep.get("settle_s") or 0.0)
    found = analyse(obs.trace.events(), window_s,
                    float(opts.get("factor", 10)),
                    float(opts.get("gap_ms", 50)))
    found.update(cell=cell, seed=seed, correct=out["result"]["correct"],
                 metrics={k: v["value"]
                          for k, v in out["result"]["metrics"].items()},
                 cycle_s=rep.get("cycle_s"), slowest=rep.get("slowest"))
    os.makedirs(os.path.join(run.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(run.ROOT, "chiprun_out",
                           f"stalls-{cell}-{seed}.json"), "w") as f:
        json.dump(found, f, indent=1)
    for r in found.get("slow_leaf_spans", [])[:20]:
        run.say(f"  slow {r['ms']:9.1f} ms (median {r['median_ms']:.2f}) "
                f"at {r['at_s']:6.2f} s  {'/'.join(r['chain'] + [r['name']])}"
                f"  window {r['window']} {r['args']}")
    for g in found.get("uncovered", [])[:20]:
        run.say(f"  uncovered {g['ms']:9.1f} ms at {g['at_s']:6.2f} s")
    brief = {k: found.get(k) for k in ("cell", "seed", "correct", "metrics",
                                       "spans", "windows",
                                       "spans_per_window")}
    brief["slow_leaf_spans"] = len(found.get("slow_leaf_spans", []))
    brief["uncovered"] = len(found.get("uncovered", []))
    brief["longest_ms"] = max(
        [r["ms"] for r in found.get("slow_leaf_spans", [])]
        + [g["ms"] for g in found.get("uncovered", [])] + [0.0])
    print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
