#!/usr/bin/env python3
"""Spread of each metric over sets of runs, as the contract defines it: the
distance between the first and third quartile (`statistics.quantiles(values,
n=4)`) as a share of the median.

    python3 benchmark/tools/spread.py <set1 files...> -- <set2 files...>

Each file holds one run's standard output (its last line is the result)."""
import json
import statistics
import sys


def values(files):
    out = {}
    for f in files:
        with open(f) as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.startswith("{")]
        if not lines:
            print(f"{f}: no result line", file=sys.stderr)
            continue
        res = json.loads(lines[-1])
        if not res["correct"]:
            print(f"{f}: correct is false", file=sys.stderr)
        for name, m in res["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def spread(xs):
    q = statistics.quantiles(xs, n=4)
    return (q[2] - q[0]) / statistics.median(xs)


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    vals = [values(s) for s in sets if s]
    for name in vals[0]:
        row = []
        for v in vals:
            xs = v.get(name, [])
            row.append(f"n={len(xs)} median={statistics.median(xs):.6g} "
                       f"spread={100 * spread(xs):.2f}%" if len(xs) >= 2
                       else f"n={len(xs)}")
        meds = [statistics.median(v[name]) for v in vals if name in v]
        drift = (f" second/first={meds[1] / meds[0]:.4f}"
                 if len(meds) > 1 and meds[0] else "")
        print(f"{name}: " + " | ".join(row) + drift)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
