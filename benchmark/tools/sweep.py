#!/usr/bin/env python3
"""The knee sweep of an open-loop cell: one fresh process per rate (each its
own cluster from the seed), one row per rate.

    python3 benchmark/tools/sweep.py <cell> <seconds> <rate> [<rate> ...]

Row: rate offered, arrivals, share completed (bound and seen in the window or
its settle), 429s, the client's backlog (submitted, not yet seen bound) at
the middle and at the end of the window, settle seconds, p50/p95/p99 ms.
The knee is the highest rate with no 429, every arrival completed, and a
backlog no deeper at the end than at the middle (give or take one window)."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv) -> int:
    cell, seconds, rates = argv[0], argv[1], argv[2:]
    print("rate attempted completed_share rejected_429 mid_backlog end_backlog "
          "settle_s p50_ms p95_ms setup_s correct", flush=True)
    for k, rate in enumerate(rates):
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "probe.py"), cell,
             str(7000 + k), seconds, "0", f"rate={rate}"],
            capture_output=True, text=True)
        rep = res = None
        for line in p.stdout.splitlines():
            if line.startswith("report "):
                rep = json.loads(line[len("report "):])
            elif line.startswith("{"):
                res = json.loads(line)
        if rep is None or res is None:
            print(rate, "failed", p.stderr[-400:], flush=True)
            continue
        done = 1.0 - res["failed"] / max(1, res["attempted"])
        v = rep["values"]
        print(rate, res["attempted"], round(done, 5), rep["rejected_429"],
              rep["mid_depth"], rep["end_depth"], round(rep["settle_s"], 3),
              round(v["startup_p50_ms"], 2), round(v["startup_p95_ms"], 2),
              round(v["setup_s"], 1), res["correct"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
