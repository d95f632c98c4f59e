#!/usr/bin/env python3
"""Run one cell through `run.execute` with the knobs the tools need:

    python3 benchmark/tools/probe.py <cell> <seed> <seconds> <trace 0|1> [key=value ...]

keys: percentage=<n>      the control: score only n% of nodes (breaks the
                          configuration's 'every node is scored' guarantee)
      rate=<per s>        arrival rate, for the knee sweep
Prints the result object as its last line, like the command."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402


def main(argv) -> int:
    cell, seed, seconds, trace = argv[0], int(argv[1]), float(argv[2]), argv[3] == "1"
    opts = dict(a.split("=", 1) for a in argv[4:])

    overrides = {}
    if "percentage" in opts:
        overrides["program"] = {"scheduler": {
            "percentage_of_nodes_to_score": int(opts["percentage"])}}
    if "rate" in opts:
        overrides["traffic"] = {"arrival": {"rate_per_s": float(opts["rate"])}}
    out = run.execute(cell, seed, seconds, trace, overrides=overrides)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
