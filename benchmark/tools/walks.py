#!/usr/bin/env python3
"""Hold the program's walk counters to the reference's own walks, one run.

    python3 benchmark/tools/walks.py <cell> <seed> <seconds> [key=value ...]

One untraced run of a cell that `reference/default_provider_adaptive.py`
judges, through `run.execute`. The replay that decides `correct` makes the
reference walk once for every bind of the stream, compared or not; this tool
notes, for each of those walks, how many nodes it tested and how many it
kept, and sums them over the window's binds. Beside them it prints what the
program booked over the same window: `tpu_walk_nodes_evaluated_total`,
`tpu_filter_rejected_nodes_total` and `tpu_walk_ended_total{by}`. Where every
pod of the window was bound by a scan launch the two sides are the same
numbers. The last line is one JSON object: `correct`, `window_binds`,
`reference` and `program` (`tested`, `rejected`, `ended`), `same`.

keys, for rehearsals on the CPU backend: rehearse=1 nodes=<n> backlog=<n>
per_node=<n> pods=<n> (resident pods a node; a node's pod capacity)."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402
from reference import default_provider_adaptive as adaptive  # noqa: E402


def main(argv) -> int:
    cell, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    opts = dict(a.split("=", 1) for a in argv[3:])
    overrides = {}
    if "nodes" in opts:
        overrides["config"] = {
            "nodes": {"count": int(opts["nodes"]),
                      "allocatable": {"pods": int(opts.get("pods", 110))}},
            "resident": {"pods_per_node": int(opts.get("per_node", 6)),
                         "services": 5},
            "check": {"first_binds": 600, "sampled_binds": 300}}
    if "backlog" in opts:
        overrides["traffic"] = {"backlog": int(opts["backlog"])}

    walks = []                      # (nodes tested, nodes kept) a walk
    quotas = set()
    walk = adaptive.Reference._walk

    def noting(self, pod):
        entry = self.last_index
        kept = walk(self, pod)
        quotas.add(self.num_to_find)
        walks.append(((self.last_index - entry) % self.n or self.n,
                      int(kept.size)))
        return kept
    adaptive.Reference._walk = noting
    out = run.execute(cell, seed, seconds, False,
                      rehearse=opts.get("rehearse") == "1",
                      overrides=overrides)
    res, rep = out["result"], out["report"]
    window = walks[len(walks) - rep["window_binds"]:]
    (quota,) = quotas
    reference = {
        "tested": sum(t for t, _k in window),
        "rejected": sum(t - k for t, k in window),
        "ended": {"quota": sum(1 for _t, k in window if k >= quota),
                  "nodes": sum(1 for _t, k in window if 0 < k < quota),
                  "none": sum(1 for _t, k in window if k == 0)}}
    moved = rep["counters"]
    program = {
        "tested": int(sum(moved.get(
            "tpu_walk_nodes_evaluated_total", {}).values())),
        "rejected": int(sum(moved.get(
            "tpu_filter_rejected_nodes_total", {}).values())),
        "ended": {by: int(moved.get("tpu_walk_ended_total", {}).get(by, 0))
                  for by in ("quota", "nodes", "none")}}
    print(json.dumps({"correct": res["correct"],
                      "window_binds": rep["window_binds"],
                      "pods_per_s": rep["values"].get("pods_per_s"),
                      "check_s": rep["check_s"], "quota": quota,
                      "reference": reference, "program": program,
                      "same": reference == program}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
