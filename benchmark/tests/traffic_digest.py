"""A digest of what a traffic mix makes from a seed: the pods, the plain
descriptions the reference is given (and which pods share one object) and,
for an open loop, the due times. `test_same_traffic.py` holds every committed
mix to the digest taken on the tree before PR 56 moved the pod shape kinds and
the arrival process into files of their own: same seed, same traffic.

    python3 benchmark/tests/traffic_digest.py      prints the table
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
PODS = 2000
DUE_SECONDS = 2.0
SEEDS = (7, 2 ** 31 + 56)


def services_of(mix: str) -> int:
    """The Services of the configuration the mix's first cell runs on."""
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["traffic"] == mix)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(os.path.dirname(BENCH_DIR), entry["file"])) as f:
        resident = json.load(f).get("resident")
    return resident["services"] if resident else 0


def digest(traffic: dict, n_services: int, seed: int) -> str:
    from lib.traffic import PodFactory, due_times
    h = hashlib.sha256()
    factory = PodFactory(traffic, n_services, seed)
    closed = traffic["kind"] == "closed_backlog"
    per_cycle = traffic["backlog"] if closed else PODS
    first_with = {}
    for k in range(PODS):
        cycle, j = divmod(k, per_cycle)
        if closed and j == 0:
            factory.new_cycle()
        pod, desc = factory.make(f"bl-{cycle}-{j}" if closed else f"arr-{k}")
        # every field of the Pod but the uid, whose counter is the process's
        fields = {f.name: getattr(pod, f.name) for f in dataclasses.fields(pod)
                  if f.name != "uid"}
        fields["labels"] = sorted(fields["labels"].items())
        fields["node_selector"] = sorted(fields["node_selector"].items())
        h.update(repr(sorted(fields.items())).encode())
        h.update(repr(sorted(desc.items())).encode())
        # equal pods share one description object: which pod made it first
        h.update(str(first_with.setdefault(id(desc), k)).encode())
    if not closed:
        h.update(repr(due_times(traffic["arrival"], DUE_SECONDS, seed)).encode())
    return h.hexdigest()[:16]


def table() -> dict:
    from lib import spec
    out = {}
    for name in sorted(os.listdir(os.path.join(BENCH_DIR, "traffic"))):
        mix = name[:-len(".json")]
        traffic = spec.load_traffic(mix)
        for seed in SEEDS:
            out[f"{mix} {seed}"] = digest(traffic, services_of(mix), seed)
    return out


if __name__ == "__main__":
    for p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
        sys.path.insert(0, p)
    print(json.dumps(table(), indent=1))
