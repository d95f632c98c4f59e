"""How `correct` is decided: the client's watch stream, replayed through the
plain reference, plus the client's own accounting.

The reference (see `reference/`) is given the cluster the benchmark built and
then every bind and delete in the order the client's watch showed them. For a
bind it is asked first which node the serial default scheduler would have
chosen, in the state that the stream has built so far; then the pod is placed
where the program put it. Identity is exact: the limit on mismatches is 0.
Decisions are compared for the first `first_binds` binds of the window and
for `sampled_binds` more, drawn from the seed among the rest, the window's
last bind always among them; all other binds only advance the state.

Feasibility is the benchmark's own arithmetic over that same state: after
every bind the node's summed requests and pod count stay within allocatable.
"""
from __future__ import annotations

import random

from lib import spec
from lib.client import ADD, BIND, DELETE


def make_reference(cfg: dict, rows: list, residents: list, services: list,
                   root: str = spec.ROOT):
    mod = spec.load_module("reference", cfg["reference"], root)
    ref = mod.Reference(rows, {"default": services},
                        cfg["scheduler"]["percentage_of_nodes_to_score"])
    for desc, node in residents:
        ref.place(desc, node)
    return ref


def replay(client, ref, mark: int, end: int, first_binds: int,
           sampled_binds: int, seed: int) -> dict:
    """Replay the client's log through `ref`. Binds with log position in
    [mark, end) are the window's; decisions are compared on a subset of
    them (see module docstring)."""
    lk, lp, ln = client.log_kind, client.log_pod, client.log_node
    window_binds = [k for k in range(mark, end) if lk[k] == BIND]
    chosen = set(window_binds[:first_binds])
    rest = window_binds[first_binds:]
    if rest and sampled_binds > 0:
        rng = random.Random(seed ^ 0xC0FFEE)
        take = min(sampled_binds, len(rest))
        chosen.update(rng.sample(rest, take - 1) if take > 1 else [])
        chosen.add(rest[-1])
    placed: dict[int, str] = {}
    mismatches = []
    compared = 0
    over_allocatable = 0
    for k in range(len(lk)):
        kind = lk[k]
        if kind == ADD:
            continue
        pid = lp[k]
        desc = client.descs[pid]
        if kind == BIND:
            node = ln[k]
            if pid in placed:        # a second bind; the run counts those
                continue
            if k in chosen:
                want = ref.decide(desc)
                compared += 1
                if want != node:
                    mismatches.append((client.keys[pid], node, want))
            else:
                ref.skip_decision()
            ref.place(desc, node)
            placed[pid] = node
            i = ref.index[node]
            if (ref.req_cpu[i] > ref.alloc_cpu[i]
                    or ref.req_mem[i] > ref.alloc_mem[i]
                    or ref.n_pods[i] > ref.alloc_pods[i]):
                over_allocatable += 1
        elif kind == DELETE:
            node = placed.pop(pid, None)
            if node is not None:
                ref.remove(desc, node)
    return {"compared": compared, "mismatches": mismatches,
            "window_binds": len(window_binds),
            "over_allocatable": over_allocatable}
