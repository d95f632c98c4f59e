"""A cluster that serves: windows of many Services' pods through `ServeLoop`.

The benchmark's cell `services-5000n-150k.arrivals-zipf-64svc` at a size the
serial oracle can follow: a few hundred nodes in three uneven zones at
upstream's default percentage (a truncated walk on a rotating order), resident
pods behind 48 Services, and an arrival script of windows of 1 to 200 pods,
each pod a replica of one of 24 Services drawn Zipf(1.1), deleted a few
windows after it was bound. The same script runs twice through a
`ServeLoop`: over the device path and over the serial oracle (`use_tpu=False`:
one `schedule_one` cycle a pod at the same window boundaries). Held:

(a) every binding of every window is the oracle's, whatever the windows
    hold: one selector group (the one-vector launch), 2 to 16 (one grouped
    launch), more than 16 (the shell's `groups` cut), pods of earlier windows
    still bound (the pod table extracts joined pods) or deleted since;
(b) the benchmark's plain reference (`benchmark/reference/`, which imports
    nothing of the program) gives the oracle's stream the same answers,
    bind for bind: two independent statements of the serial scheduler agree.

The world is built by the benchmark's own `lib/cluster.py` from the cell's
configuration file, cut down by an overlay, so the reference is handed what
the harness hands it.
"""
import functools
import os
import random
import sys

import pytest

from kubernetes_tpu.api.types import Container, Pod
from kubernetes_tpu.core.tpu_scheduler import (
    ORACLE_FALLBACKS, SCAN_SPREAD_GROUPS, SCAN_SPREAD_STEPS)
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu.ops.node_state import (
    POD_TABLE_ROWS, SELECTOR_WALK_SERVICES, SPREAD_COUNT_ENCODES)
from kubernetes_tpu.oracle.selector_index import SELECTOR_INDEX_BUILDS
from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
from kubernetes_tpu.serve import ServeLoop
from kubernetes_tpu.store.store import PODS

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = "services-5000n-150k"
# 310 nodes in zones of 104/103/103: the NodeTree's order rotates, and the
# default percentage finds 148 of them a decision
SMALL = {"nodes": {"count": 310},
         "resident": {"pods_per_node": 2, "services": 48}}
ARRIVING = 24           # Services that take arrivals, Zipf(1.1)
WINDOW, DEPTH = 128, 3  # a step drains up to 384 pods: a round is one pass
LIFETIME = 2            # a pod is deleted this many windows after its own
# window sizes by case: one group and a few; 2 to 16; more than the cap
SIZES = {"few": (1, 1, 2, 1, 3, 4, 1, 2, 3, 1),
         "some": (9, 14, 6, 20, 11, 8, 17, 5),
         "many": (70, 200, 60, 120, 90)}
CAUSES = ("class", "groups", "nominated", "unburstable", "end")
CARRIES = ("none", "single", "grouped")


@pytest.fixture(scope="module", autouse=True)
def bench_on_path():
    """`benchmark/` on the path, as the command finds its own modules."""
    sys.path.insert(0, BENCH_DIR)
    try:
        yield
    finally:
        sys.path.remove(BENCH_DIR)


def script(seed: int, case: str) -> list:
    """The arrival script: for each window the Service index of each pod."""
    rng = random.Random(seed ^ 0x21BF)
    weights = [(k + 1) ** -1.1 for k in range(ARRIVING)]
    return [rng.choices(range(ARRIVING), weights, k=size)
            for size in SIZES[case]]


def segments_of(rounds: list, cap: int) -> tuple:
    """What `_schedule_singletons_burst` makes of the script, a window a
    drain pass: (segments cut because a 17th group came, segments in all,
    distinct groups summed over segments, pods in one-group segments)."""
    cut = segs = groups = single = 0
    for pods in rounds:
        seen: set = set()
        run = 0
        for k in pods + [None]:
            if k is None or (k not in seen and len(seen) == cap):
                segs += 1
                groups += len(seen)
                single += run if len(seen) == 1 else 0
                cut += k is not None
                seen, run = set(), 0
            if k is not None:
                seen.add(k)
                run += 1
    return cut, segs, groups, single


def counters() -> dict:
    out = {("cut", c): SEGMENT_CUTS.labels(c).value for c in CAUSES}
    out.update({("steps", c): SCAN_SPREAD_STEPS.labels(c).value
                for c in CARRIES})
    out.update({("table", r): POD_TABLE_ROWS.labels(r).value
                for r in ("extracted", "reused")})
    out["groups"] = SCAN_SPREAD_GROUPS.value
    out["encodes"] = SPREAD_COUNT_ENCODES.value
    out["tested"] = SELECTOR_WALK_SERVICES.value
    out["builds"] = SELECTOR_INDEX_BUILDS.value
    out["fallbacks"] = sum(c.value
                           for c in ORACLE_FALLBACKS._children.values())
    return out


@functools.lru_cache(maxsize=None)
def served(use_tpu: bool, seed: int, case: str) -> dict:
    """One world, one run of the script. Returns the bindings window by
    window, what the counters moved, the carry shapes of the scan's
    launches, and the benchmark client's log with what the reference needs
    to replay it."""
    from lib import check, cluster, spec
    from lib.client import Client
    bench = spec.load_benchmark()
    cfg = spec.overlaid(spec.load_config(bench, CONFIG), SMALL)
    store, rows, residents, services = cluster.build(cfg, seed)
    sched = Scheduler(
        store, use_tpu=use_tpu, percentage_of_nodes_to_score=cfg[
            "scheduler"]["percentage_of_nodes_to_score"])
    sched.sync()
    loop = ServeLoop(sched, window_size=WINDOW, depth=DEPTH)
    client = Client(store, tracing=False)
    box = (Container.make(name="c", requests={
        "cpu": 100, "memory": 524288000}),)
    descs: dict = {}
    shapes = []
    real = K._schedule_batch_jit

    def spy(nodes, mut0, pods, n_pods, li, lni, ntf, n_real, positions,
            oid_seq, spread0, *a, **kw):
        shapes.append(tuple(spread0.shape[:-1]))
        return real(nodes, mut0, pods, n_pods, li, lni, ntf, n_real,
                    positions, oid_seq, spread0, *a, **kw)

    K._schedule_batch_jit = spy
    before = counters()
    bound, keys_of = [], []
    try:
        for r, svc in enumerate(script(seed, case)):
            if r >= LIFETIME:
                store.delete_many(PODS, keys_of[r - LIFETIME])
            pods = []
            for j, k in enumerate(svc):
                labels = cluster.service_label(k)
                pod = Pod(name=f"w{r}-{j:03d}", namespace="default",
                          labels=labels, containers=box)
                lab = tuple(sorted(labels.items()))
                client.register(pod, descs.setdefault(lab, {
                    "cpu": 100, "mem": 524288000, "namespace": "default",
                    "labels": lab, "kind": "plain"}))
                pods.append(pod)
            store.create_many(PODS, pods)
            keys_of.append([p.key for p in pods])
            assert loop.step() == len(pods)
            sched.pump()
            client.drain()
            mine = set(keys_of[r])
            bound.append([(p.key, p.node_name) for p in store.list(PODS)[0]
                          if p.key in mine])
    finally:
        K._schedule_batch_jit = real
    after = counters()
    client.close()
    return {"bound": bound, "shapes": shapes, "client": client,
            "moved": {k: after[k] - before[k] for k in after},
            "reference": lambda: check.make_reference(cfg, rows, residents,
                                                      services),
            "replay": check.replay}


CASES = [(seed, case) for case in SIZES for seed in (3, 2**31 + 29)]


@pytest.mark.parametrize("seed,case", CASES)
def test_serve_windows_bind_as_the_serial_oracle(seed, case):
    rounds = script(seed, case)
    want = served(False, seed, case)
    got = served(True, seed, case)
    assert all(node for window in want["bound"] for _key, node in window)
    assert [sorted(w) for w in got["bound"]] == \
        [sorted(w) for w in want["bound"]]

    # what the shell and the launches did, from the script alone
    moved = got["moved"]
    cap = K.SPREAD_GROUP_CAP
    cut, segs, groups, single = segments_of(rounds, cap)
    pods = sum(map(len, rounds))
    assert {c: moved[("cut", c)] for c in CAUSES} == {
        "class": 0, "groups": cut, "nominated": 0, "unburstable": 0,
        "end": len(rounds)}
    assert (cut > 0) == (case == "many")
    assert moved["fallbacks"] == 0
    assert moved[("steps", "none")] == 0
    assert moved[("steps", "single")] == single
    assert moved[("steps", "grouped")] == pods - single > 0
    # (a window's last segment may hold one group in any case)
    assert single > 0 or case != "few"
    # one count pass and one lookup a group a segment (a truncated walk
    # never tries the K-batch class first); a lookup tests the candidates
    # filed under the pod's label, which is the one Service that selects
    # it, not the world's 48
    assert moved["groups"] == groups == moved["encodes"]
    assert moved["tested"] == moved["encodes"]
    # no Service moves over the script: the shell's index is built once,
    # at the first window, and both its callers ask that one
    assert moved["builds"] == 1 and want["moved"]["builds"] == 0
    # behind a serve loop a grouped launch carries the cap's rows whatever
    # it holds, so the loop runs two scan programs and no third
    assert len(got["shapes"]) == segs
    assert set(got["shapes"]) <= {(), (cap,)}
    assert (cap,) in got["shapes"]
    # pods of earlier windows are still bound when the next table is made:
    # the pod table derives their rows beside the rows it reuses
    assert moved[("table", "extracted")] > 0
    assert moved[("table", "reused")] > 0
    # the oracle's world never reached the device path
    assert want["shapes"] == [] and want["moved"][("steps", "grouped")] == 0


@pytest.mark.parametrize("seed,case", CASES)
def test_plain_reference_agrees_with_the_oracle(seed, case):
    """`default_provider_adaptive` replays the stream the ORACLE's world
    showed the benchmark's client: every bind compared, none differs."""
    run = served(False, seed, case)
    client = run["client"]
    rep = run["replay"](client, run["reference"](), 0, len(client.log_kind),
                        10**9, 0, seed)
    assert rep["compared"] == rep["window_binds"] == \
        sum(map(len, script(seed, case)))
    assert rep["mismatches"] == [] and rep["over_allocatable"] == 0
    assert client.unknown_events == 0


def test_outside_a_serve_loop_the_carry_is_a_power_of_two():
    """`launch_cap` is what pins the carry's rows: a closed loop of passes
    of about as many groups (cell 9: eight) keeps the program it has."""
    import types

    import numpy as np

    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler

    def feats(n):
        return [types.SimpleNamespace(
            spread_counts=np.zeros(16, np.int64),
            spread_group=("default", frozenset({(("app", f"s{g}"),)})))
            for g in range(n)]

    algo = TPUScheduler.__new__(TPUScheduler)
    for cap, want in ((None, (2, 4, 8, 8, 16)), (2048, (16,) * 5)):
        algo.launch_cap = cap
        got = tuple(algo._spread_carry(feats(n), 16)[0].shape[0]
                    for n in (2, 3, 5, 8, 9))
        assert got == want
    assert algo._spread_carry(feats(1), 16)[1] is None
    assert algo._spread_carry(feats(17), 16) is None
