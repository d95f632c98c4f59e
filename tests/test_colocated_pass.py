"""A drain pass that holds Services' replicas and Jobs' pods, interleaved.

Such a pass is what the scheduler's queue holds on a cluster that runs
services and batch together (the benchmark's cell
`colocated-5000n-150k.rollouts-1k-8svc-jobs`): a Deployment's replicas behind
a Service are `_SPREAD`, the pods of a Job, which nothing selects, `_PLAIN`.
With no gang in the pass `Scheduler._burst_pass_planned` hands all of it to
`_schedule_singletons_burst` as one run, which keeps the two kinds in one
burst segment, and the launch carries the Services' count rows with the
Jobs' pods under group index -1 (`TPUScheduler._spread_carry`): they read
zeros and move no row. Held here, on the cell's own data files at a small
size: every binding is the serial oracle's and the benchmark's plain
reference's, a pass is one segment whatever it holds (no `plan` cut, no
`class` cut, one `end` a pass), every step of a mixed pass is a `grouped`
one, its Jobs' pods the unselected steps, and no launch is refused
(`burst-spread-mixed` stands still).
"""
import os
import sys

import pytest

from kubernetes_tpu.core.tpu_scheduler import (
    ORACLE_FALLBACKS, SCAN_SPREAD_STEPS, SCAN_SPREAD_UNSELECTED_STEPS)
from kubernetes_tpu.oracle.generic_scheduler import num_feasible_nodes_to_find
from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
from kubernetes_tpu.store.store import PODS

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CELL = "colocated-5000n-150k.rollouts-1k-8svc-jobs"
# 250 nodes in zones of 84/83/83, so the NodeTree's order rotates and the
# default percentage cuts the walk short (120 of 250); 1500 resident pods
# behind ten Services, eight of which the mix names
N_NODES = 250
SMALL = {"nodes": {"count": N_NODES},
         "resident": {"pods_per_node": 6, "services": 10}}
N_PODS = 120
MAX_PODS = 64          # so 120 pods are two drain passes
CAUSES = ("plan", "class", "groups", "nominated", "unburstable", "end")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, imported the way its command finds them
    (`benchmark/` on the path); the path is put back after."""
    sys.path.insert(0, BENCH_DIR)
    try:
        from lib import check, cluster, spec
        from lib.traffic import PodFactory
        yield {"check": check, "cluster": cluster, "spec": spec,
               "PodFactory": PodFactory}
    finally:
        sys.path.remove(BENCH_DIR)


def mix(spec, jobs_share: float) -> dict:
    """The cell's traffic mix with the Jobs' share moved: the file's own at
    0.3, the eight Services' shapes alone at 0, the three label-free sizes
    alone at 1, the shares within a kind in the file's proportions."""
    bench = spec.load_benchmark()
    traffic = spec.load_traffic(spec.find_cell(bench, CELL)["traffic"])
    shapes = traffic["pod_shapes"]
    assert abs(sum(sh["share"] for sh in shapes
                   if "labels" not in sh) - 0.3) < 1e-9
    if jobs_share != 0.3:
        kept = [sh for sh in shapes if ("labels" not in sh) == bool(jobs_share)]
        whole = sum(sh["share"] for sh in kept)
        traffic = {**traffic, "pod_shapes": [
            {**sh, "share": sh["share"] / whole} for sh in kept]}
    return traffic


def world(bench, cfg: dict, traffic: dict, seed: int):
    """The small cluster built from the seed, and the pass's pods with the
    description of each that the reference is given."""
    store, rows, residents, services = bench["cluster"].build(cfg, seed)
    factory = bench["PodFactory"](traffic, len(services), seed)
    factory.new_cycle()
    made = [factory.make(f"p-{j:03d}") for j in range(N_PODS)]
    return store, rows, residents, services, made


def bindings(store) -> dict:
    return {p.name: p.node_name for p in store.list(PODS)[0]
            if p.name.startswith("p-")}


@pytest.mark.parametrize("seed", [5, 2**31 + 47])
@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("jobs_share", [0, 0.3, 1])
def test_colocated_pass_binds_as_oracle_and_reference(bench, jobs_share,
                                                      percentage, seed):
    spec = bench["spec"]
    cfg = spec.overlaid(
        spec.load_config(spec.load_benchmark(), "colocated-5000n-150k"),
        {**SMALL, "scheduler": {"percentage_of_nodes_to_score": percentage}})
    traffic = mix(spec, jobs_share)

    # (a) the serial oracle, one cycle a pod
    store, *_rest, made = world(bench, cfg, traffic, seed)
    oracle = Scheduler(store, use_tpu=False,
                       percentage_of_nodes_to_score=percentage)
    oracle.sync()
    store.create_many(PODS, [p for p, _d in made])
    oracle.pump()
    while oracle.schedule_one(timeout=0.0):
        pass
    oracle.pump()
    want = bindings(store)
    assert len(want) == N_PODS and all(want.values())

    # the normal drain pass
    store, rows, residents, services, made = world(bench, cfg, traffic, seed)
    desc_of = {p.name: d for p, d in made}
    sched = Scheduler(store, use_tpu=True,
                      percentage_of_nodes_to_score=percentage)
    sched.sync()
    store.create_many(PODS, [p for p, _d in made])
    sched.pump()
    passes, segments = [], []
    pop, segment = sched.queue.pop_burst, sched._burst_segment

    def watched_pop(n):
        out = pop(n)
        if out:
            passes.append([p.name for p, _c in out])
        return out

    def watched_segment(pods, cycles, bucket, run, **kw):
        segments.append((run, [p.name for p in pods]))
        return segment(pods, cycles, bucket, run, **kw)

    sched.queue.pop_burst = watched_pop
    sched._burst_segment = watched_segment
    cuts0 = {c: SEGMENT_CUTS.labels(c).value for c in CAUSES}
    mixed0 = ORACLE_FALLBACKS.labels("burst-spread-mixed").value
    grouped0 = SCAN_SPREAD_STEPS.labels("grouped").value
    unselected0 = SCAN_SPREAD_UNSELECTED_STEPS.value
    while sched.schedule_burst(max_pods=MAX_PODS):
        pass
    sched.pump()
    got = bindings(store)
    assert got == want

    # (b) the benchmark's plain reference, given the binds in the order the
    # queue popped the pods, which is the order they were created in
    assert [len(p) for p in passes] == [MAX_PODS, N_PODS - MAX_PODS]
    popped = [name for p in passes for name in p]
    assert popped == [p.name for p, _d in made]
    ref = bench["check"].make_reference(cfg, rows, residents, services)
    whole = num_feasible_nodes_to_find(N_NODES, percentage) >= N_NODES
    assert ref.num_to_find == num_feasible_nodes_to_find(N_NODES, percentage)
    assert whole == (percentage == 100)
    for name in popped:
        assert ref.decide(desc_of[name]) == got[name], name
        ref.place(desc_of[name], got[name])

    # what the passes held, and how they were cut: a pass is one segment,
    # traced as `spread` where it holds a Service's pod
    kind = {p.name: "spread" if p.labels else "plain" for p, _d in made}
    changes = sum(kind[a] != kind[b] for p in passes for a, b in zip(p, p[1:]))
    assert (changes == 0) == (jobs_share != 0.3)
    if jobs_share == 0.3:
        # the kind changes 2 x 0.3 x 0.7 = 0.42 times a pod in the mean
        assert 0.25 * N_PODS < changes < 0.6 * N_PODS
    assert segments == [("plain" if jobs_share == 1 else "spread", p)
                        for p in passes]
    cuts = {c: SEGMENT_CUTS.labels(c).value - cuts0[c] for c in CAUSES}
    assert cuts == {"plan": 0, "class": 0, "groups": 0, "nominated": 0,
                    "unburstable": 0, "end": len(passes)}
    # the Services' pods carry their rows, the Jobs' pods ride them with
    # none: every step of a pass that holds a Service's pod is grouped
    # (eight Services, so also without a Job's pod), and no launch that
    # holds both kinds is refused
    jobs = sum(k == "plain" for k in kind.values())
    assert (jobs == 0, jobs == N_PODS) == (jobs_share == 0, jobs_share == 1)
    assert SCAN_SPREAD_STEPS.labels("grouped").value - grouped0 \
        == (0 if jobs_share == 1 else N_PODS)
    assert SCAN_SPREAD_UNSELECTED_STEPS.value - unselected0 \
        == (0 if jobs_share == 1 else jobs)
    assert ORACLE_FALLBACKS.labels("burst-spread-mixed").value == mixed0
