"""A drain pass that holds pods of several Services, interleaved.

Such a pass is what the scheduler's queue holds while more than one
Deployment behind a Service scales at the same moment (the benchmark's cell
`load-5000n-150k.rollouts-1k-8svc`). Pods that a Service selects, and
nothing more, share one burst class (`Scheduler._burst_class`: `_SPREAD`), so
the pass is ONE burst segment: one snapshot, one selector-spread count pass a
distinct Service, one launch whose scan carries a count row a Service
(`TPUScheduler._spread_carry`), one fetch, one commit. Held here: every
binding is the serial oracle's, and the counters that name the mechanism
count what the pass implies.
"""
import random

import pytest

from kubernetes_tpu.api.types import (
    Container, LABEL_HOSTNAME, Node, Pod, Service)
from kubernetes_tpu.core.tpu_scheduler import SCAN_SPREAD_STEPS
from kubernetes_tpu.ops.node_state import SPREAD_COUNT_ENCODES
from kubernetes_tpu.oracle.generic_scheduler import num_feasible_nodes_to_find
from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
from kubernetes_tpu.store.store import NODES, PODS, SERVICES, Store

GI = 1024 ** 3
ZONE = "failure-domain.beta.kubernetes.io/zone"
REGION = "failure-domain.beta.kubernetes.io/region"
N_NODES = 130          # zones of 44/43/43: the NodeTree's order rotates
N_PODS = 60
MAX_PODS = 32          # so 60 pods are two drain passes
CAUSES = ("class", "groups", "nominated", "unburstable", "end")
CARRIES = ("none", "single", "grouped")


def build(seed: int) -> Store:
    """130 nodes in three uneven zones, ten Services, and on two nodes in
    three a resident pod or two of a Service drawn from the seed."""
    rng = random.Random(seed)
    s = Store(watch_log_size=65536)
    for i in range(N_NODES):
        s.create(NODES, Node(
            name=f"n{i}", labels={LABEL_HOSTNAME: f"n{i}",
                                  ZONE: f"z{i % 3}", REGION: "r1"},
            allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
    for k in range(10):
        s.create(SERVICES, Service(name=f"svc-{k}",
                                   selector={"app": f"svc-{k}"}))
    box = (Container.make(name="c", requests={"cpu": 100,
                                              "memory": GI // 2}),)
    for i in range(N_NODES):
        for j in range(rng.choice((0, 1, 2))):
            s.create(PODS, Pod(name=f"res-{i}-{j}", node_name=f"n{i}",
                               labels={"app": f"svc-{rng.randrange(10)}"},
                               containers=box))
    return s


def submit(s: Store, seed: int, services: int) -> None:
    """60 pending pods, each of one of the first `services` Services, drawn
    pod by pod."""
    rng = random.Random(seed ^ 0x7AF1C)
    box = (Container.make(name="c", requests={"cpu": 100,
                                              "memory": GI // 2}),)
    for j in range(N_PODS):
        s.create(PODS, Pod(name=f"p{j:03d}", containers=box,
                           labels={"app": f"svc-{rng.randrange(services)}"}))


def bindings(s: Store) -> list:
    return sorted((p.key, p.node_name) for p in s.list(PODS)[0])


@pytest.mark.parametrize("seed", [3, 17, 2**31 + 5])
@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("services", [1, 2, 8])
def test_interleaved_services_bind_as_the_serial_oracle(services, percentage,
                                                        seed):
    # the serial oracle, one cycle a pod
    s = build(seed)
    oracle = Scheduler(s, use_tpu=False,
                       percentage_of_nodes_to_score=percentage)
    oracle.sync()
    submit(s, seed, services)
    oracle.pump()
    while oracle.schedule_one(timeout=0.0):
        pass
    oracle.pump()
    want = bindings(s)
    assert all(node for _key, node in want)

    # the normal drain pass
    s = build(seed)
    sched = Scheduler(s, use_tpu=True,
                      percentage_of_nodes_to_score=percentage)
    sched.sync()
    submit(s, seed, services)
    sched.pump()
    passes, segments = [], []
    singletons, segment = sched._schedule_singletons_burst, sched._burst_segment

    def watched_singletons(pairs, *a, **kw):
        passes.append([p.labels["app"] for p, _c in pairs])
        return singletons(pairs, *a, **kw)

    def watched_segment(pods, *a, **kw):
        segments.append([p.labels["app"] for p in pods])
        return segment(pods, *a, **kw)

    sched._schedule_singletons_burst = watched_singletons
    sched._burst_segment = watched_segment
    cuts0 = {c: SEGMENT_CUTS.labels(c).value for c in CAUSES}
    steps0 = {c: SCAN_SPREAD_STEPS.labels(c).value for c in CARRIES}
    encodes0 = SPREAD_COUNT_ENCODES.value
    while sched.schedule_burst(max_pods=MAX_PODS):
        pass
    sched.pump()
    assert bindings(s) == want

    # what the passes held, and how the shell cut them
    assert [len(p) for p in passes] == [MAX_PODS, N_PODS - MAX_PODS]
    changes = sum(a != b for p in passes for a, b in zip(p, p[1:]))
    assert (changes == 0) == (services == 1)
    # a pass is one segment, however many Services' pods it holds
    assert segments == passes
    cuts = {c: SEGMENT_CUTS.labels(c).value - cuts0[c] for c in CAUSES}
    assert cuts == {"class": 0, "groups": 0, "nominated": 0,
                    "unburstable": 0, "end": len(passes)}
    # ... and one launch: a count row a Service where it holds several, the
    # one vector the scan has always carried where it holds one
    held = [len(set(p)) for p in passes]
    assert all(k > 1 for k in held) == (services > 1)
    steps = {c: SCAN_SPREAD_STEPS.labels(c).value - steps0[c]
             for c in CARRIES}
    assert steps == {
        "none": 0,
        "single": sum(len(p) for p, k in zip(passes, held) if k == 1),
        "grouped": sum(len(p) for p, k in zip(passes, held) if k > 1)}
    # one selector-spread count pass over the pod table a distinct Service
    # and segment. A segment of ONE Service makes a second where the walk
    # is whole (every node scored: `num_to_find >= n and last_index == 0`),
    # because `schedule_burst` then tries the spec-identical segment for
    # the K-batch class first, which refuses carried spread counts, and
    # encodes its first pod again for the generic scan (PERF.md 7 (l)).
    whole = num_feasible_nodes_to_find(N_NODES, percentage) >= N_NODES
    assert whole == (percentage == 100)     # at 0 a walk stops at 63 of 130
    assert SPREAD_COUNT_ENCODES.value - encodes0 == \
        sum(k + (whole and k == 1) for k in held)
