"""`SelectorIndex` is `get_selectors` by lookup, and the shell's index never
outlives the lists it was built from.

Part one holds the index to the definition over seeded random worlds: the
same list, in the walk's order (Services in list order, then ReplicaSets),
a fresh dict a Service and the ReplicaSet's own `LabelSelector` object.
Part two drives a `Scheduler` on the CPU: a Service added, modified or
deleted between two drain passes changes the next pass's burst class and
spread group, and `tpu_selector_index_builds_total` moves by one for each
such change and by nothing over passes between which no Service moved.
"""
import random

import pytest

from kubernetes_tpu.api.types import (
    Container, LabelSelector, Node, Pod, ReplicaSet, Requirement, Service)
from kubernetes_tpu.api.quantity import requests
from kubernetes_tpu.oracle.priorities import get_selectors, spread_group_key
from kubernetes_tpu.oracle.selector_index import (
    SELECTOR_INDEX_BUILDS, LiveSelectorIndex, SelectorIndex)
from kubernetes_tpu.ops.node_state import (
    SELECTOR_WALK_SERVICES, SPREAD_COUNT_ENCODES)
from kubernetes_tpu.scheduler import _PLAIN, _SPREAD, Scheduler
from kubernetes_tpu.store.informer import InformerFactory
from kubernetes_tpu.store.store import (
    NODES, PODS, REPLICASETS, SERVICES, Store)

NAMESPACES = ("default", "blue", "green")
KEYS = ("app", "tier", "track")
VALUES = ("a", "b", "c")


def _some_labels(rng, lo, hi) -> dict:
    keys = rng.sample(KEYS, rng.randint(lo, hi))
    return {k: rng.choice(VALUES) for k in keys}


def _world(seed: int) -> tuple:
    """Few keys and values over three namespaces, so equal labels meet in
    different namespaces, selectors overlap and nest, and most pods are
    selected by several Services and ReplicaSets at once."""
    rng = random.Random(seed)
    services = []
    for j in range(rng.randint(20, 40)):
        # one to three pairs; now and then the empty selector (selects
        # nothing)
        selector = {} if rng.random() < 0.1 else _some_labels(rng, 1, 3)
        services.append(Service(name=f"s{j}", selector=selector,
                                namespace=rng.choice(NAMESPACES)))
    replicasets = []
    for j in range(rng.randint(10, 20)):
        expressions = tuple(
            Requirement(rng.choice(KEYS), rng.choice(
                ("In", "NotIn", "Exists", "DoesNotExist")),
                tuple(rng.sample(VALUES, rng.randint(1, 2))))
            for _ in range(rng.randint(1, 2)))
        selector = rng.choice((
            None,                                   # selects nothing
            LabelSelector(),                        # selects everything
            LabelSelector(match_expressions=expressions),
            LabelSelector.from_dict(_some_labels(rng, 1, 2)),
            LabelSelector.from_dict(_some_labels(rng, 1, 2), expressions)))
        replicasets.append(ReplicaSet(name=f"r{j}", selector=selector,
                                      namespace=rng.choice(NAMESPACES)))
    pods = [Pod(name=f"p{j}", labels=_some_labels(rng, 0, 3),
                namespace=rng.choice(NAMESPACES + ("empty",)))
            for j in range(60)]
    return services, replicasets, pods


@pytest.mark.parametrize("seed", list(range(12)) + [2**31 + 29])
def test_index_answers_as_the_walk(seed):
    services, replicasets, pods = _world(seed)
    index = SelectorIndex(services, replicasets)
    filed = sum(bool(s.selector) for s in services) \
        + sum(r.selector is not None for r in replicasets)
    several = unlabelled = 0
    for pod in pods:
        want = get_selectors(pod, services, replicasets)
        got, tested = index.select(pod)
        assert got == want            # member for member, in walk order
        assert [type(s) for s in got] == [type(s) for s in want]
        for sel in got:
            if isinstance(sel, dict):
                # a copy, as the walk hands out: never the Service's own
                assert all(sel is not s.selector for s in services)
            else:
                assert any(sel is r.selector for r in replicasets)
        assert spread_group_key(pod.namespace, got) == \
            spread_group_key(pod.namespace, want)
        assert len(want) <= tested <= filed
        several += len(want) > 1
        unlabelled += not pod.labels
    # the world is dense enough to show order and overlap
    assert several >= 10 and unlabelled >= 1


def test_candidates_are_what_is_filed_under_the_pods_labels():
    """5000 Services of one pair each: a lookup tests the one filed under
    the pod's pair, and a pod without labels tests none."""
    services = [Service(name=f"s{j}", selector={"app": f"svc-{j}"})
                for j in range(5000)]
    nested = Service(name="nested", selector={"app": "svc-7", "tier": "web"})
    index = SelectorIndex(services + [nested], [])
    pod = Pod(name="p", labels={"app": "svc-7", "tier": "db"})
    # both are filed under ("app", "svc-7"), the smaller pair; one selects
    assert index.select(pod) == ([{"app": "svc-7"}], 2)
    assert index.select(Pod(name="q")) == ([], 0)
    assert index.select(Pod(name="r", labels={"app": "svc-7"},
                            namespace="other")) == ([], 0)
    assert SelectorIndex().select(pod) == ([], 0)
    # a pair no pod can carry selects, by the walk, the pods WITHOUT the
    # key: it is filed under no pair and tested by every lookup
    odd = [Service(name="odd", selector={"app": None, "tier": "db"})]
    for p in (pod, Pod(name="s", labels={"tier": "db"})):
        assert SelectorIndex(odd).select(p)[0] == get_selectors(p, odd, [])
    assert SelectorIndex(odd).select(Pod(name="s", labels={"tier": "db"})) \
        == ([{"app": None, "tier": "db"}], 1)


def test_live_index_follows_the_informers_change_counts():
    store = Store()
    informers = InformerFactory(store)
    services, replicasets = (informers.informer(SERVICES),
                             informers.informer(REPLICASETS))
    live = LiveSelectorIndex(services, replicasets)
    informers.sync_all()
    pod = Pod(name="p", labels={"app": "a"})
    first = live()
    assert first.select(pod) == ([], 0) and live() is first
    store.create(SERVICES, Service(name="svc-a", selector={"app": "a"}))
    assert live() is first            # not pumped: the cache has not moved
    informers.pump_all()
    second = live()
    assert second is not first and live() is second
    assert second.select(pod) == ([{"app": "a"}], 1)
    rs = LabelSelector.from_dict({"app": "a"})
    store.create(REPLICASETS, ReplicaSet(name="rs", selector=rs))
    informers.pump_all()
    assert live().select(pod) == ([{"app": "a"}, rs], 2)
    # a relist replaces the cache: the count moves with it
    before = services.changes
    services._relist()
    assert services.changes == before + 1 and live() is not second


# -- through a Scheduler -----------------------------------------------------
GI = 1024 ** 3
BOX = (Container.make(name="c", requests=requests(cpu="100m", mem="64Mi")),)


class TestInvalidationBetweenDrainPasses:
    @staticmethod
    def _cluster():
        store = Store()
        for i in range(4):
            store.create(NODES, Node(
                name=f"n{i}", labels={"kubernetes.io/hostname": f"n{i}"},
                allocatable={"cpu": 64000, "memory": 64 * GI, "pods": 1000}))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        return store, sched

    @staticmethod
    def _drain(store, sched, tag, labels=None) -> dict:
        """One drain pass over three fresh pods labelled `labels`: what
        `_burst_classes` made of them, and what the counters moved."""
        labels = {"app": "a"} if labels is None else labels
        for j in range(3):
            store.create(PODS, Pod(name=f"{tag}-{j}", labels=dict(labels),
                                   containers=BOX))
        sched.pump()
        seen = []
        decide = sched._burst_classes
        sched._burst_classes = lambda pods: seen.append(decide(pods)) \
            or seen[-1]
        counts = (SELECTOR_INDEX_BUILDS, SPREAD_COUNT_ENCODES,
                  SELECTOR_WALK_SERVICES)
        before = [c.value for c in counts]
        try:
            assert sched.schedule_burst(max_pods=8) == 3
        finally:
            del sched._burst_classes
        sched.pump()
        (classes,) = seen
        assert len(set(classes)) == 1
        builds, encodes, tested = (
            c.value - b for c, b in zip(counts, before))
        return {"class": classes[0][0], "group": classes[0][1],
                "builds": builds, "encodes": encodes, "tested": tested}

    def test_service_added_modified_deleted(self):
        store, sched = self._cluster()
        plain = {"class": _PLAIN, "group": None, "encodes": 0, "tested": 0}

        def selected(by, builds):
            return {"class": _SPREAD, "builds": builds,
                    "group": spread_group_key("default", by)}

        # no Service yet: the first pass builds the (empty) index
        assert self._drain(store, sched, "a") == {**plain, "builds": 1}
        assert self._drain(store, sched, "b") == {**plain, "builds": 0}

        store.create(SERVICES, Service(name="svc", selector={"app": "a"}))
        got = self._drain(store, sched, "c")
        # the encode asks the same index: it finds the Service and counts
        assert got.pop("encodes") >= 1 and got.pop("tested") >= 1
        assert got == selected([{"app": "a"}], 1)
        # binds and pod events move no Service: nothing is rebuilt
        got = self._drain(store, sched, "d")
        assert got["builds"] == 0 and got["class"] is _SPREAD

        # modified: the selector narrows, and no longer selects app=a alone
        svc = store.get(SERVICES, "default/svc")
        svc.selector = {"app": "a", "tier": "web"}
        store.update(SERVICES, svc)
        # filed under ("app", "a") still, tested, and found not to select
        assert self._drain(store, sched, "e") == {
            **plain, "builds": 1, "tested": 1}
        both = {"app": "a", "tier": "web"}
        got = self._drain(store, sched, "f", labels=both)
        assert got.pop("encodes") >= 1 and got.pop("tested") >= 1
        assert got == selected([both], 0)

        # a ReplicaSet beside it: one more event, one more build
        rs = LabelSelector.from_dict({"tier": "web"})
        store.create(REPLICASETS, ReplicaSet(name="rs", selector=rs))
        got = self._drain(store, sched, "g", labels=both)
        assert (got["builds"], got["group"]) == (
            1, spread_group_key("default", [both, rs]))

        # deleted: the next pass's pods are plain again
        store.delete(SERVICES, "default/svc")
        store.delete(REPLICASETS, "default/rs")
        assert self._drain(store, sched, "h", labels=both) == {
            **plain, "builds": 1}
        assert self._drain(store, sched, "i") == {**plain, "builds": 0}

    def test_decisions_with_the_index_are_the_serial_oracles(self):
        """Services come and go between passes on two worlds, one on the
        burst path and one on the serial oracle: the same nodes."""
        def run(use_tpu):
            store = Store()
            for i in range(6):
                store.create(NODES, Node(
                    name=f"n{i}", labels={
                        "kubernetes.io/hostname": f"n{i}",
                        "failure-domain.beta.kubernetes.io/zone": f"z{i % 2}"},
                    allocatable={"cpu": 64000, "memory": 64 * GI,
                                 "pods": 1000}))
            sched = Scheduler(store, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100)
            sched.sync()
            bound = []
            steps = (
                lambda: None,
                lambda: store.create(SERVICES, Service(
                    name="a", selector={"app": "a"})),
                lambda: store.create(SERVICES, Service(
                    name="ab", selector={"app": "a", "tier": "b"})),
                lambda: store.delete(SERVICES, "default/a"),
                lambda: store.delete(SERVICES, "default/ab"))
            for r, step in enumerate(steps):
                step()
                for j in range(7):
                    labels = {"app": "a", "tier": "b"} if j % 2 else \
                        {"app": "a"}
                    store.create(PODS, Pod(name=f"r{r}-{j}", labels=labels,
                                           containers=BOX))
                sched.pump()
                if use_tpu:
                    assert sched.schedule_burst(max_pods=16) == 7
                else:
                    while sched.schedule_one():
                        pass
                sched.pump()
                bound.append([store.get(PODS, f"default/r{r}-{j}").node_name
                              for j in range(7)])
            return bound

        want, got = run(False), run(True)
        assert all(node for window in want for node in window)
        assert got == want
