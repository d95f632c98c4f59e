"""The generic scan's grouped selector-spread carry against the serial oracle.

A burst segment may hold pods of several selector groups (a group: a
namespace and the set of Services / ReplicaSets that select a pod). The scan
then carries one count row a group (`TPUScheduler._spread_carry`,
`kernels._batch_core`): a step scores its pod against its group's row, and a
bound pod is added to every row whose selectors all match it. Every case
here is a drain through the normal shell compared, binding for binding, with
the serial oracle's one cycle a pod; what the shell and the launch did is
read off their counters.
"""
import random

import pytest

from kubernetes_tpu.api.types import (
    Container, LABEL_HOSTNAME, LabelSelector, Node, Pod, ReplicaSet, Service)
from kubernetes_tpu.core.tpu_scheduler import (
    ORACLE_FALLBACKS, SCAN_POD_ROWS, SCAN_SPREAD_STEPS)
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu.parallel import sharding as S
from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
from kubernetes_tpu.store.store import (
    NODES, PODS, REPLICASETS, SERVICES, Store)

GI = 1024 ** 3
ZONE = "failure-domain.beta.kubernetes.io/zone"
REGION = "failure-domain.beta.kubernetes.io/region"
UNEVEN = 130           # zones of 44/43/43: the NodeTree's order rotates
EVEN = 129             # 43/43/43: every cycle walks the device axis
CAUSES = ("class", "groups", "nominated", "unburstable", "end")
CARRIES = ("none", "single", "grouped")


def box(cpu=100):
    return (Container.make(name="c", requests={"cpu": cpu,
                                               "memory": GI // 2}),)


class World:
    """One scenario: the objects that select pods, the label sets resident
    pods are dealt from, and the pending pods, in queue order."""
    n_nodes = UNEVEN
    max_pods = 64       # the drain pass: one pass holds every pending pod

    def selectors(self, s: Store) -> None:
        for k in range(20):
            s.create(SERVICES, Service(name=f"svc-{k}",
                                       selector={"app": f"svc-{k}"}))

    def resident_labels(self, rng) -> tuple:
        return "default", {"app": f"svc-{rng.randrange(20)}"}

    def pending(self, rng) -> list:
        raise NotImplementedError

    def build(self, seed: int) -> Store:
        rng = random.Random(seed)
        s = Store(watch_log_size=65536)
        for i in range(self.n_nodes):
            s.create(NODES, Node(
                name=f"n{i}", labels={LABEL_HOSTNAME: f"n{i}",
                                      ZONE: f"z{i % 3}", REGION: "r1"},
                allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
        self.selectors(s)
        for i in range(self.n_nodes):
            for j in range(rng.choice((0, 1, 2))):
                ns, labels = self.resident_labels(rng)
                s.create(PODS, Pod(name=f"res-{i}-{j}", namespace=ns,
                                   node_name=f"n{i}", labels=labels,
                                   containers=box()))
        return s

    def submit(self, s: Store, seed: int) -> None:
        for pod in self.pending(random.Random(seed ^ 0x7AF1C)):
            s.create(PODS, pod)


class Interleaved(World):
    """Pods of `k` Services, drawn pod by pod."""
    k = 8

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}", containers=box(),
                    labels={"app": f"svc-{rng.randrange(self.k)}"})
                for j in range(48)]


class EvenZones(Interleaved):
    n_nodes = EVEN


class TwoSelectors(World):
    """A pod that two Services select beside pods that one of them selects:
    it counts toward both, and neither's pods count toward it."""

    def selectors(self, s):
        super().selectors(s)
        s.create(SERVICES, Service(name="web", selector={"tier": "web"}))

    LABELS = ({"app": "svc-0"}, {"tier": "web"},
              {"app": "svc-0", "tier": "web"}, {"app": "svc-1"},
              {"app": "svc-1", "tier": "web"})

    def resident_labels(self, rng):
        return "default", dict(rng.choice(self.LABELS))

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}", containers=box(),
                    labels=dict(rng.choice(self.LABELS)))
                for j in range(48)]


class WithReplicaSet(TwoSelectors):
    """A ReplicaSet's selector beside the Services': `get_selectors` gives
    both kinds, a dict and a LabelSelector."""

    def selectors(self, s):
        World.selectors(self, s)
        s.create(REPLICASETS, ReplicaSet(
            name="web", selector=LabelSelector.from_dict({"tier": "web"})))


class TwoNamespaces(World):
    """Equal labels under equal Services in two namespaces: a pod counts,
    and is counted, in its own alone."""
    namespaces = ("default", "other")

    def selectors(self, s):
        for ns in self.namespaces:
            for k in range(3):
                s.create(SERVICES, Service(name=f"svc-{k}", namespace=ns,
                                           selector={"app": f"svc-{k}"}))

    def resident_labels(self, rng):
        return rng.choice(self.namespaces), {"app": f"svc-{rng.randrange(3)}"}

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}", namespace=rng.choice(self.namespaces),
                    containers=box(),
                    labels={"app": f"svc-{rng.randrange(3)}"})
                for j in range(48)]


class Skewed(World):
    """Services drawn by a skewed law: half the pods are one Service's, a
    quarter the next one's, and so on."""

    def pending(self, rng):
        def draw():
            k = 0
            while k < 6 and rng.random() < 0.5:
                k += 1
            return k
        return [Pod(name=f"p{j:03d}", containers=box(),
                    labels={"app": f"svc-{draw()}"}) for j in range(48)]


class UnlikeRequests(World):
    """Three Services' pods in three sizes: signatures that differ in their
    requests alone share a count row, and the launch stacks pod rows."""

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}",
                    containers=box(rng.choice((100, 250, 400))),
                    labels={"app": f"svc-{rng.randrange(3)}"})
                for j in range(48)]


class NoNodeMidLaunch(Interleaved):
    """The 20th pod fits nowhere: its step binds nothing and adds nothing,
    the decided prefix is read out of the launch's one block, and the tail
    runs behind the pod's serial cycle."""
    k = 4

    def pending(self, rng):
        pods = super().pending(rng)
        pods[19] = Pod(name=pods[19].name, containers=box(cpu=64000),
                       labels=pods[19].labels)
        return pods


class SeventeenServices(World):
    """More Services in a pass than a launch carries rows for."""

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}", containers=box(),
                    labels={"app": f"svc-{j % 17}"}) for j in range(40)]


class PlainBetween(Interleaved):
    """A pod that nothing selects among Services' pods: `_PLAIN` among
    `_SPREAD`, a cut on either side of it."""
    k = 4

    def pending(self, rng):
        pods = super().pending(rng)
        pods[15] = Pod(name=pods[15].name, containers=box())
        return pods


def bindings(s: Store) -> list:
    return sorted((p.key, p.node_name) for p in s.list(PODS)[0])


def serial(world: World, seed: int, percentage: int) -> list:
    s = world.build(seed)
    oracle = Scheduler(s, use_tpu=False,
                       percentage_of_nodes_to_score=percentage)
    oracle.sync()
    world.submit(s, seed)
    oracle.pump()
    while oracle.schedule_one(timeout=0.0):
        pass
    oracle.pump()
    return bindings(s)


def drained(world: World, seed: int, percentage: int, mesh=None):
    """The normal drain: (bindings, the pods of each burst segment, what the
    counters moved by)."""
    s = world.build(seed)
    sched = Scheduler(s, use_tpu=True, mesh=mesh,
                      percentage_of_nodes_to_score=percentage)
    sched.sync()
    world.submit(s, seed)
    sched.pump()
    segments = []
    segment = sched._burst_segment

    def watched(pods, *a, **kw):
        segments.append(list(pods))
        return segment(pods, *a, **kw)

    sched._burst_segment = watched
    before = counters()
    while sched.schedule_burst(max_pods=world.max_pods):
        pass
    sched.pump()
    after = counters()
    return bindings(s), segments, \
        {k: after[k] - before[k] for k in after}


def counters() -> dict:
    out = {("cut", c): SEGMENT_CUTS.labels(c).value for c in CAUSES}
    out.update({("steps", c): SCAN_SPREAD_STEPS.labels(c).value
                for c in CARRIES})
    out["stacked"] = SCAN_POD_ROWS.labels("stacked").value
    out["refused"] = ORACLE_FALLBACKS.labels("burst-spread-mixed").value
    return out


def groups_of(pods: list) -> set:
    return {(p.namespace, tuple(sorted(p.labels.items()))) for p in pods}


@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("world", [
    Interleaved, TwoSelectors, WithReplicaSet, TwoNamespaces, Skewed,
    UnlikeRequests], ids=lambda w: w.__name__)
def test_one_segment_one_grouped_launch(world, percentage):
    world = world()
    want = serial(world, 5, percentage)
    assert all(node for _key, node in want)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    assert len(segments) == 1 and len(segments[0]) == 48
    assert len(groups_of(segments[0])) > 1
    assert moved[("cut", "end")] == 1
    assert not any(moved[("cut", c)] for c in CAUSES if c != "end")
    assert moved[("steps", "grouped")] == 48
    assert moved[("steps", "single")] == moved[("steps", "none")] == 0
    assert moved["refused"] == 0
    if isinstance(world, UnlikeRequests):
        # nine signatures in three groups: rows stacked, and shared
        assert len({(p.labels["app"], p.containers) for p in segments[0]}) \
            > len(groups_of(segments[0])) == 3
        assert moved["stacked"] == 48


def test_counts_for_is_not_the_identity():
    """What TwoSelectors' launch ships: three or more groups of which the
    doubly selected pods' counts toward the others' and not the reverse."""
    world = TwoSelectors()
    shipped = []
    real = K.schedule_batch

    def spy(*a, **kw):
        shipped.append(kw["spread_groups"])
        return real(*a, **kw)

    K.schedule_batch, keep = spy, K.schedule_batch
    try:
        drained(world, 5, 0)
    finally:
        K.schedule_batch = keep
    (_group, counts_for), = shipped
    assert counts_for.shape == (8, 8)
    live = counts_for[:5, :5]
    assert live.diagonal().all() and not counts_for[5:].any()
    off = live & ~live.T
    # (app, tier) counts toward (app) and toward (tier): four such pairs
    # among svc-0, svc-1, web and the two doubles; never the other way
    assert off.sum() == 4 and not (off & off.T).any()


@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("world", [Interleaved, EvenZones],
                         ids=lambda w: w.__name__)
def test_every_shape_of_the_cycle(world, percentage):
    """Even zones walk the device axis (one program at any quota), uneven
    ones a shipped order with every node scored or under a truncated walk:
    `_cycle_core`'s three static shapes, each under a grouped carry."""
    world = world()
    for seed in (3, 2**31 + 5):
        want = serial(world, seed, percentage)
        got, segments, moved = drained(world, seed, percentage)
        assert got == want
        assert len(segments) == 1
        assert moved[("steps", "grouped")] == 48


@pytest.mark.parametrize("percentage", [0, 100])
def test_pod_without_a_node_mid_launch(percentage):
    world = NoNodeMidLaunch()
    want = serial(world, 5, percentage)
    assert [key for key, node in want if not node] == ["default/p019"]
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    # the launch stepped over all 48, decided 19, and the 28 behind the
    # failed pod went out again as a segment of their own
    assert [len(seg) for seg in segments][:1] == [48]
    assert moved[("steps", "grouped")] >= 48
    assert moved["refused"] == 0


@pytest.mark.parametrize("percentage", [0, 100])
def test_more_services_than_rows_cut_the_segment(percentage):
    world = SeventeenServices()
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    # pods 0..15 are sixteen Services'; the 17th opens the next segment,
    # which holds sixteen again before svc-15 returns
    assert [len(seg) for seg in segments] == [16, 16, 8]
    assert all(len(groups_of(seg)) <= K.SPREAD_GROUP_CAP
               for seg in segments)
    assert moved[("cut", "groups")] == 2 and moved[("cut", "end")] == 1
    assert moved[("cut", "class")] == 0
    assert moved[("steps", "grouped")] == 40
    assert moved["refused"] == 0


@pytest.mark.parametrize("percentage", [0, 100])
def test_plain_pod_between_services_pods_still_cuts(percentage):
    world = PlainBetween()
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    # the pass's planner already parts the run at the plain pod (it may
    # ride a fused window, a Service's pod may not), so each of the three
    # runs ends as a run out of pods
    assert [len(seg) for seg in segments] == [15, 1, 32]
    assert moved[("cut", "end")] == 3 and moved[("cut", "class")] == 0
    assert moved[("steps", "grouped")] == 47
    assert moved[("steps", "single")] == 0


def test_the_seam_refuses_what_it_cannot_carry():
    """Handed directly (a gang's trial does so) a launch of more groups
    than rows, or one mixing selected and unselected pods, the seam books
    `burst-spread-mixed` and returns None: a refusal stays a refusal."""
    world = SeventeenServices()
    s = world.build(5)
    sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    snap = sched.cache.update_snapshot(sched._snapshot)
    names = sched.cache.node_tree.list_names()
    many = [Pod(name=f"q{j}", containers=box(),
                labels={"app": f"svc-{j}"}) for j in range(17)]
    mixed = many[:3] + [Pod(name="bare", containers=box())]
    for pods in (many, mixed):
        before = counters()["refused"]
        assert sched.algorithm.schedule_burst(
            pods, snap.node_infos, names, bucket=32) is None
        assert counters()["refused"] == before + 1
    assert sched.algorithm.schedule_burst(
        many[:16], snap.node_infos, names, bucket=32) is not None


@pytest.mark.parametrize("percentage", [0, 100])
def test_grouped_launch_on_a_mesh_of_four(percentage):
    """The same launch with the node axis over four of the virtual host
    devices: the count rows are pinned on their last axis."""
    mesh = S.make_mesh(4)
    world = Interleaved()
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage, mesh=mesh)
    assert got == want
    assert len(segments) == 1
    assert moved[("steps", "grouped")] == 48
    assert moved["refused"] == 0


class OneService(Interleaved):
    k = 1


class OneServiceThreeSizes(World):
    """One Service's pods in three sizes: one group of three signatures."""

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}",
                    containers=box(rng.choice((100, 250, 400))),
                    labels={"app": "svc-3"}) for j in range(48)]


@pytest.mark.parametrize("percentage", [0, 100])
def test_one_group_of_unlike_requests_carries_one_vector(percentage):
    """Signatures that differ in requests alone are one selector group: the
    launch stacks their pod rows and carries the one [N] count vector (such
    a pass was cut at every change of size before the groups)."""
    world = OneServiceThreeSizes()
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    assert len(segments) == 1
    assert moved[("steps", "single")] == 48 == moved["stacked"]
    assert moved[("steps", "grouped")] == 0
    assert moved["refused"] == 0


def test_one_group_runs_the_program_it_always_ran(monkeypatch):
    """A launch of one selector group carries one [N] vector, no group
    column and no `counts_for`, whatever ran before it: after a grouped
    launch, launches like cell 7's (one Service, truncated walk) and cell
    2's (one Service, every node scored) find their compiled programs."""
    calls = []
    real = K._schedule_batch_jit

    def spy(nodes, mut0, pods, n_pods, li, lni, ntf, n_real, positions,
            oid_seq, spread0, *a, **kw):
        calls.append((spread0.ndim, "spread_group" in pods,
                      kw.get("counts_for") is not None))
        return real(nodes, mut0, pods, n_pods, li, lni, ntf, n_real,
                    positions, oid_seq, spread0, *a, **kw)

    monkeypatch.setattr(K, "_schedule_batch_jit", spy)
    one, eight = OneService(), Interleaved()
    for percentage in (0, 100):
        drained(one, 5, percentage)
    assert calls and all(c == (1, False, False) for c in calls)
    calls.clear()
    drained(eight, 5, 0)
    assert calls == [(2, True, True)]
    compiled = real._cache_size()
    calls.clear()
    for percentage in (0, 100):
        want = serial(one, 7, percentage)
        got, _segments, moved = drained(one, 7, percentage)
        assert got == want
        assert moved[("steps", "single")] == 48
        assert moved[("steps", "grouped")] == 0
    assert calls and all(c == (1, False, False) for c in calls)
    assert real._cache_size() == compiled
