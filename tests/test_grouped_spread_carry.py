"""The generic scan's grouped selector-spread carry against the serial oracle.

A burst segment may hold pods of several selector groups (a group: a
namespace and the set of Services / ReplicaSets that select a pod). The scan
then carries one count row a group (`TPUScheduler._spread_carry`,
`kernels._batch_core`): a step scores its pod against its group's row, and a
bound pod is added to every row whose selectors all match it. A pod that
nothing selects rides the rows with none of its own (group index -1): it
reads zeros, which score SelectorSpread's constant, and moves no row. The
carry has two widths: up to `kernels.SPREAD_GROUP_CAP` groups a power of two
of rows; above it, in a closed loop, `kernels.SPREAD_GROUP_WIDE` rows
whatever the launch holds; a step reads its pod's row as a masked sum at
either.
Every case here is a drain through the normal shell compared, binding for
binding, with the serial oracle's one cycle a pod; what the shell and the
launch did is read off their counters.
"""
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container, LABEL_HOSTNAME, LabelSelector, Node, Pod, ReplicaSet, Service)
from kubernetes_tpu.core.tpu_scheduler import (
    ORACLE_FALLBACKS, SCAN_POD_ROWS, SCAN_SPREAD_CARRY_LAUNCHES,
    SCAN_SPREAD_GROUPS, SCAN_SPREAD_STEPS, SCAN_SPREAD_UNSELECTED_STEPS,
    TPUScheduler)
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.coscheduling.types import LABEL_POD_GROUP, PodGroup
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu.ops.node_state import NodeStateEncoder, PodEncoder
from kubernetes_tpu.parallel import sharding as S
from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
from kubernetes_tpu.store.store import (
    NODES, PODGROUPS, PODS, REPLICASETS, SERVICES, Store)

GI = 1024 ** 3
ZONE = "failure-domain.beta.kubernetes.io/zone"
REGION = "failure-domain.beta.kubernetes.io/region"
UNEVEN = 130           # zones of 44/43/43: the NodeTree's order rotates
EVEN = 129             # 43/43/43: every cycle walks the device axis
CAUSES = ("plan", "class", "groups", "nominated", "unburstable", "end")
CARRIES = ("none", "single", "grouped")
CAP, WIDE = K.SPREAD_GROUP_CAP, K.SPREAD_GROUP_WIDE
ROWS = ("1", "2", "4", "8", str(CAP), str(WIDE))


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

    def zoned(self, i: int) -> bool:
        """Whether node `i` says which zone it is in."""
        return True

    def build(self, seed: int) -> Store:
        rng = random.Random(seed)
        s = Store(watch_log_size=65536)
        for i in range(self.n_nodes):
            zone = {ZONE: f"z{i % 3}", REGION: "r1"} if self.zoned(i) else {}
            s.create(NODES, Node(
                name=f"n{i}", labels={LABEL_HOSTNAME: f"n{i}", **zone},
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


class ManyServices(World):
    """`k` Services' pods in one pass, a pod of each in turn: more than the
    narrow carry's `CAP` rows hold, and (`WIDE` + 1) more than a launch does."""
    max_pods = 256

    def __init__(self, k: int = 17, n_pods: int = 40):
        self.k, self.n_pods = k, n_pods

    def selectors(self, s):
        for j in range(max(20, self.k)):
            s.create(SERVICES, Service(name=f"svc-{j}",
                                       selector={"app": f"svc-{j}"}))

    def resident_labels(self, rng):
        return "default", {"app": f"svc-{rng.randrange(self.k)}"}

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}", containers=box(),
                    labels={"app": f"svc-{j % self.k}"})
                for j in range(self.n_pods)]


class WideWithUnselected(ManyServices):
    """Twenty Services' pods, the first twenty one of each, so the launch
    is a wide one; then, drawn pod by pod: three in ten pods that nothing
    selects (group -1, which an indexed read would clamp onto row 0), a quarter
    svc-0's, whose residents are many (row 0 is not zeros), and pods that
    svc-18 and `web` both select beside pods of either alone (`counts_for`
    off the diagonal at rows past the narrow carry's)."""

    def __init__(self):
        super().__init__(k=20, n_pods=72)

    def selectors(self, s):
        super().selectors(s)
        s.create(SERVICES, Service(name="web", selector={"tier": "web"}))

    def resident_labels(self, rng):
        if rng.random() < 0.5:
            return "default", {"app": "svc-0"}
        return "default", dict(rng.choice((
            {"tier": "web"}, {"app": "svc-18", "tier": "web"},
            {"app": f"svc-{rng.randrange(20)}"})))

    def pending(self, rng):
        def draw(j):
            r = rng.random()
            if r < 0.3:
                return Pod(name=f"p{j:03d}",
                           containers=box(rng.choice((100, 300))))
            labels = {"app": "svc-0"} if r < 0.55 else dict(rng.choice((
                {"tier": "web"}, {"app": "svc-18", "tier": "web"},
                {"app": "svc-18"}, {"app": f"svc-{rng.randrange(20)}"})))
            return Pod(name=f"p{j:03d}", containers=box(), labels=labels)
        return super().pending(rng)[:20] + [draw(j) for j in range(20, 72)]


class PlainBetween(Interleaved):
    """A pod that nothing selects among Services' pods: `_PLAIN` among
    `_SPREAD`, in their segment."""
    k = 4

    def pending(self, rng):
        pods = super().pending(rng)
        pods[15] = Pod(name=pods[15].name, containers=box())
        return pods


class WithUnselected(World):
    """Pods of `k` Services and, three in ten, pods that nothing selects,
    in two sizes, drawn pod by pod."""
    k = 1

    def pending(self, rng):
        return [Pod(name=f"p{j:03d}", containers=box(rng.choice((100, 300))))
                if rng.random() < 0.3 else
                Pod(name=f"p{j:03d}", containers=box(),
                    labels={"app": f"svc-{rng.randrange(self.k)}"})
                for j in range(48)]


class Unzoned(WithUnselected):
    def zoned(self, i):
        return False


class HalfZoned(WithUnselected):
    def zoned(self, i):
        return i % 2 == 0


class CapAndUnselected(WithUnselected):
    """As many Services as a launch carries rows for, each met before the
    pass is half out, and unselected pods among theirs."""
    k = K.SPREAD_GROUP_CAP

    def pending(self, rng):
        pods = super().pending(rng)
        for g in range(self.k):
            pods[g] = Pod(name=pods[g].name, containers=box(),
                          labels={"app": f"svc-{g}"})
        pods[self.k] = Pod(name=pods[self.k].name, containers=box(300))
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


def drained(world: World, seed: int, percentage: int, mesh=None,
            use_tpu=True):
    """The normal drain: (bindings, the pods of each burst segment, what the
    counters moved by)."""
    s = world.build(seed)
    sched = Scheduler(s, use_tpu=use_tpu, mesh=mesh,
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
    out.update({("carry", r): SCAN_SPREAD_CARRY_LAUNCHES.labels(r).value
                for r in ROWS})
    out["stacked"] = SCAN_POD_ROWS.labels("stacked").value
    out["refused"] = ORACLE_FALLBACKS.labels("burst-spread-mixed").value
    out["rows"] = SCAN_SPREAD_GROUPS.value
    out["unselected"] = SCAN_SPREAD_UNSELECTED_STEPS.value
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
@pytest.mark.parametrize("k,n_pods,want", [
    (CAP + 1, 40, [40]), (2 * CAP + 1, 70, [70]), (111, 130, [130]),
    (WIDE, WIDE + 12, [WIDE + 12]), (WIDE + 1, WIDE + 12, [WIDE, 12])])
def test_more_services_than_rows_cut_the_segment(k, n_pods, want,
                                                 percentage):
    """A closed loop's pass of 17, 33, 111 or `WIDE` Services is ONE segment
    and one launch on the wide carry; the Service that would be one more
    than it carries opens the next segment, which runs the same program."""
    world = ManyServices(k, n_pods)
    bound = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage)
    assert got == bound
    assert [len(seg) for seg in segments] == want
    assert all(len(groups_of(seg)) <= WIDE for seg in segments)
    assert moved[("cut", "groups")] == len(want) - 1
    assert moved[("cut", "end")] == 1 and moved[("cut", "class")] == 0
    assert moved[("steps", "grouped")] == n_pods
    assert moved["rows"] == sum(len(groups_of(seg)) for seg in segments)
    assert {r: moved[("carry", r)] for r in ROWS if moved[("carry", r)]} \
        == {str(WIDE): len(want)}
    assert moved["refused"] == 0


@pytest.mark.parametrize("mesh", [None, 4], ids=["one-device", "mesh-4"])
@pytest.mark.parametrize("percentage", [0, 100])
def test_on_the_wide_carry_an_unselected_pod_moves_no_row(percentage, mesh,
                                                          monkeypatch):
    """A wide launch whose pods are Services' and pods that nothing selects
    (group -1) beside a heavy group 0: the bindings are the serial oracle's
    (a -1 clamped onto row 0 would score svc-0's counts), the carry the
    launch returns has moved by `counts_for[h]` at the column of each bound
    pod of a group h and by nothing for a pod of none, and `counts_for`
    holds pairs off its diagonal at rows past the narrow carry's."""
    launches = []
    real = K.schedule_batch

    def spy(*a, **kw):
        out = real(*a, **kw)
        launches.append((kw["spread0"], kw["spread_groups"], kw["n_pods"],
                         np.asarray(out[3]), np.asarray(out[4]["selected"])))
        return out

    world = WideWithUnselected()
    want = serial(world, 5, percentage)
    assert all(node for _key, node in want)
    monkeypatch.setattr(K, "schedule_batch", spy)
    got, segments, moved = drained(
        world, 5, percentage, mesh=S.make_mesh(mesh) if mesh else None)
    assert got == want
    assert [len(seg) for seg in segments] == [72]
    ((spread0, (group, counts_for), n_pods, spread, selected),) = launches
    assert spread0.shape[0] == WIDE and counts_for.shape == (WIDE, WIDE)
    held = int(group.max()) + 1
    assert held > CAP + 2 and n_pods == 72
    bare = [not p.labels for p in segments[0]]
    assert (group[:72] == -1).tolist() == bare and 10 < sum(bare) < 35
    assert (group[:72] == 0).sum() > 8 and spread0[0].sum() > 40
    off = counts_for & ~np.eye(WIDE, dtype=bool)
    rows, cols = np.nonzero(off)
    assert len(rows) == 2 and (rows >= CAP).all() and (cols >= CAP).all()
    assert not counts_for[held:].any() and not counts_for[:, held:].any()
    moved_by = np.zeros_like(spread0)
    for g, sel in zip(group[:72], selected[:72]):
        if g >= 0:
            moved_by[:, sel] += counts_for[g]
    assert (spread - spread0 == moved_by).all()
    assert moved["unselected"] == sum(bare) and moved["rows"] == held
    assert moved[("carry", str(WIDE))] == 1 and moved["refused"] == 0


@pytest.mark.parametrize("percentage", [0, 100])
def test_plain_pod_between_services_pods_rides_their_segment(percentage):
    world = PlainBetween()
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    # no gang in the pass, so the planner hands it over whole, and the
    # segmenter keeps the plain pod with the Services' pods: it takes no
    # count row and its step is a grouped one like theirs
    assert [len(seg) for seg in segments] == [48]
    assert moved[("cut", "end")] == 1
    assert not any(moved[("cut", c)] for c in CAUSES if c != "end")
    assert moved[("steps", "grouped")] == 48
    assert moved[("steps", "single")] == moved[("steps", "none")] == 0
    assert moved["unselected"] == 1 and moved["rows"] == 4
    assert moved["refused"] == 0


@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("world", [WithUnselected, Unzoned, HalfZoned],
                         ids=lambda w: w.__name__)
def test_one_service_and_unselected_pods_carry_two_rows(world, percentage,
                                                        monkeypatch):
    """One Service's pods beside pods that nothing selects: the rank-2
    launch with `G_pad` 2 (the rank-1 program would hand every pod the one
    vector), on nodes that all, none or half say their zone, the three
    shapes of SelectorSpread's blend."""
    shipped = []
    real = K.schedule_batch

    def spy(*a, **kw):
        shipped.append((kw["spread0"].shape, kw["spread_groups"]))
        return real(*a, **kw)

    world = world()
    want = serial(world, 5, percentage)
    assert all(node for _key, node in want)
    monkeypatch.setattr(K, "schedule_batch", spy)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    assert [len(seg) for seg in segments] == [48]
    bare = [not p.labels for p in segments[0]]
    assert 5 < sum(bare) < 25
    ((shape, (group, counts_for)),) = shipped
    assert shape[0] == 2 and counts_for.shape == (2, 2)
    assert counts_for.tolist() == [[True, False], [False, False]]
    assert (group[:48] == np.where(bare, -1, 0)).all()
    assert moved[("steps", "grouped")] == 48
    assert moved["unselected"] == sum(bare) and moved["rows"] == 1
    assert not any(moved[("cut", c)] for c in CAUSES if c != "end")
    assert moved["refused"] == 0


@pytest.mark.parametrize("percentage", [0, 100])
def test_unselected_pods_take_no_row_of_the_cap(percentage):
    """`SPREAD_GROUP_CAP` Services' pods and unselected pods in one
    segment: the pod without a group does not count against the cap in
    the shell, and takes none of the launch's rows."""
    world = CapAndUnselected()
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    assert [len(seg) for seg in segments] == [48]
    assert len(groups_of(p for p in segments[0] if p.labels)) \
        == K.SPREAD_GROUP_CAP
    assert moved[("cut", "groups")] == moved[("cut", "class")] == 0
    assert moved[("steps", "grouped")] == 48
    assert moved["rows"] == K.SPREAD_GROUP_CAP
    assert moved["unselected"] == sum(not p.labels for p in segments[0]) > 0
    assert moved["refused"] == 0


@pytest.mark.parametrize("zones", ["zoned", "unzoned", "half"])
def test_zero_counts_score_the_inert_constant(zones):
    """What lets the unselected pod ride the carry: `_fit_scores` gives a
    full-width vector of zeros the score it gives the inert default, on
    every node, bit for bit. Node and zone fractions are both 10.0 there,
    and 10.0 * (1 - 2/3) + (2/3) * 10.0 truncates to 10 in the kernels'
    softfloat as it does in Go."""
    infos, names = {}, []
    for i in range(37):
        zoned = zones == "zoned" or (zones == "half" and i % 2 == 0)
        node = Node(name=f"n{i}", labels={
            LABEL_HOSTNAME: f"n{i}",
            **({ZONE: f"z{i % 3}", REGION: "r1"} if zoned else {})},
            allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110})
        infos[node.name] = NodeInfo(node)
        names.append(node.name)
        for j in range(i % 3):
            infos[node.name].add_pod(Pod(
                name=f"res-{i}-{j}", node_name=node.name, containers=box()))
    batch = NodeStateEncoder().encode(infos, names)
    algo = TPUScheduler(percentage_of_nodes_to_score=100)
    pod = Pod(name="p", containers=box())
    arrays = algo._pod_arrays(PodEncoder(infos, batch).encode(pod),
                              batch.n_pad)
    nodes = algo._node_arrays(batch)
    assert K._inert(arrays["spread_counts"])
    zeros = {**arrays, "spread_counts": np.zeros(batch.n_pad, np.int64)}
    z_pad = 4
    valid = np.asarray(nodes["valid"])
    some = valid & (np.arange(batch.n_pad) % 5 != 0)
    only = {**{k: 0 for k in K.DEFAULT_WEIGHTS}, "selector_spread": 1}
    for weights in (K.DEFAULT_WEIGHTS, only):
        for kept in (valid, some):
            inert = np.asarray(K._fit_scores(nodes, arrays, kept, weights,
                                             z_pad))
            carried = np.asarray(K._fit_scores(nodes, zeros, kept, weights,
                                               z_pad))
            assert (inert == carried).all()
    assert (carried[:37] == K.MAX_PRIORITY).all()


@pytest.mark.parametrize("percentage", [0, 100])
def test_behind_a_serve_loop_the_mix_carries_the_caps_rows(percentage,
                                                           monkeypatch):
    """The serve-loop form of the mix: a window of one Service's pods and
    pods that nothing selects runs the cap's rows (the program the loop's
    first large window has met), not the one vector and not two rows."""
    from kubernetes_tpu.serve import ServeLoop
    shapes = []
    real = K.schedule_batch

    def spy(*a, **kw):
        shapes.append(kw["spread0"].shape[:-1])
        return real(*a, **kw)

    world = WithUnselected()
    want = serial(world, 5, percentage)
    s = world.build(5)
    sched = Scheduler(s, use_tpu=True,
                      percentage_of_nodes_to_score=percentage)
    sched.sync()
    loop = ServeLoop(sched, window_size=64, depth=1)
    world.submit(s, 5)
    monkeypatch.setattr(K, "schedule_batch", spy)
    before = counters()
    assert loop.step() == 48
    sched.pump()
    after = counters()
    assert bindings(s) == want
    assert shapes == [(K.SPREAD_GROUP_CAP,)]
    assert after[("steps", "grouped")] - before[("steps", "grouped")] == 48
    assert after["unselected"] - before["unselected"] > 5
    assert after["refused"] == before["refused"]


@pytest.mark.parametrize("launch_cap,groups,bare,want", [
    (None, 1, 0, None), (None, 1, 1, 2), (None, 2, 1, 2), (None, 3, 2, 4),
    (None, 8, 1, 8), (None, CAP, 3, CAP), (None, CAP + 1, 1, WIDE),
    (None, 111, 2, WIDE), (None, WIDE, 1, WIDE),
    (None, WIDE + 1, 1, "refused"),
    (2048, 1, 0, None), (2048, 1, 1, CAP), (2048, 5, 1, CAP),
    (2048, CAP + 1, 1, "refused")])
def test_the_carrys_rows_by_what_the_launch_holds(launch_cap, groups, bare,
                                                  want):
    """`_spread_carry` alone: `groups` selector groups and `bare` unselected
    signatures give the one vector (None), `want` rows, or a refusal; an
    unselected pod takes index -1 and counts toward no row; a group without
    counts is refused as before."""
    import types
    feats = [types.SimpleNamespace(
        spread_counts=np.full(16, g + 1, np.int64),
        spread_group=("default", frozenset({(("app", f"s{g}"),)})))
        for g in range(groups)]
    feats[1:1] = [types.SimpleNamespace(spread_counts=None, spread_group=None)
                  for _ in range(bare)]
    algo = TPUScheduler.__new__(TPUScheduler)
    algo.launch_cap = launch_cap
    carried = algo._spread_carry(feats, 16)
    if want == "refused":
        assert carried is None
        return
    spread0, spread_groups = carried
    if want is None:
        assert spread0.shape == (16,) and spread_groups is None
        return
    group, counts_for = spread_groups
    assert spread0.shape == (want, 16) and counts_for.shape == (want, want)
    assert group.tolist() == [0] + [-1] * bare + list(range(1, groups))
    assert (spread0[:groups, 0] == np.arange(1, groups + 1)).all()
    assert not spread0[groups:].any()
    assert (counts_for == np.eye(want, dtype=bool)
            & (np.arange(want) < groups)[:, None]).all()
    feats[0].spread_counts = None
    assert algo._spread_carry(feats, 16) is None


class GangAmid(WithUnselected):
    """A gang of label-free pods amid Services' pods and Jobs' pods."""
    k = 3

    def pending(self, rng):
        pods = super().pending(rng)
        for j in (20, 21, 22, 23):
            pods[j] = Pod(name=pods[j].name, containers=box(),
                          labels={LABEL_POD_GROUP: "g"})
        return pods

    def selectors(self, s):
        super().selectors(s)
        s.create(PODGROUPS, PodGroup(name="g", min_member=4))


class GangOfBothKinds(GangAmid):
    """... and a gang whose members are one Service's pod, another's and
    two that nothing selects: its trial hands the seam all four."""

    def pending(self, rng):
        pods = super().pending(rng)
        for j, app in ((20, "svc-0"), (22, "svc-1")):
            pods[j] = Pod(name=pods[j].name, containers=box(),
                          labels={LABEL_POD_GROUP: "g", "app": app})
        return pods


@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("world", [GangAmid, GangOfBothKinds],
                         ids=lambda w: w.__name__)
def test_a_gang_in_the_pass_keeps_the_planners_cuts(world, percentage):
    """Where the pass holds a gang the planner is what it was: label-free
    pods go to the fused window's run, Services' pods down the singleton
    path, and a run is handed over (`plan`) wherever the next item goes
    the other way. Both worlds drain by `schedule_burst`, the serial one
    with the referee's gang trial."""
    world = world()

    want, _segments, _moved = drained(world, 5, percentage, use_tpu=False)
    assert all(node for _key, node in want)
    got, segments, moved = drained(world, 5, percentage)
    assert got == want
    pods = world.pending(random.Random(5 ^ 0x7AF1C))
    # the route each item takes: the gang is one item where its first
    # member stood, fusable when nothing selects any member
    fusable = not isinstance(world, GangOfBothKinds)
    routes = []
    for p in pods:
        if LABEL_POD_GROUP in p.labels:
            if p.name == "p020":
                routes.append("window" if fusable else "gang")
        else:
            routes.append("singleton" if p.labels else "window")
    # an unfusable gang flushes both runs; otherwise a hand-over wherever
    # the route changes
    plan = 0
    open_runs = set()
    for r in routes:
        if r == "gang":
            plan += len(open_runs)
            open_runs.clear()
            continue
        other = {"window": "singleton", "singleton": "window"}[r]
        if other in open_runs:
            plan += 1
            open_runs.discard(other)
        open_runs.add(r)
    assert moved[("cut", "plan")] == plan > 10
    assert moved[("cut", "class")] == 0
    # no burst segment holds a Service's pod beside a pod nothing selects
    for seg in segments:
        assert len({bool(set(p.labels) - {LABEL_POD_GROUP})
                    for p in seg}) == 1
    # ... and the one launch that does is the trial of the gang of both
    # kinds, carried where the seam once sent it to the serial referee
    assert moved["unselected"] == (0 if fusable else 2)
    assert moved["refused"] == 0


class ThreeAndBare(ManyServices):
    """Three Services' pods and one that nothing selects, on a cluster of
    more Services than a launch carries rows for."""

    def __init__(self):
        super().__init__(k=WIDE + 1)

    def pending(self, rng):
        return [Pod(name=f"q{j}", containers=box(),
                    labels={"app": f"svc-{j}"}) for j in range(3)] \
            + [Pod(name="bare", containers=box())]


def test_the_seam_refuses_more_groups_than_rows_and_carries_the_mix():
    """Handed directly (a gang's trial does so) a launch of more groups
    than rows, the seam books `burst-spread-mixed` and returns None: a
    refusal stays a refusal. One mixing selected and unselected pods it
    carries, to the serial oracle's bindings."""
    world = ThreeAndBare()
    want = dict(serial(world, 5, 0))
    s = world.build(5)
    sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    snap = sched.cache.update_snapshot(sched._snapshot)
    names = sched.cache.node_tree.list_names()
    many = [Pod(name=f"r{j}", containers=box(),
                labels={"app": f"svc-{j}"}) for j in range(WIDE + 1)]
    before = counters()
    assert sched.algorithm.schedule_burst(
        many, snap.node_infos, names, bucket=256) is None
    assert counters()["refused"] == before["refused"] + 1
    before = counters()
    mixed = world.pending(None)
    hosts = sched.algorithm.schedule_burst(
        mixed, snap.node_infos, names, bucket=32)
    after = counters()
    assert hosts is not None and all(hosts)
    assert [want[p.key] for p in mixed] == hosts
    assert after["refused"] == before["refused"]
    assert after[("steps", "grouped")] - before[("steps", "grouped")] == 4
    assert after["unselected"] - before["unselected"] == 1
    assert after["rows"] - before["rows"] == 3
    # as many groups as rows are carried; behind a serve loop the rows
    # are the narrow carry's
    assert sched.algorithm.schedule_burst(
        many[:WIDE], snap.node_infos, names, bucket=256) is not None
    sched.algorithm.discard_burst_folds()
    sched.algorithm.launch_cap = 256
    before = counters()
    assert sched.algorithm.schedule_burst(
        many[:CAP + 1], snap.node_infos, names, bucket=256) is None
    assert counters()["refused"] == before["refused"] + 1
    assert sched.algorithm.schedule_burst(
        many[:CAP], snap.node_infos, names, bucket=256) is not None


@pytest.mark.parametrize("percentage", [0, 100])
@pytest.mark.parametrize("world,rows", [
    (Interleaved(), "8"), (ManyServices(2 * CAP + 1, 70), str(WIDE))],
    ids=["eight-rows", "wide"])
def test_grouped_launch_on_a_mesh_of_four(world, rows, percentage):
    """The same launch with the node axis over four of the virtual host
    devices: the count rows are pinned on their last axis, at a power of
    two of rows and at the wide carry's."""
    mesh = S.make_mesh(4)
    want = serial(world, 5, percentage)
    got, segments, moved = drained(world, 5, percentage, mesh=mesh)
    assert got == want
    assert len(segments) == 1
    assert moved[("steps", "grouped")] == len(segments[0])
    assert moved[("carry", rows)] == 1
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
