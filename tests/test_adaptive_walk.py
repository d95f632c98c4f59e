"""The adaptive percentageOfNodesToScore path (the truncated walk) against
the benchmark's plain reference `benchmark/reference/default_provider_adaptive.py`.

The program is driven through its normal path (Store.create_many, the
informer pump, Scheduler.schedule_burst, the client's watch) with the
benchmark's own client, cluster builder and replay (`benchmark/lib/`), at
sizes where walks meet nodes that do not fit, last_index wraps and a tail of
pods is unschedulable; on uneven zones with Services the launch has to take
the rotation program (the truncated walk on positions). The reference is checked too: against
`default_provider` at 100%, against the serial oracle, and for the deferred
walk of `skip_decision`. CPU backend; decisions and counts only.
"""
import os
import random
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import check, cluster  # noqa: E402
from lib.client import BIND, Client  # noqa: E402
from lib.traffic import PodFactory  # noqa: E402
from reference import default_provider as full_ref  # noqa: E402
from reference import default_provider_adaptive as adaptive  # noqa: E402

GI, MI = 1024 ** 3, 1024 ** 2
PLAIN = {"pod_shapes": [{"kind": "plain", "share": 1.0,
                         "labels": {"app": "density"},
                         "requests": {"cpu_milli": 100,
                                      "memory_bytes": 500 * MI}}],
         "service_choice": None}
SPREAD = {"pod_shapes": [{"kind": "spread-by-service", "share": 1.0,
                          "requests": {"cpu_milli": 100,
                                       "memory_bytes": 500 * MI}}],
          "service_choice": {"policy": "per-cycle"}}


def config(nodes, pods_per_node, percentage, resident=None,
           reference="default_provider_adaptive"):
    return {"nodes": {"count": nodes, "zones": 3, "region": "r1",
                      "allocatable": {"cpu_milli": 4000,
                                      "memory_bytes": 32 * GI,
                                      "pods": pods_per_node}},
            "resident": resident,
            "scheduler": {"percentage_of_nodes_to_score": percentage},
            "store": {"watch_log_size": 1 << 16},
            "reference": reference}


class Run:
    """One cluster, one scheduler, the benchmark's client; `cycle` submits a
    backlog and drives the scheduler as `lib.drive.drain_scheduler` does."""

    def __init__(self, cfg, traffic, seed, program_percentage=None,
                 tpu=True):
        from kubernetes_tpu.apis.config import SchedulerConfiguration
        from kubernetes_tpu.factory import create_scheduler
        self.cfg = cfg
        self.store, self.rows, self.residents, self.services = \
            cluster.build(cfg, seed)
        pct = (cfg["scheduler"]["percentage_of_nodes_to_score"]
               if program_percentage is None else program_percentage)
        conf = SchedulerConfiguration(percentage_of_nodes_to_score=pct)
        conf.feature_gates = {**conf.feature_gates, "TPUScoring": tpu}
        self.sched = create_scheduler(self.store, conf,
                                      **({"mesh": None} if tpu else {}))
        self.sched.sync()
        self.client = Client(self.store, tracing=False)
        self.factory = PodFactory(traffic, len(self.services), seed)
        self.tpu = tpu
        self.cycles = 0

    def cycle(self, n_pods, max_pods=256):
        """Submit `n_pods`, schedule, drain the watch. Returns the pod ids."""
        self.factory.new_cycle()
        made = [self.factory.make(f"c{self.cycles}-{j}")
                for j in range(n_pods)]
        self.cycles += 1
        ids = [self.client.register(p, d) for p, d in made]
        self.client.create([p for p, _d in made])
        self.sched.pump()
        if self.tpu:
            while self.sched.schedule_burst(max_pods=max_pods):
                pass
        else:
            while self.sched.schedule_one(timeout=0.0):
                pass
            self.sched.wait_for_binds()
        self.sched.pump()
        self.client.drain()
        return ids

    def delete(self, ids):
        self.client.delete([self.client.keys[i] for i in ids])
        self.sched.pump()
        self.client.drain()

    def bound(self, ids):
        return [i for i in ids if self.client.bind_seen_at[i] > 0.0]

    def replay(self, cfg=None, first_binds=10 ** 9, sampled_binds=0, seed=1):
        """check.replay of the whole stream through the reference that
        `cfg` (default: the run's own) names. Returns (report, reference)."""
        ref = check.make_reference(cfg or self.cfg, self.rows, self.residents,
                                   self.services)
        rep = check.replay(self.client, ref, 0, len(self.client.log_kind),
                           first_binds, sampled_binds, seed)
        return rep, ref


# -- (a) num_to_find -----------------------------------------------------------
@pytest.mark.parametrize("n,percentage,want", [
    (99, 0, 99), (100, 0, 100), (200, 0, 100), (5000, 0, 500),
    (15000, 0, 750), (1000, 30, 300), (200, 30, 100), (99, 30, 99),
    (15000, 100, 15000), (15000, 150, 15000), (6000, 0, 300)])
def test_num_to_find_table(n, percentage, want):
    from kubernetes_tpu.oracle.generic_scheduler import \
        num_feasible_nodes_to_find
    assert adaptive.num_to_find(n, percentage) == want
    assert num_feasible_nodes_to_find(n, percentage) == want


# -- (b) even zones, nodes fill, last_index wraps, an unschedulable tail --------
def filling_run(percentage, program_percentage=None, seed=7):
    """240 nodes in 3 even zones, 2 pods a node (480 slots). Cycle 1 stays
    in place while cycle 2 overflows the cluster by 70 pods; then deletes,
    and two more cycles onto the half-full cluster."""
    from kubernetes_tpu.core.tpu_scheduler import WALK_NODES
    run = Run(config(240, 2, percentage), PLAIN, seed, program_percentage)
    tested0 = WALK_NODES.labels("truncated").value
    first = run.cycle(200)
    kept = run.cycle(200)
    over = run.cycle(150)
    assert len(run.bound(first)) == 200 and len(run.bound(kept)) == 200
    assert len(run.bound(over)) == 80          # 480 slots: 70 cannot fit
    run.delete(over)
    run.delete(first)
    again = run.cycle(200)
    assert len(run.bound(again)) == 200
    run.delete(again)
    last = run.cycle(130)
    assert len(run.bound(last)) == 130
    run.walk_tested = WALK_NODES.labels("truncated").value - tested0
    return run


@pytest.fixture(scope="module")
def filled():
    return filling_run(0)


def test_program_equals_reference_where_nodes_fill_and_walks_wrap(filled):
    rep, ref = filled.replay()
    assert rep["compared"] == rep["window_binds"] == 810
    assert rep["mismatches"] == [] and rep["over_allocatable"] == 0
    # the walks were cut short, went round the cluster and met full nodes
    assert ref.num_to_find == 117
    li = filled.sched.algorithm.last_index
    assert li == ref.last_index
    assert filled.sched.algorithm.last_node_index == ref.last_node_index
    # some walks passed over nodes that were full
    assert filled.walk_tested > 117 * 810


@pytest.mark.parametrize("percentage", [30, 100])
def test_program_equals_reference_at_a_set_percentage(percentage):
    run = filling_run(percentage, seed=percentage)
    rep, ref = run.replay()
    assert rep["compared"] == 810 and rep["mismatches"] == []
    assert ref.num_to_find == {30: 100, 100: 240}[percentage]
    assert run.sched.algorithm.last_index == ref.last_index


# -- (c) uneven zones, Services: the rotation program, truncated ----------------
def test_uneven_zones_with_services_take_the_rotation_program(monkeypatch):
    from kubernetes_tpu.core import tpu_scheduler as T
    seen = []
    orig = T.K.schedule_batch

    def spy(*a, **kw):
        seen.append((kw.get("rotation") is not None, len(kw["rotation"]),
                     a[4] < a[5], kw.get("spread0") is not None))
        return orig(*a, **kw)
    monkeypatch.setattr(T.K, "schedule_batch", spy)
    fallbacks0 = sum(c.value for c in T.ORACLE_FALLBACKS._children.values())
    # 250 nodes: zones of 84/83/83, so the tree's order rotates between
    # decisions; 4 pods a node, 2 of them resident, so nodes fill
    cfg = config(250, 4, 0, resident={
        "pods_per_node": 2, "services": 5,
        "requests": {"cpu_milli": 100, "memory_bytes": 500 * MI}})
    run = Run(cfg, SPREAD, 2 ** 31 + 9)
    for k in range(5):
        ids = run.cycle(150)
        assert len(run.bound(ids)) == 150
        if k not in (1, 2):               # those 300 pods stay: nodes fill up
            run.delete(ids)
    rep, ref = run.replay()
    assert rep["compared"] == 750 and rep["mismatches"] == []
    assert rep["over_allocatable"] == 0
    assert ref.num_to_find == 120
    assert run.sched.algorithm.last_index == ref.last_index
    assert run.sched.algorithm.last_node_index == ref.last_node_index
    assert len(seen) >= 5
    # every launch: an order shipped as (positions, order ids), under a
    # truncated walk (num_to_find < n), carried spread
    assert set(seen) == {(True, 2, True, True)}
    assert sum(c.value for c in T.ORACLE_FALLBACKS._children.values()) \
        == fallbacks0


def resident_of(pods_per_node, services):
    return {"pods_per_node": pods_per_node, "services": services,
            "requests": {"cpu_milli": 100, "memory_bytes": 500 * MI}}


def rollouts(run):
    """Five rollouts of 150 replicas, each drained in launches of at most 64
    pods so that last_index, last_node_index and the tree's zone cursor carry
    from launch to launch; the second and third stay, so nodes fill."""
    for k in range(5):
        ids = run.cycle(150, max_pods=64)
        assert len(run.bound(ids)) == 150
        if k not in (1, 2):
            run.delete(ids)
    c = run.client
    return {c.keys[c.log_pod[k]]: c.log_node[k]
            for k in range(len(c.log_kind)) if c.log_kind[k] == BIND}


@pytest.mark.parametrize("n,cap,percentage,per_node,services", [
    (250, 4, 0, 2, 5),      # zones 84/83/83; 120 of 250 found, 450 of 500
                            # free slots taken: walks pass many full nodes
    (131, 5, 40, 1, 3),     # 44/44/43; 100 of 131: every second walk wraps
    (200, 6, 60, 2, 4),     # 67/67/66; 120 of 200
], ids=["250n-default", "131n-40pct-wraps", "200n-60pct"])
def test_program_reference_and_oracle_agree_on_uneven_zones(
        n, cap, percentage, per_node, services):
    """Uneven zones x Services x truncated walk over consecutive launches:
    the program (the truncated position program with carried spread counts),
    the benchmark's plain reference and the program's serial oracle."""
    from kubernetes_tpu.core.tpu_scheduler import (ORACLE_FALLBACKS,
                                                   SCAN_ORDER_STEPS,
                                                   WALK_NODES)
    cfg = config(n, cap, percentage, resident=resident_of(per_node, services))
    seed = 2 ** 31 + n
    order0 = {o: SCAN_ORDER_STEPS.labels(o).value
              for o in ("position", "gather", "axis")}
    tested0 = WALK_NODES.labels("truncated").value
    fallbacks0 = sum(c.value for c in ORACLE_FALLBACKS._children.values())
    run = Run(cfg, SPREAD, seed)
    on_device = rollouts(run)
    # every one of the 750 decisions was a step on shipped positions; the
    # permutation gathers are gone, their label stays declared at 0
    assert {o: SCAN_ORDER_STEPS.labels(o).value - v
            for o, v in order0.items()} == \
        {"position": 750, "gather": 0, "axis": 0}
    assert sum(c.value for c in ORACLE_FALLBACKS._children.values()) \
        == fallbacks0
    rep, ref = run.replay()
    assert rep["compared"] == 750 and rep["mismatches"] == []
    assert rep["over_allocatable"] == 0
    assert ref.num_to_find == adaptive.num_to_find(n, percentage) < n
    # some walks passed over full nodes on their way to the quota
    assert WALK_NODES.labels("truncated").value - tested0 \
        > 750 * ref.num_to_find
    algo = run.sched.algorithm
    assert (algo.last_index, algo.last_node_index) == \
        (ref.last_index, ref.last_node_index)
    serial = Run(cfg, SPREAD, seed, tpu=False)
    assert rollouts(serial) == on_device
    assert serial.sched.algorithm.last_index == ref.last_index


# -- (d) the reference against other statements of the same semantics ------------
def random_stream(ref_a, ref_b, seed, steps, shapes):
    """Drive two references with one random stream of decisions, placements
    and removals; every decision has to agree."""
    rng = random.Random(seed)
    live = []
    for _ in range(steps):
        if live and rng.random() < 0.3:
            pod, node = live.pop(rng.randrange(len(live)))
            ref_a.remove(pod, node)
            ref_b.remove(pod, node)
            continue
        pod = rng.choice(shapes)
        a, b = ref_a.decide(pod), ref_b.decide(pod)
        assert a == b
        if a is not None:
            ref_a.place(pod, a)
            ref_b.place(pod, a)
            live.append((pod, a))
    assert ref_a.last_node_index == ref_b.last_node_index


@pytest.mark.parametrize("n,pods_per_node", [(120, 2), (131, 3), (57, 110)])
def test_reference_at_100_equals_default_provider(n, pods_per_node):
    cfg = config(n, pods_per_node, 100)
    rows = cluster.node_rows(cfg)
    services = {"default": [{"app": f"svc-{k}"} for k in range(3)]}
    shapes = [{"cpu": 100, "mem": 500 * MI, "namespace": "default",
               "labels": (("app", f"svc-{k}"),), "kind": "spread-by-service"}
              for k in range(3)]
    shapes.append({"cpu": 100, "mem": 500 * MI, "namespace": "default",
                   "labels": (("app", "density"),), "kind": "plain"})
    a = adaptive.Reference(rows, services, 100)
    b = full_ref.Reference(rows, services, 100)
    random_stream(a, b, n, 400, shapes)
    assert a.last_index == 0


def literal_walk(r, pod, order, last_index):
    """The truncated walk one node at a time, written out from the issue's
    statement with Python lists: a third opinion on `_walk`. Returns
    (tested, kept)."""
    kept, tested = [], 0
    for i in range(r.n):
        if len(kept) >= r.num_to_find:
            break
        j = int(order[(last_index + i) % r.n])
        tested += 1
        if (r.n_pods[j] + 1 <= r.alloc_pods[j]
                and r.alloc_cpu[j] >= pod["cpu"] + r.req_cpu[j]
                and r.alloc_mem[j] >= pod["mem"] + r.req_mem[j]):
            kept.append(j)
    return tested, kept


@pytest.mark.parametrize("n,percentage", [(400, 0), (131, 30), (700, 1)])
def test_walk_equals_a_node_at_a_time_walk(n, percentage):
    rows = cluster.node_rows(config(n, 2, percentage))
    ref = adaptive.Reference(rows, {"default": []}, percentage)
    pod = {"cpu": 100, "mem": 500 * MI, "namespace": "default",
           "labels": (("app", "density"),), "kind": "plain"}
    rng = random.Random(n)
    placed = []
    for _ in range(2 * n - 40):
        # peek at the order this decision will consume without consuming it
        state = ref.order.state
        order, _rank = ref.order.next_order()
        ref.order.state = state
        entry = ref.last_index
        tested, kept = literal_walk(ref, pod, order, entry)
        got = ref._walk(pod)
        assert got.tolist() == kept
        assert ref.last_index == (entry + tested) % n
        node = ref.names[rng.choice(kept)]
        ref.place(pod, node)
        placed.append(node)
        if rng.random() < 0.2:
            ref.remove(pod, placed.pop(rng.randrange(len(placed))))


@pytest.mark.parametrize("n,cap,percentage,resident", [
    (130, 2, 30, None), (150, 2, 0, None),
    (131, 3, 40, {"pods_per_node": 1, "services": 3}), (260, 2, 10, None)])
def test_reference_equals_the_serial_oracle(n, cap, percentage, resident):
    """The program's own oracle (oracle/generic_scheduler.py, through the
    scheduler shell with the device off) as a second opinion."""
    if resident:
        resident = {**resident, "requests": {"cpu_milli": 100,
                                             "memory_bytes": 500 * MI}}
    cfg = config(n, cap, percentage, resident=resident)
    run = Run(cfg, SPREAD if resident else PLAIN, n, tpu=False)
    a = run.cycle(90)
    b = run.cycle(90)
    run.delete(a)
    c = run.cycle(60)
    assert len(run.bound(a + b + c)) == 240
    rep, ref = run.replay()
    assert rep["compared"] == 240 and rep["mismatches"] == []
    assert ref.num_to_find == 100 and ref.last_index != 0


# -- (e) controls ----------------------------------------------------------------
def test_control_program_at_100_judged_at_the_default():
    run = filling_run(0, program_percentage=100, seed=3)
    rep, _ref = run.replay()
    assert len(rep["mismatches"]) > 0


def test_control_program_at_the_default_judged_at_100(filled):
    rep, _ref = filled.replay(config(240, 2, 100,
                                     reference="default_provider"))
    assert len(rep["mismatches"]) > 0


# -- (f) the deferred walk of skip_decision is exact -------------------------------
@pytest.mark.parametrize("first,sampled", [(0, 0), (50, 40), (300, 7)])
def test_sampled_replay_leaves_the_reference_in_the_same_state(
        filled, first, sampled):
    _rep, every = filled.replay()
    rep, some = filled.replay(first_binds=first, sampled_binds=sampled)
    assert rep["compared"] == first + sampled
    assert rep["mismatches"] == []
    assert (some.last_index, some.last_node_index) == \
        (every.last_index, every.last_node_index)
    assert some.order.state == every.order.state
    assert np.array_equal(some.n_pods, every.n_pods)


def test_skip_decision_waits_for_its_place():
    ref = adaptive.Reference(cluster.node_rows(config(120, 2, 0)),
                             {"default": []}, 0)
    pod = {"cpu": 100, "mem": 500 * MI, "namespace": "default",
           "labels": (), "kind": "plain"}
    ref.skip_decision()
    assert ref.last_index == 0        # no walk yet: the pod is not known
    with pytest.raises(RuntimeError):
        ref.decide(pod)
    ref.place(pod, "node-5")
    assert ref.last_index == 100 and ref.last_node_index == 1
    assert ref.n_pods[5] == 1


# -- (g) counters and the span ------------------------------------------------------
def test_counters_and_span_of_a_300_pod_burst(monkeypatch):
    from kubernetes_tpu import obs
    from kubernetes_tpu.core import tpu_scheduler as T
    launched = []
    orig = T.K.schedule_batch

    def spy(nodes, pods, *a, **kw):
        launched.append((kw["n_pods"], pods["skip"].shape[0]))
        return orig(nodes, pods, *a, **kw)
    monkeypatch.setattr(T.K, "schedule_batch", spy)

    def snap():
        return {(f.name, k): c.value for f in (T.WALK_NODES, T.SCAN_STEPS,
                                                T.DEVICE_DISPATCH)
                for k, c in f._children.items()}

    def moved(before):
        return {k: v - before.get(k, 0) for k, v in snap().items()
                if v - before.get(k, 0)}

    # 240 nodes of 1 pod each: from the 125th pod on, walks pass full nodes
    run = Run(config(240, 1, 0), PLAIN, 5)
    obs.trace.clear()
    before = snap()
    ids = run.cycle(300, max_pods=300)
    assert len(run.bound(ids)) == 240
    got = moved(before)
    # one 300-step launch in the 512 bucket decides the 240 that fit and
    # fails the 241st; what the device decided after it is dropped and retried
    launches = got[("tpu_device_dispatch_total", ("burst_scan",))]
    assert launches == len(launched) >= 1
    assert ("tpu_device_dispatch_total", ("burst_uniform",)) not in got
    # the counter says what the device ran: a step a pod, whatever the bucket;
    # 'pad' stays declared and reads 0
    assert launched[0] == (300, 512)
    assert all(bucket == 512 for _n, bucket in launched)
    real = got[("tpu_scan_steps_total", ("real",))]
    assert real == sum(n for n, _bucket in launched) >= 300
    assert T.SCAN_STEPS._children[("pad",)].value == 0
    # the reference's walks over the same stream test as many nodes as the
    # first launch counted for its decided prefix; later launches add the
    # walks of pods that found nothing (n nodes each)
    rep, ref = run.replay()
    assert rep["mismatches"] == []
    tested = got[("tpu_walk_nodes_evaluated_total", ("truncated",))]
    assert ("tpu_walk_nodes_evaluated_total", ("full",)) not in got
    assert tested >= 117 * 124 + sum(range(118, 241))
    names = [e["name"] for e in obs.trace.events()]
    assert names.count("burst.stack") == launches
    # a stack span lies between its launch's encode and its dispatch
    assert names.index("burst.stack") < names.index("burst.dispatch")

    # every node scored: the K-batch kernel, n nodes a pod, no scan step
    run = Run(config(240, 2, 100), PLAIN, 6)
    before = snap()
    ids = run.cycle(300, max_pods=300)
    assert len(run.bound(ids)) == 300
    got = moved(before)
    assert got[("tpu_walk_nodes_evaluated_total", ("full",))] == 300 * 240
    assert not any(k[0] == "tpu_scan_steps_total" for k in got)
    assert ("tpu_walk_nodes_evaluated_total", ("truncated",)) not in got


def test_order_counter_and_rotation_span(monkeypatch):
    """`tpu_scan_order_steps_total` says how a launch's steps found their
    NodeTree order, and `burst.rotation` is in the ring with its args."""
    from kubernetes_tpu import obs
    from kubernetes_tpu.core import tpu_scheduler as T

    def steps():
        return {k[0]: c.value for k, c in T.SCAN_ORDER_STEPS._children.items()}

    def moved(before):
        return {k: v - before.get(k, 0) for k, v in steps().items()
                if v - before.get(k, 0)}

    def rotation_spans():
        return [e for e in obs.trace.events() if e["name"] == "burst.rotation"]

    resident = resident_of(2, 5)
    # uneven zones (84/83/83), truncated walk: every step sorts positions
    obs.trace.clear()
    before = steps()
    run = Run(config(250, 4, 0, resident=resident), SPREAD, 21)
    assert len(run.bound(run.cycle(150, max_pods=150))) == 150
    assert moved(before) == {"position": 150}
    spans = rotation_spans()
    assert len(spans) == 1
    # three zones: the axis order and the three rotated ones, in a bucket of 4
    assert spans[0]["args"]["orders"] == 4
    assert spans[0]["args"]["cycles"] == 256
    names = [e["name"] for e in obs.trace.events()]
    # inside the encode phase, before the pod rows are stacked
    assert names.index("burst.rotation") < names.index("burst.stack") \
        < names.index("burst.dispatch")

    # the same cluster with every node scored: positions again, no sort in
    # `filter` (the program differs, not the way the order is shipped)
    obs.trace.clear()
    before = steps()
    run = Run(config(250, 4, 100, resident=resident), SPREAD, 22)
    assert len(run.bound(run.cycle(150, max_pods=150))) == 150
    assert moved(before) == {"position": 150}
    assert len(rotation_spans()) == 1

    # even zones (80/80/80): the tree never rotates, nothing is shipped
    obs.trace.clear()
    before = steps()
    run = Run(config(240, 4, 0, resident=resident), SPREAD, 23)
    assert len(run.bound(run.cycle(150, max_pods=150))) == 150
    assert moved(before) == {"axis": 150}
    assert rotation_spans() == []
