"""Decision-parity fuzz: the TPU kernel path vs the pure-Python oracle.

For random clusters and pod streams, both schedulers must agree on every
suggested host, feasible-node set, evaluated count, per-node integer score,
and failure-reason set — including the adaptive partial search rotation and
the round-robin tie-break state, across a *sequence* of decisions with cache
updates in between (the reference's serial scheduleOne semantics).
"""
import copy
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Pod, Node, Container, ContainerPort, Taint, Toleration, Affinity,
    NodeAffinity, NodeSelectorTerm, Requirement, PreferredSchedulingTerm,
    PodAffinity, PodAntiAffinity, PodAffinityTerm, WeightedPodAffinityTerm,
    LabelSelector, Service, ImageState,
    IN, EXISTS, NO_SCHEDULE, PREFER_NO_SCHEDULE,
    LABEL_ZONE_FAILURE_DOMAIN, LABEL_ZONE_REGION, LABEL_HOSTNAME,
)
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
from kubernetes_tpu.oracle.generic_scheduler import GenericScheduler, FitError


GI = 1024 ** 3


def make_cluster(rng, n, zones=0, taint_frac=0.0, labeled_frac=0.0,
                 images=False):
    nodes = []
    for i in range(n):
        labels = {LABEL_HOSTNAME: f"n{i}"}
        if zones:
            z = i % zones
            labels[LABEL_ZONE_FAILURE_DOMAIN] = f"zone-{z}"
            labels[LABEL_ZONE_REGION] = "r1"
        if labeled_frac and rng.random() < labeled_frac:
            labels["disk"] = rng.choice(["ssd", "hdd"])
            labels["size"] = str(rng.randint(1, 100))
        taints = ()
        if taint_frac and rng.random() < taint_frac:
            effect = rng.choice([NO_SCHEDULE, PREFER_NO_SCHEDULE])
            taints = (Taint(key="team", value=rng.choice(["a", "b"]), effect=effect),)
        imgs = ()
        if images and rng.random() < 0.5:
            imgs = (ImageState(names=(f"img-{rng.randint(0, 3)}:v1",),
                               size_bytes=rng.randint(10, 2000) * 1024 * 1024),)
        nodes.append(Node(
            name=f"n{i}", labels=labels, taints=taints,
            allocatable={"cpu": rng.choice([2000, 4000, 8000]),
                         "memory": rng.choice([8, 16, 32]) * GI,
                         "pods": rng.choice([4, 8, 110])},
            images=imgs))
    return nodes


def make_pod(rng, j, selectors=False, tolerations=False, node_affinity=False,
             pod_affinity=False, ports=False, images=False):
    reqs = {}
    if rng.random() < 0.9:
        reqs["cpu"] = rng.choice([100, 500, 1000, 2000])
    if rng.random() < 0.9:
        reqs["memory"] = rng.choice([256, 512, 1024, 4096]) * 1024 * 1024
    port_list = ()
    if ports and rng.random() < 0.4:
        port_list = (ContainerPort(host_port=rng.choice([80, 8080, 9090]),
                                   container_port=80),)
    image = f"img-{rng.randint(0, 3)}:v1" if images else ""
    labels = {"app": rng.choice(["web", "db", "cache"])}
    kw = {}
    if selectors and rng.random() < 0.4:
        kw["node_selector"] = {"disk": rng.choice(["ssd", "hdd"])}
    if tolerations and rng.random() < 0.5:
        kw["tolerations"] = (Toleration(key="team", op="Equal",
                                        value=rng.choice(["a", "b"]),
                                        effect=""),)
    affinity_parts = {}
    if node_affinity and rng.random() < 0.5:
        affinity_parts["node_affinity"] = NodeAffinity(
            required=(NodeSelectorTerm(match_expressions=(
                Requirement(key="disk", op=IN, values=("ssd", "hdd")),)),)
            if rng.random() < 0.5 else None,
            preferred=(PreferredSchedulingTerm(
                weight=rng.randint(1, 100),
                preference=NodeSelectorTerm(match_expressions=(
                    Requirement(key="disk", op=IN, values=("ssd",)),))),))
    if pod_affinity and rng.random() < 0.6:
        term = PodAffinityTerm(
            label_selector=LabelSelector.from_dict({"app": rng.choice(["web", "db"])}),
            topology_key=rng.choice([LABEL_HOSTNAME, LABEL_ZONE_FAILURE_DOMAIN]))
        if rng.random() < 0.5:
            affinity_parts["pod_affinity"] = PodAffinity(
                required=(term,) if rng.random() < 0.5 else (),
                preferred=(WeightedPodAffinityTerm(weight=rng.randint(1, 100),
                                                   term=term),))
        else:
            affinity_parts["pod_anti_affinity"] = PodAntiAffinity(
                required=(term,) if rng.random() < 0.5 else (),
                preferred=(WeightedPodAffinityTerm(weight=rng.randint(1, 100),
                                                   term=term),))
    if affinity_parts:
        kw["affinity"] = Affinity(**affinity_parts)
    return Pod(name=f"p{j}", labels=labels,
               containers=(Container.make(name="c", requests=reqs, ports=port_list,
                                          image=image),), **kw)


def run_parity_sequence(rng, nodes, pods, percentage=100, services=None):
    """Run both schedulers over the same decision stream; assert parity."""
    node_infos = {n.name: NodeInfo(n) for n in nodes}
    names = [n.name for n in nodes]
    services = services or []
    oracle = GenericScheduler(percentage_of_nodes_to_score=percentage)
    tpu = TPUScheduler(percentage_of_nodes_to_score=percentage,
                       services_fn=lambda: services)
    from kubernetes_tpu.oracle.generic_scheduler import default_priority_configs
    prio_cfgs = default_priority_configs(services_fn=lambda: services)
    scheduled = 0
    for pod in pods:
        o_err = t_err = None
        o_res = t_res = None
        try:
            o_res = oracle.schedule(pod, node_infos, names,
                                    priority_configs=prio_cfgs)
        except FitError as e:
            o_err = e
        try:
            t_res = tpu.schedule(pod, node_infos, names)
        except FitError as e:
            t_err = e
        assert (o_err is None) == (t_err is None), \
            f"{pod.name}: oracle={'fit' if o_err is None else 'err'} tpu={'fit' if t_err is None else 'err'}"
        if o_err is not None:
            assert set(o_err.failed_predicates) == set(t_err.failed_predicates), pod.name
            for k in o_err.failed_predicates:
                assert set(o_err.failed_predicates[k]) == set(t_err.failed_predicates[k]), \
                    (pod.name, k, o_err.failed_predicates[k], t_err.failed_predicates[k])
            continue
        assert o_res.suggested_host == t_res.suggested_host, \
            (pod.name, o_res.suggested_host, t_res.suggested_host,
             o_res.host_priority, t_res.host_priority)
        assert o_res.evaluated_nodes == t_res.evaluated_nodes, pod.name
        assert o_res.feasible_nodes == t_res.feasible_nodes, pod.name
        assert o_res.host_priority == t_res.host_priority, \
            (pod.name, o_res.host_priority, t_res.host_priority)
        # apply the decision (assume) so the next pod sees it
        placed = copy.deepcopy(pod)
        placed.node_name = o_res.suggested_host
        node_infos[o_res.suggested_host].add_pod(placed)
        scheduled += 1
    return scheduled


class TestResourceParity:
    @pytest.mark.parametrize("n,percentage", [(6, 100), (30, 100), (130, 50), (130, 0)])
    def test_resources_only(self, n, percentage):
        rng = random.Random(42 + n + percentage)
        nodes = make_cluster(rng, n)
        pods = [make_pod(rng, j) for j in range(30)]
        assert run_parity_sequence(rng, nodes, pods, percentage) > 0

    def test_saturation_fit_errors(self):
        rng = random.Random(7)
        nodes = make_cluster(rng, 4)
        for node in nodes:
            node.allocatable["pods"] = 2
        pods = [make_pod(rng, j) for j in range(16)]  # 16 pods > 8 slots
        run_parity_sequence(rng, nodes, pods)

    def test_extended_resources(self):
        rng = random.Random(11)
        nodes = make_cluster(rng, 8)
        for i, node in enumerate(nodes):
            if i % 2 == 0:
                node.allocatable["example.com/gpu"] = 2
        pods = []
        for j in range(12):
            p = make_pod(rng, j)
            if j % 3 == 0:
                reqs = dict(p.containers[0].requests)
                reqs["example.com/gpu"] = 1
                p.containers = (Container.make(name="c", requests=reqs),)
            if j == 7:  # scalar that exists nowhere
                p.containers = (Container.make(
                    name="c", requests={"cpu": 100, "nosuch.io/dev": 1}),)
            pods.append(p)
        run_parity_sequence(rng, nodes, pods)


class TestFeatureParity:
    def test_taints_and_tolerations(self):
        rng = random.Random(13)
        nodes = make_cluster(rng, 20, taint_frac=0.5)
        pods = [make_pod(rng, j, tolerations=True) for j in range(25)]
        run_parity_sequence(rng, nodes, pods)

    def test_selectors_and_node_affinity(self):
        rng = random.Random(17)
        nodes = make_cluster(rng, 20, labeled_frac=0.7)
        pods = [make_pod(rng, j, selectors=True, node_affinity=True)
                for j in range(25)]
        run_parity_sequence(rng, nodes, pods)

    def test_host_ports(self):
        rng = random.Random(19)
        nodes = make_cluster(rng, 6)
        pods = [make_pod(rng, j, ports=True) for j in range(20)]
        run_parity_sequence(rng, nodes, pods)

    def test_zones_and_selector_spread(self):
        rng = random.Random(23)
        nodes = make_cluster(rng, 12, zones=3)
        services = [Service(name="web", selector={"app": "web"})]
        pods = [make_pod(rng, j) for j in range(20)]
        run_parity_sequence(rng, nodes, pods, services=services)

    def test_interpod_affinity(self):
        rng = random.Random(29)
        nodes = make_cluster(rng, 8, zones=2)
        pods = [make_pod(rng, j, pod_affinity=True) for j in range(18)]
        run_parity_sequence(rng, nodes, pods)

    def test_interpod_affinity_partial_labels(self):
        """Nodes MISSING the topology labels exercise the segment-sum
        rewrite's absent-label branches (ids == -1 rows, fixed nodes
        without the key): a node lacking the label must never match any
        topology pair (nodes_same_topology is False when either side lacks
        the key) — bit-identical to the oracle on a mixed cluster."""
        rng = random.Random(53)
        nodes = make_cluster(rng, 12, zones=3)
        for i, n in enumerate(nodes):
            if i % 3 == 0:
                n.labels = {k: v for k, v in n.labels.items()
                            if k != LABEL_ZONE_FAILURE_DOMAIN}
            if i % 4 == 0:
                n.labels = {k: v for k, v in n.labels.items()
                            if k != LABEL_HOSTNAME}
        pods = [make_pod(rng, j, pod_affinity=True) for j in range(24)]
        run_parity_sequence(rng, nodes, pods)

    @pytest.mark.parametrize("seed", [101, 211, 307])
    def test_interpod_affinity_heavy(self, seed):
        """Affinity-heavy worlds for the segment-sum counting path
        (node_state._interpod_pref_counts): most pods carry preferred +/-
        required terms over hostname AND zone topologies with random
        weights, so the per-(key,value) buckets accumulate many signed
        events per cycle — host_priority must stay bit-identical to the
        oracle's processTerm walk (interpod_affinity.go:116,215)."""
        rng = random.Random(seed)
        nodes = make_cluster(rng, rng.choice([9, 15]), zones=3)
        pods = [make_pod(rng, j, pod_affinity=True) for j in range(30)]
        assert run_parity_sequence(rng, nodes, pods) > 0

    def test_image_locality(self):
        rng = random.Random(31)
        nodes = make_cluster(rng, 10, images=True)
        pods = [make_pod(rng, j, images=True) for j in range(15)]
        run_parity_sequence(rng, nodes, pods)

    def test_everything_at_once(self):
        rng = random.Random(37)
        nodes = make_cluster(rng, 40, zones=3, taint_frac=0.3, labeled_frac=0.5,
                             images=True)
        services = [Service(name="web", selector={"app": "web"})]
        pods = [make_pod(rng, j, selectors=True, tolerations=True,
                         node_affinity=True, pod_affinity=True, ports=True,
                         images=True) for j in range(40)]
        run_parity_sequence(rng, nodes, pods, services=services)


class TestClusterShrink:
    def test_last_index_survives_node_removals(self):
        """last_index persists across cycles; after removals shrink the
        cluster below it, the rotation origin must wrap modulo n like the
        oracle's walk (generic_scheduler.py:148) — regression for the
        gather-free rank math assuming last_index < n_real."""
        rng = random.Random(97)
        nodes = make_cluster(rng, 7)
        node_infos = {n.name: NodeInfo(n) for n in nodes}
        names = [n.name for n in nodes]
        oracle = GenericScheduler(percentage_of_nodes_to_score=100)
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        # advance rotation state well past the post-shrink node count
        for j in range(5):
            pod = make_pod(rng, j)
            o = oracle.schedule(pod, node_infos, names)
            t = tpu.schedule(pod, node_infos, names)
            assert o.suggested_host == t.suggested_host
            placed = copy.deepcopy(pod)
            placed.node_name = o.suggested_host
            node_infos[o.suggested_host].add_pod(placed)
        assert oracle.last_index == tpu.last_index
        # pin the rotation origin past the post-shrink node count (the warm-up
        # stream may leave it anywhere); both walks must then wrap modulo n
        oracle.last_index = tpu.last_index = 5
        oracle.last_node_index = tpu.last_node_index = 3
        keep = names[:2]
        shrunk = {k: node_infos[k] for k in keep}
        for j in range(5, 11):
            pod = make_pod(rng, j)
            o_err = t_err = o = t = None
            try:
                o = oracle.schedule(pod, shrunk, keep)
            except FitError as e:
                o_err = e
            try:
                t = tpu.schedule(pod, shrunk, keep)
            except FitError as e:
                t_err = e
            assert (o_err is None) == (t_err is None)
            if o is None:
                continue
            assert o.suggested_host == t.suggested_host
            assert o.evaluated_nodes == t.evaluated_nodes
            assert t.evaluated_nodes >= 0
            assert o.host_priority == t.host_priority
            placed = copy.deepcopy(pod)
            placed.node_name = o.suggested_host
            shrunk[o.suggested_host].add_pod(placed)


class TestBurstParity:
    def test_burst_matches_serial_oracle(self):
        rng = random.Random(41)
        nodes = make_cluster(rng, 30, zones=3)
        pods = [make_pod(rng, j) for j in range(60)]
        # serial oracle with cache updates between decisions
        oracle_infos = {n.name: NodeInfo(n) for n in nodes}
        names = [n.name for n in nodes]
        oracle = GenericScheduler(percentage_of_nodes_to_score=100)
        expected = []
        for pod in pods:
            try:
                res = oracle.schedule(pod, oracle_infos, names)
                expected.append(res.suggested_host)
                placed = copy.deepcopy(pod)
                placed.node_name = res.suggested_host
                oracle_infos[res.suggested_host].add_pod(placed)
            except FitError:
                expected.append(None)
        # one burst on device
        tpu_infos = {n.name: NodeInfo(n) for n in nodes}
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        got = tpu.schedule_burst(pods, tpu_infos, names)
        assert got == expected

    def test_burst_with_adaptive_percentage(self):
        rng = random.Random(43)
        nodes = make_cluster(rng, 130)
        pods = [make_pod(rng, j) for j in range(40)]
        oracle_infos = {n.name: NodeInfo(n) for n in nodes}
        names = [n.name for n in nodes]
        oracle = GenericScheduler(percentage_of_nodes_to_score=50)
        expected = []
        for pod in pods:
            try:
                res = oracle.schedule(pod, oracle_infos, names)
                expected.append(res.suggested_host)
                placed = copy.deepcopy(pod)
                placed.node_name = res.suggested_host
                oracle_infos[res.suggested_host].add_pod(placed)
            except FitError:
                expected.append(None)
        tpu_infos = {n.name: NodeInfo(n) for n in nodes}
        tpu = TPUScheduler(percentage_of_nodes_to_score=50)
        got = tpu.schedule_burst(pods, tpu_infos, names)
        assert got == expected


class TestKernelRTCR:
    def test_rtcr_truncates_toward_zero(self):
        """Go int64 division truncates toward zero: p=55 scores 5, not 4."""
        from kubernetes_tpu.ops.node_state import NodeStateEncoder, PodEncoder
        from kubernetes_tpu.ops import kernels as K
        node = Node(name="n0", labels={LABEL_HOSTNAME: "n0"},
                    allocatable={"cpu": 10000, "memory": 10000, "pods": 110})
        infos = {"n0": NodeInfo(node)}
        enc = NodeStateEncoder()
        batch = enc.encode(infos, ["n0"])
        pod = Pod(name="p", containers=(Container.make(
            name="c", requests={"cpu": 5500, "memory": 5500}),))
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        feats = PodEncoder(infos, batch).encode(pod)
        pod_in = tpu._pod_arrays(feats, batch.n_pad)
        nodes = tpu._node_arrays(batch)
        weights = {k: 0 for k in K.DEFAULT_WEIGHTS}
        weights["rtcr"] = 1
        out = K.schedule_cycle(nodes, pod_in, 0, 0, 1, 1, 4, weights=weights)
        # p = 100 - (10000-5500)*100//10000 = 55 for both cpu and mem
        # score = (5 + 5) // 2 = 5 (Go trunc), not 4 (Python floor)
        assert int(np.asarray(out["total"])[0]) == 5
        from kubernetes_tpu.oracle import priorities as prios
        rtcr = prios.make_rtcr_map()
        assert rtcr(pod, infos["n0"]) == 5


class TestZoneRotationParity:
    """The NodeTree's zone-interleaved enumeration ROTATES between cycles
    when zone sizes are uneven (node_tree.py rotation_map): selectHost tie
    ranks land on different nodes each cycle. Burst decisions must replay
    that per-cycle rotation (kernels.py rotate branch), including the
    saturation tail where pods become unschedulable mid-burst."""

    @pytest.mark.parametrize("n_nodes,n_pods,cap", [
        (7, 70, 4000),      # uneven zones (3,2,2) + unschedulable tail
        (13, 40, 2000),     # uneven zones, all placed
        (3, 40, 16000),     # tiny cluster, deep stacking
    ])
    def test_burst_matches_oracle_under_rotation(self, n_nodes, n_pods, cap):
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        GI = 1024 ** 3
        MI = 1024 ** 2

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={"failure-domain.beta.kubernetes.io/zone": f"z{i % 3}",
                            LABEL_HOSTNAME: f"n{i}"},
                    allocatable={"cpu": cap, "memory": 8 * GI, "pods": 110}))
            return s

        def make_pods(s):
            for j in range(n_pods):
                s.create(PODS, Pod(name=f"p{j}", labels={"app": "x"},
                                   containers=(Container.make(
                                       name="c",
                                       requests={"cpu": 450,
                                                 "memory": 700 * MI}),)))

        s1, s2 = build(), build()
        tpu = Scheduler(s1, use_tpu=True, percentage_of_nodes_to_score=100)
        ora = Scheduler(s2, use_tpu=False, percentage_of_nodes_to_score=100)
        tpu.sync()
        ora.sync()
        make_pods(s1)
        make_pods(s2)
        tpu.pump()
        ora.pump()
        while tpu.schedule_burst(max_pods=64):
            pass
        while ora.schedule_one(timeout=0.0):
            pass
        tpu.pump()
        ora.pump()
        b1 = {p.key: p.node_name for p in s1.list(PODS)[0]}
        b2 = {p.key: p.node_name for p in s2.list(PODS)[0]}
        assert b1 == b2
        assert tpu.algorithm.last_node_index == ora.algorithm.last_node_index

    def test_refusal_path_matches_oracle_under_rotation(self):
        """Non-uniform pods on an uneven-zone cluster make schedule_burst
        refuse the whole burst; the serial fallback must consume exactly one
        NodeTree enumeration per pod (pod 0 reuses the segment's)."""
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        GI = 1024 ** 3
        MI = 1024 ** 2

        def build():
            s = Store(watch_log_size=65536)
            for i in range(7):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={"failure-domain.beta.kubernetes.io/zone": f"z{i % 3}",
                            LABEL_HOSTNAME: f"n{i}"},
                    allocatable={"cpu": 4000, "memory": 8 * GI, "pods": 110}))
            return s

        def make_pods(s):
            for j in range(12):
                s.create(PODS, Pod(name=f"p{j}", containers=(Container.make(
                    name="c", requests={"cpu": 450 if j % 2 == 0 else 300,
                                        "memory": 700 * MI}),)))

        s1, s2 = build(), build()
        tpu = Scheduler(s1, use_tpu=True, percentage_of_nodes_to_score=100)
        ora = Scheduler(s2, use_tpu=False, percentage_of_nodes_to_score=100)
        tpu.sync()
        ora.sync()
        make_pods(s1)
        make_pods(s2)
        tpu.pump()
        ora.pump()
        while tpu.schedule_burst(max_pods=64):
            pass
        while ora.schedule_one(timeout=0.0):
            pass
        tpu.pump()
        ora.pump()
        b1 = {p.key: p.node_name for p in s1.list(PODS)[0]}
        b2 = {p.key: p.node_name for p in s2.list(PODS)[0]}
        assert b1 == b2


class TestBanElimBurstParity:
    """The uniform kernel's banned-node fold + ELIM batching (self-matching
    hostname anti-affinity, host-port conflicts) must match the oracle
    exactly, including saturation where pods outnumber viable nodes."""

    def _run_pair(self, n_nodes, strategy_kwargs, n_pods, zones=3):
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.models.hollow import PodStrategy, make_pods
        GI = 1024 ** 3

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                labels = {LABEL_HOSTNAME: f"n{i}"}
                if zones:
                    labels["failure-domain.beta.kubernetes.io/zone"] = \
                        f"z{i % zones}"
                s.create(NODES, Node(
                    name=f"n{i}", labels=labels,
                    allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
            return s

        st = PodStrategy(count=n_pods, **strategy_kwargs)
        bindings = []
        for use_tpu in (True, False):
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100)
            sched.sync()
            for pod in make_pods(st, 0):
                s.create(PODS, pod)
            sched.pump()
            if use_tpu:
                while sched.schedule_burst(max_pods=256):
                    pass
            else:
                while sched.schedule_one(timeout=0.0):
                    pass
            sched.pump()
            bindings.append({p.key: p.node_name for p in s.list(PODS)[0]})
        assert bindings[0] == bindings[1]
        return bindings[0]

    def test_anti_affinity_saturation(self):
        # 11 nodes, 30 pods: 11 place (one per host), 19 go unschedulable
        got = self._run_pair(11, dict(
            anti_affinity_topology=LABEL_HOSTNAME,
            labels={"name": "test", "color": "green"}), 30)
        placed = [v for v in got.values() if v]
        assert len(placed) == 11
        assert len(set(placed)) == 11

    def test_host_ports_saturation(self):
        got = self._run_pair(9, dict(host_port=8080), 20)
        placed = [v for v in got.values() if v]
        assert len(placed) == 9
        assert len(set(placed)) == 9

    def test_zone_affinity_colocation(self):
        # single zone spanning the cluster (reference PodAffinity shape)
        got = self._run_pair(10, dict(
            affinity_topology="failure-domain.beta.kubernetes.io/zone",
            labels={"foo": ""}), 25, zones=1)
        placed = [v for v in got.values() if v]
        assert len(placed) == 25

    def test_anti_affinity_uneven_zone_rotation(self):
        # uneven zones force per-cycle rotation + ELIM single-step fallback
        got = self._run_pair(7, dict(
            anti_affinity_topology=LABEL_HOSTNAME,
            labels={"name": "test", "color": "green"}), 12)
        placed = [v for v in got.values() if v]
        assert len(placed) == 7


#: blanket injection rates for the under-fire parity variants — every seam
#: of the round-13 contract (device, commit_wave, fanout, native, watch).
#: Rates are high enough that a single fuzz trial fires several seams; the
#: oracle world always runs clean (it IS the referee).
CHAOS_FUZZ_RATES = {
    "device.dispatch": 0.2, "device.fetch": 0.2,
    "store.commit_wave": 0.15, "store.commit_wave.ambiguous": 0.1,
    "store.fanout": 0.15, "native.commitcore": 0.1,
    "native.heapcore": 0.1, "watch.drop": 0.1,
}


def set_world_chaos(chaos, seed: int, use_tpu: bool) -> None:
    """Install the injection plan for the TPU world of a differential
    fuzz; the oracle world (and chaos=False) disables the plane. `chaos`
    is False, True (blanket CHAOS_FUZZ_RATES), or a rates dict targeting
    one or a few seams (the per-seam smoke).

    store.commit_wave is always capped BELOW the scheduler's 4-attempt
    commit retry budget: a wave whose EVERY retry fails must re-queue its
    pods with backoff — correctness holds but bit-parity with the
    never-faulted oracle cannot, so the parity harness makes exhaustion
    structurally impossible rather than probabilistically rare."""
    from kubernetes_tpu import chaos as chaos_mod
    if chaos and use_tpu:
        rates = CHAOS_FUZZ_RATES if chaos is True else dict(chaos)
        chaos_mod.plan(seed=seed, rates=rates,
                       limits={"store.commit_wave": 3})
    else:
        chaos_mod.disable()


def node_churn_driver(use_tpu, store, seed):
    """Per-world node-kill delivery for the churn fuzz variants. The TPU
    world arms the node.dead seam, so the kill lands MID-BURST at the
    round's first launch crossing — between dispatch and fetch — where
    the launch-refusal contract (StaleNodeRefusal / the fused window's
    stale scan) replans the in-flight block against the post-churn world.
    The serial world deletes at the round boundary. The two are
    equivalent precisely because a refused launch commits nothing decided
    against the pre-churn world. Returns (kill, flush): call
    kill(victim) when the schedule says a node dies this round, flush()
    after the round's scheduling (a round with no launch crossing applies
    the kill at the boundary, where neither world decided anything)."""
    from kubernetes_tpu import chaos as chaos_mod
    from kubernetes_tpu.store.store import NODES, NotFoundError
    pending = []

    def do_kill(victim):
        try:
            store.delete(NODES, victim)
        except NotFoundError:
            pass

    def hook(point):
        if pending:
            do_kill(pending.pop())

    if use_tpu:
        chaos_mod.plan(seed=seed, rates={"node.dead": 1.0})
        chaos_mod.set_node_hook(hook)

    def kill(victim):
        if use_tpu:
            pending.append(victim)
        else:
            do_kill(victim)

    def flush():
        if pending:
            do_kill(pending.pop())
    return kill, flush


@pytest.fixture(autouse=True)
def _chaos_teardown():
    """A fuzz trial that dies mid-TPU-world must not leak its injection
    plan into the next test (the plane is process-global)."""
    yield
    from kubernetes_tpu import chaos as chaos_mod
    chaos_mod.disable()


@pytest.fixture
def flight_replay():
    """Round-12 fuzz harness: record every TPU burst in replay mode so a
    parity failure dumps an attachable artifact and a green run ALSO
    proves each recorded burst re-derives bit-identically through the
    oracle referee (obs.flight.replay)."""
    from kubernetes_tpu.obs import flight
    flight.RECORDER.configure(mode="replay", capacity=64)
    flight.RECORDER.clear()
    yield flight.RECORDER
    flight.RECORDER.configure(mode="digest")
    flight.RECORDER.clear()


def finish_with_flight(recorder, tag: str, ok: bool, msg: str) -> None:
    """Close a fuzz run: on parity failure dump the flight ring (the
    attachable repro artifact) and fail with its path; on success replay
    every recorded burst through the oracle and require bit-identity."""
    import os
    import tempfile
    path = os.path.join(tempfile.gettempdir(), f"flight-{tag}.json")
    if not ok:
        recorder.dump(path)
        raise AssertionError(
            f"{msg}\n[flight recorder dumped "
            f"{len(recorder.records())} bursts to {path}]")
    errs = recorder.replay_all()
    if errs:
        recorder.dump(path)
        raise AssertionError(
            f"flight replay divergence (dumped to {path}): {errs[:4]}")


class TestMixedWorkloadShellFuzz:
    """Differential soak at the SHELL level: randomized clusters and mixed
    pod classes (plain, node-selector, tolerations, hostname anti-affinity,
    zone affinity, host ports, priorities) scheduled by the TPU burst path
    vs the pure-oracle serial loop — bindings must be identical, covering
    burst segmentation, uniform/ELIM/ban kernels, rotation replay, refusals,
    and the serial fallback together."""

    # wave_size=4 forces every burst segment of >= 8 pods across >= 2
    # pipelined wave boundaries (the new seam: device-chained lni/folds,
    # rotation-walk slicing, per-wave commit) — the same differential soak
    # must stay bit-identical with and without the pipeline
    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("seed", [11, 23, 47, 5, 31, 61])
    def test_bindings_identical(self, seed, wave_size, flight_replay,
                                chaos=False, mesh=None, profiles=False):
        import random
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.api.types import (
            Taint, Toleration, Affinity, PodAffinity, PodAntiAffinity,
            PodAffinityTerm, ContainerPort, NO_SCHEDULE,
            LABEL_ZONE_FAILURE_DOMAIN)
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(8, 24)
        zones = rng.choice([1, 2, 3])

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                labels = {LABEL_HOSTNAME: f"n{i}",
                          LABEL_ZONE_FAILURE_DOMAIN: f"z{i % zones}"}
                if i % 3 == 0:
                    labels["disk"] = "ssd"
                taints = (Taint(key="ded", value="x", effect=NO_SCHEDULE),) \
                    if i % 5 == 0 else ()
                s.create(NODES, Node(
                    name=f"n{i}", labels=labels, taints=taints,
                    allocatable={"cpu": rng.choice([2000, 4000]),
                                 "memory": 8 * GI, "pods": 110}))
            return s

        def make_pod(j):
            cls = rng.choice(["plain", "plain", "selector", "tolerate",
                              "anti", "aff", "port", "prio"])
            kw = {"labels": {"app": cls}}
            if cls == "selector":
                kw["node_selector"] = {"disk": "ssd"}
            elif cls == "tolerate":
                kw["tolerations"] = (Toleration(
                    key="ded", value="x", effect=NO_SCHEDULE),)
            elif cls == "anti":
                kw["labels"] = {"name": "t", "color": "green"}
                kw["affinity"] = Affinity(pod_anti_affinity=PodAntiAffinity(
                    required=(PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels=(("color", "green"),)),
                        topology_key=LABEL_HOSTNAME),)))
            elif cls == "aff":
                kw["labels"] = {"foo": ""}
                kw["affinity"] = Affinity(pod_affinity=PodAffinity(
                    required=(PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels=(("foo", ""),)),
                        topology_key=LABEL_ZONE_FAILURE_DOMAIN),)))
            elif cls == "port":
                ports = (ContainerPort(host_port=8080,
                                       container_port=8080),)
                kw["containers"] = (Container.make(
                    name="c", requests={"cpu": 100}, ports=ports),)
            elif cls == "prio":
                kw["priority"] = rng.randint(1, 3)
            if "containers" not in kw:
                kw["containers"] = (Container.make(
                    name="c", requests={"cpu": rng.choice([100, 300, 700]),
                                        "memory": GI}),)
            if profiles:
                kw["scheduler_name"] = rng.choice(
                    ["default-scheduler", "tenant-most", "tenant-rank"])
            return Pod(name=f"p{j}", **kw)

        def make_profiles():
            # round-19 multi-profile draws: three distinct weight rows,
            # one rank-aware — both worlds get the same set, so mixed-
            # tenant windows pin the weight-tensor gather against the
            # per-profile serial configs
            from kubernetes_tpu.profiles import (ProfileSet,
                                                 SchedulingProfile)
            return ProfileSet([
                SchedulingProfile("default-scheduler"),
                SchedulingProfile("tenant-most", weights=(
                    ("MostRequestedPriority", 2),
                    ("BalancedResourceAllocation", 1))),
                SchedulingProfile("tenant-rank", rank_aware=True,
                                  gang_weight=3),
            ])

        # one pod stream, two worlds
        rng_state = rng.getstate()
        bindings = []
        for use_tpu in (True, False):
            set_world_chaos(chaos, seed, use_tpu)
            rng.setstate(rng_state)
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100,
                              mesh=mesh if use_tpu else None,
                              profiles=make_profiles() if profiles
                              else None)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(rng.randint(25, 50)):
                s.create(PODS, make_pod(j))
            sched.pump()
            if use_tpu:
                while sched.schedule_burst(max_pods=32):
                    pass
            else:
                while sched.schedule_one(timeout=0.0):
                    pass
            sched.pump()
            bindings.append({p.key: p.node_name for p in s.list(PODS)[0]})
        diff = {k: (bindings[0].get(k), bindings[1].get(k))
                for k in bindings[0]
                if bindings[0].get(k) != bindings[1].get(k)}
        finish_with_flight(
            flight_replay, f"mixed-{seed}-{wave_size}", not diff,
            f"seed={seed}: {len(diff)} diverged: {sorted(diff.items())[:6]}")

    # round-19: the same differential fuzz with multi-profile draws —
    # every pod draws a scheduling profile (distinct weight vectors, one
    # rank-aware) so mixed-tenant windows exercise the per-pod weight-row
    # gather on every burst path vs the per-profile oracle configs
    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("seed", [11, 47, 31])
    def test_bindings_identical_profiles(self, seed, wave_size,
                                         flight_replay):
        self.test_bindings_identical(seed, wave_size, flight_replay,
                                     profiles=True)

    def test_bindings_identical_under_injection(self, flight_replay):
        """Round-13 acceptance: the same differential fuzz stays
        bit-identical with the fault plane injecting at every seam in the
        TPU world (device faults degrade bursts to the serial path, store
        faults retry under the wave token, native cores demote, watches
        drop and resync) — a fault costs throughput, never a decision."""
        self.test_bindings_identical(23, 4, flight_replay, chaos=True)

    # round-15: the identical differential fuzz with the TPU world's node
    # axis sharded over the conftest 8-device mesh — rotation, spread,
    # uniform/ELIM, refusals and the serial fallback all run SHARDED (the
    # non-mesh variants on the same seeds pin single-device vs oracle, so
    # mesh-vs-oracle here transitively pins mesh vs the single-device
    # fused kernel referee on the same decision stream)
    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("seed", [11, 47, 61])
    def test_bindings_identical_sharded(self, seed, wave_size,
                                        flight_replay):
        from kubernetes_tpu.parallel import sharding as S
        self.test_bindings_identical(seed, wave_size, flight_replay,
                                     mesh=S.make_mesh(8))

    # round-14: nodes DIE on a seeded schedule while pods keep arriving —
    # mid-burst through the node.dead seam in the TPU world, at the round
    # boundary in the serial world (see node_churn_driver); bindings incl.
    # pods stranded on dead nodes must stay bit-identical
    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("seed", [13, 37, 53])
    def test_bindings_identical_under_node_churn(self, seed, wave_size,
                                                 flight_replay):
        import random
        from kubernetes_tpu import chaos as chaos_mod
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.utils.clock import FakeClock
        from kubernetes_tpu.api.types import (
            Taint, Toleration, ContainerPort, NO_SCHEDULE,
            LABEL_ZONE_FAILURE_DOMAIN)
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(8, 16)
        zones = rng.choice([2, 3])

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                labels = {LABEL_HOSTNAME: f"n{i}",
                          LABEL_ZONE_FAILURE_DOMAIN: f"z{i % zones}"}
                if i % 3 == 0:
                    labels["disk"] = "ssd"
                taints = (Taint(key="ded", value="x", effect=NO_SCHEDULE),) \
                    if i % 5 == 0 else ()
                s.create(NODES, Node(
                    name=f"n{i}", labels=labels, taints=taints,
                    allocatable={"cpu": rng.choice([2000, 4000]),
                                 "memory": 8 * GI, "pods": 110}))
            return s

        def make_pod(j):
            cls = rng.choice(["plain", "plain", "selector", "tolerate",
                              "port", "prio"])
            kw = {"labels": {"app": cls}}
            if cls == "selector":
                kw["node_selector"] = {"disk": "ssd"}
            elif cls == "tolerate":
                kw["tolerations"] = (Toleration(
                    key="ded", value="x", effect=NO_SCHEDULE),)
            elif cls == "port":
                ports = (ContainerPort(host_port=8080,
                                       container_port=8080),)
                kw["containers"] = (Container.make(
                    name="c", requests={"cpu": 100}, ports=ports),)
            elif cls == "prio":
                kw["priority"] = rng.randint(1, 3)
            if "containers" not in kw:
                kw["containers"] = (Container.make(
                    name="c", requests={"cpu": rng.choice([100, 300, 700]),
                                        "memory": GI}),)
            return Pod(name=f"p{j}", **kw)

        kill_rounds = set(rng.sample(range(1, 6), 2))
        rng_state = rng.getstate()
        bindings = []
        for use_tpu in (True, False):
            rng.setstate(rng_state)
            clock = FakeClock(100.0)
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu, clock=clock,
                              percentage_of_nodes_to_score=100)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            kill, flush = node_churn_driver(use_tpu, s, seed)
            next_pod = 0
            try:
                for rnd in range(8):
                    if rnd in kill_rounds:
                        live = sorted(n.name for n in s.list(NODES)[0])
                        kill(rng.choice(live))
                    sched.pump()
                    if rnd < 5:
                        for _ in range(rng.randint(4, 8)):
                            s.create(PODS, make_pod(next_pod))
                            next_pod += 1
                        sched.pump()
                    if use_tpu:
                        while sched.schedule_burst(max_pods=16):
                            pass
                    else:
                        while sched.schedule_one(timeout=0.0):
                            pass
                    flush()
                    sched.pump()
                    clock.step(2.0)
            finally:
                chaos_mod.disable()
            bindings.append({p.key: p.node_name for p in s.list(PODS)[0]})
        diff = {k: (bindings[0].get(k), bindings[1].get(k))
                for k in set(bindings[0]) | set(bindings[1])
                if bindings[0].get(k) != bindings[1].get(k)}
        finish_with_flight(
            flight_replay, f"nodechurn-{seed}-{wave_size}", not diff,
            f"seed={seed}: {len(diff)} diverged: {sorted(diff.items())[:6]}")


class TestPreemptionPressureShellFuzz:
    """Capacity-starved clusters with mixed priorities: pods fail, preempt
    (device victim scan in the TPU world, oracle Preemptor in the other),
    nominate, evict, and retry through backoff — final bindings and
    nominations must match between the TPU shell and the oracle shell under
    an identical deterministic round structure."""

    # wave_size=3 pushes every 8-pod burst across wave boundaries so the
    # failed-tail handoff (waves -> pressure batch / serial preemption)
    # crosses the new seam too
    @pytest.mark.parametrize("wave_size", [None, 3])
    @pytest.mark.parametrize("seed", [3, 5, 17, 7, 29])
    def test_preemptive_convergence_identical(self, seed, wave_size,
                                              flight_replay, chaos=False,
                                              mesh=None):
        import random
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.utils.clock import FakeClock
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(3, 8)
        cap = rng.choice([1000, 2000])

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={LABEL_HOSTNAME: f"n{i}",
                            "failure-domain.beta.kubernetes.io/zone":
                            f"z{i % 2}"},
                    allocatable={"cpu": cap, "memory": 8 * GI, "pods": 110}))
            return s

        rng_state = rng.getstate()
        outs = []
        for use_tpu in (True, False):
            set_world_chaos(chaos, seed, use_tpu)
            rng.setstate(rng_state)
            clock = FakeClock(100.0)
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu, clock=clock,
                              percentage_of_nodes_to_score=100,
                              mesh=mesh if use_tpu else None)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(rng.randint(10, 25)):
                s.create(PODS, Pod(
                    name=f"p{j}", labels={"app": "x"},
                    priority=rng.choice([0, 0, 0, 5, 9]),
                    containers=(Container.make(name="c", requests={
                        "cpu": rng.choice([300, 500, 900])}),)))
            idle = 0
            for _round in range(60):
                sched.pump()
                before = sched.metrics.schedule_attempts["scheduled"]
                if use_tpu:
                    while sched.schedule_burst(max_pods=8):
                        pass
                else:
                    while sched.schedule_one(timeout=0.0):
                        pass
                sched.pump()
                idle = 0 if sched.metrics.schedule_attempts["scheduled"] \
                    > before else idle + 1
                if idle >= 8:
                    break
                clock.step(2.0)   # deterministic backoff expiry
            outs.append(sorted((p.key, p.node_name, p.nominated_node_name)
                               for p in s.list(PODS)[0]))
        finish_with_flight(flight_replay, f"pressure-{seed}-{wave_size}",
                           outs[0] == outs[1],
                           f"seed={seed}: {outs[0]} != {outs[1]}")

    def test_preemptive_convergence_under_injection(self, flight_replay):
        """Round-13 acceptance: preemption pressure (device victim scans,
        pressure batches, nominate/evict/backoff rounds) stays
        bit-identical under the fault plane — a faulted scan falls back to
        the oracle Preemptor, a refused pressure wave reruns serially."""
        self.test_preemptive_convergence_identical(17, 3, flight_replay,
                                                   chaos=True)

    # round-15: preemption pressure with the TPU world sharded — the
    # victim planes, ghost-load carry, and schedule-else-preempt scans run
    # under NamedSharding(mesh, P("nodes")) and must converge identically
    @pytest.mark.parametrize("wave_size", [None, 3])
    @pytest.mark.parametrize("seed", [3, 17])
    def test_preemptive_convergence_sharded(self, seed, wave_size,
                                            flight_replay):
        from kubernetes_tpu.parallel import sharding as S
        self.test_preemptive_convergence_identical(
            seed, wave_size, flight_replay, mesh=S.make_mesh(8))

    # round-14: nodes DIE under preemption pressure — mid-burst via the
    # node.dead seam in the TPU world (launch refusal + victim-table/
    # mirror invalidation), at the round boundary in the serial world;
    # bindings AND nominations (incl. pods stranded on or nominated to
    # dead nodes) must stay bit-identical
    @pytest.mark.parametrize("wave_size", [None, 3])
    @pytest.mark.parametrize("seed", [7, 19, 43])
    def test_preemptive_convergence_under_node_churn(self, seed, wave_size,
                                                     flight_replay):
        import random
        from kubernetes_tpu import chaos as chaos_mod
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.utils.clock import FakeClock
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(4, 8)
        cap = rng.choice([1000, 2000])

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={LABEL_HOSTNAME: f"n{i}",
                            "failure-domain.beta.kubernetes.io/zone":
                            f"z{i % 2}"},
                    allocatable={"cpu": cap, "memory": 8 * GI, "pods": 110}))
            return s

        kill_rounds = set(rng.sample(range(2, 10), 2))
        rng_state = rng.getstate()
        outs = []
        for use_tpu in (True, False):
            rng.setstate(rng_state)
            clock = FakeClock(100.0)
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu, clock=clock,
                              percentage_of_nodes_to_score=100)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(rng.randint(10, 20)):
                s.create(PODS, Pod(
                    name=f"p{j}", labels={"app": "x"},
                    priority=rng.choice([0, 0, 0, 5, 9]),
                    containers=(Container.make(name="c", requests={
                        "cpu": rng.choice([300, 500, 900])}),)))
            kill, flush = node_churn_driver(use_tpu, s, seed)
            idle = 0
            try:
                for _round in range(60):
                    if _round in kill_rounds:
                        live = sorted(n.name for n in s.list(NODES)[0])
                        if live:
                            kill(rng.choice(live))
                        # fresh arrivals at the kill round keep the queue
                        # non-empty, so the TPU world's kill lands
                        # MID-BURST (at the round's first launch), not at
                        # an idle boundary
                        for _k in range(rng.randint(2, 4)):
                            s.create(PODS, Pod(
                                name=f"r{_round}k{_k}", labels={"app": "x"},
                                priority=rng.choice([0, 0, 5, 9]),
                                containers=(Container.make(
                                    name="c", requests={"cpu": rng.choice(
                                        [300, 500, 900])}),)))
                    sched.pump()
                    before = sched.metrics.schedule_attempts["scheduled"]
                    if use_tpu:
                        while sched.schedule_burst(max_pods=8):
                            pass
                    else:
                        while sched.schedule_one(timeout=0.0):
                            pass
                    flush()
                    sched.pump()
                    idle = 0 if sched.metrics.schedule_attempts["scheduled"] \
                        > before else idle + 1
                    if idle >= 8 and _round >= max(kill_rounds):
                        break
                    clock.step(2.0)   # deterministic backoff expiry
            finally:
                chaos_mod.disable()
            outs.append(sorted((p.key, p.node_name, p.nominated_node_name)
                               for p in s.list(PODS)[0]))
        finish_with_flight(flight_replay, f"pressure-churn-{seed}-{wave_size}",
                           outs[0] == outs[1],
                           f"seed={seed}: {outs[0]} != {outs[1]}")

    # mid-burst churn: a bound pod is DELETED and a fresh pod created
    # between pressure scans — the round-9 persistent victim table must
    # invalidate exactly the touched rows (generation-keyed dirty rows) or
    # the next scan reads stale victim slots; the oracle world re-derives
    # from scratch, so any staleness shows up as a binding divergence
    @pytest.mark.parametrize("wave_size", [None, 3])
    @pytest.mark.parametrize("seed", [11, 23, 41])
    def test_mid_burst_churn_identical(self, seed, wave_size,
                                       flight_replay):
        import random
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.utils.clock import FakeClock
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(3, 8)
        cap = rng.choice([1000, 2000])

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={LABEL_HOSTNAME: f"n{i}",
                            "failure-domain.beta.kubernetes.io/zone":
                            f"z{i % 2}"},
                    allocatable={"cpu": cap, "memory": 8 * GI, "pods": 110}))
            return s

        rng_state = rng.getstate()
        outs = []
        for use_tpu in (True, False):
            rng.setstate(rng_state)
            clock = FakeClock(100.0)
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu, clock=clock,
                              percentage_of_nodes_to_score=100)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(rng.randint(10, 20)):
                s.create(PODS, Pod(
                    name=f"p{j}", labels={"app": "x"},
                    priority=rng.choice([0, 0, 5, 9]),
                    containers=(Container.make(name="c", requests={
                        "cpu": rng.choice([300, 500, 900])}),)))
            next_id = 1000
            idle = 0
            for _round in range(60):
                sched.pump()
                before = sched.metrics.schedule_attempts["scheduled"]
                if use_tpu:
                    while sched.schedule_burst(max_pods=8):
                        pass
                else:
                    while sched.schedule_one(timeout=0.0):
                        pass
                sched.pump()
                if _round % 3 == 2 and _round < 30:
                    # deterministic churn, identical in both worlds because
                    # bindings are (asserted) identical: delete the first
                    # bound pod, create a replacement with rng-drawn spec
                    bound = sorted(p.key for p in s.list(PODS)[0]
                                   if p.node_name)
                    if bound:
                        s.delete(PODS, bound[0])
                    s.create(PODS, Pod(
                        name=f"churn-{next_id}", labels={"app": "x"},
                        priority=rng.choice([0, 5, 9]),
                        containers=(Container.make(name="c", requests={
                            "cpu": rng.choice([300, 500, 900])}),)))
                    next_id += 1
                    sched.pump()
                idle = 0 if sched.metrics.schedule_attempts["scheduled"] \
                    > before else idle + 1
                if idle >= 8:
                    break
                clock.step(2.0)   # deterministic backoff expiry
            outs.append(sorted((p.key, p.node_name, p.nominated_node_name)
                               for p in s.list(PODS)[0]))
        finish_with_flight(flight_replay, f"churn-{seed}-{wave_size}",
                           outs[0] == outs[1],
                           f"seed={seed}: {outs[0]} != {outs[1]}")


class TestSpreadBurstParity:
    """Service-matched pods ride the generic scan with carried spread
    counts and per-cycle rotation orders; bindings must match the oracle
    including the zone blend and uneven-zone rotation."""

    # wave_size=4 drives the generic scan's carried spread counts and
    # rotation walk across commit-window boundaries of the single block
    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("n_nodes,zones,n_pods", [
        (7, 3, 20),     # uneven zones -> rotated orders in-burst
        (12, 2, 30),    # even zones -> stable axis order
        (5, 1, 40),     # deep stacking on few nodes
    ])
    def test_burst_matches_oracle(self, n_nodes, zones, n_pods, wave_size):
        from kubernetes_tpu.store.store import Store, PODS, NODES, SERVICES
        from kubernetes_tpu.scheduler import Scheduler
        from kubernetes_tpu.api.types import Service
        GI = 1024 ** 3

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={LABEL_HOSTNAME: f"n{i}",
                            "failure-domain.beta.kubernetes.io/zone":
                            f"z{i % zones}",
                            "failure-domain.beta.kubernetes.io/region": "r1"},
                    allocatable={"cpu": 4000, "memory": 32 * GI,
                                 "pods": 110}))
            s.create(SERVICES, Service(name="svc", selector={"app": "web"}))
            return s

        outs = []
        for use_tpu in (True, False):
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(n_pods):
                s.create(PODS, Pod(name=f"p{j}", labels={"app": "web"},
                                   containers=(Container.make(
                                       name="c", requests={"cpu": 300,
                                                           "memory": GI}),)))
            sched.pump()
            if use_tpu:
                while sched.schedule_burst(max_pods=16):
                    pass
            else:
                while sched.schedule_one(timeout=0.0):
                    pass
            sched.pump()
            outs.append(sorted((p.key, p.node_name)
                               for p in s.list(PODS)[0]))
        assert outs[0] == outs[1]

    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("seed", [13, 37, 71])
    def test_burst_matches_oracle_with_existing_pods(self, seed, wave_size,
                                                     chaos=False,
                                                     mesh=None):
        """The vectorized spread encode counts pre-existing pods through
        the columnar table: some existing pods match the Service selector
        (non-zero spread0 carried into the burst), some differ only in
        namespace or a second label — exactly the row filters the table
        encodes."""
        import random
        from kubernetes_tpu.store.store import Store, PODS, NODES, SERVICES
        from kubernetes_tpu.scheduler import Scheduler
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(6, 12)
        zones = rng.choice([2, 3])

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={LABEL_HOSTNAME: f"n{i}",
                            "failure-domain.beta.kubernetes.io/zone":
                            f"z{i % zones}",
                            "failure-domain.beta.kubernetes.io/region": "r1"},
                    allocatable={"cpu": 8000, "memory": 32 * GI,
                                 "pods": 110}))
            s.create(SERVICES, Service(name="svc",
                                       selector={"app": "web"}))
            for j in range(rng.randint(5, 15)):
                labels = rng.choice([{"app": "web"},
                                     {"app": "web", "tier": "x"},
                                     {"app": "other"}])
                ns = rng.choice(["default", "default", "team-a"])
                s.create(PODS, Pod(name=f"e{j}", namespace=ns,
                                   labels=dict(labels),
                                   node_name=f"n{j % n_nodes}",
                                   containers=(Container.make(
                                       name="c",
                                       requests={"cpu": 100}),)))
            return s

        rng_state = rng.getstate()
        outs = []
        for use_tpu in (True, False):
            set_world_chaos(chaos, seed, use_tpu)
            rng.setstate(rng_state)
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100,
                              mesh=mesh if use_tpu else None)
            if use_tpu and wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(rng.randint(15, 30)):
                s.create(PODS, Pod(name=f"p{j}", labels={"app": "web"},
                                   containers=(Container.make(
                                       name="c", requests={"cpu": 200,
                                                           "memory": GI}),)))
            sched.pump()
            if use_tpu:
                while sched.schedule_burst(max_pods=16):
                    pass
            else:
                while sched.schedule_one(timeout=0.0):
                    pass
            sched.pump()
            outs.append(sorted((p.key, p.node_name)
                               for p in s.list(PODS)[0]))
        assert outs[0] == outs[1]

    def test_spread_under_injection(self):
        """Round-13 acceptance: the carried-spread scan path (rotation
        orders, spread0, the generic packed block) stays bit-identical
        with the fault plane firing in the TPU world."""
        self.test_burst_matches_oracle_with_existing_pods(37, 4, chaos=True)

    # round-15: carried spread + uneven-zone rotation SHARDED — exactly
    # the two features the pre-round-15 mesh path refused
    # (burst-sharded-rotation / burst-sharded-spread, now deleted)
    @pytest.mark.parametrize("wave_size", [None, 4])
    @pytest.mark.parametrize("seed", [13, 71])
    def test_spread_sharded(self, seed, wave_size):
        from kubernetes_tpu.parallel import sharding as S
        self.test_burst_matches_oracle_with_existing_pods(
            seed, wave_size, mesh=S.make_mesh(8))


class TestMidBurstPreemptionConsistency:
    """A mid-burst failure's preemption (nomination + victim deletion)
    mutates state the remaining kernel decisions never saw — the shell must
    discard those decisions (and their device folds) and finish the burst
    serially. Regression: B used to bind onto the node A had just
    nominated, and A's preemption read a device matrix polluted by B's
    discarded fold."""

    def test_later_pod_respects_fresh_nomination(self):
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        GI = 1024 ** 3

        def build():
            s = Store(watch_log_size=65536)
            s.create(NODES, Node(name="Y", labels={LABEL_HOSTNAME: "Y"},
                                 allocatable={"cpu": 1000, "memory": 8 * GI,
                                              "pods": 110}))
            s.create(PODS, Pod(name="w", priority=1, node_name="Y",
                               containers=(Container.make(
                                   name="c", requests={"cpu": 400}),)))
            return s

        results = []
        for use_tpu in (True, False):
            s = build()
            sched = Scheduler(s, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100)
            sched.sync()
            s.create(PODS, Pod(name="A", priority=5, containers=(
                Container.make(name="c", requests={"cpu": 1000}),)))
            s.create(PODS, Pod(name="B", priority=0, containers=(
                Container.make(name="c", requests={"cpu": 300}),)))
            sched.pump()
            if use_tpu:
                sched.schedule_burst(max_pods=8)
            else:
                sched.schedule_one(timeout=0.0)
                sched.schedule_one(timeout=0.0)
            sched.pump()
            results.append(sorted(
                (p.key, p.node_name, p.nominated_node_name)
                for p in s.list(PODS)[0]))
        assert results[0] == results[1]
        # the high-priority pod nominated Y (victim evicted); the later
        # low-priority pod must NOT have taken the nominated space
        assert ("default/A", "", "Y") in results[0]
        assert ("default/B", "", "") in results[0]


class TestDeploymentThroughBurstPath:
    """VERDICT r03 #3 'done' criterion: a Deployment-driven scale-up flows
    store -> deployment controller -> RS controller -> scheduler TPU burst
    -> bindings, end to end."""

    def test_deployment_scale_up_binds_via_burst(self):
        from kubernetes_tpu.store.store import (
            Store, PODS, NODES, DEPLOYMENTS)
        from kubernetes_tpu.api.types import Deployment, PodTemplate
        from kubernetes_tpu.controllers.deployment import DeploymentController
        from kubernetes_tpu.controllers.replicaset import ReplicaSetController
        from kubernetes_tpu.scheduler import Scheduler
        GI = 1024 ** 3
        store = Store(watch_log_size=65536)
        for i in range(16):
            store.create(NODES, Node(
                name=f"n{i}",
                labels={"failure-domain.beta.kubernetes.io/zone":
                        f"z{i % 3}"},
                allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
        dc = DeploymentController(store)
        rsc = ReplicaSetController(store)
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        dc.sync(); rsc.sync(); sched.sync()
        store.create(DEPLOYMENTS, Deployment(
            name="web", replicas=48, selector=LabelSelector(
                match_labels=(("app", "web"),)),
            template=PodTemplate(
                labels={"app": "web"},
                containers=(Container.make(
                    name="c", requests={"cpu": 100,
                                        "memory": GI}),))))
        dc.pump(); rsc.pump()
        sched.pump()
        bound = 0
        while True:
            n = sched.schedule_burst(max_pods=64)
            if n == 0:
                break
            bound += n
        sched.pump()
        assert bound == 48
        pods = store.list(PODS)[0]
        assert len(pods) == 48 and all(p.node_name for p in pods)
        # identically-shaped admission-defaulted pods rode ONE uniform burst
        # class (spec-identical template stamps)
        assert len({p.node_name for p in pods}) == 16   # spread over nodes


class TestBurstFailurePrefixCommit:
    """The mid-burst-failure path (tpu_scheduler rewind + shell prefix
    commit): kernel decisions before the first failure are committed, the
    tail reruns serially — bindings and requeue behavior must be identical
    to the pure serial loop. Exercises both the uniform suffix case
    (saturation) and the generic-scan interleaved case (mixed pod sizes)."""

    def _run_world(self, build, mk_pods, use_tpu):
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        s = build()
        sched = Scheduler(s, use_tpu=use_tpu,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        for p in mk_pods():
            s.create(PODS, p)
        sched.pump()
        if use_tpu:
            while sched.schedule_burst(max_pods=64):
                pass
        else:
            while sched.schedule_one(timeout=0.0):
                pass
        sched.pump()
        return {p.key: p.node_name for p in s.list(PODS)[0]}

    @pytest.mark.parametrize("seed", [5, 19, 42])
    def test_uniform_saturation_suffix(self, seed):
        """Identical pods beyond cluster capacity: the uniform kernel emits
        a frozen-state failure suffix; prefix commits, suffix reruns."""
        import random
        from kubernetes_tpu.store.store import Store, NODES
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(4, 9)
        cap = rng.choice([1000, 2000])
        per = cap // 500          # pods per node
        n_pods = n_nodes * per + rng.randint(1, 6)   # overshoot

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={"failure-domain.beta.kubernetes.io/zone":
                            f"z{i % 2}"},
                    allocatable={"cpu": cap, "memory": 32 * GI,
                                 "pods": 110}))
            return s

        def mk_pods():
            return [Pod(name=f"p{j}", labels={"app": "x"},
                        containers=(Container.make(
                            name="c", requests={"cpu": 500,
                                                "memory": GI}),))
                    for j in range(n_pods)]

        tpu = self._run_world(build, mk_pods, True)
        ser = self._run_world(build, mk_pods, False)
        assert tpu == ser
        assert sum(1 for v in tpu.values() if not v) == \
            n_pods - n_nodes * per   # the overshoot tail is unschedulable

    @pytest.mark.parametrize("seed", [7, 23, 77])
    def test_generic_interleaved_failures(self, seed):
        """Heterogeneous sizes: big pods fail mid-burst while small ones
        succeed — the generic scan rewinds to the prefix, the shell reruns
        the tail serially (possibly preempting)."""
        import random
        from kubernetes_tpu.store.store import Store, NODES
        rng = random.Random(seed)
        GI = 1024 ** 3
        n_nodes = rng.randint(3, 7)

        def build():
            s = Store(watch_log_size=65536)
            for i in range(n_nodes):
                s.create(NODES, Node(
                    name=f"n{i}",
                    allocatable={"cpu": 2000, "memory": 32 * GI,
                                 "pods": 110}))
            return s

        def mk_pods():
            rng2 = random.Random(seed + 1)
            out = []
            for j in range(rng2.randint(12, 30)):
                cpu = rng2.choice([100, 300, 1800, 2100])
                out.append(Pod(
                    name=f"p{j}", labels={"sz": str(cpu)},
                    priority=rng2.choice([0, 0, 2]),
                    containers=(Container.make(
                        name="c", requests={"cpu": cpu}),)))
            return out

        tpu = self._run_world(build, mk_pods, True)
        ser = self._run_world(build, mk_pods, False)
        assert tpu == ser


class TestDeviceFetchContract:
    """The fetch contract: every device->host synchronization is a full
    dispatch+readback round trip, so batched launches must
    fetch ONE packed result per wave regardless of how many kernel chunks
    they dispatch. Pinned via tpu_device_dispatch_total{op} /
    tpu_device_fetches_total{op} deltas — a per-chunk (or per-pod) fetch
    sneaking in fails here before it lands as a per-pod round trip."""

    def _pressure_world(self, n_nodes=4, victims_per_node=2):
        infos = {}
        names = []
        for i in range(n_nodes):
            node = Node(name=f"n{i}",
                        allocatable={"cpu": 2000, "memory": 8 * GI,
                                     "pods": 110})
            ni = NodeInfo(node)
            for v in range(victims_per_node):
                ni.add_pod(Pod(name=f"v{i}-{v}", priority=1,
                               node_name=node.name,
                               containers=(Container.make(
                                   name="c", requests={"cpu": 900}),)))
            infos[node.name] = ni
            names.append(node.name)
        return infos, names

    def test_pressure_burst_one_fetch_across_chunks(self):
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        infos, names = self._pressure_world()
        preemptors = [Pod(name=f"hi-{k}", priority=10,
                          containers=(Container.make(
                              name="c", requests={"cpu": 900}),))
                      for k in range(10)]
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        tpu.PRESSURE_B_CAP = 4      # force 3 launches in one wave
        d0 = DEVICE_DISPATCH.labels("pressure_batch").value
        f0 = DEVICE_FETCHES.labels("pressure_batch").value
        out = tpu.preempt_pressure_burst(preemptors, infos, names, [])
        assert out is not None and len(out) == 10
        assert DEVICE_DISPATCH.labels("pressure_batch").value - d0 == 3
        # 3 launches, ONE round trip: the chunk outputs ride one device_get
        assert DEVICE_FETCHES.labels("pressure_batch").value - f0 == 1

    def test_preempt_victim_scan_one_fetch(self):
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        from kubernetes_tpu.oracle import predicates as P
        infos, names = self._pressure_world()
        pod = Pod(name="hi", priority=10,
                  containers=(Container.make(
                      name="c", requests={"cpu": 900}),))
        err = FitError(pod, len(names),
                       {nm: [P.insufficient_resource("cpu")]
                        for nm in names})
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        d0 = DEVICE_DISPATCH.labels("preempt_scan").value
        f0 = DEVICE_FETCHES.labels("preempt_scan").value
        res = tpu.preempt(pod, infos, names, err, [])
        assert res is not None and res.node is not None
        assert DEVICE_DISPATCH.labels("preempt_scan").value - d0 == 1
        assert DEVICE_FETCHES.labels("preempt_scan").value - f0 == 1

    # -- round 10: EXACTLY one dispatch + one packed fetch per fused burst ----
    def _uniform_world(self, n_nodes=5):
        infos = {}
        names = []
        for i in range(n_nodes):
            node = Node(name=f"n{i}",
                        allocatable={"cpu": 4000, "memory": 32 * GI,
                                     "pods": 110})
            infos[node.name] = NodeInfo(node)
            names.append(node.name)
        return infos, names

    def test_uniform_burst_one_fetch_across_waves(self):
        """22 identical pods at wave_size=4: six commit waves all consume
        ONE fetched block from ONE dispatch — a per-wave fetch sneaking
        back in fails here before it lands as a 100ms-per-wave cliff."""
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        infos, names = self._uniform_world()
        pods = [Pod(name=f"p{k}", labels={"app": "x"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100}),))
                for k in range(22)]
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        tpu.wave_size = 4
        d0 = DEVICE_DISPATCH.labels("burst_uniform").value
        f0 = DEVICE_FETCHES.labels("burst_uniform").value
        committed = []
        hosts = tpu.schedule_burst(pods, infos, names,
                                   commit=lambda lo, hs:
                                   committed.append((lo, len(hs))) or True)
        assert hosts is not None and all(h is not None for h in hosts)
        assert len(committed) == 6    # wave-by-wave out of the one block
        assert DEVICE_DISPATCH.labels("burst_uniform").value - d0 == 1
        assert DEVICE_FETCHES.labels("burst_uniform").value - f0 == 1

    def test_scan_burst_one_fetch_even_on_failure(self):
        """Heterogeneous pods ride the generic scan; a mid-burst failure's
        prefix rewind reads the per-pod walk counters out of the SAME
        packed block — the failure path's second fetch is gone."""
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        infos, names = self._uniform_world(3)
        pods = []
        for k in range(9):
            cpu = 20000 if k == 4 else (100 if k % 2 else 300)
            pods.append(Pod(name=f"p{k}", labels={"sz": str(cpu)},
                            containers=(Container.make(
                                name="c", requests={"cpu": cpu}),)))
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        d0 = DEVICE_DISPATCH.labels("burst_scan").value
        f0 = DEVICE_FETCHES.labels("burst_scan").value
        hosts = tpu.schedule_burst(pods, infos, names)
        assert hosts is not None
        assert all(h is not None for h in hosts[:4])
        assert all(h is None for h in hosts[4:])   # undecided from failure
        assert DEVICE_DISPATCH.labels("burst_scan").value - d0 == 1
        assert DEVICE_FETCHES.labels("burst_scan").value - f0 == 1

    def test_mixed_profile_scan_burst_one_fetch(self):
        """Round 19: a window MIXING scheduling profiles rides the
        weight-tensor generic scan as ONE dispatch + ONE packed fetch —
        the per-pod weight-row gather happens in-kernel, never as extra
        device traffic."""
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        from kubernetes_tpu.profiles import ProfileSet, SchedulingProfile
        infos, names = self._uniform_world()
        pods = []
        for k in range(12):
            pods.append(Pod(
                name=f"p{k}",
                scheduler_name=["default-scheduler", "tenant-most"][k % 2],
                labels={"sz": str(k % 3)},
                containers=(Container.make(
                    name="c", requests={"cpu": [100, 300, 500][k % 3]}),)))
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        tpu.set_profiles(ProfileSet([
            SchedulingProfile("default-scheduler"),
            SchedulingProfile("tenant-most", weights=(
                ("MostRequestedPriority", 2),
                ("BalancedResourceAllocation", 1))),
        ]))
        d0 = DEVICE_DISPATCH.labels("burst_scan").value
        f0 = DEVICE_FETCHES.labels("burst_scan").value
        hosts = tpu.schedule_burst(pods, infos, names)
        assert hosts is not None and all(h is not None for h in hosts)
        assert DEVICE_DISPATCH.labels("burst_scan").value - d0 == 1
        assert DEVICE_FETCHES.labels("burst_scan").value - f0 == 1

    def test_mixed_profile_fused_window_one_fetch(self):
        """Round 19: a fused drain window mixing profiles ACROSS
        segments (a rank-aware gang + default singletons) stays ONE
        dispatch + ONE packed fetch — the gang zone-count carry and the
        tensor rows ride the launch."""
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        from kubernetes_tpu.profiles import ProfileSet, SchedulingProfile
        infos, names = self._uniform_world(6)
        gang = [Pod(name=f"g{k}", scheduler_name="tenant-rank",
                    labels={"g": "1"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100}),))
                for k in range(3)]
        singles = [Pod(name=f"s{k}",
                       containers=(Container.make(
                           name="c", requests={"cpu": 200}),))
                   for k in range(4)]
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        tpu.set_profiles(ProfileSet([
            SchedulingProfile("default-scheduler"),
            SchedulingProfile("tenant-rank", rank_aware=True,
                              gang_weight=3),
        ]))
        d0 = DEVICE_DISPATCH.labels("burst_fused").value
        f0 = DEVICE_FETCHES.labels("burst_fused").value
        res = tpu.schedule_burst_fused(
            [(singles[:2], False), (gang, True), (singles[2:], False)],
            infos, names)
        assert res is not None
        assert [seg["status"] for seg in res["segments"]] \
            == ["decided", "decided", "decided"]
        assert DEVICE_DISPATCH.labels("burst_fused").value - d0 == 1
        assert DEVICE_FETCHES.labels("burst_fused").value - f0 == 1

    def test_chunked_uniform_burst_one_fetch_per_window(self):
        """A uniform burst above `launch_cap` is a launch a window, one
        after the other: 64 pods at a cap of 16 are exactly 4 dispatches
        and 4 fetches (never one per wave or per pod), the commit windows
        arrive in order, each after its own launch's fetch, and the hosts
        are those of the same burst in one launch."""
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)

        def run_world(launch_cap):
            infos, names = self._uniform_world()
            tpu = TPUScheduler(percentage_of_nodes_to_score=100)
            tpu.launch_cap = launch_cap
            tpu.wave_size = 16           # commit windows = launch windows
            d0 = DEVICE_DISPATCH.labels("burst_uniform").value
            f0 = DEVICE_FETCHES.labels("burst_uniform").value
            windows = []
            hosts = tpu.schedule_burst(
                pods=[Pod(name=f"p{k}", labels={"app": "x"},
                          containers=(Container.make(
                              name="c", requests={"cpu": 100}),))
                      for k in range(64)],
                node_infos=infos, all_node_names=names,
                commit=lambda lo, hs: windows.append(
                    (lo, len(hs),
                     DEVICE_FETCHES.labels("burst_uniform").value - f0))
                or True)
            assert hosts is not None and all(h is not None for h in hosts)
            d = DEVICE_DISPATCH.labels("burst_uniform").value - d0
            f = DEVICE_FETCHES.labels("burst_uniform").value - f0
            return hosts, d, f, windows

        hosts, d, f, windows = run_world(16)
        assert d == 4 and f == 4, (d, f)   # 1 dispatch + 1 fetch / window
        # window k commits when k+1 launches have been fetched: no launch
        # runs ahead of the commit before it
        assert windows == [(0, 16, 1), (16, 16, 2), (32, 16, 3),
                           (48, 16, 4)]
        one_hosts, d1, f1, one_windows = run_world(None)
        assert d1 == 1 and f1 == 1
        assert [(lo, k) for lo, k, _f in one_windows] \
            == [(lo, k) for lo, k, _f in windows]
        assert hosts == one_hosts    # chunking changes launches, not bits

    @pytest.mark.parametrize("driver", ["uniform", "scan", "fused"])
    def test_burst_starts_no_thread(self, driver):
        """A burst through each driver fetches its block on the calling
        thread: no `tpu-fetch` worker, and no other thread, is left
        behind."""
        import threading
        from kubernetes_tpu.obs import flight
        infos, names = self._uniform_world()
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        before = set(threading.enumerate())
        pods = [Pod(name=f"p{k}", labels={"app": "x"},
                    containers=(Container.make(
                        name="c",
                        requests={"cpu": 100 if driver != "scan"
                                  else (100, 300)[k % 2]}),))
                for k in range(8)]
        if driver == "fused":
            assert tpu.schedule_burst_fused(
                [(pods[:4], False), (pods[4:], True)], infos, names) \
                is not None
        else:
            hosts = tpu.schedule_burst(pods, infos, names)
            assert hosts is not None and all(hosts)
        assert flight.RECORDER.records()[-1].kind == driver
        after = threading.enumerate()
        assert not [t for t in after if t.name.startswith("tpu-fetch")]
        assert set(after) <= before

    def test_fused_gang_burst_one_fetch(self):
        """A drain window containing gang segments — one decided, one
        REJECTED (rewound in the device carry) — plus singletons before
        and after is still exactly ONE dispatch and ONE packed fetch."""
        from kubernetes_tpu.core.tpu_scheduler import (BURST_SEGMENTS,
                                                       DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        from kubernetes_tpu.coscheduling.types import (LABEL_POD_GROUP,
                                                       PodGroup)
        from kubernetes_tpu.store.store import Store, PODS, NODES, PODGROUPS
        from kubernetes_tpu.scheduler import Scheduler
        store = Store(watch_log_size=65536)
        for i in range(4):
            store.create(NODES, Node(
                name=f"n{i}",
                allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        store.create(PODS, Pod(name="s0", containers=(Container.make(
            name="c", requests={"cpu": 100}),)))
        store.create(PODGROUPS, PodGroup(name="ok", min_member=3))
        for r in range(3):
            store.create(PODS, Pod(
                name=f"ok-{r}", labels={LABEL_POD_GROUP: "ok"},
                containers=(Container.make(
                    name="c", requests={"cpu": 200}),)))
        store.create(PODGROUPS, PodGroup(name="toobig", min_member=3))
        for r in range(3):
            store.create(PODS, Pod(
                name=f"toobig-{r}", labels={LABEL_POD_GROUP: "toobig"},
                containers=(Container.make(
                    name="c", requests={"cpu": 4500}),)))
        store.create(PODS, Pod(name="s1", containers=(Container.make(
            name="c", requests={"cpu": 100}),)))
        sched.pump()
        d0 = DEVICE_DISPATCH.labels("burst_fused").value
        f0 = DEVICE_FETCHES.labels("burst_fused").value
        g0 = BURST_SEGMENTS.labels("gang").value
        r0 = BURST_SEGMENTS.labels("run").value
        sched.schedule_burst(max_pods=64)
        sched.pump()
        assert DEVICE_DISPATCH.labels("burst_fused").value - d0 == 1
        assert DEVICE_FETCHES.labels("burst_fused").value - f0 == 1
        assert BURST_SEGMENTS.labels("gang").value - g0 == 2
        assert BURST_SEGMENTS.labels("run").value - r0 >= 1
        by_name = {p.name: p.node_name for p in store.list(PODS)[0]}
        assert by_name["s0"] and by_name["s1"]
        assert all(by_name[f"ok-{r}"] for r in range(3))
        # the rejected gang rewound in-scan: nothing bound, group parked
        assert not any(by_name[f"toobig-{r}"] for r in range(3))
