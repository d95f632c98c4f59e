"""Round-14 node-churn robustness plane: heartbeat leases, zone-aware
rate-limited eviction through the PDB-guarded eviction subresource, and
mid-burst node-death tolerance (stale-bind detection + requeue +
invalidation)."""
import threading

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container, Lease, LabelSelector, Node, NodeCondition, Pod,
    PodDisruptionBudget, Taint, Toleration, NO_EXECUTE, NO_SCHEDULE,
    TOLERATION_OP_EXISTS, LABEL_HOSTNAME, LABEL_ZONE_FAILURE_DOMAIN,
    node_lease_key,
)
from kubernetes_tpu.store.store import (
    Store, LEASES, NODES, PODS, PDBS, DisruptionBudgetError, NotFoundError,
)
from kubernetes_tpu.utils.clock import FakeClock

GI = 1024 ** 3


def make_node(name, zone=None, ready="True", cpu=4000):
    labels = {LABEL_HOSTNAME: name}
    if zone is not None:
        labels[LABEL_ZONE_FAILURE_DOMAIN] = zone
    return Node(name=name, labels=labels,
                allocatable={"cpu": cpu, "memory": 8 * GI, "pods": 110},
                conditions=(NodeCondition(type="Ready", status=ready),))


def bound_pod(name, node, labels=None, tolerations=(), ct=0.0):
    p = Pod(name=name, node_name=node, labels=labels or {},
            tolerations=tolerations,
            containers=(Container.make(name="c", requests={"cpu": 100}),))
    p.creation_timestamp = ct
    return p


def flip_ready(store, name, status):
    def mutate(n):
        n.conditions = (NodeCondition(type="Ready", status=status),)
        return n
    store.guaranteed_update(NODES, name, mutate)


# ---------------------------------------------------------------------------
# coordination Lease kind: apiserver + remote transport
# ---------------------------------------------------------------------------
class TestLeaseKind:
    def test_lease_serde_roundtrip(self):
        from kubernetes_tpu.api import serde
        lease = Lease(name="node-n0", holder="n0", acquire_time=1.0,
                      renew_time=2.0, lease_duration=40.0)
        d = serde.to_dict(lease)
        back = serde.from_dict(LEASES, d)
        assert back == lease

    def test_lease_kind_registered_and_leader_election_alias(self):
        from kubernetes_tpu.api import serde
        from kubernetes_tpu.utils import leader_election
        assert serde.KIND_TYPES[LEASES] is Lease
        # back-compat: the resourcelock import path is the same class
        assert leader_election.Lease is Lease

    def test_lease_served_over_http(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.store.remote import RemoteStore
        store = Store()
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url)
            remote.create(LEASES, Lease(name="node-w0", holder="w0",
                                        renew_time=5.0))
            got = remote.get(LEASES, "node-w0")
            assert got.holder == "w0" and got.renew_time == 5.0

            def renew(l):
                l.renew_time = 9.0
                return l
            remote.guaranteed_update(LEASES, "node-w0", renew)
            assert store.get(LEASES, "node-w0").renew_time == 9.0
            objs, _rv = remote.list(LEASES)
            assert [o.name for o in objs] == ["node-w0"]
            remote.delete(LEASES, "node-w0")
            with pytest.raises(NotFoundError):
                store.get(LEASES, "node-w0")


# ---------------------------------------------------------------------------
# heartbeat -> lease renewal -> health grading
# ---------------------------------------------------------------------------
class TestHeartbeatLeases:
    def test_heartbeat_renews_and_counts(self):
        from kubernetes_tpu.models.hollow import HollowKubelet, LEASE_RENEWS
        clock = FakeClock(100.0)
        store = Store()
        store.create(NODES, make_node("n0"))
        k = HollowKubelet(store, "n0", clock=clock)
        created0 = LEASE_RENEWS.labels("created").value
        renewed0 = LEASE_RENEWS.labels("renewed").value
        k.heartbeat()
        assert LEASE_RENEWS.labels("created").value == created0 + 1
        lease = store.get(LEASES, node_lease_key("n0"))
        assert lease.holder == "n0" and lease.renew_time == 100.0
        clock.step(10)
        k.heartbeat()
        assert LEASE_RENEWS.labels("renewed").value == renewed0 + 1
        assert store.get(LEASES, node_lease_key("n0")).renew_time == 110.0

    def test_monitor_grades_unknown_from_lease_staleness(self):
        from kubernetes_tpu.models.hollow import HollowKubelet
        from kubernetes_tpu.controllers.nodelifecycle import (
            NodeLifecycleController, TAINT_UNREACHABLE)
        clock = FakeClock(100.0)
        store = Store()
        for n in ("n0", "n1"):
            store.create(NODES, make_node(n))
        kubelets = {n: HollowKubelet(store, n, clock=clock)
                    for n in ("n0", "n1")}
        for k in kubelets.values():
            k.heartbeat()
        c = NodeLifecycleController(store, clock=clock,
                                    node_monitor_grace=30.0)
        c.sync()
        # inside grace: nothing graded
        clock.step(20)
        kubelets["n1"].heartbeat()
        c.pump()
        assert all(cond.status == "True"
                   for n in store.list(NODES)[0] for cond in n.conditions
                   if cond.type == "Ready")
        # n0 silent past the grace period -> Unknown + unreachable taints
        clock.step(20)
        kubelets["n1"].heartbeat()
        c.pump()
        n0 = store.get(NODES, "n0")
        assert any(cond.type == "Ready" and cond.status == "Unknown"
                   for cond in n0.conditions)
        assert {t.key for t in n0.taints} == {TAINT_UNREACHABLE}
        # the healthy heartbeater stays Ready
        assert store.get(NODES, "n1").taints == ()

    def test_clock_jump_chaos_covers_heartbeat(self):
        """A chaos clock jump swallows the grace period between two
        heartbeats: the lease goes stale through no fault of the kubelet
        and the monitor grades Unknown — the heartbeat plane is covered
        by the clock.jump seam like every other lease consumer."""
        from kubernetes_tpu import chaos
        from kubernetes_tpu.models.hollow import HollowKubelet
        from kubernetes_tpu.controllers.nodelifecycle import (
            NodeLifecycleController)
        base = FakeClock(100.0)
        chaos.plan(seed=7, rates={"clock.jump": 1.0},
                   jump_range=(50.0, 50.0))
        try:
            clock = chaos.wrap_clock(base)
            store = Store()
            store.create(NODES, make_node("n0"))
            k = HollowKubelet(store, "n0", clock=base)   # kubelet: real time
            k.heartbeat()
            c = NodeLifecycleController(store, clock=clock,
                                        node_monitor_grace=30.0)
            c.sync()
            c.pump()   # monitor's now() jumped +50s past the renew
            n0 = store.get(NODES, "n0")
            assert any(cond.type == "Ready" and cond.status == "Unknown"
                       for cond in n0.conditions)
        finally:
            chaos.disable()


# ---------------------------------------------------------------------------
# tolerationSeconds semantics (pinned table)
# ---------------------------------------------------------------------------
class TestEvictionDeadlineTable:
    TAINT = Taint(key="node.kubernetes.io/unreachable", effect=NO_EXECUTE)

    def _deadline(self, tolerations, since=100.0):
        from kubernetes_tpu.controllers.nodelifecycle import (
            NodeLifecycleController)
        pod = Pod(name="p", tolerations=tolerations)
        return NodeLifecycleController._eviction_deadline(
            pod, [self.TAINT], {self.TAINT.key: since})

    def test_no_matching_toleration_evicts_immediately(self):
        assert self._deadline(()) == 0.0

    def test_matching_without_seconds_never_evicts(self):
        tol = Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                         effect=NO_EXECUTE)
        assert self._deadline((tol,)) is None

    def test_zero_seconds_is_immediate(self):
        tol = Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                         effect=NO_EXECUTE, toleration_seconds=0)
        assert self._deadline((tol,)) == 100.0   # since + 0

    def test_negative_seconds_clamps_to_zero(self):
        tol = Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                         effect=NO_EXECUTE, toleration_seconds=-30)
        assert self._deadline((tol,)) == 100.0   # clamped, not since - 30

    def test_positive_seconds_offsets_since(self):
        tol = Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                         effect=NO_EXECUTE, toleration_seconds=7)
        assert self._deadline((tol,)) == 107.0

    def test_min_across_matching_tolerations(self):
        tols = (Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                           effect=NO_EXECUTE, toleration_seconds=30),
                Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                           effect=NO_EXECUTE, toleration_seconds=5))
        assert self._deadline(tols) == 105.0

    def test_must_tolerate_every_noexecute_taint(self):
        from kubernetes_tpu.controllers.nodelifecycle import (
            NodeLifecycleController)
        other = Taint(key="node.kubernetes.io/not-ready", effect=NO_EXECUTE)
        tol = Toleration(key=self.TAINT.key, op=TOLERATION_OP_EXISTS,
                         effect=NO_EXECUTE)
        pod = Pod(name="p", tolerations=(tol,))
        assert NodeLifecycleController._eviction_deadline(
            pod, [self.TAINT, other],
            {self.TAINT.key: 100.0, other.key: 100.0}) == 0.0


# ---------------------------------------------------------------------------
# zone-aware rate-limited eviction
# ---------------------------------------------------------------------------
class TestZonePacedEviction:
    def _controller(self, store, clock, **kw):
        from kubernetes_tpu.controllers.nodelifecycle import (
            NodeLifecycleController)
        kw.setdefault("eviction_rate", 0.5)           # 1 eviction / 2s
        kw.setdefault("secondary_eviction_rate", 0.1)  # 1 eviction / 10s
        return NodeLifecycleController(store, clock=clock, **kw)

    def test_normal_zone_paces_at_primary_rate(self):
        clock = FakeClock(1000.0)
        store = Store()
        # zone z0: 1 of 4 nodes dead -> Normal (0.25 < 0.55)
        for i in range(4):
            store.create(NODES, make_node(f"n{i}", zone="z0"))
        for j in range(3):
            store.create(PODS, bound_pod(f"p{j}", "n0", ct=float(j)))
        c = self._controller(store, clock)
        c.sync()
        flip_ready(store, "n0", "False")
        c.pump()
        from kubernetes_tpu.controllers.nodelifecycle import STATE_NORMAL
        assert c._zone_state["z0"] == STATE_NORMAL
        # burst token covers exactly one eviction; the rest are paced
        assert len(store.list(PODS)[0]) == 2
        c.pump()
        assert len(store.list(PODS)[0]) == 2   # no time passed, no token
        clock.step(2.0)
        c.pump()
        assert len(store.list(PODS)[0]) == 1
        clock.step(2.0)
        c.pump()
        assert len(store.list(PODS)[0]) == 0

    def test_partial_zone_drops_to_secondary_rate(self):
        clock = FakeClock(1000.0)
        store = Store()
        # zone z0 healthy; zone z1: 2 of 3 dead -> PartialDisruption
        store.create(NODES, make_node("h0", zone="z0"))
        for i in range(3):
            store.create(NODES, make_node(f"u{i}", zone="z1"))
        for j in range(2):
            store.create(PODS, bound_pod(f"p{j}", "u0", ct=float(j)))
        c = self._controller(store, clock)
        c.sync()
        flip_ready(store, "u0", "False")
        flip_ready(store, "u1", "Unknown")
        c.pump()
        from kubernetes_tpu.controllers.nodelifecycle import STATE_PARTIAL
        assert c._zone_state["z1"] == STATE_PARTIAL
        assert len(store.list(PODS)[0]) == 1   # burst token only
        # primary-rate interval is NOT enough at the secondary rate
        clock.step(2.0)
        c.pump()
        assert len(store.list(PODS)[0]) == 1
        # secondary rate (0.1/s) releases the next token after 10s
        clock.step(8.0)
        c.pump()
        assert len(store.list(PODS)[0]) == 0

    def test_full_disruption_zone_evicts_nothing(self):
        clock = FakeClock(1000.0)
        store = Store()
        store.create(NODES, make_node("h0", zone="z0"))   # healthy zone
        for i in range(2):
            store.create(NODES, make_node(f"d{i}", zone="z1"))
        store.create(PODS, bound_pod("p0", "d0"))
        c = self._controller(store, clock)
        c.sync()
        flip_ready(store, "d0", "False")
        flip_ready(store, "d1", "Unknown")
        c.pump()
        from kubernetes_tpu.controllers.nodelifecycle import STATE_FULL
        assert c._zone_state["z1"] == STATE_FULL
        for _ in range(5):
            clock.step(60.0)
            c.pump()
        # the pod is tainted-intolerant and long past due, but its zone is
        # fully disrupted: ZERO evictions
        assert {p.key for p in store.list(PODS)[0]} == {"default/p0"}
        # one node recovers -> zone leaves FullDisruption -> eviction flows
        flip_ready(store, "d1", "True")
        c.pump()
        assert store.list(PODS)[0] == []

    def test_no_eviction_while_budget_exhausted(self):
        clock = FakeClock(1000.0)
        store = Store()
        store.create(NODES, make_node("h0", zone="z0"))
        for i in range(3):
            store.create(NODES, make_node(f"n{i}", zone="z1"))
        store.create(PODS, bound_pod("w0", "n0", labels={"app": "web"}))
        store.create(PDBS, PodDisruptionBudget(
            name="web", selector=LabelSelector(match_labels=(("app", "web"),)),
            min_available=1, disruptions_allowed=0))
        c = self._controller(store, clock, eviction_rate=10.0)
        c.sync()
        flip_ready(store, "n0", "False")
        for _ in range(4):
            clock.step(30.0)
            c.pump()
        # due for eviction, tokens plentiful — but disruptionsAllowed == 0
        assert "default/w0" in {p.key for p in store.list(PODS)[0]}
        # the budget opens: the queued eviction lands on the next pump
        def open_budget(b):
            b.disruptions_allowed = 1
            return b
        store.guaranteed_update(PDBS, "default/web", open_budget)
        clock.step(1.0)
        c.pump()
        assert "default/w0" not in {p.key for p in store.list(PODS)[0]}

    def test_debug_section_exposes_zone_states_and_tokens(self):
        from kubernetes_tpu import obs
        clock = FakeClock(1000.0)
        store = Store()
        for i in range(2):
            store.create(NODES, make_node(f"n{i}", zone="z0"))
        c = self._controller(store, clock)
        c.sync()
        c.pump()
        snap = obs.debug_snapshot()
        assert "nodelifecycle" in snap
        zones = snap["nodelifecycle"]["zones"]
        assert zones["z0"]["state"] == "Normal"
        assert zones["z0"]["tokens"] is not None
        assert zones["z0"]["queued"] == 0


# ---------------------------------------------------------------------------
# eviction subresource: atomic PDB charge, 429 + Retry-After
# ---------------------------------------------------------------------------
class TestEvictionSubresource:
    def _cluster(self, store):
        store.create(NODES, make_node("n0"))
        for n in ("w0", "w1"):
            store.create(PODS, bound_pod(n, "n0", labels={"app": "web"}))
        store.create(PDBS, PodDisruptionBudget(
            name="web", selector=LabelSelector(match_labels=(("app", "web"),)),
            min_available=1, disruptions_allowed=1))

    def test_store_verb_charges_budget_atomically(self):
        store = Store()
        self._cluster(store)
        store.evict_pod("default/w0")
        assert store.get(PDBS, "default/web").disruptions_allowed == 0
        with pytest.raises(DisruptionBudgetError):
            store.evict_pod("default/w1")
        assert "default/w1" in {p.key for p in store.list(PODS)[0]}

    def test_concurrent_evictors_budget_of_one(self):
        """Two evictors race a budget of 1 through the live HTTP
        subresource: exactly one 201 and one 429 (+ Retry-After)."""
        import urllib.request
        import urllib.error
        from kubernetes_tpu.apiserver.server import APIServer
        store = Store()
        self._cluster(store)
        results = []
        lock = threading.Lock()

        def evict(url, key):
            req = urllib.request.Request(
                f"{url}/api/v1/pods/{key}/eviction", data=b"{}",
                method="POST", headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req) as resp:
                    with lock:
                        results.append((resp.status, None))
            except urllib.error.HTTPError as e:
                with lock:
                    results.append((e.code, e.headers.get("Retry-After")))
        with APIServer(store) as srv:
            ts = [threading.Thread(target=evict,
                                   args=(srv.url, f"default/w{i}"))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(5.0)
        codes = sorted(c for c, _ra in results)
        assert codes == [201, 429]
        retry_after = next(ra for c, ra in results if c == 429)
        assert retry_after is not None and int(retry_after) > 0
        # exactly one web pod survived; the budget reads exhausted
        left = [p for p in store.list(PODS)[0] if p.labels.get("app") == "web"]
        assert len(left) == 1
        assert store.get(PDBS, "default/web").disruptions_allowed == 0

    def test_remote_store_maps_429(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.store.remote import RemoteStore
        store = Store()
        self._cluster(store)
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url)
            gone = remote.evict_pod("default/w0")
            assert gone.name == "w0"
            with pytest.raises(DisruptionBudgetError) as ei:
                remote.evict_pod("default/w1")
            assert ei.value.retry_after > 0
            with pytest.raises(NotFoundError):
                remote.evict_pod("default/w0")

    def test_disruption_controller_reconciles_after_evictions(self):
        """The eviction charge and the controller recompute share the PDB
        status: after one eviction (2 healthy -> 1, minAvailable 1), the
        recompute re-derives disruptionsAllowed == 0 from pod state."""
        from kubernetes_tpu.controllers.disruption import DisruptionController
        store = Store()
        self._cluster(store)
        dc = DisruptionController(store)
        dc.sync()
        assert store.get(PDBS, "default/web").disruptions_allowed == 1
        store.evict_pod("default/w0")
        dc.pump()
        pdb = store.get(PDBS, "default/web")
        assert pdb.current_healthy == 1
        assert pdb.disruptions_allowed == 0


# ---------------------------------------------------------------------------
# podgc: NodeLost + recreated-pod ordering
# ---------------------------------------------------------------------------
class TestPodGCNodeLost:
    def test_orphans_force_deleted_with_nodelost_event(self):
        from kubernetes_tpu.controllers.podgc import PodGCController
        from kubernetes_tpu.store.store import EVENTS
        store = Store()
        store.create(NODES, make_node("n0"))
        store.create(PODS, bound_pod("a", "n0"))
        store.create(PODS, bound_pod("b", "ghost"))
        gc = PodGCController(store)
        gc.sync()
        store.delete(NODES, "n0")
        gc.pump()
        assert store.list(PODS)[0] == []
        reasons = {e.reason for e in store.list(EVENTS)[0]}
        assert "NodeLost" in reasons

    def test_recreated_pods_sort_by_creation_in_activeq(self):
        """node dies -> podgc force-deletes its pods (NodeLost) -> the
        workload recreates them -> they must pop from the activeQ in
        CREATION order (the PR 9 recovery-ordering contract extended to
        the churn path)."""
        from kubernetes_tpu.controllers.podgc import PodGCController
        from kubernetes_tpu.scheduler import Scheduler
        clock = FakeClock(50.0)
        store = Store()
        for i in range(2):
            store.create(NODES, make_node(f"n{i}"))
        for j in range(4):
            store.create(PODS, bound_pod(f"p{j}", "n0", ct=float(j)))
        gc = PodGCController(store)
        gc.sync()
        sched = Scheduler(store, use_tpu=False, clock=clock,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        store.delete(NODES, "n0")
        assert gc.pump() == 4
        # the "controller" recreates the lost pods (store insertion order
        # IS creation order, like any real workload controller's loop)
        for j in range(4):
            store.create(PODS, Pod(
                name=f"p{j}-r", labels={}, containers=(
                    Container.make(name="c", requests={"cpu": 100}),)))
        sched.pump()
        popped = []
        while True:
            pod = sched.queue.pop(timeout=0.0)
            if pod is None:
                break
            popped.append(pod.name)
        assert popped == [f"p{j}-r" for j in range(4)]


# ---------------------------------------------------------------------------
# NodeTree checkpoint/restore across membership changes
# ---------------------------------------------------------------------------
class TestNodeTreeChurnSafety:
    def _tree(self, spec):
        from kubernetes_tpu.cache.node_tree import NodeTree
        tree = NodeTree()
        for zone, names in spec.items():
            for n in names:
                tree.add_node(make_node(n, zone=zone))
        return tree

    def test_restore_survives_node_removal(self):
        tree = self._tree({"a": ["a0", "a1"], "b": ["b0", "b1", "b2"]})
        tree.list_names()          # advance into a post-enumeration state
        chk = tree.checkpoint()
        tree.list_names()
        tree.remove_node(make_node("b1", zone="b"))
        tree.restore(chk)
        # a full enumeration still yields every live node exactly once
        names = tree.list_names()
        assert sorted(names) == ["a0", "a1", "b0", "b2"]

    def test_restore_survives_zone_removal_and_addition(self):
        tree = self._tree({"a": ["a0"], "b": ["b0", "b1"]})
        tree.list_names()
        chk = tree.checkpoint()
        # the whole zone 'a' vanishes and a NEW zone appears in between
        tree.remove_node(make_node("a0", zone="a"))
        tree.add_node(make_node("c0", zone="c"))
        tree.restore(chk)
        names = tree.list_names()
        assert sorted(names) == ["b0", "b1", "c0"]
        # repeated enumerations stay full and finite (no cursor wedge)
        for _ in range(3):
            assert sorted(tree.list_names()) == ["b0", "b1", "c0"]


# ---------------------------------------------------------------------------
# mid-burst node death: stale binds requeue, decisions match the oracle
# ---------------------------------------------------------------------------
class TestMidBurstNodeDeath:
    N_NODES = 6
    N_PODS = 18

    def _build(self):
        s = Store(watch_log_size=65536)
        for i in range(self.N_NODES):
            s.create(NODES, make_node(f"n{i}", zone=f"z{i % 2}"))
        return s

    def _run_world(self, use_tpu, kill_phase):
        """One world of the differential churn run: node n1 dies during
        round 0 — mid-burst through the node.dead seam in the TPU world
        (between dispatch and fetch, or between the fetch and the first
        wave commit), and at the round boundary in the serial world. The
        launch-refusal contract is what makes these equivalent: a death
        observed mid-launch commits NOTHING from that launch, so every
        decision in both worlds is made against the post-churn cluster.
        Returns final bindings."""
        from kubernetes_tpu import chaos
        from kubernetes_tpu.scheduler import Scheduler
        clock = FakeClock(100.0)
        s = self._build()
        sched = Scheduler(s, use_tpu=use_tpu, clock=clock,
                          percentage_of_nodes_to_score=100)
        if use_tpu:
            sched.algorithm.wave_size = 4
        sched.sync()
        for j in range(self.N_PODS):
            s.create(PODS, Pod(name=f"p{j}", labels={"app": "x"},
                               containers=(Container.make(
                                   name="c", requests={"cpu": 700}),)))
        killed = []

        def hook(point):
            if killed or point not in kill_phase:
                return
            killed.append("n1")
            try:
                s.delete(NODES, "n1")
            except NotFoundError:
                pass
        if use_tpu:
            chaos.plan(seed=3, rates={"node.dead": 1.0})
            chaos.set_node_hook(hook)
        try:
            for rnd in range(10):
                if not use_tpu and rnd == 0:
                    # the serial referee observes the same churn schedule
                    # at the equivalent decision boundary: before any of
                    # the round's decisions
                    s.delete(NODES, "n1")
                sched.pump()
                if use_tpu:
                    while sched.schedule_burst(max_pods=8):
                        pass
                else:
                    while sched.schedule_one(timeout=0.0):
                        pass
                if use_tpu and not killed:
                    # no seam crossing this round (idle): apply directly
                    hook(next(iter(kill_phase)))
                sched.pump()
                clock.step(2.0)
        finally:
            chaos.disable()
        return {p.key: p.node_name for p in s.list(PODS)[0]}

    @pytest.mark.parametrize("kill_phase", [
        ("dispatch-fetch",), ("fetch-commit",)])
    def test_stale_binds_requeue_and_match_oracle(self, kill_phase):
        from kubernetes_tpu.scheduler import STALE_BINDS
        stale0 = STALE_BINDS.value
        tpu = self._run_world(True, kill_phase)
        # the kill fired mid-burst: decisions in flight targeted the
        # vanished node and the whole launch was refused
        assert STALE_BINDS.value > stale0
        oracle = self._run_world(False, ())
        # nothing is ever bound to the dead node, everything else lands
        assert all(v and v != "n1" for v in tpu.values())
        diff = {k: (tpu.get(k), oracle.get(k)) for k in set(tpu) | set(oracle)
                if tpu.get(k) != oracle.get(k)}
        assert not diff, f"churn divergence: {sorted(diff.items())[:6]}"

    def test_whole_launch_refused_between_fetch_and_commit(self):
        """Kill a node between the packed fetch and the first wave commit:
        the launch refuses WHOLE — zero decisions from the pre-churn block
        commit, the stale decisions count, and every pod replans against
        the post-churn world in creation order."""
        from kubernetes_tpu import chaos
        from kubernetes_tpu.scheduler import Scheduler, STALE_BINDS
        clock = FakeClock(100.0)
        s = self._build()
        sched = Scheduler(s, use_tpu=True, clock=clock,
                          percentage_of_nodes_to_score=100)
        sched.algorithm.wave_size = 4
        sched.sync()
        # big pods: one per node, so some decision targets n1's row
        for j in range(6):
            s.create(PODS, Pod(name=f"p{j}", labels={"app": "x"},
                               containers=(Container.make(
                                   name="c", requests={"cpu": 3000}),)))
        sched.pump()

        def hook(point):
            if point == "fetch-commit" and s.contains(NODES, "n1"):
                s.delete(NODES, "n1")
        chaos.plan(seed=5, rates={"node.dead": 1.0})
        chaos.set_node_hook(hook)
        stale0 = STALE_BINDS.value
        try:
            sched.schedule_burst(max_pods=8)
        finally:
            chaos.disable()
        assert STALE_BINDS.value > stale0
        # the 5 live nodes fill immediately (the replanned launch), the
        # overflow pod is pending — and n1 never received a bind
        final = {p.key: p.node_name for p in s.list(PODS)[0]}
        assert sum(1 for v in final.values() if v) == 5   # 5 live nodes
        assert all(v != "n1" for v in final.values() if v)

    def test_stale_wave_requeues_with_backoff_in_creation_order(self):
        """Kill a node AFTER the launch-level stale scan (the pre-bind
        seam inside the first wave's commit): the per-wave stale filter
        fails exactly the decisions targeting the dead node NotFound-style
        and re-queues them with backoff; the burst driver aborts the rest
        of the block and replans it post-churn."""
        from kubernetes_tpu import chaos
        from kubernetes_tpu.scheduler import Scheduler, STALE_BINDS
        clock = FakeClock(100.0)
        s = self._build()
        sched = Scheduler(s, use_tpu=True, clock=clock,
                          percentage_of_nodes_to_score=100)
        sched.algorithm.wave_size = 4
        sched.sync()
        # big pods: one per node, so several decisions target n1's row
        for j in range(6):
            s.create(PODS, Pod(name=f"p{j}", labels={"app": "x"},
                               containers=(Container.make(
                                   name="c", requests={"cpu": 3000}),)))
        sched.pump()

        def hook(point):
            if point == "pre-bind" and s.contains(NODES, "n1"):
                s.delete(NODES, "n1")
        chaos.plan(seed=5, rates={"node.dead": 1.0})
        chaos.set_node_hook(hook)
        stale0 = STALE_BINDS.value
        try:
            sched.schedule_burst(max_pods=8)
        finally:
            chaos.disable()
        assert STALE_BINDS.value > stale0
        # the stale pod(s) are in backoff, not lost, and not bound to n1
        bound = {p.key: p.node_name for p in s.list(PODS)[0] if p.node_name}
        assert all(v != "n1" for v in bound.values())
        stale_keys = [p.key for p in s.list(PODS)[0] if not p.node_name]
        assert stale_keys
        # backoff expires -> they reschedule onto live nodes, in creation
        # order (queue pop order for equal priorities)
        clock.step(15.0)
        sched.pump()
        for _ in range(5):
            sched.schedule_burst(max_pods=8)
            sched.pump()
            clock.step(5.0)
        final = {p.key: p.node_name for p in s.list(PODS)[0]}
        assert sum(1 for v in final.values() if v) == 5   # 5 live nodes
        assert all(v != "n1" for v in final.values() if v)


# ---------------------------------------------------------------------------
# obs: eager registration
# ---------------------------------------------------------------------------
class TestChurnObsEagerRegistration:
    def test_families_render_without_activity(self):
        from kubernetes_tpu import obs
        # import the owners so registration side effects run
        import kubernetes_tpu.models.hollow      # noqa: F401
        import kubernetes_tpu.controllers.nodelifecycle   # noqa: F401
        import kubernetes_tpu.scheduler          # noqa: F401
        import kubernetes_tpu.store.store        # noqa: F401
        text = obs.render_global()
        for family in ("node_lease_renew_total", "zone_disruption_state",
                       "evictions_total", "stale_bind_requeues_total"):
            assert f"# HELP {family} " in text, family


class TestBatchSerialKeys:
    """The rotation-row cache and the device mirror's key name a NodeBatch
    by the serial its encoder gave it, not by its address: CPython hands a
    freed object's address to the next one of its size."""

    def _world(self, blocks: bool):
        from kubernetes_tpu.cache.node_info import NodeInfo
        from kubernetes_tpu.cache.node_tree import NodeTree
        infos, tree = {}, NodeTree()
        for i in range(7):       # zones of 3 / 2 / 2, striped or in blocks
            zone = (0, 0, 0, 1, 1, 2, 2)[i] if blocks else i % 3
            node = Node(name=f"n{i}",
                        labels={LABEL_ZONE_FAILURE_DOMAIN: f"z{zone}"},
                        allocatable={"cpu": 4000, "memory": 1 << 30,
                                     "pods": 110})
            infos[node.name] = NodeInfo(node)
            tree.add_node(node)
        return infos, tree

    def test_released_batch_never_lends_its_rotation_rows(self):
        """World A's rows are cached; a batch of another cluster follows
        and goes unused by the rotation; then world B (A's names, other
        zones) is encoded, perhaps at A's address. Its rows are those a
        scheduler that never saw A makes."""
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        infos_b, tree_b = self._world(blocks=True)
        ref = TPUScheduler(percentage_of_nodes_to_score=100,
                           node_tree=tree_b)
        want, want_seq = ref._generic_rotation(
            ref.encoder.encode(infos_b, sorted(infos_b)), 8)
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        serials = set()
        for _ in range(40):
            infos_a, tpu.node_tree = self._world(blocks=False)
            b = tpu.encoder.encode(infos_a, sorted(infos_a))
            stale, _seq = tpu._generic_rotation(b, 8)
            assert not np.array_equal(stale, want)
            serials.add(b.serial)
            del b
            other = {"m0": infos_a["n0"]}
            serials.add(tpu.encoder.encode(other, ["m0"]).serial)
            tpu.node_tree = tree_b
            b = tpu.encoder.encode(infos_b, sorted(infos_b))
            serials.add(b.serial)
            got, got_seq = tpu._generic_rotation(b, 8)
            assert np.array_equal(got, want)
            assert np.array_equal(got_seq, want_seq)
            tpu._node_arrays(b)
            assert tpu._dev_key[-1] == b.serial
            del b
            serials.add(tpu.encoder.encode(other, ["m0"]).serial)
        assert len(serials) == 160              # never one serial twice
