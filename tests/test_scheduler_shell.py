"""Integration tests for the scheduler shell — in-process control-plane-lite
(store + informers) driving real scheduling, the analog of
test/integration/scheduler/ (no kubelet: assertions on spec.nodeName).
"""
import random

import pytest

from kubernetes_tpu.api.types import (
    Affinity, Container, ContainerPort, LabelSelector, Node, Pod,
    PodAffinityTerm, PodAntiAffinity, ReplicaSet, Service, VolumeSource,
    get_container_ports, has_pod_affinity_terms,
)
from kubernetes_tpu.api.quantity import requests
from kubernetes_tpu.coscheduling.types import LABEL_POD_GROUP
from kubernetes_tpu.ops.pod_rows import pod_class_signature
from kubernetes_tpu.oracle.priorities import (
    get_selectors, spread_group_key)
from kubernetes_tpu.oracle.selector_index import SelectorIndex
from kubernetes_tpu.scheduler import BURST_CLASS, Scheduler
from kubernetes_tpu.store.store import (
    Store, PODS, NODES, REPLICASETS, SERVICES,
)

GI = 1024 ** 3


def mknode(name, cpu=4000, mem=32 * GI, pods=110, **kw):
    return Node(name=name, allocatable={"cpu": cpu, "memory": mem, "pods": pods},
                labels={"kubernetes.io/hostname": name}, **kw)


def mkpod(name, cpu="100m", mem="500Mi", **kw):
    return Pod(name=name,
               containers=(Container.make(name="c", requests=requests(cpu=cpu, mem=mem)),),
               **kw)


@pytest.fixture(params=["oracle", "tpu"])
def make_sched(request):
    def _make(store, **kw):
        return Scheduler(store, use_tpu=(request.param == "tpu"),
                         percentage_of_nodes_to_score=100, **kw)
    return _make


class TestScheduleLoop:
    def test_schedules_all_pods(self, make_sched):
        store = Store()
        for i in range(5):
            store.create(NODES, mknode(f"n{i}"))
        sched = make_sched(store)
        sched.sync()
        for j in range(20):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        sched.pump()
        assert sched.metrics.schedule_attempts["scheduled"] == 20
        bound = [store.get(PODS, f"default/p{j}").node_name for j in range(20)]
        assert all(bound)
        # spread across nodes (LeastRequested + tie round-robin)
        assert len(set(bound)) == 5

    def test_unschedulable_then_node_arrives(self, make_sched):
        from kubernetes_tpu.utils.clock import FakeClock
        clock = FakeClock()
        store = Store()
        store.create(NODES, mknode("small", cpu=100, pods=1))
        sched = make_sched(store, clock=clock)
        sched.sync()
        store.create(PODS, mkpod("big", cpu="2"))
        sched.pump()
        assert sched.schedule_one(timeout=0.0)
        assert sched.metrics.schedule_attempts["unschedulable"] == 1
        assert sched.queue.num_pending() == 1
        # a big node appears -> queue wakes; step past the 1s retry backoff
        store.create(NODES, mknode("big-node"))
        sched.pump()
        clock.step(1.1)
        scheduled = False
        for _ in range(10):
            if sched.schedule_one(timeout=0.0):
                if store.get(PODS, "default/big").node_name:
                    scheduled = True
                    break
        assert scheduled
        assert store.get(PODS, "default/big").node_name == "big-node"

    def test_multi_scheduler_names(self, make_sched):
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = make_sched(store)
        sched.sync()
        store.create(PODS, mkpod("mine"))
        store.create(PODS, mkpod("other", scheduler_name="custom-scheduler"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        sched.pump()
        assert store.get(PODS, "default/mine").node_name == "n0"
        assert store.get(PODS, "default/other").node_name == ""

    def test_deleted_pending_pod_is_skipped(self, make_sched):
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = make_sched(store)
        sched.sync()
        store.create(PODS, mkpod("gone"))
        sched.pump()
        store.delete(PODS, "default/gone")
        sched.pump()
        assert not sched.schedule_one(timeout=0.0)
        assert sched.metrics.schedule_attempts["scheduled"] == 0


class TestBurstMode:
    def test_burst_binds_everything(self):
        store = Store()
        for i in range(8):
            store.create(NODES, mknode(f"n{i}"))
        sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=100)
        sched.sync()
        for j in range(50):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        total = 0
        while True:
            n = sched.schedule_burst(max_pods=32)
            if n == 0:
                break
            total += n
        sched.pump()
        assert total == 50
        assert all(store.get(PODS, f"default/p{j}").node_name for j in range(50))
        # cache confirmed every binding via the watch
        assert sched.cache.pod_count() == 50

    def test_burst_matches_serial_decisions(self):
        def run(mode):
            store = Store()
            for i in range(6):
                store.create(NODES, mknode(f"n{i}", cpu=2000))
            sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=100)
            sched.sync()
            for j in range(30):
                store.create(PODS, mkpod(f"p{j}", cpu="300m"))
            sched.pump()
            if mode == "burst":
                while sched.schedule_burst(max_pods=16):
                    pass
            else:
                while sched.schedule_one(timeout=0.0):
                    pass
            sched.pump()
            return [store.get(PODS, f"default/p{j}").node_name for j in range(30)]

        assert run("burst") == run("serial")


class TestPipelinedWaves:
    """The burst wave pipeline: wave k's host commit runs while wave k+1
    executes on the device; decisions, bindings, and the schedule_burst
    return value must be identical to the single-launch path."""

    def _mk(self, n_nodes=6, wave_size=4):
        store = Store()
        for i in range(n_nodes):
            store.create(NODES, mknode(f"n{i}"))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.algorithm.wave_size = wave_size
        sched.sync()
        return store, sched

    def test_multi_wave_burst_binds_everything(self):
        from kubernetes_tpu.core.tpu_scheduler import (BURST_WAVES,
                                                       DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        store, sched = self._mk()
        for j in range(22):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        waves0 = BURST_WAVES.labels("uniform").value
        disp0 = DEVICE_DISPATCH.labels("burst_uniform").value
        fetch0 = DEVICE_FETCHES.labels("burst_uniform").value
        n = sched.schedule_burst(max_pods=22)
        sched.pump()
        assert n == 22
        assert all(store.get(PODS, f"default/p{j}").node_name
                   for j in range(22))
        # fused burst contract (round 10): 22 pods at wave_size=4 -> ONE
        # dispatch, ONE packed fetch, and the commit consumes the fetched
        # block in 6 wave windows
        assert BURST_WAVES.labels("uniform").value - waves0 == 6
        assert DEVICE_DISPATCH.labels("burst_uniform").value - disp0 == 1
        assert DEVICE_FETCHES.labels("burst_uniform").value - fetch0 == 1

    def test_wave_decisions_match_single_launch(self):
        def run(wave_size):
            store = Store()
            for i in range(5):
                store.create(NODES, mknode(f"n{i}", cpu=2000))
            sched = Scheduler(store, use_tpu=True,
                              percentage_of_nodes_to_score=100)
            if wave_size:
                sched.algorithm.wave_size = wave_size
            sched.sync()
            for j in range(30):
                store.create(PODS, mkpod(f"p{j}", cpu="300m"))
            sched.pump()
            while sched.schedule_burst(max_pods=30):
                pass
            sched.pump()
            return [store.get(PODS, f"default/p{j}").node_name
                    for j in range(30)]

        assert run(3) == run(None)

    def test_wave_commit_failure_rewinds_and_reschedules(self):
        """A pod deleted between decision and commit makes its wave's
        commit short: the pipeline aborts, the in-flight wave's decisions
        are discarded, and the remainder reschedules against the forgotten
        state — everything still present ends up bound."""
        store, sched = self._mk()
        for j in range(12):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        # deleted from the store but NOT pumped: the queue still holds it,
        # so wave 0's batched bind write comes up short
        store.delete(PODS, "default/p1")
        n = sched.schedule_burst(max_pods=12)
        sched.pump()
        assert n == 11
        for j in range(12):
            if j == 1:
                continue
            assert store.get(PODS, f"default/p{j}").node_name, f"p{j}"
        # the vanished pod was forgotten, not leaked into the cache
        assert sched.cache.pod_count() == 11

    def test_return_value_ignores_concurrent_metric_observers(self):
        """pods-bound comes from _commit_burst's actual count, so another
        thread observing 'scheduled' mid-burst cannot skew it."""
        store, sched = self._mk()
        real_batch = sched.recorder.pod_events_batch

        def noisy_batch(events):
            # fires inside the burst commit window — exactly where a
            # concurrent observer would corrupt a metric-delta derivation
            sched.metrics.observe("scheduled", count=100)
            return real_batch(events)

        sched.recorder.pod_events_batch = noisy_batch
        for j in range(10):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        assert sched.schedule_burst(max_pods=10) == 10


class TestFailureObservability:
    """Reference: recordSchedulingFailure (scheduler.go:266) writes the
    PodScheduled=False condition + a FailedScheduling event; bind success
    emits Scheduled (scheduler.go:433); victims get Preempted (:325)."""

    def test_unschedulable_pod_gets_condition_and_event(self, make_sched):
        from kubernetes_tpu.api.types import (
            POD_SCHEDULED, CONDITION_FALSE, REASON_UNSCHEDULABLE)
        from kubernetes_tpu.store.store import EVENTS
        store = Store()
        store.create(NODES, mknode("small", cpu=100))
        sched = make_sched(store)
        sched.sync()
        store.create(PODS, mkpod("big", cpu="2"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        sched.pump()
        pod = store.get(PODS, "default/big")
        conds = [c for c in pod.conditions if c.type == POD_SCHEDULED]
        assert len(conds) == 1
        assert conds[0].status == CONDITION_FALSE
        assert conds[0].reason == REASON_UNSCHEDULABLE
        assert "0/1 nodes available" in conds[0].message
        events, _ = store.list(EVENTS)
        failed = [e for e in events if e.reason == "FailedScheduling"
                  and e.involved_key == "default/big"]
        assert failed and failed[0].type == "Warning"

    def test_repeat_failure_aggregates_event_count(self, make_sched):
        from kubernetes_tpu.store.store import EVENTS
        from kubernetes_tpu.utils.clock import FakeClock
        clock = FakeClock()
        store = Store()
        store.create(NODES, mknode("small", cpu=100))
        sched = make_sched(store, clock=clock)
        sched.sync()
        store.create(PODS, mkpod("big", cpu="2"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        # ride out the backoff, then fail again
        clock.step(11.0)
        sched.queue.move_all_to_active()
        while sched.schedule_one(timeout=0.0):
            pass
        events, _ = store.list(EVENTS)
        failed = [e for e in events if e.reason == "FailedScheduling"
                  and e.involved_key == "default/big"]
        assert len(failed) == 1
        assert failed[0].count == 2

    def test_bind_emits_scheduled_event(self, make_sched):
        from kubernetes_tpu.store.store import EVENTS
        store = Store()
        store.create(NODES, mknode("n1"))
        sched = make_sched(store)
        sched.sync()
        store.create(PODS, mkpod("p1"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        events, _ = store.list(EVENTS)
        sched_evs = [e for e in events if e.reason == "Scheduled"]
        assert len(sched_evs) == 1
        assert "default/p1" in sched_evs[0].message
        assert sched_evs[0].type == "Normal"

    def test_condition_cleared_pod_still_schedulable_later(self, make_sched):
        """The False condition is replaced by nothing on success (the
        scheduler never writes True — kubelet's job); binding must still
        work after a failure."""
        from kubernetes_tpu.utils.clock import FakeClock
        clock = FakeClock()
        store = Store()
        store.create(NODES, mknode("small", cpu=100, pods=1))
        sched = make_sched(store, clock=clock)
        sched.sync()
        store.create(PODS, mkpod("big", cpu="2"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        store.create(NODES, mknode("huge", cpu=8000))
        sched.pump()
        clock.step(1.1)   # ride out the retry backoff
        while sched.schedule_one(timeout=0.0):
            pass
        sched.pump()
        assert store.get(PODS, "default/big").node_name == "huge"


class TestPreemptedEvent:
    def test_victims_get_preempted_event(self):
        from kubernetes_tpu.store.store import EVENTS
        store = Store()
        store.create(NODES, mknode("n1", cpu=2000))
        sched = Scheduler(store, percentage_of_nodes_to_score=100)
        sched.sync()
        victim = mkpod("victim", cpu="2")
        store.create(PODS, victim)
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        sched.pump()
        assert store.get(PODS, "default/victim").node_name == "n1"
        pre = mkpod("pre", cpu="2")
        pre.priority = 100
        store.create(PODS, pre)
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        events, _ = store.list(EVENTS)
        preempted = [e for e in events if e.reason == "Preempted"]
        assert len(preempted) == 1
        assert preempted[0].involved_key == "default/victim"
        assert "default/pre" in preempted[0].message


class TestSelfInflictedUpdates:
    def test_condition_write_does_not_clear_backoff(self, make_sched):
        """The scheduler's own PodScheduled=False status write must not
        requeue the just-failed pod (reference isPodUpdated strips status,
        scheduling_queue.go:412); otherwise failures hot-loop with backoff
        permanently defeated."""
        from kubernetes_tpu.utils.clock import FakeClock
        clock = FakeClock()
        store = Store()
        store.create(NODES, mknode("small", cpu=100))
        sched = make_sched(store, clock=clock)
        sched.sync()
        store.create(PODS, mkpod("big", cpu="2"))
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        # deliver the scheduler's own condition/nomination writes
        sched.pump()
        # without stepping the clock, the pod must stay unschedulable:
        # a pop must NOT return it
        assert sched.queue.pop(timeout=0.0) is None
        assert sched.queue.num_pending() == 1


class TestBurstClassDecision:
    """A drain pass decides the burst class once per distinct class
    signature (`Scheduler._burst_classes`), and every later question about
    a pod's class in that pass reads that decision. The class only cuts a
    window into `_burst_segment` calls: every cut must fall where the
    per-pod walk (kept here as the reference) put it."""

    KINDS = ("plain", "plain-big", "svc-a", "svc-b", "rs", "affinity",
             "port", "volume")

    @staticmethod
    def _pod(name, kind):
        kw = {}
        if kind == "plain-big":
            return mkpod(name, cpu="300m")
        if kind in ("svc-a", "svc-b"):
            kw["labels"] = {"app": kind[-1]}
        elif kind == "rs":
            kw["labels"] = {"tier": "web"}
        elif kind == "affinity":
            term = PodAffinityTerm(
                label_selector=LabelSelector.from_dict({"app": "z"}),
                topology_key="kubernetes.io/hostname")
            kw["affinity"] = Affinity(
                pod_anti_affinity=PodAntiAffinity(required=(term,)))
        elif kind == "port":
            return Pod(name=name, containers=(Container.make(
                name="c", requests=requests(cpu="100m", mem="500Mi"),
                ports=(ContainerPort(host_port=8080, container_port=80),)),))
        elif kind == "volume":
            kw["volumes"] = (VolumeSource(name="v", pvc="data"),)
        return mkpod(name, **kw)

    @staticmethod
    def _cluster(n_nodes=4, services=("a", "b"), replicaset=True,
                 rows=True):
        store = Store()
        for i in range(n_nodes):
            store.create(NODES, mknode(f"n{i}", cpu=64000, pods=1000))
        for s in services:
            store.create(SERVICES, Service(name=f"svc-{s}",
                                           selector={"app": s}))
        if replicaset:
            store.create(REPLICASETS, ReplicaSet(
                name="rs", selector=LabelSelector.from_dict({"tier": "web"})))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        if not rows:
            # the shell without the row cache: signatures come from
            # TPUScheduler.class_signatures, not interned
            sched.pod_rows = sched.algorithm.pod_rows = None
        sched.sync()
        return store, sched

    @staticmethod
    def _record_cuts(sched):
        """Replace the two things a cut leads to with recorders: a burst
        segment's pods, a serial cycle's pod."""
        cuts = []
        sched._burst_segment = lambda pods, *_a, **_kw: cuts.append(
            ("burst", [p.name for p in pods])) or 0
        sched._process_one = lambda pod, cycle: cuts.append(
            ("serial", [pod.name])) or False
        return cuts

    @staticmethod
    def _reference_class(pod, services, replicasets):
        """`Scheduler._burst_class`, asked per pod: the signature for what
        bursts with spec-identical peers alone, one class for every pod
        whose only in-burst-dynamic feature is selector spread, one for
        the rest."""
        if has_pod_affinity_terms(pod) or get_container_ports(pod):
            return pod_class_signature(pod), None
        selectors = get_selectors(pod, services, replicasets)
        if selectors:
            return "spread", spread_group_key(pod.namespace, selectors)
        return "plain", None

    @staticmethod
    def _reference_cuts(pods, services, replicasets):
        """The per-pod walk the shell made before the per-pass decision:
        the class function on every pod, twice, compared by value. The
        scan's grouped carry takes a pod that nothing selects beside pods
        that Services select, so `plain` and `spread` do not part a
        segment; a signature's own class does."""
        carried = ("plain", "spread")

        def burst_class(pod):
            return TestBurstClassDecision._reference_class(
                pod, services, replicasets)[0]

        def together(a, b):
            return a == b or (a in carried and b in carried)

        cuts, i = [], 0
        while i < len(pods):
            if pods[i].volumes:
                cuts.append((i, i + 1, "serial"))
                i += 1
                continue
            seg_class = burst_class(pods[i])
            j = i
            while j < len(pods) and not pods[j].volumes \
                    and together(burst_class(pods[j]), seg_class):
                j += 1
            cuts.append((i, j, seg_class))
            i = j
        return cuts

    @pytest.mark.parametrize("rows", [True, False],
                             ids=["row-cache", "no-row-cache"])
    @pytest.mark.parametrize("seed", [3, 11, 2027])
    def test_cuts_equal_the_per_pod_walk(self, seed, rows):
        rng = random.Random(seed)
        store, sched = self._cluster(rows=rows)
        kinds = []
        while len(kinds) < 60:
            kinds += [rng.choice(self.KINDS)] * rng.randint(1, 6)
        for j, kind in enumerate(kinds):
            store.create(PODS, self._pod(f"p{j:03d}", kind))
        sched.pump()
        cuts = self._record_cuts(sched)
        window = []
        pop_burst = sched.queue.pop_burst
        sched.queue.pop_burst = lambda n: window.extend(pop_burst(n)) \
            or list(window)
        sched._burst_pass_planned(len(kinds))
        pods = [p for p, _c in window]
        assert len(pods) == len(kinds)
        services, replicasets = sched._services_fn(), sched._replicasets_fn()
        assert len(services) == 2 and len(replicasets) == 1
        ref = self._reference_cuts(pods, services, replicasets)
        assert len(ref) > 8       # the window really is cut many times
        # the (i, j, class) cuts of the pass, the class being the one the
        # per-pass decision gives the segment's first pod
        classes = sched._burst_classes(pods)
        names = [p.name for p in pods]
        got = []
        for how, seg in cuts:
            i, j = names.index(seg[0]), names.index(seg[-1]) + 1
            assert seg == names[i:j]
            got.append((i, j,
                        "serial" if how == "serial" else classes[i][0]))
        assert got == ref
        # classes are identical objects exactly where the per-pod
        # function's values are equal, and the groups are its groups
        old = [self._reference_class(p, services, replicasets) for p in pods]
        assert classes == old
        for a in range(len(pods)):
            for b in range(a + 1, len(pods)):
                assert (classes[a][0] is classes[b][0]) == \
                    (old[a][0] == old[b][0])

    @pytest.mark.parametrize("rows", [True, False],
                             ids=["row-cache", "no-row-cache"])
    def test_selectors_walked_once_per_signature(self, monkeypatch, rows):
        """50 Services, 64 spec-identical pods of one of them: the selector
        index is asked once in the pass, not 129 times, and tests the one
        Service filed under the pod's label, not 50; and the counter books
        that one decision and the 63 pods that shared it."""
        store, sched = self._cluster(
            services=[f"s{k}" for k in range(50)], replicaset=False,
            rows=rows)
        calls = []
        select = SelectorIndex.select

        def counting(index, pod):
            selectors, tested = select(index, pod)
            calls.append((pod.name, selectors, tested))
            return selectors, tested

        monkeypatch.setattr(SelectorIndex, "select", counting)
        for j in range(64):
            store.create(PODS, mkpod(f"p{j:02d}", labels={"app": "s7"}))
        sched.pump()
        decided0 = BURST_CLASS.labels("decided").value
        shared0 = BURST_CLASS.labels("shared").value
        cuts = self._record_cuts(sched)
        sched._burst_pass_planned(64)
        assert calls == [("p00", [{"app": "s7"}], 1)]
        assert [len(seg) for _how, seg in cuts] == [64]
        assert BURST_CLASS.labels("decided").value - decided0 == 1
        assert BURST_CLASS.labels("shared").value - shared0 == 63

    def test_counter_decided_is_distinct_signatures(self):
        store, sched = self._cluster()
        kinds = ["plain"] * 5 + ["svc-a"] * 4 + ["plain"] * 2 + ["port"]
        for j, kind in enumerate(kinds):
            store.create(PODS, self._pod(f"p{j:02d}", kind))
        sched.pump()
        self._record_cuts(sched)
        decided0 = BURST_CLASS.labels("decided").value
        shared0 = BURST_CLASS.labels("shared").value
        sched._burst_pass_planned(len(kinds))
        # three distinct signatures (plain, svc-a, port) among 12 pods
        assert BURST_CLASS.labels("decided").value - decided0 == 3
        assert BURST_CLASS.labels("shared").value - shared0 == 9

    def test_service_between_passes_reclassifies(self, monkeypatch):
        """Nothing outlives a pass: the same pod shapes are one plain
        segment before the Service exists and, after, one that holds the
        Service's two pods (`spread`) with the two that nothing selects.
        An algorithm that carries one selector group still parts them."""
        store, sched = self._cluster(services=(), replicaset=False)
        cuts = []
        sched._burst_segment = \
            lambda pods, cycles, bucket, run, **_kw: cuts.append(
                (run, len(pods))) or 0

        def one_pass(tag):
            for j, kind in enumerate(["plain", "plain", "svc-a", "svc-a"]):
                store.create(PODS, self._pod(f"{tag}{j}", kind))
            sched.pump()
            cuts.clear()
            sched._burst_pass_planned(4)
            return list(cuts)

        assert one_pass("x") == [("plain", 4)]
        store.create(SERVICES, Service(name="svc-a", selector={"app": "a"}))
        assert one_pass("y") == [("spread", 4)]
        monkeypatch.setattr(type(sched.algorithm), "spread_group_cap", 1)
        assert one_pass("z") == [("plain", 2), ("spread", 2)]

    def test_gang_fallback_without_classes_binds_as_before(self):
        """`_gang_segment`'s degraded path hands `_schedule_singletons_burst`
        no classes (members labelled for a PodGroup that does not exist):
        it decides them itself and binds what the plain path binds."""
        def run(labels):
            store, sched = self._cluster(n_nodes=6)
            for j in range(12):
                kw = {"labels": dict(labels)} if labels else {}
                store.create(PODS, mkpod(f"p{j:02d}", cpu="300m", **kw))
            sched.pump()
            seen = []
            inner = sched._schedule_singletons_burst
            sched._schedule_singletons_burst = \
                lambda pairs, bucket, classes=None: seen.append(classes) \
                or inner(pairs, bucket, classes)
            assert sched.schedule_burst(max_pods=16) == 12
            sched.pump()
            return seen, [store.get(PODS, f"default/p{j:02d}").node_name
                          for j in range(12)]

        seen_gang, gang = run({LABEL_POD_GROUP: "no-such-group"})
        seen_plain, plain = run(None)
        assert seen_gang == [None]
        assert seen_plain != [None] and len(seen_plain[0]) == 12
        assert all(gang) and gang == plain
