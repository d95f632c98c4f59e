"""Remote-transport tests: RemoteStore (the client-go analog) and the
HTTP-attached scheduler — the reflector contract of
client-go/tools/cache/reflector.go:159 (list+watch, resourceVersion
resume, 410 Gone -> re-list) over the apiserver's REST surface, so the
control plane itself crosses a real process boundary, not just kubectl."""
import time

import pytest

from kubernetes_tpu.api.types import Pod, Node, Container
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.store.remote import RemoteStore, APIStatusError
from kubernetes_tpu.store.store import (
    Store, PODS, NODES, AlreadyExistsError, ConflictError, ExpiredError,
    NotFoundError,
)

GI = 1024 ** 3


def mknode(name, cpu=4000):
    return Node(name=name,
                allocatable={"cpu": cpu, "memory": 32 * GI, "pods": 110})


def mkpod(name, cpu=100, priority=0):
    return Pod(name=name, priority=priority,
               containers=(Container.make(name="c", requests={"cpu": cpu}),))


@pytest.fixture()
def served():
    store = Store(watch_log_size=65536)
    with APIServer(store) as srv:
        yield store, RemoteStore(srv.url)


def wait_until(cond, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class TestRemoteStoreCRUD:
    def test_create_get_list_delete(self, served):
        store, remote = served
        created = remote.create(NODES, mknode("n1"))
        assert created.resource_version > 0
        got = remote.get(NODES, "n1")
        assert got.name == "n1" and got.allocatable["cpu"] == 4000
        objs, rv = remote.list(NODES)
        assert [o.name for o in objs] == ["n1"]
        assert rv == store.resource_version()
        gone = remote.delete(NODES, "n1")
        assert gone.name == "n1"
        with pytest.raises(NotFoundError):
            remote.get(NODES, "n1")
        with pytest.raises(NotFoundError):
            remote.delete(NODES, "n1")

    def test_already_exists_and_conflict(self, served):
        store, remote = served
        remote.create(NODES, mknode("n1"))
        with pytest.raises(AlreadyExistsError):
            remote.create(NODES, mknode("n1"))
        cur = remote.get(NODES, "n1")
        cur.unschedulable = True
        remote.update(NODES, cur, expect_rv=cur.resource_version)
        stale = cur   # now one version behind
        with pytest.raises(ConflictError):
            remote.update(NODES, stale, expect_rv=stale.resource_version)

    def test_guaranteed_update_retries_conflict(self, served):
        store, remote = served
        remote.create(PODS, mkpod("p1"))
        raced = {"done": False}

        def mutate(pod):
            if not raced["done"]:
                raced["done"] = True
                # out-of-band writer bumps the rv between GET and PUT
                store.set_nominated_node_name(pod.key, "other")
            pod.nominated_node_name = "winner"
            return pod

        out = remote.guaranteed_update(PODS, "default/p1", mutate)
        assert out.nominated_node_name == "winner"
        assert store.get(PODS, "default/p1").nominated_node_name == "winner"

    def test_bind_and_pod_conveniences(self, served):
        store, remote = served
        remote.create(PODS, mkpod("p1"))
        remote.bind_pod("default/p1", "n7")
        assert store.get(PODS, "default/p1").node_name == "n7"
        remote.set_nominated_node_name("default/p1", "n9")
        assert store.get(PODS, "default/p1").nominated_node_name == "n9"
        from kubernetes_tpu.api.types import (PodCondition, POD_SCHEDULED,
                                              CONDITION_FALSE)
        rv0 = store.get(PODS, "default/p1").resource_version
        cond = PodCondition(type=POD_SCHEDULED, status=CONDITION_FALSE,
                            reason="Unschedulable", message="m")
        remote.update_pod_condition("default/p1", cond)
        assert store.get(PODS, "default/p1").conditions[0].reason == \
            "Unschedulable"
        # the no-op skip must hold over the wire too (store.py:308)
        rv1 = store.get(PODS, "default/p1").resource_version
        assert rv1 > rv0
        remote.update_pod_condition("default/p1", cond)
        assert store.get(PODS, "default/p1").resource_version == rv1


class TestRemotePodGroup:
    """PodGroup verbs + watch over the wire, pinning that the client's
    error mapping matches the apiserver's status codes for the new kind
    (the CLAUDE.md remote/apiserver sync rule)."""

    def test_round_trip_and_status_subresource(self, served):
        from kubernetes_tpu.coscheduling.types import (
            PHASE_PRESCHEDULING, PodGroup)
        from kubernetes_tpu.store.store import PODGROUPS
        store, remote = served
        g = PodGroup(name="g", min_member=4, schedule_timeout_seconds=30.0)
        created = remote.create(PODGROUPS, g)
        assert created.min_member == 4
        assert created.schedule_timeout_seconds == 30.0
        got = remote.get(PODGROUPS, "default/g")
        assert got == created
        objs, _rv = remote.list(PODGROUPS)
        assert [o.key for o in objs] == ["default/g"]
        # the /status subresource: status fields land, spec untouched, and
        # the same write through BOTH transports produces the same object
        updated = remote.update_pod_group_status(
            "default/g", phase=PHASE_PRESCHEDULING, members=2, now=1.5)
        assert updated.phase == PHASE_PRESCHEDULING
        assert updated.members == 2 and updated.min_member == 4
        assert store.get(PODGROUPS, "default/g") == updated
        gone = remote.delete(PODGROUPS, "default/g")
        assert gone.key == "default/g"

    def test_error_mapping_matches_apiserver_codes(self, served):
        from kubernetes_tpu.coscheduling.types import PodGroup
        from kubernetes_tpu.store.store import PODGROUPS
        _store, remote = served
        with pytest.raises(NotFoundError):        # 404
            remote.get(PODGROUPS, "default/missing")
        with pytest.raises(NotFoundError):        # 404 on the subresource
            remote.update_pod_group_status("default/missing", phase="X")
        remote.create(PODGROUPS, PodGroup(name="g"))
        with pytest.raises(AlreadyExistsError):   # 409 AlreadyExists
            remote.create(PODGROUPS, PodGroup(name="g"))
        g = remote.get(PODGROUPS, "default/g")
        g.min_member = 2
        remote.update(PODGROUPS, g, expect_rv=g.resource_version)
        with pytest.raises(ConflictError):        # 409 Conflict (stale rv)
            stale = g.clone()
            stale.min_member = 9
            remote.update(PODGROUPS, stale, expect_rv=g.resource_version)
        with pytest.raises(NotFoundError):        # 404 on delete
            remote.delete(PODGROUPS, "default/other")

    def test_watch_streams_podgroup_events(self, served):
        from kubernetes_tpu.coscheduling.types import PodGroup
        from kubernetes_tpu.store.store import PODGROUPS
        store, remote = served
        w = remote.watch(PODGROUPS, since_rv=store.resource_version())
        try:
            store.create(PODGROUPS, PodGroup(name="g", min_member=3))
            store.update_pod_group_status("default/g", phase="PreScheduling")
            ev1 = w.next(timeout=5.0)
            ev2 = w.next(timeout=5.0)
            assert ev1.type == "ADDED" and ev1.obj.min_member == 3
            assert ev2.type == "MODIFIED" \
                and ev2.obj.phase == "PreScheduling"
        finally:
            w.stop()


class TestRemoteWatch:
    def test_stream_resume_and_types(self, served):
        store, remote = served
        remote.create(NODES, mknode("n1"))
        objs, rv = remote.list(NODES)
        w = remote.watch(NODES, since_rv=rv)
        try:
            store.create(NODES, mknode("n2"))
            store.delete(NODES, "n1")
            evs = []
            assert wait_until(lambda: (evs.extend(w.drain()), len(evs) >= 2)[1])
            assert [(e.type, e.obj.name) for e in evs[:2]] == \
                [("ADDED", "n2"), ("DELETED", "n1")]
        finally:
            w.stop()

    def test_open_past_window_raises_expired(self):
        store = Store(watch_log_size=8)
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url)
            for i in range(40):
                store.create(NODES, mknode(f"n{i}"))
            with pytest.raises(ExpiredError):
                remote.watch(NODES, since_rv=1)

    def test_reconnect_after_server_restart(self):
        """The stream drops when the server dies; the watch reconnects from
        the last seen resourceVersion once a server is back on the port and
        delivers everything written in between — reflector resume."""
        store = Store(watch_log_size=65536)
        srv = APIServer(store, port=0).start()
        port = int(srv.url.rsplit(":", 1)[1])
        remote = RemoteStore(srv.url)
        store.create(NODES, mknode("n1"))
        objs, rv = remote.list(NODES)
        w = remote.watch(NODES, since_rv=rv)
        try:
            store.create(NODES, mknode("n2"))
            evs = []
            assert wait_until(lambda: (evs.extend(w.drain()), len(evs) >= 1)[1])
            srv.stop()
            store.create(NODES, mknode("n3"))   # written while disconnected
            srv2 = APIServer(store, port=port).start()
            try:
                assert wait_until(
                    lambda: (evs.extend(w.drain()), len(evs) >= 2)[1],
                    timeout=15.0)
                assert [e.obj.name for e in evs[:2]] == ["n2", "n3"]
            finally:
                srv2.stop()
        finally:
            w.stop()


class TestWatchDecodeFailure:
    def test_malformed_event_marks_watch_expired(self, served, monkeypatch):
        """Schema drift: an event the client cannot decode must surface as
        ExpiredError from next() (informer re-lists) — the reader thread
        dying silently used to leave next() hanging forever."""
        store, remote = served
        w = remote.watch(PODS)
        try:
            def drifted(kind, d):
                raise ValueError("unknown field shape")
            monkeypatch.setattr(
                "kubernetes_tpu.store.remote.serde.from_dict", drifted)
            store.create(PODS, mkpod("p1"))

            def sees_expiry():
                try:
                    w.next(timeout=0.05)
                    return False
                except ExpiredError:
                    return True
            assert wait_until(sees_expiry)
            # terminal: every subsequent next() keeps raising
            with pytest.raises(ExpiredError):
                w.next(timeout=0.01)
        finally:
            w.stop()


class TestInformerAuthFailure:
    def test_background_relist_stops_on_revoked_token(self):
        """A 401/403 during the background re-list is not transient: the
        informer must record the error and stop instead of silently
        retrying a revoked token forever (store/informer._safe_relist)."""
        from kubernetes_tpu.store.informer import SharedInformer
        store = Store(watch_log_size=65536)
        store.create(NODES, mknode("n1"))
        inf = SharedInformer(store, NODES)
        inf.sync()

        class Revoked:
            calls = 0

            def list(self, kind):
                Revoked.calls += 1
                raise APIStatusError(401, "Unauthorized", "token revoked")

            def watch(self, kind, since_rv=None):
                raise AssertionError("watch must not open after 401")

        inf.store = Revoked()
        inf._safe_relist()
        assert isinstance(inf.last_error, APIStatusError)
        assert inf.last_error.code == 401
        assert inf._stop.is_set()          # the informer thread loop exits
        assert Revoked.calls == 1          # no retry storm

    def test_background_relist_still_retries_transient_errors(self):
        """The transient path is unchanged: a transport blip retries and
        the informer stays alive once the list lands."""
        from kubernetes_tpu.store.informer import SharedInformer
        store = Store(watch_log_size=65536)
        store.create(NODES, mknode("n1"))
        inf = SharedInformer(store, NODES)
        inf.sync()
        real = inf.store

        class Blippy:
            calls = 0

            def list(self, kind):
                Blippy.calls += 1
                if Blippy.calls == 1:
                    raise OSError("connection reset")
                return real.list(kind)

            def watch(self, kind, since_rv=None):
                return real.watch(kind, since_rv=since_rv)

        inf.store = Blippy()
        inf._safe_relist()
        assert inf.last_error is None
        assert not inf._stop.is_set()
        assert Blippy.calls == 2


class TestInformerRelist:
    def test_replace_semantics_on_relist(self, served):
        """DeltaFIFO Replace (delta_fifo.go:96): after an expired-window
        resume the informer must emit deletes for vanished keys, updates
        for changed ones, adds for new ones — not a blind add replay."""
        store, remote = served
        from kubernetes_tpu.store.informer import SharedInformer
        store.create(NODES, mknode("n1"))
        store.create(NODES, mknode("n2"))
        inf = SharedInformer(remote, NODES)
        seen = []
        inf.add_event_handler(
            on_add=lambda o: seen.append(("add", o.name)),
            on_update=lambda o, n: seen.append(("upd", n.name)),
            on_delete=lambda o: seen.append(("del", o.name)))
        inf.sync()
        assert sorted(seen) == [("add", "n1"), ("add", "n2")]
        seen.clear()
        # out-of-band world change the expired watch window would hide
        store.delete(NODES, "n1")
        store.create(NODES, mknode("n3"))
        n2 = store.get(NODES, "n2")
        n2.unschedulable = True
        store.update(NODES, n2)
        inf._relist()
        assert sorted(seen) == [("add", "n3"), ("del", "n1"), ("upd", "n2")]
        assert sorted(o.name for o in inf.list()) == ["n2", "n3"]


class TestRemoteLeaderElection:
    def test_lease_cas_over_http(self, served):
        """Leader election's lease CAS works over the remote transport
        (resourcelock semantics; Lease is a registered API kind), so
        --server --leader-elect is a working combination."""
        from kubernetes_tpu.utils.leader_election import (
            LeaderElector, LeaderElectionConfig)
        from kubernetes_tpu.utils.clock import FakeClock
        store, remote = served
        clock = FakeClock(100.0)
        a = LeaderElector(remote, LeaderElectionConfig(
            identity="a", lease_duration=15.0), clock=clock)
        b = LeaderElector(remote, LeaderElectionConfig(
            identity="b", lease_duration=15.0), clock=clock)
        assert a.try_acquire_or_renew() is True
        assert b.try_acquire_or_renew() is False
        assert a.try_acquire_or_renew() is True      # renewal (bumps rv)
        clock.step(20.0)
        # b first OBSERVES the renewed record here — the observation clock
        # resets on any record change (leaderelection.go:287 semantics), so
        # takeover needs another full lease_duration of silence
        assert b.try_acquire_or_renew() is False
        clock.step(20.0)
        assert b.try_acquire_or_renew() is True      # takeover via CAS
        assert store.get("leases", "kube-scheduler").holder == "b"


class TestRemoteScheduler:
    def test_bindings_identical_to_in_process(self):
        """The headline contract (VERDICT r4 next #4): a scheduler attached
        over HTTP produces byte-identical bindings to the in-process run on
        the same world."""
        from kubernetes_tpu.scheduler import Scheduler

        def world():
            s = Store(watch_log_size=65536)
            for i in range(6):
                s.create(NODES, mknode(f"n{i}",
                                       cpu=2000 if i % 2 else 4000))
            for j in range(20):
                s.create(PODS, mkpod(f"p{j}", cpu=[100, 300, 700][j % 3],
                                     priority=[0, 5][j % 2]))
            return s

        # in-process referee
        s_local = world()
        sched = Scheduler(s_local, use_tpu=False,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        sched.pump()
        want = sorted((p.key, p.node_name) for p in s_local.list(PODS)[0])

        # HTTP-attached run on an identical world
        s_remote = world()
        with APIServer(s_remote) as srv:
            remote = RemoteStore(srv.url)
            rsched = Scheduler(remote, use_tpu=False,
                               percentage_of_nodes_to_score=100)
            rsched.sync()

            def drain():
                rsched.pump()
                progressed = False
                while rsched.schedule_one(timeout=0.0):
                    progressed = True
                return progressed

            def all_bound():
                drain()
                pods, _ = s_remote.list(PODS)
                return all(p.node_name for p in pods)
            assert wait_until(all_bound, timeout=30.0)
        got = sorted((p.key, p.node_name) for p in s_remote.list(PODS)[0])
        assert got == want

    def test_burst_commit_over_http(self):
        """The batched burst commit degrades to per-pod binding POSTs on
        the remote transport (RemoteStore.bind_pods) — a remote-attached
        TPU-burst scheduler binds everything."""
        store = Store(watch_log_size=65536)
        for i in range(4):
            store.create(NODES, mknode(f"n{i}"))
        for j in range(10):
            store.create(PODS, mkpod(f"p{j}", cpu=100))
        from kubernetes_tpu.scheduler import Scheduler
        with APIServer(store) as srv:
            sched = Scheduler(RemoteStore(srv.url), use_tpu=True,
                              percentage_of_nodes_to_score=100)
            sched.sync()

            def all_bound():
                sched.pump()
                while sched.schedule_burst(max_pods=16):
                    pass
                pods, _ = store.list(PODS)
                return all(p.node_name for p in pods)
            assert wait_until(all_bound, timeout=60.0)
        from kubernetes_tpu.store.store import EVENTS
        scheduled = [e for e in store.list(EVENTS)[0]
                     if e.reason == "Scheduled"]
        assert len(scheduled) == 10   # batched events landed per pod

    def test_controller_manager_attaches_over_http(self):
        """The controller manager's whole surface (list / get / create /
        update / delete / guaranteed_update + informers) works over the
        remote transport: a Deployment reconciles to pods through HTTP."""
        from kubernetes_tpu.controllers.manager import ControllerManager
        from kubernetes_tpu.api.types import (Deployment, PodTemplate,
                                              LabelSelector)
        from kubernetes_tpu.store.store import DEPLOYMENTS
        store = Store(watch_log_size=65536)
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url)
            mgr = ControllerManager(remote,
                                    enabled=["deployment", "replicaset"])
            mgr.sync()
            remote.create(DEPLOYMENTS, Deployment(
                name="web", replicas=3,
                selector=LabelSelector.from_dict({"app": "web"}),
                template=PodTemplate(
                    labels={"app": "web"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100}),))))

            def reconciled():
                mgr.pump()
                pods, _ = store.list(PODS)
                return len(pods) == 3
            assert wait_until(reconciled, timeout=20.0)
            assert all(p.labels.get("app") == "web"
                       for p in store.list(PODS)[0])

    def test_cmd_scheduler_attaches_over_http(self):
        """cmd/scheduler.py --server URL: the CLI entry runs out-of-process
        against a served store (--once drain)."""
        from kubernetes_tpu.cmd import scheduler as cmd_sched
        store = Store(watch_log_size=65536)
        for i in range(3):
            store.create(NODES, mknode(f"n{i}"))
        for j in range(6):
            store.create(PODS, mkpod(f"p{j}"))
        with APIServer(store) as srv:
            rc = cmd_sched.main(["--server", srv.url, "--once",
                                 "--percentage-of-nodes-to-score", "100"])
            assert rc == 0
            pods, _ = store.list(PODS)
            assert all(p.node_name for p in pods)


class TestBackpressure429:
    """Round-16 serving backpressure over the wire: a shed pod create
    answers 429 + reason=Backpressure + Retry-After, the client maps it
    to BackpressureError (DISTINCT from the eviction subresource's
    DisruptionBudgetError) and re-sends with capped jittered backoff,
    counted on remote_request_retries_total{backpressure} — the pinned
    contract the serve lane's arrival clients ride."""

    class _ShedGate:
        """Admission gate stub: shed the first `n` pod creates with a
        deliberately huge Retry-After (the cap must bite)."""

        def __init__(self, n, retry_after=10.0):
            self.n = n
            self.retry_after = retry_after

        def admit(self, pod):
            from kubernetes_tpu.store.store import BackpressureError
            if self.n > 0:
                self.n -= 1
                raise BackpressureError(f"{pod.key}: shed",
                                        retry_after=self.retry_after)

    def test_create_honors_retry_after_capped_and_jittered(self, served):
        from kubernetes_tpu.store.remote import REQUEST_RETRIES
        store, remote = served
        store.admission_gate = self._ShedGate(2)
        sleeps = []
        remote._sleep = sleeps.append
        before = REQUEST_RETRIES.labels("backpressure").value
        out = remote.create(PODS, mkpod("p1"))
        assert out.name == "p1"
        assert store.get(PODS, "default/p1").name == "p1"
        # two sheds -> two backoffs, each the server's 10s suggestion
        # CAPPED at 2s and jittered into [0.5, 1.0]x
        assert len(sleeps) == 2
        cap = remote.BACKPRESSURE_RETRY[1]
        assert all(0.5 * cap <= s <= cap for s in sleeps), sleeps
        assert REQUEST_RETRIES.labels("backpressure").value - before == 2

    def test_sub_second_retry_after_passes_through(self, served):
        store, remote = served
        store.admission_gate = self._ShedGate(1, retry_after=0.25)
        sleeps = []
        remote._sleep = sleeps.append
        remote.create(PODS, mkpod("p2"))
        assert len(sleeps) == 1
        assert 0.125 <= sleeps[0] <= 0.25, sleeps

    def test_exhausted_backpressure_raises_the_mapped_error(self, served):
        from kubernetes_tpu.store.store import BackpressureError
        store, remote = served
        store.admission_gate = self._ShedGate(10 ** 9)
        remote._sleep = lambda _s: None
        with pytest.raises(BackpressureError) as ei:
            remote.create(PODS, mkpod("p3"))
        # the mapped error carries the server's Retry-After verbatim
        assert ei.value.retry_after == pytest.approx(10.0)
        with pytest.raises(NotFoundError):
            store.get(PODS, "default/p3")

    def test_eviction_429_still_maps_to_budget_error(self, served):
        """The eviction subresource's 429 keeps its own error type and is
        NEVER auto-retried (a landed retry would double-charge the
        budget) — the reason-split must not blur the two contracts."""
        from kubernetes_tpu.api.types import (LabelSelector,
                                              PodDisruptionBudget)
        from kubernetes_tpu.store.store import (DisruptionBudgetError,
                                                PDBS)
        store, remote = served
        remote.create(PODS, mkpod("guarded"))
        store.create(PDBS, PodDisruptionBudget(
            name="budget",
            selector=LabelSelector(match_labels=()),
            disruptions_allowed=0))
        sleeps = []
        remote._sleep = sleeps.append
        with pytest.raises(DisruptionBudgetError):
            remote.evict_pod("default/guarded")
        assert sleeps == []          # no auto-retry on budget refusals
        assert store.get(PODS, "default/guarded").name == "guarded"


class TestRetryPolicyTable:
    """Round-18 satellite pin: the per-verb-class retry budget is a correctness surface, not a tuning
    knob. In particular: a 409 (ConflictError, FencedError included) is
    a DEFINITIVE answer on every class, and Lease CAS writes (leader
    election acquire/renew/claim) get exactly ONE attempt even for
    transient transport failures — a renew ridden through retries can
    land, answer 409 to its own replay, and leave the elector believing
    a lie in either direction; the lost lease must surface to the
    elector, which steps down before the fencing window, not be retried
    into a fencing violation."""

    def _attempts(self, verb_class, exc_factory):
        import urllib.error   # noqa: F401 — factories close over it
        rs = RemoteStore("http://127.0.0.1:1")
        rs._sleep = lambda _s: None
        calls = {"n": 0}

        def boom(method, path, body=None):
            calls["n"] += 1
            raise exc_factory()
        rs._request_once = boom
        with pytest.raises(Exception):
            rs._request("PUT", "/api/v1/x", verb_class=verb_class)
        return calls["n"]

    def test_policy_table_pinned(self):
        assert RemoteStore.RETRY_POLICY == {
            "read": (4, 0.02),
            "cas": (3, 0.02),
            "bind": (4, 0.02),
            "status": (3, 0.02),
            "write": (1, 0.0),
            "lease": (1, 0.0),
        }

    def test_conflicts_never_auto_retried_on_any_class(self):
        from kubernetes_tpu.store.store import FencedError
        for verb in ("read", "cas", "bind", "status", "write", "lease"):
            assert self._attempts(verb, lambda: ConflictError("cas")) == 1
            assert self._attempts(verb, lambda: FencedError("stale")) == 1

    def test_transient_budget_per_class(self):
        import urllib.error
        expected = {"read": 4, "cas": 3, "status": 3, "write": 1,
                    "lease": 1}
        for verb, n in expected.items():
            got = self._attempts(
                verb, lambda: urllib.error.URLError("connection reset"))
            assert got == n, (verb, got, n)

    def test_lease_cas_update_routes_to_lease_class(self):
        """update(LEASES, ..., expect_rv=...) rides the one-attempt lease
        class; every other kind's CAS keeps the cas class."""
        from kubernetes_tpu.api.types import Lease
        from kubernetes_tpu.api import serde
        from kubernetes_tpu.store.store import LEASES
        rs = RemoteStore("http://127.0.0.1:1")
        seen = []

        def fake_request(method, path, body=None, verb_class="read"):
            seen.append(verb_class)
            if "leases" in path:
                return serde.to_dict(Lease(name="lock"))
            return serde.to_dict(mkpod("p"))
        rs._request = fake_request
        rs.update(LEASES, Lease(name="lock"), expect_rv=3)
        rs.update(PODS, mkpod("p"), expect_rv=3)
        rs.update(LEASES, Lease(name="lock"))   # unconditional: write
        assert seen == ["lease", "cas", "write"]
