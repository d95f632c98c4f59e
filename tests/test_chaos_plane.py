"""Round-13 fault plane: deterministic injection, graceful degradation.

Covers the chaos switchboard itself (seams, spec grammar, per-seam seeded
streams, limits), the device circuit breaker's state machine and its
scheduler integration (fault -> serial fallback, trip -> host-only,
half-open probe -> re-promotion), native-core demotion (commitcore and
heapcore swap to their pure-Python twins mid-run without losing a wave or
a queued pod), idempotent commit retry (wave-token dedupe on the embedded
store, read-before-re-POST on the remote client), the informer's
relist-backoff guard, leader-election fencing (no-two-leaders window
pinned on a fake clock), and a tier-1-speed smoke that runs one
differential fuzz trial per seam.
"""
import urllib.error
from types import SimpleNamespace

import pytest

from kubernetes_tpu import chaos
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.store.store import (
    Store, PODS, NODES, ExpiredError, NotFoundError, MODIFIED,
    WATCH_DROPPED, WAVE_DEDUP,
)
from kubernetes_tpu.utils.clock import FakeClock

GI = 1024 ** 3


@pytest.fixture(autouse=True)
def chaos_reset():
    """The plane is process-global: every test starts and ends inert."""
    chaos.disable()
    yield
    chaos.disable()


def mknode(name, cpu=4000):
    return Node(name=name,
                labels={"kubernetes.io/hostname": name,
                        "failure-domain.beta.kubernetes.io/zone": "z0"},
                allocatable={"cpu": cpu, "memory": 32 * GI, "pods": 110})


def mkpod(name, cpu=100, priority=0):
    return Pod(name=name, priority=priority, labels={"app": "x"},
               containers=(Container.make(name="c",
                                          requests={"cpu": cpu}),))


def fam_count(fam, *labels) -> float:
    child = fam._children.get(tuple(labels))
    return child.value if child is not None else 0.0


# ---------------------------------------------------------------------------
# the switchboard
# ---------------------------------------------------------------------------
class TestPlanMechanics:
    def test_seams_pinned(self):
        # a new seam cannot land unnamed: extend this set AND the README
        # table when adding one
        assert set(chaos.SEAMS) == {
            "device.dispatch", "device.fetch",
            "store.commit_wave", "store.commit_wave.ambiguous",
            "store.fanout", "native.commitcore", "native.heapcore",
            "remote.http", "watch.drop", "clock.jump", "sched.crash",
            "node.dead", "serve.shed", "fleet.lease-loss",
            "store.update_many", "store.evict_many",
        }
        assert set(chaos._FAULT_FOR) == set(chaos.SEAMS)
        assert set(chaos.OPT_IN_SEAMS) <= set(chaos.SEAMS)

    def test_spec_grammar(self):
        p = chaos._parse_spec("seed=7 all=0.5,device.fetch=0.9 limit=3")
        assert p.seed == 7 and p.limit == 3
        assert p.rates["device.fetch"] == 0.9
        assert p.rates["device.dispatch"] == 0.5
        # blanket rates skip the opt-in seams
        assert "clock.jump" not in p.rates
        assert "sched.crash" not in p.rates
        assert "node.dead" not in p.rates

    def test_spec_rejects_unknown(self):
        with pytest.raises(ValueError):
            chaos._parse_spec("device.fetcj=0.5")
        with pytest.raises(ValueError):
            chaos._parse_spec("notakv")
        with pytest.raises(ValueError):
            chaos.plan(seed=1, rates={"bogus.seam": 1.0})

    def test_env_spec(self, monkeypatch):
        monkeypatch.setenv("KTPU_CHAOS", "seed=9,watch.drop=1.0,limit=2")
        chaos._PLAN = None
        chaos._ENV_LOADED = False
        p = chaos.active()
        assert p is not None and p.seed == 9 and p.limit == 2
        assert p.rates == {"watch.drop": 1.0}

    def test_per_seam_streams_independent(self):
        # drawing one seam must not shift another seam's sequence
        a = chaos.ChaosPlan(seed=5, rates={"device.fetch": 0.3,
                                           "watch.drop": 0.3})
        seq_a = [a.should("device.fetch") for _ in range(40)]
        b = chaos.ChaosPlan(seed=5, rates={"device.fetch": 0.3,
                                           "watch.drop": 0.3})
        seq_b = []
        for _ in range(40):
            b.should("watch.drop")          # interleaved foreign draws
            seq_b.append(b.should("device.fetch"))
        assert seq_a == seq_b
        assert any(seq_a)                   # the stream actually fires

    def test_limit_caps_per_seam(self):
        p = chaos.ChaosPlan(seed=1, rates={"watch.drop": 1.0}, limit=2)
        fired = sum(p.should("watch.drop") for _ in range(10))
        assert fired == 2
        assert p.counts() == {"watch.drop": 2}

    def test_check_raises_mapped_types(self):
        chaos.plan(seed=0, rates={"device.dispatch": 1.0})
        with pytest.raises(chaos.DeviceFault):
            chaos.check("device.dispatch")
        chaos.plan(seed=0, rates={"store.commit_wave": 1.0})
        with pytest.raises(chaos.StoreFault):
            chaos.check("store.commit_wave")
        # the remote fault IS a URLError: the client's transient handlers
        # catch it unmodified
        chaos.plan(seed=0, rates={"remote.http": 1.0})
        with pytest.raises(urllib.error.URLError):
            chaos.check("remote.http")

    def test_breaker_absorbs_the_injected_fault_only(self):
        # a real jax runtime error on a local chip is a compile failure,
        # an out-of-memory or a dead device: it must propagate, so the
        # breaker's fault set holds the injected type and nothing else
        from jax.errors import JaxRuntimeError
        assert chaos.device_fault_types() == (chaos.DeviceFault,)
        assert not issubclass(JaxRuntimeError, chaos.device_fault_types())

    def test_inert_fast_path(self):
        assert chaos.active() is None
        chaos.check("device.dispatch")      # no-op, no raise
        assert chaos.take("watch.drop") is False
        assert chaos.counts() == {}

    def test_chaos_clock_jumps(self):
        base = FakeClock(100.0)
        wrapped = chaos.wrap_clock(base)
        assert wrapped.now() == 100.0       # inert plane: passthrough
        chaos.plan(seed=3, rates={"clock.jump": 1.0}, limit=1,
                   jump_range=(5.0, 5.0))
        assert wrapped.now() == 105.0       # one jump, then the skew holds
        assert wrapped.now() == 105.0
        base.step(1.0)
        assert wrapped.now() == 106.0


# ---------------------------------------------------------------------------
# the device circuit breaker
# ---------------------------------------------------------------------------
class TestCircuitBreaker:
    def test_trip_probe_promote_cycle(self):
        from kubernetes_tpu.core.breaker import DeviceCircuitBreaker
        b = DeviceCircuitBreaker(fault_threshold=3, probe_after=4)
        assert b.allow_device() and b.state == "closed"
        b.record_fault(); b.record_fault()
        assert b.state == "closed"          # below threshold
        b.record_success()
        b.record_fault(); b.record_fault()
        assert b.state == "closed"          # success reset the streak
        b.record_fault()
        assert b.state == "open" and b.trips_total == 1
        # open: refuse until the probe window, then one probe through
        assert not b.allow_device() and not b.allow_device()
        assert not b.allow_device()
        assert b.allow_device() and b.state == "half-open"
        # a faulted probe re-opens and restarts the refusal count
        b.record_fault()
        assert b.state == "open" and b.trips_total == 2
        for _ in range(3):
            assert not b.allow_device()
        assert b.allow_device() and b.state == "half-open"
        b.record_success()
        assert b.state == "closed" and b.promotions_total == 1

    def test_gauge_tracks_state(self):
        from kubernetes_tpu.core import breaker as brk
        b = brk.DeviceCircuitBreaker(fault_threshold=1, probe_after=1)
        b.record_fault("device.fetch")
        assert brk.CIRCUIT_STATE.value == brk.OPEN
        b.allow_device()
        assert brk.CIRCUIT_STATE.value == brk.HALF_OPEN
        b.record_success()
        assert brk.CIRCUIT_STATE.value == brk.CLOSED
        assert fam_count(brk.DEVICE_FAULTS, "device.fetch") >= 1


class TestDeviceDegradation:
    def _world(self, n_nodes=4, n_pods=12):
        from kubernetes_tpu.scheduler import Scheduler
        s = Store(watch_log_size=65536)
        for i in range(n_nodes):
            s.create(NODES, mknode(f"n{i}"))
        sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=100)
        sched.sync()
        for j in range(n_pods):
            s.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        return s, sched

    def test_single_fault_degrades_burst_not_run(self):
        from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
        before = fam_count(ORACLE_FALLBACKS, "device-fault")
        s, sched = self._world()
        chaos.plan(seed=0, rates={"device.dispatch": 1.0}, limit=1)
        while sched.schedule_burst(max_pods=32):
            pass
        sched.pump()
        assert all(p.node_name for p in s.list(PODS)[0])
        assert sched.algorithm.breaker.faults_total == 1
        assert fam_count(ORACLE_FALLBACKS, "device-fault") > before

    def test_trip_to_host_only_then_reprobe(self):
        s, sched = self._world(n_pods=12)
        # pin the serial fallback to the device twin-vs-device choice that
        # exercises the breaker (the default "adaptive" pick is a timing
        # heuristic — it may sidestep the device and never probe)
        sched.algorithm.serial_path = "device"
        chaos.plan(seed=0, rates={"device.dispatch": 1.0,
                                  "device.fetch": 1.0})
        # small bursts: every attempt faults at dispatch; the serial rerun
        # keeps faulting per cycle until the third consecutive fault trips
        # the circuit to host-only
        while sched.schedule_burst(max_pods=4):
            pass
        sched.pump()
        # every decision landed despite a permanently faulting device
        assert all(p.node_name for p in s.list(PODS)[0])
        b = sched.algorithm.breaker
        assert b.trips_total >= 1 and b.state != "closed"
        # faults stop (the seam heals): the half-open probe re-promotes
        chaos.disable()
        for j in range(40):
            s.create(PODS, mkpod(f"q{j}"))
        sched.pump()
        while sched.schedule_burst(max_pods=64):
            pass
        sched.pump()
        assert all(p.node_name for p in s.list(PODS)[0])
        assert b.promotions_total >= 1 and b.state == "closed"


# ---------------------------------------------------------------------------
# native-core demotion
# ---------------------------------------------------------------------------
class TestNativeDemotion:
    def test_commitcore_demotes_mid_run(self):
        s = Store(watch_log_size=256)
        if s.core_impl != "native":
            pytest.skip("native commitcore unavailable")
        s.create(PODS, mkpod("warm"))
        w = s.watch(PODS)
        rv_before = s._core.rv()
        drops = fam_count(WATCH_DROPPED, "core-demotion")
        demos = fam_count(chaos.DEMOTIONS, "commitcore")
        chaos.plan(seed=0, rates={"native.commitcore": 1.0}, limit=1)
        s.create(PODS, mkpod("after"))      # the verb that hits the seam
        assert s.core_impl == "twin"
        assert fam_count(chaos.DEMOTIONS, "commitcore") == demos + 1
        assert fam_count(WATCH_DROPPED, "core-demotion") == drops + 1
        # rv continuity: the demotion-triggering write landed on the twin
        # with the next rv — no gap, no reuse
        assert s.get(PODS, "default/after").resource_version == rv_before + 1
        # the live watcher is dropped-with-resync (its cursors died with
        # the native core), and a fresh watch rides the twin normally
        with pytest.raises(ExpiredError):
            w.next(timeout=0.01)
        w2 = s.watch(PODS)
        s.create(PODS, mkpod("post-demotion"))
        ev = w2.next(timeout=1.0)
        assert ev is not None and ev.obj.name == "post-demotion"

    def test_heapcore_demotes_without_losing_items(self):
        from kubernetes_tpu import native
        if native.load("heapcore") is None:
            pytest.skip("native heapcore unavailable")
        from kubernetes_tpu.utils.heap import NumericKeyedHeap
        h = NumericKeyedHeap(lambda it: it[0],
                             lambda it: (it[1], it[2], it[3]))
        assert getattr(h, "_native", False)
        items = [(f"k{i}", (i * 7) % 5, i, 0.0) for i in range(20)]
        for it in items:
            h.add(it)
        demos = fam_count(chaos.DEMOTIONS, "heapcore")
        chaos.plan(seed=0, rates={"native.heapcore": 1.0}, limit=1)
        h.add(("extra", 9, 99, 0.0))        # guarded entry point: demotes
        assert h._native is False
        assert fam_count(chaos.DEMOTIONS, "heapcore") == demos + 1
        # every queued item survived the migration and pops in the exact
        # ascending-triple order the native core would have produced
        got = [h.pop() for _ in range(len(h))]
        want = sorted(items + [("extra", 9, 99, 0.0)],
                      key=lambda it: (it[1], it[2], it[3]))
        assert got == [list(w) if isinstance(got[0], list) else w
                       for w in want]


# ---------------------------------------------------------------------------
# idempotent commit retry
# ---------------------------------------------------------------------------
class TestCommitWaveIdempotency:
    def _store_with_pods(self, n=3):
        s = Store(watch_log_size=256)
        s.create(NODES, mknode("n0"))
        for j in range(n):
            s.create(PODS, mkpod(f"p{j}"))
        return s

    def test_pre_land_failure_then_retry_lands(self):
        s = self._store_with_pods()
        bindings = [(f"default/p{j}", "n0") for j in range(3)]
        chaos.plan(seed=0, rates={"store.commit_wave": 1.0}, limit=1)
        with pytest.raises(chaos.StoreFault):
            s.commit_wave(bindings, token="w1")
        # nothing landed: the fault fired before the core write
        assert all(not s.get(PODS, k).node_name for k, _ in bindings)
        assert s.commit_wave(bindings, token="w1") == []
        assert all(s.get(PODS, k).node_name == "n0" for k, _ in bindings)

    def test_ambiguous_failure_dedupes_on_token(self):
        s = self._store_with_pods()
        w = s.watch(PODS)
        bindings = [(f"default/p{j}", "n0") for j in range(3)]
        dedup_before = WAVE_DEDUP.value
        chaos.plan(seed=0, rates={"store.commit_wave.ambiguous": 1.0},
                   limit=1)
        with pytest.raises(chaos.StoreFault):
            s.commit_wave(bindings, token="w1")
        # the wave LANDED (the response was lost after the fact)
        assert all(s.get(PODS, k).node_name == "n0" for k, _ in bindings)
        rv_after_land = s._core.rv()
        # the retry replays the recorded result, not the write
        assert s.commit_wave(bindings, token="w1") == []
        assert WAVE_DEDUP.value == dedup_before + 1
        assert s._core.rv() == rv_after_land
        # exactly ONE bind event per pod reached the watcher
        s.fanout_wave()
        seen: dict[str, int] = {}
        while True:
            ev = w.try_next()
            if ev is None:
                break
            if ev.type == MODIFIED and ev.obj.node_name:
                seen[ev.obj.key] = seen.get(ev.obj.key, 0) + 1
        assert seen == {k: 1 for k, _ in bindings}

    def test_scheduler_retry_loop_recovers(self):
        from kubernetes_tpu.scheduler import Scheduler, COMMIT_RETRIES
        s = Store(watch_log_size=65536)
        for i in range(3):
            s.create(NODES, mknode(f"n{i}"))
        sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=100)
        sched.sync()
        for j in range(8):
            s.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        retried = fam_count(COMMIT_RETRIES, "retried")
        recovered = fam_count(COMMIT_RETRIES, "recovered")
        # two consecutive pre-land failures; the third attempt lands
        chaos.plan(seed=0, rates={"store.commit_wave": 1.0}, limit=2)
        while sched.schedule_burst(max_pods=16):
            pass
        sched.pump()
        assert all(p.node_name for p in s.list(PODS)[0])
        assert fam_count(COMMIT_RETRIES, "retried") == retried + 2
        assert fam_count(COMMIT_RETRIES, "recovered") == recovered + 1


class TestRemoteRetryPolicy:
    def _rs(self, sleeps):
        from kubernetes_tpu.store.remote import RemoteStore
        rs = RemoteStore("http://chaos-test")
        rs._sleep = sleeps.append
        return rs

    def test_read_retries_transient_then_succeeds(self):
        from kubernetes_tpu.store.remote import REQUEST_RETRIES
        sleeps, calls = [], []
        rs = self._rs(sleeps)

        def once(method, path, body=None):
            calls.append(method)
            if len(calls) < 3:
                raise urllib.error.URLError("connection reset")
            return {"ok": 1}
        rs._request_once = once
        before = fam_count(REQUEST_RETRIES, "read")
        assert rs._request("GET", "/x") == {"ok": 1}
        assert len(calls) == 3 and len(sleeps) == 2
        assert sleeps[1] > sleeps[0]        # exponential ladder
        assert fam_count(REQUEST_RETRIES, "read") == before + 2

    def test_writes_never_auto_retry(self):
        sleeps, calls = [], []
        rs = self._rs(sleeps)

        def once(method, path, body=None):
            calls.append(method)
            raise urllib.error.URLError("connection reset")
        rs._request_once = once
        with pytest.raises(urllib.error.URLError):
            rs._request("POST", "/x", {}, verb_class="write")
        assert len(calls) == 1 and not sleeps

    def test_mapped_errors_are_answers_not_transients(self):
        from kubernetes_tpu.store.remote import APIStatusError, RemoteStore
        assert RemoteStore._is_transient(APIStatusError(503, "x", "y"))
        assert not RemoteStore._is_transient(APIStatusError(404, "x", "y"))
        assert not RemoteStore._is_transient(APIStatusError(409, "x", "y"))
        assert RemoteStore._is_transient(TimeoutError())
        assert RemoteStore._is_transient(
            chaos.RemoteFault("remote.http"))   # injected = URLError

    def test_bind_pod_ambiguous_probe_prevents_double_post(self):
        sleeps, posts = [], []
        rs = self._rs(sleeps)

        def once(method, path, body=None):
            posts.append(path)
            # the POST "lands" server-side but the response is lost
            raise urllib.error.URLError("connection reset")
        rs._request_once = once
        rs.get = lambda kind, key: SimpleNamespace(node_name="n1")
        out = rs.bind_pod("default/p0", "n1")
        assert out.node_name == "n1"
        assert len(posts) == 1              # never re-POSTed

    def test_bind_pod_retries_when_probe_says_not_landed(self):
        sleeps, posts = [], []
        rs = self._rs(sleeps)

        def once(method, path, body=None):
            posts.append(path)
            if len(posts) == 1:
                raise urllib.error.URLError("connection reset")
            return {"bound": 1}
        rs._request_once = once
        rs.get = lambda kind, key: SimpleNamespace(node_name=None)
        assert rs.bind_pod("default/p0", "n1") == {"bound": 1}
        assert len(posts) == 2

    def test_bind_pod_deleted_pod_raises(self):
        sleeps, posts = [], []
        rs = self._rs(sleeps)

        def once(method, path, body=None):
            posts.append(path)
            raise urllib.error.URLError("connection reset")
        rs._request_once = once

        def gone(kind, key):
            raise NotFoundError(key)
        rs.get = gone
        with pytest.raises(NotFoundError):
            rs.bind_pod("default/p0", "n1")


# ---------------------------------------------------------------------------
# informer relist backoff + watch-drop resync
# ---------------------------------------------------------------------------
class TestInformerRelistBackoff:
    def test_sustained_expired_window_does_not_spin(self):
        from kubernetes_tpu.store.informer import (SharedInformer,
                                                   RELIST_BACKOFF)
        s = Store(watch_log_size=256)
        s.create(NODES, mknode("n0"))
        inf = SharedInformer(s, NODES)
        inf.sync()
        sleeps: list = []
        inf._sleep = sleeps.append
        real_watch = s.watch
        box = [0]

        def flaky_watch(kind, since_rv=None):
            if box[0] < 5:
                box[0] += 1
                raise ExpiredError("log window moved")
            return real_watch(kind, since_rv=since_rv)
        s.watch = flaky_watch
        before = RELIST_BACKOFF.labels(NODES).count
        inf._relist()
        # first expiry re-lists immediately; the storm's tail climbs the
        # capped, jittered ladder instead of hot-looping list+watch
        assert len(sleeps) == 4
        assert all(0 < d <= inf.relist_backoff_cap for d in sleeps)
        assert RELIST_BACKOFF.labels(NODES).count == before + 4
        # a delivered event ends the streak: the next isolated expiry is
        # again instant
        s.watch = real_watch
        s.create(NODES, mknode("n1"))
        inf.pump()
        assert inf._expired_streak == 0

    def test_injected_watch_drop_resyncs(self):
        from kubernetes_tpu.store.informer import SharedInformer
        s = Store(watch_log_size=256)
        inf = SharedInformer(s, PODS)
        inf.sync()
        s.create(PODS, mkpod("fresh"))
        drops = fam_count(WATCH_DROPPED, "injected")
        chaos.plan(seed=0, rates={"watch.drop": 1.0}, limit=1)
        inf.pump()                          # drop -> re-list -> converge
        assert fam_count(WATCH_DROPPED, "injected") == drops + 1
        assert inf.get("default/fresh") is not None


# ---------------------------------------------------------------------------
# slow-watcher drop -> resync, end to end over the wire
# ---------------------------------------------------------------------------
class TestWatchDropResyncE2E:
    """The full drop-with-resync loop the informers and the remote client
    implement, driven end to end: a commit wave overruns the server
    store's event-log window, the overflowed server-side watcher gets
    ExpiredError at its next poll, the apiserver ends the HTTP stream,
    the remote client reconnects from its last seen resourceVersion and
    is answered 410 Gone, the informer re-lists over HTTP — and the
    caches converge. Runs on BOTH commit cores (the drop accounting and
    the cursor eviction live inside the core)."""

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_drop_relist_reconnect_converge(self, impl):
        import time
        from kubernetes_tpu import native
        if impl == "native" and native.load("commitcore") is None:
            pytest.skip("native commitcore unavailable")
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.store.informer import SharedInformer
        from kubernetes_tpu.store.remote import (RemoteStore,
                                                 WATCH_RECONNECTS)
        store = Store(watch_log_size=4, watch_queue_size=100,
                      commit_core=impl)
        assert store.core_impl == impl
        store.create(NODES, mknode("n0"))
        for j in range(8):
            store.create(PODS, mkpod(f"p{j}"))
        # which overflow reason books depends on whether the fan-out
        # flush or the server watcher's poll detects the eviction first
        # (flush-time = slow-consumer, poll-time = log-window); both are
        # the same consumer contract
        def overflow_drops():
            return (fam_count(WATCH_DROPPED, "log-window")
                    + fam_count(WATCH_DROPPED, "slow-consumer"))
        drops = overflow_drops()
        recon = fam_count(WATCH_RECONNECTS, PODS)
        with APIServer(store) as srv:
            inf = SharedInformer(RemoteStore(srv.url), PODS)
            inf.sync()
            assert len(inf.list()) == 8
            # one wave of 8 events through a 4-entry log ring: the
            # server-side watcher feeding this HTTP stream is overrun
            # before it can copy out
            store.commit_wave(
                [(f"default/p{j}", "n0") for j in range(8)], None)
            store.fanout_wave()
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                inf.pump(timeout=0.1)
                objs = inf.list()
                if len(objs) == 8 and all(p.node_name == "n0"
                                          for p in objs):
                    break
            else:
                pytest.fail("informer cache never converged after the "
                            "watch drop")
            # the loop's observable trail: the core counted the drop, and
            # the remote client reconnected after the stream ended
            assert overflow_drops() > drops
            assert fam_count(WATCH_RECONNECTS, PODS) > recon
            if inf._watch is not None:
                inf._watch.stop()


# ---------------------------------------------------------------------------
# leader-election fencing
# ---------------------------------------------------------------------------
class _FlakyStore:
    """Store proxy whose lease verbs fail while `down` — the holder's
    store connection partitions without affecting other candidates."""

    def __init__(self, store):
        self._s = store
        self.down = False

    def _gate(self):
        if self.down:
            raise OSError("store unreachable")

    def get(self, *a, **k):
        self._gate()
        return self._s.get(*a, **k)

    def create(self, *a, **k):
        self._gate()
        return self._s.create(*a, **k)

    def update(self, *a, **k):
        self._gate()
        return self._s.update(*a, **k)


class TestLeaderFencing:
    def _cfg(self, identity, clock, events, **kw):
        from kubernetes_tpu.utils.leader_election import LeaderElectionConfig
        return LeaderElectionConfig(
            identity=identity, lease_duration=15.0, renew_deadline=10.0,
            retry_period=2.0,
            on_started_leading=lambda: events.append(
                (identity, "start", clock.now())),
            on_stopped_leading=lambda: events.append(
                (identity, "stop", clock.now())), **kw)

    def test_renew_deadline_must_undercut_lease(self):
        from kubernetes_tpu.utils.leader_election import (
            LeaderElector, LeaderElectionConfig)
        with pytest.raises(ValueError):
            LeaderElector(Store(), LeaderElectionConfig(
                lease_duration=10.0, renew_deadline=10.0))

    def test_no_two_leaders_window(self):
        """The fencing invariant on a fake clock: when A's renews fail
        past renew_deadline, A fires on_stopped_leading and stops
        STRICTLY BEFORE the lease expires for everyone else — the window
        in which B can acquire never overlaps A's leadership, so two
        elected schedulers can never both commit a wave."""
        from kubernetes_tpu.utils.leader_election import LeaderElector
        clock = FakeClock(0.0)
        store = Store()
        store.create(NODES, mknode("n0"))
        for j in range(6):
            store.create(PODS, mkpod(f"p{j}"))
        events: list = []
        flaky = _FlakyStore(store)
        a = LeaderElector(flaky, self._cfg("a", clock, events), clock=clock)
        b = LeaderElector(store, self._cfg("b", clock, events), clock=clock)

        pending = [f"default/p{j}" for j in range(6)]

        def pump(dt: float):
            """One election round: advance time, step both, assert the
            exclusivity invariant, and let the current leader commit one
            scheduling wave (the thing fencing exists to serialize)."""
            clock.step(dt)
            a.step()
            b.step()
            assert not (a.is_leader and b.is_leader), \
                f"two leaders at t={clock.now()}"
            for elector, name in ((a, "a"), (b, "b")):
                if elector.is_leader and pending:
                    store.commit_wave([(pending.pop(0), "n0")],
                                      token=f"{name}:{clock.now()}")

        pump(0.0)
        assert a.is_leader and not b.is_leader
        # A's store partitions: renews fail transiently, A keeps leading
        # inside the deadline (the lease is still unexpired for B)
        flaky.down = True
        pump(5.0)
        assert a.is_leader and not b.is_leader
        # deadline blown at t=10.1 > renew_deadline: A must abdicate NOW,
        # while B still sees an unexpired lease (fencing gap)
        pump(5.1)
        assert not a.is_leader
        assert ("a", "stop", 10.1) in events
        assert not b.is_leader
        # lease expires at t=15 (A's last successful renew at t=0): only
        # AFTER that may B acquire — strictly later than A's stop
        pump(5.0)
        assert b.is_leader and not a.is_leader
        stop_t = next(t for who, what, t in events
                      if who == "a" and what == "stop")
        start_t = next(t for who, what, t in events
                       if who == "b" and what == "start")
        assert stop_t < start_t
        # the recovered side finishes the job: every wave committed by
        # exactly one holder, every pod bound exactly once
        while pending:
            pump(2.0)
        assert all(store.get(PODS, f"default/p{j}").node_name == "n0"
                   for j in range(6))


# ---------------------------------------------------------------------------
# real device errors propagate (no hidden host fallback)
# ---------------------------------------------------------------------------
class TestRealDeviceErrorsPropagate:
    """An injected DeviceFault degrades to the oracle and rewinds
    (TestDeviceDegradation above); a real JaxRuntimeError at the same seams
    reaches the caller. Nothing books it as a fault, nothing falls back,
    and the circuit stays closed — the run fails instead of finishing on
    the host under a device metric's name."""

    def _world(self, n_pods=12, gang=False, mixed=False):
        s, sched = TestDeviceDegradation()._world(n_pods=0)
        sched.algorithm.serial_path = "device"
        labels = {}
        if gang:
            from kubernetes_tpu.coscheduling.types import (LABEL_POD_GROUP,
                                                           PodGroup)
            from kubernetes_tpu.store.store import PODGROUPS
            s.create(PODGROUPS, PodGroup(name="g", min_member=n_pods))
            labels = {LABEL_POD_GROUP: "g"}
        for j in range(n_pods):
            pod = mkpod(f"p{j}", cpu=(100, 300)[j % 2] if mixed else 100)
            pod.labels = {**pod.labels, **labels}
            s.create(PODS, pod)
        sched.pump()
        return s, sched

    @staticmethod
    def _boom(*_a, **_kw):
        from jax.errors import JaxRuntimeError
        raise JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory "
                              "allocating 512MiB")

    def _assert_not_absorbed(self, sched, fallbacks_before):
        from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
        b = sched.algorithm.breaker
        assert b.faults_total == 0 and b.state == "closed"
        assert fam_count(ORACLE_FALLBACKS, "device-fault") == fallbacks_before

    @pytest.mark.parametrize("kernel,gang", [
        ("schedule_batch_uniform", False),     # uniform K-batch dispatch
        ("schedule_batch_segments", True),     # fused segmented window
    ])
    def test_dispatch_error_reaches_schedule_burst_caller(
            self, monkeypatch, kernel, gang):
        from jax.errors import JaxRuntimeError
        from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
        from kubernetes_tpu.ops import kernels as K
        before = fam_count(ORACLE_FALLBACKS, "device-fault")
        s, sched = self._world(gang=gang)
        monkeypatch.setattr(K, kernel, self._boom)
        with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
            sched.schedule_burst(max_pods=32)
        self._assert_not_absorbed(sched, before)
        assert not any(p.node_name for p in s.list(PODS)[0])

    def test_fetch_error_reaches_schedule_burst_caller(self, monkeypatch):
        from jax.errors import JaxRuntimeError
        from kubernetes_tpu.core import tpu_scheduler as T
        before = fam_count(T.ORACLE_FALLBACKS, "device-fault")
        s, sched = self._world()
        monkeypatch.setattr(T.jax, "device_get", self._boom)
        with pytest.raises(JaxRuntimeError):
            sched.schedule_burst(max_pods=32)
        self._assert_not_absorbed(sched, before)

    def test_serial_cycle_error_reaches_schedule_one_caller(
            self, monkeypatch):
        from jax.errors import JaxRuntimeError
        from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
        from kubernetes_tpu.ops import kernels as K
        before = fam_count(ORACLE_FALLBACKS, "device-fault")
        s, sched = self._world(n_pods=1)
        monkeypatch.setattr(K, "schedule_cycle", self._boom)
        with pytest.raises(JaxRuntimeError):
            sched.schedule_one(timeout=0.0)
        self._assert_not_absorbed(sched, before)

    def test_preempt_scan_error_reaches_caller(self, monkeypatch):
        from jax.errors import JaxRuntimeError
        from kubernetes_tpu.cache.node_info import NodeInfo
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        from kubernetes_tpu.ops import kernels as K
        from kubernetes_tpu.oracle.generic_scheduler import FitError
        node = mknode("n0")
        ni = NodeInfo(node)
        victim = mkpod("v", cpu=4000)
        victim.node_name = "n0"
        ni.add_pod(victim)
        incoming = mkpod("hi", cpu=4000, priority=10)
        err = FitError(incoming, 1, {"n0": ["InsufficientResource:cpu"]})
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        monkeypatch.setattr(K, "preemption_scan", self._boom)
        with pytest.raises(JaxRuntimeError):
            tpu.preempt(incoming, {"n0": ni}, ["n0"], err, [])
        assert tpu.breaker.faults_total == 0

    def test_pressure_wave_error_reaches_caller(self, monkeypatch):
        from jax.errors import JaxRuntimeError
        from kubernetes_tpu.ops import kernels as K
        from kubernetes_tpu.perf.harness import run_preempt_cell
        monkeypatch.setattr(K, "pressure_batch", self._boom)
        with pytest.raises(JaxRuntimeError):
            run_preempt_cell(4, 8, n_preemptors=2)

    @pytest.mark.parametrize("driver", ["uniform", "scan", "fused"])
    def test_injected_fault_at_the_same_seam_still_degrades(
            self, monkeypatch, driver):
        """The launch sequence's contract, one for the three drivers: a
        launch lost to an injected `device.fetch` fault decides nothing,
        books one fault and one `device-fault` fallback, leaves the walk
        counters where they were and an aborted flight record; the shell
        then binds every pod all the same."""
        from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
        from kubernetes_tpu.obs import flight
        before = fam_count(ORACLE_FALLBACKS, "device-fault")
        s, sched = self._world(gang=driver == "fused",
                               mixed=driver == "scan")
        algo = sched.algorithm
        entry = "schedule_burst_fused" if driver == "fused" \
            else "schedule_burst"
        real = getattr(algo, entry)
        first = []

        def spy(*a, **kw):
            walk = (algo.last_index, algo.last_node_index)
            out = real(*a, **kw)
            if not first:
                first.append((out,
                              (algo.last_index, algo.last_node_index) == walk,
                              flight.RECORDER.records()[-1]))
            return out
        monkeypatch.setattr(algo, entry, spy)
        chaos.plan(seed=0, rates={"device.fetch": 1.0}, limit=1)
        while sched.schedule_burst(max_pods=32):
            pass
        sched.pump()
        out, walk_kept, rec = first[0]
        assert rec.kind == driver and rec.blocks == []
        assert not out or not any(out)      # None, or no pod decided
        assert walk_kept
        assert rec.outcome["aborted"] \
            and not rec.outcome.get("hosts") \
            and not rec.outcome.get("segments")
        assert all(p.node_name for p in s.list(PODS)[0])
        assert sched.algorithm.breaker.faults_total == 1
        assert fam_count(ORACLE_FALLBACKS, "device-fault") == before + 1


# ---------------------------------------------------------------------------
# crash-restart warm recovery
# ---------------------------------------------------------------------------
class TestCrashRestartRecovery:
    """Round-13 acceptance: kill the scheduler mid-fused-burst (the
    sched.crash seam fires inside _commit_burst — after the single device
    fetch, between wave commits, on either side of the store write),
    recover() from the store, and the post-restart decision stream is
    bit-identical to an oracle that never crashed; no pod double-bound or
    lost. The seeds below are chosen to cover BOTH crash sides: the
    in-flight window landed (recover adopts, resumes at the post-window
    boundary) and not landed (recover re-queues, resumes at the
    pre-window boundary)."""

    N_NODES, N_PODS = 6, 24

    def _world(self, crash_seed, *, audit=None):
        import random
        from kubernetes_tpu.scheduler import Scheduler
        chaos.disable()
        s = Store(watch_log_size=65536)
        for i in range(self.N_NODES):
            # uneven zones: the NodeTree rotation recovery is exercised,
            # not just the walk counters
            n = mknode(f"n{i}")
            n.labels["failure-domain.beta.kubernetes.io/zone"] = f"z{i % 4}"
            s.create(NODES, n)
        sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=100)
        sched.algorithm.wave_size = 4   # several commit windows per burst
        sched.sync()
        rng = random.Random(7)
        for j in range(self.N_PODS):
            s.create(PODS, mkpod(f"p{j}",
                                 cpu=rng.choice([100, 200, 400, 800])))
        sched.pump()
        w = s.watch(PODS) if audit is not None else None
        report = None
        crashed = 0
        if crash_seed is not None:
            chaos.plan(seed=crash_seed, rates={"sched.crash": 0.3}, limit=1)
        while True:
            try:
                n = sched.schedule_burst(max_pods=16)
            except chaos.SchedulerCrash:
                crashed += 1
                chaos.disable()        # the restarted process has no plan
                report = sched.recover()
                continue
            if n == 0:
                break
            sched.pump()
        sched.pump()
        if w is not None:
            # no pod double-bound or lost: exactly ONE bind event per pod
            # reached the watch stream across crash + recovery + resume
            while True:
                ev = w.try_next()
                if ev is None:
                    break
                if ev.type == MODIFIED and ev.obj.node_name:
                    audit[ev.obj.key] = audit.get(ev.obj.key, 0) + 1
            w.stop()
        binds = sorted((p.key, p.node_name) for p in s.list(PODS)[0])
        return binds, report, crashed

    @pytest.fixture(scope="class")
    def oracle(self):
        """The never-crashed world's bindings (one build per class)."""
        binds, _, _ = self._world(None)
        assert all(n for _, n in binds)
        return binds

    # seed 2: the in-flight window LANDED before the crash (post-write
    # side); seed 5: it did NOT (pre-write side, 4 pods re-queued);
    # seed 8 crashes one window deeper on the pre-write side
    @pytest.mark.parametrize("seed", [2, 5, 8])
    def test_post_restart_stream_matches_oracle(self, seed, oracle):
        audit: dict = {}
        binds, report, crashed = self._world(seed, audit=audit)
        assert crashed == 1, "the crash seam never fired"
        assert report is not None and report["exact"], report
        assert binds == oracle
        assert audit == {k: 1 for k, _ in oracle}

    def test_both_crash_sides_covered(self):
        _, landed, _ = self._world(2)
        _, unlanded, _ = self._world(5)
        assert landed["window_landed"] is True and not landed["requeued"]
        assert unlanded["window_landed"] is False
        assert len(unlanded["requeued"]) == 4

    def test_serial_cycle_crash_recovers(self, oracle):
        """The serial bind path carries the same seams: a crash between
        decision and a landed bind recovers to the pre-decision boundary
        and the re-queued pod re-derives the identical decision."""
        import random
        from kubernetes_tpu.scheduler import Scheduler
        s = Store(watch_log_size=65536)
        for i in range(self.N_NODES):
            n = mknode(f"n{i}")
            n.labels["failure-domain.beta.kubernetes.io/zone"] = f"z{i % 4}"
            s.create(NODES, n)
        sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=100)
        sched.sync()
        rng = random.Random(7)
        for j in range(self.N_PODS):
            s.create(PODS, mkpod(f"p{j}",
                                 cpu=rng.choice([100, 200, 400, 800])))
        sched.pump()
        chaos.plan(seed=1, rates={"sched.crash": 0.1}, limit=1)
        crashed = 0
        for _ in range(4 * self.N_PODS):
            try:
                sched.schedule_one(timeout=0)
            except chaos.SchedulerCrash:
                crashed += 1
                chaos.disable()
                sched.recover()
            sched.pump()
            if all(p.node_name for p in s.list(PODS)[0]):
                break
        assert crashed == 1
        binds = sorted((p.key, p.node_name) for p in s.list(PODS)[0])
        assert binds == oracle


# ---------------------------------------------------------------------------
# tier-1 smoke: one differential fuzz trial per seam
# ---------------------------------------------------------------------------
SMOKE_SEAMS = ("device.dispatch", "device.fetch", "store.commit_wave",
               "store.commit_wave.ambiguous", "store.fanout",
               "native.commitcore", "native.heapcore", "watch.drop")


@pytest.mark.parametrize("seam", SMOKE_SEAMS)
def test_parity_smoke_one_trial_per_seam(seam):
    """Tier-1-speed chaos smoke: one mixed-workload differential fuzz
    trial per seam, that seam firing hot (0.6) and alone — bindings stay
    bit-identical to the clean oracle world, and the seam provably fired.
    The 42-trial blanket sweep lives in tests/sweep_chaos_seeds.py."""
    from tests.test_tpu_parity import TestMixedWorkloadShellFuzz
    from kubernetes_tpu.obs import flight
    before = sum(c.value for (label,), c in
                 chaos.INJECTIONS._children.items() if label == seam)
    flight.RECORDER.configure(mode="replay", capacity=64)
    flight.RECORDER.clear()
    try:
        TestMixedWorkloadShellFuzz().test_bindings_identical(
            11, 4, flight.RECORDER, chaos={seam: 0.6})
    finally:
        flight.RECORDER.configure(mode="digest")
        flight.RECORDER.clear()
    after = sum(c.value for (label,), c in
                chaos.INJECTIONS._children.items() if label == seam)
    assert after > before, f"seam {seam} never fired in the smoke trial"
