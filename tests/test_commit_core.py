"""Commit core (round 11): the native C++ batched store write + watch
fan-out behind the fused device pipeline, refereed by its pure-Python twin.

Pins the subsystem's contracts:
- native/twin parity: random op sequences produce BIT-IDENTICAL observable
  state (resourceVersions, missing keys, raises, per-watcher event
  streams, bucket contents) on `store/commit_core.PyCommitCore` and
  `native/commitcore.cpp`.
- the one-call-per-wave contract: `_commit_burst` performs exactly ONE
  store-write call (commit_wave) and ONE fan-out call (fanout_wave) per
  wave window.
- watch fan-out robustness: a slow consumer is dropped-with-resync
  (bounded backlog, ExpiredError, `watch_dropped_total{reason}`), never
  buffered unboundedly — and the informer recovers by re-listing.
- twin parity under chaos: the TestFusedWindowCrashInjection seam (store
  write dies between the single packed fetch and the first wave commit)
  replayed on a native-core store and a twin-core store lands identical
  bindings and identical pod watch streams.
- the drain/encode prologue twins: heapcore.pop_many vs the Python heap,
  and commitcore.class_signatures vs TPUScheduler._class_signature.
"""
import random
import shutil
import subprocess
import threading
import time

import pytest

from kubernetes_tpu import native
from kubernetes_tpu.api.types import (
    Affinity, Container, LabelSelector, Node, Pod, PodDisruptionBudget,
    Toleration,
)
from kubernetes_tpu.chaos import InjectedFault
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store.commit_core import PyCommitCore
from kubernetes_tpu.store.store import (
    WATCH_DROPPED, Store, AlreadyExistsError, ConflictError, Event,
    ExpiredError, NODES, NotFoundError, PDBS, PODS,
)
from kubernetes_tpu.utils.clock import FakeClock

GI = 1024 ** 3


def have_native() -> bool:
    return native.load("commitcore") is not None


def mknode(name, cpu=4000):
    return Node(name=name, labels={"kubernetes.io/hostname": name},
                allocatable={"cpu": cpu, "memory": 32 * GI, "pods": 110})


def mkpod(name, cpu=100, **kw):
    return Pod(name=name,
               containers=(Container.make(name="c", requests={"cpu": cpu}),),
               **kw)


# ---------------------------------------------------------------------------
# native/twin parity: random op sequences, observable state compared
# ---------------------------------------------------------------------------
class _Recorderless:
    """Apply one deterministic op stream to a store, recording every
    observable: results, raises, watch streams, bucket state."""

    def __init__(self, impl: str, seed: int, shared: bool = True):
        self.store = Store(watch_log_size=64, watch_queue_size=32,
                           commit_core=impl, shared_watch_classes=shared)
        # deterministic wire encoder for the byte-ring ops: both cores
        # (and both class modes) must stream identical bytes
        self.store.set_wire_encoder(
            lambda t, o, rv: f"{t}|{o.key}|{o.node_name}|{rv}".encode())
        self.rng = random.Random(seed)
        self.log = []
        self.watches = {}

    def snapshot_pods(self):
        return sorted((p.key, p.resource_version, p.node_name)
                      for p in self.store.list(PODS)[0])

    def op(self, kind, *args):
        try:
            out = getattr(self, "op_" + kind)(*args)
            self.log.append((kind, args, "ok", out))
        except (NotFoundError, AlreadyExistsError, ConflictError,
                ExpiredError, InjectedFault) as e:
            # InjectedFault: the chaos-armed sweep variant fires the
            # store.update_many / store.evict_many seams pre-land — the
            # raise itself is an observable both cores must share
            self.log.append((kind, args, type(e).__name__, None))

    def op_create(self, name):
        p = self.store.create(PODS, mkpod(name))
        return (p.key, p.resource_version)

    def op_update(self, name, rv):
        cur = self.store.get(PODS, f"default/{name}")
        cur.labels["gen"] = str(rv)
        out = self.store.update(PODS, cur, expect_rv=rv)
        return (out.key, out.resource_version)

    def op_delete(self, name):
        self.store.delete(PODS, f"default/{name}")
        return None

    def op_bind(self, name, node):
        out = self.store.bind_pod(f"default/{name}", node)
        return (out.key, out.resource_version, out.node_name)

    def op_bind_many(self, names, node):
        return self.store.bind_pods([(f"default/{n}", node) for n in names])

    def op_commit_wave(self, names, node):
        from kubernetes_tpu.store.record import EventRecorder
        rec = EventRecorder(self.store)
        pods = [mkpod(n) for n in names]
        recs = rec.make_pod_records(
            [(p, "Normal", "Scheduled", f"assigned {p.key} to {node}")
             for p in pods])
        # record names carry a process-global sequence: normalize them so
        # the two stores' streams stay comparable
        for i, r in enumerate(recs):
            r.name = f"rec-{len(self.log)}-{i}"
        missing = self.store.commit_wave(
            [(f"default/{n}", node) for n in names], recs)
        self.store.fanout_wave()
        return missing

    def op_commit_wave_binds(self, names, node):
        # the round-17 verb: Scheduled payloads built INSIDE the core
        # (native) / twin — rv assignment for the records rides the same
        # observable stream, so the compared logs pin identical
        # record-count and ordering behavior
        missing = self.store.commit_wave(
            [(f"default/{n}", node) for n in names],
            event_spec={"component": "parity-sched"})
        self.store.fanout_wave()
        return missing

    def op_advance_fence(self, scope, token):
        # the claim-handoff verb: monotonic max per scope, False when the
        # caller's token is already superseded (round 18)
        return self.store.advance_fence(f"fleet-par-s{scope}", token)

    def op_fenced_wave(self, names, node, scope, token):
        # a wave carrying a fencing token: a superseded token raises
        # FencedError (caught as a ConflictError subclass by op()) with
        # NOTHING landed — rv streams, bucket state, and watch sequences
        # must stay bit-identical across cores either way; rv-CAS
        # conflicts of re-bound pods ride the conflicts list
        confl: list = []
        missing = self.store.commit_wave(
            [(f"default/{n}", node) for n in names],
            event_spec={"component": "parity-sched"},
            fence=(f"fleet-par-s{scope}", token), conflicts=confl)
        self.store.fanout_wave()
        return (missing, confl)

    def op_update_many(self, specs, token=None, scope=None, ftoken=None):
        # the round-23 batched mutation verb: rv-CAS per item (0 = no
        # CAS), per-item conflict/missing reporting, optional fence
        # (whole-batch FencedError, caught as a ConflictError subclass)
        # and wave-style token dedupe — a replayed token answers the
        # recorded result without burning rvs
        updates = []
        for name, rv in specs:
            try:
                cur = self.store.get(PODS, f"default/{name}")
            except NotFoundError:
                cur = mkpod(name)   # pre-scan refuses it as missing
            cur.labels["gen"] = f"um-{rv}-{len(self.log)}"
            updates.append((cur, rv or None))
        fence = [(f"fleet-par-s{scope}", ftoken)] if scope is not None \
            else None
        confl: list = []
        miss: list = []
        out = self.store.update_many(PODS, updates, fence=fence,
                                     token=token, conflicts=confl,
                                     missing=miss)
        return ([(o.key, o.resource_version) for o in out], confl, miss)

    def op_create_pdb(self, name, budget):
        # empty selector matches everything in the namespace: the
        # budget gates op_evict_many refusals deterministically
        b = self.store.create(PDBS, PodDisruptionBudget(
            name=name, selector=LabelSelector.from_dict({}),
            disruptions_allowed=budget))
        return (b.key, b.resource_version)

    def op_evict_many(self, names, stop, token=None):
        # the round-23 batched PDB-charging eviction: per-item outcomes
        # (charges visible WITHIN the batch), stop_on_refusal tail-skip,
        # and token dedupe — all observable in the compared log, and the
        # charged-PDB MODIFIED + pod DELETED entries ride the rv stream
        out = self.store.evict_many([f"default/{n}" for n in names],
                                    stop_on_refusal=stop, token=token)
        return sorted(out.items())

    def op_watch(self, wid, since_rv, selector=None):
        self.watches[wid] = self.store.watch(PODS, since_rv=since_rv,
                                             selector=selector)
        return None

    def op_drain(self, wid):
        w = self.watches.get(wid)
        if w is None:
            return None
        return [(e.type, e.resource_version, e.obj.key, e.obj.node_name)
                for e in w.drain()]

    def op_drain_bytes(self, wid):
        # the serialize-once byte ring: wire lines instead of Events,
        # same cursor, same drop contract (round 20)
        w = self.watches.get(wid)
        if w is None:
            return None
        return w.drain_bytes()

    def op_stop_watch(self, wid):
        # detach moves a class refcount (round 20): classmates keep their
        # shared caches, the last member tears the class down
        w = self.watches.pop(wid, None)
        if w is not None:
            w.stop()
        return None

    def op_demote(self):
        # mid-program core demotion: watchers are adopted dropped-with-
        # resync and KEEP their (kind, selector) class membership (round
        # 20). On a twin-core store this is a twin->twin swap — the
        # observable contract (fresh log, resync raises, fences carried)
        # is identical, so the parity referee stays meaningful.
        with self.store._lock:
            self.store._demote_core()
        return None

    def op_rv(self):
        return self.store.resource_version()


def _random_program(seed: int, n_ops: int = 120):
    rng = random.Random(seed)
    names = [f"p{i}" for i in range(12)]
    prog = [("create", n) for n in rng.sample(names, 6)]
    prog.append(("watch", 0, None))
    for i in range(n_ops):
        r = rng.random()
        if r < 0.15:
            prog.append(("create", rng.choice(names)))
        elif r < 0.23:
            prog.append(("update", rng.choice(names),
                         rng.randint(1, 6) if rng.random() < 0.4 else 0))
        elif r < 0.32:
            # round 23: the batched mutation verb — plain, fenced, and
            # token-deduped variants all ride the compared stream (a
            # replayed token must answer the recorded result on BOTH
            # cores without burning rvs)
            specs = tuple((n, rng.randint(1, 6) if rng.random() < 0.4 else 0)
                          for n in rng.sample(names, rng.randint(1, 5)))
            roll = rng.random()
            if roll < 0.25:
                prog.append(("update_many", specs, None,
                             rng.randint(0, 2), rng.randint(1, 30)))
            elif roll < 0.45:
                prog.append(("update_many", specs,
                             f"um-tok-{rng.randint(0, 2)}"))
            else:
                prog.append(("update_many", specs))
        elif r < 0.39:
            prog.append(("delete", rng.choice(names)))
        elif r < 0.48:
            prog.append(("bind", rng.choice(names), f"n{rng.randint(0, 3)}"))
        elif r < 0.57:
            prog.append(("bind_many",
                         tuple(rng.sample(names, rng.randint(1, 5))),
                         f"n{rng.randint(0, 3)}"))
        elif r < 0.64:
            prog.append(("commit_wave",
                         tuple(rng.sample(names, rng.randint(1, 6))),
                         f"n{rng.randint(0, 3)}"))
        elif r < 0.69:
            prog.append(("commit_wave_binds",
                         tuple(rng.sample(names, rng.randint(1, 6))),
                         f"n{rng.randint(0, 3)}"))
        elif r < 0.72:
            # fenced-writer ops (round 18): fence advances interleave
            # with fenced waves so both STALE rejections (atomic, no rv)
            # and valid advances land in the compared stream
            prog.append(("advance_fence", rng.randint(0, 2),
                         rng.randint(1, 30)))
        elif r < 0.76:
            prog.append(("fenced_wave",
                         tuple(rng.sample(names, rng.randint(1, 4))),
                         f"n{rng.randint(0, 3)}",
                         rng.randint(0, 2), rng.randint(1, 30)))
        elif r < 0.78:
            # round 23: PDBs gate the batched evictions — low budgets
            # make refusals (and the within-batch charge overlay) common
            prog.append(("create_pdb", f"pdb{rng.randint(0, 1)}",
                         rng.randint(0, 3)))
        elif r < 0.83:
            # round 23: batched PDB-charging eviction — refused /
            # missing / skipped outcomes and the charged-PDB MODIFIED +
            # pod DELETED log entries are the compared observables
            ev = ["evict_many", tuple(rng.sample(names, rng.randint(1, 5))),
                  rng.random() < 0.5]
            if rng.random() < 0.2:
                ev.append(f"ev-tok-{rng.randint(0, 2)}")
            prog.append(tuple(ev))
        elif r < 0.875:
            # round 20: watches land in shared (kind, selector) classes —
            # repeated selectors make classmates, None joins the default
            # class, and resumes-from-rv must replay from the class cache
            prog.append(("watch", rng.randint(0, 3),
                         rng.randint(0, 40) if rng.random() < 0.5 else None,
                         rng.choice([None, "s0", "s0", "s1"])))
        elif r < 0.92:
            prog.append(("drain", rng.randint(0, 3)))
        elif r < 0.95:
            # byte-ring drains interleave with Event drains on the SAME
            # cursors (a stream serves either representation)
            prog.append(("drain_bytes", rng.randint(0, 3)))
        elif r < 0.965:
            prog.append(("stop_watch", rng.randint(0, 3)))
        elif r < 0.985:
            prog.append(("rv",))
        else:
            # mid-program core demotion: adoption must carry class
            # membership and the resync contract on both stores
            prog.append(("demote",))
    prog.append(("drain", 0))
    return prog


@pytest.mark.skipif(not have_native(), reason="commitcore did not build")
class TestNativeTwinParity:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_program_bit_identical(self, seed):
        """The referee contract: every observable of a random op stream —
        including update-expect_rv conflicts, duplicate creates, watch
        resumes from arbitrary rvs, and bounded-ring overflows — is
        bit-identical between the native core and the Python twin."""
        prog = _random_program(seed)
        runs = {}
        for impl in ("native", "twin"):
            h = _Recorderless(impl, seed)
            for op in prog:
                h.op(*op)
            runs[impl] = (h.log, h.snapshot_pods(),
                          h.store.resource_version(),
                          h.store.fence_table())
        # EventRecord uids/names were normalized; everything else must match
        assert runs["native"][1] == runs["twin"][1]
        assert runs["native"][2] == runs["twin"][2]
        assert runs["native"][0] == runs["twin"][0]
        # the round-18 fence tables advanced identically too
        assert runs["native"][3] == runs["twin"][3]

    def test_update_conflict_and_duplicate_create(self):
        for impl in ("native", "twin"):
            s = Store(commit_core=impl)
            s.create(PODS, mkpod("a"))
            with pytest.raises(AlreadyExistsError):
                s.create(PODS, mkpod("a"))
            cur = s.get(PODS, "default/a")
            with pytest.raises(ConflictError):
                s.update(PODS, cur, expect_rv=cur.resource_version + 7)
            # the failed create/update burned no rv
            assert s.resource_version() == cur.resource_version

    def test_create_many_partial_then_raise_matches(self):
        """create_many raising mid-batch leaves the earlier objects
        stored AND logged — identically on both cores."""
        streams = {}
        for impl in ("native", "twin"):
            s = Store(commit_core=impl)
            w = s.watch(PODS)
            with pytest.raises(AlreadyExistsError):
                s.create_many(PODS, [mkpod("x"), mkpod("y"), mkpod("x"),
                                     mkpod("z")])
            streams[impl] = [(e.type, e.resource_version, e.obj.key)
                             for e in w.drain()]
            assert sorted(p.key for p in s.list(PODS)[0]) == \
                ["default/x", "default/y"]
        assert streams["native"] == streams["twin"]


# ---------------------------------------------------------------------------
# the one-call-per-wave contract
# ---------------------------------------------------------------------------
class TestCommitWaveContract:
    def test_one_store_write_and_one_fanout_call_per_wave(self):
        """A burst committing in `wave_size` windows performs EXACTLY one
        commit_wave (batched bind + audit records) and one fanout_wave per
        window — the round-11 acceptance contract. 10 pods at wave_size 4
        -> 3 windows."""
        store = Store(watch_log_size=65536)
        for i in range(4):
            store.create(NODES, mknode(f"n{i}", cpu=100000))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.algorithm.wave_size = 4
        sched.sync()
        # warmup compile outside the counted window
        store.create(PODS, mkpod("warm"))
        sched.pump()
        assert sched.schedule_burst(max_pods=16) == 1
        for j in range(10):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        calls = {"commit": 0, "fanout": 0, "binds": 0}
        real_commit, real_fanout = store.commit_wave, store.fanout_wave

        def commit(bindings, events=None):
            calls["commit"] += 1
            calls["binds"] += len(bindings)
            return real_commit(bindings, events)

        def fanout():
            calls["fanout"] += 1
            return real_fanout()

        store.commit_wave, store.fanout_wave = commit, fanout
        assert sched.schedule_burst(max_pods=16) == 10
        assert calls["binds"] == 10
        assert calls["commit"] == 3, calls   # ceil(10 / wave_size=4)
        assert calls["fanout"] == 3, calls
        # every bind produced exactly one Scheduled audit record in-wave
        from kubernetes_tpu.store.store import EVENTS
        recs = [e for e in store.list(EVENTS)[0] if e.reason == "Scheduled"]
        assert len(recs) == 11  # warmup + 10

    def test_serial_path_untouched(self):
        """The serial _bind path keeps its per-pod verbs (bind_pod), so
        plugin-ful workloads never route through the wave call."""
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = Scheduler(store, use_tpu=False,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        called = []
        store.commit_wave = lambda *a, **kw: called.append(a)
        store.create(PODS, mkpod("s"))
        sched.pump()
        assert sched.schedule_one(timeout=0.0)
        assert store.get(PODS, "default/s").node_name == "n0"
        assert not called


# ---------------------------------------------------------------------------
# watch fan-out robustness (bounded queue + drop-with-resync)
# ---------------------------------------------------------------------------
class TestWatchFanoutRobustness:
    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_slow_consumer_dropped_with_resync(self, impl):
        if impl == "native" and not have_native():
            pytest.skip("commitcore did not build")
        store = Store(watch_log_size=4096, watch_queue_size=8,
                      commit_core=impl)
        fast = store.watch(PODS)
        slow = store.watch(PODS)
        base = WATCH_DROPPED.labels("slow-consumer").value
        # the fast consumer keeps copying out (backlog stays under the
        # ring bound); the slow one never does
        seen = 0
        for i in range(20):
            store.create(PODS, mkpod(f"b{i}"))
            if i % 4 == 3:
                seen += len(fast.drain())
        seen += len(fast.drain())
        assert seen == 20
        with pytest.raises(ExpiredError):
            slow.drain()
        # the drop was counted (by event) and the watch stays expired
        assert WATCH_DROPPED.labels("slow-consumer").value > base
        with pytest.raises(ExpiredError):
            slow.next(timeout=0)
        # a fresh watch resumes cleanly; the fast watcher never expired
        store.create(PODS, mkpod("c"))
        assert [e.obj.key for e in fast.drain()] == ["default/c"]

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_log_window_eviction_detected_at_poll(self, impl):
        if impl == "native" and not have_native():
            pytest.skip("commitcore did not build")
        """A wave whose PENDING entries overrun the log ring before the
        fan-out call: the poll itself detects the evicted cursor (the
        flush-time drops are the slow-consumer case above)."""
        store = Store(watch_log_size=4, watch_queue_size=100,
                      commit_core=impl)
        for i in range(8):
            store.create(PODS, mkpod(f"p{i}"))
        w = store.watch(PODS)
        base = WATCH_DROPPED.labels("log-window").value
        store.commit_wave([(f"default/p{i}", "n1") for i in range(8)], None)
        with pytest.raises(ExpiredError):
            w.drain()   # before fanout_wave: cursor already evicted
        assert WATCH_DROPPED.labels("log-window").value == base + 1

    def test_informer_recovers_by_relisting(self):
        """The consumer contract end to end: an informer whose watch is
        dropped re-lists (410 semantics) and converges to the true state."""
        from kubernetes_tpu.store.informer import SharedInformer
        store = Store(watch_log_size=4096, watch_queue_size=4)
        inf = SharedInformer(store, PODS)
        inf.sync()
        for i in range(50):
            store.create(PODS, mkpod(f"p{i}"))
        inf.pump()   # first poll raises ExpiredError internally -> relist
        assert len(inf.list()) == 50
        store.delete(PODS, "default/p0")
        inf.pump()
        assert len(inf.list()) == 49

    def test_blocked_next_wakes_on_stop(self):
        store = Store()
        w = store.watch(PODS)
        out = []
        t = threading.Thread(target=lambda: out.append(w.next(timeout=5)))
        t.start()
        time.sleep(0.05)
        w.stop()
        t.join(timeout=2)
        assert not t.is_alive() and out == [None]


# ---------------------------------------------------------------------------
# shared subscription classes + serialize-once byte ring (round 20)
# ---------------------------------------------------------------------------
class TestSharedSubscriptionClasses:
    """Watchers with identical (kind, selector) dedupe into one class:
    events materialize (and wire-encode) ONCE per class, classmates serve
    the shared objects/bytes, and the per-watcher drop-with-resync
    contract is untouched. The degenerate mode (shared_watch_classes=
    False) is the EXACT pre-round-20 per-watcher path — the differential
    referee below proves the refactor changed no observable."""

    def _skip_if_missing(self, impl):
        if impl == "native" and not have_native():
            pytest.skip("commitcore did not build")

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_differential_shared_vs_degenerate(self, impl):
        """The old-vs-new differential: the same random op programs (now
        mixing selector attaches, byte drains, detaches, and mid-program
        demotions) through shared-class fan-out and the degenerate
        class-per-watcher mode — every observable (results, raises, Event
        streams, wire-byte streams, bucket state, rv) bit-identical."""
        self._skip_if_missing(impl)
        for seed in range(3):
            prog = _random_program(seed)
            runs = {}
            for shared in (True, False):
                h = _Recorderless(impl, seed, shared=shared)
                for op in prog:
                    h.op(*op)
                runs[shared] = (h.log, h.snapshot_pods(),
                                h.store.resource_version())
            assert runs[True] == runs[False], f"seed {seed} diverged"

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_classmates_share_objects_and_bytes(self, impl):
        """Materialize-once is literal: classmates receive the SAME Event
        objects and the SAME wire-bytes objects (refcounted shares out of
        the class cache, not copies), and the core's fan-out stats book
        one materialization + one encode per event per class."""
        self._skip_if_missing(impl)
        store = Store(commit_core=impl)
        store.set_wire_encoder(
            lambda t, o, rv: f"{t}|{o.key}|{rv}".encode())
        a1 = store.watch(PODS, selector="app=a")
        a2 = store.watch(PODS, selector="app=a")
        b1 = store.watch(PODS, selector="app=a")
        b2 = store.watch(PODS, selector="app=a")
        store.create(PODS, mkpod("x"))
        store.create(PODS, mkpod("y"))
        e1, e2 = a1.drain(), a2.drain()
        assert [(e.type, e.obj.key) for e in e1] == \
            [("ADDED", "default/x"), ("ADDED", "default/y")]
        assert all(x is y for x, y in zip(e1, e2))   # shared, not equal
        l1, l2 = b1.drain_bytes(), b2.drain_bytes()
        assert l1 == [b"ADDED|default/x|1", b"ADDED|default/y|2"]
        assert all(x is y for x, y in zip(l1, l2))
        st = store.watch_plane_state()
        assert len(st["classes"]) == 1
        assert st["classes"][0]["members"] == 4
        assert st["materializations"] == 2    # once per event per class
        assert st["line_encodes"] == 2
        assert st["shared_hits"] == 4         # a2's 2 events + b2's 2 lines
        assert st["bytes_served"] == sum(len(x) for x in l1) * 2

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_slow_classmate_dropped_fast_unaffected_threaded(self, impl):
        """The threaded copy-out stress: two classmates drain at wildly
        different rates while the writer commits — the slow one is
        dropped-with-resync at the ring bound, the fast one sees every
        event in order and keeps streaming afterwards."""
        self._skip_if_missing(impl)
        store = Store(watch_log_size=4096, watch_queue_size=64,
                      commit_core=impl)
        fast = store.watch(PODS, selector="cls")
        slow = store.watch(PODS, selector="cls")
        got: list = []

        def drainer():
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                got.extend(fast.drain())
                if len(got) >= 200:
                    return
                time.sleep(0.0005)

        t = threading.Thread(target=drainer)
        t.start()
        for i in range(200):
            store.create(PODS, mkpod(f"p{i}"))
            if i % 16 == 15:
                # let the fast classmate catch up: a pause, and on a
                # loaded machine as many more as its thread needs to come
                # within a batch of the writer (bounded; the slow one
                # never drains and is dropped all the same)
                caught_up = time.monotonic() + 5
                time.sleep(0.002)
                while len(got) < i - 15 and time.monotonic() < caught_up:
                    time.sleep(0.002)
        t.join(timeout=12)
        assert not t.is_alive()
        assert len(got) == 200
        assert [e.obj.key for e in got] == \
            [f"default/p{i}" for i in range(200)]
        # the slow classmate fell past the ring bound and was dropped —
        # WITHOUT disturbing its classmate's stream above
        with pytest.raises(ExpiredError):
            slow.drain()
        # the fast classmate is still live after the classmate's drop
        store.create(PODS, mkpod("after"))
        assert [e.obj.key for e in fast.drain()] == ["default/after"]

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_adoption_carries_class_membership(self, impl):
        """Core demotion: adopted watchers keep their (kind, selector)
        class membership (members/refcounts intact in the twin), every
        adopted watcher still raises ExpiredError once (the resync
        contract), and detach after adoption unwinds the right class."""
        self._skip_if_missing(impl)
        store = Store(commit_core=impl)
        w1 = store.watch(PODS, selector="a")
        w2 = store.watch(PODS, selector="a")
        w3 = store.watch(PODS)
        store.create(PODS, mkpod("x"))
        with store._lock:
            store._demote_core()
        assert store.core_impl == "twin"
        st = store.watch_plane_state()
        members = {r["selector"]: r["members"] for r in st["classes"]}
        assert members == {"a": 2, "": 1}
        for w in (w1, w2, w3):
            with pytest.raises(ExpiredError):
                w.drain()
        # detach decrements the ADOPTED class; the last member tears the
        # class down
        w1.stop()
        w2.stop()
        st = store.watch_plane_state()
        assert {r["selector"] for r in st["classes"]} == {""}
        # a re-listed consumer joins fresh and streams normally
        w4 = store.watch(PODS, selector="a")
        store.create(PODS, mkpod("y"))
        assert [e.obj.key for e in w4.drain()] == ["default/y"]

    @pytest.mark.parametrize("impl", ["native", "twin"])
    def test_lag_observed_once_per_class(self, impl):
        """The ledger/lag contract after the refactor: the fan-out sink
        fires for MATERIALIZATIONS (once per event per class), so the lag
        histogram books each event once per class — not once per
        classmate (the old per-watcher arithmetic)."""
        self._skip_if_missing(impl)
        from kubernetes_tpu.store.store import WATCH_FANOUT_LAG
        store = Store(commit_core=impl)
        child = WATCH_FANOUT_LAG.labels(store.core_impl)
        ws = [store.watch(PODS, selector="app=a") for _ in range(3)]
        before = child.count
        store.create(PODS, mkpod("x"))
        store.create(PODS, mkpod("y"))
        for w in ws:
            assert len(w.drain()) == 2
        # 2 events, ONE class: the first classmate's drain materialized
        # (and stamped) both; the other drains were shared hits
        assert child.count == before + 2
        for w in ws:
            w.stop()


# ---------------------------------------------------------------------------
# twin parity under chaos (TestFusedWindowCrashInjection seam)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(not have_native(), reason="commitcore did not build")
class TestChaosTwinParity:
    def _run(self, impl: str):
        """The round-10 crash seam on a given core: the store write dies
        between the single packed fetch and the FIRST wave commit; the
        retry lands everything. Returns (bindings map, pod watch stream,
        rv)."""
        clock = FakeClock(100.0)
        store = Store(watch_log_size=65536, commit_core=impl)
        for i in range(4):
            store.create(NODES, mknode(f"n{i}"))
        stream_watch = store.watch(PODS)
        sched = Scheduler(store, use_tpu=True, clock=clock,
                          percentage_of_nodes_to_score=100)
        sched.algorithm.wave_size = 3
        sched.fused_run_split = 3
        sched.sync()
        for j in range(8):
            store.create(PODS, mkpod(f"s{j}", cpu=200))
        sched.pump()
        real_commit_wave = store.commit_wave
        calls = {"n": 0}

        def crashing_commit_wave(bindings, events=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("store write failed mid-commit")
            return real_commit_wave(bindings, events)

        store.commit_wave = crashing_commit_wave
        for _round in range(40):
            sched.pump()
            while sched.schedule_burst(max_pods=16):
                pass
            sched.pump()
            if all(p.node_name for p in store.list(PODS)[0]):
                break
            clock.step(61.0)
            sched.queue.flush()
        assert calls["n"] >= 2
        bound = sorted((p.key, p.node_name) for p in store.list(PODS)[0])
        stream = [(e.type, e.obj.key, e.obj.node_name)
                  for e in stream_watch.drain()]
        return bound, stream, store.resource_version()

    def test_native_and_twin_land_identical_state(self):
        native_run = self._run("native")
        twin_run = self._run("twin")
        assert native_run[0] == twin_run[0]      # final bindings
        assert native_run[1] == twin_run[1]      # pod watch sequence
        assert native_run[2] == twin_run[2]      # resourceVersion stream


# ---------------------------------------------------------------------------
# drain/encode prologue twins
# ---------------------------------------------------------------------------
class TestPrologueTwins:
    def test_heap_pop_many_matches_serial_pops(self):
        from kubernetes_tpu.utils.heap import KeyedHeap, NumericKeyedHeap
        rng = random.Random(7)
        items = [(f"k{i}", (rng.randint(-5, 5), rng.random(), float(i)))
                 for i in range(200)]
        h1 = NumericKeyedHeap(key_fn=lambda it: it[0],
                              triple_fn=lambda it: it[1])
        h2 = KeyedHeap(key_fn=lambda it: it[0],
                       less_fn=lambda a, b: a[1] < b[1])
        for it in items:
            h1.add(it)
            h2.add(it)
        while len(h1):
            k = rng.randint(1, 16)
            got = h1.pop_many(k)
            want = [h2.pop() for _ in range(len(got))]
            assert [g[0] for g in got] == [w[0] for w in want]
        assert h2.pop() is None and h1.pop_many(4) == []

    def test_pop_burst_numbering_matches_pop(self):
        from kubernetes_tpu.queue.scheduling_queue import PriorityQueue
        q1, q2 = PriorityQueue(), PriorityQueue()
        for i in range(10):
            p = mkpod(f"p{i}")
            q1.add(p)
            q2.add(p)
        burst = q1.pop_burst(6)
        serial = []
        for _ in range(6):
            pod = q2.pop(timeout=0)
            serial.append((pod.key, q2.scheduling_cycle))
        assert [(p.key, c) for p, c in burst] == serial
        assert q1.scheduling_cycle == q2.scheduling_cycle

    def test_class_signatures_batch_matches_static(self):
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        from kubernetes_tpu.api.types import (
            NodeAffinity, NodeSelectorTerm, Requirement)
        pods = [
            mkpod("plain"),
            mkpod("labeled", labels={"b": "2", "a": "1"}),
            mkpod("selector", node_selector={"zone": "z1", "arch": "amd"}),
            mkpod("tolerant",
                  tolerations=(Toleration(key="k", op="Exists",
                                          effect="NoSchedule"),)),
            mkpod("affine", affinity=Affinity(node_affinity=NodeAffinity(
                required=(NodeSelectorTerm(match_expressions=(
                    Requirement(key="x", op="In", values=("1",)),)),)))),
        ]
        batched = TPUScheduler.class_signatures(pods)
        for p, sig in zip(pods, batched):
            assert sig == TPUScheduler._class_signature(p)
        # equality grouping is what the burst prologue consumes
        twins = [mkpod("plain2"), mkpod("plain3")]
        sigs = TPUScheduler.class_signatures(twins)
        assert sigs[0] == sigs[1]


# ---------------------------------------------------------------------------
# commit_wave_binds: in-core Scheduled-record construction (round 17)
# ---------------------------------------------------------------------------
class TestCommitWaveBinds:
    """The native core builds a landed binding's Scheduled payload itself
    (zero per-pod Python on the commit thread); the twin is the referee.
    Field-for-field record parity, seq0+i naming, vanished-pod skips, and
    the store-level event_spec plumbing are pinned here."""

    def _run_core(self, impl, bindings, present, seq0=100):
        from kubernetes_tpu.api.types import EventRecord
        from kubernetes_tpu.store.commit_core import make_commit_core
        from kubernetes_tpu.store.store import (AlreadyExistsError as AE,
                                                Event as Ev,
                                                ExpiredError as EE)
        core = make_commit_core(64, 64, Ev, EE, AE, force=impl)
        pods = {}
        core.create_batch(pods, PODS,
                          [mkpod(n) for n in present], False)
        evs: dict = {}
        missing = core.commit_wave_binds(
            pods, PODS, bindings, evs, "events", EventRecord,
            "sched-x", seq0)
        recs = sorted(evs.values(), key=lambda r: r.resource_version)
        return (list(missing),
                [(r.name, r.namespace, r.involved_kind, r.involved_key,
                  r.type, r.reason, r.message, r.count, r.component)
                 for r in recs],
                core.rv())

    @pytest.mark.skipif(not have_native(), reason="commitcore did not build")
    def test_native_twin_record_parity_with_vanished_pod(self):
        bindings = [(f"default/p{i}", f"n{i % 3}") for i in range(6)]
        present = [f"p{i}" for i in range(6) if i not in (2, 4)]
        native_out = self._run_core("native", bindings, present)
        twin_out = self._run_core("twin", bindings, present)
        assert native_out == twin_out
        missing, recs, _rv = native_out
        assert sorted(missing) == ["default/p2", "default/p4"]
        # binding i names its record seq0+i; vanished pods consume their
        # seq but emit nothing
        names = [r[0] for r in recs]
        assert names == [f"p{i}.{100 + i:x}" for i in (0, 1, 3, 5)]
        assert recs[0][6] == "Successfully assigned default/p0 to n0"
        assert all(r[2] == "Pod" and r[4] == "Normal"
                   and r[5] == "Scheduled" and r[7] == 1
                   and r[8] == "sched-x" for r in recs)

    def test_event_spec_matches_prebuilt_records(self):
        """Store.commit_wave(event_spec=...) lands records identical (up
        to the reserved name seq) to the classic prebuilt-recs path."""
        from kubernetes_tpu.store.store import EVENTS

        def run(use_spec):
            s = Store(watch_log_size=1 << 12)
            for i in range(3):
                s.create(PODS, mkpod(f"p{i}"))
            bindings = [(f"default/p{i}", "n0") for i in range(3)]
            if use_spec:
                missing = s.commit_wave(bindings,
                                        event_spec={"component": "cw"})
            else:
                from kubernetes_tpu.api.types import EventRecord
                from kubernetes_tpu.store.record import (
                    build_scheduled_records, reserve_seq)
                recs = build_scheduled_records(
                    EventRecord, bindings, "cw", reserve_seq(3))
                missing = s.commit_wave(bindings, recs)
            s.fanout_wave()
            assert missing == []
            return sorted(
                (e.name.rsplit(".", 1)[0], e.namespace, e.involved_key,
                 e.type, e.reason, e.message, e.count, e.component)
                for e in s.list(EVENTS)[0])

        assert run(True) == run(False)

    def test_event_spec_dedupe_token_replays(self):
        """A retried wave under the same token must not double-emit its
        in-core-built records."""
        from kubernetes_tpu.store.store import EVENTS
        s = Store(watch_log_size=1 << 12)
        s.create(PODS, mkpod("p0"))
        bindings = [("default/p0", "n0")]
        m1 = s.commit_wave(bindings, event_spec={"component": "cw"},
                           token="t1")
        m2 = s.commit_wave(bindings, event_spec={"component": "cw"},
                           token="t1")
        assert m1 == m2 == []
        assert len(s.list(EVENTS)[0]) == 1


# ---------------------------------------------------------------------------
# native.load hardening: ASan build mode
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestAsanBuildMode:
    def test_asan_instrumented_cores_pass_a_stress_run(self, tmp_path):
        """KTPU_NATIVE_ASAN=1 builds both extensions with AddressSanitizer
        (separate cached artifact) and a preloaded-runtime subprocess
        exercises the hot paths — heap churn, commit waves, watcher
        overflow, threaded copy-out — so a native memory bug aborts THIS
        test with an ASan report instead of corrupting a production heap."""
        if shutil.which("g++") is None:
            pytest.skip("g++ not available")
        libasan = subprocess.run(
            ["gcc", "-print-file-name=libasan.so"],
            capture_output=True, text=True).stdout.strip()
        if not libasan or "/" not in libasan:
            pytest.skip("libasan not available")
        import os
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "asan_stress.py"
        script.write_text(f"""
import sys, threading
sys.path.insert(0, {repo!r})
from kubernetes_tpu import native
h = native.load("heapcore")
c = native.load("commitcore")
assert h is not None and c is not None, "asan build failed"
assert native._so_path("heapcore").endswith(
    "_asan" + native.sysconfig.get_config_var("EXT_SUFFIX"))
hh = h.HeapCore()
for i in range(2000):
    hh.add("k%d" % (i % 500), float(i % 13), float(i), 0.0, (i,))
drained = hh.pop_many(10000)
assert len(drained) == 500, len(drained)
from kubernetes_tpu.store.store import Store, PODS, ExpiredError
from kubernetes_tpu.api.types import Pod
s = Store(watch_log_size=256, watch_queue_size=16)
assert s.core_impl == "native"
fast = s.watch(PODS)
slow = s.watch(PODS)
got = []
def consume():
    while True:
        ev = fast.next(timeout=0.2)
        if ev is None:
            return
        got.append(ev.resource_version)
t = threading.Thread(target=consume)
t.start()
for i in range(200):
    s.create(PODS, Pod(name="p%d" % i))
missing = s.commit_wave([("default/p%d" % i, "n1") for i in range(200)]
                        + [("default/ghost", "n1")], None)
s.fanout_wave()
assert missing == ["default/ghost"], missing
t.join(5)
try:
    slow.drain()
    raise SystemExit("slow consumer was never dropped")
except ExpiredError:
    pass
print("ASAN-STRESS-OK", len(got))
""")
        env = dict(os.environ,
                   KTPU_NATIVE_ASAN="1",
                   LD_PRELOAD=libasan,
                   ASAN_OPTIONS="detect_leaks=0:verify_asan_link_order=0")
        # -S skips the site/jax preamble: ASan slows the interpreter and
        # the stress needs none of it
        proc = subprocess.run([sys.executable, "-S", str(script)],
                              capture_output=True, text=True, timeout=300,
                              env=env, cwd=repo)
        if proc.returncode != 0 and "cannot be preloaded" in proc.stderr:
            pytest.skip("libasan preload unsupported in this environment")
        assert proc.returncode == 0, (proc.stdout[-1000:],
                                      proc.stderr[-2000:])
        assert "ASAN-STRESS-OK" in proc.stdout
        assert "AddressSanitizer" not in proc.stderr
