"""Round-17 encode-at-admission pod-row cache: the bit-identity contract
(cached row == fresh encode, field for field), invalidation on
update/delete/recreate, interned signatures, capacity bounding, and the
batched-ingest plumbing around it (informer add-runs -> queue.add_many ->
heap push_many; gated Store.create_many; Histogram.observe_batch edges;
the ledger's finalize-on-delete leak fix)."""
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Affinity, Container, ContainerPort, LabelSelector, Node,
    PodAffinityTerm, PodAntiAffinity, Pod, Toleration, NO_SCHEDULE,
)
from kubernetes_tpu.ops.pod_rows import (
    PodRowCache, encode_row, pod_class_signature,
)
from kubernetes_tpu.store.store import (
    NODES, PODS, BackpressureError, Store,
)

GI = 1024 ** 3
LABEL_HOSTNAME = "kubernetes.io/hostname"


def mkpod(name, cpu=100, rv=0, **kw):
    p = Pod(name=name,
            containers=(Container.make(name="c", requests={"cpu": cpu}),),
            **kw)
    p.resource_version = rv
    return p


def fuzz_pod(rng, j):
    """A pod drawn from the serve fuzz's class mix (plus scalars and
    init containers, which exercise the req-vs-upd split)."""
    cls = rng.choice(["plain", "plain", "selector", "tolerate", "anti",
                      "port", "prio", "scalar", "init"])
    kw = {"labels": {"app": cls, "j": str(j % 3)}}
    reqs = {"cpu": rng.choice([100, 300, 700]), "memory": GI}
    if cls == "selector":
        kw["node_selector"] = {"disk": "ssd"}
    elif cls == "tolerate":
        kw["tolerations"] = (Toleration(key="ded", value="x",
                                        effect=NO_SCHEDULE),)
    elif cls == "anti":
        kw["affinity"] = Affinity(pod_anti_affinity=PodAntiAffinity(
            required=(PodAffinityTerm(
                label_selector=LabelSelector(
                    match_labels=(("app", "anti"),)),
                topology_key=LABEL_HOSTNAME),)))
    elif cls == "port":
        kw["containers"] = (Container.make(
            name="c", requests=dict(reqs),
            ports=(ContainerPort(host_port=8000 + j % 7,
                                 container_port=80),)),)
    elif cls == "prio":
        kw["priority"] = rng.randint(1, 5)
    elif cls == "scalar":
        reqs["example.com/gpu"] = rng.randint(1, 3)
    elif cls == "init":
        kw["init_containers"] = (Container.make(
            name="i", requests={"cpu": 2000}),)
    if "containers" not in kw:
        kw["containers"] = (Container.make(name="c", requests=reqs),)
    p = Pod(name=f"f{j}", **kw)
    p.resource_version = rng.randint(1, 1000)
    return p


class TestRowBitIdentity:
    def test_cached_row_equals_fresh_encode_fuzz(self):
        """THE contract: for a fuzzed pod population, every cached row is
        field-for-field identical to a fresh encode_row — including after
        update-in-place re-encodes."""
        rng = random.Random(7)
        rc = PodRowCache()
        pods = [fuzz_pod(rng, j) for j in range(120)]
        rc.insert_many(pods)
        # random updates: bump rv + mutate spec, re-deliver
        for p in rng.sample(pods, 40):
            p.resource_version += 1
            p.priority += 10
            p.labels["upd"] = "y"
            rc.insert(p)
        for p in pods:
            cached = rc.lookup_row(p)
            fresh = encode_row(p)
            # interned signature must EQUAL the canonical tuple
            assert cached.pop("signature") == fresh.pop("signature"), p
            assert cached == fresh, (p.name, cached, fresh)

    def test_signatures_interned_and_identical(self):
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        rng = random.Random(3)
        rc = PodRowCache()
        pods = [fuzz_pod(rng, j) for j in range(60)]
        rc.insert_many(pods)
        sigs = rc.signatures(pods)
        ref = TPUScheduler.class_signatures(pods)
        assert sigs == ref
        # equal sigs are the SAME object (interning)
        by_val = {}
        for s in sigs:
            assert by_val.setdefault(s, s) is s

    def test_gather_matches_predicates(self):
        from kubernetes_tpu.api.types import (get_container_ports,
                                              has_pod_affinity_terms)
        rng = random.Random(11)
        rc = PodRowCache()
        pods = [fuzz_pod(rng, j) for j in range(50)]
        rc.insert_many(pods)
        g = rc.gather(pods, ("has_aff_terms", "has_ports", "has_volumes"))
        assert g is not None
        for i, p in enumerate(pods):
            assert bool(g["has_aff_terms"][i]) == has_pod_affinity_terms(p)
            assert bool(g["has_ports"][i]) == bool(get_container_ports(p))
            assert bool(g["has_volumes"][i]) == bool(p.volumes)

    def test_gather_returns_none_on_any_miss(self):
        rc = PodRowCache()
        a, b = mkpod("a", rv=1), mkpod("b", rv=1)
        rc.insert(a)
        assert rc.gather([a, b]) is None          # b never delivered
        rc.insert(b)
        assert rc.gather([a, b]) is not None
        b.resource_version = 2                     # stale
        assert rc.gather([a, b]) is None


class TestInvalidation:
    def test_update_in_place_same_uid_new_rv(self):
        rc = PodRowCache()
        p = mkpod("p", cpu=100, rv=1)
        rc.insert(p)
        assert rc.lookup_row(p)["req_cpu"] == 100
        # spec change lands as a new rv on the SAME uid
        p2 = p.clone()
        p2.resource_version = 2
        p2.containers = (Container.make(name="c", requests={"cpu": 900}),)
        assert p2.uid == p.uid
        rc.insert(p2)
        assert rc.lookup_row(p2)["req_cpu"] == 900
        # the OLD rv is now stale: lookup falls back to a fresh encode of
        # the old object (still correct — contract, not cache)
        assert rc.lookup_row(p)["req_cpu"] == 100
        assert len(rc) == 1

    def test_delete_then_recreate_same_name(self):
        rc = PodRowCache()
        p = mkpod("same", cpu=100, rv=1)
        rc.insert(p)
        rc.invalidate(p)
        assert len(rc) == 0
        # recreate under the same NAME: a fresh Pod object gets a fresh
        # uid, so the old row can never serve the new pod
        p2 = mkpod("same", cpu=700, rv=9)
        assert p2.uid != p.uid
        rc.insert(p2)
        assert rc.lookup_row(p2)["req_cpu"] == 700
        assert rc.lookup_row(p)["req_cpu"] == 100   # fresh-encode fallback
        assert len(rc) == 1

    def test_capacity_bound_evicts_oldest(self):
        rc = PodRowCache(capacity=8)
        pods = [mkpod(f"p{i}", cpu=100 + i, rv=1) for i in range(12)]
        for p in pods:
            rc.insert(p)
        assert len(rc) == 8
        # evicted pods decay to the miss path, with correct values
        for p in pods[:4]:
            assert rc.lookup_row(p)["req_cpu"] == \
                encode_row(p)["req_cpu"]

    def test_slot_reuse_after_invalidate(self):
        rc = PodRowCache()
        pods = [mkpod(f"p{i}", rv=1) for i in range(20)]
        rc.insert_many(pods)
        for p in pods[::2]:
            rc.invalidate(p)
        fresh = [mkpod(f"q{i}", cpu=333, rv=1) for i in range(10)]
        rc.insert_many(fresh)
        for p in fresh:
            assert rc.lookup_row(p)["req_cpu"] == 333
        for p in pods[1::2]:
            assert rc.lookup_row(p)["req_cpu"] == 100


def _interned(rc, pods):
    """The interned signature object behind each pod's slot."""
    return [rc._sigs[rc._sig_id[rc._slot_of[p.uid][0]]] for p in pods]


def _same_state(run, single):
    """A cache filled a run at a time against one filled pod by pod: same
    slots in the same eviction order, same id column, same intern table."""
    assert list(run._slot_of.items()) == list(single._slot_of.items())
    assert run._free == single._free
    assert run._cap == single._cap
    assert run._sig_id.tolist() == single._sig_id.tolist()
    assert run._profile_id.tolist() == single._profile_id.tolist()
    assert run._sigs == single._sigs


def _encodes():
    from kubernetes_tpu.ops.pod_rows import ROW_CACHE_ENCODES
    return {k: ROW_CACHE_ENCODES.labels(k).value
            for k in ("signature", "columns")}


class TestDeliveryStoresTheSignature:
    """PR 49: delivery derives what a drain pass reads — the interned
    signature of a run in one pass (and the profile index where the cache
    has a resolver) — and every other field only when it is asked for."""

    @pytest.mark.parametrize("profiled", [False, True])
    def test_a_run_equals_its_pods_one_by_one(self, profiled):
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        rng = random.Random(17)
        pods = [fuzz_pod(rng, j) for j in range(1500)]   # past cap0: grows
        for j, p in enumerate(pods):
            p.scheduler_name = ("tenant", "default-scheduler", "other")[j % 3]
        fn = ({"default-scheduler": 0, "tenant": 1}.get if profiled
              else None)
        run, single = PodRowCache(profile_fn=fn), PodRowCache(profile_fn=fn)
        run.insert_many(pods)
        for p in pods:
            single.insert(p)
        _same_state(run, single)
        sigs = run.signatures(pods)
        assert sigs == TPUScheduler.class_signatures(pods)
        assert sigs == [pod_class_signature(p) for p in pods]
        # the SAME interned objects: one object a distinct value, and it is
        # the one the slot holds
        by_val = {}
        for got, held in zip(sigs, _interned(run, pods)):
            assert got is held
            assert by_val.setdefault(got, got) is got
        want = [0 if fn is None else (fn(p.scheduler_name) or 0)
                for p in pods]
        assert run.gather(pods, ("profile_id",))["profile_id"].tolist() \
            == want

    def test_a_run_that_holds_an_update_in_place(self):
        """The same uid twice in one run (an add and its update, two
        updates): the slot keeps the LAST delivery, as pod by pod."""
        rng = random.Random(23)
        pods = [fuzz_pod(rng, j) for j in range(40)]
        updated = []
        for p in pods[5:25:3]:
            q = p.clone()
            q.resource_version = p.resource_version + 1
            q.labels["upd"] = "y"
            updated.append(q)
        seq = pods + updated + [pods[0]]
        run, single = PodRowCache(), PodRowCache()
        run.insert_many(seq[:10])
        run.insert_many(seq[10:])
        for p in seq:
            single.insert(p)
        _same_state(run, single)
        assert len(run) == len(pods)
        for q in updated:
            assert run._slot_of[q.uid][1] == q.resource_version
            assert run.signatures([q])[0] is _interned(run, [q])[0]
            assert run.signatures([q])[0] == pod_class_signature(q)
        # the re-delivered first pod moved to the young end
        assert next(reversed(run._slot_of)) == pods[0].uid

    @pytest.mark.parametrize("capacity,first,second", [
        (8, 12, 0),      # one run crosses capacity, evicting its own head
        (8, 5, 9),       # a second run evicts the first's pods, then its own
        (16, 16, 3),     # exactly full, then past it
    ])
    def test_a_run_that_crosses_capacity(self, capacity, first, second):
        rng = random.Random(29)
        pods = [fuzz_pod(rng, j) for j in range(first + second)]
        run = PodRowCache(capacity=capacity)
        single = PodRowCache(capacity=capacity)
        run.insert_many(pods[:first])
        if second:
            run.insert_many(pods[first:])
        for p in pods:
            single.insert(p)
        _same_state(run, single)
        assert len(run) == capacity
        kept = pods[-capacity:]
        assert list(run._slot_of) == [p.uid for p in kept]
        assert run.signatures(kept) == [pod_class_signature(p) for p in kept]
        # a freed slot nobody retook reads as free
        live = {slot for slot, _rv in run._slot_of.values()}
        for slot in range(run._cap):
            assert (run._sig_id[slot] >= 0) == (slot in live)
        # an evicted pod decays to the miss path, still right
        for p in pods[:-capacity]:
            assert run.lookup_row(p) == encode_row(p)

    def test_native_batch_and_python_twin_intern_to_the_same_objects(
            self, monkeypatch):
        from kubernetes_tpu import native
        from kubernetes_tpu.ops import pod_rows
        if native.load("commitcore") is None:
            pytest.skip("commit core not built: " +
                        str(native.load_error("commitcore")))
        rng = random.Random(31)
        pods = [fuzz_pod(rng, j) for j in range(200)]
        twins = [p.clone() for p in pods]     # equal specs, other objects
        for t in twins:
            t.uid += "-twin"
        rc = PodRowCache()
        rc.insert_many(pods)                   # the native batch
        interned = len(rc._sigs)
        real_load = native.load
        monkeypatch.setattr(
            native, "load",
            lambda name: None if name == "commitcore" else real_load(name))
        assert pod_rows.class_signatures(twins) \
            == [pod_class_signature(t) for t in twins]
        rc.insert_many(twins)                  # the Python twin
        monkeypatch.undo()
        assert len(rc._sigs) == interned       # nothing new to intern
        for a, b in zip(rc.signatures(pods), rc.signatures(twins)):
            assert a is b

    @pytest.mark.parametrize("profiled", [False, True])
    def test_gather_of_every_field_equals_encode_row(self, profiled):
        from kubernetes_tpu.ops.pod_rows import _BOOL_FIELDS, _I64_FIELDS
        rng = random.Random(37)
        fn = {"default-scheduler": 0, "tenant": 2}.get if profiled else None
        pods = [fuzz_pod(rng, j) for j in range(80)]
        for j, p in enumerate(pods):
            if j % 4 == 0:
                p.scheduler_name = "tenant"
        rc = PodRowCache(profile_fn=fn)
        rc.insert_many(pods)
        g = rc.gather(pods, _I64_FIELDS + _BOOL_FIELDS)
        rows = [encode_row(p, fn) for p in pods]
        for f in _I64_FIELDS:
            assert g[f].dtype == np.int64
            assert g[f].tolist() == [r[f] for r in rows], f
        for f in _BOOL_FIELDS:
            assert g[f].dtype == np.bool_
            assert g[f].tolist() == [r[f] for r in rows], f
        for p, r in zip(pods, rows):
            got = rc.lookup_row(p)
            assert got == r
            assert got["signature"] is _interned(rc, [p])[0]
            assert all(type(got[f]) is int for f in _I64_FIELDS)
            assert all(type(got[f]) is bool for f in _BOOL_FIELDS)

    def test_counter_books_what_was_derived(self):
        rc = PodRowCache()
        pods = [mkpod(f"c{j}", rv=1) for j in range(30)]
        c0 = _encodes()
        rc.insert_many(pods[:20])
        rc.insert(pods[20])
        rc.insert_many([])
        c1 = _encodes()
        # once a delivered pod, and no column derived at delivery
        assert c1["signature"] - c0["signature"] == 21
        assert c1["columns"] == c0["columns"]
        # a drain pass's reads derive no column either
        rc.signatures(pods)
        assert rc.gather(pods[:21], ("profile_id",)) is not None
        assert _encodes() == c1
        # a field asked for is derived then, a pod at a time
        rc.lookup_row(pods[0])
        rc.lookup_row(pods[25])                      # a miss derives too
        assert _encodes()["columns"] - c1["columns"] == 2
        assert rc.gather(pods[:21], ("has_ports", "req_cpu")) is not None
        assert _encodes()["columns"] - c1["columns"] == 2 + 21
        assert rc.gather(pods, ("has_ports",)) is None   # a miss: no row made
        assert _encodes()["columns"] - c1["columns"] == 2 + 21
        assert _encodes()["signature"] == c1["signature"]

    def test_unhashable_spec_leaves_the_cache_as_it_was(self):
        rc = PodRowCache()
        good = [mkpod(f"g{j}", rv=1) for j in range(4)]
        rc.insert_many(good)
        bad = mkpod("bad", rv=1)
        bad.tolerations = [Toleration(key="k", effect=NO_SCHEDULE)]
        before = list(rc._slot_of.items())
        with pytest.raises(TypeError):
            rc.insert_many([mkpod("g9", rv=1), bad])
        assert list(rc._slot_of.items()) == before
        assert rc.signatures(good) == [pod_class_signature(p) for p in good]


class TestSchedulerWiring:
    """The shell fills/invalidates the cache at informer delivery and the
    burst prologue gathers from it — end to end on a live scheduler."""

    def _world(self, n_nodes=4):
        from kubernetes_tpu.scheduler import Scheduler
        store = Store(watch_log_size=1 << 16)
        for i in range(n_nodes):
            store.create(NODES, Node(
                name=f"n{i}", labels={LABEL_HOSTNAME: f"n{i}"},
                allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        return store, sched

    def test_rows_filled_at_delivery_and_invalidated_on_bind(self):
        store, sched = self._world()
        store.create_many(PODS, [mkpod(f"p{j}") for j in range(6)])
        sched.pump()
        assert len(sched.pod_rows) == 6
        bound = sched.schedule_burst(max_pods=64)
        assert bound == 6
        sched.pump()   # deliver the bind MODIFIEDs -> rows invalidate
        assert len(sched.pod_rows) == 0

    def test_row_cache_rows_deleted_pod(self):
        store, sched = self._world()
        store.create(PODS, mkpod("gone"))
        sched.pump()
        assert len(sched.pod_rows) == 1
        store.delete(PODS, "default/gone")
        sched.pump()
        assert len(sched.pod_rows) == 0

    def test_update_reencodes_row(self):
        store, sched = self._world()
        store.create(PODS, mkpod("u", cpu=100))
        sched.pump()
        cur = store.get(PODS, "default/u")
        cur.containers = (Container.make(name="c",
                                         requests={"cpu": 800}),)
        store.update(PODS, cur)
        sched.pump()
        got = sched.pod_rows.lookup_row(store.get(PODS, "default/u"))
        assert got["req_cpu"] == 800


    def test_create_run_leaves_what_per_pod_delivery_leaves(self):
        """1000 creates delivered as informer runs (on_add_many ->
        insert_many) against the same pods delivered one event a pump
        (on_add -> insert): the same queue, the same cache, the same
        burst decisions."""
        from kubernetes_tpu.store.informer import DELIVERED
        rng = random.Random(41)
        pods = []
        for j in range(1000):
            p = mkpod(f"r{j}", cpu=rng.choice([100, 200]),
                      labels={"app": f"svc-{j % 5}"} if j % 3 else {})
            p.priority = rng.randint(0, 2)
            pods.append(p)

        def batched():
            return DELIVERED.labels("batched", "pods", "queue").value

        store_a, run = self._world(n_nodes=40)
        b0 = batched()
        store_a.create_many(PODS, [p.clone() for p in pods])
        run.pump()
        assert batched() - b0 == 1000
        store_b, single = self._world(n_nodes=40)
        b0 = batched()
        for p in pods:
            store_b.create(PODS, p.clone())
            single.pump()
        assert batched() == b0
        _same_state(run.pod_rows, single.pod_rows)
        assert len(run.pod_rows) == 1000
        pend_a = run.queue.pending_pods()["active"]
        pend_b = single.queue.pending_pods()["active"]
        assert [(p.key, p.uid, p.resource_version) for p in pend_a] \
            == [(p.key, p.uid, p.resource_version) for p in pend_b]
        assert run.pod_rows.signatures(pend_a) \
            == [pod_class_signature(p) for p in pend_a]
        assert run.schedule_burst(max_pods=2048) \
            == single.schedule_burst(max_pods=2048) == 1000
        bound_a = {p.key: p.node_name for p in store_a.list(PODS)[0]}
        bound_b = {p.key: p.node_name for p in store_b.list(PODS)[0]}
        assert bound_a == bound_b and all(bound_a.values())


class TestBatchedIngest:
    def test_queue_add_many_matches_serial_adds(self):
        from kubernetes_tpu.queue.scheduling_queue import PriorityQueue
        rng = random.Random(5)
        pods = []
        for j in range(40):
            p = mkpod(f"p{j}", rv=1)
            p.priority = rng.randint(0, 3)
            pods.append(p)
        q1, q2 = PriorityQueue(), PriorityQueue()
        for p in pods:
            q1.add(p)
        q2.add_many(list(pods))
        order1 = [q1.pop(timeout=0).key for _ in range(len(pods))]
        order2 = [q2.pop(timeout=0).key for _ in range(len(pods))]
        assert order1 == order2

    def test_informer_add_run_delivered_as_batch(self):
        store = Store(watch_log_size=1 << 16)
        from kubernetes_tpu.store.informer import SharedInformer
        inf = SharedInformer(store, PODS)
        batches, singles, updates = [], [], []
        inf.add_event_handler(
            on_add=lambda o: singles.append(o.key),
            on_add_many=lambda objs: batches.append([o.key for o in objs]),
            on_update=lambda o, n: updates.append(n.key))
        inf.sync()
        for j in range(5):
            store.create(PODS, mkpod(f"a{j}"))
        inf.pump()
        assert batches == [[f"default/a{j}" for j in range(5)]]
        assert singles == []
        # a MODIFIED breaks the run; the two adds around it batch/loop
        store.create(PODS, mkpod("b0"))
        store.update(PODS, store.get(PODS, "default/a0"))
        store.create(PODS, mkpod("b1"))
        inf.pump()
        assert singles == ["default/b0", "default/b1"]
        assert updates == ["default/a0"]

    def test_heap_push_many_matches_serial(self):
        from kubernetes_tpu.utils.heap import NumericKeyedHeap
        rng = random.Random(9)
        items = [(f"k{i}", (rng.random(), rng.random(), float(i)))
                 for i in range(64)]
        h1 = NumericKeyedHeap(key_fn=lambda e: e[0],
                              triple_fn=lambda e: e[1])
        h2 = NumericKeyedHeap(key_fn=lambda e: e[0],
                              triple_fn=lambda e: e[1])
        for it in items:
            h1.add(it)
        h2.add_many(items)
        # replacement semantics ride the batch too
        h1.add(("k3", (0.0, 0.0, 0.0)))
        h2.add_many([("k3", (0.0, 0.0, 0.0))])
        assert [e[0] for e in h1.pop_many(100)] \
            == [e[0] for e in h2.pop_many(100)]

    def test_gated_create_many_sheds_tail_with_accepted(self):
        from kubernetes_tpu.serve.backpressure import BackpressureGate
        store = Store(watch_log_size=1 << 16)
        depth = {"v": 0}
        store.admission_gate = BackpressureGate(
            lambda: depth["v"], max_depth=5, retry_after_base=0.1)
        pods = [mkpod(f"p{j}") for j in range(8)]
        with pytest.raises(BackpressureError) as ei:
            store.create_many(PODS, pods)
        assert ei.value.accepted == 5
        assert ei.value.retry_after > 0
        stored = {p.key for p in store.list(PODS)[0]}
        assert stored == {f"default/p{j}" for j in range(5)}
        # nodes are never gated, and non-shed batches return the prefix
        out = store.create_many(NODES, [Node(name="n0")])
        assert len(out) == 1

    def test_gated_create_many_stamps_admission_batch(self):
        from kubernetes_tpu.obs import ledger as L
        from kubernetes_tpu.serve.backpressure import BackpressureGate
        L.LEDGER.reset()
        try:
            store = Store(watch_log_size=1 << 16)
            store.admission_gate = BackpressureGate(lambda: 0,
                                                    max_depth=100)
            store.create_many(PODS, [mkpod(f"p{j}") for j in range(4)])
            assert L.LEDGER.debug_state()["in_flight"] == 4
        finally:
            L.LEDGER.reset()


class TestObserveBatchEdges:
    """Satellite pin: observe_batch on empty and single-element arrays —
    the batched ledger stamps hit the empty case every quiet flush."""

    def _family(self, name):
        from kubernetes_tpu.obs.registry import Histogram
        return Histogram(name, "t", buckets=(0.001, 0.01, 0.1, 1.0))

    def test_empty_batch_is_noop(self):
        h = self._family("t_empty")
        h.observe_batch([])
        h.observe_batch(np.asarray([], dtype=np.float64))
        c = h.labels()
        assert c.count == 0 and c.sum == 0.0 and all(b == 0
                                                     for b in c.buckets)

    def test_single_element_equals_observe(self):
        for v in (0.0005, 0.001, 0.0500001, 2.0, 100.0):
            ha, hb = self._family("t_a"), self._family("t_b")
            ha.observe(v)
            hb.observe_batch([v])
            a, b = ha.labels(), hb.labels()
            assert (a.count, a.sum, a.buckets) == (b.count, b.sum,
                                                   b.buckets), v

    def test_batch_equals_observe_loop(self):
        rng = random.Random(2)
        vals = [rng.random() * 10 ** rng.randint(-4, 1)
                for _ in range(500)]
        ha, hb = self._family("t_c"), self._family("t_d")
        for v in vals:
            ha.observe(v)
        hb.observe_batch(vals)
        a, b = ha.labels(), hb.labels()
        assert a.count == b.count and a.buckets == b.buckets
        assert a.sum == pytest.approx(b.sum)


class TestLedgerFinalizeOnDelete:
    """Satellite pin: the completion-reaper leak — pods deleted while
    holding in-flight ledger slots are finalized, so a minutes-scale soak
    holds a BOUNDED in-flight/awaiting map."""

    def test_delete_finalizes_pending_and_awaiting(self):
        from kubernetes_tpu.obs import ledger as L
        L.LEDGER.reset()
        try:
            store = Store(watch_log_size=1 << 16)
            # pending record (admission-stamped, never bound)
            store.admission_gate = type(
                "G", (), {"admit": lambda self, p: None})()
            store.create(PODS, mkpod("pend"))
            assert L.LEDGER.debug_state()["in_flight"] == 1
            store.delete(PODS, "default/pend")
            assert L.LEDGER.debug_state()["in_flight"] == 0
            # bound + awaiting copy-out (commit stamped, no watcher ever
            # polls): the reaper-shaped delete must clear it
            store.admission_gate = None
            store.create(PODS, mkpod("bnd"))
            store.create(NODES, Node(name="n0"))
            L.LEDGER.stamp_enqueue("default/bnd")
            store.bind_pod("default/bnd", "n0")
            assert L.LEDGER.debug_state()["awaiting_fanout"] == 1
            store.delete(PODS, "default/bnd")
            assert L.LEDGER.debug_state()["awaiting_fanout"] == 0
            assert L.LEDGER_FINALIZED.value >= 2
        finally:
            L.LEDGER.reset()

    def test_reaper_shaped_soak_bounded(self):
        """Soak shape: create -> bind -> reap (delete) in waves with NO
        watcher draining bind events; steady-state in-flight + awaiting
        stay bounded by the live set, not by total throughput."""
        from kubernetes_tpu.obs import ledger as L
        L.LEDGER.reset()
        try:
            store = Store(watch_log_size=1 << 16)
            store.create(NODES, Node(name="n0"))
            for wave in range(30):
                keys = []
                for j in range(16):
                    p = mkpod(f"w{wave}-{j}")
                    store.create(PODS, p)
                    L.LEDGER.stamp_admission(p.key)
                    L.LEDGER.stamp_enqueue(p.key)
                    keys.append(p.key)
                store.bind_pods([(k, "n0") for k in keys])
                for k in keys:
                    store.delete(PODS, k)   # the reaper
                dbg = L.LEDGER.debug_state()
                assert dbg["in_flight"] == 0, (wave, dbg)
                assert dbg["awaiting_fanout"] == 0, (wave, dbg)
        finally:
            L.LEDGER.reset()
