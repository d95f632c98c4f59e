"""Round-16 serving tests: ServeLoop windows, the backpressure gate's
429 contract (shed, Retry-After, ledger eviction, admission stamping),
and TestServeWindowParity — the arrival-driven differential fuzz.

The parity contract: the SAME arrival sequence fed through ServeLoop
windows on the TPU burst path vs a serial oracle observing the same
arrivals at the same window boundaries (a ServeLoop over the
GenericScheduler shell: identical queue, identical window cuts, serial
per-pod decisions) yields bit-identical binding streams — including a
mid-window node death (the launch-refusal contract) and with the fault
plane injecting in the TPU world (graceful degradation)."""
import random

import pytest

from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.serve import ArrivalGenerator, BackpressureGate, ServeLoop
from kubernetes_tpu.store.store import (
    NODES, PODS, BackpressureError, NotFoundError, Store,
)
from tests.test_tpu_parity import (
    finish_with_flight, flight_replay, node_churn_driver, set_world_chaos,
)

GI = 1024 ** 3


def mknode(i, cpu=4000, zones=2):
    return Node(name=f"n{i}",
                labels={"kubernetes.io/hostname": f"n{i}",
                        "failure-domain.beta.kubernetes.io/zone":
                        f"z{i % zones}"},
                allocatable={"cpu": cpu, "memory": 32 * GI, "pods": 110})


def mkpod(name, cpu=100, **kw):
    return Pod(name=name,
               containers=(Container.make(name="c",
                                          requests={"cpu": cpu}),), **kw)


def build_world(n_nodes=6, use_tpu=True, **node_kw):
    store = Store(watch_log_size=1 << 16)
    for i in range(n_nodes):
        store.create(NODES, mknode(i, **node_kw))
    sched = Scheduler(store, use_tpu=use_tpu,
                      percentage_of_nodes_to_score=100)
    sched.sync()
    return store, sched


class TestBackpressureGate:
    def test_shed_scales_retry_after_and_counts(self):
        from kubernetes_tpu.serve.backpressure import ADMISSION_REJECTED
        depth = {"v": 0}
        gate = BackpressureGate(lambda: depth["v"], max_depth=10,
                                retry_after_base=0.1, retry_after_max=1.0)
        gate.admit(mkpod("ok"))
        assert gate.admitted == 1
        before = ADMISSION_REJECTED.labels("queue-depth").value
        depth["v"] = 10
        with pytest.raises(BackpressureError) as ei:
            gate.admit(mkpod("shed"))
        assert ei.value.retry_after == pytest.approx(0.1)
        # 5 watermarks deep -> ~5x base, capped at retry_after_max
        depth["v"] = 50
        with pytest.raises(BackpressureError) as ei:
            gate.admit(mkpod("shed"))
        assert ei.value.retry_after == pytest.approx(0.5)
        depth["v"] = 10_000
        with pytest.raises(BackpressureError) as ei:
            gate.admit(mkpod("shed"))
        assert ei.value.retry_after == pytest.approx(1.0)   # capped
        assert ADMISSION_REJECTED.labels("queue-depth").value \
            - before == 3
        assert gate.rejected == 3

    def test_shed_evicts_ledger_record(self):
        """The round-16 bugfix, pinned at the gate: a shed pod's ledger
        record dies with the 429, so the readmit measures startup from
        its own accepted create (not the shed attempt + client backoff)."""
        from kubernetes_tpu.obs import ledger as L
        L.LEDGER.reset()
        try:
            gate = BackpressureGate(lambda: 10, max_depth=10)
            pod = mkpod("p")
            L.LEDGER.stamp_admission(pod.key, t=1.0)
            with pytest.raises(BackpressureError):
                gate.admit(pod)
            # record evicted: a fresh admission opens at ITS OWN time
            L.LEDGER.stamp_admission(pod.key, t=7.0)
            L.LEDGER.stamp_enqueue(pod.key, t=7.1)
            L.LEDGER.commit_many([pod.key], t=8.0)
            assert L.LEDGER.percentile(0.5) == pytest.approx(1.0)
        finally:
            L.LEDGER.reset()

    def test_store_create_gate_and_admission_stamp(self):
        """Store.create consults the gate for pods only and stamps the
        ledger's admission slot on accept — before the informer delivers
        the pod to queue.add."""
        from kubernetes_tpu.obs import ledger as L
        L.LEDGER.reset()
        L.LEDGER.set_trace(True)
        try:
            store, sched = build_world(n_nodes=2)
            loop = ServeLoop(sched, window_size=8, depth=2)
            loop.attach_gate(max_depth=1)
            store.create(PODS, mkpod("a"))       # depth 0: admitted
            with pytest.raises(BackpressureError):
                store.create(PODS, mkpod("b"))   # backlog >= 1: shed
            # nodes are never gated
            store.create(NODES, mknode(99))
            loop.step()
            loop.drain(timeout=5.0)
            rec = L.LEDGER.trace_record("default/a")
            assert rec is not None
            assert rec[L.ADMISSION] is not None
            assert rec[L.ADMISSION] <= rec[L.ENQUEUE]
            assert sum(1 for p in store.list(PODS)[0] if p.node_name) == 1
        finally:
            L.LEDGER.set_trace(False)
            L.LEDGER.reset()


class TestServeLoop:
    def test_windows_cut_from_live_queue(self):
        store, sched = build_world()
        loop = ServeLoop(sched, window_size=4, depth=2)
        # the loop pinned a launch, and a commit wave, to its window
        assert sched.algorithm.launch_cap == 4
        assert sched.algorithm.wave_size == 4
        assert loop.step() == 0                  # nothing arrived yet
        for j in range(10):
            store.create(PODS, mkpod(f"p{j}"))
        bound = 0
        while bound < 10:
            n = loop.step()
            assert n >= 0
            bound += n
        assert loop.pods_bound == 10
        assert loop.idle_ticks >= 1
        st = loop.stats()
        assert st["windows_cut"] >= 1 and st["depth"] == 2

    def test_arrival_generator_accounting(self):
        store, sched = build_world()
        loop = ServeLoop(sched, window_size=16, depth=2)
        gen = ArrivalGenerator(store, rate=5000, total=40, seed=3)
        while not gen.finished():
            gen.tick()
            loop.step()
        loop.drain(timeout=10.0)
        g = gen.stats()
        assert g["attempted"] == 40 and g["created"] == 40
        assert sum(1 for p in store.list(PODS)[0] if p.node_name) == 40

    def test_shed_then_readmit_converges(self):
        store, sched = build_world()
        loop = ServeLoop(sched, window_size=8, depth=2)
        gate = loop.attach_gate(max_depth=6, retry_after_base=0.005)
        gen = ArrivalGenerator(store, rate=10 ** 6, total=60, seed=4)
        import time
        deadline = time.perf_counter() + 30.0
        while (not gen.finished()) and time.perf_counter() < deadline:
            gen.tick()
            loop.step()
        gen.flush_retries(timeout=10.0)
        loop.drain(timeout=10.0)
        g = gen.stats()
        assert g["rejected_429"] > 0          # the burst actually shed
        assert gate.rejected >= g["rejected_429"] > 0
        bound = sum(1 for p in store.list(PODS)[0] if p.node_name)
        assert bound == g["created"]
        assert g["attempted"] == g["created"] + g["gave_up"] \
            + g["pending_retry"]


class TestRemoteServing:
    """Admission over the wire: arrival clients POST pods through the
    apiserver (store/remote.py) WHILE the serve loop schedules — sheds
    travel as 429 + Retry-After and the remote client's capped jittered
    retry readmits them. Topology: apiserver + store + scheduler share a
    process (the cmd/cluster shape — the gate's depth_fn reads the live
    queue); arrival clients are genuinely remote."""

    def test_remote_arrivals_shed_and_converge(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.store.remote import RemoteStore
        store, sched = build_world(n_nodes=4)
        loop = ServeLoop(sched, window_size=8, depth=2)
        loop.attach_gate(max_depth=6, retry_after_base=0.005)
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url)
            gen = ArrivalGenerator(remote, rate=10 ** 6, total=40, seed=5)
            import time
            deadline = time.perf_counter() + 30.0
            while (not gen.finished()) and time.perf_counter() < deadline:
                gen.tick()
                loop.step()
            gen.flush_retries(timeout=10.0)
            loop.drain(timeout=10.0)
        g = gen.stats()
        assert loop.gate.rejected > 0          # sheds crossed the wire
        # the batched wire contract: the shed tail was accounted and
        # re-admitted off the server's Retry-After (round 17: arrivals
        # ride ONE collection POST per flush; the partial 429 carries
        # `accepted`, so nothing is lost OR double-created)
        assert g["rejected_429"] > 0
        bound = sum(1 for p in store.list(PODS)[0] if p.node_name)
        assert bound == g["created"] == 40
        assert g["attempted"] == 40 and g["gave_up"] == 0

    def test_remote_batch_create_partial_shed_accepted_count(self):
        """The collection POST's 429 surfaces `accepted` exactly: the
        prefix landed server-side, the tail did not."""
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.store.remote import RemoteStore
        store, sched = build_world(n_nodes=4)
        loop = ServeLoop(sched, window_size=8, depth=2)
        loop.attach_gate(max_depth=3)
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url)
            with pytest.raises(BackpressureError) as ei:
                remote.create_many(PODS, [mkpod(f"b{j}") for j in range(8)])
        assert ei.value.accepted == 3
        assert ei.value.retry_after > 0
        stored = {p.name for p in store.list(PODS)[0]}
        assert stored == {"b0", "b1", "b2"}


class TestServeWindowParity:
    """The arrival-driven differential fuzz (round-16 satellite): one
    arrival schedule, two worlds — ServeLoop over the TPU burst path vs
    ServeLoop over the serial oracle shell (identical queue and window
    boundaries; serial per-pod decisions) — final binding maps must be
    bit-identical. Variants: mid-window node death (the TPU world's kill
    lands between dispatch and fetch via the node.dead seam; the serial
    world kills at the same round boundary — equivalent by the
    launch-refusal contract) and blanket fault injection in the TPU
    world (degradation costs throughput, never a decision)."""

    def _mixed_pod(self, rng, j):
        from kubernetes_tpu.api.types import (
            Affinity, ContainerPort, LabelSelector, NO_SCHEDULE,
            PodAffinityTerm, PodAntiAffinity, Toleration)
        LABEL_HOSTNAME = "kubernetes.io/hostname"
        cls = rng.choice(["plain", "plain", "plain", "selector",
                          "tolerate", "anti", "port", "prio"])
        kw = {"labels": {"app": cls}}
        if cls == "selector":
            kw["node_selector"] = {"disk": "ssd"}
        elif cls == "tolerate":
            kw["tolerations"] = (Toleration(
                key="ded", value="x", effect=NO_SCHEDULE),)
        elif cls == "anti":
            kw["labels"] = {"color": "green"}
            kw["affinity"] = Affinity(pod_anti_affinity=PodAntiAffinity(
                required=(PodAffinityTerm(
                    label_selector=LabelSelector(
                        match_labels=(("color", "green"),)),
                    topology_key=LABEL_HOSTNAME),)))
        elif cls == "port":
            kw["containers"] = (Container.make(
                name="c", requests={"cpu": 100},
                ports=(ContainerPort(host_port=8080,
                                     container_port=8080),)),)
        elif cls == "prio":
            kw["priority"] = rng.randint(1, 3)
        if "containers" not in kw:
            kw["containers"] = (Container.make(
                name="c", requests={"cpu": rng.choice([100, 300, 700]),
                                    "memory": GI}),)
        return Pod(name=f"p{j}", **kw)

    def _build_nodes(self, rng, n_nodes, zones):
        from kubernetes_tpu.api.types import NO_SCHEDULE, Taint
        nodes = []
        for i in range(n_nodes):
            labels = {"kubernetes.io/hostname": f"n{i}",
                      "failure-domain.beta.kubernetes.io/zone":
                      f"z{i % zones}"}
            if i % 3 == 0:
                labels["disk"] = "ssd"
            taints = (Taint(key="ded", value="x", effect=NO_SCHEDULE),) \
                if i % 5 == 0 else ()
            nodes.append(Node(
                name=f"n{i}", labels=labels, taints=taints,
                allocatable={"cpu": rng.choice([2000, 4000]),
                             "memory": 8 * GI, "pods": 110}))
        return nodes

    @pytest.mark.parametrize("seed", [7, 19, 43])
    def test_serve_stream_identical(self, seed, flight_replay,
                                    chaos=False, death=False, mesh=None,
                                    shed_rate=0.0, update_rate=0.0):
        rng = random.Random(seed)
        n_nodes = rng.randint(8, 24)
        zones = rng.choice([1, 2, 3])
        rounds = rng.randint(4, 7)
        per_round = [rng.randint(3, 12) for _ in range(rounds)]
        window = rng.choice([4, 8])
        depth = rng.choice([2, 3])
        kill_round = rng.randrange(1, rounds) if death else None
        rng_state = rng.getstate()
        results = []
        for use_tpu in (True, False):
            set_world_chaos(chaos, seed, use_tpu)
            rng.setstate(rng_state)
            store = Store(watch_log_size=1 << 16)
            for node in self._build_nodes(rng, n_nodes, zones):
                store.create(NODES, node.clone())
            sched = Scheduler(store, use_tpu=use_tpu,
                              percentage_of_nodes_to_score=100,
                              mesh=mesh if use_tpu else None)
            sched.sync()
            loop = ServeLoop(sched, window_size=window, depth=depth)
            kill = flush = None
            if death:
                kill, flush = node_churn_driver(use_tpu, store, seed)
            shed_gate = None
            if shed_rate:
                # the DETERMINISTIC shed schedule: both worlds draw the
                # same serve.shed stream against the same create
                # sequence, and shed arrivals re-enter at the head of
                # the NEXT round (no jittered client clocks in a
                # bit-parity harness)
                from kubernetes_tpu import chaos as chaos_mod
                shed_gate = loop.attach_gate(max_depth=1 << 30)
                chaos_mod.plan(seed=seed,
                               rates={"serve.shed": shed_rate})
            j = 0
            carry = []
            for r in range(rounds):
                arrivals, carry = carry, []
                for _ in range(per_round[r]):
                    arrivals.append(self._mixed_pod(rng, j))
                    j += 1
                for pod in arrivals:
                    try:
                        store.create(PODS, pod.clone())
                    except BackpressureError:
                        carry.append(pod)   # readmit next round, in order
                if update_rate:
                    # mid-window pod updates (round-17 row-cache variant,
                    # batched in round 23): both worlds mutate the same
                    # pending pods — same rng stream over the same
                    # unbound set (identical under parity-so-far) — and
                    # the whole round's mutations land as ONE update_many
                    # at the window boundary. The consecutive MODIFIED
                    # run dispatches the informer's batched
                    # on_update_many invalidation, which the row-by-row
                    # lookup_row == encode_row assert below then covers.
                    unbound = sorted(p.key for p in store.list(PODS)[0]
                                     if not p.node_name)
                    updates = []
                    for key in unbound:
                        if rng.random() < update_rate:
                            cur = store.get(PODS, key)
                            cur.priority += 1
                            cur.labels["upd"] = str(r)
                            updates.append((cur, cur.resource_version))
                    if updates:
                        from kubernetes_tpu.store.store import (
                            BATCH_MUTATION_CALLS)
                        calls0 = BATCH_MUTATION_CALLS.labels(
                            "update_many").value
                        confl: list = []
                        miss: list = []
                        store.update_many(PODS, updates,
                                          conflicts=confl, missing=miss)
                        # pending pods, single-threaded harness: every
                        # CAS must land, in ONE batched verb call
                        assert not confl and not miss, (confl, miss)
                        assert BATCH_MUTATION_CALLS.labels(
                            "update_many").value == calls0 + 1
                if kill is not None and r == kill_round:
                    live = sorted(
                        n.name for n in store.list(NODES)[0])
                    victim = rng.choice(live)
                    kill(victim)
                loop.step()
                if flush is not None:
                    flush()
                if use_tpu and sched.pod_rows is not None:
                    # row-by-row bit-identity: every pending pod's cached
                    # row must equal a fresh encode (the contract that
                    # keeps gathered windows oracle-parity)
                    from kubernetes_tpu.ops.pod_rows import encode_row
                    for p in sched.queue.pending_pods()["active"]:
                        assert sched.pod_rows.lookup_row(p) \
                            == encode_row(p), p.key
            # shed leftovers readmit, then the backlog drains
            for pod in carry:
                try:
                    store.create(PODS, pod.clone())
                except BackpressureError:
                    pass
            while loop.step() > 0:
                pass
            sched.pump()
            results.append({p.key: p.node_name
                            for p in store.list(PODS)[0]})
            if shed_gate is not None:
                from kubernetes_tpu import chaos as chaos_mod
                chaos_mod.disable()
                assert shed_gate.rejected > 0 or shed_rate == 0.0
        tpu, oracle = results
        diff = {k: (tpu.get(k), oracle.get(k))
                for k in set(tpu) | set(oracle)
                if tpu.get(k) != oracle.get(k)}
        finish_with_flight(
            flight_replay, f"serve-{seed}", not diff,
            f"seed={seed}: {len(diff)} diverged: {sorted(diff.items())[:6]}")

    def test_serve_stream_identical_mid_window_node_death(
            self, flight_replay):
        """A node dies MID-WINDOW in the TPU world (between dispatch and
        fetch): the launch refuses whole and replans post-churn, so the
        stream matches a serial oracle that observed the death at the
        same window boundary."""
        self.test_serve_stream_identical(19, flight_replay, death=True)

    def test_serve_stream_identical_under_injection(self, flight_replay):
        """Blanket fault injection in the TPU world (device faults,
        store faults, native demotion, watch drops): serving decisions
        stay bit-identical — a fault costs throughput, never a bit."""
        self.test_serve_stream_identical(43, flight_replay, chaos=True)

    def test_serve_stream_identical_with_deterministic_sheds(
            self, flight_replay):
        """The 429 path inside the parity harness: both worlds draw the
        same serve.shed schedule, shed arrivals readmit at the next
        window boundary, and the streams stay bit-identical."""
        self.test_serve_stream_identical(7, flight_replay, shed_rate=0.3)

    def test_serve_stream_identical_with_mid_window_updates(
            self, flight_replay):
        """Round-17 row-cache variant: pending pods mutate (priority +
        labels, new resourceVersions) BETWEEN windows in both worlds —
        update-in-place invalidation must re-encode rows at delivery, the
        cached-row/fresh-encode bit-identity holds row by row, and the
        binding streams stay identical."""
        self.test_serve_stream_identical(19, flight_replay,
                                         update_rate=0.4)
