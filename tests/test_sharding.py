"""Multi-chip sharding parity: the sharded kernels must make bit-identical
decisions to the single-device kernels over the virtual 8-device CPU mesh
(conftest forces xla_force_host_platform_device_count=8).

Covers the north-star sharded path (SURVEY §2.3 last row): node axis split
across the mesh, per-shard filter/score, all-gather, replicated select —
single cycles, state folds between cycles, and the full lax.scan burst.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from kubernetes_tpu.api.types import Node, Pod, Container
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.ops.node_state import NodeStateEncoder, PodEncoder
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu.parallel import sharding as S
from kubernetes_tpu.core.tpu_scheduler import TPUScheduler

GI = 1024 ** 3


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest should have forced 8 CPU devices"
    return Mesh(np.asarray(devices[:8]), (S.NODE_AXIS,))


def _cluster(n_nodes, seed=0):
    rng = np.random.RandomState(seed)
    infos = {}
    names = []
    for i in range(n_nodes):
        labels = {"failure-domain.beta.kubernetes.io/zone": f"zone-{i % 3}",
                  "failure-domain.beta.kubernetes.io/region": "r1",
                  "kubernetes.io/hostname": f"n{i}"}
        node = Node(name=f"n{i}", labels=labels,
                    allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110})
        ni = NodeInfo(node)
        infos[node.name] = ni
        names.append(node.name)
    for j in range(n_nodes * 2):
        host = names[int(rng.randint(0, n_nodes))]
        p = Pod(name=f"warm{j}", node_name=host,
                containers=(Container.make(
                    name="c",
                    requests={"cpu": int(rng.choice([100, 500, 1000])),
                              "memory": int(rng.choice([1, 2, 4])) * GI}),))
        infos[host].add_pod(p)
    return infos, names


def _encode(infos, names, pods):
    enc = NodeStateEncoder()
    batch = enc.encode(infos, names)
    sched = TPUScheduler(percentage_of_nodes_to_score=100)
    pe = PodEncoder(infos, batch, total_num_nodes=len(names))
    per_pod = [sched._pod_arrays(pe.encode(p), batch.n_pad,
                                 upd_fields=True, pod=p) for p in pods]
    stacked = {k: np.stack([pp[k] for pp in per_pod]) for k in per_pod[0]}
    node_arrays = {k: np.asarray(v) for k, v in sched._node_arrays(batch).items()}
    return node_arrays, per_pod, stacked, batch


def _mk_pods(k, seed=1):
    rng = np.random.RandomState(seed)
    return [Pod(name=f"p{j}",
                containers=(Container.make(
                    name="c",
                    requests={"cpu": int(rng.choice([100, 250, 500, 900])),
                              "memory": int(rng.choice([1, 2, 3])) * GI}),))
            for j in range(k)]


CYCLE_KEYS = ("selected", "found", "evaluated", "max_score",
              "next_last_index", "next_last_node_index")


class TestShardedCycleParity:
    @pytest.mark.parametrize("n_nodes,seed", [(17, 0), (64, 1), (100, 2)])
    def test_cycle_matches_single_device(self, mesh, n_nodes, seed):
        infos, names = _cluster(n_nodes, seed=seed)
        pods = _mk_pods(1, seed=seed + 10)
        node_arrays, per_pod, _, batch = _encode(infos, names, pods)
        z_pad = 4
        single = K.schedule_cycle(node_arrays, per_pod[0], 3, 1,
                                  batch.n_real, batch.n_real, z_pad)
        nodes_s = S.shard_node_arrays(mesh, node_arrays)
        pod_s = S.shard_pod_arrays(mesh, per_pod[0])
        fn = S.sharded_cycle_fn(mesh, z_pad=z_pad)
        out = fn(nodes_s, pod_s,
                 jnp.asarray(3, jnp.int64), jnp.asarray(1, jnp.int64),
                 jnp.asarray(batch.n_real, jnp.int64),
                 jnp.asarray(batch.n_real, jnp.int64))
        for k in CYCLE_KEYS:
            assert int(out[k]) == int(single[k]), k
        np.testing.assert_array_equal(
            np.asarray(out["total"]), np.asarray(single["total"]))
        np.testing.assert_array_equal(
            np.asarray(out["kept"]), np.asarray(single["kept"]))
        np.testing.assert_array_equal(
            np.asarray(out["feasible"]), np.asarray(single["feasible"]))

    def test_partial_search_truncation(self, mesh):
        """Adaptive partial search: num_to_find < feasible count."""
        infos, names = _cluster(48, seed=3)
        pods = _mk_pods(1, seed=30)
        node_arrays, per_pod, _, batch = _encode(infos, names, pods)
        z_pad = 4
        single = K.schedule_cycle(node_arrays, per_pod[0], 11, 2,
                                  10, batch.n_real, z_pad)
        nodes_s = S.shard_node_arrays(mesh, node_arrays)
        pod_s = S.shard_pod_arrays(mesh, per_pod[0])
        fn = S.sharded_cycle_fn(mesh, z_pad=z_pad)
        out = fn(nodes_s, pod_s,
                 jnp.asarray(11, jnp.int64), jnp.asarray(2, jnp.int64),
                 jnp.asarray(10, jnp.int64),
                 jnp.asarray(batch.n_real, jnp.int64))
        for k in CYCLE_KEYS:
            assert int(out[k]) == int(single[k]), k


class TestShardedBurstParity:
    @pytest.mark.parametrize("n_nodes,n_burst,seed", [
        (24, 8, 0), (64, 16, 1), (100, 32, 2)])
    def test_burst_matches_single_device(self, mesh, n_nodes, n_burst, seed):
        infos, names = _cluster(n_nodes, seed=seed)
        pods = _mk_pods(n_burst, seed=seed + 20)
        node_arrays, _, stacked, batch = _encode(infos, names, pods)
        z_pad = 4
        state1, li1, lni1, _spread1, outs1 = K.schedule_batch(
            node_arrays, stacked, 0, 0, batch.n_real, batch.n_real, z_pad)
        nodes_s = S.shard_node_arrays(mesh, node_arrays)
        pods_s = S.shard_pod_batch(mesh, stacked)
        fn = S.sharded_batch_fn(mesh, z_pad=z_pad)
        zero = jnp.asarray(0, jnp.int64)
        state_s, li_s, lni_s, outs_s = fn(
            nodes_s, pods_s, zero, zero,
            jnp.asarray(batch.n_real, jnp.int64),
            jnp.asarray(batch.n_real, jnp.int64))
        np.testing.assert_array_equal(
            np.asarray(outs_s["selected"]), np.asarray(outs1["selected"]))
        np.testing.assert_array_equal(
            np.asarray(outs_s["evaluated"]), np.asarray(outs1["evaluated"]))
        np.testing.assert_array_equal(
            np.asarray(outs_s["max_score"]), np.asarray(outs1["max_score"]))
        assert int(li_s) == int(li1) and int(lni_s) == int(lni1)
        for k in K._MUTABLE:
            np.testing.assert_array_equal(
                np.asarray(state_s[k]), np.asarray(state1[k]), err_msg=k)

    def test_burst_fills_cluster(self, mesh):
        """Saturation: pods keep landing until capacity runs out; the fold
        must deplete sharded rows exactly like the single-device fold."""
        infos, names = _cluster(8, seed=5)
        # big pods: ~4 fit per node on cpu
        pods = [Pod(name=f"big{j}",
                    containers=(Container.make(
                        name="c", requests={"cpu": 900, "memory": GI}),))
                for j in range(48)]
        node_arrays, _, stacked, batch = _encode(infos, names, pods)
        z_pad = 4
        _, _, _, _, outs1 = K.schedule_batch(
            node_arrays, stacked, 0, 0, batch.n_real, batch.n_real, z_pad)
        nodes_s = S.shard_node_arrays(mesh, node_arrays)
        pods_s = S.shard_pod_batch(mesh, stacked)
        fn = S.sharded_batch_fn(mesh, z_pad=z_pad)
        zero = jnp.asarray(0, jnp.int64)
        _, _, _, outs_s = fn(nodes_s, pods_s, zero, zero,
                             jnp.asarray(batch.n_real, jnp.int64),
                             jnp.asarray(batch.n_real, jnp.int64))
        sel1 = np.asarray(outs1["selected"])
        sels = np.asarray(outs_s["selected"])
        np.testing.assert_array_equal(sels, sel1)
        assert (sel1 == -1).any(), "saturation case should reject some pods"


class TestShardedUniformKernel:
    """The uniform K-pods-per-pass kernel — the north-star throughput path —
    sharded over the mesh (VERDICT r03 #1): STAY and ELIM batch modes, state
    folds, and unschedulable tails must be bit-identical to single-chip."""

    def _burst(self, mesh_arg, infos, names, pods):
        sched = TPUScheduler(percentage_of_nodes_to_score=100, mesh=mesh_arg)
        hosts = sched.schedule_burst(pods, infos, names)
        assert hosts is not None, "burst refused — uniform path not taken"
        state = {k: np.asarray(v) for k, v in sched._dev_nodes.items()
                 if k in K._MUTABLE}
        return hosts, state

    def test_stay_mode_sharded(self, mesh):
        """Plain identical pods: every fold leaves its node at max score
        (STAY batching) for long stretches."""
        infos, names = _cluster(48, seed=7)
        pods = [Pod(name=f"u{j}", labels={"app": "u"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100, "memory": GI}),))
                for j in range(160)]
        h1, s1 = self._burst(None, infos, names, pods)
        hs, ss = self._burst(mesh, infos, names, pods)
        assert hs == h1
        assert all(h is not None for h in h1)
        for k in K._MUTABLE:
            np.testing.assert_array_equal(ss[k], s1[k], err_msg=k)

    def test_elim_mode_sharded(self, mesh):
        """Identical pods with host ports: every placement bans its own node
        (ELIM batching); pods beyond the node count become unschedulable."""
        from kubernetes_tpu.api.types import ContainerPort
        infos, names = _cluster(24, seed=8)
        pods = [Pod(name=f"e{j}", labels={"app": "e"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100, "memory": GI},
                        ports=(ContainerPort(host_port=8080,
                                             protocol="TCP"),)),))
                for j in range(40)]
        h1, s1 = self._burst(None, infos, names, pods)
        hs, ss = self._burst(mesh, infos, names, pods)
        assert hs == h1
        assert sum(1 for h in h1 if h is not None) == 24
        assert sum(1 for h in h1 if h is None) == 16
        for k in K._MUTABLE:
            np.testing.assert_array_equal(ss[k], s1[k], err_msg=k)

    def test_uniform_sharded_rotation_pipeline(self, mesh):
        """Uneven zones rotate the per-cycle NodeTree enumeration; the
        sharded uniform kernel must replay the same rotation_map walk."""
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler

        def pipeline(mesh_arg):
            store = Store(watch_log_size=65536)
            for i in range(30):
                z = "z0" if i < 15 else f"z{1 + i % 2}"
                store.create(NODES, Node(
                    name=f"n{i}",
                    labels={"failure-domain.beta.kubernetes.io/zone": z},
                    allocatable={"cpu": 4000, "memory": 32 * GI,
                                 "pods": 110}))
            sched = Scheduler(store, use_tpu=True,
                              percentage_of_nodes_to_score=100, mesh=mesh_arg)
            sched.sync()
            for j in range(100):
                store.create(PODS, Pod(
                    name=f"p{j}", labels={"app": "x"},
                    containers=(Container.make(
                        name="c",
                        requests={"cpu": 100, "memory": GI}),)))
            sched.pump()
            while sched.schedule_burst(max_pods=1024):
                pass
            sched.pump()
            return {p.key: p.node_name for p in store.list(PODS)[0]}

        sharded = pipeline(mesh)
        single = pipeline(None)
        assert sharded == single
        assert sum(1 for v in sharded.values() if v) == 100


class TestDryrunEntry:
    def test_dryrun_multichip_runs(self):
        import __graft_entry__
        __graft_entry__.dryrun_multichip(8)

    def test_entry_compiles(self):
        import __graft_entry__
        fn, args = __graft_entry__.entry()
        out = jax.jit(fn)(*args)
        sel = int(out[0])
        assert sel >= 0


class TestMeshPipelineDenseFeatures:
    """The real store->queue->cache->burst pipeline in mesh mode, with pods
    whose _POD_SHARDED mask fields are DENSE (node selectors -> sel_ok[N],
    taints -> taints_ok[N]/taint_counts[N]) — not the inert [1] broadcasts
    (VERDICT round-3 #5)."""

    def _pipeline(self, mesh):
        from kubernetes_tpu.api.types import (
            Node, Pod, Container, Taint, Toleration, NO_SCHEDULE)
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        GI = 1024 ** 3
        store = Store(watch_log_size=65536)
        for i in range(32):
            taints = (Taint(key="dedicated", value="x", effect=NO_SCHEDULE),) \
                if i % 4 == 0 else ()
            store.create(NODES, Node(
                name=f"n{i}",
                labels={"failure-domain.beta.kubernetes.io/zone":
                        f"z{i % 4}",
                        "perf-group": "a" if i % 2 == 0 else "b"},
                taints=taints,
                allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100, mesh=mesh)
        sched.sync()
        for j in range(12):
            kw = {}
            if j % 3 == 0:
                kw["node_selector"] = {"perf-group": "a"}
            if j % 3 == 1:
                kw["tolerations"] = (Toleration(
                    key="dedicated", value="x", effect=NO_SCHEDULE),)
            store.create(PODS, Pod(
                name=f"p{j}", labels={"app": "x"},
                containers=(Container.make(
                    name="c", requests={"cpu": 100 + 100 * (j % 2),
                                        "memory": GI}),), **kw))
        sched.pump()
        while sched.schedule_burst(max_pods=16):
            pass
        sched.pump()
        return {p.key: p.node_name for p in store.list(PODS)[0]}

    def test_mesh_burst_matches_single_device(self):
        import jax
        from kubernetes_tpu.parallel import sharding as S
        assert len(jax.devices()) >= 8, "conftest provisions 8 CPU devices"
        mesh = S.make_mesh(8)
        sharded = self._pipeline(mesh)
        single = self._pipeline(None)
        assert sharded == single
        assert sum(1 for v in sharded.values() if v) == 12


# ---------------------------------------------------------------------------
# Round 15: the fused single-dispatch drain window, rotation, carried spread,
# gangs, and the preemption scans all run SHARDED — one code path
# parameterized by the sharding spec (the burst-sharded-* fallbacks are gone)
# ---------------------------------------------------------------------------


def _uneven_pipeline(mesh_arg, n_nodes=13, zones=3, gangs=2, web_pods=20,
                     wave_size=None):
    """Full store->queue->cache->fused-burst pipeline on an UNEVEN-zone
    cluster (n % zones != 0 -> live NodeTree rotation) with gangs AND
    Service-matched spread pods — exactly the feature set the pre-round-15
    sharded path refused (burst-sharded-rotation / burst-sharded-spread /
    fused-mesh-mode)."""
    from kubernetes_tpu.api.types import Service
    from kubernetes_tpu.coscheduling.types import LABEL_POD_GROUP, PodGroup
    from kubernetes_tpu.store.store import (Store, PODS, NODES, PODGROUPS,
                                            SERVICES)
    from kubernetes_tpu.scheduler import Scheduler
    s = Store(watch_log_size=65536)
    for i in range(n_nodes):
        s.create(NODES, Node(
            name=f"n{i}",
            labels={"kubernetes.io/hostname": f"n{i}",
                    "failure-domain.beta.kubernetes.io/zone": f"z{i % zones}",
                    "failure-domain.beta.kubernetes.io/region": "r1"},
            allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
    s.create(SERVICES, Service(name="svc", selector={"app": "web"}))
    sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=100,
                      mesh=mesh_arg)
    if wave_size:
        sched.algorithm.wave_size = wave_size
        sched.fused_run_split = wave_size
    sched.sync()
    for g in range(gangs):
        s.create(PODGROUPS, PodGroup(name=f"g{g}", min_member=3))
        for r in range(3):
            s.create(PODS, Pod(
                name=f"g{g}r{r}", labels={LABEL_POD_GROUP: f"g{g}",
                                          "app": "gang"},
                containers=(Container.make(
                    name="c", requests={"cpu": 500, "memory": GI}),)))
    for j in range(web_pods):
        s.create(PODS, Pod(name=f"w{j}", labels={"app": "web"},
                           containers=(Container.make(
                               name="c",
                               requests={"cpu": 200, "memory": GI}),)))
    sched.pump()
    while sched.schedule_burst(max_pods=32):
        pass
    sched.pump()
    return sched, {p.key: p.node_name for p in s.list(PODS)[0]}


class TestShardedFusedSegments:
    """The fused segmented drain window (gangs + singleton runs, in-scan
    checkpoint/rewind, rotation indexed by the consumed-count t, carried
    spread) sharded over the mesh vs the single-device fused kernel."""

    @pytest.mark.parametrize("wave_size", [None, 4])
    def test_fused_window_parity(self, mesh, wave_size):
        _s1, sharded = _uneven_pipeline(mesh, wave_size=wave_size)
        _s2, single = _uneven_pipeline(None, wave_size=wave_size)
        assert sharded == single
        assert sum(1 for v in sharded.values() if v) == 26

    def test_no_sharded_fallback_labels_fire(self, mesh):
        """The deleted burst-sharded-* / fused-mesh-mode refusals must not
        fire (or even exist) when the fused pipeline runs in mesh mode."""
        from kubernetes_tpu.core.tpu_scheduler import (
            ORACLE_FALLBACKS, PRESSURE_GATES, RETIRED_FALLBACK_REASONS,
            RETIRED_PRESSURE_GATES)
        _uneven_pipeline(mesh)
        live = {k[0] for k in ORACLE_FALLBACKS._children}
        assert not (live & set(RETIRED_FALLBACK_REASONS)), live
        live_p = {k[0] for k in PRESSURE_GATES._children}
        assert not (live_p & set(RETIRED_PRESSURE_GATES)), live_p

    def test_gang_rejection_rewinds_sharded(self, mesh):
        """A gang that cannot fit rewinds the sharded carry in-scan: the
        post-rewind decisions must match single-device exactly."""
        from kubernetes_tpu.coscheduling.types import LABEL_POD_GROUP, PodGroup
        from kubernetes_tpu.store.store import Store, PODS, NODES, PODGROUPS
        from kubernetes_tpu.scheduler import Scheduler

        def pipeline(mesh_arg):
            s = Store(watch_log_size=65536)
            for i in range(9):
                s.create(NODES, Node(
                    name=f"n{i}",
                    labels={"failure-domain.beta.kubernetes.io/zone":
                            f"z{i % 2}"},
                    allocatable={"cpu": 2000, "memory": 32 * GI,
                                 "pods": 110}))
            sched = Scheduler(s, use_tpu=True,
                              percentage_of_nodes_to_score=100,
                              mesh=mesh_arg)
            sched.sync()
            # g0 fits; g1 (full-node members, more members than nodes)
            # can never place whole and must rewind in-scan
            for g, (size, cpu) in enumerate([(3, 500), (11, 2000)]):
                s.create(PODGROUPS, PodGroup(name=f"g{g}", min_member=size))
                for r in range(size):
                    s.create(PODS, Pod(
                        name=f"g{g}r{r}",
                        labels={LABEL_POD_GROUP: f"g{g}", "app": "gang"},
                        containers=(Container.make(
                            name="c", requests={"cpu": cpu}),)))
            for j in range(6):
                s.create(PODS, Pod(name=f"s{j}", labels={"app": "x"},
                                   containers=(Container.make(
                                       name="c", requests={"cpu": 900}),)))
            sched.pump()
            while sched.schedule_burst(max_pods=32):
                pass
            sched.pump()
            return {p.key: p.node_name for p in s.list(PODS)[0]}

        sharded = pipeline(mesh)
        single = pipeline(None)
        assert sharded == single
        # the rejected gang must be bound nowhere, in both worlds
        assert all(not v for k, v in sharded.items() if "/g1r" in k)


class TestShardedPressureParity:
    """preempt_pressure_burst and the single-preemptor victim scan sharded
    over the mesh (the round-9 victim table under P('nodes'))."""

    def _world(self, n_nodes=24, per_node=4):
        from kubernetes_tpu.cache.node_info import NodeInfo
        infos, names = {}, []
        uid = 0
        for i in range(n_nodes):
            node = Node(name=f"node-{i}",
                        allocatable={"cpu": 4000, "memory": 32 * GI,
                                     "pods": 110})
            ni = NodeInfo(node)
            for _ in range(per_node):
                uid += 1
                ni.add_pod(Pod(name=f"victim-{uid}", priority=1,
                               node_name=node.name,
                               containers=(Container.make(
                                   name="c", requests={"cpu": 1000}),)))
            infos[node.name] = ni
            names.append(node.name)
        return infos, names

    def test_pressure_wave_parity(self, mesh):
        infos, names = self._world()
        preemptors = [Pod(name=f"hi-{k}", priority=10,
                          containers=(Container.make(
                              name="c", requests={"cpu": 1000}),))
                      for k in range(40)]
        outs = []
        for m in (mesh, None):
            t = TPUScheduler(percentage_of_nodes_to_score=100, mesh=m)
            o = t.preempt_pressure_burst(preemptors, infos, names, [])
            assert o is not None, f"pressure refused under mesh={m}"
            outs.append([
                (x[0], x[1], sorted(v.name for v in x[2]))
                if x[0] == "nominated" else x for x in o])
        assert outs[0] == outs[1]

    def test_preempt_scan_parity(self, mesh):
        from kubernetes_tpu.oracle.generic_scheduler import FitError
        infos, names = self._world()
        incoming = Pod(name="in", priority=9,
                       containers=(Container.make(
                           name="c", requests={"cpu": 1000}),))
        err = FitError(incoming, len(names),
                       {n: ["x"] for n in names})
        res = []
        for m in (mesh, None):
            t = TPUScheduler(percentage_of_nodes_to_score=100, mesh=m)
            r = t.preempt(incoming, infos, names, err, [])
            res.append((r.node.name if r.node else None,
                        sorted(v.name for v in r.victims)))
        assert res[0] == res[1]


class TestShardPaddingSafety:
    """Uneven shard padding: n_real=17 pads to n_pad=32 over 8 shards of 4
    rows — rows 17..31 are padding living entirely in the tail shards.
    Padded rows must never win the top-k, shard-BOUNDARY rows (feasible
    node last-in-shard / first-in-next-shard) must win exactly when the
    single-device kernel says so, and the round-robin tie walk must cross
    shard boundaries in the identical order."""

    def _cluster17(self, feasible_labels=None):
        from kubernetes_tpu.cache.node_info import NodeInfo
        infos, names = {}, []
        for i in range(17):
            labels = {"kubernetes.io/hostname": f"n{i}",
                      "failure-domain.beta.kubernetes.io/zone":
                      f"zone-{i % 3}"}
            if feasible_labels and i in feasible_labels:
                labels.update(feasible_labels[i])
            node = Node(name=f"n{i}", labels=labels,
                        allocatable={"cpu": 4000, "memory": 32 * GI,
                                     "pods": 110})
            infos[node.name] = NodeInfo(node)
            names.append(node.name)
        return infos, names

    def _burst(self, mesh_arg, infos, names, pods):
        t = TPUScheduler(percentage_of_nodes_to_score=100, mesh=mesh_arg)
        return t.schedule_burst(pods, infos, names)

    @pytest.mark.parametrize("target", [3, 4, 16])
    def test_boundary_row_wins_identically(self, mesh, target):
        """target=3: last row of shard 0; 4: first row of shard 1; 16: the
        ONLY real row of shard 4 (rows 17-19 of that shard are padding)."""
        infos, names = self._cluster17(
            feasible_labels={target: {"disk": "ssd"}})
        pods = [Pod(name=f"p{j}", labels={"app": "x"},
                    node_selector={"disk": "ssd"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100, "memory": GI}),))
                for j in range(3)]
        h1 = self._burst(None, infos, names, pods)
        hs = self._burst(mesh, infos, names, pods)
        assert hs == h1
        assert h1 is not None and h1[0] == f"n{target}"
        # the padded tail (rows 17..31) can never be named
        assert all(h is None or h in names for h in h1)

    def test_tie_walk_crosses_shards_identically(self, mesh):
        """All 17 rows feasible and score-tied: 60 identical pods drive the
        round-robin tie walk across every shard boundary (and through the
        padded tail's shard) repeatedly."""
        infos, names = self._cluster17()
        pods = [Pod(name=f"p{j}", labels={"app": "t"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100, "memory": GI}),))
                for j in range(60)]
        h1 = self._burst(None, infos, names, pods)
        hs = self._burst(mesh, infos, names, pods)
        assert hs == h1
        assert all(h in names for h in h1)

    def test_invalidate_node_hits_shard_local_row(self, mesh):
        """Mid-burst node death in mesh mode: invalidate_node must drop the
        dead node's shard-local mirror/victim rows so the post-churn replan
        is bit-identical to a single-device world that saw the same death
        (the StaleNodeRefusal contract's device half)."""
        infos, names = self._cluster17()
        warm = [Pod(name=f"w{j}", labels={"app": "x"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 300, "memory": GI}),))
                for j in range(8)]
        post = [Pod(name=f"q{j}", labels={"app": "x"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 300, "memory": GI}),))
                for j in range(8)]
        dead = "n4"   # first row of shard 1

        def run(mesh_arg):
            t = TPUScheduler(percentage_of_nodes_to_score=100,
                             mesh=mesh_arg)
            first = t.schedule_burst(warm, infos, names)
            assert first is not None
            # the node dies: the shell would remove it from cache/tree and
            # call invalidate_node; replan the next burst post-churn
            t.invalidate_node(dead)
            infos2 = {k: v for k, v in infos.items() if k != dead}
            names2 = [n for n in names if n != dead]
            second = t.schedule_burst(post, infos2, names2)
            assert second is not None
            assert all(h != dead for h in second)
            return first, second

        f1, s1 = run(None)
        fs, ss = run(mesh)
        assert fs == f1 and ss == s1


@pytest.mark.slow
class TestShardedFusedContract:
    """Tier-2 gate: one fused sharded burst end-to-end under the conftest
    8-device mesh — the single-dispatch / single-fetch contract must
    survive sharding (device_dispatches == device_fetches == 1 for the
    burst) with devices == 8."""

    def test_one_dispatch_one_fetch_at_8_devices(self, mesh):
        from kubernetes_tpu.core.tpu_scheduler import (
            DEVICE_DISPATCH, DEVICE_FETCHES)
        from kubernetes_tpu.store.store import Store, PODS, NODES
        from kubernetes_tpu.scheduler import Scheduler
        assert int(mesh.devices.size) == 8
        s = Store(watch_log_size=65536)
        for i in range(48):
            s.create(NODES, Node(
                name=f"n{i}",
                labels={"failure-domain.beta.kubernetes.io/zone":
                        f"z{i % 3}"},
                allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
        sched = Scheduler(s, use_tpu=True,
                          percentage_of_nodes_to_score=100, mesh=mesh)
        sched.sync()
        assert sched.algorithm.debug_state()["devices"] == 8
        mixed = []   # mixed classes -> the FUSED window, not uniform
        for j in range(24):
            kw = {"labels": {"app": "x"}}
            cpu = 100 + 100 * (j % 3)
            mixed.append(Pod(name=f"p{j}", **kw,
                             containers=(Container.make(
                                 name="c", requests={"cpu": cpu,
                                                     "memory": GI}),)))
        # warmup compiles the bucket outside the counted burst
        for p in mixed[:4]:
            s.create(PODS, p.clone())
        sched.pump()
        while sched.schedule_burst(max_pods=32):
            pass
        sched.pump()
        fused_ops = ("burst_fused", "burst_scan", "burst_uniform")
        d0 = {op: DEVICE_DISPATCH.labels(op).value for op in fused_ops}
        f0 = {op: DEVICE_FETCHES.labels(op).value for op in fused_ops}
        for j, p in enumerate(mixed):
            s.create(PODS, Pod(name=f"m{j}", labels=dict(p.labels),
                               containers=p.containers))
        sched.pump()
        n = sched.schedule_burst(max_pods=64)
        assert n == 24
        dd = sum(DEVICE_DISPATCH.labels(op).value - d0[op]
                 for op in fused_ops)
        ff = sum(DEVICE_FETCHES.labels(op).value - f0[op]
                 for op in fused_ops)
        assert dd == 1, f"fused sharded burst paid {dd} dispatches"
        assert ff == 1, f"fused sharded burst paid {ff} fetches"
