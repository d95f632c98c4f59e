"""Perf-harness tests: small-scale versions of the scheduler_perf density
test and workload lanes, asserting correctness of the parity cells (all pods
scheduled, workload constraints respected). Nothing here is timed: speed is
`benchmark/run.py`'s job, on the chip.
"""
import pytest

from kubernetes_tpu.models.hollow import (
    NodeStrategy, PodStrategy, make_hollow_nodes, make_pods, populate_store,
)
from kubernetes_tpu.perf.harness import PerfConfig, run, setup
from kubernetes_tpu.store.store import Store, PODS, NODES


class TestHollowNodes:
    def test_node_shapes_and_zones(self):
        nodes = make_hollow_nodes(NodeStrategy(count=9, zones=3), seed=1)
        assert len(nodes) == 9
        zones = {n.labels["failure-domain.beta.kubernetes.io/zone"] for n in nodes}
        assert zones == {"zone-0", "zone-1", "zone-2"}
        assert all(n.allocatable["cpu"] == 4000 for n in nodes)
        assert all(n.allocatable["pods"] == 110 for n in nodes)

    def test_label_fractions_deterministic(self):
        st = NodeStrategy(count=100, label_fracs={"disk": ("ssd", 0.5)})
        a = make_hollow_nodes(st, seed=7)
        b = make_hollow_nodes(st, seed=7)
        assert [n.labels.get("disk") for n in a] == [n.labels.get("disk") for n in b]
        frac = sum(1 for n in a if "disk" in n.labels) / 100
        assert 0.3 < frac < 0.7

    def test_populate_with_existing_pods(self):
        store = Store()
        n, p = populate_store(store, [NodeStrategy(count=5)],
                              [PodStrategy(count=12, name_prefix="existing")])
        assert (n, p) == (5, 12)
        pods, _ = store.list(PODS)
        assert all(pod.node_name for pod in pods)
        hosts = {pod.node_name for pod in pods}
        assert len(hosts) == 5  # round-robin spread


@pytest.mark.parametrize("workload", ["plain", "anti-affinity", "node-affinity"])
@pytest.mark.parametrize("use_tpu", [True, False])
class TestPerfRuns:
    def test_small_cell_schedules_everything(self, workload, use_tpu):
        cfg = PerfConfig(nodes=20, existing_pods=10, pods=15, workload=workload,
                         use_tpu=use_tpu, burst=16 if use_tpu else 0,
                         zones=2)
        result = run(cfg, warmup=4)
        if workload == "anti-affinity":
            # one pod per node max; 10 existing occupy 10 hosts' labels...
            # existing pods share the same labels, so only nodes without an
            # existing 'density' pod can take one
            assert result.scheduled >= 5
        else:
            assert result.scheduled == 15
        assert result.throughput > 0

    def test_constraints_respected(self, workload, use_tpu):
        cfg = PerfConfig(nodes=10, existing_pods=0, pods=8, workload=workload,
                         use_tpu=use_tpu, burst=8 if use_tpu else 0)
        store, sched = setup(cfg)
        from kubernetes_tpu.models.hollow import make_pods as mp
        from kubernetes_tpu.perf.harness import _pod_strategy, _drain
        for pod in mp(_pod_strategy(cfg, cfg.pods, "w"), 0):
            store.create(PODS, pod)
        sched.pump()
        _drain(sched, cfg)
        sched.pump()
        pods, _ = store.list(PODS)
        placed = [p for p in pods if p.node_name]
        if workload == "anti-affinity":
            hosts = [p.node_name for p in placed]
            assert len(hosts) == len(set(hosts))  # one per topology
        if workload == "affinity":
            assert len({p.node_name for p in placed}) == 1  # co-located
        if workload == "node-affinity":
            nodes = {n.name: n for n in store.list(NODES)[0]}
            assert all(nodes[p.node_name].labels.get("perf-group") in ("a", "b")
                       for p in placed)


class TestBurstSerialEquivalence:
    """Burst mode must produce byte-identical placements to the serial loop
    even for workloads whose masks depend on in-burst placements (the shell
    segments those onto the serial path)."""

    @pytest.mark.parametrize("workload", ["plain", "anti-affinity", "affinity",
                                          "node-affinity"])
    def test_burst_equals_serial(self, workload):
        from kubernetes_tpu.perf.harness import _pod_strategy, _drain

        def go(burst):
            cfg = PerfConfig(nodes=6, existing_pods=0, pods=10,
                             workload=workload, use_tpu=True, burst=burst)
            store, sched = setup(cfg)
            for pod in make_pods(_pod_strategy(cfg, cfg.pods, "w"), 0):
                store.create(PODS, pod)
            sched.pump()
            _drain(sched, cfg)
            sched.pump()
            pods, _ = store.list(PODS)
            return sorted((p.name, p.node_name) for p in pods)

        assert go(16) == go(0)


class TestE2EDensity:
    """density.go analog through the full cluster-in-a-process pipeline:
    saturation throughput >= 8 pods/s and p99 startup <= 5s SLOs."""

    def test_density_slos(self):
        from kubernetes_tpu.perf.harness import run_e2e_density
        r = run_e2e_density(n_nodes=10, n_pods=30, use_tpu=False)
        assert r["saturated"]
        assert r["throughput_slo_8pps"], r
        assert r["startup_slo_5s"], r
        assert r["node_churn"] is None   # off by default

    def test_density_survives_node_churn(self):
        """Round-14 soak ingredient: a node deleted at half-load (and
        restored shortly after) must not cost saturation or the SLOs —
        in-flight decisions referencing it refuse stale and replan."""
        from kubernetes_tpu.perf.harness import run_e2e_density
        r = run_e2e_density(n_nodes=10, n_pods=30, use_tpu=True,
                            node_churn=True)
        assert r["saturated"], r
        assert r["throughput_slo_8pps"], r
        assert r["node_churn"] is not None and r["node_churn"]["restored"]


class TestSpreadWorkload:
    def test_spread_cell_schedules_and_spreads(self):
        """The spread lane: a Service selects the measured pods, so
        SelectorSpread's node+zone blend drives placement."""
        cfg = PerfConfig(nodes=12, existing_pods=0, pods=24,
                         workload="spread", use_tpu=True, burst=16)
        result = run(cfg, warmup=4)
        assert result.scheduled == 24


class TestShardMatrix:
    """Round-15 fleet-scale cells: the node axis sharded over the conftest
    8-device mesh through the single-dispatch burst path."""

    def test_shard_cell_small_verified(self):
        """Fast smoke: a 4096-node cell with the single-device parity
        referee enabled (verify doubles the runtime, so only the smoke
        cell pays it in tier-1; the fuzz suites + sweep_shard_seeds pin
        parity at every shape)."""
        from kubernetes_tpu.perf.harness import run_shard_cell
        r = run_shard_cell(4096, 256, verify=True)
        assert r["devices"] == 8
        assert r["pods_bound"] == 256
        assert r["per_device_node_rows"] == 4096 // 8
        assert r["verified_vs_single_device"]

    @pytest.mark.slow
    def test_shard_cell_50k_nodes(self):
        """The ISSUE-11 acceptance cell: >= 50k nodes through the sharded
        path — a node count whose resident planes + victim table do not
        fit one chip's HBM budget."""
        from kubernetes_tpu.perf.harness import run_shard_cell
        nodes, pods = 50_000, 2000
        r = run_shard_cell(nodes, pods)
        assert r["devices"] == 8
        assert r["pods_bound"] == pods
        assert r["per_device_node_rows"] * r["devices"] >= nodes
