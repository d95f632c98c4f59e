"""Benchmark: scheduler_perf-style throughput through the full pipeline.

Mirrors test/integration/scheduler_perf (reference: scheduler_test.go:68,
scheduler_bench_test.go:39): N fake nodes (110 pods / 4 CPU / 32Gi each,
zone-labeled), P pending pods created through the store, scheduled by the
TPU burst path (store -> informers -> cache/queue -> fused kernel ->
assume/bind). Prints ONE JSON line.

Baseline semantics (be precise about what the ratios divide by):
- `vs_baseline` divides by the reference harness's 100 pods/s "healthy
  scheduler" CI warn threshold (scheduler_test.go:35-38) — a CI floor, NOT
  a measured Go-scheduler run.
- `vs_measured_oracle` divides by a measured run of this repo's pure-Python
  oracle (the exact-semantics referee) at the same node count — the honest
  apples-to-apples ratio.

The default run also emits:
- `matrix`: the scheduler_bench_test.go-style workload lanes (plain /
  anti-affinity / affinity / node-affinity / spread at 1000 nodes / 1000
  existing / 1000 measured pods, median of repeats, reference
  scheduler_bench_test.go:39-131) plus the preemption victim-scan lane —
  so every burst kernel lane is driver-captured, not self-reported.
- `mesh`: the same north-star workload with the node axis sharded over a
  jax.sharding.Mesh of every visible device (the BASELINE.json configs[4]
  path; on a single chip this is a 1-device mesh exercising the sharded
  program — guarding against mesh-mode throughput regressions).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

BASELINE_NOTE = ("vs_baseline = throughput / 100 pods/s, the reference "
                 "harness CI warn floor (scheduler_test.go:35-38), not a "
                 "measured Go run; vs_measured_oracle is measured")


def build_cluster(store, n_nodes: int):
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.store.store import NODES
    GI = 1024 ** 3
    for i in range(n_nodes):
        store.create(NODES, Node(
            name=f"node-{i}",
            labels={"failure-domain.beta.kubernetes.io/zone": f"zone-{i % 3}",
                    "failure-domain.beta.kubernetes.io/region": "r1",
                    "kubernetes.io/hostname": f"node-{i}"},
            allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))


def make_pods(store, n_pods: int, start: int = 0):
    from kubernetes_tpu.api.types import Pod, Container
    from kubernetes_tpu.store.store import PODS
    MI = 1024 ** 2
    for j in range(start, start + n_pods):
        store.create(PODS, Pod(
            name=f"pod-{j}", labels={"app": "density"},
            containers=(Container.make(
                name="c", requests={"cpu": 100, "memory": 500 * MI}),)))


def _make_mesh(n_devices=None):
    from kubernetes_tpu.parallel import sharding as S
    return S.make_mesh(n_devices)


def _ici_total() -> float:
    """Current sum of the analytic ICI all-gather counter across ops."""
    from kubernetes_tpu.core.tpu_scheduler import ICI_ALLGATHER
    return sum(c.value for c in ICI_ALLGATHER._children.values())


def _pad_capacity(n: int) -> int:
    cap = 8
    while cap < n:
        cap *= 2
    return cap


def attach_device_report(result: dict, mesh, n_nodes: int,
                         ici0: float) -> dict:
    """The device fields every mode's one-line JSON carries: `platform` and
    `device_kind` as jax reports them (a number without them cannot be told
    from a CPU run), `devices` (mesh size; 1 off-mesh),
    `per_device_node_rows` (the node matrix's padded rows per shard — the
    HBM scale axis), and `ici_allgather_bytes` (the analytic cross-device
    traffic model booked by the sharded kernels during the run; 0
    off-mesh)."""
    import jax
    dev = jax.devices()[0]
    result["platform"] = dev.platform
    result["device_kind"] = dev.device_kind
    devices = int(mesh.devices.size) if mesh is not None else 1
    result["devices"] = devices
    result["per_device_node_rows"] = (
        _pad_capacity(n_nodes) // devices if n_nodes else 0)
    result["ici_allgather_bytes"] = int(_ici_total() - ici0)
    return result


def measure_oracle(n_nodes: int, n_pods: int) -> float:
    """Measured pods/s of the pure-Python oracle at the same node count.
    The oracle's per-pod cost is O(nodes) and flat in pod count (each cycle
    filters+scores the whole cluster), so a small pod sample measures the
    same per-cycle cost the full run would — `oracle_pods_sampled` records
    the sample size."""
    r = run_bench(n_nodes, n_pods, "oracle", 0, compare=False)
    return r["value"]


def run_bench(n_nodes: int, n_pods: int, mode: str, burst: int,
              compare: bool = True, mesh=None,
              chaos_rates: Optional[dict] = None,
              chaos_seed: int = 42, chaos_limit: int = 5) -> dict:
    from kubernetes_tpu.store.store import Store
    from kubernetes_tpu.scheduler import Scheduler

    store = Store(watch_log_size=max(65536, 2 * (n_nodes + n_pods)))
    build_cluster(store, n_nodes)
    sched = Scheduler(store, use_tpu=(mode != "oracle"),
                      percentage_of_nodes_to_score=100, mesh=mesh)
    sched.sync()

    # warmup: trigger jit compilation outside the timed window
    make_pods(store, min(64, n_pods), start=10_000_000)
    sched.pump()
    if mode == "serial" or mode == "oracle":
        while sched.schedule_one(timeout=0.0):
            pass
    else:
        while sched.schedule_burst(max_pods=burst):
            pass
    sched.pump()

    make_pods(store, n_pods)
    sched.pump()
    if mode != "oracle":
        from kubernetes_tpu.core.tpu_scheduler import (DEVICE_DISPATCH,
                                                       DEVICE_FETCHES)
        fam_total = lambda fam: sum(c.value for c in fam._children.values())
        disp0 = fam_total(DEVICE_DISPATCH)
        fetch0 = fam_total(DEVICE_FETCHES)
    # pod-lifecycle ledger: reset AFTER warmup so the startup percentiles
    # and phase split cover exactly the measured pods (warmup pods carry
    # jit-compile time in their dispatch phase). NOTE: the measured pods
    # were just enqueued by the pump above — re-stamp their arrival so the
    # queue phase starts at the timed window, not at creation.
    from kubernetes_tpu.obs.ledger import LEDGER
    LEDGER.reset()
    for p in sched.queue.pending_pods()["active"]:
        LEDGER.stamp_enqueue(p.key)
    # chaos lane: install the deterministic injection plan AFTER warmup
    # (compiles ride the happy path) so the timed loop measures
    # degraded-mode throughput with faults firing at every enabled seam.
    # The fused pipeline is so batched that a whole burst is a handful of
    # seam draws — shrink the commit windows so the store/fan-out seams
    # actually see traffic during the measured run.
    plan = None
    if chaos_rates:
        from kubernetes_tpu import chaos as chaos_mod
        if getattr(sched.algorithm, "wave_size", 0):
            sched.algorithm.wave_size = min(sched.algorithm.wave_size, 256)
        breaker = getattr(sched.algorithm, "breaker", None)
        if breaker is not None:
            # a refused gate here is a whole BURST rerun on the serial
            # twin (seconds, not microseconds) — probe after 2 refusals,
            # not 16, or an early trip pins the entire bench run to
            # host-only mode and the lane measures the twin, not the mix
            breaker.probe_after = 2
        plan = chaos_mod.plan(seed=chaos_seed, rates=chaos_rates,
                              limit=chaos_limit)
    bound = 0
    t0 = time.perf_counter()
    if mode == "serial" or mode == "oracle":
        while sched.schedule_one(timeout=0.0):
            bound += 1
    else:
        while True:
            n = sched.schedule_burst(max_pods=burst)
            if n == 0:
                break
            bound += n
            if plan is not None:
                # per-round pump: the watch-path seams (watch.drop,
                # deferred fan-out delivery) draw inside the measured
                # window, and the informers absorb injected drops with
                # the re-list + backoff machinery under test
                sched.pump()
    elapsed = time.perf_counter() - t0
    injections = None
    if plan is not None:
        from kubernetes_tpu import chaos as chaos_mod
        injections = plan.counts()
        chaos_mod.disable()   # confirm/audit below runs injection-free
    # one parent span over the timed loop — the per-launch encode /
    # dispatch / fetch spans the TPU pipeline records nest under it in the
    # trace viewer (bench.py --trace)
    from kubernetes_tpu.obs import trace as obs_trace
    obs_trace.add_span(f"bench.schedule_loop.{mode}", t0, t0 + elapsed,
                       args={"bound": bound, "nodes": n_nodes})
    sched.pump()  # confirm bindings

    throughput = bound / elapsed if elapsed > 0 else 0.0
    tag = "_mesh" if mesh is not None else ""
    if chaos_rates:
        tag += "_chaos"
    result = {
        "metric": f"sched_throughput_{n_nodes}n_{n_pods}p_{mode}{tag}",
        "value": round(throughput, 1),
        "unit": "pods/s",
        "vs_baseline": round(throughput / 100.0, 2),
    }
    if injections is not None:
        # the chaos lane's scoreboard: which faults fired (deterministic
        # per seed) and what the degradation machinery did with them
        result["chaos"] = {
            "seed": chaos_seed,
            "rates": {k: v for k, v in chaos_rates.items()},
            "limit_per_seam": chaos_limit,
            "injections": injections,
            "injections_total": sum(injections.values()),
            "breaker": sched.algorithm.breaker.debug_state()
            if getattr(sched.algorithm, "breaker", None) is not None
            else None,
            "store_impl": store.core_impl,
        }
        # degraded-mode correctness audit (the gang lane's posture): every
        # measured pod landed exactly once despite the injected faults
        from kubernetes_tpu.store.store import PODS as _PODS
        measured = sum(
            1 for p in store.list(_PODS)[0]
            if p.node_name and int(p.name.rsplit("-", 1)[1]) < n_pods)
        assert bound == n_pods, \
            f"chaos lane lost pods: bound {bound} of {n_pods}"
        assert measured == n_pods, \
            f"chaos lane store audit: {measured} != {n_pods} bound in store"
    if mode != "oracle":
        # the round-10 launch economy, driver-captured: a fused burst is
        # exactly ONE dispatch and ONE packed fetch (the headline 10k-pod
        # burst reports 1/1 here; per-wave fetches would show as ~3x)
        result["device_dispatches"] = int(fam_total(DEVICE_DISPATCH) - disp0)
        result["device_fetches"] = int(fam_total(DEVICE_FETCHES) - fetch0)
    # pod-startup SLO percentiles + per-phase latency decomposition from
    # the lifecycle ledger (the soak scoreboard fields, ROADMAP item 5)
    led = LEDGER.snapshot()
    result["startup_p50"] = led["startup_p50"]
    result["startup_p99"] = led["startup_p99"]
    result["phase_split"] = led["phase_split"]
    result["pods_completed"] = led["pods_completed"]
    if compare and mode != "oracle":
        # measured same-node-count oracle ratio next to the fixed 100 pods/s
        # CI floor (the oracle's per-pod cost is flat in pod count; sample a
        # small burst of pods at full cluster size)
        sample = min(n_pods, 100)
        oracle = measure_oracle(n_nodes, sample)
        result["oracle_measured"] = oracle
        result["oracle_pods_sampled"] = sample
        result["vs_measured_oracle"] = (round(throughput / oracle, 2)
                                        if oracle > 0 else None)
    return result


def run_churn_bench(n_nodes: int, n_pods: int, burst: int,
                    churn_seed: int = 42, kill_every: int = 2,
                    rounds: int = 10, mesh=None) -> dict:
    """`--mode churn`: steady bursts under a node kill/restore schedule
    (the round-14 robustness lane). Every `kill_every`-th round one node
    is DELETED mid-burst through the node.dead seam (the launch-refusal
    contract replans in-flight decision blocks) and one node flips
    NotReady (its pods ride the zone-paced NoExecute eviction queue
    through the PDB-guarded verb); both return two rounds later. PodGC
    force-deletes pods stranded on deleted nodes (NodeLost) and the
    bench's workload controller recreates everything lost, so the lane
    measures DEGRADED pods/s with the full churn plane active. The JSON
    reports evictions paced per zone, stale-launch refusals, NodeLost
    recreates, and the end-state audit (every surviving pod bound)."""
    import random
    from kubernetes_tpu import chaos as chaos_mod
    from kubernetes_tpu.api.types import Container, NodeCondition, Pod
    from kubernetes_tpu.controllers.nodelifecycle import (
        NodeLifecycleController)
    from kubernetes_tpu.controllers.podgc import PodGCController
    from kubernetes_tpu.store.store import (
        Store, EVICTIONS, NODES, PODS, NotFoundError)
    from kubernetes_tpu.scheduler import Scheduler, STALE_BINDS

    MI = 1024 ** 2
    rng = random.Random(churn_seed)
    store = Store(watch_log_size=max(65536, 4 * (n_nodes + n_pods)))
    build_cluster(store, n_nodes)
    node_spec = {n.name: n.clone() for n in store.list(NODES)[0]}
    sched = Scheduler(store, use_tpu=True,
                      percentage_of_nodes_to_score=100, mesh=mesh)
    sched.sync()
    # eviction pacing fast enough to SEE in a seconds-long bench window,
    # still visibly paced (not unbounded): 50 evictions/s/zone, burst 8
    nlc = NodeLifecycleController(store, eviction_rate=50.0,
                                  eviction_burst=8.0)
    gc = PodGCController(store)
    nlc.sync()
    gc.sync()

    # warmup: jit compiles outside the timed window
    make_pods(store, min(64, n_pods), start=10_000_000)
    sched.pump()
    while sched.schedule_burst(max_pods=burst):
        pass
    sched.pump()

    pending_kill: list = []

    def hook(point):
        if pending_kill:
            victim = pending_kill.pop()
            try:
                store.delete(NODES, victim)
            except NotFoundError:
                pass
    chaos_mod.plan(seed=churn_seed, rates={"node.dead": 1.0})
    chaos_mod.set_node_hook(hook)

    stale0 = STALE_BINDS.value
    evict0 = {tuple(k): c.value
              for k, c in EVICTIONS._children.items()}
    dead: list = []          # (round_killed, name)
    not_ready: list = []     # (round_flipped, name)
    killed = restored = recreated = 0
    rec_seq = 0
    per_round = max(1, n_pods // rounds)
    bound_total = 0
    t0 = time.perf_counter()
    for rnd in range(rounds):
        # restore: deleted nodes return (fresh object, same name) and
        # NotReady nodes heal after two rounds
        while dead and dead[0][0] <= rnd - 2:
            _r, name = dead.pop(0)
            store.create(NODES, node_spec[name].clone())
            restored += 1
        while not_ready and not_ready[0][0] <= rnd - 2:
            _r, name = not_ready.pop(0)

            def heal(n):
                n.conditions = (NodeCondition(type="Ready", status="True"),)
                return n
            try:
                store.guaranteed_update(NODES, name, heal)
            except NotFoundError:
                pass
        if rnd % kill_every == 0:
            live = sorted(n.name for n in store.list(NODES)[0]
                          if not any(c.status != "True"
                                     for c in n.conditions))
            if len(live) > 2:
                victim = rng.choice(live)
                pending_kill.append(victim)   # dies MID-BURST via the seam
                dead.append((rnd, victim))
                killed += 1
                flip = rng.choice([n for n in live if n != victim])

                def sicken(n):
                    n.conditions = (NodeCondition(type="Ready",
                                                  status="False"),)
                    return n
                try:
                    store.guaranteed_update(NODES, flip, sicken)
                    not_ready.append((rnd, flip))
                except NotFoundError:
                    pass
        make_pods(store, per_round, start=rnd * per_round)
        sched.pump()
        while True:
            n = sched.schedule_burst(max_pods=burst)
            if n == 0:
                break
            bound_total += n
            sched.pump()
        if pending_kill:          # idle round: apply at the boundary
            hook("boundary")
        # lifecycle plane: health grading + taints + paced evictions,
        # then PodGC sweeps pods stranded on deleted nodes
        before_ct = store.count(PODS)
        nlc.pump()
        gc.pump()
        destroyed = before_ct - store.count(PODS)
        # the workload controller recreates what churn destroyed
        # (taint-manager evictions + NodeLost force-deletes)
        for _i in range(max(0, destroyed)):
            store.create(PODS, Pod(
                name=f"pod-r{rec_seq}", labels={"app": "density"},
                containers=(Container.make(
                    name="c",
                    requests={"cpu": 100, "memory": 500 * MI}),)))
            rec_seq += 1
            recreated += 1
        sched.pump()
    elapsed = time.perf_counter() - t0
    chaos_mod.disable()
    # convergence drain: heal everything, reschedule whatever churn threw
    # back into the queue (real-clock backoffs expire in wall time)
    while dead:
        _r, name = dead.pop(0)
        store.create(NODES, node_spec[name].clone())
        restored += 1
    while not_ready:
        _r, name = not_ready.pop(0)

        def heal(n):
            n.conditions = (NodeCondition(type="Ready", status="True"),)
            return n
        try:
            store.guaranteed_update(NODES, name, heal)
        except NotFoundError:
            pass
    deadline = time.perf_counter() + 60
    while time.perf_counter() < deadline:
        sched.pump()
        nlc.pump()
        gc.pump()
        n = sched.schedule_burst(max_pods=burst)
        bound_total += n
        pending_now = [p for p in store.list(PODS)[0] if not p.node_name]
        if not pending_now and n == 0:
            break
        time.sleep(0.05)
    unbound = sum(1 for p in store.list(PODS)[0] if not p.node_name)
    evict_by_reason = {
        k[0]: c.value - evict0.get(tuple(k), 0.0)
        for k, c in EVICTIONS._children.items()
        if c.value - evict0.get(tuple(k), 0.0) > 0}
    zones = nlc.debug_state()["zones"]
    return {
        "metric": f"churn_throughput_{n_nodes}n_{n_pods}p",
        "value": round(bound_total / elapsed if elapsed > 0 else 0.0, 1),
        "unit": "pods/s",
        "baseline_note": "degraded pods/s: binds (incl. churn-recreated "
                         "pods) over the kill/restore window",
        "rounds": rounds,
        "nodes_killed": killed,
        "nodes_restored": restored,
        "pods_recreated": recreated,
        "stale_launch_refusals": int(STALE_BINDS.value - stale0),
        "evictions_by_reason": evict_by_reason,
        "evictions_per_zone": {z: v["evicted"] for z, v in zones.items()
                               if v["evicted"]},
        "zone_pacing": {z: {"state": v["state"], "rate": v["rate"],
                            "tokens": v["tokens"]}
                        for z, v in zones.items()},
        "audit_all_bound": unbound == 0,
        "pods_unbound_final": unbound,
    }


def run_preempt_bench(n_nodes: int, n_victims: int,
                      n_preemptors: int = 128, mesh=None) -> dict:
    """BASELINE.md configs[3]: preemption victim scans over `n_victims`
    lower-priority pods. A pressure wave of `n_preemptors` failed pods runs
    as ONE schedule-else-preempt launch on the device
    (kernels.pressure_batch) versus the serial oracle loop doing the same
    work: schedule -> FitError -> victim scan -> nominate per pod, each
    scan seeing the nominations before it (the reference fans
    selectVictimsOnNode over 16 goroutines PER pod,
    generic_scheduler.go:996; every launch pays a dispatch+fetch round
    trip, which the batched wave pays once). The device side
    rides the WARM persistent victim table (the steady-state condition —
    perf.harness.run_preempt_cell) and the JSON reports the per-wave
    encode vs device-scan phase split, mirroring the matrix lanes.
    Decisions are asserted identical before timing is reported."""
    from kubernetes_tpu.perf.harness import run_preempt_cell
    r = run_preempt_cell(n_nodes, n_victims, n_preemptors, mesh=mesh)
    return {
        "metric": f"preempt_scan_{n_nodes}n_{n_victims}victims",
        "value": r["scans_per_s"],
        "unit": "scans/s",
        "vs_baseline": r["vs_oracle"],
        "preemptors_per_wave": n_preemptors,
        "device_seconds": r["device_seconds"],
        "oracle_seconds": r["oracle_seconds"],
        "encode_seconds": r["encode_seconds"],
        "scan_seconds": r["scan_seconds"],
        "warm_victim_table": True,
    }


def run_gang_bench(n_nodes: int, pods_budget: int = 10000,
                   gang_sizes: tuple = (8, 64, 512), mesh=None,
                   profiles: bool = False) -> dict:
    """`--mode gang`: all-or-nothing PodGroup throughput over the same
    cell as the headline bench. Gangs of 8/64/512 spec-identical members
    (the SPMD-rank shape) split `pods_budget` three ways; every group must
    land whole — the run FAILS if any group is partially bound (the gang
    atomicity contract, driver-checked). Prints the same one-line JSON,
    which always carries `gang_locality` — the fraction of bound gangs
    whose members all landed in ONE zone.

    `--profiles` (round 19) runs TWO lanes in one invocation on identical
    workloads: a placement-blind PROFILE (default weight vector — its
    decisions are bit-identical to the no-profile scheduler by the
    per-profile parity contract) and a rank-aware profile (gang
    set-scoring). Both lanes ride the [profiles x priorities] tensor
    machinery, so their ratio isolates exactly what the knob costs — the
    set-scoring objective — not the tensor plumbing both share (that
    delta is visible against the plain `--mode gang` lane). The JSON
    reports per-lane locality + throughput; the test_bench_floors pin is
    rank-aware locality >= blind locality at >= 0.9x blind throughput."""
    from kubernetes_tpu.api.types import Pod, Container
    from kubernetes_tpu.coscheduling.types import LABEL_POD_GROUP, PodGroup
    from kubernetes_tpu.store.store import Store, NODES, PODS, PODGROUPS
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.api.types import get_zone_key
    MI = 1024 ** 2
    per_size = max(pods_budget // len(gang_sizes), max(gang_sizes))
    plan = []   # (group name, size)
    for size in gang_sizes:
        for g in range(max(1, per_size // size)):
            plan.append((f"gang-{size}-{g}", size))
    n_pods = sum(size for _, size in plan)

    def run_lane(pset, sched_name: str) -> dict:
        store = Store(watch_log_size=max(65536, 4 * (n_nodes + n_pods)))
        build_cluster(store, n_nodes)
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100,
                          mesh=mesh, profiles=pset)
        sched.sync()

        def create_gangs(tag: str, the_plan) -> int:
            total = 0
            for gname, size in the_plan:
                name = f"{tag}{gname}"
                store.create(PODGROUPS, PodGroup(name=name,
                                                 min_member=size))
                for r in range(size):
                    store.create(PODS, Pod(
                        name=f"{name}-r{r}",
                        scheduler_name=sched_name,
                        labels={LABEL_POD_GROUP: name, "app": "gang"},
                        containers=(Container.make(
                            name="c",
                            requests={"cpu": 100, "memory": 500 * MI}),)))
                total += size
            return total

        # warmup: a FULL-SIZE plan drains untimed first, so every wave
        # bucket the measured drain will hit — including the drain-window
        # bucket itself — is compiled outside the timed region (the
        # profile-tensor program compiles slower than the plain one, and
        # an in-window compile would charge that delta to the lane)
        create_gangs("warm-", [(f"w{g}", s) for g, s in plan])
        sched.pump()
        while sched.schedule_burst(max_pods=10000):
            pass
        sched.pump()

        create_gangs("", plan)
        sched.pump()
        bound = 0
        t0 = time.perf_counter()
        while True:
            n = sched.schedule_burst(max_pods=10000)
            if n == 0:
                break
            bound += n
        elapsed = time.perf_counter() - t0
        sched.pump()
        # atomicity audit: every group is bound whole or not at all —
        # plus the per-gang zone census for the locality score
        zone_of = {node.name: get_zone_key(node)
                   for node in store.list(NODES)[0]}
        by_group: dict[str, list] = {}
        zones_by_group: dict[str, set] = {}
        for p in store.list(PODS)[0]:
            g = p.labels.get(LABEL_POD_GROUP)
            if g:
                by_group.setdefault(g, []).append(bool(p.node_name))
                if p.node_name and not g.startswith("warm-"):
                    zones_by_group.setdefault(g, set()).add(
                        zone_of.get(p.node_name))
        partial = sorted(g for g, flags in by_group.items()
                         if any(flags) and not all(flags))
        assert not partial, f"partially bound gangs: {partial[:5]}"
        locality = (sum(1 for z in zones_by_group.values() if len(z) == 1)
                    / max(len(zones_by_group), 1))
        return {
            "throughput": round(bound / elapsed if elapsed > 0 else 0.0, 1),
            "locality": round(locality, 4),
            "bound": bound,
        }

    if profiles:
        from kubernetes_tpu.profiles import ProfileSet, SchedulingProfile
        blind = run_lane(ProfileSet([
            SchedulingProfile("default-scheduler"),
            SchedulingProfile("tenant-blind"),
        ]), "tenant-blind")
        rank = run_lane(ProfileSet([
            SchedulingProfile("default-scheduler"),
            SchedulingProfile("tenant-rank", rank_aware=True,
                              gang_weight=3),
        ]), "tenant-rank")
        return {
            "metric": f"gang_profiles_{n_nodes}n_{n_pods}p",
            "value": rank["throughput"],
            "unit": "pods/s",
            "vs_baseline": round(rank["throughput"]
                                 / max(blind["throughput"], 1e-9), 3),
            "gangs": {str(s): sum(1 for _g, sz in plan if sz == s)
                      for s in gang_sizes},
            "gang_locality": {"blind": blind["locality"],
                              "rank_aware": rank["locality"]},
            "throughput": {"blind": blind["throughput"],
                           "rank_aware": rank["throughput"]},
            "pods_bound": rank["bound"],
            "all_or_nothing": True,
            "profiles": True,
        }
    lane = run_lane(None, "default-scheduler")
    return {
        "metric": f"gang_throughput_{n_nodes}n_{n_pods}p",
        "value": lane["throughput"],
        "unit": "pods/s",
        "vs_baseline": round(lane["throughput"] / 100.0, 2),
        "gangs": {str(s): sum(1 for _g, sz in plan if sz == s)
                  for s in gang_sizes},
        "gang_locality": lane["locality"],
        "pods_bound": lane["bound"],
        "all_or_nothing": True,
    }


def run_serve_bench(n_nodes: int, arrival_rate: float, duration: float,
                    window: int = 2048, depth: int = 3,
                    max_depth: Optional[int] = None, mesh=None) -> dict:
    """`--mode serve`: the round-16 arrival-driven lane — pods ARRIVE at
    `arrival_rate`/s for `duration` seconds (hollow arrival clients with
    429-aware retry) while the ServeLoop cuts fused windows from the
    live activeQ under the N-deep launch queue, and the backpressure
    gate sheds past the watermark. Scores SUSTAINED pods/s (not a
    backlog drain) and the ledger's admission->commit startup
    percentiles against the density.go 5 s SLO; the cell's own audits
    (all-admitted-or-429'd, flight-recorder replay parity) gate the
    numbers. One JSON line, same multi-chip fields as every mode."""
    from kubernetes_tpu.perf.harness import run_serve_cell
    r = run_serve_cell(n_nodes, arrival_rate, duration, window=window,
                       depth=depth, max_depth=max_depth, mesh=mesh)
    adm = r["admission"]
    return {
        "metric": (f"serve_sustained_{n_nodes}n_"
                   f"{int(arrival_rate)}rps_{int(duration)}s"),
        "value": r["sustained_pods_per_s"],
        "unit": "pods/s",
        "baseline_note": "sustained pods/s over the arrival window "
                         "(bounded above by the arrival rate; the drain "
                         "benches measure peak, this lane measures "
                         "serving)",
        "arrival_rate": arrival_rate,
        "duration_s": r["duration"],
        "window": r["window"],
        "launch_depth": r["depth"],
        "windows_cut": r["windows_cut"],
        "startup_p50": r["startup_p50"],
        "startup_p99": r["startup_p99"],
        "startup_slo_5s": r["startup_slo_ok"],
        "phase_split": r["phase_split"],
        "prologue_phase_split": r["prologue_phase_split"],
        "pods_completed": r["pods_completed"],
        "admission_admitted": adm["admitted"],
        "admission_rejected": adm["rejected"],
        "arrivals": r["arrivals"],
        "audit_all_admitted_or_429": r["audit_all_admitted_or_429"],
        "parity_violations": r["parity_violations"],
    }


def run_fleet_bench(n_nodes: int, instances: int, arrival_rate: float,
                    duration: float, window: int = 2048,
                    depth: int = 3) -> dict:
    """`--mode fleet` (round 18): the active-active fleet lane — measure
    the SOLO serve baseline first (one scheduler, same store shape, same
    arrival rate, same duration), then `instances` partitioned fleet
    members on their own threads against one shared store at the same
    rate, and report aggregate pods/s with the ratio. The acceptance
    gate is `vs_solo_serve >= 1.0` WITH the in-bench zero-double-bind
    audit: an aggregate number bought by a double-bind is not a number.
    Whether N instances pass one process's rate on a chip's host is not
    measured; on the CPU backend the claim is parity-at-rate plus the
    robustness audits. One JSON line."""
    from kubernetes_tpu.perf.harness import run_fleet_cell, run_serve_cell
    solo = run_serve_cell(n_nodes, arrival_rate, duration,
                          window=window, depth=depth)
    fleet = run_fleet_cell(n_nodes, instances=instances,
                           arrival_rate=arrival_rate, duration=duration,
                           window=window, depth=depth)
    solo_rate = solo["sustained_pods_per_s"]
    agg = fleet["aggregate_pods_per_s"]
    return {
        "metric": (f"fleet_aggregate_{instances}x_{n_nodes}n_"
                   f"{int(arrival_rate)}rps_{int(duration)}s"),
        "value": agg,
        "unit": "pods/s",
        "baseline_note": "aggregate fleet pods/s vs the solo serve "
                         "baseline measured in the SAME run (same store "
                         "shape, arrival rate, and duration)",
        "instances": fleet["instances"],
        "shards": fleet["shards"],
        "arrival_rate": arrival_rate,
        "duration_s": fleet["duration"],
        "solo_serve_pods_per_s": solo_rate,
        "vs_solo_serve": round(agg / solo_rate, 3) if solo_rate else None,
        "per_instance_pods_bound": fleet["per_instance_pods_bound"],
        "startup_p99": fleet["startup_p99"],
        "startup_slo_5s": fleet["startup_slo_ok"],
        # the robustness audits that gate the number
        "double_binds": fleet["double_binds"],
        "audit_no_double_bind": fleet["audit_no_double_bind"],
        "audit_all_admitted_or_429": fleet["audit_all_admitted_or_429"],
        "partition_disjoint": fleet["partition_disjoint"],
        "fenced_waves": fleet["fenced_waves"],
        "bind_conflicts_requeued": fleet["bind_conflicts_requeued"],
        "bind_conflicts_fenced": fleet["bind_conflicts_fenced"],
        "admission_admitted": fleet["admission"]["admitted"],
        "admission_rejected": fleet["admission"]["rejected"],
        "arrivals": fleet["arrivals"],
        "solo_startup_p99": solo["startup_p99"],
        "solo_parity_violations": solo["parity_violations"],
    }


def run_soak_bench(n_nodes: int, instances: int, arrival_rate: float,
                   duration: float, watchers: int, watch_classes: int,
                   window: int = 2048, depth: int = 3, seed: int = 0,
                   soak_out: str = None) -> dict:
    """`--mode soak` (round 21): the soak scoreboard — fleet mode x
    mixed profiles x serve arrivals x steady-state churn (rolling
    updates, zone-paced node drains, gang arrivals, HPA oscillation,
    low-rate chaos) with 10k-100k shared-class watchers attached, the
    in-process time-series scraper sampling the whole registry
    throughout, and the verdict engine reading the trajectories
    (perf.soak.run_soak_cell). One JSON line carries the summary +
    every verdict; `--soak-out` writes the full SOAK artifact
    (config + trajectories + verdicts + audits)."""
    from kubernetes_tpu.perf.soak import run_soak_cell
    r = run_soak_cell(n_nodes=n_nodes, duration=duration,
                      arrival_rate=arrival_rate, instances=instances,
                      watchers=watchers, watch_classes=watch_classes,
                      window=window, depth=depth, seed=seed,
                      soak_out=soak_out)
    out = {
        "metric": (f"soak_{instances}x_{n_nodes}n_{int(arrival_rate)}rps"
                   f"_{int(duration)}s_{watchers}w"),
        "value": r["aggregate_pods_per_s"],
        "unit": "pods/s",
        "baseline_note": "sustained aggregate pods/s under the full "
                         "churn+chaos+watcher composition; the verdicts "
                         "say what (if anything) fell over first",
    }
    out.update(r)
    return out


def run_tune_bench(n_nodes: int, arrival_rate: float, duration: float,
                   window: int = 512, depth: int = 2, seed: int = 0,
                   search_budget: int = 48) -> dict:
    """`--mode tune` (round 22): the closed-loop learned-scoring lane —
    record flight-recorder worlds, run the seeded offline search (with
    the in-cell determinism audit), then serve a two-instance shadow
    A/B split where the tuner installs the searched row MID-RUN via
    ProfileSet.set_row and the promotion gate judges the windowed
    evidence at the end. The acceptance floor: the tuned shadow lane
    beats the incumbent default row on the cell's objective (windowed
    p99 and/or packing utilization) at >= 0.9x throughput, with zero
    parity violations and zero double-binds. One JSON line."""
    from kubernetes_tpu.perf.harness import run_tuner_cell
    r = run_tuner_cell(n_nodes, arrival_rate=arrival_rate,
                       duration=duration, window=window, depth=depth,
                       seed=seed, search_budget=search_budget)
    out = {
        "metric": (f"tune_shadow_ab_{n_nodes}n_{int(arrival_rate)}rps_"
                   f"{int(duration)}s"),
        "value": r["lanes"]["shadow"]["utilization"],
        "unit": "mean_node_cpu_fill",
        "baseline_note": "shadow (tuned row) lane's packing utilization "
                         "vs the incumbent default-row lane in the SAME "
                         "run; objective_win + the throughput ratio are "
                         "the floor's inputs",
    }
    out.update(r)
    return out


def run_commit_bench(n_pods: int = 4096, waves: int = 8,
                     watchers: int = 8, watch_classes: int = 1) -> dict:
    """`--mode commit`: the round-11 commit-core lane — the store-write +
    watch-fan-out tail of a burst wave in isolation (ONE commit_wave +
    ONE fanout_wave call per wave; perf.harness.run_commit_cell). Runs
    the best-available core AND the pure-Python twin on the identical
    wave sequence and asserts the observable streams bit-identical
    (per-wave missing keys + resourceVersions, and the full first-watcher
    event stream) before reporting — the same in-bench referee posture as
    the gang lane's atomicity audit. One JSON line.

    Round 20: `--watchers N` scales the fan-out plane (N watchers split
    across `--watch-classes` shared subscription classes; default 1 —
    everyone shares one materialize-once/encode-once class). At >= 1000
    watchers the lane also measures the DEGENERATE class-per-watcher
    mode at min(1000, N) watchers in the same run: its copy-out rate is
    watcher-count-independent (every copy-out pays a materialization),
    so it IS the per-watcher-extrapolated cost the scaling floor divides
    by — `vs_per_watcher` >= 5 at 10k watchers is the sublinearity gate."""
    from kubernetes_tpu.perf.harness import run_commit_cell
    audit: list = []
    r = run_commit_cell(n_pods, waves, watchers, audit=audit,
                        watch_classes=watch_classes)
    twin_audit: list = []
    t = run_commit_cell(n_pods, waves, watchers, impl="twin",
                        audit=twin_audit, watch_classes=watch_classes)
    # referee: rv assignment, missing detection, and the watch sequence
    # must be bit-identical between the native core and the twin (both
    # runs replay the same op sequence from rv 0)
    assert audit[:-1] == twin_audit[:-1], "commit core rv/missing drift"
    assert audit[-1] == twin_audit[-1], "commit core watch-stream drift"
    serial = r["serial_writes_per_s"]
    out = {
        "metric": f"commit_core_{n_pods}p_{waves}w",
        "value": r["writes_per_s"],
        "unit": "writes/s",
        "vs_baseline": round(r["writes_per_s"] / 100.0, 2),
        "events_per_s": r["events_per_s"],
        "events_delivered": r["events_delivered"],
        "watchers": watchers,
        "subscription_classes": r["subscription_classes"],
        "copyout_events_per_sec": r["copyout_events_per_sec"],
        "copyout_bytes_per_sec": r["copyout_bytes_per_sec"],
        "copyout_materializations": r["copyout_materializations"],
        "copyout_shared_hits": r["copyout_shared_hits"],
        "impl": r["impl"],
        # the round-10 per-pod shape measured in the SAME run — the
        # throttle-proof normalizer the floor test divides by
        "serial_writes_per_s": serial,
        "vs_serial": round(r["writes_per_s"] / serial, 2) if serial else None,
        "twin_writes_per_s": t["writes_per_s"],
        "twin_parity": "ok",
    }
    if watchers >= 1000:
        # degenerate (pre-round-20 per-watcher) reference lane: same cell
        # shape, capped at 1000 watchers — per-event copy-out cost in this
        # mode does not depend on watcher count, so extrapolating it to
        # `watchers` is just using its rate as-is
        d = run_commit_cell(n_pods, waves, min(1000, watchers),
                            watch_classes=watch_classes,
                            shared_classes=False)
        deg = d["copyout_events_per_sec"]
        out["degenerate_watchers"] = d["watchers"]
        out["degenerate_events_per_s"] = deg
        out["vs_per_watcher"] = (round(r["copyout_events_per_sec"] / deg, 2)
                                 if deg else None)
    return out


# the non-plain lanes of the benchmark matrix at the reference's 1000-node /
# 1000-existing cell (scheduler_bench_test.go:61-118) plus the spread lane
MATRIX_LANES = ("plain", "anti-affinity", "affinity", "node-affinity",
                "spread")


def run_matrix(repeat: int = 2, nodes: int = 1000, existing: int = 1000,
               pods: int = 1000, big_nodes: int = 5000) -> dict:
    """Median pods/s per workload lane + the preemption scan lane — one dict
    the driver captures, so a regression in any burst kernel lane shows up
    in the bench output. A lane that raises fails the bench."""
    from kubernetes_tpu.perf.harness import PerfConfig, run
    out = {}

    def median_low(vals):
        # lower-middle for even counts: the upper-middle would
        # systematically report the optimistic run
        vals.sort()
        return round(vals[(len(vals) - 1) // 2], 1)

    def lane_median(key, cfg):
        out[key] = median_low([run(cfg).throughput
                               for _ in range(max(repeat, 1))])

    for lane in MATRIX_LANES:
        lane_median(lane.replace("-", "_"),
                    PerfConfig(nodes=nodes, existing_pods=existing,
                               pods=pods, workload=lane))
    # gang (PodGroup) cell: all-or-nothing groups of 64 at the same
    # nodes/pods shape (perf.harness.run_gang_cell asserts the atomicity
    # contract before reporting)
    from kubernetes_tpu.perf.harness import run_gang_cell

    out["gang"] = median_low([
        run_gang_cell(nodes=nodes, gang_size=64, pods=pods).throughput
        for _ in range(max(repeat, 1))])
    # BASELINE configs[2]: InterPodAffinity at 5000 nodes
    # (scheduler_bench_test.go:86-91's largest affinity cell)
    lane_median("affinity_5000n",
                PerfConfig(nodes=big_nodes, existing_pods=existing,
                           pods=pods, workload="affinity"))
    p = run_preempt_bench(1000, 10000)
    out["preempt_scans_per_s"] = p["value"]
    out["preempt_vs_oracle"] = p["vs_baseline"]
    out["preempt_phase_split"] = {"encode": p.get("encode_seconds"),
                                  "scan": p.get("scan_seconds")}
    out["cell"] = f"{nodes}n_{existing}existing_{pods}p"
    return out


def run_matrix_only(repeat: int = 2) -> dict:
    """`--mode matrix`: just the workload lanes plus each lane's
    ratio-to-plain — the one-command regression check for the spread /
    affinity encode-path cliffs (ISSUE 1 acceptance: spread >= 0.55x plain,
    affinity >= 0.8x plain at the 1000n/1000existing/1000p cell)."""
    out = run_matrix(repeat=repeat)
    plain = out.get("plain")
    ratios = {}
    for lane in ("anti_affinity", "affinity", "node_affinity", "spread",
                 "gang"):
        v = out.get(lane)
        ratios[lane] = (round(v / plain, 3)
                        if plain and v is not None else None)
    out["ratio_to_plain"] = ratios
    return out


def main():
    ap = argparse.ArgumentParser()
    # None = per-mode default: the headline burst runs the 15000-node cell,
    # `--mode preempt` the BASELINE configs[3] cell (1000 nodes — its serial
    # oracle referee replays the whole wave, so the 15000-node default would
    # spend minutes in the referee, not the device)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--pods", type=int, default=None)
    ap.add_argument("--mode",
                    choices=["burst", "serial", "oracle", "preempt", "matrix",
                             "gang", "commit", "chaos", "churn", "serve",
                             "fleet", "soak", "tune"],
                    default="burst")
    # `--mode fleet` (round 18): N partitioned scheduler instances on
    # their own threads against one shared store, vs the solo serve
    # baseline measured in the same run (lease claims, fenced writes,
    # zero-double-bind audit)
    ap.add_argument("--instances", type=int, default=2,
                    help="fleet mode: scheduler instances (2-8)")
    # `--mode gang --profiles` (round 19): placement-blind vs rank-aware
    # scheduling-profile lanes in one invocation, JSON reports per-lane
    # gang locality (fraction of gangs landing single-zone) + throughput
    ap.add_argument("--profiles", action="store_true",
                    help="gang mode: run blind + rank-aware profile lanes")
    ap.add_argument("--gang-sizes", default=None,
                    help="gang mode: comma-separated gang sizes "
                         "(default 8,64,512)")
    # `--mode serve` (round 16): arrival-driven serving — pods arrive at
    # --arrival-rate for --duration seconds (minutes-scale soaks: raise
    # --duration) while the ServeLoop cuts --serve-window-sized launch
    # windows at launch-queue depth --serve-depth and the backpressure
    # gate sheds past --max-queue-depth (default: 2s of arrivals)
    ap.add_argument("--arrival-rate", type=float, default=2000.0,
                    help="serve mode: pod arrivals per second")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="serve mode: seconds of sustained arrivals")
    ap.add_argument("--serve-window", type=int, default=2048,
                    help="serve mode: launch-window size (commit/failure "
                         "granularity)")
    ap.add_argument("--serve-depth", type=int, default=3,
                    help="serve mode: launch-queue depth (windows in "
                         "flight while the oldest commits)")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="serve mode: admission watermark (activeQ + "
                         "unpumped backlog); creates past it shed with "
                         "429 + Retry-After")
    # big bursts amortize the fixed per-launch cost (dispatch + fetch);
    # the uniform kernel's pod count is dynamic, so no padding waste at any
    # size — the cap is kernels.B_CAP per launch
    ap.add_argument("--burst", type=int, default=10000)
    # `--mode preempt` wave width: failed pods per schedule-else-preempt
    # launch (the serial oracle referee replays the same count). The
    # default is one full PRESSURE_B_CAP chunk: per-wave fixed costs
    # (encode residue, dispatch, the one fetch round trip) amortize over
    # the wave exactly like the scheduling lanes' 10k-pod bursts
    ap.add_argument("--preemptors", type=int, default=128)
    # `--mode commit` fan-out scaling (round 20): N watchers split across
    # --watch-classes shared (kind, selector) subscription classes; at
    # >= 1000 watchers the degenerate per-watcher reference lane runs in
    # the same invocation and the JSON gains vs_per_watcher (the
    # sublinear-scaling floor's ratio)
    ap.add_argument("--watchers", type=int, default=8,
                    help="commit mode: live pod watchers during the "
                         "timed waves")
    ap.add_argument("--watch-classes", type=int, default=1,
                    help="commit mode: distinct (kind, selector) "
                         "subscription classes the watchers split across")
    # `--mode chaos`: the fault plane's bench lane — the headline burst
    # workload with deterministic injection at every non-opt-in seam. The
    # JSON line carries injection counts per seam, breaker state, and the
    # degraded throughput next to the measured serial-oracle floor.
    ap.add_argument("--chaos-seed", type=int, default=42)
    ap.add_argument("--chaos-rate", type=float, default=0.1,
                    help="per-call injection probability applied to every "
                         "chaos seam (clock/crash/remote are opt-in only)")
    ap.add_argument("--chaos-limit", type=int, default=5,
                    help="cap injections per seam (0 = unlimited); bounds "
                         "the degraded-serial reruns so the lane's runtime "
                         "stays a bench, not a soak")
    # host-clock timings vary run to run; report the median of N timed
    # runs (compiles are cached after the first)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--mesh", action="store_true",
                    help="shard the node axis over every visible device "
                         "(1-device mesh on a single chip)")
    # the round-15 multi-chip lane: mesh size for the headline run. Bare
    # `--devices` (or 0) = every visible device; `--devices N` = the first
    # N. Applies to every mode that dispatches device work (burst/serial/
    # preempt/gang/chaos/churn); the JSON always reports `devices`,
    # `per_device_node_rows`, and `ici_allgather_bytes`.
    ap.add_argument("--devices", type=int, nargs="?", const=0, default=None,
                    help="shard the node axis over a mesh of N devices "
                         "(bare flag or 0 = all visible)")
    # `--mode soak` (round 21): the soak scoreboard — fleet x profiles x
    # serve arrivals x churn x chaos with the watcher plane attached and
    # the time-series scraper + verdict engine reading the whole run.
    # Reuses --nodes/--instances/--arrival-rate/--duration/--watchers/
    # --watch-classes/--serve-window/--serve-depth/--chaos-seed.
    # `--mode tune` (round 22): the closed-loop learned-scoring lane.
    # Reuses --nodes/--arrival-rate/--duration/--serve-window/
    # --serve-depth/--chaos-seed; the budget caps offline simulator
    # evaluations (CEM generations = budget // 16)
    ap.add_argument("--search-budget", type=int, default=48,
                    help="tune mode: offline search evaluation budget")
    ap.add_argument("--soak-out", metavar="PATH", default=None,
                    help="soak mode: write the SOAK artifact JSON (config "
                         "+ sampled trajectories + verdicts + audits)")
    ap.add_argument("--multichip-out", metavar="PATH", default=None,
                    help="run __graft_entry__.dryrun_multichip(8) in a "
                         "subprocess and write the MULTICHIP artifact "
                         "JSON (n_devices/rc/ok/tail) to PATH, then exit")
    ap.add_argument("--no-mesh", dest="mesh_check", action="store_false",
                    help="skip the mesh-mode sub-benchmark")
    ap.add_argument("--no-matrix", dest="matrix", action="store_false",
                    help="skip the workload-lane matrix")
    ap.add_argument("--matrix-repeat", type=int, default=2)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the run's spans as Chrome trace-event JSON "
                         "(load in Perfetto / chrome://tracing); host-encode "
                         "vs device dispatch+readback separate by span "
                         "category")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="dump the end-of-run metrics-registry snapshot "
                         "(Prometheus text exposition) beside the JSON "
                         "line — the soak scoreboard artifact")
    args = ap.parse_args()

    if args.multichip_out:
        import os
        import subprocess
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        p = subprocess.run(
            [sys.executable, "-c",
             "import __graft_entry__ as g; g.dryrun_multichip(8)"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        art = {"n_devices": 8, "rc": p.returncode, "ok": p.returncode == 0,
               "skipped": False, "tail": (p.stderr + p.stdout)[-2000:]}
        with open(args.multichip_out, "w") as f:
            json.dump(art, f, indent=2)
        print(json.dumps({"multichip_out": args.multichip_out,
                          "ok": art["ok"]}))
        if not art["ok"]:
            sys.exit(1)
        return

    # one mesh decision for the whole run: --devices N (0/bare = all
    # visible) or the legacy --mesh switch (all visible)
    mesh = None
    if args.devices is not None:
        mesh = _make_mesh(args.devices if args.devices > 0 else None)
    elif args.mesh:
        mesh = _make_mesh()
    ici0 = _ici_total()
    report_nodes = [0]   # the node count the device report derives rows from

    def finish(result: dict) -> None:
        attach_device_report(result, mesh, report_nodes[0], ici0)
        if args.metrics_out:
            from kubernetes_tpu import obs
            with open(args.metrics_out, "w") as f:
                f.write(obs.render_global())
            result["metrics_out"] = args.metrics_out
        if args.trace:
            from kubernetes_tpu.obs import trace as obs_trace
            from kubernetes_tpu.core.tpu_scheduler import PIPELINE_OVERLAP
            result["trace"] = {
                "path": args.trace,
                "spans": obs_trace.export(args.trace),
                # host commit seconds that ran while a later burst wave was
                # in flight on the device (tpu_pipeline_overlap_seconds_total
                # — the wave pipeline's win; the per-wave spans show it as
                # burst.wave.commit[k] inside burst.wave.device[k+1])
                "pipeline_overlap_seconds": round(PIPELINE_OVERLAP.value, 4),
            }
        print(json.dumps(result))

    if args.trace:
        from kubernetes_tpu.obs import trace as obs_trace
        obs_trace.clear()   # only this run's spans land in the file
    n_nodes = args.nodes if args.nodes is not None \
        else (1000 if args.mode in ("preempt", "chaos", "serve", "fleet",
                                    "soak")
              else (300 if args.mode == "churn"
                    else (256 if args.mode == "tune" else 15000)))
    n_pods = args.pods if args.pods is not None \
        else (5000 if args.mode == "chaos"
              else (3000 if args.mode == "churn" else 10000))
    report_nodes[0] = n_nodes if args.mode != "commit" else 0
    if args.mode == "serve":
        result = run_serve_bench(
            n_nodes, args.arrival_rate, args.duration,
            window=args.serve_window, depth=args.serve_depth,
            max_depth=args.max_queue_depth, mesh=mesh)
        finish(result)
        return
    if args.mode == "fleet":
        result = run_fleet_bench(
            n_nodes, args.instances, args.arrival_rate, args.duration,
            window=args.serve_window, depth=args.serve_depth)
        finish(result)
        return
    if args.mode == "soak":
        # host-only composition lane (device work rides the fleet
        # instances' own serve paths); watcher defaults follow the
        # matrix gate cell, not the commit lane's tiny default
        soak_watchers = args.watchers if args.watchers != 8 else 10_000
        soak_classes = args.watch_classes if args.watch_classes != 1 else 64
        result = run_soak_bench(
            n_nodes, args.instances, args.arrival_rate, args.duration,
            watchers=soak_watchers, watch_classes=soak_classes,
            window=args.serve_window, depth=args.serve_depth,
            seed=args.chaos_seed, soak_out=args.soak_out)
        finish(result)
        return
    if args.mode == "tune":
        # host+device composition lane; the serve-scale flag defaults
        # (2000 rps / 30 s / 2048-window) are sized for one full-rate
        # lane — the tune cell splits arrivals across TWO half-rate
        # lanes, so untouched defaults drop to the matrix gate cell
        tune_rate = args.arrival_rate if args.arrival_rate != 2000.0 \
            else 250.0
        tune_duration = args.duration if args.duration != 30.0 else 12.0
        tune_window = args.serve_window if args.serve_window != 2048 \
            else 512
        result = run_tune_bench(
            n_nodes, tune_rate, tune_duration, window=tune_window,
            depth=args.serve_depth, seed=args.chaos_seed,
            search_budget=args.search_budget)
        finish(result)
        return
    if args.mode == "preempt":
        result = run_preempt_bench(n_nodes, n_pods, args.preemptors,
                                   mesh=mesh)
        finish(result)
        return
    if args.mode == "gang":
        sizes = (8, 64, 512) if not args.gang_sizes else tuple(
            int(s) for s in args.gang_sizes.split(","))
        result = run_gang_bench(n_nodes, pods_budget=n_pods, mesh=mesh,
                                gang_sizes=sizes, profiles=args.profiles)
        finish(result)
        return
    if args.mode == "commit":
        # host-only lane (no device dispatch): --pods is the per-wave
        # width; the default is one full scheduler
        # wave, shrunk at high watcher counts so the cell measures
        # fan-out, not writes (the matrix's watcher-scaling cell shapes)
        if args.pods is not None:
            commit_pods, commit_waves = args.pods, 8
        elif args.watchers >= 100_000:
            commit_pods, commit_waves = 64, 2
        elif args.watchers >= 1000:
            commit_pods, commit_waves = 256, 4
        else:
            commit_pods, commit_waves = 4096, 8
        finish(run_commit_bench(
            n_pods=commit_pods, waves=commit_waves,
            watchers=args.watchers, watch_classes=args.watch_classes))
        return
    if args.mode == "matrix":
        # just the matrix lanes + ratio-to-plain, one JSON line
        finish(run_matrix_only(repeat=args.matrix_repeat))
        return
    if args.mode == "churn":
        # the round-14 node-churn lane: kill/restore schedule + zone-paced
        # evictions around steady bursts; smaller default cell than the
        # headline (churn reruns ride the degraded paths)
        churn_burst = args.burst if args.burst != 10000 else 512
        result = run_churn_bench(
            n_nodes, n_pods, churn_burst, churn_seed=args.chaos_seed,
            mesh=mesh)
        finish(result)
        return
    if args.mode == "chaos":
        from kubernetes_tpu import chaos as chaos_mod
        # every seam the embedded burst pipeline exercises; the clock and
        # crash seams need a wrapped clock / test harness and remote.http
        # has no call site against the in-process store. Smaller bursts
        # than the headline: a device-faulted burst degrades to the serial
        # oracle path, so the refusal unit must stay bench-sized.
        rates = {s: args.chaos_rate for s in chaos_mod.SEAMS
                 if s not in ("clock.jump", "sched.crash", "remote.http")}
        chaos_burst = args.burst if args.burst != 10000 else 512
        result = run_bench(
            n_nodes, n_pods, "burst", chaos_burst, compare=True,
            mesh=mesh, chaos_rates=rates, chaos_seed=args.chaos_seed,
            chaos_limit=args.chaos_limit)
        result["baseline_note"] = BASELINE_NOTE
        finish(result)
        return
    runs = [run_bench(n_nodes, n_pods, args.mode, args.burst,
                      compare=False, mesh=mesh)
            for _ in range(max(args.repeat, 1))]
    runs.sort(key=lambda r: r["value"])
    # lower-middle for even counts, matching the matrix/mesh medians: the
    # upper-middle would systematically report the optimistic run
    result = runs[(len(runs) - 1) // 2]
    result["runs"] = [r["value"] for r in runs]
    result["baseline_note"] = BASELINE_NOTE
    if args.mode != "oracle":
        sample = min(n_pods, 100)
        oracle = measure_oracle(n_nodes, sample)
        result["oracle_measured"] = oracle
        result["oracle_pods_sampled"] = sample
        result["vs_measured_oracle"] = (
            round(result["value"] / oracle, 2) if oracle else None)
    if args.mode == "burst" and mesh is None and args.mesh_check:
        # the north-star multi-chip config on whatever devices exist: the
        # uniform kernel sharded over a mesh must NOT regress vs single-chip
        # (mesh mode once silently cost 8x)
        import jax
        m = _make_mesh()   # one mesh for all repeats (one compile)
        mesh_runs = [run_bench(n_nodes, n_pods, args.mode, args.burst,
                               compare=False, mesh=m)["value"]
                     for _ in range(max(min(args.repeat, 2), 1))]
        mesh_runs.sort()
        result["mesh"] = {
            "pods_per_s": mesh_runs[(len(mesh_runs) - 1) // 2],
            "runs": mesh_runs,
            "devices": len(jax.devices()),
        }
    if args.mode == "burst" and args.matrix:
        result["matrix"] = run_matrix(repeat=args.matrix_repeat)
    finish(result)


if __name__ == "__main__":
    main()
