#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the scheduling path runs on the chip.

One process drives the system's main path once, through the entry points a
user would call, at sizes users of a scheduler would call real, and checks
what comes out by the repo's own means (oracle comparison, flight-recorder
replay, in-cell audits). It makes no performance claim: the seconds it prints
say where a cold run spends its time, nothing else.

Stages, all in this one process (a chip belongs to one process):

- drain   the BASELINE.json headline shape (15,000 nodes in 3 zones, 10,000
          pending pods) through Store -> informers -> queue ->
          factory.create_scheduler (the CLI's path) -> schedule_burst until
          empty. Every pod bound exactly once; the first 100 bindings equal
          an oracle scheduler's on an identically built store.
- lanes   plain / anti-affinity / affinity / node-affinity / spread at
          1000 nodes / 1000 existing / 1000 pods, the gang cell, one
          preemption pressure wave and two single-preemptor scans, one
          stage each, so every kernel family compiles and executes; each
          lane's op counter must rise and a bounded burst per lane is
          replayed through the oracle.
- serial  single-pod cycles with serial_path="device"; prints the warm
          round trip of the cycle program and of a trivial program.
- walk    1000 nodes in three uneven zones at upstream's default
          percentageOfNodesToScore: the scan's truncated walk on shipped
          positions (a sort of feasible positions a step, a sort of tie
          positions); the launches are replayed through the serial oracle.
- fill    those nodes with two pod slots each and two pods a node plus a
          handful pending: ONE launch fills every slot (walks past their
          quota over full nodes, walks over every node that keep fewer than
          the quota) and meets the first pod that finds no node, after
          which it commits nothing: the handful stay pending; replayed
          through the serial oracle, tpu_walk_ended_total read.
- groups  the walk stage's cluster holding eight Services' pods, and a few
          hundred pending pods of the eight interleaved pod by pod: one
          burst segment, one launch whose scan carries a count row a
          Service, replayed through the serial oracle.
- colocated  that cluster again, the pending pods 0.7 replicas of the eight
          Services and 0.3 Jobs' pods that nothing selects, of three sizes,
          interleaved pod by pod: one burst segment, one launch whose
          scan carries a count row a Service with the Jobs' pods under no
          row (group index -1), replayed through the serial oracle.
- loadmix  that cluster holding 48 Services' pods, and 400 pending pods in
          the load test's controller mix (one Service a quarter of them,
          eight 3% each, the rest sharing a half), interleaved pod by pod:
          one drain pass of more Services than 16, which a closed loop
          runs as one burst segment and one launch on the wide carry
          (kernels.SPREAD_GROUP_WIDE count rows), replayed through the
          serial oracle.
- serve-groups  that cluster holding 24 Services' pods behind a ServeLoop:
          windows of 3, 20 and 200 pods drawn Zipf over the 24, each window
          on the scan, the large one cut where a 17th Service comes (a
          serve loop's cap, and its carries' rows, stay 16), each launch's
          pod operand built from a row a signature, every launch replayed
          through the serial oracle.
- serve   perf.harness.run_serve_cell: arrivals -> admission gate ->
          ServeLoop windows -> commit -> watch, with its two audits.
- mesh    only with more than one device: the drain again with the node
          axis sharded over all of them; shards and bindings are checked.

After every stage: no device fault was absorbed, no circuit opened, the store
runs the native commit core and the native heap is loaded. Any failed check
exits non-zero. Without an accelerator the script refuses to run and prints
no result; `--rehearse-cpu` is the one explicit way to run it on the CPU
backend, at shrunken sizes, stamped as a rehearsal that can never read as a
pass on the chip.

The last two lines of standard output are one JSON object each: the report
(versions, every check by name, per-stage wall and compile seconds, the
compile cache, "claim": null), then the verdict, which holds exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
with the device as jax reports it. A rehearsal prints the report and no
verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import traceback

REAL = {
    "drain_nodes": 15000, "drain_pods": 10000, "oracle_prefix": 100,
    "lane_nodes": 1000, "lane_existing": 1000, "lane_pods": 1000,
    "parity_pods": 32, "gang_size": 64,
    "preempt_victims": 10000, "preemptors": 128,
    "serial_nodes": 1000, "serial_cycles": 12,
    "walk_nodes": 1000, "walk_pods": 600, "groups_pods": 400,
    "fill_beyond": 5,
    "colocated_pods": 400, "loadmix_pods": 400,
    "serve_groups_windows": (3, 20, 200),
    "serve_nodes": 1000, "serve_rate": 2000.0, "serve_seconds": 5.0,
    "serve_window": 2048, "serve_parity_pods": 256,
}
REHEARSAL = {
    "drain_nodes": 300, "drain_pods": 600, "oracle_prefix": 40,
    "lane_nodes": 60, "lane_existing": 60, "lane_pods": 50,
    "parity_pods": 12, "gang_size": 8,
    "preempt_victims": 320, "preemptors": 8,
    "serial_nodes": 60, "serial_cycles": 6,
    "walk_nodes": 250, "walk_pods": 40, "groups_pods": 40,
    "fill_beyond": 3,
    "colocated_pods": 40, "loadmix_pods": 80,
    "serve_groups_windows": (3, 20, 80),
    "serve_nodes": 90, "serve_rate": 300.0, "serve_seconds": 2.0,
    "serve_window": 128, "serve_parity_pods": 48,
}

LANES = ("plain", "anti-affinity", "affinity", "node-affinity", "spread")
# the burst kernel each workload lane is built to ride (a lane that drops to
# another path, or to the oracle, fails its check)
LANE_OP = {"plain": "burst_uniform", "anti-affinity": "burst_uniform",
           "affinity": "burst_uniform", "node-affinity": "burst_uniform",
           "spread": "burst_scan"}

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


class Smoke:
    def __init__(self, sizes: dict, rehearsal: bool):
        self.sizes = sizes
        self.rehearsal = rehearsal
        self.checks: dict[str, bool] = {}
        self.stages: dict[str, dict] = {}
        self.compile_seconds = {v: 0.0 for v in COMPILE_EVENTS.values()}
        self.cache_events = {v: 0 for v in CACHE_EVENTS.values()}
        # the single-device drain's bindings, the mesh stage's referee
        self.drain_bindings: dict[str, str] = {}

    # -- bookkeeping ---------------------------------------------------------
    def check(self, name: str, ok, detail="") -> None:
        ok = bool(ok)
        self.checks[name] = ok
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail or not ok else ""), flush=True)

    def listen(self) -> None:
        from jax import monitoring

        def on_duration(event, seconds, **_kw):
            key = COMPILE_EVENTS.get(event)
            if key is not None:
                self.compile_seconds[key] += seconds

        def on_event(event, **_kw):
            key = CACHE_EVENTS.get(event)
            if key is not None:
                self.cache_events[key] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def stage(self, name: str, fn) -> None:
        """Run one stage, time it, split its compile seconds out, then run
        the after-every-stage health checks."""
        print(f"== {name}", flush=True)
        fb0 = fallback_counts()
        c0 = dict(self.compile_seconds)
        h0 = dict(self.cache_events)
        t0 = time.perf_counter()
        info = fn() or {}
        wall = time.perf_counter() - t0
        comp = {k: round(self.compile_seconds[k] - c0[k], 3)
                for k in c0}
        fb = delta(fallback_counts(), fb0)
        self.stages[name] = {
            "wall_seconds": round(wall, 3),
            "compile_seconds": round(sum(comp.values()), 3),
            "compile_split": comp,
            "cache": {k: self.cache_events[k] - h0[k] for k in h0},
            "oracle_fallbacks": fb,
            **info,
        }
        print(f"  wall {wall:.1f}s, compile {sum(comp.values()):.1f}s, "
              f"fallbacks {fb or 'none'}", flush=True)
        self.health(name)

    def health(self, stage: str) -> None:
        from kubernetes_tpu import chaos, native
        from kubernetes_tpu.core import breaker
        from kubernetes_tpu.store.store import COMMIT_WAVES, Store
        fb = fallback_counts()
        self.check(f"{stage}.no_device_fault_fallback",
                   fb.get("device-fault", 0) == 0
                   and fb.get("circuit-open", 0) == 0, fb)
        self.check(f"{stage}.no_breaker_faults",
                   family_total(breaker.DEVICE_FAULTS) == 0
                   and breaker.CIRCUIT_STATE.value == breaker.CLOSED)
        twin_waves = family(COMMIT_WAVES).get("twin", 0)
        demoted = family_total(chaos.DEMOTIONS)
        self.check(f"{stage}.store_core_native",
                   Store(watch_log_size=16).core_impl == "native"
                   and twin_waves == 0 and demoted == 0,
                   f"twin_waves={twin_waves} demotions={demoted}")
        self.check(f"{stage}.native_heap_loaded",
                   native.load("heapcore") is not None)


# -- metric helpers ----------------------------------------------------------
def family(fam) -> dict:
    """{first label value: count} of a labelled counter family."""
    return {k[0]: c.value for k, c in fam._children.items()}


def family_total(fam) -> float:
    return sum(c.value for c in fam._children.values())


def fallback_counts() -> dict:
    from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
    return family(ORACLE_FALLBACKS)


def dispatch_counts() -> dict:
    from kubernetes_tpu.core.tpu_scheduler import DEVICE_DISPATCH
    return family(DEVICE_DISPATCH)


def delta(now: dict, before: dict) -> dict:
    """The counters that moved, and by how much."""
    return {k: int(v - before.get(k, 0))
            for k, v in now.items() if v - before.get(k, 0)}


def dispatch_delta(before: dict) -> dict:
    return delta(dispatch_counts(), before)


def replayed(run_fn, capacity: int = 8) -> tuple[int, list]:
    """Run `run_fn` with the flight recorder in replay mode and re-derive
    every captured launch through the serial oracle (the repo's referee);
    `capacity` is how many launches the recorder keeps. Returns (launches
    replayed, mismatches)."""
    from kubernetes_tpu.obs import flight
    flight.RECORDER.configure(mode="replay", capacity=capacity)
    flight.RECORDER.clear()
    try:
        run_fn()
        n = sum(1 for r in flight.RECORDER.records()
                if r.capture is not None
                and r.kind in ("uniform", "scan", "fused"))
        return n, flight.RECORDER.replay_all()
    finally:
        flight.RECORDER.configure(mode="digest", capacity=8)
        flight.RECORDER.clear()


def build_cluster(store, n_nodes: int) -> None:
    """The headline's nodes: 4 CPU / 32 Gi / 110 pods each, three even
    zones (scheduler_perf's node shape)."""
    from kubernetes_tpu.models.hollow import NodeStrategy, populate_store
    populate_store(store, [NodeStrategy(count=n_nodes, zones=3,
                                        name_prefix="node")])


def make_pods(store, n_pods: int) -> None:
    """The headline's pods: 100m / 500Mi, one label."""
    from kubernetes_tpu.models.hollow import PodStrategy, make_pods as _pods
    from kubernetes_tpu.store.store import PODS
    for pod in _pods(PodStrategy(count=n_pods)):
        store.create(PODS, pod)


# -- stages ------------------------------------------------------------------
def build_services_cluster(store, n_nodes: int, k: int, rng) -> None:
    """`build_cluster`'s nodes, `k` Services (`app=svc-j`) and one resident
    pod a node on average, each on a node and behind a Service drawn from
    `rng`, so the Services' count rows differ."""
    from kubernetes_tpu.api.types import Service
    from kubernetes_tpu.models.hollow import PodStrategy, make_pods as _pods
    from kubernetes_tpu.store.store import PODS, SERVICES
    build_cluster(store, n_nodes)
    for j in range(k):
        store.create(SERVICES, Service(name=f"svc-{j}",
                                       selector={"app": f"svc-{j}"}))
    for pod in _pods(PodStrategy(count=n_nodes, name_prefix="res")):
        pod.node_name = f"node-{rng.randrange(n_nodes)}"
        pod.labels = {"app": f"svc-{rng.randrange(k)}"}
        store.create(PODS, pod)


def group_cuts(drawn: list, cap: int) -> tuple:
    """What the shell makes of one drain pass whose pods are each selected
    by the one Service `drawn` names: a segment ends before the pod whose
    Service would be one more than the `cap` a launch carries. (`groups`
    cuts, Services summed over the segments, Services of the last
    segment)."""
    cuts, groups, seen = 0, 0, set()
    for j in drawn:
        if j not in seen and len(seen) == cap:
            cuts += 1
            groups += len(seen)
            seen = set()
        seen.add(j)
    return cuts, groups + len(seen), len(seen)


def drain(smoke: Smoke, tag: str, device: bool, n_pods: int, **sched_kw):
    """Headline-shaped cluster through the CLI's path, drained; checks
    every pod bound exactly once and returns (scheduler, store,
    {pod key: node})."""
    from kubernetes_tpu.apis.config import SchedulerConfiguration
    from kubernetes_tpu.factory import create_scheduler
    from kubernetes_tpu.store.store import MODIFIED, PODS, Store
    s = smoke.sizes
    store = Store(watch_log_size=1 << 20)        # cmd/scheduler.py's size
    build_cluster(store, s["drain_nodes"])
    # the headline scores every node (benchmark/configs/headline-15000n),
    # which is also what routes a uniform backlog onto the K-batch kernel
    cfg = SchedulerConfiguration(percentage_of_nodes_to_score=100)
    if not device:
        cfg.feature_gates = {"TPUScoring": False}
    sched = create_scheduler(store, cfg, **sched_kw)
    if not device:
        # every oracle cycle at this width is "slow"; keep its per-cycle
        # step traces out of the output
        sched.slow_cycle_threshold = float("inf")
    sched.sync()
    watch = store.watch(PODS)
    make_pods(store, n_pods)
    sched.pump()
    bound = 0
    if device:
        while True:
            n = sched.schedule_burst(max_pods=s["drain_pods"])
            if n == 0:
                break
            bound += n
    else:
        while sched.schedule_one(timeout=0.0):
            bound += 1
    sched.pump()
    binds: dict[str, int] = {}
    for ev in watch.drain():
        if ev.type == MODIFIED and ev.obj.node_name:
            binds[ev.obj.key] = binds.get(ev.obj.key, 0) + 1
    watch.stop()
    placed = {p.key: p.node_name for p in store.list(PODS)[0]}
    smoke.check(f"{tag}.all_bound",
                bound == n_pods and len(placed) == n_pods
                and all(placed.values()),
                f"bound={bound} of {n_pods}")
    smoke.check(f"{tag}.bound_exactly_once",
                len(binds) == n_pods and set(binds.values()) == {1},
                f"{len(binds)} pods saw bind events")
    return sched, store, placed


def stage_drain(smoke: Smoke):
    import jax
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    s = smoke.sizes
    d0 = dispatch_counts()
    # with several devices this stage is the single-device referee for the
    # mesh stage; on one device mesh="auto" is single-device already
    kw = {"mesh": None} if len(jax.devices()) > 1 else {}
    sched, store, placed = drain(smoke, "drain", True, s["drain_pods"], **kw)
    smoke.check("drain.algorithm_is_tpu",
                isinstance(sched.algorithm, TPUScheduler),
                type(sched.algorithm).__name__)
    ops = dispatch_delta(d0)
    smoke.check("drain.op_burst_uniform", ops.get("burst_uniform", 0) > 0,
                ops)
    smoke.check("drain.store_core_native_at_exit",
                store.core_impl == "native", store.core_impl)
    t0 = time.perf_counter()
    _o, _st, want = drain(smoke, "drain.oracle", False, s["oracle_prefix"])
    oracle_s = time.perf_counter() - t0
    diff = {k: (placed.get(k), v) for k, v in want.items()
            if placed.get(k) != v}
    smoke.check("drain.oracle_prefix_identical", not diff,
                f"{len(want)} pods compared"
                + (f", first diffs {dict(list(diff.items())[:3])}"
                   if diff else ""))
    smoke.drain_bindings = placed
    return {"nodes": s["drain_nodes"], "pods": s["drain_pods"],
            "device_ops": ops, "oracle_prefix": len(want),
            "oracle_seconds": round(oracle_s, 3)}


def lane_stage(lane: str):
    """One workload lane: a full pass, then a bounded burst replayed
    through the oracle."""
    def fn(smoke: Smoke):
        from kubernetes_tpu.perf.harness import PerfConfig, run
        s = smoke.sizes
        d0 = dispatch_counts()
        res = run(PerfConfig(nodes=s["lane_nodes"],
                             existing_pods=s["lane_existing"],
                             pods=s["lane_pods"], workload=lane))
        ops = dispatch_delta(d0)
        smoke.check(f"lanes.{lane}.all_scheduled",
                    res.scheduled == s["lane_pods"],
                    f"{res.scheduled} of {s['lane_pods']}")
        smoke.check(f"lanes.{lane}.op_{LANE_OP[lane]}",
                    ops.get(LANE_OP[lane], 0) > 0, ops)
        small = PerfConfig(nodes=s["lane_nodes"],
                           existing_pods=s["lane_existing"],
                           pods=s["parity_pods"], workload=lane)
        n, mism = replayed(lambda: run(small, warmup=0))
        smoke.check(f"lanes.{lane}.replay_parity", n > 0 and not mism,
                    f"{n} launches replayed" + (f", {mism[:2]}" if mism
                                                else ""))
        return {"device_ops": ops}
    return fn


def stage_gang(smoke: Smoke):
    """All-or-nothing groups through the fused segmented scan."""
    from kubernetes_tpu.perf.harness import run_gang_cell
    s = smoke.sizes
    d0 = dispatch_counts()
    res = run_gang_cell(nodes=s["lane_nodes"], gang_size=s["gang_size"],
                        pods=s["lane_pods"])
    ops = dispatch_delta(d0)
    groups = max(1, s["lane_pods"] // s["gang_size"])
    smoke.check("lanes.gang.all_scheduled",
                res.scheduled == groups * s["gang_size"],
                f"{res.scheduled} of {groups * s['gang_size']}")
    smoke.check("lanes.gang.op_burst_fused", ops.get("burst_fused", 0) > 0,
                ops)
    n, mism = replayed(lambda: run_gang_cell(
        nodes=s["lane_nodes"], gang_size=s["gang_size"],
        pods=s["gang_size"]))
    smoke.check("lanes.gang.replay_parity", n > 0 and not mism,
                f"{n} launches replayed" + (f", {mism[:2]}" if mism else ""))
    return {"device_ops": ops}


def stage_preempt(smoke: Smoke):
    """One preemption pressure wave; run_preempt_cell asserts the device's
    decisions equal the serial oracle's before it returns."""
    from kubernetes_tpu.perf.harness import run_preempt_cell
    s = smoke.sizes
    d0 = dispatch_counts()
    run_preempt_cell(s["lane_nodes"], s["preempt_victims"], s["preemptors"])
    ops = dispatch_delta(d0)
    smoke.check("lanes.preempt.oracle_identical", True,
                "asserted inside run_preempt_cell")
    smoke.check("lanes.preempt.op_pressure_batch",
                ops.get("pressure_batch", 0) > 0
                and ops.get("vic_upload", 0) > 0, ops)
    return {"device_ops": ops}


def stage_preempt_scan(smoke: Smoke):
    """Two single-preemptor victim scans against the oracle Preemptor, the
    second after a node changed, so preempt_scan compiles and the victim
    table's dirty-row scatter runs. Priorities and start times vary, which
    is what the staged node pick ranks on."""
    import numpy as np
    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.cache.node_info import NodeInfo
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    from kubernetes_tpu.oracle.generic_scheduler import FitError
    from kubernetes_tpu.oracle.predicates import insufficient_resource
    from kubernetes_tpu.oracle.preemption import Preemptor
    s = smoke.sizes
    rng = np.random.RandomState(0)
    n_nodes = s["lane_nodes"]
    per_node = max(2, s["preempt_victims"] // n_nodes)
    infos, names = {}, []
    for i in range(n_nodes):
        node = Node(name=f"node-{i}",
                    allocatable={"cpu": 4000, "memory": 32 << 30,
                                 "pods": 110})
        ni = NodeInfo(node)
        for j in range(per_node):
            ni.add_pod(Pod(
                name=f"victim-{i}-{j}", node_name=node.name,
                priority=int(rng.randint(0, 4)),
                start_time=1.7e9 + float(rng.randint(0, 10 ** 6)) / 8.0,
                containers=(Container.make(
                    name="c", requests={"cpu": 4000 // per_node}),)))
        infos[node.name] = ni
        names.append(node.name)
    d0 = dispatch_counts()
    tpu = TPUScheduler(percentage_of_nodes_to_score=100)
    same = True
    for k in range(2):
        incoming = Pod(name=f"hi-{k}", priority=10, containers=(
            Container.make(name="c", requests={"cpu": 2500}),))
        err = FitError(incoming, len(names), {
            n: [insufficient_resource("cpu")] for n in names})
        want = Preemptor().preempt(incoming, infos, names, err)
        got = tpu.preempt(incoming, infos, names, err, [])
        if got is None or want.node is None:
            same = False
            break
        same &= (got.node.name == want.node.name
                 and sorted(p.key for p in got.victims)
                 == sorted(p.key for p in want.victims))
        # the world changes under the resident victim table: the winner's
        # victims leave, so the next scan re-sorts and scatters that row
        ni = infos[want.node.name].clone()
        for p in want.victims:
            ni.remove_pod(p)
        infos = {**infos, want.node.name: ni}
    ops = dispatch_delta(d0)
    smoke.check("lanes.preempt_scan.oracle_identical", same)
    smoke.check("lanes.preempt_scan.op_preempt_scan",
                ops.get("preempt_scan", 0) >= 2
                and ops.get("vic_scatter", 0) > 0, ops)
    return {"device_ops": ops}


def stage_serial(smoke: Smoke):
    import jax
    import jax.numpy as jnp
    from kubernetes_tpu.obs import trace as obs_trace
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    store = Store(watch_log_size=1 << 16)
    build_cluster(store, s["serial_nodes"])
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=100)
    sched.algorithm.serial_path = "device"
    sched.sync()
    make_pods(store, s["serial_cycles"])
    sched.pump()
    d0 = dispatch_counts()
    obs_trace.clear()
    cycle_ms = []
    while True:
        t0 = time.perf_counter()
        if not sched.schedule_one(timeout=0.0):
            break
        cycle_ms.append((time.perf_counter() - t0) * 1e3)
    sched.wait_for_binds()
    sched.pump()
    ops = dispatch_delta(d0)
    smoke.check("serial.all_bound",
                all(p.node_name for p in store.list(PODS)[0]))
    smoke.check("serial.op_cycle",
                ops.get("cycle", 0) == s["serial_cycles"], ops)
    fetch_ms = [e["dur"] / 1e3 for e in obs_trace.events(cat="device")
                if e.get("name") == "cycle.fetch"]
    # the floor under every launch: a trivial program, dispatch + fetch
    bump = jax.jit(lambda x: x + 1)
    x = jnp.zeros((), jnp.int32)
    int(bump(x))
    tiny_ms = []
    for _ in range(50):
        t0 = time.perf_counter()
        int(bump(x))
        tiny_ms.append((time.perf_counter() - t0) * 1e3)
    warm = cycle_ms[1:] or cycle_ms
    out = {
        "nodes": s["serial_nodes"], "cycles": len(cycle_ms),
        "first_cycle_ms": round(cycle_ms[0], 3) if cycle_ms else None,
        # whole schedule_one (snapshot, encode, dispatch, fetch, bind)
        "warm_cycle_ms_median": round(statistics.median(warm), 3),
        # device_get wait of the cycle program: execution + readback
        "warm_cycle_fetch_ms_median": (
            round(statistics.median(fetch_ms[1:] or fetch_ms), 3)
            if fetch_ms else None),
        "tiny_program_round_trip_ms_median": round(
            statistics.median(tiny_ms), 4),
    }
    print(f"  warm single-pod cycle {out['warm_cycle_ms_median']} ms "
          f"(fetch wait {out['warm_cycle_fetch_ms_median']} ms); trivial "
          f"program dispatch+fetch {out['tiny_program_round_trip_ms_median']}"
          f" ms", flush=True)
    return out


def stage_walk(smoke: Smoke):
    """A rotating NodeTree under a truncated walk: the scan program that
    takes the walk's stopping point as an order statistic of positions."""
    from kubernetes_tpu.core import tpu_scheduler as T
    from kubernetes_tpu.oracle.generic_scheduler import \
        num_feasible_nodes_to_find
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    n = s["walk_nodes"]
    assert n % 3, "the zones have to be uneven for the order to rotate"
    quota = num_feasible_nodes_to_find(n, 0)
    store = Store(watch_log_size=1 << 16)
    build_cluster(store, n)
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    make_pods(store, s["walk_pods"])
    sched.pump()
    d0 = dispatch_counts()
    order0, walked0 = family(T.SCAN_ORDER_STEPS), family(T.WALK_NODES)

    def run():
        # launches of at most 256 pods: last_index, the tie counter and the
        # tree's zone cursor carry from launch to launch
        while sched.schedule_burst(max_pods=256):
            pass
    launches, mism = replayed(run)
    sched.pump()
    ops = dispatch_delta(d0)
    order = delta(family(T.SCAN_ORDER_STEPS), order0)
    walked = delta(family(T.WALK_NODES), walked0)
    smoke.check("walk.all_bound",
                all(p.node_name for p in store.list(PODS)[0]))
    smoke.check("walk.op_burst_scan",
                ops.get("burst_scan", 0) > 0 and "burst_uniform" not in ops,
                ops)
    smoke.check("walk.every_step_on_positions",
                order == {"position": s["walk_pods"]}, order)
    # every walk stopped at its quota: no node is full, so it tested no more
    smoke.check("walk.truncated", quota < n
                and walked == {"truncated": quota * s["walk_pods"]},
                f"quota {quota} of {n}: {walked}")
    smoke.check("walk.replay_parity", launches > 0 and not mism,
                f"{launches} launches replayed"
                + (f", {mism[:2]}" if mism else ""))
    return {"nodes": n, "pods": s["walk_pods"], "num_to_find": quota,
            "device_ops": ops, "launches_replayed": launches}


def stage_fill(smoke: Smoke):
    """A cluster filled to its last pod slot and a handful of pods beyond,
    in ONE launch on a rotating order under a truncated walk: walks past
    their quota over full nodes, walks over every node that keep fewer than
    the quota, and the first pod that finds none, after which the launch
    commits nothing and its pods stay pending (no benchmark cell can hold
    those: it counts an unbound pod as lost)."""
    from kubernetes_tpu.core import tpu_scheduler as T
    from kubernetes_tpu.models.hollow import NodeStrategy, populate_store
    from kubernetes_tpu.oracle.generic_scheduler import \
        num_feasible_nodes_to_find
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    n, beyond = s["walk_nodes"], s["fill_beyond"]
    assert n % 3, "the zones have to be uneven for the order to rotate"
    quota = num_feasible_nodes_to_find(n, 0)
    slots = 2 * n
    store = Store(watch_log_size=1 << 16)
    # the headline's nodes with two pod slots each: the slots are what binds
    populate_store(store, [NodeStrategy(count=n, zones=3, pods=2,
                                        name_prefix="node")])
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    make_pods(store, slots + beyond)
    sched.pump()
    d0 = dispatch_counts()
    ended0, folds0 = family(T.WALK_ENDED), family_total(T.DISCARDED_FOLDS)
    walked0, f0 = family(T.WALK_NODES), fallback_counts()

    def run():
        while sched.schedule_burst(max_pods=slots + beyond):
            pass
    launches, mism = replayed(run)
    sched.pump()
    ops = dispatch_delta(d0)
    ended = delta(family(T.WALK_ENDED), ended0)
    walked = delta(family(T.WALK_NODES), walked0)
    pods = store.list(PODS)[0]
    pending = [p for p in pods if not p.node_name]
    per_node: dict = {}
    for p in pods:
        if p.node_name:
            per_node[p.node_name] = per_node.get(p.node_name, 0) + 1
    smoke.check("fill.every_slot_taken",
                len(per_node) == n and set(per_node.values()) == {2},
                f"{len(per_node)} nodes hold {sum(per_node.values())} pods")
    smoke.check("fill.the_rest_pending", len(pending) == beyond,
                f"{len(pending)} pending")
    smoke.check("fill.one_launch",
                ops.get("burst_scan", 0) == 1 and "burst_uniform" not in ops,
                ops)
    # every decision of the launch up to the first that found no node: some
    # stopped at the quota, the last of the fill tested every node for
    # fewer, one found none; what the launch decided after it never counted
    smoke.check("fill.walks_ended",
                ended.get("none") == 1 and ended.get("quota", 0) > 0
                and ended.get("nodes", 0) >= quota // 2
                and sum(ended.values()) == slots + 1,
                f"quota {quota} of {n}: {ended}")
    smoke.check("fill.walks_past_the_quota",
                walked.get("truncated", 0) > quota * (slots + beyond), walked)
    smoke.check("fill.launch_rewound",
                family_total(T.DISCARDED_FOLDS) - folds0 == 1)
    left = {k: v for k, v in delta(fallback_counts(), f0).items()
            if k[0] in ("device-fault", "circuit-open")}
    smoke.check("fill.no_device_fault", not left, left)
    smoke.check("fill.replay_parity", launches == 1 and not mism,
                f"{launches} launches replayed"
                + (f", {mism[:2]}" if mism else ""))
    return {"nodes": n, "pods": slots + beyond, "num_to_find": quota,
            "walks_ended": ended, "device_ops": ops,
            "launches_replayed": launches}


def stage_groups(smoke: Smoke):
    """Unlike Services' pods in one launch: the scan carries one
    selector-spread count row a Service, on uneven zones under a truncated
    walk."""
    import random
    from kubernetes_tpu.core import tpu_scheduler as T
    from kubernetes_tpu.models.hollow import PodStrategy, make_pods as _pods
    from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    n, n_pods, k = s["walk_nodes"], s["groups_pods"], 8
    assert n % 3, "the zones have to be uneven for the order to rotate"
    rng = random.Random(42)
    store = Store(watch_log_size=1 << 16)
    build_services_cluster(store, n, k, rng)
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    for pod in _pods(PodStrategy(count=n_pods)):
        pod.labels = {"app": f"svc-{rng.randrange(k)}"}
        store.create(PODS, pod)
    sched.pump()
    d0 = dispatch_counts()
    steps0, cuts0 = family(T.SCAN_SPREAD_STEPS), family(SEGMENT_CUTS)
    f0 = fallback_counts()

    def run():
        while sched.schedule_burst(max_pods=512):
            pass
    launches, mism = replayed(run)
    sched.pump()
    ops = dispatch_delta(d0)
    steps = delta(family(T.SCAN_SPREAD_STEPS), steps0)
    cuts = delta(family(SEGMENT_CUTS), cuts0)
    smoke.check("groups.all_bound",
                all(p.node_name for p in store.list(PODS)[0]))
    smoke.check("groups.one_segment_one_launch",
                cuts == {"end": 1} and ops.get("burst_scan", 0) == 1
                and "burst_uniform" not in ops, f"{cuts} {ops}")
    smoke.check("groups.every_step_grouped",
                steps == {"grouped": n_pods}, steps)
    smoke.check("groups.no_refusal",
                not delta(fallback_counts(), f0), delta(fallback_counts(), f0))
    smoke.check("groups.replay_parity", launches == 1 and not mism,
                f"{launches} launches replayed"
                + (f", {mism[:2]}" if mism else ""))
    return {"nodes": n, "pods": n_pods, "services": k, "device_ops": ops,
            "launches_replayed": launches}


def stage_colocated(smoke: Smoke):
    """Services' replicas and Jobs' pods in one drain pass: no gang in it,
    so the planner hands it over whole, the segmenter keeps both kinds in
    one burst segment, and the one launch carries the Services' count
    rows; a Job's pod reads none of them (zeros: SelectorSpread's
    constant) and moves none."""
    import random
    from kubernetes_tpu.core import tpu_scheduler as T
    from kubernetes_tpu.models.hollow import MI, PodStrategy, \
        make_pods as _pods
    from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    n, n_pods, k = s["walk_nodes"], s["colocated_pods"], 8
    assert n % 3, "the zones have to be uneven for the order to rotate"
    rng = random.Random(47)
    store = Store(watch_log_size=1 << 16)
    build_services_cluster(store, n, k, rng)
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    # the benchmark's mix: 0.7 replicas of eight Services (100m / 500Mi),
    # 0.3 Jobs' pods that nothing selects, of three sizes at 3 : 2 : 1
    jobs = ((100, 128 * MI),) * 3 + ((250, 512 * MI),) * 2 \
        + ((500, 1024 * MI),)
    kinds = []
    for j in range(n_pods):
        if rng.random() < 0.3:
            cpu, mem = rng.choice(jobs)
            pod, = _pods(PodStrategy(count=1, cpu=cpu, mem=mem, labels={}), j)
        else:
            pod, = _pods(PodStrategy(
                count=1, labels={"app": f"svc-{rng.randrange(k)}"}), j)
        kinds.append(bool(pod.labels))
        store.create(PODS, pod)
    changes = sum(a != b for a, b in zip(kinds, kinds[1:]))
    sched.pump()
    d0 = dispatch_counts()
    steps0, cuts0 = family(T.SCAN_SPREAD_STEPS), family(SEGMENT_CUTS)
    bare0 = family_total(T.SCAN_SPREAD_UNSELECTED_STEPS)
    f0 = fallback_counts()

    def run():
        while sched.schedule_burst(max_pods=512):
            pass
    launches, mism = replayed(run)
    sched.pump()
    ops = dispatch_delta(d0)
    steps = delta(family(T.SCAN_SPREAD_STEPS), steps0)
    bare = int(family_total(T.SCAN_SPREAD_UNSELECTED_STEPS) - bare0)
    cuts = delta(family(SEGMENT_CUTS), cuts0)
    smoke.check("colocated.all_bound",
                all(p.node_name for p in store.list(PODS)[0]))
    smoke.check("colocated.one_segment_one_launch",
                changes > n_pods // 5
                and cuts == {"end": 1} and ops.get("burst_scan", 0) == 1
                and "burst_uniform" not in ops, f"{changes} {cuts} {ops}")
    smoke.check("colocated.every_step_grouped",
                steps == {"grouped": n_pods}
                and bare == kinds.count(False), f"{steps} {bare}")
    smoke.check("colocated.no_refusal",
                not delta(fallback_counts(), f0), delta(fallback_counts(), f0))
    smoke.check("colocated.replay_parity", launches == 1 and not mism,
                f"{launches} launches replayed"
                + (f", {mism[:2]}" if mism else ""))
    return {"nodes": n, "pods": n_pods, "services": k,
            "jobs_pods": kinds.count(False), "changes_of_kind": changes,
            "device_ops": ops, "launches_replayed": launches}


def stage_loadmix(smoke: Smoke):
    """A drain pass of far more Services' pods than 16, in the load test's
    controller mix: a closed loop's launch carries them all on the wide
    carry, so the pass is one segment and one launch (the serve-loop form,
    cut at the 16-group cap, is the stage `serve-groups`)."""
    import random
    from kubernetes_tpu.core import tpu_scheduler as T
    from kubernetes_tpu.models.hollow import PodStrategy, make_pods as _pods
    from kubernetes_tpu.ops.kernels import SPREAD_GROUP_CAP, SPREAD_GROUP_WIDE
    from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    n, n_pods, k = s["walk_nodes"], s["loadmix_pods"], 48
    assert n % 3, "the zones have to be uneven for the order to rotate"
    rng = random.Random(60)
    # load.go's computePodCounts: a big controller owns a quarter of the
    # pods, eight medium ones 3% each, the small ones share the other half
    weights = [0.25] + [0.03] * 8 + [0.51 / (k - 9)] * (k - 9)
    store = Store(watch_log_size=1 << 16)
    build_services_cluster(store, n, k, rng)
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    drawn = rng.choices(range(k), weights, k=n_pods)
    held = len(set(drawn))
    for pod, j in zip(_pods(PodStrategy(count=n_pods)), drawn):
        pod.labels = {"app": f"svc-{j}"}
        store.create(PODS, pod)
    sched.pump()
    d0 = dispatch_counts()
    steps0, cuts0 = family(T.SCAN_SPREAD_STEPS), family(SEGMENT_CUTS)
    rows0 = family(T.SCAN_SPREAD_CARRY_LAUNCHES)
    groups0 = T.SCAN_SPREAD_GROUPS.value
    f0 = fallback_counts()

    def run():
        while sched.schedule_burst(max_pods=512):
            pass
    launches, mism = replayed(run)
    sched.pump()
    ops = dispatch_delta(d0)
    steps = delta(family(T.SCAN_SPREAD_STEPS), steps0)
    cuts = delta(family(SEGMENT_CUTS), cuts0)
    rows = delta(family(T.SCAN_SPREAD_CARRY_LAUNCHES), rows0)
    groups = int(T.SCAN_SPREAD_GROUPS.value - groups0)
    smoke.check("loadmix.all_bound",
                all(p.node_name for p in store.list(PODS)[0]))
    smoke.check("loadmix.one_segment_one_launch",
                SPREAD_GROUP_CAP < held <= SPREAD_GROUP_WIDE
                and cuts == {"end": 1} and ops.get("burst_scan", 0) == 1
                and "burst_uniform" not in ops,
                f"{cuts} {ops}, {held} Services in the pass")
    smoke.check("loadmix.a_count_row_a_service",
                steps == {"grouped": n_pods} and groups == held,
                f"{steps}, {groups} groups carried, {held} expected")
    smoke.check("loadmix.on_the_wide_carry",
                rows == {str(SPREAD_GROUP_WIDE): 1}, rows)
    smoke.check("loadmix.no_refusal",
                not delta(fallback_counts(), f0), delta(fallback_counts(), f0))
    smoke.check("loadmix.replay_parity", launches == 1 and not mism,
                f"{launches} launches replayed"
                + (f", {mism[:2]}" if mism else ""))
    return {"nodes": n, "pods": n_pods, "services": k,
            "services_in_the_pass": held, "carry_rows": rows,
            "device_ops": ops, "launches_replayed": launches}


def stage_serve_groups(smoke: Smoke):
    """A serve loop whose windows hold many Services' pods: every window on
    the scan, a window of more Services than one launch carries cut by the
    shell, pods of earlier windows still bound when the next is planned."""
    import random
    from kubernetes_tpu.core import tpu_scheduler as T
    from kubernetes_tpu.models.hollow import PodStrategy, make_pods as _pods
    from kubernetes_tpu.ops.kernels import SPREAD_GROUP_CAP
    from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
    from kubernetes_tpu.serve import ServeLoop
    from kubernetes_tpu.store.store import PODS, Store
    s = smoke.sizes
    n, windows, k = s["walk_nodes"], s["serve_groups_windows"], 24
    assert n % 3, "the zones have to be uneven for the order to rotate"
    rng = random.Random(43)
    weights = [(j + 1) ** -1.1 for j in range(k)]
    store = Store(watch_log_size=1 << 16)
    build_services_cluster(store, n, k, rng)
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    loop = ServeLoop(sched, window_size=max(windows), depth=3)
    # each launch's pod operand: (rows built from Python objects, the
    # launch's signatures, rows the gather filled)
    stacked = []
    stack = sched.algorithm._stack_pods

    def watched(*a):
        rows0 = family(T.SCAN_STACK_ROWS)
        out = stack(*a)
        rows = delta(family(T.SCAN_STACK_ROWS), rows0)
        stacked.append((rows.get("built"), out[1], rows.get("taken")))
        return out
    sched.algorithm._stack_pods = watched
    d0 = dispatch_counts()
    steps0, cuts0 = family(T.SCAN_SPREAD_STEPS), family(SEGMENT_CUTS)
    rows0 = family(T.SCAN_SPREAD_CARRY_LAUNCHES)
    groups0 = T.SCAN_SPREAD_GROUPS.value
    f0 = fallback_counts()
    launches, mism, bound, want_cuts, want_groups = 0, [], 0, 0, 0
    for w, size in enumerate(windows):
        drawn = rng.choices(range(k), weights, k=size)
        n_cuts, n_groups, _last = group_cuts(drawn, SPREAD_GROUP_CAP)
        want_cuts += n_cuts
        want_groups += n_groups
        for pod, j in zip(_pods(PodStrategy(count=size,
                                            name_prefix=f"w{w}")), drawn):
            pod.labels = {"app": f"svc-{j}"}
            store.create(PODS, pod)

        def run():
            nonlocal bound
            bound += loop.step()
        got, bad = replayed(run)
        launches += got
        mism += bad
    sched.pump()
    ops = dispatch_delta(d0)
    steps = delta(family(T.SCAN_SPREAD_STEPS), steps0)
    cuts = delta(family(SEGMENT_CUTS), cuts0)
    rows = delta(family(T.SCAN_SPREAD_CARRY_LAUNCHES), rows0)
    groups = int(T.SCAN_SPREAD_GROUPS.value - groups0)
    smoke.check("serve_groups.all_bound", bound == sum(windows)
                and all(p.node_name for p in store.list(PODS)[0]), bound)
    smoke.check("serve_groups.every_window_on_the_scan",
                ops.get("burst_scan", 0) == len(windows) + want_cuts
                and "burst_uniform" not in ops, ops)
    smoke.check("serve_groups.cut_at_the_group_cap",
                want_cuts > 0 and cuts == {"groups": want_cuts,
                                           "end": len(windows)},
                f"{cuts}, {want_cuts} expected")
    # the loop's two scan programs, and not the closed loop's wide carry
    smoke.check("serve_groups.on_the_caps_rows",
                set(rows) <= {"1", str(SPREAD_GROUP_CAP)}
                and rows.get(str(SPREAD_GROUP_CAP), 0) >= want_cuts
                and sum(rows.values()) == len(windows) + want_cuts, rows)
    smoke.check("serve_groups.a_count_row_a_service",
                set(steps) <= {"grouped", "single"}
                and sum(steps.values()) == sum(windows)
                and steps.get("grouped", 0) > 0 and groups == want_groups,
                f"{steps}, {groups} groups carried, {want_groups} expected")
    # the pods differ in their Service alone, so a launch's signatures are
    # its Services; the bucket is the loop's drain pass, whatever it holds
    smoke.check("serve_groups.a_row_built_a_signature",
                len(stacked) == len(windows) + want_cuts
                and all(built <= sigs + 1 and taken >= 3 * max(windows)
                        for built, sigs, taken in stacked)
                and sum(sigs for _b, sigs, _t in stacked) == want_groups,
                stacked)
    smoke.check("serve_groups.no_refusal",
                not delta(fallback_counts(), f0), delta(fallback_counts(), f0))
    smoke.check("serve_groups.replay_parity",
                launches == len(windows) + want_cuts and not mism,
                f"{launches} launches replayed"
                + (f", {mism[:2]}" if mism else ""))
    return {"nodes": n, "windows": list(windows), "services": k,
            "group_cuts": want_cuts, "device_ops": ops,
            "launches_replayed": launches}


def stage_serve(smoke: Smoke):
    from kubernetes_tpu.perf.harness import run_serve_cell
    s = smoke.sizes
    d0 = dispatch_counts()
    r = run_serve_cell(n_nodes=s["serve_nodes"],
                       arrival_rate=s["serve_rate"],
                       duration=s["serve_seconds"],
                       window=s["serve_window"], depth=3,
                       parity_pods=s["serve_parity_pods"], seed=0)
    ops = dispatch_delta(d0)
    smoke.check("serve.audit_all_admitted_or_429",
                r["audit_all_admitted_or_429"])
    smoke.check("serve.parity_violations_zero",
                r["parity_violations"] == 0, r["parity_errors"])
    smoke.check("serve.pods_completed", r["pods_completed"] > 0,
                r["pods_completed"])
    smoke.check("serve.op_burst", ops.get("burst_uniform", 0) > 0, ops)
    return {"nodes": s["serve_nodes"], "arrival_rate": s["serve_rate"],
            "windows_cut": r["windows_cut"],
            "pods_completed": r["pods_completed"],
            "admission": {k: r["admission"].get(k)
                          for k in ("admitted", "rejected")},
            "device_ops": ops}


def stage_mesh(smoke: Smoke):
    import jax
    from kubernetes_tpu.parallel import sharding as S
    s = smoke.sizes
    d = len(jax.devices())
    sched, _store, placed = drain(smoke, "mesh", True, s["drain_pods"])
    algo = sched.algorithm
    smoke.check("mesh.mesh_spans_all_devices",
                algo.mesh is not None and int(algo.mesh.devices.size) == d)
    n_pad = algo.encoder._batch.n_pad
    bad = []
    for k in S._SHARDED_1D:
        arr = algo._dev_nodes[k]
        shards = arr.addressable_shards
        if len({sh.device for sh in shards}) != d or any(
                sh.data.shape[0] != n_pad // d for sh in shards):
            bad.append((k, [tuple(sh.data.shape) for sh in shards]))
    smoke.check("mesh.node_axis_sharded", not bad,
                bad or f"{len(S._SHARDED_1D)} leaves, {n_pad // d} rows "
                       f"per device on {d} devices")
    want = smoke.drain_bindings
    diff = [k for k, v in want.items() if placed.get(k) != v]
    smoke.check("mesh.bindings_equal_single_device",
                len(want) == len(placed) and not diff,
                f"{len(diff)} of {len(want)} differ")
    return {"devices": d, "rows_per_device": n_pad // d}


# -- entry -------------------------------------------------------------------
def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def versions() -> dict:
    import importlib.metadata as md
    import jax
    import jaxlib
    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        out["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        out["libtpu"] = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU backend at shrunken sizes; the "
                         "output is stamped a rehearsal and is never ok")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    # the package places the compile cache and enables x64 on import,
    # before anything compiles; jax itself is first touched here
    import kubernetes_tpu.ops as ops
    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: refusing to run: jax reports platform "
              f"{device['platform']!r} ({device['kind']}), not 'tpu'. This "
              f"script only proves anything on the chip.", file=sys.stderr)
        return 2
    if args.rehearse_cpu and device["platform"] != "cpu":
        print("chip_smoke: --rehearse-cpu is for the CPU backend "
              "(JAX_PLATFORMS=cpu)", file=sys.stderr)
        return 2
    cache_dir = (os.environ.get(ops.COMPILE_CACHE_ENV)
                 or ops.DEFAULT_COMPILE_CACHE_DIR)
    cache_before = cache_entries(cache_dir)
    print(f"device: {device}  versions: {versions()}", flush=True)
    print(f"compile cache: {cache_dir} ({cache_before} entries; "
          f"{'from ' + ops.COMPILE_CACHE_ENV if os.environ.get(ops.COMPILE_CACHE_ENV) else 'in-checkout default'})",
          flush=True)

    smoke = Smoke(REHEARSAL if args.rehearse_cpu else REAL,
                  rehearsal=args.rehearse_cpu)
    smoke.listen()

    # the native cores build here, loudly: the smoke does not run on twins
    from kubernetes_tpu import native
    for name in ("commitcore", "heapcore"):
        if native.load(name) is None:
            print(f"chip_smoke: native extension {name!r} did not build or "
                  f"import:\n{native.load_error(name)}", file=sys.stderr)
            return 3

    stages = [("drain", stage_drain)]
    stages += [(f"lanes.{lane}", lane_stage(lane)) for lane in LANES]
    stages += [("lanes.gang", stage_gang), ("lanes.preempt", stage_preempt),
               ("lanes.preempt_scan", stage_preempt_scan),
               ("serial", stage_serial), ("walk", stage_walk),
               ("fill", stage_fill), ("groups", stage_groups),
               ("colocated", stage_colocated), ("loadmix", stage_loadmix),
               ("serve-groups", stage_serve_groups), ("serve", stage_serve)]
    if len(dev) > 1:
        stages.append(("mesh", stage_mesh))
    for name, fn in stages:
        try:
            smoke.stage(name, lambda fn=fn: fn(smoke))
        except Exception:
            # a stage that raises fails the smoke; the later stages still
            # run, so one call to the chip reports every broken path
            traceback.print_exc()
            smoke.check(f"{name}.completed", False)
    if len(dev) == 1:
        print("== mesh\n  did not run: one device visible", flush=True)

    passed = all(smoke.checks.values())
    report = {
        "device": device,
        "versions": versions(),
        "rehearsal": smoke.rehearsal,
        "checks_passed": passed,
        "failed_checks": sorted(k for k, v in smoke.checks.items() if not v),
        "checks": smoke.checks,
        "stages": smoke.stages,
        "mesh_stage": ("ran" if "mesh" in smoke.stages
                       else "did not run: one device visible"),
        "wall_seconds": round(time.perf_counter() - t_start, 3),
        "compile_seconds": round(sum(smoke.compile_seconds.values()), 3),
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get(ops.COMPILE_CACHE_ENV)),
            "entries_before": cache_before,
            "entries_after": cache_entries(cache_dir),
            **smoke.cache_events},
        "claim": None,
    }
    print(json.dumps(report), flush=True)
    if not smoke.rehearsal:
        # the verdict: these two keys and nothing else, last on stdout
        print(json.dumps({"ok": passed, "device": device}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
