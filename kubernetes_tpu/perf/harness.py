"""Parity cells for the tests and the on-chip smoke. Not the benchmark.

Each cell builds a small cluster in process (`mustSetupScheduler`,
scheduler_perf/util.go:34: store + scheduler, no kubelet), drives one path
of the scheduler over it and audits what came out before it returns: the
workload lanes of scheduler_bench_test.go:39-131 (`run`), gang atomicity
(`run_gang_cell`), a preemption wave against the serial oracle
(`run_preempt_cell`), the sharded program against the single-device one
(`run_shard_cell`), the serve loop's admitted-or-429 and oracle-parity
audits (`run_serve_cell`), the commit core against its twin
(`run_commit_cell`), the cluster-in-a-process pipeline (`run_e2e_density`).
`tests/` and `chip_smoke.py` call them for those audits.

The benchmark is `benchmark/run.py` over the cells of `BENCHMARK.json`, on
the chip, timed by its own client; the driver records it in
`PERF_LEDGER.jsonl`. A `throughput`, `pods_per_s` or `scans_per_s` field
returned here is a count over this process's loop on whatever backend ran
it (the CPU, in the tests): it says the cell made progress and is never
quoted as a speed.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

from kubernetes_tpu.api.types import LABEL_HOSTNAME, LABEL_ZONE_FAILURE_DOMAIN
from kubernetes_tpu.models.hollow import (
    NodeStrategy, PodStrategy, make_pods, populate_store,
)
from kubernetes_tpu.store.store import Store, EVENTS, PODS
from kubernetes_tpu.scheduler import Scheduler


@dataclass
class PerfConfig:
    nodes: int = 100
    existing_pods: int = 0
    pods: int = 3000
    zones: int = 0
    # plain | anti-affinity | affinity | node-affinity | spread
    workload: str = "plain"
    use_tpu: bool = True
    burst: int = 1024           # 0 = serial schedule_one loop
    percentage_of_nodes_to_score: int = 100


@dataclass
class PerfResult:
    scheduled: int
    elapsed: float
    throughput: float           # pods/s over the whole run
    min_qps: float              # worst 1s-window rate (density metric)
    attempts: dict = field(default_factory=dict)


def _pod_strategy(cfg: PerfConfig, count: int, prefix: str) -> PodStrategy:
    st = PodStrategy(count=count, name_prefix=prefix)
    if cfg.workload == "anti-affinity":
        # makeBasePodWithPodAntiAffinity: hostname topology
        # (scheduler_bench_test.go:151)
        st.anti_affinity_topology = LABEL_HOSTNAME
    elif cfg.workload == "affinity":
        # makeBasePodWithPodAffinity: ZONE topology with every node labeled
        # zone1 (scheduler_bench_test.go:175, NewLabelNodePrepareStrategy
        # :100) — co-location is per zone, so the workload never saturates a
        # single node the way a hostname topology would
        st.affinity_topology = LABEL_ZONE_FAILURE_DOMAIN
    elif cfg.workload == "node-affinity":
        st.node_affinity_key = "perf-group"
        st.node_affinity_values = ("a", "b")
    elif cfg.workload not in ("plain", "spread"):
        raise ValueError(f"unknown workload {cfg.workload!r}")
    # "spread" pods are plain-shaped; the Service created in setup() makes
    # SelectorSpreadPriority count them (selector_spreading.go:66)
    return st


def setup(cfg: PerfConfig) -> tuple[Store, Scheduler]:
    """mustSetupScheduler analog."""
    store = Store(watch_log_size=max(65536, 4 * (cfg.nodes + cfg.pods
                                                 + cfg.existing_pods)))
    node_st = NodeStrategy(count=cfg.nodes, zones=cfg.zones)
    if cfg.workload == "node-affinity":
        node_st.label_fracs = {"perf-group": ("a", 0.5)}
    elif cfg.workload == "affinity" and not cfg.zones:
        # reference: NewLabelNodePrepareStrategy(LabelZoneFailureDomain,
        # "zone1") — one zone spanning the whole cluster
        node_st.zones = 1
    elif cfg.workload == "spread" and not cfg.zones:
        # zone blend is 2/3 of the spread score (selector_spreading.go:34);
        # exercise it
        node_st.zones = 3
    # "The setup strategy creates pods with no affinity rules"
    # (scheduler_bench_test.go:68,93): existing pods are PLAIN regardless of
    # the measured workload's shape
    existing = ([PodStrategy(count=cfg.existing_pods, name_prefix="existing",
                             labels={"app": "setup"})]
                if cfg.existing_pods else [])
    populate_store(store, [node_st], existing)
    if cfg.workload == "spread":
        from kubernetes_tpu.api.types import Service
        from kubernetes_tpu.store.store import SERVICES
        store.create(SERVICES, Service(name="spread-svc",
                                       selector={"app": "density"}))
    sched = Scheduler(store, use_tpu=cfg.use_tpu,
                      percentage_of_nodes_to_score=cfg.percentage_of_nodes_to_score)
    sched.sync()
    return store, sched


def run(cfg: PerfConfig, warmup: int = 64) -> PerfResult:
    store, sched = setup(cfg)
    # warmup outside the timed window (jit compilation, informer sync)
    if warmup:
        wst = _pod_strategy(cfg, warmup, "warmup")
        if cfg.workload == "anti-affinity":
            # warmup pods must exercise the same kernels WITHOUT consuming
            # the measured workload's anti-affinity capacity: a distinct
            # label set self-anti-affines among the warmup pods only (the
            # reference sizes its cells so every measured pod fits,
            # scheduler_bench_test.go:61-66)
            wst.labels = {"app": "warmup"}
        for pod in make_pods(wst, 0):
            store.create(PODS, pod)
        sched.pump()
        _drain(sched, cfg)
        sched.pump()
    for pod in make_pods(_pod_strategy(cfg, cfg.pods, "measured"), 0):
        store.create(PODS, pod)
    sched.pump()
    before = sched.metrics.schedule_attempts["scheduled"]
    windows: list[tuple[float, int]] = []
    t0 = time.perf_counter()
    last_t, last_n = t0, before
    while True:
        n = _drain_step(sched, cfg)
        now = time.perf_counter()
        cur = sched.metrics.schedule_attempts["scheduled"]
        if now - last_t >= 1.0:
            windows.append((now - last_t, cur - last_n))
            last_t, last_n = now, cur
        if n == 0:
            break
    elapsed = time.perf_counter() - t0
    sched.pump()
    scheduled = sched.metrics.schedule_attempts["scheduled"] - before
    throughput = scheduled / elapsed if elapsed > 0 else 0.0
    if windows:
        min_qps = min(count / dt for dt, count in windows if dt > 0)
    else:
        min_qps = throughput
    return PerfResult(scheduled, elapsed, throughput, min_qps,
                      dict(sched.metrics.schedule_attempts))


def _drain_step(sched: Scheduler, cfg: PerfConfig) -> int:
    if cfg.burst:
        return sched.schedule_burst(max_pods=cfg.burst)
    return 1 if sched.schedule_one(timeout=0.0) else 0


def _drain(sched: Scheduler, cfg: PerfConfig) -> None:
    while _drain_step(sched, cfg):
        pass


def run_preempt_cell(n_nodes: int, n_victims: int,
                     n_preemptors: int = 128, mesh=None) -> dict:
    """Preemption pressure-wave cell (BASELINE configs[3]): `n_preemptors`
    failed pods run as ONE schedule-else-preempt launch on the device
    (kernels.pressure_batch) against `n_victims` lower-priority pods spread
    over `n_nodes`, vs the serial oracle doing the same work per pod (the
    reference fans selectVictimsOnNode over 16 goroutines PER pod,
    generic_scheduler.go:996). The device side runs with a WARM persistent
    victim table (TPUScheduler.prewarm_preempt) — the steady-state
    condition, since production scans ride a table maintained incrementally
    across cycles — and reports the residual per-wave encode vs device-scan
    phase split. Decisions are asserted identical before timing is
    reported; returns {scans_per_s, vs_oracle, device_seconds,
    oracle_seconds, encode_seconds, scan_seconds, preemptors}."""
    import time as _t
    from kubernetes_tpu.api.types import Pod, Node, Container
    from kubernetes_tpu.cache.node_info import NodeInfo
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    from kubernetes_tpu.oracle import predicates as preds
    from kubernetes_tpu.oracle.generic_scheduler import (FitError,
                                                         GenericScheduler)
    from kubernetes_tpu.oracle.preemption import Preemptor
    GI = 1024 ** 3
    per_node = max(1, n_victims // n_nodes)
    cpu_each = 4000 // per_node
    infos = {}
    names = []
    uid = 0
    for i in range(n_nodes):
        node = Node(name=f"node-{i}",
                    allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110})
        ni = NodeInfo(node)
        for _ in range(per_node):
            uid += 1
            p = Pod(name=f"victim-{uid}", priority=1, node_name=node.name,
                    containers=(Container.make(
                        name="c", requests={"cpu": cpu_each}),))
            ni.add_pod(p)
        infos[node.name] = ni
        names.append(node.name)
    preemptors = [Pod(name=f"hi-{k}", priority=10, containers=(
        Container.make(name="c", requests={"cpu": cpu_each}),))
        for k in range(n_preemptors)]

    def device_wave(tpu):
        out = tpu.preempt_pressure_burst(preemptors, infos, names, [])
        assert out is not None
        return out

    device_wave(TPUScheduler(percentage_of_nodes_to_score=100,
                             mesh=mesh))  # compile
    tpu = TPUScheduler(percentage_of_nodes_to_score=100, mesh=mesh)
    tpu.prewarm_preempt(infos, names, [])   # steady-state victim table
    t0 = _t.perf_counter()
    got = device_wave(tpu)
    dev = _t.perf_counter() - t0

    def oracle_wave():
        # the serial referee: schedule-else-preempt with nominated ghosts,
        # successes folded — normalized to the same outcome tuples the
        # device wave returns (a fit-able nodes/pods ratio must compare,
        # not crash)
        nominated: dict = {}
        nom_fn = lambda n: list(nominated.get(n, []))
        g = GenericScheduler(percentage_of_nodes_to_score=100,
                             nominated_pods_fn=nom_fn)
        world = dict(infos)
        out = []
        for pod in preemptors:
            funcs = preds.default_predicate_set(world)
            try:
                r = g.schedule(pod, world, names, predicate_funcs=funcs)
            except FitError as err:
                res = Preemptor().preempt(pod, world, names, err,
                                          nominated_pods_fn=nom_fn)
                if res.node is None:
                    out.append(("failed", not res.nominated_to_clear))
                    continue
                ghost = pod.clone()
                ghost.node_name = res.node.name
                nominated.setdefault(res.node.name, []).append(ghost)
                out.append(("nominated", res.node.name,
                            sorted(v.name for v in res.victims)))
                continue
            assumed = pod.clone()
            assumed.node_name = r.suggested_host
            ni = world[r.suggested_host].clone()
            ni.add_pod(assumed)
            world = {**world, r.suggested_host: ni}
            out.append(("bound", r.suggested_host))
        return out

    t0 = _t.perf_counter()
    want = oracle_wave()
    ora = _t.perf_counter() - t0
    norm = [("nominated", o[1], sorted(v.name for v in o[2]))
            if o[0] == "nominated" else o for o in got]
    assert norm == want, f"device/oracle preempt divergence: {norm} != {want}"
    phases = tpu.last_preempt_phases or {}
    return {
        "scans_per_s": round(n_preemptors / dev, 2),
        "vs_oracle": round(ora / dev, 2),
        "device_seconds": round(dev, 4),
        "oracle_seconds": round(ora, 4),
        "encode_seconds": round(phases.get("encode", 0.0), 4),
        "scan_seconds": round(phases.get("scan", 0.0), 4),
        "preemptors": n_preemptors,
    }


def run_shard_cell(n_nodes: int, n_pods: int = 2000, devices=None,
                   verify: bool = False, existing_per_node: int = 0) -> dict:
    """Mesh-sharded burst cell at fleet scale (50k-200k nodes) — the
    node-axis cells one chip's HBM cannot hold once the resident state is
    counted (at 200k nodes the [N_pad, P=128] victim slot planes alone are
    7 planes x 256k x 128 x 8B ~ 1.8 GiB, plus the [N_pad] node planes and
    the fused carry + checkpoint copies). The node axis rides NamedSharding(mesh, P("nodes")) over
    `devices` chips (default: every visible device), the burst runs the
    single-dispatch/single-fetch fused contract, and throughput counts
    decided pods.

    `verify=True` additionally reruns the identical cell single-device and
    asserts bit-identical placements — the parity referee for the scale
    cells (expensive: doubles the runtime; the fuzz suites + shard sweep
    pin parity at small N every run, so the matrix cells default to the
    sharded timing only)."""
    import time as _t
    import numpy as np
    from kubernetes_tpu.api.types import Node, Pod, Container
    from kubernetes_tpu.cache.node_info import NodeInfo
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    from kubernetes_tpu.parallel import sharding as S
    GI = 1024 ** 3
    infos = {}
    names = []
    for i in range(n_nodes):
        # uneven zones (n % 3 != 0 at the matrix sizes) keep the NodeTree
        # rotation machinery live at scale in callers that attach a tree
        node = Node(name=f"n{i}",
                    labels={"failure-domain.beta.kubernetes.io/zone":
                            f"z{i % 3}"},
                    allocatable={"cpu": 4000, "memory": 32 * GI,
                                 "pods": 110})
        ni = NodeInfo(node)
        for e in range(existing_per_node):
            ni.add_pod(Pod(name=f"w{i}-{e}", node_name=node.name,
                           containers=(Container.make(
                               name="c", requests={"cpu": 100}),)))
        infos[node.name] = ni
        names.append(node.name)

    def mk_pods(tag: str, count: int):
        return [Pod(name=f"{tag}{j}", labels={"app": "shard"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100, "memory": GI}),))
                for j in range(count)]

    mesh = S.make_mesh(devices)
    n_dev = int(mesh.devices.size)

    def cell(mesh_arg):
        ts = TPUScheduler(percentage_of_nodes_to_score=100, mesh=mesh_arg)
        # warmup: compile the (bucket, class) signature outside the window
        warm = ts.schedule_burst(mk_pods("warm", 8), infos, names,
                                 bucket=n_pods)
        assert warm is not None, "shard cell refused the warmup burst"
        t0 = _t.perf_counter()
        hosts = ts.schedule_burst(mk_pods("p", n_pods), infos, names,
                                  bucket=n_pods)
        dt = _t.perf_counter() - t0
        assert hosts is not None, "shard cell refused the measured burst"
        return ts, hosts, dt

    ts, hosts, dt = cell(mesh)
    if verify:
        _ts1, hosts1, _dt1 = cell(None)
        assert hosts == hosts1, (
            "sharded cell diverged from single-device at "
            f"{n_nodes} nodes: first diff at "
            f"{next(i for i, (a, b) in enumerate(zip(hosts, hosts1)) if a != b)}")
    n_pad = ts.encoder._batch.n_pad
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "pods_bound": sum(1 for h in hosts if h is not None),
        "pods_per_s": round(n_pods / dt, 1) if dt else 0.0,
        "devices": n_dev,
        "per_device_node_rows": n_pad // max(n_dev, 1),
        "verified_vs_single_device": bool(verify),
    }


def run_serve_cell(n_nodes: int = 1000, arrival_rate: float = 2000.0,
                   duration: float = 30.0, window: int = 2048,
                   depth: int = 3, max_depth: Optional[int] = None,
                   mesh=None, parity_windows: int = 3,
                   parity_pods: int = 256, seed: int = 0,
                   max_resident: Optional[int] = None) -> dict:
    """Arrival-driven serving cell: an
    ArrivalGenerator feeds pods at `arrival_rate`/s for `duration`
    seconds while a ServeLoop (window_size=`window`, `depth` windows'
    worth of pods a step) cuts fused windows from the live activeQ, with a
    BackpressureGate shedding creates past `max_depth` (default: two
    seconds of arrivals) with 429 + Retry-After.

    Scores SUSTAINED pods/s over the arrival window (not a drain of a
    pre-built backlog) AND the ledger-derived startup percentiles
    (admission->commit — the accepted create IS the left boundary, so
    queue wait and shed-then-readmit backoffs are scored honestly)
    against the density.go 5 s SLO. Two in-cell audits gate the numbers:

    - all-admitted-or-429'd: every generated arrival either landed in
      the store AND got bound, or was shed and is accounted (re-admitted
      later, or given up after the client's retry budget) — nothing is
      silently dropped by gate, queue, or loop;
    - parity: after the timed window, `parity_windows` serve windows of
      fresh arrivals run with the flight recorder in replay mode and
      every captured launch is re-derived through the serial oracle —
      `parity_violations` must be 0 (decisions under arrival load are
      the same bits a serial oracle produces).

    Serving means pods COMPLETE: a drain bench's resident set only
    grows, but minutes at thousands of arrivals/s would exceed any
    fixed cluster's capacity. A completion reaper (the hollow stand-in
    for workloads finishing) deletes the oldest BOUND arrivals whenever
    the resident set exceeds `max_resident` (default: half the cell's
    pod capacity), so the cell reaches a steady state — arrivals in,
    completions out — and the SLO is scored in the regime the issue
    names. Reaped pods stay in the audit: created == still-in-store +
    reaped, and nothing admitted is ever lost.

    The single-threaded cooperative drive (gen.tick interleaved with
    loop.step) keeps the arrival sequence deterministic per seed; wall
    pacing still holds because tick() creates whatever the elapsed time
    owes."""
    import time as _t
    from collections import deque
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.obs import flight as obs_flight
    from kubernetes_tpu.obs.ledger import LEDGER
    from kubernetes_tpu.serve import ArrivalGenerator, ServeLoop
    from kubernetes_tpu.store.store import (MODIFIED, NODES, ExpiredError,
                                            NotFoundError)
    GI = 1024 ** 3
    est = int(arrival_rate * duration)
    # 64k-event watch window: the serve consumers (informers + the reap
    # watch) are pumped every step, so their backlog stays tiny — the old
    # 256k ring only meant the event log GREW for the first ~45 s of a
    # soak, and every gen2 GC pass over that still-growing heap landed as
    # a multi-ms pause inside some window's prologue (round-17 tail fix)
    store = Store(watch_log_size=1 << 16)
    for i in range(n_nodes):
        # uneven zones (n % 3 != 0 at most sizes) keep NodeTree rotation
        # live — serving must replay the same walk the oracle does
        store.create(NODES, Node(
            name=f"node-{i}",
            labels={"failure-domain.beta.kubernetes.io/zone":
                    f"zone-{i % 3}",
                    "kubernetes.io/hostname": f"node-{i}"},
            allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
    sched = Scheduler(store, use_tpu=True,
                      percentage_of_nodes_to_score=100, mesh=mesh)
    sched.sync()
    loop = ServeLoop(sched, window_size=window, depth=depth)
    # warmup BEFORE the gate attaches: jit compiles ride ungated creates
    warm = ArrivalGenerator(store, rate=10 ** 9, total=64,
                            name_prefix="warm-", seed=seed)
    warm.tick()
    warm.tick()
    loop.drain(timeout=30.0)
    gate = loop.attach_gate(
        max_depth=(int(max_depth) if max_depth is not None
                   else max(4 * window, int(2 * arrival_rate))),
        # a calmer Retry-After floor for over-capacity cells: the base
        # 50 ms suggestion let shed clients re-arrive six-figure times
        # per second, and the retry storm itself ate serving capacity
        # (no effect on cells that keep up — they never shed)
        retry_after_base=0.25)
    LEDGER.reset()
    gen = ArrivalGenerator(store, rate=arrival_rate, seed=seed)
    # completion reaper: a watch collects binds in commit order; when the
    # resident arrival set outgrows `max_resident` the oldest bound pods
    # are deleted (the hollow "workload finished"), keeping the cell in
    # the steady serving regime instead of filling the cluster
    cap = n_nodes * min(110, 4000 // 100)   # the cell's pod capacity
    resident_target = (int(max_resident) if max_resident is not None
                       else max(4 * window, cap // 2))
    reap_watch = store.watch(PODS)
    bound_fifo: deque = deque()
    seen_bound: set = set()
    reaped = 0

    def reap() -> None:
        nonlocal reaped
        try:
            events = reap_watch.drain()
        except ExpiredError:       # dropped-with-resync: rebuild from list
            events = []
            bound_fifo.clear()
            seen_bound.clear()
            for p in store.list(PODS)[0]:
                if p.node_name and p.name.startswith(gen.name_prefix):
                    bound_fifo.append(p.key)
                    seen_bound.add(p.key)
        for ev in events:
            if ev.type == MODIFIED and ev.obj.node_name \
                    and ev.obj.name.startswith(gen.name_prefix) \
                    and ev.obj.key not in seen_bound:
                bound_fifo.append(ev.obj.key)
                seen_bound.add(ev.obj.key)
        if len(bound_fifo) > resident_target:
            batch = []
            while len(bound_fifo) > resident_target:
                batch.append(bound_fifo.popleft())
            # ONE batched delete per reap pass (one store lock + one
            # fan-out flush) — per-pod deletes put one lock+flush per
            # completion on the serving loop's critical path
            reaped += len(store.delete_many(PODS, batch))

    # GC posture of a serving process: full collection BEFORE the timed
    # window, then freeze the steady heap and re-freeze periodically —
    # without this, cyclic-GC gen2 passes over the growing heap (measured
    # ~127 ms each, 16 per 25 s cell) land as stop-the-world pauses
    # inside window prologues, and the backlog each pause leaves behind
    # compounds into oversized windows (round-17 tail fix; the pauses
    # showed up as the encode phase's p99)
    import gc as _gc
    _gc.collect()
    _gc.freeze()
    _gc_thresholds = _gc.get_threshold()
    # young generations keep collecting (most garbage dies there); the
    # full-heap generation is deferred to the explicit collect after the
    # run — a serving process cannot afford 100ms+ stop-the-world passes
    # on its window critical path
    _gc.set_threshold(_gc_thresholds[0], _gc_thresholds[1], 1 << 16)
    bound0 = loop.pods_bound
    t0 = _t.perf_counter()
    t_end = t0 + duration
    while _t.perf_counter() < t_end:
        # reap BEFORE the arrivals tick: the fresh creates then land
        # immediately adjacent to the step's informer pump, so the
        # admission (watch-to-enqueue) phase measures delivery, not the
        # reaper's housekeeping
        reap()
        gen.tick()
        if loop.step() == 0:
            _t.sleep(min(loop.tick_interval, 0.001))
    elapsed = _t.perf_counter() - t0
    sustained = (loop.pods_bound - bound0) / elapsed if elapsed else 0.0
    # arrivals stop; settle every shed retry and drain the queue (keep
    # reaping: a full cluster must keep completing for the tail to land)
    deadline = _t.perf_counter() + 90.0
    while _t.perf_counter() < deadline:
        gen.flush_retries(timeout=0.5)
        reap()
        if loop.step() == 0 and gen.stats()["pending_retry"] == 0 \
                and sched.queue.num_pending() == 0:
            break
    reap_watch.stop()
    # normal GC posture for the audits and beyond; the deferred full
    # collection runs here, OFF the timed window
    _gc.set_threshold(*_gc_thresholds)
    _gc.unfreeze()
    _gc.collect()
    g = gen.stats()
    # -- audit 1: all-admitted-or-429'd ----------------------------------
    measured = [p for p in store.list(PODS)[0]
                if p.name.startswith(gen.name_prefix)]
    unbound = sum(1 for p in measured if not p.node_name)
    assert len(measured) + reaped == g["created"], \
        (f"arrival accounting leak: {len(measured)} in store + {reaped} "
         f"reaped != {g['created']} created")
    assert unbound == 0, f"{unbound} admitted arrivals never bound"
    assert g["attempted"] == g["created"] + g["gave_up"] \
        + g["pending_retry"], f"arrival accounting leak: {g}"
    led = LEDGER.snapshot()
    # -- audit 2: serve-window parity through the flight recorder --------
    obs_flight.RECORDER.configure(mode="replay",
                                  capacity=max(parity_windows, 1))
    obs_flight.RECORDER.clear()
    par = ArrivalGenerator(store, rate=10 ** 9, total=parity_pods,
                           name_prefix="par-", seed=seed + 1)
    violations: list = []
    try:
        while not par.finished():
            par.tick()
            loop.step()
        loop.drain(timeout=30.0)
        violations = obs_flight.RECORDER.replay_all()
    finally:
        obs_flight.RECORDER.configure(mode="digest")
        obs_flight.RECORDER.clear()
    return {
        "nodes": n_nodes,
        "arrival_rate": arrival_rate,
        "duration": round(elapsed, 2),
        "sustained_pods_per_s": round(sustained, 1),
        "window": window,
        "depth": depth,
        "windows_cut": loop.windows_cut,
        "idle_ticks": loop.idle_ticks,
        "startup_p50": led["startup_p50"],
        "startup_p99": led["startup_p99"],
        "startup_slo_ok": led["startup_slo_ok"],
        # windowed twins (trailing 30 s): a late-run stall flips these
        # while the cumulative numbers above still average it away
        "startup_p50_windowed": led["startup_p50_windowed"],
        "startup_p99_windowed": led["startup_p99_windowed"],
        "startup_slo_ok_windowed": led["startup_slo_ok_windowed"],
        "slo_burn_rate": led["slo_burn_rate"],
        "phase_split": led["phase_split"],
        "pods_completed": led["pods_completed"],
        "workload_reaped": reaped,
        "resident_target": resident_target,
        "arrivals": g,
        "admission": gate.debug_state(),
        "audit_all_admitted_or_429": True,   # the asserts above gate it
        "parity_violations": len(violations),
        "parity_errors": violations[:3],
    }


def run_gang_cell(nodes: int = 1000, gang_size: int = 64,
                  pods: int = 1000, existing: int = 0,
                  use_tpu: bool = True, burst: int = 1024) -> PerfResult:
    """Gang matrix cell: `pods // gang_size` PodGroups of spec-identical
    members scheduled all-or-nothing through the burst path; throughput
    counts member pods. Asserts the atomicity contract (no partially
    bound group) before reporting — a gang-path regression fails the cell
    rather than reporting corrupt numbers."""
    from kubernetes_tpu.coscheduling.types import LABEL_POD_GROUP, PodGroup
    from kubernetes_tpu.store.store import PODGROUPS
    cfg = PerfConfig(nodes=nodes, existing_pods=existing, pods=pods,
                     use_tpu=use_tpu, burst=burst)
    store, sched = setup(cfg)
    MI = 1024 ** 2
    from kubernetes_tpu.api.types import Pod, Container

    def create_gangs(tag: str, count: int, size: int) -> None:
        for g in range(count):
            name = f"{tag}-{size}-{g}"
            store.create(PODGROUPS, PodGroup(name=name, min_member=size))
            for r in range(size):
                store.create(PODS, Pod(
                    name=f"{name}-r{r}",
                    labels={LABEL_POD_GROUP: name, "app": "gang"},
                    containers=(Container.make(
                        name="c",
                        requests={"cpu": 100, "memory": 500 * MI}),)))

    create_gangs("warmup", 1, gang_size)   # compile outside the window
    sched.pump()
    _drain(sched, cfg)
    sched.pump()
    n_groups = max(1, pods // gang_size)
    create_gangs("measured", n_groups, gang_size)
    sched.pump()
    before = sched.metrics.schedule_attempts["scheduled"]
    t0 = time.perf_counter()
    _drain(sched, cfg)
    elapsed = time.perf_counter() - t0
    sched.pump()
    by_group: dict[str, list] = {}
    for p in store.list(PODS)[0]:
        g = p.labels.get(LABEL_POD_GROUP)
        if g:
            by_group.setdefault(g, []).append(bool(p.node_name))
    partial = [g for g, flags in by_group.items()
               if any(flags) and not all(flags)]
    assert not partial, f"partially bound gangs: {partial[:5]}"
    scheduled = sched.metrics.schedule_attempts["scheduled"] - before
    throughput = scheduled / elapsed if elapsed > 0 else 0.0
    return PerfResult(scheduled, elapsed, throughput, throughput,
                      dict(sched.metrics.schedule_attempts))


def run_commit_cell(n_pods: int = 4096, waves: int = 8,
                    n_watchers: int = 8, impl: Optional[str] = None,
                    audit: Optional[list] = None,
                    watch_classes: int = 1,
                    shared_classes: bool = True) -> dict:
    """Commit-core cell: the store-write +
    fan-out tail of a burst wave in isolation — `waves` waves of `n_pods`
    binds each, every wave ONE `commit_wave` call (batched bind + the
    Scheduled audit-record creates) and ONE `fanout_wave` call, with
    `n_watchers` live pod watchers copying events out on their own
    threads (the overlap the core's GIL-released poll buys).

    Round 20: the watchers split across `watch_classes` distinct
    (kind, selector) subscription classes (1 = everyone shares one
    materialize-once/encode-once class — the north-star shape); half of
    each class drains the Event lane, half the serialize-once byte ring
    (the apiserver's wire encoding), so the copy-out phase pays both
    representations once per class. `shared_classes=False` runs the
    degenerate class-per-watcher mode — the pre-round-20 per-watcher
    fan-out path, the scaling floor's extrapolation baseline.

    Reports writes/s (binds + event creates landed; the watchers are
    ATTACHED during the timed loop, so every fanout_wave pays its cursor
    publishes) and copy-out events/s + bytes/s (the drain phase, timed
    on its own — on a single-core box a concurrent consumer just
    timeshares the GIL with the commit loop and turns both numbers into
    scheduler noise; the threaded-overlap correctness is pinned by
    tests/test_commit_core.py instead). `impl` pins the core
    ("native"/"twin"); when `audit` is a list, every wave's (missing,
    rv-after) and the full first-watcher event stream are appended so the
    caller can referee native vs twin bit-for-bit. The serial per-pod
    reference only runs at <= 1024 watchers (each serial verb's flush
    walks every watcher — at 100k that measures the walk, not the verb)."""
    from kubernetes_tpu.api.types import Container, Pod
    from kubernetes_tpu.apiserver.server import wire_line
    from kubernetes_tpu.store.record import EventRecorder
    store = Store(watch_log_size=max(1 << 17, 8 * n_pods * waves),
                  commit_core=impl, shared_watch_classes=shared_classes)
    store.set_wire_encoder(wire_line)
    recorder = EventRecorder(store)
    MI = 1024 ** 2
    # one fresh pod set PER WAVE: the round-18 rv-CAS bind refuses
    # re-binding an already-bound pod (the fleet's double-bind guard), so
    # the steady-state commit path is exercised with distinct unbound
    # pods each wave — the per-binding work (clone, setattr, rv, log
    # append) is identical to the old rebind loop
    for wv in range(waves):
        for j in range(n_pods):
            store.create(PODS, Pod(
                name=f"p{wv}-{j}", labels={"app": "commit"},
                containers=(Container.make(
                    name="c", requests={"cpu": 100, "memory": 500 * MI}),)))
    pods_by_key = {p.key: p for p in store.list(PODS)[0]}
    wave_keys = [[f"default/p{wv}-{j}" for j in range(n_pods)]
                 for wv in range(waves)]
    n_classes = max(1, min(watch_classes, n_watchers))
    watches = [store.watch(PODS, selector=f"wc{i % n_classes}")
               for i in range(n_watchers)]
    writes = 0
    t0 = time.perf_counter()
    for wv in range(waves):
        keys = wave_keys[wv]
        bindings = [(k, f"n{wv}") for k in keys]
        recs = recorder.make_pod_records([
            (pods_by_key[k], "Normal", "Scheduled",
             f"Successfully assigned {k} to n{wv}") for k in keys])
        missing = store.commit_wave(bindings, recs)
        store.fanout_wave()
        writes += 2 * len(bindings) - len(missing)
        if audit is not None:
            audit.append((list(missing), store.resource_version()))
    elapsed = time.perf_counter() - t0
    # copy-out phase: drain every watcher (Event materialization — once
    # per class in shared mode — happens here, on the consumer side; the
    # cost fan-out moved OFF the commit thread above). Odd watchers drain
    # the serialize-once byte ring instead of the Event lane, so each
    # class pays one materialization AND one wire encoding per event and
    # every classmate after the first serves shared objects/bytes.
    stats_before = store.watch_plane_state()
    delivered = 0
    audit_stream: list = []
    t1 = time.perf_counter()
    for i, w in enumerate(watches):
        if i % 2 == 1:
            delivered += len(w.drain_bytes())
            continue
        evs = w.drain()
        delivered += len(evs)
        if audit is not None and i == 0:
            audit_stream = [(e.type, e.resource_version, e.obj.key,
                             e.obj.node_name) for e in evs]
    t_drain = time.perf_counter() - t1
    # class-plane accounting over the drain window (cumulative core
    # counters; the subtraction isolates this cell's copy-out phase)
    stats_after = store.watch_plane_state()
    n_live_classes = len(stats_after["classes"])
    drain_bytes_served = (stats_after["bytes_served"]
                          - stats_before["bytes_served"])
    drain_materializations = (stats_after["materializations"]
                              - stats_before["materializations"])
    drain_shared_hits = (stats_after["shared_hits"]
                         - stats_before["shared_hits"])
    # reference: the per-pod verb shape (serial bind_pod + its record
    # construction + per-record create, watchers still attached — the
    # same work per write the wave loop timed) measured IN THE SAME RUN,
    # so the floor check can normalize against whatever CPU
    # quota/throttle this box is under right now (absolute writes/s here
    # swing 3-4x run to run with cgroup credits)
    ref_n = min(n_pods, 1024) if n_watchers <= 1024 else 0
    # fresh unbound pods for the serial reference (the rv-CAS bind would
    # refuse re-binding the wave pods); created OUTSIDE the timed loop
    for j in range(ref_n):
        store.create(PODS, Pod(
            name=f"ref-{j}", labels={"app": "commit"},
            containers=(Container.make(
                name="c", requests={"cpu": 100, "memory": 500 * MI}),)))
    ref_pods = {p.key: p for p in store.list(PODS)[0]
                if p.name.startswith("ref-")}
    t2 = time.perf_counter()
    for j in range(ref_n):
        k = f"default/ref-{j}"
        store.bind_pod(k, "ref")
        rec = recorder.make_pod_records([
            (ref_pods[k], "Normal", "Scheduled",
             f"Successfully assigned {k} to ref")])[0]
        store.create(EVENTS, rec, move=True)
    t_ref = time.perf_counter() - t2
    for w in watches:
        w.stop()
    if audit is not None:
        audit.append(audit_stream)
    copyout_rate = round(delivered / t_drain, 1) if t_drain else 0.0
    return {
        "writes_per_s": round(writes / elapsed, 1) if elapsed else 0.0,
        "events_per_s": copyout_rate,
        "serial_writes_per_s": (round(2 * ref_n / t_ref, 1)
                                if ref_n and t_ref else None),
        "writes": writes,
        "events_delivered": delivered,
        "waves": waves,
        "watchers": n_watchers,
        "subscription_classes": n_live_classes,
        "copyout_events_per_sec": copyout_rate,
        "copyout_bytes_per_sec": (round(drain_bytes_served / t_drain, 1)
                                  if t_drain else 0.0),
        "copyout_bytes": drain_bytes_served,
        "copyout_materializations": drain_materializations,
        "copyout_shared_hits": drain_shared_hits,
        "shared_watch_classes": store.shared_watch_classes,
        "impl": store.core_impl,
    }


def run_e2e_density(n_nodes: int = 50, n_pods: int = 150,
                    use_tpu: bool = True, node_churn: bool = False) -> dict:
    """e2e scalability density analog (test/e2e/scalability/density.go):
    pods created through the FULL cluster-in-a-process pipeline (apiserver
    admission -> scheduler -> hollow kubelets running them), reporting
    cluster-wide saturation throughput (SLO >= 8 pods/s, density.go:58) and
    pod startup latency percentiles against the <= 5s SLO
    (density.go:56,987-992). Startup = create time -> observed Running.

    `node_churn=True` is the round-14 soak ingredient (ROADMAP item 5's
    "node drains + evictions" lane): one node is DELETED at half-load
    while the scheduler is saturated — in-flight decisions referencing it
    refuse stale and replan — and re-added shortly after; the SLOs must
    hold through the churn and the report carries the refusal count."""
    import time as _t
    from kubernetes_tpu.cmd.cluster import Cluster
    from kubernetes_tpu.api.types import Pod, Container
    from kubernetes_tpu.models.hollow import MI
    from kubernetes_tpu.obs.ledger import LEDGER
    from kubernetes_tpu.scheduler import STALE_BINDS
    from kubernetes_tpu.store.store import NODES, NotFoundError
    LEDGER.reset()   # scope the decomposition to this density run
    stale0 = STALE_BINDS.value
    churn_report = None
    with Cluster(n_nodes=n_nodes, api_port=-1, use_tpu=use_tpu,
                 kubelet_interval=0.02) as cluster:
        created: dict[str, float] = {}
        started: dict[str, float] = {}
        t0 = _t.perf_counter()
        victim = None
        for j in range(n_pods):
            p = Pod(name=f"density-{j}", labels={"app": "density"},
                    containers=(Container.make(
                        name="c", requests={"cpu": 100, "memory": 200 * MI}),))
            cluster.store.create(PODS, p)
            created[p.key] = _t.perf_counter()
            if node_churn and j == n_pods // 2:
                # node death at half-load, while the scheduler is mid-drain
                nodes = sorted(n.name for n in cluster.store.list(NODES)[0])
                victim = nodes[len(nodes) // 2]
                victim_obj = cluster.store.get(NODES, victim)
                try:
                    cluster.store.delete(NODES, victim)
                except NotFoundError:
                    victim_obj = None
        if node_churn and victim is not None and victim_obj is not None:
            _t.sleep(0.2)   # let in-flight launches observe the death
            restored = victim_obj.clone()
            restored.resource_version = 0
            cluster.store.create(NODES, restored)
            churn_report = {"victim": victim, "restored": True}

        def all_running():
            pods, _rv = cluster.store.list(PODS)
            now = _t.perf_counter()
            running = 0
            for p in pods:
                if p.phase == "Running":
                    running += 1
                    started.setdefault(p.key, now)
            return running >= n_pods
        ok = cluster.wait_for(all_running, timeout=120)
        elapsed = _t.perf_counter() - t0
    lats = sorted(started[k] - created[k] for k in started)
    pct = lambda q: lats[min(len(lats) - 1, int(q * len(lats)))] if lats else None
    led = LEDGER.snapshot()
    return {
        "saturated": ok,
        "throughput": round(n_pods / elapsed, 1) if elapsed else 0.0,
        "startup_p50": round(pct(0.50), 3) if lats else None,
        "startup_p99": round(pct(0.99), 3) if lats else None,
        "startup_slo_5s": bool(lats) and pct(0.99) <= 5.0,
        "throughput_slo_8pps": (n_pods / elapsed) >= 8.0 if elapsed else False,
        # the ledger's view of the same run: scheduling (enqueue->commit)
        # percentiles + the full per-phase decomposition — "where did my
        # 5 seconds go" for the density SLO
        "sched_startup_p50": led["startup_p50"],
        "sched_startup_p99": led["startup_p99"],
        # windowed twins (trailing 30 s) beside the cumulative numbers:
        # a stall in the run's last seconds moves these while the
        # cumulative percentiles still average it away
        "sched_startup_p50_windowed": led["startup_p50_windowed"],
        "sched_startup_p99_windowed": led["startup_p99_windowed"],
        "sched_slo_ok_windowed": led["startup_slo_ok_windowed"],
        "sched_slo_burn_rate": led["slo_burn_rate"],
        "sched_phase_split": led["phase_split"],
        "node_churn": (dict(churn_report,
                            stale_refusals=int(STALE_BINDS.value - stale0))
                       if churn_report is not None else None),
    }
