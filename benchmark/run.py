#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in `BENCHMARK.json`, its configuration under
`benchmark/configs/`, its traffic mix under `benchmark/traffic/` and, for a
traced run, its per-layer metrics under `benchmark/metrics/`; builds the
cluster from the seed; drives the program through its public entry points
(`factory.create_scheduler`, `Scheduler.sync/pump/schedule_burst`,
`ServeLoop`, `Store.create_many/delete_many/watch`) with the benchmark's own
client; and prints, as the last line of standard output, one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` when
traced), then `compared`: each number `correct` was decided from, beside its
limit, which are also the run's last lines on standard error. Everything else
it prints comes on earlier lines.

It runs on a TPU only. `--rehearse` is the one way to run it on the CPU
backend (tiny cells, for tests): a rehearsal prints its report and no result
line, so nothing of it can be read as a measurement.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import spec  # noqa: E402

# A traced run traces this much of its window (then up to the next loop
# boundary) unless the traffic mix says otherwise (`trace_seconds`): a mix
# whose every launch is a long scan fills a trace fast.
TRACE_SECONDS = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process started (Linux: /proc), so that set-up
    counts the interpreter's start and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


_T_IMPORT = time.perf_counter()


class Refused(Exception):
    """The run cannot measure anything here; no result line is printed."""

    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code


def say(*a) -> None:
    print(*a, flush=True)


# -- set-up --------------------------------------------------------------------
def open_device(chips: int, rehearse: bool) -> dict:
    """Touch jax (through the package, which places the compile cache and
    enables x64 first) and refuse what cannot be measured."""
    if not os.path.isdir(os.path.join(ROOT, "kubernetes_tpu")):
        raise Refused(3, "the program (kubernetes_tpu/) is not in this "
                         "checkout: nothing to measure")
    import kubernetes_tpu.ops  # noqa: F401  (cache dir + x64, before jax use)
    import jax
    # every program of the cell goes to the persistent cache, the small ones
    # too, so that the second run of a cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        if device["platform"] != "cpu":
            raise Refused(2, "--rehearse is for the CPU backend "
                             "(JAX_PLATFORMS=cpu)")
        return device
    if device["platform"] != "tpu":
        raise Refused(2, f"jax reports platform {device['platform']!r} "
                         f"({device['kind']}), not 'tpu'; this benchmark "
                         f"measures the chip only")
    if device["count"] != chips:
        raise Refused(2, f"the cell asks for {chips} chip(s) and jax sees "
                         f"{device['count']}")
    from kubernetes_tpu import native
    for name in ("commitcore", "heapcore"):
        if native.load(name) is None:
            raise Refused(3, f"native extension {name!r} did not build:\n"
                             f"{native.load_error(name)}")
    return device


class CompileCounter:
    """Counts executables built or fetched from the cache, and the seconds
    spent on it, through jax's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += seconds


class PauseWatch:
    """What could stall the window from outside the program: the
    interpreter's garbage collections (count and longest pause by
    generation, through `gc.callbacks`) and the operating system's account of
    this process (page faults, context switches). For the report only."""

    def __init__(self):
        import resource
        self._resource = resource
        self.pauses = {g: [0, 0.0] for g in (0, 1, 2)}
        self._t = 0.0
        self._ru = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            rec = self.pauses[info["generation"]]
            rec[0] += 1
            rec[1] = max(rec[1], time.perf_counter() - self._t)

    def start(self) -> None:
        self._ru = self._resource.getrusage(self._resource.RUSAGE_SELF)
        gc.callbacks.append(self._cb)

    def stop(self) -> dict:
        gc.callbacks.remove(self._cb)
        ru = self._resource.getrusage(self._resource.RUSAGE_SELF)
        return {"gc": {f"gen{g}": [n, round(p, 4)]
                       for g, (n, p) in self.pauses.items()},
                **{k: getattr(ru, k) - getattr(self._ru, k)
                   for k in ("ru_minflt", "ru_majflt", "ru_nvcsw",
                             "ru_nivcsw")},
                "cpu_s": round(ru.ru_utime + ru.ru_stime
                               - self._ru.ru_utime - self._ru.ru_stime, 3)}


def make_scheduler(store, cfg: dict):
    from kubernetes_tpu.apis.config import SchedulerConfiguration
    from kubernetes_tpu.factory import create_scheduler
    sc = cfg["scheduler"]
    conf = SchedulerConfiguration(
        percentage_of_nodes_to_score=sc["percentage_of_nodes_to_score"])
    conf.feature_gates = {**conf.feature_gates, **sc["feature_gates"]}
    kw = {} if sc["mesh"] == "auto" else {"mesh": None}
    sched = create_scheduler(store, conf, **kw)
    sched.sync()
    return sched


def warm_backlog(client, sched, factory, traffic) -> None:
    """Whole cycles of the cell's own shape: at least two (the second meets
    the rows the first one's deletes dirtied, which is a program of its own),
    and as many as it takes for the client to have seen `warm_binds` binds,
    so that the window opens on a system in its steady state (the store's
    event records at their retention cap)."""
    from lib import drive
    seen = k = 0
    while k < 2 or seen < traffic.get("warm_binds", 0):
        seen += drive.backlog_cycle(client, sched, factory, traffic["backlog"],
                                    f"warm-{k}")["bound_seen"]
        k += 1


def warm_arrivals(client, loop, factory, traffic) -> None:
    """Every shape the open loop can meet: a window over the launch cap, a
    window of one, and a row scatter of every bucket from 16 rows to the
    launch cap (deletes of 16, 32, ... pods, each followed by a small
    window). Then batches until the client has seen `warm_binds` binds (see
    `warm_backlog`)."""
    window = traffic["serve"]["window_size"]
    seq = [0]

    def submit(n: int) -> list:
        made = [factory.make(f"warm-{seq[0] + j}") for j in range(n)]
        seq[0] += n
        ids = [client.register(p, d) for p, d in made]
        client.create([p for p, _d in made])
        deadline = time.perf_counter() + 120.0
        while any(client.bind_seen_at[i] == 0.0 for i in ids):
            loop.step()
            client.drain()
            if time.perf_counter() > deadline:
                raise RuntimeError("warm-up pods were not bound in 120 s")
        return ids

    def retire(ids: list) -> None:
        client.delete([client.keys[i] for i in ids])
        loop.step()
        client.drain()

    live = submit(2 * window + 104)
    live += submit(1)
    size = 16
    while size <= window and len(live) > size:
        batch, live = live[:size], live[size:]
        client.delete([client.keys[i] for i in batch])
        live += submit(3)
        size *= 2
    retire(live)
    retire(submit(2))
    while seq[0] < traffic.get("warm_binds", 0):
        retire(submit(3 * window))


# -- one run -------------------------------------------------------------------
def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            rehearse: bool = False, root: str = ROOT,
            overrides: dict | None = None, hook=None) -> dict:
    """Run one cell and return the result object (and, under `report`, what
    else the run learned). The last two arguments are for the tools and
    tests under benchmark/. `overrides` is data laid over the loaded data,
    never over a file: `{"config": {...}, "traffic": {...}, "program":
    {...}}`, where `program` changes only the configuration the program is
    built with while the reference keeps judging by the file (that is how a
    control runs). `hook(sched, store)` runs once the scheduler is built
    (tests break the timed path with it)."""
    age0 = process_age_s()
    t_enter = time.perf_counter()
    overrides = overrides or {}
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, cell_name)
    cfg = spec.overlaid(spec.load_config(bench, cell["config"], root),
                        overrides.get("config"))
    traffic = spec.overlaid(spec.load_traffic(cell["traffic"], root),
                            overrides.get("traffic"))
    e2e_defs = spec.metrics_for(bench, cell, "end_to_end")
    layer_defs = spec.metrics_for(bench, cell, "per_layer") if trace else []
    readers = []
    for m in layer_defs:
        mf = spec.load_metric(m["name"], root)
        mod = importlib.import_module(f"readers.{mf['reader']}")
        readers.append((m, mf, mod.read))

    t0 = time.perf_counter()
    device = open_device(cell["chips"], rehearse)
    open_s = time.perf_counter() - t0
    peaks = None if rehearse else spec.load_peaks(device["kind"], root)
    compiles = CompileCounter()
    say(f"cell {cell_name} seed {seed} seconds {seconds} trace {int(trace)} "
        f"device {device}")

    from lib import check, cluster, counters, drive
    from lib.client import BIND, Client
    from lib.stats import percentile
    from lib.traffic import PodFactory, due_times
    from kubernetes_tpu.obs.ledger import LEDGER

    t0 = time.perf_counter()
    store, rows, residents, services = cluster.build(cfg, seed)
    sched = make_scheduler(store, spec.overlaid(cfg, overrides.get("program")))
    build_s = time.perf_counter() - t0
    if hook:
        hook(sched, store)
    client = Client(store, tracing=trace)
    factory = PodFactory(traffic, len(services), seed, root)
    kind = traffic["kind"]
    loop = None
    made = due = None
    t0 = time.perf_counter()
    if kind == "closed_backlog":
        warm_backlog(client, sched, factory, traffic)
    else:
        from kubernetes_tpu.serve import ServeLoop
        serve = traffic["serve"]
        loop = ServeLoop(sched, window_size=serve["window_size"],
                         depth=serve["depth"])
        warm_arrivals(client, loop, factory, traffic)
        rate = float(traffic["arrival"]["rate_per_s"])
        loop.attach_gate(
            max_depth=max(4 * serve["window_size"],
                          int(serve["gate_seconds"] * rate)),
            retry_after_base=serve["retry_after_base_s"])
        due = due_times(traffic["arrival"], seconds, seed, root)
        made = [factory.make(f"arr-{j}") for j in range(len(due))]
    warmup_s = time.perf_counter() - t0

    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{cell_name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    cap = min(float(traffic.get("trace_seconds", TRACE_SECONDS)), seconds)
    tracer = drive.Tracer(trace_dir, cap,
                          0.0 if kind == "closed_backlog" else seconds - cap)

    # the GC posture of a serving process, as run_serve_cell holds it: full
    # collection before the window, the steady heap frozen, the full-heap
    # generation deferred until after the window
    gc.collect()
    gc.freeze()
    thresholds = gc.get_threshold()
    gc.set_threshold(thresholds[0], thresholds[1], 1 << 16)
    LEDGER.reset()
    before = counters.snapshot()
    compiles_before = compiles.count
    spent0 = dict(client.spent)
    mark = len(client.log_kind)
    pauses = PauseWatch()
    pauses.start()
    setup_s = age0 + (time.perf_counter() - t_enter)
    try:
        if kind == "closed_backlog":
            win = drive.run_backlog(client, sched, factory, traffic, seconds,
                                    tracer)
        else:
            win = drive.run_arrivals(client, loop, made, due, traffic,
                                     seconds, seed, tracer)
    finally:
        tracer.maybe_stop(force=True)
        outside = pauses.stop()
        gc.set_threshold(*thresholds)
        gc.unfreeze()
    end = len(client.log_kind)
    moved = counters.delta(counters.snapshot(), before)
    ledger = LEDGER.snapshot()["phase_split"]
    compiles_in_window = compiles.count - compiles_before
    window_s = win["t_end"] - win["t_start"]
    spent = {k: client.spent[k] - spent0[k] for k in spent0}
    gc.collect()

    # -- end-to-end metrics, all from the client's clocks ---------------------
    # a rate is all the window's work over all the window's time: making
    # the pods, create_many, the scheduler, the watch, delete_many and the
    # pump that digests the deletes
    values = {"setup_s": setup_s}
    pending_s = None
    if kind == "closed_backlog":
        values["pods_per_s"] = win["bound_seen"] / window_s
        pending_s = sum(c["seconds"] for c in win["cycles"])
        attempted, failed = win["attempted"], win["attempted"] - win["bound_seen"]
    else:
        lat = win["latencies"]
        values["startup_p50_ms"] = percentile(lat, 0.50) * 1e3
        values["startup_p95_ms"] = percentile(lat, 0.95) * 1e3
        attempted, failed = win["attempted"], win["failed"]

    # -- the device ------------------------------------------------------------
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device["memory_peak_bytes"] = peak

    # -- per-layer metrics (traced run) ------------------------------------------
    reduction = None
    layer_values = {}
    if trace:
        from lib import trace as tr
        path = tr.newest_xplane(trace_dir)
        if path is not None:
            reduction = tr.reduce_xplane(path)
        traced_binds = 0
        if tracer.t0 is not None and tracer.t1 is not None:
            for k in range(mark, end):
                if client.log_kind[k] == BIND:
                    t = client.bind_seen_at[client.log_pod[k]]
                    if tracer.t0 <= t <= tracer.t1:
                        traced_binds += 1
        ctx = {
            "kind": kind, "window_s": window_s,
            "pods_bound": win["bound_seen"], "client_spent": spent,
            "pending_pods_per_s": (win["bound_seen"] / pending_s
                                   if pending_s else None),
            "outside_pending_share": (100.0 * (1.0 - pending_s / window_s)
                                      if pending_s else None),
            "ledger": ledger, "counters": moved,
            "latencies": win.get("latencies", []), "late": win.get("late", []),
            "trace": reduction,
            "trace_window_s": (tracer.t1 - tracer.t0
                               if tracer.t1 is not None else None),
            "trace_pods_bound": traced_binds,
            "warmup_s": warmup_s, "compiles_in_window": compiles_in_window,
            "peaks": peaks, "cfg": cfg, "traffic": traffic,
            "n_devices": device["count"],
        }
        for m, mf, read in readers:
            v = read(ctx, **mf["args"])
            if v is not None:
                layer_values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduction is not None:
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = ctx["trace_window_s"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- correct ---------------------------------------------------------------
    t0 = time.perf_counter()
    ref = check.make_reference(cfg, rows, residents, services, root)
    rep = check.replay(client, ref, mark, end, cfg["check"]["first_binds"],
                       cfg["check"]["sampled_binds"], seed)
    check_s = time.perf_counter() - t0
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    left_device = counters.total(moved, "tpu_oracle_fallback_total",
                                 ["device-fault", "circuit-open"])
    twin_waves = counters.total(moved, "store_commit_waves_total", ["twin"])
    bound_twice = sum(1 for c in client.bind_count if c > 1)
    gave_up = win.get("gave_up", 0)
    compared = [
        # (short name, what, value, limit): passes when value <= limit
        ("bindings_differ", "bindings that differ from the reference's",
         len(rep["mismatches"]), 0),
        ("bound_twice", "pods bound twice", bound_twice, 0),
        ("over_allocatable", "binds that put a node over its allocatable",
         rep["over_allocatable"], 0),
        ("pods_lost", "pods lost (attempted - bound and seen - given up)",
         attempted - win["bound_seen"] - gave_up, 0),
        ("shed_given_up", "pods shed and given up", gave_up, 0),
        ("unknown_watch_events", "watch events for pods the client never made",
         client.unknown_events, 0),
        ("device_fallbacks", "oracle fallbacks by device-fault or open breaker",
         left_device, 0),
        ("twin_commit_waves", "commit waves on the twin (non-native) core",
         twin_waves, 0),
        ("not_tpu_scheduler", "algorithm is not a TPUScheduler",
         0 if isinstance(sched.algorithm, TPUScheduler) else 1, 0),
        ("store_not_native", "store core is not native",
         0 if store.core_impl == "native" else 1, 0),
        ("no_compared_binding", "window without a compared binding",
         0 if rep["compared"] else 1, 0),
    ]
    if rehearse:
        # a CPU rehearsal may run without the native cores
        compared = [c for c in compared if "native" not in c[1]]
    say(f"  {rep['compared']} of {rep['window_binds']} window binds compared "
        f"with the reference in {check_s:.2f} s")
    correct = True
    for _name, what, value, limit in compared:
        ok = value <= limit
        correct &= ok
        say(f"  [{'ok' if ok else 'FAIL'}] {what}: {value} (limit {limit})")
    for key, node, want in rep["mismatches"][:5]:
        say(f"    {key}: bound to {node}, reference says {want}")
    client.close()

    metrics = layer_values if trace else {
        m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
        for m in e2e_defs}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace and reduction is not None:
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    # each number compared beside its limit, last in the line; `main` prints
    # the same as the run's last lines on standard error
    result["compared"] = {name: {"value": int(value), "limit": limit}
                          for name, _what, value, limit in compared}
    report = {
        "values": values, "window_s": window_s, "pending_s": pending_s,
        "build_s": build_s,
        "warmup_s": warmup_s, "check_s": check_s,
        "compile_events": compiles.count, "compile_seconds": compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "client_spent": spent, "ledger": ledger,
        "compared": rep["compared"], "window_binds": rep["window_binds"],
        "cycle_s": [round(c["seconds"], 4) for c in win.get("cycles", [])],
        "cycle_cpu_s": [round(c["cpu_s"], 4) for c in win.get("cycles", [])],
        "outside": outside,
        "open_s": open_s, "trace_stop_s": tracer.stop_s,
        "rejected_429": win.get("rejected_429"), "course": win.get("course"),
        "slowest": win.get("slowest"),
        "mid_depth": win.get("mid_depth"), "end_depth": win.get("end_depth"),
        "settle_s": (win["t_done"] - win["t_end"]) if "t_done" in win else None,
        "counters": {k: {"/".join(lab): v for lab, v in ch.items()}
                     for k, ch in moved.items()
                     if k.startswith(("tpu_", "serve_", "store_commit",
                                      "admission_"))},
        "trace": ({k: reduction[k] for k in ("busy_s_per_device", "modules",
                                              "line_names", "host_spans",
                                              "collective_s")}
                  if reduction else None),
    }
    say("report " + json.dumps(report))
    return {"result": result, "report": report}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend, for tests: prints no result line")
    args = ap.parse_args(argv)
    try:
        out = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                      rehearse=args.rehearse)
    except Refused as e:
        print(f"benchmark: refusing to run: {e}", file=sys.stderr)
        return e.code
    except (spec.SpecError, FileNotFoundError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4
    if args.rehearse:
        say("rehearsal on the CPU backend: no result line")
        return 0 if out["result"]["correct"] else 1
    print(json.dumps(out["result"]), flush=True)
    for name, c in out["result"]["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
