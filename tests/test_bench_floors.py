"""Bench regression floors (slow; excluded from tier-1's `-m 'not slow'`).

Runs the real `bench.py --mode matrix` as a subprocess on the CPU backend
and asserts per-lane `ratio_to_plain` floors, so the next spread-lane-style
cliff (PR 1's 0.17x regression lived in self-reported numbers for a full
round) fails CI instead of landing silently. Floors are deliberately below
the currently measured ratios (spread ~0.7x, affinity ~1.5x on CPU) —
they catch cliffs, not variance.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# lane -> (min ratio_to_plain, min absolute pods/s on the CPU backend).
# A lane fails only when it misses BOTH: the ratio catches a lane-local
# cliff, the absolute floor keeps the check robust to the plain lane's
# own scheduler-machine variance (plain has been observed swinging 13k..
# 29k pods/s run to run on loaded CI boxes, which would whipsaw a pure
# ratio). Historic cliffs both checks catch: spread at 0.11-0.17x /
# ~1.6k pods/s (PR 1's encode cliff and a round-7 recompile-in-loop
# bug), affinity at ~4.7k pods/s.
LANE_FLOORS = {
    "spread": (0.5, 3500.0),
    "affinity": (1.0, 5000.0),
    "anti_affinity": (0.15, 2000.0),
    "node_affinity": (0.5, 6000.0),
    # gang (PodGroup) lane: groups of 64 spec-identical members placed
    # all-or-nothing through the burst trial + commit path; the per-group
    # gather/commit overhead must stay a bounded tax on the plain lane
    # (measured ~0.5-0.8x plain on CPU at the 1000n/1000p cell)
    "gang": (0.25, 2000.0),
}


@pytest.mark.slow
def test_matrix_ratio_to_plain_floors():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # single CPU device: the bench's own shape
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "matrix",
         "--matrix-repeat", "2"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # ONE JSON line on stdout (bench contract); warnings go to stderr
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert "errors" not in out, out["errors"]
    plain = out.get("plain")
    assert plain and plain > 0, out
    ratios = out.get("ratio_to_plain") or {}
    for lane, (ratio_floor, abs_floor) in LANE_FLOORS.items():
        ratio = ratios.get(lane)
        absolute = out.get(lane)
        assert ratio is not None and absolute is not None, \
            f"lane {lane} missing from {out}"
        assert ratio >= ratio_floor or absolute >= abs_floor, \
            (f"{lane} cliffed: {ratio}x plain (floor {ratio_floor}x) AND "
             f"{absolute} pods/s (floor {abs_floor}) — matrix: {out}")
    # the preemption lane must have run and beaten the serial oracle, and
    # report the encode vs device-scan phase split (round 9)
    assert out.get("preempt_scans_per_s"), out
    assert out.get("preempt_vs_oracle") and out["preempt_vs_oracle"] > 1.0
    split = out.get("preempt_phase_split")
    assert split and split.get("encode") is not None \
        and split.get("scan") is not None, out


@pytest.mark.slow
def test_preempt_mode_floor():
    """`bench.py --mode preempt` (the victim-table lane's standalone
    entry): one JSON line, decisions already asserted identical to the
    oracle inside the bench, scans/s above a cliff-catching floor, and the
    warm-table + phase-split contract present. The floor is far below the
    measured ~4000 scans/s at this cell on CPU — it catches a return of
    the per-scan [N, P] re-encode (which ran this cell at ~300 scans/s),
    not variance."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "preempt",
         "--nodes", "300", "--pods", "3000", "--preemptors", "64"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["unit"] == "scans/s"
    assert out["preemptors_per_wave"] == 64
    assert out["warm_victim_table"] is True
    # device wave must beat the serial oracle referee outright
    assert out["vs_baseline"] > 1.0, out
    # cliff floor: per-scan re-encode regressions land ~10x under this
    assert out["value"] >= 1000.0, out
    # the phase split is reported and accounts for the device seconds
    assert out["encode_seconds"] >= 0.0 and out["scan_seconds"] > 0.0, out
    assert out["encode_seconds"] + out["scan_seconds"] \
        <= out["device_seconds"] * 1.05, out


@pytest.mark.slow
def test_commit_mode_floor():
    """`bench.py --mode commit` (the round-11 commit-core lane): one JSON
    line, the in-bench native-vs-twin referee passed (twin_parity — rv
    assignment, missing keys, and the watch stream bit-identical), and
    writes/s above the floors. The lane measures ~310-390k writes/s
    native (~210-270k twin) on this CPU unthrottled — comfortably past
    the >=100k round-11 acceptance target — but the box's cgroup CPU
    quota swings absolute numbers 3-4x run to run, so the check is
    two-part: (a) vs_serial — the wave path against the per-pod verb
    shape doing the same work per write, measured in the SAME run (the
    serial verbs share the core body by design, so the steady ratio is
    ~1.2x; a broken batching path would land visibly below 1) — and (b)
    a conservative absolute floor that survives a fully throttled run
    (observed throttled runs: 58k/95k; an interpreter-bound per-pod
    regression lands ~10x under the unthrottled numbers)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "commit"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["unit"] == "writes/s"
    assert out["twin_parity"] == "ok"
    assert out["events_delivered"] > 0 and out["events_per_s"] > 0
    assert out["vs_serial"] is not None and out["vs_serial"] >= 0.95, out
    floor = 30000.0 if out["impl"] == "native" else 20000.0
    assert out["value"] >= floor, out
    assert out["twin_writes_per_s"] >= 20000.0, out


@pytest.mark.slow
def test_commit_watcher_scaling_floor():
    """Round-20 watcher-scaling floor: `bench.py --mode commit --watchers
    10000` fans every commit out to 10k watchers in ONE subscription
    class. The gate is vs_per_watcher — shared-class copy-out rate over
    the degenerate (class-per-watcher) rate measured in the SAME run; the
    degenerate path materializes per watcher, so its rate IS the
    per-watcher-extrapolated cost. Shared classes materialize once per
    class, so the ratio scales ~linearly with watchers-per-class
    (measured ~800x at this cell on CPU); the >= 5x floor catches any
    return of per-watcher materialization (which lands at ~1x), not
    variance. Byte-ring accounting must show real shared traffic too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "commit",
         "--watchers", "10000"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["unit"] == "writes/s"
    assert out["twin_parity"] == "ok"
    assert out["watchers"] == 10000
    assert out["subscription_classes"] == 1
    # the scaling gate: shared copy-out vs the per-watcher-extrapolated
    # baseline from the degenerate cell run in the same invocation
    assert out["degenerate_events_per_s"] and out["degenerate_events_per_s"] > 0
    assert out["vs_per_watcher"] is not None, out
    assert out["vs_per_watcher"] >= 5.0, out
    # the byte ring served shared lines (serialize-once actually engaged)
    assert out["copyout_bytes_per_sec"] > 0, out
    assert out["copyout_shared_hits"] > out["copyout_materializations"], out


@pytest.mark.slow
def test_commit_mode_twin_floor():
    """Twin-only commit lane: the pure-Python core must hold its own
    absolute floor when pinned via KTPU_COMMITCORE=twin — the env var is
    set ONLY in the bench subprocess (exporting it into the test process
    would leak into other subprocess tests that assert the native core).
    Guards the twin's shared-class path staying a real implementation,
    not a stub that only passes parity at toy sizes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", KTPU_COMMITCORE="twin")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "commit"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["unit"] == "writes/s"
    assert out["impl"] == "twin"
    assert out["twin_parity"] == "ok"   # twin vs twin referee still runs
    assert out["events_delivered"] > 0 and out["events_per_s"] > 0
    assert out["value"] >= 20000.0, out


@pytest.mark.slow
def test_headline_ledger_fields_and_metrics_out(tmp_path):
    """Round-12: the headline JSON line gains the soak-scoreboard fields
    (startup_p50/startup_p99/phase_split from the pod-lifecycle ledger)
    and `--metrics-out` dumps the end-of-run registry snapshot beside it.
    Floors are shape checks, not variance tripwires: percentiles ordered
    and positive, every phase present, the device phases (fetch+commit)
    actually attributed, and the metrics artifact lints clean with the
    new families inside."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    metrics_path = tmp_path / "metrics.prom"
    proc = subprocess.run(
        [sys.executable, "bench.py", "--nodes", "300", "--pods", "2000",
         "--repeat", "1", "--no-matrix", "--no-mesh",
         "--metrics-out", str(metrics_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["pods_completed"] == 2000, out
    assert 0 < out["startup_p50"] <= out["startup_p99"], out
    split = out["phase_split"]
    assert set(split) == {"admission", "queue", "encode", "dispatch",
                          "fetch", "commit", "fanout"}, split
    # the burst path pays real time in fetch (the packed readback) and
    # commit (store write tail) — a zeroed phase means a dead stamp
    assert split["fetch"] > 0 and split["commit"] > 0, split
    # ledger stamping must not add device traffic (the 1/1 contract)
    assert out["device_fetches"] <= out["device_dispatches"], out
    # the metrics artifact: full exposition, lint-clean, ledger inside
    from kubernetes_tpu.obs.lint import lint_exposition
    text = metrics_path.read_text()
    assert lint_exposition(text) == []
    assert "pod_e2e_duration_seconds_bucket" in text
    assert "pod_startup_seconds_p99" in text
    assert out["metrics_out"] == str(metrics_path)


@pytest.mark.slow
def test_gang_mode_floor():
    """`bench.py --mode gang` (the gang lane's standalone entry): one JSON
    line, the atomicity audit passed (all_or_nothing — the bench itself
    asserts no partially bound group), and throughput above a
    cliff-catching floor at a small cell."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "gang",
         "--nodes", "500", "--pods", "1500"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["all_or_nothing"] is True
    assert set(out["gangs"]) == {"8", "64", "512"}
    assert out["pods_bound"] > 0
    # cliff floor, not a variance tripwire (plain runs 10k+ pods/s here)
    assert out["value"] >= 1000.0, out


@pytest.mark.slow
def test_gang_profiles_floor():
    """`bench.py --mode gang --profiles` (round 19): the rank-aware
    scheduling-profile lane must beat the placement-blind baseline on
    gang locality (fraction of gangs landing single-zone) without giving
    up throughput — locality >= blind AND throughput >= 0.9x blind. Both
    lanes ride the weight-tensor machinery on identical workloads, so
    the ratio isolates the gang set-scoring objective's cost. Gangs of
    6/12 on a 3-zone 48-node cell: small enough for single-zone packing
    to be achievable, so the locality gap is decisive (blind scatters
    round-robin, rank-aware packs)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "gang", "--profiles",
         "--nodes", "48", "--pods", "480", "--gang-sizes", "6,12",
         "--no-matrix", "--no-mesh"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["all_or_nothing"] is True and out["profiles"] is True
    loc = out["gang_locality"]
    thr = out["throughput"]
    # the rank-aware objective must actually buy locality on this cell
    # (blind scatters: its single-zone fraction sits near zero)
    assert loc["rank_aware"] >= loc["blind"], out
    assert loc["rank_aware"] >= 0.8, out
    # ... without giving up throughput vs the placement-blind baseline
    assert thr["rank_aware"] >= 0.9 * thr["blind"], out


@pytest.mark.slow
def test_chaos_mode_floor():
    """`bench.py --mode chaos` (the round-13 fault-plane lane): one JSON
    line with per-seam injection counts, the in-bench correctness audit
    passed (every measured pod bound exactly once under injection), and
    DEGRADED throughput still above the measured serial-oracle baseline —
    the graceful-degradation contract: a fault costs throughput, never
    correctness, and the mixed run must still beat a scheduler that never
    used the device at all."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "chaos",
         "--nodes", "300", "--pods", "5000"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"].endswith("_chaos")
    ch = out["chaos"]
    # the plan actually fired: the run is a chaos run, not a happy path
    # (seed 42 at the default rates/cell injects across >= 5 seams)
    assert ch["injections_total"] >= 5, ch
    assert len(ch["injections"]) >= 3, ch
    # the scoreboard fields the soak PR inherits
    assert ch["seed"] == 42 and ch["breaker"] is not None, ch
    assert out["pods_completed"] == 5000, out
    # degraded mode must still beat the serial-oracle floor
    assert out["vs_measured_oracle"] is not None
    assert out["vs_measured_oracle"] > 1.0, out


@pytest.mark.slow
def test_churn_mode_floor():
    """`bench.py --mode churn` (the round-14 node-churn lane): steady
    bursts while nodes die mid-burst (node.dead seam -> launch refusal)
    and return, NotReady nodes feed the zone-paced NoExecute eviction
    queue, and PodGC + the workload controller recycle what churn
    destroys. The lane must actually churn (kills, stale refusals, paced
    evictions all nonzero), converge (every surviving pod bound), and
    hold a cliff-floor throughput (the default cell runs ~800+ pods/s
    degraded on CPU; 100 is the collapse tripwire, not a variance one)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "churn"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"].startswith("churn_throughput_"), out
    # the schedule actually churned, mid-burst
    assert out["nodes_killed"] >= 3, out
    assert out["nodes_restored"] == out["nodes_killed"], out
    assert out["stale_launch_refusals"] >= 1, out
    # evictions flowed through the PDB-guarded verb, paced per zone
    assert sum(out["evictions_by_reason"].values()) >= 1, out
    assert out["evictions_per_zone"], out
    # ...and everything the churn destroyed was recycled and re-landed
    assert out["pods_recreated"] >= 1, out
    assert out["audit_all_bound"] is True, out
    assert out["value"] >= 100.0, out


#: PROFILE round 16's recorded host prologue at the 1000n/2000rps cell:
#: encode ~853 + admission ~543 pod-seconds over ~60k scheduled pods
ROUND16_PROLOGUE_PER_POD = (853.0 + 543.0) / 60_000


def _run_serve(extra, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "serve", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_serve_mode_floor():
    """`bench.py --mode serve` (the round-16 arrival-driven lane) at the
    acceptance cell — 1000 nodes, 2000 arrivals/s sustained for 30 s:
    one JSON line whose own audits passed (every arrival admitted-and-
    bound or 429'd-and-accounted; zero flight-recorder replay parity
    violations), sustained pods/s within 10% of the arrival rate (the
    lane is bounded above by arrivals — a serving scheduler that keeps
    up scores ~rate; 0.9x is the fell-behind tripwire), and the
    ledger-derived startup_p99 under the density.go 5 s SLO. The
    multi-chip fields ride every mode's JSON, serve included."""
    out = _run_serve(["--nodes", "1000", "--arrival-rate", "2000",
                      "--duration", "30"])
    assert out["unit"] == "pods/s"
    assert out["audit_all_admitted_or_429"] is True
    assert out["parity_violations"] == 0, out
    assert out["value"] >= 0.9 * 2000, out
    assert 0 < out["startup_p50"] <= out["startup_p99"], out
    assert out["startup_p99"] <= 5.0, out
    assert out["startup_slo_5s"] is True, out
    # shed accounting is present (zero is fine when the device keeps up)
    assert out["admission_rejected"] == out["arrivals"]["rejected_429"] \
        or out["admission_rejected"] >= out["arrivals"]["rejected_429"]
    assert out["pods_completed"] > 0
    # admission phase actually stamped (the gate opened the records)
    assert out["phase_split"]["admission"] > 0, out["phase_split"]
    # the round-15 device-report fields ride the serve lane too
    assert out["devices"] == 1 and "per_device_node_rows" in out
    assert out["launch_depth"] >= 3
    # round-17 host-prologue guard at 30 s: the short cell is dominated
    # by the reaper-onset transient (one interval books 3-6x the steady
    # state), so the tight 0.6x floor lives on the 90 s soak below; here
    # we only trip on a gross regression past the round-16 baseline
    pro = out["prologue_phase_split"]
    assert pro["encode_pod_seconds"] > 0
    assert pro["admission_pod_seconds"] > 0
    assert pro["per_scheduled_pod"] <= ROUND16_PROLOGUE_PER_POD, pro


@pytest.mark.slow
def test_serve_raised_rate_cell():
    """The round-17 raised sustained-rate cell: 4000 arrivals/s on CPU —
    double the round-16 acceptance rate. Pre-round-17 this rate
    collapsed the loop to ~2100 pods/s with p99 past 9 s: the gate's
    50 ms Retry-After floor let shed clients re-create six-figure times
    per second THROUGH THE PER-POD PATH, and the retry storm itself ate
    the capacity. With batched retries, the calmer suggestion floor,
    and the gathered prologue, the box sustains ~3990 pods/s at p99
    ~0.2 s (watermark sized to ~1 s of rate per the PROFILE watermark
    arithmetic; sheds allowed — backpressure IS the contract)."""
    out = _run_serve(["--nodes", "1000", "--arrival-rate", "4000",
                      "--duration", "30", "--max-queue-depth", "4096"])
    assert out["audit_all_admitted_or_429"] is True
    assert out["parity_violations"] == 0, out
    assert out["startup_p99"] <= 5.0, out
    assert out["value"] >= 0.8 * 4000, out


@pytest.mark.slow
def test_serve_mode_soak():
    """The long soak variant: minutes-scale sustained serving (90 s at
    the acceptance cell) — the SLO and both audits must hold over a
    window long enough for backlog drift to surface (a loop that slowly
    falls behind passes a 30 s cell and fails here as p99 climbs)."""
    out = _run_serve(["--nodes", "1000", "--arrival-rate", "2000",
                      "--duration", "90"], timeout=1500)
    assert out["value"] >= 0.9 * 2000, out
    assert out["startup_p99"] <= 5.0, out
    assert out["audit_all_admitted_or_429"] is True
    assert out["parity_violations"] == 0, out
    # round-17 host-prologue floor (the issue's acceptance cell): encode
    # + admission pod-seconds per scheduled pod <= 0.6x the round-16
    # recorded baseline — the encode-at-admission row cache, stable
    # device axis, batched arrival ingest, and in-core event records.
    # (Measured 0.54x on the reference CPU box; the reaper-onset
    # transient amortizes over 90 s, which is why the floor lives here.)
    pro = out["prologue_phase_split"]
    assert pro["per_scheduled_pod"] <= 0.6 * ROUND16_PROLOGUE_PER_POD, pro


@pytest.mark.slow
def test_fleet_mode_floor():
    """`bench.py --mode fleet` (the round-18 active-active lane) at the
    acceptance cell — 2 instances, 1000 nodes, 2000 arrivals/s for 20 s
    against ONE shared store, with the solo serve baseline measured in
    the same run. The gates: the zero-double-bind audit (the tripwire
    counter the whole fleet design exists to pin at zero), every arrival
    admitted-and-bound or 429'd-and-accounted, live claim sets disjoint,
    and aggregate pods/s >= 0.95x the solo baseline (both runs are
    arrival-bound when the box keeps up, so the ratio sits at ~1.0 on
    CPU — whether N instances pass one process's rate on a chip's host
    is not measured (ROADMAP S9); 0.95 absorbs run variance
    without letting a real regression through)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "fleet", "--instances", "2",
         "--nodes", "1000", "--arrival-rate", "2000", "--duration", "20"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["unit"] == "pods/s"
    assert out["instances"] == 2
    # the three robustness audits gate the number
    assert out["double_binds"] == 0, out
    assert out["audit_no_double_bind"] is True
    assert out["audit_all_admitted_or_429"] is True
    assert out["partition_disjoint"] is True
    # aggregate throughput floor vs the same-run solo baseline
    assert out["vs_solo_serve"] is not None
    assert out["vs_solo_serve"] >= 0.95, out
    assert out["value"] >= 0.9 * 2000, out
    assert out["startup_p99"] <= 5.0, out
    # every instance did real work (the partition actually spread)
    shares = list(out["per_instance_pods_bound"].values())
    assert len(shares) == 2 and all(s > 0 for s in shares), out


# the pre-batched-churn-plane soak smoke number, recorded on the
# reference CPU box immediately before the round-23 PR landed (the
# 2000n / 2 inst / 600 rps / 60 s / 5k-watcher cell; arrival-bound, so
# the headline sits just above the drained arrival rate rather than at
# machine capacity). The floor is 0.9x: batching the churn verbs must
# never COST sustained throughput — the win shows up in verb-count and
# lock-hold arithmetic (PROFILE.md round 23), not this arrival-bound
# headline.
ROUND22_SOAK_SMOKE_PODS_PER_S = 157.2


@pytest.mark.slow
def test_soak_mode_floor():
    """`bench.py --mode soak` at the smoke cell (round 23): the churn
    plane rides BATCHED verbs end to end — the cell must finish with
    zero double-binds, zero parity violations, every detector evaluated
    pass-or-named, sustained pods/s >= 0.9x the recorded pre-PR smoke
    number, the batch-mutation counters proving the churn actors and
    the zone evictor really flushed one verb per batch, and the
    packing_utilization lane (cluster_resource_utilization's cpu child)
    sampled."""
    from kubernetes_tpu.obs.timeseries import DETECTORS
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "bench.py", "--mode", "soak",
         "--nodes", "2000", "--instances", "2",
         "--arrival-rate", "600", "--duration", "60",
         "--watchers", "5000", "--watch-classes", "64"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["unit"] == "pods/s"
    # audits gate the number
    assert out["double_binds"] == 0, out
    assert out["parity_violations"] == 0, out
    assert out["partition_disjoint"] is True
    assert out["audit_no_double_bind"] is True
    assert out["audit_all_admitted_or_accounted"] is True
    # every detector answered — by name, pass or fail, never skipped
    assert out["verdicts_evaluated"] == len(DETECTORS)
    names = {v.split(":", 1)[0] for v in out["verdicts"]}
    assert names == set(DETECTORS)
    # throughput floor vs the recorded pre-PR smoke number
    assert out["value"] >= 0.9 * ROUND22_SOAK_SMOKE_PODS_PER_S, out
    # the churn plane really rode the batched verbs: restamps + drain
    # flips on update_many, rolls + the reaper on delete_many, and the
    # drained zone's pods through the batched PDB-charging eviction
    bm = out["batch_mutations"]
    assert bm["update_many"]["calls"] > 0, bm
    assert bm["delete_many"]["calls"] > 0, bm
    assert bm["evict_many"]["calls"] > 0, bm
    assert bm["update_many"]["objects"] >= bm["update_many"]["calls"], bm
    # the packing lane was sampled from the live fill gauge
    packing = out["packing_utilization"]
    assert packing["samples"] > 0, packing
    assert packing["max"] is not None and packing["max"] > 0.0, packing


@pytest.mark.slow
def test_sharded_lane_floor():
    """Round-15 sharded lane: `bench.py --devices` must (a) report the
    multi-chip fields — devices > 1, per_device_node_rows, a non-zero
    ici_allgather_bytes — with the single-fetch-per-burst contract intact,
    and (b) NOT regress the one-chip case: the sharded program on a
    1-device mesh stays >= 0.9x the unsharded program at small N (the
    VERDICT r03 guard — mesh mode once silently cost 8x). The 8-way ratio
    itself is not floored here: 8 virtual XLA CPU devices timeshare one
    host, so its collective overhead measures the harness, not the
    sharding (the real multi-chip ratio needs a four-chip host; not
    measured).
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")

    def run(extra):
        proc = subprocess.run(
            [sys.executable, "bench.py", "--nodes", "500", "--pods", "800",
             "--burst", "800", "--repeat", "3", "--no-matrix", "--no-mesh",
             *extra],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=1800)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    plain = run([])
    assert plain["devices"] == 1
    assert plain["ici_allgather_bytes"] == 0

    one = run(["--devices", "1"])
    assert one["devices"] == 1
    ratio = one["value"] / plain["value"]
    assert ratio >= 0.9, (
        f"sharding regressed the one-chip case: 1-device mesh "
        f"{one['value']} vs plain {plain['value']} ({ratio:.2f}x)")

    eight = run(["--devices", "8"])
    assert eight["devices"] == 8
    assert eight["per_device_node_rows"] == 512 // 8
    # ONE fetch for the single 800-pod burst of the timed loop — the
    # single-dispatch/single-fetch contract survives sharding
    assert eight["device_fetches"] == 1, eight
    assert eight["ici_allgather_bytes"] > 0, eight
    assert eight["pods_completed"] == 800
