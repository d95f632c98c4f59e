"""Tier-1 guard for the yardstick: `benchmark/run.py`'s `execute`, the
function every cell of `BENCHMARK.json` is measured by, rehearsed on the CPU
backend at a tiny size, once per kind of run. A program change that breaks it
is found here and not on the chip. `correct` is the benchmark's own verdict
(the client's watch replayed through `benchmark/reference/`); nothing here is
a speed. `benchmark/tests/` holds the benchmark's own, fuller tests (by hand).
"""
import importlib
import json
import os
import sys

import pytest

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

BACKLOG = "headline-15000n.backlog-10k"
ROLLOUT = "density-5000n-150k.rollout-1k"
ARRIVALS = "headline-15000n.arrivals-steady"
ADAPTIVE = "headline-15000n-adaptive.backlog-10k"
DENSITY_ADAPTIVE = "density-5000n-150k-adaptive.rollout-1k"
MIXED = "inuse-15000n-135k.backlog-10k-mixed"
LOAD = "load-5000n-150k.rollouts-1k-8svc"
SERVICES = "services-5000n-150k.arrivals-zipf-64svc"
COLOCATED = "colocated-5000n-150k.rollouts-1k-8svc-jobs"
LOADMIX = "loadmix-5000n-150k.rollouts-1k-111svc"
PODCAP = "podcap-5000n-150k.backlog-9900-fill"
# the scan cells whose 15,000 nodes reach kernels.SCORE_BOARD_MIN_ROWS
BOARD = (ADAPTIVE, MIXED)

# (config overlay, traffic overlay): the sizes benchmark/tests rehearses at.
# The adaptive cell needs more than 100 nodes for the walk to be cut short
# (num_to_find = 117 of 240), and so does the density cell at the default
# percentage, on zones of 84/83/83 so that the NodeTree's order rotates
# (num_to_find = 120 of 250). The mixed cell keeps its even zones (240 = 3 x
# 80: the scan walks the device axis, as at 15,000) and a backlog whose dirty
# rows stay in one scatter bucket (at most 120, never 64 or fewer). The load
# cell takes the density cell's 250 nodes (a truncated walk on a rotating
# order, as at 5000) and ten Services, eight of which its mix names. The
# services cell takes the same 250 nodes and 80 Services, 64 of which its mix
# names, at a rate whose windows hold one pod to a few (the warm-up's first
# pass holds 232 pods: more than sixteen Services, so the shell cuts it).
# The colocated cell takes the load cell's sizes: its mix names the same
# eight Services, and Jobs' pods that nothing selects between their replicas.
# The loadmix cell takes the same 250 nodes and 120 Services, 111 of which its
# mix names, and passes of 250 pods: about 80 Services a pass, more than the
# power-of-two carry's 16 rows, so every pass is one launch on the wide carry.
# The podcap cell takes the same 250 nodes with 6 resident pods on nodes of 8
# slots, and passes of 495 pods for the 500 free slots: a pass's last walks
# test all 250 nodes and keep fewer than the quota's 120.
AT_50 = {"nodes": {"count": 50},
         "check": {"first_binds": 200, "sampled_binds": 60}}
SMALL = {
    BACKLOG: (AT_50, {"warm_binds": 0, "backlog": 70}),
    ROLLOUT: ({**AT_50, "resident": {"pods_per_node": 6, "services": 5}},
              {"warm_binds": 0, "backlog": 70}),
    ARRIVALS: (AT_50, {"warm_binds": 0, "arrival": {"rate_per_s": 150.0},
                       "lifetime_s": 0.4, "serve": {"window_size": 64}}),
    ADAPTIVE: ({"nodes": {"count": 240},
                "check": {"first_binds": 200, "sampled_binds": 200}},
               {"warm_binds": 0, "backlog": 150}),
    DENSITY_ADAPTIVE: ({"nodes": {"count": 250},
                        "resident": {"pods_per_node": 6, "services": 5},
                        "check": {"first_binds": 200, "sampled_binds": 100}},
                       {"warm_binds": 0, "backlog": 150}),
    MIXED: ({"nodes": {"count": 240},
             "resident": {"pods_per_node": 3, "services": 5},
             "check": {"first_binds": 200, "sampled_binds": 100}},
            {"warm_binds": 0, "backlog": 120}),
    LOAD: ({"nodes": {"count": 250},
            "resident": {"pods_per_node": 6, "services": 10},
            "check": {"first_binds": 200, "sampled_binds": 100}},
           {"warm_binds": 0, "backlog": 150}),
    SERVICES: ({"nodes": {"count": 250},
                "resident": {"pods_per_node": 6, "services": 80},
                "check": {"first_binds": 300, "sampled_binds": 200}},
               {"arrival": {"rate_per_s": 200.0}, "lifetime_s": 0.4,
                "serve": {"window_size": 64}}),
}
SMALL[COLOCATED] = SMALL[LOAD]
SMALL[LOADMIX] = ({"nodes": {"count": 250},
                   "resident": {"pods_per_node": 6, "services": 120},
                   "check": {"first_binds": 300, "sampled_binds": 200}},
                  {"warm_binds": 0, "backlog": 250})
SMALL[PODCAP] = ({"nodes": {"count": 250, "allocatable": {"pods": 8}},
                  "resident": {"pods_per_node": 6, "services": 5},
                  "check": {"first_binds": 600, "sampled_binds": 300}},
                 {"backlog": 495})
# the control of an adaptive cell: the program scores every node while the
# reference judges at the file's default percentage
EVERY_NODE = {"scheduler": {"percentage_of_nodes_to_score": 100}}


@pytest.fixture(scope="module")
def execute():
    """`benchmark/run.py`'s `execute`, imported the way the command finds its
    own modules (`benchmark/` on the path); the path is put back after."""
    sys.path.insert(0, BENCH_DIR)
    try:
        import run
        yield run.execute
    finally:
        sys.path.remove(BENCH_DIR)


def rehearse(execute, cell, seed, hook=None, program=None):
    config, traffic = SMALL[cell]
    return execute(cell, seed, 1.5, False, rehearse=True, hook=hook,
                   overrides={"config": config, "traffic": traffic,
                              "program": program})


def altering_a_binding_of(cycle):
    """A hook that breaks the timed path where an answer is produced: the
    first pod of a commit wave of window cycle `cycle` is committed to the
    node of the wave's second pod (the first such wave that holds two pods
    on unlike nodes)."""
    def hook(sched, store):
        commit_wave = store.commit_wave
        done = False

        def altered(bindings, *a, **kw):
            nonlocal done
            if not done and len(bindings) > 1 and cycle in bindings[0][0] \
                    and bindings[0][1] != bindings[1][1]:
                bindings = [(bindings[0][0], bindings[1][1]), *bindings[1:]]
                done = True
            return commit_wave(bindings, *a, **kw)
        store.commit_wave = altered
    return hook


altered_binding = altering_a_binding_of("/bl-1-")
# the load cell's window may hold one cycle only
altered_load_binding = altering_a_binding_of("/bl-0-")


def counter_metric(name, res, rep, pods=None, moved=None):
    """A `program_counter` metric of `benchmark/metrics/`, read by its own
    reader from the run's moved counters: the window's, as the report has
    them, or `moved` over `pods` pods where the caller took its own."""
    from lib import spec
    mf = spec.load_metric(name)
    reader = importlib.import_module(f"readers.{mf['reader']}")
    if moved is None:
        pods = res["attempted"]
        moved = {fam: {tuple(lab.split("/")): v for lab, v in ch.items()}
                 for fam, ch in rep["counters"].items()}
    return reader.read({"pods_bound": pods, "counters": moved}, **mf["args"])


@pytest.mark.parametrize("cell,seed,hook,program", [
    (BACKLOG, 1, None, None),               # K-batch kernel
    (ROLLOUT, 2**31 + 5, None, None),       # generic scan + spread
    (ARRIVALS, 3, None, None),              # serve loop
    (ADAPTIVE, 2**31 + 17, None, None),     # truncated walk
    (BACKLOG, 11, altered_binding, None),   # the guard can fail
    # truncated walk on shipped positions + carried spread counts
    (DENSITY_ADAPTIVE, 2**31 + 23, None, None),
    (DENSITY_ADAPTIVE, 2**31 + 23, None, EVERY_NODE),   # its control
    # eight pod sizes onto nodes that hold pods: stacked rows, uneven board
    (MIXED, 2**31 + 41, None, None),
    # eight Services' pods interleaved: a burst segment a change of Service
    (LOAD, 2**31 + 77, None, None),
    (LOAD, 2**31 + 77, altered_load_binding, None),
    (LOAD, 2**31 + 77, None, EVERY_NODE),               # its control
    # 64 Services' replicas through the serve loop: windows on the scan
    (SERVICES, 2**31 + 91, None, None),
    (SERVICES, 2**31 + 91, None, EVERY_NODE),           # its control
    # eight Services' replicas and Jobs' pods interleaved: a segment a run
    (COLOCATED, 2**31 + 83, None, None),
    (COLOCATED, 2**31 + 83, None, EVERY_NODE),          # its control
    # 111 Services' replicas interleaved: a segment every 16 Services
    (LOADMIX, 2**31 + 50, None, None),
    (LOADMIX, 2**31 + 50, None, EVERY_NODE),            # its control
    # label-free pods of three sizes for the last pod slots: full nodes
    (PODCAP, 2**31 + 54, None, None),
    (PODCAP, 2**31 + 54, None, EVERY_NODE),             # its control
], ids=["backlog", "rollout", "arrivals", "adaptive", "altered-binding",
        "density-adaptive", "density-adaptive-control", "mixed",
        "load", "load-altered-binding", "load-control",
        "services", "services-control",
        "colocated", "colocated-control",
        "loadmix", "loadmix-control",
        "podcap", "podcap-control"])
def test_rehearsed_cell(monkeypatch, execute, cell, seed, hook, program):
    if cell in BOARD:
        # these cells hold 16,384 node rows, enough for the scan to carry
        # its score board; the rehearsal's 240 nodes, which `mesh="auto"`
        # may spread over the CPU's devices, stand in for them
        from kubernetes_tpu.ops import kernels
        monkeypatch.setattr(kernels, "SCORE_BOARD_MIN_ROWS", 1)
    broken = hook is not None or program is not None
    before = {}
    if cell in (LOAD, SERVICES, COLOCATED, LOADMIX) and not broken:
        # the shell's counters are not in the report: take the whole run's
        from lib import counters
        hook = lambda sched, store: before.update(counters.snapshot())
    out = rehearse(execute, cell, seed, hook, program)
    res, rep = out["result"], out["report"]
    assert rep["compared"] > 0
    if broken:
        assert res["correct"] is False
        return
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert rep["compiles_in_window"] == 0
    if cell in (ROLLOUT, ADAPTIVE, DENSITY_ADAPTIVE, MIXED, LOAD, SERVICES,
                COLOCATED, LOADMIX, PODCAP):
        # the generic scan's cells: at 16,384 rows every launch carries the
        # score board (one pod class, or up to eight in the mixed cell), at
        # the density cells' 8192 every step rescores every row
        how = "carried" if cell in BOARD else "full"
        assert rep["counters"]["tpu_scan_score_steps_total"] == \
            {how: res["attempted"]}
        assert counter_metric("score_carried_steps_per_pod.backlog",
                              res, rep) == (1.0 if cell in BOARD else 0.0)
    else:
        assert "tpu_scan_score_steps_total" not in rep["counters"]
    if cell == ADAPTIVE:
        moved = rep["counters"]
        # the truncated regime on the generic scan, never the K-batch kernel
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        # the pod count is the scan's trip count: one step per pod given
        assert moved["tpu_scan_steps_total"]["real"] == res["attempted"]
        # even zones: no order is shipped
        assert moved["tpu_scan_order_steps_total"] == \
            {"axis": res["attempted"]}
        # one pod copied: its row is one object, broadcast; every node fits
        assert moved["tpu_scan_pod_rows_total"] == \
            {"shared": res["attempted"]}
        assert "tpu_filter_rejected_nodes_total" not in moved
        assert moved["tpu_pick_tied_nodes_total"][""] >= res["attempted"]
    if cell == DENSITY_ADAPTIVE:
        moved = rep["counters"]
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        # every step of every launch walks shipped positions, as cell 2's do
        assert moved["tpu_scan_order_steps_total"] == \
            {"position": res["attempted"]}
        assert counter_metric("rotation_position_steps_per_pod.backlog",
                              res, rep) == 1.0
        assert counter_metric("rotation_gather_steps_per_pod.backlog",
                              res, rep) == 0.0
        assert moved["tpu_scan_steps_total"]["real"] == res["attempted"]
        # a walk stops at its quota: 120 of 250 nodes, none of them full
        assert moved["tpu_walk_nodes_evaluated_total"] == \
            {"truncated": 120 * res["attempted"]}
        # ... so every walk ended there, none on the last node
        assert moved["tpu_walk_ended_total"] == {"quota": res["attempted"]}
        assert counter_metric("walk_exhausted_share.backlog", res, rep) == 0.0
        assert counter_metric("walk_unschedulable_per_pod.backlog",
                              res, rep) == 0.0
    if cell == PODCAP:
        moved = rep["counters"]
        pods = res["attempted"]
        backlog = SMALL[PODCAP][1]["backlog"]
        # a pass is one segment of plain pods of three sizes and one launch
        # of the scan on shipped positions, no spread carry, rows stacked
        assert "tpu_oracle_fallback_total" not in moved
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        assert moved["tpu_device_dispatch_total"]["burst_scan"] \
            == pods / backlog
        assert moved["tpu_scan_order_steps_total"] == {"position": pods}
        assert moved["tpu_scan_spread_steps_total"] == {"none": pods}
        assert moved["tpu_scan_pod_rows_total"] == {"stacked": pods}
        # walks pass their quota's 120 positions over full nodes, the
        # pod-count filter says no, and a pass's last walks come up short:
        # with 500 - k slots left fewer than 120 nodes fit from pod 382 on
        # whatever the seed, and from pod 263 on at the earliest
        tested = counter_metric("walk_nodes_per_pod.backlog", res, rep)
        rejected = counter_metric("filter_rejected_nodes_per_pod.backlog",
                                  res, rep)
        assert 120 < tested < 250 and 0 < rejected < tested - 1
        ended = moved["tpu_walk_ended_total"]
        assert set(ended) == {"quota", "nodes"}
        assert sum(ended.values()) == pods
        short = counter_metric("walk_exhausted_share.backlog", res, rep)
        assert short == 100.0 * ended["nodes"] / pods
        assert 100.0 * 114 / backlog <= short <= 100.0 * 233 / backlog
        # every pod of every pass found a node
        assert counter_metric("walk_unschedulable_per_pod.backlog",
                              res, rep) == 0.0
        # the band selectHost chooses from is a few nodes at a pass's end
        assert moved["tpu_pick_tied_nodes_total"][""] / pods < 120
    if cell == LOAD:
        moved = rep["counters"]
        # no window is refused: a pass of eight Services' pods is one
        # segment and one launch of the scan, a count row a Service
        assert "tpu_oracle_fallback_total" not in moved
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        assert moved["tpu_scan_order_steps_total"] == \
            {"position": res["attempted"]}
        assert moved["tpu_walk_nodes_evaluated_total"] == \
            {"truncated": 120 * res["attempted"]}
        backlog = SMALL[LOAD][1]["backlog"]
        launches = moved["tpu_device_dispatch_total"]["burst_scan"]
        assert launches == res["attempted"] / backlog
        assert moved["tpu_scan_spread_steps_total"] == \
            {"grouped": res["attempted"]}
        assert counter_metric("spread_grouped_steps_per_pod.backlog",
                              res, rep) == 1.0
        # ... and a Service selects every pod
        assert counter_metric("spread_unselected_steps_per_pod.backlog",
                              res, rep) == 0.0
        assert moved["tpu_scan_pod_rows_total"] == \
            {"stacked": res["attempted"]}
        # a row built a signature (eight) and the pad's: 150 pods in 256
        assert moved["tpu_scan_stack_rows_total"] == \
            {"built": 9 * launches, "taken": 256 * launches}
        assert counter_metric("stack_rows_built_per_pod.backlog",
                              res, rep) == 9 / backlog
        # one spread count pass a Service and launch (a truncated walk
        # never tries the K-batch class first)
        assert moved["tpu_spread_count_encodes_total"] == {"": 8 * launches}
        encodes = counter_metric("spread_encodes_per_pod.backlog", res, rep)
        assert encodes == 8 / backlog
        # one pod-table call a launch; the pass before's pods were bound
        # and deleted again, so generations moved and every row is where it
        # was: the cached columns are shared, nothing is gathered
        assert moved["tpu_pod_table_calls_total"] == {"shared": launches}
        assert counter_metric("pod_table_gathered_call_share.backlog",
                              res, rep) == 0.0
        # ... and every node whose generation moved kept its row range by
        # its join stamps (PR 53): none is put together again
        assert set(moved["tpu_pod_table_moved_nodes_total"]) == {"kept"}
        assert counter_metric("pod_table_kept_node_share.backlog",
                              res, rep) == 100.0
        # the shell's side, over warm-up (two cycles) and window: a pass
        # ends where it is out of pods and nowhere else
        whole = counters.delta(counters.snapshot(), before)
        pods = 2 * backlog + res["attempted"]
        cuts = whole["scheduler_burst_segment_cuts_total"]
        assert cuts == {("end",): pods / backlog}
        assert counter_metric("segment_class_cuts_per_pod.backlog",
                              res, rep, pods, whole) == 0.0
    if cell == COLOCATED:
        moved = rep["counters"]
        # a launch that holds a pod without spread counts beside pods with
        # is carried, not refused
        assert "tpu_oracle_fallback_total" not in moved
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        assert moved["tpu_walk_nodes_evaluated_total"] == \
            {"truncated": 120 * res["attempted"]}
        # a pass is one launch and every step of it a grouped one: a row a
        # Service, and the Jobs' pods, three in ten, ride them with none
        backlog = SMALL[COLOCATED][1]["backlog"]
        launches = moved["tpu_device_dispatch_total"]["burst_scan"]
        assert launches == res["attempted"] / backlog
        assert counter_metric("pods_per_dispatch.backlog", res, rep) == backlog
        assert moved["tpu_scan_spread_steps_total"] == \
            {"grouped": res["attempted"]}
        assert counter_metric("spread_grouped_steps_per_pod.backlog",
                              res, rep) == 1.0
        assert moved["tpu_scan_spread_groups_total"] == {"": 8 * launches}
        unselected = counter_metric(
            "spread_unselected_steps_per_pod.backlog", res, rep)
        assert unselected == moved[
            "tpu_scan_spread_unselected_steps_total"][""] / res["attempted"]
        assert 0.15 < unselected < 0.45
        assert moved["tpu_spread_count_encodes_total"] == {"": 8 * launches}
        # the shell's side, over warm-up (two cycles) and window: no gang
        # in a pass, so the planner hands it over whole, and it ends where
        # it is out of pods and nowhere else
        whole = counters.delta(counters.snapshot(), before)
        pods = 2 * backlog + res["attempted"]
        cuts = whole["scheduler_burst_segment_cuts_total"]
        assert cuts == {("end",): pods / backlog}
        assert whole["tpu_device_dispatch_total"][("burst_scan",)] \
            == pods / backlog
        for name in ("segment_plan_cuts_per_pod.backlog",
                     "segment_class_cuts_per_pod.backlog"):
            assert counter_metric(name, res, rep, pods, whole) == 0.0
        assert counter_metric("segment_end_cuts_per_pod.backlog",
                              res, rep, pods, whole) == 1 / backlog
        # every create reached the pod-row cache once, in a run, and left
        # its interned signature there and no derived column
        assert whole["pod_row_cache_encodes_total"] == \
            {("signature",): pods}
        assert counter_metric("pod_rows_signature_only_per_pod.backlog",
                              res, rep, pods, whole) == 1.0
    if cell == LOADMIX:
        from kubernetes_tpu.ops.kernels import SPREAD_GROUP_WIDE
        moved = rep["counters"]
        pods = res["attempted"]
        # every pass goes to the scan whole, with a count row a Service it
        # holds, none is refused, and no pod goes uncounted
        assert "tpu_oracle_fallback_total" not in moved
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        assert moved["tpu_walk_nodes_evaluated_total"] == \
            {"truncated": 120 * pods}
        assert moved["tpu_scan_spread_steps_total"] == {"grouped": pods}
        launches = moved["tpu_device_dispatch_total"]["burst_scan"]
        backlog = SMALL[LOADMIX][1]["backlog"]
        assert launches == pods / backlog
        assert counter_metric("pods_per_dispatch.backlog", res, rep) \
            == backlog
        # one count pass and one lookup in the selector index a Service a
        # pass, the lookup testing the one Service under the pod's label
        groups = moved["tpu_scan_spread_groups_total"][""]
        assert groups == moved["tpu_spread_count_encodes_total"][""]
        for name in ("spread_groups_per_pod.backlog",
                     "spread_encodes_per_pod.backlog",
                     "selector_services_tested_per_pod.backlog"):
            assert counter_metric(name, res, rep) == groups / pods
        assert 16 * launches < groups <= 111 * launches
        # a pass of more Services than the narrow carry's 16 rows is one launch
        # on the wide carry, whatever it holds: one scan program (no compile
        # in the window, above: the warm-up's passes met it); the metric
        # that read the 16-row label has nothing left to count
        assert moved["tpu_scan_spread_carry_launches_total"] == \
            {str(SPREAD_GROUP_WIDE): launches}
        assert counter_metric("spread_carry_wide_launch_share.backlog",
                              res, rep) == 100.0
        assert counter_metric("spread_carry_full_launch_share.backlog",
                              res, rep) == 0.0
        # the shell's side, over warm-up (two cycles) and window: a pass is
        # never cut and ends where it is out of pods
        whole = counters.delta(counters.snapshot(), before)
        pods = 2 * backlog + res["attempted"]
        cuts = whole["scheduler_burst_segment_cuts_total"]
        assert cuts == {("end",): pods / backlog}
        assert whole["tpu_device_dispatch_total"][("burst_scan",)] \
            == pods / backlog
        for name in ("segment_group_cuts_per_pod.backlog",
                     "segment_plan_cuts_per_pod.backlog",
                     "segment_class_cuts_per_pod.backlog"):
            assert counter_metric(name, res, rep, pods, whole) == 0.0
    if cell == SERVICES:
        moved = rep["counters"]
        pods = res["attempted"]
        # every serve window goes to the scan, on a truncated rotating walk
        assert "tpu_oracle_fallback_total" not in moved
        assert set(moved["tpu_device_dispatch_total"]) == \
            {"burst_scan", "scatter"}
        assert counter_metric("scan_steps_per_pod.arrivals", res, rep) == 1.0
        assert counter_metric("walk_nodes_per_pod.arrivals", res, rep) == 120
        # one count row a Service where a segment holds several, one vector
        # where it holds one: no pod goes uncounted
        steps = moved["tpu_scan_spread_steps_total"]
        assert set(steps) <= {"grouped", "single"} and steps["grouped"] > 0
        assert sum(steps.values()) == pods
        assert counter_metric("spread_grouped_steps_per_pod.arrivals",
                              res, rep) == steps["grouped"] / pods
        # the deployment's two counters: one count pass and one lookup in
        # the selector index a group a segment; a lookup tests the one
        # Service filed under the pod's label, not all 80
        groups = moved["tpu_scan_spread_groups_total"][""]
        assert groups == moved["tpu_spread_count_encodes_total"][""] > 0
        assert counter_metric("spread_groups_per_pod.arrivals",
                              res, rep) == groups / pods
        assert counter_metric("selector_services_tested_per_pod.arrivals",
                              res, rep) == groups / pods
        # no Service moves in the window: the warm-up's index serves it
        assert "tpu_selector_index_builds_total" not in moved
        assert counter_metric("selector_index_builds.arrivals", res, rep) == 0
        # the pods differ in their Service alone: a launch builds a row a
        # group and the pad's, and gathers them to the drain's bucket
        launches = moved["tpu_device_dispatch_total"]["burst_scan"]
        rows = moved["tpu_scan_stack_rows_total"]
        assert rows == {"built": groups + launches, "taken": 256 * launches}
        assert counter_metric("stack_rows_built_per_pod.arrivals",
                              res, rep) == rows["built"] / pods
        # pods of earlier windows are still bound when a table is made
        assert counter_metric("pod_table_rows_extracted_per_pod.arrivals",
                              res, rep) > 0
        assert counter_metric("pod_table_rows_reused_per_pod.arrivals",
                              res, rep) > 0
        # ... one call a launch, and where pods joined or left since the
        # call before, the changed nodes' rows are spliced into the
        # previous columns (PR 53); on 250 nodes a window's binds can be
        # too many pieces for that, and such a call gathers
        calls = moved["tpu_pod_table_calls_total"]
        assert sum(calls.values()) == launches
        assert set(calls) <= {"spliced", "gathered", "shared"}
        assert calls["spliced"] > 0
        assert counter_metric("pod_table_spliced_call_share.arrivals",
                              res, rep) == 100.0 * calls["spliced"] / launches
        assert counter_metric("pod_table_gathered_call_share.arrivals",
                              res, rep) \
            == 100.0 * calls.get("gathered", 0) / launches
        # the shell's side, over warm-up and window: the warm-up's 232-pod
        # pass holds more than sixteen Services and is cut; no class cut
        whole = counters.delta(counters.snapshot(), before)
        cuts = whole["scheduler_burst_segment_cuts_total"]
        assert set(cuts) == {("groups",), ("end",)}
        bound = whole["serve_pods_scheduled_total"][()]
        assert counter_metric("segment_group_cuts_per_pod.arrivals", res, rep,
                              bound, whole) == cuts[("groups",)] / bound > 0
        assert counter_metric("segment_class_cuts_per_pod.arrivals", res, rep,
                              bound, whole) == 0.0
    if cell == MIXED:
        moved = rep["counters"]
        # unlike plain pods share one segment, and it goes to the scan
        assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
        assert moved["tpu_scan_order_steps_total"] == \
            {"axis": res["attempted"]}
        # every launch held more than one signature: its rows were stacked
        assert moved["tpu_scan_pod_rows_total"] == \
            {"stacked": res["attempted"]}
        # the filter said no, and the board is not one tie over every node
        assert moved["tpu_filter_rejected_nodes_total"][""] > 0
        tied = moved["tpu_pick_tied_nodes_total"][""] / res["attempted"]
        assert 1 <= tied < 0.9 * 240


with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(execute, cell):
    """What a traced run of the cell opens before it touches the device: its
    configuration, its traffic mix, and for every per-layer metric the file
    and the reader it names (and, for a roofline, the byte model)."""
    from lib import spec
    bench = spec.load_benchmark()
    entry = spec.find_cell(bench, cell)
    cfg = spec.load_config(bench, entry["config"])
    importlib.import_module(f"reference.{cfg['reference']}")
    spec.load_traffic(entry["traffic"])
    e2e = [m["name"] for m in spec.metrics_for(bench, entry, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.metrics_for(bench, entry, "per_layer")
    assert layer
    for m in layer:
        mf = spec.load_metric(m["name"])
        reader = importlib.import_module(f"readers.{mf['reader']}")
        assert callable(reader.read)
        if mf["reader"] == "trace_program_roofline":
            model = importlib.import_module(
                f"roofline.{mf['args'].get('module', 'bytes')}")
            assert getattr(model, mf["args"]["model"])(
                rows=8192, pods=1000, nodes=5000) > 0


# the encode phase opened (PR 52): the three spans' wall shares and the pod
# table's calls by path, over readers that were there; stem -> (reader, its
# arguments, the program file that books the span or the counter)
ENCODE_OPENED = {
    f"encode_{part}_wall_share": (
        "trace_span_share",
        {"form": "wall_share", "spans": [f"burst.encode.{part}"]}, source)
    for part, source in (("table", "ops/node_state.py"),
                         ("count", "ops/node_state.py"),
                         ("carry", "core/tpu_scheduler.py"))}
ENCODE_OPENED["pod_table_gathered_call_share"] = (
    "counter_label_share",
    {"family": "tpu_pod_table_calls_total", "labels": ["gathered"]},
    "ops/node_state.py")
# every metric of the opened encode by name, and the table's delta by rows
# (PR 53): one metric the open loop, one the closed
OPENED = {f"{stem}.{suffix}": v for stem, v in ENCODE_OPENED.items()
          for suffix in ("backlog", "arrivals")}
OPENED["pod_table_spliced_call_share.arrivals"] = (
    "counter_label_share",
    {"family": "tpu_pod_table_calls_total", "labels": ["spliced"]},
    "ops/node_state.py")
OPENED["pod_table_kept_node_share.backlog"] = (
    "counter_label_share",
    {"family": "tpu_pod_table_moved_nodes_total", "labels": ["kept"]},
    "ops/node_state.py")
# how a truncated walk ended (PR 54)
OPENED["walk_exhausted_share.backlog"] = (
    "counter_label_share",
    {"family": "tpu_walk_ended_total", "labels": ["nodes", "none"]},
    "core/tpu_scheduler.py")
OPENED["walk_unschedulable_per_pod.backlog"] = (
    "counter_delta_per_pod",
    {"family": "tpu_walk_ended_total", "labels": ["none"]},
    "core/tpu_scheduler.py")


@pytest.mark.parametrize("name", list(OPENED))
def test_encode_opened_metric_is_read_where_it_is_listed(execute, name):
    """Each new metric's file loads, names a reader that is there and the
    span or family the program books, and every cell its entry names lists
    it and reports the end-to-end metric it moves."""
    from lib import spec
    bench = spec.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    mf = spec.load_metric(name)
    reader, args, source = OPENED[name]
    assert (mf["reader"], mf["args"]) == (reader, args)
    assert callable(importlib.import_module(f"readers.{reader}").read)
    booked = (args.get("spans") or [args.get("family")])[0]
    with open(os.path.join(os.path.dirname(BENCH_DIR), "kubernetes_tpu",
                           source)) as f:
        assert f'"{booked}"' in f.read()
    assert entry["workloads"]
    for cell in entry["workloads"]:
        cell_entry = spec.find_cell(bench, cell)
        assert name in [m["name"] for m in spec.metrics_for(
            bench, cell_entry, "per_layer")]
        assert entry["moves"] in [m["name"] for m in spec.metrics_for(
            bench, cell_entry, "end_to_end")]
