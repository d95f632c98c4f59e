"""The data files PR 54 adds: the configuration is cell 7's cluster on nodes
capped at 32 pods, so that 150,000 resident pods take 93.75% of its pod
slots; the traffic mix fills the last 10,000 slots with 9,900 label-free pods
of three sizes; the cell reports cell 8's metrics, the rotation's three and
two counters of how a walk ended. No cell count, no "last" and no other
cell's list is held here, so the next cell breaks no test of this file."""
import importlib
import json
import math
import os

import numpy as np

from cells import reporting
from lib import cluster, spec
from lib.traffic import PodFactory

NEW = "podcap-5000n-150k.backlog-9900-fill"
CELL7 = "density-5000n-150k-adaptive.rollout-1k"
CELL8 = "inuse-15000n-135k.backlog-10k-mixed"
ROTATION = ("rotation_wall_share.backlog", "kernel_rotate_us_per_pod.backlog",
            "schedule_batch_rotation_roofline.backlog")
FAMILY = "tpu_walk_ended_total"
# name -> (reader, args, unit)
ADDED = {
    "walk_exhausted_share.backlog": (
        "counter_label_share",
        {"family": FAMILY, "labels": ["nodes", "none"]}, "%"),
    "walk_unschedulable_per_pod.backlog": (
        "counter_delta_per_pod",
        {"family": FAMILY, "labels": ["none"]}, "pods/pod"),
}


def short_walk_bounds(slots: int, backlog: int, quota: int) -> tuple:
    """The fewest and the most decisions of a pass that can find fewer than
    `quota` nodes with a free slot, two slots a node: with k pods bound,
    s = slots - k are left on ceil(s/2) to s nodes."""
    fewest = sum(1 for k in range(backlog) if slots - k < quota)
    most = sum(1 for k in range(backlog)
               if math.ceil((slots - k) / 2) < quota)
    return fewest, most


def test_config_is_cell_7s_cluster_on_nodes_of_32_pod_slots():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "podcap-5000n-150k")
    base = spec.load_config(bench, "density-5000n-150k-adaptive")
    assert {k for k in base if base[k] != cfg[k]} == {
        "name", "source", "deployment", "nodes", "guarantees", "assumed"}
    assert cfg["nodes"] == spec.overlaid(
        base["nodes"], {"allocatable": {"pods": 32}})
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {
                "decisions", "capacity"}
    decisions = cfg["guarantees"]["decisions"]
    assert "exact identity, limit 0" in decisions
    for word in ("passes its quota", "keeps fewer than the quota",
                 "moves by all n", "keeps a single node"):
        assert word in decisions
    assert "more than its 32 pods" in cfg["guarantees"]["capacity"]
    assert cfg["nodes"]["count"] == 5000 and cfg["nodes"]["zones"] == 3
    assert cfg["resident"]["pods_per_node"] == 30
    assert cfg["resident"]["services"] == 500
    assert cfg["scheduler"]["percentage_of_nodes_to_score"] == 0
    assert cfg["scheduler"]["mesh"] == "auto"
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["store"] == {"watch_log_size": 2 ** 20}
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    for word in ("density.go:56", "5000 nodes", "150000 pods", "30 a node",
                 "GKE", "/26", "AKS", "maxPods 30", "EKS", "m5.large 29"):
        assert word in cfg["source"]
    assert cfg["source"] != base["source"]
    assumed = " ".join(cfg["assumed"])
    for word in ("remembers", "system pods", "32 - 30 = 2 pod slots",
                 "500m + 500m = 1000m", "9,900 pods", "10,000 free slots",
                 "deleted before the next"):
        assert word in assumed
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/podcap-5000n-150k.json"
    assert len(entry["why"]) <= 200
    names = [c["name"] for c in bench["configs"]]
    assert names.index(cfg["name"]) > names.index(base["name"])


def test_mix_is_three_job_sizes_for_the_last_slots():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "podcap-5000n-150k")
    tr = spec.load_traffic("backlog-9900-fill")
    mixed = spec.load_traffic("backlog-10k-mixed")
    jobs = spec.load_traffic("rollouts-1k-8svc-jobs")
    assert tr["kind"] == "closed_backlog" and tr["backlog"] == 9900
    assert "warm_binds" not in tr and tr["trace_seconds"] == 1.5 \
        == mixed["trace_seconds"]
    assert tr["lifetime_s"] is None and tr["service_choice"] is None
    shapes = tr["pod_shapes"]
    assert [sh["share"] for sh in shapes] == [0.5, 0.3, 0.2]
    assert all(sh["kind"] == "plain" and "labels" not in sh for sh in shapes)
    # cell 11's three Job sizes, cell 8's three most common
    assert [sh["requests"] for sh in shapes] == \
        [sh["requests"] for sh in jobs["pod_shapes"] if "labels" not in sh] \
        == [sh["requests"] for sh in mixed["pod_shapes"][:3]]
    assert [(sh["requests"]["cpu_milli"], sh["requests"]["memory_bytes"])
            for sh in shapes] == [(100, 128 << 20), (250, 512 << 20),
                                  (500, 1 << 30)]

    # the arithmetic the `why` carries: what a node has free, that any two
    # of the pods fit in it, that the pass fits the cluster's free slots
    res, alloc = cfg["resident"], cfg["nodes"]["allocatable"]
    free_slots = alloc["pods"] - res["pods_per_node"]
    free_cpu = alloc["cpu_milli"] - \
        res["pods_per_node"] * res["requests"]["cpu_milli"]
    free_mem = alloc["memory_bytes"] - \
        res["pods_per_node"] * res["requests"]["memory_bytes"]
    assert (free_slots, free_cpu) == (2, 1000)
    assert round(free_mem / 2 ** 30, 2) == 17.35
    big = max(shapes, key=lambda sh: sh["requests"]["cpu_milli"])["requests"]
    assert 2 * big["cpu_milli"] == free_cpu          # the equality case
    assert 2 * big["memory_bytes"] < free_mem
    slots = cfg["nodes"]["count"] * free_slots
    assert tr["backlog"] <= slots == 10000
    from reference.default_provider_adaptive import num_to_find
    quota = num_to_find(cfg["nodes"]["count"], 0)
    assert quota == 500
    fewest, most = short_walk_bounds(slots, tr["backlog"], quota)
    assert (fewest, most) == (399, 898)
    for word in ("2 pod slots, 1000m and 17.35 GiB free",
                 "500m + 500m = 1000m", "9,900 <= 10,000",
                 "before pod 9,003", "from pod 9,502 on",
                 "at least 399 and at most 898", "4.03-9.07%",
                 "between 51 and 101"):
        assert word in tr["why"]
    assert round(100 * fewest / tr["backlog"], 2) == 4.03
    assert round(100 * most / tr["backlog"], 2) == 9.07
    left = slots - (tr["backlog"] - 1)
    assert (math.ceil(left / 2), left) == (51, 101)
    assert "who sends it" in " ".join(tr["assumed"])

    # drawn from the seed pod by pod: the same seed the same draw, the
    # shares about the file's
    seed = 2 ** 31 + 54
    f = PodFactory(tr, res["services"], seed)
    f.new_cycle()
    descs = [f.make(f"p-{j}")[1] for j in range(tr["backlog"])]
    g = PodFactory(tr, res["services"], seed)
    g.new_cycle()
    assert [g.make(f"p-{j}")[1] for j in range(tr["backlog"])] == descs
    assert all(d["labels"] == () and d["kind"] == "plain" for d in descs)
    for sh in shapes:
        share = sum(d["cpu"] == sh["requests"]["cpu_milli"]
                    for d in descs) / len(descs)
        assert abs(share - sh["share"]) < 0.02


def test_a_small_fill_meets_the_regimes_in_the_reference():
    """The reference alone, on 250 nodes of 8 slots holding 6: a pass of 495
    pods for 500 slots binds whole, its walks pass the quota, and between
    the arithmetic's bounds come up short, a few nodes kept at the end."""
    bench = spec.load_benchmark()
    cfg = spec.overlaid(spec.load_config(bench, "podcap-5000n-150k"), {
        "nodes": {"count": 250, "allocatable": {"pods": 8}},
        "resident": {"pods_per_node": 6, "services": 5}})
    tr = spec.load_traffic("backlog-9900-fill")
    from lib import check
    seed = 2 ** 31 + 54
    rows = cluster.node_rows(cfg)
    plan = cluster.resident_plan(cfg, seed)
    req = cfg["resident"]["requests"]
    residents = [({"cpu": req["cpu_milli"], "mem": req["memory_bytes"],
                   "namespace": "default", "kind": "plain",
                   "labels": tuple(cluster.service_label(
                       plan[i * 6 + j]).items())}, r["name"])
                 for i, r in enumerate(rows) for j in range(6)]
    services = [cluster.service_label(k) for k in range(5)]
    ref = check.make_reference(cfg, rows, residents, services)
    assert ref.num_to_find == 120
    f = PodFactory(tr, len(services), seed)
    f.new_cycle()
    tested, kept = [], []
    walk = ref._walk

    def noting(pod):
        entry = ref.last_index
        out = walk(pod)
        tested.append((ref.last_index - entry) % ref.n or ref.n)
        kept.append(int(out.size))
        return out
    ref._walk = noting
    for j in range(495):
        desc = f.make(f"p-{j}")[1]
        node = ref.decide(desc)
        assert node is not None
        ref.place(desc, node)
    assert int(np.max(ref.n_pods)) == 8
    fewest, most = short_walk_bounds(500, 495, 120)
    short = sum(1 for k in kept if k < 120)
    assert (fewest, most) == (114, 233) and fewest <= short <= most
    assert all(t == 250 for t, k in zip(tested, kept) if k < 120)
    assert any(t > 120 and k == 120 for t, k in zip(tested, kept))
    assert 3 <= kept[-1] <= 6 and min(kept) > 0


def test_cell_reports_cell_8s_metrics_the_rotations_and_the_two_counters():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    assert cell["config"] == "podcap-5000n-150k"
    assert cell["traffic"] == "backlog-9900-fill" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["pods_per_s", "setup_s"]
    mine = names(cell, "per_layer")
    eight = names(spec.find_cell(bench, CELL8), "per_layer")
    seven = names(spec.find_cell(bench, CELL7), "per_layer")
    # whatever cell 8 reports, this cell reports; of cell 7's the rotation's
    # three besides; and the two it adds
    assert set(mine) == set(eight) | set(ROTATION) | set(ADDED)
    assert set(ROTATION) <= set(seven) and not set(ROTATION) & set(eight)
    assert set(ADDED) <= set(seven)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (reader, args, unit) in ADDED.items():
        m = entries[name]
        # a truncated walk books the family; cell 8 scores every node
        assert reporting(bench, name)[-1] == NEW and CELL7 in reporting(
            bench, name) and CELL8 not in reporting(bench, name)
        assert m["unit"] == unit
        assert m["moves"] == "pods_per_s" and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert m["layer"] == entries["walk_nodes_per_pod.backlog"]["layer"]
        mf = spec.load_metric(name)
        assert mf["reader"] == reader and mf["args"] == args
    # appended, never put first or in the middle
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(NEW) > cells.index(CELL8)
    for m in bench["end_to_end"] + bench["per_layer"]:
        lst = m.get("workloads", ())
        if m["name"] not in ADDED and NEW in lst:
            others = [c for c in (CELL7, CELL8) if c in lst]
            assert others and all(lst.index(NEW) > lst.index(c)
                                  for c in others)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024 and json.loads(raw) == bench
    assert cell["chips"] == 1


def test_every_metric_the_cell_lists_has_its_file_and_its_reader():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    for m in spec.metrics_for(bench, cell, "per_layer"):
        mf = spec.load_metric(m["name"])
        reader = importlib.import_module(f"readers.{mf['reader']}")
        assert callable(reader.read)


def test_the_two_metrics_read_the_counter_and_a_parent_without_it():
    from readers import counter_delta_per_pod
    read = lambda name, c: importlib.import_module(
        f"readers.{spec.load_metric(name)['reader']}").read(
            c, **spec.load_metric(name)["args"])
    ctx = {"pods_bound": 9900, "counters": {FAMILY: {
        ("quota",): 9300.0, ("nodes",): 597.0, ("none",): 3.0}}}
    assert read("walk_exhausted_share.backlog", ctx) == 100.0 * 600 / 9900
    assert read("walk_unschedulable_per_pod.backlog", ctx) == 3 / 9900
    # where every walk stops at its quota (cell 7): both read 0
    all_quota = {"pods_bound": 1000,
                 "counters": {FAMILY: {("quota",): 1000.0}}}
    assert read("walk_exhausted_share.backlog", all_quota) == 0.0
    assert read("walk_unschedulable_per_pod.backlog", all_quota) == 0.0
    # a commit without the counter (the parent): nothing, and none raised
    bare = {"pods_bound": 1000, "counters": {}}
    assert read("walk_exhausted_share.backlog", bare) is None
    assert read("walk_unschedulable_per_pod.backlog", bare) == 0.0
    assert counter_delta_per_pod.read(
        {**bare, "pods_bound": 0},
        **spec.load_metric("walk_unschedulable_per_pod.backlog")["args"]) \
        is None
