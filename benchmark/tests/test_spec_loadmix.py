"""The data files PR 50 adds: the configuration is cell 10's cluster (the
envelope holding the load test's 5000 Services) while the load test's own
controller mix creates its replicas; the traffic mix is computePodCounts(1000)
over 1000, 111 Services' shapes drawn pod by pod; the cell reports cell 9's
metrics and four counters of what it shows. No cell count and no "last
configuration" is held here, so the next cell breaks no test of this file."""
import importlib
import json
import os

from cells import reporting
from lib import cluster, spec
from lib.traffic import PodFactory

NEW = "loadmix-5000n-150k.rollouts-1k-111svc"
CELL9 = "load-5000n-150k.rollouts-1k-8svc"
CARRY = "tpu_scan_spread_carry_launches_total"
# name -> (reader, args, unit, better, layer), each over what its
# `.arrivals` twin reads but the last, which reads this PR's counter
ADDED = {
    "segment_group_cuts_per_pod.backlog": (
        "counter_delta_per_pod",
        {"family": "scheduler_burst_segment_cuts_total",
         "labels": ["groups"]}, "cuts/pod", "lower", "shell"),
    "spread_groups_per_pod.backlog": (
        "counter_delta_per_pod", {"family": "tpu_scan_spread_groups_total"},
        "groups/pod", "lower", "device seam"),
    "selector_services_tested_per_pod.backlog": (
        "counter_delta_per_pod",
        {"family": "tpu_selector_walk_services_total"},
        "services/pod", "lower", "device seam"),
    "spread_carry_wide_launch_share.backlog": (
        "counter_label_share", {"family": CARRY, "labels": ["128"]},
        "%", "higher", "device seam"),
}


def compute_pod_counts(total: int) -> tuple:
    """load.go's computePodCounts as the configuration's `assumed` states
    it: (small, medium, big) controllers of 5, 30 and 250 replicas."""
    big = total // 4 // 250
    total -= big * 250
    medium = total // 3 // 30
    total -= medium * 30
    return total // 5, medium, big


def test_config_is_cell_10s_cluster_in_the_load_tests_creation_phase():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "loadmix-5000n-150k")
    base = spec.load_config(bench, "services-5000n-150k")
    assert {k for k in base if base[k] != cfg[k]} == {
        "name", "source", "deployment", "guarantees", "assumed"}
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {"decisions"}
    decisions = cfg["guarantees"]["decisions"]
    assert "exact identity, limit 0" in decisions
    assert "more selector groups than one launch carries" in decisions
    assert "however the shell cuts the pass into segments" in decisions
    assert cfg["nodes"]["count"] == 5000 and cfg["nodes"]["zones"] == 3
    assert cfg["resident"]["pods_per_node"] == 30
    assert cfg["resident"]["services"] == 5000
    assert cfg["scheduler"]["percentage_of_nodes_to_score"] == 0
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    for word in ("load.go", "computePodCounts", "density.go:56",
                 "5000 nodes", "150000 pods"):
        assert word in cfg["source"]
    assert cfg["source"] != base["source"]
    assumed = " ".join(cfg["assumed"])
    for word in ("total / 4 / 250", "total / 3 / 30", "total / 5",
                 "1 controller of 250, 8 of 30 and 102 of 5",
                 "remembers", "k % services", "150,000 + 1000"):
        assert word in assumed
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/loadmix-5000n-150k.json"
    assert len(entry["why"]) <= 200
    names = [c["name"] for c in bench["configs"]]
    assert names.index(cfg["name"]) > names.index(base["name"])


def test_mix_is_compute_pod_counts_of_the_pass_over_the_pass():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "loadmix-5000n-150k")
    tr = spec.load_traffic("rollouts-1k-111svc")
    base = spec.load_traffic("rollouts-1k-8svc")
    assert {k for k in base if base[k] != tr[k]} == {
        "pod_shapes", "why", "assumed"} and set(tr) == set(base)
    assert tr["kind"] == "closed_backlog" and tr["backlog"] == 1000
    assert tr["warm_binds"] == 2000 and tr["trace_seconds"] == 1.0
    assert compute_pod_counts(3000) == (300, 25, 3)    # load.go's own example
    small, medium, big = compute_pod_counts(tr["backlog"])
    assert (small, medium, big) == (102, 8, 1)
    assert 250 * big + 30 * medium + 5 * small == tr["backlog"]
    shapes = tr["pod_shapes"]
    assert len(shapes) == big + medium + small == 111
    want = [250] * big + [30] * medium + [5] * small
    assert [sh["share"] for sh in shapes] == [r / tr["backlog"] for r in want]
    assert abs(sum(sh["share"] for sh in shapes) - 1.0) <= 1e-9
    for k, sh in enumerate(shapes):
        assert sh == {**base["pod_shapes"][0], "share": sh["share"],
                      "labels": cluster.service_label(k)}

    # every pod binds: a pass fits the least a node has free, many times
    res, alloc = cfg["resident"], cfg["nodes"]["allocatable"]
    assert alloc["cpu_milli"] - \
        res["pods_per_node"] * res["requests"]["cpu_milli"] == 1000
    assert alloc["pods"] - res["pods_per_node"] == 80
    assert tr["backlog"] < cfg["nodes"]["count"]

    # the reference's own matcher: a replica is selected by exactly one
    # resident Service
    from reference.default_provider_adaptive import Reference
    n_services = res["services"]
    assert n_services >= len(shapes)
    services = [cluster.service_label(k) for k in range(n_services)]
    rows = cluster.node_rows(spec.overlaid(cfg, {"nodes": {"count": 6}}))
    ref = Reference(rows, {"default": services}, 0)
    seed = 2 ** 31 + 7
    f = PodFactory(tr, n_services, seed)
    f.new_cycle()
    descs = [f.make(f"p-{j}")[1] for j in range(1000)]
    assert {len(ref._selectors(d)) for d in descs} == {1}

    # drawn from the seed pod by pod: the same seed the same draw, about
    # 250 / 30 / 5 pods of a controller, about 103 Services a pass
    g = PodFactory(tr, n_services, seed)
    g.new_cycle()
    assert [g.make(f"p-{j}")[1] for j in range(1000)] == descs
    count = lambda k: sum(
        d["labels"] == tuple(cluster.service_label(k).items()) for d in descs)
    assert 200 < count(0) < 300
    assert all(12 < count(k) < 55 for k in range(1, 9))
    assert all(count(k) < 20 for k in range(9, 111))
    assert 95 <= len({d["labels"] for d in descs}) <= 111
    # what the shell's cut rule makes of it: 41-47 segments a pass
    cuts, seen = 0, set()
    for d in descs:
        if d["labels"] not in seen and len(seen) == 16:
            cuts, seen = cuts + 1, set()
        seen.add(d["labels"])
    assert 38 <= cuts + 1 <= 50


def test_cell_reports_cell_9s_metrics_and_the_four_counters():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    assert cell["config"] == "loadmix-5000n-150k"
    assert cell["traffic"] == "rollouts-1k-111svc" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["pods_per_s", "setup_s"]
    # whatever cell 9 reports, this cell reports, and the four it adds
    ninth = names(spec.find_cell(bench, CELL9), "per_layer")
    assert [n for n in names(cell, "per_layer") if n not in ADDED] == ninth
    assert not set(ADDED) & set(ninth)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (reader, args, unit, better, layer) in ADDED.items():
        m = entries[name]
        assert reporting(bench, name)[0] == NEW and m["unit"] == unit
        assert m["moves"] == "pods_per_s" and m["better"] == better
        assert m["source"] == "program_counter" and m["layer"] == layer
        mf = spec.load_metric(name)
        assert mf["reader"] == reader and mf["args"] == args
        twin = name.replace(".backlog", ".arrivals")
        if twin in entries:
            assert entries[twin]["layer"] == layer
            assert entries[twin]["unit"] == unit
            assert spec.load_metric(twin)["args"] == args
    # appended, never put first or in the middle
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(NEW) > cells.index(CELL9)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024 and json.loads(raw) == bench
    # four chips only where something exists only across chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_every_metric_the_cell_lists_has_its_file_and_its_reader():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    for m in spec.metrics_for(bench, cell, "per_layer"):
        mf = spec.load_metric(m["name"])
        reader = importlib.import_module(f"readers.{mf['reader']}")
        assert callable(reader.read)


def test_the_four_metrics_read_their_counters_and_a_parent_without_them():
    from readers import counter_delta_per_pod, counter_label_share
    ctx = {"pods_bound": 1000, "counters": {
        "scheduler_burst_segment_cuts_total": {("groups",): 43.0,
                                               ("end",): 1.0},
        "tpu_scan_spread_groups_total": {(): 693.0},
        "tpu_selector_walk_services_total": {(): 693.0},
        CARRY: {("128",): 33.0, ("4",): 11.0}}}
    read = lambda name, c: importlib.import_module(
        f"readers.{spec.load_metric(name)['reader']}").read(
            c, **spec.load_metric(name)["args"])
    assert read("segment_group_cuts_per_pod.backlog", ctx) == 0.043
    assert read("spread_groups_per_pod.backlog", ctx) == 0.693
    assert read("selector_services_tested_per_pod.backlog", ctx) == 0.693
    assert read("spread_carry_wide_launch_share.backlog", ctx) == 75.0
    # a commit without the counter (the parent): nothing, and none raised
    bare = {"pods_bound": 1000, "counters": {}}
    assert read("spread_carry_wide_launch_share.backlog", bare) is None
    assert counter_label_share.read(
        {"counters": {CARRY: {("8",): 4.0}}}, CARRY, ["128"]) == 0.0
    for name in list(ADDED)[:3]:
        assert read(name, bare) == 0.0
        assert counter_delta_per_pod.read(
            {**bare, "pods_bound": 0}, **spec.load_metric(name)["args"]) \
            is None
