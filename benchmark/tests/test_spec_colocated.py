"""The data files PR 47 adds: the configuration is cell 9's cluster run as
clusters of its size are run, services and batch in one cell; the traffic mix
is cell 9's eight Services' shapes beside three label-free sizes, the Jobs'
pods, drawn pod by pod; the cell reports cell 9's metrics and the two
counters of what it shows. No cell count and no "last configuration" is
held here, so the next cell breaks no test of this file."""
import importlib
import json
import os

from cells import reporting
from lib import cluster, spec
from lib.traffic import PodFactory

NEW = "colocated-5000n-150k.rollouts-1k-8svc-jobs"
CELL9 = "load-5000n-150k.rollouts-1k-8svc"
ADDED = {"segment_plan_cuts_per_pod.backlog": ("plan", "cuts/pod"),
         "segment_end_cuts_per_pod.backlog": ("end", "segments/pod")}
FAMILY = "scheduler_burst_segment_cuts_total"


def test_config_is_cell_9s_cluster_with_batch_in_the_cell():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "colocated-5000n-150k")
    base = spec.load_config(bench, "load-5000n-150k")
    assert {k for k in base if base[k] != cfg[k]} == {
        "name", "source", "deployment", "guarantees", "assumed"}
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {"decisions"}
    decisions = cfg["guarantees"]["decisions"]
    assert "exact identity, limit 0" in decisions
    assert "no Service selects" in decisions and "constant" in decisions
    assert cfg["nodes"]["count"] == 5000 and cfg["nodes"]["zones"] == 3
    assert cfg["resident"]["pods_per_node"] == 30
    assert cfg["resident"]["services"] == 500
    assert cfg["scheduler"]["percentage_of_nodes_to_score"] == 0
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    for word in ("density.go:56", "5000 nodes", "150000 pods", "Borg",
                 "cluster-trace-v2018", "getSelectors"):
        assert word in cfg["source"]
    assert cfg["source"] != base["source"]
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/colocated-5000n-150k.json"
    assert len(entry["why"]) <= 200
    names = [c["name"] for c in bench["configs"]]
    assert names.index(cfg["name"]) > names.index(base["name"])


def test_mix_is_cell_9s_services_and_the_jobs_pods_between_them():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "colocated-5000n-150k")
    tr = spec.load_traffic("rollouts-1k-8svc-jobs")
    base = spec.load_traffic("rollouts-1k-8svc")
    sizes = spec.load_traffic("backlog-10k-mixed")
    assert {k for k in base if base[k] != tr[k]} == {
        "pod_shapes", "why", "assumed"} and set(tr) == set(base)
    assert tr["kind"] == "closed_backlog" and tr["backlog"] == 1000
    assert tr["warm_binds"] == 2000 and tr["trace_seconds"] == 1.0
    shapes = tr["pod_shapes"]
    replicas = [sh for sh in shapes if "labels" in sh]
    jobs = [sh for sh in shapes if "labels" not in sh]
    assert shapes == replicas + jobs and all(
        sh["kind"] == "plain" for sh in shapes)
    # cell 9's eight shapes, at 0.7 of their share
    assert [{**sh, "share": 0.125} for sh in replicas] == base["pod_shapes"]
    assert all(sh["share"] == 0.0875 for sh in replicas)
    # cell 8's three most common sizes, in their order
    assert [sh["requests"] for sh in jobs] == \
        [sh["requests"] for sh in sizes["pod_shapes"][:3]]
    assert [sh["share"] for sh in jobs] == [0.15, 0.10, 0.05]
    assert abs(sum(sh["share"] for sh in jobs) - 0.30) < 1e-12

    # every pod binds: the largest pass fits the least a node has free
    res, alloc = cfg["resident"], cfg["nodes"]["allocatable"]
    free_cpu = alloc["cpu_milli"] - \
        res["pods_per_node"] * res["requests"]["cpu_milli"]
    free_mem = alloc["memory_bytes"] - \
        res["pods_per_node"] * res["requests"]["memory_bytes"]
    assert free_cpu == 1000 and alloc["pods"] - res["pods_per_node"] == 80
    assert all(sh["requests"]["cpu_milli"] <= free_cpu
               and sh["requests"]["memory_bytes"] <= free_mem
               for sh in shapes)
    assert tr["backlog"] < cfg["nodes"]["count"]

    # the reference's own matcher: a replica is selected by exactly one
    # resident Service, a Job's pod by none
    from reference.default_provider_adaptive import Reference
    n_services = res["services"]
    services = [cluster.service_label(k) for k in range(n_services)]
    rows = cluster.node_rows(spec.overlaid(cfg, {"nodes": {"count": 6}}))
    ref = Reference(rows, {"default": services}, 0)
    seed = 2 ** 31 + 7
    f = PodFactory(tr, n_services, seed)
    f.new_cycle()
    descs = [f.make(f"p-{j}")[1] for j in range(1000)]
    selected = [len(ref._selectors(d)) for d in descs]
    assert set(selected) == {0, 1}
    assert [bool(d["labels"]) for d in descs] == [bool(n) for n in selected]

    # drawn from the seed pod by pod: the same seed the same draw, about
    # 300 Jobs' pods a pass, the kind changing 0.42 times a pod
    g = PodFactory(tr, n_services, seed)
    g.new_cycle()
    assert [g.make(f"p-{j}")[1] for j in range(1000)] == descs
    assert 250 < selected.count(0) < 350
    changes = sum(a != b for a, b in zip(selected, selected[1:]))
    assert 0.36 < changes / 999 < 0.48
    for k in range(8):
        lab = tuple(cluster.service_label(k).items())
        assert 55 < sum(d["labels"] == lab for d in descs) < 125


def test_cell_reports_cell_9s_metrics_and_the_two_counters():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    assert cell["config"] == "colocated-5000n-150k"
    assert cell["traffic"] == "rollouts-1k-8svc-jobs" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["pods_per_s", "setup_s"]
    # whatever cell 9 reports, this cell reports, and nothing else
    assert names(cell, "per_layer") == \
        names(spec.find_cell(bench, CELL9), "per_layer")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (cause, unit) in ADDED.items():
        m = entries[name]
        # cells 9 and 11 and no cell before them: a pass of one class of
        # pods meets neither cause
        assert reporting(bench, name)[:2] == [CELL9, NEW]
        assert m["unit"] == unit
        assert m["moves"] == "pods_per_s" and m["better"] == "lower"
        assert m["source"] == "program_counter" and m["layer"] == "shell"
        mf = spec.load_metric(name)
        assert mf["reader"] == "counter_delta_per_pod"
        assert mf["args"] == {"family": FAMILY, "labels": [cause]}
    # appended, never put first or in the middle
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(NEW) > cells.index(CELL9)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index("segment_class_cuts_per_pod.backlog") < \
        order.index("segment_plan_cuts_per_pod.backlog") < \
        order.index("segment_end_cuts_per_pod.backlog")
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024 and json.loads(raw) == bench


def test_every_metric_the_cell_lists_has_its_file_and_its_reader():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    for m in spec.metrics_for(bench, cell, "per_layer"):
        mf = spec.load_metric(m["name"])
        reader = importlib.import_module(f"readers.{mf['reader']}")
        assert callable(reader.read)


def test_the_two_metrics_read_the_counter_and_nothing_as_zero():
    from readers import counter_delta_per_pod
    ctx = {"pods_bound": 1000, "counters": {
        FAMILY: {("plan",): 420.0, ("end",): 421.0}}}
    mf = spec.load_metric("segment_plan_cuts_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 0.42
    mf = spec.load_metric("segment_end_cuts_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 0.421
    mf = spec.load_metric("segment_class_cuts_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 0.0
    # a commit without the cause (the parent) or the family: 0, none raised
    for moved in ({FAMILY: {("end",): 421.0}}, {}):
        bare = {"pods_bound": 1000, "counters": moved}
        mf = spec.load_metric("segment_plan_cuts_per_pod.backlog")
        assert counter_delta_per_pod.read(bare, **mf["args"]) == 0.0
        assert counter_delta_per_pod.read({**bare, "pods_bound": 0},
                                          **mf["args"]) is None
