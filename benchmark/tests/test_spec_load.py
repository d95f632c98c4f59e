"""The data files PR 41 adds: the configuration is cell 7's cluster under
SIG-scalability's load test, the traffic mix is eight plain shapes that each
carry the label of one resident Service, drawn pod by pod, and the cell
reports cell 7's metrics and the two counters of what it shows."""
import json
import os

from cells import reports
from lib import cluster, spec
from lib.traffic import PodFactory

NEW = "load-5000n-150k.rollouts-1k-8svc"
CELL2 = "density-5000n-150k.rollout-1k"
CELL5 = "headline-15000n-adaptive.backlog-10k"
CELL7 = "density-5000n-150k-adaptive.rollout-1k"
CELL8 = "inuse-15000n-135k.backlog-10k-mixed"
ADDED = {"segment_class_cuts_per_pod.backlog":
             ("scheduler_burst_segment_cuts_total", ["class"], "shell"),
         "spread_encodes_per_pod.backlog":
             ("tpu_spread_count_encodes_total", None, "device seam")}


def test_config_is_cell_7s_cluster_under_the_load_test():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "load-5000n-150k")
    base = spec.load_config(bench, "density-5000n-150k-adaptive")
    assert {k for k in base if base[k] != cfg[k]} == {
        "name", "source", "deployment", "guarantees", "assumed"}
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {"decisions"}
    assert "exact identity, limit 0" in cfg["guarantees"]["decisions"]
    assert cfg["scheduler"] == {"percentage_of_nodes_to_score": 0,
                                "mesh": "auto",
                                "feature_gates": {"TPUScoring": True}}
    assert cfg["nodes"]["count"] == 5000 and cfg["nodes"]["zones"] == 3
    assert cfg["resident"]["pods_per_node"] == 30
    assert cfg["resident"]["services"] == 500
    assert 5000 * 30 == 150000 == 500 * 300
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    for word in ("load.go", "concurrently", "5000 nodes", "150000 pods",
                 "30 pods a node"):
        assert word in cfg["source"]
    assert cfg["source"] != base["source"]
    assert not any("bench.py" in line for line in cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/load-5000n-150k.json"
    assert len(entry["why"]) <= 200
    # appended: it comes after the configuration it is a sibling of (no
    # count and no "last" is held here, so the next cell breaks no test)
    names = [c["name"] for c in bench["configs"]]
    assert names.index(cfg["name"]) > names.index(base["name"])
    from reference.default_provider_adaptive import num_to_find
    assert num_to_find(cfg["nodes"]["count"], 0) == 500


def test_mix_is_eight_services_interleaved_pod_by_pod():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "load-5000n-150k")
    tr = spec.load_traffic("rollouts-1k-8svc")
    rollout = spec.load_traffic("rollout-1k")
    assert tr["kind"] == "closed_backlog" and tr["backlog"] == 1000
    assert tr["lifetime_s"] is None and tr["service_choice"] is None
    assert tr["warm_binds"] == 2000 and tr["trace_seconds"] == 1.0
    assert (tr["backlog"], tr["warm_binds"], tr["trace_seconds"]) == (
        rollout["backlog"], rollout["warm_binds"], rollout["trace_seconds"])
    shapes = tr["pod_shapes"]
    assert len(shapes) == 8 and len(tr["why"]) > 0 and len(tr["assumed"]) >= 4
    assert [sh["labels"] for sh in shapes] == \
        [cluster.service_label(k) for k in range(8)]
    for sh in shapes:
        assert sh["kind"] == "plain" and sh["share"] == 0.125
        assert sh["requests"] == cfg["resident"]["requests"] == \
            rollout["pod_shapes"][0]["requests"]

    # each label is the selector of exactly one resident Service, by the
    # reference's own matcher, whatever the pod's kind
    from reference.default_provider_adaptive import Reference
    n_services = cfg["resident"]["services"]
    services = [cluster.service_label(k) for k in range(n_services)]
    rows = cluster.node_rows(spec.overlaid(cfg, {"nodes": {"count": 6}}))
    ref = Reference(rows, {"default": services}, 0)
    f = PodFactory(tr, n_services, 2 ** 31 + 7)
    f.new_cycle()
    matched = set()
    descs = [f.make(f"p-{j}")[1] for j in range(1000)]
    for d in {id(d): d for d in descs}.values():
        sels = ref._selectors(d)
        assert len(sels) == 1 and d["kind"] == "plain"
        assert dict(sels[0]) == dict(d["labels"])
        matched.add(sels[0])
    assert matched == {tuple(cluster.service_label(k).items())
                       for k in range(8)}
    # 300 resident pods behind each of them at the configuration's size
    plan = cluster.resident_plan(cfg, 2 ** 31 + 7)
    assert all(plan.count(k) == 300 for k in range(8))

    # drawn from the seed pod by pod: the same seed the same draw, every
    # Service about an eighth, and a change of Service after 7 pods in 8
    g = PodFactory(tr, n_services, 2 ** 31 + 7)
    g.new_cycle()
    again = [g.make(f"p-{j}")[1] for j in range(1000)]
    drawn = [d["labels"] for d in descs]
    assert drawn == [d["labels"] for d in again]
    for k in range(8):
        lab = tuple(cluster.service_label(k).items())
        assert 90 < drawn.count(lab) < 165
    changes = sum(a != b for a, b in zip(drawn, drawn[1:]))
    assert 0.83 < changes / 999 < 0.92


def test_cell_reports_cell_7s_metrics_and_the_two_counters():
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(NEW) > cells.index(CELL8)
    cell = spec.find_cell(bench, NEW)
    assert cell["config"] == "load-5000n-150k"
    assert cell["traffic"] == "rollouts-1k-8svc" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["pods_per_s", "setup_s"]
    layer = names(cell, "per_layer")
    seventh = names(spec.find_cell(bench, CELL7), "per_layer")
    # whatever cell 7 reports, this cell reports
    assert set(seventh) <= set(layer) and set(ADDED) <= set(layer)
    for name in ("schedule_batch_roofline.backlog",
                 "schedule_batch_rotation_roofline.backlog",
                 "kernel_rotate_us_per_pod.backlog",
                 "encode_wall_share.backlog", "pods_per_dispatch.backlog",
                 "oracle_fallback.backlog", "device_idle.backlog",
                 "class_shared_per_pod.backlog", "warmup_s"):
        assert name in layer
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (family, labels, layer_name) in ADDED.items():
        m = entries[name]
        for cell_name in (NEW, CELL2, CELL5, CELL7, CELL8):
            assert reports(bench, cell_name, name)
        assert m["moves"] == "pods_per_s" and m["better"] == "lower"
        assert m["source"] == "program_counter" and m["layer"] == layer_name
        mf = spec.load_metric(name)
        assert mf["reader"] == "counter_delta_per_pod"
        assert mf["args"]["family"] == family
        assert mf["args"].get("labels") == labels
    # appended: the two metrics after every metric PR 37 left
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index("rotation_position_steps_per_pod.backlog") < \
        order.index("segment_class_cuts_per_pod.backlog") < \
        order.index("spread_encodes_per_pod.backlog")
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024 and json.loads(raw) == bench


def test_the_two_metrics_read_the_counters_and_nothing_as_zero():
    from readers import counter_delta_per_pod
    ctx = {"pods_bound": 1000, "counters": {
        "scheduler_burst_segment_cuts_total": {("class",): 830.0,
                                               ("end",): 1.0},
        "tpu_spread_count_encodes_total": {(): 831.0}}}
    mf = spec.load_metric("segment_class_cuts_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 0.83
    mf = spec.load_metric("spread_encodes_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 0.831
    # a commit without the counters (the parent): 0, and nothing raised
    bare = {"pods_bound": 1000, "counters": {}}
    for name in ADDED:
        mf = spec.load_metric(name)
        assert counter_delta_per_pod.read(bare, **mf["args"]) == 0.0
        assert counter_delta_per_pod.read({**bare, "pods_bound": 0},
                                          **mf["args"]) is None
