"""The data files PR 34 adds: the configuration is the headline cluster's
nodes holding the density pod 9 to a node, the traffic mix is eight plain
sizes whose every pod always fits, and the cell reports cell 5's metrics
plus the three counters of what it adds."""
import json
import os

from cells import reports
from lib import spec
from lib.traffic import PodFactory

NEW = "inuse-15000n-135k.backlog-10k-mixed"
CELL2 = "density-5000n-150k.rollout-1k"
CELL5 = "headline-15000n-adaptive.backlog-10k"
CELL7 = "density-5000n-150k-adaptive.rollout-1k"
ADDED = {"scan_stacked_rows_per_pod.backlog": "tpu_scan_pod_rows_total",
         "pick_tied_nodes_per_pod.backlog": "tpu_pick_tied_nodes_total",
         "filter_rejected_nodes_per_pod.backlog":
             "tpu_filter_rejected_nodes_total"}
MI, GI = 2 ** 20, 2 ** 30


def test_config_is_the_headline_cluster_in_use():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "inuse-15000n-135k")
    base = spec.load_config(bench, "headline-15000n")
    density = spec.load_config(bench, "density-5000n-150k")
    assert cfg["nodes"] == base["nodes"]
    assert cfg["guarantees"] == base["guarantees"]
    assert cfg["scheduler"] == base["scheduler"] and cfg["store"] == base["store"]
    assert cfg["scheduler"]["percentage_of_nodes_to_score"] == 100
    assert cfg["reference"] == "default_provider"
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    for word in ("15,000 nodes", "150,000 pods", "scheduler_perf"):
        assert word in cfg["source"]
    res = cfg["resident"]
    assert res == {"pods_per_node": 9, "services": 450,
                   "requests": density["resident"]["requests"]}
    nodes = cfg["nodes"]["count"]
    assert nodes * res["pods_per_node"] == 135000 == 450 * 300
    # with a 10,000-pod backlog bound the cluster stays inside the threshold
    assert 135000 + 10000 <= 150000
    assert res["pods_per_node"] * res["requests"]["cpu_milli"] == 900
    assert res["pods_per_node"] * res["requests"]["memory_bytes"] == 4718592000
    assert not any("bench.py" in line for line in cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/inuse-15000n-135k.json"
    assert len(entry["why"]) <= 200


def test_mix_is_eight_plain_sizes_and_every_pod_always_fits():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "inuse-15000n-135k")
    tr = spec.load_traffic("backlog-10k-mixed")
    assert tr["kind"] == "closed_backlog" and tr["backlog"] == 10000
    assert tr["lifetime_s"] is None and tr["service_choice"] is None
    assert tr["warm_binds"] == 20000 and tr["trace_seconds"] == 1.5
    shapes = tr["pod_shapes"]
    assert [(sh["share"], sh["requests"]["cpu_milli"],
             sh["requests"]["memory_bytes"]) for sh in shapes] == [
        (0.30, 100, 128 * MI), (0.25, 250, 512 * MI), (0.20, 500, GI),
        (0.10, 500, 4 * GI), (0.08, 1000, 2 * GI), (0.04, 1000, 8 * GI),
        (0.02, 2000, 8 * GI), (0.01, 3000, 24 * GI)]
    assert all(sh["kind"] == "plain" and "labels" not in sh for sh in shapes)
    assert abs(sum(sh["share"] for sh in shapes) - 1.0) < 1e-12
    shares = [sh["share"] for sh in shapes]
    assert shares == sorted(shares, reverse=True)      # small common, large rare
    mean_cpu = sum(sh["share"] * sh["requests"]["cpu_milli"] for sh in shapes)
    mean_mem = sum(sh["share"] * sh["requests"]["memory_bytes"] for sh in shapes)
    assert abs(mean_cpu - 432.5) < 1e-9 and abs(mean_mem / GI - 1.6425) < 1e-9

    # every pod always fits: a backlog touches at most `backlog` nodes, so
    # at least nodes - backlog hold their residents only, and such a node
    # takes the largest size; hence no pod is ever unschedulable and more
    # than one node is feasible for every pod (`skip_decision`)
    alloc = cfg["nodes"]["allocatable"]
    res = cfg["resident"]
    used_cpu = res["pods_per_node"] * res["requests"]["cpu_milli"]
    used_mem = res["pods_per_node"] * res["requests"]["memory_bytes"]
    untouched = cfg["nodes"]["count"] - tr["backlog"]
    assert untouched >= 5000 > 1
    free_cpu, free_mem = alloc["cpu_milli"] - used_cpu, alloc["memory_bytes"] - used_mem
    assert (free_cpu, free_mem) == (3100, 29641146368)
    assert all(sh["requests"]["cpu_milli"] <= free_cpu
               and sh["requests"]["memory_bytes"] <= free_mem for sh in shapes)
    assert res["pods_per_node"] + tr["backlog"] < alloc["pods"] * untouched
    # a cycle asks for 4325 CPU of the 46,500 free
    assert tr["backlog"] * mean_cpu / 1000 == 4325.0
    assert cfg["nodes"]["count"] * free_cpu / 1000 == 46500.0
    # the largest size meets PodFitsResources' equality: exactly 4000m on a
    # node that took the smallest this cycle, too much on one that took more
    big, small = shapes[-1]["requests"], shapes[0]["requests"]
    assert used_cpu + small["cpu_milli"] + big["cpu_milli"] == alloc["cpu_milli"]
    assert used_mem + small["memory_bytes"] + big["memory_bytes"] \
        <= alloc["memory_bytes"]
    assert used_cpu + shapes[1]["requests"]["cpu_milli"] + big["cpu_milli"] \
        > alloc["cpu_milli"]

    # the generator that is there draws the sizes from the seed, interleaved
    f = PodFactory(tr, res["services"], 2 ** 31 + 7)
    drawn = [f._shape()["requests"]["cpu_milli"] for _ in range(10000)]
    g = PodFactory(tr, res["services"], 2 ** 31 + 7)
    assert drawn == [g._shape()["requests"]["cpu_milli"] for _ in range(10000)]
    assert set(drawn) == {100, 250, 500, 1000, 2000, 3000}
    assert 2700 < drawn.count(100) < 3300 and 50 < drawn.count(3000) < 160
    assert len({tuple(drawn[k:k + 10]) for k in range(0, 1000, 10)}) > 90


def test_cell_reports_cell_5s_metrics_and_what_it_adds():
    bench = spec.load_benchmark()
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    cell = spec.find_cell(bench, NEW)
    assert cell["config"] == "inuse-15000n-135k"
    assert cell["traffic"] == "backlog-10k-mixed" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["pods_per_s", "setup_s"]
    layer = names(cell, "per_layer")
    fifth = names(spec.find_cell(bench, CELL5), "per_layer")
    # cell 5's, less what only a truncated walk books (this cell scores
    # every node)
    assert set(layer) <= set(fifth) and set(ADDED) <= set(layer)
    assert set(fifth) - set(layer) == {"walk_exhausted_share.backlog",
                                       "walk_unschedulable_per_pod.backlog"}
    for name in ("walk_nodes_per_pod.backlog", "scan_steps_per_pod.backlog",
                 "stack_wall_share.backlog", "schedule_batch_roofline.backlog",
                 "scatter_rows_roofline.backlog", "warmup_s",
                 "compiles_in_window", "program_compiles_in_window",
                 "oracle_fallback.backlog", "kernel_us_per_pod.backlog"):
        assert name in layer
    for name, family in ADDED.items():
        for cell_name in (NEW, CELL2, CELL5, CELL7):
            assert reports(bench, cell_name, name)
        mf = spec.load_metric(name)
        assert mf["reader"] == "counter_delta_per_pod"
        assert mf["args"]["family"] == family
    order = [m["name"] for m in bench["per_layer"]]
    assert [order.index(n) for n in ADDED] == sorted(order.index(n)
                                                     for n in ADDED)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024 and json.loads(raw) == bench


def test_the_counter_reader_reads_nothing_as_zero_and_the_sum_per_pod():
    from readers import counter_delta_per_pod
    ctx = {"pods_bound": 10000, "counters": {
        "tpu_scan_pod_rows_total": {("stacked",): 10000.0},
        "tpu_pick_tied_nodes_total": {(): 116777955.0}}}
    mf = spec.load_metric("scan_stacked_rows_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 1.0
    shared = {"pods_bound": 1000, "counters": {
        "tpu_scan_pod_rows_total": {("shared",): 1000.0}}}
    assert counter_delta_per_pod.read(shared, **mf["args"]) == 0.0
    mf = spec.load_metric("pick_tied_nodes_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 11677.7955
    # a commit without the counter (the parent): 0, and nothing raised
    mf = spec.load_metric("filter_rejected_nodes_per_pod.backlog")
    assert counter_delta_per_pod.read(ctx, **mf["args"]) == 0.0
    assert counter_delta_per_pod.read({**ctx, "pods_bound": 0},
                                      **mf["args"]) is None
