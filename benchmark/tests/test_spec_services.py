"""The data files PR 43 adds: the configuration is cell 9's cluster holding
the load test's Services (5000 of 30 pods), the traffic mix is an open loop of
64 plain shapes that each carry the label of one resident Service, their
shares Zipf(1.1), and every metric the cell lists has its file and a reader.
No cell count and no "last" entry is held here."""
import importlib

import pytest

from cells import reporting, reports
from lib import cluster, spec
from lib.traffic import PodFactory, due_times

NEW = "services-5000n-150k.arrivals-zipf-64svc"
CONFIG, MIX = "services-5000n-150k", "arrivals-zipf-64svc"
CELL3 = "headline-15000n.arrivals-steady"
# (counter family, labels) behind each program_counter metric the PR adds
COUNTED = {
    "scan_steps_per_pod.arrivals": ("tpu_scan_steps_total", None),
    "spread_grouped_steps_per_pod.arrivals":
        ("tpu_scan_spread_steps_total", ["grouped"]),
    "spread_encodes_per_pod.arrivals":
        ("tpu_spread_count_encodes_total", None),
    "segment_group_cuts_per_pod.arrivals":
        ("scheduler_burst_segment_cuts_total", ["groups"]),
    "segment_class_cuts_per_pod.arrivals":
        ("scheduler_burst_segment_cuts_total", ["class"]),
    "pod_table_rows_extracted_per_pod.arrivals":
        ("tpu_pod_table_rows_total", ["extracted"]),
    "pod_table_rows_reused_per_pod.arrivals":
        ("tpu_pod_table_rows_total", ["reused"]),
    "walk_nodes_per_pod.arrivals": ("tpu_walk_nodes_evaluated_total", None),
    "selector_services_tested_per_pod.arrivals":
        ("tpu_selector_walk_services_total", None),
    "spread_groups_per_pod.arrivals": ("tpu_scan_spread_groups_total", None),
}
TRACED = ("stack_wall_share.arrivals", "rotation_wall_share.arrivals",
          "kernel_filter_us_per_pod.arrivals",
          "kernel_score_us_per_pod.arrivals",
          "kernel_pick_us_per_pod.arrivals", "kernel_fold_us_per_pod.arrivals")


def test_config_is_cell_9s_cluster_holding_5000_services():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, CONFIG)
    base = spec.load_config(bench, "load-5000n-150k")
    assert {k for k in base if base[k] != cfg[k]} == {
        "name", "source", "deployment", "resident", "guarantees", "assumed"}
    assert {k for k in base["resident"]
            if base["resident"][k] != cfg["resident"][k]} == {"services"}
    assert cfg["resident"]["services"] == 5000
    assert 5000 * 30 == 150000 == cfg["nodes"]["count"] * \
        cfg["resident"]["pods_per_node"]
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {"decisions"}
    assert "exact identity, limit 0" in cfg["guarantees"]["decisions"]
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    for word in ("load.go", "5, 30 and 250", "Service per controller",
                 "density.go:56", "5000 nodes", "150000 pods", "110"):
        assert word in cfg["source"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])
    # every Service holds 30 resident pods, whatever the seed deals
    plan = cluster.resident_plan(cfg, 2 ** 31 + 7)
    assert len(plan) == 150000
    assert all(plan.count(k) == 30 for k in (0, 63, 64, 4999))
    from reference.default_provider_adaptive import num_to_find
    assert num_to_find(cfg["nodes"]["count"], 0) == 500


def test_mix_is_64_services_zipf_drawn_pod_by_pod():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, CONFIG)
    tr = spec.load_traffic(MIX)
    steady = spec.load_traffic("arrivals-steady")
    assert tr["kind"] == "open_arrivals"
    assert tr["arrival"]["process"] == "poisson"
    assert tr["lifetime_s"] == 5.0 and tr["service_choice"] is None
    assert tr["serve"] == steady["serve"]
    assert tr.get("warm_binds", 0) == 0
    shapes = tr["pod_shapes"]
    assert len(shapes) == 64
    assert [sh["labels"] for sh in shapes] == \
        [cluster.service_label(k) for k in range(64)]
    weights = [(k + 1) ** -1.1 for k in range(64)]
    for sh, w in zip(shapes, weights):
        assert sh["kind"] == "plain"
        assert sh["requests"] == cfg["resident"]["requests"]
        assert sh["share"] == pytest.approx(w / sum(weights), abs=1e-12)
    shares = [sh["share"] for sh in shapes]
    assert abs(sum(shares) - 1.0) <= 1e-9
    assert shares == sorted(shares, reverse=True)
    assert 0.24 < shares[0] < 0.26
    assert 0.62 < sum(shares[:8]) < 0.64 and 0.75 < sum(shares[:16]) < 0.77

    # the rate is 0.6 x a knee that the file records with its rows
    knee = tr["knee"]
    rate = tr["arrival"]["rate_per_s"]
    assert knee["share"] == 0.6 and rate % 50 == 0
    assert rate <= 0.6 * knee["rate_per_s"] < rate + 50
    assert knee["rows"] and "c9ed275" in knee["commit"]

    # each label is the selector of exactly one resident Service, by the
    # reference's own matcher
    from reference.default_provider_adaptive import Reference
    services = [cluster.service_label(k) for k in range(5000)]
    rows = cluster.node_rows(spec.overlaid(cfg, {"nodes": {"count": 6}}))
    ref = Reference(rows, {"default": services}, 0)
    f = PodFactory(tr, 5000, 2 ** 31 + 7)
    descs = [f.make(f"p-{j}")[1] for j in range(4000)]
    for d in {id(d): d for d in descs}.values():
        sels = ref._selectors(d)
        assert len(sels) == 1 and dict(sels[0]) == dict(d["labels"])

    # the same seed the same draw; a window of 70 holds about 27 Services
    # and one of 150 about 40, so sixteen groups cut most windows
    g = PodFactory(tr, 5000, 2 ** 31 + 7)
    drawn = [d["labels"] for d in descs]
    assert drawn == [g.make(f"p-{j}")[1]["labels"] for j in range(4000)]
    first = tuple(cluster.service_label(0).items())
    assert 0.22 < drawn.count(first) / 4000 < 0.28
    for size, lo, hi in ((70, 22, 32), (150, 34, 46)):
        held = [len(set(drawn[i:i + size]))
                for i in range(0, 4000 - size, size)]
        assert lo < sum(held) / len(held) < hi
    due = due_times(tr["arrival"], 30.0, 2 ** 31 + 7)
    assert 0.85 * rate * 30 < len(due) < 1.15 * rate * 30


def test_every_metric_the_cell_lists_has_its_file():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["startup_p50_ms", "setup_s"]
    layer = names(cell, "per_layer")
    third = names(spec.find_cell(bench, CELL3), "per_layer")
    # everything cell 3 reports, and what the deployment adds
    assert set(third) <= set(layer)
    assert set(COUNTED) | set(TRACED) <= set(layer) - set(third)
    assert not [n for n in layer if n.endswith(".backlog")]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in layer:
        mf = spec.load_metric(name)
        reader = importlib.import_module(f"readers.{mf['reader']}")
        assert callable(reader.read)
        if name in COUNTED or name in TRACED:
            # the scan path under an open loop: no K-batch arrival cell
            assert not reports(bench, CELL3, name)
            assert entries[name]["moves"] == "startup_p50_ms"
    for name, (family, labels) in COUNTED.items():
        mf = spec.load_metric(name)
        assert mf["reader"] == "counter_delta_per_pod"
        assert mf["args"]["family"] == family
        assert mf["args"].get("labels") == labels
        assert entries[name]["source"] == "program_counter"
    # appended: the cell comes after cell 3
    assert reporting(bench, "startup_p50_ms").index(NEW) > \
        reporting(bench, "startup_p50_ms").index(CELL3)


def test_the_counted_metrics_read_the_counters_and_nothing_as_zero():
    from readers import counter_delta_per_pod
    ctx = {"pods_bound": 1000, "counters": {
        "scheduler_burst_segment_cuts_total": {("groups",): 20.0,
                                               ("end",): 12.0},
        "tpu_selector_walk_services_total": {(): 2_500_000.0},
        "tpu_scan_spread_groups_total": {(): 500.0}}}
    read = lambda name, c: counter_delta_per_pod.read(
        c, **spec.load_metric(name)["args"])
    assert read("segment_group_cuts_per_pod.arrivals", ctx) == 0.02
    assert read("segment_class_cuts_per_pod.arrivals", ctx) == 0.0
    assert read("selector_services_tested_per_pod.arrivals", ctx) == 2500.0
    assert read("spread_groups_per_pod.arrivals", ctx) == 0.5
    # a commit without the counters (the parent): 0, and nothing raised
    bare = {"pods_bound": 1000, "counters": {}}
    for name in COUNTED:
        assert read(name, bare) == 0.0
        assert read(name, {**bare, "pods_bound": 0}) is None
