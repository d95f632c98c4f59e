"""The data files this PR adds load, and say what the issue asked of them."""
import json
import os

from cells import reporting, reports
from lib import spec

ADAPTIVE = "headline-15000n-adaptive.backlog-10k"
NEAR_KNEE = "headline-15000n.arrivals-near-knee"


def test_adaptive_config_is_the_headline_at_the_default_percentage():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "headline-15000n-adaptive")
    base = spec.load_config(bench, "headline-15000n")
    assert cfg["scheduler"]["percentage_of_nodes_to_score"] == 0
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["check"] == {"first_binds": 10000, "sampled_binds": 10000}
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    changed = {k for k in base if base[k] != cfg[k]}
    assert changed == {"name", "source", "deployment", "scheduler",
                       "guarantees", "reference", "check", "assumed"}
    assert {k for k in base["scheduler"]
            if base["scheduler"][k] != cfg["scheduler"][k]} == \
        {"percentage_of_nodes_to_score"}
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {"decisions"}
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []


def test_adaptive_cell_reports_its_metrics():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, ADAPTIVE)
    assert cell["traffic"] == "backlog-10k" and cell["chips"] == 1
    e2e = [m["name"] for m in spec.metrics_for(bench, cell, "end_to_end")]
    assert e2e == ["pods_per_s", "setup_s"]
    layer = [m["name"] for m in spec.metrics_for(bench, cell, "per_layer")]
    first = [m["name"] for m in spec.metrics_for(
        bench, spec.find_cell(bench, "headline-15000n.backlog-10k"),
        "per_layer")]
    # cell 1's metrics, with the scan's roofline in place of the K-batch
    # one, and what only a scan launch or a truncated walk books
    assert set(first) - set(layer) == {
        "schedule_batch_uniform_roofline.backlog"}
    assert "schedule_batch_roofline.backlog" in set(layer) - set(first)
    assert not reports(bench, ADAPTIVE,
                       "schedule_batch_uniform_roofline.backlog")
    for name in layer:
        spec.load_metric(name)
    for name in ("walk_nodes_per_pod.backlog", "scan_steps_per_pod.backlog",
                 "stack_wall_share.backlog"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["moves"] == "pods_per_s" and name in layer
        # cell 1 reads them too (nothing booked there), the arrival cells not
        assert reports(bench, "headline-15000n.backlog-10k", name)
        assert NEAR_KNEE not in reporting(bench, name)


def test_near_knee_traffic_is_arrivals_steady_at_another_rate():
    bench = spec.load_benchmark()
    near = spec.load_traffic("arrivals-near-knee")
    steady = spec.load_traffic("arrivals-steady")
    assert near["arrival"]["rate_per_s"] == 5200
    assert near["knee"]["rate_per_s"] == 6500 and near["knee"]["share"] == 0.8
    assert {k for k in steady if steady[k] != near[k]} == \
        {"arrival", "knee", "why"}
    cell = spec.find_cell(bench, NEAR_KNEE)
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    steady_cell = spec.find_cell(bench, "headline-15000n.arrivals-steady")
    for group in ("end_to_end", "per_layer"):
        assert names(cell, group) == names(steady_cell, group)


def test_benchmark_json_stays_inside_its_limits():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    bench = json.loads(raw)
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200
