"""The data files and the two code files PR 32 adds: the configuration is the
density envelope at upstream's default percentage and nothing else, its cell
reports cell 2's metrics and the rotation path's, the byte model's arithmetic
by hand, and the named-scope reader on the trace recorded on the chip."""
import gzip
import os
import shutil

import pytest

from cells import reporting, reports
from lib import spans as sp
from lib import spec
from readers import trace_named_scope_time, trace_program_roofline
from roofline import bytes as rb
from roofline import bytes_rotation as rot

NEW = "density-5000n-150k-adaptive.rollout-1k"
CELL2 = "density-5000n-150k.rollout-1k"
CELL5 = "headline-15000n-adaptive.backlog-10k"
SPANS_GZ = os.path.join(os.path.dirname(__file__), "spans.xplane.pb.gz")


def test_config_is_the_density_envelope_at_the_default_percentage():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "density-5000n-150k-adaptive")
    base = spec.load_config(bench, "density-5000n-150k")
    assert cfg["scheduler"]["percentage_of_nodes_to_score"] == 0
    assert cfg["reference"] == "default_provider_adaptive"
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert "generic_scheduler.go:434-453" in cfg["source"]
    assert "density.go:56" in cfg["source"]
    assert {k for k in base if base[k] != cfg[k]} <= {
        "name", "source", "deployment", "scheduler", "guarantees",
        "reference", "check", "assumed"}
    assert {k for k in base["scheduler"]
            if base["scheduler"][k] != cfg["scheduler"][k]} == \
        {"percentage_of_nodes_to_score"}
    assert {k for k in base["guarantees"]
            if base["guarantees"][k] != cfg["guarantees"][k]} == {"decisions"}
    assert cfg["check"]["first_binds"] >= base["check"]["first_binds"]
    assert cfg["check"]["sampled_binds"] >= base["check"]["sampled_binds"]
    assert not any("bench.py" in line for line in cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    # 5000 nodes at the default: 10%, 500 nodes found a decision
    from reference.default_provider_adaptive import num_to_find
    assert num_to_find(cfg["nodes"]["count"], 0) == 500


def test_cell_reports_cell_2s_metrics_and_the_rotation_paths():
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, NEW)
    assert cell["traffic"] == "rollout-1k" and cell["chips"] == 1
    names = lambda c, g: [m["name"] for m in spec.metrics_for(bench, c, g)]
    assert names(cell, "end_to_end") == ["pods_per_s", "setup_s"]
    layer = names(cell, "per_layer")
    for name in layer:
        spec.load_metric(name)
    second = names(spec.find_cell(bench, CELL2), "per_layer")
    assert set(second) - set(layer) == set()
    # what the truncated walk on a rotating order adds to cell 2's
    assert set(layer) - set(second) >= {
        "walk_nodes_per_pod.backlog", "scan_steps_per_pod.backlog",
        "stack_wall_share.backlog", "kernel_rotate_us_per_pod.backlog",
        "schedule_batch_rotation_roofline.backlog"}
    # the rotation's own metrics: both density cells, and cell 5 (an even
    # tree on the axis) the step count only
    for name in ("rotation_position_steps_per_pod.backlog",
                 "rotation_wall_share.backlog"):
        assert reports(bench, NEW, name) and reports(bench, CELL2, name)
    assert reports(bench, CELL5, "rotation_position_steps_per_pod.backlog")
    assert not reports(bench, CELL5, "rotation_wall_share.backlog")
    every = [w["name"] for w in bench["workloads"]]
    for name in ("warmup_s", "compiles_in_window",
                 "program_compiles_in_window"):
        assert reporting(bench, name) == every
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        assert len(f.read().encode()) <= 64 * 1024
    assert len(cell["why"]) <= 200


def test_rotation_bytes_by_hand():
    # 5000 nodes in 8192 rows, a rollout of 1000: the scan's planes (125
    # bytes a row, 4 a pod), two order tables of 4 x 8192 int32, 4 a pod
    assert rb.schedule_batch(8192, 1000) == 8192 * 125 + 4000
    assert rot.schedule_batch_rotation(8192, 1000, 5000) == \
        8192 * 125 + 4000 + 2 * 4 * 8192 * 4 + 4000
    assert rot.schedule_batch_rotation(8192, 1000) \
        - rb.schedule_batch(8192, 1000) == 262144 + 4000
    # once a launch, never a row of a table per pod
    assert rot.schedule_batch_rotation(8192, 2000) \
        - rot.schedule_batch_rotation(8192, 1000) == 8000
    ctx = {"trace": {"devices": 1, "modules": {
        "jit__schedule_batch_jit": {"seconds": 0.9, "launches": 3.0}}},
        "peaks": {"hbm_bytes_per_s": 819e9},
        "cfg": {"nodes": {"count": 5000}}, "trace_pods_bound": 3000}
    got = trace_program_roofline.read(ctx, "jit__schedule_batch_jit",
                                      "schedule_batch_rotation",
                                      module="bytes_rotation")
    want = 100.0 * (1294144 * 3 / 819e9) / 0.9
    assert abs(got - want) < 1e-12 and 0 < got < 0.01


@pytest.mark.skipif(not os.path.exists(SPANS_GZ), reason="no recorded trace")
def test_named_scope_reader_on_the_recorded_trace(tmp_path, monkeypatch):
    path = str(tmp_path / "spans.xplane.pb")
    with gzip.open(SPANS_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(sp, "find_xplane", lambda: path)
    ctx = {"trace": {"devices": 1}, "trace_pods_bound": 10}
    # a scope the module lists reads as its own reader reads it
    for scope in ("filter", "pick"):
        want = sp.load(path)["scope_ns"][scope] / 1e3 / 10
        assert trace_named_scope_time.read(ctx, scope) == want > 0
    # a scope no recorded program opens: time 0, and the module's tuple is
    # put back
    assert trace_named_scope_time.read(ctx, "rotate") == 0.0
    assert sp.SCOPES == ("filter", "score", "pick", "fold")
    assert trace_named_scope_time.read({**ctx, "trace": None}, "rotate") is None
