"""The loader refuses what the data files may not hold."""
import json
import os
import shutil

import pytest

from lib import spec


@pytest.fixture()
def root(tmp_path):
    """A copy of the benchmark's data files that a test may spoil, with the
    code files the traffic mixes name (their kinds and processes)."""
    dst = tmp_path / "repo"
    os.makedirs(dst / "benchmark")
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), dst)
    for d in ("configs", "traffic", "metrics", "shapes", "arrivals"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, d), dst / "benchmark" / d)
    shutil.copy(os.path.join(spec.BENCH_DIR, "peaks.json"), dst / "benchmark")
    return str(dst)


def edit(path, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


def test_committed_files_load(root):
    bench = spec.load_benchmark(root)
    for cell in bench["workloads"]:
        spec.load_config(bench, cell["config"], root)
        spec.load_traffic(cell["traffic"], root)
        assert spec.metrics_for(bench, cell, "end_to_end")
        for m in spec.metrics_for(bench, cell, "per_layer"):
            spec.load_metric(m["name"], root)
    assert spec.load_peaks("TPU v5 lite", root)["hbm_bytes_per_s"] == 819e9


def test_every_cell_reports_setup_and_one_more(root):
    bench = spec.load_benchmark(root)
    for cell in bench["workloads"]:
        names = [m["name"] for m in spec.metrics_for(bench, cell, "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_for(bench, cell, "per_layer")


@pytest.mark.parametrize("spoil", [
    lambda b: b.update(extra=1),
    lambda b: b["workloads"][0].update(note="x"),
    lambda b: b["end_to_end"][0].update(why="x"),
    lambda b: b["end_to_end"][0].update(name="pods per s"),
    lambda b: b["end_to_end"][0].update(unit="pods per second"),
    lambda b: b["end_to_end"][0].update(better="faster"),
    lambda b: b["per_layer"][0].update(source="guess"),
    lambda b: b["workloads"][0].update(chips=2),
    lambda b: b["per_layer"].append(dict(b["per_layer"][0])),
])
def test_benchmark_json_refused(root, spoil):
    edit(os.path.join(root, "BENCHMARK.json"), spoil)
    with pytest.raises(spec.SpecError):
        spec.load_benchmark(root)


@pytest.mark.parametrize("spoil", [
    lambda c: c.update(speedup=2),
    lambda c: c["nodes"].update(racks=4),
    lambda c: c["scheduler"].update(mesh="maybe"),
    lambda c: c["check"].pop("first_binds"),
    lambda c: c["nodes"].update(count="many"),
])
def test_config_refused(root, spoil):
    bench = spec.load_benchmark(root)
    edit(os.path.join(root, "benchmark/configs/headline-15000n.json"), spoil)
    with pytest.raises(spec.SpecError):
        spec.load_config(bench, "headline-15000n", root)


@pytest.mark.parametrize("name,spoil", [
    ("backlog-10k", lambda t: t.update(burst=3)),
    ("backlog-10k", lambda t: t.update(kind="half_open")),
    ("backlog-10k", lambda t: t["pod_shapes"][0].update(kind="gpu")),
    # kinds no committed cell drives and no reference states are not carried
    ("backlog-10k", lambda t: t["pod_shapes"][0].update(kind="anti-affinity")),
    ("backlog-10k", lambda t: t["pod_shapes"][0].update(priority=7)),
    ("backlog-10k", lambda t: t["pod_shapes"][0]["requests"].update(sigma=0.5)),
    ("rollout-1k", lambda t: t["service_choice"].update(policy="zipf")),
    ("arrivals-steady", lambda t: t["arrival"].update(process="onoff")),
    ("arrivals-steady", lambda t: t.update(service_choice={"policy": "per-cycle"})),
    ("backlog-10k", lambda t: t["pod_shapes"][0].update(share=0.5)),
    ("backlog-10k", lambda t: t.pop("backlog")),
    ("rollout-1k", lambda t: t["service_choice"].update(policy="random")),
    ("arrivals-steady", lambda t: t["arrival"].update(process="uniform")),
    ("arrivals-steady", lambda t: t["serve"].update(threads=4)),
    ("arrivals-steady", lambda t: t.pop("lifetime_s")),
])
def test_traffic_refused(root, name, spoil):
    edit(os.path.join(root, f"benchmark/traffic/{name}.json"), spoil)
    with pytest.raises(spec.SpecError):
        spec.load_traffic(name, root)


def test_metric_file_and_peaks_refused(root):
    edit(os.path.join(root, "benchmark/metrics/warmup_s.json"),
         lambda m: m.update(unit="s"))
    with pytest.raises(spec.SpecError):
        spec.load_metric("warmup_s", root)
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9", root)
    with pytest.raises(spec.SpecError):
        spec.find_cell(spec.load_benchmark(root), "no-such.cell")
