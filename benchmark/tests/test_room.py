"""`BENCHMARK.json` after PR 56: a per-layer metric that every cell of its
end-to-end metric reports carries no `workloads` list (`spec.metrics_for`'s
rule says the same), eleven metrics are retired, and every cell reports what
it reported on the parent tree less those. `parent_per_layer.json` is the
parent's answer, written down from the tree before PR 56: `order` the 121
entries, `cells` each cell's metrics as indexes into it."""
import json
import os

import pytest

from lib import spec

RETIRED = {
    "queue_share.backlog", "queue_share.arrivals", "encode_share.backlog",
    "encode_share.arrivals", "fetch_share.backlog", "fetch_share.arrivals",
    "commit_share.backlog", "commit_share.arrivals", "fanout_share.backlog",
    "rotation_gather_steps_per_pod.backlog",
    "spread_carry_full_launch_share.backlog"}
# PR 56 lists every truncated-walk scan cell under the two walk metrics
WALK = {"walk_exhausted_share.backlog", "walk_unschedulable_per_pod.backlog"}
WALK_JOINED = {"headline-15000n-adaptive.backlog-10k",
               "load-5000n-150k.rollouts-1k-8svc",
               "colocated-5000n-150k.rollouts-1k-8svc-jobs",
               "loadmix-5000n-150k.rollouts-1k-111svc"}

with open(os.path.join(os.path.dirname(__file__),
                       "parent_per_layer.json")) as _f:
    PARENT = json.load(_f)


def test_the_entries_are_the_parents_less_the_retired_in_order():
    bench = spec.load_benchmark()
    assert [m["name"] for m in bench["per_layer"]] == \
        [n for n in PARENT["order"] if n not in RETIRED]
    assert RETIRED <= set(PARENT["order"])


@pytest.mark.parametrize("cell", sorted(PARENT["cells"]))
def test_cell_reports_the_parents_metrics_less_the_retired(cell):
    bench = spec.load_benchmark()
    entry = spec.find_cell(bench, cell)
    want = {PARENT["order"][i] for i in PARENT["cells"][cell]} - RETIRED
    if cell in WALK_JOINED:
        want |= WALK
    got = [m["name"] for m in spec.metrics_for(bench, entry, "per_layer")]
    assert got == [n for n in PARENT["order"] if n in want]
    for name in got:
        spec.load_metric(name)


def test_no_list_says_what_leaving_it_out_says():
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    full = {m["name"]: set(m.get("workloads", cells))
            for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if "workloads" in m:
            assert m["workloads"], m["name"]
            assert set(m["workloads"]) < full[m["moves"]], m["name"]
            assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]


def test_the_file_has_room_and_is_written_as_it_is_read():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) < 64 * 1024
    assert json.dumps(json.loads(raw), indent=1) == raw


def test_a_retired_metric_has_no_entry_and_the_ledger_reader_is_gone():
    bench = spec.load_benchmark()
    assert not RETIRED & {m["name"] for m in bench["per_layer"]}
    assert not os.path.exists(os.path.join(
        spec.BENCH_DIR, "readers", "ledger_phase_share.py"))
    for name in os.listdir(os.path.join(spec.BENCH_DIR, "metrics")):
        assert spec.load_metric(name[:-len(".json")])["reader"] != \
            "ledger_phase_share"
