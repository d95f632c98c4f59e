"""The cell `headline-15000n-adaptive.backlog-10k` rehearsed at 240 nodes on
the CPU backend (num_to_find = 117 of 240, so the walk is cut short and
last_index goes round): `correct` comes out true; false for an altered
binding; false for the control, where the program scores every node while the
reference keeps judging by the file's default percentage."""
import run
from test_rehearsal import altered_binding

CELL = "headline-15000n-adaptive.backlog-10k"


def go(seed, program=None, hook=None):
    return run.execute(CELL, seed, 1.5, False, rehearse=True, hook=hook,
                       overrides={
                           "config": {"nodes": {"count": 240},
                                      "check": {"first_binds": 200,
                                                "sampled_binds": 200}},
                           "traffic": {"warm_binds": 0, "backlog": 150},
                           "program": program})


def test_sound_run_is_correct():
    out = go(2**31 + 17)
    res, rep = out["result"], out["report"]
    assert res["correct"] is True and res["failed"] == 0
    assert rep["compared"] > 300 and rep["compiles_in_window"] == 0
    moved = rep["counters"]
    # the truncated regime on the generic scan, never the K-batch kernel
    assert "burst_uniform" not in moved["tpu_device_dispatch_total"]
    assert set(moved["tpu_walk_nodes_evaluated_total"]) == {"truncated"}
    # on empty nodes every walk stops after exactly num_to_find nodes
    assert moved["tpu_walk_nodes_evaluated_total"]["truncated"] == \
        117 * res["attempted"]
    steps = moved["tpu_scan_steps_total"]
    assert steps["real"] == res["attempted"]
    # the pod count is the loop's trip count: no step runs for a pad row
    assert steps.get("pad", 0) == 0


def test_altered_binding_is_not_correct():
    out = go(11, hook=altered_binding("/bl-1-"))
    assert out["result"]["correct"] is False


def test_control_every_node_scored_is_not_correct():
    full = {"scheduler": {"percentage_of_nodes_to_score": 100}}
    assert go(5, program=full)["result"]["correct"] is False
