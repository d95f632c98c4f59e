"""A whole run of each traffic kind at 50 nodes on the CPU backend: `correct`
comes out true; with the timed path broken underneath (one binding altered
where it is committed) or with a guarantee broken (the control: only half the
nodes scored) it comes out false. The look for a chip is skipped
(`rehearse=True`); the rest of the run is the command's own."""
import pytest

import run

BACKLOG = "headline-15000n.backlog-10k"
ROLLOUT = "density-5000n-150k.rollout-1k"
ARRIVALS = "headline-15000n.arrivals-steady"


LIGHT = {
    BACKLOG: {"warm_binds": 0, "backlog": 70},
    ROLLOUT: {"warm_binds": 0, "backlog": 70},
    ARRIVALS: {"warm_binds": 0, "arrival": {"rate_per_s": 150.0},
               "lifetime_s": 0.4, "serve": {"window_size": 64}},
}


def small(cell, nodes=50):
    cfg = {"nodes": {"count": nodes},
           "check": {"first_binds": 200, "sampled_binds": 60}}
    if cell == ROLLOUT:
        cfg["resident"] = {"pods_per_node": 6, "services": 5}
    return cfg


def go(cell, seed, nodes=50, program=None, hook=None):
    return run.execute(cell, seed, 1.5, False, rehearse=True, hook=hook,
                       overrides={"config": small(cell, nodes),
                                  "traffic": LIGHT[cell], "program": program})


@pytest.mark.parametrize("cell,seed", [(BACKLOG, 1), (ROLLOUT, 2**31 + 5),
                                       (ARRIVALS, 3)])
def test_sound_run_is_correct(cell, seed):
    out = go(cell, seed)
    res = out["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert out["report"]["compared"] > 0
    assert out["report"]["compiles_in_window"] == 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    # each number compared beside its limit, last in the line
    assert list(res)[-1] == "compared" and "bindings_differ" in res["compared"]
    assert all(c["value"] <= c["limit"] for c in res["compared"].values())
    rep = out["report"]
    if "pods_per_s" in res["metrics"]:
        # all the window's binds over all the window's time
        assert res["metrics"]["pods_per_s"]["value"] == \
            res["attempted"] / rep["window_s"]
        assert 0 < rep["pending_s"] < rep["window_s"]


def altered_binding(prefix):
    """Break the timed path where an answer is produced: the first window
    pod's binding is committed to the node of the second."""
    def hook(sched, store):
        orig = store.commit_wave
        state = {"done": False}

        def commit_wave(bindings, *a, **kw):
            if not state["done"] and len(bindings) > 1 \
                    and prefix in bindings[0][0] \
                    and bindings[0][1] != bindings[1][1]:
                bindings = [(bindings[0][0], bindings[1][1])] + list(bindings[1:])
                state["done"] = True
            return orig(bindings, *a, **kw)
        store.commit_wave = commit_wave
    return hook


@pytest.mark.parametrize("cell,prefix", [(BACKLOG, "/bl-1-"),
                                         (ROLLOUT, "/bl-0-")])
def test_altered_binding_is_not_correct(cell, prefix):
    out = go(cell, 11, hook=altered_binding(prefix))
    assert out["result"]["correct"] is False
    over = {k for k, c in out["result"]["compared"].items()
            if c["value"] > c["limit"]}
    assert "bindings_differ" in over


def test_control_half_the_nodes_scored_is_not_correct():
    """The control breaks the configuration's 'every node is scored': the
    program is told 50%, the reference keeps judging at the file's 100%."""
    half = {"scheduler": {"percentage_of_nodes_to_score": 50}}
    out = go(BACKLOG, 5, nodes=300, program=half)
    assert out["result"]["correct"] is False
    assert go(BACKLOG, 5, nodes=300)["result"]["correct"] is True
