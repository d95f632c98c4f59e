"""The trace reducer on the small trace recorded on the chip and kept here
(`small.xplane.pb`: three launches of a tiny jitted program on one TPU v5
lite inside the benchmark's span names; `tools/record_trace.py` made it)."""
import os

import pytest

from lib import trace as tr

SMALL = os.path.join(os.path.dirname(__file__), "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_reduce_small_trace():
    red = tr.reduce_xplane(SMALL)
    assert red is not None and red["devices"] == 1
    assert tr.OPS_LINE in red["line_names"]
    assert 0 < red["busy_s"] < 0.05
    assert red["device_ops"] and all(s > 0 for _n, s in red["device_ops"])
    assert abs(sum(red["busy_s_per_device"]) - red["busy_s"]) < 1e-12
    assert red["host_spans"] == 6        # 3 x client.create, 3 x schedule_burst
    assert red["collective_s"] == 0.0
    # the program was launched three times
    assert any(m["launches"] == 3 for m in red["modules"].values())
    named = dict(red["idle_gaps"])
    assert "client.create" in named and named["client.create"] > 0.004
