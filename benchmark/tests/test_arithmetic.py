"""Percentiles, interval unions, due times and the trace helpers on fixed
inputs."""
import math

from lib import spec
from lib import trace as tr
from lib.stats import percentile
from lib.traffic import due_times


def test_percentile_fixed_inputs():
    xs = [5, 1, 4, 2, 3]
    assert percentile(xs, 0.0) == 1
    assert percentile(xs, 0.5) == 3
    assert percentile(xs, 1.0) == 5
    assert percentile(xs, 0.95) == 4.8
    assert percentile([7], 0.99) == 7
    assert math.isnan(percentile([], 0.5))
    import numpy as np
    ys = [0.3, 9.1, 2.2, 4.4, 8.0, 1.5, 6.6]
    for q in (0.1, 0.5, 0.95, 0.99):
        assert abs(percentile(ys, q) - np.percentile(ys, 100 * q)) < 1e-12


def test_due_times_poisson():
    arr = {"process": "poisson", "rate_per_s": 500.0}
    a = due_times(arr, 4.0, seed=7)
    assert a == due_times(arr, 4.0, seed=7)          # the seed fixes them
    assert a != due_times(arr, 4.0, seed=8)
    assert all(0 <= x < 4.0 for x in a) and a == sorted(a)
    assert abs(len(a) - 2000) < 5 * math.sqrt(2000)  # Poisson count
    assert len(due_times(arr, 4.0, seed=2**31 + 99)) > 0   # large seeds


def test_merge_and_gap_naming():
    assert tr.merge([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    busy = [[0, 10], [30, 40], [100, 110]]
    host = [(8, 25, "sched.pump"), (25, 28, "client.create"),
            (50, 90, "client.watch_drain")]
    gaps = tr.name_gaps(busy, host)
    # between busy intervals: pump 15, create 3, nothing 2 + 20, drain 40;
    # before the first and after the last busy interval no span reaches
    assert gaps == {"sched.pump": 15, "client.create": 3, tr.NO_SPAN: 22,
                    "client.watch_drain": 40}
    lead = tr.name_gaps([[10, 20]], [(0, 12, "sched.pump"), (18, 30, "loop.step")])
    assert lead == {"sched.pump": 10, "loop.step": 10}
    assert tr.clean("%while.62 = (u32[5,16385]{0,1:T(8,128)}) while(%x)") == "while.62"
    assert tr.clean("jit__scatter_rows(17137043916951534555)") == "jit__scatter_rows"
    assert tr.clean("copy") == "copy"
    assert tr.top({"a": 2e9, "b": 3e9}, 1e-9) == [["b", 3.0], ["a", 2.0]]


def test_overlaid_merges_objects_and_leaves_the_base():
    base = {"a": {"x": 1, "y": 2}, "b": [1, 2], "c": 3}
    got = spec.overlaid(base, {"a": {"y": 5}, "b": [9], "d": None})
    assert got == {"a": {"x": 1, "y": 5}, "b": [9], "c": 3, "d": None}
    assert base == {"a": {"x": 1, "y": 2}, "b": [1, 2], "c": 3}
    assert spec.overlaid(base, None) == base
