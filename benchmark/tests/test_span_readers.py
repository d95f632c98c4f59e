"""The two readers of the program's spans and the kernels' scopes
(`readers/trace_span_share.py`, `readers/trace_scope_time.py`) and what they
share (`lib/spans.py`): on fixed inputs, and on the small trace recorded on
the chip and kept here (`spans.xplane.pb.gz`: two windows of a 48-node
scheduler on one TPU v5 lite inside the benchmark's span names;
`tools/record_spans_trace.py` made it). Also the stall finder's reduction
(`tools/stalls.py`) on a fixed ring."""
import gzip
import os
import shutil
import sys

import pytest

from lib import spans as sp
from lib import trace as tr
from readers import counter_delta, trace_scope_time, trace_span_share

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import stalls  # noqa: E402

SPANS_GZ = os.path.join(os.path.dirname(__file__), "spans.xplane.pb.gz")


def test_flatten_gives_self_time_to_the_innermost_span():
    nested = [(0, 100, "sched.schedule_burst"), (10, 90, "burst.plan"),
              (20, 40, "burst.encode"), (25, 30, "burst.encode.nodes"),
              (50, 80, "burst.wave.commit"), (55, 60, "burst.commit.store"),
              (110, 120, "client.create")]
    seg = sp.flatten(nested)
    assert seg == [
        (0, 10, "sched.schedule_burst"), (10, 20, "burst.plan"),
        (20, 25, "burst.encode"), (25, 30, "burst.encode.nodes"),
        (30, 40, "burst.encode"), (40, 50, "burst.plan"),
        (50, 55, "burst.wave.commit"), (55, 60, "burst.commit.store"),
        (60, 80, "burst.wave.commit"), (80, 90, "burst.plan"),
        (90, 100, "sched.schedule_burst"), (110, 120, "client.create")]
    self_time: dict = {}
    for s, e, name in seg:
        self_time[name] = self_time.get(name, 0) + e - s
    # a span's duration minus what its children cover
    assert self_time["burst.plan"] == 80 - 20 - 30
    assert self_time["burst.encode"] == 20 - 5
    assert self_time["burst.wave.commit"] == 30 - 5
    assert sum(self_time.values()) == 100 + 10     # nothing counted twice
    # a span that leaks past its parent is cut to it; an empty one is dropped
    assert sp.flatten([(0, 10, "a"), (5, 15, "b"), (7, 7, "c")]) == [
        (0, 5, "a"), (5, 10, "b")]


def test_matches_scope_of_and_wire_reader():
    assert sp.matches("pump.pods", ["pump."])
    assert sp.matches("burst.encode", ["burst.encode"])
    assert not sp.matches("burst.encode.nodes", ["burst.encode"])
    assert sp.scope_of("jit(f)/jit(main)/while/body/filter/add") == "filter"
    assert sp.scope_of("jit(f)/while/body/pick/score/mul") == "score"
    assert sp.scope_of("jit(f)/while/body/closed_call/add") is None
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab" + bytes(
        [0x1D, 1, 0, 0, 0])
    assert list(sp._fields(msg)) == [(1, 0, 300), (2, 2, b"ab"),
                                     (3, 5, bytes([1, 0, 0, 0]))]


def fake_ctx(**kw):
    ctx = {"trace": {"devices": 1}, "trace_window_s": 1e-6,
           "trace_pods_bound": 10, "counters": {}}
    ctx.update(kw)
    return ctx


def test_readers_on_fixed_segments(monkeypatch):
    """1000 ns traced; busy [0,100] and [600,700]: idle 100..600 and the
    tail 700..1000 (the host spans reach 1000)."""
    segments = sp.flatten([
        (0, 1000, "sched.schedule_burst"), (50, 900, "burst.plan"),
        (100, 300, "burst.encode"), (150, 250, "burst.scatter"),
        (300, 500, "burst.fetch")])
    got = {"segments": segments, "busy0": [[0, 100], [600, 700]],
           "spans": {"sched.schedule_burst": 1, "burst.plan": 1,
                     "burst.encode": 1, "burst.scatter": 1,
                     "burst.fetch": 1},
           "scope_ns": {"filter": 4000.0, "score": 0.0, "pick": 1000.0,
                        "fold": 0.0},
           "scoped_programs": ["jit_x(1)"]}
    monkeypatch.setattr(sp, "find_xplane", lambda: "x")
    monkeypatch.setattr(sp, "load", lambda path: got)
    ctx = fake_ctx()
    share = lambda spans: trace_span_share.read(  # noqa: E731
        ctx, "wall_share", spans)
    assert share(["burst.encode"]) == pytest.approx(10.0)   # 200 - 100
    assert share(["burst.scatter", "burst.fetch"]) == pytest.approx(30.0)
    assert share(["burst.plan"]) == pytest.approx(45.0)     # 850 - 400
    assert share(["pump."]) == 0.0
    # idle 800: encode 100..150 + 250..300, scatter 150..250, fetch
    # 300..500, plan 500..600 + 700..900, the wrapper alone 900..1000
    assert trace_span_share.read(ctx, "idle_unnamed") == pytest.approx(
        100.0 * 100 / 800)
    assert trace_scope_time.read(ctx, "filter") == pytest.approx(0.4)
    assert trace_scope_time.read(ctx, "fold") == 0.0
    # an older program: no span and no scope of its own -> nothing
    old = dict(got, spans={"sched.schedule_burst": 1}, scoped_programs=[])
    monkeypatch.setattr(sp, "load", lambda path: old)
    assert trace_span_share.read(ctx, "wall_share", ["pump."]) is None
    assert trace_span_share.read(ctx, "idle_unnamed") is None
    assert trace_scope_time.read(ctx, "filter") is None
    # no trace at all (a CPU rehearsal)
    assert trace_span_share.read(fake_ctx(trace=None), "idle_unnamed") is None
    monkeypatch.setattr(sp, "find_xplane", lambda: None)
    assert trace_scope_time.read(ctx, "pick") is None


def test_counter_delta_tells_absent_from_unmoved():
    import kubernetes_tpu.core.tpu_scheduler  # noqa: F401  (the family)
    assert counter_delta.read(fake_ctx(), "no_such_family_total") is None
    moved = {"tpu_device_dispatch_total": {("scatter",): 3.0, ("x",): 2.0}}
    ctx = fake_ctx(counters=moved)
    assert counter_delta.read(ctx, "tpu_device_dispatch_total") == 5.0
    assert counter_delta.read(ctx, "tpu_device_dispatch_total",
                              ["scatter"]) == 3.0
    assert counter_delta.read(fake_ctx(), "tpu_device_dispatch_total") == 0.0


@pytest.mark.skipif(not os.path.exists(SPANS_GZ), reason="no recorded trace")
def test_recorded_trace_has_program_spans_and_scopes(tmp_path):
    path = str(tmp_path / "spans.xplane.pb")
    with gzip.open(SPANS_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    got = sp.load(path)
    names = got["spans"]
    # two windows, each one plan, one commit wave, one fetch
    for name in ("burst.plan", "burst.encode", "burst.dispatch",
                 "burst.fetch", "burst.wave.commit", "burst.commit.store"):
        assert names.get(name) == 2, (name, names)
    assert names["pump.pods"] >= 4 and names["client.create"] == 2
    seg = got["segments"]
    assert all(a[1] <= b[0] for a, b in zip(seg, seg[1:]))   # disjoint
    self_ns: dict = {}
    for s, e, name in seg:
        self_ns[name] = self_ns.get(name, 0) + e - s
    assert self_ns["burst.fetch"] > 0 and self_ns["burst.plan"] > 0
    # the uniform kernel's stages are on the device, under their scopes
    assert any(p.startswith("jit__schedule_batch_uniform")
               for p in got["scoped_programs"])
    assert got["scope_ns"]["filter"] > 0 and got["scope_ns"]["pick"] > 0
    # leaf operations only: the scopes together cannot pass the busy time
    busy = sum(e - s for s, e in got["busy0"])
    assert 0 < sum(got["scope_ns"].values()) <= busy
    # every idle gap of the chip has an innermost span to go to
    gaps = tr.name_gaps(got["busy0"], seg)
    assert gaps.get(tr.NO_SPAN, 0.0) < 0.1 * sum(gaps.values())


def test_stalls_reduction_on_a_fixed_ring():
    def ev(name, ts, dur, window=1, tid=1, **args):
        return {"name": name, "ts": ts, "dur": dur, "tid": tid,
                "args": {"window": window, **args}}
    ring = []
    for k in range(6):       # six windows 100 ms apart, 10 ms each
        t = k * 100_000
        slow = 40_000 if k == 4 else 0
        ring += [ev("burst.plan", t, 10_000 + slow, window=k),
                 ev("burst.encode", t + 1_000, 2_000, window=k),
                 ev("burst.fetch", t + 4_000, 3_000 + slow, window=k),
                 ev("burst.wave.device", t + 3_500, 3_600 + slow, window=k),
                 ev("loop.step", t - 100, 10_200 + slow, window=k)]
    found = stalls.analyse(ring, window_s=0.52, factor=10, gap_ms=50)
    assert found["windows"] == 6 and found["spans_per_window"] == 3.0
    (slow,) = found["slow_leaf_spans"]       # the leaf, not its parent
    assert slow["name"] == "burst.fetch" and slow["window"] == 4
    assert slow["chain"] == ["loop.step", "burst.plan"]
    assert slow["ms"] == 43.0
    # 90 ms between windows, but 50 ms after the slow one
    assert [round(g["ms"]) for g in found["uncovered"]] == [90, 90, 90, 90]
    assert found["by_name"]["burst.encode"]["count"] == 6
