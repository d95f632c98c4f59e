"""A drain pass that holds far more Services' pods than one launch carries.

Such a pass is what the scheduler's queue holds when SIG-scalability's load
test creates its controllers (the benchmark's cell
`loadmix-5000n-150k.rollouts-1k-111svc`: one controller of 250 replicas,
eight of 30 and 102 of 5, each behind a Service, their pods interleaved in
creation order), or when a node-pool drain hands the scheduler the pods of a
hundred Deployments. `Scheduler._schedule_singletons_burst` ends a burst
segment before the pod whose selector group would be the 17th
(`kernels.SPREAD_GROUP_CAP`), so a pass is many segments, each a launch with
one count row a Service, and the pass's last segment holds whatever groups
are left, 1 to 16. Held here, on the cell's own data files at a small size:
every binding is the serial oracle's and the benchmark's plain reference's
however the pass is cut; the `groups` cuts are what the cut rule gives for
the pod order; every segment after a cut pads its carry to the cap's rows,
so a cut pass runs ONE scan program whatever its last segment holds
(`tpu_scan_spread_carry_launches_total{rows}`); and a pass that is never cut
(cells 9 and 11) keeps the power-of-two carry it had.
"""
import os
import sys
import types

import numpy as np
import pytest

from kubernetes_tpu.core.tpu_scheduler import (
    DEVICE_DISPATCH, ORACLE_FALLBACKS, SCAN_SPREAD_CARRY_LAUNCHES,
    SCAN_SPREAD_GROUPS, SCAN_SPREAD_STEPS, TPUScheduler)
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu.oracle.generic_scheduler import num_feasible_nodes_to_find
from kubernetes_tpu.scheduler import SEGMENT_CUTS, Scheduler
from kubernetes_tpu.store.store import PODS

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CELL = "loadmix-5000n-150k.rollouts-1k-111svc"
CELL9 = "load-5000n-150k.rollouts-1k-8svc"
# 250 nodes in zones of 84/83/83, so the NodeTree's order rotates and the
# default percentage cuts the walk short (120 of 250); 1500 resident pods
# behind 120 Services, 111 of which the mix names
N_NODES = 250
SMALL = {"nodes": {"count": N_NODES},
         "resident": {"pods_per_node": 6, "services": 120}}
N_PODS = 250
MAX_PODS = 256          # so the 250 pods are one drain pass
CAUSES = ("plan", "class", "groups", "nominated", "unburstable", "end")
ROWS = ("1", "2", "4", "8", "16")
CAP = K.SPREAD_GROUP_CAP
# a seed for every carry a last segment would run unpadded: the groups its
# pods hold (1, 2, 3-4, 5-8, 9-16) name the vector program and the four
# count-row ones (test_the_seeds_meet_every_carry_size holds them to it)
SEEDS = {2**31 + 20: 1, 6: 2, 2**31 + 45: 4, 2**31 + 8: 8, 2**31 + 5: 16}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, imported the way its command finds them
    (`benchmark/` on the path); the path is put back after."""
    sys.path.insert(0, BENCH_DIR)
    try:
        from lib import check, cluster, spec
        from lib.traffic import PodFactory
        yield {"check": check, "cluster": cluster, "spec": spec,
               "PodFactory": PodFactory}
    finally:
        sys.path.remove(BENCH_DIR)


def files(spec, cell: str = CELL):
    """The new cell's configuration at the small size, and the traffic mix
    of `cell` (the new cell's own, or cell 9's eight Services on it)."""
    b = spec.load_benchmark()
    cfg = spec.overlaid(
        spec.load_config(b, spec.find_cell(b, CELL)["config"]), SMALL)
    return cfg, spec.load_traffic(spec.find_cell(b, cell)["traffic"])


def world(bench, cfg: dict, traffic: dict, seed: int, n_pods: int = N_PODS):
    """The small cluster built from the seed, and the pass's pods with the
    description of each that the reference is given."""
    store, rows, residents, services = bench["cluster"].build(cfg, seed)
    factory = bench["PodFactory"](traffic, len(services), seed)
    factory.new_cycle()
    made = [factory.make(f"p-{j:03d}") for j in range(n_pods)]
    return store, rows, residents, services, made


def cut_rule(apps: list) -> tuple:
    """What `_schedule_singletons_burst` makes of one pass whose pods are
    each selected by the one Service `apps` names: (`groups` cuts, distinct
    Services summed over the segments, Services of the last segment)."""
    cuts = groups = 0
    seen: set = set()
    for app in apps:
        if app not in seen and len(seen) == CAP:
            cuts += 1
            groups += len(seen)
            seen = set()
        seen.add(app)
    return cuts, groups + len(seen), len(seen)


def bindings(store) -> dict:
    return {p.name: p.node_name for p in store.list(PODS)[0]
            if p.name.startswith("p-")}


def counters() -> dict:
    out = {("cut", c): SEGMENT_CUTS.labels(c).value for c in CAUSES}
    out.update({("rows", r): SCAN_SPREAD_CARRY_LAUNCHES.labels(r).value
                for r in ROWS})
    out.update({("steps", c): SCAN_SPREAD_STEPS.labels(c).value
                for c in ("none", "single", "grouped")})
    out["groups"] = SCAN_SPREAD_GROUPS.value
    out["launches"] = DEVICE_DISPATCH.labels("burst_scan").value
    out["refused"] = sum(ORACLE_FALLBACKS.labels(r).value for r in (
        "burst-spread-mixed", "burst-affinity-mixed", "device-fault",
        "circuit-open"))
    return out


def delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in counters().items()}


def serial_oracle(bench, cfg, traffic, seed, n_pods=N_PODS) -> dict:
    store, *_rest, made = world(bench, cfg, traffic, seed, n_pods)
    oracle = Scheduler(store, use_tpu=False, percentage_of_nodes_to_score=0)
    oracle.sync()
    store.create_many(PODS, [p for p, _d in made])
    oracle.pump()
    while oracle.schedule_one(timeout=0.0):
        pass
    oracle.pump()
    want = bindings(store)
    assert len(want) == n_pods and all(want.values())
    return want


def test_the_seeds_meet_every_carry_size(bench):
    """Over the parametrised seeds the last segment of the pass holds 1, 2,
    3-4, 5-8 and 9-16 Services: unpadded, the vector program and each of
    the four count-row programs; and every pass is cut many times."""
    _cfg, traffic = files(bench["spec"])
    for seed, rows in SEEDS.items():
        factory = bench["PodFactory"](traffic, 120, seed)
        factory.new_cycle()
        apps = [dict(factory.make(f"p-{j}")[1]["labels"])["app"]
                for j in range(N_PODS)]
        cuts, _groups, last = cut_rule(apps)
        assert cuts >= 8 and len(set(apps)) > 4 * CAP
        assert rows == (1 if last == 1
                        else max(2, 1 << (last - 1).bit_length()))


@pytest.mark.parametrize("seed", list(SEEDS))
def test_loadmix_pass_binds_as_oracle_and_reference(bench, seed):
    cfg, traffic = files(bench["spec"])
    want = serial_oracle(bench, cfg, traffic, seed)

    # the normal drain pass
    store, rows, residents, services, made = world(bench, cfg, traffic, seed)
    desc_of = {p.name: d for p, d in made}
    app_of = {p.name: p.labels["app"] for p, _d in made}
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    store.create_many(PODS, [p for p, _d in made])
    sched.pump()
    segments = []
    segment = sched._burst_segment

    def watched(pods, cycles, bucket, run, **kw):
        segments.append((run, kw["full_carry"], [p.name for p in pods]))
        return segment(pods, cycles, bucket, run, **kw)

    sched._burst_segment = watched
    before = counters()
    while sched.schedule_burst(max_pods=MAX_PODS):
        pass
    sched.pump()
    got = bindings(store)
    assert got == want

    # the benchmark's plain reference, given the binds in the order the
    # queue popped the pods, which is the order they were created in
    popped = [name for _run, _full, names in segments for name in names]
    assert popped == [p.name for p, _d in made]
    ref = bench["check"].make_reference(cfg, rows, residents, services)
    assert ref.num_to_find == num_feasible_nodes_to_find(N_NODES, 0) == 120
    for name in popped:
        assert ref.decide(desc_of[name]) == got[name], name
        ref.place(desc_of[name], got[name])

    # how the pass was cut: where the 17th Service of a segment came, and
    # nowhere else; every segment is one launch with a count row a Service
    cuts, groups, last = cut_rule([app_of[name] for name in popped])
    d = delta(before)
    assert {c: d[("cut", c)] for c in CAUSES} == {
        "plan": 0, "class": 0, "groups": cuts, "nominated": 0,
        "unburstable": 0, "end": 1}
    assert len(segments) == cuts + 1 == d["launches"]
    assert all(run == "spread" for run, _full, _names in segments)
    for _run, _full, names in segments[:-1]:
        assert len({app_of[name] for name in names}) == CAP
    assert len({app_of[name] for name in segments[-1][2]}) == last
    assert d["groups"] == groups and d["refused"] == 0
    # the segments after the first cut pad their carry, so the last one,
    # whatever it holds (a seed a carry size), runs the cut ones' program:
    # the counter's labels sum to the carrying launches, all under 16
    assert [full for _run, full, _names in segments] == [False] + [True] * cuts
    assert {r: d[("rows", r)] for r in ROWS} == {
        "1": 0, "2": 0, "4": 0, "8": 0, "16": cuts + 1}
    assert d[("steps", "grouped")] == N_PODS
    assert d[("steps", "single")] == d[("steps", "none")] == 0


@pytest.mark.parametrize("cell,n_pods,rows", [
    (CELL9, 150, "8"),      # eight Services a pass: cells 9 and 11
    (CELL, 12, None),       # a pass of few Services of the mix: never cut
])
def test_a_pass_that_is_not_cut_keeps_its_power_of_two_carry(bench, cell,
                                                             n_pods, rows):
    seed = 2**31 + 77
    cfg, traffic = files(bench["spec"], cell)
    want = serial_oracle(bench, cfg, traffic, seed, n_pods)
    store, *_rest, made = world(bench, cfg, traffic, seed, n_pods)
    held = len({p.labels["app"] for p, _d in made})
    assert 1 < held <= CAP
    if rows is None:
        rows = str(max(2, 1 << (held - 1).bit_length()))
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    store.create_many(PODS, [p for p, _d in made])
    sched.pump()
    before = counters()
    while sched.schedule_burst(max_pods=MAX_PODS):
        pass
    sched.pump()
    assert bindings(store) == want
    d = delta(before)
    assert d[("cut", "groups")] == 0 and d[("cut", "end")] == 1
    assert d["launches"] == 1 and d["groups"] == held
    assert {r: d[("rows", r)] for r in ROWS if d[("rows", r)]} == {rows: 1}


@pytest.mark.parametrize("launch_cap,full,groups,want", [
    (None, True, 1, CAP), (None, True, 2, CAP), (None, True, 5, CAP),
    (None, True, CAP, CAP), (None, True, CAP + 1, "refused"),
    (None, False, 1, None), (None, False, 5, 8),
    # a serve loop's two programs stand: the vector for one group
    (2048, True, 1, None), (2048, True, 5, CAP), (2048, False, 5, CAP)])
def test_the_carrys_rows_after_a_cut(launch_cap, full, groups, want):
    """`_spread_carry` alone: after a `groups` cut a closed loop's carry has
    the cap's rows whatever the segment holds, one group included; the
    spare rows are zero and count toward nothing."""
    feats = [types.SimpleNamespace(
        spread_counts=np.full(16, g + 1, np.int64),
        spread_group=("default", frozenset({(("app", f"s{g}"),)})))
        for g in range(groups)]
    algo = TPUScheduler.__new__(TPUScheduler)
    algo.launch_cap = launch_cap
    carried = algo._spread_carry(feats, 16, full)
    if want == "refused":
        assert carried is None
        return
    spread0, spread_groups = carried
    if want is None:
        assert spread0.shape == (16,) and spread_groups is None
        return
    group, counts_for = spread_groups
    assert spread0.shape == (want, 16) and counts_for.shape == (want, want)
    assert group.tolist() == list(range(groups))
    assert (spread0[:groups, 0] == np.arange(1, groups + 1)).all()
    assert not spread0[groups:].any()
    assert (counts_for == np.eye(want, dtype=bool)
            & (np.arange(want) < groups)[:, None]).all()
