"""A drain pass that holds the pods of a hundred Services.

Such a pass is what the scheduler's queue holds when SIG-scalability's load
test creates its controllers (the benchmark's cell
`loadmix-5000n-150k.rollouts-1k-111svc`: one controller of 250 replicas,
eight of 30 and 102 of 5, each behind a Service, their pods interleaved in
creation order), or when a node-pool drain hands the scheduler the pods of a
hundred Deployments. `Scheduler._schedule_singletons_burst` ends a burst
segment before the pod whose selector group would be one more than the
algorithm's `spread_group_cap` carries in a launch, and that cap has two
widths (`TPUScheduler.spread_group_cap`). A closed loop carries
`kernels.SPREAD_GROUP_WIDE` count rows: the pass is ONE segment and one
launch, its carry padded to that width whatever it holds above
`kernels.SPREAD_GROUP_CAP` groups (17 or 111), so a process meets one scan
program more; a pass of still more groups is cut there, and the segments
after the cut run the same program. Behind a serve loop (`launch_cap`
pinned) the cut stays at `SPREAD_GROUP_CAP` and every grouped carry has that
many rows. Held here, on the cell's own data files at a small size: every
binding is the serial oracle's and the benchmark's plain reference's
however the pass is cut; the `groups` cuts are what the cut rule gives for
the pod order; the carries' rows are the width's
(`tpu_scan_spread_carry_launches_total{rows}`); and a pass of at most
`SPREAD_GROUP_CAP` groups (cells 9 and 11) keeps the power-of-two carry it
had.
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
CAP, WIDE = K.SPREAD_GROUP_CAP, K.SPREAD_GROUP_WIDE
ROWS = ("1", "2", "4", "8", str(CAP), str(WIDE))
# a seed for every carry the last segment of a pass cut at CAP groups would
# run unpadded: the groups its pods hold (1, 2, 3-4, 5-8, 9-16) name the
# vector program and the four count-row ones
# (test_the_seeds_meet_every_carry_size holds them to it)
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


def cut_rule(apps: list, cap: int = CAP) -> tuple:
    """What `_schedule_singletons_burst` makes of one pass whose pods are
    each selected by the one Service `apps` names, where a launch carries
    `cap` groups: (`groups` cuts, distinct Services summed over the
    segments, Services of the last segment)."""
    cuts = groups = 0
    seen: set = set()
    for app in apps:
        if app not in seen and len(seen) == cap:
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


def oracle_drain(store, made: list) -> dict:
    """The bindings of the serial oracle, one cycle a pod of `made`."""
    oracle = Scheduler(store, use_tpu=False, percentage_of_nodes_to_score=0)
    oracle.sync()
    store.create_many(PODS, [p for p, _d in made])
    oracle.pump()
    while oracle.schedule_one(timeout=0.0):
        pass
    oracle.pump()
    want = bindings(store)
    assert len(want) == len(made) and all(want.values())
    return want


def serial_oracle(bench, cfg, traffic, seed, n_pods=N_PODS) -> dict:
    store, *_rest, made = world(bench, cfg, traffic, seed, n_pods)
    return oracle_drain(store, made)


def test_the_seeds_meet_every_carry_size(bench):
    """Over the parametrised seeds the last segment of the pass, cut at
    `CAP` groups as behind a serve loop, holds 1, 2, 3-4, 5-8 and 9-16
    Services: unpadded, the vector program and each of the four count-row
    programs; every pass is cut many times there, and holds fewer groups
    than a closed loop's launch carries."""
    _cfg, traffic = files(bench["spec"])
    for seed, rows in SEEDS.items():
        factory = bench["PodFactory"](traffic, 120, seed)
        factory.new_cycle()
        apps = [dict(factory.make(f"p-{j}")[1]["labels"])["app"]
                for j in range(N_PODS)]
        cuts, _groups, last = cut_rule(apps)
        assert cuts >= 8 and 4 * CAP < len(set(apps)) <= WIDE
        assert rows == (1 if last == 1
                        else max(2, 1 << (last - 1).bit_length()))


def drain_watched(sched, store, made, serve: bool):
    """The normal drain pass over `made`, a closed loop's or (`serve`) with
    the launch cap a serve loop pins: (bindings, the segments as (run,
    full_carry, pod names), what the counters moved by)."""
    sched.sync()
    if serve:
        sched.algorithm.launch_cap = MAX_PODS      # what ServeLoop sets
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
    return bindings(store), segments, delta(before)


def held_to_the_reference(bench, cfg, rows, residents, services, made,
                          segments, got) -> list:
    """The benchmark's plain reference, given the binds in the order the
    queue popped the pods, which is the order they were created in;
    returns that order."""
    desc_of = {p.name: d for p, d in made}
    popped = [name for _run, _full, names in segments for name in names]
    assert popped == [p.name for p, _d in made]
    ref = bench["check"].make_reference(cfg, rows, residents, services)
    assert ref.num_to_find == num_feasible_nodes_to_find(N_NODES, 0) == 120
    for name in popped:
        assert ref.decide(desc_of[name]) == got[name], name
        ref.place(desc_of[name], got[name])
    return popped


@pytest.mark.parametrize("loop", ["closed", "serve"])
@pytest.mark.parametrize("seed", list(SEEDS))
def test_loadmix_pass_binds_as_oracle_and_reference(bench, seed, loop):
    cfg, traffic = files(bench["spec"])
    want = serial_oracle(bench, cfg, traffic, seed)

    store, rows, residents, services, made = world(bench, cfg, traffic, seed)
    app_of = {p.name: p.labels["app"] for p, _d in made}
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    got, segments, d = drain_watched(sched, store, made, loop == "serve")
    assert got == want
    popped = held_to_the_reference(bench, cfg, rows, residents, services,
                                   made, segments, got)

    # how the pass was cut: a closed loop's not at all (its launch carries
    # the pass's Services with room), a serve loop's where the 17th Service
    # of a segment came, and nowhere else; every segment is one launch with
    # a count row a Service
    cap = CAP if loop == "serve" else WIDE
    cuts, groups, last = cut_rule([app_of[name] for name in popped], cap)
    assert cuts == 0 if loop == "closed" else cuts >= 8
    assert {c: d[("cut", c)] for c in CAUSES} == {
        "plan": 0, "class": 0, "groups": cuts, "nominated": 0,
        "unburstable": 0, "end": 1}
    assert len(segments) == cuts + 1 == d["launches"]
    assert all(run == "spread" for run, _full, _names in segments)
    for _run, _full, names in segments[:-1]:
        assert len({app_of[name] for name in names}) == cap
    assert len({app_of[name] for name in segments[-1][2]}) == last
    assert d["groups"] == groups and d["refused"] == 0
    assert [full for _run, full, _names in segments] == [False] + [True] * cuts
    # the counter's labels sum to the carrying launches: a closed loop's one
    # on the wide carry; a serve loop's on its cap's rows whatever a segment
    # holds (a seed a carry size), but the one vector for one Service, its
    # two programs
    vector = int(loop == "serve" and last == 1)
    assert {r: d[("rows", r)] for r in ROWS if d[("rows", r)]} == {
        k: v for k, v in ((str(cap), cuts + 1 - vector), ("1", vector)) if v}
    assert d[("steps", "grouped")] == N_PODS - (
        len(segments[-1][2]) if vector else 0)
    assert d[("steps", "single")] == (len(segments[-1][2]) if vector else 0)
    assert d[("steps", "none")] == 0


def dealt(bench, traffic: dict, k: int, n_pods: int) -> list:
    """`n_pods` pods of the mix's shape, pod j a replica of Service j mod k:
    a pass of exactly `k` selector groups."""
    shape = traffic["pod_shapes"][0]
    factories = [bench["PodFactory"](
        {**traffic, "pod_shapes": [{
            **shape, "share": 1.0,
            "labels": bench["cluster"].service_label(j)}]}, k, 0)
        for j in range(k)]
    return [factories[j % k].make(f"p-{j:03d}") for j in range(n_pods)]


@pytest.mark.parametrize("k,n_pods,want", [
    (CAP + 1, 60, [60]), (2 * CAP + 1, 80, [80]), (111, 140, [140]),
    (WIDE + 1, WIDE + 20, [WIDE, 20])])
def test_a_closed_loops_pass_of_k_groups(bench, k, n_pods, want):
    """A closed loop's pass of 17, 33 and 111 selector groups is ONE segment
    on the wide carry; one of a group more than it carries is cut once, and
    both segments run the wide program. Every binding is the serial
    oracle's and the benchmark's plain reference's."""
    seed = 2**31 + 51
    spec = bench["spec"]
    cfg, traffic = files(spec)
    cfg = spec.overlaid(cfg, {"resident": {"services": WIDE + 12}})

    def built():
        store, rows, residents, services = bench["cluster"].build(cfg, seed)
        return store, rows, residents, services, dealt(bench, traffic, k,
                                                       n_pods)

    store, *_rest, made = built()
    bound = oracle_drain(store, made)

    store, rows, residents, services, made = built()
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=0)
    got, segments, d = drain_watched(sched, store, made, serve=False)
    assert got == bound
    held_to_the_reference(bench, cfg, rows, residents, services, made,
                          segments, got)
    assert [len(names) for _run, _full, names in segments] == want
    assert [full for _run, full, _names in segments] \
        == [False] + [True] * (len(want) - 1)
    assert d[("cut", "groups")] == len(want) - 1 and d[("cut", "end")] == 1
    assert d["launches"] == len(want) and d["refused"] == 0
    assert d["groups"] == sum(min(k, n) for n in want)
    assert {r: d[("rows", r)] for r in ROWS if d[("rows", r)]} \
        == {str(WIDE): len(want)}
    assert d[("steps", "grouped")] == n_pods


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
    (None, True, 1, WIDE), (None, True, 2, WIDE), (None, True, 5, WIDE),
    (None, True, CAP, WIDE), (None, True, CAP + 1, WIDE),
    (None, True, WIDE, WIDE), (None, True, WIDE + 1, "refused"),
    (None, False, 1, None), (None, False, 5, 8), (None, False, CAP, CAP),
    (None, False, CAP + 1, WIDE), (None, False, 111, WIDE),
    # a serve loop's two programs stand: the vector for one group
    (2048, True, 1, None), (2048, True, 5, CAP), (2048, False, 5, CAP),
    (2048, False, CAP + 1, "refused")])
def test_the_carrys_rows_after_a_cut(launch_cap, full, groups, want):
    """`_spread_carry` alone: after a `groups` cut a closed loop's carry has
    its cap's rows, the wide width, whatever the segment holds, one group
    included, as has any launch of more groups than the narrow carry's
    `CAP`; behind a serve loop the cap and the rows are the narrow one's; the
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
