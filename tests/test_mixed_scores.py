"""Unlike pods on an uneven board: the score arithmetic and the filter at the
fractions the cell `inuse-15000n-135k.backlog-10k-mixed` sends, against the
benchmark's plain reference (`benchmark/reference/default_provider.py`, numpy
int64 and IEEE float64, nothing of the program).

(a) Every `(cpu, mem)` sum that up to four of the mix's eight pod sizes reach
on a node holding its 9 residents (900m / 4,718,592,000 B) and on an empty
node is one row of a board; for every size arriving, the kernel's
LeastRequested + BalancedResourceAllocation and its PodFitsResources equal
the reference's on every row, the exact fits among them (3000m onto 1000m
used is 4000m of 4000m). (b) A 300-pod burst of the mix on 120 nodes through
`Scheduler.schedule_burst`, every binding compared: the launch stacks pod
rows that differ in value, and rows with an inert field beside rows with the
same field dense. CPU backend; decisions and counts only.
"""
import itertools
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import cluster, counters  # noqa: E402
from reference.default_provider import Reference  # noqa: E402

from kubernetes_tpu.api.types import Container, Node, Pod  # noqa: E402
from kubernetes_tpu.cache.node_info import NodeInfo  # noqa: E402
from kubernetes_tpu.ops import kernels as K  # noqa: E402

from test_adaptive_walk import Run, config  # noqa: E402
from test_sharding import _encode  # noqa: E402

GI = 1024 ** 3
with open(os.path.join(BENCH, "traffic", "backlog-10k-mixed.json")) as _f:
    MIX = json.load(_f)
SIZES = [(sh["requests"]["cpu_milli"], sh["requests"]["memory_bytes"])
         for sh in MIX["pod_shapes"]]
ALLOC = {"cpu": 4000, "memory": 32 * GI, "pods": 110}
RESIDENT = (100, 524288000)
# a burst's failing fractions found on the chip go here: (cpu, mem) a node
# holds before the pod arrives
FOUND_ON_CHIP: list = []


def _pod(name, cpu, mem, node_name="", **kw):
    return Pod(name=name, namespace="default", node_name=node_name,
               containers=(Container.make(
                   name="c", requests={"cpu": cpu, "memory": mem}),), **kw)


@pytest.fixture(scope="module")
def board():
    """One node per distinct sum: (NodeInfos, names, reference). The
    reference is given the same pods through `place`."""
    loads = {}
    for base in (0, 9):
        for k in range(5):
            for combo in itertools.combinations_with_replacement(SIZES, k):
                pods = [RESIDENT] * base + list(combo)
                cpu = sum(c for c, _m in pods)
                mem = sum(m for _c, m in pods)
                if cpu <= ALLOC["cpu"] and mem <= ALLOC["memory"]:
                    loads.setdefault((cpu, mem), pods)
    for cpu, mem in FOUND_ON_CHIP:
        loads.setdefault((cpu, mem), [(cpu, mem)])
    assert (1000, 9 * RESIDENT[1] + SIZES[0][1]) in loads   # the exact fit
    rows, infos, names = [], {}, []
    for i, (_load, pods) in enumerate(sorted(loads.items())):
        name = f"n{i}"
        labels = {cluster.ZONE_LABEL: f"zone-{i % 3}",
                  cluster.REGION_LABEL: "r1", cluster.HOSTNAME_LABEL: name}
        ni = NodeInfo(Node(name=name, labels=labels, allocatable=dict(ALLOC)))
        for j, (c, m) in enumerate(pods):
            ni.add_pod(_pod(f"{name}-{j}", c, m, node_name=name))
        infos[name] = ni
        names.append(name)
        rows.append({"name": name, "zone": f"zone-{i % 3}", "region": "r1",
                     "zone_key": cluster.zone_key("r1", f"zone-{i % 3}"),
                     "cpu": ALLOC["cpu"], "mem": ALLOC["memory"],
                     "pods": ALLOC["pods"], "load": pods})
    ref = Reference(rows, {"default": []}, 100)
    for r in rows:
        for c, m in r["load"]:
            ref.place({"cpu": c, "mem": m, "namespace": "default",
                       "labels": (), "kind": "plain"}, r["name"])
    return infos, names, ref


@pytest.mark.parametrize("cpu,mem", SIZES,
                         ids=[f"{c}m-{m // 2**20}Mi" for c, m in SIZES])
def test_scores_and_fit_on_every_reachable_sum(board, cpu, mem):
    infos, names, ref = board
    n = len(names)
    assert n > 300                    # hundreds of distinct fraction pairs
    node_arrays, per_pod, _stacked, batch = _encode(
        infos, names, [_pod("arriving", cpu, mem)])
    pod = per_pod[0]
    desc = {"cpu": cpu, "mem": mem, "namespace": "default", "labels": (),
            "kind": "plain"}
    want = ref._resource_scores(desc)
    fits = ((ref.n_pods + 1 <= ref.alloc_pods)
            & (ref.alloc_cpu >= cpu + ref.req_cpu)
            & (ref.alloc_mem >= mem + ref.req_mem))
    # the two row-local priorities alone, as every burst kernel computes them
    local = np.asarray(K._local_total(
        dict(K.DEFAULT_WEIGHTS), pod["nz_cpu"] + node_arrays["nz_cpu"],
        pod["nz_mem"] + node_arrays["nz_mem"], node_arrays["alloc_cpu"],
        node_arrays["alloc_mem"]))[:n]
    np.testing.assert_array_equal(local, want)
    # and through one whole cycle: the filter, and the total up to the
    # priorities that are constant over nodes for a plain pod
    out = K.schedule_cycle(node_arrays, pod, 0, 0, n, n, 4)
    np.testing.assert_array_equal(np.asarray(out["feasible"])[:n], fits)
    assert 0 < fits.sum() < n         # the filter says yes and says no
    total = np.asarray(out["total"])[:n]
    assert len(set((total - want)[fits].tolist())) == 1
    assert int(out["num_ties"]) == int(
        (total[fits] == total[fits].max()).sum())
    assert int(out["evaluated"]) - int(out["found"]) == n - fits.sum()
    if cpu == 3000:
        # PodFitsResources' equality case: 3000m onto 1000m used
        at = [i for i in range(n) if ref.req_cpu[i] == 1000]
        assert at and all(fits[i] for i in at
                          if ref.req_mem[i] + mem <= ALLOC["memory"])
        assert not any(fits[i] for i in range(n) if ref.req_cpu[i] > 1000)


def test_mixed_burst_against_the_reference(monkeypatch):
    """300 pods of the mix in ONE scan launch on 120 nodes that hold
    residents; every tenth pod carries a node selector that every node
    matches, so its `sel_ok` row is dense beside the others' inert one and
    the decision is a plain pod's."""
    cfg = config(120, 110, 100, resident={
        "pods_per_node": 3, "services": 5,
        "requests": {"cpu_milli": 100, "memory_bytes": 524288000}},
        reference="default_provider")
    run = Run(cfg, MIX, seed=2**31 + 57)
    made = [run.factory.make(f"mix-{j}") for j in range(300)]
    pods = [p if j % 10 else
            _pod(p.name, d["cpu"], d["mem"],
                 node_selector={cluster.REGION_LABEL: "r1"})
            for j, (p, d) in enumerate(made)]
    assert len({(d["cpu"], d["mem"]) for _p, d in made}) >= 6
    ids = [run.client.register(p, d) for p, (_q, d) in zip(pods, made)]
    run.client.create(pods)
    run.sched.pump()
    stacked_rows = []
    stack = run.sched.algorithm._stack_pods

    def spy(per_pod, bucket, profile_ids=None):
        out = stack(per_pod, bucket, profile_ids)
        stacked_rows.append((per_pod, bucket, out[0]))
        return out
    monkeypatch.setattr(run.sched.algorithm, "_stack_pods", spy)
    before = counters.snapshot()
    while run.sched.schedule_burst(max_pods=512):
        pass
    run.sched.pump()
    run.client.drain()
    moved = counters.delta(counters.snapshot(), before)
    launches = moved["tpu_device_dispatch_total"]
    assert launches[("burst_scan",)] == 1 and ("burst_uniform",) not in launches
    assert moved["tpu_scan_pod_rows_total"] == {("stacked",): 300}
    assert 300 <= counters.total(moved, "tpu_pick_tied_nodes_total") < 300 * 120
    # full nodes were met
    assert counters.total(moved, "tpu_filter_rejected_nodes_total") > 0
    # the stacking's span says how many signatures it stacked: the sizes
    # drawn, and each of them again under the node selector
    from kubernetes_tpu import obs
    span = [e for e in obs.trace.events() if e["name"] == "burst.stack"][-1]
    assert span["args"]["signatures"] == len(
        {(d["cpu"], d["mem"], j % 10 == 0) for j, (_p, d) in enumerate(made)})

    # what the launch was handed: rows that differ in value, and an inert
    # [1] field beside the same field dense [n_pad], broadcast up row by row
    (per_pod, B, out), = stacked_rows
    n_pad = 128
    assert len(per_pod) == 300        # the pad rows are the stacking's own
    assert B == 512 and out["req_cpu"].shape == (B,)
    assert [int(v) for v in out["req_cpu"][:300]] == \
        [d["cpu"] for _p, d in made]
    assert [int(v) for v in out["upd_mem"][:300]] == \
        [d["mem"] for _p, d in made]
    assert {np.shape(pp["sel_ok"]) for pp in per_pod} == {(1,), (n_pad,)}
    assert out["sel_ok"].shape == (B, n_pad)
    assert out["sel_ok"][:, :120].all()              # every real node
    assert out["taints_ok"].shape == (B, 1)          # inert for every pod
    assert not out["skip"][:300].any() and out["skip"][300:].all()

    rep, _ref = run.replay()
    assert len(run.bound(ids)) == 300
    assert rep["compared"] == 300 and rep["mismatches"] == []
    assert rep["over_allocatable"] == 0
