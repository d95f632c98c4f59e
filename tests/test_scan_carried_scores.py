"""The generic scan's carried score board (`kernels._batch_core`, `score_tab`).

A launch that carries the row-local resource scores and rescores only the
row a step bound has to return, bit for bit, what the same launch returns
when every step rescores every row (no `classes`: the program of every
launch before the board), and what serial `schedule_cycle` calls with a
host-side fold return: the packed block, the aux rows (`found`,
`evaluated`, `max_score`, `lni_after`) and the carry (`state`, `li`, `lni`,
`spread`). Cases: one class; the eight sizes of the benchmark's mixed
backlog; more classes than the bound (no board); nodes that fill
mid-launch; steps that bind nothing and skip rows; each rotation mode and
the carried spread; a pod count below the bucket; a chained second launch;
the 8-device CPU mesh; and through `schedule_burst`, the rule that chooses
(no weight row, classes within SCORE_CLASS_CAP, rows from
SCORE_BOARD_MIN_ROWS) as the counter books it. CPU backend; decisions and
counts only.
"""
from functools import partial

import numpy as np
import pytest

from kubernetes_tpu.api.types import Node, Pod, Container
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.ops import kernels as K

from test_dynamic_pod_count import Z_PAD, _carry, _serial, _setup, _stack
from test_sharding import _cluster, _encode

GI, MI = 1024 ** 3, 1024 ** 2
# benchmark/traffic/backlog-10k-mixed.json's sizes and shares
MIXED = [(100, 128 * MI, .30), (250, 512 * MI, .25), (500, GI, .20),
         (500, 4 * GI, .10), (1000, 2 * GI, .08), (1000, 8 * GI, .04),
         (2000, 8 * GI, .02), (3000, 24 * GI, .01)]
AUX = ("found", "evaluated", "max_score", "lni_after")


@pytest.fixture(scope="module")
def mesh():
    import jax
    from jax.sharding import Mesh
    from kubernetes_tpu.parallel import sharding as S
    devices = jax.devices()
    assert len(devices) >= 8, "conftest should have forced 8 CPU devices"
    return Mesh(np.asarray(devices[:8]), (S.NODE_AXIS,))


def _pods(sizes, k, seed, shares=None):
    """`k` pods, each of a size drawn from `sizes` ((cpu, mem) pairs; a
    size of (0, 0) is a pod with no requests at all)."""
    rng = np.random.RandomState(seed)
    draw = rng.choice(len(sizes), size=k, p=shares)
    return [Pod(name=f"p{j}", containers=(Container.make(
        name="c", requests={} if sizes[s] == (0, 0) else
        {"cpu": sizes[s][0], "memory": sizes[s][1]}),))
        for j, s in enumerate(draw)]


def _tight_cluster():
    """Ten nodes that sixty pods fill: eight of 2000m / 4Gi and two of
    300m / 512Mi, where pods without requests pile up past the node's
    capacity in `nz_*` (LeastRequested's `req > cap`) while they still fit."""
    infos, names = {}, []
    for i in range(10):
        small = i >= 8
        node = Node(name=f"n{i}", labels={
            "failure-domain.beta.kubernetes.io/zone": f"zone-{i % 3}",
            "failure-domain.beta.kubernetes.io/region": "r1",
            "kubernetes.io/hostname": f"n{i}"},
            allocatable={"cpu": 300 if small else 2000,
                         "memory": (512 * MI) if small else 4 * GI,
                         "pods": 110})
        infos[node.name] = NodeInfo(node)
        names.append(node.name)
    return infos, names


WORLDS = {
    # 40 nodes of 4000m / 32Gi holding 80 residents
    "one": lambda: (_cluster(40, seed=4), _pods([(250, GI)], 256, 1)),
    "mixed": lambda: (_cluster(40, seed=4), _pods(
        [s[:2] for s in MIXED], 256, 2, [s[2] for s in MIXED])),
    "forty": lambda: (_cluster(40, seed=4), _pods(
        [(100 + 10 * c, (1 + c % 5) * 256 * MI) for c in range(40)], 256, 3)),
    # 1000m x2 and 500m x4 land exactly on 2000m (PodFitsResources'
    # equality, Balanced's `full`), and most of the 64 pods find no node
    "tight": lambda: (_tight_cluster(), _pods(
        [(1000, GI), (500, 2 * GI), (250, 256 * MI), (0, 0)], 64, 5)),
}
_BUILT = {}


def world(name):
    if name not in _BUILT:
        (infos, names), pods = WORLDS[name]()
        node_arrays, per_pod, _stacked, batch = _encode(infos, names, pods)
        _BUILT[name] = node_arrays, per_pod, batch
    return _BUILT[name]


def _equal(got, want, n_pods):
    """Two launches' returns: packed, aux rows, carry."""
    np.testing.assert_array_equal(np.asarray(got[4]["packed"]),
                                  np.asarray(want[4]["packed"]))
    for key in AUX:
        np.testing.assert_array_equal(
            np.asarray(got[4][key])[:n_pods],
            np.asarray(want[4][key])[:n_pods], err_msg=key)
    state, li, lni, spread = _carry(got)
    state_w, li_w, lni_w, spread_w = _carry(want)
    assert (li, lni) == (li_w, lni_w)
    np.testing.assert_array_equal(spread, spread_w)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], state_w[key], err_msg=key)


# name, world, mode (test_dynamic_pod_count._setup's), pods, bucket, classes
# the launch has to hold (None: over the bound, no board), rows made skip.
# No bucket of 64 on the 40-node worlds: test_dynamic_pod_count counts that
# program's first compile, and a worker may run this file before it.
CASES = [
    ("one-class", "one", "truncated", 96, 96, 1, ()),
    ("eight-classes", "mixed", "truncated", 256, 256, 8, ()),
    ("over-the-bound", "forty", "truncated", 256, 256, None, ()),
    ("nodes-fill", "tight", "truncated", 64, 64, 4, ()),
    ("nodes-fill-every-node-scored", "tight", "full", 64, 64, 4, ()),
    ("skip-rows", "mixed", "truncated", 96, 96, None, (0, 7, 8, 40, 95)),
    ("rotate", "mixed", "rotation", 128, 128, None, ()),
    ("rotate-pos", "mixed", "rotation_full", 128, 128, None, ()),
    ("carry-spread", "one", "spread", 128, 128, 1, ()),
    ("below-the-bucket", "mixed", "truncated", 150, 256, 8, ()),
    ("one-pod", "mixed", "truncated", 1, 16, 1, ()),
    ("sharded", "mixed", "sharded", 128, 128, None, ()),
    ("sharded-nodes-fill", "tight", "sharded", 64, 64, 4, ()),
]


@pytest.mark.parametrize("name,world_name,mode,n_pods,bucket,n_classes,skips",
                         CASES, ids=[c[0] for c in CASES])
def test_carried_board_is_the_full_rescore(mesh, name, world_name, mode,
                                           n_pods, bucket, n_classes, skips):
    node_arrays, per_pod, batch = world(world_name)
    kw, ntf, li0, lni0 = _setup(mode, batch, bucket)
    if mode == "full":
        ntf, li0 = batch.n_real, 0
    if mode == "sharded":
        kw["mesh"] = mesh
    rows = list(per_pod[:bucket])
    for j in skips:
        rows[j] = {**rows[j], "skip": np.asarray(True)}
    pods = _stack(rows)
    classes = K.score_classes(pods["nz_cpu"], pods["nz_mem"], n_pods)
    if name == "over-the-bound":
        assert classes is None
        assert len({(int(c), int(m)) for c, m in zip(
            pods["nz_cpu"], pods["nz_mem"])}) > K.SCORE_CLASS_CAP
    else:
        cls, tab = classes
        if n_classes is not None:
            assert len(np.unique(cls[:n_pods])) == n_classes == len(tab)
    launch = partial(K.schedule_batch, node_arrays, pods, last_index=li0,
                     last_node_index=lni0, num_to_find=ntf,
                     n_real=batch.n_real, z_pad=Z_PAD, n_pods=n_pods, **kw)
    carried, full = launch(classes=classes), launch()
    _equal(carried, full, n_pods)

    serial_kw = {k: v for k, v in kw.items() if k != "mesh"}
    want, nodes, li_s, lni_s, spread_s = _serial(
        node_arrays, rows[:n_pods], batch, serial_kw, ntf, li0, lni0)
    block = np.asarray(carried[4]["packed"]).reshape(5, bucket)
    np.testing.assert_array_equal(block[:, :n_pods], want)
    assert (block[:, n_pods:] == -1).all()
    state, li, lni, spread = _carry(carried)
    assert (li, lni) == (li_s, lni_s)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], nodes[key], err_msg=key)
    if mode == "spread":
        np.testing.assert_array_equal(spread, spread_s)

    sel = want[0]
    if skips:
        assert all(sel[j] == -1 for j in skips)
    if world_name == "tight":
        # steps that bound nothing, and a step after them that bound
        miss = sel.index(-1)
        assert any(s >= 0 for s in sel[miss:])
        n = batch.n_real
        alloc, nz = node_arrays["alloc_cpu"][:n], nodes["nz_cpu"][:n]
        assert (nodes["req_cpu"][:n] == alloc).any()    # the equality case
        assert (nz > alloc).any()                       # `req > cap`
        # a later pod met both kinds of row: the next pod's board holds them
        assert (nz >= alloc).sum() > 2


def test_a_chained_launch_builds_its_board_from_the_carry():
    """`carry_in`: the second launch's board is built from the first
    launch's device-resident state, not from the snapshot's rows."""
    node_arrays, per_pod, batch = world("mixed")
    first, second = _stack(per_pod[:96]), _stack(per_pod[96:224])
    common = dict(num_to_find=10, n_real=batch.n_real, z_pad=Z_PAD)

    def chain(carried):
        def classes(pods):
            return K.score_classes(pods["nz_cpu"], pods["nz_mem"],
                                   len(pods["skip"])) if carried else None
        a = K.schedule_batch(node_arrays, first, 35, 3,
                             classes=classes(first), **common)
        return a, K.schedule_batch(node_arrays, second, a[1], a[2],
                                   carry_in=(a[0], None),
                                   classes=classes(second), **common)
    (a, b), (a_full, b_full) = chain(True), chain(False)
    _equal(a, a_full, 96)
    _equal(b, b_full, 128)
    want, nodes, li_s, lni_s, _spread = _serial(
        node_arrays, per_pod[:224], batch, {}, 10, 35, 3)
    got = np.concatenate([np.asarray(x[4]["packed"]).reshape(5, -1)
                          for x in (a, b)], axis=1)
    # lni rides the block as a delta from its own launch's start
    got[2, 96:] += got[2, 95]
    np.testing.assert_array_equal(got, want)
    state, li, lni, _s = _carry(b)
    assert (li, lni) == (li_s, lni_s)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], nodes[key], err_msg=key)


def test_batch_core_both_ways_under_one_jit():
    """`_batch_core` called directly with and without `score_tab`: the
    board is one more member of the carry, nothing of the return."""
    import jax
    import jax.numpy as jnp
    node_arrays, per_pod, batch = world("tight")
    pods = _stack(per_pod[:64])
    cls, tab = K.score_classes(pods["nz_cpu"], pods["nz_mem"], 64)
    z = jnp.zeros((1, 1), jnp.int32)
    mut0 = {k: node_arrays[k] for k in K._MUTABLE}

    @jax.jit
    def run(nodes, mut0, pods, tab):
        i64 = partial(jnp.asarray, dtype=jnp.int64)
        return K._batch_core(
            nodes, mut0, pods, i64(64), i64(0), i64(0), i64(batch.n_real),
            i64(batch.n_real), z, jnp.zeros(1, jnp.int32),
            jnp.zeros((), jnp.int64), Z_PAD, dict(K.DEFAULT_WEIGHTS), False,
            False, score_tab=tab)
    _equal(run(node_arrays, mut0, {**pods, "score_class": cls}, tab),
           run(node_arrays, mut0, pods, None), 64)


@pytest.mark.parametrize("sizes,n_pods,s_pad", [
    ([(100, 1)], 5, 1), ([(100, 1), (100, 2)], 5, 2),
    ([(1, 5), (2, 4), (3, 3)], 9, 4), ([(c, 7) for c in range(5)], 40, 8),
    ([(c, c) for c in range(16)], 64, 16), ([(c, 1) for c in range(17)], 66, None),
    ([(7, 7)], 0, 1),
], ids=["1", "2", "3-in-4", "5-in-8", "16", "17-none", "no-pods"])
def test_score_classes(sizes, n_pods, s_pad):
    B = 128
    rng = np.random.RandomState(len(sizes))
    draw = rng.randint(0, len(sizes), B)
    if n_pods:                      # every size is among the real pods
        draw[:len(sizes)] = np.arange(len(sizes))
        draw[:n_pods] = rng.permutation(draw[:n_pods])
    nz_cpu = np.array([sizes[d][0] for d in draw], np.int64)
    nz_mem = np.array([sizes[d][1] for d in draw], np.int64)
    # rows from n_pods on are never stepped over, whatever they hold
    nz_cpu[n_pods:] = 999_999
    got = K.score_classes(nz_cpu, nz_mem, n_pods)
    if s_pad is None:
        assert got is None
        return
    cls, tab = got
    assert cls.dtype == np.int32 and cls.shape == (B,)
    assert tab.dtype == np.int64 and tab.shape == (s_pad, 2)
    np.testing.assert_array_equal(tab[cls[:n_pods], 0], nz_cpu[:n_pods])
    np.testing.assert_array_equal(tab[cls[:n_pods], 1], nz_mem[:n_pods])
    assert (cls[n_pods:] == 0).all()
    real = len(set(sizes)) if n_pods else 1
    assert (tab[real:] == tab[0]).all()       # the spare rows repeat class 0


def test_callers_that_pass_no_board_trace_no_board():
    """`schedule_cycle` and a launch without classes lower to programs that
    hold no [S, n_pad] plane and read no class: what they were."""
    import jax
    node_arrays, per_pod, batch = world("mixed")
    pods = _stack(per_pod[:16])
    i64 = partial(np.asarray, dtype=np.int64)
    z = np.zeros((1, 1), np.int32)
    mut0 = {k: node_arrays[k] for k in K._MUTABLE}
    args = (node_arrays, mut0, pods, i64(16), i64(0), i64(0), i64(10),
            i64(batch.n_real), z, np.zeros(1, np.int32), i64(0))
    statics = (Z_PAD, tuple(sorted(K.DEFAULT_WEIGHTS.items())), False, False)
    n_pad = batch.n_pad
    cls, tab = K.score_classes(pods["nz_cpu"], pods["nz_mem"], 16)
    full = K._schedule_batch_jit.lower(*args, None, *statics).as_text()
    board = K._schedule_batch_jit.lower(
        args[0], args[1], {**pods, "score_class": cls}, *args[3:],
        tab, *statics).as_text()
    plane = f"tensor<{len(tab)}x{n_pad}xi64>"
    assert plane in board and plane not in full
    # one jitted function, so one program name in a trace and in
    # tpu_compiles_total, whichever way a launch goes
    assert K._schedule_batch_jit.__name__ == "_schedule_batch_jit"
    assert "jit__schedule_batch_jit" in full[:400]
    assert "jit__schedule_batch_jit" in board[:400]


def _scan_counters():
    from kubernetes_tpu.core import tpu_scheduler as T
    return {(f.name, k[0]): c.value
            for f in (T.SCAN_SCORE_STEPS, T.SCAN_STEPS)
            for k, c in f._children.items()}


@pytest.mark.parametrize("n_sizes,min_rows,label", [
    (1, 256, "carried"), (8, 256, "carried"), (40, 256, "full"),
    (1, 512, "full"), (1, None, "full")],
    ids=["1", "8", "40-over-the-cap", "1-below-the-rows", "1-as-shipped"])
def test_the_counter_says_how_a_launch_scored(monkeypatch, n_sizes, min_rows,
                                              label):
    """`tpu_scan_score_steps_total` is booked once a launch, with the
    launch's pod count, under `carried` or `full`; the decisions are the
    reference's either way. The 240 nodes are 256 rows: the board is
    carried from SCORE_BOARD_MIN_ROWS rows on, which as shipped is more
    than any CPU test holds."""
    from test_adaptive_walk import Run, config
    if min_rows is not None:
        monkeypatch.setattr(K, "SCORE_BOARD_MIN_ROWS", min_rows)
    assert K.SCORE_BOARD_MIN_ROWS > 256 or min_rows == 256
    sizes = [(100 + 10 * c, (1 + c % 5) * 64 * MI) for c in range(n_sizes)]
    traffic = {"pod_shapes": [
        {"kind": "plain", "share": 1.0 / n_sizes,
         "requests": {"cpu_milli": c, "memory_bytes": m}} for c, m in sizes],
        "service_choice": None}
    # percentage 0 on 240 nodes: the truncated walk, so the generic scan
    run = Run(config(240, 110, 0), traffic, seed=2**31 + n_sizes)
    before = _scan_counters()
    ids = run.cycle(300, max_pods=300)
    assert len(run.bound(ids)) == 300
    moved = {k: v - before.get(k, 0) for k, v in _scan_counters().items()
             if v - before.get(k, 0)}
    assert moved == {("tpu_scan_score_steps_total", label): 300,
                     ("tpu_scan_steps_total", "real"): 300}
    rep, _ref = run.replay()
    assert rep["compared"] == 300 and rep["mismatches"] == []
