"""A rotating NodeTree's truncated walk in position space.

When uneven zones rotate the enumeration between cycles, the kernels get
the cycle's order as POSITIONS (`pos[j]` = node j's place in it) and take
what the walk decides as order statistics of each node's offset from the
walk's origin: the node the walk stops at is the `num_to_find`-th smallest
offset among feasible nodes (`kernels._cycle_core`, `pos` given,
`full_scan=False`). No permutation is shipped or applied.

The referee applies one, on the host: the same rows laid out in the cycle's
enumeration order are a cluster whose order IS its axis, which the axis
program (`schedule_cycle`: the cumsum walk every parity test holds to the
oracle) decides; its answer is carried back through the permutation. A
plain Python walk over the feasible mask checks `kept` and `evaluated` a
second way. `_batch_core` with the program is held to that serial cycle and
a host-side fold, bit for bit on `packed`, the aux rows and the carry.
CPU backend; decisions and counts only.
"""
from functools import partial

import numpy as np
import pytest

import jax

from kubernetes_tpu.cache.node_tree import NodeTree
from kubernetes_tpu.ops import kernels as K

from test_dynamic_pod_count import (Z_PAD, _carry, _fold, _rotated_cycle,
                                    _stack)
from test_scan_carried_scores import AUX
from test_sharding import _cluster, _encode, _mk_pods

N = 40                      # zones of 14 / 13 / 13: the tree's order rotates
CYCLE_KEYS = ("selected", "found", "evaluated", "max_score", "num_ties",
              "next_last_index", "next_last_node_index")


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh
    from kubernetes_tpu.parallel import sharding as S
    devices = jax.devices()
    assert len(devices) >= 8, "conftest should have forced 8 CPU devices"
    return Mesh(np.asarray(devices[:8]), (S.NODE_AXIS,))


@pytest.fixture(scope="module")
def world():
    """40 nodes in three uneven zones holding 80 residents, 512 pending pods
    of mixed requests, and the orders as permutations of axis rows: the
    tree's own three (one per zone its cursor can start from), then two
    drawn at random. Rows past n_real tail every permutation."""
    infos, names = _cluster(N, seed=4)
    node_arrays, per_pod, _stacked, batch = _encode(
        infos, names, _mk_pods(512, seed=9))
    tree = NodeTree()
    for nm in names:
        tree.add_node(infos[nm].node)
    assert sorted(len(v) for v in tree._tree.values()) == [13, 13, 14]
    assert tree.rotation_map() != [0, 1, 2]         # the order does rotate
    n, n_pad = batch.n_real, batch.n_pad
    tail = np.arange(n, n_pad)
    rng = np.random.RandomState(7)
    perms = [np.concatenate([[batch.index[nm]
                              for nm in tree.order_for_start(r)], tail])
             for r in range(3)]
    perms += [np.concatenate([rng.permutation(n), tail]) for _ in range(2)]
    perms = np.asarray(perms, np.int32)
    assert len({tuple(p) for p in perms}) == 5
    positions = np.empty_like(perms)
    for l, perm in enumerate(perms):
        positions[l, perm] = np.arange(n_pad, dtype=np.int32)
    return node_arrays, per_pod, batch, perms, positions


def _laid_out(nodes, pod, perm):
    """The cluster with its rows in enumeration order: every per-node plane
    of the nodes and of the pod taken through `perm`."""
    n_pad = len(perm)
    nodes_p = {k: np.asarray(v)[perm] for k, v in nodes.items()}
    pod_p = {k: (np.asarray(v)[perm] if np.ndim(v) == 1
                 and np.shape(v)[0] == n_pad else v) for k, v in pod.items()}
    return nodes_p, pod_p


def _referee(nodes, pod, li, lni, ntf, n_real, perm, pos):
    """The axis program on the laid-out cluster, carried back."""
    nodes_p, pod_p = _laid_out(nodes, pod, perm)
    out = K.schedule_cycle(nodes_p, pod_p, li, lni, ntf, n_real, Z_PAD)
    got = {k: int(out[k]) for k in CYCLE_KEYS}
    if got["selected"] >= 0:
        got["selected"] = int(perm[got["selected"]])
    for k in ("kept", "feasible", "total"):
        got[k] = np.asarray(out[k])[pos]
    return got


def _walk(feasible, perm, li, ntf, n):
    """Upstream's loop: test nodes in order from `li` until `ntf` fit."""
    kept = np.zeros(len(feasible), bool)
    for step in range(n):
        row = perm[(li + step) % n]
        if feasible[row]:
            kept[row] = True
            if kept.sum() == ntf:
                return kept, step + 1
    return kept, n


def _with(pod, n_pad, fit=None, skip=False):
    """`pod` made to fit only on the rows `fit` (a node selector's mask)."""
    pod = dict(pod)
    if fit is not None:
        ok = np.zeros(n_pad, bool)
        ok[list(fit)] = True
        pod["sel_ok"] = ok
    if skip:
        pod["skip"] = np.asarray(True)
    return pod


# name, rows the pod fits on (None: every node), num_to_find, last_index,
# last_node_index, order, rows made invalid
EVERY = None
CYCLES = [
    ("more-fit-than-wanted", EVERY, 10, 0, 0, 1, ()),
    ("as-many-as-wanted", range(5, 15), 10, 3, 2, 1, ()),
    ("fewer-than-wanted", range(5, 12), 10, 3, 2, 2, ()),
    ("walk-wraps", EVERY, 10, N - 4, 5, 1, ()),
    ("walk-wraps-and-falls-short", (0, 1, 38, 39), 10, N - 2, 1, 2, ()),
    ("stops-on-its-last-node", EVERY, 10, 17, 0, 2, ()),
    ("one-node-fits", (23,), 10, 30, 4, 1, ()),
    ("no-node-fits", (), 10, 12, 4, 2, ()),
    ("skip-pod", EVERY, 10, 12, 4, 1, ()),
    ("wants-all-but-one", EVERY, N - 1, 9, 7, 2, ()),
    ("wants-every-node", EVERY, N, 9, 7, 1, ()),
    ("wants-one", EVERY, 1, 39, 0, 2, ()),
    ("invalid-rows-are-walked-over", EVERY, 10, 20, 3, 1, (21, 22, 25, 3)),
    ("last-index-past-the-cluster", EVERY, 10, N + 7, 3, 2, ()),
    ("axis-order", EVERY, 10, 33, 6, None, ()),
] + [(f"order-{l}-li-{li}", EVERY, 12, li, l + li, l, ())
     for l in range(5) for li in (0, 13, 31)]


@pytest.mark.parametrize("name,fit,ntf,li,lni,order,invalid", CYCLES,
                         ids=[c[0] for c in CYCLES])
def test_truncated_walk_on_positions_is_the_serial_walk(
        world, name, fit, ntf, li, lni, order, invalid):
    node_arrays, per_pod, batch, perms, positions = world
    n, n_pad = batch.n_real, batch.n_pad
    if order is None:           # the identity is an order like any other
        perm = pos = np.arange(n_pad, dtype=np.int32)
    else:
        perm, pos = perms[order], positions[order]
    nodes = {k: np.array(v) for k, v in node_arrays.items()}
    nodes["valid"][list(invalid)] = False
    pod = _with(per_pod[0], n_pad, fit, skip=name == "skip-pod")
    i64 = partial(np.asarray, dtype=np.int64)

    out = jax.jit(partial(K._one_cycle, weights=dict(K.DEFAULT_WEIGHTS),
                          z_pad=Z_PAD))(
        nodes, pod, i64(li), i64(lni), i64(ntf), i64(n), pos=pos)
    want = _referee(nodes, pod, li, lni, ntf, n, perm, pos)
    for k in CYCLE_KEYS:
        assert int(out[k]) == want[k], k
    for k in ("kept", "feasible", "total"):
        np.testing.assert_array_equal(np.asarray(out[k]), want[k], err_msg=k)

    feasible = np.asarray(out["feasible"])
    kept, evaluated = _walk(feasible[:n], perm[:n], li % n, ntf, n)
    if name == "skip-pod":
        assert not feasible.any() and int(out["evaluated"]) == 0
        assert int(out["next_last_index"]) == li
    else:
        np.testing.assert_array_equal(np.asarray(out["kept"])[:n], kept)
        assert int(out["evaluated"]) == evaluated
        assert int(out["found"]) == kept.sum() == min(feasible.sum(), ntf)
    assert not np.asarray(out["kept"])[n:].any()
    assert not np.asarray(out["kept"])[list(invalid)].any()

    # the case is the one its name says
    F = int(feasible.sum())
    if fit is not EVERY and name != "skip-pod":
        assert F == len(fit)
    if name in ("fewer-than-wanted", "walk-wraps-and-falls-short"):
        assert F < ntf and evaluated == n
    if name == "as-many-as-wanted":
        assert F == ntf and evaluated < n
    if name.startswith("walk-wraps"):
        assert (pos[np.flatnonzero(kept)] < li).any()   # went round
    if name == "stops-on-its-last-node":
        assert evaluated == ntf
    if name == "one-node-fits":
        # selectHost is skipped: the tie counter does not move
        assert int(out["selected"]) == 23
        assert int(out["next_last_node_index"]) == lni
    if name == "no-node-fits":
        assert int(out["selected"]) == -1 and int(out["num_ties"]) == 0
        assert int(out["next_last_index"]) == (li + n) % n
    if name == "wants-every-node":
        # what the program without the sort in `filter` returns
        full = _rotated_cycle(nodes, pod, i64(li), i64(lni), i64(ntf),
                              i64(n), pos, full_scan=True)
        for k in full:
            assert int(full[k]) == int(out[k]), k
    if name == "invalid-rows-are-walked-over":
        assert evaluated > ntf


# ---------------------------------------------------------------------------
# the scan: one such cycle a pod, each folding its decision
# ---------------------------------------------------------------------------
def _serial(node_arrays, rows, batch, perms, positions, seq, ntf, li, lni,
            spread0=None):
    """One referee cycle a pod, the decision folded on the host
    (NodeInfo.AddPod's aggregates) before the next."""
    nodes = {k: np.array(v) for k, v in node_arrays.items()}
    spread = None if spread0 is None else spread0.copy()
    lni0, block, aux = lni, [], []
    for t, pod in enumerate(rows):
        if spread is not None:
            pod = {**pod, "spread_counts": spread}
        o = seq[t]
        out = _referee(nodes, pod, li, lni, ntf, batch.n_real, perms[o],
                       positions[o])
        s = out["selected"]
        li, lni = out["next_last_index"], out["next_last_node_index"]
        block.append((s, li, lni - lni0, out["num_ties"],
                      out["evaluated"] - out["found"]))
        aux.append((out["found"], out["evaluated"], out["max_score"], lni))
        _fold(nodes, pod, s, spread)
    return np.asarray(block).T, np.asarray(aux).T, nodes, li, lni, spread


def _held_to_serial(got, n_pods, bucket, want):
    block_w, aux_w, nodes, li_w, lni_w, spread_w = want
    block = np.asarray(got[4]["packed"]).reshape(5, bucket)
    np.testing.assert_array_equal(block[:, :n_pods], block_w)
    assert (block[:, n_pods:] == -1).all()
    for key, row in zip(AUX, aux_w):
        np.testing.assert_array_equal(np.asarray(got[4][key])[:n_pods], row,
                                      err_msg=key)
    state, li, lni, spread = _carry(got)
    assert (li, lni) == (li_w, lni_w)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], nodes[key], err_msg=key)
    if spread_w is not None:
        np.testing.assert_array_equal(spread, spread_w)
    return block_w[0]


# name, pods, bucket, num_to_find, last_index, rows made skip
SCANS = [
    ("every-order", 96, 128, 10, N - 5, ()),
    ("nodes-fill", 300, 512, 15, 3, ()),
    ("skip-rows", 64, 64, 10, 20, (0, 9, 10, 63)),
    ("wants-all-but-one", 48, 64, N - 1, 11, ()),
    ("carried-spread", 96, 128, 10, N - 5, ()),
    ("sharded", 96, 128, 10, N - 5, ()),
    ("sharded-carried-spread", 64, 64, 12, 7, ()),
    ("one-pod", 1, 16, 10, 39, ()),
]


@pytest.mark.parametrize("name,n_pods,bucket,ntf,li0,skips", SCANS,
                         ids=[c[0] for c in SCANS])
def test_scan_on_positions_is_serial_cycles_and_a_host_fold(
        world, mesh, name, n_pods, bucket, ntf, li0, skips):
    node_arrays, per_pod, batch, perms, positions = world
    n, n_pad = batch.n_real, batch.n_pad
    rng = np.random.RandomState(len(name))
    seq = rng.randint(0, len(perms), size=bucket).astype(np.int32)
    seq[:5] = np.arange(5)                  # every order id at least once
    rows = list(per_pod[:bucket])
    for j in skips:
        rows[j] = _with(rows[j], n_pad, skip=True)
    kw = {}
    spread0 = None
    if "spread" in name:
        spread0 = np.zeros(n_pad, np.int64)
        spread0[:n] = rng.randint(0, 4, size=n)
        kw["spread0"] = spread0
    if name.startswith("sharded"):
        kw["mesh"] = mesh
    got = K.schedule_batch(node_arrays, _stack(rows), li0, 3, ntf, n, Z_PAD,
                           rotation=(positions, seq), n_pods=n_pods, **kw)
    want = _serial(node_arrays, rows[:n_pods], batch, perms, positions, seq,
                   ntf, li0, 3, spread0)
    sel = _held_to_serial(got, n_pods, bucket, want)
    if skips:
        assert all(sel[j] == -1 for j in skips)
    if name == "nodes-fill":
        assert -1 in sel and sel[-1] >= 0     # the loop goes on after a miss
    if n_pods > 16:
        assert min(np.diff(want[0][1])) < 0   # last_index went round


def test_a_chained_launch_walks_on_from_the_carry(world):
    """`carry_in`: a second launch takes the first's device-resident state
    and walk counters, and its own slice of the order ids."""
    node_arrays, per_pod, batch, perms, positions = world
    n = batch.n_real
    seq = np.random.RandomState(3).randint(0, 5, size=192).astype(np.int32)
    spread0 = np.zeros(batch.n_pad, np.int64)
    spread0[:n] = np.arange(n) % 3
    common = dict(num_to_find=10, n_real=n, z_pad=Z_PAD)
    a = K.schedule_batch(node_arrays, _stack(per_pod[:64]), N - 3, 2,
                         rotation=(positions, seq[:64]), spread0=spread0,
                         **common)
    b = K.schedule_batch(node_arrays, _stack(per_pod[64:192]), a[1], a[2],
                         rotation=(positions, seq[64:]),
                         carry_in=(a[0], a[3]), **common)
    want = _serial(node_arrays, per_pod[:192], batch, perms, positions, seq,
                   10, N - 3, 2, spread0)
    block = np.concatenate([np.asarray(x[4]["packed"]).reshape(5, -1)
                            for x in (a, b)], axis=1)
    # lni rides the block as a delta from its own launch's start
    block[2, 64:] += block[2, 63]
    np.testing.assert_array_equal(block, want[0])
    state, li, lni, spread = _carry(b)
    assert (li, lni) == (want[3], want[4])
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], want[2][key], err_msg=key)
    np.testing.assert_array_equal(spread, want[5])


def test_the_launch_reads_its_regime_off_its_own_operands(world, monkeypatch):
    """`schedule_batch` compiles a sort into `filter` when its num_to_find
    is below its n_real and not otherwise; no argument says which."""
    node_arrays, per_pod, batch, _perms, positions = world
    n = batch.n_real
    pods = _stack(per_pod[:16])
    seen = []
    real = K._schedule_batch_jit

    def spy(*a, **kw):
        seen.append((a[-2], kw["full_scan"]))
        return real(*a, **kw)
    monkeypatch.setattr(K, "_schedule_batch_jit", spy)
    seq = np.zeros(16, np.int32)
    for ntf in (n - 1, n, n + 5):
        K.schedule_batch(node_arrays, pods, 0, 0, ntf, n, Z_PAD,
                         rotation=(positions, seq))
    K.schedule_batch(node_arrays, pods, 0, 0, n, n, Z_PAD)
    # (rotate, full_scan): the axis program is one program at any quota
    assert seen == [(True, False), (True, True), (True, True),
                    (False, False)]

    def sorts(full_scan):
        i64 = partial(np.asarray, dtype=np.int64)
        text = jax.jit(partial(
            K._one_cycle, weights=dict(K.DEFAULT_WEIGHTS), z_pad=Z_PAD,
            full_scan=full_scan)).lower(
            node_arrays, per_pod[0], i64(0), i64(0), i64(10), i64(n),
            pos=positions[1]).as_text(debug_info=True)
        return text.count("call @sort("), text
    (one, full_text), (two, walk_text) = sorts(True), sorts(False)
    assert (one, two) == (1, 2)
    # the scope that prices the order nests in both stages it is paid in
    assert "filter/rotate/" in walk_text and "pick/rotate/" in walk_text
    assert "filter/rotate/" not in full_text and "pick/rotate/" in full_text


@pytest.mark.parametrize("ntf", [10, N - 1, N], ids=["10", "n-1", "every"])
def test_segment_kernel_walks_the_same_positions(world, ntf):
    """`_segments_core` looks the order up by enumerations consumed; with
    every segment placed whole that is the pod's index, and the launch is
    the serial cycles again: singleton runs and a gang that fits."""
    node_arrays, per_pod, batch, perms, positions = world
    n, B, n_pods = batch.n_real, 64, 50
    seq = np.random.RandomState(ntf).randint(0, 5, size=B).astype(np.int32)
    seg_start = np.zeros(B, bool)
    gang = np.zeros(B, bool)
    seg_start[[0, 20, 28, n_pods]] = True
    gang[20:28] = True
    state, li, lni, _spread, packed = K.schedule_batch_segments(
        node_arrays, _stack(per_pod[:B]), seg_start, gang, n_pods, N - 6, 2,
        ntf, n, Z_PAD, rotation=(positions, seq))
    block_w, _aux, nodes, li_w, lni_w, _s = _serial(
        node_arrays, per_pod[:n_pods], batch, perms, positions, seq, ntf,
        N - 6, 2)
    got = np.asarray(packed).reshape(4, B)
    np.testing.assert_array_equal(got[:3, :n_pods], block_w[:3])
    np.testing.assert_array_equal(got[3, :n_pods], np.arange(1, n_pods + 1))
    assert (got[:, n_pods:] == -1).all() and (block_w[0] >= 0).all()
    assert (int(li), int(lni)) == (li_w, lni_w)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(np.asarray(state[key]), nodes[key],
                                      err_msg=key)
