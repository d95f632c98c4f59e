"""The generic scan runs as many steps as it was given pods, not as many as
its bucket has rows: `n_pods` is a dynamic trip count of `kernels._batch_core`.

A launch of `n_pods` pods in a power-of-two bucket has to return what a
launch whose bucket is exactly `n_pods` returns, and what serial
`schedule_cycle` calls with a host-side fold return: selections, the
`li_after` / `lni_delta` prefix of the packed block, and the carry (`state`,
`li`, `lni`, `spread`) — with the truncated walk, a rotating order under
it and with every node scored (the two position programs), carried spread,
and on the 8-device CPU mesh. The rows from `n_pods` on hold
pods that would fit (not skip pods), so a step that read one would show.
CPU backend; decisions and counts only.
"""
from functools import partial

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from kubernetes_tpu import ops
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu.parallel import sharding as S

from test_sharding import _cluster, _encode, _mk_pods

N_NODES = 40
Z_PAD = 4
COUNTS = [(1, 16), (15, 16), (16, 16), (17, 32), (150, 256), (300, 512)]
MODES = ["truncated", "rotation", "rotation_full", "spread", "sharded"]


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest should have forced 8 CPU devices"
    return Mesh(np.asarray(devices[:8]), (S.NODE_AXIS,))


@pytest.fixture(scope="module")
def world():
    """40 nodes with resident pods and 512 pending pods of mixed requests:
    enough that a 300-pod burst fills nodes and leaves some pods unplaced."""
    infos, names = _cluster(N_NODES, seed=4)
    node_arrays, per_pod, _stacked, batch = _encode(
        infos, names, _mk_pods(512, seed=9))
    return node_arrays, per_pod, batch


def _stack(per_pod):
    return {k: np.stack([pp[k] for pp in per_pod]) for k in per_pod[0]}


def _setup(mode, batch, bucket):
    """The launch's keywords and walk origin for a mode."""
    n, n_pad = batch.n_real, batch.n_pad
    rng = np.random.RandomState(len(mode))
    kw, ntf, li0 = {}, 10, n - 5            # the walk wraps at once
    if mode in ("rotation", "rotation_full"):
        # 4 enumeration orders as positions; invalid rows keep their index
        positions = np.stack([np.concatenate([rng.permutation(n),
                                              np.arange(n, n_pad)])
                              for _ in range(4)]).astype(np.int32)
        seq = rng.randint(0, 4, size=bucket).astype(np.int32)
        kw["rotation"] = (positions, seq)
        if mode == "rotation_full":
            ntf, li0 = n, 7                 # every node scored
    elif mode == "spread":
        spread0 = np.zeros(n_pad, np.int64)
        spread0[:n] = rng.randint(0, 4, size=n)
        kw["spread0"] = spread0
    return kw, ntf, li0, 3


@partial(jax.jit, static_argnames=("full_scan",))
def _rotated_cycle(nodes, pod, li, lni, ntf, n_real, pos, full_scan):
    out = K._one_cycle(nodes, pod, li, lni, ntf, n_real,
                       dict(K.DEFAULT_WEIGHTS), Z_PAD, pos=pos,
                       full_scan=full_scan)
    return {k: out[k] for k in ("selected", "next_last_index",
                                "next_last_node_index", "num_ties",
                                "found", "evaluated")}


def _fold(nodes, pod, s, spread):
    """A decision folded on the host: NodeInfo.AddPod's aggregates."""
    if s < 0:
        return
    for key, upd in (("req_cpu", "upd_cpu"), ("req_mem", "upd_mem"),
                     ("req_eph", "upd_eph"), ("req_scalar", "upd_scalar"),
                     ("nz_cpu", "nz_cpu"), ("nz_mem", "nz_mem")):
        nodes[key][s] += pod[upd]
    nodes["pod_count"][s] += 1
    if spread is not None:
        spread[s] += 1


def _serial(node_arrays, per_pod, batch, kw, ntf, li, lni):
    """One cycle a pod, the decision folded on the host (NodeInfo.AddPod's
    aggregates) before the next."""
    nodes = {k: np.array(v) for k, v in node_arrays.items()}
    spread = None if "spread0" not in kw else kw["spread0"].copy()
    if "rotation" in kw:
        positions, seq = kw["rotation"]
    lni0, sel, li_after, lni_delta = lni, [], [], []
    tied, rejected = [], []
    i64 = partial(np.asarray, dtype=np.int64)
    for t, pod in enumerate(per_pod):
        if spread is not None:
            pod = {**pod, "spread_counts": spread}
        if "rotation" in kw:
            out = _rotated_cycle(nodes, pod, i64(li), i64(lni), i64(ntf),
                                 i64(batch.n_real), positions[seq[t]],
                                 full_scan=ntf >= batch.n_real)
        else:
            out = K.schedule_cycle(nodes, pod, li, lni, ntf, batch.n_real,
                                   Z_PAD)
        s = int(out["selected"])
        li, lni = int(out["next_last_index"]), int(out["next_last_node_index"])
        sel.append(s)
        li_after.append(li)
        lni_delta.append(lni - lni0)
        tied.append(int(out["num_ties"]))
        rejected.append(int(out["evaluated"]) - int(out["found"]))
        _fold(nodes, pod, s, spread)
    return (sel, li_after, lni_delta, tied, rejected), nodes, li, lni, spread


def _carry(ret):
    state, li, lni, spread, _outs = ret
    return ({k: np.asarray(v) for k, v in state.items()}, int(li), int(lni),
            np.asarray(spread))


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n_pods,bucket", COUNTS)
def test_a_launch_runs_its_pods_not_its_bucket(world, mesh, mode, n_pods,
                                               bucket):
    node_arrays, per_pod, batch = world
    kw, ntf, li0, lni0 = _setup(mode, batch, bucket)
    if mode == "sharded":
        kw["mesh"] = mesh
    launch = partial(K.schedule_batch, node_arrays, last_index=li0,
                     last_node_index=lni0, num_to_find=ntf,
                     n_real=batch.n_real, z_pad=Z_PAD, **kw)
    # rows from n_pods on are pods like any other, never skip pods
    dyn = launch(pods=_stack(per_pod[:bucket]), n_pods=n_pods)
    exact_kw = {k: v[:-1] + (v[-1][:n_pods],) for k, v in kw.items()
                if k == "rotation"}
    exact = launch(pods=_stack(per_pod[:n_pods]), **exact_kw)

    block = np.asarray(dyn[4]["packed"]).reshape(5, bucket)
    assert (block[:, n_pods:] == -1).all()            # the fixed fill
    np.testing.assert_array_equal(
        block[:, :n_pods],
        np.asarray(exact[4]["packed"]).reshape(5, n_pods))
    for key in ("selected", "found", "evaluated", "max_score", "lni_after"):
        np.testing.assert_array_equal(
            np.asarray(dyn[4][key])[:n_pods], np.asarray(exact[4][key]),
            err_msg=key)
    state, li, lni, spread = _carry(dyn)
    state_x, li_x, lni_x, spread_x = _carry(exact)
    assert (li, lni) == (li_x, lni_x)
    np.testing.assert_array_equal(spread, spread_x)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], state_x[key], err_msg=key)

    rows, nodes, li_s, lni_s, spread_s = _serial(
        node_arrays, per_pod[:n_pods], batch, kw, ntf, li0, lni0)
    sel, li_after, _lni_delta, tied, rejected = rows
    # selected | li after | lni delta | tied nodes | tested and unfit
    np.testing.assert_array_equal(block[:, :n_pods], rows)
    assert all(t >= 1 for s, t in zip(sel, tied) if s >= 0)
    assert all(t == 0 and r > 0 for s, t, r in zip(sel, tied, rejected)
               if s < 0)
    assert (li, lni) == (li_s, lni_s)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], nodes[key], err_msg=key)
    if mode == "spread":
        np.testing.assert_array_equal(spread, spread_s)
    if n_pods == 300:
        assert -1 in sel and sel[-1] >= 0     # the loop goes on after a miss
    if mode != "rotation_full" and n_pods > 16:
        assert min(np.diff(li_after)) < 0     # last_index went round


def test_two_pod_counts_in_one_bucket_cost_one_compile(world):
    node_arrays, per_pod, batch = world
    pods = _stack(per_pod[:64])

    def compiles():
        return sum(c.value for c in ops.COMPILES._children.values())

    def launch(n_pods):
        out = K.schedule_batch(node_arrays, pods, 0, 0, 10, batch.n_real,
                               Z_PAD, n_pods=n_pods)
        return np.asarray(out[4]["packed"]).reshape(5, 64)

    before = compiles()
    first = launch(40)
    assert compiles() > before            # a 64-row bucket is new here
    after_first = compiles()
    second = launch(23)
    assert compiles() == after_first
    np.testing.assert_array_equal(second[:, :23], first[:, :23])
    assert (second[:, 23:] == -1).all() and (first[:, 23:40] != -1).any()
