"""The walk's two counters in a launch: `last_index` reduced once at the
launch's head and carried as an int32 below n, `last_node_index` carried as
the int64 it is with its tie index taken in 32 bits below 2**31 and by the
long division only past it (`kernels._walk_origin`, `kernels._tie_index`).

Every integer a launch produces has to be the integer the arithmetic gives,
for any counter upstream's process can hold. Two referees, both one serial
cycle a pod with the decision folded on the host:

- the serial `schedule_cycle` (or the position cycle) at the RAW counters,
  which holds the scan to the serial program;
- Python-int arithmetic: the modulo of a big counter is taken in Python and
  the cycle is asked again at the REDUCED counters (`last_index % n`, and
  `last_node_index % num_ties`, which is below the node count and so in the
  32-bit branch whatever the counter was). The cycle at the raw counter has
  to choose the node the reduced one chooses, and `li_after`, `lni_after`
  and the returned counters have to be Python's.

A structural guard keeps the long division out of the loop bodies: no `rem`
or `div` on int64 scalars in a step of `_batch_core`, `_segments_core` or
`_pressure_core`, outside the branch of the one `cond` that is there for the
counter past 2**31, and the step as the TPU's compiler leaves it (compiled
here for a described v5e, nothing runs) is some 700 top-level instructions
where the three remainders made it 6100. CPU backend; decisions and counts
only.
"""
import inspect
import math
import os
import re
from functools import partial

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.ops import kernels as K

from test_dynamic_pod_count import (MODES, Z_PAD, _carry, _fold,
                                    _rotated_cycle, _setup, _stack,
                                    mesh)  # noqa: F401  (a fixture)
from test_sharding import _cluster, _encode, _mk_pods
from test_trace_spans import _record_calls

N_NODES = 40
N_PODS, BUCKET = 20, 32
GI = 1 << 30
# 0 | wraps at once | the cluster's size itself | a cluster that shrank
LAST_INDEX = {"0": 0, "n-1": N_NODES - 1, "n": N_NODES, "3n+7": 3 * N_NODES + 7}
# 2**31-2 crosses 2**31 inside the launch; 2**32+3 and 2**53+1 are past what
# an int32, a uint32 and a float64 hold
LAST_NODE_INDEX = {"0": 0, "2**31-2": 2 ** 31 - 2, "2**31": 2 ** 31,
                   "2**32+3": 2 ** 32 + 3, "2**53+1": 2 ** 53 + 1,
                   "2**62": 2 ** 62}


@pytest.fixture(scope="module")
def world():
    infos, names = _cluster(N_NODES, seed=4)
    node_arrays, per_pod, _stacked, batch = _encode(
        infos, names, _mk_pods(64, seed=9))
    assert batch.n_real == N_NODES
    return node_arrays, per_pod, batch


def _cycle(nodes, pod, kw, t, li, lni, ntf, n):
    """One serial cycle of the mode `kw` describes, as plain ints."""
    if "rotation" in kw:
        positions, seq = kw["rotation"]
        i64 = partial(np.asarray, dtype=np.int64)
        out = _rotated_cycle(nodes, pod, i64(li), i64(lni), i64(ntf), i64(n),
                             positions[seq[t]], full_scan=ntf >= n)
    else:
        out = K.schedule_cycle(nodes, pod, li, lni, ntf, n, Z_PAD)
    return {k: int(out[k]) for k in ("selected", "found", "evaluated",
                                     "num_ties", "next_last_index",
                                     "next_last_node_index")}


def _serial(node_arrays, per_pod, kw, ntf, n, li, lni):
    """The two referees in one pass. Returns the packed block's first three
    rows and the `lni_after` row as Python ints, the folded nodes, the
    counters and the spread counts."""
    nodes = {k: np.array(v) for k, v in node_arrays.items()}
    spread = None if "spread0" not in kw else kw["spread0"].copy()
    lni0, rows, lni_after = lni, [], []
    for t, pod in enumerate(per_pod):
        if spread is not None:
            pod = {**pod, "spread_counts": spread}
        raw = _cycle(nodes, pod, kw, t, li, lni, ntf, n)
        # Python's modulo, then a cycle whose counters need none
        k = lni % max(raw["num_ties"], 1)
        small = _cycle(nodes, pod, kw, t, li % n, k, ntf, n)
        for key in ("selected", "found", "evaluated", "num_ties"):
            assert raw[key] == small[key], (t, key, li, lni)
        li = (li + raw["evaluated"]) % n
        lni += raw["found"] > 1
        assert raw["next_last_index"] == li, (t, li)
        assert raw["next_last_node_index"] == lni, (t, lni)
        assert small["next_last_node_index"] - k == (raw["found"] > 1)
        rows.append((raw["selected"], li, lni - lni0))
        lni_after.append(lni)
        _fold(nodes, pod, raw["selected"], spread)
    return np.asarray(rows).T, lni_after, nodes, li, lni, spread


def _held(got, n_pods, bucket, want):
    rows, lni_after, nodes, li_w, lni_w, spread_w = want
    block = np.asarray(got[4]["packed"]).reshape(5, bucket)
    np.testing.assert_array_equal(block[:3, :n_pods], rows)
    assert (block[:, n_pods:] == -1).all()
    assert [int(v) for v in np.asarray(got[4]["lni_after"])[:n_pods]] \
        == lni_after
    np.testing.assert_array_equal(
        np.asarray(got[4]["li_after"])[:n_pods], rows[1])
    np.testing.assert_array_equal(
        np.asarray(got[4]["selected"])[:n_pods], rows[0])
    state, li, lni, spread = _carry(got)
    assert (li, lni) == (li_w, lni_w)
    assert got[1].dtype == got[2].dtype == np.int64      # the jit's boundary
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], nodes[key], err_msg=key)
    if spread_w is not None:
        np.testing.assert_array_equal(spread, spread_w)


@pytest.mark.parametrize("lni_name", list(LAST_NODE_INDEX))
@pytest.mark.parametrize("li_name", list(LAST_INDEX))
@pytest.mark.parametrize("mode", MODES)
def test_a_launch_counts_as_python_counts(world, mesh, mode, li_name,
                                          lni_name):
    node_arrays, per_pod, batch = world
    n = batch.n_real
    kw, ntf, _li, _lni = _setup(mode, batch, BUCKET)
    li0, lni0 = LAST_INDEX[li_name], LAST_NODE_INDEX[lni_name]
    got = K.schedule_batch(
        node_arrays, _stack(per_pod[:BUCKET]), li0, lni0, ntf, n, Z_PAD,
        n_pods=N_PODS, **kw, **({"mesh": mesh} if mode == "sharded" else {}))
    want = _serial(node_arrays, per_pod[:N_PODS], kw, ntf, n, li0, lni0)
    _held(got, N_PODS, BUCKET, want)
    # the case is the one its name says: the tie counter moved, and with it
    # the launch crossed 2**31 where it started two below
    assert want[4] - lni0 >= 3
    if mode != "rotation_full":
        assert min(np.diff(np.concatenate([[li0 % n], want[0][1]]))) < 0


def test_a_chained_launch_starts_from_the_first_ones_device_scalars(world):
    """`carry_in`: the second launch's counters are the first's outputs,
    device scalars it never fetched, and the first crossed 2**31."""
    node_arrays, per_pod, batch = world
    n = batch.n_real
    kw, ntf, _li, _lni = _setup("spread", batch, 64)
    li0, lni0 = 3 * n + 7, 2 ** 31 - 2
    common = dict(num_to_find=ntf, n_real=n, z_pad=Z_PAD)
    a = K.schedule_batch(node_arrays, _stack(per_pod[:16]), li0, lni0,
                         spread0=kw["spread0"], **common)
    assert isinstance(a[1], jax.Array) and isinstance(a[2], jax.Array)
    b = K.schedule_batch(node_arrays, _stack(per_pod[16:48]), a[1], a[2],
                         carry_in=(a[0], a[3]), n_pods=24, **common)
    rows, lni_after, nodes, li_w, lni_w, spread_w = _serial(
        node_arrays, per_pod[:40], kw, ntf, n, li0, lni0)
    block = np.concatenate(
        [np.asarray(a[4]["packed"]).reshape(5, 16),
         np.asarray(b[4]["packed"]).reshape(5, 32)[:, :24]], axis=1)
    # lni rides the block as a delta from its own launch's start
    block[2, 16:] += block[2, 15]
    np.testing.assert_array_equal(block[:3], rows)
    assert [int(v) for v in np.concatenate(
        [np.asarray(a[4]["lni_after"]),
         np.asarray(b[4]["lni_after"])[:24]])] == lni_after
    assert int(a[2]) > 2 ** 31 > lni0
    state, li, lni, spread = _carry(b)
    assert (li, lni) == (li_w, lni_w)
    for key in K._MUTABLE:
        np.testing.assert_array_equal(state[key], nodes[key], err_msg=key)
    np.testing.assert_array_equal(spread, spread_w)


def _segments(world, li0, lni0):
    node_arrays, per_pod, batch = world
    seg_start = np.zeros(BUCKET, bool)
    gang = np.zeros(BUCKET, bool)
    seg_start[[0, 6, 12, N_PODS]] = True
    gang[6:12] = True                       # a gang that fits: no rewind
    return K.schedule_batch_segments(
        node_arrays, _stack(per_pod[:BUCKET]), seg_start, gang, N_PODS, li0,
        lni0, 10, batch.n_real, Z_PAD)


def test_the_segment_kernel_counts_as_python_counts(world):
    node_arrays, per_pod, batch = world
    n = batch.n_real
    li0, lni0 = 3 * n + 7, 2 ** 31 - 2
    state, li, lni, _spread, packed = _segments(world, li0, lni0)
    rows, _after, nodes, li_w, lni_w, _s = _serial(
        node_arrays, per_pod[:N_PODS], {}, 10, n, li0, lni0)
    got = np.asarray(packed).reshape(4, BUCKET)
    assert (rows[0] >= 0).all()             # every segment placed whole
    np.testing.assert_array_equal(got[:3, :N_PODS], rows)
    assert (got[:, N_PODS:] == -1).all()
    assert (int(li), int(lni)) == (li_w, lni_w) and lni_w > 2 ** 31
    assert li.dtype == lni.dtype == np.int64
    for key in K._MUTABLE:
        np.testing.assert_array_equal(np.asarray(state[key]), nodes[key],
                                      err_msg=key)


# ---------------------------------------------------------------------------
# the pressure scan: no serial twin to ask, so the counter's own period. What
# a cycle reads of last_node_index is its remainder by a tie count of at most
# n, so a counter and its remainder by lcm(1..n) decide alike.
# ---------------------------------------------------------------------------
PRESSURE_NODES = 6


def _pressure_launch(monkeypatch):
    """The operands of one `pressure_batch` launch: six equal nodes with
    room for one more pod each and a victim to evict, nine preemptors. Six
    bind, choosing among the nodes that tie, and three preempt."""
    from kubernetes_tpu.cache.node_info import NodeInfo
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    infos, names = {}, []
    for i in range(PRESSURE_NODES):
        node = Node(name=f"n{i}", allocatable={"cpu": 2000, "memory": 8 * GI,
                                               "pods": 110})
        ni = NodeInfo(node)
        ni.add_pod(Pod(name=f"v{i}", priority=1, node_name=node.name,
                       containers=(Container.make(
                           name="c", requests={"cpu": 900}),)))
        infos[node.name] = ni
        names.append(node.name)
    pods = [Pod(name=f"hi-{k}", priority=10, containers=(
        Container.make(name="c", requests={"cpu": 900}),)) for k in range(9)]
    calls = _record_calls(monkeypatch, "pressure_batch")
    tpu = TPUScheduler(percentage_of_nodes_to_score=100)
    assert tpu.preempt_pressure_burst(pods, infos, names, []) is not None
    (args, kw), = calls
    monkeypatch.undo()
    return list(args), kw


def test_the_pressure_scan_counts_as_python_counts(monkeypatch):
    args, kw = _pressure_launch(monkeypatch)
    n = PRESSURE_NODES
    period = math.lcm(*range(1, n + 1))
    assert int(args[8]) == n

    def launch(li0, lni0):
        a = list(args)
        a[5], a[6] = li0, lni0
        _mut, _ghost, li, lni, outs = K.pressure_batch(*a, **kw)
        assert li.dtype == lni.dtype == np.int64
        return int(li), int(lni), {k: np.asarray(v) for k, v in outs.items()}

    picks = set()
    for li0, lni0 in ((3 * n + 1, 2 ** 31 - 2), (n - 1, 2 ** 62 + 1),
                      (0, 2 ** 32 + 3)):
        li, lni, outs = launch(li0, lni0)
        li_s, lni_s, outs_s = launch(li0 % n, lni0 % period)
        for key in outs:
            np.testing.assert_array_equal(outs[key], outs_s[key], err_msg=key)
        bound = outs["selected"][outs["selected"] >= 0]
        assert sorted(bound) == list(range(n))      # six bind, a node each
        assert (outs["winner"][6:9] >= 0).all()     # three preempt
        # a bind among two nodes or more moves the tie counter: five do
        assert lni - lni0 == lni_s - lni0 % period == n - 1
        # every pod's walk tests all six nodes
        assert li == li_s == li0 % n
        picks.add(tuple(bound))
    assert len(picks) == 3          # the counter does decide the order


# ---------------------------------------------------------------------------
# the guard: the long division stays out of the steps
# ---------------------------------------------------------------------------
LOOPS = ("while", "scan")


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _long_divisions(jaxpr, in_loop=False, found=None):
    """(`rem`/`div` eqns on 64-bit integer scalars inside a loop body and
    outside any `cond` branch, `cond`s met inside a loop body)."""
    found = ([], []) if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if in_loop and name in ("rem", "div") and all(
                v.aval.shape == () and v.aval.dtype in (np.int64, np.uint64)
                for v in eqn.invars):
            found[0].append(eqn)
        if name == "cond":
            if in_loop:
                found[1].append(eqn)
            continue
        for sub in _subjaxprs(eqn):
            _long_divisions(sub, in_loop or name in LOOPS, found)
    return found


def _scan_jaxpr(world, mode):
    node_arrays, per_pod, batch = world
    kw, ntf, li0, lni0 = _setup(mode, batch, BUCKET)
    return jax.make_jaxpr(lambda: K.schedule_batch(
        node_arrays, _stack(per_pod[:BUCKET]), li0, lni0, ntf, batch.n_real,
        Z_PAD, n_pods=N_PODS, **kw)[1:3])()


GUARDED = ["truncated", "rotation", "rotation_full", "spread", "segments",
           "pressure"]


@pytest.mark.parametrize("program", GUARDED)
def test_no_step_divides_a_64_bit_scalar(world, monkeypatch, program):
    if program == "segments":
        closed = jax.make_jaxpr(lambda: _segments(world, 3, 5)[1:3])()
    elif program == "pressure":
        args, kw = _pressure_launch(monkeypatch)
        closed = jax.make_jaxpr(
            lambda: K.pressure_batch(*args, **kw)[2:4])()
    else:
        closed = _scan_jaxpr(world, program)
    divisions, conds = _long_divisions(closed.jaxpr)
    assert not divisions, [str(e) for e in divisions]
    # way (a): one conditional a step, and the long division is in it
    (cond,) = conds
    inside = [_long_divisions(b.jaxpr, in_loop=True)[0]
              for b in cond.params["branches"]]
    assert sorted(len(d) for d in inside) == [0, 1]


def test_the_guard_sees_a_division_when_there_is_one():
    """The guard on a loop that does what the scan did: it finds the
    remainder, so its silence above says something."""
    def body(i, c):
        return (c[0] + 1, c[1] + c[0] % (c[1] + 1))
    closed = jax.make_jaxpr(lambda a, b: jax.lax.fori_loop(
        0, 4, body, (a, b)))(np.int64(5), np.int64(3))
    divisions, conds = _long_divisions(closed.jaxpr)
    assert len(divisions) == 1 and not conds
    # and a vector's division, or a 32-bit one, is not what it looks for
    closed = jax.make_jaxpr(lambda a, b: jax.lax.fori_loop(
        0, 4, lambda i, c: (c[0] % 7, c[1] % np.int32(7)), (a, b)))(
            np.arange(4, dtype=np.int64), np.int32(3))
    assert _long_divisions(closed.jaxpr) == ([], [])


# ---------------------------------------------------------------------------
# the step as the TPU's compiler leaves it: compiled for a described v5e
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    """A chip that is described and not attached (and the compile cache off
    meanwhile: an entry compiled for it cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _computations(hlo_text):
    """name -> instruction lines, of every computation of a compiled module."""
    out = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\{\n)", hlo_text):
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", block)
        if head:
            out[head.group(1)] = [line for line in block.split("\n")[1:]
                                  if " = " in line]
    return out


@pytest.mark.parametrize("mode", ["truncated", "rotation"])
def test_the_compiled_step_is_short_on_a_v5e(world, one_chip, monkeypatch,
                                             mode):
    node_arrays, per_pod, batch = world
    kw, ntf, li0, lni0 = _setup(mode, batch, BUCKET)
    real, seen = K._schedule_batch_jit, []
    monkeypatch.setattr(K, "_schedule_batch_jit",
                        lambda *a, **k: seen.append((a, k)) or real(*a, **k))
    K.schedule_batch(node_arrays, _stack(per_pod[:BUCKET]), li0, lni0, ntf,
                     batch.n_real, Z_PAD, n_pods=N_PODS,
                     spread0=np.zeros(batch.n_pad, np.int64), **kw)
    (args, kwargs), = seen
    static = ("z_pad", "weights_tuple", "rotate", "carry_spread", "full_scan")
    bound = inspect.signature(real.__wrapped__).bind(*args, **kwargs)
    shapes = {k: v if k in static else jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        v)
        for k, v in bound.arguments.items()}
    text = real.lower(**shapes).compile().as_text()
    comps = _computations(text)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    step = max((comps[b] for b in bodies), key=len)     # the scan's own loop
    assert 300 < len(step) < 1000, len(step)
    # way (a) stayed a conditional (a select would run the long division every
    # step): one a step, the long division in one branch, one remainder in
    # the other
    conds = [line for line in step if " conditional(" in line]
    assert len(conds) == 1 and text.count(" conditional(") == 1
    branches = re.search(r"branch_computations=\{([^}]*)\}", conds[0]).group(1)
    sizes = sorted(len(comps[b.strip().lstrip("%")])
                   for b in branches.split(","))
    assert sizes[0] < 40 and sizes[1] > 1000, sizes
