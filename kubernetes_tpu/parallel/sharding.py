"""Multi-chip node-axis sharding: per-shard filter/score, ICI all-gather,
global select.

The node matrix is the scale axis (the reference's equivalent is the node
count, walked by 16 goroutines — generic_scheduler.go:518). Here the axis is
sharded across a `jax.sharding.Mesh`: every chip evaluates feasibility and
scores for its node rows; the tiny per-node results (feasible bits + int64
totals, ~16B/node) ride an ICI all-gather; the selection (rotation cumsum,
quota, round-robin tie-break) runs replicated so every chip agrees on the
binding decision. XLA inserts the collectives from sharding constraints —
the scaling-book recipe, not hand-written NCCL.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import kubernetes_tpu.ops  # noqa: F401  (x64)
from kubernetes_tpu.ops import kernels as K

NODE_AXIS = "nodes"

# node-array fields sharded along the node axis; everything else replicates
_SHARDED_1D = (
    "valid", "alloc_cpu", "alloc_mem", "alloc_eph", "allowed_pods",
    "req_cpu", "req_mem", "req_eph", "nz_cpu", "nz_mem", "pod_count",
    "zone_id",
)
_SHARDED_2D = ("alloc_scalar", "req_scalar")
# per-pod [N] arrays sharded the same way
_POD_SHARDED = (
    "sel_ok", "taints_ok", "unsched_ok", "ports_ok", "host_ok",
    "interpod_code", "node_aff_counts", "taint_counts", "spread_counts",
    "interpod_counts", "interpod_tracked", "image_sums", "prefer_avoid",
)


def make_mesh(n_devices: int | None = None) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices).reshape(-1), (NODE_AXIS,))


def node_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(NODE_AXIS))


def node_sharding_2d(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(NODE_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _put_by_keys(mesh: Mesh, arrays: dict, sharded_keys,
                 sharded_spec: NamedSharding,
                 sharded_2d_spec: NamedSharding | None = None) -> dict:
    """device_put `arrays`: keys in `sharded_keys` get the node-axis spec
    (2D keys their own spec when given); everything else replicates."""
    repl = replicated(mesh)
    n_dev = mesh.devices.size
    out = {}
    for k, v in arrays.items():
        # inert [*, 1] broadcast fields can't split over the node axis —
        # they replicate (the kernel broadcasts them per shard)
        splittable = np.shape(v)[-1] % n_dev == 0 if np.ndim(v) else False
        if sharded_2d_spec is not None and k in _SHARDED_2D:
            out[k] = jax.device_put(v, sharded_2d_spec)
        elif k in sharded_keys and splittable:
            out[k] = jax.device_put(v, sharded_spec)
        else:
            out[k] = jax.device_put(v, repl)
    return out


def shard_node_arrays(mesh: Mesh, nodes: dict) -> dict:
    """device_put node arrays with the node axis split across the mesh."""
    return _put_by_keys(mesh, nodes, _SHARDED_1D, node_sharding(mesh),
                        node_sharding_2d(mesh))


def shard_pod_arrays(mesh: Mesh, pod: dict) -> dict:
    return _put_by_keys(mesh, pod, _POD_SHARDED, node_sharding(mesh))


def shard_pod_batch(mesh: Mesh, pods: dict) -> dict:
    """device_put a stacked [B, ...] pod batch: per-node [B, N] arrays are
    sharded along the node axis (axis 1); per-pod scalars replicate."""
    return _put_by_keys(mesh, pods, _POD_SHARDED,
                        NamedSharding(mesh, P(None, NODE_AXIS)))


def _constrain_nodes(mesh: Mesh, nodes: dict) -> dict:
    """Pin node arrays to the node-axis sharding inside jit."""
    shard = node_sharding(mesh)
    shard2 = node_sharding_2d(mesh)
    n_dev = mesh.devices.size
    out = {}
    for k, v in nodes.items():
        if k in _SHARDED_2D:
            out[k] = jax.lax.with_sharding_constraint(v, shard2)
        elif k in _SHARDED_1D and v.shape[-1] % n_dev == 0:
            out[k] = jax.lax.with_sharding_constraint(v, shard)
        else:
            out[k] = v
    return out


def sharded_cycle_fn(mesh: Mesh, z_pad: int, weights=None,
                     use_wtab: bool = False):
    """A jitted scheduling cycle with the node axis sharded across the mesh.

    The per-node phases (feasibility, scores) are constrained to the node
    sharding so each chip evaluates its rows; GSPMD inserts the collectives
    (the feasibility cumsum and score reductions become all-gathers/psums
    over ICI) and the tiny scalar selection epilogue replicates. Decisions
    are bit-identical to the single-device kernel (tests/test_sharding.py).
    Returns fn(nodes, pod, last_index, last_node_index, num_to_find, n_real)
    — with `use_wtab`, fn takes a trailing replicated [P, K] profile
    weight table and `pod` carries `profile_id`.
    """
    weights_tuple = tuple(sorted((weights or K.DEFAULT_WEIGHTS).items()))

    if use_wtab:
        def fn(nodes, pod, last_index, last_node_index, num_to_find,
               n_real, wtab):
            nodes = _constrain_nodes(mesh, nodes)
            return K._one_cycle(nodes, pod, last_index, last_node_index,
                                num_to_find, n_real, dict(weights_tuple),
                                z_pad, wtab=wtab)
    else:
        def fn(nodes, pod, last_index, last_node_index, num_to_find,
               n_real):
            nodes = _constrain_nodes(mesh, nodes)
            return K._one_cycle(nodes, pod, last_index, last_node_index,
                                num_to_find, n_real, dict(weights_tuple),
                                z_pad)

    return jax.jit(fn)


_UNIFORM_CACHE: dict = {}


def sharded_uniform_fn(mesh: Mesh, weights_tuple, flags, b_cap, k_batch,
                       rotate, ban, has_extra, use_wtab: bool = False):
    """The uniform K-pods-per-pass burst kernel (kernels._uniform_core) with
    its node-axis state sharded over the mesh — the north-star multi-chip
    configuration (BASELINE.json configs[4]; the 16-way fan-out it replaces
    is generic_scheduler.go:518).

    Each chip folds and rescores its node rows inside the while-loop; the
    scratch-padded [N+1] carried vectors (scores, banned set, resource rows)
    are pinned to the node sharding every pass, so GSPMD keeps the O(N)
    sweep distributed and inserts all-gathers only for the tiny tie-cumsum /
    searchsorted epilogue (bool + int32 per node over ICI). Decisions are
    bit-identical to the single-device kernel (tests/test_sharding.py).
    Compiled once per (mesh, class-shape) and cached."""
    # Mesh is hashable/eq-comparable: content-equal meshes share the entry
    # (keying on id() would recompile per Mesh object and pin dead meshes)
    key = (mesh, weights_tuple, flags, b_cap, k_batch, rotate, ban,
           has_extra, use_wtab)
    fn = _UNIFORM_CACHE.get(key)
    if fn is not None:
        return fn
    shard1 = node_sharding(mesh)
    shard2 = NamedSharding(mesh, P(None, NODE_AXIS))

    def constrain(v):
        # GSPMD pads the odd scratch column onto the last shard
        return jax.lax.with_sharding_constraint(
            v, shard2 if v.ndim == 2 else shard1)

    if use_wtab:
        # profile tensor mode: the tiny [P, K] weight table replicates and
        # the class's row is gathered once by the scalar profile id
        def f(nodes, cls, n_pods, lni, n_real, perm, oid_seq, extra_ok,
              wtab, pid):
            nodes = _constrain_nodes(mesh, nodes)
            return K._uniform_core(nodes, cls, n_pods, lni, n_real, perm,
                                   oid_seq, extra_ok, dict(weights_tuple),
                                   flags, b_cap, k_batch, rotate, ban,
                                   has_extra, constrain=constrain,
                                   wtab=wtab, pid=pid)
    else:
        def f(nodes, cls, n_pods, lni, n_real, perm, oid_seq, extra_ok):
            nodes = _constrain_nodes(mesh, nodes)
            return K._uniform_core(nodes, cls, n_pods, lni, n_real, perm,
                                   oid_seq, extra_ok, dict(weights_tuple),
                                   flags, b_cap, k_batch, rotate, ban,
                                   has_extra, constrain=constrain)

    fn = _UNIFORM_CACHE[key] = jax.jit(f)
    return fn


def node_constrainer(mesh: Mesh):
    """A pytree-aware `constrain` hook for the kernel cores: node-axis
    leaves ([N] vectors, [N, *] planes — the axis is FIRST on every
    carried state/spread/ghost/victim structure) are pinned to the mesh's
    node sharding; leaves whose leading dim can't split evenly (inert [1]
    broadcasts, scalars, scratch-padded odd lengths) pass through
    untouched and replicate. The cores call this on every loop carry, so
    GSPMD keeps the O(N) sweep distributed across iterations instead of
    collapsing the carry onto one chip."""
    n_dev = mesh.devices.size
    s1 = node_sharding(mesh)
    s2 = node_sharding_2d(mesh)

    def one(v):
        if v.ndim >= 1 and v.shape[0] > 1 and v.shape[0] % n_dev == 0:
            return jax.lax.with_sharding_constraint(
                v, s2 if v.ndim == 2 else s1)
        return v

    return lambda tree: jax.tree_util.tree_map(one, tree)


# jit caches for the sharded kernel programs, keyed on (mesh, statics) —
# Mesh is hashable/eq-comparable, so content-equal meshes share entries
_SCAN_CACHE: dict = {}
_SEG_CACHE: dict = {}
_PRESSURE_CACHE: dict = {}
_PREEMPT_CACHE: dict = {}


def sharded_scan_fn(mesh: Mesh, z_pad: int, weights_tuple, rotate: bool,
                    carry_spread: bool, full_scan: bool,
                    use_wtab: bool = False):
    """The generic burst kernel (kernels._batch_core) with the
    node axis sharded over the mesh — the SAME program single-device runs,
    parameterized by the sharding spec: each chip folds the selected pod's
    deltas into its node rows every step (the carried _MUTABLE state and
    spread vector are pinned to the node sharding), rotation position rows
    replicate (they are tiny [L, N] index tables), the pod count (the
    loop's dynamic trip count) is a replicated scalar, and the per-node
    feasibility/score vectors ride XLA collectives (all-gather over ICI)
    into the replicated select epilogue. The carried score board
    (`score_tab` given) and the grouped spread rows (`counts_for` given:
    spread0 [G_pad, n_pad], one row a selector group) have the node axis
    LAST and are pinned on it like the state rows.
    Decisions are bit-identical to the single-device scan
    (tests/test_sharding.py + the sharded fuzz variants). Compiled once
    per (mesh, statics) and cached."""
    key = (mesh, z_pad, weights_tuple, rotate, carry_spread, full_scan,
           use_wtab)
    fn = _SCAN_CACHE.get(key)
    if fn is not None:
        return fn
    c = node_constrainer(mesh)

    if use_wtab:
        # profile tensor mode: the replicated [P, K] weight table rides the
        # operands and each step gathers its pod's row (profile_id in pods)
        def f(nodes, mut0, pods, n_pods, wtab, last_index, last_node_index,
              num_to_find, n_real, positions, oid_seq, spread0,
              counts_for=None):
            nodes = _constrain_nodes(mesh, nodes)
            return K._batch_core(nodes, mut0, pods, n_pods, last_index,
                                 last_node_index, num_to_find, n_real,
                                 positions, oid_seq, spread0, z_pad,
                                 dict(weights_tuple), rotate, carry_spread,
                                 full_scan=full_scan, constrain=c,
                                 wtab=wtab, counts_for=counts_for)
    else:
        def f(nodes, mut0, pods, n_pods, last_index, last_node_index,
              num_to_find, n_real, positions, oid_seq, spread0,
              score_tab=None, counts_for=None):
            nodes = _constrain_nodes(mesh, nodes)
            return K._batch_core(nodes, mut0, pods, n_pods, last_index,
                                 last_node_index, num_to_find, n_real,
                                 positions, oid_seq, spread0, z_pad,
                                 dict(weights_tuple), rotate, carry_spread,
                                 full_scan=full_scan, constrain=c,
                                 score_tab=score_tab, counts_for=counts_for)

    fn = _SCAN_CACHE[key] = jax.jit(f)
    return fn


def sharded_segments_fn(mesh: Mesh, z_pad: int, weights_tuple,
                        rotate: bool, carry_spread: bool, full_scan: bool,
                        use_wtab: bool = False, gang_score: bool = False):
    """The fused segmented drain-window kernel (kernels._segments_core)
    sharded over the mesh: the whole while_loop carry — live mutable rows,
    spread, AND the in-scan gang checkpoint — stays under
    NamedSharding(mesh, P("nodes")); a gang rewind is a shard-local
    element-wise select between two identically-sharded carries, rotation
    stays indexed by the consumed-count t with the position table replicated,
    and the single [4B] packed output replicates (per-pod, tiny).
    Decisions bit-identical to the single-device fused kernel."""
    key = (mesh, z_pad, weights_tuple, rotate, carry_spread, full_scan,
           use_wtab, gang_score)
    fn = _SEG_CACHE.get(key)
    if fn is not None:
        return fn
    c = node_constrainer(mesh)

    if use_wtab or gang_score:
        # profile tensor mode / rank-aware gang set-scoring: the weight
        # table replicates (a dummy rides when only gang_score is on) and
        # the tiny [z_pad] gang zone-count carry replicates with the
        # scalar walk counters
        def f(nodes, mut0, pods, seg_start, gang, n_pods, last_index,
              last_node_index, num_to_find, n_real, positions,
              oid_seq, spread0, wtab):
            nodes = _constrain_nodes(mesh, nodes)
            return K._segments_core(nodes, mut0, pods, seg_start, gang,
                                    n_pods, last_index, last_node_index,
                                    num_to_find, n_real, positions,
                                    oid_seq, spread0, z_pad,
                                    dict(weights_tuple), rotate,
                                    carry_spread, full_scan=full_scan,
                                    constrain=c,
                                    wtab=wtab if use_wtab else None,
                                    gang_score=gang_score)
    else:
        def f(nodes, mut0, pods, seg_start, gang, n_pods, last_index,
              last_node_index, num_to_find, n_real, positions,
              oid_seq, spread0):
            nodes = _constrain_nodes(mesh, nodes)
            return K._segments_core(nodes, mut0, pods, seg_start, gang,
                                    n_pods, last_index, last_node_index,
                                    num_to_find, n_real, positions,
                                    oid_seq, spread0, z_pad,
                                    dict(weights_tuple), rotate,
                                    carry_spread, full_scan=full_scan,
                                    constrain=c)

    fn = _SEG_CACHE[key] = jax.jit(f)
    return fn


def sharded_pressure_fn(mesh: Mesh, z_pad: int, weights_tuple):
    """The schedule-else-preempt pressure kernel (kernels._pressure_core)
    sharded over the mesh: mutable rows, the accumulated nominated-ghost
    load, and the [N, P] victim planes all split on the node axis; the
    5-criteria node pick reduces over tiny per-node aggregates and
    replicates. Decisions bit-identical to the single-device kernel."""
    key = (mesh, z_pad, weights_tuple)
    fn = _PRESSURE_CACHE.get(key)
    if fn is not None:
        return fn
    c = node_constrainer(mesh)

    def f(nodes, mut0, ghost0, pods, vic, last_index, last_node_index,
          num_to_find, n_real):
        nodes = _constrain_nodes(mesh, nodes)
        return K._pressure_core(nodes, c(mut0), c(ghost0), pods, c(vic),
                                last_index, last_node_index, num_to_find,
                                n_real, z_pad, dict(weights_tuple),
                                constrain=c)

    fn = _PRESSURE_CACHE[key] = jax.jit(f)
    return fn


def sharded_preempt_fn(mesh: Mesh, check_res: bool, has_req: bool):
    """The single-preemptor victim scan (kernels._preempt_scan_core)
    sharded over the mesh — per-node victim selection and the reprieve
    scan run shard-local; the staged pick replicates."""
    key = (mesh, check_res, has_req)
    fn = _PREEMPT_CACHE.get(key)
    if fn is not None:
        return fn
    c = node_constrainer(mesh)

    def f(nodes, vic, pod, feas_static, order_rank, n_real, max_prio):
        nodes = _constrain_nodes(mesh, nodes)
        return K._preempt_scan_core(nodes, c(vic), pod, c(feas_static),
                                    c(order_rank), n_real, max_prio,
                                    check_res, has_req, constrain=c)

    fn = _PREEMPT_CACHE[key] = jax.jit(f)
    return fn


def shard_victim_planes(mesh: Mesh, planes: dict) -> dict:
    """device_put the resident [N, P] victim-table planes with the node
    axis (axis 0) split across the mesh — the round-9 VictimStack under
    NamedSharding(mesh, P("nodes")). Planes whose row count can't split
    evenly replicate (tiny clusters)."""
    n_dev = mesh.devices.size
    s2 = node_sharding_2d(mesh)
    repl = replicated(mesh)
    return {k: jax.device_put(
                v, s2 if np.ndim(v) == 2 and np.shape(v)[0] % n_dev == 0
                else repl)
            for k, v in planes.items()}


def sharded_batch_fn(mesh: Mesh, z_pad: int, weights=None):
    """The full scheduling *step* over the mesh: the generic burst loop with
    the node axis sharded and the complete mutable-state fold
    (kernels._MUTABLE — req_cpu/mem/eph/scalar, nz_cpu/nz_mem, pod_count)
    constrained back onto the node sharding every iteration. `n_pods`, the
    loop's trip count, rides as a replicated scalar (None = every row).

    This is the multi-chip twin of kernels.schedule_batch, now riding the
    SAME _batch_core the single-device jit compiles (one code path
    parameterized by the sharding spec): each chip folds the selected
    pod's deltas into its node rows; the per-node feasibility / score
    vectors ride XLA collectives (all-gather over ICI) for the replicated
    selection epilogue inside _cycle_core. Decisions are bit-identical to
    the single-device scan (see tests/test_sharding.py)."""
    weights_tuple = tuple(sorted((weights or K.DEFAULT_WEIGHTS).items()))
    inner = sharded_scan_fn(mesh, z_pad, weights_tuple, rotate=False,
                            carry_spread=False, full_scan=False)

    def fn(nodes, pods, last_index, last_node_index, num_to_find, n_real,
           n_pods=None):
        z = jnp.zeros((1, 1), jnp.int32)
        mut0 = {k: nodes[k] for k in K._MUTABLE}
        if n_pods is None:
            n_pods = pods["skip"].shape[0]
        state, li, lni, _spread, outs = inner(
            nodes, mut0, pods, jnp.asarray(n_pods, jnp.int64), last_index,
            last_node_index, num_to_find, n_real, z,
            jnp.zeros(1, jnp.int32), jnp.zeros((), jnp.int64))
        return state, li, lni, outs

    return fn
