"""Fused scheduling-cycle kernels: filter + score + select over all nodes.

One jitted computation replaces the reference's per-cycle goroutine fan-out
(core/generic_scheduler.go:457 findNodesThatFit, :672 PrioritizeNodes, :286
selectHost): every node is evaluated at once on the MXU/VPU, and the
reference's *sequential* semantics are reproduced exactly:

- adaptive partial search (numFeasibleNodesToFind :434): feasibility is
  computed for all nodes, then the first `num_to_find` feasible nodes *in
  rotation order from last_index* are kept (a cumsum emulates the
  sequential walk's stopping point — same feasible set, same "evaluated"
  count, same last_index advance). Checked on the chip at 15,000 nodes
  (750 found per decision, 10,000-step scan launches in a 16,384 bucket,
  last_index going round the cluster) against the benchmark's independent
  reference by the cell `headline-15000n-adaptive.backlog-10k`; walks over
  full nodes by tests/test_adaptive_walk.py on the CPU. Where uneven zones
  rotate the enumeration between cycles the walk runs on each node's
  POSITION in the cycle's order, and its stopping point is an order
  statistic of the feasible positions (`_cycle_core`, `pos`): on the chip
  in the cell `density-5000n-150k-adaptive.rollout-1k`.
- integer 0-10 scores with the reference's exact int64/float64 formulas
  (the float64 ones as correctly rounded integer arithmetic, ops/exactf64.py;
  no f64 and no vector integer division reaches the device),
  normalized over the kept set only.
- round-robin tie-break among max-score nodes via last_node_index (:292).

The batched variant runs one serial cycle per pending pod of a burst against
one snapshot, folding each decision's resource deltas into the node state on
device — serially-equivalent decisions at one kernel launch for the burst.
The burst's operands are padded to a bucket for one compile per bucket; the
pod count is a dynamic trip count, so the launch runs as many steps as it was
given pods. Its carry holds, beside the mutable node rows, the walk counters
and the spread counts, the SCORE BOARD: the four row-local resource
priorities (`_local_total`) of each class of pod in the launch against every
node, [S_pad, n_pad]. A step moves one node row, so it reads its pod's row of
the board and rescores one column, where it used to divide over every node.
The rule that chooses is in the operands: the board when the launch has no
per-pod weight row, at most SCORE_CLASS_CAP classes (`score_classes`) and at
least SCORE_BOARD_MIN_ROWS node rows a device (below that, dividing over every
row is the cheaper step), every row every step otherwise; both are
`_schedule_batch_jit`, one name in a trace and in the compile counters.

Every core names its stages with `jax.named_scope`, in upstream's words:
`filter` (feasibility and the adaptive walk; for preemption, the victim
selection), `score`, `pick` (selectHost; pickOneNodeForPreemption) and `fold`
(the decision's delta into the carried node state). A scope is op metadata,
set while tracing and free at run time; a device trace is read by these names
(`SCOPES`), which survive a refactor that renumbers `fusion.9`. Inside
`filter` and `pick`, `rotate` names what a cycle does only because its
NodeTree order is not the device axis (`_cycle_core`, `pos` given): each
node's offset from the walk's origin and the sorts that take order statistics
of it. Nested, so the four stages still add up to what they did.
"""
from __future__ import annotations

import struct
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import kubernetes_tpu.ops  # noqa: F401  (enables x64)
from kubernetes_tpu.ops import exactf64 as xf

MAX_PRIORITY = 10
SCOPES = ("filter", "score", "pick", "fold")
MB = 1024 * 1024
IMAGE_MIN = 23 * MB
IMAGE_MAX = 1000 * MB
ZONE_WEIGHTING = 2.0 / 3.0

# The reference's float64 score expressions run as correctly rounded integer
# arithmetic (ops/exactf64.py): the TPU's emulated f64 is not IEEE, and a
# one-ulp difference flips a truncated score at a boundary.
_F_TEN = xf.constant(float(MAX_PRIORITY))
_F_ZONE_W = xf.constant(ZONE_WEIGHTING)
_F_NODE_W = xf.constant(1.0 - ZONE_WEIGHTING)


def _balanced_thresholds():
    """BalancedResourceAllocation's tail, g(D) = int((1 - D) * 10), is
    non-increasing in the fraction difference D (each rounding is monotone),
    so g(D) is the number of k in 1..10 with D <= T[k-1], where T[k-1] is
    the largest double whose g is still >= k. The T are found here, once,
    by bisection over double bit patterns (which order like the values)
    with the host's own IEEE arithmetic. Returns the ([10] m, [10] e)
    pairs."""
    def g(bits: int) -> int:
        d = struct.unpack("<d", struct.pack("<q", bits))[0]
        return int((1.0 - d) * float(MAX_PRIORITY))

    one = struct.unpack("<q", struct.pack("<d", 1.0))[0]
    pairs = []
    for k in range(1, MAX_PRIORITY + 1):
        lo, hi = 0, one                     # g(0.0) = 10 >= k > g(1.0) = 0
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if g(mid) >= k else (lo, mid)
        pairs.append(xf.constant(
            struct.unpack("<d", struct.pack("<q", lo))[0]))
    return tuple(np.asarray(v, np.int64) for v in zip(*pairs))


_BALANCED_T = _balanced_thresholds()


def _ratio_score(num, den):
    """The pair fl(10 * fl(num / den)) — the reference's
    `float64(MaxPriority) * (float64(num) / float64(den))` — for integer
    `den > 0`; `num` is clamped into [0, den] (rows outside it are rows the
    caller masks out anyway)."""
    return xf.fmul_small(xf.fdiv_int(jnp.clip(num, 0, den), den),
                         MAX_PRIORITY)

# fail-first codes (order of the default predicate set in
# predicates.PREDICATE_ORDERING)
FAIL_NONE = 0
FAIL_UNSCHEDULABLE = 1
FAIL_GENERAL = 2
FAIL_DISK = 3          # NoDiskConflict (ordering: before taints)
FAIL_TAINTS = 4
FAIL_MAXVOL = 5        # Max*VolumeCount family
FAIL_VOLBIND = 6       # CheckVolumeBinding
FAIL_VOLZONE = 7       # NoVolumeZoneConflict
FAIL_INTERPOD = 8

# general_bits layout (GeneralPredicates sub-failures, predicates.go:1112)
BIT_PODS = 0
BIT_CPU = 1
BIT_MEM = 2
BIT_EPH = 3
BIT_SCALAR0 = 4          # bit 4+s for scalar resource s (s < 36)
BIT_UNKNOWN_SCALAR = 59     # pod wants a scalar no node advertises
BIT_HOST = 60
BIT_PORTS = 61
BIT_SELECTOR = 62

# default priority weights (reference: defaults.go:108, register_priorities.go)
DEFAULT_WEIGHTS = {
    "selector_spread": 1,
    "interpod": 1,
    "least_requested": 1,
    "most_requested": 0,      # ClusterAutoscalerProvider swaps this for least
    "rtcr": 0,                # RequestedToCapacityRatioPriority (default shape)
    "balanced": 1,
    "prefer_avoid": 10000,
    "node_affinity": 1,
    "taint_toleration": 1,
    "image_locality": 1,
}

# profile scoring tensor (round 19): column order of the
# [profiles x priorities] int64 weight table the profile-aware kernels
# gather per-pod rows from (`wtab[pod["profile_id"]]`). The last column,
# "gang_locality", is the rank-aware gang set-scoring objective — zero
# for placement-blind profiles, so the default row reproduces today's
# scoring exactly. profiles.ProfileSet.weight_table() builds tables in
# THIS order; changing it is a wire-format change for resident tensors.
PRIORITY_AXIS = ("selector_spread", "interpod", "least_requested",
                 "most_requested", "rtcr", "balanced", "prefer_avoid",
                 "node_affinity", "taint_toleration", "image_locality",
                 "gang_locality")
_AXIS_INDEX = {n: i for i, n in enumerate(PRIORITY_AXIS)}


def _wsel(weights, wrow, name):
    """Effective weight of one priority family: the static python int
    (single-profile path — folds at trace time, today's programs) or the
    pod's gathered tensor-row lane (tensor mode — the STATIC `weights`
    dict then only gates which families compile in: a family any profile
    weights is computed once and scaled per pod, including to zero)."""
    if wrow is None:
        return weights[name]
    return wrow[_AXIS_INDEX[name]]


def _i64(x):
    return jnp.asarray(x, dtype=jnp.int64)


def _inert(arr) -> bool:
    """True when a per-node pod field was left at its default: the encoder
    emits shape-(1,) arrays for features the pod/cluster doesn't exercise
    (tpu_scheduler._pod_arrays), so whole priority/predicate families can be
    skipped at *trace* time — the shape is static."""
    return arr.ndim >= 1 and arr.shape[-1] == 1


def _local_total(weights, req_cpu, req_mem, alloc_cpu, alloc_mem,
                 wrow=None):
    """The four row-local resource priorities (least/most/RTCR/balanced),
    exact integer/float formulas. `req_*` is pod-nonzero + node-nonzero.
    Works elementwise on [N] vectors and on single-row scalars — both the
    full-cycle kernel and the uniform-burst incremental rescore call this,
    so the two paths cannot drift. `wrow` (optional) is one pod's gathered
    [K] weight-tensor row: families gate on the STATIC `weights` union and
    scale by the traced lane (_wsel)."""
    total = jnp.zeros_like(alloc_cpu)

    # The 0..10 (and 0..100) quotients below are counted, not divided
    # (xf.small_div), and the halvings are shifts of non-negative sums.
    if weights["least_requested"]:
        def least(req, cap):
            ok = (cap > 0) & (req <= cap)
            return jnp.where(ok, xf.small_div(
                jnp.maximum(cap - req, 0) * MAX_PRIORITY,
                jnp.maximum(cap, 1), MAX_PRIORITY), 0)
        total = total + _wsel(weights, wrow, "least_requested") * (
            (least(req_cpu, alloc_cpu) + least(req_mem, alloc_mem)) >> 1)

    if weights["most_requested"]:
        def most(req, cap):
            ok = (cap > 0) & (req <= cap)
            return jnp.where(ok, xf.small_div(
                req * MAX_PRIORITY, jnp.maximum(cap, 1), MAX_PRIORITY), 0)
        total = total + _wsel(weights, wrow, "most_requested") * (
            (most(req_cpu, alloc_cpu) + most(req_mem, alloc_mem)) >> 1)

    if weights["rtcr"]:
        # RequestedToCapacityRatio, default broken-linear shape {0->10,100->0}
        # (requested_to_capacity_ratio.go:39): score(p) = 10 + trunc(-10p/100);
        # Go int64 division truncates toward zero -> -(10p // 100) for p >= 0
        def rtcr_res(req, cap):
            p = jnp.where((cap == 0) | (req > cap), 100,
                          100 - xf.small_div(
                              jnp.maximum(cap - req, 0) * 100,
                              jnp.maximum(cap, 1), 100))
            return 10 - xf.small_div(p, 10, MAX_PRIORITY)   # 10p // 100
        total = total + _wsel(weights, wrow, "rtcr") * (
            (rtcr_res(req_cpu, alloc_cpu) + rtcr_res(req_mem, alloc_mem)) >> 1)

    if weights["balanced"]:
        # int((1 - |cpuF - memF|) * 10), 0 when either fraction reaches 1
        # (a zero capacity reads as fraction 1). For integers below 2**53,
        # fl(req / cap) >= 1.0 exactly when req >= cap.
        full = ((alloc_cpu == 0) | (req_cpu >= alloc_cpu)
                | (alloc_mem == 0) | (req_mem >= alloc_mem))
        rc, rm, ac, am = jnp.broadcast_arrays(req_cpu, req_mem,
                                              alloc_cpu, alloc_mem)
        fm, fe = xf.fdiv_int(jnp.where(full, 0, jnp.stack([rc, rm])),
                             jnp.where(full, 1, jnp.stack([ac, am])))
        cpu_f, mem_f = (fm[0], fe[0]), (fm[1], fe[1])
        swap = xf.ge(mem_f, cpu_f)
        dm, de = xf.fsub(xf.select(swap, mem_f, cpu_f),
                         xf.select(swap, cpu_f, mem_f))
        # int((1 - diff) * 10) = how many thresholds diff stays within
        balanced = jnp.where(full, 0, jnp.sum(
            xf.ge(_BALANCED_T, (dm[..., None], de[..., None])),
            axis=-1, dtype=jnp.int64))
        total = total + _wsel(weights, wrow, "balanced") * balanced

    return total


@jax.named_scope("score")
def _fit_scores(nodes, pod, kept, weights, z_pad, wrow=None, gang=None,
                local=None):
    """Enabled priorities, masked-normalized over `kept`. Returns total[N] i64.

    Zero-weight priorities and inert (default-valued, shape-[1]) pod fields
    are skipped at trace time: a plain-pod burst compiles down to
    LeastRequested + BalancedAllocation + integer constants; ops that
    provably contribute a constant are folded into one scalar.

    `wrow` (tensor mode) is this pod's [K] weight row — the STATIC
    `weights` dict becomes the cross-profile union gate and every family
    scales by its lane. `gang` = (gz[z_pad], member) is the rank-aware
    gang set-scoring input: gz counts THIS segment's already-placed
    members per zone, and nodes score min(count, 10) * gang weight — the
    group objective that prefers packing a gang into few zones, via the
    same one-hot zone reduction the spread family uses.

    `local` [N] i64 (optional) is `_local_total` of this pod against these
    rows, already computed: the generic scan carries it (`_batch_core`'s
    score board) and every other caller passes none and computes it here."""
    if local is None:
        local = _local_total(
            weights, pod["nz_cpu"] + nodes["nz_cpu"],
            pod["nz_mem"] + nodes["nz_mem"], nodes["alloc_cpu"],
            nodes["alloc_mem"], wrow=wrow)

    const = 0   # python-int accumulator for provably-constant scores
    total = jnp.zeros(nodes["valid"].shape, dtype=jnp.int64) + local

    if gang is not None and weights.get("gang_locality"):
        # gang-locality (rank-aware set-scoring): zone member counts of the
        # current gang segment, gathered per node through a dense one-hot
        # [N, Z] reduction (no scatter/gather serialization), clipped at
        # MAX_PRIORITY like every integer priority. Zone 0 = "no zone"
        # scores 0; non-members contribute and read nothing.
        gz, gmember = gang
        zone_id = nodes["zone_id"]
        gw = _wsel(weights, wrow, "gang_locality")
        zh = zone_id[:, None] == jnp.arange(z_pad, dtype=zone_id.dtype)[None, :]
        glc = jnp.sum(jnp.where(zh, gz[None, :], 0), axis=1)
        gl = jnp.minimum(glc, MAX_PRIORITY)
        total = total + jnp.where(gmember & (zone_id > 0), gw * gl, 0)

    if weights["node_affinity"]:
        na = pod["node_aff_counts"]
        if _inert(na):
            pass   # all counts 0 -> normalized score 0 everywhere
        else:
            # NodeAffinity: NormalizeReduce(10, reverse=False) over kept
            na_max = jnp.max(jnp.where(kept, na, 0))
            total = total + _wsel(weights, wrow, "node_affinity") * jnp.where(
                na_max == 0, na, xf.small_div(
                    MAX_PRIORITY * na, jnp.maximum(na_max, 1), MAX_PRIORITY))

    if weights["taint_toleration"]:
        tt = pod["taint_counts"]
        if _inert(tt):
            const = const + _wsel(weights, wrow, "taint_toleration") \
                * MAX_PRIORITY
        else:
            # TaintToleration: NormalizeReduce(10, reverse=True) over kept
            tt_max = jnp.max(jnp.where(kept, tt, 0))
            total = total + _wsel(weights, wrow, "taint_toleration") * jnp.where(
                tt_max == 0, MAX_PRIORITY,
                MAX_PRIORITY - xf.small_div(
                    MAX_PRIORITY * tt, jnp.maximum(tt_max, 1), MAX_PRIORITY))

    if weights["selector_spread"]:
        sc = pod["spread_counts"]
        if _inert(sc):
            # all counts 0 -> node and zone fractions are both max -> 10
            const = const + _wsel(weights, wrow, "selector_spread") \
                * MAX_PRIORITY
        else:
            # SelectorSpread: node + zone blend (selector_spreading.go:99).
            # Zone aggregation runs as dense one-hot [N, Z] reductions —
            # z_pad is tiny and the former .at[zone_id].add/.max scatters +
            # zone_counts[zone_id] gather serialize badly (XLA lowers them
            # to scalar loops on CPU and slow scatter paths on TPU); inside
            # the burst scan that cost repeated PER POD and was the
            # dominant term of the spread lane's 0.27x-of-plain cliff
            zone_id = nodes["zone_id"]
            max_by_node = jnp.max(jnp.where(kept, sc, 0))
            f = xf.select(max_by_node > 0,
                          _ratio_score(max_by_node - sc,
                                       jnp.maximum(max_by_node, 1)),
                          _F_TEN)
            in_zone = kept & (zone_id > 0)
            zh = zone_id[:, None] == jnp.arange(z_pad, dtype=zone_id.dtype)[None, :]
            izh = zh & in_zone[:, None]                       # [N, Z]
            zone_counts = jnp.sum(jnp.where(izh, sc[:, None], 0), axis=0)
            zone_present = jnp.any(izh, axis=0)
            have_zones = jnp.any(in_zone)
            max_by_zone = jnp.max(jnp.where(zone_present, zone_counts, 0))
            # the zone score is a function of the zone: computed on [Z],
            # then read per node through the one-hot (each row has exactly
            # one true lane in zh -> the sum IS the gather)
            zs_z = xf.select(max_by_zone > 0,
                             _ratio_score(max_by_zone - zone_counts,
                                          jnp.maximum(max_by_zone, 1)),
                             _F_TEN)
            zs = tuple(jnp.sum(jnp.where(zh, v[None, :], 0), axis=1)
                       for v in zs_z)
            f = xf.select(have_zones & (zone_id > 0),
                          xf.fadd(xf.fmul(f, _F_NODE_W),
                                  xf.fmul(_F_ZONE_W, zs)), f)
            total = total + _wsel(weights, wrow, "selector_spread") \
                * xf.ftrunc(f)

    if weights["interpod"]:
        ic = pod["interpod_counts"]
        tracked = pod["interpod_tracked"]
        if _inert(ic) and _inert(tracked):
            pass   # nothing tracked -> 0 everywhere
        else:
            # InterPodAffinity preferred: min-max over kept∩tracked
            sel = kept & tracked
            ic_max = jnp.maximum(
                jnp.max(jnp.where(sel, ic, jnp.iinfo(jnp.int64).min)), 0)
            ic_min = jnp.minimum(
                jnp.min(jnp.where(sel, ic, jnp.iinfo(jnp.int64).max)), 0)
            diff = ic_max - ic_min
            total = total + _wsel(weights, wrow, "interpod") * jnp.where(
                (diff > 0) & tracked,
                xf.ftrunc(_ratio_score(ic - ic_min, jnp.maximum(diff, 1))),
                0)

    if weights["image_locality"]:
        s = pod["image_sums"]
        if _inert(s):
            pass   # sum 0 -> clip to IMAGE_MIN -> score 0
        else:
            # ImageLocality (image_locality.go:42)
            sc = jnp.clip(s, IMAGE_MIN, IMAGE_MAX)
            total = total + _wsel(weights, wrow, "image_locality") * (
                xf.small_div(MAX_PRIORITY * (sc - IMAGE_MIN),
                             IMAGE_MAX - IMAGE_MIN, MAX_PRIORITY))

    if weights["prefer_avoid"]:
        pa = pod["prefer_avoid"]
        if _inert(pa):
            const = const + _wsel(weights, wrow, "prefer_avoid") \
                * MAX_PRIORITY
        else:
            total = total + _wsel(weights, wrow, "prefer_avoid") * pa

    return total + const


@jax.named_scope("filter")
def _feasibility(nodes, pod):
    """Returns (feasible[N], fail_first[N] i8, general_bits[N] i64).

    Inert (shape-[1], default all-pass) mask families drop out at trace time."""
    valid = nodes["valid"]
    # GeneralPredicates: resources
    bits = jnp.zeros(valid.shape, dtype=jnp.int64)
    check_res = pod["check_resources"]
    pods_over = check_res & (nodes["pod_count"] + 1 > nodes["allowed_pods"])
    bits |= jnp.where(pods_over, 1 << BIT_PODS, 0)
    has_req = pod["has_request"] & check_res
    over_cpu = nodes["alloc_cpu"] < pod["req_cpu"] + nodes["req_cpu"]
    over_mem = nodes["alloc_mem"] < pod["req_mem"] + nodes["req_mem"]
    over_eph = nodes["alloc_eph"] < pod["req_eph"] + nodes["req_eph"]
    bits |= jnp.where(has_req & over_cpu, 1 << BIT_CPU, 0)
    bits |= jnp.where(has_req & over_mem, 1 << BIT_MEM, 0)
    bits |= jnp.where(has_req & over_eph, 1 << BIT_EPH, 0)
    # scalar resources: [N,S]
    over_scalar = nodes["alloc_scalar"] < pod["req_scalar"][None, :] + nodes["req_scalar"]
    wants_scalar = pod["req_scalar"][None, :] > 0
    scalar_fail = has_req & wants_scalar & over_scalar          # [N,S]
    s_count = scalar_fail.shape[1]
    scalar_bits = jnp.sum(
        jnp.where(scalar_fail,
                  (1 << (BIT_SCALAR0 + jnp.arange(s_count, dtype=jnp.int64)))[None, :],
                  0), axis=1)
    bits |= scalar_bits
    bits |= jnp.where(check_res & pod["unknown_scalar"],
                      _i64(1) << BIT_UNKNOWN_SCALAR, 0)
    if not _inert(pod["host_ok"]):
        bits |= jnp.where(~pod["host_ok"], 1 << BIT_HOST, 0)
    if not _inert(pod["ports_ok"]):
        bits |= jnp.where(~pod["ports_ok"], 1 << BIT_PORTS, 0)
    if not _inert(pod["sel_ok"]):
        bits |= jnp.where(~pod["sel_ok"], 1 << BIT_SELECTOR, 0)

    general_fail = bits != 0
    # padding entries in a burst bucket: infeasible everywhere, no state fold
    skip = pod["skip"]

    # PREDICATE_ORDERING: unschedulable, general, disk, taints, max-volume,
    # volume binding, volume zone, inter-pod affinity. Built lowest-priority
    # first; each later overwrite wins, so the result is the FIRST failing
    # predicate in the ordering. Inert families emit no ops.
    fail_first = FAIL_NONE
    for mask_key, code in (("interpod_code", FAIL_INTERPOD),
                           ("volzone_ok", FAIL_VOLZONE),
                           ("volbind_ok", FAIL_VOLBIND),
                           ("maxvol_ok", FAIL_MAXVOL),
                           ("taints_ok", FAIL_TAINTS),
                           ("disk_ok", FAIL_DISK)):
        field = pod[mask_key]
        if _inert(field):
            continue
        failed = (field > 0) if mask_key == "interpod_code" else ~field
        fail_first = jnp.where(failed, code, fail_first)
    fail_first = jnp.where(general_fail, FAIL_GENERAL, fail_first)
    if not _inert(pod["unsched_ok"]):
        fail_first = jnp.where(~pod["unsched_ok"], FAIL_UNSCHEDULABLE, fail_first)
    feasible = valid & (fail_first == FAIL_NONE) & ~skip
    return feasible, fail_first.astype(jnp.int8), bits


@jax.named_scope("rotate")
def _kth_smallest(mask, rel, k):
    """The k-th (0-based) smallest of the walk offsets `rel` [N] i32 under
    `mask`, by one sort; a fill above every offset when the mask holds no
    more than k of them (`dynamic_slice` clamps k into the axis)."""
    ranked = jnp.sort(jnp.where(mask, rel, jnp.int32(2 ** 30)))
    return jax.lax.dynamic_slice(ranked, (k,), (1,))[0]


def _walk_origin(last_index, n_real):
    """The walk's origin as a cycle uses it: `last_index` reduced into
    [0, n), int32. A launch calls this ONCE, at its head and outside its
    loop: last_index persists across cycles while the cluster may shrink and
    the oracle's walk is modulo n (generic_scheduler.py:148), so the one
    int64 remainder a launch stays, here. From then on the origin is
    carried reduced (`_cycle_core` hands the next one back reduced), and no
    step divides."""
    return (_i64(last_index) % jnp.maximum(n_real, 1)).astype(jnp.int32)


def _tie_index(last_node_index, num_ties):
    """`last_node_index mod num_ties` as an int32 (selectHost :292), exact
    for any non-negative int64 counter. The TPU has no 64-bit divide: XLA
    unrolls an int64 `rem` by a traced scalar into some 1800 scalar
    instructions of u32 long division, 18 us of every step of a loop on a
    v5e (PERF.md section 6, PR 55). upstream's counter grows by one a pod
    for the life of the process, so it is below 2**31 for the first two
    thousand million pods, and `num_ties` is at most the node count: the
    branch taken then is one 32-bit remainder, and only a counter past
    2**31 pays for the long division, in a branch of its own. A `cond`,
    not a `where`: a select would compute both."""
    return jax.lax.cond(
        last_node_index < 2 ** 31,
        lambda: jax.lax.rem(last_node_index.astype(jnp.int32),
                            num_ties.astype(jnp.int32)),
        lambda: (last_node_index % num_ties.astype(jnp.int64)).astype(
            jnp.int32))


def _one_cycle(nodes, pod, last_index, last_node_index, num_to_find, n_real,
               weights, z_pad, **step):
    """A launch of ONE cycle (`schedule_cycle`, the mesh's
    `sharded_cycle_fn`): the head every launch has, the walk's origin
    reduced once, then the step the loops run. `step` is `_cycle_core`'s
    keywords; `next_last_index` goes back as the int64 the boundary has
    always had."""
    out = _cycle_core(nodes, pod, _walk_origin(last_index, n_real),
                      _i64(last_node_index), num_to_find, n_real, weights,
                      z_pad, **step)
    return {**out, "next_last_index": _i64(out["next_last_index"])}


def _cycle_core(nodes, pod, li, last_node_index, num_to_find, n_real,
                weights, z_pad, pos=None, full_scan=False, ghost=None,
                wtab=None, gang=None, local=None):
    """One fused cycle. The reference's sequential walk from last_index
    (generic_scheduler.go:486,519) is emulated WITHOUT materializing the
    rotation permutation: for natural index j, its 1-based rank in rotation
    order among feasible nodes is S[j]-pre (j >= li) or F-pre+S[j] (j < li),
    where S is the natural-order feasibility cumsum, pre = S[li-1], F = S[-1]
    — no gathers, int32 counters (TPU has no native int64).

    That holds for the two walk counters too. `li` is the walk's origin
    ALREADY REDUCED into [0, n), int32: every launch reduces its
    `last_index` once at its head (`_walk_origin`) and carries what this
    cycle hands back, `next_last_index`, which is reduced by construction
    (li < n and evaluated <= n, so one compare-and-subtract is the whole
    modulo). `last_node_index` stays the int64 it is upstream (an int64
    add is four scalar instructions); what a cycle needs of it is
    `_tie_index`, exact past 2**31 and 32-bit below. No 64-bit division or
    remainder by a traced scalar is left in a step
    (tests/test_walk_counters.py holds the loop bodies to that).

    When the per-cycle NodeTree enumeration differs from the device axis
    (uneven zones rotate the zone-interleaved order between cycles —
    node_tree.py rotation_map), `pos` supplies THIS cycle's order as
    positions: pos[j] = node j's place in the enumeration (the inverse of
    the permutation; rows past n_real keep their own index). No mask is
    ever moved into position space or back: with rel[j] = node j's offset
    from the walk's origin, everything the walk decides is an order
    statistic of rel, taken by a sort (scope `rotate`), and last_index keeps
    its positional meaning. pos=None is the identity fast path above.

    - The walk stops at the `num_to_find`-th smallest rel among feasible
      nodes, `kth`: kept = feasible & (rel <= kth), evaluated = kth + 1.
      With fewer feasible nodes than that the slot holds the mask's fill,
      which every rel is below: kept = feasible, evaluated = n. One [N]
      sort in `filter`; right for any num_to_find >= 1.
    - selectHost's k-th tie in walk order is the k-th smallest rel among
      the ties: one [N] sort in `pick`.

    `full_scan` (STATIC; with `pos` only) is the caller's word that
    num_to_find >= n_real, which it knows on the host before the launch:
    every feasible node is kept and the walk tests all n, so `filter` needs
    no order statistic and its sort is not compiled in. The two regimes are
    two programs because they want different work, chosen from the launch's
    own operands (schedule_batch), never by a switch. On a TPU v5e the
    permutation gathers these sorts replaced cost 67 us each at 8192 rows,
    three a step; what the sorts cost is in PERF.md section 5.

    `wtab` (tensor mode) is the resident [profiles x priorities] weight
    table; this pod's row is gathered by `pod["profile_id"]` and every
    score family scales by its lane (the static `weights` dict gates
    which families compile in — the cross-profile union). `gang` threads
    the rank-aware gang set-scoring input into _fit_scores, `local` the
    generic scan's carried row-local scores."""
    n_pad = nodes["valid"].shape[0]
    i32 = jnp.int32
    i = jnp.arange(n_pad, dtype=i32)
    nr = jnp.asarray(n_real, i32)
    ntf = jnp.asarray(num_to_find, i32)
    in_range = i < nr

    @jax.named_scope("rotate")
    def walk_offsets():
        # positions of valid nodes are distinct in [0, n), so are these
        return jnp.where(pos >= li, pos - li, nr - li + pos)

    # Nominated-ghost two-pass (podFitsOnNode :598,627) for resource-only
    # ghosts: pass 1 filters against ghost-augmented usage; pass 2 (without
    # ghosts) is implied, since removing pods only frees resources. Scores
    # run on the RAW rows — PrioritizeNodes never adds nominated pods.
    if ghost is not None:
        fnodes = {**nodes,
                  "req_cpu": nodes["req_cpu"] + ghost["cpu"],
                  "req_mem": nodes["req_mem"] + ghost["mem"],
                  "req_eph": nodes["req_eph"] + ghost["eph"],
                  "pod_count": nodes["pod_count"] + ghost["cnt"]}
    else:
        fnodes = nodes
    feasible, fail_first, general_bits = _feasibility(fnodes, pod)
    feas = feasible & in_range

    rel = None
    with jax.named_scope("filter"):
        if pos is not None:
            F = jnp.sum(feas.astype(i32))
            found = jnp.minimum(F, ntf)
            if full_scan:
                # every feasible node is kept and the walk tests all n
                kept = feas
                evaluated = nr
            else:
                rel = walk_offsets()
                kth = _kth_smallest(feas, rel, ntf - 1)
                kept = feas & (rel <= kth)
                evaluated = jnp.where(F >= ntf, kth + 1, nr)
        else:
            S = jnp.cumsum(feas.astype(i32))
            F = S[-1]                                   # total feasible
            pre = jnp.where(li > 0, S[jnp.maximum(li - 1, 0)], 0)
            after = i >= li
            rank = jnp.where(after, S - pre, F - pre + S)
            kept = feas & (rank <= ntf)
            found = jnp.minimum(F, ntf)
            # the node where the sequential walk stops: the one feasible j
            # with rank == num_to_find; evaluated = its rotation offset + 1
            jstar = jnp.argmax(kept & (rank == ntf)).astype(i32)
            stop = jnp.where(jstar >= li, jstar - li, nr - li + jstar)
            evaluated = jnp.where(F >= ntf, stop + 1, nr)
        # a skip (bucket-padding) pod consumes no rotation state
        evaluated = jnp.where(pod["skip"], 0, evaluated)

    wrow = None if wtab is None else wtab[pod["profile_id"]]
    total = _fit_scores(nodes, pod, kept, weights, z_pad, wrow=wrow,
                        gang=gang, local=local)

    with jax.named_scope("pick"):
        tmask = jnp.where(kept, total, jnp.iinfo(jnp.int64).min)
        max_score = jnp.max(tmask)
        is_tie = kept & (tmask == max_score)
        num_ties = jnp.maximum(jnp.sum(is_tie.astype(i32)), 1)
        # round-robin k-th tie in rotation order (selectHost :286-295)
        k = _tie_index(last_node_index, num_ties)
        if pos is not None:
            # k-th tie by enumeration position relative to the walk origin;
            # ties exclude invalid rows
            if rel is None:
                rel = walk_offsets()
            kth_tie = _kth_smallest(is_tie, rel, k)
            sel = jnp.argmax(is_tie & (rel == kth_tie)).astype(jnp.int64)
        else:
            T = jnp.cumsum(is_tie.astype(i32))
            preT = jnp.where(li > 0, T[jnp.maximum(li - 1, 0)], 0)
            trank = jnp.where(after, T - preT, T[-1] - preT + T)
            sel = jnp.argmax(is_tie & (trank == k + 1)).astype(jnp.int64)
    selected = jnp.where(found > 0, sel, -1)
    nxt, n_safe = li + evaluated, jnp.maximum(nr, 1)

    return {
        "selected": selected,
        "found": found.astype(jnp.int64),
        "evaluated": evaluated.astype(jnp.int64),
        "max_score": jnp.where(found > 0, max_score, 0),
        # how wide the top band was: the nodes selectHost chose among
        "num_ties": jnp.where(found > 0, num_ties, 0),
        "total": total,
        "kept": kept,
        "feasible": feasible,
        "fail_first": fail_first,
        "general_bits": general_bits,
        # (li + evaluated) mod n: both are at most n (an empty cluster walks
        # modulo 1, as `_walk_origin` does)
        "next_last_index": jnp.where(nxt >= n_safe, nxt - n_safe, nxt),
        # selectHost is skipped when only one node is feasible
        # (generic_scheduler.go:244-250), so the tie counter doesn't move
        "next_last_node_index": last_node_index + jnp.where(found > 1, 1, 0),
    }


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple"))
def _schedule_cycle_jit(nodes, pod, last_index, last_node_index, num_to_find,
                        n_real, z_pad, weights_tuple):
    weights = dict(weights_tuple)
    return _one_cycle(nodes, pod, last_index, last_node_index, num_to_find,
                      n_real, weights, z_pad)


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple"))
def _schedule_cycle_wtab_jit(nodes, pod, wtab, last_index, last_node_index,
                             num_to_find, n_real, z_pad, weights_tuple):
    return _one_cycle(nodes, pod, last_index, last_node_index, num_to_find,
                      n_real, dict(weights_tuple), z_pad, wtab=wtab)


def schedule_cycle(nodes, pod, last_index, last_node_index, num_to_find, n_real,
                   z_pad, weights=None, wtab=None):
    """One scheduling cycle. `nodes`/`pod` are dicts of device arrays.
    (Nominated-ghost cycles run only inside the pressure batch —
    _pressure_batch_jit — which calls _cycle_core with its carried ghost.)

    `wtab` (tensor mode) is the resident [P, K] profile weight table;
    `pod` must then carry `profile_id` and `weights` is the static union
    gate dict — ONE compiled program scores every profile."""
    weights_tuple = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    if wtab is not None:
        return _schedule_cycle_wtab_jit(
            nodes, pod, wtab, _i64(last_index), _i64(last_node_index),
            _i64(num_to_find), _i64(n_real), z_pad, weights_tuple)
    return _schedule_cycle_jit(
        nodes, pod, _i64(last_index), _i64(last_node_index), _i64(num_to_find),
        _i64(n_real), z_pad, weights_tuple)


# ---------------------------------------------------------------------------
# Batched burst: a loop over the burst's pods, folding decisions into node
# state
# ---------------------------------------------------------------------------
_MUTABLE = ("req_cpu", "req_mem", "req_eph", "req_scalar",
            "nz_cpu", "nz_mem", "pod_count")


def gang_carry_checkpoint(dev_nodes):
    """Group-boundary checkpoint of the device-resident carry (the gang
    generalization of the per-wave rewind contract). Device arrays are
    immutable: every in-trial fold builds NEW arrays (`state.at[...]` /
    `{**dev, **rows}`), leaving the checkpointed rows untouched on device —
    so a shallow dict copy pins the pre-gang matrix, and restoring it is a
    ZERO-COPY rewind (no host re-upload, no dispatch). The copy guards
    against in-place dict mutation only; the arrays themselves cannot be
    written. Invalidated by any dirty-row scatter or full re-upload between
    checkpoint and rewind (the caller tracks that with an epoch counter and
    falls back to discarding the matrix)."""
    return None if dev_nodes is None else dict(dev_nodes)


@jax.named_scope("fold")
def _fold_state(state, pod, sel, hit):
    """Fold one decision's resource delta into the mutable node state.

    Mirrors the cache's NodeInfo.AddPod aggregate update
    (reference: nodeinfo/node_info.go:498) applied to the dense matrix.
    """
    idx = jnp.maximum(sel, 0)
    delta = jnp.where(hit, 1, 0)
    return {
        "req_cpu": state["req_cpu"].at[idx].add(jnp.where(hit, pod["upd_cpu"], 0)),
        "req_mem": state["req_mem"].at[idx].add(jnp.where(hit, pod["upd_mem"], 0)),
        "req_eph": state["req_eph"].at[idx].add(jnp.where(hit, pod["upd_eph"], 0)),
        "req_scalar": state["req_scalar"].at[idx].add(
            jnp.where(hit, pod["upd_scalar"], jnp.zeros_like(pod["upd_scalar"]))),
        "nz_cpu": state["nz_cpu"].at[idx].add(jnp.where(hit, pod["nz_cpu"], 0)),
        "nz_mem": state["nz_mem"].at[idx].add(jnp.where(hit, pod["nz_mem"], 0)),
        "pod_count": state["pod_count"].at[idx].add(delta),
    }


# The generic scan carries the row-local scores of at most this many pod
# classes, a class being a distinct (nz_cpu, nz_mem) pair among a launch's
# pods. The board is [S_pad, n_pad] int64 in the loop's carry (16 classes at
# 16,384 rows: 2 MB). On a TPU v5e at 16,384 rows (PERF.md, PR 35) a step's
# one-column rescore costs about 40 us whether it holds 1 class or 8 (a chain
# of some 300 small dependent operations: latency, not width), and reading a
# pod's row costs about 1 us a class (8 us at 8); `_local_total` over every
# row costs 68. So the board wins by 27 us a step at one class and would stop
# winning near 28 classes; 16 keeps a margin, bounds the board's build at a
# launch's head (one `_local_total` over [S_pad, n_pad]) and the programs a
# process can compile (one per power of two: 1, 2, 4, 8, 16). A launch of
# more classes runs `_local_total` over every row, every step.
SCORE_CLASS_CAP = 16

# ... and only over at least this many node rows a device. The one-column
# rescore's 40 us do not shrink with the cluster; `_local_total` over every
# row does: 68 us at 16,384 rows, 37 at 8192. Measured on a TPU v5e (PERF.md,
# PR 35): at 16,384 rows the board takes 28-30 us off a step, at 8192 it adds
# 4 (125.9 against 121.9 us, and 1% of `pods_per_s` lost). Nothing between
# the two was measured; the bound is the size that was seen to win.
SCORE_BOARD_MIN_ROWS = 16384

# The generic scan carries the selector-spread counts of a launch's selector
# groups (a group: a namespace and the set of Services / ReplicaSets that
# select a pod), one [n_pad] int64 row each; a step reads its pod's row as
# the board's is read and adds one column. The carry has two widths. Up to
# this many groups it is padded to a power of two (2, 4, 8, 16; a launch of
# one group carries one vector, the program of every launch before the
# rows), so a stream of launches of about as many groups runs one program.
# It is also all a launch behind a serve loop carries
# (`TPUScheduler.spread_group_cap`): a loop pads EVERY window's carry to its
# cap so that no window compiles.
SPREAD_GROUP_CAP = SCORE_CLASS_CAP

# ... and a closed loop's launch of more groups than that carries this many
# rows whatever it holds (17 or 111), so a process meets ONE more scan
# program. Neither number is a semantic limit: the bindings are the serial
# oracle's however a pass is cut. The shell ends a burst segment before the
# pod whose group would be one more than a launch carries, and every segment
# pays a snapshot, the pod table's upkeep, an encode, a stack, a launch, a
# fetch and a commit wave: at 16 rows the load test's own 1000-pod pass (111
# Services named, ~103 met a pass) was 43 segments of 51 ms of which the
# device worked 3, 436 pods/s where a pass of eight Services read 3396
# (PERF_LEDGER, PR 50); at 128 it is one segment and 3073 (PERF.md, PR 51).
# Why 128: it holds that pass with room, and its price is the host's, a
# [128, 8192] int64 (8 MB) zero fill and upload a launch; 256 read no faster
# (3029 against 3062 pods/s on one seed, a traced pass 0.33 s against 0.30)
# and no cell sends more groups. On the device the width costs nothing that
# shows: a step reads its pod's row as a masked sum over the group axis at
# every G_pad, and at 128 rows x 8192 a TPU v5e runs the step in 137.5 us,
# the 8-row step of a pass of eight Services (137.5), where one dynamic
# slice for the row and one for `counts_for[h]`, masked for the pod nothing
# selects, ran it in 141.4, the 3.9 us under `filter` (PERF.md, PR 51: two
# seeds, both orders).
SPREAD_GROUP_WIDE = 128


def score_classes(nz_cpu, nz_mem, n_pods):
    """The score classes of a launch, made on the host from its stacked
    [B] `nz_cpu` / `nz_mem` pod columns: `(cls[B] int32, tab[S_pad, 2]
    int64)` with `tab[cls[i]] == (nz_cpu[i], nz_mem[i])` for every real pod
    (rows from `n_pods` on take class 0: they are never stepped over), or
    None when the launch holds more than SCORE_CLASS_CAP classes and has to
    rescore every row each step. S_pad is the class count rounded up to a
    power of two (the spare rows repeat class 0), so a stream of launches
    of about as many classes compiles one program."""
    cls = np.zeros(len(nz_cpu), np.int32)
    pairs = np.stack([np.asarray(nz_cpu, np.int64)[:n_pods],
                      np.asarray(nz_mem, np.int64)[:n_pods]], axis=1)
    if not len(pairs):
        return cls, np.zeros((1, 2), np.int64)
    tab, inverse = np.unique(pairs, axis=0, return_inverse=True)
    if len(tab) > SCORE_CLASS_CAP:
        return None
    cls[:n_pods] = inverse.reshape(-1)
    spare = (1 << (len(tab) - 1).bit_length()) - len(tab)
    return cls, np.concatenate([tab, np.repeat(tab[:1], spare, axis=0)])


def _batch_core(nodes, mut0, pods, n_pods, last_index, last_node_index,
                num_to_find, n_real, positions, oid_seq,
                spread0, z_pad, weights, rotate, carry_spread,
                full_scan=False, constrain=None, wtab=None,
                score_tab=None, counts_for=None):
    """Body of the generic burst kernel: one serial cycle per pod, each
    folding its decision into the carried node state.

    The pod count is a DYNAMIC operand of a single loop (as in
    _segments_core and _uniform_core): the [B, ...] operands keep the
    caller's bucket shape, so there is one compile per bucket, and the loop
    runs exactly `n_pods` iterations — 10,000 pods in a 16,384 bucket pay
    for 10,000 cycles. Rows from `n_pods` on are never read; their output
    rows keep a fixed fill (-1 in the packed block, 0 elsewhere).

    `rotate` (STATIC) says the NodeTree's order rotates between cycles:
    step i then walks `positions[oid_seq[i]]`, each node's place in that
    cycle's enumeration, and `full_scan` (STATIC) says every node is scored
    (`_cycle_core`). Without `rotate` every step walks the device axis and
    neither operand is read.

    `constrain` (optional) pins the node-axis carry — the mutable state
    rows and the carried spread vector — to a mesh sharding every
    iteration, so the O(N) sweep stays split across chips while the scalar
    select epilogue replicates (parallel/sharding.py wraps this for mesh
    mode; None = single-chip identity, the exact program the jit wrapper
    below compiles). `wtab` (tensor mode) makes the loop profile-aware:
    `pods["profile_id"]` [B] rides the operands, and each step's cycle
    gathers that pod's weight row — a window MIXING tenants scores in the
    one launch.

    `score_tab` [S_pad, 2] int64 (optional; `score_classes` makes it, with
    `pods["score_class"]` [B]) puts the SCORE BOARD into the carry:
    board[s, j] = `_local_total` of a pod of class s (its nz_cpu, nz_mem)
    against node row j. Those four priorities are row-local and a step
    moves one row (`_fold_state` adds the bound pod to row `sel`), so the
    board is built once at the launch's head, a step reads its pod's row
    of it in place of `_local_total` over every node, and after the fold
    rescores column `sel` alone for all S_pad classes — with the same
    function, so board and full recompute are the same int64 by
    construction; a step that bound nothing rescores an unmoved row and
    writes back what was there. None = `_local_total` over [N] every step,
    the program of every launch before the board. The caller chooses from
    the launch's operands: the board when there is no `wtab` (a per-pod
    weight row would make the board per profile), the launch holds at most
    SCORE_CLASS_CAP classes and a device holds at least
    SCORE_BOARD_MIN_ROWS node rows. Both are the one jitted function
    `_schedule_batch_jit` (None is an empty pytree), so a device trace and
    `tpu_compiles_total` keep finding the scan under the name they know.

    `spread0` (with `carry_spread`) is the selector-spread counts the loop
    carries, and its RANK says how. [n_pad]: every pod of the launch is
    selected by the same Services / ReplicaSets, so one count vector is
    carried and each placement folds +1 on its node
    (selector_spreading.go:66 counting semantics). [G_pad, n_pad], with
    `pods["spread_group"]` [B] and `counts_for` [G_pad, G_pad] bool: the
    launch holds pods of G_pad selector groups at most, row g the counts a
    pod of group g scores against, and `counts_for[h, g]` says a bound pod
    of group h counts toward row g (g's selectors all match it). A step
    reads its pod's row as the board's row is read, hands it to
    `_cycle_core` as the one vector it has always scored, and after the
    fold adds `counts_for[h]` to column `sel`. The rank is a static shape:
    a launch of one group is the rank-1 program, operand for operand, and
    so is G_pad: the same read and the same add at 2 rows and at
    SPREAD_GROUP_WIDE."""
    if constrain is None:
        constrain = lambda v: v
    assert score_tab is None or wtab is None
    i32 = jnp.int32
    static = {k: v for k, v in nodes.items() if k not in _MUTABLE}
    # the carried counts stand in for the stacked per-pod field, which the
    # caller leaves inert so that no [B, N] upload happens
    if carry_spread:
        pods = {k: v for k, v in pods.items() if k != "spread_counts"}
    grouped = carry_spread and spread0.ndim == 2
    assert grouped == (counts_for is not None)
    B = pods["skip"].shape[0]

    @jax.named_scope("score")
    def class_scores(rows, j=None):
        """`_local_total` of every class against the node rows `rows`: the
        whole board [S_pad, N], or column `j` of it [S_pad]. One function
        for the build and for a step's rescore."""
        cls_cpu, cls_mem = score_tab[:, 0], score_tab[:, 1]
        if j is None:
            cls_cpu, cls_mem = cls_cpu[:, None], cls_mem[:, None]
        at = (lambda v: v) if j is None else (lambda v: v[j])
        return _local_total(
            weights, cls_cpu + at(rows["nz_cpu"]), cls_mem + at(rows["nz_mem"]),
            at(static["alloc_cpu"]), at(static["alloc_mem"]))

    def constrain_board(board):
        # the node axis is the board's LAST, and the grouped spread rows'
        # (a step reads a contiguous row); `constrain` pins node-axis-first
        # trees
        return constrain(board.T).T

    constrain_spread = constrain_board if grouped else constrain

    def body(i, carry):
        state, li, lni, spread, board, packed, aux = carry
        pod = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
               for k, v in pods.items()}
        local = None
        if score_tab is not None:
            with jax.named_scope("score"):
                # the pod's row of the board, read as a masked sum over the
                # class axis: a dynamic slice along it costs a TPU v5e 16 us
                # at 8 classes x 16,384 rows, this 8, and with one class the
                # mask alone 2 (PERF.md, PR 35)
                if len(score_tab) == 1:
                    local = board[0]
                else:
                    mine = jnp.arange(len(score_tab)) == pod["score_class"]
                    local = jnp.sum(jnp.where(mine[:, None], board, 0),
                                    axis=0)
        pos = positions[oid_seq[i]] if rotate else None
        if grouped:
            with jax.named_scope("spread"):
                # the pod's row, a masked sum over the group axis like the
                # board's above, at SPREAD_GROUP_WIDE rows too (a slice
                # there is slower, above); group -1, the pod nothing
                # selects, matches no row: it reads zeros and moves none
                mine = jnp.arange(len(counts_for)) == pod["spread_group"]
                pod = {**pod, "spread_counts": jnp.sum(
                    jnp.where(mine[:, None], spread, 0), axis=0)}
        elif carry_spread:
            pod = {**pod, "spread_counts": spread}
        full = {**static, **state}
        out = _cycle_core(full, pod, li, lni, num_to_find, n_real, weights,
                          z_pad, pos=pos, full_scan=full_scan, wtab=wtab,
                          local=local)
        sel = out["selected"]
        hit = out["found"] > 0
        new_state = constrain(_fold_state(state, pod, sel, hit))
        if grouped:
            with jax.named_scope("spread"):
                # the bound pod counts toward every row whose selectors
                # all match it: row `mine` of counts_for, added to column
                # sel
                toward = jnp.any(mine[:, None] & counts_for, axis=0)
                spread = constrain_spread(
                    spread.at[:, jnp.maximum(sel, 0)].add(jnp.where(
                        hit & ~pod["skip"], toward, False).astype(
                            spread.dtype)))
        elif carry_spread:
            spread = constrain(spread.at[jnp.maximum(sel, 0)].add(
                jnp.where(hit & ~pod["skip"], 1, 0)))
        if score_tab is not None:
            idx = jnp.maximum(sel, 0)
            board = constrain_board(
                board.at[:, idx].set(class_scores(new_state, idx)))
        li, lni = out["next_last_index"], out["next_last_node_index"]
        packed = packed.at[:, i].set(jnp.stack([
            sel.astype(i32), li,
            (lni - last_node_index).astype(i32),
            out["num_ties"].astype(i32),
            (out["evaluated"] - out["found"]).astype(i32)]))
        aux = aux.at[:, i].set(jnp.stack([
            out["found"], out["evaluated"], out["max_score"], lni]))
        return new_state, li, lni, spread, board, packed, aux

    board0 = None if score_tab is None \
        else constrain_board(class_scores(mut0))
    init = (constrain(mut0), _walk_origin(last_index, n_real),
            last_node_index, constrain_spread(spread0),
            board0, jnp.full((5, B), -1, i32), jnp.zeros((4, B), jnp.int64))
    state, li, lni, spread, _board, packed, aux = jax.lax.fori_loop(
        jnp.int32(0), jnp.asarray(n_pods, i32), body, init)
    # ONE packed fetch block [5B] i32: selections, then the walk counters
    # AFTER each pod (li absolute — it is < n, and the loop carries it as
    # the int32 it is written as: reduced once above, `_walk_origin`, and by
    # a compare-and-subtract a step; lni as a delta from the launch's start
    # so it fits i32, the carry keeping the int64, whose tie index is exact
    # past 2**31, `_tie_index`) — a mid-burst failure's prefix rewind
    # reads the counters straight out of the single fetched block instead
    # of paying a second round trip for the evaluated/found vectors — then
    # two words a pod for the host's counters: the nodes that tied for the
    # best score, and the nodes the walk tested that did not fit
    outs = {"selected": packed[0].astype(jnp.int64), "li_after": packed[1],
            "found": aux[0], "evaluated": aux[1], "max_score": aux[2],
            "lni_after": aux[3], "packed": packed.reshape(5 * B)}
    return state, li.astype(jnp.int64), lni, spread, outs


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple", "rotate",
                                   "carry_spread", "full_scan"))
def _schedule_batch_jit(nodes, mut0, pods, n_pods, last_index,
                        last_node_index, num_to_find, n_real, positions,
                        oid_seq, spread0, score_tab, z_pad,
                        weights_tuple, rotate, carry_spread,
                        full_scan=False, counts_for=None):
    return _batch_core(nodes, mut0, pods, n_pods, last_index,
                       last_node_index, num_to_find, n_real, positions,
                       oid_seq, spread0, z_pad,
                       dict(weights_tuple), rotate, carry_spread,
                       full_scan=full_scan, score_tab=score_tab,
                       counts_for=counts_for)


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple", "rotate",
                                   "carry_spread", "full_scan"))
def _schedule_batch_wtab_jit(nodes, mut0, pods, n_pods, wtab, last_index,
                             last_node_index, num_to_find, n_real,
                             positions, oid_seq, spread0, z_pad,
                             weights_tuple, rotate, carry_spread,
                             full_scan=False, counts_for=None):
    return _batch_core(nodes, mut0, pods, n_pods, last_index,
                       last_node_index, num_to_find, n_real, positions,
                       oid_seq, spread0, z_pad,
                       dict(weights_tuple), rotate, carry_spread,
                       full_scan=full_scan, wtab=wtab,
                       counts_for=counts_for)


def _rotation_operands(rotation, num_to_find, n_real):
    """(rotate, full_scan, positions, oid_seq) of a launch: the two statics
    that choose its program and the order operands (placeholders when the
    tree never rotates). `full_scan` is read off the launch's own host
    integers; the axis program is one program at any quota."""
    if rotation is None:
        return (False, False, jnp.zeros((1, 1), jnp.int32),
                jnp.zeros(1, jnp.int32))
    positions, oid_seq = (jnp.asarray(a, jnp.int32) for a in rotation)
    return True, int(num_to_find) >= int(n_real), positions, oid_seq


def schedule_batch(nodes, pods, last_index, last_node_index, num_to_find, n_real,
                   z_pad, weights=None, rotation=None, spread0=None,
                   carry_in=None, mesh=None, wtab=None,
                   n_pods=None, classes=None, spread_groups=None):
    """Schedule a burst of pods against one snapshot, decisions serially
    equivalent to per-pod cycles. `pods` is a dict of [B, ...] arrays
    padded to the caller's bucket (one compile per bucket); `n_pods` is the
    DYNAMIC real count — the loop runs exactly that many cycles, rows from
    `n_pods` on are never read, and their rows of the outputs keep a fixed
    fill (-1 in `packed`). None = all B rows.

    `rotation` = (positions[L, n_pad], oid_seq[B]) supplies each in-burst
    cycle's NodeTree enumeration order when it differs from the device axis
    (uneven zones): positions[l][j] = node j's place in the enumeration
    under order l (the inverse of its permutation; rows past n_real keep
    their own index), oid_seq[i] the order of cycle i. None = the axis
    order every cycle. Whether the walk is truncated is read off the
    launch's own `num_to_find` and `n_real` (host integers): with every
    node scored (num_to_find >= n_real) the program without a sort in
    `filter` runs, the one with it otherwise (`_cycle_core`). `spread0`
    carries selector-spread counts across the burst: [n_pad] where one set
    of Services / ReplicaSets selects every pod, or [G_pad, n_pad] with
    `spread_groups` = (group[B] int32, counts_for[G_pad, G_pad] bool), one
    row a selector group (`_batch_core`; G_pad a power of two from 2 to
    SPREAD_GROUP_CAP, or SPREAD_GROUP_WIDE).

    `carry_in` = (mut_state, spread) chains a pipelined wave straight off
    the previous wave's device-resident carry (no host round trip):
    mut_state is the prior return's `state` dict (the _MUTABLE rows),
    spread its carried count vector. `last_index`/`last_node_index` may
    likewise be the prior launch's device scalars. Returns
    (state, li, lni, spread, outs); outs["packed"] is the ONE-fetch block
    [5B] i32 — selected | li-after-each-pod | lni-delta-after-each-pod |
    tied-nodes | tested-nodes-that-did-not-fit —
    so a caller fetches a single array per launch and re-derives any
    failure-prefix rewind from slices of it.

    `mesh` shards the node axis of the scan across a jax.sharding.Mesh
    (parallel/sharding.py): the SAME _batch_core program runs with the
    carried state pinned to NamedSharding(mesh, P("nodes")) and the select
    epilogue's tiny per-node vectors riding an ICI all-gather — sharded vs
    single-device is one code path parameterized by the sharding spec, so
    decisions are bit-identical by construction (pinned by
    tests/test_sharding.py + the sharded fuzz variants).

    `wtab` (tensor mode) is the [P, K] profile weight table (PRIORITY_AXIS
    columns); `pods` must then carry a `profile_id` [B] column and
    `weights` the static cross-profile union gate dict.

    `classes` = `score_classes(pods["nz_cpu"], pods["nz_mem"], n_pods)`,
    or None: with it (never beside a `wtab`) the loop carries the score
    board and a step rescores the one row it bound (_batch_core); without
    it every step scores every row. The same decisions either way."""
    weights_tuple = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    score_tab = None
    if classes is not None:
        assert wtab is None, "a per-pod weight row makes the board per profile"
        pods = {**pods, "score_class": classes[0]}
        score_tab = jnp.asarray(classes[1], jnp.int64)
    counts_for = None
    if spread_groups is not None:
        assert np.ndim(spread0) == 2 and carry_in is None
        pods = {**pods, "spread_group": spread_groups[0]}
        counts_for = jnp.asarray(spread_groups[1], bool)
    rotate, full_scan, positions, oid_seq = _rotation_operands(
        rotation, num_to_find, n_real)
    carry_spread = spread0 is not None or (
        carry_in is not None and carry_in[1] is not None)
    if carry_in is not None:
        mut0, s0 = carry_in
        if s0 is None:
            s0 = jnp.zeros((), jnp.int64)
    else:
        mut0 = {k: nodes[k] for k in _MUTABLE}
        s0 = jnp.asarray(spread0, jnp.int64) if spread0 is not None \
            else jnp.zeros((), jnp.int64)
    if wtab is not None:
        wtab = jnp.asarray(wtab, jnp.int64)
    n_pods = _i64(pods["skip"].shape[0] if n_pods is None else n_pods)
    if mesh is not None:
        from kubernetes_tpu.parallel import sharding as S
        fn = S.sharded_scan_fn(mesh, z_pad, weights_tuple, rotate,
                               carry_spread, full_scan,
                               use_wtab=wtab is not None)
        if wtab is not None:
            return fn(nodes, mut0, pods, n_pods, wtab, _i64(last_index),
                      _i64(last_node_index), _i64(num_to_find),
                      _i64(n_real), positions, oid_seq, s0,
                      counts_for=counts_for)
        return fn(nodes, mut0, pods, n_pods, _i64(last_index),
                  _i64(last_node_index), _i64(num_to_find), _i64(n_real),
                  positions, oid_seq, s0, score_tab, counts_for=counts_for)
    if wtab is not None:
        return _schedule_batch_wtab_jit(
            nodes, mut0, pods, n_pods, wtab, _i64(last_index),
            _i64(last_node_index), _i64(num_to_find), _i64(n_real),
            positions, oid_seq, s0, z_pad, weights_tuple, rotate,
            carry_spread, full_scan=full_scan, counts_for=counts_for)
    return _schedule_batch_jit(
        nodes, mut0, pods, n_pods, _i64(last_index), _i64(last_node_index),
        _i64(num_to_find), _i64(n_real), positions, oid_seq, s0,
        score_tab, z_pad, weights_tuple, rotate, carry_spread,
        full_scan=full_scan, counts_for=counts_for)


# ---------------------------------------------------------------------------
# Segmented burst: the whole wave chain — singleton runs AND gang segments —
# in ONE launch, with gang boundaries as scan segment boundaries
# ---------------------------------------------------------------------------
# The round-8 gang contract moved the atomicity boundary from the wave to the
# group, but the trial still ran as its own launch (one dispatch+fetch per
# gang, hundreds of them per drain of small gangs).
# This kernel fuses a whole drain window: the carry holds BOTH the live state
# (mutable rows, li, lni, spread, t) and a CHECKPOINT of it taken at each
# segment start; a gang member that finds no node rewinds the live carry to
# the checkpoint in-scan (gang_checkpoint/gang_rewind semantics, now inside
# the scan), the rest of its segment is skipped, and the next segment
# proceeds against the rewound state — exactly the serial shell's
# trial→reject→park→continue sequence, with zero extra round trips.
#
# `t` counts NodeTree enumerations actually consumed: each non-skipped cycle
# advances it, a gang rewind restores it, and the per-cycle rotation order is
# looked up as oid_seq[t] (not the scan position) — so a rejected gang leaves
# the rotation walk exactly where it found it, matching the serial world's
# tree.checkpoint()/restore(). The host pre-slices the walk long enough for
# the all-segments-succeed case; consumed entries never exceed that.
#
# A failed SINGLETON (non-gang) pod does not rewind anything: the host-side
# burst contract still discards everything from the first singleton failure
# (its serial rerun may preempt), and the packed block carries the per-pod
# walk counters so the prefix rewind costs no second fetch.


def _segments_core(nodes, mut0, pods, seg_start, gang, n_pods,
                   last_index, last_node_index, num_to_find, n_real,
                   positions, oid_seq, spread0, z_pad,
                   weights, rotate, carry_spread, full_scan=False,
                   constrain=None, wtab=None, gang_score=False):
    """`rotate` / `full_scan` (STATIC) and `positions` / `oid_seq` as in
    `_batch_core`, but the order id is looked up by enumerations CONSUMED
    (`oid_seq[t]`, see the block comment above).

    The pod count is a DYNAMIC operand of a single lax.while_loop (the
    uniform kernel's trick): the [B, ...] operands are padded to the
    caller's bucket for one compile per bucket, but the loop runs exactly
    `n_pods` iterations — a 1.5k-pod gang window inside a 16k bucket pays
    for 1.5k cycles, not 16k padded scan steps.

    `constrain` (optional) pins the node-axis pieces of BOTH carries — the
    live mutable rows/spread AND the in-scan gang checkpoint — to a mesh
    sharding each iteration (parallel/sharding.py wraps this for mesh
    mode; None = single-chip identity). The checkpoint/rewind pick() is a
    per-element where over identically-sharded operands, so a gang rewind
    stays shard-local — no collective beyond the select epilogue's
    all-gather.

    `wtab`/`gang_score` (round 19): profile weight-tensor gathering per
    pod, plus the rank-aware gang set-scoring carry — a tiny [z_pad]
    zone-count vector `gz` rides the live carry (and therefore the gang
    checkpoint/rewind machinery for free): it RESETS at every segment
    start, each placed GANG member one-hot-folds its node's zone, and
    later members of the same segment score nodes by
    min(members_in_zone, 10) * the member's profile gang weight
    (_fit_scores). A rewound gang restores gz with the rest of the
    carry; singleton segments never read it."""
    if constrain is None:
        constrain = lambda v: v
    i32 = jnp.int32
    static = {k: v for k, v in nodes.items() if k not in _MUTABLE}
    B = seg_start.shape[0]

    def pick(pred, new, old):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(pred, a, b), new, old)

    def body(carry):
        cur, chk, t, chk_t, failed, i, out = carry
        pod = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
               for k, v in pods.items()}
        sflag = seg_start[i]
        gflag = gang[i]
        if gang_score:
            # the gang zone-count vector resets at every segment start
            # BEFORE the checkpoint pick, so a rewind restores the reset
            # (zero) counts — exactly the serial trial's fresh tracker
            st_g, li_g, lni_g, sp_g, gz_g = cur
            gz_g = jnp.where(sflag, jnp.zeros_like(gz_g), gz_g)
            cur = (st_g, li_g, lni_g, sp_g, gz_g)
        # segment boundary: re-checkpoint the whole live carry (device
        # arrays are immutable, so this pins the pre-segment rows the same
        # way gang_carry_checkpoint does host-side — zero-copy)
        chk = pick(sflag, cur, chk)
        chk_t = jnp.where(sflag, t, chk_t)
        failed = jnp.where(sflag, False, failed)
        if gang_score:
            state, li, lni, spread, gz = cur
        else:
            state, li, lni, spread = cur
            gz = None
        # a member behind its segment's first failure consumes nothing:
        # the serial trial's post-failure decisions are discarded anyway
        eskip = pod["skip"] | (gflag & failed)
        pod = {**pod, "skip": eskip}
        pos = positions[oid_seq[t]] if rotate else None
        if carry_spread:
            pod = {**pod, "spread_counts": spread}
        full = {**static, **state}
        out_c = _cycle_core(full, pod, li, lni, num_to_find, n_real,
                            weights, z_pad, pos=pos, full_scan=full_scan,
                            wtab=wtab,
                            gang=(gz, gflag) if gang_score else None)
        sel = out_c["selected"]
        hit = out_c["found"] > 0
        new_state = constrain(_fold_state(state, pod, sel, hit))
        new_spread = spread
        if carry_spread:
            new_spread = constrain(spread.at[jnp.maximum(sel, 0)].add(
                jnp.where(hit & ~eskip, 1, 0)))
        if gang_score:
            # a placed gang member one-hot-folds its node's zone into the
            # segment's count vector (zone 0 = "no zone" never counts)
            selz = static["zone_id"][jnp.maximum(sel, 0)]
            gadd = hit & ~eskip & gflag & (selz > 0)
            new_gz = gz + ((jnp.arange(z_pad, dtype=selz.dtype) == selz)
                           & gadd).astype(gz.dtype)
            new_cur = (new_state, out_c["next_last_index"],
                       out_c["next_last_node_index"], new_spread, new_gz)
        else:
            new_cur = (new_state, out_c["next_last_index"],
                       out_c["next_last_node_index"], new_spread)
        new_t = t + jnp.where(eskip, 0, jnp.int32(1))
        # gang member found no node: rewind the live carry to the segment
        # checkpoint — the in-scan gang_rewind
        fail_now = gflag & ~hit & ~eskip
        cur2 = pick(fail_now, chk, new_cur)
        t2 = jnp.where(fail_now, chk_t, new_t)
        failed = failed | fail_now
        li2, lni2 = cur2[1], cur2[2]
        col = jnp.stack([
            jnp.where(hit & ~eskip, sel, jnp.int64(-1)).astype(i32),
            li2,
            (lni2 - last_node_index).astype(i32),
            t2])
        return (cur2, chk, t2, chk_t, failed, i + 1, out.at[:, i].set(col))

    li0 = _walk_origin(last_index, n_real)
    if gang_score:
        init_cur = (constrain(mut0), li0, last_node_index,
                    constrain(spread0), jnp.zeros(z_pad, jnp.int64))
    else:
        init_cur = (constrain(mut0), li0, last_node_index,
                    constrain(spread0))
    out0 = jnp.full((4, B), -1, i32)
    init = (init_cur, init_cur, jnp.int32(0), jnp.int32(0),
            jnp.zeros((), bool), jnp.int32(0), out0)
    Bn = jnp.asarray(n_pods, i32)
    (cur, _chk, _t, _ct, _f, _i, out) = jax.lax.while_loop(
        lambda c: c[5] < Bn, body, init)
    state, li, lni, spread = (cur[0], cur[1].astype(jnp.int64), cur[2],
                              cur[3])
    # ONE packed fetch block [4B] i32: selections (−1 = miss / rewound gang
    # member / padding), then the post-pod walk counters and the consumed-
    # enumeration count — every boundary the host commit needs (decided
    # prefixes, rejected-gang detection, rewind targets, NodeTree advance)
    # is a slice of this single array
    return state, li, lni, spread, out.reshape(4 * B)


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple", "rotate",
                                   "carry_spread", "full_scan"))
def _schedule_batch_seg_jit(nodes, mut0, pods, seg_start, gang, n_pods,
                            last_index, last_node_index, num_to_find, n_real,
                            positions, oid_seq, spread0, z_pad,
                            weights_tuple, rotate, carry_spread, full_scan):
    return _segments_core(nodes, mut0, pods, seg_start, gang, n_pods,
                          last_index, last_node_index, num_to_find, n_real,
                          positions, oid_seq, spread0, z_pad,
                          dict(weights_tuple), rotate, carry_spread,
                          full_scan=full_scan)


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple", "rotate",
                                   "carry_spread", "full_scan", "gang_score",
                                   "use_wtab"))
def _schedule_batch_seg_prof_jit(nodes, mut0, pods, seg_start, gang, n_pods,
                                 last_index, last_node_index, num_to_find,
                                 n_real, positions, oid_seq, spread0,
                                 wtab, z_pad, weights_tuple, rotate,
                                 carry_spread, full_scan, gang_score,
                                 use_wtab):
    return _segments_core(nodes, mut0, pods, seg_start, gang, n_pods,
                          last_index, last_node_index, num_to_find, n_real,
                          positions, oid_seq, spread0, z_pad,
                          dict(weights_tuple), rotate, carry_spread,
                          full_scan=full_scan,
                          wtab=wtab if use_wtab else None,
                          gang_score=gang_score)


def schedule_batch_segments(nodes, pods, seg_start, gang, n_pods,
                            last_index, last_node_index, num_to_find,
                            n_real, z_pad, weights=None, rotation=None,
                            spread0=None, mesh=None,
                            wtab=None, gang_score=False):
    """Schedule a segmented drain window — singleton runs and all-or-nothing
    gang segments — in ONE launch with ONE packed fetch (see block comment).

    `pods` is a dict of [B, ...] stacked arrays padded to the caller's
    bucket (one compile per bucket); `n_pods` is the DYNAMIC real count —
    the while_loop runs exactly that many cycles, so bucket padding costs
    nothing at run time. `seg_start[B]` marks each segment's first pod;
    `gang[B]` marks members of all-or-nothing segments.
    `rotation` follows schedule_batch's contract except the
    per-cycle order id sequence is indexed by enumerations CONSUMED (gang
    rewinds restore the cursor), so it must be the plain burst-wide walk,
    unsliced. Returns (state, li, lni, spread, packed[4B] i32) with
    packed = selected | li_after | lni_delta | t_after (entries past
    n_pods are -1 filler).

    `mesh` runs the SAME _segments_core program with the node axis of the
    live carry AND the gang checkpoint sharded across the mesh
    (parallel/sharding.py) — in-scan gang rewinds, rotation by consumed
    count t, and spread carries all run sharded, decisions bit-identical
    to the single-device kernel.

    `wtab` (tensor mode) is the [P, K] profile weight table (pods carry
    `profile_id` [B]); `gang_score=True` compiles the rank-aware gang
    set-scoring carry in (see _segments_core) — both off reproduce the
    pre-profile program exactly."""
    weights_tuple = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    rotate, full_scan, positions, oid_seq = _rotation_operands(
        rotation, num_to_find, n_real)
    mut0 = {k: nodes[k] for k in _MUTABLE}
    carry_spread = spread0 is not None
    s0 = jnp.asarray(spread0, jnp.int64) if carry_spread \
        else jnp.zeros((), jnp.int64)
    profile_mode = wtab is not None or gang_score
    if wtab is not None:
        wtab = jnp.asarray(wtab, jnp.int64)
    if mesh is not None:
        from kubernetes_tpu.parallel import sharding as S
        fn = S.sharded_segments_fn(mesh, z_pad, weights_tuple, rotate,
                                   carry_spread, full_scan,
                                   use_wtab=wtab is not None,
                                   gang_score=bool(gang_score))
        if profile_mode:
            w = wtab if wtab is not None else jnp.zeros(
                (1, len(PRIORITY_AXIS)), jnp.int64)
            return fn(nodes, mut0, pods, jnp.asarray(seg_start, bool),
                      jnp.asarray(gang, bool), _i64(n_pods),
                      _i64(last_index), _i64(last_node_index),
                      _i64(num_to_find), _i64(n_real), positions,
                      oid_seq, s0, w)
        return fn(nodes, mut0, pods, jnp.asarray(seg_start, bool),
                  jnp.asarray(gang, bool), _i64(n_pods), _i64(last_index),
                  _i64(last_node_index), _i64(num_to_find), _i64(n_real),
                  positions, oid_seq, s0)
    if profile_mode:
        w = wtab if wtab is not None else jnp.zeros(
            (1, len(PRIORITY_AXIS)), jnp.int64)
        return _schedule_batch_seg_prof_jit(
            nodes, mut0, pods, jnp.asarray(seg_start, bool),
            jnp.asarray(gang, bool), _i64(n_pods), _i64(last_index),
            _i64(last_node_index), _i64(num_to_find), _i64(n_real),
            positions, oid_seq, s0, w, z_pad, weights_tuple, rotate,
            carry_spread, full_scan, bool(gang_score), wtab is not None)
    return _schedule_batch_seg_jit(
        nodes, mut0, pods, jnp.asarray(seg_start, bool),
        jnp.asarray(gang, bool), _i64(n_pods), _i64(last_index),
        _i64(last_node_index), _i64(num_to_find), _i64(n_real),
        positions, oid_seq, s0, z_pad, weights_tuple, rotate,
        carry_spread, full_scan)


# ---------------------------------------------------------------------------
# Uniform-class burst: every pod in the burst shares one feature class
# ---------------------------------------------------------------------------
# The throughput workloads (ReplicaSet scale-ups; the scheduler_perf plain
# matrix) enqueue thousands of identical pods. For those, per-pod O(N) work
# is provably wasted: at percentageOfNodesToScore=100 with last_index == 0,
# selectHost's round-robin tie walk (generic_scheduler.go:286-295) assigns
# CONSECUTIVE pods to CONSECUTIVE tie ranks — `ix = lastNodeIndex % len(ties)`
# with lastNodeIndex incrementing by 1 — for as long as the tie set itself
# does not change. A node leaves the tie set only when a fold crosses one of
# the integer-truncation boundaries of the score formulas (every ~4th pod on
# a node at the scheduler_perf shape), so in the common regime the tie set is
# stable across hundreds of consecutive decisions.
#
# This kernel therefore schedules K pods per O(N) pass in one of two batch
# modes, chosen each pass by probing lane 0's post-fold state:
#
# - STAY: while every fold leaves its node AT max score and feasible, the
#   tie set is constant and consecutive pods take consecutive tie ranks
#   (lni+j mod T). Validated per lane; cut at the first leaver.
# - ELIM: while every fold REMOVES its node from the tie set (score drops
#   below max, or the placement bans the node — host-port conflicts and
#   self-matching hostname anti-affinity), the serial walk's shrinking
#   modulo `(lni+i) mod (T-i)` resolves to ORIGINAL tie ranks lni+2i for as
#   long as lni+i < T-i (quotient-0 prefix) and found_i = F-i stays > 1.
#   Validated per lane; cut at the first stayer.
#
# Ranks resolve with a vectorized searchsorted, K fold deltas scatter to
# (provably distinct) rows, and the longest valid prefix is accepted (always
# >= 1: pod 0's decision depends only on the pass-start state); the rest
# retry next pass, so the worst case degrades to one pod per pass and
# decisions stay bit-identical to the serial scan in all cases. Failure
# *reasons* are not computed — the shell re-runs unschedulable pods through
# the serial path, which reports them.
#
# The pod count is a DYNAMIC operand of a single lax.while_loop: one compile
# serves every burst size (no bucket padding, no trailing-segment waste).
#
# Eligibility (checked by the caller, tpu_scheduler._uniform_class): pods
# value-identical in requests, fold deltas, labels, and affinity/port specs;
# num_to_find >= n_real and last_index == 0. Per-node masks that cannot
# change in-burst (node selector/affinity, taints, unschedulable, hostname,
# existing-pod affinity state) merge into the static `extra_ok`; in-burst
# interactions reduce to the banned-node fold (`ban`: each placement bans
# its own node for the rest of the burst — exact for identical pods with
# host ports or self-matching hostname anti-affinity). Row-local scores
# shift all nodes equally when constant families (inert taint/spread/
# prefer-avoid, constant interpod counts) are dropped, so argmax and the
# round-robin tie walk match the generic kernel.

K_BATCH = 512        # pods resolved per O(N) pass (static)
B_CAP = 16384        # output-buffer capacity (static); callers chunk above it

# per-window device-arg conversion caches (round 17, serving prologue):
# uniform class scalars keyed by VALUE, the rotation perm table keyed by
# host-array identity (the entry pins the np object so ids cannot recycle)
_UNIFORM_CLS_CACHE: dict = {}
_PERM_DEV_CACHE: dict = {}


def _uniform_core(nodes, cls, n_pods, last_node_index, n_real,
                  perm, oid_seq, extra_ok, weights, flags,
                  b_cap, k_batch, rotate, ban, has_extra, constrain=None,
                  wtab=None, pid=None):
    """Body of the uniform-class burst kernel. `constrain` (optional) pins
    node-axis arrays — the carried [R, N1]/[N1] state and the static alloc
    vectors — to a mesh sharding so the O(N) sweep splits across chips while
    the scalar tie-walk epilogue replicates (parallel/sharding.py wraps this
    for the north-star multi-chip config; None = single-chip identity).

    `wtab`/`pid` (tensor mode): the window's shared weight row is gathered
    ONCE from the resident [P, K] table by the class's profile id — a
    uniform window is single-profile by construction (the profile id is
    part of the window's uniformity contract: different rows change the
    tie structure the K-batch modes rely on), so one compiled program
    serves every profile and the row is just data."""
    if constrain is None:
        constrain = lambda v: v
    wrow = None if wtab is None else wtab[pid]
    check_res, has_req, carry_eph, static_eph, carried_s, static_s = flags
    i32 = jnp.int32
    n_pad = nodes["valid"].shape[0]
    in_range = jnp.arange(n_pad, dtype=i32) < jnp.asarray(n_real, i32)
    ok = nodes["valid"] & in_range
    if has_extra:
        # static per-node masks: node selector/affinity, taints,
        # unschedulable, hostname, existing-pod (anti-)affinity state
        ok &= extra_ok
    if check_res and has_req:
        # resource families whose node-side state cannot change in-burst
        # (fold delta zero) collapse to a static mask
        if static_eph:
            ok &= ~(nodes["alloc_eph"] < cls["req_eph"] + nodes["req_eph"])
        for s in static_s:
            ok &= ~(nodes["alloc_scalar"][:, s]
                    < cls["req_scalar"][s] + nodes["req_scalar"][:, s])

    # one scratch column at index n_pad: inactive scatter/gather lanes park
    # there so active lanes (distinct by construction) never collide
    def pad1(v):
        return jnp.concatenate([v, jnp.zeros(1, v.dtype)])
    ok = constrain(pad1(ok))
    alloc_cpu = constrain(pad1(nodes["alloc_cpu"]))
    alloc_mem = constrain(pad1(nodes["alloc_mem"]))
    allowed = constrain(pad1(nodes["allowed_pods"]))
    alloc_eph = constrain(pad1(nodes["alloc_eph"]))

    rows = [nodes["req_cpu"], nodes["req_mem"], nodes["nz_cpu"],
            nodes["nz_mem"], nodes["pod_count"]]
    delta = [cls["upd_cpu"], cls["upd_mem"], cls["nz_cpu"], cls["nz_mem"], 1]
    ieph = None
    if carry_eph:
        ieph = len(rows)
        rows.append(nodes["req_eph"])
        delta.append(cls["upd_eph"])
    isc0 = len(rows)
    alloc_sc = []
    for s in carried_s:
        rows.append(nodes["req_scalar"][:, s])
        delta.append(cls["upd_scalar"][s])
        alloc_sc.append(constrain(pad1(nodes["alloc_scalar"][:, s])))
    st0 = constrain(jnp.stack([pad1(r) for r in rows]))
    delta_vec = jnp.stack([jnp.asarray(d, jnp.int64) for d in delta])
    I32_MIN = jnp.int32(-2**31)

    with jax.named_scope("score"):
        tot0 = constrain(_local_total(
            weights, cls["nz_cpu"] + st0[2], cls["nz_mem"] + st0[3],
            alloc_cpu, alloc_mem, wrow=wrow).astype(i32))
    jlane = jnp.arange(k_batch, dtype=i32)
    B = jnp.asarray(n_pods, i32)

    @jax.named_scope("filter")
    def resource_fit(rowvals, idx):
        """PodFitsResources for the incoming pod against row state `rowvals`
        ([R] or [R, K]) at node(s) `idx` — shared by the sweep and the
        post-fold stays check so the two cannot drift."""
        fit = ok[idx] if idx is not None else ok
        a_cpu = alloc_cpu[idx] if idx is not None else alloc_cpu
        a_mem = alloc_mem[idx] if idx is not None else alloc_mem
        a_pods = allowed[idx] if idx is not None else allowed
        if check_res:
            fit &= rowvals[4] + 1 <= a_pods
            if has_req:
                fit &= (a_cpu >= cls["req_cpu"] + rowvals[0]) \
                    & (a_mem >= cls["req_mem"] + rowvals[1])
                if carry_eph:
                    a_eph = alloc_eph[idx] if idx is not None else alloc_eph
                    fit &= a_eph >= cls["req_eph"] + rowvals[ieph]
                for jj, s in enumerate(carried_s):
                    a_s = alloc_sc[jj][idx] if idx is not None else alloc_sc[jj]
                    fit &= a_s >= cls["req_scalar"][s] + rowvals[isc0 + jj]
        return fit

    def lane_fit(rowvals, idx):
        """Post-fold score + feasibility of selected rows — shared by the
        lane-0 probe and the batch validation."""
        with jax.named_scope("score"):
            nt = _local_total(
                weights, cls["nz_cpu"] + rowvals[2],
                cls["nz_mem"] + rowvals[3],
                alloc_cpu[idx], alloc_mem[idx], wrow=wrow).astype(i32)
        return nt, resource_fit(rowvals, idx)

    def body(carry):
        st, tot, banned, lni, done, out = carry
        feas = resource_fit(st, None)
        if ban:
            feas &= ~banned
        with jax.named_scope("pick"):
            tm = jnp.where(feas, tot, I32_MIN)
            mx = jnp.max(tm)
            tie = feas & (tm == mx)
            T = jnp.sum(tie, dtype=i32)
            F = jnp.sum(feas, dtype=i32)
            T64 = T.astype(jnp.int64)
            remaining = B - done
            # the multi-pod paths need >= 2 ties (a single-tie fold can change
            # num_ties, shifting the modulo walk) and F > 1 (so lastNodeIndex
            # advances exactly 1 per pod); F == 0 means every remaining pod is
            # equally unschedulable -> emit-all -1
            kbig = (T >= 2) & (F > 1)
            if rotate:
                oid = jax.lax.dynamic_slice(oid_seq, (done,), (k_batch,))
                tie_perm = tie[perm]                     # [L, N1]
                C_all = jnp.cumsum(tie_perm.astype(i32), axis=1)
            else:
                C = jnp.cumsum(tie.astype(i32))

            # -- lane-0 probe: pick STAY vs ELIM batching (identical position
            # formula at lane 0, so the probe is mode-neutral)
            if ban:
                elim = kbig        # a placement always bans its own node
            else:
                pos0 = (lni % jnp.maximum(T64, 1)).astype(i32)
                if rotate:
                    c0 = C_all[oid[0]]
                    p0 = jnp.sum(c0 < pos0 + 1, dtype=i32)
                    sel0 = perm[oid[0], jnp.minimum(p0, n_pad)]
                else:
                    sel0 = jnp.searchsorted(C, pos0 + 1,
                                            method="compare_all").astype(i32)
                nt0, fit0 = lane_fit(st[:, sel0] + delta_vec, sel0)
                elim = ((nt0 != mx) | ~fit0) & kbig

            m_stay = jnp.minimum(jnp.minimum(remaining, k_batch), T)
            # ELIM quotient-0 prefix: lni + i < T - i, i.e. m <= (T - lni + 1)/2;
            # bans shrink F, so m <= F - 1 keeps found_i > 1 for every lane
            max_elim = jnp.maximum(((T64 - lni + 1) // 2).astype(i32), 1)
            m_elim = jnp.minimum(jnp.minimum(remaining, k_batch),
                                 jnp.minimum(max_elim, jnp.maximum(F - 1, 1)))
            if rotate:
                # the original-rank formula assumes ONE tie order; limit the
                # batch to this pass's constant-order prefix (ranks are distinct
                # within one order, so the rank->node map stays consistent).
                # Identity-heavy walks — uneven-zone clusters whose cursor sits
                # at a fixed point — keep FULL ELIM batching this way.
                same = jnp.cumprod((oid == oid[0]).astype(i32), dtype=i32)
                m_elim = jnp.minimum(m_elim, jnp.maximum(
                    jnp.sum(same, dtype=i32), 1))
            m = jnp.where(F == 0, jnp.minimum(remaining, k_batch),
                          jnp.where(elim, m_elim,
                                    jnp.where(kbig, m_stay, 1)))
            active = (jlane < m) & (F > 0)
            j64 = jlane.astype(jnp.int64)
            pos_stay = ((lni + j64) % jnp.maximum(T64, 1)).astype(i32)
            pos_elim = jnp.minimum(lni + 2 * j64,
                                   jnp.maximum(T64 - 1, 0)).astype(i32)
            pos = jnp.where(elim & (m > 1), pos_elim, pos_stay)
            if not rotate:
                # stable per-cycle order == the device axis: tie rank -> node via
                # one cumsum (positions are distinct for the chosen mode's valid
                # prefix, so active lanes never collide)
                selq = jnp.searchsorted(C, pos + 1, method="compare_all").astype(i32)
                sel = jnp.where(active, selq, n_pad)
            else:
                # per-cycle rotated orders: lane j ranks ties in the order of ITS
                # cycle (done + j), one of the <= L distinct zone-interleaved
                # enumerations in `perm` (NodeTree.order_for_start)
                crows = C_all[oid]                       # [K, N1]
                posp = jnp.sum(crows < (pos + 1)[:, None], axis=1, dtype=i32)
                selq = perm[oid, jnp.minimum(posp, n_pad)]
                sel = jnp.where(active, selq, n_pad)
            rows_after = st[:, sel] + delta_vec[:, None]
            new_tot, fit_after = lane_fit(rows_after, sel)
            # serial equivalence per lane: STAY needs every earlier fold to leave
            # its node AT max score and feasible (tie set unchanged); ELIM needs
            # every earlier fold to REMOVE its node (rank formula). Either way
            # the first offender's own decision is still exact -> cut after it.
            leaves = jnp.ones_like(fit_after) if ban \
                else ((new_tot != mx) | ~fit_after)
            fail = jnp.where(elim, ~leaves, leaves) & active
            first_bad = jnp.where(jnp.any(fail), jnp.argmax(fail).astype(i32),
                                  jnp.int32(k_batch))
            v = jnp.where(F == 0, m, jnp.minimum(first_bad + 1, m))
            if rotate:
                # distinct ranks under DIFFERENT orders can name the same node;
                # the second fold would see stale state — cut the batch before
                # the first duplicate (it retries next pass)
                owner = jnp.full(n_pad + 1, k_batch, i32).at[sel].min(
                    jnp.where(active, jlane, k_batch))
                dup = active & (owner[sel] != jlane)
                first_dup = jnp.where(jnp.any(dup), jnp.argmax(dup).astype(i32),
                                      jnp.int32(k_batch))
                v = jnp.minimum(v, first_dup)
                # F==0 emits no selections, so the dup cut (which needs F>0
                # lanes) cannot zero it: active is all-False there and v stays m
                v = jnp.where(F == 0, m, jnp.maximum(v, 1))
            accept = active & (jlane < v)
        with jax.named_scope("fold"):
            st = st.at[:, sel].add(
                jnp.where(accept[None, :], delta_vec[:, None], 0))
            # route non-accepted lanes to the scratch column: under rotation a
            # rejected lane's sel may DUPLICATE an accepted lane's node, and a
            # duplicate .set would clobber the accepted score write
            selw = jnp.where(accept, sel, n_pad)
            tot = tot.at[selw].set(new_tot)
            if ban:
                banned = banned.at[selw].max(accept)
            emit = jnp.where((jlane < v) & (F > 0), sel, -1)
            out = jax.lax.dynamic_update_slice(out, emit, (done,))
            lni = lni + jnp.where(F > 1, v, 0).astype(jnp.int64)
        return (constrain(st), constrain(tot), constrain(banned),
                lni, done + v, out)

    out0 = jnp.full(b_cap + k_batch, -1, i32)
    lni0 = jnp.asarray(last_node_index, jnp.int64)
    banned0 = constrain(jnp.zeros(n_pad + 1, dtype=bool))
    st, tot, _banned, lni, done, out = jax.lax.while_loop(
        lambda c: c[4] < B, body, (st0, tot0, banned0, lni0, jnp.int32(0), out0))
    # pack the lastNodeIndex advance into the selection buffer so the caller
    # fetches ONE array — each separate device->host read is its own host
    # synchronization
    out = out.at[b_cap].set((lni - lni0).astype(i32))

    unpad = lambda v: v[:n_pad]
    out_rows = {"req_cpu": unpad(st[0]), "req_mem": unpad(st[1]),
                "nz_cpu": unpad(st[2]), "nz_mem": unpad(st[3]),
                "pod_count": unpad(st[4])}
    if carry_eph:
        out_rows["req_eph"] = unpad(st[ieph])
    if carried_s:
        rs = nodes["req_scalar"]
        for jj, s in enumerate(carried_s):
            rs = rs.at[:, s].set(unpad(st[isc0 + jj]))
        out_rows["req_scalar"] = rs
    # the absolute lastNodeIndex stays DEVICE-RESIDENT so a pipelined wave
    # k+1 can launch from wave k's counter without a host round trip (the
    # packed delta above still lets the host track it from the fetch)
    return out_rows, out[: b_cap + 1], lni


@partial(jax.jit, static_argnames=("weights_tuple", "flags", "b_cap", "k_batch",
                                   "rotate", "ban", "has_extra"))
def _schedule_batch_uniform_jit(nodes, cls, n_pods, last_node_index, n_real,
                                perm, oid_seq, extra_ok, weights_tuple, flags,
                                b_cap, k_batch, rotate, ban, has_extra):
    return _uniform_core(nodes, cls, n_pods, last_node_index, n_real, perm,
                         oid_seq, extra_ok, dict(weights_tuple), flags, b_cap,
                         k_batch, rotate, ban, has_extra)


@partial(jax.jit, static_argnames=("weights_tuple", "flags", "b_cap",
                                   "k_batch", "rotate", "ban", "has_extra"))
def _schedule_batch_uniform_prof_jit(nodes, cls, n_pods, last_node_index,
                                     n_real, perm, oid_seq, extra_ok, wtab,
                                     pid, weights_tuple, flags, b_cap,
                                     k_batch, rotate, ban, has_extra):
    return _uniform_core(nodes, cls, n_pods, last_node_index, n_real, perm,
                         oid_seq, extra_ok, dict(weights_tuple), flags, b_cap,
                         k_batch, rotate, ban, has_extra, wtab=wtab, pid=pid)


def schedule_batch_uniform(nodes, cls, n_pods, last_node_index, n_real,
                           check_resources, weights=None, rotation=None,
                           extra_ok=None, ban=False, mesh=None, cap=None,
                           wtab=None, pid=0):
    """Uniform-class burst (see block comment above). `cls` holds the shared
    per-pod scalars: req_cpu/req_mem/req_eph, req_scalar[S], nz_cpu/nz_mem,
    upd_cpu/upd_mem/upd_eph, upd_scalar[S], has_request. Returns
    (folded_state_rows, packed[B_CAP+1], lni_device) where packed[:n_pods]
    are per-pod node indices (-1 = unschedulable), packed[B_CAP] is the
    lastNodeIndex advance — one array, one host fetch — and lni_device is
    the absolute post-burst lastNodeIndex as a device scalar, so a
    pipelined wave can pass it straight into the next launch
    (`last_node_index` accepts a device scalar or a host int). `n_pods`
    must be <= B_CAP; chunk larger bursts.

    `cap` (static, default B_CAP) sizes the packed output buffer: wave
    callers pass their fixed wave bucket so the per-wave fetch ships
    cap+1 int32s instead of the full 16K buffer (the lni-advance slot
    moves to packed[cap]).

    `rotation` = None when the per-cycle NodeTree enumeration is stable and
    equals the device axis; otherwise (perm[L, n_pad+1] int32 — the <= L
    distinct per-cycle orders as axis indices, scratch-padded — and
    oid_seq[B_CAP + K_BATCH] int32 — cycle t's order id, t counted from this
    burst's first pod).

    `extra_ok` [n_pad] bool merges burst-static per-node masks into
    feasibility; `ban=True` makes every placement ban its own node for the
    rest of the burst (identical pods with host ports / self-matching
    hostname anti-affinity)."""
    cap = B_CAP if cap is None else int(cap)
    if n_pods > cap:
        raise ValueError(f"uniform burst of {n_pods} exceeds cap={cap}")
    weights_tuple = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    # class scalars + derived flags + device conversion, cached by VALUE:
    # a serving loop dispatches hundreds of same-class windows per second,
    # and the eleven per-field jnp conversions were a measurable slice of
    # each window's encode span
    cls_key = (int(cls["req_cpu"]), int(cls["req_mem"]),
               int(cls["req_eph"]), cls["req_scalar"].tobytes(),
               int(cls["nz_cpu"]), int(cls["nz_mem"]),
               int(cls["upd_cpu"]), int(cls["upd_mem"]),
               int(cls["upd_eph"]), cls["upd_scalar"].tobytes(),
               bool(cls["has_request"]))
    hit = _UNIFORM_CLS_CACHE.get(cls_key)
    if hit is None:
        has_req = bool(cls.pop("has_request"))
        carry_eph = bool(cls["upd_eph"] != 0)
        static_eph = bool(not carry_eph and cls["req_eph"] != 0)
        carried_s = tuple(int(s) for s in range(len(cls["req_scalar"]))
                          if cls["upd_scalar"][s] != 0)
        static_s = tuple(int(s) for s in range(len(cls["req_scalar"]))
                         if cls["req_scalar"][s] != 0
                         and cls["upd_scalar"][s] == 0)
        cls_dev = {k: jnp.asarray(v, jnp.int64) for k, v in cls.items()}
        if len(_UNIFORM_CLS_CACHE) >= 64:
            _UNIFORM_CLS_CACHE.clear()
        hit = _UNIFORM_CLS_CACHE[cls_key] = (
            has_req, carry_eph, static_eph, carried_s, static_s, cls_dev)
    has_req, carry_eph, static_eph, carried_s, static_s, cls = hit
    flags = (bool(check_resources), has_req, carry_eph, static_eph,
             carried_s, static_s)
    if rotation is None:
        perm = jnp.zeros((1, 1), jnp.int32)      # unused placeholder
        oid_seq = jnp.zeros(1, jnp.int32)
    else:
        # the perm table is stable across a serving run's windows (cached
        # rows upstream): convert once per distinct host array, verified
        # by identity (the cache pins the np object, so ids can't recycle)
        ent = _PERM_DEV_CACHE.get(id(rotation[0]))
        if ent is None or ent[0] is not rotation[0]:
            if len(_PERM_DEV_CACHE) >= 64:
                _PERM_DEV_CACHE.clear()
            ent = (rotation[0], jnp.asarray(rotation[0], jnp.int32))
            _PERM_DEV_CACHE[id(rotation[0])] = ent
        perm = ent[1]
        oid_seq = jnp.asarray(rotation[1], jnp.int32)
    has_extra = extra_ok is not None
    extra = jnp.asarray(extra_ok, bool) if has_extra \
        else jnp.zeros(1, dtype=bool)
    if wtab is not None:
        wtab = jnp.asarray(wtab, jnp.int64)
    if mesh is not None:
        # north-star multi-chip config: node-axis state sharded over the
        # mesh, tie-walk epilogue replicated (parallel/sharding.py)
        from kubernetes_tpu.parallel import sharding as S
        fn = S.sharded_uniform_fn(mesh, weights_tuple, flags, cap, K_BATCH,
                                  rotation is not None, bool(ban), has_extra,
                                  use_wtab=wtab is not None)
        if wtab is not None:
            return fn(nodes, cls, _i64(n_pods), _i64(last_node_index),
                      _i64(n_real), perm, oid_seq, extra, wtab, _i64(pid))
        return fn(nodes, cls, _i64(n_pods), _i64(last_node_index),
                  _i64(n_real), perm, oid_seq, extra)
    if wtab is not None:
        return _schedule_batch_uniform_prof_jit(
            nodes, cls, _i64(n_pods), _i64(last_node_index), _i64(n_real),
            perm, oid_seq, extra, wtab, _i64(pid), weights_tuple, flags,
            cap, K_BATCH, rotation is not None, bool(ban), has_extra)
    return _schedule_batch_uniform_jit(
        nodes, cls, _i64(n_pods), _i64(last_node_index), _i64(n_real),
        perm, oid_seq, extra, weights_tuple, flags, cap, K_BATCH,
        rotation is not None, bool(ban), has_extra)


# ---------------------------------------------------------------------------
# Device preemption: vmapped victim selection + node pick
# ---------------------------------------------------------------------------
# Mirror of selectNodesForPreemption/selectVictimsOnNode/pickOneNode
# (generic_scheduler.go:966,1054,837). The reference fans victim selection
# out over 16 goroutines; here every candidate node runs at once:
#
#   1. remove ALL lower-priority pods per node, check the incoming pod fits
#   2. reprieve loop: victims arrive ALREADY SORTED by the host into the
#      reference's processing order (PDB-violating first, each group by
#      descending importance = priority desc, start asc); a lax.scan re-adds
#      one per step and keeps it iff the pod still fits
#   3. per-node aggregates feed the staged 5-criteria pick: fewest PDB
#      violations -> lowest FIRST-victim priority (the reference reads
#      Pods[0], :876) -> smallest sum of (priority + 2^31) -> fewest victims
#      -> latest earliest-start among the highest-priority victims -> first
#      in candidate order.
#
# Eligibility (host-checked): the fit that matters is resources + static
# masks only — no affinity/ports/volumes on the incoming pod or any
# potential victim, no active nominations. Anything else runs the oracle.

PREEMPT_P = 128    # victim slots per node (>= AllowedPodNumber cap of 110)

# Victim start times reach the device as int64 order keys, not float64: the
# node pick only ever compares them, integer compares are exact on every
# backend, and the TPU's emulated f64 need not hold a double's 53 bits.
START_KEY_INF = 0x7FF0000000000000       # start_order_key(+inf)


def start_order_key(start):
    """Host-side: float64 start times -> int64 keys that order (and tie)
    exactly like the floats. A non-negative double's bit pattern already
    orders like its value; negatives flip their magnitude bits."""
    bits = (np.asarray(start, np.float64) + 0.0).view(np.int64)   # -0 -> +0
    return np.where(bits < 0, bits ^ np.int64(0x7FFFFFFFFFFFFFFF), bits)


@jax.named_scope("filter")
def _victim_select(nodes, vic, valid_v, req_cpu, req_mem, req_eph,
                   ghost, feas_static, check_res, has_req, constrain=None):
    """selectVictimsOnNode over every node at once (:1054): remove all
    masked victims, check fit, then the order-dependent reprieve scan.
    `valid_v` [N, P] masks which slots are potential victims FOR THIS
    preemptor (priority < preemptor's); `ghost` ({cpu,mem,eph,cnt} [N] or
    None) adds non-removable nominated-pod usage — selectVictimsOnNode's
    fit runs the two-pass with them added (preemption.py:277), and for
    resource-only ghosts the without-pass is implied. `check_res`/`has_req`
    may be Python bools or traced booleans. Returns (feas0[N], victims[N,P],
    aggregates dict for the node pick). `constrain` (optional) pins the
    reprieve scan's [N] carry to a mesh sharding — the per-slot scan then
    runs every node row shard-local."""
    if constrain is None:
        constrain = lambda v: v
    i64 = jnp.int64
    n_pad = nodes["alloc_cpu"].shape[0]
    cr = jnp.asarray(check_res, bool)
    hr = jnp.asarray(has_req, bool) & cr
    nvic_all = jnp.sum(valid_v, axis=1, dtype=i64)
    base_cpu = nodes["req_cpu"] - jnp.sum(
        jnp.where(valid_v, vic["cpu"], 0), axis=1)
    base_mem = nodes["req_mem"] - jnp.sum(
        jnp.where(valid_v, vic["mem"], 0), axis=1)
    base_eph = nodes["req_eph"] - jnp.sum(
        jnp.where(valid_v, vic["eph"], 0), axis=1)
    base_cnt = nodes["pod_count"] - nvic_all
    if ghost is not None:
        base_cpu = base_cpu + ghost["cpu"]
        base_mem = base_mem + ghost["mem"]
        base_eph = base_eph + ghost["eph"]
        base_cnt = base_cnt + ghost["cnt"]

    def fits(rc, rm, re, pc):
        f = jnp.ones(n_pad, dtype=bool)
        f &= ~cr | (pc + 1 <= nodes["allowed_pods"])
        f &= ~hr | ((nodes["alloc_cpu"] >= req_cpu + rc)
                    & (nodes["alloc_mem"] >= req_mem + rm)
                    & (nodes["alloc_eph"] >= req_eph + re))
        return f

    feas0 = feas_static & fits(base_cpu, base_mem, base_eph, base_cnt)

    def step(carry, xs):
        rc, rm, re, pc = carry
        vcpu, vmem, veph, vval = xs
        nrc, nrm, nre = rc + vcpu, rm + vmem, re + veph
        npc = pc + jnp.where(vval, 1, 0)
        keep = fits(nrc, nrm, nre, npc) & vval & feas0
        return (constrain((jnp.where(keep, nrc, rc), jnp.where(keep, nrm, rm),
                           jnp.where(keep, nre, re), jnp.where(keep, npc, pc))),
                vval & ~keep)

    xs = (vic["cpu"].T, vic["mem"].T, vic["eph"].T, valid_v.T)   # [P, N]
    _carry, victim_t = jax.lax.scan(
        step, (base_cpu, base_mem, base_eph, base_cnt), xs)
    victims = victim_t.T & feas0[:, None]            # [N, P]

    nv = jnp.sum(victims, axis=1, dtype=i64)
    viol_ct = jnp.sum(victims & vic["violating"], axis=1, dtype=i64)
    first_idx = jnp.argmax(victims, axis=1)
    first_prio = jnp.take_along_axis(
        vic["prio"], first_idx[:, None], axis=1)[:, 0]
    sum_prio = jnp.sum(
        jnp.where(victims, vic["prio"] + (1 << 31), 0), axis=1)
    I64_MIN = jnp.iinfo(i64).min
    high = jnp.max(jnp.where(victims, vic["prio"], I64_MIN), axis=1)
    # start times are order keys (start_order_key): integer compares, exact
    earliest_high = jnp.min(
        jnp.where(victims & (vic["prio"] == high[:, None]),
                  vic["start"], START_KEY_INF), axis=1)
    return feas0, victims, {"nv": nv, "viol_ct": viol_ct,
                            "first_prio": first_prio, "sum_prio": sum_prio,
                            "earliest_high": earliest_high}


@jax.named_scope("pick")
def _pick_one_node(feas0, agg, order_rank):
    """pickOneNodeForPreemption (:837): zero-victim instant win, then the
    staged 5-criteria reduction, ties broken by first-in-candidate-order
    (`order_rank` — any strictly order-isomorphic ranking works)."""
    i32, i64 = jnp.int32, jnp.int64
    any_cand = jnp.any(feas0)
    zerov = feas0 & (agg["nv"] == 0)
    rank = jnp.asarray(order_rank, i64)
    BIGR = jnp.asarray(1 << 60, i64)

    def argmin_rank(mask):
        return jnp.argmin(jnp.where(mask, rank, BIGR)).astype(i32)

    m = feas0
    I64_MAX = jnp.iinfo(i64).max
    for crit in (agg["viol_ct"], agg["first_prio"], agg["sum_prio"],
                 agg["nv"]):
        m &= crit == jnp.min(jnp.where(m, crit, I64_MAX))
    # latest earliest-start wins (None start times read as +inf, :176-180)
    m &= agg["earliest_high"] == jnp.max(
        jnp.where(m, agg["earliest_high"], jnp.iinfo(i64).min))
    winner = jnp.where(jnp.any(zerov), argmin_rank(zerov), argmin_rank(m))
    return jnp.where(any_cand, winner, -1)


def _preempt_scan_core(nodes, vic, pod, feas_static, order_rank, n_real,
                       max_prio, check_res, has_req, constrain=None):
    i32 = jnp.int32
    n_pad = nodes["alloc_cpu"].shape[0]
    in_range = jnp.arange(n_pad, dtype=i32) < jnp.asarray(n_real, i32)
    # the resident victim table holds EVERY snapshot pod in reprieve order;
    # this preemptor's potential-victim mask is one device-side compare
    # (the sort key is priority-monotone, so masking preserves the order)
    valid_v = vic["valid"] & (vic["prio"] < max_prio)
    feas0, victims, agg = _victim_select(
        nodes, vic, valid_v, pod["req_cpu"], pod["req_mem"],
        pod["req_eph"], None, feas_static & in_range, check_res, has_req,
        constrain=constrain)
    winner = _pick_one_node(feas0, agg, order_rank)
    w = jnp.maximum(winner, 0)
    out = jnp.concatenate([
        jnp.stack([winner.astype(i32),
                   agg["nv"][w].astype(i32), agg["viol_ct"][w].astype(i32)]),
        victims[w].astype(i32)])
    return out


@partial(jax.jit, static_argnames=("check_res", "has_req"))
def _preemption_scan_jit(nodes, vic, pod, feas_static, order_rank, n_real,
                         max_prio, check_res, has_req):
    return _preempt_scan_core(nodes, vic, pod, feas_static, order_rank,
                              n_real, max_prio, check_res, has_req)


def preemption_scan(nodes, vic, pod, feas_static, order_rank, n_real,
                    check_resources, has_request, max_prio, mesh=None):
    """One launch over all candidate nodes. `vic` arrays are [N, P] slot
    planes of the persistent victim table — ALL snapshot pods pre-sorted
    into reprieve processing order per node; slots of priority >= `max_prio`
    (the preemptor's) are masked out on device. Returns packed i32
    [3 + P]: winner node index (-1 = no candidate), its victim count and
    PDB-violation count, then the winner's per-slot victim flags (aligned
    to the sorted order the host supplied). `mesh` runs the same scan with
    the node axis (rows + victim planes) sharded across the mesh."""
    if mesh is not None:
        from kubernetes_tpu.parallel import sharding as S
        fn = S.sharded_preempt_fn(mesh, bool(check_resources),
                                  bool(has_request))
        return fn(nodes, vic, pod, feas_static, order_rank, _i64(n_real),
                  _i64(max_prio))
    return _preemption_scan_jit(nodes, vic, pod, feas_static, order_rank,
                                _i64(n_real), _i64(max_prio),
                                bool(check_resources), bool(has_request))


# ---------------------------------------------------------------------------
# Batched preemption pressure: schedule-else-preempt scan over a failed tail
# ---------------------------------------------------------------------------
# The serial failure path pays one dispatch+readback round trip PER failed
# pod: schedule -> FitError -> victim scan ->
# nominate. This kernel runs the whole failed tail in ONE launch, replaying
# the reference's serial semantics exactly (scheduleOne -> preempt per pod,
# scheduler.go:438,292):
#
#   per pod, in queue order (priorities non-increasing — host-gated):
#   1. one _cycle_core schedule attempt with accumulated nominated-ghost
#      usage (podFitsOnNode two-pass, :598,627 — for resource-only ghosts
#      pass 2 is implied); a success folds its delta into the node state
#      like the burst kernel and consumes rotation/tie counters.
#   2. on failure, the victim scan (selectVictimsOnNode :1054 semantics,
#      _victim_select) over every node with this preemptor's victim mask
#      (slot priority < preemptor priority) and the ghost-augmented base
#      load; the 5-criteria pick chooses the node (:837); the winner's
#      usage folds into the ghost vector so later pods see the nomination.
#
#   `any_cand` replays nodesWherePreemptionMightHelp (:1142) from the
#   cycle's fail-first codes: a node is a candidate unless its FIRST
#   failing predicate's reasons contain an unresolvable member (:65-84) —
#   the caller needs this to distinguish "no candidates" (clear the pod's
#   own stale nomination, :330-333) from "candidates but no fit".


def _resolvable_candidates(fail_first, general_bits):
    """nodesWherePreemptionMightHelp from device fail codes: recorded
    failure reasons are the FIRST failing predicate's (pod_fits_on_node
    breaks on first failure); GENERAL carries host/selector bits whose
    reasons are unresolvable (generic_scheduler.go:65-84)."""
    unresolv = ((fail_first == FAIL_UNSCHEDULABLE)
                | (fail_first == FAIL_TAINTS)
                | (fail_first == FAIL_VOLZONE)
                | (fail_first == FAIL_VOLBIND)
                | ((fail_first == FAIL_GENERAL)
                   & (((general_bits >> BIT_HOST) & 1)
                      | ((general_bits >> BIT_SELECTOR) & 1)).astype(bool)))
    return ~unresolv


def _pressure_core(nodes, mut0, ghost0, pods, vic, last_index,
                   last_node_index, num_to_find, n_real, z_pad,
                   weights, constrain=None):
    """Body of the schedule-else-preempt pressure kernel. `constrain`
    (optional) pins the node-axis carries — the mutable rows and the
    accumulated nominated-ghost load — to a mesh sharding each step
    (parallel/sharding.py; None = single-chip identity). The victim planes
    are [N, P] node-axis-first and ride the callers' sharded upload."""
    if constrain is None:
        constrain = lambda v: v
    i32 = jnp.int32
    static = {k: v for k, v in nodes.items() if k not in _MUTABLE}
    n_pad = nodes["alloc_cpu"].shape[0]
    in_range = jnp.arange(n_pad, dtype=i32) < jnp.asarray(n_real, i32)
    axis_rank = jnp.arange(n_pad, dtype=jnp.int64)

    def step(carry, pod):
        mut, ghost, li, lni = carry
        full = {**static, **mut}
        out = _cycle_core(full, pod, li, lni, num_to_find, n_real, weights,
                          z_pad, ghost=ghost)
        sel = out["selected"]
        hit = out["found"] > 0
        skip = jnp.any(pod["skip"])
        mut2 = constrain(_fold_state(mut, pod, sel, hit))
        # victim scan with this preemptor's mask and the ghost base. The
        # static feasibility is the pod's own mask families (victim removal
        # cannot change them — eligibility host-gated): a winner must pass
        # every non-resource predicate outright.
        feas_stat = in_range & static["valid"]
        for key in ("sel_ok", "taints_ok", "unsched_ok", "host_ok",
                    "ports_ok", "disk_ok", "maxvol_ok", "volbind_ok",
                    "volzone_ok"):
            feas_stat = feas_stat & pod[key]
        feas_stat = feas_stat & (pod["interpod_code"] == 0)
        valid_k = vic["valid"] & (vic["prio"] < pod["pprio"])
        feas0, victims, agg = _victim_select(
            {**static, **mut}, vic, valid_k, pod["req_cpu"], pod["req_mem"],
            pod["req_eph"], ghost, feas_stat, pod["check_resources"],
            pod["has_request"], constrain=constrain)
        winner_raw = _pick_one_node(feas0, agg, axis_rank)
        cand = in_range & _resolvable_candidates(out["fail_first"],
                                                 out["general_bits"])
        any_cand = jnp.any(cand) & ~hit & ~skip
        preempted = (~hit) & (~skip) & (winner_raw >= 0)
        winner = jnp.where(hit, -2, jnp.where(skip, -1, winner_raw))
        w = jnp.maximum(winner_raw, 0)
        ghost2 = constrain({
            "cpu": ghost["cpu"].at[w].add(
                jnp.where(preempted, pod["upd_cpu"], 0)),
            "mem": ghost["mem"].at[w].add(
                jnp.where(preempted, pod["upd_mem"], 0)),
            "eph": ghost["eph"].at[w].add(
                jnp.where(preempted, pod["upd_eph"], 0)),
            "cnt": ghost["cnt"].at[w].add(jnp.where(preempted, 1, 0)),
        })
        return ((mut2, ghost2, out["next_last_index"],
                 out["next_last_node_index"]), {
            "selected": jnp.where(hit, sel, -1),
            "winner": winner,
            "any_cand": any_cand,
            "victims": victims[w].astype(jnp.int8),
        })

    init = (constrain(mut0), constrain(ghost0),
            _walk_origin(last_index, n_real), last_node_index)
    (mut, ghost, li, lni), outs = jax.lax.scan(step, init, pods)
    return mut, ghost, li.astype(jnp.int64), lni, outs


@partial(jax.jit, static_argnames=("z_pad", "weights_tuple"))
def _pressure_batch_jit(nodes, mut0, ghost0, pods, vic, last_index,
                        last_node_index, num_to_find, n_real, z_pad,
                        weights_tuple):
    return _pressure_core(nodes, mut0, ghost0, pods, vic, last_index,
                          last_node_index, num_to_find, n_real, z_pad,
                          dict(weights_tuple))


def pressure_batch(nodes, mut0, ghost0, pods, vic, last_index,
                   last_node_index, num_to_find, n_real, z_pad, weights=None,
                   mesh=None):
    """Schedule-else-preempt a failed burst tail in one launch. `pods` is a
    dict of [B, ...] stacked arrays (including `pprio` [B] preemptor
    priorities and the upd_* fold fields); `vic` arrays are [N, P] with ALL
    pods of priority < the batch maximum, pre-sorted per node into the
    reprieve processing order. Returns (mut_state, ghost, li, lni, outs)
    where outs carries per-pod: selected (>=0 bound host row, -1 failed),
    winner (-2 bound, -1 no preemption, >=0 nominated node row), any_cand,
    and the winner's victim slot flags [P]. `mesh` runs the same
    _pressure_core program with the node axis (mutable rows, ghost load,
    victim planes) sharded across the mesh — decisions bit-identical."""
    weights_tuple = tuple(sorted((weights or DEFAULT_WEIGHTS).items()))
    if mesh is not None:
        from kubernetes_tpu.parallel import sharding as S
        fn = S.sharded_pressure_fn(mesh, z_pad, weights_tuple)
        return fn(nodes, mut0, ghost0, pods, vic, _i64(last_index),
                  _i64(last_node_index), _i64(num_to_find), _i64(n_real))
    return _pressure_batch_jit(nodes, mut0, ghost0, pods, vic,
                               _i64(last_index), _i64(last_node_index),
                               _i64(num_to_find), _i64(n_real), z_pad,
                               weights_tuple)
