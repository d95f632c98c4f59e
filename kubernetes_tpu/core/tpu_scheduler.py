"""TPU-backed scheduling algorithm — the device twin of the oracle.

Drop-in for oracle.GenericScheduler (same schedule() contract, same
ScheduleResult/FitError), but filter/score/select run as one fused kernel
over the dense node matrix (ops/kernels.py). Decision parity: identical
suggested hosts, feasible sets, evaluated counts, and integer scores.

Two paths:
- schedule(): one pod per launch — used for parity testing and for pods with
  features the burst path doesn't batch yet.
- schedule_burst(): one device loop over many pending pods against one
  snapshot, folding each decision's resource delta into device state —
  serially-equivalent decisions at one launch (the throughput path;
  reference equivalent is the serial scheduleOne loop, scheduler.go:438).
  A launch's operands are padded to a power-of-two bucket (one compile per
  bucket); its trip count is the number of pods it was given.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from kubernetes_tpu.api.types import Pod
from kubernetes_tpu.cache.node_info import NodeInfo
from kubernetes_tpu.oracle import predicates as P
from kubernetes_tpu.oracle.priorities import counts_toward
from kubernetes_tpu.oracle.generic_scheduler import (
    ScheduleResult, FitError, num_feasible_nodes_to_find,
    DEFAULT_PERCENTAGE_OF_NODES_TO_SCORE,
)
from kubernetes_tpu.ops.node_state import (
    NodeStateEncoder, PodEncoder, PodFeatures, NodeBatch,
    IPA_EXISTING_ANTI, IPA_OWN_AFFINITY, IPA_OWN_ANTI,
)
from kubernetes_tpu.ops import kernels as K
from kubernetes_tpu import chaos, obs
from kubernetes_tpu.core import StaleNodeRefusal
from kubernetes_tpu.core.breaker import DeviceCircuitBreaker
from kubernetes_tpu.obs import trace as obs_trace
from kubernetes_tpu.obs import flight as obs_flight
from kubernetes_tpu.obs import ledger as obs_ledger

# exception classes the circuit breaker absorbs at the device seams: the
# chaos plane's injected DeviceFault only. A real jax runtime error (compile
# failure, out of memory, dead device) propagates to the caller.
_DEVICE_FAULTS = chaos.device_fault_types()

#: rotation-row cache miss sentinel (None is a legal cached value:
#: "this order IS the identity")
_ROT_MISS = object()

import jax
import jax.numpy as jnp

# device-pipeline counters (the /metrics view of the pipeline's cost model:
# every dispatch is a launch, every fetch a host synchronization that ships
# bytes, and every fallback/refusal moves work back to host Python)
DEVICE_DISPATCH = obs.counter(
    "tpu_device_dispatch_total",
    "Device program dispatches, by op.", ("op",))
DEVICE_FETCHED_BYTES = obs.counter(
    "tpu_device_fetched_bytes_total",
    "Bytes fetched device-to-host, by op.", ("op",))
DEVICE_FETCHES = obs.counter(
    "tpu_device_fetches_total",
    "Device-to-host fetch synchronizations, by op — each one is a full "
    "dispatch+readback round trip, so per-launch fetch counts are "
    "load-bearing (one per wave/launch, never per pod).", ("op",))
BURST_WAVES = obs.counter(
    "tpu_burst_waves_total",
    "Burst commit waves, by path — since round 10 a wave is a commit "
    "window consumed out of the single fetched decision block, not a "
    "separate device launch (tpu_device_fetches_total pins that).",
    ("path",))
BURST_SEGMENTS = obs.counter(
    "tpu_burst_scan_segments_total",
    "Segments scheduled through the fused segmented burst scan, by kind: "
    "'run' (singleton sub-ranges) and 'gang' (all-or-nothing PodGroup "
    "sub-ranges whose checkpoint/rewind happens inside the device carry).",
    ("kind",))
ORACLE_FALLBACKS = obs.counter(
    "tpu_oracle_fallback_total",
    "Decisions routed off the device path (host twin / serial rerun), "
    "by reason.", ("reason",))
PRESSURE_GATES = obs.counter(
    "tpu_pressure_gate_rejections_total",
    "preempt_pressure_burst refusals, by gate.", ("gate",))
SCATTER_ROWS = obs.counter(
    "tpu_scatter_rows_total",
    "Dirty node rows re-uploaded by the row scatter: the rows that "
    "changed, without the padding to the scatter's power-of-two bucket.")
WALK_NODES = obs.counter(
    "tpu_walk_nodes_evaluated_total",
    "Nodes the filter's walk tested (upstream's `evaluated`), summed over "
    "every decision of schedule_burst's launches, by regime: 'truncated' "
    "(num_to_find < n: the walk stops at its quota; read from the li_after "
    "block the scan launch fetches anyway) or 'full' (every node, n per "
    "pod).", ("regime",))
WALK_ENDED = obs.counter(
    "tpu_walk_ended_total",
    "Decisions of schedule_burst's generic scan launches under a truncated "
    "walk (num_to_find < n), by what ended the walk: 'quota' (it stopped at "
    "its num_to_find-th fitting node), 'nodes' (it tested every node and "
    "kept at least one, fewer than the quota) or 'none' (it tested every "
    "node and kept none: the pod is unschedulable, and what the launch "
    "decided after it is discarded and not counted). A launch that scores "
    "every node books nothing: its walks are over all nodes by rule. Read "
    "from the packed block the launch fetches anyway (nodes tested minus "
    "nodes rejected is nodes kept).", ("by",))
SCAN_STEPS = obs.counter(
    "tpu_scan_steps_total",
    "Steps the device ran in schedule_burst's generic scan launches, by "
    "kind: 'real' (one per pod). 'pad' (skip pods that filled the burst up "
    "to its power-of-two bucket) reads 0 since the pod count became the "
    "loop's trip count: the pad rows are never stepped over.", ("kind",))
SCAN_STEPS.labels("pad")
SCAN_ORDER_STEPS = obs.counter(
    "tpu_scan_order_steps_total",
    "Steps of schedule_burst's generic scan launches, by how a step finds "
    "its NodeTree enumeration order: 'axis' (no order shipped: the tree "
    "never rotates, so every step walks the device axis) or 'position' (a "
    "rotating tree: each node's position in the cycle's order is shipped, "
    "and a step sorts tie positions, under a truncated walk feasible "
    "positions too). 'gather' (a step that permuted its masks through "
    "perms/inv_perms) reads 0 since the truncated walk runs on positions "
    "as well: no launch ships a permutation. Booked once a launch, beside "
    "tpu_scan_steps_total.", ("order",))
SCAN_ORDER_STEPS.labels("gather")
SCAN_SCORE_STEPS = obs.counter(
    "tpu_scan_score_steps_total",
    "Steps of schedule_burst's generic scan launches, by how the row-local "
    "resource scores (least/most requested, RTCR, balanced) reached a "
    "step: 'carried' (the launch carries a score board of its pods' "
    "classes and a step rescores the one row it bound) or 'full' (every "
    "row rescored every step: a per-pod weight row, more classes than "
    "kernels.SCORE_CLASS_CAP, or fewer node rows a device than "
    "kernels.SCORE_BOARD_MIN_ROWS). Booked once a launch, beside "
    "tpu_scan_steps_total.", ("scores",))
SCAN_POD_ROWS = obs.counter(
    "tpu_scan_pod_rows_total",
    "Pods of schedule_burst's generic scan launches, by how a launch's "
    "[B] pod operand was made: 'stacked' (the launch held pods of more "
    "than one signature, so _stack_pods stacked rows taken from "
    "per-signature arrays) or 'shared' (every pod of the launch was one "
    "object, broadcast). Booked once a launch, beside "
    "tpu_scan_steps_total.", ("rows",))
SCAN_STACK_ROWS = obs.counter(
    "tpu_scan_stack_rows_total",
    "Rows of the [B] pod operands that _stack_pods made for the scan "
    "launches (schedule_burst's generic scan, the fused window, the "
    "pressure scan): 'built' (rows assembled from Python objects: one a "
    "distinct per-signature dict of the launch, and the pad row where the "
    "pods do not fill the bucket) and 'taken' (rows of the operand the "
    "gather filled: the bucket B). Booked once a launch, where the rows "
    "are stacked.", ("rows",))
SCAN_SPREAD_STEPS = obs.counter(
    "tpu_scan_spread_steps_total",
    "Steps of schedule_burst's generic scan launches, by how the launch "
    "carries selector-spread counts: 'none' (no Service or ReplicaSet "
    "selects its pods), 'single' (one set of them selects every pod: one "
    "[N] count vector in the loop's carry) or 'grouped' (pods of 2 to "
    "spread_group_cap selector groups, or of 1 and up beside pods "
    "that nothing selects: one count row a group, a step reads its pod's "
    "row and adds a column). Booked once a launch, beside "
    "tpu_scan_steps_total.", ("carry",))
SCAN_SPREAD_GROUPS = obs.counter(
    "tpu_scan_spread_groups_total",
    "Selector groups whose spread counts schedule_burst's generic scan "
    "launches carried: 1 for a 'single' launch, the 1 to "
    "spread_group_cap count rows its pods read for a 'grouped' "
    "one (the spare rows of the padded carry are not counted), nothing "
    "for 'none'. Booked once a launch, beside "
    "tpu_scan_spread_steps_total.")
SCAN_SPREAD_CARRY_LAUNCHES = obs.counter(
    "tpu_scan_spread_carry_launches_total",
    "schedule_burst's generic scan launches that carried selector-spread "
    "counts, by the padded row count of the launch's carry, which names "
    "the scan program it ran: '1' (the one [N] vector), '2', '4', '8', "
    "'16' (count rows, the spare ones included: a power of two of the "
    "groups held up to kernels.SPREAD_GROUP_CAP, which is also what every "
    f"grouped launch behind a serve loop carries) or '{K.SPREAD_GROUP_WIDE}' "
    "(kernels.SPREAD_GROUP_WIDE: a closed loop's launch of more groups "
    "than 16, and a segment that follows a groups cut of its drain "
    "pass); nothing for a launch that carries none. Booked once a "
    "launch, beside tpu_scan_spread_groups_total.", ("rows",))
SCAN_SPREAD_UNSELECTED_STEPS = obs.counter(
    "tpu_scan_spread_unselected_steps_total",
    "Steps of the 'grouped' launches of tpu_scan_spread_steps_total whose "
    "pod no Service or ReplicaSet selects: it rides the count rows with "
    "none of its own (group index -1), reads zeros and adds its binding "
    "to no row. Booked once a launch, beside "
    "tpu_scan_spread_steps_total.")
PICK_TIED_NODES = obs.counter(
    "tpu_pick_tied_nodes_total",
    "Nodes that tied for the best score (selectHost's round-robin set), "
    "summed over every decision of schedule_burst's generic scan launches; "
    "read from the packed block the launch fetches anyway.")
FILTER_REJECTED_NODES = obs.counter(
    "tpu_filter_rejected_nodes_total",
    "Nodes the filter's walk tested that did not fit (upstream's evaluated "
    "minus found), summed over every decision of schedule_burst's generic "
    "scan launches; read from the packed block the launch fetches anyway.")
DISCARDED_FOLDS = obs.counter(
    "tpu_burst_folds_discarded_total",
    "Device-resident burst folds dropped after a mid-burst failure.")
GANG_REWIND_FOLDS = obs.counter(
    "gang_rewind_folds_total",
    "Device-resident fold sets discarded by a gang (PodGroup) rewind — a "
    "trial-placed gang that missed minMember dropped its in-flight folds "
    "and the carries rewound to the pre-gang checkpoint.")

# burst phase -> (span name, span category, pod-lifecycle ledger slot).
# "kernel" is the async dispatch, which returns before the device finishes;
# "fetch" waits for the result, so it holds the device's execution time
# plus the readback.
_PHASES = {"encode": ("burst.encode", "host", obs_ledger.ENCODE),
           "kernel": ("burst.dispatch", "device", obs_ledger.DISPATCH),
           "fetch": ("burst.fetch", "device", obs_ledger.FETCH)}


class _BurstPhases:
    """The phase boundaries of one burst. `open(phase)` opens the phase's
    span; `close()` ends it and, at that same instant, stamps the ledger
    slot of every in-flight pod of the burst (one clock read + O(pods) dict
    writes; committed pods already left the ledger) and observes the phase
    histogram. `abandon()` ends a phase that a refusal or a fault cut
    short: the span stands (the host spent that time), nothing is stamped."""

    __slots__ = ("_metrics", "_keys", "_phase", "_span")

    def __init__(self, metrics, keys: list):
        self._metrics = metrics
        self._keys = keys
        self._phase = self._span = None

    def open(self, phase: str) -> None:
        name, cat, _slot = _PHASES[phase]
        self._phase = phase
        self._span = obs_trace.begin(name, cat=cat)

    def close(self, **args) -> None:
        phase, span = self._phase, self._span
        self._phase = self._span = None
        now = span.end(**args)
        if self._metrics is not None:
            self._metrics.observe_phase(phase, now - span.t0)
        obs_ledger.LEDGER.stamp_many(self._keys, _PHASES[phase][2], t=now)

    def abandon(self) -> None:
        if self._span is not None:
            self._span.end()
            self._phase = self._span = None

# every reason the victim-table eligibility gate can refuse a preemption
# for (the old single "victims-not-inert" label, split per class so
# /metrics shows WHICH gate sends scans back to the oracle). `preempt`
# prefixes with "preempt-victims-", preempt_pressure_burst with
# "victims-"; test_obs pins the set.
VICTIM_GATE_REASONS = ("affinity-terms", "ports", "scalar", "term-match",
                       "overflow")

# fallback/gate labels RETIRED in round 15: the sharded kernels now model
# rotation, carried spread, gang segments, and pressure scans, so these
# refusal paths were deleted outright. A dead label reading 0 forever would
# mask a silent regression back to host scheduling — test_obs pins that no
# live code path (and no eager registration) resurrects them.
RETIRED_FALLBACK_REASONS = ("burst-sharded-rotation", "burst-sharded-spread",
                            "fused-mesh-mode")
RETIRED_PRESSURE_GATES = ("mesh-mode",)


def _fetched_nbytes(obj) -> int:
    """Total nbytes of a fetched pytree (dict/list/tuple of ndarrays)."""
    if isinstance(obj, dict):
        return sum(_fetched_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_fetched_nbytes(v) for v in obj)
    return int(getattr(obj, "nbytes", 8))


def _pad_pow2(n: int, minimum: int = 1) -> int:
    c = minimum
    while c < n:
        c *= 2
    return c


@jax.jit
def _scatter_rows(dev: dict, rows, upd: dict) -> dict:
    """Write generation-dirty rows into the device-resident node matrix —
    the sparse delta upload of SURVEY §2.4 (mirror of the cache's
    incremental snapshot walk, reference cache.go:210-246). One dispatch
    for all fields."""
    out = dict(dev)
    for k, v in upd.items():
        out[k] = dev[k].at[rows].set(v)
    return out


class TPUScheduler:
    def __init__(self,
                 percentage_of_nodes_to_score: int = DEFAULT_PERCENTAGE_OF_NODES_TO_SCORE,
                 hard_pod_affinity_weight: int = 1,
                 services_fn=lambda: [],
                 replicasets_fn=lambda: [],
                 selector_index_fn=None,
                 collect_host_priority: bool = True,
                 nominated=None,
                 volume_listers=None, volume_binder=None,
                 node_tree=None,
                 serial_path: str = "device",
                 mesh=None):
        self.percentage_of_nodes_to_score = percentage_of_nodes_to_score
        self.hard_pod_affinity_weight = hard_pod_affinity_weight
        self.services_fn = services_fn
        self.replicasets_fn = replicasets_fn
        # the owner of the two lists may keep a `SelectorIndex` over them
        # (the shell's `LiveSelectorIndex`); without one, each PodEncoder
        # builds its own from the lists it is given
        self.selector_index_fn = selector_index_fn
        self.collect_host_priority = collect_host_priority
        self.check_resources = True   # PodFitsResources enabled (provider/policy)
        self.weights = None           # None -> kernels.DEFAULT_WEIGHTS
        self.enabled_predicates = None  # None -> all
        self.priority_name_weights = None  # provider/policy priorities by name
        # scheduling profiles (round 19): when a ProfileSet with real
        # multi-profile content attaches (set_profiles), scoring runs the
        # [profiles x priorities] weight-tensor path — per-pod rows
        # gathered on device by profile_id, one launch scoring every
        # profile; None / a degenerate default set keeps the exact
        # pre-profile kernel programs
        self.profiles = None
        self._ptab = None             # host [P, K] tensor (tensor mode)
        self._wtab_dev = None         # device-resident copy, lazy
        self._union_weights = None    # static cross-profile gate dict
        self._profile_static = None   # per-profile static kernel rows
        self._gang_score = False      # any profile rank-aware
        self._oracle_cfgs_prof = None  # per-profile host-twin configs
        # NominatedPodMap handle; when preemption has nominated pods, cycles
        # fall back to the oracle's two-pass fitting (podFitsOnNode :627) —
        # the device kernel doesn't model ghost pods yet
        self.nominated = nominated
        self.volume_listers = volume_listers
        self.volume_binder = volume_binder
        # NodeTree handle: burst decisions must replay the per-cycle
        # zone-interleaved enumeration rotation (node_tree.py rotation_map);
        # None = callers that feed a fixed name order (tests, sharded twin)
        self.node_tree = node_tree
        self._oracle = None
        self._oracle_cfgs = None
        self.last_index = 0
        self.last_node_index = 0
        # single-pod path policy: "device" (kernel always — the parity-test
        # configuration), "host" (twin always), "adaptive" (measure both,
        # use the faster; the production shell's choice)
        self.serial_path = serial_path
        self._lat_ora: Optional[float] = None
        self._lat_dev: Optional[float] = None
        self._serial_cycles = 0
        # multi-chip mode: node axis sharded over a jax.sharding.Mesh
        # (parallel/sharding.py — per-shard filter/score, ICI all-gather,
        # replicated select). mesh="auto" builds one over every visible
        # device; None stays single-chip. Cycles, generic-scan bursts AND
        # the uniform K-batch kernel all run sharded — the north-star
        # multi-chip config (BASELINE.json configs[4]) rides the uniform
        # path with per-shard sweeps and a replicated tie-walk epilogue.
        if mesh == "auto":
            import jax as _jax
            mesh = None
            if len(_jax.devices()) > 1:
                from kubernetes_tpu.parallel import sharding as S
                mesh = S.make_mesh()
        self.mesh = mesh
        self._sharded_cycle = None
        # optional SchedulerMetrics handle (the shell injects it): burst
        # calls observe encode/kernel/fetch phase durations
        # (scheduling_duration_seconds{operation}, metrics.go:67-169)
        self.metrics = None
        # mid-burst node-death scan (the shell injects
        # `(decided_hosts, all_names) -> dead set` against its store):
        # when a node vanishes between dispatch and commit, the wave
        # driver raises StaleNodeRefusal BEFORE any of the launch's
        # decisions commit — the shell invalidates the node and replans
        # post-churn
        self.stale_scan = None
        self.encoder = NodeStateEncoder()
        # device-resident node matrix: full upload on rebuild, dirty-row
        # scatter otherwise (SURVEY §2.4 delta uploader)
        self._dev_nodes: Optional[dict] = None
        self._dev_key = None
        # device-resident victim table (the [N, P] slot planes preemption
        # scans read): full upload on rebuild/permute, dirty-row scatter
        # otherwise — same delta contract as the node matrix
        self._dev_vic: Optional[dict] = None
        self._dev_vic_key = None
        # encode vs device-scan wall seconds of the last pressure launch
        # (perf.harness.run_preempt_cell returns the split)
        self.last_preempt_phases: Optional[dict] = None
        # upload/scatter epoch: bumps whenever HOST data lands in the
        # device matrix (burst folds do NOT bump it) — a gang checkpoint
        # whose epoch still matches can restore its pinned matrix without
        # a re-upload (kernels.gang_carry_checkpoint's zero-copy rewind)
        self._dev_epoch = 0
        # inert per-pod fields are shape [1] and broadcast in the kernel —
        # the common case uploads ~nothing (vs [N] per field per pod)
        self._defaults = {
            "ones_bool": np.ones(1, dtype=bool),
            "zeros_i64": np.zeros(1, dtype=np.int64),
            "zeros_i8": np.zeros(1, dtype=np.int8),
            "zeros_bool": np.zeros(1, dtype=bool),
            "tens_i64": np.full(1, 10, dtype=np.int64),
        }
        # shared scalar singletons: identical-by-identity inputs let
        # _stack_pods broadcast a field instead of gathering it
        self._true = np.bool_(True)
        self._false = np.bool_(False)
        self._zero_i64 = np.int64(0)
        self._zero_scalars: dict[int, np.ndarray] = {}
        # zero ghost-load vectors by n_pad (device arrays are immutable, so
        # every pressure launch can share one set instead of re-creating
        # four jnp.zeros per wave)
        self._ghost_zeros: dict[int, dict] = {}
        # device circuit breaker: a failed launch/fetch degrades that
        # burst/cycle to the serial oracle path (decisions identical);
        # repeated faults trip to host-only mode, re-promoted by a
        # half-open probe (core/breaker.py)
        self.breaker = DeviceCircuitBreaker()
        # walk counters at the last wave window handed to the commit
        # callback — the scheduler shell's crash-restart checkpoint source
        # (None = no exact per-window counters on this path)
        self.commit_marker: Optional[dict] = None
        # rotation-row cache (round 17): order_for_start(rr) -> axis-index
        # row, keyed on the NodeBatch's serial (a rebuild/permute makes a
        # batch with the next serial, which drops the rows). A serving
        # loop cuts hundreds of small windows per second against a stable
        # tree; without this every window re-extracts each distinct
        # enumeration order as an O(N) python walk — the encode prologue's
        # top cost at 1k nodes.
        self._rot_rows: dict[int, np.ndarray] = {}
        self._rot_rows_b: Optional[int] = None

    def _shared_zero_scalar(self, n: int) -> np.ndarray:
        arr = self._zero_scalars.get(n)
        if arr is None:
            arr = self._zero_scalars[n] = np.zeros(n, dtype=np.int64)
        return arr

    # -- scheduling profiles (round 19) --------------------------------------
    def set_profiles(self, profiles) -> None:
        """Attach a profiles.ProfileSet. In tensor mode (multiple
        profiles, non-default vectors, or any rank-aware profile) every
        scoring path switches to the resident [profiles x priorities]
        weight tensor: windows gather each pod's row by profile_id, the
        static `weights` dicts become the cross-profile union gate, and
        the fused segment kernel compiles the gang set-scoring carry in
        when any profile is rank-aware. A degenerate default set keeps
        the pre-profile programs — decisions trivially bit-identical."""
        self.profiles = profiles
        self._ptab = None
        self._wtab_dev = None
        self._union_weights = None
        self._profile_static = None
        self._gang_score = False
        self._oracle_cfgs_prof = None
        self._oracle_cfgs = None   # rebuilt per profile on next fallback
        if profiles is not None and profiles.tensor_mode():
            self._ptab = profiles.weight_table()
            self._union_weights = profiles.union_kernel_weights()
            self._profile_static = [profiles.kernel_row(i)
                                    for i in range(len(profiles))]
            self._gang_score = any(p.rank_aware for p in profiles)

    def _profile_id(self, pod: Pod) -> int:
        if self.profiles is None:
            return 0
        pid = self.profiles.index_of(pod.scheduler_name)
        return 0 if pid is None else pid

    def _profile_ids(self, pods: list):
        """Per-pod profile-id vector for a window (None off the tensor
        path). One np.take from the pod-row cache's profile_id column,
        stored at delivery, when every pod's slot is live; the per-pod
        fallback is bit-identical by the row contract."""
        if self._ptab is None:
            return None
        rc = self.pod_rows
        if rc is not None:
            g = rc.gather(pods, ("profile_id",))
            if g is not None:
                return g["profile_id"].astype(np.int64)
        return np.asarray([self._profile_id(p) for p in pods], np.int64)

    def _wtab(self):
        """The device-resident weight tensor (uploaded once; tiny, so it
        replicates across the mesh)."""
        if self._wtab_dev is None:
            tab = jnp.asarray(self._ptab, jnp.int64)
            if self.mesh is not None:
                from kubernetes_tpu.parallel import sharding as S
                tab = jax.device_put(tab, S.replicated(self.mesh))
            self._wtab_dev = tab
        return self._wtab_dev

    # -- device input assembly ----------------------------------------------
    _NODE_FIELDS = ("valid", "alloc_cpu", "alloc_mem", "alloc_eph",
                    "allowed_pods", "req_cpu", "req_mem", "req_eph",
                    "nz_cpu", "nz_mem", "pod_count", "alloc_scalar",
                    "req_scalar", "zone_id")

    def _node_arrays(self, b: NodeBatch) -> dict:
        """Device node matrix, kept resident across cycles; only rows the
        encoder marked generation-dirty are re-uploaded. In mesh mode the
        node axis is split across the chips at upload time."""
        key = (b.n_pad, len(b.scalar_names), b.serial)
        if self._dev_nodes is None or self._dev_key != key or b.dirty_rows is None:
            with obs_trace.span("burst.upload", cat="device", rows=b.n_pad):
                host = {k: np.asarray(getattr(b, k))
                        for k in self._NODE_FIELDS}
                if self.mesh is not None:
                    from kubernetes_tpu.parallel import sharding as S
                    self._dev_nodes = S.shard_node_arrays(self.mesh, host)
                else:
                    self._dev_nodes = {k: jnp.asarray(v)
                                       for k, v in host.items()}
            DEVICE_DISPATCH.labels("upload").inc()
            self._dev_epoch += 1
            self._dev_key = key
            b.dirty_rows = []   # host state fully mirrored; start tracking
            return self._dev_nodes
        if b.dirty_rows:
            # dedupe, then pad the row list to a power-of-two bucket
            # (duplicate writes of identical values are harmless) so the
            # scatter compiles per bucket, not per row count
            rows = np.asarray(sorted(set(b.dirty_rows)), dtype=np.int32)
            n_rows = len(rows)
            with obs_trace.span("burst.scatter", cat="device", rows=n_rows):
                bucket = _pad_pow2(n_rows, 16)
                rows = np.concatenate(
                    [rows,
                     np.full(bucket - n_rows, rows[0], dtype=np.int32)])
                upd = {k: getattr(b, k)[rows] for k in self._NODE_FIELDS}
                self._dev_nodes = _scatter_rows(self._dev_nodes, rows, upd)
            DEVICE_DISPATCH.labels("scatter").inc()
            SCATTER_ROWS.inc(n_rows)
            self._dev_epoch += 1
            b.dirty_rows = []
        return self._dev_nodes

    def _pod_arrays(self, f: PodFeatures, n_pad: int,
                    upd_fields: bool = False, pod: Optional[Pod] = None) -> dict:
        """Dense device inputs for one pod. Feature fields the pod doesn't
        exercise stay shape [1] (kernel broadcasts them) — `n_pad` is only
        the target for fields the encoder actually materialized."""
        d = self._defaults
        out = {
            "req_cpu": self._zero_i64 if f.req_cpu == 0 else np.int64(f.req_cpu),
            "req_mem": self._zero_i64 if f.req_mem == 0 else np.int64(f.req_mem),
            "req_eph": self._zero_i64 if f.req_eph == 0 else np.int64(f.req_eph),
            "req_scalar": (f.req_scalar if f.req_scalar.any()
                           else self._shared_zero_scalar(len(f.req_scalar))),
            "has_request": self._true if f.has_request else self._false,
            "unknown_scalar": self._true if f.unknown_scalars else self._false,
            "skip": self._false,
            "check_resources": self._true if self.check_resources else self._false,
            "nz_cpu": np.int64(f.nz_cpu),
            "nz_mem": np.int64(f.nz_mem),
            "sel_ok": f.sel_ok if f.sel_ok is not None else d["ones_bool"],
            "taints_ok": f.taints_ok if f.taints_ok is not None else d["ones_bool"],
            "unsched_ok": f.unsched_ok if f.unsched_ok is not None else d["ones_bool"],
            "ports_ok": f.ports_ok if f.ports_ok is not None else d["ones_bool"],
            "host_ok": f.host_ok if f.host_ok is not None else d["ones_bool"],
            "disk_ok": f.disk_ok if f.disk_ok is not None else d["ones_bool"],
            "maxvol_ok": f.maxvol_ok if f.maxvol_ok is not None else d["ones_bool"],
            "volbind_ok": f.volbind_ok if f.volbind_ok is not None else d["ones_bool"],
            "volzone_ok": f.volzone_ok if f.volzone_ok is not None else d["ones_bool"],
            "interpod_code": f.interpod_code if f.interpod_code is not None else d["zeros_i8"],
            "node_aff_counts": f.node_aff_counts if f.node_aff_counts is not None else d["zeros_i64"],
            "taint_counts": f.taint_counts if f.taint_counts is not None else d["zeros_i64"],
            "spread_counts": f.spread_counts if f.spread_counts is not None else d["zeros_i64"],
            "interpod_counts": f.interpod_counts if f.interpod_counts is not None else d["zeros_i64"],
            "interpod_tracked": f.interpod_tracked if f.interpod_tracked is not None else d["zeros_bool"],
            "image_sums": f.image_sums if f.image_sums is not None else d["zeros_i64"],
            "prefer_avoid": f.prefer_avoid if f.prefer_avoid is not None else d["tens_i64"],
        }
        if upd_fields:
            # node-state delta on add (regular containers only, node_info.py
            # calculate_resource; reference: node_info.go:578)
            from kubernetes_tpu.cache.node_info import calculate_resource
            upd = calculate_resource(pod)
            if upd.scalar:
                upd_scalar = np.zeros_like(f.req_scalar)
                for name, q in upd.scalar.items():
                    upd_scalar[list(self.encoder._scalar_vocab).index(name)] = q
            else:
                upd_scalar = self._shared_zero_scalar(len(f.req_scalar))
            out.update({
                "upd_cpu": self._zero_i64 if upd.milli_cpu == 0 else np.int64(upd.milli_cpu),
                "upd_mem": self._zero_i64 if upd.memory == 0 else np.int64(upd.memory),
                "upd_eph": self._zero_i64 if upd.ephemeral_storage == 0
                           else np.int64(upd.ephemeral_storage),
                "upd_scalar": upd_scalar,
            })
        return out

    def _stack_pods(self, per_pod: list[dict], bucket: int,
                    profile_ids=None) -> tuple[dict, int]:
        """(operands, signatures): the [bucket, ...] pod operands of a scan
        launch from its pods' dicts, and how many DISTINCT dicts those are
        (pods of one signature share one dict object). One row a distinct
        dict is stacked, plus the pad row where the pods do not fill the
        bucket (the last pod's row marked `skip`: it gives the operand its
        shape and is never stepped over), and one gather over a [bucket]
        index brings each field to its length: the Python-object work is
        O(fields x signatures), whatever the bucket.

        A field that is inert ([1]-shaped) for every pod stays [B, 1] — the
        scan broadcasts it — so plain pods upload O(B) data, not O(B*N).
        Fields holding the SAME object in every row (the shared inert
        defaults / scalar singletons) are broadcast views, not gathers.
        `profile_ids` (tensor mode) is a per-pod scalar and rides as the
        [bucket] vector it is, the pad rows taking the last pod's."""
        n = len(per_pod)
        ids = np.fromiter(map(id, per_pod), np.int64, n)
        _ids, first, index = np.unique(ids, return_index=True,
                                       return_inverse=True)
        rows = [per_pod[i] for i in first]
        signatures = len(rows)
        if n < bucket:
            rows.append(dict(per_pod[-1], skip=self._true))
            index = np.concatenate(
                [index, np.full(bucket - n, signatures, index.dtype)])
        out = {}
        for k in rows[0]:
            vals = [pp[k] for pp in rows]
            v0 = vals[0]
            if all(v is v0 for v in vals):
                out[k] = np.broadcast_to(v0, (bucket,) + np.shape(v0))
                continue
            shapes = {np.shape(v) for v in vals}
            if len(shapes) > 1:
                # mixed inert/dense: broadcast the inert ones up
                target = max(shapes, key=len) if len({len(s) for s in shapes}) > 1 \
                    else max(shapes)
                vals = [np.broadcast_to(v, target) for v in vals]
            out[k] = np.take(np.stack(vals), index, axis=0)
        if profile_ids is not None:
            out["profile_id"] = np.concatenate(
                [profile_ids, np.full(bucket - n, profile_ids[-1], np.int64)])
        SCAN_STACK_ROWS.labels("built").inc(len(rows))
        SCAN_STACK_ROWS.labels("taken").inc(bucket)
        return out, signatures

    # -- reason decoding -----------------------------------------------------
    def _decode_reasons(self, b: NodeBatch, f: PodFeatures, idx: int,
                        fail_first: np.ndarray, general_bits: np.ndarray) -> list[str]:
        code = int(fail_first[idx])
        if code == K.FAIL_UNSCHEDULABLE:
            return [P.ERR_NODE_UNSCHEDULABLE]
        if code == K.FAIL_TAINTS:
            return [P.ERR_TAINTS_TOLERATIONS_NOT_MATCH]
        if code == K.FAIL_DISK:
            return ["NoDiskConflict"]
        if code == K.FAIL_MAXVOL:
            return ["MaxVolumeCount"]
        if code in (K.FAIL_VOLBIND, K.FAIL_VOLZONE):
            if f.volbind_reasons and idx in f.volbind_reasons:
                return list(f.volbind_reasons[idx])
            return (["VolumeBindingNoMatch"] if code == K.FAIL_VOLBIND
                    else ["NoVolumeZoneConflict"])
        if code == K.FAIL_INTERPOD:
            ipa = int(f.interpod_code[idx]) if f.interpod_code is not None else 0
            if ipa == IPA_EXISTING_ANTI:
                return [P.ERR_POD_AFFINITY_NOT_MATCH,
                        P.ERR_EXISTING_PODS_ANTI_AFFINITY_RULES_NOT_MATCH]
            if ipa == IPA_OWN_AFFINITY:
                return [P.ERR_POD_AFFINITY_NOT_MATCH, P.ERR_POD_AFFINITY_RULES_NOT_MATCH]
            return [P.ERR_POD_AFFINITY_NOT_MATCH, P.ERR_POD_ANTI_AFFINITY_RULES_NOT_MATCH]
        # general predicates, reason order as predicates.general_predicates
        bits = int(general_bits[idx])
        reasons = []
        if bits & (1 << K.BIT_PODS):
            reasons.append(P.insufficient_resource("pods"))
        if bits & (1 << K.BIT_CPU):
            reasons.append(P.insufficient_resource("cpu"))
        if bits & (1 << K.BIT_MEM):
            reasons.append(P.insufficient_resource("memory"))
        if bits & (1 << K.BIT_EPH):
            reasons.append(P.insufficient_resource("ephemeral-storage"))
        for s, name in enumerate(b.scalar_names):
            if bits & (1 << (K.BIT_SCALAR0 + s)):
                reasons.append(P.insufficient_resource(name))
        if bits & (1 << K.BIT_UNKNOWN_SCALAR):
            reasons.extend(P.insufficient_resource(n) for n in f.unknown_scalars)
        if bits & (1 << K.BIT_HOST):
            reasons.append(P.ERR_POD_NOT_MATCH_HOST_NAME)
        if bits & (1 << K.BIT_PORTS):
            reasons.append(P.ERR_POD_NOT_FITS_HOST_PORTS)
        if bits & (1 << K.BIT_SELECTOR):
            reasons.append(P.ERR_NODE_SELECTOR_NOT_MATCH)
        return reasons

    def _oracle_fallback(self):
        from kubernetes_tpu.oracle.generic_scheduler import (
            GenericScheduler, default_priority_configs)
        if self._oracle is None:
            self._oracle = GenericScheduler(
                percentage_of_nodes_to_score=self.percentage_of_nodes_to_score,
                hard_pod_affinity_weight=self.hard_pod_affinity_weight,
                nominated_pods_fn=self.nominated.pods_for_node)
            if self.profiles is not None:
                # per-profile twin configs: the serial referee scores with
                # the SAME weight vector the tensor row carries
                self._oracle_cfgs_prof = [
                    self.profiles.oracle_configs(
                        i, services_fn=self.services_fn,
                        replicasets_fn=self.replicasets_fn,
                        hard_pod_affinity_weight=self.hard_pod_affinity_weight)
                    for i in range(len(self.profiles))]
                self._oracle_cfgs = self._oracle_cfgs_prof[0]
            elif self.priority_name_weights is not None:
                from kubernetes_tpu.factory import build_priority_configs
                self._oracle_cfgs = build_priority_configs(
                    self.priority_name_weights,
                    services_fn=self.services_fn,
                    replicasets_fn=self.replicasets_fn,
                    hard_pod_affinity_weight=self.hard_pod_affinity_weight)
            else:
                self._oracle_cfgs = default_priority_configs(
                    services_fn=self.services_fn, replicasets_fn=self.replicasets_fn,
                    hard_pod_affinity_weight=self.hard_pod_affinity_weight)
        return self._oracle

    # -- single-pod cycle ----------------------------------------------------
    # Adaptive path selection: a synchronous single-pod decision on the
    # device costs a full dispatch+readback round trip, while the host twin
    # costs O(nodes) Python. schedule() measures both and keeps using the
    # faster — decisions are identical either way (the twin is the parity
    # referee). The device is probed only once the twin's cycle exceeds
    # _DEVICE_PROBE_MS, so small clusters never pay a speculative round
    # trip; the slower path is re-probed periodically so a changed cluster
    # size can flip the choice back. The 30 ms threshold was chosen against
    # a round trip far longer than a local chip's; its premise is unmeasured
    # on the local chip (chip_smoke.py prints the warm round trip).
    _DEVICE_PROBE_MS = 30.0
    _REPROBE_EVERY = 1024

    def _schedule_host_twin(self, pod: Pod, node_infos: dict[str, NodeInfo],
                            all_node_names: list[str],
                            extra_configs=None) -> ScheduleResult:
        o = self._oracle_fallback()
        o.last_index, o.last_node_index = self.last_index, self.last_node_index
        from kubernetes_tpu.factory import (
            build_predicate_set, DEFAULT_PREDICATE_NAMES)
        funcs = build_predicate_set(
            sorted(self.enabled_predicates) if self.enabled_predicates
            else DEFAULT_PREDICATE_NAMES,
            node_infos, volume_listers=self.volume_listers,
            volume_binder=self.volume_binder,
            services_fn=self.services_fn)
        cfgs = self._oracle_cfgs
        if self._oracle_cfgs_prof is not None:
            cfgs = self._oracle_cfgs_prof[self._profile_id(pod)]
        if extra_configs:
            cfgs = list(cfgs) + list(extra_configs)
        try:
            return o.schedule(pod, node_infos, all_node_names,
                              predicate_funcs=funcs,
                              priority_configs=cfgs)
        finally:
            self.last_index = o.last_index
            self.last_node_index = o.last_node_index

    def _serial_pick_host_twin(self) -> bool:
        ora, dev = self._lat_ora, self._lat_dev
        if ora is None:
            return True                      # first cycle: host twin
        if ora < self._DEVICE_PROBE_MS / 1e3:
            return True                      # twin fast enough; don't probe
        if dev is None:
            return False                     # twin is slow: probe the device
        if self._serial_cycles % self._REPROBE_EVERY == 0:
            return ora >= dev                # re-probe the losing path
        return ora < dev

    def _device_fault(self, exc: BaseException) -> str:
        """Book one absorbed device fault with the circuit breaker; returns
        the seam name the injected fault carries."""
        seam = exc.seam
        self.breaker.record_fault(seam)
        return seam

    def schedule(self, pod: Pod, node_infos: dict[str, NodeInfo],
                 all_node_names: list[str],
                 extra_configs=None) -> ScheduleResult:
        if not all_node_names:
            raise FitError(pod, 0, {})
        self._serial_cycles += 1
        if extra_configs:
            # trial-scoped extra priorities (the rank-aware gang serial
            # referee's GangLocalityPriority, bound to live trial state):
            # the host twin IS the reference for that objective
            use_twin = True
            reason = "gang-locality-serial"
        elif self.nominated is not None and self.nominated.has_any():
            use_twin = True     # two-pass ghost-pod fitting lives on the twin
            reason = "nominated-ghosts"
        elif not self.breaker.allow_device():
            use_twin = True     # circuit open: host-only until a probe wins
            reason = "circuit-open"
        elif self.serial_path == "adaptive":
            use_twin = self._serial_pick_host_twin()
            reason = "adaptive-twin-faster"
        else:
            use_twin = self.serial_path == "host"
            reason = "serial-path-host"
        if use_twin:
            ORACLE_FALLBACKS.labels(reason).inc()
        import time as _time
        t0 = _time.perf_counter()
        try:
            if use_twin:
                return self._schedule_host_twin(pod, node_infos,
                                                all_node_names,
                                                extra_configs=extra_configs)
            try:
                return self._schedule_device(pod, node_infos, all_node_names)
            except _DEVICE_FAULTS as e:
                # a failed launch/fetch degrades THIS cycle to the host
                # twin — the decision is identical; only latency differs
                self._device_fault(e)
                ORACLE_FALLBACKS.labels("device-fault").inc()
                use_twin = True
                return self._schedule_host_twin(pod, node_infos,
                                                all_node_names)
        finally:
            dt = _time.perf_counter() - t0
            if use_twin:
                self._lat_ora = dt if self._lat_ora is None \
                    else 0.7 * self._lat_ora + 0.3 * dt
            else:
                self._lat_dev = dt if self._lat_dev is None \
                    else 0.7 * self._lat_dev + 0.3 * dt

    def _pod_encoder(self, node_infos: dict[str, NodeInfo],
                     b: NodeBatch) -> PodEncoder:
        """A PodEncoder over one encoded snapshot, with this algorithm's
        listers, settings and selector index."""
        index_fn = self.selector_index_fn
        return PodEncoder(
            node_infos, b, self.services_fn(), self.replicasets_fn(),
            hard_pod_affinity_weight=self.hard_pod_affinity_weight,
            enabled=self.enabled_predicates,
            volume_listers=self.volume_listers,
            volume_binder=self.volume_binder,
            state_encoder=self.encoder,
            selector_index=index_fn() if index_fn is not None else None)

    def _schedule_device(self, pod: Pod, node_infos: dict[str, NodeInfo],
                         all_node_names: list[str]) -> ScheduleResult:
        b = self.encoder.encode(node_infos, all_node_names)
        nodes = self._node_arrays(b)
        enc = self._pod_encoder(node_infos, b)
        feats = enc.encode(pod)
        pod_in = self._pod_arrays(feats, b.n_pad)
        wtab = None
        weights = self.weights
        if self._ptab is not None:
            # tensor mode: the pod's profile row is gathered on device —
            # one compiled cycle program scores every profile
            pod_in["profile_id"] = np.int64(self._profile_id(pod))
            wtab = self._wtab()
            weights = self._union_weights
        n = b.n_real
        num_to_find = num_feasible_nodes_to_find(n, self.percentage_of_nodes_to_score)
        z_pad = _pad_pow2(len(b.zone_names), 4)
        chaos.check("device.dispatch")
        if self.mesh is not None:
            # node axis split over the chips; collectives ride ICI and the
            # select epilogue replicates (parallel/sharding.py)
            from kubernetes_tpu.parallel import sharding as S
            ckey = (z_pad, wtab is not None)
            if self._sharded_cycle is None or self._sharded_cycle[0] != ckey:
                self._sharded_cycle = (ckey, S.sharded_cycle_fn(
                    self.mesh, z_pad=z_pad, weights=weights,
                    use_wtab=wtab is not None))
            pod_sharded = S.shard_pod_arrays(self.mesh, pod_in)
            if wtab is not None:
                out = self._sharded_cycle[1](
                    nodes, pod_sharded,
                    K._i64(self.last_index), K._i64(self.last_node_index),
                    K._i64(num_to_find), K._i64(n), wtab)
            else:
                out = self._sharded_cycle[1](
                    nodes, pod_sharded,
                    K._i64(self.last_index), K._i64(self.last_node_index),
                    K._i64(num_to_find), K._i64(n))
        else:
            out = K.schedule_cycle(nodes, pod_in, self.last_index,
                                   self.last_node_index,
                                   num_to_find, n, z_pad, weights=weights,
                                   wtab=wtab)
        # ONE device->host fetch for everything the decision needs: each
        # separate readback is its own host synchronization, so the scalars
        # and per-node vectors come back together
        fetch = {"selected": out["selected"], "found": out["found"],
                 "evaluated": out["evaluated"],
                 "next_last_index": out["next_last_index"],
                 "next_last_node_index": out["next_last_node_index"]}
        need_vectors = self.collect_host_priority
        if need_vectors:
            fetch.update(kept=out["kept"], total=out["total"],
                         fail_first=out["fail_first"],
                         general_bits=out["general_bits"])
        with obs_trace.span("cycle.fetch", cat="device"):
            chaos.node_dead_point("dispatch-fetch")
            chaos.check("device.fetch")
            h = jax.device_get(fetch)
            chaos.node_dead_point("fetch-commit")
        self.breaker.record_success()
        DEVICE_DISPATCH.labels("cycle").inc()
        DEVICE_FETCHES.labels("cycle").inc()
        DEVICE_FETCHED_BYTES.labels("cycle").inc(_fetched_nbytes(h))
        found = int(h["found"])
        evaluated = int(h["evaluated"])
        start = self.last_index
        self.last_index = int(h["next_last_index"])
        if found == 0:
            if need_vectors:
                fail_first, general_bits = h["fail_first"], h["general_bits"]
            else:
                fail_first, general_bits = jax.device_get(
                    (out["fail_first"], out["general_bits"]))
            failed = {}
            for pos in range(evaluated):
                idx = (start + pos) % n
                failed[b.names[idx]] = self._decode_reasons(
                    b, feats, idx, fail_first, general_bits)
            raise FitError(pod, n, failed)
        self.last_node_index = int(h["next_last_node_index"])
        sel = int(h["selected"])
        host = b.names[sel]
        host_priority = []
        failed = {}
        if need_vectors:
            kept, total = h["kept"], h["total"]
            fail_first, general_bits = h["fail_first"], h["general_bits"]
            for pos in range(evaluated):
                idx = (start + pos) % n
                if kept[idx]:
                    # single-feasible-node cycles skip scoring entirely
                    # (generic_scheduler.go:244-250)
                    score = 0 if found == 1 else int(total[idx])
                    host_priority.append((b.names[idx], score))
                elif fail_first[idx] != K.FAIL_NONE:
                    failed[b.names[idx]] = self._decode_reasons(
                        b, feats, idx, fail_first, general_bits)
        return ScheduleResult(host, evaluated, found, host_priority, failed)

    # -- burst path ----------------------------------------------------------
    # per-node mask fields that CANNOT change from in-burst placements —
    # they depend on node labels/taints/spec and pre-burst pods only
    _STATIC_MASKS = ("sel_ok", "taints_ok", "unsched_ok", "host_ok",
                     "ports_ok")
    # score/filter families the uniform kernel does not model at all
    _INERT_REQUIRED = ("disk_ok", "maxvol_ok", "volbind_ok", "volzone_ok",
                       "node_aff_counts", "taint_counts", "spread_counts",
                       "image_sums", "prefer_avoid")

    @staticmethod
    def _class_signature(pod: Pod):
        """Spec fields that determine a pod's device features against a fixed
        snapshot — equal signatures imply identical encoder output. The
        canonical definition lives in ops.pod_rows (the pod-row cache
        stores it at delivery); this staticmethod stays the public twin the
        parity tests pin against the native batch."""
        from kubernetes_tpu.ops.pod_rows import pod_class_signature
        return pod_class_signature(pod)

    @staticmethod
    def class_signatures(pods: list) -> list:
        """Batched _class_signature — the burst encode prologue's per-pod
        tuple build as ONE native call (commitcore.class_signatures) when
        the extension is built, with the per-pod function as the twin
        (tuples are equal element-for-element by construction; pinned by
        the commit-core parity tests). The one definition lives in
        ops.pod_rows, where a run of creates calls it at delivery."""
        from kubernetes_tpu.ops.pod_rows import class_signatures
        return class_signatures(pods)

    def _signatures(self, pods: list) -> list:
        """Window-prologue signatures: gathered from the pod-row cache,
        which stored them at delivery, when the shell attached one (interned — equal
        sigs are the SAME tuple object, so uniformity checks and the
        per-sig memos below hit by identity), else the batched native
        build. Values are bit-identical either way (pod_rows fuzz)."""
        rc = self.pod_rows
        if rc is not None:
            return rc.signatures(pods)
        return self.class_signatures(pods)

    def _uniform_class(self, p0: Pod, f0, b: NodeBatch,
                       node_infos: dict[str, NodeInfo]) -> Optional[tuple]:
        """Eligibility + class extraction for a burst of pods spec-identical
        to `p0` (the caller verified signatures): when the feature
        interactions are expressible as (static per-node mask, optional
        self-node ban), return (cls_scalars, extra_ok, ban); else None.

        Mirrors the eligibility contract in kernels.py: static families
        merge into extra_ok; in-burst interactions must reduce to each
        placement banning its own node (host ports / self-matching hostname
        anti-affinity); score families must be provably uniform across
        valid nodes so they cancel out of the tie structure."""
        from kubernetes_tpu.cache.node_info import calculate_resource
        from kubernetes_tpu.api.types import (
            get_container_ports, LABEL_HOSTNAME)
        if f0.unknown_scalars:
            return None
        upd = calculate_resource(p0)
        upd_scalar = np.zeros_like(f0.req_scalar)
        for name, q in upd.scalar.items():
            upd_scalar[list(self.encoder._scalar_vocab).index(name)] = q
        cls = {"req_cpu": f0.req_cpu, "req_mem": f0.req_mem,
               "req_eph": f0.req_eph, "req_scalar": f0.req_scalar,
               "nz_cpu": f0.nz_cpu, "nz_mem": f0.nz_mem,
               "upd_cpu": upd.milli_cpu, "upd_mem": upd.memory,
               "upd_eph": upd.ephemeral_storage,
               "upd_scalar": upd_scalar,
               "has_request": f0.has_request}
        for field in self._INERT_REQUIRED:
            if getattr(f0, field) is not None:
                return None
        nreal = b.n_real
        # interpod scores must be a constant shift: every valid node tracked
        # and equal counts -> min-max normalizes to 0 everywhere, and stays
        # 0 as in-burst placements (no preferred terms, symmetric hard
        # affinity over a single topology group) add uniformly
        if f0.interpod_counts is not None or f0.interpod_tracked is not None:
            tr, ic = f0.interpod_tracked, f0.interpod_counts
            if tr is None or not bool(np.all(tr[:nreal])):
                return None
            if ic is None or (nreal and int(np.ptp(ic[:nreal])) != 0):
                return None
        extra: Optional[np.ndarray] = None

        def and_mask(m) -> None:
            nonlocal extra
            if m is not None:
                mm = np.asarray(m, dtype=bool)
                if mm.shape[0] != b.n_pad:      # inert [1] fields
                    return
                extra = mm.copy() if extra is None else (extra & mm)

        for field in self._STATIC_MASKS:
            and_mask(getattr(f0, field))
        if f0.interpod_code is not None:
            and_mask(f0.interpod_code == 0)
        ban = bool(get_container_ports(p0))   # identical host ports conflict
        a = p0.affinity
        if a is not None and (a.pod_affinity is not None
                              or a.pod_anti_affinity is not None):
            pa, paa = a.pod_affinity, a.pod_anti_affinity
            if (pa and pa.preferred) or (paa and paa.preferred):
                return None

            def self_match(term) -> bool:
                if term.namespaces and p0.namespace not in term.namespaces:
                    return False
                return term.label_selector is not None \
                    and term.label_selector.matches(p0.labels)

            ban_anti = False
            for term in (paa.required if paa else ()):
                if self_match(term):
                    # in-burst placements ban their topology group; the
                    # node-ban fold is exact only for singleton groups
                    if term.topology_key != LABEL_HOSTNAME:
                        return None
                    ban_anti = True
            for term in (pa.required if pa else ()):
                if self_match(term):
                    # placements add matches in their group; feasibility
                    # stays at the static base only when every valid node
                    # is in ONE group (then it is all-pass after bootstrap)
                    vals = set()
                    for i in range(nreal):
                        node = node_infos[b.names[i]].node
                        vals.add(None if node is None
                                 else node.labels.get(term.topology_key))
                    if len(vals) != 1 or None in vals:
                        return None
            if ban_anti:
                hosts = set()
                for i in range(nreal):
                    node = node_infos[b.names[i]].node
                    h = None if node is None else node.labels.get(LABEL_HOSTNAME)
                    if h is None or h in hosts:
                        return None       # hostname groups must be singleton
                    hosts.add(h)
                ban = True
        return cls, extra, ban

    def _axis_order(self, all_node_names: list):
        """(axis_order, start0) for a burst launch: the node order to
        encode the mirror on, plus the zone-start index whose enumeration
        equals `all_node_names` when the resident axis is KEPT STALE.

        A rotating tree hands every window a differently-ordered
        enumeration; re-encoding the mirror on it forces an O(N) host
        permute plus a FULL device re-upload per window — the serving
        prologue's biggest fixed cost. But the kernels model per-cycle
        enumerations through the rotation program uniformly (cycle 0 is
        only special by convention), so when this launch's enumeration is
        provably order_for_start(r) of the resident axis's tree
        (NodeTree.last_enum_start + the membership-keyed order cache),
        the mirror keeps its axis and cycle 0 rides order id r — a
        gather, not a recompute. Any doubt (membership moved, caller-fed
        name lists, mid-state enumerations, non-rotating trees) falls
        back to axis == enumeration, the pre-round-17 behavior."""
        tree = self.node_tree
        b = self.encoder._batch
        if tree is None or b is None or b.names == all_node_names \
                or not self._tree_rotates():
            return all_node_names, None
        rr = tree.last_enum_start
        if rr is None:
            return all_node_names, None
        order = tree._order_cache.get(rr)
        if order is None or order != all_node_names:
            return all_node_names, None
        if len(b.names) != len(all_node_names) \
                or set(b.names) != set(all_node_names):
            return all_node_names, None   # membership moved: rebuild
        return b.names, rr

    def _rot_cached(self, b: NodeBatch, rr: int, identity: np.ndarray,
                    kind: str):
        """Padded axis-index row for the enumeration starting at zone
        index `rr`, or None when it equals the identity (axis) order —
        cached per NodeBatch serial (`kind` keys the two pad layouts:
        "u" pads with the n_pad scratch row, "g" with the invalid-row
        tail). The tree's orders are a function of its membership, and
        membership changes always rebuild/permute the batch (the next
        serial), so serial-keyed invalidation is exact."""
        if self._rot_rows_b != b.serial:
            self._rot_rows = {}
            self._rot_rows_b = b.serial
        key = (kind, rr)
        got = self._rot_rows.get(key, _ROT_MISS)
        if got is not _ROT_MISS:
            return got
        names = self.node_tree.order_for_start(rr)
        raw = np.fromiter((b.index[nm] for nm in names), np.int32,
                          len(names))
        if np.array_equal(raw, identity[: len(raw)]):
            row = None
        elif kind == "u":
            row = np.concatenate([
                raw, np.full(b.n_pad + 1 - len(raw), b.n_pad,
                             dtype=np.int32)])
        else:
            row = np.concatenate([
                raw, np.arange(b.n_real, b.n_pad, dtype=np.int32)])
        self._rot_rows[key] = row
        return row

    def _rot_identity(self, b: NodeBatch, kind: str) -> np.ndarray:
        """The axis-order (identity) permutation row, cached with the
        per-order rows."""
        if self._rot_rows_b != b.serial:
            self._rot_rows = {}
            self._rot_rows_b = b.serial
        key = ("id", kind)
        row = self._rot_rows.get(key)
        if row is None:
            if kind == "u":
                row = np.concatenate([
                    np.arange(b.n_real, dtype=np.int32),
                    np.full(b.n_pad + 1 - b.n_real, b.n_pad,
                            dtype=np.int32)])
            else:
                row = np.arange(b.n_pad, dtype=np.int32)
            self._rot_rows[key] = row
        return row

    def _burst_rotation(self, b: NodeBatch, n_pods: int,
                        start0: Optional[int] = None):
        """Per-cycle enumeration orders for a burst: pod 0 rides the device
        axis (the list_names() enumeration the shell just consumed); pod
        i >= 1 rides the order starting at the tree's current zone index
        walked i-1 steps through rotation_map. Returns None only when the
        tree can NEVER rotate (equal-size zones, single zone, no tree); an
        identity walk on a rotating tree still returns the (all-zero)
        machinery — rotation presence is a CLUSTER property, not a
        per-burst one, so the jit signature never flips between bursts
        (each flip costs a fresh multi-second XLA compile). The permutation
        row count is padded to a power-of-two bucket for the same reason."""
        if not self._tree_rotates():
            return None
        tree = self.node_tree
        nxt = tree.rotation_map()
        r = tree.zone_index
        length = n_pods + K.K_BATCH
        identity = self._rot_identity(b, "u")
        perm_rows = [identity]
        id_of_r: dict[int, int] = {}

        def order_id(rr: int) -> int:
            iid = id_of_r.get(rr)
            if iid is None:
                row = self._rot_cached(b, rr, identity, "u")
                if row is None:
                    iid = 0
                else:
                    perm_rows.append(row)
                    iid = len(perm_rows) - 1
                id_of_r[rr] = iid
            return iid

        seq = np.zeros(length, dtype=np.int32)
        if start0 is not None:
            # stale-axis mode (_axis_order): cycle 0's enumeration is
            # order_for_start(start0) of the RESIDENT axis, shipped as a
            # rotation order like every later cycle — no mirror permute
            seq[0] = order_id(start0)
        if nxt[r] == r:
            # fixed-point walk: every cycle >= 1 repeats P_r
            seq[1:] = order_id(r)
        else:
            for i in range(1, length):
                seq[i] = order_id(r)
                r = nxt[r]
        # stacked table cached by the row set (rows are pinned in the
        # per-batch cache, so the id tuple is stable): windows against a
        # stable tree reuse ONE host array — and downstream, one device
        # conversion (kernels._PERM_DEV_CACHE keys on its identity)
        skey = ("stack-u", tuple(map(id, perm_rows)))
        perms = self._rot_rows.get(skey)
        if perms is None:
            perms = np.stack(perm_rows)
            l_pad = _pad_pow2(len(perm_rows), 4)
            if len(perm_rows) < l_pad:
                perms = np.concatenate(
                    [perms,
                     np.repeat(perms[:1], l_pad - len(perm_rows), axis=0)])
            self._rot_rows[skey] = perms
        return perms, seq

    def _tree_rotates(self) -> bool:
        """True when the NodeTree's per-cycle enumeration can EVER differ
        from the device axis: multiple zones with uneven sizes (even sizes
        return the cursor to its start every full enumeration, so every
        cycle repeats the axis order)."""
        tree = self.node_tree
        if tree is None or len(tree._zones) <= 1:
            return False
        sizes = {len(tree._tree[z]) for z in tree._zones}
        return len(sizes) > 1

    def _generic_rotation(self, b: NodeBatch, bucket: int,
                          start0: Optional[int] = None):
        """(positions[L, n_pad], oid_seq[bucket]) for the generic scan:
        positions[l][j] is axis row j's place in enumeration order l (rows
        past n_real keep their own index, behind every valid node), and
        oid_seq[t] the order of in-burst cycle t. oid_seq[0] is the axis
        itself (the enumeration the shell just consumed for pod 0)."""
        tree = self.node_tree
        if tree is None:
            return None
        nxt = tree.rotation_map()
        r = tree.zone_index
        n_pad = b.n_pad
        identity = self._rot_identity(b, "g")
        perm_rows = [identity]
        id_of_r: dict[int, int] = {}

        def order_id(rr: int) -> int:
            iid = id_of_r.get(rr)
            if iid is None:
                row = self._rot_cached(b, rr, identity, "g")
                if row is None:
                    iid = 0
                else:
                    perm_rows.append(row)
                    iid = len(perm_rows) - 1
                id_of_r[rr] = iid
            return iid

        seq = np.zeros(bucket, dtype=np.int32)
        if start0 is not None:
            seq[0] = order_id(start0)   # stale-axis mode (_axis_order)
        for t in range(1, bucket):
            seq[t] = order_id(r)
            r = nxt[r]
        # the number of distinct orders varies with the starting zone index;
        # pad to a fixed row bucket so one compile serves every burst
        l_pad = _pad_pow2(len(perm_rows), 4)
        while len(perm_rows) < l_pad:
            perm_rows.append(perm_rows[0])
        skey = ("stack-g", tuple(map(id, perm_rows)))
        positions = self._rot_rows.get(skey)
        if positions is None:
            positions = np.empty((l_pad, n_pad), np.int32)
            for l, perm in enumerate(perm_rows):
                positions[l, perm] = np.arange(n_pad, dtype=np.int32)
            self._rot_rows[skey] = positions
        return positions, seq

    def _scan_rotation(self, b: NodeBatch, bucket: int,
                       start0: Optional[int]):
        """`rotation` for a generic scan launch, already on the device;
        None when the tree never rotates. The rotation program is selected
        from CLUSTER shape (uneven zones), not from whether THIS burst's
        walk happens to be the identity: the identity is just data (order
        id 0), while flip-flopping the jit signature between bursts costs a
        fresh 10s+ XLA compile mid-workload each time the zone cursor lands
        on a fixed point. What is shipped is the <= L distinct orders as
        POSITIONS and each cycle's order id, whatever num_to_find is: a
        step finds the k-th tie, and under a truncated walk the node the
        walk stops at, by sorting positions (kernels._cycle_core), and
        which of the two programs a launch runs is read off its own
        num_to_find and n (kernels.schedule_batch)."""
        if not self._tree_rotates():
            return None
        sp = obs_trace.begin("burst.rotation", cycles=bucket)
        positions, seq = self._generic_rotation(b, bucket, start0)
        up = (jnp.asarray(positions, jnp.int32), jnp.asarray(seq, jnp.int32))
        sp.end(orders=int(positions.shape[0]))
        return up

    # -- bursts: one launch, wave-windowed commit -----------------------------
    # A launch is ONE dispatch and ONE packed fetch (`_launch`), and the
    # host consumes the fetched block in `wave_size` COMMIT windows
    # (bounded store/event batches; a short commit stops consumption at
    # that window). A uniform burst above its cap runs as chunks, one
    # launch after the other: a chunk's decisions land before the next
    # chunk is dispatched.
    wave_size = 4096
    # cap on a uniform launch's chunk (None = B_CAP): the serve loop pins
    # it to its window size, so a window is one launch of one compiled
    # shape
    launch_cap: Optional[int] = None

    @property
    def spread_group_cap(self) -> int:
        """Selector groups whose pods a segment may hold
        (`Scheduler._schedule_singletons_burst` cuts there; `_spread_carry`
        carries as many rows). A closed loop carries a whole pass's groups,
        K.SPREAD_GROUP_WIDE. Behind a serve loop (`launch_cap` pinned) it
        stays K.SPREAD_GROUP_CAP: a loop pads EVERY window's carry to its
        cap so that no window compiles, which at the wide width is a
        [128, 8192] int64 (8 MB) zero fill and upload for windows that
        hold two or three groups (cell 10: 2.3, and no `groups` cut in its
        window: PERF_LEDGER, PR 50), paid out of `startup_p50_ms` for
        nothing; past a loop's knee, where windows hold hundreds of pods,
        the wide carry would save cuts, and no cell sends that yet
        (ROADMAP.md R2/R3)."""
        return K.SPREAD_GROUP_CAP if self.launch_cap else K.SPREAD_GROUP_WIDE

    # pod-row cache (ops.pod_rows.PodRowCache), attached by the scheduler
    # shell: window planning gathers the interned signatures (and, in
    # tensor mode, the profile ids) stored at delivery instead of building
    # them at line rate. None = the per-window build (identical decisions
    # either way)
    pod_rows = None

    def _launch(self, op: str, ph: _BurstPhases, fl, dispatch,
                read=None, **span_args) -> tuple:
        """One launch of a burst driver: ONE dispatch and ONE packed fetch.
        `dispatch()` calls the kernel, books what only its driver counts and
        returns (what the driver keeps of the call, the packed block on the
        device). Returns (that, the block on the host). Nothing of the
        launch has reached the walk counters or a commit when a chaos seam
        raises here: the caller decides what stands (`_refuse_launch`).
        `read(block)` books what the driver counts from the block itself
        and returns what the `burst.fetch` span says of it (its args)."""
        ph.open("kernel")
        t_d = obs_trace.now()
        chaos.check("device.dispatch")
        kept, packed = dispatch()
        DEVICE_DISPATCH.labels(op).inc()
        ph.close()   # the dispatch is async; the fetch waits
        ph.open("fetch")
        chaos.node_dead_point("dispatch-fetch")
        chaos.check("device.fetch")
        h = np.asarray(jax.device_get(packed))
        chaos.node_dead_point("fetch-commit")
        t_done = obs_trace.now()
        DEVICE_FETCHES.labels(op).inc()
        DEVICE_FETCHED_BYTES.labels(op).inc(h.nbytes)
        # the launch from its dispatch to its block on the host, in the
        # ring alone (the profiler's trace has the device's own lines)
        obs_trace.add_span("burst.wave.device", t_d, t_done, cat="device",
                           args=span_args or None)
        obs_flight.RECORDER.note_block(fl, h)
        ph.close(**(read(h) if read is not None else {}))
        return kept, h

    def _refuse_launch(self, exc: BaseException, fl, outcome: dict) -> None:
        """A launch lost to an injected device fault decides nothing: book
        the fault, drop the resident folds (the host mirror is
        authoritative again) and close the flight record with `outcome`,
        an aborted one. What the launch's pods fall back to is the
        caller's: a None return sends them to the shell's serial path,
        decisions identical."""
        self._device_fault(exc)
        self.discard_burst_folds()
        ORACLE_FALLBACKS.labels("device-fault").inc()
        obs_flight.RECORDER.note_outcome(fl, outcome)

    def schedule_burst(self, pods: list[Pod], node_infos: dict[str, NodeInfo],
                       all_node_names: list[str],
                       bucket: Optional[int] = None,
                       commit=None, full_carry: bool = False
                       ) -> Optional[list[Optional[str]]]:
        """Schedule `pods` against one snapshot; returns per-pod host (or
        None when unschedulable). Decisions are serially equivalent to
        calling schedule() per pod with cache assumes in between. Returns
        None (whole-burst refusal) when burst semantics can't be made
        serial-equivalent here — the shell then runs the pods serially.

        The folded state persists on device: the caller MUST apply the
        returned placements to its cache (as the scheduler shell does via
        assume + note_burst_assumed) before the next cycle.

        `bucket` sizes the launch's operands (padded to a power of two, so
        a warm-up burst of any size in the bucket compiles the program a
        later burst runs); the device steps over `len(pods)` of them, not
        over the bucket.

        `full_carry` pads a selector-spread carry to the cap's rows
        whatever the pods hold (`_spread_carry`): the shell sets it for a
        segment that follows a `groups` cut of its drain pass, so that a
        cut pass runs one scan program. It changes no decision.

        `commit(lo, hosts) -> bool` (optional) is the wave-window sink:
        the whole burst is ONE dispatch and ONE packed fetch, and `commit`
        is called with consecutive `wave_size` windows of DECIDED hosts
        (never None) consumed out of that single fetched block (a uniform
        burst above its cap is several such launches, one after the
        other). Returning False
        signals a commit failure — the algorithm stops consuming the
        block, discards the undelivered decisions and the device folds
        (the host mirror is authoritative again), rewinds the walk
        counters to the delivered prefix, and returns that prefix with a
        None tail, exactly like the mid-burst-failure rewind contract.
        Decisions passed to `commit` are never re-returned as the
        caller's responsibility twice: the returned list still contains
        them, but the caller knows how far its own callback committed."""
        if not all_node_names or not pods:
            return [None] * len(pods)
        self.commit_marker = None
        if not self.breaker.allow_device():
            # circuit open (host-only mode): refuse the whole burst BEFORE
            # any dispatch — the shell runs the pods serially, where
            # schedule() picks the host twin under the same open circuit
            ORACLE_FALLBACKS.labels("circuit-open").inc()
            return None
        ph = _BurstPhases(self.metrics, [p.key for p in pods])
        ph.open("encode")
        try:
            return self._burst_phases(ph, pods, node_infos, all_node_names,
                                      bucket, commit, full_carry)
        finally:
            ph.abandon()   # a refusal or an error inside a phase

    def _burst_phases(self, ph: _BurstPhases, pods: list[Pod],
                      node_infos: dict[str, NodeInfo],
                      all_node_names: list[str], bucket: Optional[int],
                      commit, full_carry: bool
                      ) -> Optional[list[Optional[str]]]:
        """schedule_burst from the open encode phase on: encode, then the
        wave driver's dispatch and fetch phases."""
        # stable-axis mode: keep the resident mirror/device axis when this
        # enumeration is a proven rotation of it (cycle 0 rides order id
        # start0) — the serving lane's windows skip the per-window permute
        # + full re-upload entirely
        axis_order, start0 = self._axis_order(all_node_names)
        with obs_trace.span("burst.encode.nodes"):
            b = self.encoder.encode(node_infos, axis_order)
        self._node_arrays(b)
        enc = self._pod_encoder(node_infos, b)
        n = b.n_real
        num_to_find = num_feasible_nodes_to_find(n, self.percentage_of_nodes_to_score)
        bucket = _pad_pow2(bucket if bucket else len(pods), 16)
        uniform = None
        feats: Optional[list] = None
        # signatures from the pod-row cache (interned at delivery — the
        # identity fast path below) or the batched native build
        sigs = self._signatures(pods)
        s0 = sigs[0]
        uniform_spec = all(s is s0 or s == s0 for s in sigs)
        # tensor mode: per-pod profile ids (row-cache gather); a uniform
        # window must be single-PROFILE too — different weight rows change
        # the tie structure the K-batch modes rely on, so mixed-profile
        # windows ride the generic scan (which gathers rows per pod)
        pids = self._profile_ids(pods)
        pid0 = 0 if pids is None else int(pids[0])
        uniform_profile = pids is None or int(pids.min()) == int(pids.max())
        if num_to_find >= n and self.last_index == 0 and uniform_profile:
            # spec-identical pods produce identical encoder output against a
            # fixed snapshot, so the uniform path encodes ONE pod — per-pod
            # feature encoding (IPA topology counting in particular) is the
            # dominant host cost for affinity bursts
            if uniform_spec:
                with obs_trace.span("burst.encode.pods"):
                    uniform = self._uniform_class(
                        pods[0], enc.encode(pods[0]), b, node_infos)
        if uniform is not None:
            # K-pods-per-pass kernel: dynamic pod count (one compile for any
            # burst size), carried int32 scores, consecutive-tie-rank batch
            # resolution with exact prefix validation (kernels.py K_BATCH)
            cls, extra_ok, ban = uniform
            rotation = self._burst_rotation(b, len(pods), start0)
            # flight recorder: capture BEFORE any wave commit can mutate
            # the cache's NodeInfos (deep capture clones the world here)
            fl = obs_flight.RECORDER.begin("uniform", self, [(pods, False)],
                                           all_node_names, node_infos)
            ph.close()
            sel = self._uniform_waves(pods, b, cls, extra_ok, ban, rotation,
                                      n, commit, ph, bucket, fl=fl,
                                      pid=pid0)
            if sel is None:
                # device fault during a commit-less trial: whole-burst
                # refusal (nothing committed, counters rewound)
                return None
            return [b.names[s] for s in sel] \
                + [None] * (len(pods) - len(sel))
        from kubernetes_tpu.api.types import (
            has_pod_affinity_terms, get_container_ports)
        if any(has_pod_affinity_terms(p) or get_container_ports(p)
               for p in pods):
            # the generic scan encodes per-node masks ONCE per burst; pods
            # whose masks depend on in-burst placements (affinity/ports)
            # are only safe on the uniform path above — refuse, the shell
            # runs them serially
            ORACLE_FALLBACKS.labels("burst-affinity-mixed").inc()
            return None
        # spec-identical pods produce identical encoder output against a
        # fixed snapshot: encode ONE pod per signature and share (the O(N)
        # python feature loops — spread counting especially — dominate
        # otherwise; interned sigs make the memo an identity-hit dict)
        with obs_trace.span("burst.encode.pods"):
            if uniform_spec:
                feats = [enc.encode(pods[0])] * len(pods)
            else:
                feat_by_sig: dict = {}
                feats = []
                for p, sig in zip(pods, sigs):
                    f = feat_by_sig.get(sig)
                    if f is None:
                        f = feat_by_sig[sig] = enc.encode(p)
                    feats.append(f)
        # selector-spread counts change with every in-burst placement: the
        # scan carries them, one [N] vector where one set of Services /
        # ReplicaSets selects every pod, else one row a selector group
        spread0 = spread_groups = None
        carry_spread = any(f.spread_counts is not None for f in feats)
        if carry_spread:
            sp = obs_trace.begin("burst.encode.carry")
            carried = self._spread_carry(feats, b.n_pad, full_carry)
            if carried is None:
                sp.end(groups=0, rows=0)     # refused: nothing is carried
                ORACLE_FALLBACKS.labels("burst-spread-mixed").inc()
                return None
            spread0, spread_groups = carried
            vector = spread_groups is None       # one group's [n_pad] counts
            sp.end(groups=1 if vector else int(spread_groups[0].max()) + 1,
                   rows=1 if vector else int(spread0.shape[0]))
        # per-cycle rotated enumeration orders (uneven zones)
        rotation = self._scan_rotation(b, bucket, start0)
        # one device-array dict per SIGNATURE (equal sigs -> identical
        # _pod_arrays output by construction), so _stack_pods builds one
        # row a signature and gathers it to the pods that share it
        arr_by_feat: dict = {}
        for p, f in zip(pods, feats):
            if id(f) not in arr_by_feat:
                pp = arr_by_feat[id(f)] = self._pod_arrays(
                    f, b.n_pad, upd_fields=True, pod=p)
                if carry_spread:
                    # the counts ride the carry; the stacked per-pod field
                    # stays inert so no [B, N] upload happens
                    pp["spread_counts"] = self._defaults["zeros_i64"]
                if uniform_spec:
                    break
        per_pod = [arr_by_feat[id(f)] for f in feats]
        z_pad = _pad_pow2(len(b.zone_names), 4)
        # mesh mode rides the SAME _scan_waves driver below: since round 15
        # the generic scan kernel is one code path parameterized by the
        # sharding spec (K.schedule_batch(mesh=...)), so rotation, carried
        # spread counts, and the single-dispatch/single-fetch contract all
        # run sharded — the old burst-sharded-rotation / burst-sharded-
        # spread oracle fallbacks are deleted, not dodged.
        fl = obs_flight.RECORDER.begin("scan", self, [(pods, False)],
                                       all_node_names, node_infos)
        ph.close()
        return self._scan_waves(pods, b, per_pod, spread0, rotation,
                                num_to_find, n, z_pad, bucket,
                                commit, ph, fl=fl,
                                spread_groups=spread_groups,
                                profile_ids=pids)

    def _spread_carry(self, feats: list, n_pad: int,
                      full: bool = False) -> Optional[tuple]:
        """(spread0, spread_groups) for a generic scan launch whose pods
        carry selector-spread counts; `feats` are the pods' features, one
        object a signature. A pod's group is what SelectorSpread can tell
        of it (`PodFeatures.spread_group`: its namespace and the set of
        selectors that select it), so signatures that differ in requests
        alone share a group, and the group's counts are the vector the
        encoder made for any pod of it.

        One group and nothing else: (its [n_pad] vector, None), the launch
        every burst of one Service's pods has always been. Up to
        `spread_group_cap` groups: ([G_pad, n_pad] rows, (group of each pod
        [len(feats)] int32, counts_for [G_pad, G_pad] bool)); the spare
        rows are zero and no pod reads or moves them. G_pad, which names
        the scan program: up to K.SPREAD_GROUP_CAP groups a power of two,
        so that a stream of launches of about as many groups runs one
        program; above it (a closed loop alone) K.SPREAD_GROUP_WIDE
        whatever the launch holds, 17 or 111, so a process meets ONE more
        program. A pod that nothing selects (no counts, no group) rides
        the rows with none of its own, under group index -1: a step's
        masked sum over the group axis then reads it zeros and adds its
        binding to no row, and zeros score SelectorSpread's constant bit
        for bit on every node, zoned or not (`_fit_scores`; held by
        tests/test_grouped_spread_carry.py's
        test_zero_counts_score_the_inert_constant). Such a launch is
        always the rank-2 one, also beside one group: the rank-1 program
        hands every pod the one vector. Behind a serve loop (`launch_cap`
        pinned) the group count changes from window to window, and a
        program first met there is a compile inside a window that pods
        wait on: G_pad is then the cap itself, so the loop runs two scan
        programs (one vector, the cap's rows) and its first large window
        has met both. `full` (a segment that follows a `groups` cut of its
        drain pass, `Scheduler._schedule_singletons_burst`) gives a closed
        loop the cap's rows too, and also for ONE group: every segment
        before it held the cap's groups, and the pass's last holds whatever
        is left, 1 to the cap, so a loop of cut passes would meet the
        vector program and every power of two one pass or another, each a
        compile where it is first met; padded, a cut pass runs one program,
        which its first segment has met (a serve loop's two are met
        already, and it keeps them). None where the carry cannot be made
        exact: more
        groups than the cap, counts off the node axis, or a group without
        counts."""
        keys: dict = {}
        rows = []
        group_of_feat: dict = {}
        unselected = False
        for f in feats:
            if id(f) in group_of_feat:
                continue
            if f.spread_counts is None:
                if f.spread_group is not None:
                    return None
                group_of_feat[id(f)] = -1
                unselected = True
                continue
            if f.spread_counts.shape[-1] != n_pad:
                return None
            g = keys.get(f.spread_group)
            if g is None:
                g = keys[f.spread_group] = len(rows)
                rows.append(f.spread_counts)
            group_of_feat[id(f)] = g
        cap = self.spread_group_cap
        full = full and not self.launch_cap
        if len(rows) == 1 and not unselected and not full:
            return rows[0], None
        if len(rows) > cap:
            return None
        g_pad = cap if self.launch_cap or full \
            or len(rows) > K.SPREAD_GROUP_CAP else _pad_pow2(len(rows), 2)
        spread0 = np.zeros((g_pad, n_pad), np.int64)
        spread0[:len(rows)] = rows
        counts_for = np.zeros((g_pad, g_pad), bool)
        for h, h_key in enumerate(keys):
            for g, g_key in enumerate(keys):
                counts_for[h, g] = counts_toward(h_key, g_key)
        group = np.fromiter((group_of_feat[id(f)] for f in feats), np.int32,
                            len(feats))
        return spread0, (group, counts_for)

    def _uniform_waves(self, pods: list[Pod], b: NodeBatch, cls, extra_ok,
                       ban: bool, rotation, n: int, commit,
                       ph: _BurstPhases, bucket: int,
                       fl=None, pid: int = 0) -> Optional[list]:
        """Driver for the uniform kernel: the burst is ONE launch (one
        dispatch, one packed [cap+1] fetch) up to its cap, and above it a
        chunk a launch, one after the other; the commit consumes each
        fetched block wave-by-wave (`wave_size` windows: bounded
        store/event batches). Returns the decided selection prefix (device
        axis indices, all >= 0); the caller pads the undecided tail with
        None.

        Rewind contract, read off a launch's one block: the uniform
        kernel's failures are a frozen-state suffix (F==0 persists for
        identical pods), so the decided prefix is exactly the block's
        leading non-negative run. A commit failure (callback returned
        False) stops consumption — the rest of the block is discarded
        along with the resident folds, and the returned prefix ends at the
        last window handed to the callback. Chunks that landed before a
        failure, an abort or a fault stand."""
        # the launch cap IS the caller's burst bucket (clamped to B_CAP,
        # and to launch_cap when the serve loop pinned window-sized
        # chunks): the warmup burst rides the same bucket, so the one
        # compile per (bucket, class-flags) signature happens outside any
        # timed loop
        hard = K.B_CAP if not self.launch_cap \
            else min(K.B_CAP, int(self.launch_cap))
        cap = _pad_pow2(max(1, min(bucket, hard)), 16)
        W = max(1, min(int(self.wave_size), cap))
        n_pods = len(pods)
        li_entry, lni_entry = self.last_index, self.last_node_index
        tensor = self._ptab is not None
        sel: list[int] = []

        def outcome(**flags) -> dict:
            # device-decided hosts up to the last commit/abort boundary;
            # `failed` marks that the NEXT pod found no node on device
            return {"hosts": [b.names[s] for s in sel],
                    "failed": False, "aborted": False, **flags}

        for ci, lo in enumerate(range(0, n_pods, cap)):
            chunk = min(cap, n_pods - lo)

            def dispatch():
                rot = rotation
                if rotation is not None:
                    win = np.empty(cap + K.K_BATCH, dtype=np.int32)
                    piece = rotation[1][lo: lo + len(win)]
                    win[: len(piece)] = piece
                    win[len(piece):] = piece[-1] if len(piece) else 0
                    rot = (rotation[0], win)
                rows, packed, _lni = K.schedule_batch_uniform(
                    self._dev_nodes, dict(cls), chunk,
                    self.last_node_index, n, self.check_resources,
                    weights=self._union_weights if tensor else self.weights,
                    rotation=rot, extra_ok=extra_ok, ban=ban,
                    mesh=self.mesh, cap=cap,
                    wtab=self._wtab() if tensor else None, pid=pid)
                WALK_NODES.labels("full").inc(chunk * n)
                return rows, packed

            try:
                rows, h = self._launch("burst_uniform", ph, fl, dispatch,
                                       chunk=ci)
            except _DEVICE_FAULTS as e:
                # the faulted chunk decided nothing, and the rest of the
                # burst degrades to the serial oracle path via the
                # undecided-tail contract
                if commit is None:
                    # pure trial (gang): nothing was committed — rewind
                    # the walk counters earlier chunks consumed and refuse
                    # outright, so the caller reruns the WHOLE trial
                    # through the serial referee instead of misreading the
                    # undecided tail as a rejected gang
                    self.last_index, self.last_node_index = \
                        li_entry, lni_entry
                    sel = []
                self._refuse_launch(e, fl, outcome(aborted=True))
                return None if commit is None else sel
            self._dev_nodes = {**self._dev_nodes, **rows}
            chunk_sel = h[:chunk].tolist()
            bad = next((i for i, s in enumerate(chunk_sel) if s < 0), chunk)
            if commit is not None and self.stale_scan is not None:
                # mid-burst node death: none of THIS chunk's decisions
                # have committed and its lni advance is not yet applied,
                # so earlier (already-committed) chunks stand and this
                # chunk refuses whole — the shell invalidates the dead
                # rows and replans the remainder post-churn
                decided = [b.names[s] for s in chunk_sel[:bad]]
                dead = self.stale_scan(decided, b.names[:n])
                if dead:
                    self.discard_burst_folds()
                    obs_flight.RECORDER.note_outcome(
                        fl, outcome(aborted=True))
                    raise StaleNodeRefusal(
                        dead, max(1, sum(1 for hn in decided if hn in dead)))
            lni_chunk_start = self.last_node_index
            self.last_node_index += int(h[cap])
            aborted = False
            # commit consumes the fetched block wave-by-wave
            for wlo in range(0, bad, W):
                hi = min(wlo + W, bad)
                BURST_WAVES.labels("uniform").inc()
                sel.extend(chunk_sel[wlo:hi])
                if commit is None:
                    continue
                # crash-restart checkpoint marker (the shell's recovery
                # context source): exact walk counters at this window's
                # two boundaries where the block carries them. The uniform
                # kernel never advances last_index, and the packed block
                # only holds the CHUNK's lni advance — so mid-chunk window
                # boundaries have no exact lni (None; recovery degrades to
                # reconcile-only there).
                self.commit_marker = {
                    "li0": li_entry,
                    "lni0": (lni_chunk_start if wlo == 0 else None),
                    "li1": li_entry,
                    "lni1": (self.last_node_index if hi == chunk
                             else None),
                    "committed0": lo + wlo, "committed1": lo + hi,
                }
                with obs_trace.span("burst.wave.commit", chunk=ci):
                    ok = commit(lo + wlo,
                                [b.names[s] for s in chunk_sel[wlo:hi]])
                if not ok:
                    aborted = True
                    break
            if bad < chunk or aborted:
                if aborted:
                    self.discard_burst_folds()
                obs_flight.RECORDER.note_outcome(
                    fl, outcome(failed=bad < chunk, aborted=aborted))
                return sel
        self.breaker.record_success()
        obs_flight.RECORDER.note_outcome(fl, outcome())
        return sel

    def _scan_waves(self, pods: list[Pod], b: NodeBatch, per_pod: list,
                    spread0, rotation, num_to_find: int,
                    n: int, z_pad: int, bucket: int, commit,
                    ph: _BurstPhases, fl=None,
                    spread_groups=None,
                    profile_ids=None) -> list[Optional[str]]:
        """Single-launch driver for the generic scan burst: the whole
        burst runs as ONE launch whose operands have the caller's bucket
        shape (so the warmup burst compiles the same program) and whose
        trip count is `len(pods)`, a dynamic operand: the pad rows give
        the operands their shape and are never stepped over. The host
        fetches ONE packed [5B] block — selections, the per-pod walk
        counters and two words a pod for the tie and rejection counters,
        rows from `len(pods)` on a fixed fill. Commit then consumes the
        block wave-by-wave.

        Rewind contract, re-derived from slices of the single block: the
        scan keeps deciding after a failed pod, so everything from the
        first failure on is undecided and the committed-prefix counters
        are read straight out of the block (li_after/lni_delta at the
        last decided pod) — the failure path's second fetch is gone. A
        commit failure stops consumption at that window; the counters
        rewind to the last window handed to the callback and the resident
        folds drop either way (the host mirror is authoritative again)."""
        B = bucket
        n_pods = len(pods)
        W = max(1, min(int(self.wave_size), B))
        sp = obs_trace.begin("burst.stack")
        stacked, signatures = self._stack_pods(per_pod, B, profile_ids)
        # a per-pod weight row (tensor mode) makes the row-local scores per
        # profile, and over few node rows a device the score board costs
        # more than it saves: those launches rescore every row each step
        tensor = self._ptab is not None
        shards = 1 if self.mesh is None else self.mesh.size
        carried = not tensor and \
            b.n_pad // shards >= K.SCORE_BOARD_MIN_ROWS
        classes = K.score_classes(
            stacked["nz_cpu"], stacked["nz_mem"], n_pods) \
            if carried else None
        if spread_groups is not None:
            # the pad rows take group 0: they are never stepped over
            group = np.zeros(B, np.int32)
            group[:n_pods] = spread_groups[0]
            spread_groups = (group, spread_groups[1])
        sp.end(signatures=signatures)

        def dispatch():
            state, _li_out, _lni_out, _spread, outs = K.schedule_batch(
                self._dev_nodes, stacked, self.last_index,
                self.last_node_index, num_to_find, n, z_pad,
                weights=self._union_weights if tensor else self.weights,
                rotation=rotation, spread0=spread0,
                mesh=self.mesh, wtab=self._wtab() if tensor else None,
                n_pods=n_pods, classes=classes, spread_groups=spread_groups)
            SCAN_STEPS.labels("real").inc(n_pods)
            SCAN_SPREAD_STEPS.labels(
                "none" if spread0 is None else
                "single" if spread_groups is None else "grouped").inc(n_pods)
            if spread0 is not None:
                # an unselected pod's -1 lies under every row's index, and
                # a carried launch holds a selected pod: max() + 1 is rows
                SCAN_SPREAD_GROUPS.inc(
                    1 if spread_groups is None
                    else int(spread_groups[0][:n_pods].max()) + 1)
                SCAN_SPREAD_CARRY_LAUNCHES.labels(
                    "1" if spread_groups is None
                    else str(spread0.shape[0])).inc()
            if spread_groups is not None:
                SCAN_SPREAD_UNSELECTED_STEPS.inc(
                    int((spread_groups[0][:n_pods] < 0).sum()))
            SCAN_SCORE_STEPS.labels(
                "full" if classes is None else "carried").inc(n_pods)
            SCAN_POD_ROWS.labels(
                "stacked" if signatures > 1 else "shared").inc(n_pods)
            SCAN_ORDER_STEPS.labels(
                "axis" if rotation is None else "position").inc(n_pods)
            return state, outs["packed"]

        def read_walks(h) -> dict:
            PICK_TIED_NODES.inc(
                int(h[3 * B:3 * B + n_pods].sum(dtype=np.int64)))
            rejected = h[4 * B:4 * B + n_pods]
            FILTER_REJECTED_NODES.inc(int(rejected.sum(dtype=np.int64)))
            if num_to_find >= n:
                WALK_NODES.labels("full").inc(n_pods * n)
                return {}
            # a walk tests 1..n nodes and moves last_index by that many
            # mod n, so a step of 0 is a walk over all n
            moved = np.diff(h[B:B + n_pods].astype(np.int64),
                            prepend=self.last_index % n) % n
            tested = np.where(moved == 0, n, moved)
            WALK_NODES.labels("truncated").inc(int(tested.sum()))
            # how each walk ended, up to the first pod that found no node
            # (what the launch decided after it is discarded): nodes
            # tested minus nodes rejected is nodes kept
            none = h[:n_pods] < 0
            d = int(np.argmax(none)) + 1 if none.any() else n_pods
            kept = tested[:d] - rejected[:d]
            ended = {"quota": int((kept >= num_to_find).sum()),
                     "none": int((kept == 0).sum())}
            ended["nodes"] = d - ended["quota"] - ended["none"]
            for by, count in ended.items():
                WALK_ENDED.labels(by).inc(count)
            return ended

        try:
            state, h = self._launch("burst_scan", ph, fl, dispatch,
                                    read=read_walks)
        except _DEVICE_FAULTS as e:
            # the launch precedes every commit and counter update: refuse
            # the whole burst — the shell reruns the pods serially (host
            # twin under an open circuit) against the untouched host
            # mirror
            return self._refuse_launch(
                e, fl, {"hosts": [], "failed": False, "aborted": True})
        self.breaker.record_success()
        sel_arr = h[:n_pods]
        li_after = h[B:2 * B]
        lni_delta = h[2 * B:3 * B]
        lni0 = self.last_node_index
        neg = sel_arr < 0
        bad = int(np.argmax(neg)) if neg.any() else n_pods
        committed = bad
        aborted = False
        li_entry = self.last_index
        if commit is not None and self.stale_scan is not None:
            # mid-burst node death: a node from this launch's world is
            # gone from the store. NOTHING has committed (single fetch
            # precedes the first wave commit) and the walk counters are
            # untouched — drop the folds and refuse the launch whole; the
            # shell invalidates the dead rows and replans against the
            # post-churn world
            decided = [b.names[s] for s in sel_arr[:bad].tolist()]
            dead = self.stale_scan(decided, b.names[:n])
            if dead:
                self.discard_burst_folds()
                obs_flight.RECORDER.note_outcome(fl, {
                    "hosts": [], "failed": False, "aborted": True})
                raise StaleNodeRefusal(
                    dead, max(1, sum(1 for hn in decided if hn in dead)))
        if commit is not None:
            committed = 0
            for wlo in range(0, bad, W):
                hi = min(wlo + W, bad)
                BURST_WAVES.labels("scan").inc()
                # crash-restart checkpoint marker: the packed block carries
                # per-pod walk counters, so BOTH boundaries of every window
                # are exact on this path (recovery picks the side matching
                # what the store says actually landed)
                self.commit_marker = {
                    "li0": (li_entry if wlo == 0
                            else int(li_after[wlo - 1])),
                    "lni0": (lni0 if wlo == 0
                             else lni0 + int(lni_delta[wlo - 1])),
                    "li1": int(li_after[hi - 1]),
                    "lni1": lni0 + int(lni_delta[hi - 1]),
                    "committed0": wlo, "committed1": hi,
                }
                with obs_trace.span("burst.wave.commit"):
                    ok = commit(
                        wlo, [b.names[s] for s in sel_arr[wlo:hi].tolist()])
                committed = hi
                if not ok:
                    aborted = True
                    break
        # walk counters at the consumed boundary, straight from the block
        if committed > 0:
            self.last_index = int(li_after[committed - 1])
            self.last_node_index = lni0 + int(lni_delta[committed - 1])
        if bad < n_pods or aborted:
            # post-failure scan folds (or folds for decisions a failed
            # commit discarded) never became decisions: drop the device
            # matrix — the host mirror reflects exactly the committed
            # prefix after note_burst_assumed
            self.discard_burst_folds()
        else:
            # persist the folds: the device-resident matrix is
            # authoritative for rows the scan mutated (the host mirror
            # catches up via note_burst_assumed; external changes still
            # arrive via dirty rows)
            self._dev_nodes = {**self._dev_nodes, **state}
        obs_flight.RECORDER.note_outcome(fl, {
            # the full device-decided prefix (commit aborts shorten the
            # RETURNED prefix but not what the device decided)
            "hosts": [b.names[s] for s in sel_arr[:bad].tolist()],
            "failed": bad < n_pods,
            "aborted": aborted,
        })
        return [b.names[s] for s in sel_arr[:committed].tolist()] \
            + [None] * (n_pods - committed)

    # -- fused segmented burst: one launch per drain window -------------------
    # The shell advertises gang segments to this entry so a whole drain
    # window — singleton runs AND PodGroups — rides ONE dispatch and ONE
    # packed fetch (kernels.schedule_batch_segments): gang boundaries are
    # scan segment boundaries, and the round-8 gang_checkpoint/gang_rewind
    # contract runs inside the device carry instead of as one launch per
    # gang trial.
    supports_fused_segments = True

    def schedule_burst_fused(self, segments, node_infos: dict[str, NodeInfo],
                             all_node_names: list[str],
                             bucket: Optional[int] = None):
        """Schedule a segmented drain window in ONE launch + ONE packed
        fetch. `segments` = [(pods, is_gang), ...] in queue order.

        Gang segments are all-or-nothing ON DEVICE: a member that finds no
        node rewinds the carry (mutable rows, li, lni, rotation cursor) to
        the segment checkpoint in-scan, the rest of the segment is
        skipped, and the window continues against the rewound state —
        exactly the serial trial→reject→park→continue sequence, with zero
        extra round trips and no discarded in-flight device work.

        Returns None when the window isn't expressible on this path (the
        caller falls back to the per-segment machinery), else
        {"segments": [...], "consumed": n_enumerations} with per-segment
        records:
          {"status": "decided",  "hosts": [...], "li", "lni", "t"}
          {"status": "rejected", "placed": k,    "li", "lni", "t"}  (gang)
          {"status": "failed",   "hosts": [decided prefix], "li","lni","t"}
          {"status": "undecided"}   (at/after a singleton failure)
        The li/lni/t triple is the carry at that segment's END boundary —
        the caller's abort target (fused_rewind) when a later commit comes
        up short. On return, last_index/lastNodeIndex are already set to
        the end of the decided prefix (a singleton failure's prefix is
        re-derived from per-pod slices of the single fetched block), and
        the resident folds persist unless that failure polluted them."""
        from kubernetes_tpu.api.types import (has_pod_affinity_terms,
                                              get_container_ports)
        n_total = sum(len(p) for p, _g in segments)
        if not all_node_names or n_total == 0:
            return None
        self.commit_marker = None
        if not self.breaker.allow_device():
            # circuit open (host-only mode): refuse the window before any
            # dispatch — the shell's per-segment fallback runs the serial
            # loop, where schedule() picks the host twin
            ORACLE_FALLBACKS.labels("circuit-open").inc()
            return None
        if self.nominated is not None and self.nominated.has_any():
            ORACLE_FALLBACKS.labels("fused-nominated-ghosts").inc()
            return None
        flat = [p for seg_pods, _g in segments for p in seg_pods]
        if any(has_pod_affinity_terms(p) or get_container_ports(p)
               or p.volumes for p in flat):
            # per-node masks that depend on in-burst placements (and volume
            # reservations) have no segment-rewind story on device
            ORACLE_FALLBACKS.labels("fused-pod-features").inc()
            return None
        ph = _BurstPhases(self.metrics, [p.key for p in flat])
        ph.open("encode")
        try:
            return self._fused_phases(ph, segments, flat, n_total,
                                      node_infos, all_node_names, bucket)
        finally:
            ph.abandon()   # a refusal or an error inside a phase

    def _fused_phases(self, ph: _BurstPhases, segments, flat: list[Pod],
                      n_total: int, node_infos: dict[str, NodeInfo],
                      all_node_names: list[str], bucket: Optional[int]):
        """schedule_burst_fused from the open encode phase on."""
        axis_order, start0 = self._axis_order(all_node_names)
        with obs_trace.span("burst.encode.nodes"):
            b = self.encoder.encode(node_infos, axis_order)
        nodes = self._node_arrays(b)
        enc = self._pod_encoder(node_infos, b)
        feat_by_sig: dict = {}
        arr_by_sig: dict = {}
        per_pod = []
        with obs_trace.span("burst.encode.pods"):
            for p, sig in zip(flat, self._signatures(flat)):
                f = feat_by_sig.get(sig)
                if f is None:
                    f = feat_by_sig[sig] = enc.encode(p)
                if f.spread_counts is not None:
                    # selector-spread counts carry through rewinds only
                    # with a checkpointed spread vector the shell's
                    # plain-class gate already excludes; refuse rather
                    # than drift
                    ORACLE_FALLBACKS.labels("fused-spread-selectors").inc()
                    return None
                pp = arr_by_sig.get(sig)
                if pp is None:
                    # one array dict per signature: _stack_pods builds
                    # its row once (same values — equal sigs imply
                    # identical _pod_arrays output)
                    pp = arr_by_sig[sig] = self._pod_arrays(
                        f, b.n_pad, upd_fields=True, pod=p)
                per_pod.append(pp)
        n = b.n_real
        num_to_find = num_feasible_nodes_to_find(
            n, self.percentage_of_nodes_to_score)
        B = _pad_pow2(max(bucket or 16, n_total), 16)
        # one burst-wide walk, indexed by enumerations CONSUMED inside
        # the kernel (the carried t) — a rejected gang rewinds the
        # cursor, so the walk must NOT be pre-sliced by pod position
        rotation = self._scan_rotation(b, B, start0)
        seg_start = np.zeros(B, dtype=bool)
        gang = np.zeros(B, dtype=bool)
        idx = 0
        for seg_pods, is_gang in segments:
            seg_start[idx] = True
            if is_gang:
                gang[idx: idx + len(seg_pods)] = True
            BURST_SEGMENTS.labels("gang" if is_gang else "run").inc()
            idx += len(seg_pods)
        if idx < B:
            seg_start[idx] = True   # padding: its own inert segment
        # tensor mode: each pod selects its weight row in-kernel
        stacked, _signatures = self._stack_pods(
            per_pod, B, self._profile_ids(flat))
        z_pad = _pad_pow2(len(b.zone_names), 4)
        # flight recorder: the fused window is THE canonical record — gang
        # boundaries, rewinds and rotation state all ride one launch
        fl = obs_flight.RECORDER.begin("fused", self, segments,
                                       all_node_names, node_infos)
        ph.close()
        tensor = self._ptab is not None

        def dispatch():
            state, _li, _lni, _spread, packed = K.schedule_batch_segments(
                nodes, stacked, seg_start, gang, n_total, self.last_index,
                self.last_node_index, num_to_find, n, z_pad,
                weights=self._union_weights if tensor else self.weights,
                rotation=rotation,
                mesh=self.mesh, wtab=self._wtab() if tensor else None,
                gang_score=self._gang_score)
            return state, packed

        try:
            state, h = self._launch("burst_fused", ph, fl, dispatch)
        except _DEVICE_FAULTS as e:
            # the launch precedes every counter update and commit: refuse
            # the window — the shell reruns every entry through the
            # per-segment machinery against the untouched host mirror
            # (which cascades to the serial loop under an open circuit)
            return self._refuse_launch(
                e, fl, {"segments": [], "consumed": 0, "aborted": True})
        self.breaker.record_success()
        sel = h[:B]
        li_after = h[B:2 * B]
        lni_delta = h[2 * B:3 * B]
        t_after = h[3 * B:4 * B]
        li0, lni0 = self.last_index, self.last_node_index

        def boundary(j: int) -> tuple[int, int, int]:
            if j < 0:
                return li0, lni0, 0
            return (int(li_after[j]), lni0 + int(lni_delta[j]),
                    int(t_after[j]))

        results = []
        fail_at = None   # first SINGLETON failure: everything after is
        idx = 0          # undecided (its serial rerun may preempt)
        for seg_pods, is_gang in segments:
            L = len(seg_pods)
            if fail_at is not None:
                results.append({"status": "undecided"})
                idx += L
                continue
            ss = sel[idx: idx + L]
            end_li, end_lni, end_t = boundary(idx + L - 1)
            def seqs(k: int) -> dict:
                # per-member walk counters (window-grain rewind targets for
                # a short commit inside a singleton run)
                return {"li_seq": li_after[idx: idx + k],
                        "lni_seq": lni0 + lni_delta[idx: idx + k],
                        "t_seq": t_after[idx: idx + k]}

            if is_gang:
                if (ss < 0).any():
                    # the kernel already rewound the carry; the boundary is
                    # the (restored) pre-gang state
                    results.append({"status": "rejected",
                                    "placed": int((ss >= 0).sum()),
                                    "li": end_li, "lni": end_lni,
                                    "t": end_t})
                else:
                    results.append({"status": "decided",
                                    "hosts": [b.names[s]
                                              for s in ss.tolist()],
                                    "li": end_li, "lni": end_lni,
                                    "t": end_t, **seqs(L)})
            elif (ss < 0).any():
                k = int(np.argmax(ss < 0))
                fail_at = idx + k
                end_li, end_lni, end_t = boundary(idx + k - 1)
                results.append({"status": "failed",
                                "hosts": [b.names[s]
                                          for s in ss[:k].tolist()],
                                "li": end_li, "lni": end_lni, "t": end_t,
                                **seqs(k)})
            else:
                results.append({"status": "decided",
                                "hosts": [b.names[s] for s in ss.tolist()],
                                "li": end_li, "lni": end_lni, "t": end_t,
                                **seqs(L)})
            idx += L
        if fail_at is not None:
            li_f, lni_f, consumed = boundary(fail_at - 1)
            # post-failure folds never became decisions: drop the matrix
            self.discard_burst_folds()
        else:
            li_f, lni_f, consumed = boundary(n_total - 1)
            self._dev_nodes = {**self._dev_nodes, **state}
        self.last_index, self.last_node_index = li_f, lni_f
        obs_flight.RECORDER.note_outcome(fl, {
            "segments": [{k: r[k] for k in ("status", "hosts", "placed")
                          if k in r} for r in results],
            "consumed": consumed,
        })
        return {"segments": results, "consumed": consumed}

    def fused_rewind(self, li: int, lni: int) -> None:
        """Abort handler for a fused window: a SHORT segment commit (pods
        vanished between decision and commit) makes the shell stop
        consuming the block — the walk counters rewind to the segment
        boundary it got from schedule_burst_fused and the resident folds
        drop (decisions past the boundary are discarded; the host mirror
        is authoritative again)."""
        self.last_index = int(li)
        self.last_node_index = int(lni)
        self.discard_burst_folds()

    # -- device preemption ---------------------------------------------------
    def preempt(self, pod: Pod, node_infos: dict[str, NodeInfo],
                all_node_names: list[str], fit_error, pdbs: list):
        """Device victim scan (kernels.preemption_scan): one launch replaces
        the reference's 16-goroutine fan-out over candidate nodes
        (generic_scheduler.go:966). Returns a PreemptionResult with
        decisions identical to the oracle Preemptor, or None when this
        preemption isn't expressible as resources + static masks (the
        caller falls back to the oracle).

        Eligible when: no active nominations, the incoming pod carries no
        volumes or extended-resource requests, and every POTENTIAL VICTIM
        (lower-priority pod on a candidate node) is mask-inert: it has no
        (anti-)affinity terms, declares no host ports when the incoming pod
        wants one, and matches none of the incoming pod's required
        (anti-)affinity term selectors. Affinity-bearing BYSTANDERS
        (priority >= the preemptor, or off the candidate set) are fine —
        they are never removed, so the pod's masks (selector/taints/ports/
        inter-pod-affinity) are invariant under victim removal and fold
        into the static feasibility vector."""
        from kubernetes_tpu.oracle.preemption import (
            pod_eligible_to_preempt_others, nodes_where_preemption_might_help,
            PreemptionResult, no_possible_victims)
        from kubernetes_tpu.api.types import (
            get_container_ports, get_resource_request)
        if not all_node_names:
            return None
        if not self.breaker.allow_device():
            # circuit open: the oracle Preemptor runs this scan instead
            ORACLE_FALLBACKS.labels("circuit-open").inc()
            return None
        if self.nominated is not None and self.nominated.has_any():
            ORACLE_FALLBACKS.labels("preempt-nominated-ghosts").inc()
            return None
        if pod.volumes:
            ORACLE_FALLBACKS.labels("preempt-pod-volumes").inc()
            return None
        req = get_resource_request(pod)
        if req.scalar:
            ORACLE_FALLBACKS.labels("preempt-scalar-request").inc()
            return None
        pod_ports = bool(get_container_ports(pod))
        a = pod.affinity
        pod_terms = []
        if a is not None:
            for grp in (a.pod_affinity, a.pod_anti_affinity):
                if grp is not None and grp.required:
                    pod_terms.extend(grp.required)
        if not pod_eligible_to_preempt_others(pod, node_infos):
            return PreemptionResult(None, [], [])
        candidates = nodes_where_preemption_might_help(
            node_infos, all_node_names, fit_error.failed_predicates)
        if not candidates:
            # preemption can't help anywhere: clear the pod's own stale
            # nomination (generic_scheduler.go:330-333)
            return PreemptionResult(None, [], [pod])
        if no_possible_victims(pod, node_infos, candidates):
            # same fast path as the oracle Preemptor — skip the device launch
            return PreemptionResult(None, [], [])
        b = self.encoder.encode(node_infos, all_node_names)
        nodes = self._node_arrays(b)
        vic, slots, gate = self._victim_inputs(
            node_infos, b, candidates, pod.priority, pdbs, pod=pod,
            pod_ports=pod_ports, pod_terms=pod_terms)
        if vic is None:
            ORACLE_FALLBACKS.labels(f"preempt-victims-{gate}").inc()
            return None
        enc = self._pod_encoder(node_infos, b)
        f = enc.encode(pod)
        if f.unknown_scalars:
            ORACLE_FALLBACKS.labels("preempt-unknown-scalars").inc()
            return None
        n_pad = b.n_pad
        feas = np.zeros(n_pad, bool)
        order_rank = np.full(n_pad, 1 << 30, np.int64)
        for order, name in enumerate(candidates):
            i = b.index[name]
            feas[i] = True
            order_rank[i] = order
        for mask in (f.sel_ok, f.taints_ok, f.unsched_ok, f.host_ok,
                     f.ports_ok):
            if mask is not None:
                feas &= np.asarray(mask, bool)
        if f.interpod_code is not None:
            # static under victim removal: no victim carries terms or
            # matches the pod's (gated above), so the full-cluster IPA
            # verdict holds for every mutated candidate
            feas &= np.asarray(f.interpod_code) == 0
        pod_in = {"req_cpu": np.int64(req.milli_cpu),
                  "req_mem": np.int64(req.memory),
                  "req_eph": np.int64(req.ephemeral_storage)}
        try:
            with obs_trace.span("preempt.scan", cat="device"):
                chaos.check("device.dispatch")
                chaos.check("device.fetch")
                out = np.asarray(K.preemption_scan(
                    nodes, vic, pod_in, feas, order_rank, b.n_real,
                    self.check_resources, f.has_request, pod.priority,
                    mesh=self.mesh))
        except _DEVICE_FAULTS as e:
            # the scan reads resident state and mutates nothing: refuse —
            # the caller falls back to the oracle Preemptor, whose
            # decisions are identical by the parity contract
            self._device_fault(e)
            ORACLE_FALLBACKS.labels("device-fault").inc()
            return None
        self.breaker.record_success()
        DEVICE_DISPATCH.labels("preempt_scan").inc()
        DEVICE_FETCHES.labels("preempt_scan").inc()
        DEVICE_FETCHED_BYTES.labels("preempt_scan").inc(out.nbytes)
        winner = int(out[0])
        if winner < 0:
            return PreemptionResult(None, [], [])
        name = b.names[winner]
        flags = out[3:].astype(bool)
        # a zero-victim winner has no slots entry (preemption can still
        # pick it when another pod's nomination freed nothing — rare)
        victims = [p for j, p in enumerate(slots.get(name, ())) if flags[j]]
        return PreemptionResult(node_infos[name].node, victims, [])

    # victim-table planes the kernels read, device key <- host field
    _VIC_FIELDS = (("cpu", "cpu"), ("mem", "mem"), ("eph", "eph"),
                   ("prio", "prio"), ("start", "start"),
                   ("valid", "valid"), ("violating", "viol"))

    @classmethod
    def _vic_planes(cls, vt, rows=None) -> dict:
        """Host victim planes as the device reads them (all rows, or the
        given ones): start times go up as integer order keys."""
        out = {k: getattr(vt, f) if rows is None else getattr(vt, f)[rows]
               for k, f in cls._VIC_FIELDS}
        out["start"] = K.start_order_key(out["start"])
        return out

    def _victim_inputs(self, node_infos: dict[str, NodeInfo], b: NodeBatch,
                       names, max_prio: int, pdbs: list,
                       pod: Optional[Pod] = None, pod_ports: bool = False,
                       pod_terms=()):
        """Resident [N, P] victim planes + slots map for a preemption scan.

        The table itself is persistent (encoder.victim_table: cached per
        node generation, re-sorted only for dirty rows, permuted on
        NodeTree rotation) and stays in HBM — a scan uploads only dirty
        rows. The eligibility gates that used to abort a per-scan Python
        encode midway are O(1) mask reads over the cached inertness-class
        planes, checked over exactly the candidate set: a potential victim
        (priority < max_prio on a candidate node) carrying affinity terms,
        conflicting ports, scalar resources, or matching the incoming
        pod's required terms — or a node the slot cap can't represent —
        still refuses, per-reason (VICTIM_GATE_REASONS), and the caller
        falls back to the oracle. Returns (vic dict, slots, None) or
        (None, None, reason)."""
        vt = self.encoder.victim_table(node_infos, b, pdbs,
                                       cap=K.PREEMPT_P)
        if len(names) == b.n_real and (names is b.names or
                                       list(names) == b.names):
            # whole-axis candidate set (the pressure path): skip the
            # per-name index gather
            cand = np.arange(b.n_real, dtype=np.int64)
        else:
            cand = np.fromiter((b.index[nm] for nm in names), np.int64,
                               len(names))
        # the overflow gate EXTENDS the old one: it fires on total pod
        # count > cap, a superset of the old potential-victim count check —
        # a dropped slot could be anyone's victim, so refuse outright
        if bool(vt.overflow[cand].any()):
            return None, None, "overflow"
        pot = vt.valid[cand] & (vt.prio[cand] < max_prio)
        if bool((pot & vt.aff[cand]).any()):
            return None, None, "affinity-terms"
        if pod_ports and bool((pot & vt.ports[cand]).any()):
            return None, None, "ports"
        if bool((pot & vt.scalar[cand]).any()):
            return None, None, "scalar"
        if pod_terms:
            from kubernetes_tpu.oracle.predicates import (
                pod_matches_any_term_mask)
            t = vt.table
            is_cand = np.zeros(b.n_pad, bool)
            is_cand[cand] = True
            hr = t.holder_row
            on_cand = (hr >= 0) & is_cand[np.where(hr >= 0, hr, 0)]
            pot_rows = on_cand & (t.prio < max_prio)
            if bool(pot_rows.any()) and bool(
                    (pod_matches_any_term_mask(pod, pod_terms, t)
                     & pot_rows).any()):
                return None, None, "term-match"
        return self._upload_victims(vt), vt.slots, None

    def _upload_victims(self, vt) -> dict:
        """Sync the device-resident victim planes from the host table:
        full upload on rebuild/permute (dirty_rows None), dirty-row scatter
        otherwise, nothing at all in the steady state — the same delta
        contract as the node matrix."""
        key = (vt.P, vt.valid.shape[0])
        if (self._dev_vic is None or self._dev_vic_key != key
                or vt.dirty_rows is None):
            host = self._vic_planes(vt)
            if self.mesh is not None:
                # the round-9 victim table under NamedSharding(mesh,
                # P("nodes")): [N, P] slot planes split on the node axis,
                # same residency/delta contract as the node matrix
                from kubernetes_tpu.parallel import sharding as S
                self._dev_vic = S.shard_victim_planes(self.mesh, host)
            else:
                self._dev_vic = {k: jnp.asarray(v) for k, v in host.items()}
            self._dev_vic_key = key
            DEVICE_DISPATCH.labels("vic_upload").inc()
            vt.dirty_rows = []
            return self._dev_vic
        if vt.dirty_rows:
            rows = np.asarray(sorted(set(vt.dirty_rows)), dtype=np.int32)
            bucket = _pad_pow2(len(rows), 16)
            rows = np.concatenate(
                [rows, np.full(bucket - len(rows), rows[0], dtype=np.int32)])
            self._dev_vic = _scatter_rows(self._dev_vic, rows,
                                          self._vic_planes(vt, rows))
            DEVICE_DISPATCH.labels("vic_scatter").inc()
            vt.dirty_rows = []
        return self._dev_vic

    def prewarm_preempt(self, node_infos: dict[str, NodeInfo],
                        all_node_names: list[str], pdbs: list) -> None:
        """Build + upload the node matrix and the persistent victim table
        outside any timed/decision window — the steady-state condition:
        in production the table is maintained incrementally across cycles,
        so a preemption wave never pays the cold build. Consumes no
        rotation state and folds nothing."""
        b = self.encoder.encode(node_infos, all_node_names)
        self._node_arrays(b)
        self._upload_victims(
            self.encoder.victim_table(node_infos, b, pdbs, cap=K.PREEMPT_P))

    # batched pressure chunks: bounds the [B, ...] upload and lets chunk
    # k+1's launch overlap chunk k's on-device execution
    PRESSURE_B_CAP = 128

    def preempt_pressure_burst(self, pods: list[Pod],
                               node_infos: dict[str, NodeInfo],
                               all_node_names: list[str], pdbs: list):
        """Schedule-else-preempt a failed burst tail in ONE launch
        (kernels.pressure_batch) instead of one dispatch+fetch round trip per
        failed pod. Replays the serial loop exactly: per pod in queue order, a
        ghost-aware schedule attempt (podFitsOnNode two-pass,
        generic_scheduler.go:598,627), then the victim scan + 5-criteria
        node pick (:966,1054,837), accumulating nominations as ghost load
        for the pods behind it.

        Eligible when: no pre-existing nominations, the NodeTree enumeration
        is the device axis every cycle (even zones), pod priorities are
        non-increasing (queue pop order — so every accumulated ghost counts
        for every later pod), each pod is resource-only (no volumes /
        affinity terms / host ports / scalars / stale nomination / spread
        selector match), and every potential victim is mask-inert. Returns
        None to refuse (shell falls back to the serial loop) or a per-pod
        outcome list:
          ("bound", host_name)           — scheduled, delta folded on device
          ("nominated", node, victims)   — preemption chose `node`
          ("failed", any_candidates)     — no fit, no preemption; the flag
            distinguishes "no candidate nodes" (the oracle clears the pod's
            own stale nomination, :330-333) from "candidates but no fit"."""
        from kubernetes_tpu.api.types import (has_pod_affinity_terms,
                                              get_container_ports,
                                              get_resource_request)
        if not pods or not all_node_names:
            return None
        import time as _time
        _t0 = _time.perf_counter()
        if not self.breaker.allow_device():
            # circuit open: the serial loop (host twin + oracle Preemptor)
            # runs the tail instead — decisions identical
            PRESSURE_GATES.labels("circuit-open").inc()
            return None
        if self.nominated is not None and self.nominated.has_any():
            PRESSURE_GATES.labels("nominated-ghosts").inc()
            return None
        if self._tree_rotates():
            PRESSURE_GATES.labels("tree-rotation").inc()
            return None
        prios = [p.priority for p in pods]
        if any(a < bb for a, bb in zip(prios, prios[1:])):
            PRESSURE_GATES.labels("priority-order").inc()
            return None
        for p in pods:
            if p.volumes or p.nominated_node_name:
                PRESSURE_GATES.labels("pod-features").inc()
                return None
            if has_pod_affinity_terms(p) or get_container_ports(p):
                PRESSURE_GATES.labels("pod-features").inc()
                return None
            if get_resource_request(p).scalar:
                PRESSURE_GATES.labels("pod-features").inc()
                return None
        axis_order, start0 = self._axis_order(all_node_names)
        b = self.encoder.encode(node_infos, axis_order)
        nodes = self._node_arrays(b)
        enc = self._pod_encoder(node_infos, b)
        feat_by_sig: dict = {}
        feats = []
        for p in pods:
            sig = self._class_signature(p)
            f = feat_by_sig.get(sig)
            if f is None:
                f = feat_by_sig[sig] = enc.encode(p)
            feats.append(f)
        for f in feats:
            if f.unknown_scalars:
                PRESSURE_GATES.labels("pod-features").inc()
                return None
            if f.spread_counts is not None:
                # selector-spread scoring depends on in-burst placements;
                # the pressure scan doesn't carry spread counts
                PRESSURE_GATES.labels("spread-selectors").inc()
                return None
        press_weights = self.weights
        if self._ptab is not None:
            # tensor mode: the pressure kernel scores with ONE static
            # per-profile row (its ghost/victim machinery has no per-pod
            # row gather); a mixed-profile tail degrades to the serial
            # loop, whose per-pod twin configs are exact
            pids = self._profile_ids(pods)
            if int(pids.min()) != int(pids.max()):
                PRESSURE_GATES.labels("profile-mixed").inc()
                return None
            press_weights = self._profile_static[int(pids[0])]
        vic, slots, gate = self._victim_inputs(node_infos, b, all_node_names,
                                               prios[0], pdbs)
        if vic is None:
            PRESSURE_GATES.labels(f"victims-{gate}").inc()
            return None
        per_pod = []
        for p, f in zip(pods, feats):
            d = self._pod_arrays(f, b.n_pad, upd_fields=True, pod=p)
            d["pprio"] = np.int64(p.priority)
            per_pod.append(d)
        n = b.n_real
        num_to_find = num_feasible_nodes_to_find(
            n, self.percentage_of_nodes_to_score)
        z_pad = _pad_pow2(len(b.zone_names), 4)
        mut0 = {k: nodes[k] for k in K._MUTABLE}
        ghost_key = (b.n_pad, self.mesh)
        ghost0 = self._ghost_zeros.get(ghost_key)
        if ghost0 is None:
            ghost0 = {k: jnp.zeros(b.n_pad, jnp.int64)
                      for k in ("cpu", "mem", "eph", "cnt")}
            if self.mesh is not None:
                # ghost load lives on the node axis: split it like the rows
                from kubernetes_tpu.parallel import sharding as S
                ghost0 = {k: jax.device_put(v, S.node_sharding(self.mesh))
                          if v.shape[0] % self.mesh.devices.size == 0 else v
                          for k, v in ghost0.items()}
            self._ghost_zeros[ghost_key] = ghost0
        li, lni = self.last_index, self.last_node_index
        # flight recorder: pressure waves are dump-only records (no oracle
        # replay harness) — the digest still pins inputs + outcomes
        fl = obs_flight.RECORDER.begin("pressure", self, [(pods, False)],
                                       all_node_names, node_infos)
        # encode vs device-scan phase boundary: everything above is host
        # encode + delta upload; everything below is dispatch + the one
        # fetch that pays the round trip (perf.harness.run_preempt_cell)
        _t_enc = _time.perf_counter()
        # after the fact and in the ring alone: the gates above return
        # from the middle of it, and no benchmark cell drives this path
        obs_trace.add_span("pressure.encode", _t0, _t_enc, cat="host")
        outs_chunks = []
        try:
            for lo in range(0, len(per_pod), self.PRESSURE_B_CAP):
                chaos.check("device.dispatch")
                chunk = per_pod[lo: lo + self.PRESSURE_B_CAP]
                stacked, _signatures = self._stack_pods(
                    chunk, _pad_pow2(len(chunk), 8))
                mut0, ghost0, li, lni, outs = K.pressure_batch(
                    nodes, mut0, ghost0, stacked, vic, li, lni, num_to_find,
                    n, z_pad, weights=press_weights, mesh=self.mesh)
                DEVICE_DISPATCH.labels("pressure_batch").inc()
                outs_chunks.append(outs)
            # ONE fetch for every chunk's outputs + the final counters
            with obs_trace.span("pressure.fetch", cat="device"):
                chaos.check("device.fetch")
                h_chunks, li, lni = jax.device_get((outs_chunks, li, lni))
        except _DEVICE_FAULTS as e:
            # everything so far is device-local (the resident matrix,
            # counters, and host mirror are untouched until after the
            # fetch): refuse the wave — the shell's serial loop re-derives
            # identical schedule/preempt decisions through the oracle
            self._device_fault(e)
            PRESSURE_GATES.labels("device-fault").inc()
            obs_flight.RECORDER.note_outcome(fl, {"outcomes": [],
                                                  "aborted": True})
            return None
        self.breaker.record_success()
        # ONE synchronization for the whole wave regardless of chunk count —
        # the fetch contract the preemption-lane test pins
        DEVICE_FETCHES.labels("pressure_batch").inc()
        DEVICE_FETCHED_BYTES.labels("pressure_batch").inc(
            _fetched_nbytes(h_chunks))
        self.last_preempt_phases = {
            "encode": _t_enc - _t0,
            "scan": _time.perf_counter() - _t_enc,
        }
        outcomes = []
        k = 0
        for h in h_chunks:
            bb = len(h["selected"])
            for j in range(bb):
                if k >= len(pods):
                    break
                sel = int(h["selected"][j])
                win = int(h["winner"][j])
                if sel >= 0:
                    outcomes.append(("bound", b.names[sel]))
                elif win >= 0:
                    name = b.names[win]
                    flags = h["victims"][j].astype(bool)
                    victims = [p for s, p in enumerate(slots.get(name, []))
                               if flags[s]]
                    outcomes.append(("nominated", name, victims))
                else:
                    outcomes.append(("failed", bool(h["any_cand"][j])))
                k += 1
        # persist: the mutable rows now live on device (successes folded);
        # the shell syncs the host mirror per bound pod via
        # note_burst_assumed, exactly like the burst prefix commit
        self._dev_nodes = {**self._dev_nodes, **mut0}
        self.last_index = int(li)
        self.last_node_index = int(lni)
        obs_flight.RECORDER.note_outcome(fl, {"outcomes": [
            oc if oc[0] != "nominated"
            else ("nominated", oc[1], sorted(v.name for v in oc[2]))
            for oc in outcomes]})
        return outcomes

    # -- gang (PodGroup) checkpoint/rewind -----------------------------------
    # PR 3's rewind contract generalized from per-wave to per-GROUP: a gang
    # trial runs through the ordinary wave machinery (schedule_burst with no
    # commit callback, so nothing reaches the cache/store), and either the
    # WHOLE gang's folds persist or the carries — li, lni, the device-resident
    # node matrix, and (via the shell) the NodeTree rotation cursor — rewind
    # to this checkpoint as if the gang was never attempted.
    def gang_checkpoint(self) -> dict:
        """Snapshot the device carries at a group boundary. The matrix
        snapshot is kernels.gang_carry_checkpoint's zero-copy pin: trial
        folds build new arrays, so the pre-gang rows stay resident and a
        same-epoch rewind restores them without a re-upload."""
        return {"li": self.last_index, "lni": self.last_node_index,
                "dev": K.gang_carry_checkpoint(self._dev_nodes),
                "key": self._dev_key, "epoch": self._dev_epoch}

    def gang_rewind(self, chk: dict) -> None:
        """Discard everything since `chk`: in-flight folds are dropped and
        last_index/lastNodeIndex rewind to the pre-gang prefix. When no
        host upload/scatter happened since the checkpoint (the epoch
        matches), the pinned pre-gang matrix is restored in place — the
        common case pays ZERO device traffic for a rejected gang; otherwise
        the matrix is discarded and re-uploads from the host mirror (which
        never saw the trial: gang folds only commit on success)."""
        self.last_index = chk["li"]
        self.last_node_index = chk["lni"]
        if self._dev_nodes is not None:
            GANG_REWIND_FOLDS.inc()
        if chk["dev"] is not None and self._dev_epoch == chk["epoch"]:
            self._dev_nodes = chk["dev"]
            self._dev_key = chk["key"]
        else:
            self.discard_burst_folds()

    def discard_burst_folds(self) -> None:
        """Forget the device-resident node matrix: in-scan folds for burst
        decisions the shell discarded (the serial tail after a mid-burst
        failure) must not leak into later cycles — the next use re-uploads
        from the host mirror, which only reflects consumed decisions."""
        if self._dev_nodes is not None:
            DISCARDED_FOLDS.inc()
        self._dev_nodes = None

    def invalidate_node(self, host: str) -> None:
        """Mid-burst node death (the shell's _invalidate_dead_node): the
        device-resident node matrix and victim table carry a row for a
        node the store no longer has — drop both, and forget the
        encoder's per-node generation entries for `host` so nothing
        keyed to the dead row survives. The cache removal (which the
        shell performs first) changed NodeTree membership, so the next
        encode() sees a different node_order and rebuilds the mirror;
        the victim table rebuilds from its generation cache on the next
        scan. In-flight burst decisions past the detection point are
        discarded by the driver's abort/rewind contract."""
        self.discard_burst_folds()
        self._dev_vic = None
        self._dev_vic_key = None
        enc = self.encoder
        enc._generations.pop(host, None)
        enc._vt_gens.pop(host, None)

    def recover_device(self, li: Optional[int] = None,
                       lni: Optional[int] = None) -> None:
        """Crash-restart device reset (Scheduler.recover): drop every
        device-resident structure — the node matrix (in-flight folds for
        decisions that never committed must not survive the crash) and the
        victim table — and rewind the walk counters to the recovered
        commit boundary. The next encode re-uploads from the host mirror,
        which the cache reconcile has already made authoritative; the
        victim table rebuilds from its generation cache."""
        self.discard_burst_folds()
        self._dev_vic = None
        self._dev_vic_key = None
        if li is not None:
            self.last_index = int(li)
        if lni is not None:
            self.last_node_index = int(lni)
        self.commit_marker = None

    def debug_state(self) -> dict:
        """The /debug/sched device section: mirror shape + epochs, walk
        counters, victim-table generations/dirty rows, serial-path
        latencies — everything a stuck-scheduler triage reads first."""
        dev = self._dev_nodes
        mirror = None
        if dev is not None:
            any_field = dev.get("valid")
            mirror = {"fields": len(dev),
                      "n_pad": (None if any_field is None
                                else int(any_field.shape[-1]))}
        vt = getattr(self.encoder, "_vt", None)
        vic = None
        if vt is not None:
            vic = {"P": int(vt.P), "rows": int(vt.valid.shape[0]),
                   "generations": len(getattr(self.encoder, "_vt_gens", {})),
                   "dirty_rows": (None if vt.dirty_rows is None
                                  else len(vt.dirty_rows)),
                   "resident": self._dev_vic is not None}
        return {
            "mirror": mirror,
            "dev_epoch": self._dev_epoch,
            "breaker": self.breaker.debug_state(),
            "last_index": self.last_index,
            "last_node_index": self.last_node_index,
            "victim_table": vic,
            "mesh": self.mesh is not None,
            "devices": (1 if self.mesh is None
                        else int(self.mesh.devices.size)),
            "serial_path": self.serial_path,
            "serial_lat_ms": {
                "host_twin": (None if self._lat_ora is None
                              else round(self._lat_ora * 1e3, 3)),
                "device": (None if self._lat_dev is None
                           else round(self._lat_dev * 1e3, 3))},
        }

    def note_burst_assumed(self, pod: Pod, host: str, generation: int) -> None:
        """Post-burst bookkeeping for one placed pod: fold the same delta
        the device scan applied into the host numpy mirror and sync the
        encoder's generation map to the cache's post-assume generation, so
        the next encode() neither re-encodes nor re-uploads the row."""
        b = self.encoder._batch
        if b is None or host not in b.index:
            return
        self.encoder.note_assumed(b, host, pod, generation=generation,
                                  mark_dirty=False)

    def note_burst_assumed_many(self, pods: list[Pod], hosts: list[str],
                                generations: list) -> None:
        """Batched note_burst_assumed for a committed wave: one vectorized
        mirror scatter + one generation-map update instead of a Python call
        chain per pod (encoder.note_assumed_many). Entries whose node left
        the mirror or the cache (generation None) are skipped, matching the
        per-pod path's guard."""
        b = self.encoder._batch
        if b is None:
            return
        keep = [(p, h, g) for p, h, g in zip(pods, hosts, generations)
                if g is not None and h in b.index]
        if not keep:
            return
        kp, kh, kg = zip(*keep)
        self.encoder.note_assumed_many(b, list(kp), list(kh), list(kg))
