"""Semantic oracle: Score priorities, exact reference integer/float behavior.

Pure-Python transliteration of the semantics of
pkg/scheduler/algorithm/priorities/ — Map/Reduce over nodes, integer scores
0-10 (MaxPriority), weighted sum done by the caller. Float blends
(BalancedAllocation, SelectorSpread zone weighting, InterPodAffinity
min-max normalize) use IEEE double exactly as the Go code does.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

from kubernetes_tpu.api.types import (
    Pod, Node, Service, ReplicaSet, get_pod_nonzero_requests, get_zone_key,
    PREFER_NO_SCHEDULE, tolerations_tolerate_taint,
)
from kubernetes_tpu.cache.node_info import NodeInfo, normalized_image_name
from kubernetes_tpu.oracle.predicates import (
    pod_matches_term_props, nodes_same_topology,
)

MAX_PRIORITY = 10  # reference: pkg/scheduler/api/types.go:35

# ---------------------------------------------------------------------------
# Resource-allocation scorers (reference: resource_allocation.go:39 —
# all of them consume the pod's *nonzero* request + node NonZeroRequest)
# ---------------------------------------------------------------------------


def _pod_plus_node_nonzero(pod: Pod, ni: NodeInfo) -> tuple[int, int]:
    cpu, mem = get_pod_nonzero_requests(pod)
    return cpu + ni.nonzero_cpu, mem + ni.nonzero_mem


def least_requested_score(requested: int, capacity: int) -> int:
    """Reference: least_requested.go:44 — (cap-req)*10/cap, int64 truncation."""
    if capacity == 0 or requested > capacity:
        return 0
    return ((capacity - requested) * MAX_PRIORITY) // capacity


def most_requested_score(requested: int, capacity: int) -> int:
    """Reference: most_requested.go:46."""
    if capacity == 0 or requested > capacity:
        return 0
    return (requested * MAX_PRIORITY) // capacity


def least_requested_map(pod: Pod, ni: NodeInfo) -> int:
    cpu, mem = _pod_plus_node_nonzero(pod, ni)
    return (least_requested_score(cpu, ni.allocatable.milli_cpu)
            + least_requested_score(mem, ni.allocatable.memory)) // 2


def most_requested_map(pod: Pod, ni: NodeInfo) -> int:
    cpu, mem = _pod_plus_node_nonzero(pod, ni)
    return (most_requested_score(cpu, ni.allocatable.milli_cpu)
            + most_requested_score(mem, ni.allocatable.memory)) // 2


def balanced_allocation_map(pod: Pod, ni: NodeInfo) -> int:
    """Reference: balanced_resource_allocation.go:41 — float64 fractions,
    int64 truncation of (1-|cpuF-memF|)*10. Under the
    BalanceAttachedNodeVolumes gate with per-cycle transient volume counts
    (written by the Max*VolumeCount predicates), the three-fraction variance
    form applies instead (balanced_resource_allocation.go:44-58)."""
    cpu, mem = _pod_plus_node_nonzero(pod, ni)
    cpu_frac = _fraction(cpu, ni.allocatable.milli_cpu)
    mem_frac = _fraction(mem, ni.allocatable.memory)
    from kubernetes_tpu.utils import features
    if features.enabled("BalanceAttachedNodeVolumes") \
            and ni.transient_allocatable_volumes is not None \
            and ni.transient_allocatable_volumes > 0:
        vol_frac = (ni.transient_requested_volumes
                    / ni.transient_allocatable_volumes)
        if cpu_frac >= 1 or mem_frac >= 1 or vol_frac >= 1:
            return 0
        mean = (cpu_frac + mem_frac + vol_frac) / 3.0
        variance = ((cpu_frac - mean) ** 2 + (mem_frac - mean) ** 2
                    + (vol_frac - mean) ** 2) / 3.0
        return int((1 - variance) * float(MAX_PRIORITY))
    if cpu_frac >= 1 or mem_frac >= 1:
        return 0
    diff = abs(cpu_frac - mem_frac)
    return int((1 - diff) * float(MAX_PRIORITY))


def resource_limits_map(pod: Pod, ni: NodeInfo) -> int:
    """Reference: resource_limits.go:36 ResourceLimitsPriorityMap — score 1
    when the node's allocatable satisfies the pod's cpu OR memory limit
    (tie-break nudge toward nodes that can honor limits), else 0."""
    from kubernetes_tpu.api.types import get_resource_limits
    limits = get_resource_limits(pod)
    alloc = ni.allocatable

    def compute(limit: int, allocatable: int) -> int:
        return 1 if limit != 0 and allocatable != 0 and limit <= allocatable \
            else 0

    return 1 if (compute(limits.milli_cpu, alloc.milli_cpu) == 1
                 or compute(limits.memory, alloc.memory) == 1) else 0


def _fraction(req: int, cap: int) -> float:
    if cap == 0:
        return 1.0
    return req / cap


# Requested-to-capacity-ratio broken-linear (reference: requested_to_capacity_ratio.go)
DEFAULT_RTCR_SHAPE: tuple[tuple[int, int], ...] = ((0, 10), (100, 0))


def _trunc_div(a: int, b: int) -> int:
    """Go int64 division truncates toward zero; Python // floors."""
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


def broken_linear(shape: tuple[tuple[int, int], ...], p: int) -> int:
    """Reference: buildBrokenLinearFunction :128 — integer segment
    interpolation with Go's truncate-toward-zero division."""
    for i, (u, s) in enumerate(shape):
        if p <= u:
            if i == 0:
                return shape[0][1]
            u0, s0 = shape[i - 1]
            return s0 + _trunc_div((s - s0) * (p - u0), u - u0)
    return shape[-1][1]


def make_rtcr_map(shape: tuple[tuple[int, int], ...] = DEFAULT_RTCR_SHAPE
                  ) -> Callable[[Pod, NodeInfo], int]:
    def resource_score(requested: int, capacity: int) -> int:
        if capacity == 0 or requested > capacity:
            return broken_linear(shape, 100)
        return broken_linear(shape, 100 - (capacity - requested) * 100 // capacity)

    def rtcr_map(pod: Pod, ni: NodeInfo) -> int:
        cpu, mem = _pod_plus_node_nonzero(pod, ni)
        return (resource_score(cpu, ni.allocatable.milli_cpu)
                + resource_score(mem, ni.allocatable.memory)) // 2

    return rtcr_map


# ---------------------------------------------------------------------------
# Node affinity (reference: node_affinity.go:34 + NormalizeReduce(10, false))
# ---------------------------------------------------------------------------
def node_affinity_map(pod: Pod, ni: NodeInfo) -> int:
    affinity = pod.affinity
    count = 0
    if affinity is not None and affinity.node_affinity is not None:
        for term in affinity.node_affinity.preferred:
            if term.weight == 0:
                continue
            if term.preference.match_expressions and term.preference.matches(ni.node.labels):
                count += term.weight
    return count


def normalize_reduce(max_priority: int, reverse: bool,
                     scores: list[int]) -> list[int]:
    """Reference: reduce.go:28 NormalizeReduce."""
    max_count = max(scores) if scores else 0
    if max_count == 0:
        return [max_priority] * len(scores) if reverse else list(scores)
    out = []
    for s in scores:
        s = max_priority * s // max_count
        if reverse:
            s = max_priority - s
        out.append(s)
    return out


# ---------------------------------------------------------------------------
# Taint/toleration (reference: taint_toleration.go + NormalizeReduce(10, true))
# ---------------------------------------------------------------------------
def taint_toleration_map(pod: Pod, ni: NodeInfo) -> int:
    tolerations = [t for t in pod.tolerations
                   if not t.effect or t.effect == PREFER_NO_SCHEDULE]
    count = 0
    for taint in ni.taints:
        if taint.effect != PREFER_NO_SCHEDULE:
            continue
        if not tolerations_tolerate_taint(tolerations, taint):
            count += 1
    return count


# ---------------------------------------------------------------------------
# Image locality (reference: image_locality.go)
# ---------------------------------------------------------------------------
MB = 1024 * 1024
IMAGE_MIN_THRESHOLD = 23 * MB
IMAGE_MAX_THRESHOLD = 1000 * MB


def image_locality_map(pod: Pod, ni: NodeInfo, total_num_nodes: int) -> int:
    total = 0
    for c in pod.containers:
        state = ni.image_states.get(normalized_image_name(c.image))
        if state is not None:
            spread = state.num_nodes / total_num_nodes
            total += int(state.size_bytes * spread)
    s = min(max(total, IMAGE_MIN_THRESHOLD), IMAGE_MAX_THRESHOLD)
    return MAX_PRIORITY * (s - IMAGE_MIN_THRESHOLD) // (IMAGE_MAX_THRESHOLD - IMAGE_MIN_THRESHOLD)


# ---------------------------------------------------------------------------
# NodePreferAvoidPods (reference: node_prefer_avoid_pods.go, weight 10000)
# ---------------------------------------------------------------------------
def node_prefer_avoid_pods_map(pod: Pod, ni: NodeInfo) -> int:
    owner = pod.owner_ref  # (kind, name, uid) controller ref
    if owner is None or owner[0] not in ("ReplicationController", "ReplicaSet"):
        return MAX_PRIORITY
    return 0 if owner[2] in ni.node.prefer_avoid_pod_uids else MAX_PRIORITY


# ---------------------------------------------------------------------------
# Selector spreading (reference: selector_spreading.go)
# ---------------------------------------------------------------------------
ZONE_WEIGHTING = 2.0 / 3.0


def get_selectors(pod: Pod, services: list[Service],
                  replicasets: list[ReplicaSet]) -> list:
    """Selectors of services / RC / RS / STS that select this pod
    (reference: selector_spreading.go getSelectors): a walk over both
    lists, every member tested. This is the definition. The serial oracle's
    SelectorSpread calls it (`generic_scheduler.default_priority_configs`,
    `factory`); the burst path gets the same list, member for member and
    in this order, from `selector_index.SelectorIndex`, which
    `tests/test_selector_index.py` holds to this function."""
    selectors = []
    for svc in services:
        if svc.namespace != pod.namespace or not svc.selector:
            continue
        if all(pod.labels.get(k) == v for k, v in svc.selector.items()):
            selectors.append(dict(svc.selector))
    for rs in replicasets:
        if rs.namespace != pod.namespace or rs.selector is None:
            continue
        if rs.selector.matches(pod.labels):
            selectors.append(rs.selector)
    return selectors


def spread_group_key(namespace: str, selectors: list) -> tuple:
    """What `get_selectors`' answer for a pod makes of it as far as
    SelectorSpread can tell: (namespace, the set of its selectors). Pods of
    one key read the same counts and count toward the same pods' counts,
    whatever else their specs hold. A bound pod of key h counts toward the
    pods of key g exactly when `counts_toward(h, g)`."""
    return (namespace, frozenset(
        tuple(sorted(s.items())) if isinstance(s, dict) else s
        for s in selectors))


def counts_toward(h: tuple, g: tuple) -> bool:
    """True when a pod of spread group `h` is counted by a pod of group `g`
    (`selector_spread_map`: same namespace, and every selector of g matches
    it). `get_selectors` gives a pod EVERY selector of its namespace that
    matches its labels, so g's all match a pod of h exactly when they are
    among h's."""
    return h[0] == g[0] and g[1] <= h[1]


def _selector_matches(selector, labels: dict[str, str]) -> bool:
    if isinstance(selector, dict):
        return all(labels.get(k) == v for k, v in selector.items())
    return selector.matches(labels)


def selector_spread_map(pod: Pod, ni: NodeInfo, selectors: list) -> int:
    """Count of existing same-namespace pods on the node matching ALL selectors."""
    if not ni.pods or not selectors:
        return 0
    count = 0
    for existing in ni.pods:
        if existing.namespace != pod.namespace or existing.deleted:
            continue
        if all(_selector_matches(sel, existing.labels) for sel in selectors):
            count += 1
    return count


def selector_spread_reduce(node_infos: dict[str, NodeInfo],
                           hosts: list[str], counts: list[int]) -> list[int]:
    """Reference: CalculateSpreadPriorityReduce — node+zone blend 1/3:2/3."""
    max_by_node = max(counts) if counts else 0
    counts_by_zone: dict[str, int] = {}
    for host, c in zip(hosts, counts):
        zone = get_zone_key(node_infos[host].node)
        if zone:
            counts_by_zone[zone] = counts_by_zone.get(zone, 0) + c
    max_by_zone = max(counts_by_zone.values()) if counts_by_zone else 0
    have_zones = len(counts_by_zone) != 0

    out = []
    for host, c in zip(hosts, counts):
        f_score = float(MAX_PRIORITY)
        if max_by_node > 0:
            f_score = float(MAX_PRIORITY) * ((max_by_node - c) / max_by_node)
        if have_zones:
            zone = get_zone_key(node_infos[host].node)
            if zone:
                zone_score = float(MAX_PRIORITY)
                if max_by_zone > 0:
                    zone_score = float(MAX_PRIORITY) * ((max_by_zone - counts_by_zone[zone]) / max_by_zone)
                f_score = (f_score * (1.0 - ZONE_WEIGHTING)) + (ZONE_WEIGHTING * zone_score)
        out.append(int(f_score))
    return out


# ---------------------------------------------------------------------------
# Inter-pod affinity priority (reference: interpod_affinity.go:116)
# ---------------------------------------------------------------------------
DEFAULT_HARD_POD_AFFINITY_WEIGHT = 1  # apis/config HardPodAffinitySymmetricWeight default


def interpod_affinity_priority(pod: Pod, node_infos: dict[str, NodeInfo],
                               nodes: list[Node],
                               hard_pod_affinity_weight: int = DEFAULT_HARD_POD_AFFINITY_WEIGHT
                               ) -> list[int]:
    """Function-style priority over the filtered `nodes` list; min-max
    normalized to 0-10 with 0 included in the min/max fold."""
    a = pod.affinity
    has_aff = a is not None and a.pod_affinity is not None
    has_anti = a is not None and a.pod_anti_affinity is not None

    counts: dict[str, int] = {}
    tracked: set[str] = set()
    for name, ni in node_infos.items():
        if has_aff or has_anti or ni.pods_with_affinity:
            counts[name] = 0
            tracked.add(name)

    def node_of(p: Pod) -> Optional[Node]:
        ni = node_infos.get(p.node_name)
        return ni.node if ni else None

    def process_term(term, defining: Pod, to_check: Pod, fixed_node: Node, weight: int):
        if fixed_node is None:
            return
        if pod_matches_term_props(to_check, defining, term):
            for name in tracked:
                n = node_infos[name].node
                if n is not None and nodes_same_topology(n, fixed_node, term.topology_key):
                    counts[name] += weight

    def process_pod(existing: Pod):
        existing_node = node_of(existing)
        ea = existing.affinity
        e_has_aff = ea is not None and ea.pod_affinity is not None
        e_has_anti = ea is not None and ea.pod_anti_affinity is not None
        if has_aff:
            for wt in a.pod_affinity.preferred:
                process_term(wt.term, pod, existing, existing_node, wt.weight)
        if has_anti:
            for wt in a.pod_anti_affinity.preferred:
                process_term(wt.term, pod, existing, existing_node, -wt.weight)
        if e_has_aff:
            if hard_pod_affinity_weight > 0:
                for term in ea.pod_affinity.required:
                    process_term(term, existing, pod, existing_node, hard_pod_affinity_weight)
            for wt in ea.pod_affinity.preferred:
                process_term(wt.term, existing, pod, existing_node, wt.weight)
        if e_has_anti:
            for wt in ea.pod_anti_affinity.preferred:
                process_term(wt.term, existing, pod, existing_node, -wt.weight)

    for ni in node_infos.values():
        if ni.node is None:
            continue
        pods = ni.pods if (has_aff or has_anti) else ni.pods_with_affinity
        for existing in pods:
            process_pod(existing)

    max_count = min_count = 0
    for node in nodes:
        if node.name in counts:
            max_count = max(max_count, counts[node.name])
            min_count = min(min_count, counts[node.name])

    diff = max_count - min_count
    out = []
    for node in nodes:
        f_score = 0.0
        if diff > 0 and node.name in counts:
            f_score = float(MAX_PRIORITY) * ((counts[node.name] - min_count) / diff)
        out.append(int(f_score))
    return out


def equal_priority_map(pod: Pod, ni: NodeInfo) -> int:
    return 1


# ---------------------------------------------------------------------------
# Gang locality (round 19 — rank-aware gang set-scoring, the serial half
# of the device kernels' per-segment zone-count carry)
# ---------------------------------------------------------------------------
def gang_locality_map(zone_counts: dict, ni: NodeInfo) -> int:
    """Score a candidate node by how many members of the CURRENT gang
    trial already landed in its zone, clipped at MAX_PRIORITY — the group
    objective that prefers packing a tightly-coupled gang into few
    zones/ICI domains. `zone_counts` is the trial's live {zone_key:
    members placed} map (reset per gang, updated after every member's
    assume); nodes without a zone score 0. Must stay bit-identical to the
    kernel's gang term in ops.kernels._fit_scores: min(count, 10),
    weighted by the member profile's gang weight at the caller."""
    zone = get_zone_key(ni.node) if ni.node is not None else ""
    if not zone:
        return 0
    return min(int(zone_counts.get(zone, 0)), MAX_PRIORITY)
