"""Plain reference: the default provider's serial schedule(), on numpy planes.

Independent of the program: imports nothing of `kubernetes_tpu`, and is fed
only what the benchmark's own client made (the cluster it built from the
seed) or saw on its own watch (the order of binds and deletes). It states the
semantics the configuration promises, one pod at a time:

- filter: PodFitsResources (pod count, cpu, memory) over every node, in the
  zone-interleaved order of the node tree; all other default predicates pass
  for the pod shapes this reference accepts (no taints, ports, selectors,
  volumes or affinity) and it refuses any other shape;
- score: LeastRequested + BalancedResourceAllocation + SelectorSpread (node
  1/3, zone 2/3), each 0..10 with the default weights 1. The other default
  priorities are constant over nodes for these shapes (NodeAffinity 0,
  TaintToleration 10, InterPodAffinity 0, ImageLocality 0, NodePreferAvoidPods
  10 x 10000), so they cannot move an argmax or a tie and are left out;
- select: round-robin among the maximum-score nodes by a counter that rises
  once per decision with more than one feasible node.

Every node is scored (`percentage_of_nodes_to_score` = 100): the
configuration states it, and the reference refuses any other value.

Arithmetic is the Go reference's: int64 with truncating division for the
resource scores, IEEE float64 in the written order of operations for the
balanced and spread scores (numpy float64 is IEEE, so the truncations agree
bit for bit).
"""
from __future__ import annotations

import numpy as np

MAX_PRIORITY = 10
ZONE_WEIGHTING = 2.0 / 3.0
DEFAULT_MILLI_CPU = 100            # non-zero request defaults
DEFAULT_MEMORY = 200 * 1024 * 1024
SHAPE_KINDS = ("plain", "spread-by-service")


class NodeOrder:
    """The node tree's per-decision enumeration: zones in order of first
    appearance, one node from each zone in turn; a zone that has handed out
    its last node is exhausted, and when all are, every cursor returns to 0
    while the zone index keeps its place. One decision consumes one full
    enumeration. Orders are cached by the state they start from."""

    def __init__(self, zone_of_node: list[str]):
        zones: list[str] = []
        members: dict[str, list[int]] = {}
        for i, z in enumerate(zone_of_node):
            if z not in members:
                members[z] = []
                zones.append(z)
            members[z].append(i)
        self.members = [np.asarray(members[z], dtype=np.int64) for z in zones]
        self.n = len(zone_of_node)
        self.state = (0, tuple(0 for _ in zones), frozenset())
        self._cache: dict = {}

    def _walk(self, state):
        zi, cursors, exhausted = state
        cursors = list(cursors)
        exhausted = set(exhausted)
        nz = len(self.members)
        out = np.empty(self.n, dtype=np.int64)
        k = 0
        while k < self.n:
            if len(exhausted) == nz:
                for z in exhausted:
                    cursors[z] = 0
                exhausted.clear()
            z = zi
            zi = (zi + 1) % nz
            if z in exhausted:
                continue
            idx = cursors[z]
            size = len(self.members[z])
            if idx >= size - 1:
                exhausted.add(z)
            if idx < size:
                cursors[z] = idx + 1
                out[k] = self.members[z][idx]
                k += 1
        return out, (zi, tuple(cursors), frozenset(exhausted))

    def next_order(self):
        """(order, rank): the nodes in this decision's enumeration order, and
        each node's position in it."""
        hit = self._cache.get(self.state)
        if hit is None:
            order, nxt = self._walk(self.state)
            rank = np.empty(self.n, dtype=np.int64)
            rank[order] = np.arange(self.n)
            hit = self._cache[self.state] = (order, rank, nxt)
        self.state = hit[2]
        return hit[0], hit[1]


class Reference:
    """Serial reference scheduler over a fixed node set.

    `nodes`: list of dicts {name, zone_key, cpu, mem, pods} in creation
    order. `services`: {namespace: [selector dict, ...]}. Pods are dicts
    {cpu, mem, namespace, labels (tuple of sorted items), kind}."""

    def __init__(self, nodes: list[dict], services: dict,
                 percentage_of_nodes_to_score: int = 100):
        if percentage_of_nodes_to_score != 100:
            raise ValueError("this reference states the semantics at 100% of "
                             "nodes scored only")
        n = len(nodes)
        self.n = n
        self.names = [nd["name"] for nd in nodes]
        self.index = {nm: i for i, nm in enumerate(self.names)}
        self.alloc_cpu = np.array([nd["cpu"] for nd in nodes], dtype=np.int64)
        self.alloc_mem = np.array([nd["mem"] for nd in nodes], dtype=np.int64)
        self.alloc_pods = np.array([nd["pods"] for nd in nodes], dtype=np.int64)
        self.req_cpu = np.zeros(n, dtype=np.int64)
        self.req_mem = np.zeros(n, dtype=np.int64)
        self.nz_cpu = np.zeros(n, dtype=np.int64)
        self.nz_mem = np.zeros(n, dtype=np.int64)
        self.n_pods = np.zeros(n, dtype=np.int64)
        zone_keys = [nd["zone_key"] for nd in nodes]
        zones = sorted(set(z for z in zone_keys if z))
        self.zone_id = np.array([zones.index(z) if z else -1
                                 for z in zone_keys], dtype=np.int64)
        self.n_zones = len(zones)
        self.order = NodeOrder(zone_keys)
        self.services = services
        self.last_node_index = 0
        # (namespace, selector items) -> per-node count of matching pods
        self._match: dict = {}
        # every pod on a node, so that a selector first seen later can be
        # counted: (namespace, labels) -> per-node count
        self._by_labels: dict = {}
        # (namespace, labels) -> the cached selector keys that label set matches
        self._keys_of: dict = {}
        # non-zero request -> [resource-score plane, nodes touched since]
        self._res_cache: dict = {}
        self._no_selector_spread = None

    # -- state -------------------------------------------------------------
    @staticmethod
    def nonzero(pod: dict) -> tuple[int, int]:
        return (pod["cpu"] if pod["cpu"] else DEFAULT_MILLI_CPU,
                pod["mem"] if pod["mem"] else DEFAULT_MEMORY)

    def _selectors(self, pod: dict) -> list[tuple]:
        labels = dict(pod["labels"])
        out = []
        for sel in self.services.get(pod["namespace"], ()):
            if sel and all(labels.get(k) == v for k, v in sel.items()):
                out.append(tuple(sorted(sel.items())))
        return out

    def _counts_for(self, namespace: str, selectors: list[tuple]):
        """Per-node count of pods in `namespace` matching ALL selectors."""
        key = (namespace, tuple(selectors))
        arr = self._match.get(key)
        if arr is None:
            arr = np.zeros(self.n, dtype=np.int64)
            sels = [dict(s) for s in selectors]
            for (ns, labels), cnt in self._by_labels.items():
                if ns != namespace:
                    continue
                ld = dict(labels)
                if all(all(ld.get(k) == v for k, v in s.items())
                       for s in sels):
                    arr += cnt
            self._match[key] = arr
            self._keys_of.clear()     # a new selector: re-derive who matches
        return arr

    def _touch(self, pod: dict, node: int, sign: int) -> None:
        self.req_cpu[node] += sign * pod["cpu"]
        self.req_mem[node] += sign * pod["mem"]
        nzc, nzm = self.nonzero(pod)
        self.nz_cpu[node] += sign * nzc
        self.nz_mem[node] += sign * nzm
        self.n_pods[node] += sign
        for entry in self._res_cache.values():
            entry[1].add(node)
        lk = (pod["namespace"], pod["labels"])
        cnt = self._by_labels.get(lk)
        if cnt is None:
            cnt = self._by_labels[lk] = np.zeros(self.n, dtype=np.int64)
        cnt[node] += sign
        keys = self._keys_of.get(lk)
        if keys is None:
            ld = dict(pod["labels"])
            keys = self._keys_of[lk] = [
                key for key in self._match
                if key[0] == pod["namespace"] and all(
                    all(ld.get(k) == v for k, v in s) for s in key[1])]
        for key in keys:
            self._match[key][node] += sign

    def place(self, pod: dict, node_name: str) -> None:
        self._touch(pod, self.index[node_name], +1)

    def remove(self, pod: dict, node_name: str) -> None:
        self._touch(pod, self.index[node_name], -1)

    def skip_decision(self, feasible_many: bool = True) -> None:
        """Advance the rotating state past a decision that is not compared."""
        self.order.next_order()
        if feasible_many:
            self.last_node_index += 1

    # -- one decision ------------------------------------------------------
    def decide(self, pod: dict) -> str | None:
        """The node the serial default scheduler binds `pod` to now, or None
        when no node fits. Advances the rotating state exactly as one
        scheduling cycle does."""
        if pod["kind"] not in SHAPE_KINDS:
            raise ValueError(f"no reference for pod shape {pod['kind']!r}")
        _order, rank = self.order.next_order()
        fits = ((self.n_pods + 1 <= self.alloc_pods)
                & (self.alloc_cpu >= pod["cpu"] + self.req_cpu)
                & (self.alloc_mem >= pod["mem"] + self.req_mem))
        n_fit = int(np.count_nonzero(fits))
        if n_fit == 0:
            return None
        if n_fit == 1:
            return self.names[int(np.flatnonzero(fits)[0])]
        # scores over every node (those that do not fit are masked out below;
        # the spread score takes its maxima over the fitting nodes only)
        total = self._resource_scores(pod)
        total = total + self._spread(pod, fits)
        total = np.where(fits, total, -1)
        best = np.flatnonzero(total == total.max())
        # the tied nodes in enumeration order; the counter picks among them
        k = self.last_node_index % best.size
        self.last_node_index += 1
        ranks = rank[best]
        pick = best[np.argpartition(ranks, k)[k]] if best.size > 1 else best[0]
        return self.names[int(pick)]

    def _resource_scores(self, pod: dict) -> np.ndarray:
        """LeastRequested + BalancedResourceAllocation for this pod on every
        node. A node's value depends only on the pod's non-zero request and
        the node's own sums, so the plane is kept per request and only the
        nodes touched since are worked out again."""
        key = self.nonzero(pod)
        entry = self._res_cache.get(key)
        if entry is None:
            idx = np.arange(self.n)
            plane = np.empty(self.n, dtype=np.int64)
            self._res_cache[key] = [plane, set()]
        else:
            plane, dirty = entry
            if not dirty:
                return plane
            idx = np.fromiter(dirty, dtype=np.int64, count=len(dirty))
            dirty.clear()
        nzc, nzm = key
        cpu = self.nz_cpu[idx] + nzc
        mem = self.nz_mem[idx] + nzm
        cap_c, cap_m = self.alloc_cpu[idx], self.alloc_mem[idx]

        def least(req, cap):
            ok = (cap != 0) & (req <= cap)
            safe = np.where(cap == 0, 1, cap)
            return np.where(ok, ((cap - req) * MAX_PRIORITY) // safe, 0)

        total = (least(cpu, cap_c) + least(mem, cap_m)) // 2
        with np.errstate(divide="ignore", invalid="ignore"):
            fc = np.where(cap_c == 0, 1.0, cpu / cap_c.astype(np.float64))
            fm = np.where(cap_m == 0, 1.0, mem / cap_m.astype(np.float64))
        bal = ((1 - np.abs(fc - fm)) * float(MAX_PRIORITY)).astype(np.int64)
        plane[idx] = total + np.where((fc >= 1) | (fm >= 1), 0, bal)
        return plane

    def _spread(self, pod: dict, fits: np.ndarray) -> np.ndarray:
        """SelectorSpread over the fitting nodes, as a plane over all nodes
        (values at nodes that do not fit are not used)."""
        selectors = self._selectors(pod)
        if not selectors:
            # all counts are 0: both maxima are 0, every score is the full one
            if self._no_selector_spread is None:
                full = float(MAX_PRIORITY)
                blended = (full * (1.0 - ZONE_WEIGHTING)) + (ZONE_WEIGHTING * full)
                self._no_selector_spread = np.where(
                    self.zone_id >= 0, int(blended), int(full)).astype(np.int64)
            return self._no_selector_spread
        counts = np.where(
            fits, self._counts_for(pod["namespace"], selectors), 0)
        max_node = int(counts.max())
        f = np.full(self.n, float(MAX_PRIORITY))
        if max_node > 0:
            f = float(MAX_PRIORITY) * ((max_node - counts) / float(max_node))
        zoned = fits & (self.zone_id >= 0)
        if zoned.any():
            zid = self.zone_id
            by_zone = np.bincount(zid[zoned], weights=counts[zoned],
                                  minlength=self.n_zones).astype(np.int64)
            present = np.bincount(zid[zoned], minlength=self.n_zones) > 0
            max_zone = int(by_zone[present].max())
            zs = np.full(self.n, float(MAX_PRIORITY))
            if max_zone > 0:
                zc = by_zone[np.where(zid >= 0, zid, 0)]
                zs = float(MAX_PRIORITY) * ((max_zone - zc) / float(max_zone))
            blended = (f * (1.0 - ZONE_WEIGHTING)) + (ZONE_WEIGHTING * zs)
            f = np.where(self.zone_id >= 0, blended, f)
        return f.astype(np.int64)
