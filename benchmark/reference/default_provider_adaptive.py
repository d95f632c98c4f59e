"""Plain reference: the default provider's serial schedule() at any
`percentageOfNodesToScore`, upstream's adaptive default (0) among them.

`reference/default_provider.py` states the semantics when every node is
scored. This file states what changes when the walk over the nodes is cut
short (generic_scheduler.go:434-453 numFeasibleNodesToFind, :457-530
findNodesThatFit, :286-295 selectHost), and takes the planes, the node order
and the score arithmetic from that file. It imports nothing of
`kubernetes_tpu`. Per decision:

- `num_to_find`: all n nodes when n < 100 or the percentage is >= 100; else
  p = the percentage when it is > 0, else max(50 - n // 125, 5); then
  max(n * p // 100, 100). 15,000 nodes at the default: 750.
- the walk: the decision consumes one full enumeration of the node tree, as
  at 100%. Positions last_index, last_index + 1, ... (mod n) of it are tested
  with PodFitsResources until `num_to_find` nodes fit or all n are tested.
  The nodes that fit are kept, in walk order; last_index moves on by the
  number tested (mod n).
- score: LeastRequested and BalancedResourceAllocation are a node's own;
  SelectorSpread takes its node and zone maxima over the kept nodes only.
- select: the k-th of the maximum-score kept nodes in walk order, from the
  last_index the decision entered with; k = last_node_index mod the number
  tied. last_node_index rises iff more than one node was kept. No node
  kept: None.

One departure from upstream, which the program's oracle makes too
(`oracle/generic_scheduler.py:1-11`): upstream's walk runs on 16 workers that
race for the stop, so which nodes it keeps past the quota varies from run to
run. This is the single-worker serial walk, which stops at the node that
fills the quota. A decision's order is one whole enumeration of the node tree
and last_index a position in it, as upstream has it (`NodeTree.AllNodes`,
node_tree.go:200; `allNodeNames[(g.lastIndex+i)%allNodes]`, :519).

`check.replay` calls `skip_decision()` for a bind it does not compare,
without the pod, and `place(pod, node)` straight after it. How far such a
decision moves last_index depends on the pod and on the state, so
`skip_decision` only notes that a decision is pending, and `place` makes its
walk (tested and kept counts; no scores) before the placement touches the
state. Nothing is assumed about how many nodes fit.
"""
from __future__ import annotations

import numpy as np

from reference.default_provider import SHAPE_KINDS
from reference.default_provider import Reference as FullWalkReference

MIN_FEASIBLE_NODES = 100          # generic_scheduler.go:57
MIN_FEASIBLE_PERCENTAGE = 5       # generic_scheduler.go:62
DEFAULT_PERCENTAGE = 50           # api/types.go:40
# the walk tests nodes a block at a time; a block is never smaller than this
# (a block's size changes the work, never the answer)
MIN_BLOCK = 256


def num_to_find(n: int, percentage: int) -> int:
    """numFeasibleNodesToFind: how many fitting nodes end the walk."""
    if n < MIN_FEASIBLE_NODES or percentage >= 100:
        return n
    p = percentage
    if p <= 0:
        p = max(DEFAULT_PERCENTAGE - n // 125, MIN_FEASIBLE_PERCENTAGE)
    return max(n * p // 100, MIN_FEASIBLE_NODES)


class Reference(FullWalkReference):
    """Serial reference scheduler over a fixed node set, with the truncated
    walk. Arguments as `default_provider.Reference`; any percentage."""

    def __init__(self, nodes: list[dict], services: dict,
                 percentage_of_nodes_to_score: int = 0):
        super().__init__(nodes, services, 100)
        self.percentage = percentage_of_nodes_to_score
        self.num_to_find = num_to_find(self.n, self.percentage)
        self.last_index = 0
        self._pending = False

    # -- the walk ----------------------------------------------------------
    def _fits(self, pod: dict, idx: np.ndarray) -> np.ndarray:
        """PodFitsResources on the nodes `idx`."""
        return ((self.n_pods[idx] + 1 <= self.alloc_pods[idx])
                & (self.alloc_cpu[idx] >= pod["cpu"] + self.req_cpu[idx])
                & (self.alloc_mem[idx] >= pod["mem"] + self.req_mem[idx]))

    def _walk(self, pod: dict) -> np.ndarray:
        """One decision's findNodesThatFit: consumes an enumeration, moves
        last_index, and returns the kept nodes in walk order."""
        order, _rank = self.order.next_order()
        n, want = self.n, self.num_to_find
        entry = self.last_index
        kept: list[np.ndarray] = []
        found = tested = 0
        while tested < n and found < want:
            hi = min(n, tested + max(want - found, MIN_BLOCK))
            idx = order[(entry + np.arange(tested, hi)) % n]
            fit = self._fits(pod, idx)
            upto = np.cumsum(fit)
            if found + int(upto[-1]) >= want:
                # the walk stops at the node that fills the quota
                stop = int(np.searchsorted(upto, want - found)) + 1
                idx, fit = idx[:stop], fit[:stop]
                hi = tested + stop
            kept.append(idx[fit])
            found += int(np.count_nonzero(fit))
            tested = hi
        self.last_index = (entry + tested) % n
        return np.concatenate(kept) if kept else np.empty(0, dtype=np.int64)

    # -- decisions ---------------------------------------------------------
    def _no_pending(self) -> None:
        if self._pending:
            raise RuntimeError("skip_decision() was not followed by place()")

    def skip_decision(self) -> None:
        """A decision that is not compared: its walk waits for the pod,
        which the `place` that follows brings."""
        self._no_pending()
        self._pending = True

    def place(self, pod: dict, node_name: str) -> None:
        if self._pending:
            self._pending = False
            if self._walk(pod).size > 1:
                self.last_node_index += 1
        super().place(pod, node_name)

    def remove(self, pod: dict, node_name: str) -> None:
        self._no_pending()
        super().remove(pod, node_name)

    def decide(self, pod: dict) -> str | None:
        """The node the serial default scheduler binds `pod` to now, or None
        when no tested node fits. Advances last_index and last_node_index
        exactly as one scheduling cycle does."""
        self._no_pending()
        if pod["kind"] not in SHAPE_KINDS:
            raise ValueError(f"no reference for pod shape {pod['kind']!r}")
        kept = self._walk(pod)
        if kept.size == 0:
            return None
        if kept.size == 1:
            return self.names[int(kept[0])]
        mask = np.zeros(self.n, dtype=bool)
        mask[kept] = True
        total = (self._resource_scores(pod) + self._spread(pod, mask))[kept]
        # `kept` is in walk order from the entry last_index, so the tied
        # nodes are too; the counter picks among them
        best = np.flatnonzero(total == total.max())
        k = self.last_node_index % best.size
        self.last_node_index += 1
        return self.names[int(kept[best[k]])]
