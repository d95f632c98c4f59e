"""Which Services and ReplicaSets select a pod, asked of an index.

`priorities.get_selectors` is the definition: a walk over every Service and
ReplicaSet of the cluster, each tested against the pod. The burst path asks
that question once a distinct class signature a drain pass in the shell
(`Scheduler._burst_class`) and once more an encode
(`PodEncoder._encode_scores`), so on a cluster of thousands of Services the
walk is most of what a small window costs. `SelectorIndex.select` gives
`get_selectors`' answer, member for member and in its order, for the cost
of the pod's own labels: a selector that selects a pod has every pair among
the pod's labels, so filing each selector under ONE of its pairs finds every
candidate, and each candidate is then tested in full.

The index holds the lists it was built from and nothing newer:
`LiveSelectorIndex` rebuilds it when an informer's change count has moved.
The serial oracle's priority functions keep calling `get_selectors`.
"""
from operator import itemgetter

from kubernetes_tpu import obs
from kubernetes_tpu.oracle.priorities import _selector_matches

SELECTOR_INDEX_BUILDS = obs.counter(
    "tpu_selector_index_builds_total",
    "Selector indexes built over the Service and ReplicaSet lists "
    "(SelectorIndex): one count a build. A scheduler's index is rebuilt "
    "when either informer's change count has moved, so between two drain "
    "passes with no Service or ReplicaSet event this does not move.")

_POSITION = itemgetter(0)


def _filing_pair(pairs):
    """The pair a selector is filed under: its smallest (a pod that the
    selector selects carries it), or None when it has none a pod can
    carry, and every lookup of its namespace has to test it."""
    return min((kv for kv in pairs if kv[1] is not None), default=None)


class SelectorIndex:
    """`get_selectors(pod, services, replicasets)` for the two lists given,
    by lookup. Entries are `(position, selector)`: a Service's selector
    dict or a ReplicaSet's `LabelSelector`, at its place in the walk
    (Services in list order, then ReplicaSets)."""

    def __init__(self, services=(), replicasets=()):
        SELECTOR_INDEX_BUILDS.inc()
        # namespace -> ({(key, value): entries}, entries every lookup tests)
        self._by_ns: dict = {}
        pos = 0
        for svc in services:
            if svc.selector:
                self._file(svc.namespace, _filing_pair(svc.selector.items()),
                           (pos, svc.selector))
            pos += 1
        for rs in replicasets:
            if rs.selector is not None:
                # expressions only, or the empty selector (matches
                # everything): no pair to file it under
                self._file(rs.namespace,
                           _filing_pair(rs.selector.match_labels),
                           (pos, rs.selector))
            pos += 1

    def _file(self, namespace, pair, entry) -> None:
        by_pair, always = self._by_ns.setdefault(namespace, ({}, []))
        if pair is None:
            always.append(entry)
        else:
            by_pair.setdefault(pair, []).append(entry)

    def select(self, pod) -> tuple[list, int]:
        """(`get_selectors`' answer for `pod`, the candidates tested in
        full to get it)."""
        filed = self._by_ns.get(pod.namespace)
        if filed is None:
            return [], 0
        by_pair, always = filed
        labels = pod.labels
        candidates = list(always)
        for pair in labels.items():
            candidates += by_pair.get(pair, ())
        found = [e for e in candidates if _selector_matches(e[1], labels)]
        if len(found) > 1:
            found.sort(key=_POSITION)
        return ([dict(s) if isinstance(s, dict) else s for _pos, s in found],
                len(candidates))


class LiveSelectorIndex:
    """The index over two informers' caches, as a callable beside their
    `list`: the same `SelectorIndex` for as long as neither informer's
    change count has moved, a new one built from the lists of the moment
    when one has. A Service created, modified or deleted between two drain
    passes is in the next pass's answers."""

    def __init__(self, services, replicasets):
        self._services = services
        self._replicasets = replicasets
        self._built_at = None
        self._index = None

    def __call__(self) -> SelectorIndex:
        if self._built_at != (self._services.changes,
                              self._replicasets.changes):
            s_at, services = self._services.versioned_list()
            r_at, replicasets = self._replicasets.versioned_list()
            self._index = SelectorIndex(services, replicasets)
            self._built_at = (s_at, r_at)
        return self._index
