"""Pod-row cache: what a drain pass reads about a pod, stored at delivery.

A drain pass asks one thing about every pod it pops: its class signature,
INTERNED, so that the pass's class decision, the window's uniformity test
and the per-signature memos of the burst drivers compare by identity. The
signature is a pure function of the pod's SPEC, which is immutable between
resourceVersions, so the cache derives it ONCE, at informer delivery, a run
of pods at a time: one batched signature call, one interning pass, a slot a
uid, one assignment into the id column (and, in tensor mode, the pod's
profile index beside it: the one column a window gathers). Nothing else is
derived at delivery. The feature row (`encode_row`) is still this module's
to define, and `lookup_row` / `gather` answer with it, derived from the pod
they are handed.

Slots are keyed by (uid, resourceVersion): an update-in-place (same uid,
new rv) overwrites its slot, a delete frees it, and a stale or missing slot
falls back to a fresh derivation (counted, never wrong). The bit-identity
contract (what the cache answers equals a fresh `encode_row` /
`pod_class_signature`, field for field) keeps burst decisions oracle-parity
by construction; tests/test_pod_rows.py fuzz-pins it, and the serve parity
sweep drives it with mid-window pod updates.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from kubernetes_tpu import native, obs
from kubernetes_tpu.api.types import (
    Pod, get_container_ports, get_pod_nonzero_requests, get_resource_request,
    has_pod_affinity_terms,
)

ROW_CACHE_HITS = obs.counter(
    "pod_row_cache_hits_total",
    "Pod-row cache lookups by outcome: hit (slot live at the pod's "
    "(uid, resourceVersion)), miss (pod never delivered through the "
    "informer: derived fresh on the spot), stale (the slot's "
    "resourceVersion lags the pod's: derived fresh).", ("outcome",))
ROW_CACHE_ENCODES = obs.counter(
    "pod_row_cache_encodes_total",
    "Pods the pod-row cache derived something for, by what: signature (a "
    "pod delivered through insert / insert_many, which stores its interned "
    "class signature and nothing else of its spec; booked once a run), "
    "columns (a pod whose encode_row was derived because lookup_row or "
    "gather asked for a field of it).",
    ("derived",))
ROW_CACHE_ROWS = obs.gauge(
    "pod_row_cache_rows",
    "Live rows in the most recently constructed pod-row cache.")


def pod_class_signature(pod: Pod) -> tuple:
    """Spec fields that determine a pod's device features against a fixed
    snapshot — equal signatures imply identical encoder output. THE
    canonical definition (TPUScheduler._class_signature and the native
    commitcore.class_signatures batch are its twins; the commit-core
    parity tests pin all three element-for-element)."""
    return (pod.namespace, tuple(sorted(pod.labels.items())),
            tuple(sorted(pod.node_selector.items())), pod.affinity,
            pod.tolerations, pod.node_name, pod.containers,
            pod.init_containers)


def class_signatures(pods) -> list:
    """`pod_class_signature` of a run of pods: ONE native call
    (commitcore.class_signatures) where the extension is built, the
    per-pod function otherwise. The tuples are equal element for element
    either way (pinned by the commit-core parity tests)."""
    mod = native.load("commitcore")
    if mod is not None:
        return mod.class_signatures(pods)
    return [pod_class_signature(p) for p in pods]


#: int64 fields of a row, in row order; profile_id (round 19) is the pod's
#: scheduling-profile index: the one column the cache keeps (tensor mode
#: gathers it a window, so mixed-tenant windows select their weight-tensor
#: rows without touching the pod specs)
_I64_FIELDS = ("req_cpu", "req_mem", "req_eph", "nz_cpu", "nz_mem",
               "upd_cpu", "upd_mem", "upd_eph", "priority", "profile_id")
#: bool fields of a row
_BOOL_FIELDS = ("has_request", "has_scalar", "has_aff_terms", "has_ports",
                "has_volumes")


def _profile_id(pod: Pod, profile_fn) -> int:
    """`profile_fn(scheduler_name) -> Optional[int]` (profiles.ProfileSet
    .index_of); None/unset resolves to 0, the default profile row."""
    pid = profile_fn(pod.scheduler_name) if profile_fn is not None else None
    return 0 if pid is None else int(pid)


def encode_row(pod: Pod, profile_fn=None) -> dict:
    """THE per-pod feature row: every spec-derived scalar of a pod, in one
    place; `lookup_row` and `gather` answer with exactly this, and the
    bit-identity fuzz compares them to it. Scalar (extended-resource)
    requests are kept as sorted name->quantity items, NOT vocab-aligned
    arrays: the scalar vocab belongs to the node snapshot, so alignment
    happens at the window (cheap — scalar pods are rare) while the row
    stays snapshot-independent."""
    from kubernetes_tpu.cache.node_info import calculate_resource
    req = get_resource_request(pod)
    upd = calculate_resource(pod)
    nz_cpu, nz_mem = get_pod_nonzero_requests(pod)
    return {
        "req_cpu": req.milli_cpu, "req_mem": req.memory,
        "req_eph": req.ephemeral_storage,
        "nz_cpu": nz_cpu, "nz_mem": nz_mem,
        "upd_cpu": upd.milli_cpu, "upd_mem": upd.memory,
        "upd_eph": upd.ephemeral_storage,
        "priority": pod.priority,
        "profile_id": _profile_id(pod, profile_fn),
        "has_request": bool(req.milli_cpu or req.memory
                            or req.ephemeral_storage or req.scalar),
        "has_scalar": bool(req.scalar or upd.scalar),
        "has_aff_terms": has_pod_affinity_terms(pod),
        "has_ports": bool(get_container_ports(pod)),
        "has_volumes": bool(pod.volumes),
        "req_scalar_items": tuple(sorted(req.scalar.items())),
        "upd_scalar_items": tuple(sorted(upd.scalar.items())),
        "signature": pod_class_signature(pod),
    }


class PodRowCache:
    """Interned class signatures (and profile indices) of pending pods,
    keyed by (uid, resourceVersion).

    Filled at informer delivery (insert/insert_many on the pending-pod
    handlers), overwritten on update (same uid, new rv), freed on delete.
    `signatures` serves the drain pass and `gather(pods, ("profile_id",))`
    tensor mode's window from what delivery stored; `lookup_row` and
    `gather` of any other field derive `encode_row` from the pod in hand.
    A miss or stale slot falls back to a fresh derivation — identical
    values by the bit-identity contract, so the cache can only be fast,
    never wrong.

    Capacity-bounded: past `capacity` live rows, the oldest insertion is
    evicted (the window falls back to fresh derivations for it — the same
    degradation as a miss)."""

    def __init__(self, capacity: int = 1 << 17, profile_fn=None):
        self.capacity = int(capacity)
        #: scheduling-profile resolver (profiles.ProfileSet.index_of);
        #: applied at delivery AND wherever a row is derived, so the
        #: bit-identity contract holds column-for-column; without one the
        #: profile_id column stays the zeros it is made as
        self.profile_fn = profile_fn
        cap0 = 1024
        self._cap = cap0
        self._profile_id = np.zeros(cap0, dtype=np.int64)
        self._sig_id = np.full(cap0, -1, dtype=np.int32)
        # signature interning: equal sigs share ONE tuple object, so the
        # window's uniformity check is a pointer compare
        self._sig_of: dict = {}          # sig tuple -> id
        self._sigs: list = []            # id -> interned sig tuple
        # slot map: uid -> (slot, rv); insertion-ordered for the capacity
        # eviction (dict preserves insertion order)
        self._slot_of: dict[str, tuple[int, int]] = {}
        self._free: list[int] = list(range(cap0 - 1, -1, -1))
        ROW_CACHE_ROWS.set_function(lambda: float(len(self._slot_of)))

    def __len__(self) -> int:
        return len(self._slot_of)

    # -- maintenance (informer delivery) -------------------------------------
    def _grow(self) -> None:
        new_cap = self._cap * 2
        pid = np.zeros(new_cap, dtype=np.int64)
        pid[: self._cap] = self._profile_id
        self._profile_id = pid
        sid = np.full(new_cap, -1, dtype=np.int32)
        sid[: self._cap] = self._sig_id
        self._sig_id = sid
        self._free.extend(range(new_cap - 1, self._cap - 1, -1))
        self._cap = new_cap

    def _intern_sig(self, sig: tuple) -> int:
        sid = self._sig_of.get(sig)
        if sid is None:
            sid = self._sig_of[sig] = len(self._sigs)
            self._sigs.append(sig)
        return sid

    def insert(self, pod: Pod) -> None:
        """Deliver one pod: `insert_many`'s run of one."""
        self.insert_many((pod,))

    def insert_many(self, pods) -> None:
        """Deliver a run of pods at their current (uid, resourceVersion) —
        called at informer delivery (adds and updates both land here; an
        existing slot for a uid is overwritten in place). The run's
        signatures come from ONE batched call and go into the id column
        with one assignment; nothing else of a pod's spec is derived."""
        if not pods:
            return
        # interning first: an unhashable spec raises here, before any slot
        # of the run is taken
        sigs = class_signatures(pods)
        ids = list(map(self._sig_of.get, sigs))
        if None in ids:
            ids = [self._intern_sig(sig) for sig in sigs]
        slot_of, free, profile_fn = self._slot_of, self._free, self.profile_fn
        # the run's writes by slot: a slot written twice in one run (a uid
        # delivered twice, a slot freed by eviction and retaken) keeps its
        # last value, as a pod-by-pod delivery would
        sid_at: dict[int, int] = {}
        for pod, sid in zip(pods, ids):
            uid = pod.uid
            existing = slot_of.pop(uid, None)
            if existing is not None:
                slot = existing[0]
            else:
                if len(slot_of) >= self.capacity:
                    # bound the table: evict the oldest insertion (it decays
                    # to the miss path, never to a wrong row)
                    oldest = next(iter(slot_of))
                    sid_at.pop(slot_of[oldest][0], None)
                    self.invalidate_uid(oldest)
                if not free:
                    self._grow()
                slot = free.pop()
            # (re-)append so eviction order stays oldest-write-first
            slot_of[uid] = (slot, pod.resource_version)
            sid_at[slot] = sid
            if profile_fn is not None:
                self._profile_id[slot] = _profile_id(pod, profile_fn)
        self._sig_id[list(sid_at)] = list(sid_at.values())
        ROW_CACHE_ENCODES.labels("signature").inc(len(pods))

    def invalidate_uid(self, uid: str) -> None:
        got = self._slot_of.pop(uid, None)
        if got is not None:
            slot = got[0]
            self._sig_id[slot] = -1
            self._free.append(slot)

    def invalidate(self, pod: Pod) -> None:
        """Delete-side invalidation (the informer's on_delete)."""
        self.invalidate_uid(pod.uid)

    def invalidate_many(self, pods: list) -> None:
        """Batched delete-side invalidation (round 23): one call per
        informer delete run — the freed slots land in one pass."""
        for pod in pods:
            self.invalidate_uid(pod.uid)

    # -- drain-pass reads ------------------------------------------------------
    def _slot(self, pod: Pod) -> int:
        """Slot for `pod` at its exact resourceVersion, or -1 (miss /
        stale). Books the outcome counter."""
        got = self._slot_of.get(pod.uid)
        if got is None:
            ROW_CACHE_HITS.labels("miss").inc()
            return -1
        slot, rv = got
        if rv != pod.resource_version:
            ROW_CACHE_HITS.labels("stale").inc()
            return -1
        ROW_CACHE_HITS.labels("hit").inc()
        return slot

    def signatures(self, pods: list) -> list:
        """Per-pod class signatures, interned: cache hits gather the
        shared tuple by id (equal sigs are the SAME object — the window's
        uniformity check becomes identity); misses derive fresh through
        the canonical function and intern the result, so the returned
        list is bit-identical to a per-pod `pod_class_signature` pass."""
        slot_of = self._slot_of
        slots = []
        fresh = []      # positions the cache cannot serve (miss / stale)
        for i, pod in enumerate(pods):
            got = slot_of.get(pod.uid)
            if got is not None and got[1] == pod.resource_version:
                slots.append(got[0])
            else:
                ROW_CACHE_HITS.labels(
                    "miss" if got is None else "stale").inc()
                slots.append(0)
                fresh.append(i)
        # one np.take and one booking for the window's hits (a window is
        # 10,000 pods a pass in the backlog cells, and both the shell's
        # class decision and the encode prologue come through here)
        ids = self._sig_id[slots].tolist()
        for i in fresh:
            ids[i] = self._intern_sig(pod_class_signature(pods[i]))
        if len(fresh) < len(pods):
            ROW_CACHE_HITS.labels("hit").inc(len(pods) - len(fresh))
        sigs = self._sigs
        return [sigs[i] for i in ids]

    def _derive(self, pods: list) -> list:
        rows = [encode_row(pod, self.profile_fn) for pod in pods]
        ROW_CACHE_ENCODES.labels("columns").inc(len(rows))
        return rows

    def lookup_row(self, pod: Pod) -> dict:
        """One pod's row: `encode_row` of the pod in hand, with the
        INTERNED signature object where the pod's slot is live (identical
        values either way; the derivation is the contract)."""
        slot = self._slot(pod)
        row, = self._derive([pod])
        if slot >= 0:
            row["signature"] = self._sigs[self._sig_id[slot]]
        return row

    def gather(self, pods: list, fields: tuple = _BOOL_FIELDS) -> Optional[dict]:
        """Columnar read for a window's pods: `profile_id`, the column
        delivery stores, by ONE np.take; any other field derived from the
        pods in hand. Returns None when any pod misses (the caller falls
        back to its per-pod path — correctness never depends on the
        cache)."""
        slots = np.empty(len(pods), dtype=np.int64)
        slot_of = self._slot_of
        for i, pod in enumerate(pods):
            got = slot_of.get(pod.uid)
            if got is None or got[1] != pod.resource_version:
                ROW_CACHE_HITS.labels(
                    "miss" if got is None else "stale").inc()
                return None
            slots[i] = got[0]
        ROW_CACHE_HITS.labels("hit").inc(len(pods))
        out = {}
        rows = None
        for f in fields:
            if f == "profile_id":
                out[f] = np.take(self._profile_id, slots)
                continue
            if rows is None:
                rows = self._derive(pods)
            out[f] = np.fromiter(
                (row[f] for row in rows), count=len(rows),
                dtype=np.int64 if f in _I64_FIELDS else bool)
        return out

    def debug_state(self) -> dict:
        return {"rows": len(self._slot_of), "capacity": self.capacity,
                "signatures_interned": len(self._sigs)}
