"""The store's audit-record retention: `Store._trim_events_locked` evicts
the oldest EventRecords past `events_cap`, through each of its three
callers (`create`, `create_many`, `commit_wave`) and on both commit cores
(the trim sits above the core). The contract first, then the defect the
single ordered walk removed: a trim whose cost grew with the number of
records evicted before it."""
import gc
import time

import pytest

from kubernetes_tpu import native
from kubernetes_tpu.api.types import Container, EventRecord, Pod
from kubernetes_tpu.store.store import (
    ADDED, DELETED, EVENTS, EVENTS_TRIMMED, PODS, Store,
)

CAP = 8
CALLERS = ("create", "create_many", "commit_wave")
needs_native = pytest.mark.skipif(native.load("commitcore") is None,
                                  reason="commitcore did not build")
CORES = ["twin", pytest.param("native", marks=needs_native)]


def record(i: int, name: str = "") -> EventRecord:
    return EventRecord(name=name or f"e{i}", involved_kind="Pod",
                       involved_key=f"default/p{i}", type="Normal",
                       reason="Scheduled")


def add(store: Store, caller: str, ids) -> None:
    """Land one audit record per id, about pod `default/p<id>`, through
    `caller`: one call per record for `create`, one call for the whole
    batch otherwise (a batch larger than the cap evicts its own oldest)."""
    ids = list(ids)
    if caller == "create":
        for i in ids:
            store.create(EVENTS, record(i))
    elif caller == "create_many":
        store.create_many(EVENTS, [record(i) for i in ids], move=True)
    else:
        store.create_many(PODS, [
            Pod(name=f"p{i}", containers=(
                Container.make(name="c", requests={"cpu": 100}),))
            for i in ids])
        missing = store.commit_wave([(key, "n0") for key in keys(ids)],
                                    event_spec={"component": "cw"})
        assert missing == []
        store.fanout_wave()


def held(store: Store) -> list[str]:
    """The pods the bucket's records are about, in the bucket's order."""
    return [r.involved_key for r in store.list(EVENTS)[0]]


def pods_of(events, etype: str) -> list[str]:
    return [e.obj.involved_key for e in events if e.type == etype]


def keys(ids) -> list[str]:
    return [f"default/p{i}" for i in ids]


@pytest.mark.parametrize("core", CORES)
@pytest.mark.parametrize("caller", CALLERS)
class TestTrimContract:
    def test_newest_cap_records_stay_and_every_eviction_is_announced(
            self, caller, core):
        store = Store(events_cap=CAP, commit_core=core,
                      debug_integrity=True)
        assert store.core_impl == core
        watch = store.watch(EVENTS)
        before = EVENTS_TRIMMED.value
        add(store, caller, range(10))       # two over, in one batch or ten
        assert store.count(EVENTS) == CAP   # exact after every write
        add(store, caller, range(10, 20))   # ten over
        assert store.count(EVENTS) == CAP
        assert held(store) == keys(range(12, 20))
        events = watch.drain()
        assert pods_of(events, ADDED) == keys(range(20))
        assert pods_of(events, DELETED) == keys(range(12))   # oldest first
        rvs = [e.resource_version for e in events]
        assert rvs == sorted(set(rvs))      # each entry its own next_rv()
        assert EVENTS_TRIMMED.value - before == 12
        live = {f"default/{r.name}" for r in store.list(EVENTS)[0]}
        assert {k for kind, k in store._integrity if kind == EVENTS} <= live
        store.check_integrity()

    def test_hand_deleted_and_recreated_keys_follow_the_dicts_order(
            self, caller, core):
        """A record deleted by hand leaves a hole the trim must step over;
        one created again under its old key is the NEWEST record, whatever
        its name says. Eviction follows the dict's insertion order."""
        store = Store(events_cap=CAP, commit_core=core,
                      debug_integrity=True)
        watch = store.watch(EVENTS)
        before = EVENTS_TRIMMED.value
        add(store, caller, range(10))               # evicts 0, 1
        first = watch.drain()
        assert pods_of(first, DELETED) == keys([0, 1])
        by_pod = {e.obj.involved_key: e.obj.name for e in first
                  if e.type == ADDED}
        store.delete(EVENTS, "default/" + by_pod["default/p4"])
        store.delete(EVENTS, "default/" + by_pod["default/p3"])
        # under their old keys: 0 was evicted, 3 deleted by hand
        store.create(EVENTS, record(0, by_pod["default/p0"]))
        store.create(EVENTS, record(3, by_pod["default/p3"]))
        assert held(store) == keys([2, 5, 6, 7, 8, 9, 0, 3])
        add(store, caller, range(10, 14))           # four over
        assert store.count(EVENTS) == CAP
        assert held(store) == keys([8, 9, 0, 3, 10, 11, 12, 13])
        events = watch.drain()
        assert pods_of(events, DELETED) == keys([4, 3, 2, 5, 6, 7])
        gone = [(e.obj.name, e.obj.resource_version)
                for e in first + events if e.type == DELETED]
        assert len(set(gone)) == len(gone) == 8     # nothing evicted twice
        assert EVENTS_TRIMMED.value - before == 6   # hand deletes are not
        store.check_integrity()


@pytest.mark.parametrize("cap", [None, 0])
def test_no_cap_no_trim(cap):
    store = Store(events_cap=cap)
    before = EVENTS_TRIMMED.value
    add(store, "create_many", range(3 * CAP))
    assert store.count(EVENTS) == 3 * CAP
    assert EVENTS_TRIMMED.value == before


@needs_native    # the bound is the native core's: the twin's log append is Python
def test_trim_cost_does_not_grow_with_records_evicted_before():
    """The defect, at the real cap. A dict keeps popped entries as
    tombstones at the head of its entry array until its next resize, and
    `next(iter(bucket))` per evicted record walked all of them: with 65,536
    live records the head holds up to ~109,000, and a wave's trim climbed
    from its foot to a peak over ~26 waves of 4096 before the resize let it
    fall again. Here: fill to the cap, then 120,000 more records in waves
    of 4096, past one whole sawtooth; CPU seconds of the thread in
    `_trim_events_locked` per wave (this sandbox's CPUs, native core, the
    suite's integrity mode on; three runs of the parent, ten of the fix,
    five of them beside eight busy processes):

    - parent 31a3ccd: 0.0066-0.0073 s at the foot, 0.231-0.301 s at the
      peak, slowest over fastest 34-46
    - the single ordered walk: fastest 0.0025-0.0037 s, slowest
      0.0054-0.0077 s, slowest over fastest 1.6-2.7

    The ratio is the gate, whatever the machine. The absolute bound sits
    in the middle of the 30x between the fix's slowest and the parent's
    peak, about five times from each, for a box where both scale alike."""
    cap, wave, more = 1 << 16, 4096, 120_000
    store = Store(events_cap=cap, commit_core="native")
    trim, seconds = store._trim_events_locked, []

    def timed_trim():
        t0 = time.thread_time()
        trim()
        seconds.append(time.thread_time() - t0)

    def fill(lo, hi):
        store.create_many(EVENTS, [record(i) for i in range(lo, hi)],
                          move=True)

    gc.disable()    # a full collection over 180,000 records is not the trim
    try:
        fill(0, cap)
        store._trim_events_locked = timed_trim
        before = EVENTS_TRIMMED.value
        for lo in range(cap, cap + more, wave):
            fill(lo, lo + wave)
            assert store.count(EVENTS) == cap
    finally:
        gc.enable()
    assert len(seconds) == -(-more // wave)
    assert EVENTS_TRIMMED.value - before == len(seconds) * wave
    assert max(seconds) < 10 * min(seconds)
    assert max(seconds) < 0.045
