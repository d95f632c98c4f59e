"""A cluster filled to its last slot and three pods beyond, under a truncated
walk on a rotating order: what `podcap-5000n-150k.backlog-9900-fill` runs on
the chip, and the half of it no cell can hold (a pod that stays pending).

The program is driven through its normal path (Store.create_many, the
informer pump, Scheduler.schedule_burst, the client's watch) and held, decision
by decision, to the benchmark's plain reference
(`benchmark/reference/default_provider_adaptive.py`) and to the program's
serial oracle (the same shell with the device off): walks that pass their
quota because nodes on their way are full, decisions that test every node and
keep fewer than the quota (`last_index` moves by n), decisions that keep one
node (`last_node_index` does not rise), and a decision that keeps none in the
middle of a launch (the pods stay pending, nothing after it is committed from
that launch, the walk counters rewind to the committed prefix). Then a delete
frees slots and the pending pods bind. `tpu_walk_ended_total` has to read what
the reference's own walks give. CPU backend; decisions and counts only.
"""
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import check, cluster  # noqa: E402
from lib.client import BIND, DELETE, Client  # noqa: E402
from lib.traffic import PodFactory  # noqa: E402

GI, MI = 1024 ** 3, 1024 ** 2
NODES = 250                     # zones of 84/83/83: the tree's order rotates
QUOTA = 120                     # num_to_find at 250 nodes and percentage 0
PER_NODE = 6                    # resident pods a node, 100m / 500Mi each
SLOTS = 2 * NODES
BEYOND = 3

# what makes a node full after two of the pass's pods
FULLNESS = {
    # its pod slots: 8 a node, three sizes of which any two fit in 3400m
    "pod-count": ({"cpu_milli": 4000, "memory_bytes": 32 * GI,
                   "pods": PER_NODE + 2},
                  [(0.5, 100, 128 * MI), (0.3, 250, 512 * MI),
                   (0.2, 500, GI)]),
    # its CPU: 110 slots a node and 1000m free of 1600m, pods of 500m, so a
    # node's second pod meets PodFitsResources' equality case
    "cpu": ({"cpu_milli": PER_NODE * 100 + 1000, "memory_bytes": 32 * GI,
             "pods": 110},
            [(1.0, 500, GI)]),
}


def config(allocatable):
    return {"nodes": {"count": NODES, "zones": 3, "region": "r1",
                      "allocatable": allocatable},
            "resident": {"pods_per_node": PER_NODE, "services": 5,
                         "requests": {"cpu_milli": 100,
                                      "memory_bytes": 500 * MI}},
            "scheduler": {"percentage_of_nodes_to_score": 0},
            "store": {"watch_log_size": 1 << 16},
            "reference": "default_provider_adaptive"}


def traffic(sizes):
    return {"pod_shapes": [{"kind": "plain", "share": share,
                            "requests": {"cpu_milli": cpu,
                                         "memory_bytes": mem}}
                           for share, cpu, mem in sizes],
            "service_choice": None}


class Fill:
    """One cluster, one scheduler on a clock the test moves, the benchmark's
    client. `submit` creates pods; `drain` drives the scheduler as
    `lib.drive.drain_scheduler` does, in launches of at most `max_pods`."""

    def __init__(self, fullness, seed, tpu=True):
        from kubernetes_tpu.apis.config import SchedulerConfiguration
        from kubernetes_tpu.factory import create_scheduler
        from kubernetes_tpu.utils.clock import FakeClock
        allocatable, sizes = FULLNESS[fullness]
        self.cfg = config(allocatable)
        self.store, self.rows, self.residents, self.services = \
            cluster.build(self.cfg, seed)
        conf = SchedulerConfiguration(percentage_of_nodes_to_score=0)
        conf.feature_gates = {**conf.feature_gates, "TPUScoring": tpu}
        self.clock = FakeClock()
        self.sched = create_scheduler(self.store, conf, clock=self.clock,
                                      **({"mesh": None} if tpu else {}))
        self.sched.sync()
        self.client = Client(self.store, tracing=False)
        self.factory = PodFactory(traffic(sizes), len(self.services), seed)
        self.tpu = tpu

    def submit(self, n_pods, tag):
        made = [self.factory.make(f"{tag}-{j}") for j in range(n_pods)]
        ids = [self.client.register(p, d) for p, d in made]
        self.client.create([p for p, _d in made])
        return ids

    def drain(self, max_pods=256):
        self.sched.pump()
        if self.tpu:
            while self.sched.schedule_burst(max_pods=max_pods):
                pass
        else:
            while self.sched.schedule_one(timeout=0.0):
                pass
            self.sched.wait_for_binds()
        self.sched.pump()
        self.client.drain()

    def delete(self, ids):
        self.client.delete([self.client.keys[i] for i in ids])
        self.sched.pump()
        self.client.drain()

    def bound(self, ids):
        return [i for i in ids if self.client.bind_seen_at[i] > 0.0]

    def binds(self):
        c = self.client
        return [(c.keys[c.log_pod[k]], c.log_node[k])
                for k in range(len(c.log_kind)) if c.log_kind[k] == BIND]

    def run(self):
        """The fill: every slot and three pods beyond in one pass, drained in
        launches of 256, so the last launch holds the last slots and the
        three; then three of the pass's pods are deleted, the back-off runs
        out and the pending pods take the freed slots."""
        ids = self.submit(SLOTS + BEYOND, "fill")
        self.drain()
        self.after_fill = (self.sched.algorithm.last_index,
                           self.sched.algorithm.last_node_index)
        self.fill_binds = len(self.binds())
        pending = [i for i in ids if i not in set(self.bound(ids))]
        self.delete(self.bound(ids)[100:100 + BEYOND])
        self.clock.step(30.0)
        self.drain()
        return ids, pending


class Walks:
    """The reference, noting how each of its walks ended."""

    def __init__(self, fill):
        self.ref = check.make_reference(fill.cfg, fill.rows, fill.residents,
                                        fill.services)
        self.ended = []          # (nodes tested, nodes kept) a decision
        self.placed = {}         # pod id -> the node it is bound to
        walk = self.ref._walk

        def noting(pod):
            entry = self.ref.last_index
            kept = walk(pod)
            moved = (self.ref.last_index - entry) % self.ref.n
            self.ended.append((moved or self.ref.n, int(kept.size)))
            return kept
        self.ref._walk = noting

    def replay(self, client, lo=0, hi=None):
        """Every bind of log positions [lo, hi) decided and compared, every
        delete applied. Returns the binds that differ."""
        c = client
        wrong = []
        for k in range(lo, len(c.log_kind) if hi is None else hi):
            pid = c.log_pod[k]
            if c.log_kind[k] == BIND:
                want = self.ref.decide(c.descs[pid])
                if want != c.log_node[k]:
                    wrong.append((c.keys[pid], c.log_node[k], want))
                self.ref.place(c.descs[pid], c.log_node[k])
                self.placed[pid] = c.log_node[k]
            elif c.log_kind[k] == DELETE:
                self.ref.remove(c.descs[pid], self.placed.pop(pid))
        return wrong


def ended_counts():
    from kubernetes_tpu.core.tpu_scheduler import WALK_ENDED
    return {by: WALK_ENDED.labels(by).value
            for by in ("quota", "nodes", "none")}


@pytest.mark.parametrize("fullness", list(FULLNESS))
@pytest.mark.parametrize("seed", [2 ** 31 + 54, 7])
def test_fill_to_the_last_slot_and_three_beyond(fullness, seed):
    from kubernetes_tpu.core.tpu_scheduler import ORACLE_FALLBACKS
    fallbacks0 = {k: c.value for k, c in ORACLE_FALLBACKS._children.items()}
    ended0 = ended_counts()
    fill = Fill(fullness, seed)
    ids, pending = fill.run()
    client = fill.client
    # the fill bound every slot and left the three pods behind
    assert fill.fill_binds == SLOTS and len(pending) == BEYOND
    # ... which took the freed slots once the queue had moved them
    assert len(fill.bound(ids)) == SLOTS + BEYOND
    assert len(fill.binds()) == SLOTS + BEYOND

    # the benchmark's reference, decision by decision, through the fill
    walks = Walks(fill)
    ref = walks.ref
    assert ref.num_to_find == QUOTA < ref.n == NODES
    fill_end = [k for k in range(len(client.log_kind))
                if client.log_kind[k] == BIND][SLOTS - 1] + 1
    assert walks.replay(client, 0, fill_end) == []
    assert (ref.last_index, ref.last_node_index) == fill.after_fill
    filled = list(walks.ended)
    assert len(filled) == SLOTS
    assert max(int(x) for x in ref.n_pods) <= fill.cfg["nodes"][
        "allocatable"]["pods"]
    # the regimes the fill has to meet: walks that stop at the quota, at its
    # first positions and far past them; walks over all n that keep fewer;
    # and one node kept, where the tie counter stays
    assert any(t == QUOTA == k for t, k in filled)
    assert any(QUOTA < t and k == QUOTA for t, k in filled)
    short = [(t, k) for t, k in filled if k < QUOTA]
    assert short and all(t == NODES for t, _k in short)
    assert filled[-1][1] == 1
    # the three beyond: the reference finds no node either, each walk tests
    # every node, and neither counter moves (n mod n; no tie to break)
    state = (ref.last_index, ref.last_node_index)
    for i in pending:
        assert ref.decide(client.descs[i]) is None
    assert walks.ended[SLOTS:] == [(NODES, 0)] * BEYOND
    assert (ref.last_index, ref.last_node_index) == state

    # the counter: the launches of the fill decided 500 pods and met the
    # first of the three; the other two never became decisions of a launch
    # (the shell ran the tail of that launch through its failure path)
    want = {"quota": sum(1 for _t, k in filled if k >= QUOTA),
            "nodes": sum(1 for _t, k in filled if 0 < k < QUOTA),
            "none": 1}
    assert want["quota"] + want["nodes"] == SLOTS and want["nodes"] > 0
    # ... and after the delete one launch held the three, each of which
    # found the three freed nodes or fewer
    del walks.ended[SLOTS:]
    assert walks.replay(client, fill_end) == []
    after = walks.ended[SLOTS:]
    assert len(after) == BEYOND and all(0 < k < QUOTA for _t, k in after)
    want["nodes"] += BEYOND
    moved = {by: v - ended0[by] for by, v in ended_counts().items()}
    assert moved == want
    algo = fill.sched.algorithm
    assert (algo.last_index, algo.last_node_index) == \
        (ref.last_index, ref.last_node_index)
    # no launch was refused and no decision left the device path for a fault
    assert {k: c.value for k, c in ORACLE_FALLBACKS._children.items()
            if c.value != fallbacks0.get(k, 0) and k[0] in (
                "device-fault", "circuit-open")} == {}

    # the program's serial oracle, bind for bind
    serial = Fill(fullness, seed, tpu=False)
    serial.run()
    assert serial.binds() == fill.binds()
    assert serial.after_fill == fill.after_fill


def test_nothing_after_the_first_failure_is_committed_from_its_launch():
    """One launch holds the last two slots and five pods: the two bind from
    the launch, the third finds no node, and the launch commits nothing
    after it: its walk counters stand at the committed prefix."""
    fill = Fill("pod-count", 11)
    ids = fill.submit(SLOTS - 2, "most")
    fill.drain()
    assert len(fill.bound(ids)) == SLOTS - 2
    from kubernetes_tpu.core import tpu_scheduler as T
    returned = []
    burst = fill.sched.algorithm.schedule_burst

    def spy(*a, **kw):
        returned.append(burst(*a, **kw))
        return returned[-1]
    fill.sched.algorithm.schedule_burst = spy
    ended0 = ended_counts()
    folds0 = T.DISCARDED_FOLDS.value
    last = fill.submit(5, "last")
    fill.drain()
    assert len(returned) == 1
    hosts = returned[0]
    assert [h is not None for h in hosts] == [True, True, False, False, False]
    assert len(fill.bound(last)) == 2
    assert fill.bound(last) == last[:2]
    moved = {by: v - ended0[by] for by, v in ended_counts().items()}
    assert moved == {"quota": 0, "nodes": 2, "none": 1}
    assert T.DISCARDED_FOLDS.value == folds0 + 1
    # ... and the launch's `burst.fetch` span says the same of its block
    from kubernetes_tpu.obs import trace
    fetches = [e for e in trace.events() if e["name"] == "burst.fetch"]
    assert {by: fetches[-1]["args"][by] for by in moved} == moved
    walks = Walks(fill)
    assert walks.replay(fill.client) == []
    algo = fill.sched.algorithm
    assert (algo.last_index, algo.last_node_index) == \
        (walks.ref.last_index, walks.ref.last_node_index)
    assert walks.ended[-2:] == [(NODES, 2), (NODES, 1)]
