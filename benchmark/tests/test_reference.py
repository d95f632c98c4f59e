"""The plain reference against the program's own serial oracle, pod for pod,
on small clusters: even and uneven zones, empty and resident-filled nodes,
plain and spread pods, nodes that fill up. (The reference imports nothing of
the program; this test does, to compare the two.)"""
import random

import pytest

from reference.default_provider import NodeOrder, Reference

GI, MI = 1024 ** 3, 1024 ** 2


def test_node_order_follows_the_tree():
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.cache.node_tree import NodeTree
    for sizes in ([2, 1, 1], [1, 2], [3, 3, 3], [1, 3, 2], [4]):
        zones = [f"z{z}" for z, n in enumerate(sizes) for _ in range(n)]
        random.Random(1).shuffle(zones)
        tree = NodeTree()
        for i, z in enumerate(zones):
            tree.add_node(Node(name=f"n{i}", labels={
                "failure-domain.beta.kubernetes.io/zone": z}))
        order = NodeOrder(zones)
        for _cycle in range(7):
            want = [tree.next() for _ in zones]
            assert [f"n{i}" for i in order.next_order()[0]] == want


@pytest.mark.parametrize("n,per,svc,batch,cpu", [
    (10, 0, 0, 30, 100), (11, 0, 0, 40, 100), (7, 0, 0, 60, 1000),
    (50, 5, 4, 60, 100), (101, 6, 7, 120, 100)])
def test_reference_equals_the_serial_oracle(n, per, svc, batch, cpu):
    import kubernetes_tpu.ops  # noqa: F401
    from kubernetes_tpu.api.types import Container, Node, Pod, Service
    from kubernetes_tpu.apis.config import SchedulerConfiguration
    from kubernetes_tpu.factory import create_scheduler
    from kubernetes_tpu.store.store import (MODIFIED, NODES, PODS, SERVICES,
                                            Store)
    rng = random.Random(n)
    store = Store(watch_log_size=1 << 16)
    zone = lambda i: f"zone-{i % 3}"
    store.create_many(NODES, [Node(
        name=f"node-{i}",
        labels={"failure-domain.beta.kubernetes.io/zone": zone(i),
                "failure-domain.beta.kubernetes.io/region": "r1"},
        allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110})
        for i in range(n)])
    ref = Reference(
        [{"name": f"node-{i}", "zone_key": "r1:\x00:" + zone(i), "cpu": 4000,
          "mem": 32 * GI, "pods": 110} for i in range(n)],
        {"default": [{"app": f"svc-{k}"} for k in range(svc)]})
    plan = [k % svc for k in range(n * per)] if svc else []
    rng.shuffle(plan)
    cont = (Container.make(name="c", requests={"cpu": cpu, "memory": 500 * MI}),)
    resident = []
    for i in range(n):
        for j in range(per):
            k = plan[i * per + j]
            resident.append(Pod(name=f"res-{i}-{j}", labels={"app": f"svc-{k}"},
                                node_name=f"node-{i}", containers=cont))
            ref.place({"cpu": cpu, "mem": 500 * MI, "namespace": "default",
                       "labels": (("app", f"svc-{k}"),), "kind": "plain"},
                      f"node-{i}")
    if resident:
        store.create_many(PODS, resident)
        store.create_many(SERVICES, [Service(
            name=f"svc-{k}", selector={"app": f"svc-{k}"}) for k in range(svc)])
    cfg = SchedulerConfiguration(percentage_of_nodes_to_score=100)
    cfg.feature_gates = {"TPUScoring": False}
    sched = create_scheduler(store, cfg)
    sched.sync()
    watch = store.watch(PODS)
    compared = 0
    for cyc in range(3):
        lab = {"app": f"svc-{rng.randrange(svc)}"} if svc else {"app": "density"}
        new = [Pod(name=f"p-{cyc}-{j}", labels=dict(lab), containers=cont)
               for j in range(batch)]
        store.create_many(PODS, new)
        sched.pump()
        while sched.schedule_one(timeout=0.0):
            pass
        sched.wait_for_binds()
        sched.pump()
        desc = {"cpu": cpu, "mem": 500 * MI, "namespace": "default",
                "labels": tuple(sorted(lab.items())),
                "kind": "spread-by-service" if svc else "plain"}
        placed = []
        for ev in watch.drain():
            if ev.type == MODIFIED and ev.obj.node_name:
                assert ref.decide(desc) == ev.obj.node_name, ev.obj.name
                ref.place(desc, ev.obj.node_name)
                placed.append(ev.obj.node_name)
                compared += 1
        if cyc == 1:        # leave the second batch in place: nodes fill up
            continue
        store.delete_many(PODS, [p.key for p in new])
        sched.pump()
        watch.drain()
        for node in placed:
            ref.remove(desc, node)
    assert compared >= min(batch, 28)   # the 7-node case fills up
