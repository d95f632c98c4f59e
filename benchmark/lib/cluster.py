"""The cluster a configuration describes, built from the seed.

The scheduler_perf node shape, zone labels by `i % zones`, pods created
through the store's batched verbs (the structure of the pre-chip `bench.py`'s
`build_cluster`/`make_pods`, which PR 30 deleted). The same pass yields what
the plain reference is given: the nodes as plain dicts and the resident pods'
placements. Nothing here reads anything back from the program.
"""
from __future__ import annotations

import random

ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"
REGION_LABEL = "failure-domain.beta.kubernetes.io/region"
HOSTNAME_LABEL = "kubernetes.io/hostname"


def zone_key(region: str, zone: str) -> str:
    """pkg/util/node.GetZoneKey."""
    if not region and not zone:
        return ""
    return region + ":\x00:" + zone


def node_rows(cfg: dict) -> list[dict]:
    """The configuration's nodes as the reference takes them."""
    nd = cfg["nodes"]
    alloc = nd["allocatable"]
    rows = []
    for i in range(nd["count"]):
        zone = f"zone-{i % nd['zones']}" if nd["zones"] else ""
        region = nd["region"] if nd["zones"] else ""
        rows.append({"name": f"node-{i}", "zone": zone, "region": region,
                     "zone_key": zone_key(region, zone),
                     "cpu": alloc["cpu_milli"], "mem": alloc["memory_bytes"],
                     "pods": alloc["pods"]})
    return rows


def resident_plan(cfg: dict, seed: int) -> list[int]:
    """Service index of each resident pod, node-major: every service gets
    the same number of pods, dealt over the slots by the seed."""
    res = cfg.get("resident")
    if not res:
        return []
    total = cfg["nodes"]["count"] * res["pods_per_node"]
    plan = [k % res["services"] for k in range(total)]
    random.Random(seed ^ 0x5EED5).shuffle(plan)
    return plan


def service_label(k: int) -> dict:
    return {"app": f"svc-{k}"}


def build(cfg: dict, seed: int):
    """Create the store, its nodes, resident pods and services. Returns
    (store, rows, residents, services) where residents is a list of
    (pod description for the reference, node name) and services a list of
    selector dicts."""
    from kubernetes_tpu.api.types import Container, Node, Pod, Service
    from kubernetes_tpu.store.store import NODES, PODS, SERVICES, Store
    store = Store(watch_log_size=cfg["store"]["watch_log_size"])
    rows = node_rows(cfg)
    nodes = []
    for r in rows:
        labels = {HOSTNAME_LABEL: r["name"]}
        if r["zone"]:
            labels[ZONE_LABEL] = r["zone"]
            labels[REGION_LABEL] = r["region"]
        nodes.append(Node(name=r["name"], labels=labels,
                          allocatable={"cpu": r["cpu"], "memory": r["mem"],
                                       "pods": r["pods"]}))
    store.create_many(NODES, nodes)
    residents, services = [], []
    res = cfg.get("resident")
    if res:
        req = res["requests"]
        container = (Container.make(name="c", requests={
            "cpu": req["cpu_milli"], "memory": req["memory_bytes"]}),)
        plan = resident_plan(cfg, seed)
        descs = {}
        pods = []
        per = res["pods_per_node"]
        for i, r in enumerate(rows):
            for j in range(per):
                k = plan[i * per + j]
                pods.append(Pod(name=f"res-{i}-{j}", labels=service_label(k),
                                node_name=r["name"], containers=container))
                d = descs.get(k)
                if d is None:
                    d = descs[k] = {
                        "cpu": req["cpu_milli"], "mem": req["memory_bytes"],
                        "namespace": "default", "kind": "plain",
                        "labels": tuple(sorted(service_label(k).items()))}
                residents.append((d, r["name"]))
        store.create_many(PODS, pods)
        services = [service_label(k) for k in range(res["services"])]
        store.create_many(SERVICES, [
            Service(name=f"svc-{k}", selector=dict(sel))
            for k, sel in enumerate(services)])
    return store, rows, residents, services
