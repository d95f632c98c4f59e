"""The one generator: the pod shapes and the service choice the committed
cells drive, from fixed seeds."""
import collections

import pytest

from lib.traffic import PodFactory

MI = 1024 ** 2
REQ = {"cpu_milli": 100, "memory_bytes": 500 * MI}
PER_CYCLE = {"policy": "per-cycle"}


def traffic(shapes, choice=None):
    return {"pod_shapes": shapes, "service_choice": choice}


def test_same_seed_same_pods_and_shared_descriptions():
    tr = traffic([{"kind": "plain", "share": 1.0, "labels": {"app": "x"},
                   "requests": REQ}])
    a = PodFactory(tr, 0, 2**31 + 7)
    b = PodFactory(tr, 0, 2**31 + 7)
    pa, da = a.make("p-0")
    pb, db = b.make("p-0")
    assert (pa.name, pa.labels, pa.containers) == (pb.name, pb.labels, pb.containers)
    assert da == db == {"cpu": 100, "mem": 500 * MI, "namespace": "default",
                        "labels": (("app", "x"),), "kind": "plain"}
    assert a.make("p-1")[1] is da        # equal pods share one description


def services_of(f, n):
    return [int(f.make(f"p-{j}")[0].labels["app"].split("-")[1])
            for j in range(n)]


def test_one_service_per_cycle_drawn_from_the_seed():
    tr = traffic([{"kind": "spread-by-service", "share": 1.0, "requests": REQ}],
                 PER_CYCLE)
    f, g = PodFactory(tr, 50, 1), PodFactory(tr, 50, 1)
    per_cycle = []
    for _ in range(30):
        f.new_cycle()
        g.new_cycle()
        ks = services_of(f, 40)
        assert len(set(ks)) == 1 and 0 <= ks[0] < 50
        assert services_of(g, 40) == ks              # the seed fixes them
        per_cycle.append(ks[0])
    assert len(set(per_cycle)) > 10                  # and cycles differ


def test_shares_of_two_shapes():
    shapes = [
        {"kind": "plain", "share": 0.7, "labels": {"app": "a"}, "requests": REQ},
        {"kind": "spread-by-service", "share": 0.3,
         "requests": {"cpu_milli": 200, "memory_bytes": 256 * MI}},
    ]
    f = PodFactory(traffic(shapes, PER_CYCLE), 5, 4)
    made = [f.make(f"p-{j}") for j in range(2000)]
    kinds = collections.Counter(d["kind"] for _p, d in made)
    assert abs(kinds["plain"] / 2000 - 0.7) < 0.04
    for pod, d in made:
        assert pod.affinity is None and pod.namespace == "default"
        assert d["cpu"] == (100 if d["kind"] == "plain" else 200)


def test_spread_needs_services():
    with pytest.raises(ValueError):
        PodFactory(traffic([{"kind": "spread-by-service", "share": 1.0,
                             "requests": REQ}], PER_CYCLE), 0, 1)
    with pytest.raises(ValueError):
        PodFactory(traffic([{"kind": "spread-by-service", "share": 1.0,
                             "requests": REQ}]), 5, 1)
