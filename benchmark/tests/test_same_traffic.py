"""Same seed, same traffic: every committed mix makes, draw for draw, the
pods, the descriptions and the due times it made before PR 56 moved the pod
shape kinds and the arrival process into `shapes/` and `arrivals/`. The
digests were taken on the parent tree (`traffic_digest.py`, run there)."""
import pytest

from lib import spec
from traffic_digest import SEEDS, digest, services_of

PARENT = {
    "arrivals-near-knee": ("39d12c6d98603a3f", "c48f895951838f06"),
    "arrivals-steady": ("fcc187272af378ef", "a33efb7c90bf54c0"),
    "arrivals-zipf-64svc": ("63b2cc1ad74a14dc", "958aec3157e54c38"),
    "backlog-10k-mixed": ("9167149712d81af1", "6f6a1e2dd42e5b3c"),
    "backlog-10k": ("a26c34a87dce2724", "a26c34a87dce2724"),
    "backlog-9900-fill": ("a098943fa8da65de", "c044bbc363b966e5"),
    "rollout-1k": ("4c44a52f8483dcce", "d4c253d04f73460a"),
    "rollouts-1k-111svc": ("83d91e4b46aebce0", "a75aaabed34d445d"),
    "rollouts-1k-8svc-jobs": ("4a85413fa2282846", "76b53b518fe1115f"),
    "rollouts-1k-8svc": ("79fc2ba6da378128", "aff18ac60a5a0706"),
}


@pytest.mark.parametrize("which", (0, 1), ids=[str(s) for s in SEEDS])
@pytest.mark.parametrize("mix", sorted(PARENT))
def test_the_mix_makes_what_the_parent_made(mix, which):
    traffic = spec.load_traffic(mix)
    assert digest(traffic, services_of(mix), SEEDS[which]) == \
        PARENT[mix][which]


def test_every_mix_a_cell_names_is_held():
    bench = spec.load_benchmark()
    assert set(PARENT) <= {w["traffic"] for w in bench["workloads"]}
