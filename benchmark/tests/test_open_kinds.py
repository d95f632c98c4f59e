"""A pod shape kind and an arrival process are files found by name: a test
lays a root of its own over the tree, writes there a kind, a process, the
reference that states the kind, a traffic mix, a configuration and the
entries of one cell, and the cell loads, makes its pods and rehearses on the
CPU backend with no committed file edited. A name without its file is a
`SpecError` that says which file was looked for."""
import json
import os
import shutil

import pytest

import run
from lib import spec
from lib.traffic import PodFactory, due_times

KIND = '''"""Pods of `tenants` tenants, a pod's tenant drawn from the seed."""
REQUIRED = {"tenants": int}
OPTIONAL = {"tier": str}


def check(traffic, n_services):
    if traffic.get("service_choice"):
        raise ValueError("tenant pods take no service_choice")


def make(entry, factory):
    k = factory.rng.randrange(entry["tenants"])
    labels = {**(entry.get("labels") or {}), "tenant": f"t-{k}"}
    return ({"labels": labels, "service_account_name": f"sa-{k}"},
            {"tenant": k})
'''
PROCESS = '''"""Arrivals `gap` apart in bursts of `burst`, the phase from the seed."""
import random

REQUIRED = {"burst": int}
OPTIONAL = {}


def due_times(arrival, seconds, seed):
    gap = arrival["burst"] / float(arrival["rate_per_s"])
    t = random.Random(seed).random() * gap
    out = []
    while t < seconds:
        out.extend([t] * arrival["burst"])
        t += gap
    return out
'''
REFERENCE = '''"""States the kind `tenant`: nothing selects a tenant's label, so the
default provider places such a pod as it places a plain one."""
from reference import default_provider as base


class Reference(base.Reference):
    def decide(self, pod):
        if pod["kind"] == "tenant":
            assert 0 <= pod["tenant"] < 4
            pod = {**pod, "kind": "plain"}
        return super().decide(pod)
'''
MIX = {
    "kind": "open_arrivals",
    "arrival": {"process": "on-off", "rate_per_s": 150.0, "burst": 5},
    "pod_shapes": [
        {"kind": "tenant", "share": 0.5, "tenants": 4, "tier": "batch",
         "requests": {"cpu_milli": 100, "memory_bytes": 134217728}},
        {"kind": "plain", "share": 0.5, "labels": {"app": "density"},
         "requests": {"cpu_milli": 100, "memory_bytes": 524288000}}],
    "lifetime_s": 0.4, "service_choice": None,
    "serve": {"window_size": 64, "depth": 3, "gate_seconds": 2.0,
              "retry_after_base_s": 0.25, "give_up_after": 64,
              "settle_timeout_s": 60.0},
    "why": "a test's mix: bursts of five, half of them tenants' pods"}
CELL = "tenants-15000n.bursts-of-5"


@pytest.fixture()
def root(tmp_path):
    """The tree's data and the code its data names, copied, and one
    deployment more, as a later PR would add it: new files and entries."""
    dst = tmp_path / "repo"
    os.makedirs(dst / "benchmark")
    for d in ("configs", "traffic", "metrics", "shapes", "arrivals",
              "reference"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, d), dst / "benchmark" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "headline-15000n")
    cfg.update(name="tenants-15000n", reference="default_provider_tenant")
    files = {"shapes/tenant.py": KIND, "arrivals/on_off.py": PROCESS,
             "reference/default_provider_tenant.py": REFERENCE,
             "traffic/bursts-of-5.json": json.dumps(MIX),
             "configs/tenants-15000n.json": json.dumps(cfg)}
    for rel, text in files.items():
        assert not os.path.exists(os.path.join(spec.BENCH_DIR, rel))
        (dst / "benchmark" / rel).write_text(text)
    bench["configs"].append({
        "name": "tenants-15000n", "source": cfg["source"],
        "file": "benchmark/configs/tenants-15000n.json", "reduced": [],
        "why": "a test's deployment"})
    bench["workloads"].append({
        "name": CELL, "config": "tenants-15000n", "traffic": "bursts-of-5",
        "chips": 1, "why": "a test's cell"})
    # the one list a new open-loop cell joins: its end-to-end metric's
    next(m for m in bench["end_to_end"]
         if m["name"] == "startup_p50_ms")["workloads"].append(CELL)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return str(dst)


def test_the_new_cell_reports_what_every_open_loop_cell_reports(root):
    bench = spec.load_benchmark(root)
    names = lambda c, g: [m["name"] for m in spec.metrics_for(
        bench, spec.find_cell(bench, c), g)]
    assert names(CELL, "end_to_end") == ["startup_p50_ms", "setup_s"]
    # entries only: the metrics without a list come with the end-to-end one
    assert names(CELL, "per_layer") == [
        m["name"] for m in bench["per_layer"] if "workloads" not in m
        and m["moves"] in ("startup_p50_ms", "setup_s")]
    assert set(names(CELL, "per_layer")) <= set(
        names("headline-15000n.arrivals-steady", "per_layer"))


def test_the_kind_and_the_process_load_and_make_traffic(root):
    traffic = spec.load_traffic("bursts-of-5", root)
    a, b = (PodFactory(traffic, 0, 2 ** 31 + 9, root) for _ in range(2))
    made = [a.make(f"p-{j}") for j in range(400)]
    again = [b.make(f"p-{j}") for j in range(400)]
    assert [(p.labels, p.service_account_name, d) for p, d in made] == \
        [(p.labels, p.service_account_name, d) for p, d in again]
    tenants = [(p, d) for p, d in made if d["kind"] == "tenant"]
    assert 120 < len(tenants) < 280
    for pod, desc in tenants:
        k = desc["tenant"]
        assert pod.labels == {"tenant": f"t-{k}"} == dict(desc["labels"])
        assert pod.service_account_name == f"sa-{k}" and 0 <= k < 4
    # equal pods share one description: four tenants' and the plain pods'
    assert len({id(d) for _p, d in made}) == 5
    due = due_times(traffic["arrival"], 2.0, 7, root)
    assert due == sorted(due) and len(due) % 5 == 0
    assert 250 <= len(due) <= 305 and len(set(due)) == len(due) // 5
    assert due == due_times(traffic["arrival"], 2.0, 7, root)


def test_the_new_cell_rehearses_correct(root):
    at_50 = {"nodes": {"count": 50},
             "check": {"first_binds": 200, "sampled_binds": 60}}
    out = run.execute(CELL, 2 ** 31 + 56, 1.5, False, rehearse=True,
                      root=root, overrides={"config": at_50})
    res = out["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 150 and out["report"]["compared"] > 150
    assert set(res["metrics"]) == {"startup_p50_ms", "setup_s"}


@pytest.mark.parametrize("spoil,looked_for", [
    (lambda t: t["pod_shapes"][0].update(kind="anti-affinity"),
     os.path.join("benchmark", "shapes", "anti_affinity.py")),
    (lambda t: t["arrival"].update(process="mmpp"),
     os.path.join("benchmark", "arrivals", "mmpp.py")),
])
def test_a_name_without_its_file_names_the_file(root, spoil, looked_for):
    path = os.path.join(root, "benchmark", "traffic", "bursts-of-5.json")
    mix = json.loads(json.dumps(MIX))
    spoil(mix)
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(spec.SpecError) as e:
        spec.load_traffic("bursts-of-5", root)
    assert os.path.join(root, looked_for) in str(e.value)


@pytest.mark.parametrize("spoil", [
    lambda t: t["pod_shapes"][0].pop("tenants"),          # the kind's own key
    lambda t: t["pod_shapes"][0].update(tier=3),
    lambda t: t["pod_shapes"][1].update(tenants=4),       # not plain's key
    lambda t: t["arrival"].pop("burst"),
    lambda t: t["arrival"].update(period_s=1.0),
    lambda t: t["pod_shapes"][0].pop("kind"),
])
def test_an_entry_holds_the_keys_its_file_states(root, spoil):
    path = os.path.join(root, "benchmark", "traffic", "bursts-of-5.json")
    mix = json.loads(json.dumps(MIX))
    spoil(mix)
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(spec.SpecError):
        spec.load_traffic("bursts-of-5", root)


def test_the_committed_reference_refuses_a_kind_it_does_not_state(root):
    from reference import default_provider
    ref = default_provider.Reference(
        [{"name": "n0", "zone": "", "region": "", "zone_key": "", "cpu": 1000,
          "mem": 2 ** 30, "pods": 10}], {"default": []}, 100)
    with pytest.raises(ValueError):
        ref.decide({"cpu": 100, "mem": 1, "namespace": "default",
                    "labels": (), "kind": "tenant", "tenant": 0})
