"""A scan launch's `[B, ...]` pod operands: one row a signature, gathered.

`TPUScheduler._stack_pods` assembles the operands from one row a DISTINCT
per-signature dict plus the pad row and brings each field to the bucket's
length by one gather. The plain reference kept here is the loop it replaced
(a B-long Python list a field, an identity test over it, `np.stack`): every
field must come out equal in value, dtype and shape, so the jitted programs
are handed what they were always handed. The rows are the real thing:
`_pod_arrays` output for `PodFeatures` made by hand. One case goes through
the shell: a serve-sized bucket around a 12-pod window.
"""
import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Container, LABEL_HOSTNAME, Node, Pod, Service)
from kubernetes_tpu.core.tpu_scheduler import (
    ORACLE_FALLBACKS, SCAN_STACK_ROWS, TPUScheduler)
from kubernetes_tpu.ops import COMPILES
from kubernetes_tpu.ops.node_state import PodFeatures
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store.store import NODES, PODS, SERVICES, Store

GI = 1024 ** 3
N_PAD = 128
SIZES = ((100, GI // 2), (250, GI), (400, GI), (100, 2 * GI), (500, GI // 4),
         (1000, 4 * GI), (50, GI // 8), (2000, GI), (0, 0))


def reference(per_pod: list, bucket: int, true, profile_ids=None) -> dict:
    """The loop as it stood: the pad dict repeated to the bucket, tensor
    mode's `profile_id` a shallow dict a pod, a list of `bucket` objects a
    field."""
    wave = list(per_pod)
    if profile_ids is not None:
        wave = [dict(pp, profile_id=np.int64(profile_ids[i]))
                for i, pp in enumerate(wave)]
    if len(wave) < bucket:
        pad = dict(wave[-1])
        pad["skip"] = true
        wave.extend([pad] * (bucket - len(wave)))
    out = {}
    for k in wave[0]:
        vals = [pp[k] for pp in wave]
        v0 = vals[0]
        if all(v is v0 for v in vals):
            out[k] = np.broadcast_to(v0, (len(vals),) + np.shape(v0))
            continue
        shapes = {np.shape(v) for v in vals}
        if len(shapes) > 1:
            target = max(shapes, key=len) \
                if len({len(s) for s in shapes}) > 1 else max(shapes)
            vals = [np.broadcast_to(v, target) for v in vals]
        out[k] = np.stack(vals)
    return out


def row(algo: TPUScheduler, cpu: int, mem: int, scalars=(0, 0),
        **features) -> dict:
    """One signature's dict, as `schedule_burst` makes it."""
    f = PodFeatures(req_cpu=cpu, req_mem=mem, req_eph=0,
                    req_scalar=np.asarray(scalars, np.int64),
                    has_request=bool(cpu or mem or any(scalars)),
                    nz_cpu=cpu or 100, nz_mem=mem or 200 * 1024 ** 2,
                    **features)
    pod = Pod(name="p", containers=(Container.make(
        name="c", requests={"cpu": cpu, "memory": mem}),))
    return algo._pod_arrays(f, N_PAD, upd_fields=True, pod=pod)


def dealt(rows: list, n: int, seed: int) -> list:
    """`n` pods over the signatures' dicts, every signature at least once."""
    rng = np.random.default_rng(seed)
    picks = np.concatenate([np.arange(len(rows)),
                            rng.integers(0, len(rows), n - len(rows))])
    rng.shuffle(picks)
    return [rows[i] for i in picks]


def one_signature(algo):
    return [row(algo, 100, GI // 2)] * 1000, 1024, None


def nine_in_8192(algo):
    rows = [row(algo, c, m) for c, m in SIZES]
    return dealt(rows, 12, 1), 8192, None


def eight_in_512(algo):
    rows = [row(algo, c, m) for c, m in SIZES[:8]]
    return dealt(rows, 300, 2), 512, None


def inert_beside_dense(algo):
    """A node selector's dense `sel_ok` and spread counts on some rows, the
    shared `[1]` default on the others."""
    sel = np.arange(N_PAD) % 3 > 0
    rows = [row(algo, 100, GI), row(algo, 100, GI, sel_ok=sel),
            row(algo, 250, GI, sel_ok=~sel,
                node_aff_counts=np.arange(N_PAD, dtype=np.int64)),
            row(algo, 250, GI)]
    return dealt(rows, 40, 3), 64, None


def scalar_beside_zero(algo):
    """A pod that asks for an extended resource: its `req_scalar` row is
    its own array, the others hold the shared zero row."""
    rows = [row(algo, 100, GI), row(algo, 100, GI, scalars=(0, 3)),
            row(algo, 250, GI)]
    assert rows[0]["req_scalar"] is rows[2]["req_scalar"]
    assert rows[1]["req_scalar"].any()
    return dealt(rows, 20, 4), 32, None


def no_pad_row(algo):
    rows = [row(algo, c, m) for c, m in SIZES[:3]]
    return dealt(rows, 16, 5), 16, None


def full_and_alike(algo):
    """No pad row and one signature: nothing differs, every field a view."""
    return [row(algo, 100, GI)] * 16, 16, None


def profile_a_pod(algo):
    """Tensor mode: the weight row a pod selects is a per-pod scalar over
    per-signature dicts."""
    rows = [row(algo, c, m) for c, m in SIZES[:4]]
    per_pod = dealt(rows, 50, 6)
    pids = np.random.default_rng(6).integers(0, 3, 50).astype(np.int64)
    return per_pod, 64, pids


def a_dict_a_pod(algo):
    """The pressure scan's chunk: every pod its own dict with its
    priority, the singletons still shared."""
    per_pod = []
    for j in range(20):
        d = row(algo, *SIZES[j % 4])
        d["pprio"] = np.int64(j % 3)
        per_pod.append(d)
    return per_pod, 24, None


CASES = [one_signature, nine_in_8192, eight_in_512, inert_beside_dense,
         scalar_beside_zero, no_pad_row, full_and_alike, profile_a_pod,
         a_dict_a_pod]


def stack_rows() -> dict:
    return {k: SCAN_STACK_ROWS.labels(k).value for k in ("built", "taken")}


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_stacked_operand_is_the_loops(case):
    algo = TPUScheduler()
    per_pod, bucket, pids = case(algo)
    handed = list(per_pod)
    want = reference(per_pod, bucket, algo._true, pids)
    before = stack_rows()
    got, signatures = algo._stack_pods(per_pod, bucket, pids)
    moved = {k: v - before[k] for k, v in stack_rows().items()}

    assert set(got) == set(want) and len(want) >= 31
    for k, w in want.items():
        g = got[k]
        assert (g.shape, g.dtype) == (w.shape, w.dtype), k
        np.testing.assert_array_equal(g, w, err_msg=k)
        # what was a zero-stride view of one shared object still is
        assert (g.strides[0] == 0) == (w.strides[0] == 0) or bucket == 1, k
    distinct = len({id(pp) for pp in per_pod})
    assert signatures == distinct
    assert moved == {"built": distinct + (len(per_pod) < bucket),
                     "taken": bucket}
    # the caller's list is not padded behind its back
    assert len(per_pod) == len(handed) and \
        all(a is b for a, b in zip(per_pod, handed))
    assert not any("profile_id" in pp for pp in per_pod)


def test_the_cases_show_what_they_claim():
    """The fields the cases are about do differ, mix shapes and stay shared
    where the docstrings say so."""
    algo = TPUScheduler()
    out, _ = algo._stack_pods(*inert_beside_dense(algo)[:2])
    assert out["sel_ok"].shape == (64, N_PAD) and not out["sel_ok"].all()
    assert out["node_aff_counts"].shape == (64, N_PAD)
    assert out["taints_ok"].shape == (64, 1)
    assert out["taints_ok"].strides[0] == 0
    out, _ = algo._stack_pods(*scalar_beside_zero(algo)[:2])
    assert out["req_scalar"].shape == (32, 2)
    assert set(out["req_scalar"].sum(axis=1).tolist()) == {0, 3}
    assert out["upd_scalar"].strides[0] == 0
    out, n = algo._stack_pods(*nine_in_8192(algo)[:2])
    assert n == 9 and out["skip"][12:].all() and not out["skip"][:12].any()
    assert len(set(zip(out["req_cpu"][:12].tolist(),
                       out["req_mem"][:12].tolist()))) == 9
    out, _ = algo._stack_pods(*full_and_alike(algo)[:2])
    assert all(v.strides[0] == 0 for v in out.values())
    per_pod, bucket, pids = profile_a_pod(algo)
    out, n = algo._stack_pods(per_pod, bucket, pids)
    assert n == 4 and out["profile_id"].dtype == np.int64
    assert out["profile_id"][:50].tolist() == pids.tolist()
    assert (out["profile_id"][50:] == pids[-1]).all()


# -- through the shell --------------------------------------------------------
ZONE = "failure-domain.beta.kubernetes.io/zone"
SERVICES_IN_WINDOW = 9


def world() -> Store:
    s = Store(watch_log_size=65536)
    for i in range(130):            # zones of 44/43/43: the order rotates
        s.create(NODES, Node(
            name=f"n{i}", labels={LABEL_HOSTNAME: f"n{i}", ZONE: f"z{i % 3}"},
            allocatable={"cpu": 4000, "memory": 32 * GI, "pods": 110}))
    for k in range(SERVICES_IN_WINDOW):
        s.create(SERVICES, Service(name=f"svc-{k}",
                                   selector={"app": f"svc-{k}"}))
    for i in range(0, 130, 2):
        s.create(PODS, Pod(
            name=f"res-{i}", node_name=f"n{i}",
            labels={"app": f"svc-{i % SERVICES_IN_WINDOW}"},
            containers=(Container.make(
                name="c", requests={"cpu": 100, "memory": GI // 2}),)))
    return s


def window(w: int) -> list:
    """12 pods of 9 Services, each Service's pods of one size."""
    return [Pod(name=f"w{w}-{j:02d}",
                labels={"app": f"svc-{j % SERVICES_IN_WINDOW}"},
                containers=(Container.make(name="c", requests={
                    "cpu": 100 + 50 * (j % SERVICES_IN_WINDOW),
                    "memory": GI // 2}),))
            for j in range(12)]


def bindings(s: Store) -> list:
    return sorted((p.key, p.node_name) for p in s.list(PODS)[0])


def test_a_serve_sized_bucket_builds_a_row_a_signature():
    """A 12-pod window of 9 Services behind a pinned `launch_cap`, drained
    by a pass of 6144 (`ServeLoop(2048, 3)`): the bucket is 8192, the rows
    built are the signatures' and the pad's, the binds are the serial
    oracle's, and the second window compiles nothing."""
    s = world()
    oracle = Scheduler(s, use_tpu=False, percentage_of_nodes_to_score=0)
    oracle.sync()
    for w in range(2):
        s.create_many(PODS, window(w))
        oracle.pump()
        while oracle.schedule_one(timeout=0.0):
            pass
        oracle.pump()
    want = bindings(s)
    assert all(node for _key, node in want)

    s = world()
    sched = Scheduler(s, use_tpu=True, percentage_of_nodes_to_score=0)
    sched.sync()
    sched.algorithm.launch_cap = 2048
    fallbacks = sum(c.value for c in ORACLE_FALLBACKS._children.values())
    for w in range(2):
        s.create_many(PODS, window(w))
        sched.pump()
        before = stack_rows()
        compiled = sum(c.value for c in COMPILES._children.values())
        assert sched.schedule_burst(max_pods=6144) == 12
        sched.pump()
        moved = {k: v - before[k] for k, v in stack_rows().items()}
        assert moved["taken"] == 8192
        # one launch: nine signatures' rows and the pad's
        assert moved["built"] == SERVICES_IN_WINDOW + 1
        if w:
            assert sum(c.value for c in COMPILES._children.values()) \
                == compiled
    assert bindings(s) == want
    assert sum(c.value for c in ORACLE_FALLBACKS._children.values()) \
        == fallbacks
