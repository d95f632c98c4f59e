"""Differential fuzz for the columnar encode path (ISSUE 1 tentpole).

The vectorized twins — predicates.selector_match_mask /
pod_matches_term_props_mask over the PodTable, and the PodEncoder's
vectorized selector-spread / taint / image-locality / inter-pod loops —
must be bit-identical to a row-by-row scalar evaluation. These fuzzes
compare them directly against the scalar oracle primitives over random
snapshots, independent of (and faster than) the kernel parity suite.
"""
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Pod, Node, Container, Taint, Toleration, Requirement, LabelSelector,
    PodAffinityTerm, Service, ReplicaSet, ImageState,
    IN, NOT_IN, EXISTS, DOES_NOT_EXIST, GT, LT,
    NO_SCHEDULE, PREFER_NO_SCHEDULE, LABEL_HOSTNAME,
    LABEL_ZONE_FAILURE_DOMAIN,
)
from kubernetes_tpu.cache.node_info import NodeInfo, normalized_image_name
from kubernetes_tpu.oracle.predicates import (
    pod_matches_term_props, pod_matches_term_props_mask,
    selector_match_mask, InterPodAffinityChecker,
)
from kubernetes_tpu.oracle.priorities import _selector_matches, get_selectors
from kubernetes_tpu.ops.node_state import (
    NodeStateEncoder, PodEncoder, build_pod_table,
    IPA_EXISTING_ANTI, IPA_OWN_AFFINITY, IPA_OWN_ANTI,
)

GI = 1024 ** 3

KEYS = ["app", "tier", "size", "disk", ""]
VALS = ["web", "db", "7", "42", "-3", "x y", "", "10q"]
NAMESPACES = ["default", "kube-system", "team-a"]


def rand_labels(rng):
    return {k: rng.choice(VALS)
            for k in rng.sample(KEYS, rng.randint(0, len(KEYS)))}


def rand_pod(rng, j):
    return Pod(name=f"p{j}", namespace=rng.choice(NAMESPACES),
               labels=rand_labels(rng),
               containers=(Container.make(name="c", requests={"cpu": 50}),))


def rand_snapshot(rng, n_nodes=6, n_pods=40):
    infos = {}
    names = []
    for i in range(n_nodes):
        labels = {LABEL_HOSTNAME: f"n{i}"}
        if rng.random() < 0.7:
            labels[LABEL_ZONE_FAILURE_DOMAIN] = f"z{i % 3}"
        node = Node(name=f"n{i}", labels=labels,
                    allocatable={"cpu": 64000, "memory": 64 * GI,
                                 "pods": 110})
        infos[node.name] = NodeInfo(None if rng.random() < 0.05 else node)
        names.append(node.name)
    for j in range(n_pods):
        p = rand_pod(rng, j)
        host = rng.choice(names)
        p.node_name = host
        if rng.random() < 0.1:
            p.deleted = True
        infos[host].add_pod(p)
    return infos, names


def make_table(infos, names):
    enc = NodeStateEncoder()
    b = enc.encode(infos, names)
    return enc.pod_table(infos, b), b, enc


def rand_requirement(rng):
    op = rng.choice([IN, NOT_IN, EXISTS, DOES_NOT_EXIST, GT, LT])
    values = tuple(rng.sample(VALS, rng.randint(0, 3)))
    return Requirement(key=rng.choice(KEYS), op=op, values=values)


def rand_selector(rng):
    if rng.random() < 0.4:
        return {k: rng.choice(VALS)
                for k in rng.sample(KEYS, rng.randint(0, 2))}
    return LabelSelector(
        match_labels=tuple(sorted(
            (k, rng.choice(VALS))
            for k in rng.sample(KEYS, rng.randint(0, 2)))),
        match_expressions=tuple(rand_requirement(rng)
                                for _ in range(rng.randint(0, 3))))


class TestSelectorMaskTwins:
    @pytest.mark.parametrize("seed", range(12))
    def test_selector_match_mask_equals_scalar(self, seed):
        rng = random.Random(1000 + seed)
        infos, names = rand_snapshot(rng)
        table, _b, _e = make_table(infos, names)
        for _ in range(25):
            sel = rand_selector(rng)
            mask = selector_match_mask(sel, table)
            want = [_selector_matches(sel, p.labels) for p in table.pods]
            assert mask.tolist() == want, sel

    @pytest.mark.parametrize("seed", range(12))
    def test_term_props_mask_equals_scalar(self, seed):
        rng = random.Random(2000 + seed)
        infos, names = rand_snapshot(rng)
        table, _b, _e = make_table(infos, names)
        defining = rand_pod(rng, 999)
        for _ in range(20):
            sel = rand_selector(rng)
            term = PodAffinityTerm(
                label_selector=None if rng.random() < 0.15
                else (sel if not isinstance(sel, dict)
                      else LabelSelector.from_dict(sel)),
                topology_key=rng.choice(
                    [LABEL_HOSTNAME, LABEL_ZONE_FAILURE_DOMAIN]),
                namespaces=tuple(rng.sample(NAMESPACES,
                                            rng.randint(0, 2))))
            mask = pod_matches_term_props_mask(defining, term, table)
            want = [pod_matches_term_props(p, defining, term)
                    for p in table.pods]
            assert mask.tolist() == want, term


class TestEncoderVectorParity:
    """The PodEncoder's vectorized score/filter loops vs their scalar
    definitions, over random snapshots."""

    def _encoder(self, rng, infos, b, enc, services=(), replicasets=()):
        return PodEncoder(infos, b, services=list(services),
                          replicasets=list(replicasets),
                          state_encoder=enc)

    @pytest.mark.parametrize("seed", range(10))
    def test_spread_counts_equal_scalar_loop(self, seed):
        rng = random.Random(3000 + seed)
        infos, names = rand_snapshot(rng)
        _t, b, enc = make_table(infos, names)
        services = [Service(name=f"s{i}", namespace=rng.choice(NAMESPACES),
                            selector={k: rng.choice(VALS)
                                      for k in rng.sample(KEYS, 1)})
                    for i in range(3)]
        replicasets = [
            ReplicaSet(name=f"rs{i}", namespace=rng.choice(NAMESPACES),
                       selector=LabelSelector(
                           match_labels=tuple(sorted(
                               (k, rng.choice(VALS))
                               for k in rng.sample(KEYS, 1))),
                           match_expressions=tuple(
                               rand_requirement(rng)
                               for _ in range(rng.randint(0, 2)))))
            for i in range(2)]
        pe = self._encoder(rng, infos, b, enc, services, replicasets)
        for j in range(8):
            pod = rand_pod(rng, j)
            f = pe.encode(pod)
            selectors = get_selectors(pod, services, replicasets)
            want = np.zeros(b.n_pad, dtype=np.int64)
            for i in range(b.n_real):
                ni = infos[b.names[i]]
                for existing in ni.pods:
                    if existing.namespace != pod.namespace or existing.deleted:
                        continue
                    if selectors and all(_selector_matches(s, existing.labels)
                                         for s in selectors):
                        want[i] += 1
            if selectors:
                assert f.spread_counts is not None
                assert f.spread_counts.tolist() == want.tolist()
            else:
                assert f.spread_counts is None

    @pytest.mark.parametrize("seed", range(6))
    def test_taint_counts_equal_scalar_loop(self, seed):
        from kubernetes_tpu.api.types import tolerations_tolerate_taint
        rng = random.Random(4000 + seed)
        infos, names = rand_snapshot(rng)
        # sprinkle taints (duplicates included) onto the nodes
        for ni in infos.values():
            if ni.node is None or rng.random() < 0.4:
                continue
            taints = tuple(
                Taint(key=rng.choice(["team", "ded"]),
                      value=rng.choice(["a", "b"]),
                      effect=rng.choice([NO_SCHEDULE, PREFER_NO_SCHEDULE]))
                for _ in range(rng.randint(1, 3)))
            ni.set_node(Node(name=ni.node.name, labels=ni.node.labels,
                             taints=taints,
                             allocatable={"cpu": 64000, "memory": 64 * GI,
                                          "pods": 110}))
        enc = NodeStateEncoder()
        b = enc.encode(infos, names)
        pe = self._encoder(rng, infos, b, enc)
        for j in range(6):
            pod = rand_pod(rng, j)
            pod.tolerations = tuple(
                Toleration(key="team", op="Equal",
                           value=rng.choice(["a", "b"]), effect="")
                for _ in range(rng.randint(0, 2)))
            f = pe.encode(pod)
            tols = [t for t in pod.tolerations
                    if not t.effect or t.effect == PREFER_NO_SCHEDULE]
            want = np.zeros(b.n_pad, dtype=np.int64)
            for i in range(b.n_real):
                for taint in infos[b.names[i]].taints:
                    if taint.effect == PREFER_NO_SCHEDULE and \
                            not tolerations_tolerate_taint(tols, taint):
                        want[i] += 1
            if f.taint_counts is not None:
                assert f.taint_counts.tolist() == want.tolist()
            else:
                assert not want.any()

    @pytest.mark.parametrize("seed", range(6))
    def test_image_sums_equal_scalar_loop(self, seed):
        rng = random.Random(5000 + seed)
        infos, names = rand_snapshot(rng)
        for ni in infos.values():
            if ni.node is None or rng.random() < 0.5:
                continue
            imgs = tuple(ImageState(names=(f"img-{rng.randint(0, 3)}:v1",),
                                    size_bytes=rng.randint(1, 2000) * 1024 * 1024)
                         for _ in range(rng.randint(1, 2)))
            ni.set_node(Node(name=ni.node.name, labels=ni.node.labels,
                             allocatable={"cpu": 64000, "memory": 64 * GI,
                                          "pods": 110},
                             images=imgs))
        enc = NodeStateEncoder()
        b = enc.encode(infos, names)
        pe = self._encoder(rng, infos, b, enc)
        for j in range(6):
            image = f"img-{rng.randint(0, 3)}:v1"
            pod = Pod(name=f"ip{j}", containers=(
                Container.make(name="c", requests={"cpu": 50}, image=image),
                Container.make(name="d", requests={"cpu": 50}, image=image),))
            f = pe.encode(pod)
            want = np.zeros(b.n_pad, dtype=np.int64)
            for i in range(b.n_real):
                ni = infos[b.names[i]]
                total = 0
                for c in pod.containers:
                    state = ni.image_states.get(normalized_image_name(c.image))
                    if state is not None:
                        spread = state.num_nodes / pe.total_num_nodes
                        total += int(state.size_bytes * spread)
                want[i] = total
            if f.image_sums is not None:
                assert f.image_sums.tolist() == want.tolist()
            else:
                assert not want.any()

    @pytest.mark.parametrize("seed", range(8))
    def test_interpod_codes_equal_scalar_check(self, seed):
        from kubernetes_tpu.oracle import predicates as P
        from kubernetes_tpu.api.types import (
            Affinity, PodAffinity, PodAntiAffinity)
        rng = random.Random(6000 + seed)
        infos, names = rand_snapshot(rng)
        # give some existing pods required (anti-)affinity terms
        for ni in infos.values():
            for p in list(ni.pods):
                if rng.random() < 0.25:
                    term = PodAffinityTerm(
                        label_selector=LabelSelector.from_dict(
                            {"app": rng.choice(["web", "db"])}),
                        topology_key=rng.choice(
                            [LABEL_HOSTNAME, LABEL_ZONE_FAILURE_DOMAIN]))
                    ni.remove_pod(p)
                    p.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(
                        required=(term,)))
                    ni.add_pod(p)
        enc = NodeStateEncoder()
        b = enc.encode(infos, names)
        pe = self._encoder(rng, infos, b, enc)
        for j in range(6):
            pod = rand_pod(rng, j)
            pod.node_name = ""
            if rng.random() < 0.7:
                term = PodAffinityTerm(
                    label_selector=LabelSelector.from_dict(
                        {"app": rng.choice(["web", "db"])}),
                    topology_key=rng.choice(
                        [LABEL_HOSTNAME, LABEL_ZONE_FAILURE_DOMAIN, "nope"]))
                if rng.random() < 0.5:
                    pod.affinity = Affinity(
                        pod_affinity=PodAffinity(required=(term,)))
                else:
                    pod.affinity = Affinity(
                        pod_anti_affinity=PodAntiAffinity(required=(term,)))
            f = pe.encode(pod)
            # scalar referee: a FRESH checker without the table source
            ipa = InterPodAffinityChecker(infos)
            want = np.zeros(b.n_pad, dtype=np.int8)
            for i in range(b.n_real):
                ok, reasons = ipa.check(pod, infos[b.names[i]])
                if not ok:
                    if P.ERR_EXISTING_PODS_ANTI_AFFINITY_RULES_NOT_MATCH \
                            in reasons:
                        want[i] = IPA_EXISTING_ANTI
                    elif P.ERR_POD_AFFINITY_RULES_NOT_MATCH in reasons:
                        want[i] = IPA_OWN_AFFINITY
                    else:
                        want[i] = IPA_OWN_ANTI
            got = f.interpod_code if f.interpod_code is not None \
                else np.zeros(b.n_pad, dtype=np.int8)
            assert got.tolist() == want.tolist(), pod.affinity


def table_rows(t):
    """A PodTable decoded through its vocabularies, one tuple per row."""
    ns = {v: k for k, v in t.ns_vocab.items()}
    keys = {v: k for k, v in t.key_vocab.items()}
    vals = {v: k for k, v in t.val_vocab.items()}
    rows = []
    for i, pd in enumerate(t.pods):
        labels = {keys[k]: vals[v]
                  for k, v in zip(t.key_ids[i].tolist(),
                                  t.val_ids[i].tolist()) if k >= 0}
        rows.append((id(pd), int(t.holder_row[i]), bool(t.holder_has_obj[i]),
                     int(t.name_row[i]), ns[int(t.ns_id[i])],
                     bool(t.deleted[i]), bool(t.has_affinity[i]), labels,
                     int(t.prio[i]), float(t.start[i]), int(t.res_cpu[i]),
                     int(t.res_mem[i]), int(t.res_eph[i]),
                     bool(t.has_scalar[i]), bool(t.has_aff_terms[i]),
                     bool(t.has_ports[i])))
    return rows


def plain_rows(infos, b):
    """The same tuples, row by row from the snapshot: the loop the
    columnar build replaced, kept as its reference."""
    from kubernetes_tpu.api.types import (
        get_container_ports, has_pod_affinity_terms)
    from kubernetes_tpu.cache.node_info import calculate_resource
    rows = []
    for name, ni in infos.items():
        aff = set(map(id, ni.pods_with_affinity))
        for pd in ni.pods:
            r = calculate_resource(pd)
            rows.append((
                id(pd), b.index.get(name, -1), ni.node is not None,
                b.index.get(pd.node_name, -1)
                if pd.node_name in infos else -1,
                pd.namespace, pd.deleted, id(pd) in aff, dict(pd.labels),
                pd.priority,
                pd.start_time if pd.start_time is not None else np.inf,
                r.milli_cpu, r.memory, r.ephemeral_storage, bool(r.scalar),
                has_pod_affinity_terms(pd), bool(get_container_ports(pd))))
    return rows


def rand_held_pod(rng, j, names):
    """A resident pod that exercises every cached column."""
    from kubernetes_tpu.api.types import (
        Affinity, ContainerPort, PodAntiAffinity)
    req = {"cpu": rng.choice([50, 100, 250]),
           "memory": rng.choice([0, GI, 2 * GI])}
    if rng.random() < 0.2:
        req["example.com/gpu"] = 1
    ports = (ContainerPort(host_port=8000 + j % 50),) \
        if rng.random() < 0.2 else ()
    p = rand_pod(rng, j)
    p.containers = (Container.make(name="c", requests=req, ports=ports),)
    p.priority = rng.choice([0, 0, 10, 1000])
    p.start_time = rng.choice([None, 1.0, 2.5, float(j)])
    p.deleted = rng.random() < 0.1
    if rng.random() < 0.2:
        p.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(required=(
            PodAffinityTerm(LabelSelector(match_labels=(("app", "web"),)),
                            LABEL_HOSTNAME),)))
    # mostly the holder's name; now and then another node's, or none
    p.node_name = rng.choice(names + ["gone", ""]) \
        if rng.random() < 0.15 else None
    return p


def table_paths():
    """{path: calls} of tpu_pod_table_calls_total."""
    from kubernetes_tpu.ops.node_state import POD_TABLE_CALLS
    return {p: POD_TABLE_CALLS.labels(p).value
            for p in ("returned", "shared", "spliced", "gathered", "built")}


def plain_node(name):
    return Node(name=name, labels={LABEL_HOSTNAME: name},
                allocatable={"cpu": 64000, "memory": 64 * GI, "pods": 110})


def held_pod(name, host, labels=None):
    p = Pod(name=name, namespace="default",
            labels={"app": "web"} if labels is None else labels,
            containers=(Container.make(name="c", requests={"cpu": 50}),))
    p.node_name = host
    return p


def cluster(n_nodes, per=3, empty=()):
    """`n_nodes` nodes of `per` pods each (none on `empty`) and an encoder
    that has cut its first table of them."""
    infos = {f"n{i}": NodeInfo(plain_node(f"n{i}")) for i in range(n_nodes)}
    for h, ni in infos.items():
        for j in range(0 if h in empty else per):
            ni.add_pod(held_pod(f"{h}-{j}", h))
    names = list(infos)
    enc = NodeStateEncoder()
    assert checked_table(enc, infos, names)[1] == "built"
    return infos, names, enc


def checked_table(enc, infos, names):
    """(the encoder's table of the snapshot, the path the call left on,
    what the call booked of moved nodes and their rows), the table held to
    a fresh build and to the plain loop."""
    from kubernetes_tpu.ops.node_state import (
        POD_TABLE_MOVED_NODES, POD_TABLE_ROWS)

    def booked():
        return {r: fam.labels(r).value
                for fam, rs in ((POD_TABLE_MOVED_NODES, ("kept", "changed")),
                                (POD_TABLE_ROWS, ("reused", "extracted")))
                for r in rs}

    b = enc.encode(infos, names)
    calls, before = table_paths(), booked()
    t = enc.pod_table(infos, b)
    (path,) = [k for k, v in table_paths().items() if v != calls[k]]
    moved = {r: v - before[r] for r, v in booked().items()}
    fresh = build_pod_table(infos, b)
    want = plain_rows(infos, b)
    assert table_rows(t) == want
    assert table_rows(fresh) == want
    assert t.key_ids.shape == fresh.key_ids.shape
    for f in ("key_ids", "val_ids", "ns_id", "prio", "start"):
        assert getattr(t, f).flags["C_CONTIGUOUS"], f
    return t, path, moved


class TestPodTableCache:
    def test_generation_cache_reuses_blocks_and_tracks_changes(self):
        rng = random.Random(7)
        infos, names = rand_snapshot(rng, n_nodes=4, n_pods=10)
        enc = NodeStateEncoder()
        b = enc.encode(infos, names)
        t1 = enc.pod_table(infos, b)
        t2 = enc.pod_table(infos, b)
        assert t2.key_ids.tolist() == t1.key_ids.tolist()
        # a new pod on one node must appear after the generation bump
        host = names[0]
        extra = rand_pod(rng, 99)
        extra.labels = {"fresh": "yes"}
        extra.node_name = host
        infos[host].add_pod(extra)
        t3 = enc.pod_table(infos, b)
        assert len(t3.pods) == len(t1.pods) + 1
        m = selector_match_mask({"fresh": "yes"}, t3)
        assert m.sum() == 1
        assert t3.pods[int(np.nonzero(m)[0][0])] is extra

    def test_standalone_build_matches_cached(self):
        rng = random.Random(8)
        infos, names = rand_snapshot(rng, n_nodes=4, n_pods=12)
        enc = NodeStateEncoder()
        b = enc.encode(infos, names)
        ta = enc.pod_table(infos, b)
        tb = build_pod_table(infos, b)
        # same rows, same holder mapping (vocab ids may differ — compare
        # via decoded masks)
        assert [p.name for p in ta.pods] == [p.name for p in tb.pods]
        assert ta.holder_row.tolist() == tb.holder_row.tolist()
        for sel in ({"app": "web"}, {"tier": "db"}, {}):
            assert selector_match_mask(sel, ta).tolist() == \
                selector_match_mask(sel, tb).tolist()

    @pytest.mark.parametrize("seed", range(8))
    def test_delta_table_equals_fresh_build(self, seed):
        """Random adds, removes, replacements with changed labels,
        deletion marks, pods bound and gone again, re-snapshotted nodes and
        nodes leaving and joining, one to six of them between two tables:
        after every step the delta-kept table equals a from-scratch
        build_pod_table, and both equal the plain row-by-row loop, field
        for field in row order; and the call left on the path the step
        asks for (tpu_pod_table_calls_total)."""
        rng = random.Random(seed)
        infos, names = {}, []
        spare = [f"n{i}" for i in range(80)]

        def join():
            name = spare.pop(0)
            node = Node(name=name, labels={LABEL_HOSTNAME: name},
                        allocatable={"cpu": 64000, "memory": 64 * GI,
                                     "pods": 110})
            infos[name] = NodeInfo(None if rng.random() < 0.1 else node)
            names.append(name)

        def add(host, p):
            if p.node_name is None:
                p.node_name = host
            infos[host].add_pod(p)

        for _ in range(70):
            join()
        serial = iter(range(10 ** 6))
        for _ in range(150):
            add(rng.choice(names), rand_held_pod(rng, next(serial), names))

        def mutate(op, step):
            held = [(h, p) for h in names for p in infos[h].pods]
            if op == "add":
                add(rng.choice(names),
                    rand_held_pod(rng, next(serial), names))
            elif op == "bump":
                # bound and gone again between two tables: the generation
                # moved twice, the join stamps are what they were
                h = rng.choice(names)
                p = rand_held_pod(rng, next(serial), names)
                add(h, p)
                infos[h].remove_pod(p)
            elif op == "join" and spare:
                join()
                for _ in range(rng.randint(0, 3)):
                    add(names[-1], rand_held_pod(rng, next(serial), names))
            elif op == "leave" and len(names) > 2:
                gone = names.pop(rng.randrange(len(names)))
                del infos[gone]
                spare.append(gone)
            elif op == "resnap":
                # what update_snapshot does to a changed node
                h = rng.choice(names)
                infos[h] = infos[h].clone()
            elif held and op not in ("none", "join", "leave"):
                h, p = rng.choice(held)
                infos[h].remove_pod(p)
                if op == "relabel":      # the store's way: a new object
                    p = p.clone()
                    p.labels = rand_labels(rng)
                    p.labels["step"] = str(step)
                elif op == "inplace":    # same object, then the re-add
                    p.labels["step"] = str(step)
                    p.priority += 1
                elif op == "delete":
                    p = p.clone()
                    p.deleted = True
                if op != "remove":
                    infos[h].add_pod(p)

        def stamps():
            return {h: list(ni.pod_gens) for h, ni in infos.items()
                    if ni.pods}

        enc = NodeStateEncoder()
        was = None               # (generations, stamps, batch) last call
        for step in range(40):
            ops = [rng.choice(["add", "add", "remove", "relabel", "inplace",
                               "delete", "leave", "join", "resnap", "bump",
                               "bump", "none"])
                   for _ in range(rng.choice([1, 1, 1, 2, 3, 6]))]
            for op in ops:
                mutate(op, step)
            b = enc.encode(infos, names)
            calls = table_paths()
            t = enc.pod_table(infos, b)
            (path,) = [k for k, v in table_paths().items() if v != calls[k]]
            now = ({h: ni.generation for h, ni in infos.items()}, stamps(), b)
            if was is None:
                assert path == "built"
            elif list(now[1].items()) == list(was[1].items()):
                # every row where it was: nothing is copied, whatever
                # generations moved and whichever empty nodes came or went
                assert path == ("returned" if now[0] == was[0]
                                and b is was[2] else "shared"), (seed, ops)
            else:
                assert path in ("spliced", "gathered"), (seed, ops)
                # a node whose rows changed, came or went is at most two
                # more pieces; few enough of them are spliced
                k = sum(now[1].get(h) != was[1].get(h)
                        for h in now[1].keys() | was[1].keys())
                if (2 * k + 1) * 16 <= len(infos):
                    assert path == "spliced", (seed, ops, k)
            was = now
            fresh = build_pod_table(infos, b)
            want = plain_rows(infos, b)
            assert table_rows(t) == want, (seed, step, ops)
            assert table_rows(fresh) == want, (seed, step, ops)
            assert t.key_ids.shape == fresh.key_ids.shape
            assert enc.pod_table(infos, b) is t

    @pytest.mark.parametrize("where", [
        "first", "last", "emptied", "first_pod", "swapped", "node_left",
        "node_joined", "two_nodes"])
    def test_splice_at_the_edges(self, where):
        """One or two nodes of 64 change between two tables, at every place
        a splice has an edge case: the call is `spliced` and the table is a
        fresh build's."""
        infos, names, enc = cluster(64, empty=("n7",))
        if where in ("first", "last"):
            host = names[0 if where == "first" else -1]
            infos[host].add_pod(held_pod("new", host))
        elif where == "emptied":
            for p in list(infos["n5"].pods):
                infos["n5"].remove_pod(p)
        elif where == "first_pod":
            infos["n7"].add_pod(held_pod("new", "n7"))
        elif where == "swapped":             # as many rows as before
            infos["n9"].remove_pod(infos["n9"].pods[1])
            infos["n9"].add_pod(held_pod("new", "n9"))
        elif where == "node_left":
            del infos["n11"]
            names.remove("n11")
        elif where == "node_joined":
            infos["n64"] = NodeInfo(plain_node("n64"))
            names.append("n64")
            infos["n64"].add_pod(held_pod("new", "n64"))
        elif where == "two_nodes":           # neighbours: no range between
            infos["n20"].add_pod(held_pod("new-a", "n20"))
            infos["n21"].remove_pod(infos["n21"].pods[0])
        assert checked_table(enc, infos, names)[1] == "spliced"
        assert checked_table(enc, infos, names)[1] == "returned"

    @pytest.mark.parametrize("how", ["many_nodes", "reordered"])
    def test_gathered_is_for_what_a_splice_is_wrong_for(self, how):
        """Every fourth node changed at once, or the snapshot in another
        order: one gather a column, and still a fresh build's table."""
        infos, names, enc = cluster(48)
        if how == "many_nodes":
            for h in names[::4]:
                infos[h].add_pod(held_pod(f"new-{h}", h))
        else:
            infos["n0"] = infos.pop("n0")    # the first node goes last
            infos["n3"].add_pod(held_pod("new", "n3"))
        assert checked_table(enc, infos, names)[1] == "gathered"

    @pytest.mark.parametrize("step,width", [
        ("widest_leaves", 1), ("one_of_two_widest_leaves", 4),
        ("wider_joins", 5), ("widest_leaves_narrower_joins", 2),
        ("widest_leaves_as_wide_joins", 4), ("narrow_leaves", 4)])
    def test_label_width_follows_the_widest_row_left(self, step, width):
        """The label columns are as wide as the widest row of the table, as
        a fresh build's are, through a splice: narrower when the widest row
        left, wider when a wider one joined."""
        wide = {f"k{i}": "v" for i in range(4)}
        infos, names, enc = cluster(96)
        infos["n4"].add_pod(held_pod("wide", "n4", labels=wide))
        if step == "one_of_two_widest_leaves":
            infos["n30"].add_pod(held_pod("wide-2", "n30", labels=wide))
        t, _, _ = checked_table(enc, infos, names)
        assert t.key_ids.shape[1] == 4
        if step == "narrow_leaves":
            infos["n4"].remove_pod(infos["n4"].pods[0])
        elif step != "wider_joins":
            infos["n4"].remove_pod(infos["n4"].pods[-1])
        labels = {"wider_joins": {f"j{i}": "v" for i in range(5)},
                  "widest_leaves_narrower_joins": {"a": "1", "b": "2"},
                  "widest_leaves_as_wide_joins": {
                      f"j{i}": "v" for i in range(4)}}.get(step)
        if labels:
            infos["n40"].add_pod(held_pod("joins", "n40", labels=labels))
        t, path, _ = checked_table(enc, infos, names)
        assert path == "spliced"
        assert t.key_ids.shape[1] == t.val_ids.shape[1] == width

    def test_a_table_handed_out_before_a_splice_reads_what_it_read(self):
        """PodEncoder keeps its table a segment, the victim stack keeps one
        between scans: a later call copies, it never writes into the arrays
        or the pod list an earlier table holds."""
        infos, names, enc = cluster(96)
        t1, _, _ = checked_table(enc, infos, names)
        rows1, pods1 = table_rows(t1), list(t1.pods)
        infos["n6"].add_pod(held_pod("new", "n6", labels={"a": "b", "c": "d"}))
        t2, path, _ = checked_table(enc, infos, names)
        assert path == "spliced" and t2 is not t1
        rows2 = table_rows(t2)
        infos["n6"].remove_pod(infos["n6"].pods[0])
        infos["n30"].remove_pod(infos["n30"].pods[-1])
        t3, path, _ = checked_table(enc, infos, names)
        assert path == "spliced"
        assert table_rows(t1) == rows1 and t1.pods == pods1
        assert table_rows(t2) == rows2
        assert len(t1.pods) + 1 == len(t2.pods) == len(t3.pods) + 2
        # a generation alone shares the columns and the list, and copies
        # nothing: the two tables are one set of arrays
        infos["n2"].set_node(plain_node("n2"))
        t4, path, _ = checked_table(enc, infos, names)
        assert path == "shared"
        assert t4.pods is t3.pods and t4.key_ids is t3.key_ids

    def test_moved_nodes_are_booked_by_what_their_stamps_said(self):
        """A closed loop's pass between two tables (pods bound and deleted
        again on most nodes) and a window's delta (a pod joined here, one
        left there): tpu_pod_table_moved_nodes_total says how many moved
        nodes kept their range, tpu_pod_table_rows_total counts their rows
        as reused."""
        infos, names, enc = cluster(96)

        def moved(want_path):
            _, path, booked = checked_table(enc, infos, names)
            assert path == want_path
            return booked

        for h in names[:40]:
            p = held_pod(f"pass-{h}", h)
            infos[h].add_pod(p)
            infos[h].remove_pod(p)
        assert moved("shared") == {"kept": 40, "changed": 0,
                                   "reused": 120, "extracted": 0}
        infos["n1"].add_pod(held_pod("joined", "n1"))
        infos["n2"].remove_pod(infos["n2"].pods[0])
        p = held_pod("pass", "n3")
        infos["n3"].add_pod(p)
        infos["n3"].remove_pod(p)
        assert moved("spliced") == {"kept": 1, "changed": 2,
                                    "reused": 3 + 3 + 2, "extracted": 1}
        assert moved("returned") == {"kept": 0, "changed": 0,
                                     "reused": 0, "extracted": 0}

    def test_cost_follows_change(self):
        """One pod added to one node of a snapshot: one row is extracted,
        that node's other rows are reused, no other node is looked at; a
        held pod mutated in place is seen once the cache re-adds it."""
        from kubernetes_tpu.cache.cache import SchedulerCache, Snapshot
        from kubernetes_tpu.ops.node_state import POD_TABLE_ROWS
        rng = random.Random(11)
        cache = SchedulerCache()
        names = [f"n{i}" for i in range(12)]
        for name in names:
            cache.add_node(Node(name=name, labels={LABEL_HOSTNAME: name},
                                allocatable={"cpu": 64000, "memory": 64 * GI,
                                             "pods": 110}))
        for j in range(60):
            p = rand_pod(rng, j)
            p.node_name = names[j % len(names)]
            cache.add_pod(p)
        snap = Snapshot()
        enc = NodeStateEncoder()

        def table():
            cache.update_snapshot(snap)
            b = enc.encode(snap.node_infos, names)
            before = {r: POD_TABLE_ROWS.labels(r).value
                      for r in ("extracted", "reused")}
            t = enc.pod_table(snap.node_infos, b)
            assert table_rows(t) == plain_rows(snap.node_infos, b)
            return t, {r: POD_TABLE_ROWS.labels(r).value - v
                       for r, v in before.items()}

        _, moved = table()
        assert moved == {"extracted": 60, "reused": 0}
        extra = rand_pod(rng, 99)
        extra.node_name = "n3"
        cache.add_pod(extra)
        others = len(snap.node_infos["n3"].pods)
        _, moved = table()
        assert moved == {"extracted": 1, "reused": others}
        # bound and gone again, as a rollout's pods are between two tables:
        # the node's generation moved twice and nothing is derived
        cache.remove_pod(extra)
        _, moved = table()
        assert moved == {"extracted": 0, "reused": others}
        victim = snap.node_infos["n5"].pods[0]
        victim.labels["mutated"] = "in-place"
        cache.update_pod(victim, victim)
        t, moved = table()
        assert moved == {"extracted": 1,
                         "reused": len(snap.node_infos["n5"].pods) - 1}
        m = selector_match_mask({"mutated": "in-place"}, t)
        assert [t.pods[i] for i in np.flatnonzero(m)] == [victim]
        _, moved = table()
        assert moved == {"extracted": 0, "reused": 0}

    @pytest.mark.parametrize("n_nodes,joins,path", [
        (6, 20, "gathered"), (400, 6, "spliced")])
    def test_victim_table_after_delta_equals_fresh(self, n_nodes, joins,
                                                   path):
        """The victim stack reads the cached victim columns and t.pods by
        row: after a delta on either path it is a fresh encoder's."""
        from kubernetes_tpu.api.types import PodDisruptionBudget
        rng = random.Random(12)
        infos, names = rand_snapshot(rng, n_nodes=n_nodes, n_pods=0)
        for j in range(50):
            host = rng.choice(names)
            p = rand_held_pod(rng, j, names)
            p.node_name = host
            infos[host].add_pod(p)
        pdbs = [PodDisruptionBudget(
            name="b", namespace="default", disruptions_allowed=0,
            selector=LabelSelector(match_labels=(("app", "web"),)))]
        enc = NodeStateEncoder()
        enc.victim_table(infos, enc.encode(infos, names), pdbs)
        for j in range(50, 50 + joins):
            host = rng.choice(names)
            if infos[host].pods and rng.random() < 0.5:
                infos[host].remove_pod(rng.choice(infos[host].pods))
            p = rand_held_pod(rng, j, names)
            p.node_name = host
            infos[host].add_pod(p)
        b = enc.encode(infos, names)
        calls = table_paths()
        got = enc.victim_table(infos, b, pdbs)
        assert table_paths()[path] == calls[path] + 1
        fresh_enc = NodeStateEncoder()
        want = fresh_enc.victim_table(
            infos, fresh_enc.encode(infos, names), pdbs)
        assert got.P == want.P
        for f in ("cpu", "mem", "eph", "prio", "start", "valid", "viol",
                  "aff", "ports", "scalar", "count", "overflow"):
            assert np.array_equal(getattr(got, f), getattr(want, f)), f
        for name in names:
            assert [id(p) for p in got.slots[name]] == \
                [id(p) for p in want.slots[name]], name


class TestPermutedReencode:
    def test_reordered_enumeration_matches_fresh_encode(self):
        """The permute fast path (same node set, rotated order) must
        produce exactly the arrays a from-scratch encode would."""
        rng = random.Random(9)
        infos, names = rand_snapshot(rng, n_nodes=7, n_pods=25)
        enc = NodeStateEncoder()
        b1 = enc.encode(infos, names)
        order2 = names[3:] + names[:3]
        b2 = enc.encode(infos, order2)
        fresh = NodeStateEncoder().encode(infos, order2)
        assert b2.names == fresh.names
        assert b2.dirty_rows is None     # full re-upload required
        for field in ("valid", "alloc_cpu", "alloc_mem", "alloc_eph",
                      "allowed_pods", "req_cpu", "req_mem", "req_eph",
                      "nz_cpu", "nz_mem", "pod_count"):
            assert getattr(b2, field).tolist() == \
                getattr(fresh, field).tolist(), field
        assert b2.alloc_scalar.tolist() == fresh.alloc_scalar.tolist()
        assert b2.req_scalar.tolist() == fresh.req_scalar.tolist()
        # zone vocab may be ordered differently between encoders; compare
        # decoded zone names per row instead of raw ids
        z2 = [b2.zone_names[i] for i in b2.zone_id[:b2.n_real]]
        zf = [fresh.zone_names[i] for i in fresh.zone_id[:fresh.n_real]]
        assert z2 == zf
