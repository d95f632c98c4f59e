"""REST apiserver + admission + kubectl: the user-facing API surface
(reference: staging/src/k8s.io/apiserver, plugin/pkg/admission/priority,
cmd/kubectl)."""
import io
import json
import threading
import urllib.request

import pytest

from kubernetes_tpu.api.types import (
    Pod, Node, Container, PriorityClass, Affinity, PodAntiAffinity,
    PodAffinityTerm, LabelSelector, Taint, Toleration, LABEL_HOSTNAME,
    NO_SCHEDULE,
)
from kubernetes_tpu.api import serde
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.store.store import (
    Store, PODS, NODES, PRIORITYCLASSES,
)

GI = 1024 ** 3


@pytest.fixture()
def server():
    store = Store()
    with APIServer(store) as srv:
        yield store, srv.url


def req(url, method="GET", body=None):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(url, data=data, method=method,
                               headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(r) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


class TestSerde:
    def test_pod_round_trip_with_nested_spec(self):
        pod = Pod(name="p", labels={"a": "b"},
                  node_selector={"zone": "z1"},
                  affinity=Affinity(pod_anti_affinity=PodAntiAffinity(
                      required=(PodAffinityTerm(
                          label_selector=LabelSelector(
                              match_labels=(("a", "b"),)),
                          topology_key=LABEL_HOSTNAME),))),
                  tolerations=(Toleration(key="k", value="v",
                                          effect=NO_SCHEDULE,
                                          toleration_seconds=5.0),),
                  containers=(Container.make(
                      name="c", requests={"cpu": 100, "memory": GI}),))
        d = serde.to_dict(pod)
        back = serde.from_dict(PODS, json.loads(json.dumps(d)))
        assert back == pod

    def test_node_round_trip(self):
        node = Node(name="n", labels={"z": "1"},
                    taints=(Taint(key="k", effect=NO_SCHEDULE),),
                    allocatable={"cpu": 4000, "memory": GI, "pods": 110})
        back = serde.from_dict(NODES, json.loads(json.dumps(
            serde.to_dict(node))))
        assert back == node

    def test_quoted_forward_ref_fields_rebuild(self):
        """tuple[\"PodCondition\", ...] style annotations: the nested quoted
        name survives get_type_hints as a bare string inside the builtin
        generic — decode must still rebuild the dataclass, not hand back
        raw dicts (regression: PodScheduled conditions arrived as dicts
        over the remote transport)."""
        from kubernetes_tpu.api.types import (PodCondition, POD_SCHEDULED,
                                              CONDITION_FALSE)
        pod = Pod(name="p")
        pod.conditions = (PodCondition(type=POD_SCHEDULED,
                                       status=CONDITION_FALSE,
                                       reason="Unschedulable", message="m"),)
        back = serde.from_dict(PODS, json.loads(json.dumps(
            serde.to_dict(pod))))
        assert isinstance(back.conditions[0], PodCondition)
        assert back.conditions[0].reason == "Unschedulable"
        assert back == pod


class TestRESTSurface:
    def test_crud_and_binding(self, server):
        store, url = server
        with urllib.request.urlopen(f"{url}/healthz") as resp:
            assert resp.status == 200 and resp.read() == b"ok"
        st, created = req(f"{url}/api/v1/nodes", "POST", serde.to_dict(Node(
            name="n0", allocatable={"cpu": 4000, "memory": GI, "pods": 10})))
        assert st == 201 and created["resource_version"] > 0
        st, created = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="p0", containers=(Container.make(
                name="c", requests={"cpu": 100}),))))
        assert st == 201
        st, _ = req(f"{url}/api/v1/pods/default/p0/binding", "POST",
                    {"node": "n0"})
        assert st == 201
        st, got = req(f"{url}/api/v1/pods/default/p0")
        assert got["node_name"] == "n0"
        st, lst = req(f"{url}/api/v1/pods")
        assert len(lst["items"]) == 1 and lst["resourceVersion"] > 0
        st, _ = req(f"{url}/api/v1/pods/default/p0", "DELETE")
        assert st == 200
        with pytest.raises(urllib.error.HTTPError) as e:
            req(f"{url}/api/v1/pods/default/p0")
        assert e.value.code == 404

    def test_update_conflict(self, server):
        store, url = server
        _, created = req(f"{url}/api/v1/nodes", "POST",
                         serde.to_dict(Node(name="n0")))
        stale = dict(created)
        created["unschedulable"] = True
        st, _ = req(f"{url}/api/v1/nodes/n0", "PUT", created)
        assert st == 200
        stale["unschedulable"] = False
        with pytest.raises(urllib.error.HTTPError) as e:
            req(f"{url}/api/v1/nodes/n0", "PUT", stale)
        assert e.value.code == 409

    def test_watch_stream(self, server):
        store, url = server
        got = []
        done = threading.Event()

        def watcher():
            with urllib.request.urlopen(
                    f"{url}/api/v1/pods?watch=true") as resp:
                for raw in resp:
                    line = raw.strip()
                    if line:
                        got.append(json.loads(line))
                        if len(got) >= 2:
                            done.set()
                            return

        t = threading.Thread(target=watcher, daemon=True)
        t.start()
        import time
        time.sleep(0.2)
        store.create(PODS, Pod(name="w0"))
        store.delete(PODS, "default/w0")
        assert done.wait(5), f"watch delivered {got}"
        assert [e["type"] for e in got] == ["ADDED", "DELETED"]
        assert got[0]["object"]["name"] == "w0"

    def test_watch_byte_ring_shared_class(self, server):
        """Round 20: two HTTP watchers on the same ?selector ride ONE
        subscription class server-side — the watch route streams
        pre-encoded lines out of the shared byte ring (wire shape
        unchanged from the per-watcher encode path), and the store books
        the second stream's lines as shared-ring hits, not re-encodes.

        Waits on the subscription and on delivery, never on the clock.
        The second stream joins once the first has been served and replays
        from the first's resourceVersion, so both its lines MUST come out
        of the ring: two streams woken by one event race for it (the pick
        is under the core's lock, the encode is not), and which of them
        encodes is then the scheduler's choice, not the ring's."""
        store, url = server
        got1, got2 = [], []

        def watcher(got, since):
            q = "" if since is None else f"&resourceVersion={since}"
            with urllib.request.urlopen(
                    f"{url}/api/v1/pods?watch=true&selector=app%3Da{q}"
                    ) as resp:
                for raw in resp:
                    line = raw.strip()
                    if line:
                        got.append(json.loads(line))
                        if len(got) >= 4:
                            return

        def wait_for(pred, what):
            import time
            deadline = time.monotonic() + 10
            while not pred():
                assert time.monotonic() < deadline, (what, got1, got2)
                time.sleep(0.002)

        def members():
            return sum(c["members"]
                       for c in store.watch_plane_state()["classes"])

        threading.Thread(target=watcher, args=(got1, None),
                         daemon=True).start()
        wait_for(lambda: members() == 1, "first stream subscribed")
        _, rv0 = store.list(PODS)
        store.create(PODS, Pod(name="b0"))
        store.delete(PODS, "default/b0")
        wait_for(lambda: len(got1) == 2, "first stream served")
        st = store.watch_plane_state()
        assert (st["shared_hits"], st["line_encodes"]) == (0, 2), st
        # the classmate: every line a serialize-once cache hit
        threading.Thread(target=watcher, args=(got2, rv0),
                         daemon=True).start()
        wait_for(lambda: len(got2) == 2, "second stream served")
        assert members() == 2
        st = store.watch_plane_state()
        assert (st["shared_hits"], st["line_encodes"]) == (2, 2), st
        # both live on the same events: the same bytes, and every line
        # either a ring hit or the encode that filled the ring
        store.create(PODS, Pod(name="b1"))
        store.delete(PODS, "default/b1")
        wait_for(lambda: len(got1) == 4 and len(got2) == 4,
                 "both streams served")
        assert got1 == got2
        assert [e["type"] for e in got1] == ["ADDED", "DELETED"] * 2
        assert got1[0]["object"]["name"] == "b0"
        assert got1[0]["resourceVersion"] > 0
        st = store.watch_plane_state()
        assert st["shared_hits"] >= 2, st
        assert st["line_encodes"] >= 4, st
        assert st["shared_hits"] + st["line_encodes"] == 8, st

    def test_priority_admission(self, server):
        store, url = server
        req(f"{url}/api/v1/priorityclasses", "POST",
            serde.to_dict(PriorityClass(name="high", value=1000)))
        req(f"{url}/api/v1/priorityclasses", "POST",
            serde.to_dict(PriorityClass(name="base", value=7,
                                        global_default=True)))
        _, p = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="p1", priority_class_name="high")))
        assert p["priority"] == 1000
        _, p = req(f"{url}/api/v1/pods", "POST",
                   serde.to_dict(Pod(name="p2")))
        assert p["priority"] == 7 and p["priority_class_name"] == "base"
        with pytest.raises(urllib.error.HTTPError) as e:
            req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
                name="p3", priority_class_name="nope")))
        assert e.value.code == 422


class TestKubectl:
    def _run(self, url, *argv):
        import contextlib
        from kubernetes_tpu.cmd import kubectl
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = kubectl.main(["--server", url, *argv])
        assert rc == 0
        return out.getvalue()

    def test_get_describe_delete_drain(self, server, tmp_path):
        store, url = server
        store.create(NODES, Node(
            name="n0", allocatable={"cpu": 4000, "memory": GI, "pods": 10}))
        manifest = {"items": [
            {"kind": "pods", "name": "web-1", "labels": {"app": "web"},
             "containers": [{"name": "c",
                             "requests": [["cpu", 100]]}]},
        ]}
        f = tmp_path / "m.json"
        f.write_text(json.dumps(manifest))
        out = self._run(url, "create", "-f", str(f))
        assert "pods/web-1 created" in out
        store.bind_pod("default/web-1", "n0")
        out = self._run(url, "get", "pods")
        assert "web-1" in out and "n0" in out
        out = self._run(url, "get", "nodes")
        assert "n0" in out and "Ready" in out
        out = self._run(url, "describe", "pods", "default/web-1")
        assert "node_name: n0" in out
        out = self._run(url, "cordon", "n0")
        assert "cordoned" in out
        assert store.get(NODES, "n0").unschedulable
        out = self._run(url, "drain", "n0")
        assert "pod/default/web-1 evicted" in out
        assert not store.list(PODS)[0]
        out = self._run(url, "uncordon", "n0")
        assert not store.get(NODES, "n0").unschedulable


class TestClusterInAProcess:
    """kubeadm-analog bootstrap (cmd/cluster.py): every control-plane
    component live over one store, driven purely through kubectl + REST —
    ReplicaSet create -> controller creates pods -> scheduler binds ->
    hollow kubelets run them -> disruption controller reconciles the PDB."""

    def test_kubectl_driven_end_to_end(self, tmp_path):
        from kubernetes_tpu.cmd.cluster import Cluster
        from kubernetes_tpu.cmd import kubectl
        import contextlib

        def kc(url, *argv):
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = kubectl.main(["--server", url, *argv])
            assert rc == 0
            return out.getvalue()

        with Cluster(n_nodes=6, api_port=0, use_tpu=False,
                     kubelet_interval=0.05) as cluster:
            url = cluster.url
            manifest = {"items": [
                {"kind": "replicasets", "name": "web",
                 "selector": {"match_labels": [["app", "web"]]},
                 "replicas": 4},
                {"kind": "poddisruptionbudgets", "name": "web-pdb",
                 "selector": {"match_labels": [["app", "web"]]},
                 "min_available": 3},
            ]}
            f = tmp_path / "m.json"
            f.write_text(json.dumps(manifest))
            kc(url, "create", "-f", str(f))

            def all_running():
                _, lst = req(f"{url}/api/v1/pods")
                pods = lst["items"]
                return len(pods) == 4 and all(
                    p["node_name"] and p["phase"] == "Running"
                    for p in pods)
            assert cluster.wait_for(all_running, timeout=15), \
                req(f"{url}/api/v1/pods")[1]

            def pdb_reconciled():
                _, pdb = req(f"{url}/api/v1/poddisruptionbudgets/default/web-pdb")
                return (pdb["current_healthy"], pdb["disruptions_allowed"]) \
                    == (4, 1)
            assert cluster.wait_for(pdb_reconciled, timeout=10)

            # kill a pod through kubectl: the RS controller replaces it and
            # the scheduler + kubelet bring it back to Running
            _, lst = req(f"{url}/api/v1/pods")
            victim = lst["items"][0]
            kc(url, "delete", "pods",
               f"{victim['namespace']}/{victim['name']}")
            assert cluster.wait_for(all_running, timeout=15)
            out = kc(url, "get", "replicasets")
            assert "web" in out


class TestAdmissionDefaults:
    def test_default_toleration_seconds_and_limit_ranger(self, server):
        from kubernetes_tpu.controllers.nodelifecycle import (
            TAINT_NOT_READY, TAINT_UNREACHABLE)
        store, url = server
        _, p = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="bare", containers=(Container.make(name="c"),))))
        # DefaultTolerationSeconds: both NoExecute tolerations, 300s
        tols = {t["key"]: t for t in p["tolerations"]}
        assert set(tols) == {TAINT_NOT_READY, TAINT_UNREACHABLE}
        assert all(t["toleration_seconds"] == 300.0 and
                   t["effect"] == "NoExecute" for t in tols.values())
        # LimitRanger: request defaults applied
        reqs = dict(map(tuple, p["containers"][0]["requests"]))
        assert reqs == {"cpu": 100, "memory": 200 * 1024 ** 2}
        # explicit values survive untouched
        _, p = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="explicit",
            tolerations=(Toleration(key=TAINT_NOT_READY, op="Exists",
                                    effect="NoExecute",
                                    toleration_seconds=7.0),),
            containers=(Container.make(name="c",
                                       requests={"cpu": 900,
                                                 "memory": GI}),))))
        tols = {t["key"]: t for t in p["tolerations"]}
        assert tols[TAINT_NOT_READY]["toleration_seconds"] == 7.0
        assert dict(map(tuple, p["containers"][0]["requests"]))["cpu"] == 900


class TestKubectlApply:
    def test_apply_creates_then_configures(self, server, tmp_path):
        store, url = server
        import contextlib
        from kubernetes_tpu.cmd import kubectl

        def kc(*argv):
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                assert kubectl.main(["--server", url, *argv]) == 0
            return out.getvalue()

        f = tmp_path / "rs.json"
        f.write_text(json.dumps({"kind": "replicasets", "name": "web",
                                 "selector": {"match_labels": [["app", "web"]]},
                                 "replicas": 2}))
        assert "created" in kc("apply", "-f", str(f))
        from kubernetes_tpu.store.store import REPLICASETS
        assert store.get(REPLICASETS, "default/web").replicas == 2
        f.write_text(json.dumps({"kind": "replicasets", "name": "web",
                                 "selector": {"match_labels": [["app", "web"]]},
                                 "replicas": 5}))
        assert "configured" in kc("apply", "-f", str(f))
        assert store.get(REPLICASETS, "default/web").replicas == 5


class TestWatchResume:
    def test_resume_from_rv_and_410_gone(self, server):
        store, url = server
        # generate history
        for j in range(5):
            store.create(PODS, Pod(name=f"h{j}"))
        rv = store.resource_version()
        store.create(PODS, Pod(name="after"))
        # resume from rv: only the later event arrives
        got = []
        def watcher():
            with urllib.request.urlopen(
                    f"{url}/api/v1/pods?watch=true&resourceVersion={rv}") as r:
                for raw in r:
                    line = raw.strip()
                    if line:
                        got.append(json.loads(line))
                        return
        t = threading.Thread(target=watcher, daemon=True)
        t.start()
        t.join(5)
        assert got and got[0]["object"]["name"] == "after"
        # a resume point older than the log window is 410 Gone -> re-list
        small = Store(watch_log_size=4)
        with APIServer(small) as srv2:
            for j in range(10):
                small.create(PODS, Pod(name=f"x{j}"))
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    f"{srv2.url}/api/v1/pods?watch=true&resourceVersion=1")
            assert e.value.code == 410


class TestDrainHonorsPDB:
    """drain consults the disruption controller's reconciled
    disruptions_allowed like the eviction subresource (reference:
    pkg/registry/core/pod/rest/eviction.go); --disable-eviction keeps the
    unconditional-delete mode."""

    def _drain(self, url, *argv):
        import contextlib
        from kubernetes_tpu.cmd import kubectl
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = kubectl.main(["--server", url, "drain", *argv])
        return rc, out.getvalue(), err.getvalue()

    def test_drain_refuses_when_budget_exhausted(self, server):
        from kubernetes_tpu.api.types import PodDisruptionBudget
        from kubernetes_tpu.store.store import PDBS
        store, url = server
        store.create(NODES, Node(
            name="n0", allocatable={"cpu": 4000, "memory": GI, "pods": 10}))
        # PDB allows ONE disruption across the two web pods
        store.create(PDBS, PodDisruptionBudget(
            name="web-pdb",
            selector=LabelSelector(match_labels=(("app", "web"),)),
            min_available=1, disruptions_allowed=1,
            current_healthy=2, desired_healthy=1, expected_pods=2))
        for n in ("w0", "w1"):
            store.create(PODS, Pod(
                name=n, node_name="n0", labels={"app": "web"},
                containers=(Container.make(name="c"),)))
        # an unbudgeted pod drains freely
        store.create(PODS, Pod(
            name="loose", node_name="n0", labels={"app": "batch"},
            containers=(Container.make(name="c"),)))
        rc, out, err = self._drain(url, "n0")
        assert rc == 1            # one eviction refused
        assert "pod/default/loose evicted" in out
        assert out.count("evicted") == 2   # loose + exactly one web pod
        assert "violate the pod's disruption budget" in err
        remaining = [p.name for p in store.list(PODS)[0]]
        assert len(remaining) == 1 and remaining[0].startswith("w")
        assert store.get(NODES, "n0").unschedulable
        # --disable-eviction clears the survivor unconditionally
        rc, out, _err = self._drain(url, "n0", "--disable-eviction")
        assert rc == 0 and not store.list(PODS)[0]


class TestAdmissionOnPut:
    """The chain runs on UPDATES (VERDICT r03 weak #6): the create-then-PUT
    escape hatch around LimitRanger/quota is closed."""

    def _put(self, url, kind, obj, user=None):
        data = json.dumps(serde.to_dict(obj)).encode()
        headers = {"Content-Type": "application/json"}
        if user:
            headers["X-Remote-User"] = user
        r = urllib.request.Request(f"{url}/api/v1/{kind}/{obj.key}",
                                   data=data, method="PUT", headers=headers)
        try:
            with urllib.request.urlopen(r) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    def test_oversized_put_rejected_by_quota(self, server):
        from kubernetes_tpu.api.types import ResourceQuota
        from kubernetes_tpu.store.store import RESOURCEQUOTAS
        store, url = server
        store.create(RESOURCEQUOTAS, ResourceQuota(
            name="q", hard={"cpu": 500}))
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="p", containers=(Container.make(
                name="c", requests={"cpu": 400, "memory": GI}),))))
        assert code == 201
        big = serde.from_dict("pods", body)
        big.containers = (Container.make(
            name="c", requests={"cpu": 2000, "memory": GI}),)
        code, body = self._put(url, "pods", big)
        assert code == 422 and "exceeded quota" in body["message"]
        # the rejected delta must not leak into usage
        assert store.get(RESOURCEQUOTAS, "default/q").used["cpu"] == 400
        # a conforming PUT (shrink) lands and replenishes
        small = store.get(PODS, "default/p")
        small.containers = (Container.make(
            name="c", requests={"cpu": 100, "memory": GI}),)
        code, _ = self._put(url, "pods", small)
        assert code == 200
        assert store.get(RESOURCEQUOTAS, "default/q").used["cpu"] == 100

    def test_put_reapplies_limitranger_defaults(self, server):
        store, url = server
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="d", containers=(Container.make(name="c"),))))
        assert code == 201
        stripped = serde.from_dict("pods", body)
        stripped.containers = (Container(name="c", requests=()),)
        code, body = self._put(url, "pods", stripped)
        assert code == 200
        reqs = dict(store.get(PODS, "default/d").containers[0].requests)
        assert reqs.get("cpu") == 100 and "memory" in reqs


class TestNodeRestriction:
    def test_kubelet_identity_limited_to_own_node(self, server):
        store, url = server
        for nm in ("n0", "n1"):
            store.create(NODES, Node(
                name=nm, allocatable={"cpu": 1000, "memory": GI, "pods": 10}))
        helper = TestAdmissionOnPut()
        own = store.get(NODES, "n0")
        own.unschedulable = True
        code, _ = helper._put(url, "nodes", own, user="system:node:n0")
        assert code == 200
        other = store.get(NODES, "n1")
        other.unschedulable = True
        code, body = helper._put(url, "nodes", other, user="system:node:n0")
        assert code == 422 and "not allowed" in body["message"]
        # a node identity may not create pods bound to ANOTHER node
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="mirror", node_name="n1",
            containers=(Container.make(name="c"),))))
        assert code == 201   # no identity: unrestricted
        data = json.dumps(serde.to_dict(Pod(
            name="mirror2", node_name="n1",
            containers=(Container.make(name="c"),)))).encode()
        r = urllib.request.Request(
            f"{url}/api/v1/pods", data=data, method="POST",
            headers={"Content-Type": "application/json",
                     "X-Remote-User": "system:node:n0"})
        try:
            urllib.request.urlopen(r)
            assert False, "cross-node mirror pod must be rejected"
        except urllib.error.HTTPError as e:
            assert e.code == 422


class TestPodTolerationRestriction:
    def test_namespace_whitelist_and_defaults(self, server):
        from kubernetes_tpu.api.types import Namespace, Toleration
        from kubernetes_tpu.store.store import NAMESPACES
        store, url = server
        store.create(NAMESPACES, Namespace(
            name="locked",
            annotations={
                "scheduler.alpha.kubernetes.io/defaultTolerations":
                    '[{"key": "team", "operator": "Equal", "value": "a", '
                    '"effect": "NoSchedule"}]',
                "scheduler.alpha.kubernetes.io/tolerationsWhitelist":
                    '[{"key": "team", "operator": "Equal", "value": "a", '
                    '"effect": "NoSchedule"}]',
            }))
        ok = Pod(name="good", namespace="locked",
                 containers=(Container.make(name="c"),))
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(ok))
        assert code == 201
        stored = store.get(PODS, "locked/good")
        assert any(t.key == "team" and t.value == "a"
                   for t in stored.tolerations), "defaults merged"
        bad = Pod(name="bad", namespace="locked",
                  tolerations=(Toleration(key="other", value="x",
                                          effect="NoSchedule"),),
                  containers=(Container.make(name="c"),))
        data = json.dumps(serde.to_dict(bad)).encode()
        r = urllib.request.Request(f"{url}/api/v1/pods", data=data,
                                   method="POST",
                                   headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(r)
            assert False, "non-whitelisted toleration must be rejected"
        except urllib.error.HTTPError as e:
            assert e.code == 422


class TestAntiAffinityAdmission:
    def test_non_hostname_required_anti_affinity_rejected(self, server):
        store, url = server
        bad = Pod(name="wide", affinity=Affinity(
            pod_anti_affinity=PodAntiAffinity(required=(
                PodAffinityTerm(
                    label_selector=LabelSelector(
                        match_labels=(("app", "x"),)),
                    topology_key="failure-domain.beta.kubernetes.io/zone"),
            ))), containers=(Container.make(name="c"),))
        data = json.dumps(serde.to_dict(bad)).encode()
        r = urllib.request.Request(f"{url}/api/v1/pods", data=data,
                                   method="POST",
                                   headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(r)
            assert False, "zone-wide required anti-affinity must be rejected"
        except urllib.error.HTTPError as e:
            assert e.code == 422
        ok = Pod(name="narrow", affinity=Affinity(
            pod_anti_affinity=PodAntiAffinity(required=(
                PodAffinityTerm(
                    label_selector=LabelSelector(
                        match_labels=(("app", "x"),)),
                    topology_key=LABEL_HOSTNAME),
            ))), containers=(Container.make(name="c"),))
        code, _ = req(f"{url}/api/v1/pods", "POST", serde.to_dict(ok))
        assert code == 201


class TestEventRateLimit:
    def test_event_burst_throttled(self):
        from kubernetes_tpu.apiserver.admission import (
            AdmissionChain, AdmissionError, EventRateLimit)
        from kubernetes_tpu.api.types import EventRecord
        from kubernetes_tpu.store.store import Store, EVENTS
        store = Store()
        fake_now = [0.0]
        chain = AdmissionChain(plugins=[
            EventRateLimit(qps=10, burst=3, clock=lambda: fake_now[0])])
        def mk(i):
            return EventRecord(name=f"e{i}", involved_kind="Pod",
                               involved_key=f"default/p{i}", type="Normal",
                               reason="Scheduled")
        for i in range(3):
            chain.admit(EVENTS, mk(i), store)
        with pytest.raises(AdmissionError):
            chain.admit(EVENTS, mk(3), store)
        fake_now[0] += 0.2    # 2 tokens replenish
        chain.admit(EVENTS, mk(4), store)


class TestAdmissionPutBypassesClosed:
    """The PUT-path bypass vectors from review: old-binding hijack,
    whitelist/anti-affinity injection, over-cap shrink blocking."""

    def test_kubelet_cannot_steal_other_nodes_pod(self, server):
        store, url = server
        store.create(PODS, Pod(name="victim", node_name="n1",
                               containers=(Container.make(name="c"),)))
        helper = TestAdmissionOnPut()
        stolen = store.get(PODS, "default/victim")
        stolen.node_name = "n0"     # rewrite the binding in the body
        code, body = helper._put(url, "pods", stolen, user="system:node:n0")
        assert code == 422 and "not allowed" in body["message"]
        unbound = store.get(PODS, "default/victim")
        unbound.node_name = ""      # unbinding is a modification too
        code, _ = helper._put(url, "pods", unbound, user="system:node:n0")
        assert code == 422

    def test_put_cannot_inject_forbidden_toleration(self, server):
        from kubernetes_tpu.api.types import Namespace, Toleration
        from kubernetes_tpu.store.store import NAMESPACES
        store, url = server
        store.create(NAMESPACES, Namespace(
            name="locked",
            annotations={
                "scheduler.alpha.kubernetes.io/tolerationsWhitelist": "[]"}))
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="p", namespace="locked",
            containers=(Container.make(name="c"),))))
        assert code == 201
        helper = TestAdmissionOnPut()
        hacked = store.get(PODS, "locked/p")
        hacked.tolerations = hacked.tolerations + (
            Toleration(key="smuggled", value="x", effect="NoSchedule"),)
        code, body = helper._put(url, "pods", hacked)
        assert code == 422 and "whitelist" in body["message"]
        # re-PUT with only the create-time (cluster-default) tolerations: ok
        same = store.get(PODS, "locked/p")
        same.labels["touch"] = "1"
        code, _ = helper._put(url, "pods", same)
        assert code == 200

    def test_put_cannot_inject_zone_anti_affinity(self, server):
        store, url = server
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="p", containers=(Container.make(name="c"),))))
        assert code == 201
        helper = TestAdmissionOnPut()
        hacked = store.get(PODS, "default/p")
        hacked.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(
            required=(PodAffinityTerm(
                label_selector=LabelSelector(match_labels=(("a", "b"),)),
                topology_key="failure-domain.beta.kubernetes.io/zone"),)))
        code, _ = helper._put(url, "pods", hacked)
        assert code == 422

    def test_shrinking_put_allowed_when_over_cap(self, server):
        """An admin lowering hard caps below current usage must not block
        the shrinking updates that recover the namespace."""
        from kubernetes_tpu.api.types import ResourceQuota
        from kubernetes_tpu.store.store import RESOURCEQUOTAS
        store, url = server
        store.create(RESOURCEQUOTAS, ResourceQuota(
            name="q", hard={"cpu": 1000}))
        code, body = req(f"{url}/api/v1/pods", "POST", serde.to_dict(Pod(
            name="p", containers=(Container.make(
                name="c", requests={"cpu": 600, "memory": GI}),))))
        assert code == 201
        # cap lowered below usage
        def lower(cur):
            cur.hard = {"cpu": 500}
            return cur
        store.guaranteed_update(RESOURCEQUOTAS, "default/q", lower)
        helper = TestAdmissionOnPut()
        shrink = store.get(PODS, "default/p")
        shrink.containers = (Container.make(
            name="c", requests={"cpu": 300, "memory": GI}),)
        code, _ = helper._put(url, "pods", shrink)
        assert code == 200
        assert store.get(RESOURCEQUOTAS, "default/q").used["cpu"] == 300


class TestServiceAccountAdmission:
    """plugin/pkg/admission/serviceaccount: pods default to the namespace's
    'default' account; a named account must exist."""

    def _serve(self):
        from kubernetes_tpu.apiserver.server import APIServer
        store = Store()
        return store, APIServer(store)

    def test_defaults_to_default_account(self):
        from kubernetes_tpu.store.remote import RemoteStore
        store, srv = self._serve()
        with srv:
            RemoteStore(srv.url).create(PODS, Pod(
                name="p1", containers=(Container.make(
                    name="c", requests={"cpu": 100}),)))
        assert store.get(PODS, "default/p1").service_account_name == "default"

    def test_named_account_must_exist(self):
        from kubernetes_tpu.store.remote import RemoteStore, APIStatusError
        from kubernetes_tpu.store.store import SERVICEACCOUNTS
        from kubernetes_tpu.api.types import ServiceAccount
        import pytest as _pytest
        store, srv = self._serve()
        with srv:
            remote = RemoteStore(srv.url)
            bad = Pod(name="bad", service_account_name="robot",
                      containers=(Container.make(
                          name="c", requests={"cpu": 100}),))
            with _pytest.raises(APIStatusError) as ei:
                remote.create(PODS, bad)
            assert ei.value.code == 422
            store.create(SERVICEACCOUNTS, ServiceAccount(name="robot"))
            remote.create(PODS, bad)
        assert store.get(PODS, "default/bad").service_account_name == "robot"
