"""The one span API inside the bind path (`obs.trace.span` / `begin`): a span
is recorded once and lands in the ring and, while a `jax.profiler` session
runs, in the profiler's trace under the same name, nesting and window
number; spans are per pump, per window and per commit wave (and, inside a
pump, per poll batch, index pass and handler run; inside an encode, per
pod-table call, per selector group's count pass and per spread carry), never
per pod; a pump's deliveries are counted by whether a handler took the run
whole and the pod table's calls by the path they left on; the kernels'
stages carry `jax.named_scope` names; compiles are counted by program."""
import glob
import os
import re

import pytest

from kubernetes_tpu import obs
from kubernetes_tpu.api.types import Container, Node, Pod, Service
from kubernetes_tpu.ops import node_state as NS
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store.informer import DELIVERED, SharedInformer
from kubernetes_tpu.store.store import NODES, PODS, SERVICES, Store

GI = 1024 ** 3

# the program's spans on the bind path of a plain burst, by the layer
# boundary each sits on (PERF.md section 3 lists them with their metrics)
BIND_PATH_SPANS = {
    "pump.pods", "burst.plan", "burst.snapshot", "burst.encode",
    "burst.encode.nodes", "burst.encode.pods", "burst.dispatch",
    "burst.fetch", "burst.wave.commit", "burst.commit.cache",
    "burst.commit.store", "burst.commit.fanout", "burst.commit.finish"}
# what a pump opens inside `pump.pods`: a poll and an index pass a batch, and
# one span per run of same-type events and handler the scheduler registered
PUMP_CHILDREN = {
    "pump.poll", "pump.index", "pump.cache.added", "pump.queue.added",
    "pump.cache.modified", "pump.queue.modified"}
# what an encode opens where a Service selects the pods: the pod table's
# upkeep and a count pass a selector group inside `burst.encode.pods`, the
# carry's assembly inside `burst.encode`
ENCODE_CHILDREN = {"burst.encode.table": "burst.encode.pods",
                   "burst.encode.count": "burst.encode.pods",
                   "burst.encode.carry": "burst.encode"}
TABLE_PATHS = ("returned", "shared", "spliced", "gathered", "built")


def mknode(name: str) -> Node:
    return Node(name=name, allocatable={"cpu": 64000, "memory": 256 * GI,
                                        "pods": 110})


def mkpod(name: str, cpu: int = 100, **kw) -> Pod:
    return Pod(name=name, labels={"app": "x"},
               containers=(Container.make(name="c", requests={"cpu": cpu}),),
               **kw)


def make_sched(n_nodes: int = 4):
    store = Store()
    for i in range(n_nodes):
        store.create(NODES, mknode(f"n{i}"))
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=100)
    sched.sync()
    return store, sched


def drain(sched, max_pods: int = 128) -> int:
    sched.pump()
    bound = 0
    while True:
        n = sched.schedule_burst(max_pods=max_pods)
        if n == 0:
            break
        bound += n
    sched.pump()
    return bound


def delivered() -> dict:
    """{(path, kind, handler): events} of the delivery counter."""
    return {k: c.value for k, c in DELIVERED._children.items()}


def moved(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in delivered().items()
            if v - before.get(k, 0)}


def burst(store, sched, tag: str, n: int) -> list[dict]:
    """One window of `n` identical pods; the ring's spans of it."""
    for j in range(n):
        store.create(PODS, mkpod(f"{tag}-{j}"))
    obs.trace.clear()
    assert drain(sched) == n
    return obs.trace.events()


def table_calls() -> dict:
    """{path: calls} of `tpu_pod_table_calls_total`, every path listed."""
    return {p: NS.POD_TABLE_CALLS.labels(p).value for p in TABLE_PATHS}


def make_service_sched(k: int = 8, n_nodes: int = 8):
    """`k` Services, a resident replica of each, and a scheduler whose
    drain pass holds every pending pod."""
    store = Store()
    for i in range(n_nodes):
        store.create(NODES, mknode(f"n{i}"))
    for j in range(k):
        store.create(SERVICES, Service(name=f"svc-{j}",
                                       selector={"app": f"svc-{j}"}))
        store.create(PODS, Pod(name=f"res-{j}", node_name=f"n{j % n_nodes}",
                               labels={"app": f"svc-{j}"},
                               containers=mkpod("x").containers))
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=100)
    sched.sync()
    return store, sched


def service_burst(store, sched, tag: str, n: int, k: int = 8) -> list[dict]:
    """One drain pass of `n` pods, `k` Services' interleaved pod by pod; the
    ring's spans of it."""
    for j in range(n):
        store.create(PODS, Pod(name=f"{tag}-{j}",
                               labels={"app": f"svc-{j % k}"},
                               containers=mkpod("x").containers))
    obs.trace.clear()
    assert drain(sched, max_pods=512) == n
    return obs.trace.events()


class TestSpanApi:
    def test_begin_end_pair_records_parent_window_and_late_args(self):
        obs.trace.clear()
        w = obs.trace.next_window()
        with obs.trace.span("outer"):
            sp = obs.trace.begin("inner", cat="device", rows=3)
            t1 = sp.end(events=7)
        assert sp.t0 <= t1 == sp.t1
        inner, outer = obs.trace.events()
        assert inner["name"] == "inner" and inner["cat"] == "device"
        assert inner["args"] == {"rows": 3, "events": 7, "parent": "outer",
                                 "window": w}
        assert outer["args"] == {"window": w}
        assert obs.trace.next_window() > w

    def test_cancelled_span_leaves_no_record_and_restores_parent(self):
        obs.trace.clear()
        with obs.trace.span("outer"):
            obs.trace.begin("empty").cancel()
            with obs.trace.span("after"):
                pass
        names = [e["name"] for e in obs.trace.events()]
        assert names == ["after", "outer"]
        assert obs.trace.events()[0]["args"]["parent"] == "outer"

    def test_windows_are_per_thread(self):
        import threading
        def window_now() -> int:
            with obs.trace.span("probe") as sp:
                pass
            return sp._window

        w = obs.trace.next_window()
        seen = []
        t = threading.Thread(
            target=lambda: seen.append((window_now(),
                                        obs.trace.next_window())))
        t.start()
        t.join(5.0)
        assert not t.is_alive()
        assert seen[0][0] == 0 and seen[0][1] > w
        assert window_now() == w

    def test_span_error_still_closes(self):
        obs.trace.clear()
        with pytest.raises(ValueError):
            with obs.trace.span("outer"):
                with obs.trace.span("boom"):
                    raise ValueError("x")
        assert [e["name"] for e in obs.trace.events()] == ["boom", "outer"]
        with obs.trace.span("next"):
            pass
        assert "parent" not in obs.trace.events()[-1].get("args", {})


class TestBindPathSpans:
    def test_plain_burst_opens_every_layer_boundary_once_per_window(self):
        store, sched = make_sched()
        evs = burst(store, sched, "a", 6)
        names = [e["name"] for e in evs]
        assert BIND_PATH_SPANS <= set(names), BIND_PATH_SPANS - set(names)
        # burst.encode host, burst.fetch device (the fetch waits for the
        # device): the attribution test_obs pins
        cats = {e["name"]: e["cat"] for e in evs}
        assert cats["burst.encode"] == "host"
        assert cats["burst.fetch"] == "device"
        # one window: every burst.* span carries its number, and the pump
        # that digests its binds carries it too
        plan = [e for e in evs if e["name"] == "burst.plan"]
        assert len(plan) == 1 and plan[0]["args"]["pods"] == 6
        w = plan[0]["args"]["window"]
        for e in evs:
            if e["name"].startswith(("burst.", "store.")):
                assert e["args"]["window"] == w, e
        assert [e["args"].get("window", 0) for e in evs
                if e["name"] == "pump.pods"][-1] >= w    # the binds' pump
        # nesting, by the recorded parent
        parent = {e["name"]: e["args"].get("parent") for e in evs}
        assert parent["burst.plan"] is None
        assert parent["burst.snapshot"] == "burst.plan"
        assert parent["burst.encode"] == "burst.plan"
        assert parent["burst.encode.nodes"] == "burst.encode"
        assert parent["burst.encode.pods"] == "burst.encode"
        assert parent["burst.dispatch"] == "burst.plan"
        assert parent["burst.wave.commit"] == "burst.plan"
        for leaf in ("cache", "store", "fanout", "finish"):
            assert parent[f"burst.commit.{leaf}"] == "burst.wave.commit"

    def test_pump_span_tallies_events_by_type_and_idle_pump_is_silent(self):
        store, sched = make_sched()
        evs = burst(store, sched, "t", 5)
        pumps = [e["args"] for e in evs if e["name"] == "pump.pods"]
        assert pumps[0]["events"] == 5 and pumps[0]["added"] == 5
        assert sum(p.get("modified", 0) for p in pumps) == 5   # the binds
        obs.trace.clear()
        assert sched.pump() == 0
        assert sched.schedule_burst(max_pods=16) == 0
        assert obs.trace.events() == []     # idle tick: nothing recorded
        store.delete_many(PODS, [f"default/t-{j}" for j in range(5)])
        sched.pump()
        (gone,) = [e["args"] for e in obs.trace.events()
                   if e["name"] == "pump.pods"]
        assert gone["events"] == 5 and gone["deleted"] == 5

    def test_pump_children_nest_under_the_pump_and_leave_it_its_self_time(
            self):
        """Every child lies inside its `pump.pods`, beside and not over its
        siblings, under the pump's window: the children's durations plus
        the pump's self time are the pump's duration."""
        store, sched = make_sched()
        burst(store, sched, "c", 6)
        store.delete_many(PODS, [f"default/c-{j}" for j in range(6)])
        sched.pump()
        evs = [e for e in obs.trace.events() if e["name"].startswith("pump.")]
        names = {e["name"] for e in evs}
        assert PUMP_CHILDREN | {"pump.cache.deleted",
                                "pump.queue.deleted"} <= names
        pumps = [e for e in evs if e["name"] == "pump.pods"]
        assert len(pumps) == 3      # the creates, the binds, the deletes
        kids = [e for e in evs if e["name"] != "pump.pods"]
        for k in kids:
            assert k["args"]["parent"] == "pump.pods"
        for p in pumps:
            mine = sorted((k for k in kids
                           if p["ts"] <= k["ts"] < p["ts"] + p["dur"]),
                          key=lambda k: k["ts"])
            assert [k["name"] for k in mine][:2] == ["pump.poll",
                                                     "pump.index"]
            edge = p["ts"]
            for k in mine:
                # (microseconds as floats: a nanosecond of rounding)
                assert k["ts"] >= edge - 1e-3, (k["name"], "over a sibling")
                edge = k["ts"] + k["dur"]
                assert k["args"].get("window", 0) == p["args"].get(
                    "window", 0)
                assert k["args"]["events"] == 6
            assert edge <= p["ts"] + p["dur"] + 1e-3
            self_us = p["dur"] - sum(k["dur"] for k in mine)
            assert -1e-3 <= self_us <= p["dur"]
        assert sum(len([k for k in kids
                        if p["ts"] <= k["ts"] < p["ts"] + p["dur"]])
                   for p in pumps) == len(kids)

    def test_deliveries_are_counted_by_whether_the_handler_took_the_run(
            self):
        """5 pods created, bound and deleted: what each registered handler
        can take whole follows from its registration (its filter and its
        `on_*_many` callbacks), and the counter reads that."""
        store, sched = make_sched()
        handlers = sched.informers.informer(PODS)._handlers
        assert [h.name for h in handlers] == ["cache", "queue"]
        pending, bound = mkpod("x"), mkpod("x", node_name="n0")
        want: dict = {}

        def book(path, h, n=5):
            key = (path, PODS, h.name)
            want[key] = want.get(key, 0) + n

        for h in handlers:
            if h.filter_fn(pending):                    # the creates
                book("batched" if h.on_add_many else "single", h)
            if h.filter_fn(pending) and h.filter_fn(bound):   # the binds
                book("batched" if h.on_update_many else "single", h)
            elif h.filter_fn(pending) or h.filter_fn(bound):
                book("single", h)    # update-as-delete / update-as-add
            if h.filter_fn(bound):                      # the deletes
                book("batched" if h.on_delete_many else "single", h)
        before = delivered()
        burst(store, sched, "d", 5)
        store.delete_many(PODS, [f"default/d-{j}" for j in range(5)])
        sched.pump()
        assert moved(before) == want
        # today: the creates reach the queue whole, the deletes the cache
        # whole, and each bind goes one by one through BOTH handlers
        assert want == {("batched", PODS, "queue"): 5,
                        ("single", PODS, "cache"): 5,
                        ("single", PODS, "queue"): 5,
                        ("batched", PODS, "cache"): 5}

    def test_unnamed_handler_reads_handler_and_only_a_pump_opens_spans(self):
        store = Store()
        store.create(PODS, mkpod("listed"))
        inf = SharedInformer(store, PODS)
        got = []
        inf.add_event_handler(on_add=lambda p: got.append(p.name))
        before = delivered()
        obs.trace.clear()
        inf.sync()                  # the list's replay: object by object
        assert obs.trace.events() == []
        assert moved(before) == {("single", PODS, "handler"): 1}
        for j in range(2):
            store.create(PODS, mkpod(f"u-{j}"))
        assert inf.pump() == 2
        assert got == ["listed", "u-0", "u-1"]
        assert [(e["name"], e["args"]["events"])
                for e in obs.trace.events()] == [
            ("pump.poll", 2), ("pump.index", 2), ("pump.handler.added", 2),
            ("pump.pods", 2)]
        assert moved(before) == {("single", PODS, "handler"): 3}
        # the background thread's path: event by event, so no span either
        store.create(PODS, mkpod("bg"))
        obs.trace.clear()
        inf._apply(inf._watch.try_next())
        assert got[-1] == "bg" and obs.trace.events() == []
        assert moved(before) == {("single", PODS, "handler"): 4}

    def test_scatter_span_and_rows_counter_count_real_rows(self):
        from kubernetes_tpu.core import tpu_scheduler as T
        store, sched = make_sched(n_nodes=40)
        burst(store, sched, "w", 3)          # full upload, folds resident
        store.delete_many(PODS, [f"default/w-{j}" for j in range(3)])
        before = T.SCATTER_ROWS.value
        evs = burst(store, sched, "s", 3)    # the 3 freed rows are dirty
        (sc,) = [e for e in evs if e["name"] == "burst.scatter"]
        assert sc["cat"] == "device"
        assert sc["args"]["parent"] == "burst.encode"
        rows = sc["args"]["rows"]
        assert 1 <= rows <= 3                # not the 16-row bucket
        assert T.SCATTER_ROWS.value - before == rows

    def test_trim_span_only_when_the_wave_trims(self):
        store = Store(events_cap=8)
        for i in range(4):
            store.create(NODES, mknode(f"n{i}"))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        evs = burst(store, sched, "u", 6)    # 6 records: under the cap
        assert not [e for e in evs if e["name"] == "store.trim_events"]
        evs = burst(store, sched, "v", 6)    # 12 records: trims 4
        (trim,) = [e for e in evs if e["name"] == "store.trim_events"]
        assert trim["args"]["records"] == 4
        assert trim["args"]["parent"] == "burst.commit.store"

    @pytest.mark.parametrize("n_pods", [1, 64])
    def test_spans_per_window_do_not_depend_on_pods(self, n_pods):
        """None per pod: a window of 1 pod and a window of 64 open the same
        spans, the same number of times."""
        store, sched = make_sched(n_nodes=8)
        burst(store, sched, "warm", 2)       # upload + compile behind us
        calls = table_calls()
        evs = burst(store, sched, f"k{n_pods}", n_pods)
        assert table_calls() == calls
        count: dict = {}
        for e in evs:
            count[e["name"]] = count.get(e["name"], 0) + 1
        assert count == {
            "pump.pods": 2, "pump.poll": 2, "pump.index": 2,
            "pump.cache.added": 1, "pump.queue.added": 1,
            "pump.cache.modified": 1, "pump.queue.modified": 1,
            "burst.plan": 1, "burst.snapshot": 1,
            "burst.encode": 1, "burst.encode.nodes": 1,
            "burst.encode.pods": 1, "burst.dispatch": 1, "burst.fetch": 1,
            "burst.wave.device": 1, "burst.wave.commit": 1,
            "burst.commit.cache": 1, "burst.commit.store": 1,
            "burst.commit.fanout": 1, "burst.commit.finish": 1}
        # no Service selects these pods: nothing asks for the pod table
        assert not set(ENCODE_CHILDREN) & set(count)

    def test_refused_burst_closes_its_phase_and_stamps_nothing(self):
        """A refusal from the middle of encode ends the span (the host
        spent that time) without an ENCODE stamp; the next span has no
        stale parent."""
        from kubernetes_tpu.cache.node_info import NodeInfo
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        from kubernetes_tpu.obs import ledger as L
        infos = {f"n{i}": NodeInfo(mknode(f"n{i}")) for i in range(3)}
        from kubernetes_tpu.api.types import ContainerPort
        pods = [mkpod(f"r{k}", cpu=100 + k) for k in range(3)]
        pods[1] = Pod(name="r1", labels={"app": "x"}, containers=(
            Container.make(name="c", requests={"cpu": 100},
                           ports=[ContainerPort(host_port=80,
                                                container_port=80)]),))
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        obs.trace.clear()
        L.LEDGER.reset()
        assert tpu.schedule_burst(pods, infos, sorted(infos)) is None
        names = [e["name"] for e in obs.trace.events()]
        assert "burst.encode" in names and "burst.dispatch" not in names
        assert L.LEDGER.snapshot()["phase_split"].get("encode", 0.0) == 0.0
        with obs.trace.span("after"):
            pass
        assert "parent" not in obs.trace.events()[-1].get("args", {})


class TestEncodeSpans:
    def test_children_nest_under_the_encode_and_leave_it_its_self_time(self):
        """The table's upkeep and the count passes lie inside
        `burst.encode.pods`, the carry inside `burst.encode`, side by side
        under the window's number: a parent's duration is its children's
        plus a self time of its own, and no count pass holds the table's
        build."""
        store, sched = make_service_sched()
        evs = service_burst(store, sched, "a", 40)
        (plan,) = [e for e in evs if e["name"] == "burst.plan"]
        kids = [e for e in evs if e["name"] in ENCODE_CHILDREN]
        assert {e["name"] for e in kids} == set(ENCODE_CHILDREN)
        for e in kids:
            assert e["args"]["parent"] == ENCODE_CHILDREN[e["name"]], e
            assert e["args"]["window"] == plan["args"]["window"]
        for parent in ("burst.encode.pods", "burst.encode"):
            (p,) = [e for e in evs if e["name"] == parent]
            mine = sorted((e for e in evs
                           if e["args"].get("parent") == parent),
                          key=lambda e: e["ts"])
            assert mine
            edge = p["ts"]
            for e in mine:
                assert e["ts"] >= edge - 1e-3, (e["name"], "over a sibling")
                edge = e["ts"] + e["dur"]
            assert edge <= p["ts"] + p["dur"] + 1e-3
            assert 0 < p["dur"] - sum(e["dur"] for e in mine) <= p["dur"]
        (table,) = [e for e in kids if e["name"] == "burst.encode.table"]
        counts = [e for e in kids if e["name"] == "burst.encode.count"]
        # the first selected pod's encode builds the table, then counts
        assert table["ts"] + table["dur"] <= min(c["ts"] for c in counts) \
            + 1e-3

    @pytest.mark.parametrize("n_pods", [40, 400])
    def test_one_count_pass_a_selector_group_not_a_pod(self, n_pods):
        """A segment of eight Services' pods opens eight count passes, one
        table upkeep and one carry, however many pods it holds, and the
        count spans are the counter's movement."""
        store, sched = make_service_sched()
        service_burst(store, sched, "warm", 16)
        encodes = NS.SPREAD_COUNT_ENCODES.value
        calls = table_calls()
        evs = service_burst(store, sched, f"g{n_pods}", n_pods)
        assert len([e for e in evs if e["name"] == "burst.plan"]) == 1
        count: dict = {}
        for e in evs:
            if e["name"] in ENCODE_CHILDREN:
                count[e["name"]] = count.get(e["name"], 0) + 1
        assert count == {"burst.encode.table": 1, "burst.encode.count": 8,
                         "burst.encode.carry": 1}
        assert NS.SPREAD_COUNT_ENCODES.value - encodes == 8
        for e in evs:
            if e["name"] == "burst.encode.count":
                # a Service's resident replica and what earlier passes bound
                assert e["args"]["selectors"] == 1
                assert e["args"]["matched"] >= 1
        (carry,) = [e for e in evs if e["name"] == "burst.encode.carry"]
        assert carry["args"]["groups"] == 8 and carry["args"]["rows"] == 8
        # the binds of the pass before joined the table's rows
        (table,) = [e for e in evs if e["name"] == "burst.encode.table"]
        # (on eight nodes a changed node is no small share: one gather)
        assert table["args"]["path"] == "gathered"
        assert table["args"]["fresh"] == 16 and table["args"]["moved"] >= 1
        assert 0 <= table["args"]["kept"] < table["args"]["moved"]
        moved = {p: v - calls[p] for p, v in table_calls().items()}
        assert moved == {"returned": 0, "shared": 0, "spliced": 0,
                         "gathered": 1, "built": 0}

    def test_one_service_carries_the_vector(self):
        store, sched = make_service_sched(k=1)
        evs = service_burst(store, sched, "v", 12, k=1)
        (carry,) = [e for e in evs if e["name"] == "burst.encode.carry"]
        assert carry["args"]["groups"] == 1 and carry["args"]["rows"] == 1
        assert len([e for e in evs
                    if e["name"] == "burst.encode.table"]) == 1

    def test_pod_table_calls_are_counted_by_the_path_they_left_on(self):
        """built, then returned / shared / spliced / gathered for a call
        with nothing moved / generations moved with every row in place (a
        Node update; a pod bound and gone again: the span's `kept`) / a pod
        joined on one node of many / a pod joined on every third node; the
        span carries the label that was booked, the five labels sum to the
        calls, and the moved nodes are booked by what their stamps said."""
        from kubernetes_tpu.cache.node_info import NodeInfo
        infos = {}
        for i in range(48):
            ni = infos[f"n{i}"] = NodeInfo(mknode(f"n{i}"))
            for j in range(2):
                ni.add_pod(mkpod(f"r{i}-{j}", node_name=f"n{i}"))
        enc = NS.NodeStateEncoder()
        b = enc.encode(infos, sorted(infos))
        before = table_calls()
        nodes = {r: NS.POD_TABLE_MOVED_NODES.labels(r).value
                 for r in ("kept", "changed")}
        obs.trace.clear()

        def call() -> dict:
            enc.pod_table(infos, b)
            last = obs.trace.events()[-1]
            assert last["name"] == "burst.encode.table"
            return {k: v for k, v in last["args"].items()
                    if k not in ("parent", "window")}

        assert call() == {"path": "built", "rows": 96, "moved": 48,
                          "kept": 0, "fresh": 96}
        assert call() == {"path": "returned", "rows": 96, "moved": 0,
                          "kept": 0, "fresh": 0}
        infos["n1"].set_node(mknode("n1"))       # a generation, no row
        assert call() == {"path": "shared", "rows": 96, "moved": 1,
                          "kept": 1, "fresh": 0}
        gone = mkpod("bound-and-gone", node_name="n3")
        infos["n3"].add_pod(gone)
        infos["n3"].remove_pod(gone)             # two generations, no row
        assert call() == {"path": "shared", "rows": 96, "moved": 1,
                          "kept": 1, "fresh": 0}
        infos["n2"].add_pod(mkpod("joined", node_name="n2"))
        infos["n1"].set_node(mknode("n1"))
        assert call() == {"path": "spliced", "rows": 97, "moved": 2,
                          "kept": 1, "fresh": 1}
        for i in range(0, 48, 3):
            infos[f"n{i}"].add_pod(mkpod(f"wave-{i}", node_name=f"n{i}"))
        assert call() == {"path": "gathered", "rows": 113, "moved": 16,
                          "kept": 0, "fresh": 16}
        assert call()["path"] == "returned"
        moved = {p: v - before[p] for p, v in table_calls().items()}
        assert moved == {"built": 1, "returned": 2, "shared": 2,
                         "spliced": 1, "gathered": 1}
        assert {r: NS.POD_TABLE_MOVED_NODES.labels(r).value - v
                for r, v in nodes.items()} == {"kept": 3, "changed": 65}
        spans = [e["args"]["path"] for e in obs.trace.events()]
        assert sum(moved.values()) == len(spans) == 7
        assert {p: spans.count(p) for p in TABLE_PATHS} == moved
        # an encoder without a state encoder builds its table in one shot
        NS.build_pod_table(infos, b)
        assert obs.trace.events()[-1]["args"]["path"] == "built"

    def test_serial_cycle_opens_the_same_spans_outside_a_burst(self):
        """`_schedule_device` reaches the same encoder: a selected pod's
        cycle opens the table's span and the count pass's, under no
        `burst.encode.pods`, and binds."""
        store, sched = make_service_sched(k=2, n_nodes=4)
        sched.algorithm.serial_path = "device"
        store.create(PODS, Pod(name="c0", labels={"app": "svc-1"},
                               containers=mkpod("x").containers))
        sched.pump()
        obs.trace.clear()
        assert sched.schedule_one(timeout=0.0)
        sched.wait_for_binds()
        evs = obs.trace.events()
        names = [e["name"] for e in evs]
        assert names.count("burst.encode.table") == 1
        assert names.count("burst.encode.count") == 1
        assert "burst.encode.pods" not in names
        assert "cycle.fetch" in names
        (table,) = [e for e in evs if e["name"] == "burst.encode.table"]
        assert table["args"]["path"] == "built"
        assert store.get(PODS, "default/c0").node_name


class TestProfilerSeesTheSameSpans:
    def test_host_plane_has_ring_names_nesting_and_window(self, tmp_path):
        """A profiler session on the CPU backend around a tiny burst: the
        `.xplane.pb`'s host plane shows the program's spans with the names,
        the nesting and the window number the ring has."""
        import jax
        from jax.profiler import ProfileData
        store, sched = make_sched()
        burst(store, sched, "warm", 2)
        for j in range(6):
            store.create(PODS, mkpod(f"p-{j}"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        obs.trace.clear()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert drain(sched) == 6
        finally:
            jax.profiler.stop_trace()
        ring = [e for e in obs.trace.events()
                if e["name"] != "burst.wave.device"]   # ring alone: after
        assert BIND_PATH_SPANS <= {e["name"] for e in ring}   # the fact
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        names = {e["name"] for e in ring}
        seen = []      # (start, end, name, window) of the program's spans
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        stats = dict(e.stats)
                        if stats.get("empty"):
                            continue   # a drain that popped nothing
                        seen.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name, stats.get("window")))
        seen.sort(key=lambda r: (r[0], -r[1]))
        # same spans, same order of opening
        by_start = sorted(ring, key=lambda e: (e["ts"], -e["dur"]))
        assert [r[2] for r in seen] == [e["name"] for e in by_start]
        # same window number
        assert [r[3] for r in seen] == [e["args"]["window"]
                                        for e in by_start]
        # same nesting: the innermost span that contains one is the parent
        # the ring recorded
        stack = []
        for s0, s1, name, _w in seen:
            while stack and stack[-1][1] < s1:
                stack.pop()
            want = next(e for e in by_start if e["name"] == name
                        )["args"].get("parent")
            assert (stack[-1][2] if stack else None) == want, name
            stack.append((s0, s1, name))
        # what only the end knew rides the annotation too
        pump = next(dict(e.stats) for plane in
                    ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name == "pump.pods")
        assert pump["events"] == 6
        child = next(dict(e.stats) for plane in
                     ProfileData.from_file(path).planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for e in line.events
                     if e.name == "pump.queue.added")
        assert child["events"] == 6 and child["window"] == pump["window"]

    def test_encode_spans_ride_the_profilers_trace_with_their_late_args(
            self, tmp_path):
        """The three encode spans of a Services' burst are annotations in
        the `.xplane.pb` too, under the window's number, with what only
        their end knew (`path`, `matched`, `rows`)."""
        import jax
        from jax.profiler import ProfileData
        store, sched = make_service_sched()
        service_burst(store, sched, "warm", 16)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            ring = service_burst(store, sched, "p", 24)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        seen: dict = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in ENCODE_CHILDREN:
                            seen.setdefault(e.name, []).append(dict(e.stats))
        want = {n: [e["args"] for e in ring if e["name"] == n]
                for n in ENCODE_CHILDREN}
        assert {n: len(v) for n, v in seen.items()} == \
            {"burst.encode.table": 1, "burst.encode.count": 8,
             "burst.encode.carry": 1}
        for name, stats in seen.items():
            for got, args in zip(stats, want[name]):
                for k, v in args.items():
                    if k != "parent":
                        assert got[k] == v, (name, k)

    def test_no_session_no_annotation(self):
        sp = obs.trace.begin("quiet")
        assert sp._ann is None
        sp.end()


class TestCompileCounters:
    @staticmethod
    def _total(family) -> float:
        return sum(c.value for c in family._children.values())

    def test_compile_counter_moves_on_first_call_only(self):
        import jax
        import jax.numpy as jnp
        from kubernetes_tpu import ops

        @jax.jit
        def span_test_program(x):
            return x * 3 + 1

        x5, x6 = jnp.arange(5), jnp.arange(6)    # their own programs: first
        n0, s0 = self._total(ops.COMPILES), self._total(ops.COMPILE_SECONDS)
        span_test_program(x5).block_until_ready()
        assert self._total(ops.COMPILES) == n0 + 1
        assert self._total(ops.COMPILE_SECONDS) > s0
        span_test_program(x5).block_until_ready()
        assert self._total(ops.COMPILES) == n0 + 1
        span_test_program(x6).block_until_ready()      # a new shape
        assert self._total(ops.COMPILES) == n0 + 2

    def test_program_label_is_bounded(self, monkeypatch):
        """The first programs of a process have a child each; once the
        bound is reached, new names share `other`."""
        import jax
        import jax.numpy as jnp
        from kubernetes_tpu import ops
        x7, x8 = jnp.arange(7), jnp.arange(8)

        @jax.jit
        def bounded_first(v):
            return v + 11

        @jax.jit
        def bounded_second(v):
            return v + 12

        monkeypatch.setattr(ops, "_compile_programs", set())
        monkeypatch.setattr(ops, "MAX_COMPILE_PROGRAMS", 1)
        other = ops.COMPILES.labels("other")
        other0 = other.value
        bounded_first(x7).block_until_ready()
        assert ops._compile_programs == {"jit(bounded_first)"}
        bounded_first(x8).block_until_ready()      # a known name keeps its own
        assert ops.COMPILES.labels("jit(bounded_first)").value == 2
        assert other.value == other0
        bounded_second(x7).block_until_ready()     # past the bound
        assert ops._compile_programs == {"jit(bounded_first)"}
        assert other.value == other0 + 1


def _record_calls(monkeypatch, jit_name: str) -> list:
    """Calls of one of kernels.py's jitted programs, as (args, kwargs)."""
    from kubernetes_tpu.ops import kernels as K
    real = getattr(K, jit_name)
    calls: list = []

    def recorder(*a, **kw):
        calls.append((a, kw))
        return real(*a, **kw)

    monkeypatch.setattr(K, jit_name, recorder)
    return calls


def _drive_cycle():
    store, sched = make_sched()
    sched.algorithm.serial_path = "device"
    store.create(PODS, mkpod("c0"))
    sched.pump()
    assert sched.schedule_one(timeout=0.0)
    sched.wait_for_binds()


def _drive_uniform():
    store, sched = make_sched()
    burst(store, sched, "u", 4)


def _drive_scan():
    store, sched = make_sched()
    for j in range(4):
        store.create(PODS, mkpod(f"s{j}", cpu=100 + 50 * (j % 2)))
    assert drain(sched) == 4


def _drive_segments():
    from kubernetes_tpu.coscheduling.types import (
        LABEL_POD_GROUP, PodGroup)
    from kubernetes_tpu.store.store import PODGROUPS
    store, sched = make_sched()
    store.create(PODGROUPS, PodGroup(name="g", min_member=2))
    for j in range(2):
        store.create(PODS, mkpod(f"s{j}"))
    for j in range(2):
        p = mkpod(f"m{j}")
        store.create(PODS, Pod(name=p.name, containers=p.containers,
                               labels={**p.labels, LABEL_POD_GROUP: "g"}))
    assert drain(sched) == 4


def _pressure_world():
    from kubernetes_tpu.cache.node_info import NodeInfo
    infos, names = {}, []
    for i in range(4):
        node = Node(name=f"n{i}", allocatable={"cpu": 2000,
                                               "memory": 8 * GI,
                                               "pods": 110})
        ni = NodeInfo(node)
        for v in range(2):
            ni.add_pod(Pod(name=f"v{i}-{v}", priority=1,
                           node_name=node.name,
                           containers=(Container.make(
                               name="c", requests={"cpu": 900}),)))
        infos[node.name] = ni
        names.append(node.name)
    return infos, names


def _drive_preempt_scan():
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    from kubernetes_tpu.oracle import predicates as P
    from kubernetes_tpu.oracle.generic_scheduler import FitError
    infos, names = _pressure_world()
    pod = Pod(name="hi", priority=10, containers=(
        Container.make(name="c", requests={"cpu": 900}),))
    err = FitError(pod, len(names), {nm: [P.insufficient_resource("cpu")]
                                     for nm in names})
    tpu = TPUScheduler(percentage_of_nodes_to_score=100)
    assert tpu.preempt(pod, infos, names, err, []) is not None


def _drive_pressure():
    from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
    infos, names = _pressure_world()
    pods = [Pod(name=f"hi-{k}", priority=10, containers=(
        Container.make(name="c", requests={"cpu": 900}),))
        for k in range(3)]
    tpu = TPUScheduler(percentage_of_nodes_to_score=100)
    assert tpu.preempt_pressure_burst(pods, infos, names, []) is not None


# core -> (its jitted program, a drive that launches it, the stages it has:
# one cycle folds nothing; the victim scan neither scores nor folds)
CORES = {
    "_cycle_core": ("_schedule_cycle_jit", _drive_cycle,
                    ("filter", "score", "pick")),
    "_batch_core": ("_schedule_batch_jit", _drive_scan,
                    ("filter", "score", "pick", "fold")),
    "_segments_core": ("_schedule_batch_seg_jit", _drive_segments,
                       ("filter", "score", "pick", "fold")),
    "_uniform_core": ("_schedule_batch_uniform_jit", _drive_uniform,
                      ("filter", "score", "pick", "fold")),
    "_preempt_scan_core": ("_preemption_scan_jit", _drive_preempt_scan,
                           ("filter", "pick")),
    "_pressure_core": ("_pressure_batch_jit", _drive_pressure,
                       ("filter", "score", "pick", "fold")),
}


class TestKernelScopes:
    @pytest.mark.parametrize("core", sorted(CORES))
    def test_lowered_text_carries_the_stage_scopes(self, core, monkeypatch):
        """Each core's lowered program, with its locations, names the
        stages it has: the names a device trace is read by."""
        from kubernetes_tpu.ops import kernels as K
        jit_name, drive, stages = CORES[core]
        real = getattr(K, jit_name)
        calls = _record_calls(monkeypatch, jit_name)
        drive()
        assert calls, f"{jit_name} was not launched"
        args, kwargs = calls[0]
        text = real.lower(*args, **kwargs).as_text(debug_info=True)
        assert set(stages) <= set(K.SCOPES)
        for stage in stages:
            # a name stack component: `.../filter/add`, or `filter/add`
            # inside a loop body that is lowered as a function of its own
            assert re.search(rf'["/]{stage}/', text), (core, stage)
