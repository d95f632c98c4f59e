"""Observability layer tests: the obs registry/exposition/lint/trace stack
plus its wiring into every component (ISSUE 2) — the component-base/metrics
+ utiltrace analogs.

Covers the satellites explicitly:
- label-value escaping in exposition output (the old renderer interpolated
  raw strings into {key="..."});
- SchedulerMetrics.reset() vs a fresh instance (the old reset_metrics copy
  silently missed newly added fields);
- the slow-cycle Trace wired into the scheduler loop (was dead code);
- exposition-format invariants linted over every registered family;
- a live APIServer /metrics scrape validated end-to-end through the lint
  helper (the route used to 404).
"""
import dataclasses
import json
import logging
import urllib.request

import pytest

from kubernetes_tpu import obs
from kubernetes_tpu.obs.lint import lint_exposition
from kubernetes_tpu.obs.registry import (
    Registry, escape_label_value, format_value,
)
from kubernetes_tpu.api.types import Pod, Node, Container
from kubernetes_tpu.scheduler import Scheduler, SchedulerMetrics, Histogram
from kubernetes_tpu.store.store import Store, PODS, NODES
from kubernetes_tpu.utils.clock import FakeClock

GI = 1024 ** 3


def mknode(name, cpu=4000):
    return Node(name=name,
                allocatable={"cpu": cpu, "memory": 32 * GI, "pods": 110})


def mkpod(name, cpu=100):
    return Pod(name=name,
               containers=(Container.make(name="c", requests={"cpu": cpu}),))


def family_total(fam) -> float:
    """Sum over every child of a family (delta-friendly for the global
    registry, which accumulates across tests)."""
    return sum(c.value for c in fam._children.values())


class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = Registry()
        c = r.counter("t_requests_total", "Requests.", ("verb",))
        c.labels("get").inc()
        c.labels(verb="get").inc(2)
        assert c.labels("get").value == 3
        g = r.gauge("t_depth", "Depth.")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value == 3
        h = r.histogram("t_latency_seconds", "Latency.")
        h.observe(0.003)
        h.observe_many(0.1, 3)
        child = h.labels()
        assert child.count == 4
        assert child.sum == pytest.approx(0.303)
        with pytest.raises(ValueError):
            c.labels("get").inc(-1)

    def test_get_or_create_is_idempotent_and_shape_checked(self):
        r = Registry()
        a = r.counter("t_shared_total", "Shared.", ("op",))
        b = r.counter("t_shared_total", "Shared.", ("op",))
        assert a is b
        with pytest.raises(ValueError):
            r.gauge("t_shared_total", "Different type.")
        with pytest.raises(ValueError):
            r.counter("t_shared_total", "Different labels.", ("other",))

    def test_label_value_escaping_in_render(self):
        # the satellite: quote / backslash / newline in a label value must
        # render escaped per the Prometheus text format
        r = Registry()
        c = r.counter("t_escaped_total", "Escaping.", ("result",))
        c.labels('we"ird\\lane\nx').inc()
        text = r.render()
        assert r'result="we\"ird\\lane\nx"' in text
        assert "\n\n" not in text.strip()          # no raw newline leaked
        assert lint_exposition(text) == []
        assert escape_label_value('a"b') == 'a\\"b'

    def test_format_value_integers_render_clean(self):
        assert format_value(3.0) == "3"
        assert format_value(0) == "0"
        assert format_value(0.25) == "0.25"

    def test_format_value_specials_use_prometheus_spellings(self):
        # NaN is the no-data value for callback gauges (a GC'd
        # component's reader, a tuner lane that committed nothing) —
        # the scrape must carry it, never crash on int(NaN)
        assert format_value(float("nan")) == "NaN"
        assert format_value(float("inf")) == "+Inf"
        assert format_value(float("-inf")) == "-Inf"

    def test_nan_callback_gauge_renders_and_lints(self):
        r = Registry()
        g = r.gauge("t_gone_util", "Reader outlived its component.")
        g.set_function(lambda: float("nan"))
        text = r.render()
        assert "t_gone_util NaN" in text
        assert lint_exposition(text) == []

    def test_callback_gauge_reads_at_collect_time(self):
        r = Registry()
        depth = [7]
        g = r.gauge("t_live_depth", "Live depth.")
        g.set_function(lambda: depth[0])
        assert "t_live_depth 7" in r.render()
        depth[0] = 9
        assert "t_live_depth 9" in r.render()


class TestLint:
    def test_clean_scrape_passes(self):
        r = Registry()
        r.counter("l_total", "A counter.", ("x",)).labels("a").inc()
        h = r.histogram("l_seconds", "A histogram.", ("op",))
        h.labels("enc").observe(0.01)
        assert lint_exposition(r.render()) == []

    def test_catches_unescaped_label(self):
        bad = '# TYPE broken_total counter\nbroken_total{x="a} 1\n'
        assert any("labels" in p or "unparseable" in p
                   for p in lint_exposition(bad))

    def test_catches_nonmonotonic_buckets_and_inf_mismatch(self):
        bad = ('# TYPE h histogram\n'
               'h_bucket{le="0.1"} 5\nh_bucket{le="0.2"} 3\n'
               'h_bucket{le="+Inf"} 9\nh_sum 1.0\nh_count 8\n')
        probs = lint_exposition(bad)
        assert any("monotonic" in p for p in probs)
        assert any("+Inf" in p and "_count" in p for p in probs)

    def test_catches_missing_sum_and_inf(self):
        bad = ('# TYPE h histogram\n'
               'h_bucket{le="0.1"} 1\nh_count 1\n')
        probs = lint_exposition(bad)
        assert any("+Inf" in p for p in probs)
        assert any("_sum" in p for p in probs)

    def test_catches_duplicate_type_and_split_family(self):
        bad = ('# TYPE a_total counter\na_total 1\n'
               '# TYPE b_total counter\nb_total 1\n'
               'a_total{x="y"} 2\n')
        probs = lint_exposition(bad)
        assert any("contiguous" in p for p in probs)
        bad2 = ('# TYPE a_total counter\n# TYPE a_total counter\na_total 1\n')
        assert any("duplicate TYPE" in p for p in lint_exposition(bad2))


class TestExpositionInvariants:
    """Satellite: one lint pass over EVERY registered family — the global
    registry (all components) and a live scheduler scrape, including a
    hostile label value routed through a phase histogram."""

    def test_global_registry_lints_clean(self):
        # importing the wired modules registers every component's families
        import kubernetes_tpu.apiserver.server       # noqa: F401
        import kubernetes_tpu.controllers.base       # noqa: F401
        import kubernetes_tpu.store.informer         # noqa: F401
        import kubernetes_tpu.store.remote           # noqa: F401
        import kubernetes_tpu.core.tpu_scheduler     # noqa: F401
        import kubernetes_tpu.ops.node_state         # noqa: F401
        text = obs.render_global()
        assert lint_exposition(text) == []
        for family in ("apiserver_request_total", "workqueue_depth",
                       "informer_relists_total",
                       "remote_watch_decode_failures_total",
                       "tpu_device_dispatch_total",
                       "tpu_encoder_dirty_row_reencodes_total"):
            assert f"# TYPE {family} " in text, family

    def test_scheduler_scrape_lints_clean_with_hostile_labels(self):
        from kubernetes_tpu.metrics import render_metrics
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = Scheduler(store, percentage_of_nodes_to_score=100,
                          clock=FakeClock())
        sched.sync()
        store.create(PODS, mkpod("p1"))
        sched.pump()
        sched.schedule_one(timeout=0.0)
        sched.pump()
        # the old renderer emitted this unescaped -> unparseable scrape
        sched.metrics.observe_phase('weird"op\\x\n', 0.01)
        text = render_metrics(sched)
        assert lint_exposition(text) == []
        assert r'operation="weird\"op\\x\n"' in text


class TestSchedulerMetricsReset:
    """Satellite: Metrics.reset() lives next to the dataclass and derives
    from the field list — a reset instance must equal a fresh one no matter
    which fields were touched."""

    def test_reset_equals_fresh(self):
        m = SchedulerMetrics()
        m.observe("scheduled", 3)
        m.observe("custom-result")
        m.binding_count = 7
        m.preemption_attempts = 2
        m.preemption_victims = 5
        m.e2e_latency_sum = 1.25
        m.observe_phase("encode", 0.5)
        m.observe_phase("kernel", 0.1, count=4)
        m.binding_duration.observe(0.2)
        m.e2e_duration.observe_many(0.3, 2)
        assert m != SchedulerMetrics()
        m.reset()
        # dataclass equality covers EVERY field (Histogram compares by
        # value), so a newly added field missed by reset() fails here
        assert m == SchedulerMetrics()

    def test_reset_covers_every_declared_field(self):
        # belt and braces: every field must be reassigned by reset()
        m = SchedulerMetrics()
        sentinels = {}
        for f in dataclasses.fields(m):
            sentinels[f.name] = getattr(m, f.name)
        m.reset()
        for f in dataclasses.fields(m):
            # mutable containers must be FRESH objects, not the old ones
            if isinstance(sentinels[f.name], (dict, Histogram)):
                assert getattr(m, f.name) is not sentinels[f.name], f.name

    def test_reset_metrics_wrapper_still_serves_delete_verb(self):
        from kubernetes_tpu.metrics import render_metrics, reset_metrics
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = Scheduler(store, percentage_of_nodes_to_score=100,
                          clock=FakeClock())
        sched.sync()
        store.create(PODS, mkpod("p1"))
        sched.pump()
        sched.schedule_one(timeout=0.0)
        reset_metrics(sched)
        assert 'result="scheduled"} 0' in render_metrics(sched)


class TestSlowCycleTrace:
    """Satellite: Trace.log_if_long (generic_scheduler.go:185 analog) is
    wired into the scheduling cycle — a slow cycle emits its step
    timeline; a fast one stays quiet."""

    def _run_one(self, caplog, threshold):
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = Scheduler(store, percentage_of_nodes_to_score=100,
                          clock=FakeClock())
        sched.slow_cycle_threshold = threshold
        sched.sync()
        store.create(PODS, mkpod("p1"))
        sched.pump()
        with caplog.at_level(logging.WARNING, logger="kubernetes_tpu"):
            sched.schedule_one(timeout=0.0)
        return caplog.text

    def test_slow_cycle_emits_step_timeline(self, caplog):
        text = self._run_one(caplog, threshold=0.0)
        assert "scheduling cycle default/p1" in text
        for step in ("snapshot updated", "scheduling algorithm",
                     "pod assumed", "binding"):
            assert step in text, step
        # folded into the span layer too: the slow cycle's steps land in
        # the obs ring buffer for /debug/traces
        names = [e["name"] for e in obs.trace.events()]
        assert any("scheduling cycle default/p1" in n for n in names)

    def test_fast_cycle_stays_quiet(self, caplog):
        text = self._run_one(caplog, threshold=10.0)
        assert "scheduling cycle" not in text

    def test_unschedulable_cycle_traces_preemption_step(self, caplog):
        store = Store()
        store.create(NODES, mknode("n0", cpu=100))
        sched = Scheduler(store, percentage_of_nodes_to_score=100,
                          clock=FakeClock())
        sched.slow_cycle_threshold = 0.0
        sched.sync()
        store.create(PODS, mkpod("big", cpu=4000))
        sched.pump()
        with caplog.at_level(logging.WARNING, logger="kubernetes_tpu"):
            sched.schedule_one(timeout=0.0)
        assert "preemption" in caplog.text


class TestSpans:
    def test_span_nesting_records_parent(self):
        obs.trace.clear()
        with obs.trace.span("outer"):
            with obs.trace.span("inner", cat="device", detail=1):
                pass
        evs = obs.trace.events()
        by_name = {e["name"]: e for e in evs}
        assert by_name["inner"]["args"]["parent"] == "outer"
        assert by_name["inner"]["cat"] == "device"
        assert by_name["inner"]["ph"] == "X"
        assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]

    def test_chrome_export_shape(self, tmp_path):
        obs.trace.clear()
        with obs.trace.span("work"):
            pass
        out = tmp_path / "trace.json"
        n = obs.trace.export(str(out))
        assert n == 1
        doc = json.loads(out.read_text())
        (ev,) = doc["traceEvents"]
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(ev)

    def test_ring_buffer_is_bounded(self):
        obs.trace.set_capacity(8)
        try:
            for i in range(32):
                obs.trace.add_span(f"s{i}", 0.0, 0.001)
            evs = obs.trace.events()
            assert len(evs) == 8
            assert evs[0]["name"] == "s24"   # oldest fell off
        finally:
            obs.trace.set_capacity(obs.trace.DEFAULT_CAPACITY)


class TestDevicePipelineCounters:
    def test_burst_records_dispatches_bytes_and_spans(self):
        from kubernetes_tpu.core import tpu_scheduler as T
        obs.trace.clear()
        before_disp = family_total(T.DEVICE_DISPATCH)
        before_bytes = family_total(T.DEVICE_FETCHED_BYTES)
        store = Store()
        for i in range(4):
            store.create(NODES, mknode(f"n{i}"))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        for j in range(6):
            store.create(PODS, mkpod(f"p{j}"))
        sched.pump()
        while sched.schedule_burst(max_pods=8):
            pass
        sched.pump()
        assert family_total(T.DEVICE_DISPATCH) > before_disp
        assert family_total(T.DEVICE_FETCHED_BYTES) > before_bytes
        # device-cost attribution: host encode and device dispatch+fetch
        # are separate spans (the fetch span waits for the device)
        cats = {e["name"]: e["cat"] for e in obs.trace.events()}
        assert cats.get("burst.encode") == "host"
        assert cats.get("burst.fetch") == "device"

    def test_encoder_counts_reencodes(self):
        from kubernetes_tpu.ops import node_state as NS
        from kubernetes_tpu.cache.node_info import NodeInfo
        before = NS.ROW_REENCODES.value
        enc = NS.NodeStateEncoder()
        infos = {f"n{i}": NodeInfo(mknode(f"n{i}")) for i in range(3)}
        enc.encode(infos, sorted(infos))
        assert NS.ROW_REENCODES.value == before + 3
        # unchanged generations: no re-encode on the second pass
        enc.encode(infos, sorted(infos))
        assert NS.ROW_REENCODES.value == before + 3


class TestAPIServerMetricsE2E:
    """Satellite: scrape a LIVE APIServer's /metrics end-to-end and push it
    through the lint helper — plus the acceptance criterion that families
    from all four layers show up in one scrape."""

    def test_live_scrape_serves_all_layers_and_lints(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.store.remote import RemoteStore
        from kubernetes_tpu.controllers.base import DirtyKeyController

        class NodeNoop(DirtyKeyController):
            KIND = NODES

            def reconcile(self, obj):
                pass

        # device-pipeline families register at import; give them children
        from kubernetes_tpu.core import tpu_scheduler as T  # noqa: F401
        store = Store()
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url, timeout=5.0)
            remote.create(NODES, mknode("n0"))
            ctrl = NodeNoop(remote)
            ctrl.sync()               # list+watch over HTTP -> informer
            assert ctrl.pump() >= 0
            with pytest.raises(Exception):
                remote.get(NODES, "missing")   # a 404 sample
            text = urllib.request.urlopen(
                srv.url + "/metrics").read().decode()
            traces = json.loads(urllib.request.urlopen(
                srv.url + "/debug/traces").read())
        assert lint_exposition(text) == []
        # layer 1: apiserver request metrics (with code labels)
        assert 'apiserver_request_total{verb="create",resource="nodes"' \
            in text
        assert 'code="404"' in text
        assert "apiserver_request_duration_seconds_bucket" in text
        # layer 2: controller workqueue metrics
        assert 'workqueue_adds_total{name="NodeNoop"}' in text
        assert 'workqueue_work_duration_seconds_count{name="NodeNoop"}' \
            in text
        # layer 3: informer / remote client metrics
        assert 'informer_relists_total{kind="nodes"}' in text
        assert "# TYPE remote_watch_decode_failures_total counter" in text
        # layer 4: device pipeline families
        assert "# TYPE tpu_device_dispatch_total counter" in text
        assert "# TYPE tpu_oracle_fallback_total counter" in text
        # and the traces endpoint serves Chrome trace-event JSON
        assert isinstance(traces["traceEvents"], list)

    def test_watch_gauge_tracks_open_streams(self):
        from kubernetes_tpu.apiserver.server import (APIServer,
                                                     ACTIVE_WATCHES)
        from kubernetes_tpu.store.remote import RemoteStore
        store = Store()
        with APIServer(store) as srv:
            remote = RemoteStore(srv.url, timeout=5.0)
            _, rv = remote.list(NODES)
            w = remote.watch(NODES, since_rv=rv)
            deadline = 50
            while ACTIVE_WATCHES.labels(NODES).value < 1 and deadline:
                import time
                time.sleep(0.02)
                deadline -= 1
            assert ACTIVE_WATCHES.labels(NODES).value >= 1
            w.stop()


class TestTraceDropCounter:
    """Satellite: the span ring used to drop spans silently on overflow —
    obs_trace_dropped_total books every span the deque pushes off."""

    def test_overflow_increments_counter(self):
        fam = obs.counter("obs_trace_dropped_total", "x")
        obs.trace.set_capacity(4)
        try:
            obs.trace.clear()
            before = fam.value
            for i in range(10):
                obs.trace.add_span(f"d{i}", 0.0, 0.001)
            assert fam.value == before + 6
            assert len(obs.trace.events()) == 4
        finally:
            obs.trace.set_capacity(obs.trace.DEFAULT_CAPACITY)

    def test_no_drops_under_capacity(self):
        fam = obs.counter("obs_trace_dropped_total", "x")
        obs.trace.clear()
        before = fam.value
        obs.trace.add_span("fits", 0.0, 0.001)
        assert fam.value == before


class TestBucketOverrides:
    """Satellite: per-family histogram bucket overrides — µs-scale
    families must not silently inherit (or be silently overridden back
    to) the ms-scale default ladder."""

    def test_override_renders_and_lints(self):
        r = Registry()
        h = r.histogram("t_micro_seconds", "µs-scale.",
                        buckets=obs.MICRO_BUCKETS)
        h.observe(5e-6)
        text = r.render()
        assert lint_exposition(text) == []
        assert 'le="1e-06"' in text
        # the 5µs sample does NOT land in the first (1µs) bucket — the
        # whole point of the override vs the 1ms default floor
        assert 't_micro_seconds_bucket{le="1e-06"} 0' in text

    def test_conflicting_override_raises_same_default_reuses(self):
        r = Registry()
        h = r.histogram("t_shape_seconds", "x", buckets=obs.MICRO_BUCKETS)
        # declare-without-buckets reuse keeps working (default = silence)
        assert r.histogram("t_shape_seconds", "x") is h
        assert r.histogram("t_shape_seconds", "x",
                           buckets=obs.MICRO_BUCKETS) is h
        with pytest.raises(ValueError):
            r.histogram("t_shape_seconds", "x", buckets=(0.5, 1.0))

    def test_observe_batch_matches_serial_observes(self):
        r = Registry()
        a = r.histogram("t_batch_a_seconds", "x", buckets=obs.MICRO_BUCKETS)
        b = r.histogram("t_batch_b_seconds", "x", buckets=obs.MICRO_BUCKETS)
        vals = [0.0, 1e-6, 3e-6, 2e-4, 0.5, 100.0]
        a.observe_batch(vals)
        for v in vals:
            b.observe(v)
        assert a.labels().buckets == b.labels().buckets
        assert a.labels().count == b.labels().count
        assert a.labels().sum == pytest.approx(b.labels().sum)


class TestDebugEndpoints:
    """Satellites + tentpole part 3: /debug/traces grows ?limit= and
    ?cat= filters, and GET /debug/sched serves the deep-introspection
    snapshot — on the apiserver AND the scheduler command's server."""

    def _seed_spans(self):
        obs.trace.clear()
        obs.trace.add_span("h1", 0.0, 0.001, cat="host")
        obs.trace.add_span("d1", 0.0, 0.002, cat="device")
        obs.trace.add_span("h2", 0.0, 0.003, cat="host")

    def test_apiserver_traces_filters(self):
        from kubernetes_tpu.apiserver.server import APIServer
        self._seed_spans()
        with APIServer(Store()) as srv:
            full = json.loads(urllib.request.urlopen(
                srv.url + "/debug/traces").read())
            assert {"h1", "d1", "h2"} <= {e["name"]
                                          for e in full["traceEvents"]}
            lim = json.loads(urllib.request.urlopen(
                srv.url + "/debug/traces?limit=1").read())
            assert [e["name"] for e in lim["traceEvents"]] == ["h2"]
            cat = json.loads(urllib.request.urlopen(
                srv.url + "/debug/traces?cat=device").read())
            assert [e["name"] for e in cat["traceEvents"]] == ["d1"]
            both = json.loads(urllib.request.urlopen(
                srv.url + "/debug/traces?cat=host&limit=1").read())
            assert [e["name"] for e in both["traceEvents"]] == ["h2"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/debug/traces?limit=x")
            assert ei.value.code == 400

    def test_apiserver_debug_sched_snapshot(self):
        from kubernetes_tpu.apiserver.server import APIServer
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100,
                          clock=FakeClock())
        sched.sync()
        store.create(PODS, mkpod("p1"))
        sched.pump()
        with APIServer(store) as srv:
            snap = json.loads(urllib.request.urlopen(
                srv.url + "/debug/sched").read())
        # scheduler section (registered via the obs debug registry)
        q = snap["scheduler"]["queue"]
        assert {"active_depth", "backoff_depth", "unschedulable_depth",
                "scheduling_cycle", "parked_gangs"} <= set(q)
        assert q["active_depth"] == 1
        dev = snap["scheduler"]["device"]
        assert {"mirror", "dev_epoch", "last_index",
                "victim_table"} <= set(dev)
        assert "ledger" in snap["scheduler"]
        # the server's own store section: rv + per-watcher cursor lag
        assert snap["store"]["resource_version"] >= 2
        assert isinstance(snap["store"]["watchers"], list)
        assert snap["store"]["commit_core"] in ("native", "twin")

    def test_scheduler_command_serves_debug_endpoints(self):
        from kubernetes_tpu.apis.config import SchedulerConfiguration
        from kubernetes_tpu.cmd.scheduler import serve_http
        store = Store()
        store.create(NODES, mknode("n0"))
        sched = Scheduler(store, percentage_of_nodes_to_score=100,
                          clock=FakeClock())
        sched.sync()
        self._seed_spans()
        server = serve_http(sched, SchedulerConfiguration(), 0)
        try:
            base = f"http://127.0.0.1:{server.server_address[1]}"
            lim = json.loads(urllib.request.urlopen(
                base + "/debug/traces?limit=1&cat=host").read())
            assert [e["name"] for e in lim["traceEvents"]] == ["h2"]
            snap = json.loads(urllib.request.urlopen(
                base + "/debug/sched").read())
            assert snap["scheduler"]["queue"]["active_depth"] == 0
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/debug/traces?limit=-2")
            assert ei.value.code == 400
        finally:
            server.shutdown()
            server.server_close()


class TestVictimGateReasonLabels:
    """The old single victims-not-inert fallback counter is split per gate
    reason (round 9): every reason the victim-table eligibility check can
    refuse for gets its own label, in BOTH preempt (tpu_oracle_fallback_total
    {reason=preempt-victims-*}) and preempt_pressure_burst
    (tpu_pressure_gate_rejections_total{gate=victims-*})."""

    EXPECTED = {"affinity-terms", "ports", "scalar", "term-match", "overflow"}

    def _snapshot(self, victim):
        from kubernetes_tpu.api.types import Node
        from kubernetes_tpu.cache.node_info import NodeInfo
        node = Node(name="n0", allocatable={"cpu": 1000,
                                            "memory": 8 * 1024 ** 3,
                                            "pods": 200})
        ni = NodeInfo(node)
        victim.node_name = "n0"
        ni.add_pod(victim)
        return {"n0": ni}

    def _preempt(self, incoming, infos):
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        from kubernetes_tpu.oracle.generic_scheduler import FitError
        err = FitError(incoming, 1, {"n0": ["InsufficientResource:cpu"]})
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        return tpu.preempt(incoming, infos, ["n0"], err, [])

    def test_label_set_and_per_reason_fires(self):
        from kubernetes_tpu.api.types import (
            Pod, Container, ContainerPort, Affinity, PodAntiAffinity,
            PodAffinityTerm, LabelSelector, LABEL_HOSTNAME)
        from kubernetes_tpu.core.tpu_scheduler import (
            ORACLE_FALLBACKS, PRESSURE_GATES, TPUScheduler,
            VICTIM_GATE_REASONS)
        assert set(VICTIM_GATE_REASONS) == self.EXPECTED

        def mk(name, cpu=1000, priority=0, **kw):
            return Pod(name=name, priority=priority, containers=(
                Container.make(name="c", requests={"cpu": cpu},
                               **kw.pop("cmake", {})),), **kw)

        anti = Affinity(pod_anti_affinity=PodAntiAffinity(
            required=(PodAffinityTerm(
                label_selector=LabelSelector(match_labels=(("a", "b"),)),
                topology_key=LABEL_HOSTNAME),)))

        def fired(child):
            before = child.value
            return lambda: child.value - before

        # affinity-terms: the potential victim carries required terms
        v = mk("v", priority=0)
        v.affinity = anti
        d = fired(ORACLE_FALLBACKS.labels("preempt-victims-affinity-terms"))
        assert self._preempt(mk("hi", priority=9), self._snapshot(v)) is None
        assert d() == 1
        # ports: incoming pod wants a host port a victim also declares
        ports = (ContainerPort(host_port=8080, container_port=8080),)
        vp = Pod(name="v", priority=0, containers=(
            Container.make(name="c", requests={"cpu": 1000}, ports=ports),))
        hip = Pod(name="hi", priority=9, containers=(
            Container.make(name="c", requests={"cpu": 1000}, ports=ports),))
        d = fired(ORACLE_FALLBACKS.labels("preempt-victims-ports"))
        assert self._preempt(hip, self._snapshot(vp)) is None
        assert d() == 1
        # scalar: the victim requests an extended resource
        vs = Pod(name="v", priority=0, containers=(
            Container.make(name="c", requests={"cpu": 1000,
                                               "example.com/gpu": 1}),))
        d = fired(ORACLE_FALLBACKS.labels("preempt-victims-scalar"))
        assert self._preempt(mk("hi", priority=9), self._snapshot(vs)) is None
        assert d() == 1
        # term-match: a victim matches the incoming pod's required term
        vt = mk("v", priority=0, labels={"a": "b"})
        hit = mk("hi", priority=9)
        hit.affinity = anti
        d = fired(ORACLE_FALLBACKS.labels("preempt-victims-term-match"))
        assert self._preempt(hit, self._snapshot(vt)) is None
        assert d() == 1
        # overflow: more pods on a candidate node than the slot cap
        from kubernetes_tpu.api.types import Node
        from kubernetes_tpu.cache.node_info import NodeInfo
        from kubernetes_tpu.ops.kernels import PREEMPT_P
        node = Node(name="n0", allocatable={"cpu": 300000,
                                            "memory": 8 * 1024 ** 3,
                                            "pods": 500})
        ni = NodeInfo(node)
        for i in range(PREEMPT_P + 1):
            p = mk(f"v{i}", cpu=1, priority=0)
            p.node_name = "n0"
            ni.add_pod(p)
        d = fired(ORACLE_FALLBACKS.labels("preempt-victims-overflow"))
        assert self._preempt(mk("hi", cpu=300000, priority=9),
                             {"n0": ni}) is None
        assert d() == 1
        # the pressure path increments its own per-reason gate family
        v2 = mk("v", priority=0)
        v2.affinity = anti
        d = fired(PRESSURE_GATES.labels("victims-affinity-terms"))
        tpu = TPUScheduler(percentage_of_nodes_to_score=100)
        assert tpu.preempt_pressure_burst(
            [mk("hi", priority=9)], self._snapshot(v2), ["n0"], []) is None
        assert d() == 1


class TestRetiredShardedFallbackLabels:
    """Round-15 satellite: the sharded-path refusal labels
    (burst-sharded-rotation / burst-sharded-spread / fused-mesh-mode and
    the pressure gate's mesh-mode) were DELETED when the sharded kernels
    learned rotation, spread, gang segments, and pressure scans. A dead
    fallback label reading 0 forever would mask a silent regression back
    to host scheduling, so this pin fails if any code path (or eager
    registration) resurrects them."""

    def test_retired_labels_never_materialize(self):
        import inspect
        from kubernetes_tpu.core import tpu_scheduler as ts
        retired = ts.RETIRED_FALLBACK_REASONS + ts.RETIRED_PRESSURE_GATES
        assert set(retired) == {"burst-sharded-rotation",
                                "burst-sharded-spread", "fused-mesh-mode",
                                "mesh-mode"}
        src = inspect.getsource(ts)
        for label in retired:
            # the ONLY mention left in the module is the RETIRED tuple
            # itself — no .labels("...") call site survives
            assert src.count(f'"{label}"') == 1, (
                f"retired label {label!r} has a live call site again")
            assert not any(label in tuple(k)
                           for k in ts.ORACLE_FALLBACKS._children), label
            assert not any(label in tuple(k)
                           for k in ts.PRESSURE_GATES._children), label
