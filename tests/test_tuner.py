"""Closed-loop learned scoring (round 22): the tuner subsystem.

- ProfileSet.set_row runs the EXACT ctor validation (unknown priorities,
  policy weight bounds, unknown rows) and mutates nothing on failure —
  table tests mirroring TestProfileValidation; an identity write of the
  default vector must NOT flip a degenerate default set into tensor mode.
- Flight records pin the active weight rows: a set_row AFTER capture must
  not perturb replay (the capture carries a ProfileSet snapshot + the
  weight-table slice), and a tampered pinned table must FAIL the guard.
- The offline simulator is deterministic (same seed + same worlds =>
  identical candidate ranking, bit-for-bit) and the reward actually
  separates packing rows from spreading rows.
- The promotion gate: table-driven promote / hold / demote — NaN and
  no-data windows HOLD, never promote; SLO breach demotes on the
  shadow's own evidence.
- The satellites: cluster_resource_utilization gauges (+ /debug/sched),
  per-lane ledger windows (window_percentile/window_count with a key
  match), ShadowTuner's write paths.
"""
import math

import numpy as np
import pytest

from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.obs import flight
from kubernetes_tpu.obs.ledger import PodLifecycleLedger
from kubernetes_tpu.obs.timeseries import SeriesView
from kubernetes_tpu.profiles import (
    DEFAULT_PROFILE_NAME, ProfileSet, ProfileValidationError,
    SchedulingProfile,
)
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store.store import NODES, PODS, Store
from kubernetes_tpu.tuner import (
    BanditSearch, CEMSearch, PromotionGate, ShadowTuner, simulate, tune,
    worlds_from_recorder,
)
from kubernetes_tpu.tuner.controller import (
    lane_series, lane_utilization, prefix_lanes,
)

GI = 1024 ** 3


def mknode(i, cpu=4000, zone=None):
    return Node(name=f"n{i}",
                labels={"kubernetes.io/hostname": f"n{i}",
                        "failure-domain.beta.kubernetes.io/zone":
                        zone or f"z{i % 2}"},
                allocatable={"cpu": cpu, "memory": 32 * GI, "pods": 110})


def mkpod(name, cpu=100, sched=DEFAULT_PROFILE_NAME, **kw):
    return Pod(name=name, scheduler_name=sched,
               containers=(Container.make(
                   name="c", requests={"cpu": cpu, "memory": GI}),), **kw)


@pytest.fixture
def replay_recorder():
    rec = flight.RECORDER
    rec.configure(mode="replay", capacity=32)
    rec.clear()
    yield rec
    rec.configure(mode="digest")
    rec.clear()


def two_profiles():
    return ProfileSet([
        SchedulingProfile(DEFAULT_PROFILE_NAME),
        SchedulingProfile("shadow-tuner"),
    ])


# ---------------------------------------------------------------------------
# set_row validation (satellite 2)
# ---------------------------------------------------------------------------
class TestSetRowValidation:
    @pytest.mark.parametrize("target,weights,frag", [
        # unknown priority names are errors (same table as the ctor's)
        ("shadow-tuner", {"NoSuchPriority": 1}, "unknown priority"),
        # positive-weight bound (api/validation)
        ("shadow-tuner", {"LeastRequestedPriority": 0}, "positive"),
        ("shadow-tuner", {"LeastRequestedPriority": -3}, "positive"),
        # MAX_WEIGHT bound: weight * MaxPriority must fit int32
        ("shadow-tuner", {"LeastRequestedPriority": 1 << 31}, "too large"),
        # unknown rows are refused before any validation
        ("nobody", {"LeastRequestedPriority": 1}, "no profile named"),
        (7, {"LeastRequestedPriority": 1}, "no profile at index"),
    ])
    def test_bad_writes_refused_and_nothing_mutates(self, target,
                                                    weights, frag):
        ps = two_profiles()
        before = [p.name_weights() for p in ps.profiles]
        v0 = ps.version
        with pytest.raises(ProfileValidationError) as ei:
            ps.set_row(target, weights)
        assert frag in str(ei.value)
        assert [p.name_weights() for p in ps.profiles] == before
        assert ps.version == v0           # failed writes don't bump

    def test_rank_aware_gang_weight_rides_same_bounds(self):
        ps = two_profiles()
        with pytest.raises(ProfileValidationError, match="positive"):
            ps.set_row("shadow-tuner", {}, rank_aware=True, gang_weight=0)
        with pytest.raises(ProfileValidationError, match="too large"):
            ps.set_row("shadow-tuner", {}, rank_aware=True,
                       gang_weight=1 << 31)

    def test_good_write_lands_in_tensor_and_bumps_version(self):
        ps = two_profiles()
        v0 = ps.version
        i = ps.index_of("shadow-tuner")
        prof = ps.set_row("shadow-tuner", {"MostRequestedPriority": 7})
        assert prof.name == "shadow-tuner"
        assert ps.profiles[i].name_weights() == {"MostRequestedPriority": 7}
        assert ps.version == v0 + 1
        # the tensor row reflects the write; row 0 is untouched
        from kubernetes_tpu.ops.kernels import PRIORITY_AXIS
        col = PRIORITY_AXIS.index("most_requested")
        wtab = ps.weight_table()
        assert wtab[i, col] == 7
        assert np.array_equal(wtab[0], two_profiles().weight_table()[0])

    def test_identity_write_keeps_degenerate_set_degenerate(self):
        # a default-vector write into a solo default set must NOT flip
        # tensor_mode() — default-profile bit-identity rides that path
        ps = ProfileSet([SchedulingProfile(DEFAULT_PROFILE_NAME)])
        assert not ps.tensor_mode()
        ps.set_row(DEFAULT_PROFILE_NAME, {})          # {} = default row
        assert not ps.tensor_mode()
        ps.set_row(DEFAULT_PROFILE_NAME,
                   ps.default.name_weights())         # explicit identity
        assert not ps.tensor_mode()
        # a genuinely different row DOES engage tensor mode
        ps.set_row(DEFAULT_PROFILE_NAME, {"MostRequestedPriority": 3})
        assert ps.tensor_mode()

    def test_snapshot_pins_rows_across_later_writes(self):
        ps = two_profiles()
        snap = ps.snapshot()
        w0 = snap.weight_table().copy()
        ps.set_row("shadow-tuner", {"MostRequestedPriority": 50})
        assert np.array_equal(snap.weight_table(), w0)
        assert not np.array_equal(ps.weight_table(), w0)


# ---------------------------------------------------------------------------
# flight capture pins the active rows (satellite 3)
# ---------------------------------------------------------------------------
class TestFlightRowPin:
    def _cluster(self, profiles):
        store = Store()
        for i in range(6):
            store.create(NODES, mknode(i))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100,
                          profiles=profiles)
        sched.sync()
        return store, sched

    def _burst(self, store, sched, names):
        for name, sname in names:
            store.create(PODS, mkpod(name, sched=sname))
        sched.pump()
        while sched.schedule_burst(max_pods=64):
            pass
        sched.pump()

    def test_replay_green_across_mid_run_row_write(self, replay_recorder):
        ps = two_profiles()
        store, sched = self._cluster(ps)
        self._burst(store, sched,
                    [(f"a{j}", "shadow-tuner" if j % 2 else
                      DEFAULT_PROFILE_NAME) for j in range(8)])
        # the live tuner write between bursts
        ps.set_row("shadow-tuner", {"MostRequestedPriority": 40})
        sched.reload_profiles()
        self._burst(store, sched,
                    [(f"b{j}", "shadow-tuner" if j % 2 else
                      DEFAULT_PROFILE_NAME) for j in range(8)])
        recs = replay_recorder.records()
        assert len(recs) >= 2
        # records straddling the write each replay against THEIR rows
        for rec in recs:
            assert replay_recorder.replay(rec) == [], rec.kind
        # the pre-write capture pinned the pre-write table
        w_pre = recs[0].capture["wtab"]
        w_post = recs[-1].capture["wtab"]
        assert not np.array_equal(w_pre, w_post)
        i = ps.index_of("shadow-tuner")
        from kubernetes_tpu.ops.kernels import PRIORITY_AXIS
        col = PRIORITY_AXIS.index("most_requested")
        assert w_pre[i, col] != 40 and w_post[i, col] == 40

    def test_tampered_pinned_table_fails_replay(self, replay_recorder):
        ps = two_profiles()
        store, sched = self._cluster(ps)
        self._burst(store, sched, [(f"p{j}", DEFAULT_PROFILE_NAME)
                                   for j in range(4)])
        rec = replay_recorder.records()[0]
        rec.capture["wtab"] = rec.capture["wtab"] + 1
        errs = replay_recorder.replay(rec)
        assert errs and "weight table" in errs[0]


# ---------------------------------------------------------------------------
# offline simulator + search determinism (satellite 4b)
# ---------------------------------------------------------------------------
class TestSimulatorDeterminism:
    def _worlds(self, recorder):
        store = Store()
        for i in range(5):
            store.create(NODES, mknode(i))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100)
        sched.sync()
        for j in range(10):
            store.create(PODS, mkpod(f"p{j}",
                                     cpu=(100, 300, 700)[j % 3],
                                     labels={"app": "x"}))
        sched.pump()
        while sched.schedule_burst(max_pods=8):
            pass
        sched.pump()
        worlds = worlds_from_recorder(recorder)
        assert worlds
        return worlds

    def test_same_row_same_reward_bit_for_bit(self, replay_recorder):
        worlds = self._worlds(replay_recorder)
        row = {"MostRequestedPriority": 13, "SelectorSpreadPriority": 2}
        a = [simulate(w, row).as_dict() for w in worlds]
        b = [simulate(w, row).as_dict() for w in worlds]
        assert a == b

    def test_reward_separates_packing_from_spreading(self,
                                                     replay_recorder):
        worlds = self._worlds(replay_recorder)
        pack = sum(simulate(w, {"MostRequestedPriority": 100}).packing
                   for w in worlds)
        spread = sum(simulate(w, {"LeastRequestedPriority": 100}).packing
                     for w in worlds)
        assert pack > spread    # the packing term is live, not decorative

    def test_same_seed_identical_ranking(self, replay_recorder):
        worlds = self._worlds(replay_recorder)
        keys = ["LeastRequestedPriority", "MostRequestedPriority",
                "BalancedResourceAllocation"]

        def score(w):
            return sum(simulate(world, w).reward for world in worlds)

        runs = [CEMSearch(keys, seed=5, population=8,
                          iterations=2).run(score) for _ in range(2)]
        assert runs[0].best_weights == runs[1].best_weights
        assert runs[0].best_reward == runs[1].best_reward
        assert runs[0].history == runs[1].history
        # different seeds explore differently (the RNG is the only
        # nondeterminism, and it is seeded)
        other = CEMSearch(keys, seed=6, population=8,
                          iterations=2).run(score)
        assert other.evaluated == runs[0].evaluated

    def test_tune_entrypoint_deterministic_and_bounded(self,
                                                       replay_recorder):
        worlds = self._worlds(replay_recorder)
        keys = ["LeastRequestedPriority", "MostRequestedPriority"]
        a = tune(worlds, keys, seed=3, budget=32)
        b = tune(worlds, keys, seed=3, budget=32)
        assert (a.best_weights, a.best_reward) == (b.best_weights,
                                                   b.best_reward)
        from kubernetes_tpu.apis.policy import MAX_WEIGHT
        for v in a.best_weights.values():
            assert 0 < v < MAX_WEIGHT
        # every row the search proposes passes ctor validation
        ps = two_profiles()
        ps.set_row("shadow-tuner", a.best_weights)

    def test_bandit_fallback_on_thin_worlds(self, replay_recorder):
        worlds = self._worlds(replay_recorder)[:1]
        r = tune(worlds, ["LeastRequestedPriority"], seed=1, budget=8)
        assert r.strategy == "bandit"
        r2 = tune(worlds, ["LeastRequestedPriority"], seed=1, budget=8)
        assert r.best_weights == r2.best_weights


# ---------------------------------------------------------------------------
# promotion gate (satellite 4a)
# ---------------------------------------------------------------------------
def gate_doc(sh_p99, in_p99, sh_u, in_u):
    """A series document shaped like the scraper's: one column per lane
    per family. Lists may hold None (scraped NaN)."""
    n = len(sh_p99)

    def fam(sh, inc):
        return {"type": "gauge", "series": {
            'lane="shadow"': {"value": list(sh)},
            'lane="incumbent"': {"value": list(inc)},
        }}
    return {"interval": 0.25, "samples": n, "window": n,
            "t": [0.25 * k for k in range(n)],
            "families": {
                "tuner_lane_p99_seconds": fam(sh_p99, in_p99),
                "tuner_lane_utilization": fam(sh_u, in_u),
            }}


class TestPromotionGate:
    @pytest.mark.parametrize("case,doc,want", [
        # shadow strictly better on both axes -> promote
        ("wins_both", gate_doc([0.2] * 8, [0.5] * 8,
                               [0.6] * 8, [0.4] * 8), "promote"),
        # better p99, utilization within tolerance -> promote
        ("wins_p99", gate_doc([0.2] * 8, [0.5] * 8,
                              [0.39] * 8, [0.40] * 8), "promote"),
        # ties everywhere: no win -> hold
        ("no_win", gate_doc([0.5] * 8, [0.5] * 8,
                            [0.4] * 8, [0.4] * 8), "hold"),
        # better p99 but a real utilization regression -> hold
        ("util_regress", gate_doc([0.2] * 8, [0.5] * 8,
                                  [0.2] * 8, [0.4] * 8), "hold"),
        # better utilization but p99 regression past tolerance -> hold
        ("p99_regress", gate_doc([0.9] * 8, [0.5] * 8,
                                 [0.6] * 8, [0.4] * 8), "hold"),
        # shadow breaches the 5s SLO -> demote (its own evidence)
        ("slo_breach", gate_doc([6.0] * 8, [0.5] * 8,
                                [0.6] * 8, [0.4] * 8), "demote"),
        # SLO breach outranks a dark incumbent lane
        ("breach_dark_incumbent", gate_doc([6.0] * 8, [None] * 8,
                                           [0.6] * 8, [None] * 8),
         "demote"),
        # all-NaN shadow -> hold, never promote
        ("nan_shadow", gate_doc([None] * 8, [0.5] * 8,
                                [None] * 8, [0.4] * 8), "hold"),
        # all-NaN incumbent (shadow looks great) -> hold, never promote
        ("nan_incumbent", gate_doc([0.2] * 8, [None] * 8,
                                   [0.6] * 8, [None] * 8), "hold"),
        # thin window: fewer valid samples than min_samples -> hold
        ("thin", gate_doc([0.2] * 2, [0.5] * 2,
                          [0.6] * 2, [0.4] * 2), "hold"),
        # empty document -> hold
        ("empty", {"t": [], "families": {}}, "hold"),
        # missing families entirely -> hold
        ("missing_family", {"t": [0.0, 0.25], "families": {}}, "hold"),
    ])
    def test_verdict_table(self, case, doc, want):
        g = PromotionGate()
        got = g.decide(doc)
        assert got["decision"] == want, (case, got["reason"])
        if want != "promote":
            # no-data cases must NEVER read as promote under any of the
            # gate's orderings — re-check via a fresh gate instance too
            assert PromotionGate().decide(doc)["decision"] != "promote"

    def test_tail_judges_recent_window_not_startup(self):
        # a shadow that was bad early but clearly wins the trailing half
        # promotes: the tail fraction scopes the comparison
        doc = gate_doc([3.0] * 4 + [0.2] * 4, [0.5] * 8,
                       [0.6] * 8, [0.4] * 8)
        assert PromotionGate().decide(doc)["decision"] == "promote"

    def test_lane_series_reads_one_child(self):
        doc = gate_doc([0.1, 0.2], [0.7, 0.8], [0.5, 0.5], [0.4, 0.4])
        v = SeriesView(doc)
        sh = lane_series(v, "tuner_lane_p99_seconds", "shadow")
        inc = lane_series(v, "tuner_lane_p99_seconds", "incumbent")
        assert list(sh) == [0.1, 0.2] and list(inc) == [0.7, 0.8]
        # the summed col() view would have blended them — the reason
        # lane_series exists
        assert list(v.col("tuner_lane_p99_seconds", "value")) == \
            [pytest.approx(0.8), pytest.approx(1.0)]
        missing = lane_series(v, "no_such_family", "shadow")
        assert np.all(np.isnan(missing))


# ---------------------------------------------------------------------------
# shadow controller writes
# ---------------------------------------------------------------------------
class TestShadowTuner:
    def test_install_promote_demote_write_rows(self):
        ps = two_profiles()
        t = ShadowTuner(ps, "shadow-tuner")
        assert t.incumbent == DEFAULT_PROFILE_NAME
        row = {"MostRequestedPriority": 21}
        t.install(row)
        assert ps.profile_for("shadow-tuner").name_weights() == row
        assert ps.default.name_weights() != row
        t.apply({"decision": "promote"})
        assert ps.default.name_weights() == row
        t.install({"MostRequestedPriority": 99})
        t.apply({"decision": "demote"})
        # demote reverts the shadow to the (promoted) incumbent row
        assert ps.profile_for("shadow-tuner").name_weights() == row
        assert t.installed is None
        v = ps.version
        t.apply({"decision": "hold"})              # hold writes nothing
        assert ps.version == v

    def test_refresh_reaches_live_scheduler(self):
        ps = two_profiles()
        store = Store()
        for i in range(4):
            store.create(NODES, mknode(i))
        sched = Scheduler(store, use_tpu=True,
                          percentage_of_nodes_to_score=100, profiles=ps)
        sched.sync()
        t = ShadowTuner(ps, "shadow-tuner", schedulers=[sched])
        t.install({"MostRequestedPriority": 17})
        # the algorithm's refreshed weight table carries the new row
        algo_tab = sched.algorithm.profiles.weight_table()
        from kubernetes_tpu.ops.kernels import PRIORITY_AXIS
        col = PRIORITY_AXIS.index("most_requested")
        assert algo_tab[ps.index_of("shadow-tuner"), col] == 17

    def test_unknown_rows_refused_at_ctor(self):
        ps = two_profiles()
        with pytest.raises(ValueError):
            ShadowTuner(ps, "nobody")
        with pytest.raises(ValueError):
            ShadowTuner(ps, "shadow-tuner", incumbent="nobody")

    def test_debug_section_registered(self):
        from kubernetes_tpu import obs
        ps = two_profiles()
        t = ShadowTuner(ps, "shadow-tuner")
        t.install({"MostRequestedPriority": 5})
        state = obs.debug_snapshot()["tuner"]
        assert state["shadow"] == "shadow-tuner"
        assert state["shadow_weights"] == {"MostRequestedPriority": 5}
        assert state["profile_version"] == ps.version


# ---------------------------------------------------------------------------
# per-lane ledger windows + utilization (satellites 1 + gate plumbing)
# ---------------------------------------------------------------------------
class TestLaneWindows:
    def test_window_percentile_filters_by_lane(self):
        led = PodLifecycleLedger()
        lanes = prefix_lanes("tn-i-", "tn-s-")
        t0 = 1000.0
        for k, lat in (("ns/tn-i-1", 1.0), ("ns/tn-i-2", 3.0),
                       ("ns/tn-s-1", 0.1), ("ns/tn-s-2", 0.3)):
            led.stamp_enqueue(k, t=t0)
            led.commit_many([k], t=t0 + lat)
        now = t0 + 10.0
        inc = led.window_percentile(0.99, window=60.0, now=now,
                                    match=lanes["incumbent"])
        sh = led.window_percentile(0.99, window=60.0, now=now,
                                   match=lanes["shadow"])
        assert inc == pytest.approx(3.0)
        assert sh == pytest.approx(0.3)
        assert led.window_count(60.0, now, lanes["incumbent"]) == 2
        assert led.window_count(60.0, now, lanes["shadow"]) == 2
        # the unfiltered view still sees everything
        assert led.window_count(60.0, now) == 4
        # outside the window: nothing
        assert led.window_count(5.0, t0 + 100.0, lanes["shadow"]) == 0

    def test_lane_utilization_reads_hosting_nodes_only(self):
        from kubernetes_tpu.cache.node_info import NodeInfo
        lanes = prefix_lanes("tn-i-", "tn-s-")
        nis = {}
        for i in range(3):
            ni = NodeInfo()
            ni.set_node(mknode(i, cpu=1000))
            nis[f"n{i}"] = ni
        p = mkpod("tn-i-0", cpu=500)
        p.node_name = "n0"
        nis["n0"].add_pod(p)
        q = mkpod("tn-s-0", cpu=250)
        q.node_name = "n1"
        nis["n1"].add_pod(q)
        assert lane_utilization(nis, lanes["incumbent"]) == \
            pytest.approx(0.5)
        assert lane_utilization(nis, lanes["shadow"]) == \
            pytest.approx(0.25)
        empty = lane_utilization(
            {}, lanes["shadow"])
        assert math.isnan(empty)          # no-data is NaN, not zero


class TestClusterUtilizationGauge:
    def test_cluster_utilization_math(self):
        from kubernetes_tpu.cache.node_info import (
            NodeInfo, cluster_utilization)
        nis = {}
        for i in range(2):
            ni = NodeInfo()
            ni.set_node(mknode(i, cpu=1000))
            nis[f"n{i}"] = ni
        p = mkpod("a", cpu=500)
        p.node_name = "n0"
        nis["n0"].add_pod(p)
        u = cluster_utilization(nis)
        assert u["cpu"] == pytest.approx(0.25)     # 500 / 2000
        assert set(u) == {"cpu", "memory", "ephemeral_storage"}
        assert cluster_utilization({})["cpu"] == 0.0

    def test_gauge_and_debug_section_live(self):
        from kubernetes_tpu import obs
        from kubernetes_tpu.scheduler import CLUSTER_UTILIZATION
        store = Store()
        for i in range(2):
            store.create(NODES, mknode(i, cpu=1000))
        sched = Scheduler(store, percentage_of_nodes_to_score=100)
        sched.sync()
        store.create(PODS, mkpod("a", cpu=500))
        sched.pump()
        sched.schedule_one()
        sched.pump()
        # the snapshot refreshes at the START of a cycle: a second
        # pod's cycle folds pod a into the view the gauge reads
        store.create(PODS, mkpod("b", cpu=100))
        sched.pump()
        sched.schedule_one()
        dbg = obs.debug_snapshot()["scheduler"]
        assert dbg["utilization"]["cpu"] == pytest.approx(0.25)
        # the gauge family reads through the registered callback
        assert float(CLUSTER_UTILIZATION.labels("cpu").value) == \
            pytest.approx(0.25)
