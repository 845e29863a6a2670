"""Fleet time-series store, SLO engine, scaling observatory
(docs/observability.md "Time series & SLOs" / "Scaling observatory").

Covers: sampler bounds/downsampling/retention, counter-rate and windowed
histogram-percentile sampling (incl. the reset_for_reinit epoch re-anchor
the heal path exercises), the fleet `/history` and `/slo` endpoints, SLO
arm/clear hysteresis + exit-code mode, journal size-capped rotation, and
the scaling-efficiency math on synthetic throughput curves.
"""
import json
import urllib.request

import pytest

from kungfu_tpu.monitor.counters import Counters
from kungfu_tpu.monitor.slo import (
    SLO_EXIT_CODE,
    SLOEngine,
    SLORule,
    load_rules,
    resolve_exit_code,
)
from kungfu_tpu.monitor.timeseries import (
    CountersSampler,
    Series,
    TimeSeriesStore,
    percentile_from_buckets,
)

pytestmark = pytest.mark.timeseries


# -- series / store bounds -------------------------------------------------------------


class TestSeriesBounds:
    def test_fine_ring_bounded_and_downsampled(self):
        s = Series(fine_cap=16, coarse_cap=8, chunk=4)
        for i in range(100):
            s.append(float(i), float(i))
        assert len(s.fine) <= 16
        assert len(s.coarse) <= 8
        # the newest samples stay at full resolution
        assert s.latest() == (99.0, 99.0)
        assert [v for _, v in s.fine][-3:] == [97.0, 98.0, 99.0]

    def test_coarse_points_aggregate_min_max_avg(self):
        s = Series(fine_cap=4, coarse_cap=8, chunk=4)
        for i, v in enumerate([1.0, 3.0, 2.0, 4.0]):
            s.append(float(i), v)
        s.append(4.0, 9.0)  # overflows: folds the first chunk
        t0, t1, mn, mx, avg, n = s.coarse[0]
        assert (t0, t1) == (0.0, 3.0)
        assert (mn, mx) == (1.0, 4.0)
        assert avg == pytest.approx(2.5)
        assert n == 4

    def test_coarse_retention_is_bounded_too(self):
        s = Series(fine_cap=4, coarse_cap=2, chunk=4)
        for i in range(100):
            s.append(float(i), float(i))
        assert len(s.coarse) == 2  # oldest coarse points dropped
        assert len(s) <= 4 + 2

    def test_store_series_cap_counts_drops(self):
        store = TimeSeriesStore(max_series=2)
        store.record("a", 0.0, 1.0)
        store.record("b", 0.0, 1.0)
        store.record("c", 0.0, 1.0)  # past the cap: dropped, counted
        store.record("a", 1.0, 2.0)  # existing series keep recording
        assert store.names() == ["a", "b"]
        assert store.dropped_series == 1
        assert store.latest("a") == (1.0, 2.0)

    def test_snapshot_round_trip_and_rank_filters(self):
        store = TimeSeriesStore()
        store.record("gauge:g", 0.0, 1.0)
        store.record("gauge:g@0", 0.0, 2.0)
        store.record("gauge:g@1", 0.0, 3.0)
        fleet = store.snapshot()["series"]
        assert set(fleet) == {"gauge:g"}  # rank splits hidden by default
        split = store.snapshot(include_ranks=True)["series"]
        assert set(split) == {"gauge:g", "gauge:g@0", "gauge:g@1"}
        one = store.snapshot(rank=1)["series"]
        assert set(one) == {"gauge:g@1"}
        restored = TimeSeriesStore.from_snapshot(store.snapshot(
            include_ranks=True))
        assert restored.latest("gauge:g@1") == (0.0, 3.0)

    def test_dump_is_atomic_and_readable(self, tmp_path):
        store = TimeSeriesStore()
        store.record("gauge:x", 1.0, 2.0)
        path = str(tmp_path / "timeseries-test.json")
        assert store.dump(path) == path
        with open(path) as f:
            snap = json.load(f)
        assert snap["series"]["gauge:x"]["fine"] == [[1.0, 2.0]]
        # no torn tmp file left behind
        assert list(tmp_path.iterdir()) == [tmp_path / "timeseries-test.json"]


# -- percentile math -------------------------------------------------------------------


def test_percentile_from_buckets():
    pairs = [(10.0, 50), (100.0, 45), (float("inf"), 5)]
    assert percentile_from_buckets(pairs, 0.5) <= 10.0
    assert 10.0 <= percentile_from_buckets(pairs, 0.9) <= 100.0
    assert percentile_from_buckets(pairs, 0.99) >= 100.0
    assert percentile_from_buckets([], 0.5) is None
    assert percentile_from_buckets([(10.0, 0)], 0.5) is None


# -- counters sampler ------------------------------------------------------------------


class TestCountersSampler:
    def test_gauges_rates_and_windowed_percentiles(self):
        c = Counters()
        store = TimeSeriesStore()
        s = CountersSampler(c, store)
        c.set_gauge("queue_depth", 3.0)
        c.inc_event("steps", 10)
        c.observe_hist("step_latency_ms", 10.0)
        s.sample_once(now=0.0)
        c.inc_event("steps", 5)
        for _ in range(10):
            c.observe_hist("step_latency_ms", 400.0)
        s.sample_once(now=2.0)
        assert store.latest("gauge:queue_depth") == (2.0, 3.0)
        # rate = 5 events over 2 s
        assert store.latest("rate:steps")[1] == pytest.approx(2.5)
        # the WINDOWED p99 sees only the new 400ms observations — the
        # 10 ms sample from the first window cannot dilute it
        t, p99 = store.latest("hist:step_latency_ms:p99")
        assert t == 2.0 and p99 >= 250.0

    def test_windowed_percentile_recovers_after_slow_window(self):
        """The SLO-clear enabler: after a slow window passes, the delta
        percentile drops back — a lifetime percentile would stay pinned."""
        c = Counters()
        store = TimeSeriesStore()
        s = CountersSampler(c, store)
        for _ in range(20):
            c.observe_hist("step_latency_ms", 300.0)
        s.sample_once(now=0.0)
        for _ in range(20):
            c.observe_hist("step_latency_ms", 2.0)
        s.sample_once(now=1.0)
        _, p99 = store.latest("hist:step_latency_ms:p99")
        assert p99 <= 50.0
        # lifetime percentile stays high — proving the window matters
        assert c.hist_percentile("step_latency_ms", 0.99) >= 200.0

    def test_no_new_observations_stay_silent(self):
        c = Counters()
        store = TimeSeriesStore()
        s = CountersSampler(c, store)
        c.observe_hist("step_latency_ms", 10.0)
        s.sample_once(now=0.0)
        s.sample_once(now=1.0)  # nothing new
        pts = store.recent("hist:step_latency_ms:p99", 0.0)
        assert len(pts) == 1  # stale windows don't fabricate samples

    def test_survives_reset_for_reinit(self):
        """The heal-path interaction: reset_for_reinit drops hists and
        rate windows mid-flight; the sampler must re-anchor, never emit a
        negative rate or a percentile of the dead incarnation."""
        c = Counters()
        store = TimeSeriesStore()
        s = CountersSampler(c, store)
        c.inc_event("steps", 10)
        c.add_egress("grad", 100)
        c.observe_hist("step_latency_ms", 500.0)
        s.sample_once(now=0.0)
        c.reset_for_reinit()  # heal re-rendezvous
        c.observe_hist("step_latency_ms", 5.0)
        c.inc_event("steps", 2)
        s.sample_once(now=1.0)
        # rates re-anchored (no sample until the next healthy delta)
        for _, v in store.recent("rate:steps", 0.0):
            assert v >= 0.0
        # the post-heal percentile reflects ONLY the new incarnation
        t, p99 = store.latest("hist:step_latency_ms:p99")
        assert t == 1.0 and p99 <= 50.0
        c.inc_event("steps", 4)
        s.sample_once(now=2.0)
        assert store.latest("rate:steps")[1] == pytest.approx(4.0)


# -- SLO engine ------------------------------------------------------------------------


def _engine(rule, store, journal):
    return SLOEngine(store, rules=[rule], journal=journal, clock=lambda: 0.0)


class TestSLOEngine:
    def test_arm_clear_hysteresis(self):
        events = []
        store = TimeSeriesStore()
        rule = SLORule("lat", "gauge:m", "<=", 100.0, sustain_s=2.0,
                       clear_s=2.0)
        eng = _engine(rule, store, lambda ev, **kw: events.append((ev, kw)))
        # violation shorter than sustain: no breach
        store.record("gauge:m", 0.0, 500.0)
        eng.evaluate(now=0.0)
        assert eng.active() == []
        store.record("gauge:m", 1.0, 500.0)
        eng.evaluate(now=1.0)
        assert eng.active() == []
        store.record("gauge:m", 2.5, 500.0)
        eng.evaluate(now=2.5)  # sustained past 2 s -> breach
        assert eng.active() == ["lat"]
        assert [e for e, _ in events] == ["slo_breach"]
        assert events[0][1]["rule"] == "lat"
        # healthy again, but must SUSTAIN health to clear
        store.record("gauge:m", 3.0, 10.0)
        eng.evaluate(now=3.0)
        assert eng.active() == ["lat"]
        store.record("gauge:m", 5.5, 10.0)
        eng.evaluate(now=5.5)
        assert eng.active() == []
        assert [e for e, _ in events] == ["slo_breach", "slo_cleared"]
        assert eng.breach_total == 1  # a cleared breach still counts

    def test_flapping_never_arms(self):
        """A boundary-hugging metric alternating healthy/violating can
        never sustain a violation window — the anti-flap contract."""
        events = []
        store = TimeSeriesStore()
        rule = SLORule("f", "gauge:m", "<=", 100.0, sustain_s=3.0)
        eng = _engine(rule, store, lambda ev, **kw: events.append(ev))
        for i in range(20):
            v = 500.0 if i % 2 else 50.0
            store.record("gauge:m", float(i), v)
            eng.evaluate(now=float(i))
        assert events == [] and eng.breach_total == 0

    def test_same_sample_does_not_advance_streak(self):
        """Polling /slo faster than the sampler must not fake sustain."""
        store = TimeSeriesStore()
        rule = SLORule("lat", "gauge:m", "<=", 100.0, sustain_s=2.0)
        eng = _engine(rule, store, lambda *a, **k: None)
        store.record("gauge:m", 0.0, 500.0)
        for _ in range(50):
            eng.evaluate(now=10.0)  # one violating sample, many evals
        assert eng.active() == []

    def test_no_data_is_not_a_breach(self):
        store = TimeSeriesStore()
        rule = SLORule("ghost", "gauge:absent", "<=", 1.0, sustain_s=0.0)
        eng = _engine(rule, store, lambda *a, **k: None)
        rep = eng.evaluate(now=1.0)
        assert rep["rules"]["ghost"]["no_data"] is True
        assert eng.breach_total == 0

    def test_ratio_expr(self):
        store = TimeSeriesStore()
        store.record("a", 1.0, 30.0)
        store.record("b", 1.0, 10.0)
        rule = SLORule("ratio", "a/b", "<=", 2.0, sustain_s=0.0)
        eng = _engine(rule, store, lambda *a, **k: None)
        eng.evaluate(now=1.0)
        assert eng.active() == ["ratio"]  # 3.0 > 2.0

    def test_exit_code_contract(self):
        assert resolve_exit_code(0, 0) == 0
        assert resolve_exit_code(0, 2) == SLO_EXIT_CODE
        assert resolve_exit_code(7, 3) == 7  # real failures never masked

    def test_load_rules_file_and_defaults(self, tmp_path, monkeypatch):
        monkeypatch.delenv("KFT_SLO_FILE", raising=False)
        defaults = load_rules()
        assert any(r.name == "scaling_efficiency" for r in defaults)
        assert any(r.name == "step_latency_p99" for r in defaults)
        p = tmp_path / "slo.json"
        p.write_text(json.dumps({"rules": [
            {"name": "mine", "metric": "gauge:x", "op": ">=",
             "threshold": 1.0, "sustain_s": 5.0, "severity": "page"},
        ]}))
        mine = load_rules(str(p))
        assert [r.name for r in mine] == ["mine"]  # file takes control
        p.write_text(json.dumps({"include_defaults": True, "rules": [
            {"name": "step_latency_p99", "metric": "gauge:x",
             "op": "<=", "threshold": 9.0},
        ]}))
        merged = load_rules(str(p))
        by_name = {r.name: r for r in merged}
        assert by_name["step_latency_p99"].threshold == 9.0  # override wins
        assert "scaling_efficiency" in by_name

    def test_bad_op_rejected(self):
        with pytest.raises(ValueError):
            SLORule("x", "gauge:x", "!=", 1.0)


# -- fleet endpoints -------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.read().decode(), r.headers.get("Content-Type")


class TestFleetHistoryAndSLO:
    def _fleet(self, rules=None):
        from kungfu_tpu.monitor import FleetAggregator, MonitorServer

        c0, c1 = Counters(), Counters()
        for c, lat in ((c0, 10.0), (c1, 30.0)):
            c.observe_hist("step_latency_ms", lat)
            c.inc_event("steps", 4)
            c.set_gauge("heal_mttr_s", 1.0)
        s0 = MonitorServer(counters=c0, host="127.0.0.1").start()
        s1 = MonitorServer(counters=c1, host="127.0.0.1").start()
        agg = FleetAggregator(
            lambda: [(0, f"http://127.0.0.1:{s0.port}"),
                     (1, f"http://127.0.0.1:{s1.port}")],
            host="127.0.0.1", slo_rules=rules or [],
        )
        return agg, (s0, c0), (s1, c1)

    def test_history_endpoint_fleet_and_rank_views(self):
        agg, (s0, c0), (s1, c1) = self._fleet()
        agg._sampler.straggler = False
        try:
            agg._thread.start()
            agg._sampler.tick(now=1.0)
            c0.inc_event("steps", 6)
            c1.inc_event("steps", 2)
            c0.observe_hist("step_latency_ms", 20.0)
            agg._sampler.tick(now=2.0)
            body, ctype = _get(f"http://127.0.0.1:{agg.port}/history")
            assert ctype == "application/json"
            snap = json.loads(body)
            names = set(snap["series"])
            assert "rate:steps" in names
            assert "hist:step_latency_ms:p99" in names
            assert not any("@" in n for n in names)  # fleet-summed view
            # fleet rate == sum across ranks: 8 events over 1 s
            pts = snap["series"]["rate:steps"]["fine"]
            assert pts[-1][1] == pytest.approx(8.0)
            body, _ = _get(
                f"http://127.0.0.1:{agg.port}/history?split=rank&series=rate:")
            split = json.loads(body)
            assert "rate:steps@0" in split["series"]
            assert split["series"]["rate:steps@0"]["fine"][-1][1] == pytest.approx(6.0)
            body, _ = _get(f"http://127.0.0.1:{agg.port}/history?rank=1")
            only1 = json.loads(body)
            assert set(k.split("@")[1] for k in only1["series"]) == {"1"}
        finally:
            agg.close()
            s0.close()
            s1.close()

    def test_slo_endpoint_reports_breach(self):
        rule = SLORule("mttr", "gauge:heal_mttr_s", "<=", 0.5, sustain_s=0.0)
        agg, (s0, _), (s1, _) = self._fleet(rules=[rule])
        agg._sampler.straggler = False
        try:
            agg._thread.start()
            agg._sampler.tick(now=1.0)  # heal_mttr_s avg = 1.0 > 0.5
            body, ctype = _get(f"http://127.0.0.1:{agg.port}/slo")
            assert ctype == "application/json"
            rep = json.loads(body)
            assert rep["active"] == ["mttr"]
            assert rep["rules"]["mttr"]["breached"] is True
            assert agg.slo_breach_total() == 1
        finally:
            agg.close()
            s0.close()
            s1.close()

    def test_worker_history_endpoint(self):
        from kungfu_tpu.monitor import MonitorServer

        c = Counters()
        store = TimeSeriesStore()
        CountersSampler(c, store).sample_once(now=0.0)
        c.set_gauge("g", 5.0)
        CountersSampler(c, store).sample_once(now=1.0)
        srv = MonitorServer(counters=c, host="127.0.0.1",
                            ts_store=store).start()
        try:
            body, ctype = _get(f"http://127.0.0.1:{srv.port}/history")
            assert ctype == "application/json"
            snap = json.loads(body)
            assert snap["series"]["gauge:g"]["fine"][-1] == [1.0, 5.0]
        finally:
            srv.close()


# -- prometheus exposition compliance --------------------------------------------------


class TestPrometheusCompliance:
    @staticmethod
    def _check_exposition(text):
        """Text-format 0.0.4: every sample's family has exactly one
        preceding # TYPE (and a # HELP), families are contiguous."""
        typed, helped, seen_families = {}, set(), []
        family_of_sample = []
        for line in text.splitlines():
            if not line.strip():
                continue
            if line.startswith("# HELP "):
                helped.add(line.split()[2])
                continue
            if line.startswith("# TYPE "):
                name = line.split()[2]
                assert name not in typed, f"duplicate TYPE for {name}"
                typed[name] = line.split()[3]
                seen_families.append(name)
                continue
            name = line.split("{")[0].split(" ")[0]
            family_of_sample.append(name)
        for name in family_of_sample:
            base = name
            for sfx in ("_bucket", "_sum", "_count"):
                if name.endswith(sfx) and name[: -len(sfx)] in typed:
                    base = name[: -len(sfx)]
            assert base in typed, f"sample {name} has no TYPE"
            assert base in helped, f"sample {name} has no HELP"

    def test_worker_exposition(self):
        c = Counters()
        c.add_egress("peer", 10)
        c.inc_event("heals")
        c.set_gauge("g", 1.0)
        c.observe_hist("step_latency_ms", 5.0)
        c.observe_hist("collective_latency_ms", 5.0, label="grad")
        self._check_exposition(c.prometheus_text())

    def test_fleet_exposition_and_content_types(self):
        from kungfu_tpu.monitor import FleetAggregator, MonitorServer

        c = Counters()
        c.inc_event("steps", 3)
        c.observe_hist("step_latency_ms", 5.0)
        srv = MonitorServer(counters=c, host="127.0.0.1").start()
        agg = FleetAggregator(
            lambda: [(0, f"http://127.0.0.1:{srv.port}"),
                     (1, "http://127.0.0.1:1")],  # dead rank
            host="127.0.0.1", timeout_s=0.5, slo_rules=[],
        )
        try:
            agg._thread.start()
            body, ctype = _get(f"http://127.0.0.1:{agg.port}/metrics")
            assert ctype == "text/plain; version=0.0.4"
            self._check_exposition(body)
            # the 0/1 reachability series appears exactly once, complete
            assert body.count('# TYPE kungfu_fleet_ranks_scraped') == 1
            assert 'kungfu_fleet_ranks_scraped{rank="0"} 1' in body
            assert 'kungfu_fleet_ranks_scraped{rank="1"} 0' in body
            wbody, wctype = _get(f"http://127.0.0.1:{srv.port}/metrics")
            assert wctype == "text/plain; version=0.0.4"
            assert "# HELP kungfu_events_total" in wbody
        finally:
            agg.close()
            srv.close()


# -- journal rotation ------------------------------------------------------------------


class TestJournalRotation:
    def test_rotates_at_cap_and_reads_in_order(self, tmp_path):
        from kungfu_tpu.monitor.journal import (
            Journal,
            read_journal_segments,
            segment_paths,
        )

        p = str(tmp_path / "journal-x.jsonl")
        j = Journal(p, max_bytes=2048)
        n = 120  # ~150 B/record -> several rotations
        for i in range(n):
            j.emit("tick", i=i)
        j.close()
        assert j.rotations >= 2
        segs = segment_paths(p)
        assert segs[-1] == p and len(segs) == 3  # .2, .1, live
        events = read_journal_segments(p)
        idx = [e["i"] for e in events]
        assert idx == sorted(idx)  # oldest-first across segments
        assert idx[-1] == n - 1  # newest record in the live file
        # retention is bounded: the oldest records aged out
        assert idx[0] > 0

    def test_merge_journals_folds_segments(self, tmp_path):
        from kungfu_tpu.monitor.journal import Journal, merge_journals

        p = str(tmp_path / "journal-y.jsonl")
        j = Journal(p, max_bytes=1024)
        for i in range(40):
            j.emit("tick", i=i)
        j.close()
        merged = merge_journals([p])
        assert len(merged) > 6  # more than one segment's worth survived
        assert [e["i"] for e in merged] == sorted(e["i"] for e in merged)

    def test_no_cap_no_rotation(self, tmp_path):
        from kungfu_tpu.monitor.journal import Journal, segment_paths

        p = str(tmp_path / "journal-z.jsonl")
        j = Journal(p)  # unbounded by default
        for i in range(50):
            j.emit("tick", i=i)
        j.close()
        assert j.rotations == 0
        assert segment_paths(p) == [p]


# -- scaling-efficiency math -----------------------------------------------------------


class TestScalingMath:
    def test_slo_gate_on_synthetic_curves(self):
        from kungfu_tpu.monitor.slo import evaluate_scaling_slo

        engine, breached = evaluate_scaling_slo([0.95, 0.9, 0.85])
        assert not breached
        journal = []
        engine, breached = evaluate_scaling_slo(
            [0.95, 0.2], journal=lambda ev, **kw: journal.append((ev, kw)))
        assert breached and engine.breach_total == 1
        assert journal[0][0] == "slo_breach"
        assert journal[0][1]["rule"] == "scaling_efficiency"
