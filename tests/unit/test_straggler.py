"""Straggler observatory: attribution math, skew detector, anomaly
watchdog, hotspot classification, fleet /stragglers endpoint, and graded
policies.

Synthetic span streams drive the detector contracts from the issue: a
clean fleet produces ZERO flags, one slow rank is flagged with the correct
rank (and the correct attribution shape: the victim carries compute, its
peers carry collective-wait), and a recovering rank is cleared only after
the hysteresis window.
"""
import json
import socket
import threading
import time
import urllib.request

import pytest

from kungfu_tpu.monitor.counters import Counters
from kungfu_tpu.monitor.straggler import (
    AnomalyWatchdog,
    LinkHotspot,
    StragglerDetector,
    StragglerMonitor,
    arrival_skews,
    collective_arrivals,
    link_of,
    normalize_spans,
    step_phases,
)
from kungfu_tpu.utils.trace import Span

pytestmark = pytest.mark.straggler


def _clean_rank_spans(steps=12, step_s=5.0, train_s=0.05, data_s=0.01,
                      jitter=0.0):
    """One healthy rank's elastic-loop spans: fast data, fast train, steps
    aligned on a shared clock (job-relative seconds)."""
    spans = []
    for n in range(steps):
        t0 = n * step_s + jitter
        spans.append(Span("step:data", t0, data_s, args={"step": n}))
        arr = t0 + data_s
        spans.append(Span("step:train", arr, train_s,
                          args={"step": n, "t_arrive": arr}))
        spans.append(Span("step", t0, data_s + train_s, args={"step": n}))
    return spans


def _victim_rank_spans(steps=12, slow_from=4, delay_s=4.0, step_s=5.0):
    """The slow rank: an un-spanned stall (the injected sleep / the slow
    compute) BEFORE data+train, so it arrives late at the collective and
    waits ~nothing inside it."""
    spans = []
    for n in range(steps):
        t0 = n * step_s
        d = delay_s if n >= slow_from else 0.0
        spans.append(Span("step:data", t0 + d, 0.01, args={"step": n}))
        arr = t0 + d + 0.01
        spans.append(Span("step:train", arr, 0.05,
                          args={"step": n, "t_arrive": arr}))
        spans.append(Span("step", t0, d + 0.07, args={"step": n}))
    return spans


def _peer_rank_spans(steps=12, slow_from=4, delay_s=4.0, step_s=5.0):
    """A clean peer of the victim: arrives on time, then blocks INSIDE the
    collective waiting for the late arriver."""
    spans = []
    for n in range(steps):
        t0 = n * step_s
        blocked = delay_s if n >= slow_from else 0.0
        spans.append(Span("step:data", t0, 0.01, args={"step": n}))
        spans.append(Span("step:train", t0 + 0.01, 0.05 + blocked,
                          args={"step": n, "t_arrive": t0 + 0.01}))
        spans.append(Span("step", t0, 0.07 + blocked, args={"step": n}))
    return spans


def _quiet_detector(**kw):
    events = []
    kw.setdefault("journal", lambda e, **f: events.append((e, f)))
    return StragglerDetector(**kw), events


# -- span plumbing ---------------------------------------------------------------------


class TestSpanPlumbing:
    def test_normalize_chrome_events(self):
        evs = [
            {"name": "step", "ph": "X", "ts": 1_000_000, "dur": 500_000,
             "cat": "train", "pid": 0, "args": {"step": 3}},
            {"name": "process_name", "ph": "M", "pid": 0},   # metadata: dropped
            {"name": "evt", "ph": "i", "ts": 5.0, "pid": 0},  # instant: dropped
        ]
        spans = normalize_spans(evs)
        assert len(spans) == 1
        s = spans[0]
        assert s.name == "step" and s.t_start == 1.0 and s.dur == 0.5
        assert s.args == {"step": 3}

    def test_normalize_passes_spans_through(self):
        s = Span("x", 1.0, 2.0)
        assert normalize_spans([s]) == [s]

    def test_step_phases(self):
        spans = _clean_rank_spans(steps=2, train_s=0.5, data_s=0.1)
        phases = step_phases(spans)
        assert set(phases) == {0, 1}
        d = phases[0]
        assert d["data_s"] == pytest.approx(0.1)
        assert d["train_s"] == pytest.approx(0.5)
        assert d["step_s"] == pytest.approx(0.6)
        assert d["train_arrival"] == pytest.approx(0.1)

    def test_collective_arrivals_occurrence_indexing(self):
        spans = [
            Span("collective:grad", 1.0, 0.1, args={"t_arrive": 1.0}),
            Span("collective:vote", 1.2, 0.1, args={"t_arrive": 1.2}),
            Span("collective:grad", 2.0, 0.1, args={"t_arrive": 2.0}),
        ]
        out = collective_arrivals(spans)
        assert [k for k, _, _ in out] == [
            ("collective:grad", 0), ("collective:vote", 0),
            ("collective:grad", 1),
        ]
        # start_counts lets incremental consumes continue the numbering
        counts = {}
        collective_arrivals(spans[:2], start_counts=counts)
        more = collective_arrivals(spans[2:], start_counts=counts)
        assert more[0][0] == ("collective:grad", 1)

    def test_arrival_skews(self):
        skews = arrival_skews({0: 10.0, 1: 10.1, 2: 14.0})
        assert skews[0] == 0.0
        assert skews[1] == pytest.approx(0.1)
        assert skews[2] == pytest.approx(4.0)


# -- detector --------------------------------------------------------------------------


class TestDetector:
    def test_clean_fleet_zero_flags(self):
        det, events = _quiet_detector()
        for _ in range(20):
            for r in range(4):
                det.add_sample(r, 0.5 + 0.1 * r, step_ms=10.0,
                               step_s=0.01, data_s=0.001, wait_s=0.002)
            rep = det.evaluate()
            assert rep["suspected"] == []
        assert events == []

    def test_slow_rank_flagged_with_correct_rank(self):
        det, events = _quiet_detector(arm_after=2)
        for _ in range(8):
            det.add_sample(0, 1.0, step_ms=4000.0)
            det.add_sample(1, 2.0, step_ms=4000.0)
            det.add_sample(2, 4000.0, step_ms=4000.0)
        det.evaluate()
        rep = det.evaluate()  # arm_after=2 consecutive verdicts
        assert rep["suspected"] == [2]
        assert [e for e, _ in events] == ["straggler_suspected"]
        assert events[0][1]["rank"] == 2
        assert events[0][1]["skew_ms"] > 1000

    def test_single_blip_not_flagged(self):
        """Hysteresis: one qualifying evaluation does not flag."""
        det, events = _quiet_detector(arm_after=2, window=4)
        for _ in range(4):
            det.add_sample(0, 1.0, step_ms=1000.0)
            det.add_sample(1, 3000.0, step_ms=1000.0)
        det.evaluate()  # one flagged verdict
        # fresh clean samples displace the window before the second verdict
        for _ in range(4):
            det.add_sample(0, 1.0, step_ms=1000.0)
            det.add_sample(1, 1.0, step_ms=1000.0)
        rep = det.evaluate()
        assert rep["suspected"] == []
        assert events == []

    def test_recovering_rank_cleared_after_hysteresis(self):
        det, events = _quiet_detector(arm_after=1, clear_after=3, window=4)
        for _ in range(4):
            det.add_sample(0, 1.0, step_ms=1000.0)
            det.add_sample(1, 3000.0, step_ms=1000.0)
        assert det.evaluate()["suspected"] == [1]
        # recovery: clean samples roll the slow ones out of the window
        for _ in range(4):
            det.add_sample(0, 1.0, step_ms=10.0)
            det.add_sample(1, 1.0, step_ms=10.0)
        assert det.evaluate()["suspected"] == [1]  # clear_streak 1/3
        assert det.evaluate()["suspected"] == [1]  # 2/3
        rep = det.evaluate()                       # 3/3 -> cleared
        assert rep["suspected"] == []
        assert [e for e, _ in events] == ["straggler_suspected",
                                          "straggler_cleared"]
        assert events[1][1]["rank"] == 1

    def test_min_samples_gate(self):
        det, events = _quiet_detector(min_samples=4, arm_after=1)
        for _ in range(3):  # below the gate
            det.add_sample(0, 1.0)
            det.add_sample(1, 9000.0)
            det.evaluate()
        assert events == []

    def test_absolute_floor_suppresses_microskew(self):
        """A rank that is a z-outlier by microseconds is not a straggler."""
        det, events = _quiet_detector(arm_after=1, min_skew_ms=50.0)
        for _ in range(8):
            det.add_sample(0, 0.01, step_ms=10.0)
            det.add_sample(1, 0.01, step_ms=10.0)
            det.add_sample(2, 0.4, step_ms=10.0)  # 0.4ms "outlier"
        assert det.evaluate()["suspected"] == []
        assert events == []

    def test_input_starvation_journaled(self):
        det, events = _quiet_detector(arm_after=2, starve_min_steps=8,
                                      data_frac_threshold=0.6)
        for _ in range(10):
            det.add_sample(0, 1.0, step_ms=100.0, step_s=0.1,
                           data_s=0.08, wait_s=0.005)  # 80% data-wait
            det.add_sample(1, 1.0, step_ms=100.0, step_s=0.1,
                           data_s=0.01, wait_s=0.005)
        det.evaluate()
        rep = det.evaluate()
        assert rep["input_starved"] == [0]
        assert rep["ranks"]["0"]["attribution"]["data_frac"] >= 0.6
        starve = [f for e, f in events if e == "input_starvation"]
        assert len(starve) == 1 and starve[0]["rank"] == 0

    def test_counters_gauges_and_events(self):
        c = Counters()
        det, _ = _quiet_detector(arm_after=1, counters=c)
        for _ in range(8):
            det.add_sample(0, 1.0, step_ms=1000.0)
            det.add_sample(1, 3000.0, step_ms=1000.0)
        det.evaluate()
        g = c.gauges()
        assert g["stragglers_suspected"] == 1
        assert g["straggler_skew_ms_rank1"] > 1000
        assert c.events()["straggler_suspected"] == 1


# -- anomaly watchdog ------------------------------------------------------------------


class TestAnomalyWatchdog:
    def _watchdog(self, **kw):
        events = []
        kw.setdefault("journal", lambda e, **f: events.append((e, f)))
        kw.setdefault("baseline_window", 10)
        kw.setdefault("recent_window", 4)
        kw.setdefault("arm_after", 2)
        kw.setdefault("clear_after", 3)
        return AnomalyWatchdog(**kw), events

    def test_no_regression_on_flat_stream(self):
        w, events = self._watchdog()
        for _ in range(40):
            assert w.observe(10.0) is None
        assert not w.active and events == []

    def test_regression_then_clear_pair(self):
        w, events = self._watchdog()
        for _ in range(12):
            w.observe(10.0)
        outs = [w.observe(25.0) for _ in range(6)]
        assert "regression" in outs and w.active
        assert events[0][0] == "anomaly_regression"
        assert events[0][1]["ratio"] >= 2.0
        outs = [w.observe(10.0) for _ in range(10)]
        assert "cleared" in outs and not w.active
        assert [e for e, _ in events] == ["anomaly_regression",
                                          "anomaly_cleared"]

    def test_single_spike_is_not_a_regression(self):
        """One outlier step (a GC pause, a poll) must not alarm: the recent
        MEDIAN never moves, so the arm streak never starts."""
        w, events = self._watchdog(arm_after=3)
        for _ in range(12):
            w.observe(10.0)
        w.observe(200.0)  # a 20x single-step spike
        for _ in range(8):
            w.observe(10.0)
        assert not w.active and events == []

    def test_reset_drops_baseline(self):
        w, _ = self._watchdog()
        for _ in range(12):
            w.observe(10.0)
        w.reset()
        # post-reset, 30ms IS the new baseline: no alarm
        for _ in range(20):
            assert w.observe(30.0) is None
        assert not w.active

    def test_gauges(self):
        c = Counters()
        w, _ = self._watchdog(counters=c)
        for _ in range(12):
            w.observe(10.0)
        for _ in range(6):
            w.observe(40.0)
        g = c.gauges()
        assert g["anomaly_active"] == 1.0
        assert g["anomaly_step_ratio"] >= 2.0
        assert c.events()["anomaly_regressions"] == 1


# -- hotspot ---------------------------------------------------------------------------


def _prom_hist(op: str, cum: dict) -> str:
    lines = ["# TYPE collective_latency_ms histogram"]
    for le, v in cum.items():
        lines.append(f'collective_latency_ms_bucket{{op="{op}",le="{le}"}} {v}')
    return "\n".join(lines) + "\n"


class TestLinkHotspot:
    def test_link_of(self):
        assert link_of("probe:dcn:int8:1048576") == "dcn"
        assert link_of("cross_all_reduce") == "dcn"
        assert link_of("probe:ici:none:4096") == "ici"
        assert link_of("grad-allreduce") is None

    def test_dcn_inflation_attributed(self):
        events = []
        h = LinkHotspot(min_count=3,
                        journal=lambda e, **f: events.append((e, f)))
        fast = {"1": 0, "5": 10, "10": 10, "50": 10, "+Inf": 10}
        h.consume(0, _prom_hist("probe:dcn:int8", fast))    # delta anchor
        h.consume(0, _prom_hist("probe:ici:none",
                                {"1": 8, "5": 8, "+Inf": 8}))
        # both links observe a healthy window
        h.consume(0, _prom_hist("probe:dcn:int8",
                                {"1": 0, "5": 20, "10": 20, "50": 20,
                                 "+Inf": 20}))
        h.consume(0, _prom_hist("probe:ici:none",
                                {"1": 16, "5": 16, "+Inf": 16}))
        assert h.evaluate()["link"] is None
        # DCN latencies inflate into the 10-50ms bucket; ICI stays flat
        h.consume(0, _prom_hist("probe:dcn:int8",
                                {"1": 0, "5": 20, "10": 20, "50": 30,
                                 "+Inf": 30}))
        h.consume(0, _prom_hist("probe:ici:none",
                                {"1": 24, "5": 24, "+Inf": 24}))
        rep = h.evaluate()
        assert rep["link"] == "dcn"
        assert rep["links"]["dcn"]["ratio"] >= 2.0
        assert rep["links"]["ici"]["ratio"] <= 1.3
        assert [e for e, _ in events] == ["link_hotspot"]
        assert events[0][1]["link"] == "dcn"


# -- fleet-side monitor ----------------------------------------------------------------


class TestStragglerMonitor:
    def _monitor(self):
        events = []
        det = StragglerDetector(arm_after=2,
                                journal=lambda e, **f: events.append((e, f)))
        return StragglerMonitor(detector=det), events

    def test_slow_rank_end_to_end(self):
        mon, events = self._monitor()
        mon.consume_spans(0, _peer_rank_spans())
        mon.consume_spans(1, _peer_rank_spans())
        mon.consume_spans(2, _victim_rank_spans())
        mon.report(ranks_expected={0, 1, 2})
        rep = mon.report(ranks_expected={0, 1, 2})
        assert rep["suspected"] == [2]
        assert rep["matched"] == 12
        att = {r: s["attribution"] for r, s in rep["ranks"].items()}
        # the victim carries compute; its peers carry collective-wait
        assert att["2"]["compute_frac"] > 0.9
        assert att["2"]["collective_wait_frac"] < 0.05
        assert att["0"]["collective_wait_frac"] > 0.5
        assert att["0"]["compute_frac"] < 0.2

    def test_rescrape_does_not_double_count(self):
        """The /trace ring re-serves old spans every scrape; the high-water
        mark must consume each span once."""
        mon, _ = self._monitor()
        for r in range(2):
            mon.consume_spans(r, _clean_rank_spans())
        mon.report(ranks_expected={0, 1})
        matched = mon.matched
        for r in range(2):
            mon.consume_spans(r, _clean_rank_spans())  # identical re-scrape
        mon.report(ranks_expected={0, 1})
        assert mon.matched == matched

    def test_partial_rank_waits_for_the_fleet(self):
        """A step becomes a sample only once EVERY expected rank reported
        it — a rank whose scrape failed this round just defers matching."""
        mon, _ = self._monitor()
        mon.consume_spans(0, _clean_rank_spans())
        rep = mon.report(ranks_expected={0, 1})
        assert rep["matched"] == 0
        mon.consume_spans(1, _clean_rank_spans())
        rep = mon.report(ranks_expected={0, 1})
        assert rep["matched"] == 12

    def test_session_collective_spans_feed_skew(self):
        """Session-level workloads have no step spans — `collective:*`
        spans with t_arrive match by occurrence index."""
        events = []
        det = StragglerDetector(arm_after=1, min_samples=4,
                                journal=lambda e, **f: events.append((e, f)))
        mon = StragglerMonitor(detector=det)
        for r in (0, 1):
            mon.consume_spans(r, [
                Span("collective:grad", i * 1.0, 0.01,
                     args={"t_arrive": i * 1.0})
                for i in range(8)
            ])
        mon.consume_spans(2, [
            Span("collective:grad", i * 1.0 + 0.5, 0.01,
                 args={"t_arrive": i * 1.0 + 0.5})  # 500ms late every time
            for i in range(8)
        ])
        rep = mon.report(ranks_expected={0, 1, 2})
        assert rep["suspected"] == [2]
        assert rep["ranks"]["2"]["skew_ms_mean"] == pytest.approx(500.0)

    def test_chrome_roundtrip(self):
        from kungfu_tpu.utils.trace import export_chrome_trace

        mon, _ = self._monitor()
        for r in range(2):
            trace = export_chrome_trace(_clean_rank_spans(), pid=r)
            mon.consume_chrome(r, trace)
        rep = mon.report(ranks_expected={0, 1})
        assert rep["matched"] == 12


# -- fleet aggregator: /stragglers + parallel scrape -----------------------------------


class TestFleetStragglers:
    def test_stragglers_endpoint(self):
        from kungfu_tpu.monitor import FleetAggregator, MonitorServer
        from kungfu_tpu.utils.trace import TraceBuffer

        bufs = []
        for spans in (_peer_rank_spans(), _victim_rank_spans()):
            b = TraceBuffer()
            for s in spans:
                b.add(s)
            bufs.append(b)
        servers = [MonitorServer(counters=Counters(), host="127.0.0.1",
                                 trace_buffer=b).start() for b in bufs]
        agg = FleetAggregator(
            lambda: [(r, f"http://127.0.0.1:{s.port}")
                     for r, s in enumerate(servers)],
            host="127.0.0.1",
        ).start()
        try:
            rep = None
            for _ in range(3):  # polls build the rolling stats
                body = urllib.request.urlopen(
                    f"http://{agg.host}:{agg.port}/stragglers", timeout=10
                ).read().decode()
                rep = json.loads(body)
            assert rep["suspected"] == [1]
            assert rep["ranks"]["1"]["attribution"]["compute_frac"] > 0.9
            assert "hotspot" in rep
        finally:
            agg.close()
            for s in servers:
                s.close()

    def test_parallel_scrape_bounded_by_one_timeout(self):
        """Four wedged workers must cost ~one timeout total, not four
        serialized — the wedged-worker isolation contract."""
        from kungfu_tpu.monitor import FleetAggregator, MonitorServer

        srv = MonitorServer(counters=Counters(), host="127.0.0.1").start()
        wedged = []
        for _ in range(4):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            s.listen(8)  # accepts connections, never answers
            wedged.append(s)
        agg = FleetAggregator(
            lambda: [(0, f"http://127.0.0.1:{srv.port}")] + [
                (i + 1, f"http://127.0.0.1:{w.getsockname()[1]}")
                for i, w in enumerate(wedged)
            ],
            host="127.0.0.1", timeout_s=1.0,
        )
        try:
            t0 = time.monotonic()
            text = agg.merged_metrics()
            elapsed = time.monotonic() - t0
            assert elapsed < 2.5, f"scrape took {elapsed:.1f}s (serialized?)"
            assert 'kungfu_fleet_ranks_scraped{rank="0"} 1' in text
            for i in range(4):
                assert f'kungfu_fleet_ranks_scraped{{rank="{i + 1}"}} 0' in text
        finally:
            agg.close()
            srv.close()
            for w in wedged:
                w.close()


# -- trace flush (crash-durable dumps) -------------------------------------------------


class TestTraceFlush:
    def test_flush_dump_atomic_and_valid(self, tmp_path, monkeypatch):
        from kungfu_tpu.utils import trace as T

        monkeypatch.setenv(T.DUMP_DIR_ENV, str(tmp_path))
        buf = T.TraceBuffer()
        buf.add(Span("step", 0.5, 0.01, cat="train", args={"step": 1}))
        monkeypatch.setattr(T, "_global_buffer", buf)
        path = T.flush_dump("test")
        assert path is not None
        with open(path) as f:
            trace = json.load(f)
        assert [e["name"] for e in trace["traceEvents"]
                if e.get("ph") == "X"] == ["step"]
        # incremental: a later flush replaces the dump atomically
        buf.add(Span("step", 1.0, 0.01, cat="train", args={"step": 2}))
        assert T.flush_dump("test") == path
        with open(path) as f:
            assert len([e for e in json.load(f)["traceEvents"]
                        if e.get("ph") == "X"]) == 2
        assert not list(tmp_path.glob("*.tmp*"))  # no torn temp files left

    def test_flush_noop_when_unconfigured(self, monkeypatch):
        from kungfu_tpu.utils import trace as T

        monkeypatch.delenv(T.DUMP_DIR_ENV, raising=False)
        assert T.flush_dump("test") is None

    def test_flush_interval_env(self, monkeypatch):
        from kungfu_tpu.utils import trace as T

        monkeypatch.delenv(T.FLUSH_EVERY_ENV, raising=False)
        assert T._flush_interval_s() == T.DEFAULT_FLUSH_S
        monkeypatch.setenv(T.FLUSH_EVERY_ENV, "2.5")
        assert T._flush_interval_s() == 2.5
        monkeypatch.setenv(T.FLUSH_EVERY_ENV, "0")
        assert T._flush_interval_s() == 0.0
        monkeypatch.setenv(T.FLUSH_EVERY_ENV, "junk")
        assert T._flush_interval_s() == T.DEFAULT_FLUSH_S


# -- graded response policies ----------------------------------------------------------


class TestStragglerPolicy:
    def _reports(self, seq):
        it = iter(seq)
        last = {"box": seq[-1]}
        def fn():
            try:
                return next(it)
            except StopIteration:
                return last["box"]
        return fn

    def test_sustained_straggler_triggers_replan_once(self):
        from kungfu_tpu.policy import StragglerPolicy

        calls = []
        pol = StragglerPolicy(
            self._reports([{"suspected": [2]}] * 10),
            replan=lambda reason: calls.append(reason),
            poll_every=1, sustain=3, cooldown_steps=100,
        )
        for _ in range(5):
            pol.after_step({})
        assert calls == ["straggler"]  # fired once, then cooldown holds
        assert pol.any_flagged() and pol.flagged_ranks == {2}

    def test_blip_does_not_escalate(self):
        from kungfu_tpu.policy import StragglerPolicy

        calls = []
        pol = StragglerPolicy(
            self._reports([{"suspected": [1]}, {"suspected": []},
                           {"suspected": [1]}, {"suspected": []}]),
            replan=lambda reason: calls.append(reason),
            poll_every=1, sustain=2,
        )
        for _ in range(4):
            pol.after_step({})
        assert calls == []

    def test_starvation_callback_on_transition(self):
        from kungfu_tpu.policy import StragglerPolicy

        starved = []
        pol = StragglerPolicy(
            self._reports([{"suspected": [], "input_starved": []},
                           {"suspected": [], "input_starved": [0]},
                           {"suspected": [], "input_starved": [0]}]),
            on_starvation=lambda ranks: starved.append(ranks),
            poll_every=1,
        )
        for _ in range(3):
            pol.after_step({})
        assert starved == [[0]]  # once on the transition, not per poll

    def test_unreachable_aggregator_is_not_fatal(self):
        from kungfu_tpu.policy import StragglerPolicy

        def boom():
            raise OSError("connection refused")

        pol = StragglerPolicy(boom, poll_every=1)
        pol.after_step({})  # must not raise
        assert not pol.any_flagged()


class TestReplanStragglerTrigger:
    class FakePlanner:
        def __init__(self, size=2):
            self.session = type("S", (), {"size": size})()
            self.calls = []

        def replan(self, reason, install_for_bytes=0, reps=0):
            self.calls.append(reason)

    def test_metrics_key(self):
        from kungfu_tpu.planner.replan import ReplanPolicy

        fp = self.FakePlanner()
        pol = ReplanPolicy(fp, cooldown_steps=0)
        pol.after_step({"straggler": True})
        assert fp.calls == ["straggler"]

    def test_straggler_fn(self):
        from kungfu_tpu.planner.replan import ReplanPolicy
        from kungfu_tpu.policy import StragglerPolicy

        sp = StragglerPolicy(lambda: {"suspected": [1]}, poll_every=1)
        sp.after_step({})
        fp = self.FakePlanner()
        pol = ReplanPolicy(fp, straggler_fn=sp.any_flagged, cooldown_steps=0)
        pol.after_step({})
        assert fp.calls == ["straggler"]


# -- e2e drill (slow tier; scripts/check.sh runs it too) -------------------------------


@pytest.mark.faults
@pytest.mark.slow
class TestStragglerDrillE2E:
    def test_slow_rank_fingered_not_killed(self):
        from kungfu_tpu.chaos.__main__ import run_straggler_drill

        s = run_straggler_drill(np_=3, timeout_s=240.0)
        assert s["ok"], (s["failures"], s["output_tail"][-2000:])
        assert s["flagged_rank"] == 2
        assert s["false_positives"] == []
        assert s["time_to_flag_s"] < s["stall_deadline_s"]
        assert s["worker_slow_events"] >= 1
