"""Monitoring: rate windows, Prometheus exposition, HTTP endpoint,
interference vote + strategy switch.

Reference coverage analog: the monitor test in CI (ci.yaml runs the Go
monitor test with a 10ms period) and the adaptation tests.
"""
import time
import pytest
import urllib.request

import jax.numpy as jnp
import numpy as np

from kungfu_tpu.monitor import (
    Counters,
    InterferenceDetector,
    MonitorServer,
    RateWindow,
)
from kungfu_tpu.plan import Strategy, make_mesh
from kungfu_tpu.session import Session


def test_rate_window():
    w = RateWindow(window_s=10.0)
    t0 = 100.0
    w.add(1000, t=t0)
    w.add(1000, t=t0 + 1.0)
    assert w.total == 2000
    assert w.rate(now=t0 + 1.0) == 1000.0  # 1000 bytes over 1 s window delta
    # samples age out of the window
    assert w.rate(now=t0 + 100.0) == 0.0


def test_counters_and_prometheus_text():
    c = Counters()
    c.add_egress("peerA", 512)
    c.add_ingress("peerA", 256)
    c.add_egress("peerB", 1)
    text = c.prometheus_text()
    assert 'egress_total_bytes{peer="peerA"} 512' in text
    assert 'ingress_total_bytes{peer="peerA"} 256' in text
    assert 'egress_total_bytes{peer="peerB"} 1' in text
    assert "egress_rate_bytes_per_sec" in text
    etot, itot = c.totals()
    assert etot == {"peerA": 512, "peerB": 1}


def test_monitor_http_endpoint():
    c = Counters()
    c.add_egress("x", 42)
    srv = MonitorServer(counters=c, host="127.0.0.1", port=0).start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert 'egress_total_bytes{peer="x"} 42' in body
        # 404 on unknown path
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/bogus", timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.close()


def test_session_records_egress(monkeypatch):
    from kungfu_tpu.monitor.counters import global_counters

    monkeypatch.setenv("KFT_CONFIG_ENABLE_MONITORING", "1")
    sess = Session(make_mesh(dp=-1))
    x = jnp.ones((sess.size, 4), jnp.float32)
    sess.all_reduce(x, name="egress-probe")
    etot, _ = global_counters().totals()
    assert etot.get("egress-probe", 0) == x.nbytes


def test_session_skips_counters_when_disabled(monkeypatch):
    from kungfu_tpu.monitor.counters import global_counters

    monkeypatch.delenv("KFT_CONFIG_ENABLE_MONITORING", raising=False)
    sess = Session(make_mesh(dp=-1))
    sess.all_reduce(jnp.ones((sess.size, 4), jnp.float32), name="silent-probe")
    etot, _ = global_counters().totals()
    assert "silent-probe" not in etot


class _FakeSession:
    """Deterministic throughput playback for the vote logic."""

    def __init__(self, real: Session):
        self._real = real
        self.strategy = Strategy.BINARY_TREE_STAR
        self.size = real.size
        self.stats = real.stats
        self._tput = 100.0

    def throughput(self):
        return self._tput

    def all_reduce(self, x, name=""):
        return self._real.all_reduce(x, name=name)

    def lift(self, value):
        return self._real.lift(value)

    def local_row(self, stacked):
        return self._real.local_row(stacked)

    def set_strategy(self, s):
        self.strategy = s


def test_interference_vote_switches_strategy():
    real = Session(make_mesh(dp=-1))
    fake = _FakeSession(real)
    det = InterferenceDetector(fake, min_samples=2)
    for _ in range(3):
        det.observe()  # builds reference at 100.0
    assert not det.local_vote()
    fake._tput = 50.0  # below 0.8 * 100
    assert det.local_vote()
    # all 8 virtual peers vote identically -> majority -> switch
    old = fake.strategy
    assert det.check()
    assert fake.strategy != old


def test_interference_no_switch_when_healthy():
    real = Session(make_mesh(dp=-1))
    fake = _FakeSession(real)
    det = InterferenceDetector(fake, min_samples=2)
    for _ in range(3):
        det.observe()
    old = fake.strategy
    assert not det.check()
    assert fake.strategy == old


def test_trace_scope_and_events(monkeypatch):
    import logging
    from kungfu_tpu.utils import trace_scope, log_event

    records = []

    class Sink(logging.Handler):
        def emit(self, r):
            records.append(r.getMessage())

    sink = Sink()
    logger = logging.getLogger("kungfu.trace")
    logger.addHandler(sink)
    from kungfu_tpu.utils.trace import global_trace_buffer

    buf = global_trace_buffer()
    buf.clear()
    try:
        # disabled: no output, no span
        monkeypatch.delenv("KFT_CONFIG_ENABLE_TRACE", raising=False)
        with trace_scope("quiet"):
            pass
        assert records == [] and len(buf) == 0
        monkeypatch.setenv("KFT_CONFIG_ENABLE_TRACE", "1")
        with trace_scope("noisy"):
            time.sleep(0.01)
        log_event("checkpoint-done")
    finally:
        logger.removeHandler(sink)
    # a scope is held by the ring buffer, not logged: only the event logs
    assert records and all("checkpoint-done" in r for r in records)
    (noisy,) = [s for s in buf.spans() if s.name == "noisy"]
    assert noisy.dur >= 0.01
    buf.clear()


def test_rate_window_slow_traffic_not_zero():
    """One add per >window interval must still report a real rate
    (regression: single-in-window sample returned 0)."""
    w = RateWindow(window_s=5.0)
    w.add(1000, t=0.0)
    w.add(1000, t=10.0)  # slower than the window
    assert w.rate(now=10.0) == pytest.approx(100.0)  # 1000 B / 10 s


def test_rate_window_idle_gap_burst():
    """A resumed burst after a long idle gap must not be averaged over the gap
    (the stale delta-anchor bias found in review)."""
    w = RateWindow(window_s=5.0)
    w.add(1000, t=0.0)
    w.add(1000, t=10.0)  # becomes the stale anchor
    # idle until t=600, then a burst at ~1000 B/s
    w.add(1000, t=600.0)
    w.add(1000, t=601.0)
    w.add(1000, t=602.0)
    r = w.rate(now=602.0)
    assert 500.0 <= r <= 2000.0, r  # not ~5 B/s over the 592 s gap


def test_rate_window_slow_traffic_still_measured():
    w = RateWindow(window_s=5.0)
    w.add(700, t=0.0)
    w.add(700, t=7.0)  # one add per 7 s, slower than the window
    assert w.rate(now=7.0) == pytest.approx(100.0)


# -- histograms ------------------------------------------------------------------------


class TestHistogram:
    def test_bucketing_and_cumulative(self):
        from kungfu_tpu.monitor import Histogram

        h = Histogram(bounds=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 5.0, 50.0, 5000.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(5060.5)
        assert h.cumulative() == [("1", 1), ("10", 3), ("100", 4), ("+Inf", 5)]

    def test_percentiles(self):
        from kungfu_tpu.monitor import Histogram

        h = Histogram()
        for v in [2.0] * 50 + [20.0] * 45 + [2000.0] * 5:
            h.observe(v)
        assert h.percentile(0.5) <= 2.5  # in the [1, 2.5] bucket
        assert 10.0 <= h.percentile(0.9) <= 25.0
        assert h.percentile(0.99) >= 1000.0
        assert Histogram().percentile(0.5) is None

    def test_counters_hist_exposition(self):
        c = Counters()
        c.observe_hist("step_latency_ms", 12.0)
        c.observe_hist("collective_latency_ms", 3.0, label="grad")
        text = c.prometheus_text()
        assert "# TYPE step_latency_ms histogram" in text
        assert 'step_latency_ms_bucket{le="25"} 1' in text
        assert 'step_latency_ms_bucket{le="+Inf"} 1' in text
        assert "step_latency_ms_sum 12.0" in text
        assert "step_latency_ms_count 1" in text
        assert 'collective_latency_ms_bucket{op="grad",le="5"} 1' in text
        assert 'collective_latency_ms_sum{op="grad"} 3.0' in text
        assert c.hist_percentile("step_latency_ms", 0.5) == pytest.approx(12.0, rel=0.6)
        assert c.hist_percentile("missing", 0.5) is None

    def test_hist_thread_safety(self):
        import threading

        c = Counters()

        def work():
            for i in range(500):
                c.observe_hist("step_latency_ms", float(i % 97))
                c.inc_event("steps")
                c.set_gauge("g", float(i))

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.events()["steps"] == 2000
        summaries = c.hist_summaries()
        assert summaries["step_latency_ms"][""]["count"] == 2000

    def test_reset_for_reinit_keeps_lifecycle(self):
        c = Counters()
        c.add_egress("grad", 100)
        c.observe_hist("step_latency_ms", 5.0)
        c.inc_event("heals")
        c.set_gauge("heal_mttr_s", 1.5)
        c.reset_for_reinit()
        etot, _ = c.totals()
        assert etot == {}
        assert c.hist_summaries() == {}
        # lifecycle events + gauges describe the JOB, not one incarnation
        assert c.events() == {"heals": 1}
        assert c.gauges() == {"heal_mttr_s": 1.5}


# -- monitor server: /trace + close path -----------------------------------------------


def test_monitor_server_trace_endpoint_and_close_joins():
    import json

    from kungfu_tpu.utils.trace import Span, TraceBuffer

    buf = TraceBuffer()
    buf.add(Span("step", 0.5, 0.01, cat="train"))
    srv = MonitorServer(counters=Counters(), host="127.0.0.1", port=0,
                        trace_buffer=buf).start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/trace", timeout=5
        ).read().decode()
        trace = json.loads(body)
        assert [e["name"] for e in trace["traceEvents"]] == ["step"]
    finally:
        srv.close()
    # the shutdown-leak fix: close() joins the server thread and is idempotent
    assert not srv._thread.is_alive()
    srv.close()


def test_monitor_server_close_without_start():
    srv = MonitorServer(counters=Counters(), host="127.0.0.1", port=0)
    srv.close()  # must not hang waiting for a serve_forever that never ran
    srv.close()


# -- fleet aggregation -----------------------------------------------------------------


class TestFleetAggregation:
    def _two_workers(self):
        from kungfu_tpu.utils.trace import Span, TraceBuffer

        c0, c1 = Counters(), Counters()
        c0.add_egress("grad", 100)
        c1.add_egress("grad", 50)
        c1.add_egress("only-r1", 7)
        c0.observe_hist("step_latency_ms", 10.0)
        c1.observe_hist("step_latency_ms", 30.0)
        c0.inc_event("heals")
        c1.inc_event("heals", 2)
        c0.set_gauge("heal_mttr_s", 1.0)
        c1.set_gauge("heal_mttr_s", 3.0)
        b0, b1 = TraceBuffer(), TraceBuffer()
        b0.add(Span("step", 0.0, 0.1, cat="train"))
        b1.add(Span("step", 0.05, 0.1, cat="train"))
        s0 = MonitorServer(counters=c0, host="127.0.0.1", trace_buffer=b0).start()
        s1 = MonitorServer(counters=c1, host="127.0.0.1", trace_buffer=b1).start()
        return s0, s1

    def test_merged_counters_equal_worker_sums(self):
        from kungfu_tpu.monitor import FleetAggregator

        s0, s1 = self._two_workers()
        agg = FleetAggregator(
            lambda: [(0, f"http://127.0.0.1:{s0.port}"),
                     (1, f"http://127.0.0.1:{s1.port}")],
            host="127.0.0.1",
        )
        try:
            text = agg.merged_metrics()
            # counters: fleet value == sum of the per-worker endpoints
            assert 'egress_total_bytes{peer="grad"} 150' in text
            assert 'egress_total_bytes{peer="grad",rank="0"} 100' in text
            assert 'egress_total_bytes{peer="grad",rank="1"} 50' in text
            # a series only one rank has still merges
            assert 'egress_total_bytes{peer="only-r1"} 7' in text
            assert 'kungfu_events_total{event="heals"} 3' in text
            # histogram components sum like counters
            assert "step_latency_ms_count 2" in text
            assert "step_latency_ms_sum 40" in text
            # gauges: min/max/avg + per-rank breakdown
            assert 'kungfu_gauge{name="heal_mttr_s",agg="min"} 1' in text
            assert 'kungfu_gauge{name="heal_mttr_s",agg="max"} 3' in text
            assert 'kungfu_gauge{name="heal_mttr_s",agg="avg"} 2' in text
            assert 'kungfu_gauge{name="heal_mttr_s",rank="1"} 3' in text
            # both ranks accounted for
            assert 'kungfu_fleet_ranks_scraped{rank="0"} 1' in text
            assert 'kungfu_fleet_ranks_scraped{rank="1"} 1' in text
        finally:
            agg.close()
            s0.close()
            s1.close()

    def test_merged_timeline_per_rank_lanes(self):
        from kungfu_tpu.monitor import FleetAggregator

        s0, s1 = self._two_workers()
        agg = FleetAggregator(
            lambda: [(0, f"http://127.0.0.1:{s0.port}"),
                     (1, f"http://127.0.0.1:{s1.port}")],
            host="127.0.0.1",
        )
        try:
            tl = agg.merged_timeline()
            pids = {e["pid"] for e in tl["traceEvents"]}
            assert pids == {0, 1}
            steps = [e for e in tl["traceEvents"] if e["name"] == "step"]
            assert len(steps) == 2 and {e["pid"] for e in steps} == {0, 1}
        finally:
            agg.close()
            s0.close()
            s1.close()

    def test_dead_worker_reported_not_fatal(self):
        from kungfu_tpu.monitor import FleetAggregator

        s0, _ = self._two_workers()
        agg = FleetAggregator(
            lambda: [(0, f"http://127.0.0.1:{s0.port}"),
                     (1, "http://127.0.0.1:1")],  # nobody listens there
            host="127.0.0.1", timeout_s=0.5,
        )
        try:
            text = agg.merged_metrics()
            assert 'kungfu_fleet_ranks_scraped{rank="0"} 1' in text
            assert 'kungfu_fleet_ranks_scraped{rank="1"} 0' in text
            assert "kungfu_fleet_scrape_errors_total 1" in text
        finally:
            agg.close()
            s0.close()

    def test_parse_prometheus_roundtrip(self):
        from kungfu_tpu.monitor import parse_prometheus

        types, series = parse_prometheus(
            "# TYPE x counter\nx{a=\"b\"} 3\nx 4.5\n# TYPE g gauge\ng 1\n"
        )
        assert types == {"x": "counter", "g": "gauge"}
        assert series[("x", (("a", "b"),))] == 3.0
        assert series[("x", ())] == 4.5
        assert series[("g", ())] == 1.0


# -- journal ---------------------------------------------------------------------------


class TestJournal:
    def test_round_trip(self, tmp_path):
        from kungfu_tpu.monitor.journal import Journal, read_journal

        p = str(tmp_path / "journal-test.jsonl")
        j = Journal(p)
        j.emit("resize", version=2, old_size=2, new_size=3)
        j.emit("heal", version=3, mttr_s=1.5, phases={"teardown_s": 0.1})
        j.close()
        events = read_journal(p)
        assert [e["event"] for e in events] == ["resize", "heal"]
        assert events[0]["version"] == 2
        assert events[1]["phases"] == {"teardown_s": 0.1}
        for e in events:
            assert "t_wall" in e and "t_job" in e
            assert "rank" in e and "cluster_version" in e

    def test_context_stamps_and_override(self, tmp_path):
        from kungfu_tpu.monitor import journal as J

        p = str(tmp_path / "journal-ctx.jsonl")
        j = J.Journal(p)
        old = dict(J._context)
        try:
            J.set_journal_context(rank=3, cluster_version=7)
            j.emit("strategy_switch", old="STAR", new="RING")
            j.emit("heal_shrink", cluster_version=8)  # explicit field wins
        finally:
            J._context.update(old)
        j.close()
        e0, e1 = J.read_journal(p)
        assert e0["rank"] == 3 and e0["cluster_version"] == 7
        assert e1["cluster_version"] == 8

    def test_merge_orders_by_wall_time(self, tmp_path):
        import json

        from kungfu_tpu.monitor.journal import merge_journals

        a, b = tmp_path / "journal-a.jsonl", tmp_path / "journal-b.jsonl"
        a.write_text(json.dumps({"event": "late", "t_wall": 20.0}) + "\n")
        b.write_text(json.dumps({"event": "early", "t_wall": 10.0}) + "\n"
                     + "NOT JSON — torn write\n"
                     + json.dumps({"event": "mid", "t_wall": 15.0}) + "\n")
        merged = merge_journals([str(a), str(b)])
        assert [e["event"] for e in merged] == ["early", "mid", "late"]

    def test_journal_event_noop_when_unconfigured(self, monkeypatch):
        from kungfu_tpu.monitor import journal as J

        monkeypatch.delenv(J.JOURNAL_FILE_ENV, raising=False)
        monkeypatch.delenv(J.JOURNAL_DIR_ENV, raising=False)
        J._reset_for_tests()
        try:
            J.journal_event("anything", field=1)  # must not raise
            assert J.global_journal() is None
        finally:
            J._reset_for_tests()

    def test_journal_event_writes_via_env(self, tmp_path, monkeypatch):
        from kungfu_tpu.monitor import journal as J

        path = str(tmp_path / "j.jsonl")
        monkeypatch.setenv(J.JOURNAL_FILE_ENV, path)
        J._reset_for_tests()
        try:
            J.journal_event("preemption", step=12)
            events = J.read_journal(path)
            assert events[0]["event"] == "preemption" and events[0]["step"] == 12
        finally:
            J._reset_for_tests()
