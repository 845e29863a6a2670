"""Launcher-side fleet telemetry aggregator.

Per-worker endpoints (monitor.server) answer for one rank; pod-scale
debugging needs the merged view — "Scale MLPerf-0.6 models on Google TPU-v3
Pods" calls the merged cross-host timeline the difference between debugging
and guessing.  This module gives the launcher (`kungfu-run -telemetry`) a
single endpoint over the whole job:

  /metrics   every worker's Prometheus text merged: counters (and histogram
             components) SUMMED across ranks, gauges aggregated as
             min/max/avg — each series also broken out per rank with a
             `rank="N"` label.  The summed series carry exactly the
             per-worker names/labels, so a fleet counter always equals the
             sum of the worker endpoints it scraped.
  /timeline  every worker's /trace buffer merged into ONE Chrome trace,
             each rank in its own process lane (pid = rank), plus the
             launcher's own lane ("router" — the serving front door's spans
             live in this process) and Perfetto flow arrows for
             cross-process request hops (monitor.requests).  Events dedupe
             by (lane, span_id), so overlapping scrapes can't double-draw.
  /requests  the distributed-request assembler (monitor.requests): per-rank
             /trace feeds stitched into per-request timelines by trace_id,
             with per-phase latency attribution, a bounded reservoir of
             completed requests and the tail sampler (slowest-N + failover/
             SLO-breach touched).
  /ranks     JSON scrape status per rank (reachable, error, url).
  /stragglers  the straggler observatory's merged report (monitor.straggler):
             per-rank compute/data-wait/collective-wait attribution, arrival
             skew + suspicion flags, DCN/ICI hotspot, input starvation.
  /history   the fleet time-series store (monitor.timeseries): the fleet
             sampler's merged-scrape history as JSON series, fleet-summed
             by default, `?split=rank` / `?rank=N` for the per-rank view,
             `?series=<prefix>` to filter.
  /slo       the SLO rule engine's evaluated state (monitor.slo): per-rule
             breached/no_data, active breaches, lifetime breach_total.
  /programs  every rank's compiled-program registry (monitor.programs):
             signatures, budgets, storms per rank.
  /profile   on-demand fleet profiling: `?secs=N` fans the workers'
             jax.profiler capture out in parallel under its own deadline
             (a capture blocks for N seconds by design; `&python=1` is
             passed on to the workers).

Scrapes fan out in PARALLEL with a per-target timeout, so one wedged worker
costs one timeout — not a timeout per wedged rank serialized — and can never
stall the merged endpoints for the whole fleet.  Scrapes happen on demand
per request; the aggregator holds no state between requests beyond the
scrape-error counter and the straggler observatory's rolling windows (those
are the point: /stragglers needs history), so a healed/resized cluster is
picked up by the next request via `targets_fn`.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from ..utils import get_logger
from .counters import help_and_type
from .server import monitor_port

log = get_logger("kungfu.fleet")

# rank -> base URL of that worker's monitor endpoint
Targets = List[Tuple[int, str]]

_SERIES_RE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{([^}]*)\})?\s+(\S+)$")
_LABEL_RE = re.compile(r'(\w+)="([^"]*)"')


def parse_prometheus(text: str) -> Tuple[Dict[str, str], Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float]]:
    """(types, series) from one exposition body.

    types: metric name -> kind from `# TYPE` lines.
    series: (name, sorted-label-tuple) -> value.
    """
    types: Dict[str, str] = {}
    series: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SERIES_RE.match(line)
        if not m:
            continue
        name, rawlabels, value = m.groups()
        try:
            v = float(value)
        except ValueError:
            continue
        labels = tuple(sorted(_LABEL_RE.findall(rawlabels or "")))
        series[(name, labels)] = v
    return types, series


def _series_kind(name: str, types: Dict[str, str]) -> str:
    """counter | gauge | histogram-component for one series name."""
    if name in types:
        return types[name]
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix) and types.get(name[: -len(suffix)]) == "histogram":
            return "counter"  # histogram components merge by summation
    return "gauge"


def _fmt(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(round(v, 6))


def _series_sort_key(key):
    """Stable output order; histogram `le` labels sort numerically so
    bucket series stay ascending (what downstream scrapers expect)."""
    name, labels = key

    def lab_key(kv):
        k, v = kv
        if k == "le":
            try:
                return (k, float("inf") if v == "+Inf" else float(v), "")
            except ValueError:
                return (k, float("inf"), v)
        return (k, 0.0, v)

    return (name, tuple(lab_key(kv) for kv in labels))


def merge_prometheus(texts: Dict[int, str],
                     all_ranks: Optional[Set[int]] = None) -> str:
    """Merge per-rank exposition bodies into the fleet body.

    Counters keep their exact per-worker name+labels with the SUM across
    ranks as the value (the fleet counter == sum of worker counters), plus
    a per-rank breakdown with an added rank label.  Gauges get agg="min/
    max/avg" series plus the per-rank breakdown.  `all_ranks` names every
    TARGETED rank — the `kungfu_fleet_ranks_scraped` series is a complete
    0/1 reachability signal, emitted exactly once (a real Prometheus
    rejects duplicate metric families in one exposition).
    """
    types: Dict[str, str] = {}
    # (name, labels) -> {rank: value}
    merged: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Dict[int, float]] = {}
    for rank, text in texts.items():
        t, series = parse_prometheus(text)
        types.update(t)
        for key, v in series.items():
            merged.setdefault(key, {})[rank] = v

    lines: List[str] = []
    lines.extend(help_and_type("kungfu_fleet_ranks_scraped", "gauge"))
    for rank in sorted(all_ranks if all_ranks is not None else set(texts)):
        up = 1 if rank in texts else 0
        lines.append(f'kungfu_fleet_ranks_scraped{{rank="{rank}"}} {up}')

    emitted_types = set()
    for (name, labels) in sorted(merged, key=_series_sort_key):
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and types.get(name[: -len(suffix)]) == "histogram":
                base = name[: -len(suffix)]
        if base == "kungfu_fleet_ranks_scraped":
            continue  # already emitted as the complete 0/1 series above
        if base not in emitted_types:
            emitted_types.add(base)
            lines.extend(help_and_type(base, types.get(base, "gauge")))
        per_rank = merged[(name, labels)]
        lab = ",".join(f'{k}="{v}"' for k, v in labels)
        kind = _series_kind(name, types)
        if kind in ("counter", "histogram"):
            total = sum(per_rank.values())
            lines.append(f"{name}{{{lab}}} {_fmt(total)}" if lab
                         else f"{name} {_fmt(total)}")
        else:
            vals = list(per_rank.values())
            for agg, v in (("min", min(vals)), ("max", max(vals)),
                           ("avg", sum(vals) / len(vals))):
                al = f'{lab},agg="{agg}"' if lab else f'agg="{agg}"'
                lines.append(f"{name}{{{al}}} {_fmt(v)}")
        for rank in sorted(per_rank):
            rl = f'{lab},rank="{rank}"' if lab else f'rank="{rank}"'
            lines.append(f"{name}{{{rl}}} {_fmt(per_rank[rank])}")
    return "\n".join(lines) + "\n"


def dedupe_chrome_events(events: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Drop duplicate events from a merged Chrome trace.

    Spans carrying a distributed span id dedupe by (lane, span_id) — the
    satellite fix for re-scraped /trace feeds folding the same span into
    one export twice; everything else falls back to the full event shape."""
    seen = set()
    out: List[Dict[str, Any]] = []
    for ev in events:
        args = ev.get("args") or {}
        sid = args.get("span_id")
        if sid:
            key = ("sid", ev.get("pid"), sid)
        else:
            key = (ev.get("pid"), ev.get("tid"), ev.get("name"),
                   ev.get("ph"), ev.get("ts"), ev.get("dur"), ev.get("id"))
        if key in seen:
            continue
        seen.add(key)
        out.append(ev)
    return out


def merge_chrome_traces(traces: Sequence[Tuple[Any, str, Dict[str, Any]]]) -> Dict[str, Any]:
    """One merged Chrome trace from per-process exports.

    traces: (pid, lane_name, chrome_trace_dict) triples — each source's
    events are re-homed onto its pid so every rank gets its own process
    lane in Perfetto; the sources' own process_name metadata is replaced.
    """
    events: List[Dict[str, Any]] = []
    other: Dict[str, Any] = {}
    for pid, lane, trace in traces:
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": lane}})
        sort = pid if isinstance(pid, int) else len(other)
        events.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"sort_index": sort}})
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                continue
            ev = dict(ev)
            ev["pid"] = pid
            events.append(ev)
        if trace.get("otherData"):
            other[str(pid)] = trace["otherData"]
    return {"traceEvents": events, "displayTimeUnit": "ms", "otherData": other}


def targets_from_workers(workers) -> Targets:
    """PeerList -> [(rank, monitor base URL)] via the +16000 port contract."""
    out: Targets = []
    for rank, p in enumerate(workers):
        out.append((rank, f"http://{p.host}:{monitor_port(p.port)}"))
    return out


class FleetAggregator:
    """HTTP server merging every worker's /metrics and /trace on demand.

    targets_fn is consulted per scrape, so elastic resizes/heals are
    reflected without restarting the aggregator.
    """

    def __init__(self, targets_fn: Callable[[], Targets],
                 host: str = "0.0.0.0", port: int = 0, timeout_s: float = 3.0,
                 slo_rules=None, sample_interval_s: Optional[float] = None):
        self.targets_fn = targets_fn
        self.timeout_s = timeout_s
        self._scrape_errors = 0
        # persistent fan-out pool: per-request pools would pay thread spawn
        # per scrape AND block shutdown on a wedged fetch; result(timeout=)
        # below bounds the caller, urlopen's socket timeout bounds the thread
        self._pool = ThreadPoolExecutor(max_workers=16,
                                        thread_name_prefix="kft-scrape")
        self._straggler = None  # monitor.straggler.StragglerMonitor, lazy
        self._requests = None   # monitor.requests.RequestMonitor, lazy
        # fleet time-series store + SLO engine + sampler (the long-horizon
        # layer: /history and /slo read these; the sampler thread fills
        # them every KFT_TS_INTERVAL_S so breaches are detected even when
        # nobody polls)
        from .counters import global_counters
        from .slo import SLOEngine, load_rules
        from .timeseries import FleetSampler, TimeSeriesStore

        self.ts_store = TimeSeriesStore()
        self.slo_engine = SLOEngine(
            self.ts_store,
            rules=slo_rules if slo_rules is not None else load_rules(),
            counters=global_counters(),
            attribution_fn=self._slo_attribution,
        )
        self._sampler = FleetSampler(
            self, self.ts_store, engine=self.slo_engine,
            interval_s=sample_interval_s, local_counters=global_counters(),
        )
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                split = urllib.parse.urlsplit(self.path)
                path = split.path.rstrip("/")
                query = urllib.parse.parse_qs(split.query)
                try:
                    if path in ("", "/metrics"):
                        body = outer.merged_metrics().encode()
                        ctype = "text/plain; version=0.0.4"
                    elif path == "/timeline":
                        body = json.dumps(outer.merged_timeline()).encode()
                        ctype = "application/json"
                    elif path == "/ranks":
                        body = json.dumps(outer.rank_status()).encode()
                        ctype = "application/json"
                    elif path == "/stragglers":
                        body = json.dumps(outer.straggler_report()).encode()
                        ctype = "application/json"
                    elif path == "/requests":
                        body = json.dumps(outer.requests_report()).encode()
                        ctype = "application/json"
                    elif path == "/history":
                        body = json.dumps(outer.history(query)).encode()
                        ctype = "application/json"
                    elif path == "/slo":
                        body = json.dumps(outer.slo_report()).encode()
                        ctype = "application/json"
                    elif path == "/programs":
                        body = json.dumps(outer.programs_report()).encode()
                        ctype = "application/json"
                    elif path == "/profile":
                        try:
                            secs = float((query.get("secs") or ["2"])[0])
                        except ValueError:
                            secs = 2.0
                        python = (query.get("python") or ["0"])[0] == "1"
                        body = json.dumps(
                            outer.profile_fleet(secs, python=python)).encode()
                        ctype = "application/json"
                    else:
                        self.send_response(404)
                        self.end_headers()
                        return
                except Exception as e:  # noqa: BLE001 - a scrape must not kill the server
                    body = f"fleet aggregation error: {e}".encode()
                    self.send_response(500)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self._srv = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._srv.server_address[:2]
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True, name="kft-fleet"
        )
        self._closed = False

    # -- scraping ---------------------------------------------------------------------

    def _fetch(self, url: str) -> str:
        with urllib.request.urlopen(url, timeout=self.timeout_s) as r:
            return r.read().decode()

    def scrape(self, path: str = "/metrics") -> Tuple[Dict[int, str], Dict[int, str]]:
        """({rank: body}, {rank: error}) for one fan-out scrape.

        All targets are fetched concurrently under one shared deadline: the
        whole scrape costs ~one `timeout_s` even when several workers are
        wedged, instead of a timeout per wedged rank serialized."""
        bodies: Dict[int, str] = {}
        errors: Dict[int, str] = {}
        futs = [(rank, self._pool.submit(self._fetch, base + path))
                for rank, base in self.targets_fn()]
        deadline = time.monotonic() + self.timeout_s + 0.5
        for rank, fut in futs:
            try:
                bodies[rank] = fut.result(
                    timeout=max(0.05, deadline - time.monotonic()))
            except Exception as e:  # noqa: BLE001 - OSError/TimeoutError/...
                self._scrape_errors += 1
                errors[rank] = str(e) or type(e).__name__
                fut.cancel()  # frees the slot if the fetch never started
        return bodies, errors

    def merged_metrics(self) -> str:
        bodies, errors = self.scrape("/metrics")
        # per-rank reachability is emitted by merge_prometheus as ONE
        # complete 0/1 series over every TARGETED rank: external pollers —
        # the serving load balancer, an alerting rule — need "rank present
        # and healthy" as a positive signal they can sum, and a compliant
        # exposition allows each metric family exactly once
        text = merge_prometheus(bodies, all_ranks=set(bodies) | set(errors))
        text += "\n".join(help_and_type(
            "kungfu_fleet_scrape_errors_total", "counter")) + "\n"
        text += f"kungfu_fleet_scrape_errors_total {self._scrape_errors}\n"
        return text

    def merged_timeline(self) -> Dict[str, Any]:
        traces, _ = self._scrape_traces()
        mon = self._requests_monitor()
        for rank, _, trace in traces:
            mon.consume_chrome(rank, trace)
        merged = merge_chrome_traces(traces)
        merged["traceEvents"] = dedupe_chrome_events(merged["traceEvents"])
        # cross-lane arrows: shipped-KV and requeued requests hop between
        # rank lanes; the assembler's flow pairs draw them in Perfetto
        merged["traceEvents"].extend(mon.flow_events())
        return merged

    def _scrape_traces(self) -> Tuple[List[Tuple[Any, str, Dict[str, Any]]], Dict]:
        """Every rank's /trace plus this process's own buffer (the serving
        router's lane — its spans never cross a socket) as parsed
        (lane, name, trace) triples."""
        bodies, errors = self.scrape("/trace")
        traces: List[Tuple[Any, str, Dict[str, Any]]] = []
        for rank in sorted(bodies):
            try:
                traces.append((rank, f"rank {rank}", json.loads(bodies[rank])))
            except ValueError:
                errors[rank] = "invalid trace JSON"
        from ..utils import trace as T

        buf = T.global_trace_buffer()
        if T.enabled() and len(buf):
            traces.append(("router", "router",
                           T.export_chrome_trace(buf, pid="router")))
        return traces, errors

    def _requests_monitor(self):
        if self._requests is None:
            from .requests import RequestMonitor

            self._requests = RequestMonitor(
                breach_active_fn=lambda: bool(self.slo_engine.active()))
        return self._requests

    def requests_report(self) -> Dict[str, Any]:
        """One assembler update + report — `/requests`.  Each call scrapes
        every rank's /trace (duplicate spans dedupe, so polling is safe)
        and stitches newly completed requests into timelines."""
        traces, errors = self._scrape_traces()
        mon = self._requests_monitor()
        for rank, _, trace in traces:
            mon.consume_chrome(rank, trace)
        return mon.report(scrape_errors=errors)

    def _slo_attribution(self, rule,
                         viol_since: Optional[float] = None
                         ) -> Optional[Dict[str, Any]]:
        """Phase attribution attached to `slo_breach` journal events for
        request-latency rules: the tail sampler names the dominant phase
        (e.g. dominant_phase=kv_ship) so a breach is actionable without
        replaying the fleet.  The window opens a little before the
        violation's first bad sample (that sample's request completed
        earlier), so the attribution describes the requests that caused
        THIS breach, not ancient history."""
        if "request_latency" not in getattr(rule, "metric", ""):
            return None
        try:
            self.requests_report()  # refresh from the live fleet
        except Exception:  # noqa: BLE001 - attribution is best-effort
            pass
        since = (viol_since - 5.0) if viol_since is not None else None
        # the rule's threshold defines the violating set: requests slower
        # than it VOTE on the dominant phase (request_latency rules are in
        # milliseconds; timelines are in seconds)
        min_lat = None
        try:
            if getattr(rule, "metric", "").startswith("hist:request_latency_ms"):
                min_lat = float(rule.threshold) / 1e3
        except (TypeError, ValueError):
            min_lat = None
        att = self._requests_monitor().attribution(since_t=since,
                                                   min_latency_s=min_lat)
        if not att:
            return None
        return {
            "dominant_phase": att.get("dominant_p99_phase"),
            "dominant_phase_frac": att.get("dominant_p99_frac"),
            "phase_p99_fracs": {p: v.get("p99")
                                for p, v in (att.get("phases") or {}).items()},
        }

    def straggler_report(self) -> Dict[str, Any]:
        """One straggler-observatory update + report (docs/observability.md).

        Each request scrapes every rank's /trace (incremental — the monitor
        high-water-marks what it has already consumed) and /metrics (for the
        link-labelled latency histograms), feeds the rolling detector, and
        returns the merged per-rank attribution + suspicion report.  Poll it
        periodically: rolling statistics need more than one observation."""
        from .straggler import StragglerMonitor

        if self._straggler is None:
            self._straggler = StragglerMonitor()
        mon = self._straggler
        expected = {rank for rank, _ in self.targets_fn()}
        traces, terrs = self.scrape("/trace")
        for rank in sorted(traces):
            try:
                mon.consume_chrome(rank, json.loads(traces[rank]))
            except ValueError:
                terrs[rank] = "invalid trace JSON"
        metrics, _ = self.scrape("/metrics")
        for rank, text in metrics.items():
            mon.consume_metrics(rank, text)
        return mon.report(ranks_expected=expected, scrape_errors=terrs)

    def rank_status(self) -> Dict[str, Any]:
        targets = self.targets_fn()
        bodies, errors = self.scrape("/metrics")
        return {
            "targets": {str(r): url for r, url in targets},
            "reachable": sorted(bodies),
            "errors": {str(r): e for r, e in errors.items()},
        }

    # -- time series + SLO ------------------------------------------------------------

    def history(self, query: Optional[Dict[str, List[str]]] = None) -> Dict[str, Any]:
        """The fleet time-series store as JSON (docs/observability.md).

        Query params: `series=<prefix>` filters names, `split=rank`
        includes the per-rank `...@N` splits, `rank=N` selects one rank's
        splits only, `tenant=T` selects the tenant-labeled hist series
        (`hist:<m>[T]:<pct>`).  Default: the fleet-summed view."""
        from .timeseries import sample_interval_s

        query = query or {}
        prefix = (query.get("series") or [""])[0]
        tenant = (query.get("tenant") or [""])[0]
        rank = None
        if query.get("rank"):
            try:
                rank = int(query["rank"][0])
            except ValueError:
                rank = None
        include_ranks = (query.get("split") or [""])[0] == "rank"
        snap = self.ts_store.snapshot(prefix=prefix,
                                      include_ranks=include_ranks, rank=rank,
                                      contains=f"[{tenant}]" if tenant else "")
        snap["interval_s"] = self._sampler.interval_s or sample_interval_s()
        snap["ticks"] = self._sampler.ticks
        return snap

    # -- program observatory ----------------------------------------------------------

    def programs_report(self) -> Dict[str, Any]:
        """Every rank's compiled-program registry (/programs) merged into
        one per-rank view — which rank blew its signature budget, which is
        storming."""
        bodies, errors = self.scrape("/programs")
        ranks: Dict[str, Any] = {}
        for rank, text in bodies.items():
            try:
                ranks[str(rank)] = json.loads(text)
            except ValueError:
                errors[rank] = "invalid programs JSON"
        return {"ranks": ranks,
                "errors": {str(r): e for r, e in errors.items()}}

    def _fetch_slow(self, url: str, timeout_s: float) -> str:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            return r.read().decode()

    def profile_fleet(self, secs: float, python: bool = False) -> Dict[str, Any]:
        """Fan /profile?secs=N out to every rank concurrently and collect
        each capture's result JSON (`python` passes `&python=1` on: the
        workers' captures then run the profiler's Python tracer).  Uses its
        own deadline — a capture legitimately blocks for `secs`, which the
        ordinary scrape timeout would cut off mid-profile."""
        try:
            secs = min(max(float(secs), 0.05), 120.0)
        except (TypeError, ValueError):
            secs = 2.0
        # trace SERIALIZATION dominates short captures (jax.profiler's
        # stop_trace writes the whole protobuf dump, ~10-20 s even for a
        # 0.3 s window), so the deadline budgets a flat dump allowance on
        # top of the capture itself
        per_target = secs + self.timeout_s + 30.0
        futs = [(rank, self._pool.submit(
                    self._fetch_slow, f"{base}/profile?secs={secs:g}"
                    + ("&python=1" if python else ""), per_target))
                for rank, base in self.targets_fn()]
        out: Dict[str, Any] = {"secs": secs, "ranks": {}, "errors": {}}
        deadline = time.monotonic() + per_target + 0.5
        for rank, fut in futs:
            try:
                out["ranks"][str(rank)] = json.loads(fut.result(
                    timeout=max(0.05, deadline - time.monotonic())))
            except Exception as e:  # noqa: BLE001 - per-rank capture failures isolate
                self._scrape_errors += 1
                out["errors"][str(rank)] = str(e) or type(e).__name__
                fut.cancel()
        return out

    def slo_report(self) -> Dict[str, Any]:
        """One SLO evaluation + report — `/slo`.  Evaluation is per-sample
        idempotent, so polling faster than the sampler is safe."""
        return self.slo_engine.evaluate()

    def slo_breach_total(self) -> int:
        return self.slo_engine.breach_total

    # -- lifecycle --------------------------------------------------------------------

    def start(self) -> "FleetAggregator":
        self._thread.start()
        self._sampler.start()
        log.info("fleet telemetry on http://%s:%d/metrics (+ /timeline, "
                 "/history, /slo)", self.host, self.port)
        return self

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._sampler.close()
        # on-exit dump: the fleet's metric history survives the job for
        # `python -m kungfu_tpu.monitor --merge` forensics
        d = (os.environ.get("KFT_TRACE_DUMP_DIR")
             or os.environ.get("KFT_JOURNAL_DIR"))
        if d and self.ts_store.names():
            self.ts_store.dump(os.path.join(d, "timeseries-fleet.json"))
        if self._thread.is_alive():
            self._srv.shutdown()
        self._srv.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5)
        self._pool.shutdown(wait=False)
