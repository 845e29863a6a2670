"""Byte counters with windowed rates (reference srcs/go/monitor/counters.go).

The reference accumulates per-peer egress/ingress bytes at the rchannel
client/server and computes rates over a sampling window (counters.go:13-110).
On TPU the data plane is inside XLA, so the byte stream is accounted at the
Session boundary instead: every collective records (bytes entering the
collective) per op name, and the store/elastic layers record their own host
traffic per peer.  Rates use the same windowed-delta scheme.
"""
from __future__ import annotations

import bisect
import math
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple


class RateWindow:
    """Windowed byte-rate estimator (counters.go rate sampling)."""

    def __init__(self, window_s: float = 5.0):
        self.window_s = window_s
        self._samples: deque = deque()  # (t, cumulative_bytes)
        self._total = 0

    def add(self, nbytes: int, t: Optional[float] = None) -> None:
        t = time.monotonic() if t is None else t
        self._total += nbytes
        self._samples.append((t, self._total))
        self._trim(t)

    def _trim(self, now: float) -> None:
        # keep one sample older than the window as the delta anchor:
        # traffic slower than one add per window must not read as 0 B/s
        while len(self._samples) >= 2 and now - self._samples[1][0] > self.window_s:
            self._samples.popleft()

    @property
    def total(self) -> int:
        return self._total

    def rate(self, now: Optional[float] = None) -> float:
        """Bytes/sec over the window."""
        now = time.monotonic() if now is None else now
        self._trim(now)
        if not self._samples:
            return 0.0
        t0, b0 = self._samples[0]
        t1, b1 = self._samples[-1]
        if len(self._samples) >= 3 and t1 - t0 > self.window_s:
            # the retained anchor can be arbitrarily old after an idle gap;
            # measuring from it would average the gap into a resumed burst.
            # With >=2 in-window samples, measure from the first of those.
            t0, b0 = self._samples[1]
        if t1 <= t0:
            return 0.0
        return (b1 - b0) / (t1 - t0)


# latency-oriented exponential-ish bucket bounds, milliseconds; denser from
# 10 to 50, where a decode step of a billion-parameter model on one chip
# lies (21 ms), so that its percentiles are not one bucket's interpolation
DEFAULT_BUCKETS_MS = (
    0.5, 1.0, 2.5, 5.0, 10.0, 15.0, 20.0, 25.0, 30.0, 40.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
)


class Histogram:
    """Fixed-bucket Prometheus-style histogram with percentile estimation.

    NOT internally locked — Counters serializes every write/read under its
    single lock (the same discipline the RateWindow tables use), so the
    histogram itself stays a plain counting structure.
    """

    def __init__(self, bounds: Tuple[float, ...] = DEFAULT_BUCKETS_MS):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        self.max = 0.0

    def observe(self, value: float) -> None:
        value = float(value)
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.sum += value
        self.count += 1
        if value > self.max:
            self.max = value

    def cumulative(self) -> List[Tuple[str, int]]:
        """[(le, cumulative_count)] including the "+Inf" row."""
        out: List[Tuple[str, int]] = []
        cum = 0
        for b, c in zip(self.bounds, self.counts):
            cum += c
            out.append((f"{b:g}", cum))
        out.append(("+Inf", cum + self.counts[-1]))
        return out

    def percentile(self, p: float) -> Optional[float]:
        """Nearest-rank percentile, linearly interpolated inside the
        containing bucket; the open +Inf bucket is bounded by the observed
        max.  None with no observations."""
        if self.count == 0:
            return None
        rank = max(1, math.ceil(min(max(p, 0.0), 1.0) * self.count))
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if cum + c >= rank:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) else self.max
                hi = min(hi, self.max) if self.max > 0 else hi
                if hi <= lo:
                    return lo
                return lo + (hi - lo) * (rank - cum) / c
            cum += c
        return self.max  # pragma: no cover - unreachable (counts sum to count)


# HELP strings for the exposition format — a real Prometheus scraping the
# worker/fleet endpoints unmodified expects `# HELP` + `# TYPE` per family
# (text format version 0.0.4).  Unknown families get a generic line.
METRIC_HELP: Dict[str, str] = {
    "egress_total_bytes": "Bytes sent per peer/op at the session boundary.",
    "ingress_total_bytes": "Bytes received per peer at the session boundary.",
    "egress_rate_bytes_per_sec": "Windowed egress byte rate per peer.",
    "ingress_rate_bytes_per_sec": "Windowed ingress byte rate per peer.",
    "collective_logical_total_bytes":
        "Uncompressed collective payload bytes per op.",
    "collective_wire_total_bytes":
        "Bytes the chosen wire format actually moved per op.",
    "collective_compression_ratio": "logical/wire bytes per op (gauge).",
    "collective_quantization_error":
        "Last relative L2 quantization error per op (gauge).",
    "kungfu_events_total": "Lifecycle event counts by event kind.",
    "kungfu_gauge": "Last observed value of a named gauge.",
    "step_latency_ms": "Per-step wall latency histogram (ms).",
    "compile_ms":
        "XLA compile-time histogram (ms; op= labels tracked programs).",
    "collective_latency_ms": "Per-collective wall latency histogram (ms).",
    "collective_overlap":
        "Bucketed gradient-sync dispatch-to-ready latency histogram (ms).",
    "kft_moe_assignments_total":
        "Rows the slot-cache programs routed to each expert, by layer.",
    "kft_moe_experts_hit_total":
        "Distinct experts that owned a row, summed over decode calls and layers.",
    "kft_moe_decode_layer_calls_total":
        "Expert-layer calls of the slot-cache programs (steps x expert layers).",
    "kft_serve_param_bytes":
        "Bytes of parameters the serving engine holds on the device, by dtype.",
    "kft_serve_decode_attn_rows_total":
        "Cache rows of the decode-step attention a layer, summed over steps: "
        "spanned (cache), written and fetched, and the part of each that "
        "is free slots' (written_free, fetched_free).",
    "kft_serve_decode_rows_total":
        "Slot-steps of the decode and verify steps: live (the slot held a "
        "request) and free (its row did no work).",
    "kft_serve_decode_steps_total":
        "Decode steps by what was in flight at their dispatch: ahead (the "
        "step before unread) and synced (nothing); wasted_rows the rows "
        "computed for a request that had ended.",
    "kft_serve_cache_bytes":
        "Bytes of the serving engine's slot cache by kind: rows (leaves "
        "with a position axis, cursors beside them) and state (what a "
        "recurrent layer keeps a slot).",
    "kft_serve_scan_tokens_total":
        "Tokens the recurrent layers' scan walked, a layer: prefill (real "
        "tokens, not bucket padding) and decode (live slot-steps).",
    "kft_serve_sparse_rows_total":
        "Cache rows of the block-selected attention of the decode steps, a "
        "layer and a KV head: written (what busy slots held), fetched (the "
        "rows of the blocks chosen for them) and kernels (compressed keys "
        "their selector scored).",
    "kft_boot_seconds":
        "Seconds of each boot phase of this process so far, on the job "
        "clock (spans of category boot, docs/observability.md Boot).",
    "kft_program_setup_seconds":
        "Seconds this process spent building programs, by stage: trace and "
        "lower (each instant of a thread once), load (persistent-cache "
        "retrieval), compile (real compilations).",
    "kungfu_fleet_ranks_scraped": "1 if the rank answered the fleet scrape.",
    "kungfu_fleet_scrape_errors_total": "Failed fleet scrape fan-out fetches.",
}


def metric_help(name: str) -> str:
    return METRIC_HELP.get(name, f"{name} (kungfu_tpu metric).")


def help_and_type(name: str, kind: str) -> List[str]:
    """The `# HELP` + `# TYPE` header pair for one metric family."""
    return [f"# HELP {name} {metric_help(name)}", f"# TYPE {name} {kind}"]


class Counters:
    """Named egress/ingress accumulators with Prometheus-text exposition."""

    def __init__(self, window_s: float = 5.0):
        self._lock = threading.Lock()
        self._window_s = window_s
        self._egress: Dict[str, RateWindow] = {}
        self._ingress: Dict[str, RateWindow] = {}
        # compressed-collective accounting: logical payload vs bytes the
        # wire actually carried, per op name, + last relative quant error
        self._logical: Dict[str, RateWindow] = {}
        self._wire: Dict[str, RateWindow] = {}
        self._quant_err: Dict[str, float] = {}
        # self-healing accounting: named lifecycle events (worker_failures,
        # heals, worker_restarts, preemptions) + gauges (heal_mttr_s)
        self._events: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        # latency histograms keyed (metric, label): ("step_latency_ms", "")
        # or ("collective_latency_ms", "grad-allreduce").  All writes/reads
        # go through the single Counters lock.
        self._hists: Dict[Tuple[str, str], Histogram] = {}
        # counters kept elsewhere (on the device) and read only at a scrape
        self._sources: List[Callable[[], Dict[str, Dict[str, float]]]] = []
        # incarnation epoch: reset_for_reinit bumps it so delta-based
        # consumers (the time-series sampler) re-anchor instead of reading
        # negative rates against a dead incarnation's totals
        self._epoch = 0

    def _get(self, table: Dict[str, RateWindow], key: str) -> RateWindow:
        w = table.get(key)
        if w is None:
            w = table[key] = RateWindow(self._window_s)
        return w

    def add_egress(self, key: str, nbytes: int) -> None:
        with self._lock:
            self._get(self._egress, key).add(nbytes)

    def add_ingress(self, key: str, nbytes: int) -> None:
        with self._lock:
            self._get(self._ingress, key).add(nbytes)

    def add_wire(self, key: str, logical_bytes: int, wire_bytes: int) -> None:
        """Record one collective's byte accounting: `logical_bytes` is the
        uncompressed payload, `wire_bytes` what the chosen wire format moved
        (config.wire_bytes).  Equal for uncompressed collectives."""
        with self._lock:
            self._get(self._logical, key).add(logical_bytes)
            self._get(self._wire, key).add(wire_bytes)

    def record_quant_error(self, key: str, rel_error: float) -> None:
        """Last observed relative L2 quantization error for an op (gauge)."""
        with self._lock:
            self._quant_err[key] = float(rel_error)

    def wire_totals(self) -> Tuple[Dict[str, int], Dict[str, int]]:
        """(logical, wire) cumulative bytes per op name."""
        with self._lock:
            return (
                {k: w.total for k, w in self._logical.items()},
                {k: w.total for k, w in self._wire.items()},
            )

    def compression_ratios(self) -> Dict[str, float]:
        """logical/wire per op — 1.0 = uncompressed, ~3.9 = int8@256."""
        logical, wire = self.wire_totals()
        return {
            k: logical[k] / wire[k]
            for k in logical
            if wire.get(k, 0) > 0
        }

    def quant_errors(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._quant_err)

    def add_source(self, fn: Callable[[], Dict[str, Dict[str, float]]]) -> None:
        """Register counters this object does not hold: `fn()` is called at
        every scrape (and at both ends of a profile capture) and returns
        {family name: {label text ('' or 'k="v",...'): value}}."""
        with self._lock:
            self._sources.append(fn)

    def source_families(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            sources = list(self._sources)
        out: Dict[str, Dict[str, float]] = {}
        for fn in sources:
            out.update(fn())
        return out

    def inc_event(self, key: str, n: int = 1) -> None:
        """Count one lifecycle event (worker failure, heal, restart, ...)."""
        with self._lock:
            self._events[key] = self._events.get(key, 0) + n

    def record_collective_impl(self, impl: str) -> None:
        """Count one dispatched collective by the engine that moved its
        bytes: "xla" | "pallas" | "pallas_fused" (fallback-aware — the
        Session records what actually executed).  Exposed as
        kungfu_events_total{event="collective_impl_<impl>"} so a fleet
        scrape attributes traffic between the XLA lowerings and the
        hand-scheduled Pallas ring kernels for free; the per-bucket
        `collective_overlap` histogram (observe_hist) carries the
        bucketed gradient-sync layout next to it."""
        self.inc_event(f"collective_impl_{impl}")

    def set_gauge(self, key: str, value: float) -> None:
        """Record the last observed value of a named gauge (e.g. heal MTTR)."""
        with self._lock:
            self._gauges[key] = float(value)

    def observe_hist(self, metric: str, value: float, label: str = "") -> None:
        """One histogram observation (e.g. a step/collective latency, ms)."""
        with self._lock:
            h = self._hists.get((metric, label))
            if h is None:
                h = self._hists[(metric, label)] = Histogram()
            h.observe(value)

    def hist_percentile(self, metric: str, p: float, label: str = "") -> Optional[float]:
        with self._lock:
            h = self._hists.get((metric, label))
            return None if h is None else h.percentile(p)

    def hist_summaries(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """{metric: {label: {count, sum, p50, p99}}} snapshot."""
        with self._lock:
            out: Dict[str, Dict[str, Dict[str, float]]] = {}
            for (metric, label), h in self._hists.items():
                out.setdefault(metric, {})[label] = {
                    "count": h.count,
                    "sum": round(h.sum, 3),
                    "p50": h.percentile(0.50),
                    "p99": h.percentile(0.99),
                }
            return out

    def reset_for_reinit(self) -> None:
        """Drop per-incarnation distributions after a heal re-rendezvous:
        rate windows and latency histograms measured against the old cluster
        would pollute the new world's throughput/interference signals.
        Lifecycle event counts and gauges (heals, mttr) survive — they
        describe the job, not one incarnation."""
        with self._lock:
            for table in (self._egress, self._ingress, self._logical, self._wire):
                table.clear()
            self._hists.clear()
            self._epoch += 1

    def snapshot_json(self) -> Dict:
        """JSON-serializable snapshot of every accumulator: byte totals,
        events, gauges, and full histogram state (bucket bounds + counts +
        sum + count + max).  The planner's offline cost-model fit consumes
        this — `load_snapshot` reconstructs a Counters from it, so a dumped
        fleet scrape tunes plans on a machine that never ran the job."""
        with self._lock:
            return {
                "version": 1,
                "epoch": self._epoch,
                "window_s": self._window_s,
                "egress": {k: w.total for k, w in self._egress.items()},
                "ingress": {k: w.total for k, w in self._ingress.items()},
                "logical": {k: w.total for k, w in self._logical.items()},
                "wire": {k: w.total for k, w in self._wire.items()},
                "quant_err": dict(self._quant_err),
                "events": dict(self._events),
                "gauges": dict(self._gauges),
                "hists": [
                    {
                        "metric": metric, "label": label,
                        "bounds": list(h.bounds), "counts": list(h.counts),
                        "sum": h.sum, "count": h.count, "max": h.max,
                    }
                    for (metric, label), h in sorted(self._hists.items())
                ],
            }

    @classmethod
    def load_snapshot(cls, snap: Dict) -> "Counters":
        """Rebuild a Counters from `snapshot_json` output.

        Histograms round-trip exactly (buckets + sums + counts + max);
        byte totals are restored as one lump sample each, so cumulative
        totals are exact but windowed *rates* are meaningless on a loaded
        snapshot — the planner only reads totals and histograms."""
        c = cls(window_s=float(snap.get("window_s", 5.0)))
        now = time.monotonic()
        with c._lock:
            for field, table in (("egress", c._egress), ("ingress", c._ingress),
                                 ("logical", c._logical), ("wire", c._wire)):
                for k, total in (snap.get(field) or {}).items():
                    c._get(table, k).add(int(total), t=now)
            c._quant_err.update(snap.get("quant_err") or {})
            c._events.update(snap.get("events") or {})
            c._gauges.update(snap.get("gauges") or {})
            for h in snap.get("hists") or []:
                hist = Histogram(bounds=tuple(h["bounds"]))
                counts = [int(x) for x in h["counts"]]
                if len(counts) != len(hist.counts):
                    raise ValueError(
                        f"histogram {h.get('metric')}/{h.get('label')}: "
                        f"{len(counts)} bucket counts for "
                        f"{len(hist.counts)} buckets"
                    )
                hist.counts = counts
                hist.sum = float(h["sum"])
                hist.count = int(h["count"])
                hist.max = float(h.get("max", 0.0))
                c._hists[(h["metric"], h.get("label", ""))] = hist
        return c

    def events(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._events)

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def egress_rates(self) -> Dict[str, float]:
        with self._lock:
            return {k: w.rate() for k, w in self._egress.items()}

    def ingress_rates(self) -> Dict[str, float]:
        with self._lock:
            return {k: w.rate() for k, w in self._ingress.items()}

    def totals(self) -> Tuple[Dict[str, int], Dict[str, int]]:
        with self._lock:
            return (
                {k: w.total for k, w in self._egress.items()},
                {k: w.total for k, w in self._ingress.items()},
            )

    def prometheus_text(self) -> str:
        """Exposition format matching the reference's metric names
        (counters.go:57-60,100-147: egress_total_bytes{peer=...} etc.)."""
        lines: List[str] = []
        etot, itot = self.totals()
        erate, irate = self.egress_rates(), self.ingress_rates()
        for metric, table in (
            ("egress_total_bytes", etot),
            ("ingress_total_bytes", itot),
            ("egress_rate_bytes_per_sec", erate),
            ("ingress_rate_bytes_per_sec", irate),
        ):
            lines.extend(help_and_type(
                metric, "counter" if "total" in metric else "gauge"))
            for key in sorted(table):
                lines.append(f'{metric}{{peer="{key}"}} {table[key]}')
        ltot, wtot = self.wire_totals()
        for metric, table, kind in (
            ("collective_logical_total_bytes", ltot, "counter"),
            ("collective_wire_total_bytes", wtot, "counter"),
            ("collective_compression_ratio", self.compression_ratios(), "gauge"),
            ("collective_quantization_error", self.quant_errors(), "gauge"),
        ):
            if not table:
                continue
            lines.extend(help_and_type(metric, kind))
            for key in sorted(table):
                lines.append(f'{metric}{{op="{key}"}} {table[key]}')
        ev, ga = self.events(), self.gauges()
        if ev:
            lines.extend(help_and_type("kungfu_events_total", "counter"))
            for key in sorted(ev):
                lines.append(f'kungfu_events_total{{event="{key}"}} {ev[key]}')
        if ga:
            lines.extend(help_and_type("kungfu_gauge", "gauge"))
            for key in sorted(ga):
                lines.append(f'kungfu_gauge{{name="{key}"}} {ga[key]}')
        for family, table in sorted(self.source_families().items()):
            # a source's family is a counter when it is named like one
            lines.extend(help_and_type(
                family, "counter" if family.endswith("_total") else "gauge"))
            for labels, value in table.items():
                lab = f"{{{labels}}}" if labels else ""
                lines.append(f"{family}{lab} {value}")
        with self._lock:
            # snapshot under the lock, render outside it
            hists = [
                (metric, label, h.cumulative(), h.sum, h.count)
                for (metric, label), h in sorted(self._hists.items())
            ]
        seen_types = set()
        for metric, label, cum, hsum, hcount in hists:
            if metric not in seen_types:
                seen_types.add(metric)
                lines.extend(help_and_type(metric, "histogram"))
            lab = f'op="{label}",' if label else ""
            for le, c in cum:
                lines.append(f'{metric}_bucket{{{lab}le="{le}"}} {c}')
            sl = f'{{op="{label}"}}' if label else ""
            lines.append(f"{metric}_sum{sl} {round(hsum, 3)}")
            lines.append(f"{metric}_count{sl} {hcount}")
        return "\n".join(lines) + "\n"


_global = Counters()


def global_counters() -> Counters:
    return _global


def counters_if_enabled() -> Optional[Counters]:
    """Global byte counters, or None when monitoring is off — hot paths must
    not pay lock+deque overhead nobody reads (gate mirrors the reference's
    KUNGFU_CONFIG_ENABLE_MONITORING, peer.go:92-99).  Callers evaluate this
    once per object: the env gate cannot meaningfully change mid-process."""
    from .server import enabled

    return _global if enabled() else None
