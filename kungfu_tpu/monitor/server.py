"""Prometheus-text HTTP endpoint (reference peer.go:92-99 + counters.go).

The reference serves /metrics on self.Port+10000 when
KUNGFU_CONFIG_ENABLE_MONITORING=true.  Same contract here with KFT_* names;
the port offset differs (16000) to stay clear of the store (+15000) and the
jax.distributed coordinator (+20000) while remaining below the Linux
ephemeral range.

Besides /metrics the endpoint serves /trace — this worker's span ring
buffer (utils.trace) as Chrome-trace JSON, the per-rank feed the
launcher-side fleet aggregator (monitor.fleet) merges into one timeline —
and /history: this worker's self-sampled time-series store
(monitor.timeseries; `?series=<prefix>` filters by name prefix).

The program observatory (monitor.programs) adds /programs — the compiled-
program registry report (signatures, budgets, storms) — and
/profile?secs=N: an on-demand jax.profiler capture dumped atomically to
KFT_TRACE_DUMP_DIR (no-op JSON when the profiler can't run; `&python=1`
adds the profiler's Python tracer, off by default).
"""
from __future__ import annotations

import json
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..utils import get_logger
from ..utils.envflag import env_flag
from .counters import Counters, global_counters

log = get_logger("kungfu.monitor")

ENABLE_ENV = "KFT_CONFIG_ENABLE_MONITORING"
MONITOR_PORT_OFFSET = 16000


def monitor_port(worker_port: int) -> int:
    p = worker_port + MONITOR_PORT_OFFSET
    if not (0 < p <= 65535):
        raise ValueError(f"worker port {worker_port} leaves no room for monitor port")
    return p


def enabled() -> bool:
    return env_flag(ENABLE_ENV)


class MonitorServer:
    """Serves GET /metrics (Prometheus text) and GET /trace (Chrome-trace
    JSON of this worker's span buffer)."""

    def __init__(self, counters: Optional[Counters] = None,
                 host: str = "0.0.0.0", port: int = 0, trace_buffer=None,
                 ts_store=None):
        self.counters = counters if counters is not None else global_counters()
        self.trace_buffer = trace_buffer  # None = the global span buffer
        self.ts_store = ts_store  # None = the global worker store
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                split = urllib.parse.urlsplit(self.path)
                path = split.path.rstrip("/")
                query = urllib.parse.parse_qs(split.query)
                if path in ("", "/metrics"):
                    body = outer.counters.prometheus_text().encode()
                    ctype = "text/plain; version=0.0.4"
                elif path == "/trace":
                    from ..utils import trace as T

                    buf = outer.trace_buffer
                    if buf is None:
                        buf = T.global_trace_buffer()
                    body = json.dumps(T.export_chrome_trace(buf)).encode()
                    ctype = "application/json"
                elif path == "/history":
                    from . import timeseries as TS

                    store = outer.ts_store
                    if store is None:
                        store = TS.worker_store()
                    prefix = (query.get("series") or [""])[0]
                    snap = store.snapshot(prefix=prefix)
                    snap["interval_s"] = TS.sample_interval_s()
                    body = json.dumps(snap).encode()
                    ctype = "application/json"
                elif path == "/programs":
                    from . import programs as P

                    body = json.dumps(P.global_registry().report()).encode()
                    ctype = "application/json"
                elif path == "/profile":
                    # blocks this handler thread for `secs` — fine under
                    # ThreadingHTTPServer, the other endpoints keep serving
                    from . import programs as P

                    try:
                        secs = float((query.get("secs") or ["2"])[0])
                    except ValueError:
                        secs = 2.0
                    python = (query.get("python") or ["0"])[0] == "1"
                    body = json.dumps(
                        P.capture_profile(secs, python=python)).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # silence default stderr spam
                pass

        self._srv = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._srv.server_address[:2]
        self._thread = threading.Thread(target=self._srv.serve_forever, daemon=True)
        self._closed = False

    def start(self) -> "MonitorServer":
        self._thread.start()
        log.info("monitoring on http://%s:%d/metrics", self.host, self.port)
        return self

    def close(self) -> None:
        """Idempotent full shutdown: stop serving, release the socket, JOIN
        the server thread.  The join matters on heal paths — a healed worker
        re-binds the same monitor port, and a still-draining thread holding
        the old socket makes the re-bind a coin flip."""
        if self._closed:
            return
        self._closed = True
        if self._thread.is_alive():
            self._srv.shutdown()  # only safe once serve_forever is running
        self._srv.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5)


def maybe_start_monitor(worker_port: int, host: str = "0.0.0.0") -> Optional[MonitorServer]:
    """Start the endpoint iff KFT_CONFIG_ENABLE_MONITORING is set
    (the reference's gate, peer.go:92-99).  Also arms the process-global
    time-series self-sampler (monitor.timeseries) behind the same gate, so
    every monitored worker serves `/history` — the sampler daemon is
    process-global and survives the heal/resize teardown that closes and
    re-binds this endpoint."""
    if not enabled():
        return None
    from .programs import maybe_install
    from .timeseries import maybe_start_worker_sampler

    maybe_install()  # compile listener + memory census (KFT_PROGRAMS gate)
    maybe_start_worker_sampler()
    return MonitorServer(host=host, port=monitor_port(worker_port)).start()
