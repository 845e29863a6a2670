"""Tracing/profiling — span recorder + jax.profiler integration.

Reference: include/kungfu/utils/trace.hpp (TRACE_SCOPE macros compiled in
behind KUNGFU_ENABLE_TRACE) and the Python event logger stamping times since
proc/job start (srcs/python/kungfu/_utils.py:33-50).

The reference's TRACE_SCOPE only logs; here every scope lands instead
in a per-process ring buffer of `Span`s with *job-relative monotonic*
timestamps, exportable as Chrome-trace/Perfetto JSON (`export_chrome_trace`)
— so pod-scale debugging gets the merged cross-host timeline the MLPerf
TPU-pod work calls essential.  The monitor endpoint serves the buffer at
`/trace`, the launcher-side fleet aggregator merges every rank's buffer
into one timeline with per-rank lanes (kungfu_tpu.monitor.fleet), and
`KFT_TRACE_DUMP_DIR` makes each worker dump its buffer at exit so dead
jobs can be merged offline (`python -m kungfu_tpu.monitor --merge`).

Clock discipline: durations and timeline positions derive from
`time.monotonic()` only — an NTP step mid-job must never corrupt a span.
Wall-clock is stamped exactly once per process as *anchor metadata* (the
proc-start wall/mono pair below) so offline tooling can align timelines
from hosts whose monotonic clocks are unrelated.

`trace_scope(name)` always opens a `jax.profiler.TraceAnnotation` (in a
process that has imported jax), so every scope lands on the profiler's own
timeline beside the device's operations whenever a capture is running, and
costs a few microseconds when none is.  With KFT_CONFIG_ENABLE_TRACE set it
also records a `Span` in the ring buffer.  `record_span` / `child_span` are
timed by hand, after the fact, so they reach the ring only.

Boot phases (docs/observability.md "Boot"): a span of category `boot` is
kept whether or not KFT_CONFIG_ENABLE_TRACE is set, in a short bounded list
of its own (`boot_spans()`, at most BOOT_CAPACITY a process), so every
start can say where its seconds went; the ring and its gate are untouched.
The job clock a boot is read on is anchored on the launcher's
`KFT_JOB_START`, which both launchers stamp always (`stamp_job_start`: the
launcher's real start, the kernel's), and each spawn stamps
`KFT_PROC_START`.  monitor/boot.py assembles the start record from them.
`profile_to(dir)` wraps a block in a full `jax.profiler.trace` capture.

Distributed trace context (docs/observability.md "Request tracing"): a
`TraceContext` is a (trace_id, span_id) pair in the W3C traceparent shape
(`00-<32 hex>-<16 hex>-01`, `format_traceparent`/`parse_traceparent`) that
rides every serving HTTP hop as a `traceparent` header.  A thread pushes a
context with `trace_context(ctx)`; every `trace_scope` under it allocates a
child span id and re-parents nested scopes, so one request's spans — across
the router, a prefill rank and a decode rank — stitch into a single tree by
(trace_id, span_id, parent_id).  `child_span` records a span under an
explicit (possibly remote) parent for phases timed by hand.  The fleet-side
assembler (monitor.requests) consumes each rank's /trace and stitches the
trees into per-request timelines.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple, Union

from .log import get_logger

log = get_logger("kungfu.trace")

ENABLE_ENV = "KFT_CONFIG_ENABLE_TRACE"
BUFFER_CAPACITY_ENV = "KFT_TRACE_BUFFER"  # ring capacity, spans
DUMP_DIR_ENV = "KFT_TRACE_DUMP_DIR"  # dump the buffer here at process exit
FLUSH_EVERY_ENV = "KFT_TRACE_FLUSH_S"  # incremental flush period (0 = off)
DEFAULT_CAPACITY = 8192
DEFAULT_FLUSH_S = 10.0

# wall/monotonic anchor pair, stamped once at import (reference
# _utils.py:33-50: the launcher stamps KFT_JOB_START; each worker stamps its
# own proc start).  Durations use the monotonic clock ONLY; the wall stamp
# is anchor metadata for cross-host alignment.  Despite the name this is the
# time THIS MODULE was imported, which is when the package's imports reach
# it, not the start of the process: `process_start_mono()` is that.
_PROC_START_MONO = time.monotonic()
_PROC_START_WALL = time.time()


def _job_start_wall() -> float:
    v = os.environ.get("KFT_JOB_START")
    try:
        return float(v) if v else _PROC_START_WALL
    except ValueError:
        return _PROC_START_WALL


# job start projected onto this process's monotonic clock: the one place the
# wall clock is consulted; every later stamp is pure monotonic arithmetic,
# so an NTP step mid-job shifts nothing
_JOB_START_MONO = _PROC_START_MONO - (_PROC_START_WALL - _job_start_wall())


def job_now(mono: Optional[float] = None) -> float:
    """Seconds since job start, on the monotonic clock."""
    return (time.monotonic() if mono is None else mono) - _JOB_START_MONO


def wall_to_mono(wall: float) -> float:
    """A wall-clock stamp another process took (`KFT_PROC_START`) on this
    process's monotonic clock, through the import-time anchor pair."""
    return _PROC_START_MONO - (_PROC_START_WALL - wall)


_process_start_mono: Optional[float] = None


def process_start_mono() -> float:
    """This process's real start (the kernel's: `/proc/self/stat`) on its
    monotonic clock, read once; where the kernel does not say, the import
    of this module, which is the earliest stamp the process itself holds."""
    global _process_start_mono
    if _process_start_mono is None:
        try:
            with open("/proc/self/stat") as f:
                after_comm = f.read().rsplit(")", 1)[1].split()
            started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")  # field 22
            age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
            _process_start_mono = min(time.monotonic() - age, _PROC_START_MONO)
        except (OSError, ValueError, IndexError, AttributeError):
            _process_start_mono = _PROC_START_MONO
    return _process_start_mono


def stamp_job_start() -> None:
    """A launcher's first act: `KFT_JOB_START` is this process's real start
    unless an outer launcher stamped it already (every child inherits it),
    and this process's own job clock is re-anchored on it, so the
    launcher's seconds before its first worker are on the job clock too."""
    global _JOB_START_MONO
    mono = process_start_mono()
    os.environ.setdefault(
        "KFT_JOB_START", repr(_PROC_START_WALL - (_PROC_START_MONO - mono)))
    _JOB_START_MONO = wall_to_mono(_job_start_wall())


def enabled() -> bool:
    from .envflag import env_flag

    return env_flag(ENABLE_ENV)


# -- distributed trace context ---------------------------------------------------------

#: the header carrying the context across serving HTTP hops (W3C name)
TRACEPARENT_HEADER = "traceparent"
_HEX = frozenset("0123456789abcdef")


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """One hop's position in a distributed trace: the trace and the span
    that any child spans recorded under this context parent to."""

    trace_id: str  # 32 lowercase hex chars
    span_id: str   # 16 lowercase hex chars ("" = trace-only context)


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def format_traceparent(ctx: TraceContext) -> str:
    """W3C-traceparent-style wire form: `00-<trace_id>-<span_id>-01`."""
    return f"00-{ctx.trace_id}-{ctx.span_id}-01"


def parse_traceparent(header: Optional[str]) -> Optional[TraceContext]:
    """TraceContext from a traceparent header, or None on any malformation
    (a bad header degrades to an untraced request, never an error)."""
    parts = (header or "").strip().lower().split("-")
    if len(parts) != 4:
        return None
    ver, trace_id, span_id, flags = parts
    if len(ver) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    if not (set(ver) <= _HEX and set(trace_id) <= _HEX
            and set(span_id) <= _HEX and set(flags) <= _HEX):
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id=trace_id, span_id=span_id)


_ctx_tls = threading.local()


def current_context() -> Optional[TraceContext]:
    """The thread's innermost active TraceContext, or None."""
    stack = getattr(_ctx_tls, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def trace_context(ctx: Optional[TraceContext]) -> Iterator[Optional[TraceContext]]:
    """Make `ctx` the thread's current context for the block (None = no-op,
    so callers can pass through an unparsed/absent header unconditionally)."""
    if ctx is None:
        yield None
        return
    stack = getattr(_ctx_tls, "stack", None)
    if stack is None:
        stack = _ctx_tls.stack = []
    stack.append(ctx)
    try:
        yield ctx
    finally:
        stack.pop()


@dataclasses.dataclass
class Span:
    """One recorded scope: job-relative start + duration, both monotonic."""

    name: str
    t_start: float  # seconds since job start
    dur: float  # seconds; 0.0 for instant events
    cat: str = ""
    tid: int = 0
    phase: str = "X"  # Chrome trace phase: "X" complete, "i" instant
    args: Optional[Dict[str, Any]] = None
    # distributed trace identity; empty on purely-local spans
    trace_id: str = ""
    span_id: str = ""
    parent_id: str = ""

    def to_chrome(self, pid: Union[int, str]) -> Dict[str, Any]:
        ev: Dict[str, Any] = {
            "name": self.name,
            "cat": self.cat or "kungfu",
            "ph": self.phase,
            "ts": round(self.t_start * 1e6, 1),  # Chrome trace wants us
            "pid": pid,
            "tid": self.tid,
        }
        if self.phase == "X":
            ev["dur"] = round(self.dur * 1e6, 1)
        else:
            ev["s"] = "t"  # thread-scoped instant
        args = dict(self.args) if self.args else {}
        if self.span_id:
            # trace identity rides in args so the Chrome export round-trips
            # through /trace scrapes and offline dumps unchanged
            args["span_id"] = self.span_id
            if self.trace_id:
                args["trace_id"] = self.trace_id
            if self.parent_id:
                args["parent_id"] = self.parent_id
        if args:
            ev["args"] = args
        return ev


class TraceBuffer:
    """Bounded thread-safe ring of Spans (oldest dropped first)."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get(BUFFER_CAPACITY_ENV, "") or DEFAULT_CAPACITY)
            except ValueError:
                capacity = DEFAULT_CAPACITY
        self.capacity = max(1, capacity)
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=self.capacity)
        self._dropped = 0

    def add(self, span: Span) -> None:
        with self._lock:
            dropped = len(self._spans) == self.capacity
            if dropped:
                self._dropped += 1
                n = self._dropped
            self._spans.append(span)
        if dropped:
            # a truncated trace must be tellable from a short one: the
            # counter/gauge pair lets assemblers (and operators) see that
            # spans fell off the ring before they were scraped
            _count_dropped(n)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped


def _count_dropped(total: int) -> None:
    """Bump the `trace_spans_dropped` counter + gauge (best-effort: span
    recording must never fail because monitoring is mid-teardown)."""
    try:
        from ..monitor.counters import global_counters

        c = global_counters()
        c.inc_event("trace_spans_dropped")
        c.set_gauge("trace_spans_dropped", float(total))
    except Exception:  # noqa: BLE001 - pure telemetry
        pass


def export_chrome_trace(
    spans: Union[TraceBuffer, Sequence[Span]],
    pid: Optional[Union[int, str]] = None,
    process_name: str = "",
) -> Dict[str, Any]:
    """Chrome-trace/Perfetto JSON object for one process's spans.

    Open the written file in https://ui.perfetto.dev or chrome://tracing.
    The wall/monotonic anchor pair rides along under "otherData" so offline
    merges can align timelines across hosts.
    """
    dropped = None
    if isinstance(spans, TraceBuffer):
        dropped = spans.dropped
        spans = spans.spans()
    if pid is None:
        pid = os.getpid()
    events: List[Dict[str, Any]] = []
    if process_name:
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": process_name},
        })
    events.extend(s.to_chrome(pid) for s in spans)
    other: Dict[str, Any] = {
        "proc_start_wall": _PROC_START_WALL,
        "job_start_wall": _job_start_wall(),
    }
    if dropped is not None:
        # assemblers use this to mark timelines whose spans fell off the
        # ring as truncated rather than presenting a misleading tree
        other["spans_dropped"] = dropped
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": other,
    }


# -- boot phases -----------------------------------------------------------------------

#: the category whose spans are kept with tracing off (module docstring)
BOOT_CAT = "boot"
#: a start has a dozen phases and one first call for each tracked program;
#: what a long-lived process adds later (respawns, new signatures) stops here
BOOT_CAPACITY = 64

_boot_lock = threading.Lock()
_boot_spans: List[Span] = []
_start_record_dir = ""


def _keep(span: Span, ring: bool) -> None:
    """A finished span to where it is kept: the boot list for a boot phase
    (tracing on or off), the ring when tracing is on."""
    if span.cat == BOOT_CAT:
        with _boot_lock:
            if len(_boot_spans) < BOOT_CAPACITY:
                _boot_spans.append(span)
    if ring:
        global_trace_buffer().add(span)


def boot_spans() -> List[Span]:
    """The process's boot phases so far, in the order they closed."""
    with _boot_lock:
        return list(_boot_spans)


def package_import_mono() -> Tuple[float, float]:
    """(start, end) of `import kungfu_tpu` on the monotonic clock, as the
    package's `__init__` stamped them: the first and last statement of the
    program a process runs before its entry point's own."""
    pkg = sys.modules.get(__name__.split(".")[0])
    t0 = getattr(pkg, "_IMPORT_T0", _PROC_START_MONO)
    return t0, getattr(pkg, "_IMPORT_T1", None) or max(t0, _PROC_START_MONO)


_backend_seen = False


def backend_phase(t0_mono: float, t1_mono: float, **args: Any) -> None:
    """Keep `boot:backend`, the TPU runtime coming up at the process's first
    question about devices, once: whoever asked later found them there."""
    global _backend_seen
    if not _backend_seen:
        _backend_seen = True
        record_span("boot:backend", t0_mono, t1_mono, cat=BOOT_CAT, args=args)


def backend_devices():
    """`jax.devices()`, as the `boot:backend` phase where this is the first
    time the process asks (`plan.make_mesh`, below this module, stamps its
    own question for monitor/programs.py `boot_phases` to hand over)."""
    import jax

    if _backend_seen:
        return jax.devices()
    t0 = time.monotonic()
    with _annotation("boot:backend", None):
        devs = jax.devices()
    backend_phase(t0, time.monotonic(), platform=devs[0].platform,
                  devices=len(devs))
    return devs


def _reset_boot_for_tests() -> None:
    """A process with no boot so far: no phase kept, no backend phase
    taken, no record armed."""
    global _backend_seen, _start_record_dir
    with _boot_lock:
        _boot_spans.clear()
    _backend_seen, _start_record_dir = False, ""


def arm_start_record(directory: str) -> None:
    """Let this process write its start record (monitor/boot.py) under
    `directory`: called by the program's entry points and by
    `env.enable_compile_cache()`, never at import, so importing the
    package or running a unit test writes nothing."""
    global _start_record_dir
    _start_record_dir = directory


def start_record_dir() -> str:
    """Where this process's start record goes ("" = it writes none):
    KFT_TRACE_DUMP_DIR when set, else what `arm_start_record` was given."""
    if not _start_record_dir:
        return ""
    return os.environ.get(DUMP_DIR_ENV) or _start_record_dir


# -- global per-process buffer ---------------------------------------------------------

_global_buffer: Optional[TraceBuffer] = None
_global_lock = threading.Lock()


def _dump_identity() -> str:
    spec = os.environ.get("KFT_SELF_SPEC", "")
    if spec:
        return spec.replace(":", "-").replace("/", "-")
    return f"pid{os.getpid()}"


def flush_dump(reason: str = "manual") -> Optional[str]:
    """Write the span ring to KFT_TRACE_DUMP_DIR *now*, atomically.

    Crash durability: the exit-time dump never runs for a rank that dies by
    SIGKILL or `os._exit` (stall kill, chaos crash, OOM), so its lane used
    to vanish from post-mortem timelines.  The periodic flush thread (and
    the SIGTERM/preemption path) call this instead — tmp-file + rename, so
    a kill mid-write leaves the previous complete dump, never a torn one.
    Returns the written path, or None (not configured / empty / IO error —
    a flush must never take the process down)."""
    d = os.environ.get(DUMP_DIR_ENV)
    buf = _global_buffer
    if not d or buf is None or len(buf) == 0:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"trace-{_dump_identity()}.json")
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(export_chrome_trace(buf, process_name=_dump_identity()), f)
        os.replace(tmp, path)
        log.info("trace buffer flushed to %s (%d spans, %s)",
                 path, len(buf), reason)
        return path
    except OSError as e:
        log.warning("trace flush (%s) failed: %s", reason, e)
        return None


def _dump_at_exit() -> None:  # pragma: no cover - exercised in subprocess drills
    flush_dump("exit")


def _flush_interval_s() -> float:
    try:
        v = os.environ.get(FLUSH_EVERY_ENV, "")
        return max(0.0, float(v)) if v else DEFAULT_FLUSH_S
    except ValueError:
        return DEFAULT_FLUSH_S


_flush_thread: Optional[threading.Thread] = None


def _start_flush_thread() -> None:
    """Daemon flusher so a crashed rank's lane is at most one interval
    stale in the dump dir.  Started once, only when a dump dir is set."""
    global _flush_thread
    interval = _flush_interval_s()
    if interval <= 0 or _flush_thread is not None:
        return

    def loop() -> None:  # pragma: no cover - timing loop; flush_dump is tested
        while True:
            time.sleep(interval)
            flush_dump("periodic")

    _flush_thread = threading.Thread(target=loop, daemon=True,
                                     name="kft-trace-flush")
    _flush_thread.start()


def global_trace_buffer() -> TraceBuffer:
    """The process-wide span ring (what /trace serves and trace_scope fills)."""
    global _global_buffer
    if _global_buffer is None:
        with _global_lock:
            if _global_buffer is None:
                _global_buffer = TraceBuffer()
                if os.environ.get(DUMP_DIR_ENV):
                    import atexit

                    atexit.register(_dump_at_exit)
                    _start_flush_thread()
    return _global_buffer


def record_span(name: str, t0_mono: float, t1_mono: Optional[float] = None,
                cat: str = "", args: Optional[Dict[str, Any]] = None) -> None:
    """Record a span from explicit monotonic stamps (for phases timed by
    hand, e.g. the heal decomposition).  No-op when tracing is off, unless
    the span is a boot phase (`cat="boot"`: kept in the boot list always).
    Under an active TraceContext the span joins that trace as a child."""
    on = enabled()
    if not on and cat != BOOT_CAT:
        return
    t1 = time.monotonic() if t1_mono is None else t1_mono
    ctx = current_context()
    _keep(Span(
        name=name, t_start=job_now(t0_mono), dur=max(0.0, t1 - t0_mono),
        cat=cat, tid=threading.get_ident() & 0x7FFFFFFF, args=args,
        trace_id=ctx.trace_id if ctx else "",
        span_id=new_span_id() if ctx else "",
        parent_id=ctx.span_id if ctx else "",
    ), on)


def child_span(name: str, t0_mono: float, t1_mono: Optional[float] = None,
               *, trace_id: str, parent_id: str = "", span_id: str = "",
               cat: str = "", args: Optional[Dict[str, Any]] = None) -> str:
    """Record one span under an explicit (possibly remote) parent — the
    cross-process hop primitive: the parent span id arrived over the wire
    (traceparent header / request body), not from this thread's context.
    Returns the recorded span's id ("" when tracing is off or no trace_id),
    so callers can hand it to the NEXT hop as its parent."""
    if not enabled() or not trace_id:
        return ""
    sid = span_id or new_span_id()
    t1 = time.monotonic() if t1_mono is None else t1_mono
    global_trace_buffer().add(Span(
        name=name, t_start=job_now(t0_mono), dur=max(0.0, t1 - t0_mono),
        cat=cat, tid=threading.get_ident() & 0x7FFFFFFF, args=args,
        trace_id=trace_id, span_id=sid, parent_id=parent_id,
    ))
    return sid


def log_event(name: str, **args: Any) -> None:
    """One-line event + an instant span in the buffer (t on the monotonic
    job clock; wall time appears only in the export's anchor metadata).
    Under an active TraceContext the instant joins that trace."""
    if not enabled():
        return
    t = job_now()
    log.info("[event] %s +%.3fs job +%.3fs proc", name, t,
             time.monotonic() - _PROC_START_MONO)
    ctx = current_context()
    global_trace_buffer().add(Span(
        name=name, t_start=t, dur=0.0, cat="event", phase="i",
        tid=threading.get_ident() & 0x7FFFFFFF, args=args or None,
        trace_id=ctx.trace_id if ctx else "",
        span_id=new_span_id() if ctx else "",
        parent_id=ctx.span_id if ctx else "",
    ))


def _annotation(name: str, args: Optional[Dict[str, Any]]):
    """The scope as the profiler sees it: a `TraceAnnotation` carrying the
    scalar `args` as stats.  A process that has not imported jax itself
    (launcher and router parents) never imports it through here: it gets a
    null context."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    stats = {k: v for k, v in args.items()
             if isinstance(v, (bool, int, float, str))} if args else {}
    return profiler.TraceAnnotation(name, **stats)


@contextlib.contextmanager
def trace_scope(name: str, cat: str = "",
                args: Optional[Dict[str, Any]] = None,
                track: bool = False) -> Iterator[None]:
    """Scoped span: always an annotation on the profiler's timeline (seen by
    whatever capture is running, the device's operations beside it), and
    with KFT_CONFIG_ENABLE_TRACE also a `Span` in the ring buffer.  Nesting
    is free — Chrome trace viewers nest "X" events by ts/dur containment
    per thread.

    Under an active TraceContext the scope allocates a child span id and
    becomes the current context for its body, so nested scopes chain into
    the distributed span tree.  `track=True` allocates a span id even with
    no context — for batch-level spans (one decode step serving many
    requests) that need a stable dedup identity without belonging to a
    single trace.  `args` is held by reference and serialized at scrape
    time, so a scope body may fill in outcome fields (e.g. per-round
    acceptance) before it closes; the annotation takes its scalars as they
    are when the scope opens.  A boot phase (`cat="boot"`) is also kept in
    the boot list, tracing on or off."""
    with _annotation(name, args):
        on = enabled()
        if not on and cat != BOOT_CAT:
            yield
            return
        parent = current_context()
        sid = new_span_id() if (parent is not None or track) else ""
        child = TraceContext(parent.trace_id, sid) if parent is not None else None
        t0 = time.monotonic()
        try:
            with trace_context(child):
                yield
        finally:
            t1 = time.monotonic()
            _keep(Span(
                name=name, t_start=job_now(t0), dur=t1 - t0, cat=cat,
                tid=threading.get_ident() & 0x7FFFFFFF, args=args,
                trace_id=parent.trace_id if parent else "",
                span_id=sid,
                parent_id=parent.span_id if parent else "",
            ), on)


@contextlib.contextmanager
def profile_to(logdir: str) -> Iterator[None]:
    """Full profiler capture of the block into `logdir` (Perfetto-viewable)."""
    import jax.profiler

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profile written to %s", logdir)
