"""Program observatory — the compiler/memory plane under the wall-clock plane.

The fleet watches time exhaustively (spans, series, SLOs, request traces)
but was blind to what XLA does underneath: recompile storms surfaced only
as mysterious latency (PR 14 found one by accident), the serving engine
*promises* "one compiled decode signature" with nothing enforcing it, and
the tuner's HBM footprint model was never checked against what the device
actually allocates.  This module is that plane:

  CompileWatch      `jax.monitoring` listeners on JAX's own compile events.
                    The backend-compile duration feeds `compiles_total` and
                    the `compile_ms` histogram; in JAX 0.9.0 that event
                    wraps `compile_or_get_cached`, so it fires on a
                    persistent-cache HIT too (after `cache_hits` and
                    `cache_retrieval_time_sec`): `compiles` counts hits,
                    `compile_ms` on a warm start is reading and
                    deserialising executables, and `cache_misses` is the
                    count of real compilations.  The ledger beside them
                    (`compile_ledger`) keeps, for each program under JAX's
                    `fun_name`, its trace / lower / load / compile seconds
                    from the trace and lowering time spans and the two
                    cache events, and totals in which a nested trace counts
                    once.  The ledger listens from `listen()` on (an entry
                    point's first act), the three older counters from
                    `maybe_install()`, where they always started.
                    Where jax.monitoring is absent the `track()`
                    wrapper falls back to wall-clocking the first call per
                    signature — the tracing-callback path.
  ProgramRegistry   per-process registry of tracked programs: fn name ->
                    {shape/dtype digest -> compile ms, call count}.  Every
                    NEW digest journals `program_compiled`; a sustained
                    burst of new digests for the SAME program journals
                    `recompile_storm` and feeds the shipped SLO rule
                    (monitor.slo: `rate:recompile_storm` must stay 0).
  signature budgets `track(..., budget=n)` / `declare_budget` assert the
                    promised signature count at runtime (KFT_SIG_BUDGET
                    overrides, "name=n,name2=m").  Overruns journal
                    `sig_budget_exceeded` and count — they never raise:
                    observability must not take the job down.
  memory census     a timeseries tick callback sampling `jax.live_arrays()`
                    and per-device `memory_stats()` into the `live_arrays`
                    / `live_array_bytes` / `hbm_bytes_in_use` gauges, plus
                    `journal_footprint` comparing a tuner/footprint.py
                    prediction against the measured census (`hbm_footprint`
                    with rel_err — the cost model's honesty loop).
  capture_profile   on-demand `jax.profiler` capture behind the worker
                    `/profile?secs=N` endpoint (monitor.server; fleet
                    fan-out in monitor.fleet; `&python=1` adds the
                    profiler's Python tracer): atomic dump next to the
                    trace dumps, the capture window recorded as a
                    `profile:capture` span so it lands in /timeline, and
                    an interpreter-safe no-op fallback (the JSON says
                    noop=true instead of 500ing).

Gating: KFT_PROGRAMS=0 disables everything — `track()` returns the fn
unchanged (no wrapper, no digest work), `maybe_install` is a no-op, the
census never registers.  Enabled (the default), the per-call cost is one
pytree flatten and a dictionary lookup on the leaves' (shape, dtype)
objects — the digest string is computed once for each new signature — and
shows in a profile as the `programs:digest` span; counters are only
touched when monitoring is on (counters_if_enabled).
"""
from __future__ import annotations

import hashlib
import json
import os
import socket
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..utils import get_logger
from ..plan import mesh as plan_mesh
from ..utils.trace import (
    BOOT_CAT,
    Span,
    backend_phase,
    boot_spans,
    job_now,
    trace_scope,
)
from .journal import journal_event

log = get_logger("kungfu.programs")

PROGRAMS_ENV = "KFT_PROGRAMS"            # "0" disables the whole observatory
SIG_BUDGET_ENV = "KFT_SIG_BUDGET"        # "name=n,name2=m" budget overrides
STORM_WINDOW_ENV = "KFT_PROGRAMS_STORM_WINDOW_S"
STORM_MIN_ENV = "KFT_PROGRAMS_STORM_MIN"

DEFAULT_STORM_WINDOW_S = 30.0
#: new digests of ONE program within the window that count as a storm.
#: 4 distinct signatures in 30 s is already pathological for any hot fn —
#: steady state is 0 new digests per window.
DEFAULT_STORM_MIN = 4

#: the jax-internal duration event around `compiler.compile_or_get_cached`
#: (jax/_src/interpreters/pxla.py, dispatch.BACKEND_COMPILE_EVENT): one for
#: every program jit builds, compiled or served by the persistent cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: recorded when the persistent compilation cache serves an executable; the
#: backend-compile event of the same program still follows (it wraps the
#: lookup), so `compiles` counts this program too and its `compile_ms` is
#: the retrieval, not a compilation
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: seconds `_cache_read` took on that hit: file read, decompression and
#: deserialisation onto the device; fired inside the backend-compile event
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: Python tracing of a jitted function to a jaxpr (`fun_name`: the bare
#: function name) and the jaxpr's lowering to an MLIR module (`fun_name`:
#: `jit(name)`, what the device trace's `XLA Modules` line carries).  Both
#: also arrive as time spans; a jit called while another is traced is
#: traced inside its caller's span
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

#: rows `compile_ledger` reports by name (the rest fold into `other`), and
#: names it keeps at all (a process has as many as its code has jits)
LEDGER_ROWS = 32
LEDGER_NAMES_MAX = 512
#: intervals a thread's union may hold while a caller's trace is open
UNION_INTERVALS_MAX = 1 << 17


def programs_enabled() -> bool:
    """The observatory gate: on unless KFT_PROGRAMS=0."""
    return os.environ.get(PROGRAMS_ENV, "1") != "0"


def _env_float(name: str, default: float) -> float:
    try:
        v = os.environ.get(name, "")
        return float(v) if v else default
    except ValueError:
        return default


def _env_budgets() -> Dict[str, int]:
    """Parse KFT_SIG_BUDGET ("serve.decode=1,train_step=2"); malformed
    entries are skipped, not fatal — a typo must not change behaviour."""
    out: Dict[str, int] = {}
    for part in os.environ.get(SIG_BUDGET_ENV, "").split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, v = part.partition("=")
        try:
            out[name.strip()] = int(v)
        except ValueError:
            continue
    return out


def _counters():
    from .counters import counters_if_enabled

    return counters_if_enabled()


# -- CompileWatch: the process-global compile listener ---------------------------------

_watch_lock = threading.Lock()
_watch: Dict[str, Any] = {
    "installed": False,   # maybe_install ran (idempotence latch)
    "active": False,      # the listener is live and the three below count
    "listening": None,    # listen() ran: the ledger takes events in
    "compile_ms": 0.0,    # cumulative backend-compile ms this process
    "compiles": 0,
    "cache_hits": 0,      # executables served by the persistent cache
    "cache_misses": 0,    # backend events with no hit before them: real compiles
    "cache_load_ms": 0.0,  # cache retrieval on those hits
    "trace_ms": 0.0,      # union of the trace spans, a thread at a time
    "lower_ms": 0.0,      # union of the lowering spans, less traces inside
    "events": 0,          # trace and lowering spans the ledger took in
}
# the ledger, under _watch_lock: program name -> row; and for each thread
# that traces, the disjoint intervals its spans cover so far ([traces],
# [traces and lowerings]), from which the totals grow
_ledger: Dict[str, Dict[str, Any]] = {}
_aliases: Dict[Any, Dict[str, Any]] = {}
_unions: Dict[int, tuple] = {}
_hit_tls = threading.local()  # .load_s: this thread's hit awaiting its backend event


def _program_name(fun_name: Any) -> str:
    """The ledger's key: `jit(step)` as the lowering and backend events
    spell it; a trace event's bare `step` is brought to that form."""
    name = str(fun_name or "?")
    return name if "(" in name else f"jit({name})"


def _row(fun_name: Any, secs: float) -> Dict[str, Any]:
    """The row of the program an event of `secs` seconds names (caller
    holds _watch_lock); past LEDGER_NAMES_MAX names the rest share one."""
    row = _aliases.get(fun_name)  # by the spelling JAX sent: no string work
    if row is None:
        name = _program_name(fun_name)
        if name not in _ledger and len(_ledger) >= LEDGER_NAMES_MAX:
            name = "(more)"
        row = _ledger.get(name)
        if row is None:
            row = _ledger[name] = {
                "trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0,
                "compile_s": 0.0, "hit": 0, "miss": 0,
                "t_first": round(job_now() - secs, 4)}
        if len(_aliases) < 2 * LEDGER_NAMES_MAX:
            _aliases[fun_name] = row
    return row


def _union_add(iv: List[tuple], s: float, e: float) -> float:
    """Add [s, e] to `iv`, disjoint (start, end, seconds covered) in order;
    returns by how much their union grew.  Spans arrive as they END, a
    nested one before its caller, so only the tail can be covered or
    touched.  A caller's trace holds every jit it calls (each `jnp`
    function is one: tens of thousands in a model's step), and all of them
    wait here until the caller's own span takes them up."""
    covered = 0.0
    while iv and iv[-1][0] >= s:
        _, b, m = iv.pop()
        covered += m
        e = max(e, b)
    if iv and iv[-1][1] > s:
        a, b, m = iv.pop()
        covered += m
        s, e = a, max(e, b)
    iv.append((s, e, e - s))
    if len(iv) > UNION_INTERVALS_MAX:
        # never seen: the older half as one stretch with its seconds (a
        # span that later starts inside it would count its gaps as new)
        head = iv[:len(iv) // 2]
        iv[:len(head)] = [(head[0][0], head[-1][1], sum(m for _, _, m in head))]
    return (e - s) - covered


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kw: Any) -> None:
    """jax.monitoring time-span listener: tracing and lowering, the two
    stages of a first call that are Python.  Per program the seconds JAX
    reports (a caller's include its callees'); in the totals each instant
    of a thread counts once."""
    if event == TRACE_EVENT:
        key = "trace_s"
    elif event == LOWER_EVENT:
        key = "lower_s"
    else:
        return
    start, end = float(start_time), float(end_time)
    tid = threading.get_ident()
    with _watch_lock:
        _watch["events"] += 1
        _row(kw.get("fun_name"), end - start)[key] += end - start
        pair = _unions.get(tid)
        if pair is None:
            if len(_unions) >= 64:
                _unions.clear()  # threads long gone; totals are kept
            pair = _unions[tid] = ([], [])
        grew = _union_add(pair[1], start, end)
        if key == "trace_s":
            traced = _union_add(pair[0], start, end)
            _watch["trace_ms"] += traced * 1e3
            grew -= traced
        _watch["lower_ms"] += grew * 1e3


def _on_duration_event(event: str, duration_secs: float, **kw: Any) -> None:
    """jax.monitoring duration listener: fires for EVERY program jit builds
    in the process, tracked or not, compiled or loaded from the persistent
    cache — `compiles_total`.  On a hit the retrieval's own duration comes
    first, on the same thread; it decides which column of the program's
    ledger row the backend event lands in."""
    if event == CACHE_LOAD_EVENT:
        _hit_tls.load_s = float(duration_secs)
        return
    if event != BACKEND_COMPILE_EVENT:
        return
    secs = float(duration_secs)
    ms = secs * 1000.0
    load_s = getattr(_hit_tls, "load_s", None)
    _hit_tls.load_s = None
    with _watch_lock:
        counted = _watch["active"]
        if counted:
            _watch["compile_ms"] += ms
            _watch["compiles"] += 1
        row = _row(kw.get("fun_name"), secs)
        if load_s is None:
            _watch["cache_misses"] += 1
            row["compile_s"] += secs
            row["miss"] += 1
        else:
            _watch["cache_load_ms"] += load_s * 1e3
            row["load_s"] += load_s
            row["hit"] += 1
    c = _counters() if counted else None
    if c is not None:
        c.inc_event("compiles_total")
        c.observe_hist("compile_ms", ms)


def _on_event(event: str, **kw: Any) -> None:
    if event != CACHE_HIT_EVENT or not _watch["active"]:
        return
    with _watch_lock:
        _watch["cache_hits"] += 1
    c = _counters()
    if c is not None:
        c.inc_event("compile_cache_hits")


_STAGES = ("trace_s", "lower_s", "load_s", "compile_s")


def compile_ledger() -> Dict[str, Any]:
    """{"programs": the LEDGER_ROWS largest by seconds, each {program,
    trace_s, lower_s, load_s, compile_s, hit, miss, t_first}, "other": the
    rest as one row with `programs`, how many}.  A program's seconds are
    what JAX reports under its name (a caller's trace holds its callees');
    the watch's totals are the ones that count each instant once."""
    with _watch_lock:
        rows = [dict(r, program=name) for name, r in _ledger.items()]
    rows.sort(key=lambda r: -sum(r[k] for k in _STAGES))
    rest = rows[LEDGER_ROWS:]
    other = {k: sum(r[k] for r in rest) for k in _STAGES + ("hit", "miss")}
    other["programs"] = len(rest)
    for r in rows[:LEDGER_ROWS] + [other]:
        for k in _STAGES:
            r[k] = round(r[k], 6)
    return {"programs": rows[:LEDGER_ROWS], "other": other}


def boot_phases() -> List[Span]:
    """The process's boot spans, `make_mesh`'s first question about devices
    among them (`plan/mesh.py` stamps it, being below the recorder)."""
    asked = plan_mesh.first_asked
    if asked is not None:
        backend_phase(asked[0], asked[1], platform=asked[2], devices=asked[3])
    return boot_spans()


def compile_watch_state() -> Dict[str, Any]:
    """Snapshot of the global watch ({installed, active, listening,
    compile_ms, compiles, cache_hits, cache_misses, cache_load_ms,
    trace_ms, lower_ms, events}), the ledger's rows (`programs`, `other`)
    and the process's boot phases (`boot`: name, start on the job clock,
    seconds, args)."""
    with _watch_lock:
        out = dict(_watch)
    out.update(compile_ledger())
    out["boot"] = [
        {"name": s.name, "t": round(s.t_start, 4), "s": round(s.dur, 4),
         "args": s.args or {}} for s in boot_phases()]
    return out


def _compile_ms_anchor() -> float:
    with _watch_lock:
        return float(_watch["compile_ms"])


def listen() -> bool:
    """Register the jax.monitoring listeners (idempotent): from here on the
    ledger takes in every program's trace, lowering, load and compile.  An
    entry point calls this first thing, so a boot is whole in the ledger;
    `compile_ms`, `compiles` and `cache_hits` start counting at
    `maybe_install`, where they always did (a serving worker's are the
    programs of its requests, not of its boot).  False where KFT_PROGRAMS=0
    or jax.monitoring is absent."""
    if not programs_enabled():
        return False
    with _watch_lock:
        if _watch["listening"] is not None:
            return bool(_watch["listening"])
        _watch["listening"] = False
    try:
        from jax import monitoring as jmon

        jmon.register_event_duration_secs_listener(_on_duration_event)
        jmon.register_event_listener(_on_event)
        jmon.register_event_time_span_listener(_on_time_span)
    except Exception as e:  # noqa: BLE001 - fallback path takes over
        log.debug("jax.monitoring unavailable (%s): track() will wall-clock "
                  "first calls instead", e)
        return False
    with _watch_lock:
        _watch["listening"] = True
    return True


def maybe_install() -> bool:
    """Arm the observatory (idempotent): the jax.monitoring listeners
    (`listen`), the watch's compile counters and the live-array census
    tick.  Returns True when the listener is live; False means track()
    wall-clocks compiles instead (old jax, or jax.monitoring absent).
    Called from monitor.server.maybe_start_monitor and from the first
    track()."""
    if not programs_enabled():
        return False
    with _watch_lock:
        if _watch["installed"]:
            return bool(_watch["active"])
        _watch["installed"] = True
    try:
        from .timeseries import register_tick_callback

        register_tick_callback(_census_tick)
    except Exception as e:  # noqa: BLE001 - census is best-effort
        log.debug("census tick not registered: %s", e)
    if not listen():
        return False
    with _watch_lock:
        _watch["active"] = True
    c = _counters()
    if c is not None:
        from . import boot

        c.add_source(boot.families)  # /metrics, and a capture's counters.json
    return True


# -- signature digests -----------------------------------------------------------------


def signature_digest(args: tuple, kwargs: Dict[str, Any]) -> str:
    """Shape/dtype digest of one call's arguments — the registry's proxy
    for jit's cache key.  Array leaves contribute (shape, dtype), python
    leaves their type (jit re-traces on new static/weak-typed values of a
    DIFFERENT kind; equal-typed scalars share a lowering for our jit call
    sites, which pass them as traced args).  The treedef guards against
    structural changes that alias leaf-wise."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    parts: List[str] = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{tuple(shape)}:{dtype}")
        else:
            parts.append(f"py:{type(leaf).__name__}")
    raw = f"{treedef}|{';'.join(parts)}"
    return hashlib.sha1(raw.encode()).hexdigest()[:12]


# -- the registry ----------------------------------------------------------------------


class _Program:
    """One tracked fn's compile history.  Guarded by the registry lock."""

    __slots__ = ("name", "digests", "budget", "recompile_t", "storm_active",
                 "storms", "budget_over")

    def __init__(self, name: str):
        self.name = name
        self.digests: Dict[str, Dict[str, Any]] = {}  # digest -> record
        self.budget: Optional[int] = None
        self.recompile_t: deque = deque()  # job-time of each NEW non-first digest
        self.storm_active = False
        self.storms = 0
        self.budget_over = 0


class ProgramRegistry:
    """Per-process compiled-program registry: name -> signature digests with
    compile times and call counts, plus the storm detector and signature
    budgets.  Thread-safe; journal/counter emission happens outside the
    lock (journal IO must never serialize the callers)."""

    def __init__(self, storm_window_s: Optional[float] = None,
                 storm_min: Optional[int] = None,
                 clock: Callable[[], float] = job_now):
        self._lock = threading.Lock()
        self._programs: Dict[str, _Program] = {}
        self.clock = clock
        self.storm_window_s = (
            _env_float(STORM_WINDOW_ENV, DEFAULT_STORM_WINDOW_S)
            if storm_window_s is None else float(storm_window_s))
        self.storm_min = (
            max(2, int(_env_float(STORM_MIN_ENV, DEFAULT_STORM_MIN)))
            if storm_min is None else max(2, int(storm_min)))
        self.storms_total = 0
        self.budget_violations = 0

    def _get(self, name: str) -> _Program:
        p = self._programs.get(name)
        if p is None:
            p = self._programs[name] = _Program(name)
        return p

    # -- budgets ----------------------------------------------------------------------

    def declare_budget(self, name: str, budget: Optional[int]) -> None:
        """Declare (or renew) a program's expected signature count.
        KFT_SIG_BUDGET overrides the declared value.  Re-declaring RESETS
        the counted signatures: an elastic rebuild or a fresh engine
        legitimately recompiles everything, and its promise starts over."""
        env = _env_budgets().get(name)
        with self._lock:
            p = self._get(name)
            p.budget = env if env is not None else (
                None if budget is None else int(budget))
            p.digests.clear()
            p.recompile_t.clear()
            p.storm_active = False

    def check_budgets(self) -> List[str]:
        """Every budget violation as a human-readable string ([] = clean) —
        the drill-side assertion surface."""
        with self._lock:
            return [
                f"{p.name}: {len(p.digests)} signatures > budget {p.budget}"
                for p in sorted(self._programs.values(), key=lambda p: p.name)
                if p.budget is not None and len(p.digests) > p.budget
            ]

    # -- per-call accounting ----------------------------------------------------------

    def note_call(self, name: str, digest: str) -> bool:
        """Count one call; True when the digest is NEW for this program
        (the caller should time the call and report note_compiled)."""
        with self._lock:
            p = self._get(name)
            rec = p.digests.get(digest)
            if rec is not None:
                rec["calls"] += 1
                return False
            return True

    def note_compiled(self, name: str, digest: str, compile_ms: float,
                      count_global: bool = False) -> None:
        """Record one new signature: journal `program_compiled`, run the
        storm detector, check the budget.  `count_global` makes this call
        also feed `compiles_total`/`compile_ms` — the fallback path when
        the jax.monitoring listener isn't live."""
        t = self.clock()
        with self._lock:
            p = self._get(name)
            if digest in p.digests:  # raced another thread: theirs won
                p.digests[digest]["calls"] += 1
                return
            p.digests[digest] = {
                "compile_ms": round(float(compile_ms), 3),
                "t_job": round(t, 4),
                "calls": 1,
            }
            n_sigs = len(p.digests)
            is_recompile = n_sigs > 1
            storm = False
            if is_recompile:
                p.recompile_t.append(t)
                cutoff = t - self.storm_window_s
                while p.recompile_t and p.recompile_t[0] < cutoff:
                    p.recompile_t.popleft()
                if len(p.recompile_t) >= self.storm_min:
                    if not p.storm_active:
                        storm = True
                        p.storm_active = True
                        p.storms += 1
                        self.storms_total += 1
                else:
                    p.storm_active = False  # burst drained: re-arm
            over = p.budget is not None and n_sigs > p.budget
            if over:
                p.budget_over += 1
                self.budget_violations += 1
            recompiles = len(p.recompile_t)
            budget = p.budget
        journal_event("program_compiled", program=name, digest=digest,
                      compile_ms=round(float(compile_ms), 3),
                      signatures=n_sigs)
        c = _counters()
        if c is not None:
            c.inc_event("program_compiled")
            c.observe_hist("compile_ms", float(compile_ms), label=name)
            if count_global:
                c.inc_event("compiles_total")
                c.observe_hist("compile_ms", float(compile_ms))
        if storm:
            log.warning(
                "recompile storm: %s hit %d new signatures in %.0fs "
                "(every one is a full XLA compile on the hot path)",
                name, recompiles, self.storm_window_s)
            journal_event("recompile_storm", program=name,
                          recompiles=recompiles,
                          window_s=self.storm_window_s)
            if c is not None:
                c.inc_event("recompile_storm")
                c.set_gauge("recompile_storms", float(self.storms_total))
        if over:
            log.warning("signature budget exceeded: %s compiled %d "
                        "signatures, promised %s", name, n_sigs, budget)
            journal_event("sig_budget_exceeded", program=name, budget=budget,
                          signatures=n_sigs)
            if c is not None:
                c.inc_event("sig_budget_exceeded")

    # -- introspection ----------------------------------------------------------------

    def signatures(self, name: str) -> int:
        with self._lock:
            p = self._programs.get(name)
            return 0 if p is None else len(p.digests)

    def compiles_total(self) -> int:
        """Total NEW signatures across every tracked program — constant
        once a workload is warm (the PR-14 regression invariant)."""
        with self._lock:
            return sum(len(p.digests) for p in self._programs.values())

    def report(self) -> Dict[str, Any]:
        """JSON-ready snapshot (the worker /programs endpoint body)."""
        with self._lock:
            programs = {
                p.name: {
                    "signatures": len(p.digests),
                    "budget": p.budget,
                    "calls": sum(r["calls"] for r in p.digests.values()),
                    "compile_ms_total": round(
                        sum(r["compile_ms"] for r in p.digests.values()), 3),
                    "storms": p.storms,
                    "budget_over": p.budget_over,
                    "digests": {d: dict(r) for d, r in p.digests.items()},
                }
                for p in self._programs.values()
            }
            out = {
                "enabled": programs_enabled(),
                "storm_window_s": self.storm_window_s,
                "storm_min": self.storm_min,
                "storms_total": self.storms_total,
                "budget_violations": self.budget_violations,
                "programs": programs,
            }
        out["watch"] = compile_watch_state()
        return out


_registry = ProgramRegistry()


def global_registry() -> ProgramRegistry:
    return _registry


# -- track(): the per-fn hook ----------------------------------------------------------


def track(name: str, fn: Callable, budget: Optional[int] = None,
          registry: Optional[ProgramRegistry] = None) -> Callable:
    """Wrap a jit-compiled callable with per-signature accounting.

    Every call computes the aval digest of its arguments; a new digest is
    a new compiled program, so the wrapper times that first call — the
    jax.monitoring listener's ms delta when live, the wall clock otherwise
    — and reports it to the registry (journal, storm detector, budget).
    Passing `budget` declares the expected signature count (KFT_SIG_BUDGET
    overrides); re-wrapping re-declares, so a rebuilt trainer/engine
    starts a fresh promise.  With KFT_PROGRAMS=0 the fn is returned
    UNCHANGED — the disabled path has no wrapper at all."""
    if not programs_enabled():
        return fn
    reg = _registry if registry is None else registry
    maybe_install()
    if budget is not None or _env_budgets().get(name) is not None:
        reg.declare_budget(name, budget)

    return _Tracked(name, fn, reg)


class _Tracked:
    """Callable wrapper produced by :func:`track`.

    A class (not a closure) so attribute access falls through to the
    wrapped jit object — `.lower()`, `._cache_size()`, AOT introspection
    all keep working on the tracked fn."""

    def __init__(self, name: str, fn: Callable, reg: "ProgramRegistry"):
        self.__name__ = f"tracked[{name}]"
        self.__wrapped__ = fn
        self._kft_program = name
        self._kft_registry = reg
        # signature key -> digest string: grows by one entry for each new
        # signature, which is a compile, so the budget bounds it
        self._kft_digests: Dict[Any, str] = {}

    def _digest(self, args: tuple, kwargs: Dict[str, Any]) -> str:
        """`signature_digest` of the call, memoised: a steady-state call
        hashes the treedef and the leaves' (shape, dtype) objects and never
        builds the text the digest is a SHA-1 of."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        sig: List[Any] = []
        for leaf in leaves:
            try:
                sig.append((leaf.shape, leaf.dtype))
            except AttributeError:  # a python scalar: its type, as the digest
                sig.append(type(leaf))
        key = (treedef, tuple(sig))
        digest = self._kft_digests.get(key)
        if digest is None:
            digest = self._kft_digests[key] = signature_digest(args, kwargs)
        return digest

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        fn, reg, name = self.__wrapped__, self._kft_registry, self._kft_program
        with trace_scope("programs:digest", cat="programs"):
            digest = self._digest(args, kwargs)
            new = reg.note_call(name, digest)
        if not new:
            return fn(*args, **kwargs)
        listener = bool(_watch["active"])
        anchor = _compile_ms_anchor() if listener else 0.0
        t0 = time.monotonic()
        # trace + lower + load-or-compile of this signature, until the call
        # returns (its first execution is dispatched, not awaited): a boot
        # phase, and the only span a later call never opens
        with trace_scope("boot:first_call", cat=BOOT_CAT,
                         args={"program": name}):
            out = fn(*args, **kwargs)
        wall_ms = (time.monotonic() - t0) * 1000.0
        delta = (_compile_ms_anchor() - anchor) if listener else 0.0
        # the listener's delta is the real compile time; when it saw
        # nothing (listener absent, or jit served a cached executable)
        # the first-call wall time is the honest upper bound
        reg.note_compiled(name, digest, delta if delta > 0.0 else wall_ms,
                          count_global=not listener)
        from . import boot

        boot.refresh()  # a first call after boot complete: the record again
        return out

    def __getattr__(self, attr: str) -> Any:
        return getattr(self.__wrapped__, attr)

    def __repr__(self) -> str:
        return f"<tracked[{self._kft_program}] of {self.__wrapped__!r}>"


# -- memory census ---------------------------------------------------------------------


def measure_live_bytes() -> Dict[str, float]:
    """One live-array census: array count + summed bytes, plus per-device
    HBM in use where the backend reports memory_stats (absent on CPU)."""
    out = {"live_arrays": 0.0, "live_array_bytes": 0.0}
    try:
        import jax

        arrs = jax.live_arrays()
    except Exception:  # noqa: BLE001 - census must never raise
        return out
    total = 0
    for a in arrs:
        try:
            total += int(a.nbytes)
        except Exception:  # noqa: BLE001 - deleted/donated mid-walk
            continue
    out["live_arrays"] = float(len(arrs))
    out["live_array_bytes"] = float(total)
    hbm = 0.0
    seen = False
    try:
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 - backend without stats
                stats = None
            if stats and "bytes_in_use" in stats:
                hbm += float(stats["bytes_in_use"])
                seen = True
    except Exception:  # noqa: BLE001
        pass
    if seen:
        out["hbm_bytes_in_use"] = hbm
    return out


def _census_tick() -> None:
    """Timeseries tick callback: publish the census as gauges, so the
    sampler turns them into `gauge:live_arrays` / `gauge:live_array_bytes`
    / `gauge:hbm_bytes_in_use` series for free — no extra thread."""
    if sys.is_finalizing():  # never enter the XLA client during teardown
        return
    c = _counters()
    if c is None:
        return
    for k, v in measure_live_bytes().items():
        c.set_gauge(k, v)


def journal_footprint(program: str, predicted_bytes: float,
                      measured_bytes: Optional[float] = None) -> Dict[str, Any]:
    """Compare a predicted HBM footprint (tuner/footprint.py) against the
    measured census and journal `hbm_footprint` with the relative error —
    the honesty loop that keeps the cost model's gate calibrated.  With
    measured_bytes=None the current census supplies it (device HBM where
    reported, else live-array bytes)."""
    if not programs_enabled():
        return {}
    if measured_bytes is None:
        census = measure_live_bytes()
        measured_bytes = census.get("hbm_bytes_in_use",
                                    census["live_array_bytes"])
    predicted = float(predicted_bytes)
    measured = float(measured_bytes)
    rel_err = abs(measured - predicted) / max(predicted, 1.0)
    rec = {
        "program": program,
        "predicted_bytes": int(predicted),
        "measured_bytes": int(measured),
        "rel_err": round(rel_err, 4),
    }
    journal_event("hbm_footprint", **rec)
    c = _counters()
    if c is not None:
        c.set_gauge("hbm_footprint_rel_err", rel_err)
    return rec


# -- on-demand profiling ---------------------------------------------------------------

PROFILE_MAX_SECS = 120.0
_profile_lock = threading.Lock()
_profile_seq = 0


def capture_profile(secs: float, out_dir: Optional[str] = None,
                    python: bool = False) -> Dict[str, Any]:
    """Capture a jax.profiler device trace for `secs` seconds and dump it
    atomically next to the trace dumps (KFT_TRACE_DUMP_DIR).  The capture
    holds the device's operations, the runtime's own host events and every
    `trace_scope` of the program; the profiler's Python tracer, whose
    frames would bury those scopes (a reader names a device-idle gap by
    the shortest host event open in it), runs only with `python=True`, for
    hunting a frame no scope names.  The capture
    window is recorded as a `profile:capture` span so it shows up in
    /timeline next to whatever it overlapped.  Any failure — profiler
    absent, already running, interpreter-only build — degrades to a no-op
    result (ok=false, noop=true), never an exception: this sits behind an
    HTTP endpoint and a fleet fan-out.

    Counters that live on the device (`Counters.add_source`: the experts'
    routing counts) are read at both ends of the capture into
    `<capture>/counters.json` ({"start": families, "end": families}), so a
    reader has them over the very window the device trace covers."""
    global _profile_seq
    try:
        secs = min(max(float(secs), 0.05), PROFILE_MAX_SECS)
    except (TypeError, ValueError):
        secs = 2.0
    out_dir = out_dir or os.environ.get("KFT_TRACE_DUMP_DIR") or tempfile.gettempdir()
    with _profile_lock:
        _profile_seq += 1
        n = _profile_seq
    from .journal import _identity

    dest = os.path.join(out_dir, f"profile-{_identity()}-{n}")
    result: Dict[str, Any] = {"secs": secs, "python": bool(python),
                              "t_start": round(job_now(), 4)}
    with trace_scope("profile:capture", cat="profile",
                     args={"secs": secs, "seq": n}):
        try:
            import jax.profiler

            os.makedirs(out_dir, exist_ok=True)
            # stage in a tempdir ON THE SAME FILESYSTEM so the final
            # os.replace is atomic — a mid-capture kill leaves only a
            # .profile-tmp-* dir, never a half-written artifact
            tmp = tempfile.mkdtemp(prefix=".profile-tmp-", dir=out_dir)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python else 0
            c = _counters()
            read_sources = c.source_families if c is not None else dict
            jax.profiler.start_trace(tmp, profiler_options=options)
            try:
                start = read_sources()
                time.sleep(secs)
                end = read_sources()
            finally:
                t_end = time.monotonic()
                _stop_trace_xplane_only(tmp)
            if end:
                with open(os.path.join(tmp, "counters.json"), "w") as f:
                    json.dump({"start": start, "end": end}, f)
            os.replace(tmp, dest)
            # capture's end to artifact ready: the process serves on
            # meanwhile, slower, so this is part of what a capture costs
            result.update(ok=True, noop=False, path=dest,
                          dump_s=round(time.monotonic() - t_end, 3))
        except Exception as e:  # noqa: BLE001 - no-op fallback is the contract
            log.warning("profile capture degraded to no-op: %s", e)
            result.update(ok=False, noop=True, error=str(e))
    result["t_end"] = round(job_now(), 4)
    return result


def _stop_trace_xplane_only(log_dir: str) -> None:
    """`jax.profiler.stop_trace()` less its trace-viewer export: the
    session is stopped and its XSpace written where the public call puts
    it (`<log_dir>/plugins/profile/<time>/<host>.xplane.pb`, what
    TensorBoard, `ProfileData` and the benchmark's reduction read), and
    the conversion to `trace.json.gz` is left out.  For 3 s of a serving
    worker (450,000 device events) that conversion took 10 of the 18 s
    between the capture's end and the artifact, on the cores the worker
    serves from (PERF.md section 6, PR 23)."""
    from jax._src import profiler as jax_profiler  # no public stop without export

    state = jax_profiler._profile_state
    with state.lock:
        if state.profile_session is None:
            raise RuntimeError("No profile started")
        try:
            xspace = state.profile_session.stop()
        finally:
            state.reset()
    run_dir = os.path.join(log_dir, "plugins", "profile",
                           time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, f"{socket.gethostname()}.xplane.pb"),
              "wb") as f:
        f.write(xspace)


def _reset_for_tests() -> None:
    """Fresh registry + watch counters (the listener itself stays
    registered with jax — it is idempotent and feed-only)."""
    global _registry
    _registry = ProgramRegistry()
    with _watch_lock:
        for k, v in _watch.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                _watch[k] = type(v)()
        _ledger.clear()
        _aliases.clear()
        _unions.clear()
