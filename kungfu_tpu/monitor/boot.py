"""The start record — where one process's start went, told by the process.

Every process of a job keeps its boot phases (`utils/trace.py`: spans of
category `boot`, kept with tracing off) on the job clock, which is anchored
on the launcher's `KFT_JOB_START`; a worker also keeps the compile ledger
(`monitor/programs.py`).  This module puts the two together
(docs/observability.md "Boot"):

  enter(role, dir)  an entry point's first call: names the process's role
                    (`launcher`, `supervisor`, `serve-worker`; a process
                    nobody names is a `trainer`, the caller's script),
                    lets it write its record under `dir`, and records by
                    hand what ran before any span could: `boot:interpreter`
                    (the spawn, `KFT_PROC_START`, to the package's first
                    statement) and `boot:imports` (from there to this call;
                    for a trainer to the end of `import kungfu_tpu`) for a
                    worker, `boot:<role>.imports` for a launcher
  complete()        boot complete, an event the program knows (a launcher's
                    first worker spawned, `SERVE_WORKER_READY:`, the return
                    of the first `train:step`): closes a launcher's
                    enclosing span, logs the `BOOT:` line, writes the record
  refresh()         after a first call that came later: the record again
  record()          the record itself; `families()` the two `/metrics`
                    families, `kft_boot_seconds{phase}` and
                    `kft_program_setup_seconds{stage}`

The record is `start-<role>-<identity>.json` (tmp + rename; the newest
RECORDS_KEPT stay) under `trace.start_record_dir()`: KFT_TRACE_DUMP_DIR
when set, else `starts/` in the compile-cache directory, and only in a
process an entry point or `env.enable_compile_cache()` armed.  What the
program cannot span (the caller's own imports, its `jax.devices()`) lies
between two phases and is listed as a gap with both neighbours named.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

from ..utils import get_logger
from ..utils import trace as T
from . import programs

log = get_logger("kungfu.boot")

LAUNCHER_ROLES = ("launcher", "supervisor")
#: start records kept in a directory, newest first
RECORDS_KEPT = 32
#: a spawn stamp older than this at the process's own start was inherited
#: from a grandparent, not stamped for this process
SPAWN_STALE_S = 5.0
#: shorter stretches between two phases are not listed as gaps
GAP_MIN_S = 0.001

_lock = threading.Lock()
_state: Dict[str, Any] = {"role": "", "complete": None, "entered": False}


def role() -> str:
    return _state["role"] or "trainer"


def boot_name(part: str) -> str:
    """`boot:<role>.<part>`: a launcher's sub-phase under its own role."""
    return f"boot:{role()}.{part}"


def _spawn_mono(start_mono: float) -> float:
    """The launcher's spawn of this process on its monotonic clock, or its
    own start where no launcher stamped one for it."""
    try:
        spawn = T.wall_to_mono(float(os.environ.get("KFT_PROC_START", "")))
    except ValueError:
        return start_mono
    return spawn if 0.0 <= start_mono - spawn < SPAWN_STALE_S else start_mono


def enter(role_name: str = "", directory: str = "") -> None:
    """Name the role, arm the record (with a `directory`) and record what
    ran before the entry point; the second call of a process is a no-op."""
    with _lock:
        if _state["entered"]:
            return
        _state["entered"] = True
        _state["role"] = role_name
    if directory:
        T.arm_start_record(directory)
    start = T.process_start_mono()
    if role_name in LAUNCHER_ROLES:
        T.record_span(boot_name("imports"), start, cat=T.BOOT_CAT)
        return
    programs.listen()  # a worker's ledger takes its whole boot in
    t0, t1 = T.package_import_mono()
    T.record_span("boot:interpreter", _spawn_mono(start), t0, cat=T.BOOT_CAT,
                  args={"process_start": round(T.job_now(start), 4)})
    # a named worker's entry point is the program's own, so everything up
    # to this call was its imports; a trainer's is the caller's script, and
    # what it imports after the package lies in a gap
    T.record_span("boot:imports", t0, None if role_name else t1,
                  cat=T.BOOT_CAT)


def complete() -> None:
    """Boot complete; later calls do nothing."""
    with _lock:
        if _state["complete"] is not None:
            return
        _state["complete"] = T.job_now()
    enter()  # a trainer nobody entered: its interpreter and import phases
    if role() in LAUNCHER_ROLES:
        T.record_span(f"boot:{role()}", T.process_start_mono(),
                      cat=T.BOOT_CAT)
    rec = record()
    log.info("%s", boot_line(rec))
    write_record(rec)


def launcher_spawned() -> None:
    """A launcher's boot is over once its first worker is spawned (a
    `ProcRunner` some other process starts completes nothing)."""
    if role() in LAUNCHER_ROLES:
        complete()


def refresh() -> None:
    """The record again, where boot was complete already (a first call in
    a warm-up request, a new signature): rare, and never in a steady
    window, which compiles nothing."""
    if _state["complete"] is not None:
        write_record()


def _phases() -> List[Dict[str, Any]]:
    """Boot spans by start, each with its nesting depth (0: contained in
    no other)."""
    out, open_ends = [], []
    for s in sorted(programs.boot_phases(), key=lambda s: (s.t_start, -s.dur)):
        while open_ends and open_ends[-1] <= s.t_start + 1e-9:
            open_ends.pop()
        out.append({"name": s.name, "t": round(s.t_start, 4) + 0.0,  # no -0.0
                    "s": round(s.dur, 4), "depth": len(open_ends),
                    "args": dict(s.args or {})})
        open_ends.append(s.t_start + s.dur)
    return out


def _gaps(phases: List[Dict[str, Any]], end: float) -> List[Dict[str, Any]]:
    """What no phase covers between job start and `end`, each stretch with
    the phase before and after it."""
    gaps, at, after = [], 0.0, "job_start"
    top = [p for p in phases if p["depth"] == 0 and p["t"] < end]
    for p in top + [{"name": "boot_complete", "t": end, "s": 0.0}]:
        if p["t"] - at >= GAP_MIN_S:
            gaps.append({"after": after, "before": p["name"],
                         "t": round(at, 4), "s": round(p["t"] - at, 4)})
        if p["t"] + p["s"] > at:
            at, after = p["t"] + p["s"], p["name"]
    return gaps


def record() -> Dict[str, Any]:
    """This process's start as one JSON-ready object: who it is, the job
    clock's anchor, its phases and their gaps up to boot complete (now,
    where boot is not complete yet), and the compile ledger."""
    phases = _phases()
    done = _state["complete"]
    end = T.job_now() if done is None else done
    watch = programs.compile_watch_state()
    watch.pop("boot", None)
    return {
        "version": 1, "role": role(), "identity": T._dump_identity(),
        "pid": os.getpid(), "ppid": os.getppid(),
        "job_start_wall": T._job_start_wall(),
        "process_start": round(T.job_now(T.process_start_mono()), 4) + 0.0,
        "boot_complete": None if done is None else round(done, 4),
        "written": round(T.job_now(), 4),
        "phases": phases, "gaps": _gaps(phases, end), "ledger": watch,
    }


def phase_seconds(phases: List[Dict[str, Any]]) -> Dict[str, float]:
    """Seconds by phase name, summed over the spans that carry it."""
    out: Dict[str, float] = {}
    for p in phases:
        out[p["name"]] = out.get(p["name"], 0.0) + p["s"]
    return out


def _stage_seconds(ledger: Dict[str, Any]) -> Dict[str, float]:
    rows = list(ledger.get("programs", [])) + [ledger.get("other", {})]
    return {"trace": ledger.get("trace_ms", 0.0) / 1e3,
            "lower": ledger.get("lower_ms", 0.0) / 1e3,
            "load": ledger.get("cache_load_ms", 0.0) / 1e3,
            "compile": sum(r.get("compile_s", 0.0) for r in rows)}


def boot_line(rec: Optional[Dict[str, Any]] = None) -> str:
    """The operator's view of a start, one line."""
    rec = record() if rec is None else rec
    sec, led = phase_seconds(rec["phases"]), rec["ledger"]
    interp = next((p for p in rec["phases"]
                   if p["name"] == "boot:interpreter"), None)
    stage = _stage_seconds(led)
    rows = list(led.get("programs", [])) + [led.get("other", {})]
    named = {
        "total": rec["boot_complete"] or rec["written"],
        # a worker's launcher share: job start to its spawn
        "launcher": max(0.0, interp["t"]) if interp
        else sec.get(f"boot:{rec['role']}", 0.0),
        "interpreter": sec.get("boot:interpreter", 0.0),
        "imports": sec.get("boot:imports", 0.0)
        + sec.get(f"boot:{rec['role']}.imports", 0.0),
        "backend": sec.get("boot:backend", 0.0),
        "weights": sum(sec.get(k, 0.0) for k in (
            "boot:weights", "boot:resident", "boot:engine", "train:init")),
        "first_call": sec.get("train:lower", 0.0) + sec.get("boot:first_call", 0.0),
    }
    return ("BOOT: " + " ".join(f"{k}={v:.2f}" for k, v in named.items())
            + " (" + " ".join(f"{k}={v:.2f}" for k, v in stage.items()) + ")"
            + f" programs={len(rows) - 1 + rows[-1].get('programs', 0)}"
            + f" hits={sum(r.get('hit', 0) for r in rows)}"
            + f" misses={led.get('cache_misses', 0)}")


def families() -> Dict[str, Dict[str, float]]:
    """`Counters.add_source` rows: seconds of each boot phase so far, and
    of each stage of building programs (the ledger's totals)."""
    watch = programs.compile_watch_state()
    boot = phase_seconds([{"name": p["name"], "s": p["s"]}
                          for p in watch["boot"]])
    return {
        "kft_boot_seconds": {
            f'phase="{name}"': round(s, 4) for name, s in sorted(boot.items())},
        "kft_program_setup_seconds": {
            f'stage="{stage}"': round(s, 4)
            for stage, s in _stage_seconds(watch).items()},
    }


def write_record(rec: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Write the record now, atomically, and keep the directory to its
    newest RECORDS_KEPT; None where this process writes none, or on an IO
    error (a record must never take the process down)."""
    d = T.start_record_dir()
    if not d:
        return None
    rec = record() if rec is None else rec
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"start-{rec['role']}-{rec['identity']}.json")
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, path)
        _prune(d)
        return path
    except OSError as e:
        log.warning("start record not written: %s", e)
        return None


def _prune(d: str) -> None:
    """Drop all but the newest RECORDS_KEPT records of `d`.  Other
    processes write and prune here too: a file that went meanwhile is
    theirs to have removed."""
    aged = []
    for name in os.listdir(d):
        if name.startswith("start-") and name.endswith(".json"):
            try:
                aged.append((os.stat(os.path.join(d, name)).st_mtime, name))
            except OSError:
                continue
    for _, name in sorted(aged, reverse=True)[RECORDS_KEPT:]:
        try:
            os.remove(os.path.join(d, name))
        except OSError:
            continue


def _reset_for_tests() -> None:
    with _lock:
        _state.update(role="", complete=None, entered=False)
    T._reset_boot_for_tests()
