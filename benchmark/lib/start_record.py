"""The program's own account of a start, read for the `setup_*` metrics.

Each process of a job writes `start-<role>-<identity>.json` when its boot is
complete (kungfu_tpu/monitor/boot.py; docs/observability.md "Boot"): its
boot phases on the job clock, which starts with the launcher's process, the
gaps between them, and the compile ledger.  A serving fleet under the
benchmark writes into the cell's run directory (`KFT_TRACE_DUMP_DIR`), a
training job into `starts/` of the compile-cache directory.

`of_run(ctx)` finds THIS run's pair: the launcher's (or the supervisor's)
record whose parent process is this benchmark process and whose job started
after it did, and the worker's record of the same job (same job start,
child of that launcher).  Anything else is some earlier run's, or nobody's
(the parent commit writes no record), and the readers then return `None`.

One timeline, four disjoint stretches of it (`stretches`):

  launch     job start -> the worker's entry point: the launcher's own
             share (its imports, its configuration, the spawn), then the
             worker's interpreter start (`boot:interpreter`)
  backend    the worker's entry -> devices ready: `boot:imports`,
             `boot:backend`, and between them what the caller's script
             does itself (its imports, its `jax.devices()`)
  weights    `train:init`, or `boot:weights` + `boot:resident` + `boot:engine`
  first_use  `train:lower` and every `boot:first_call`, as their union

and `setup_unnamed_s` is what they leave of the benchmark's `setup_s`.  The
ledger's totals (`trace_ms`, `lower_ms`, `cache_load_ms`) cut across the
stretches and are never added to them.
"""
from __future__ import annotations

import glob
import json
import os
import time

from .configs import ROOT

LAUNCHERS = ("launcher", "supervisor")
WEIGHTS = ("train:init", "boot:weights", "boot:resident", "boot:engine")
FIRST_USE = ("train:lower", "boot:first_call")
#: a job's start may read this much before this process's own (two clocks
#: met through the wall clock, and the kernel's 10 ms tick)
SLACK_S = 1.0

_memo = {}


def own_start_wall() -> float:
    """This (benchmark) process's real start, the kernel's, as wall time;
    its import of this module where the kernel does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED


_IMPORTED = time.time()


def record_dirs(ctx: dict) -> list:
    """Where this run's records can be: the cell's run directory, then
    `starts/` of the compile cache the children were given."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", "") or os.path.join(
        ROOT, ".jax_cache")
    return [os.path.join(ROOT, ".bench_out", ctx["cell"]["name"]),
            os.path.join(cache, "starts")]


def load_records(directory: str) -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "start-*.json"))):
        try:
            with open(path) as f:
                out.append(json.load(f))
        except (OSError, ValueError):
            continue  # one being replaced, or cut short: not this run's
    return out


def find(dirs, parent_pid: int, not_before: float):
    """{"launcher": record, "worker": record} of the job that process
    `parent_pid` started at or after wall time `not_before`, or None."""
    for d in dirs:
        recs = load_records(d)
        for top in recs:
            if (top.get("role") not in LAUNCHERS or top.get("ppid") != parent_pid
                    or top.get("job_start_wall", 0.0) < not_before - SLACK_S):
                continue
            workers = [r for r in recs if r.get("role") not in LAUNCHERS
                       and r.get("ppid") == top.get("pid")
                       and r.get("job_start_wall") == top["job_start_wall"]
                       and r.get("boot_complete") is not None]
            if workers:  # the first incarnation, where one was respawned
                return {"launcher": top,
                        "worker": min(workers, key=lambda r: r["process_start"])}
    return None


def of_run(ctx: dict):
    """This run's records, or None (see the module docstring)."""
    key = (ctx["cell"]["name"], os.getpid())
    if key not in _memo:
        _memo[key] = find(record_dirs(ctx), os.getpid(), own_start_wall())
    return _memo[key]


# -- interval arithmetic on the job clock ----------------------------------------------


def union(intervals) -> list:
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def minus(intervals, taken) -> list:
    """`intervals` (disjoint, sorted) less `taken` (the same)."""
    out = []
    for s, e in intervals:
        for ts, te in taken:
            if te <= s or ts >= e:
                continue
            if ts > s:
                out.append([s, ts])
            s = max(s, te)
            if s >= e:
                break
        if s < e:
            out.append([s, e])
    return out


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def spans(worker: dict, names) -> list:
    return [[p["t"], p["t"] + p["s"]] for p in worker["phases"] if p["name"] in names]


def stretches(worker: dict):
    """{"launch", "backend", "weights", "first_use"}: disjoint interval
    lists on the job clock; None where the record lacks the entry or the
    devices' phase."""
    entry = spans(worker, ("boot:interpreter",))
    ready = spans(worker, ("boot:backend",))
    if not entry or not ready:
        return None
    t_entry = entry[0][1]
    t_ready = max(t_entry, ready[0][1])
    out = {"launch": [[0.0, t_entry]], "backend": [[t_entry, t_ready]]}
    taken = union(out["launch"] + out["backend"])
    for key, names in (("weights", WEIGHTS), ("first_use", FIRST_USE)):
        out[key] = minus(union(spans(worker, names)), taken)
        taken = union(taken + out[key])
    return out


def stretch_seconds(ctx: dict, key: str):
    run = of_run(ctx)
    parts = run and stretches(run["worker"])
    return measure(parts[key]) if parts else None


def ledger_seconds(ctx: dict, *totals_ms: str):
    run = of_run(ctx)
    if not run:
        return None
    ledger = run["worker"].get("ledger", {})
    if any(k not in ledger for k in totals_ms):
        return None
    return sum(ledger[k] for k in totals_ms) / 1e3


def unnamed_seconds(ctx: dict):
    """`setup_s` less the four stretches: the benchmark's own spawn, gaps
    the program cannot span, the driver's warm-up beyond the programs'
    first calls, a training worker's second warm-up step."""
    run = of_run(ctx)
    parts = run and stretches(run["worker"])
    setup = ctx["values"].get("setup_s")
    if not parts or setup is None:
        return None
    return setup - sum(measure(v) for v in parts.values())
