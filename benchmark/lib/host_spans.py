"""The program's own spans, read out of the capture a traced run already takes.

Every `trace_scope` of the program (`kungfu_tpu/utils/trace.py`) is an
annotation on the profiler's timeline, so the cut-down trace the harness
saves for a traced run (`.bench_out/<cell>/events.json.gz`, written by
`xplane.py` from the same `.xplane.pb` the device metrics come from) holds
them among its host events, on the clock of the device's operations.  Two
reductions over them:

  `ms_per(red, names, per)`     summed duration of the spans called `names`
                                over the occurrences of the span `per`: the
                                mean host time of a phase per decode step
  `idle_seconds(red, pick)`     device-idle seconds lying under the program
                                spans `pick` chooses, each instant counted
                                for the innermost span open at it (the one
                                that started last); `pick(None)` chooses the
                                idle under no program span at all

Device idle is device 0's: the complement, inside its window, of the union
of its op events, as `xplane.reduce_trace` takes it for the idle share.
`load_trace` merges the host's threads into one list; the program opens its
`serve:` and `train:` spans on one thread each, so "innermost" is well
defined.  A capture with no such span (a parent commit from before the
spans, a trace recorded before them) reduces to nothing and a reader then
returns `None`: the metric is left out of the line, not read as zero.
"""
from __future__ import annotations

import functools
import os
import re

import numpy as np

from benchmark.lib import xplane as X

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: what the program's own scopes are called (utils/trace.py callers)
PROGRAM_SPAN = re.compile(r"^(serve|programs|train):")


def program_spans(trace: dict) -> list:
    return [[n, s, d] for n, s, d in trace.get("host", []) if PROGRAM_SPAN.match(n)]


def innermost(spans) -> list:
    """[[start, end, name]], disjoint and sorted: at each instant under any
    span, the name of the one that started last among those open."""
    out, stack, cur = [], [], 0.0

    def emit(end, name):
        nonlocal cur
        if end > cur:
            out.append([cur, end, name])
            cur = end

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            emit(end, top)
        if stack:
            emit(s, stack[-1][0])
        cur = max(cur, s)
        stack.append((name, s + d))
    while stack:
        top, end = stack.pop()
        emit(end, top)
    return out


def reduce_spans(trace: dict) -> dict:
    """{"spans": {name: {"count", "seconds"}}, "idle": {"window_s", "idle_s",
    "under": {innermost span name, or None for no span: idle seconds}}};
    `idle` is None where the trace has no device plane."""
    spans = program_spans(trace)
    by_name = {}
    for name, _, d in spans:
        rec = by_name.setdefault(name, {"count": 0, "seconds": 0.0})
        rec["count"] += 1
        rec["seconds"] += d
    idle = None
    ops = X.leaves(trace["devices"][0]["ops"]) if trace.get("devices") else []
    if ops:
        busy = np.asarray(X.union([[s, s + d] for _, s, d in ops]), np.float64)
        lo, hi = busy[0, 0], busy[-1, 1]
        before = np.concatenate([[0.0], np.cumsum(busy[:, 1] - busy[:, 0])])

        def busy_until(t):  # device-busy seconds in [lo, t]
            i = np.searchsorted(busy[:, 0], t, side="right") - 1
            return before[i] + np.minimum(t, busy[i, 1]) - busy[i, 0]

        idle_s = float((hi - lo) - before[-1])
        under = {}
        for s, e, name in innermost(spans):
            s, e = min(max(s, lo), hi), min(max(e, lo), hi)
            under[name] = under.get(name, 0.0) + float(
                (e - s) - (busy_until(e) - busy_until(s)))
        under[None] = idle_s - sum(under.values())
        idle = {"window_s": float(hi - lo), "idle_s": idle_s, "under": under}
    return {"spans": by_name, "idle": idle}


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int) -> dict:
    return reduce_spans(X.read_trace(path))


def of_run(ctx: dict):
    """The reduction of this traced run's capture, or None (an untraced
    run, or no capture kept)."""
    path = os.path.join(ROOT, ".bench_out", ctx["cell"]["name"], "events.json.gz")
    if not ctx.get("trace") or not os.path.exists(path):
        return None
    return _reduce_file(path, os.stat(path).st_mtime_ns)


def ms_per(red, names, per: str):
    """Milliseconds of the spans `names` per occurrence of the span `per`."""
    if not red or not red["spans"].get(per):
        return None
    total = sum(red["spans"].get(n, {"seconds": 0.0})["seconds"] for n in names)
    return 1e3 * total / red["spans"][per]["count"]


def idle_seconds(red, pick):
    """Device-idle seconds under the innermost spans `pick(name)` accepts
    (`pick(None)`: under no program span); None without a device plane or
    without any program span in the capture."""
    if not red or not red["idle"] or not red["spans"]:
        return None
    return sum(v for name, v in red["idle"]["under"].items() if pick(name))


# -- the serving cell's split of its device idle ---------------------------------------

FETCH = re.compile(r"^serve:.*\.fetch$")

#: every instant of device idle falls in exactly one
SERVE_IDLE = {
    "unloaded": lambda n: n == "serve:idle",
    "in_fetch": lambda n: n is not None and bool(FETCH.match(n)),
    "in_host": lambda n: n is not None and n != "serve:idle" and not FETCH.match(n),
    "unnamed": lambda n: n is None,
}


def serve_idle_share(ctx: dict, part: str):
    """Percent of device 0's window idle under one part of `SERVE_IDLE`; a
    capture without a `serve:` span has no such split."""
    red = of_run(ctx)
    if not red or not any(n.startswith("serve:") for n in red["spans"]):
        return None
    s = idle_seconds(red, SERVE_IDLE[part])
    return None if s is None else 100.0 * s / red["idle"]["window_s"]
