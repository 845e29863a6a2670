#!/usr/bin/env python3
"""From a profiler trace to numbers: the benchmark's one trace reduction.

Two stages, so the second can be checked on a small recorded trace:

  1. `load_trace(dir)`: the `.xplane.pb` the JAX profiler wrote, read with
     `jax.profiler.ProfileData`, cut down to plain lists: for each device
     plane (`/device:TPU:n`) the events of its op line ("XLA Ops") and its
     program line ("XLA Modules"); for the host, the events of every thread.
  2. `reduce_trace(trace, spec)`: busy union, idle share, buckets of events
     by regular expression (seconds, and the part of them during which no
     other op runs on that device), the operations that took most time, and
     the longest idle gaps named by the host span open at the time.

Times are seconds; device numbers are averaged over the device planes.  Run
as a program it is the reduce child of `run.py` (the parent never imports
JAX):  xplane.py <trace dir> <spec.json> <out.json>
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
#: gaps shorter than this are the device's own turn-around between two ops
MIN_GAP_S = 20e-6
MAX_HOST_EVENTS = 400_000


# -- stage 1 ---------------------------------------------------------------------------


def load_trace(trace_dir: str) -> dict:
    """{"devices": [{"name", "ops": [[name, start_s, dur_s]], "modules": [...]}],
        "host": [[name, start_s, dur_s]], "lines": {plane/line: events}}"""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host, lines = [], [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [[short_name(e.name) if m else e.name,
                       e.start_ns * 1e-9, e.duration_ns * 1e-9]
                      for e in line.events]
            lines[f"{plane.name}|{line.name}"] = len(events)
            if m and line.name == OP_LINE:
                dev = _device(devices, plane.name)
                dev["ops"] = events
            elif m and line.name == MODULE_LINE:
                _device(devices, plane.name)["modules"] = events
            elif plane.name.startswith("/host:") and len(host) < MAX_HOST_EVENTS:
                host.extend(events)
    return {"devices": devices, "host": host, "lines": lines,
            "file": os.path.relpath(files[-1], trace_dir)}


def short_name(text: str) -> str:
    """An op event carries its whole HLO instruction as its name:
    `%attn.8 = (bf16[...]) custom-call(...), custom_call_target="tpu_custom_call"`.
    Kept: the instruction's own name, and a custom call's target in
    brackets (`attn.8 [tpu_custom_call]`): a Mosaic kernel is told from
    XLA's own custom calls by it."""
    name = text.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name} [{target.group(1)}]" if target else name


def _device(devices: list, name: str) -> dict:
    for d in devices:
        if d["name"] == name:
            return d
    devices.append({"name": name, "ops": [], "modules": []})
    return devices[-1]


def save_trace(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def read_trace(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic ---------------------------------------------------------------


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Parts of merged `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def leaves(events):
    """Events that contain no other event of the same line: a `while` or a
    `call` spans its body's ops and would count them twice."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    out = []
    for i, (name, s, d) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d + 1e-12 \
                and (nxt[1] > s or nxt[2] < d):
            continue
        out.append([name, s, d])
    return out


def base_name(name: str) -> str:
    """`fusion.123` -> `fusion`, `attn.8 [tpu_custom_call]` -> `attn
    [tpu_custom_call]`: one row for each kind of operation."""
    head, sep, tail = name.partition(" ")
    return (re.sub(r"[.\-_]?\d+$", "", head) or head) + sep + tail


# -- stage 2 ---------------------------------------------------------------------------


def reduce_trace(trace: dict, spec: dict) -> dict:
    """`spec["buckets"]`: {name: {"match": regex, "line": "XLA Ops" |
    "XLA Modules", "pair": optional regex pair for async start/done}}."""
    buckets = spec.get("buckets", {})
    per_dev = []
    for dev in trace["devices"]:
        ops = leaves(dev["ops"])
        if not ops:
            continue
        busy = union([[s, s + d] for _, s, d in ops])
        t_lo, t_hi = busy[0][0], busy[-1][1]
        out = {"busy_s": length(busy), "window_s": t_hi - t_lo, "buckets": {},
               "busy": busy, "ops": ops}
        for bname, b in buckets.items():
            rx = re.compile(b["match"])
            # all ops of the line, containers too: a `while` can be matched
            # as a whole; `seconds` is a union, so nothing counts twice
            src = dev["ops"] if b.get("line", OP_LINE) == OP_LINE else dev["modules"]
            hit = [[n, s, d] for n, s, d in src if rx.search(n)]
            ivs = [[s, s + d] for _, s, d in hit]
            if b.get("async_pairs"):
                ivs += _async_spans(hit)
            mine = union(ivs)
            other = union([[s, s + d] for n, s, d in ops if not rx.search(n)])
            out["buckets"][bname] = {
                "seconds": length(mine), "events": len(hit),
                "exposed_s": length(subtract(mine, other)),
                "op_seconds": sum(d for _, _, d in hit)}
        per_dev.append(out)
    if not per_dev:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "buckets": {},
                "device_ops": [], "idle_gaps": [], "lines": trace.get("lines", {})}
    n = len(per_dev)
    avg = lambda f: sum(f(d) for d in per_dev) / n  # noqa: E731
    first = per_dev[0]
    by_name = {}
    for name, _, d in first["ops"]:
        k = base_name(name)
        by_name[k] = by_name.get(k, 0.0) + d
    modules = {}
    for name, _, d in trace["devices"][0]["modules"]:
        k = re.sub(r"\(\d+\)$", "", name)
        m = modules.setdefault(k, {"count": 0, "seconds": 0.0})
        m["count"] += 1
        m["seconds"] += d
    return {
        "devices": n,
        "busy_s": avg(lambda d: d["busy_s"]),
        "window_s": avg(lambda d: d["window_s"]),
        "buckets": {b: {k: avg(lambda d, b=b, k=k: d["buckets"][b][k])
                        for k in ("seconds", "events", "exposed_s", "op_seconds")}
                    for b in buckets},
        "device_ops": [[k, v] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle_gaps(first["busy"], trace.get("host", []),
                               spec.get("span_prefix", "bench:")),
        "modules": modules,
        "lines": trace.get("lines", {}),
    }


def _async_spans(hit):
    """[start-op begin, done-op end] for `x-start.N` / `x-done.N` pairs: the
    time a collective is in flight, whatever runs meanwhile."""
    starts, spans = {}, []
    for name, s, d in sorted(hit, key=lambda x: x[1]):
        m = re.match(r"^(.*)-(start|done)(.*)$", name)
        if not m:
            continue
        key = m.group(1) + m.group(3)
        if m.group(2) == "start":
            starts[key] = s
        elif key in starts:
            spans.append([starts.pop(key), s + d])
    return spans


def idle_gaps(busy, host_events, span_prefix: str, top: int = 10,
              consider: int = 300):
    """The device's idle time inside the window, summed by what the host was
    doing: the benchmark's own span open at the gap's middle if there is
    one, else the shortest host event open then, else `unattributed`."""
    gaps = [[busy[i][1], busy[i + 1][0]] for i in range(len(busy) - 1)
            if busy[i + 1][0] - busy[i][1] >= MIN_GAP_S]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = {}
    host = [(n, s, d) for n, s, d in host_events if d > 0]
    starts = np.asarray([s for _, s, _ in host], np.float64)
    durs = np.asarray([d for _, _, d in host], np.float64)
    own = np.asarray([n.startswith(span_prefix) for n, _, _ in host], bool)
    for s, e in gaps[:consider]:
        mid = (s + e) / 2
        open_now = np.flatnonzero((starts <= mid) & (mid < starts + durs))
        mine = open_now[own[open_now]]
        pick = mine if len(mine) else open_now
        name = host[pick[np.argmin(durs[pick])]][0] if len(pick) else "unattributed"
        named[name] = named.get(name, 0.0) + (e - s)
    rest = sum(e - s for s, e in gaps[consider:])
    if rest:
        named["shorter gaps, not named"] = rest
    return [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])[:top]]


def main(argv) -> int:
    trace_dir, spec_path, out_path = argv[1:4]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reads a file, needs no chip
    with open(spec_path) as f:
        spec = json.load(f)
    trace = load_trace(trace_dir)
    if spec.get("keep_events"):
        save_trace(trace, spec["keep_events"])
    out = reduce_trace(trace, spec)
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
