"""Metric readers: one small file for each metric, found by the metric's name.

A reader is `<name>.json` (declarative) or `<name>.py` (a module with
`read(ctx)`, and optionally `TRACE_BUCKETS`).  Either gets the run's context

  ctx["values"]   numbers the driver and the worker took: host clocks, counters
  ctx["trace"]    the reduced trace of a traced run (xplane.reduce_trace), or None
  ctx["config"], ctx["traffic"], ctx["cell"], ctx["device"], ctx["peaks"]

and returns a number, or None when there is nothing to read; the harness then
leaves the metric out of the line.  Declarative kinds:

  {"kind": "value", "key": K, "scale": 1.0}
      ctx["values"][K] * scale
  {"kind": "trace", "bucket": {"match": REGEX, "line": "XLA Ops" | "XLA Modules",
                               "async_pairs": false},
   "reduce": "share_of_busy" | "exposed_share" | "ms_per_step"}
      events of the device trace whose name matches, reduced
  {"kind": "idle_share"}
      1 - device busy time over the traced window, in percent
"""
from __future__ import annotations

import importlib.util
import json
import os


class Reader:
    def __init__(self, name: str, path: str):
        self.name, self.path = name, path
        self.doc, self.mod = None, None
        if path.endswith(".json"):
            with open(path) as f:
                self.doc = json.load(f)
        else:
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + name.replace("-", "_").replace(".", "_"), path)
            self.mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(self.mod)

    def trace_buckets(self) -> dict:
        """Buckets this reader wants the trace reduced into."""
        if self.doc is not None:
            if self.doc["kind"] == "trace":
                return {self.name: self.doc["bucket"]}
            return {}
        return dict(getattr(self.mod, "TRACE_BUCKETS", {}))

    def read(self, ctx: dict):
        if self.mod is not None:
            return self.mod.read(ctx)
        d = self.doc
        kind = d["kind"]
        if kind == "value":
            v = ctx["values"].get(d["key"])
            return None if v is None else float(v) * d.get("scale", 1.0)
        trace = ctx.get("trace")
        if not trace or not trace.get("devices"):
            return None
        if kind == "idle_share":
            return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
        if kind == "trace":
            b = trace["buckets"].get(self.name)
            if b is None:
                return None
            how = d["reduce"]
            if how == "share_of_busy":
                return 100.0 * b["seconds"] / trace["busy_s"]
            if how == "exposed_share":
                return 100.0 * b["exposed_s"] / b["seconds"] if b["seconds"] else None
            if how == "ms_per_step":
                steps = ctx["values"].get("traced_steps")
                return 1e3 * b["seconds"] / steps if steps else None
        raise ValueError(f"{self.path}: unknown reader {d}")


def load_peaks(device_kind: str) -> dict:
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/lib/peaks.json; add it with its source")
    return table[device_kind]
