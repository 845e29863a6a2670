#!/usr/bin/env python3
"""One command runs one cell once:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are data
(`BENCHMARK.json` and the files it names; see benchmark/README.md): nothing
about a cell is written here.  This parent never imports JAX, because one
process owns a chip at a time: training cells go through `python -m
kungfu_tpu.run -np 1 python benchmark/lib/train_worker.py`, serving cells
through `python -m kungfu_tpu.serving`, each child killed with its process
group.  No TPU is a non-zero exit, never a CPU run.  The last line of
standard output is the result, one JSON object; everything else goes to
standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import metrics as M  # noqa: E402
from benchmark.lib.configs import load_json  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.procs import Child, ChildFailed  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def run_training_cell(cell, config_path, traffic_path, args, out_dir, rehearse):
    out = os.path.join(out_dir, "worker.json")
    cmd = [sys.executable, "-m", "kungfu_tpu.run", "-np", "1"]
    if rehearse:
        cmd += ["-platform", rehearse]
    cmd += [sys.executable, os.path.join(ROOT, "benchmark", "lib", "train_worker.py"),
            "--config", config_path, "--traffic", traffic_path,
            "--seed", str(args.seed), "--seconds", str(args.seconds),
            "--trace", str(args.trace),
            "--trace-dir", os.path.join(out_dir, "trace"),
            "--out", out, "--t0", repr(T0)]
    child = Child("train", cmd, cwd=ROOT)
    rc = child.wait(1500)
    if rc != 0 or not os.path.exists(out):
        raise ChildFailed(f"the launcher exited {rc}"
                          + ("" if os.path.exists(out) else " and left no result"))
    res = load_json(out)
    boot = child.first_line(r"DEVICE: ")
    values = {k: v for k, v in res.items()
              if isinstance(v, (int, float)) and not isinstance(v, bool)}
    values["worker_boot_s"] = boot[0] if boot else None
    values["train_tokens_per_s_chip"] = res["tokens"] / res["window_s"] / res["chips"]
    values["traced_steps"] = (res.get("traced") or {}).get("steps")
    for k, v in res["setup_parts_s"].items():
        values["setup_" + k + "_s"] = v
    return {"device": res["device"], "values": values, "correct": res["correct"],
            "attempted": res["steps"], "failed": res["nonfinite_steps"],
            "rehearsal": res["rehearsal"], "detail": res,
            "trace_dir": os.path.join(out_dir, "trace") if args.trace else ""}


def run_serving(cell, config_path, traffic_path, args, out_dir, rehearse):
    from benchmark.lib.serve_driver import run_serving_cell

    return run_serving_cell(cell, config_path, traffic_path, args.seed,
                            args.seconds, bool(args.trace), out_dir, T0, rehearse)


DRIVERS = {"train": run_training_cell, "open": run_serving, "closed": run_serving}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench-root", default=ROOT,
                    help="directory holding BENCHMARK.json and its data files "
                         "(default: the checkout)")
    args = ap.parse_args(argv)
    rehearse = os.environ.get("KFT_BENCH_REHEARSE", "")
    # every program goes to the persistent cache, however quick its compile:
    # the program's initialisation runs some eighty small ones op by op
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    man = Manifest(args.bench_root)
    cell = man.cell(args.workload)
    config_path, traffic_path = man.config_file(cell), man.traffic_file(cell)
    traffic = load_json(traffic_path)
    group = "per_layer" if args.trace else "end_to_end"
    readers = [(m, M.Reader(m["name"], path)) for m, path in
               man.metrics_for(cell, group)]
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    log(f"cell {cell['name']}: config {cell['config']}, traffic {cell['traffic']} "
        f"({traffic['kind']}), {cell['chips']} chip(s), seed {args.seed}, "
        f"{args.seconds:g}s, trace {args.trace}")
    try:
        run = DRIVERS[traffic["kind"]](cell, config_path, traffic_path, args,
                                       out_dir, rehearse)
    except ChildFailed as e:
        log(f"FAILED: {e}")
        return 1
    device = run["device"]
    if device["platform"] != "tpu" and not rehearse:
        log(f"FAILED: ran on {device['platform']}, not a TPU")
        return 1
    if device["count"] != cell["chips"]:
        log(f"FAILED: {device['count']} device(s), the cell asks for {cell['chips']}")
        return 1
    trace = None
    if args.trace:
        spec = {"buckets": {}, "span_prefix": "bench:",
                "keep_events": os.path.join(out_dir, "events.json.gz")}
        for _, r in readers:
            spec["buckets"].update(r.trace_buckets())
        spec_path = os.path.join(out_dir, "trace_spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        reduced = os.path.join(out_dir, "trace_reduced.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "lib", "xplane.py"),
             run["trace_dir"], spec_path, reduced], cwd=ROOT, env=env,
            stdout=sys.stderr, timeout=600).returncode
        if rc != 0:
            log(f"FAILED: the trace reduction exited {rc}")
            return 1
        trace = load_json(reduced)
        if not trace["devices"] and not rehearse:
            log("FAILED: the traced window holds no device operation")
            return 1
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
    ctx = {"values": run["values"], "trace": trace, "cell": cell,
           "config": load_json(config_path), "traffic": traffic, "device": device,
           "peaks": None if rehearse else M.load_peaks(device["kind"])}
    metrics = {}
    for m, reader in readers:
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log("detail: " + json.dumps(run["detail"]))
    log("values: " + json.dumps({k: v for k, v in run["values"].items()
                                 if isinstance(v, (int, float, type(None)))}))
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if rehearse:
        result["rehearsal"] = rehearse  # not a chip run: no device number here counts
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
