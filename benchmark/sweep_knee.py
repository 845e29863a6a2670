#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip, by a sweep of rates.

    python benchmark/sweep_knee.py --workload <open-loop cell> \
        --rates 3,4,5,6,7 --seconds 30 --out chiprun_out/knee.json

Boots the cell's fleet once, warms it as a run does, then offers the cell's
mix at each rate for `--seconds`, drains, and records for each rate the share
of requests answered inside the window, the backlog at the window's end and
the latencies.  The knee is the highest rate the system sustains: nearly all
requests answered within the window's own horizon and no backlog that grows.
The result is kept beside the traffic mix (`<mix>.knee.json`) with the
chosen rate, so a later benchmark issue can tell when the rate has been
overtaken.  Benchmark runs never search: they offer the fixed rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import traffic as T  # noqa: E402
from benchmark.lib.configs import load_json  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.serve_driver import Fleet, warm_up  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma list, requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--bench-root", default=ROOT)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    rehearse = os.environ.get("KFT_BENCH_REHEARSE", "")
    man = Manifest(args.bench_root)
    cell = man.cell(args.workload)
    config = load_json(man.config_file(cell))
    traffic = load_json(man.traffic_file(cell))
    vocab, max_len = config["vocab_size"], config["max_position_embeddings"]
    out_dir = os.path.join(ROOT, ".bench_out", "sweep-" + cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    fleet = Fleet(config, args.seed, out_dir, rehearse)
    rows = []
    try:
        fleet.wait_ready(1500)
        warm_up(fleet, traffic, vocab, args.seed)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            schedule = T.open_schedule(traffic, vocab, max_len, args.seed + k,
                                       args.seconds, rate)
            records, _ = T.run_open_loop(
                fleet.url, schedule, args.seconds,
                float(traffic.get("drain_s", 30.0)), f"k{k}")
            s = T.summarize(records, args.seconds)
            row = {"rate_per_s": rate, "seed": args.seed + k,
                   "offered_out_tokens_per_s":
                       sum(r["asked"] for r in records) / args.seconds,
                   "share_answered_in_window":
                       s["answered_in_window"] / max(1, s["attempted"])}
            row.update({k2: s[k2] for k2 in (
                "attempted", "failed", "answered_in_window",
                "backlog_at_window_end", "serve_out_tokens_per_s",
                "ttft_p50_ms", "ttft_p90_ms", "tpot_p50_ms", "tpot_p90_ms",
                "generator_late_ms_p50")})
            rows.append(row)
            print("KNEE_ROW: " + json.dumps(row), flush=True)
            time.sleep(2.0)  # the fleet is drained: run_open_loop waited
        device = fleet.device()
    finally:
        fleet.stop()
    doc = {"workload": cell["name"], "traffic": cell["traffic"],
           "seconds": args.seconds, "device": device,
           "rehearsal": bool(rehearse), "rows": rows}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
