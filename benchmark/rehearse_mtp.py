#!/usr/bin/env python3
"""One forced drafting run of a serving cell's configuration, outside the benchmark.

    python benchmark/rehearse_mtp.py --workload <cell> --seed <n> \
        --seconds 30 --out chiprun_out/mtp.json

The cell's file keeps the multi-token-prediction module off the timed path
(`program.mtp_layers` 0): with seeded weights a draft matches the target's
choice about once in a vocabulary, and the engine's own policy turns such a
drafter off after four rounds, so a cell that timed it would guard a number
no user sees.  What can be had without the released weights is had here:
the engine is built from the cell's file with `mtp_layers` 1 and the
target-resident drafter (`kungfu_tpu/serving/spec.py` `MTPDrafter`) held on
(`disable_below` 0), the cell's arrivals and lengths
(`benchmark/lib/traffic.py`) are replayed against it twice in one process,
with the drafter and without, and the checker child
(`benchmark/lib/serve_check.py`, the comparison a cell's `correct` rests on)
reads what the drafting pass served.  Out come `correct`, the acceptance
counters, the mean time of a verify-2 round with its draft beside the plain
step's, and from those the acceptance rate above which drafting pays here:
a round commits 1 + a tokens in `round_ms`, a plain step 1 in `plain_ms`,
so they deliver the same tokens a millisecond at a = round_ms / plain_ms - 1.

This parent never imports JAX (one process owns a chip at a time): the
engine runs in one child, the checker in the next, with the chip free
between.  `KFT_BENCH_REHEARSE=cpu` with `--bench-root <a copy with tiny
sizes>` runs it on the CPU (benchmark/tests/test_pangu_metrics.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def replay(eng, schedule, tag: str) -> dict:
    """Offer `schedule` [(due seconds, request)] to the engine at its due
    times and step it until everything is answered: what it served and how
    long its slot-cache steps took, by kind."""
    from kungfu_tpu.serving.request import Request

    steps = {"spec": [], "plain": []}
    busy = {"spec": 0, "plain": 0}
    spec_step, decode_step = eng._spec_decode_step, eng._decode_step
    was_round = [False]

    def spec_wrapped():
        was_round[0] = True
        return spec_step()

    def decode_wrapped():
        # `_decode_step` hands over to the round when the drafter is ready
        was_round[0] = False
        n, t = eng.slot_mgr.active_count, time.monotonic()
        out = decode_step()
        kind = "spec" if was_round[0] else "plain"
        steps[kind].append(time.monotonic() - t)
        busy[kind] += n
        return out

    eng._spec_decode_step, eng._decode_step = spec_wrapped, decode_wrapped
    pending, t_open, at = [], time.monotonic(), 0
    try:
        while at < len(schedule) or eng.queue.depth() or eng.slot_mgr.active_count:
            now = time.monotonic() - t_open
            while at < len(schedule) and schedule[at][0] <= now:
                req = schedule[at][1]
                pending.append((req, eng.submit(Request(
                    req_id=f"{tag}-{at}", prompt=tuple(req["prompt"]),
                    max_new_tokens=int(req["max_new_tokens"])))))
                at += 1
            if eng.queue.depth() or eng.slot_mgr.active_count:
                eng.step()
            else:
                time.sleep(0.001)
    finally:
        del eng._spec_decode_step, eng._decode_step  # the class's own again
    served = []
    for k, (req, p) in enumerate(pending):
        toks = list(p.result.tokens)
        n = len(req["prompt"])
        served.append({"id": f"{tag}-{k}", "prompt_len": n, "prompt": toks[:n],
                       "new": toks[n:], "ok": p.result.status == "ok"
                       and len(toks) == n + int(req["max_new_tokens"])})
    out = {"seconds": time.monotonic() - t_open, "served": served}
    for kind, ts in steps.items():
        out[kind + "_steps"] = len(ts)
        out[kind + "_step_ms_mean"] = 1e3 * sum(ts) / len(ts) if ts else None
        out[kind + "_busy_slots_mean"] = busy[kind] / len(ts) if ts else None
    return out


def engine_child(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()
    import jax

    from benchmark.lib import traffic as T
    from benchmark.lib.configs import load_json, transformer_config
    from kungfu_tpu.models.transformer import resident_params
    from kungfu_tpu.serving.engine import ServingEngine
    from kungfu_tpu.serving.spec import MTPDrafter
    from kungfu_tpu.serving.worker import seed_params

    config, traffic = load_json(job["config"]), load_json(job["traffic"])
    cfg = transformer_config(config, mtp_layers=1)
    slots = int(config["deployment"]["slots"])
    t0 = time.monotonic()
    # as the worker does: the float32 checkpoint form leaf by leaf, then the
    # resident form, each float32 leaf deleted as its narrower copy arrives
    params = jax.block_until_ready(resident_params(
        cfg, seed_params(cfg, job["seed"]), donate=True))
    drafter = MTPDrafter(cfg, params, slots=slots, disable_below=0.0)
    eng = ServingEngine(cfg, params, slots=slots, queue_capacity=4096,
                        spec=drafter)
    boot_s = time.monotonic() - t0
    vocab, max_len = config["vocab_size"], config["max_position_embeddings"]
    schedule = T.open_schedule(traffic, vocab, max_len, job["seed"],
                               job["seconds"])
    # every shape once, outside the clock: each prefill bucket the mix
    # reaches, the round's programs and the plain step's
    warm = [(0.0, {"prompt": [1] * n, "max_new_tokens": 4})
            for n in T.warmup_prompt_lens(traffic)]
    replay(eng, warm, "warm")
    eng.spec = None
    replay(eng, warm[:1], "warm-plain")
    eng.spec = drafter
    before = drafter.stats()
    with_draft = replay(eng, schedule, "d")
    after = drafter.stats()
    eng.spec = None
    plain = replay(eng, schedule, "p")
    dev = jax.devices()[0]
    same = sum(a["new"] == b["new"] for a, b in
               zip(with_draft["served"], plain["served"]))
    rounds = after["rounds"] - before["rounds"]
    accepted = after["accepted_tokens"] - before["accepted_tokens"]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "boot_s": boot_s, "requests": len(schedule),
        "failed": sum(not r["ok"] for r in with_draft["served"]),
        "param_bytes": eng.param_bytes,
        "memory": {k: v for k, v in (dev.memory_stats() or {}).items()
                   if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
        "spec": {"k": after["k"], "rounds": rounds, "accepted_tokens": accepted,
                 "committed_tokens": after["committed_tokens"]
                 - before["committed_tokens"],
                 "accept_rate": accepted / rounds if rounds else None,
                 "disabled_slots": after["disabled_slots"],
                 "draft_rows": drafter.attn_rows()["draft_written"]},
        "drafting": {k: v for k, v in with_draft.items() if k != "served"},
        "plain": {k: v for k, v in plain.items() if k != "served"},
        "requests_with_the_plain_passes_tokens": same,
    }
    round_ms = with_draft["spec_step_ms_mean"]
    plain_ms = plain["plain_step_ms_mean"]
    out["round_ms"], out["plain_ms"] = round_ms, plain_ms
    out["break_even_acceptance"] = (round_ms / plain_ms - 1.0
                                    if round_ms and plain_ms else None)
    # the four shortest served requests, as a cell's check takes them
    served = sorted((r for r in with_draft["served"] if r["ok"]),
                    key=lambda r: r["prompt_len"] + len(r["new"]))[:4]
    out["check_job"] = {
        "config": job["config"], "seed": job["seed"], "out": job["check_out"],
        "served": [{"id": r["id"], "prompt_len": r["prompt_len"],
                    "tokens": r["prompt"] + r["new"]} for r in served]}
    with open(job["out"], "w") as f:
        json.dump(out, f)
    print("MTP_ENGINE: " + json.dumps({k: v for k, v in out.items()
                                       if k != "check_job"}), flush=True)
    return 0


def main(argv=None) -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--engine-child":
        return engine_child(sys.argv[2])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--bench-root", default=ROOT)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    from benchmark.lib.manifest import Manifest
    from benchmark.lib.procs import Child

    rehearse = os.environ.get("KFT_BENCH_REHEARSE", "")
    man = Manifest(args.bench_root)
    cell = man.cell(args.workload)
    out_dir = os.path.join(ROOT, ".bench_out", "mtp-" + cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    job = {"config": man.config_file(cell), "traffic": man.traffic_file(cell),
           "seed": args.seed, "seconds": args.seconds,
           "out": os.path.join(out_dir, "engine.json"),
           "check_out": os.path.join(out_dir, "check.json")}
    job_path = os.path.join(out_dir, "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = rehearse
    for path in (job["out"], job["check_out"]):
        if os.path.exists(path):
            os.remove(path)
    rc = Child("mtp-engine", [sys.executable, os.path.abspath(__file__),
                              "--engine-child", job_path],
               cwd=ROOT, env=env).wait(3000)
    if rc != 0 or not os.path.exists(job["out"]):
        print(f"FAILED: the engine child exited {rc}", file=sys.stderr)
        return 1
    with open(job["out"]) as f:
        result = json.load(f)
    if result["device"]["platform"] != "tpu" and not rehearse:
        print("FAILED: ran on " + result["device"]["platform"], file=sys.stderr)
        return 1
    check_job = os.path.join(out_dir, "check_job.json")
    with open(check_job, "w") as f:
        json.dump(result.pop("check_job"), f)
    rc = Child("check", [sys.executable, os.path.join(
        ROOT, "benchmark", "lib", "serve_check.py"), check_job],
        cwd=ROOT, env=env).wait(900)
    check = {"ok": False, "error": f"the checker exited {rc}"}
    if rc == 0 and os.path.exists(job["check_out"]):
        with open(job["check_out"]) as f:
            check = json.load(f)
    result["check"] = {k: v for k, v in check.items() if k != "requests"}
    result["correct"] = bool(check.get("ok") and result["failed"] == 0)
    if rehearse:
        result["rehearsal"] = rehearse
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
