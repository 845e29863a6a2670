#!/usr/bin/env python3
"""The benchmark's training worker, started as a user starts a job:

    python -m kungfu_tpu.run -np 1 python benchmark/lib/train_worker.py ...

It builds the configuration's `TransformerLM` under `MeshTrainer` on every
chip it finds, warms the one step program, then runs steps back to back for
`--seconds` on token batches made from `--seed` and already resident on the
device.  With `--trace 1` a few steps in the steady part are captured by the
JAX profiler, under the benchmark's own `TraceAnnotation`s.  After the window
it compares the system's logits with the configuration's plain reference.
Everything it measured goes to `--out` as one JSON object; the parent
(`run.py`) turns that into metrics.  No TPU is a failure, never a CPU run
(only `KFT_BENCH_REHEARSE=cpu`, which the tests set, lets it run elsewhere,
and the result then says so).
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: the system's logits against the float32 reference, relative to the
#: reference's RMS and largest value.  On the chip bf16 compute over
#: float32 params lands at about a third of these (PERF.md, Findings);
#: an 8-bit float (3 mantissa bits, 16 times coarser than bf16's 8) cannot
#: meet them, a skipped layer or a wrong rotation misses by orders.
LOGIT_TOL_RMS = 0.03
LOGIT_TOL_MAX = 0.08


def make_optimizer(deployment: dict):
    """The configuration's optimizer; `lm_adamw` is what the repo's LM
    example trains with (float32 moments: 16 bytes a parameter with the
    parameter and its gradient)."""
    from kungfu_tpu.optimizers import lm_adamw

    if deployment.get("optimizer", "lm_adamw") != "lm_adamw":
        raise SystemExit(f"unknown optimizer {deployment['optimizer']!r}")
    return lm_adamw(float(deployment.get("lr", 3e-4)), warmup_steps=20,
                    total_steps=10_000)


def make_batches(seed: int, n: int, batch: int, seq_len: int, vocab: int):
    """`n` token batches [batch, seq_len] from the seed: ramps from a random
    start, so there is structure to learn and the loss falls."""
    import numpy as np

    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        start = rng.randint(0, vocab // 2, size=(batch, 1))
        out.append(((start + np.arange(seq_len)[None, :]) % vocab).astype(np.int32))
    return out


def check_against_reference(config, cfg, trainer, state, seed, n_seq, n_pos):
    """System logits on a seeded sample against the plain reference, on the
    same parameters, outside the window."""
    import numpy as np
    import jax

    from benchmark.lib.configs import load_reference

    ref = load_reference(config)
    rng = np.random.RandomState(seed + 1)
    sample = rng.randint(0, cfg.vocab_size, size=(n_seq, n_pos)).astype(np.int32)
    placed = trainer.shard_batch(sample)
    model = trainer.model
    with trainer.mesh:
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            state.params, placed)
        want = jax.jit(lambda p, t: ref.forward(p, t, config))(
            state.params, placed)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = got - want
    rms_ref = float(np.sqrt(np.mean(want ** 2)))
    out = {
        "sample": [n_seq, n_pos],
        "ref_rms": rms_ref,
        "ref_max_abs": float(np.abs(want).max()),
        "err_rms_rel": float(np.sqrt(np.mean(err ** 2)) / rms_ref),
        "err_max_rel": float(np.abs(err).max() / np.abs(want).max()),
        "tol_rms_rel": LOGIT_TOL_RMS, "tol_max_rel": LOGIT_TOL_MAX,
        "argmax_agree": float(np.mean(got.argmax(-1) == want.argmax(-1))),
    }
    out["ok"] = bool(np.isfinite(got).all()
                     and out["err_rms_rel"] <= LOGIT_TOL_RMS
                     and out["err_max_rel"] <= LOGIT_TOL_MAX)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--t0", type=float, default=0.0,
                    help="epoch seconds at which the benchmark process started")
    args = ap.parse_args(argv)
    t_worker = time.time()
    t0 = args.t0 or t_worker

    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    cache_dir = enable_compile_cache()

    import numpy as np
    import jax

    from benchmark.lib.configs import load_json, transformer_config
    from kungfu_tpu.models.transformer import TransformerLM, _attention_kind, lm_loss
    from kungfu_tpu.monitor import programs
    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    programs.maybe_install()
    config, traffic = load_json(args.config), load_json(args.traffic)
    dep = config["deployment"]
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"DEVICE: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}", flush=True)
    rehearse = os.environ.get("KFT_BENCH_REHEARSE", "") == device["platform"] != "tpu"
    if device["platform"] != "tpu" and not rehearse:
        raise SystemExit(f"the benchmark needs a TPU; JAX found {device['platform']}")
    if len(devs) != dep["chips"]:
        raise SystemExit(f"the cell asks for {dep['chips']} chip(s); "
                         f"JAX found {len(devs)}")
    t_device = time.time()

    mesh = make_mesh(**dep["mesh"])
    cfg = transformer_config(config, mesh=mesh)
    seq_len = int(traffic["seq_len"])
    batch = int(dep["sequences_per_chip"]) * len(devs)
    if seq_len > cfg.max_len:
        raise SystemExit(f"traffic seq_len {seq_len} > max_len {cfg.max_len}")
    model = TransformerLM(cfg)
    trainer = MeshTrainer(
        model, lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
        make_optimizer(dep), mesh=mesh)
    host_batches = make_batches(args.seed, int(traffic.get("pool_batches", 8)),
                                batch, seq_len, cfg.vocab_size)
    state = trainer.init(jax.random.PRNGKey(args.seed), host_batches[0])
    jax.block_until_ready(state.params)
    t_init = time.time()
    pool = [trainer.shard_batch(b) for b in host_batches]
    n_params = sum(int(x.size) for x in jax.tree.leaves(state.params))
    param_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(state.params)})

    # warm-up: the one step program.  Its lowered text is read for the
    # Mosaic calls; then two steps, the first of which compiles (or loads
    # the program from the persistent cache)
    mosaic_calls = trainer.lower_step(state, pool[0]).as_text().count(
        "tpu_custom_call")
    t_lowered = time.time()
    losses = []
    for b in pool[:2]:
        state, m = trainer.train_step(state, b)
        losses.append(float(jax.block_until_ready(m["loss"])))
    first_loss = losses[0]
    watch_open = programs.compile_watch_state()
    t_open_epoch = time.time()
    setup_s = t_open_epoch - t0

    # the window: steps back to back, one step dispatched ahead of the one
    # whose loss is being fetched, so the host hides behind the device
    from jax.profiler import TraceAnnotation

    trace_steps = int(traffic.get("trace_steps", 4)) if args.trace else 0
    trace_from = int(traffic.get("trace_after_steps", 3)) if trace_steps else -1
    trace_to = trace_from + trace_steps
    pending = collections.deque()
    host_ms, done_t, win_losses = [], [], []

    def fetch():
        with TraceAnnotation("bench:loss_fetch"):
            win_losses.append(float(pending.popleft()))
        done_t.append(time.perf_counter())

    def drain():  # a clean edge: nothing in flight
        while pending:
            fetch()

    i = 0
    t_open = time.perf_counter()
    while True:
        if i == trace_from:
            drain()
            os.makedirs(args.trace_dir, exist_ok=True)
            jax.profiler.start_trace(args.trace_dir)
        h0 = time.perf_counter()
        with TraceAnnotation("bench:shard_batch"):
            b = trainer.shard_batch(pool[i % len(pool)])
        with TraceAnnotation("bench:step_dispatch"):
            state, m = trainer.train_step(state, b)
        host_ms.append((time.perf_counter() - h0) * 1e3)
        pending.append(m["loss"])
        i += 1
        if len(pending) > 1:
            fetch()
        if i == trace_to:
            drain()
            jax.profiler.stop_trace()
        if i >= trace_to and done_t and done_t[-1] - t_open >= args.seconds:
            break
    drain()  # the window closes on the last step's loss
    t_close = time.perf_counter()
    window_s = t_close - t_open
    watch_close = programs.compile_watch_state()
    mem = [d.memory_stats() or {} for d in devs]
    peak = max((s.get("peak_bytes_in_use", 0) for s in mem), default=0)

    # step times between completions, without the steps around the capture
    step_ms = np.diff(np.asarray([t_open] + done_t)) * 1e3
    if trace_steps:
        keep = np.ones(len(step_ms), bool)
        keep[max(0, trace_from - 1):trace_to + 2] = False
        step_ms = step_ms[keep] if keep.any() else step_ms
    all_losses = losses + win_losses
    finite = bool(np.isfinite(all_losses).all())
    reference = check_against_reference(
        config, cfg, trainer, state, args.seed,
        n_seq=max(2, len(devs)), n_pos=int(traffic.get("check_positions", 256)))
    result = {
        "device": dict(device, memory_peak_bytes=int(peak)),
        "rehearsal": bool(rehearse),
        "steps": i, "batch": batch, "seq_len": seq_len, "chips": len(devs),
        "tokens": i * batch * seq_len, "window_s": window_s,
        "setup_s": setup_s,
        "setup_parts_s": {
            "launch_to_worker": t_worker - t0, "worker_to_device": t_device - t_worker,
            "init": t_init - t_device, "lower": t_lowered - t_init,
            "first_steps": t_open_epoch - t_lowered},
        "step_ms_p50": float(np.median(step_ms)),
        "host_step_ms_p50": float(np.median(host_ms)),
        "first_loss": first_loss, "last_loss": all_losses[-1],
        "loss_finite": finite, "loss_fell": bool(all_losses[-1] < first_loss),
        "nonfinite_steps": int(np.sum(~np.isfinite(win_losses))),
        "mosaic_calls": mosaic_calls,
        "attention": _attention_kind(cfg),
        "compute_dtype": np.dtype(cfg.dtype).name, "param_dtypes": param_dtypes,
        "params": n_params, "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "compiles_setup": watch_open["compiles"],
        "compile_s": watch_open["compile_ms"] / 1e3,
        "cache_hits": watch_open["cache_hits"],
        "compiles_in_window": watch_close["compiles"] - watch_open["compiles"],
        "compile_cache_dir": cache_dir,
        "peak_hbm_bytes": int(peak),
        "traced": {"steps": trace_steps} if trace_steps else None,
        "reference": reference,
    }
    result["correct"] = bool(
        finite and result["loss_fell"] and reference["ok"]
        and result["compiles_in_window"] == 0
        and (mosaic_calls > 0 or rehearse))
    with open(args.out + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(args.out + ".tmp", args.out)
    print("RESULT: " + json.dumps({k: v for k, v in result.items()
                                   if k not in ("setup_parts_s",)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
