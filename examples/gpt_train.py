"""End-to-end LLM pretraining: a modern GPT-style decoder (GQA, rotary
embeddings, SwiGLU) trained with MeshTrainer over a dp x sp mesh, batches
from the chunked file dataset or synthetic tokens, async orbax
checkpointing with kill-and-resume.

This is the "switch from the reference" showcase: every piece — the
launcher-compatible env contract, distributed optimizer, sequence
parallelism, flash kernels (on TPU), the C++ file loader, checkpoints —
is the framework's own. The reference (model-agnostic DP) has no LM
example; reference analog for the training-loop shape is
examples/tf2_mnist_gradient_tape.py.

Run (8-virtual-device CPU mesh):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/gpt_train.py --dp 4 --sp 2 --steps 30

or single real TPU chip:  python examples/gpt_train.py --steps 50
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from kungfu_tpu.env import apply_platform_override, enable_compile_cache

apply_platform_override()
enable_compile_cache()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=0, help="0 = all devices")
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8, help="global batch (sequences)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--n-kv-heads", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--ckpt-dir", default="", help="enable checkpointing")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--kv-int8", action="store_true",
                    help="decode with an int8-quantized KV cache (half the "
                         "cache-read bytes / double the context per chip)")
    ap.add_argument("--generate", type=int, default=0, metavar="N",
                    help="after training, greedily generate N tokens from a "
                         "training-distribution prompt (KV-cache decode)")
    ap.add_argument("--data", default="synthetic", choices=["synthetic", "files"],
                    help="files = stream token chunks via the C++ loader")
    ap.add_argument("--data-dir", default="/tmp/kft_gpt_tokens",
                    help="token-chunk dir for --data files (built if missing)")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import (
        TransformerConfig, TransformerLM, _attention_kind, lm_loss,
    )
    from kungfu_tpu.monitor import programs
    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    programs.maybe_install()  # counts every XLA compile in this process
    n_dev = len(jax.devices())
    dev = jax.devices()[0]
    print(f"DEVICE: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={n_dev}", flush=True)
    dp = args.dp or max(1, n_dev // args.sp)
    mesh = make_mesh(dp=dp, sp=args.sp) if args.sp > 1 else make_mesh(dp=dp)
    cfg = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=args.n_heads, n_kv_heads=args.n_kv_heads, rope=True,
        ffn="swiglu", tie_embeddings=True, d_ff=4 * args.d_model,
        max_len=args.seq_len,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
        attention="ring" if args.sp > 1 else "auto", mesh=mesh,
    )
    model = TransformerLM(cfg)
    from kungfu_tpu.optimizers import lm_adamw

    trainer = MeshTrainer(
        model,
        lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
        lm_adamw(3e-4, warmup_steps=max(2, args.steps // 10),
                 total_steps=max(args.steps, 10)),
        mesh=mesh,
    )

    rng = np.random.RandomState(0)

    def synthetic_batches():
        # synthetic token stream with learnable bigram structure so the
        # loss visibly falls
        while True:
            start = rng.randint(0, args.vocab // 2, size=(args.batch, 1))
            ramp = (start + np.arange(args.seq_len)[None, :]) % args.vocab
            yield ramp.astype(np.int32)

    def file_batches():
        # token sequences as a chunked idx dir streamed by the C++ loader
        # (the idx machinery is shape-generic: [N, seq_len] int32 works the
        # same as [N, H, W, C] images; labels carry the sample index)
        from kungfu_tpu import data_files as df

        if not os.path.isdir(args.data_dir):
            n = 4096
            start = rng.randint(0, args.vocab // 2, size=(n, 1))
            toks = ((start + np.arange(args.seq_len)) % args.vocab).astype(
                np.int32
            )
            df.write_chunks(args.data_dir, toks,
                            np.arange(n, dtype=np.int32),
                            samples_per_chunk=512)
        ds = df.FileDataset(args.data_dir)
        if tuple(ds.sample_shape) != (args.seq_len,):
            raise SystemExit(
                f"--data-dir {args.data_dir} holds seq_len "
                f"{ds.sample_shape} chunks but --seq-len is {args.seq_len}; "
                "delete the dir or point at a matching one"
            )
        # bounded sample: a full scan of a multi-GB memmapped corpus
        # would block startup for minutes
        vmax = max(
            int(c[: max(1, 65536 // max(1, c.shape[-1]))].max())
            for c in ds.images
        )
        if vmax >= args.vocab:
            raise SystemExit(
                f"--data-dir tokens reach id {vmax} but --vocab is "
                f"{args.vocab}; delete the dir or raise --vocab"
            )
        loader = df.FileBatchLoader(ds, batch_size=args.batch, threads=2,
                                    queue_cap=4)
        try:
            while True:
                toks, _ = next(loader)
                yield toks
        finally:
            loader.close()

    it = file_batches() if args.data == "files" else synthetic_batches()
    t_setup = time.perf_counter()
    state = trainer.init(jax.random.PRNGKey(0), next(it))

    manager = None
    start_step = 0
    if args.ckpt_dir:
        from kungfu_tpu.checkpoint import CheckpointManager

        manager = CheckpointManager(args.ckpt_dir)
        if manager.latest_step() is not None:
            # checkpoints hold plain pytrees; rebuild the TrainState around
            # the restored leaves (placed onto the current mesh via `like`)
            like = {"params": state.params, "opt_state": state.opt_state}
            tree, meta = manager.restore(like=like)
            # re-place every leaf onto the live state's sharding (restore
            # can drop the mesh placement of scalar leaves)
            tree = jax.tree.map(
                lambda x, ref: jax.device_put(x, ref.sharding), tree, like
            )
            start_step = int(meta.get("step", 0))
            state = type(state)(
                params=tree["params"], opt_state=tree["opt_state"],
                step=start_step,
            )
            print(f"# resumed from step {start_step}")

    def maybe_generate():
        if args.generate <= 0:
            if args.kv_int8:
                print("# --kv-int8 does nothing without --generate N "
                      "(it configures the decode cache)", flush=True)
            return
        from kungfu_tpu.models.transformer import generate

        prompt = jnp.asarray(next(it)[:1, :8])
        # KV cache holds max_len positions; clamp instead of crashing
        n = min(args.generate, cfg.max_len - int(prompt.shape[1]))
        if n <= 0:
            print(f"# --generate skipped: no cache room past the prompt "
                  f"(max_len {cfg.max_len})")
            return
        if n < args.generate:
            print(f"# --generate clamped to {n} (max_len {cfg.max_len})")
        # decode runs single-device: gather one replica's params off the
        # mesh (multi-controller-safe)
        host_params = jax.tree.map(
            lambda x: jax.device_put(np.asarray(x)),
            trainer.eval_params(state),
        )
        gcfg = cfg
        if args.kv_int8:
            import dataclasses

            gcfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        out = np.asarray(generate(gcfg, host_params, prompt, n))
        print(f"# prompt    {np.asarray(prompt)[0].tolist()}")
        print(f"# generated {out[0, prompt.shape[1]:].tolist()}")

    if start_step >= args.steps:
        print(f"# checkpoint already at step {start_step} >= --steps "
              f"{args.steps}; nothing to train")
        maybe_generate()  # sampling from a finished run is still useful
        return 0
    # set-up: init, the compile of the step, and the first step it runs.
    # The compiled program's text is read here too: with the persistent
    # cache on, the step's own first call then finds it already compiled.
    batch = trainer.shard_batch(next(it))
    hits = programs.compile_watch_state()["cache_hits"]
    mosaic_calls = trainer.lower_step(state, batch).compile().as_text().count(
        "tpu_custom_call"
    )
    step_cache_hits = programs.compile_watch_state()["cache_hits"] - hits
    state, metrics = trainer.train_step(state, batch)
    first_loss = loss = float(jax.block_until_ready(metrics["loss"]))
    setup_s = time.perf_counter() - t_setup
    compiles_warm = programs.compile_watch_state()["compiles"]
    print(f"# step {start_step + 1} loss {loss:.4f}", flush=True)
    print(f"# set-up (init, compile, first step) {setup_s:.1f}s", flush=True)
    t0 = time.perf_counter()
    for i in range(start_step + 1, args.steps):
        state, metrics = trainer.train_step(state, trainer.shard_batch(next(it)))
        if (i + 1) % 10 == 0 or i + 1 == args.steps:
            loss = float(np.asarray(metrics["loss"]))
            print(f"# step {i + 1} loss {loss:.4f}", flush=True)
        if manager is not None and (i + 1) % args.ckpt_every == 0:
            manager.save(
                i + 1,
                {"params": state.params, "opt_state": state.opt_state},
                meta={"step": i + 1},
            )
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    recompiles = programs.compile_watch_state()["compiles"] - compiles_warm
    if manager is not None:
        manager.wait()
    steady = args.steps - start_step - 1
    step_ms = dt / steady * 1e3 if steady else float("nan")
    tok_s = steady * args.batch * args.seq_len / dt if steady else float("nan")
    maybe_generate()
    param_dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(state.params)})
    print(
        f"RESULT: example=gpt_train loss={loss:.4f} first_loss={first_loss:.4f} "
        f"steps={args.steps} mesh={dict(mesh.shape)} "
        f"attention={_attention_kind(cfg)} dtype={jnp.dtype(cfg.dtype).name} "
        f"param_dtypes={'+'.join(param_dtypes)} mosaic_calls={mosaic_calls} "
        f"setup_s={setup_s:.1f} step_ms={step_ms:.1f} "
        f"step_cache_hits={step_cache_hits} "
        f"compiles_after_warmup={recompiles} tokens_per_sec={tok_s:.0f}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
