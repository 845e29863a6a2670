"""Fine-tune a HF Llama checkpoint with a distributed optimizer, then decode.

The ecosystem on-ramp in one file: `models/hf.py` maps a transformers
LlamaForCausalLM onto the flagship TransformerLM (bit-level logits parity),
the loaded tree drops straight into DataParallelTrainer with any
`kungfu_tpu.optimizers` transform, and the tuned weights decode through the
KV cache (optionally int8).

By default this builds a RANDOM tiny Llama locally (no network, CI-safe);
point --hf-dir at a real downloaded checkpoint directory to use one.

Run (8-virtual-device CPU mesh):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_finetune.py --steps 20 --generate 12

Reference analog: none (the reference is model-agnostic DP with no LM or
checkpoint-interop story); training-loop shape follows
examples/tf2_mnist_gradient_tape.py.
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
    ap.add_argument("--hf-dir", default="",
                    help="directory of a saved HF Llama checkpoint; empty = "
                         "build a random tiny model locally")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--generate", type=int, default=0, metavar="N")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--save-dir", default="",
                    help="write the tuned weights back in HF format "
                         "(save_into + save_pretrained)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kungfu_tpu.models.hf import load_llama
    from kungfu_tpu.models.transformer import TransformerLM, generate, lm_loss
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.train import DataParallelTrainer

    if args.hf_dir:
        from transformers import LlamaForCausalLM

        hf = LlamaForCausalLM.from_pretrained(args.hf_dir)
    else:
        import torch
        from transformers import LlamaConfig, LlamaForCausalLM

        torch.manual_seed(0)
        hf = LlamaForCausalLM(LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
        ))
    cfg, params = load_llama(hf, dtype=jnp.float32)
    hf_cfg = hf.config
    del hf  # torch weights copied; a fresh model is rebuilt for --save-dir
    model = TransformerLM(cfg)
    n_params = sum(np.asarray(x).size for x in jax.tree.leaves(params))
    print(f"# loaded llama: {n_params / 1e6:.2f}M params, "
          f"d_model={cfg.d_model} layers={cfg.n_layers} "
          f"kv_heads={cfg.kv_heads}", flush=True)

    def loss_fn(p, batch):
        return lm_loss(model.apply({"params": p}, batch), batch)

    trainer = DataParallelTrainer(loss_fn, synchronous_sgd(optax.adamw(args.lr)))
    state = trainer.init(params)
    rng = np.random.RandomState(0)
    # toy corpus: a repeating ramp the model can memorize quickly
    seq = (np.arange(args.batch * args.seq_len) % 17).astype(np.int32)
    tokens = seq.reshape(args.batch, args.seq_len)
    batch = trainer.shard_batch(tokens)

    t0 = time.perf_counter()
    loss = float("nan")
    for i in range(args.steps):
        state, m = trainer.train_step(state, batch)
        if (i + 1) % 10 == 0 or i + 1 == args.steps:
            loss = float(np.asarray(m["loss"]))
            print(f"# step {i + 1} loss {loss:.4f}", flush=True)
    dt = time.perf_counter() - t0
    tps = args.steps * tokens.size / dt

    tuned = None
    if args.generate > 0 or args.save_dir:  # one device->host gather
        tuned = jax.tree.map(np.asarray, trainer.eval_params(state))

    if args.generate > 0:
        import dataclasses

        gcfg = dataclasses.replace(
            cfg, kv_cache_dtype="int8" if args.kv_int8 else cfg.kv_cache_dtype
        )
        out = np.asarray(
            generate(gcfg, tuned, jnp.asarray(tokens[:1, :8]), args.generate)
        )
        print(f"# generated {out[0, 8:].tolist()}", flush=True)

    if args.save_dir:
        from transformers import LlamaForCausalLM

        from kungfu_tpu.models.hf import save_into

        target = LlamaForCausalLM(hf_cfg)  # fresh shell, built only now
        save_into(target, tuned)
        target.save_pretrained(args.save_dir)
        print(f"# tuned weights saved in HF format at {args.save_dir}",
              flush=True)

    print(f"RESULT: example=llama_finetune loss={loss:.4f} "
          f"steps={args.steps} tokens_per_sec={tps:.0f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
