"""BERT-style masked-LM pretraining: a bidirectional encoder trained with
the MLM objective, corruption happening INSIDE the compiled step via
MeshTrainer's per-step rng threading (4-arg loss), dropout on.

Reference analog: the reference benchmarks BERT throughput only
(tests/go/fakemodel/bert.go grad sizes); this trains the real objective.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/bert_train.py --dp 8 --steps 60
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from kungfu_tpu.env import apply_platform_override, enable_compile_cache

apply_platform_override()
enable_compile_cache()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=0, help="0 = all devices")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=512, help="last id = [MASK]")
    ap.add_argument("--dropout", type=float, default=0.1)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import (
        TransformerConfig, TransformerLM, mlm_corrupt, mlm_loss,
    )
    from kungfu_tpu.optimizers import lm_adamw
    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    mask_id = args.vocab - 1
    mesh = make_mesh(dp=args.dp or -1)
    cfg = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=args.n_heads, d_ff=4 * args.d_model, max_len=args.seq_len,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
        causal=False, rope=True, dropout=args.dropout, tie_embeddings=True,
        attention="auto", mesh=mesh,
    )
    model = TransformerLM(cfg)

    def loss_fn(m, p, tokens, rng):
        r_corrupt, r_drop = jax.random.split(rng)
        corrupted, sel = mlm_corrupt(
            r_corrupt, tokens, args.vocab - 1, mask_id
        )
        logits = m.apply(
            {"params": p}, corrupted, train=True, rngs={"dropout": r_drop}
        )
        return mlm_loss(logits, tokens, sel)

    trainer = MeshTrainer(
        model, loss_fn,
        lm_adamw(3e-4, warmup_steps=max(2, args.steps // 10),
                 total_steps=max(args.steps, 10)),
        mesh=mesh,
    )

    rng = np.random.RandomState(0)

    def batch():
        # structured sequences (ramps) so masked positions are predictable
        start = rng.randint(0, args.vocab // 2, size=(args.batch, 1))
        return ((start + np.arange(args.seq_len)) % (args.vocab - 1)).astype(
            np.int32
        )

    state = trainer.init(jax.random.PRNGKey(0), batch())
    import time

    t0 = time.perf_counter()
    loss = float("nan")
    for i in range(args.steps):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch()))
        if (i + 1) % 20 == 0 or i + 1 == args.steps:
            loss = float(np.asarray(metrics["loss"]))
            print(f"# step {i + 1} mlm loss {loss:.4f}", flush=True)
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.batch * args.seq_len / dt
    print(
        f"RESULT: example=bert_train mlm_loss={loss:.4f} steps={args.steps} "
        f"mesh={dict(mesh.shape)} tokens_per_sec={tok_s:.0f}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
