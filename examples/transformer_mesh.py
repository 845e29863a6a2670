"""Multi-axis transformer training with the public MeshTrainer.

Run on the 8-virtual-device CPU mesh (or any TPU slice):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/transformer_mesh.py --dp 2 --sp 2 --tp 2

The mesh combines data (dp), sequence (sp, ring attention), and tensor
(tp, Megatron-style) parallelism; MeshTrainer + the logical-axis rules do
all the sharding — no manual collectives in user code.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()
    steps = max(1, args.steps)

    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM, lm_loss
    from kungfu_tpu.plan import MeshSpec, make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    mesh = make_mesh(MeshSpec.make(dp=args.dp, sp=args.sp, tp=args.tp))
    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_len=args.seq_len, dtype=jnp.float32,
        attention="ring" if args.sp > 1 else "auto", mesh=mesh,
    )
    model = TransformerLM(cfg)

    def loss_fn(model, params, toks):
        return lm_loss(model.apply({"params": params}, toks), toks)

    trainer = MeshTrainer(model, loss_fn, optax.adamw(3e-3), mesh=mesh)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 256, size=(4 * args.dp, args.seq_len)).astype(np.int32)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    batch = trainer.shard_batch(tokens)
    for i in range(steps):
        state, metrics = trainer.train_step(state, batch)
        print(f"step {state.step} loss {float(np.asarray(metrics['loss'])):.4f}",
              flush=True)
    print(f"RESULT: transformer-mesh mesh={dict(mesh.shape)} "
          f"final_loss={float(np.asarray(metrics['loss'])):.4f}", flush=True)


if __name__ == "__main__":
    main()
