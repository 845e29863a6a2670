"""FSDP (ZeRO-3) transformer training — sharded params + optimizer state.

The reference replicates the model on every worker (DP only); a mesh with
an `fsdp` axis under MeshTrainer shards every parameter over it (the embed
dims, by the default rules table) and Adam's moments with their parameters,
so the per-device memory is model_bytes * 3 / n_shard + activations — the
capability that lets a BERT/GPT-class model train on chips it cannot fit on
replicated.  XLA inserts the per-layer all-gathers and the gradient
reductions.  Hybrid sharded-DP: add a `dp` axis and each fsdp group holds
one replica.  The same rules table composes with `tp`, `sp` and `ep` axes
(examples/transformer_mesh.py).

Run on the 8-virtual-device CPU mesh (or a real pod slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/fsdp_transformer.py --fsdp 4 --dp 2 --steps 30
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fsdp", type=int, default=4)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4, help="per data-shard batch")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss,
    )
    from kungfu_tpu.plan import MeshSpec, make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    mesh = make_mesh(MeshSpec.make(dp=args.dp, fsdp=args.fsdp))
    cfg = TransformerConfig(
        vocab_size=1024, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=4, d_ff=args.d_model * 4, max_len=args.seq, dtype=jnp.float32,
        mesh=mesh,
    )
    trainer = MeshTrainer(
        TransformerLM(cfg),
        lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
        optax.adam(1e-3), mesh=mesh)

    rng = np.random.RandomState(0)
    world = args.dp * args.fsdp
    tokens = rng.randint(0, cfg.vocab_size,
                         size=(args.batch * world, args.seq)).astype(np.int32)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    n_params = sum(int(l.size) for l in jax.tree.leaves(state.params))

    # a matrix and its Adam moments live one share a device
    qk = state.params["block_0"]["attn"]["q"]["kernel"]
    mu = state.opt_state[0].mu["block_0"]["attn"]["q"]["kernel"]
    for leaf in (qk, mu):
        local = leaf.addressable_shards[0].data.shape
        assert local[0] * args.fsdp == leaf.shape[0], (local, leaf.shape)
    print(f"params: {n_params:,}; q kernel {qk.shape} stored as {local} "
          f"a device over fsdp={args.fsdp}, spec {qk.sharding.spec}")

    batch = trainer.shard_batch(tokens)
    metrics = {"loss": float("nan")}
    for step in range(args.steps):
        state, metrics = trainer.train_step(state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(np.asarray(metrics['loss'])):.4f}")

    # reassembled full params round-trip for eval/checkpoint
    full = trainer.eval_params(state)
    got = sum(int(np.asarray(l).size) for l in jax.tree.leaves(full))
    assert got == n_params, (got, n_params)
    print(f"RESULT: fsdp={args.fsdp} dp={args.dp} "
          f"loss={float(np.asarray(metrics['loss'])):.4f} params={n_params}")


if __name__ == "__main__":
    main()
