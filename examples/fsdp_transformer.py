"""FSDP (ZeRO-3) transformer training — sharded params + optimizer state.

The reference replicates the model on every worker (DP only); FSDPTrainer
shards every parameter and Adam-moment leaf across the `fsdp` mesh axis so
the per-device memory is model_bytes * 3 / n_shard + activations — the
capability that lets a BERT/GPT-class model train on chips it cannot fit
on replicated.  Hybrid sharded-DP: add a `dp` axis and each fsdp group
holds one replica (grads pmean over dp after the reduce_scatter).

Run on the 8-virtual-device CPU mesh (or a real pod slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/fsdp_transformer.py --fsdp 4 --dp 2 --steps 30

Gradient compression (docs/compression.md): `--compress int8` quantizes the
cross-replica dp gradient mean — in hybrid sharded DP that is the slow
(typically cross-host/DCN) hop, while the fsdp reduce_scatter/all_gather
traffic stays full precision.  ~3.9x fewer bytes on that leg; the loss curve
should be indistinguishable (per-block int8 error ~0.4% of each block's
dynamic range).

Composition notes (FSDPTrainer vs MeshTrainer):
  * FSDPTrainer owns the data axes; it flattens params to chunks, so it
    composes with activation-level TP only via the model's own shard_map
    islands (e.g. ring attention over an `sp` axis is fine: the gathered
    full params feed the model exactly as in the replicated case).
  * For Megatron-style parameter TP use MeshTrainer with an fsdp mesh axis
    in `rules` instead — chunk-flattened storage and dimension-aligned TP
    sharding are different layouts for the same bytes; pick per model.
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
    ap.add_argument("--compress", default=None,
                    help="dp-leg gradient wire format: int8 | int8-sr | fp8 "
                         "| bf16 (default: uncompressed)")
    ap.add_argument("--bucket-bytes", type=int, default=0,
                    help="bucket the dp-leg gradient sync: one collective "
                         "per size bucket instead of one fused block "
                         "(docs/pallas.md; 0 = single fused tree)")
    args = ap.parse_args()

    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    import flax.linen as nn
    from jax.sharding import Mesh

    from kungfu_tpu.fsdp import FSDPTrainer
    from kungfu_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss,
    )

    devs = jax.devices()
    need = args.fsdp * args.dp
    assert len(devs) >= need, f"need {need} devices, have {len(devs)}"
    mesh = Mesh(np.array(devs[:need]).reshape(args.dp, args.fsdp), ("dp", "fsdp"))

    cfg = TransformerConfig(
        vocab_size=1024, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=4, d_ff=args.d_model * 4, max_len=args.seq, dtype=jnp.float32,
    )
    model = TransformerLM(cfg)

    def loss_fn(params, tokens):
        return lm_loss(model.apply({"params": params}, tokens), tokens)

    tokens0 = jnp.zeros((1, args.seq), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens0)["params"])
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))

    compress = None
    if args.compress:
        from kungfu_tpu import compression as comp

        # a CompressionConfig is a plain frozen value: build one explicitly
        # (comp.CompressionConfig(scheme="int8", block=128)) or resolve a
        # registered name from the CLI
        compress = comp.resolve(args.compress)
        print(f"dp-leg gradient wire: {compress.describe()} "
              f"({compress.compression_ratio(1 << 20):.2f}x fewer bytes)")

    trainer = FSDPTrainer(loss_fn, optax.adam(1e-3), mesh=mesh,
                          compression=compress,
                          bucket_bytes=args.bucket_bytes or None)
    state = trainer.init(params)

    # every param/moment leaf is chunked (n_fsdp, chunk) and sharded on dim 0
    leaf = jax.tree.leaves(state.params)[0]
    local = leaf.addressable_shards[0].data.shape[0]
    print(f"params: {n_params:,}; chunk leaves sharded {leaf.shape[0]} ways "
          f"({local} rows/device) over fsdp={args.fsdp}")

    rng = np.random.RandomState(0)
    world = args.dp * args.fsdp
    tokens = rng.randint(0, cfg.vocab_size,
                         size=(args.batch * world, args.seq)).astype(np.int32)
    batch = trainer.shard_batch(tokens)
    metrics = {"loss": float("nan")}
    for step in range(args.steps):
        state, metrics = trainer.train_step(state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(np.asarray(metrics['loss'])):.4f}")

    # reassembled full params round-trip for eval/checkpoint
    full = trainer.eval_params(state)
    got = sum(int(np.prod(np.asarray(l).shape)) for l in jax.tree.leaves(full))
    assert got == n_params, (got, n_params)
    print(f"RESULT: fsdp={args.fsdp} dp={args.dp} "
          f"loss={float(np.asarray(metrics['loss'])):.4f} params={n_params}")


if __name__ == "__main__":
    main()
