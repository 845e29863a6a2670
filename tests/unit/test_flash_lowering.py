"""TPU (Mosaic) lowering regression for the Pallas flash kernels.

`jax.export` cross-platform lowering runs the Pallas->Mosaic TPU compiler
on the CPU host — no TPU device needed — so tiling/layout violations in the
kernels (e.g. non-8/128-aligned trailing block dims) fail HERE instead of
on the chip.  This is the strongest kernel evidence available off-chip;
the attention bench records the on-chip numbers.
"""

import jax
# on the pinned JAX, `jax.export` is importable but not set as a module
# attribute until the submodule import runs (newer JAX attaches it lazily);
# the explicit import makes `jax.export.export` below work on both
import jax.export  # noqa: F401
import jax.numpy as jnp

from kungfu_tpu.ops.flash import flash_attention, flash_attention_with_lse


def _export_ok(fn, *args):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert len(exp.mlir_module_serialized) > 0
    return exp


def test_fwd_bwd_lowers_for_tpu():
    """MHA fwd + the Pallas backward (dq + dk/dv kernels) lower to Mosaic."""
    q = jnp.zeros((2, 1024, 8, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=False)
            .astype(jnp.float32) ** 2
        )

    _export_ok(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_gqa_lse_lowers_for_tpu():
    """GQA (index-mapped kv + group-accumulation dkv grid) and the
    lse-cotangent path lower to Mosaic."""
    q = jnp.zeros((1, 512, 8, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 512, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(jnp.sin(lse))

    _export_ok(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_unpadded_length_lowers_for_tpu():
    """L not a multiple of the block (padding path) still lowers."""
    q = jnp.zeros((1, 300, 4, 64), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=False, interpret=False)

    _export_ok(f, q, q, q)


def test_sliding_window_lowers_for_tpu():
    """Windowed kernels (block-skip loop bounds) lower to Mosaic."""
    q = jnp.zeros((1, 1024, 4, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, window=256,
                            interpret=False).astype(jnp.float32) ** 2
        )

    _export_ok(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_large_blocks_head128_lower_for_tpu():
    """Large asymmetric tiling — 256x512 blocks at head_dim 128 (the
    tuner's flash sweep's candidate shapes) — lowers to Mosaic fwd+bwd.  The
    TransformerConfig flash_block plumb-through is guarded one level up
    (test_tpu_lowering.test_transformer_custom_blocks_lower)."""
    q = jnp.zeros((1, 2048, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=False,
                            block_q=256, block_k=512, backward="pallas")
            .astype(jnp.float32) ** 2
        )

    _export_ok(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
