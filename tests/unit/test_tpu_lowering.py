"""TPU cross-platform lowering of the driver-contract hot paths.

Like tests/unit/test_flash_lowering.py but one level up: the flagship
forward (`__graft_entry__.entry` shape) and the single-device train steps
compile for the TPU target on the CPU host via jax.export.  A change that
breaks TPU lowering of the model/optimizer path fails here without a chip.

The transformer's attention auto-selection keys off the HOST backend (cpu
here), so the flash kernels are pinned to the compiled path for these
tests — otherwise export would silently lower the XLA reference instead
of the Mosaic kernels the TPU run uses.
"""
import contextlib
import functools

import pytest

import jax
import jax.numpy as jnp
import optax

# compile-heavy: excluded from the fast dev loop (pytest -m "not slow")
pytestmark = [pytest.mark.filterwarnings("ignore"), pytest.mark.slow]


@contextlib.contextmanager
def pin_compiled_kernels():
    """Force interpret=False during EXPORT ONLY — eager calls (model.init)
    must keep the auto path, since the compiled kernel cannot execute on
    the CPU host."""
    import kungfu_tpu.ops.flash as F

    orig_fa, orig_lse = F.flash_attention, F.flash_attention_with_lse
    F.flash_attention = functools.partial(orig_fa, interpret=False)
    F.flash_attention_with_lse = functools.partial(orig_lse, interpret=False)
    try:
        yield
    finally:
        F.flash_attention = orig_fa
        F.flash_attention_with_lse = orig_lse


def _export_ok(fn, *args, expect_mosaic=False):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert len(exp.mlir_module_serialized) > 0
    if expect_mosaic:  # the Pallas kernels actually made it into the module
        assert "tpu_custom_call" in exp.mlir_module()
    return exp


def test_transformer_fwd_lowers():
    """entry()-shaped flagship forward (flash attention on-TPU path)."""
    import flax.linen as nn

    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=8, d_ff=1024,
        max_len=256, dtype=jnp.bfloat16, attention="flash",
    )
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])
    with pin_compiled_kernels():
        _export_ok(lambda p, t: model.apply({"params": p}, t), params,
                   tokens, expect_mosaic=True)


def test_transformer_train_step_lowers():
    """S-SGD train step on a GQA+rope+swiglu decoder with the flash
    kernels — the gpt_train.py hot path."""
    import flax.linen as nn

    from kungfu_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss,
    )

    cfg = TransformerConfig(
        vocab_size=512, d_model=128, n_layers=2, n_heads=8, n_kv_heads=2,
        rope=True, ffn="swiglu", d_ff=512, max_len=128, dtype=jnp.bfloat16,
        attention="flash",
    )
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])
    tx = optax.adamw(3e-4)
    opt = tx.init(params)

    def step(params, opt, tokens):
        loss, g = jax.value_and_grad(
            lambda p: lm_loss(model.apply({"params": p}, tokens), tokens)
        )(params)
        upd, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, upd), opt, loss

    with pin_compiled_kernels():
        _export_ok(step, params, opt, tokens, expect_mosaic=True)


def test_resnet_train_step_lowers():
    """The ResNet-50 S-SGD step (bf16 BN, batch_stats threaded)."""
    from kungfu_tpu.models.resnet import ResNet50
    from kungfu_tpu.models.slp import softmax_cross_entropy

    model = ResNet50(num_classes=1000, norm_dtype=jnp.bfloat16)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3), jnp.bfloat16),
        train=False,
    )
    params, stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt = tx.init(params)
    images = jnp.zeros((8, 224, 224, 3), jnp.bfloat16)
    labels = jnp.zeros((8,), jnp.int32)

    def step(params, opt, stats, images, labels):
        def loss_fn(p, st):
            logits, mut = model.apply(
                {"params": p, "batch_stats": st}, images, train=True,
                mutable=["batch_stats"],
            )
            return softmax_cross_entropy(logits, labels), mut["batch_stats"]

        (loss, stats), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, stats
        )
        upd, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, upd), opt, stats, loss

    _export_ok(step, params, opt, stats, images, labels)


def test_transformer_custom_blocks_lower():
    """Non-default flash_block_q/flash_block_k reach the kernel THROUGH
    TransformerConfig (guards the Attention-module plumb-through: a kwarg
    swap or a dropped kwarg at either flash call site would change or
    break this lowering)."""
    import flax.linen as nn

    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=1, n_heads=2, d_ff=512,
        max_len=512, dtype=jnp.bfloat16, attention="flash", rope=True,
        flash_block_q=256, flash_block_k=512,
    )
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 512), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])
    import dataclasses

    with pin_compiled_kernels():
        exp = _export_ok(lambda p, t: model.apply({"params": p}, t), params,
                         tokens, expect_mosaic=True)
        # assert the non-default tiling actually took effect: the same
        # model exported with default 128x128 blocks must produce a
        # DIFFERENT Mosaic module (same param tree, so any difference is
        # the kernel tiling)
        dmodel = TransformerLM(dataclasses.replace(
            cfg, flash_block_q=128, flash_block_k=128))
        dexp = _export_ok(lambda p, t: dmodel.apply({"params": p}, t),
                          params, tokens, expect_mosaic=True)
    assert exp.mlir_module() != dexp.mlir_module(), (
        "custom flash_block_q/k produced an identical module: the config "
        "values are not reaching the kernel"
    )


def test_int8_decode_step_lowers():
    """KV-cache decode with the int8 cache (quantize + int8
    dynamic_update_slice + fused dequant einsum) compiles for TPU — the
    serving path's on-chip viability, incl. its layout/tiling."""
    import dataclasses

    import flax.linen as nn

    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_layers=1, n_heads=4, n_kv_heads=2,
        d_ff=256, max_len=128, dtype=jnp.bfloat16, causal=True, rope=True,
        attention="full",
    )
    dcfg = dataclasses.replace(cfg, decode=True, kv_cache_dtype="int8")
    dmodel = TransformerLM(dcfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = dmodel.init(jax.random.PRNGKey(0), tokens[:, :1])
    params = nn.meta.unbox(variables["params"])
    cache = variables["cache"]

    def step(p, c, t):
        return dmodel.apply({"params": p, "cache": c}, t, mutable=["cache"])

    # prefill (8 tokens) and single-token decode both must lower
    _export_ok(step, params, cache, tokens)
    _export_ok(step, params, cache, tokens[:, :1])
