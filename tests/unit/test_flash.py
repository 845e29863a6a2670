"""Pallas flash-attention kernel vs the plain-XLA reference (interpreter
mode on CPU; the same code compiles for TPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.ops.flash import flash_attention
from kungfu_tpu.parallel.ring_attention import full_attention

# compile-heavy: excluded from the fast dev loop (pytest -m 'not slow');
# CI runs the full suite unfiltered
pytestmark = pytest.mark.slow


def _rand(b, l, h, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, l, h, d)
    return [jax.random.normal(k, shape, dtype) for k in ks]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("l", [64, 128, 192])
def test_matches_reference(causal, l):
    q, k, v = _rand(2, l, 2, 32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_unpadded_lengths():
    """Sequence not a multiple of the block size: padded tail must not leak."""
    q, k, v = _rand(1, 100, 2, 32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_bf16_inputs():
    q, k, v = _rand(1, 128, 2, 32, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = full_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match(causal):
    q, k, v = _rand(1, 96, 2, 16, seed=3)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_jit_and_scale():
    q, k, v = _rand(1, 64, 1, 16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=False, scale=0.5, interpret=True))
    out = f(q, k, v)
    ref = full_attention(q, k, v, causal=False, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_lse_matches_reference(causal):
    from kungfu_tpu.ops.flash import flash_attention_with_lse

    q, k, v = _rand(2, 64, 2, 16, seed=5)
    o, lse = flash_attention_with_lse(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    # reference lse from the raw scores
    scale = 1.0 / (16 ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((64, 64), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(full_attention(q, k, v, causal=causal)), atol=2e-5
    )


def test_lse_gradient():
    """Differentiating THROUGH the lse output (the ring-merge path) must
    agree with autodiff on the plain-XLA computation."""
    from kungfu_tpu.ops.flash import flash_attention_with_lse

    q, k, v = _rand(1, 48, 1, 16, seed=7)
    scale = 1.0 / (16 ** 0.5)

    def f_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=False,
                                          block_q=16, block_k=16, interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def f_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_unpadded_length(causal):
    """L not a multiple of the block: padded q rows carry a REAL lse in the
    forward and must be masked by position in the Pallas dk/dv kernel."""
    q, k, v = _rand(1, 100, 2, 16, seed=11)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32, interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_bwd_xla_pallas_agree():
    """backward="xla" (the A/B arm) must give the same grads as the Pallas
    backward."""
    q, k, v = _rand(1, 96, 2, 16, seed=13)

    def loss(q, k, v, backward=None):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=32, block_k=32, interpret=True,
                                       backward=backward) ** 2)

    g_pallas = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(lambda q, k, v: loss(q, k, v, "xla"),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("bwd", ["pallas", "xla"])
def test_bwd_explicit_argument(bwd):
    """backward= forces the chosen implementation and matches the reference
    gradients."""
    q, k, v = _rand(1, 96, 2, 16, seed=13)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=32, block_k=32,
                                       interpret=True, backward=bwd) ** 2)

    def ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_bwd_bad_argument_raises():
    q, k, v = _rand(1, 32, 1, 16)
    # call time, not first-gradient time: a typo on an inference-only path
    # must not be silently accepted
    with pytest.raises(ValueError, match="backward"):
        flash_attention(q, k, v, causal=True, interpret=True, backward="nope")


# the backward arm's rule (mode, explicit argument) is tested in tier-1:
# tests/unit/test_flash_cached.py::test_bwd_auto_selection


@pytest.mark.parametrize("causal", [True, False])
def test_lse_gradient_unpadded(causal):
    """lse-cotangent path (ring merge) through the Pallas backward with an
    unpadded length."""
    from kungfu_tpu.ops.flash import flash_attention_with_lse

    q, k, v = _rand(1, 40, 1, 16, seed=17)
    scale = 1.0 / (16 ** 0.5)

    def f_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                          block_q=16, block_k=16, interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def f_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            pos = jnp.arange(s.shape[-1])
            s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [1, 2])
def test_gqa_kernel_matches_expanded(causal, hkv):
    """GQA kv (index-mapped, no repeats) must equal MHA on repeated kv —
    forward AND gradients (the dk/dv group-accumulation grid)."""
    b, l, h, d = 2, 96, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, hkv, d))
    v = jax.random.normal(ks[2], (b, l, hkv, d))
    group = h // hkv

    def f_gqa(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32, interpret=True) ** 2)

    def f_rep(q, k, v):
        return jnp.sum(flash_attention(
            q, jnp.repeat(k, group, 2), jnp.repeat(v, group, 2),
            causal=causal, block_q=32, block_k=32, interpret=True) ** 2)

    np.testing.assert_allclose(float(f_gqa(q, k, v)), float(f_rep(q, k, v)),
                               rtol=1e-5)
    # f_rep repeats INSIDE the differentiated fn, so autodiff already sums
    # its kv grads over the group — shapes match g_gqa directly
    g_gqa = jax.grad(f_gqa, argnums=(0, 1, 2))(q, k, v)
    g_rep = jax.grad(f_rep, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_gqa, g_rep):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)


def test_gqa_kernel_unpadded_length_and_lse():
    """GQA + L not a multiple of the block + the lse variant."""
    from kungfu_tpu.ops.flash import flash_attention_with_lse

    b, l, h, hkv, d = 1, 72, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, hkv, d))
    v = jax.random.normal(ks[2], (b, l, hkv, d))

    def f_gqa(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                          block_q=32, block_k=32, interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def f_rep(q, k, v):
        o, lse = flash_attention_with_lse(
            q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True,
            block_q=32, block_k=32, interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    g_gqa = jax.grad(f_gqa, argnums=(0, 1, 2))(q, k, v)
    g_rep = jax.grad(f_rep, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_gqa, g_rep):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)


def test_gqa_xla_bwd_matches():
    """The backward="xla" path must reduce GQA dk/dv over the group too."""
    b, l, h, hkv, d = 1, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(29), 3)
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, hkv, d))
    v = jax.random.normal(ks[2], (b, l, hkv, d))

    def loss(q, k, v, backward=None):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=32, block_k=32, interpret=True,
                                       backward=backward) ** 2)

    g_pallas = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(lambda q, k, v: loss(q, k, v, "xla"),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_pallas, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-5)


def _windowed_reference(q, k, v, window):
    """Masked full attention: causal AND within the last `window` keys."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    pos = jnp.arange(q.shape[1])
    m = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < window)
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("l,window", [(96, 32), (100, 17), (128, 64)])
def test_sliding_window_matches_reference(l, window):
    q, k, v = _rand(2, l, 2, 16, seed=31)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    ref = _windowed_reference(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("hkv", [1, 4])
def test_sliding_window_gradients(hkv):
    """Windowed grads (dq block-start skip + dkv block-end skip) vs the
    masked reference, incl. GQA."""
    b, l, h, d, w = 1, 96, 4, 16, 40
    ks = jax.random.split(jax.random.PRNGKey(33), 3)
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, hkv, d))
    v = jax.random.normal(ks[2], (b, l, hkv, d))

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=w,
                                       block_q=32, block_k=32,
                                       interpret=True) ** 2)

    def f_ref(q, k, v):
        kk = jnp.repeat(k, h // hkv, 2) if hkv != h else k
        vv = jnp.repeat(v, h // hkv, 2) if hkv != h else v
        return jnp.sum(_windowed_reference(q, kk, vv, w) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_window_requires_causal():
    q, k, v = _rand(1, 32, 1, 16)
    with pytest.raises(AssertionError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)
