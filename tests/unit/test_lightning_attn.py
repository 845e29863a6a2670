"""Lightning (linear) attention (kungfu_tpu/ops/lightning_attn.py).

The kernel's body in the Pallas interpreter against the `lax.scan` it
replaces on TPU: the chunked form against the token-by-token recurrence
over decode and prefill shapes, padding that never enters the state, free
rows whose state is not moved, the choice between kernel and scan from what
a call shows of itself, its Mosaic lowering at the published widths, and
one traced kernel a shape.
"""
import numpy as np
import pytest

import jax
import jax.export  # noqa: F401  (not an attribute until imported, on the pinned JAX)
import jax.numpy as jnp

from kungfu_tpu.ops import lightning_attn as la
from kungfu_tpu.ops.lightning_attn import (
    KERNEL_NAME, decay_slopes, kernel_chunk, lightning_attention,
    lightning_attention_reference)

TOL = 2e-4   # float32 sums of up to 128 products in another order, |o| ~ 40


def operands(B, L, H, e, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(ks[i], (B, L, H, e), jnp.float32).astype(dtype)
               for i in range(3))
    s0 = jax.random.normal(ks[3], (B, H, e, e), jnp.float32)
    return q, k, v, decay_slopes(H), s0


def worst(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


def test_the_decay_is_the_published_schedule():
    lam = np.exp(-np.asarray(decay_slopes(32)))
    assert lam.shape == (32,)
    np.testing.assert_allclose(lam[0], np.exp(-2.0 ** -0.25), rtol=1e-6)
    np.testing.assert_allclose(lam[-1], np.exp(-2.0 ** -8), rtol=1e-6)
    assert (np.diff(lam) > 0).all()      # later heads remember longer


def test_the_reference_is_the_recurrence_written_out():
    q, k, v, slopes, s0 = operands(1, 5, 2, 4)
    o, s = lightning_attention_reference(q, k, v, slopes, s0,
                                         jnp.asarray([5], jnp.int32))
    lam = np.exp(-np.asarray(slopes))
    state = np.asarray(s0[0]).copy()
    for t in range(5):
        for h in range(2):
            state[h] = lam[h] * state[h] + np.outer(k[0, t, h], v[0, t, h])
            np.testing.assert_allclose(
                np.asarray(o[0, t, h]), np.asarray(q[0, t, h]) @ state[h] / 2.0,
                rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[0]), state, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,L,H,e,n_valid", [
    (2, 256, 8, 128, [200, 256]),     # a prefill: one row partly padding
    (1, 384, 8, 128, [130]),          # whole chunks of padding after a cut one
    (3, 1, 16, 128, [1, 0, 1]),       # a decode step with a free slot
    (3, 1, 16, 128, [0, 0, 1]),       # free slots before the first busy one
    (2, 1, 16, 128, [0, 0]),          # nobody holds a request
    (2, 4, 8, 128, [4, 2]),           # a verify step's few rows
    (2, 16, 4, 32, [16, 5]),          # the smallest bucket, a narrow head
], ids=["prefill", "prefill_pad_chunks", "decode_free", "decode_free_first",
        "decode_all_free", "verify", "bucket16"])
def test_the_chunked_kernel_is_the_token_by_token_recurrence(B, L, H, e, n_valid):
    q, k, v, slopes, s0 = operands(B, L, H, e)
    n = jnp.asarray(n_valid, jnp.int32)
    want_o, want_s = lightning_attention_reference(q, k, v, slopes, s0, n)
    got_o, got_s = lightning_attention(q, k, v, slopes, s0, n, interpret=True)
    assert got_o.shape == (B, L, H, e) and got_o.dtype == jnp.float32
    assert got_s.shape == s0.shape and got_s.dtype == jnp.float32
    assert worst(got_o, want_o) < TOL and worst(got_s, want_s) < TOL
    for b, real in enumerate(n_valid):
        # padding's outputs are zero and a row with no token keeps its state
        assert not np.asarray(got_o[b, real:]).any()
        if real == 0:
            np.testing.assert_array_equal(np.asarray(got_s[b]), np.asarray(s0[b]))


def test_a_call_of_l_tokens_is_l_chained_calls():
    q, k, v, slopes, s0 = operands(2, 256, 8, 128, seed=3)
    n = jnp.asarray([256, 256], jnp.int32)
    whole_o, whole_s = lightning_attention(q, k, v, slopes, s0, n, interpret=True)
    s, half = s0, []
    for lo in (0, 128):
        o, s = lightning_attention(q[:, lo:lo + 128], k[:, lo:lo + 128],
                                   v[:, lo:lo + 128], slopes, s,
                                   jnp.asarray([128, 128], jnp.int32),
                                   interpret=True)
        half.append(o)
    assert worst(jnp.concatenate(half, 1), whole_o) < TOL
    assert worst(s, whole_s) < TOL


def test_bf16_operands_keep_a_float32_state():
    q, k, v, slopes, s0 = operands(2, 128, 8, 128, jnp.bfloat16)
    n = jnp.asarray([128, 100], jnp.int32)
    want_o, want_s = lightning_attention_reference(q, k, v, slopes, s0, n)
    got_o, got_s = lightning_attention(q, k, v, slopes, s0, n, interpret=True)
    assert got_s.dtype == jnp.float32
    assert worst(got_o, want_o) < TOL and worst(got_s, want_s) < TOL


def test_selection_from_what_a_call_shows(monkeypatch):
    assert kernel_chunk(1, 32, 128, interpret=True) == 8      # padded to a tile
    assert kernel_chunk(8, 32, 128, interpret=True) == 8
    assert kernel_chunk(12288, 32, 128, interpret=True) == 128
    assert kernel_chunk(16, 32, 128, interpret=True) == 16
    assert kernel_chunk(200, 32, 128, interpret=True) is None  # no whole chunks
    assert kernel_chunk(128, 12, 128, interpret=True) is None  # heads in eights
    assert kernel_chunk(128, 32, 64, interpret=False) is None  # half a lane tile
    assert kernel_chunk(128, 32, 64, interpret=True) == 128
    monkeypatch.delenv("KFT_PALLAS", raising=False)
    assert kernel_chunk(128, 32, 128) is None                  # no kernels here
    q, k, v, slopes, s0 = operands(1, 16, 8, 128)
    text = jax.jit(lightning_attention).lower(
        q, k, v, slopes, s0, jnp.asarray([5], jnp.int32)).as_text()
    assert "while" in text and KERNEL_NAME not in text


@pytest.mark.parametrize("shape", ["decode_16_slots", "prefill_12288",
                                   "prefill_16"])
def test_the_kernel_lowers_for_tpu_at_the_published_widths(shape):
    """No chip: `jax.export` for the TPU platform at 32 heads of 128: a
    decode step over 16 slots, the cell's one prefill bucket and the
    smallest one."""
    B, L = {"decode_16_slots": (16, 1), "prefill_12288": (1, 12288),
            "prefill_16": (1, 16)}[shape]
    S = jax.ShapeDtypeStruct
    qkv = S((B, L, 32, 128), jnp.bfloat16)
    text = jax.export.export(
        jax.jit(lambda *a: lightning_attention(*a, interpret=False)),
        platforms=["tpu"])(
        qkv, qkv, qkv, S((32,), jnp.float32),
        S((B, 32, 128, 128), jnp.float32), S((B,), jnp.int32)).mlir_module()
    assert "tpu_custom_call" in text and KERNEL_NAME in text


def test_layers_of_one_shape_share_one_traced_kernel():
    q, k, v, slopes, s0 = operands(2, 1, 8, 128)
    n = jnp.ones((2,), jnp.int32)

    def two_layers(q, k, v, slopes, s0, n):
        o, s = lightning_attention(q, k, v, slopes, s0, n, interpret=True)
        return lightning_attention(q + o, k, v, slopes, s, n, interpret=True)

    text = jax.jit(two_layers).lower(q, k, v, slopes, s0, n).as_text()
    assert text.count("func.func private @_attn_pallas") == 1
    assert text.count("call @_attn_pallas") == 2


def test_the_state_is_rewritten_in_place():
    """The state operand is aliased to the state output of the kernel."""
    q, k, v, slopes, s0 = operands(2, 1, 8, 128)
    text = jax.export.export(
        jax.jit(lambda *a: la._attn_pallas(
            *a, chunk=8, interpret=False, vmem_bytes=64 << 20)),
        platforms=["tpu"])(q, k, v, slopes, s0,
                           jnp.ones((2,), jnp.int32)).mlir_module()
    assert "output_tuple_indices = [1], operand_index = 6" in text
