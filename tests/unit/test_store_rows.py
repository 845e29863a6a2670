"""`models/transformer.py::_store_rows`: a call's new cache rows, every slot's
at its own cursor, against the vmapped `dynamic_update_slice` it replaced,
bit for bit: every leaf kind of the slot cache (K/V planes, latent rows, the
int8 cache beside its float32 scales), a decode step, a verify round and a
prefill bucket, cursors at 0, mid-way, on the last start that fits and past
it (the clamp), free slots beside busy ones.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.models.transformer import _store_rows

MAX_LEN = 32

LEAVES = {  # name -> (tail of the leaf after [B, max_len], dtype)
    "cursors_f32": ((), jnp.float32),            # rank 2
    "latent_bf16": ((24,), jnp.bfloat16),        # rank 3: cached_latent
    "scales_f32": ((2,), jnp.float32),           # rank 3: scale_k / scale_v
    "planes_bf16": ((2, 8), jnp.bfloat16),       # rank 4: cached_k / cached_v
    "planes_f32": ((2, 8), jnp.float32),
    "planes_int8": ((2, 8), jnp.int8),
}

SHAPES = [(1, 1), (1, 4), (1, 16), (8, 1), (8, 4), (64, 1), (64, 4)]


def _cursors(kind, B, L):
    last = MAX_LEN - L
    if kind == "mixed":
        # a free slot (cursor 0, a dummy row) beside every other case
        return [(0, MAX_LEN // 2, last, last + 1, MAX_LEN + 3, 0, 1, last - 1)[
            b % 8] for b in range(B)]
    return [{"zero": 0, "mid": MAX_LEN // 2, "last_fit": last,
             "past": last + 1, "far_past": MAX_LEN + 3}[kind]] * B


def _vmapped(cache, rows, idx0):
    tail = (0,) * (cache.ndim - 2)
    return jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i,) + tail))(cache, rows, idx0)


def _draw(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), dtype)
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize(
    "cursors", ["zero", "mid", "last_fit", "past", "far_past", "mixed"])
@pytest.mark.parametrize("leaf", list(LEAVES))
@pytest.mark.parametrize("B,L", SHAPES, ids=[f"B{b}_L{l}" for b, l in SHAPES])
def test_rows_land_where_the_vmapped_write_put_them(B, L, leaf, cursors):
    tail, dtype = LEAVES[leaf]
    rng = np.random.default_rng(B * 100 + L)
    cache = _draw(rng, (B, MAX_LEN) + tail, dtype)
    rows = _draw(rng, (B, L) + tail, dtype)
    idx0 = jnp.asarray(_cursors(cursors, B, L), jnp.int32)
    got = _store_rows(cache, rows, idx0)
    want = _vmapped(cache, rows, idx0)
    assert got.shape == cache.shape and got.dtype == cache.dtype
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # and by hand: slot b's rows at its clamped start, nothing else moved
    expect = np.array(cache)
    for b, i in enumerate(np.clip(np.asarray(idx0), 0, MAX_LEN - L)):
        expect[b, i:i + L] = np.asarray(rows)[b]
    assert np.asarray(got).tobytes() == expect.tobytes()


@pytest.mark.parametrize("B,L", [(1, 16), (8, 1), (8, 4)],
                         ids=["prefill", "decode", "verify"])
def test_the_int8_cache_and_its_scales_take_the_same_rows_under_jit(B, L):
    """The two leaves of a quantised cache through one jitted, donated call,
    as a step makes it."""
    rng = np.random.default_rng(7)
    cache = _draw(rng, (B, MAX_LEN, 2, 8), jnp.int8)
    scale = _draw(rng, (B, MAX_LEN, 2), jnp.float32)
    rows = _draw(rng, (B, L, 2, 8), jnp.int8)
    srows = _draw(rng, (B, L, 2), jnp.float32)
    idx0 = jnp.asarray(_cursors("mixed", B, L), jnp.int32)
    want = (_vmapped(cache, rows, idx0), _vmapped(scale, srows, idx0))
    want = [np.asarray(w).tobytes() for w in want]
    step = jax.jit(lambda c, s, r, sr, i: (
        _store_rows(c, r, i), _store_rows(s, sr, i)), donate_argnums=(0, 1))
    got = step(cache, scale, rows, srows, idx0)
    assert [np.asarray(g).tobytes() for g in got] == want
