"""The selective-scan kernel (ops/selective_scan.py `kft_selective_scan`),
its body in the Pallas interpreter against the `lax.scan` definition, at
both shapes the serving engine brings: a prefill (one row, a bucket of
tokens, some of them padding) and a decode step (a row a slot, one token,
some slots free), from a state that is not zero."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.ops.selective_scan import (
    KERNEL_NAME, kernel_chunk, selective_scan, selective_scan_reference)

N = 16

#: float32 on both sides and the same operations in the same order a token;
#: the interpreter's exp and the scan's are XLA's own on the CPU.  3e-6
#: measured over 256 tokens of states of magnitude 1 (the decay multiplies a
#: rounding down, it does not grow it).
TOL = 2e-5


def operands(B, L, D, seed=0, n=N):
    r = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f(r.normal(size=(B, L, D)))
    delta = f(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=(B, L, D))))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                          (n, D))
    b, c = f(r.normal(size=(B, L, n))), f(r.normal(size=(B, L, n)))
    return x, delta, a, b, c, f(r.normal(size=(B, n, D)))


def worst(a, b):
    return float(jnp.abs(a - b).max())


PREFILLS = {  # (tokens, channels, real tokens)
    "bucket_16_prompt_of_1": (16, 256, 1),
    "bucket_16_prompt_of_3": (16, 256, 3),
    "bucket_16_whole": (16, 256, 16),
    "bucket_32_prompt_of_19": (32, 1024, 19),
    "two_chunks_the_second_all_padding": (256, 512, 100),
    "two_chunks_the_second_half_padding": (256, 512, 200),
}


@pytest.mark.parametrize("case", sorted(PREFILLS))
def test_a_prefill_matches_the_scan(case):
    L, D, n = PREFILLS[case]
    args = operands(1, L, D, seed=L + n)
    n_valid = jnp.asarray([n], jnp.int32)
    assert kernel_chunk(L, D, interpret=True) == min(L, 128)
    want_y, want_h = selective_scan_reference(*args, n_valid)
    got_y, got_h = selective_scan(*args, n_valid, interpret=True)
    assert worst(got_y, want_y) < TOL and worst(got_h, want_h) < TOL
    # the padding: no output, and the state is the one after token n - 1
    assert not np.asarray(got_y)[0, n:].any()
    x, delta, a, b, c, h0 = args
    short = (x[:, :n], delta[:, :n], a, b[:, :n], c[:, :n], h0)
    _, h_short = selective_scan_reference(*short, n_valid)
    assert worst(got_h, h_short) < TOL


DECODES = {  # (slots, tokens a slot, which slots are live)
    "every_slot_busy": (4, 1, [1, 1, 1, 1]),
    "free_slots_between": (5, 1, [1, 0, 0, 1, 0]),
    "no_slot_busy": (3, 1, [0, 0, 0]),
    "the_first_slots_free": (6, 1, [0, 0, 1, 0, 1, 0]),
    "a_verify_width_of_3": (3, 3, [1, 0, 1]),
}


@pytest.mark.parametrize("case", sorted(DECODES))
def test_a_decode_step_matches_the_scan_and_a_free_slot_keeps_its_state(case):
    B, L, live = DECODES[case]
    args = operands(B, L, 256, seed=B)
    n_valid = jnp.asarray(live, jnp.int32) * L
    assert kernel_chunk(L, 256, interpret=True) == L
    want_y, want_h = selective_scan_reference(*args, n_valid)
    got_y, got_h = selective_scan(*args, n_valid, interpret=True)
    assert worst(got_y, want_y) < TOL and worst(got_h, want_h) < TOL
    for slot, busy in enumerate(live):
        same = np.array_equal(np.asarray(got_h[slot]), np.asarray(args[5][slot]))
        assert same == (not busy)
        if not busy:
            assert not np.asarray(got_y[slot]).any()


def test_l_tokens_in_one_call_are_l_chained_calls():
    args = operands(2, 4, 256, seed=9)
    x, delta, a, b, c, h = args
    n = jnp.full((2,), 4, jnp.int32)
    whole_y, whole_h = selective_scan(*args, n, interpret=True)
    ys = []
    for t in range(4):
        y, h = selective_scan(x[:, t:t + 1], delta[:, t:t + 1], a,
                              b[:, t:t + 1], c[:, t:t + 1], h,
                              jnp.ones((2,), jnp.int32), interpret=True)
        ys.append(y)
    assert worst(whole_y, jnp.concatenate(ys, axis=1)) < TOL
    assert worst(whole_h, h) < TOL


def test_the_state_is_rewritten_in_place():
    """The kernel's state output is the input's buffer: under a jit that
    donates it, no second [slots, N, D] array is made."""
    args = operands(4, 1, 256)
    n = jnp.ones((4,), jnp.int32)
    want = selective_scan_reference(*args, n)[1]
    step = jax.jit(lambda *a: selective_scan(*a, interpret=True),
                   donate_argnums=(5,))
    text = step.lower(*args, n).as_text()
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    h0 = args[5]
    _, got = step(*args, n)
    assert worst(got, want) < TOL and h0.is_deleted()


@pytest.mark.parametrize("mode, tokens, channels, want", [
    ("off", 1, 5120, None),                 # no Pallas: the lax.scan
    ("interpret", 1, 5120, 1),              # a decode step, whole
    ("interpret", 4, 5120, 4),              # a verify width
    ("interpret", 16, 5120, 16),            # the smallest bucket: one chunk
    ("interpret", 2048, 5120, 128),         # the largest: 16 chunks
    ("interpret", 12, 5120, None),          # not whole sublane tiles
    ("interpret", 192, 5120, None),         # no whole number of chunks
    ("interpret", 64, 96, 64),              # a test's width, interpreted
    ("interpret", 64, 5120 + 128, None),    # no whole number of sub-tiles
])
def test_one_function_says_which_form_runs(mode, tokens, channels, want,
                                           monkeypatch):
    monkeypatch.setenv("KFT_PALLAS", mode)
    assert kernel_chunk(tokens, channels) == want


def test_the_dispatcher_takes_the_scan_when_the_kernel_is_off(monkeypatch):
    monkeypatch.setenv("KFT_PALLAS", "off")
    args = operands(1, 16, 128)
    n = jnp.asarray([5], jnp.int32)
    text = jax.jit(selective_scan).lower(*args, n).as_text()
    assert "while" in text and KERNEL_NAME not in text
    monkeypatch.setenv("KFT_PALLAS", "interpret")
    y, h = selective_scan(*args, n)
    want_y, want_h = selective_scan_reference(*args, n)
    assert worst(y, want_y) < TOL and worst(h, want_h) < TOL


@pytest.mark.parametrize("shape", ["decode_64_slots", "prefill_2048",
                                   "prefill_16"])
def test_the_kernel_lowers_for_tpu_at_the_published_widths(shape):
    """No chip: `jax.export` for the TPU platform at d_inner 5120, state
    16: a decode step over 64 slots, the largest and the smallest prefill
    bucket."""
    B, L = {"decode_64_slots": (64, 1), "prefill_2048": (1, 2048),
            "prefill_16": (1, 16)}[shape]
    f32, D = jnp.float32, 5120
    S = jax.ShapeDtypeStruct
    text = jax.export.export(
        jax.jit(lambda *a: selective_scan(*a, interpret=False)),
        platforms=["tpu"])(
        S((B, L, D), jnp.bfloat16), S((B, L, D), f32), S((N, D), f32),
        S((B, L, N), f32), S((B, L, N), f32), S((B, N, D), f32),
        S((B,), jnp.int32)).mlir_module()
    assert "tpu_custom_call" in text and KERNEL_NAME in text


def test_mixers_of_one_shape_share_one_traced_kernel():
    """Two calls of one shape inside one program lower to ONE function
    holding the kernel, called twice: the body is traced once a shape, not
    once a mixer (26 of them a program at the published depth)."""
    args = operands(2, 1, 256)
    n = jnp.ones((2,), jnp.int32)

    def two_mixers(x, delta, a, b, c, h, n):
        y, h = selective_scan(x, delta, a, b, c, h, n, interpret=True)
        return selective_scan(x + y, delta, a, b, c, h, n, interpret=True)

    text = jax.jit(two_mixers).lower(*args, n).as_text()
    assert text.count("func.func private @_scan_pallas") == 1
    assert text.count("call @_scan_pallas") == 2
