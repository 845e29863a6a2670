"""Lightning (linear) attention: a matrix-valued recurrence, state in, state out.

`lightning_attention(q, k, v, slopes, s0, n_valid)` walks L tokens of each of
B rows through the recurrence, for head h with decay lam_h = exp(-slopes[h]),

    S_t = lam_h * S_{t-1} + k_t^T v_t            S in R^{e x e}, float32
    o_t = (q_t S_t) / sqrt(e)

from the state `s0` a row brings, and hands back (o, the state after the
row's last real token).  q, k, v [B, L, H, e] (after their norms and their
rotation: the caller's), slopes [H] float32 (`decay_slopes`: not learned),
s0 [B, H, e, e] float32, n_valid [B] int32: a row's tokens at positions >=
n_valid[b] are not real (the right padding of a prefill bucket, or the token
of a serving slot that holds no request, n_valid 0): they leave the state as
it is and their o is zero.  o [B, L, H, e] and the state are float32 whatever
dtype q, k and v arrive in.  The output norm, the gate and the projections
are the caller's (models/transformer.py `LightningAttention`).

`lightning_attention_reference` is the definition: a `lax.scan` over the
tokens.  It is what runs off TPU (`compat.pallas_mode() == "off"`), what a
shape the kernel does not take runs, and what the training-mode model calls
(it has a gradient; the kernel has none).

On TPU the same contract is a Mosaic kernel the trace names
`kft_lightning_attn`; under KFT_PALLAS=interpret its body runs in the Pallas
interpreter.  It is the chunked form of the same sums: for a chunk of C
tokens that starts from S_prev, with i, j the tokens' places in the chunk,

    o_i   = lam^(i+1) q_i S_prev + sum_{j<=i} lam^(i-j) (q_i . k_j) v_j
    S_new = lam^C S_prev + sum_j lam^(C-1-j) k_j^T v_j

so a chunk is four matmuls a head and no token waits for the one before it.
The state, its decay and every sum are float32; the matmuls take Mosaic's
default precision, as every matmul of a bf16 model does (the matrix unit
rounds a float32 operand; the interpreter multiplies in float32): each
token's k^T v enters the state rounded once, and nothing compounds.
Every power of lam is exp(-slope x a distance >= 0): no 1 / lam^j, which
overflows at 128 tokens of the fastest head (lam = 0.43).  The grid walks
(block of `_HEADS` heads, row, chunk).  A grid step holds the heads' states
[_HEADS, e, e] in its output block, which stays there from the row's first
chunk to its last (the block index does not move along the chunk axis) and
is the input state's own buffer (`input_output_aliases`): a decode step
rewrites the slot cache's state leaf in place.  A chunk that lies wholly in
the padding is skipped, a chunk that is partly padding counts its real
tokens (C becomes their number, the padding's keys are zero), and a row with
no real token at all (a free serving slot) moves no state: its steps point
the state's blocks at a neighbouring row's, already in VMEM, so a decode
step reads and writes the states of its BUSY slots only (the rows are the
inner of the two outer grid axes for that: ops/selective_scan.py has the same
arrangement over one axis).

Both shapes the serving engine brings run the one kernel: a prefill (B = 1,
L = the bucket, n_valid = the prompt's real tokens) in chunks of `_CHUNK`
tokens, and a decode step (B = slots, L = 1, n_valid 1 or 0), whose one
token is padded to the eight rows of a float32 tile and is a chunk with one
real token.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compat

#: the kernel's name in a device trace (benchmark/layer_metrics/lin_attn_*)
KERNEL_NAME = "kft_lightning_attn"

#: tokens of one grid step of a long call: four [128, 128] matmuls a head
_CHUNK = 128
#: heads of one grid step: their states are 8 x 64 KB, so a decode step is
#: slots x H / 8 steps of a 512 KB read and write, not slots x H of 64 KB
_HEADS = 8
_ROWS = 8  # a call of fewer tokens is padded to one float32 sublane tile


def decay_slopes(heads: int) -> jax.Array:
    """[H] float32: head h forgets by lam_h = exp(-2^(-8 (h + 1) / H)) a
    token (the published schedule of Lightning Attention; not learned)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / heads)


def lightning_attention_reference(q, k, v, slopes, s0, n_valid):
    """The recurrence as a `lax.scan` over the tokens (the definition)."""
    B, L, H, e = q.shape
    f32 = jnp.float32
    lam = jnp.exp(-slopes.astype(f32))[None, :, None, None]
    valid = jnp.arange(L)[None, :] < n_valid[:, None]            # [B, L]

    def step(s, inp):
        q_t, k_t, v_t, real = inp                  # [B, H, e] x 3, [B]
        s_new = lam * s + k_t[..., :, None] * v_t[..., None, :]
        s = jnp.where(real[:, None, None, None], s_new, s)
        o = jnp.einsum("bhd,bhde->bhe", q_t, s) * (e ** -0.5)
        return s, jnp.where(real[:, None, None], o, 0.0)

    first = lambda t: jnp.moveaxis(t.astype(f32), 1, 0)  # noqa: E731
    s, o = jax.lax.scan(step, s0.astype(f32),
                        (first(q), first(k), first(v),
                         jnp.moveaxis(valid, 1, 0)))
    return jnp.moveaxis(o, 0, 1), s


def kernel_chunk(tokens: int, heads: int, head_dim: int,
                 interpret=None) -> Optional[int]:
    """Tokens of one grid step when the kernel takes a call of `tokens`
    tokens a row here, None when the `lax.scan` form does: the one question
    every call site asks (as `selective_scan.kernel_chunk`)."""
    mode = compat.pallas_mode(interpret)
    if mode == "off" or heads % min(_HEADS, heads) \
            or (mode == "compiled" and head_dim % 128):
        return None  # compiled, a head is whole lane tiles
    if tokens <= _ROWS:
        return _ROWS                       # a decode or verify step, padded
    chunk = min(_CHUNK, tokens)
    return chunk if tokens % chunk == 0 and chunk % _ROWS == 0 else None


# one cached trace a shape, as `selective_scan._scan_pallas`: the lightning
# layers of a program hit jit's trace cache after the first
@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "vmem_bytes"))
def _attn_pallas(q, k, v, slopes, s0, n_valid, *, chunk: int, interpret: bool,
                 vmem_bytes: int):
    B, L, H, e = q.shape
    hb = min(_HEADS, H)
    pad = -L % chunk
    assert H % hb == 0, (H, hb)
    f32 = jnp.float32
    scale = e ** -0.5

    def flat(t):       # [B, L, H, e] -> [B, L + pad, H * e]: a head a lane tile
        return jnp.pad(t.reshape(B, L, H * e), ((0, 0), (0, pad), (0, 0)))

    # a row with no real token neither reads nor writes its state: its grid
    # steps point the state's blocks at the nearest row that has one (the
    # last such row at or before it, else the first after it), whose block
    # is then already in VMEM, and touch nothing.  Only when no row has a
    # token does a row point at itself, and carries its state through
    has_token = n_valid > 0
    index = jnp.arange(B, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(has_token, index, -1))
    first = jnp.argmax(has_token).astype(jnp.int32)
    held = jnp.where(last >= 0, last,
                     jnp.where(has_token.any(), first, index))

    def kernel(n_ref, held_ref, slope_ref, q_ref, k_ref, v_ref, s0_ref,
               o_ref, s_ref):
        heads, row, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(jnp.logical_and(step == 0, held_ref[row] == row))
        def _():
            s_ref[...] = s0_ref[...]

        n, base = n_ref[row], step * chunk

        @pl.when(base >= n)
        def _():                           # the whole chunk is padding
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(base < n)
        def _():
            real = jnp.minimum(n - base, chunk)    # the chunk's real tokens
            i = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            j = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            is_real = i < real
            real_f = jnp.zeros((1, 1), f32) + real.astype(f32)
            for u in range(hb):
                g = slope_ref[heads * hb + u]
                at = pl.ds(u * e, e)
                q_u = q_ref[:, at].astype(f32)
                k_u = jnp.where(is_real, k_ref[:, at].astype(f32), 0.0)
                v_u = v_ref[:, at].astype(f32)
                s_prev = s_ref[u]
                qk = jax.lax.dot_general(
                    q_u, k_u, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)                  # [C, C]
                within = jnp.where(
                    i >= j, jnp.exp(-g * (i - j).astype(f32)), 0.0)
                o = jnp.dot(qk * within, v_u, preferred_element_type=f32)
                o = o + jnp.exp(-g * (i + 1).astype(f32)) * jnp.dot(
                    q_u, s_prev, preferred_element_type=f32)
                o_ref[:, at] = jnp.where(is_real, o * scale, 0.0)
                # a key `real - 1 - i` tokens before the chunk's last real
                # one; the padding's keys are zero and take no power
                k_d = k_u * jnp.exp(
                    -g * jnp.where(is_real, real - 1 - i, 0).astype(f32))
                s_ref[u] = (jnp.exp(-g * real_f) * s_prev
                            + jax.lax.dot_general(
                                k_d, v_u, (((0,), (0,)), ((), ())),
                                preferred_element_type=f32))

    tokens = lambda h, r, s, n, held, g: (r, s, h)             # noqa: E731
    state = lambda h, r, s, n, held, g: (held[r], h, 0, 0)     # noqa: E731
    o, s = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, L + pad, H * e), f32),
                   jax.ShapeDtypeStruct((B, H, e, e), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H // hb, B, (L + pad) // chunk),
            in_specs=[
                pl.BlockSpec((None, chunk, hb * e), tokens),       # q
                pl.BlockSpec((None, chunk, hb * e), tokens),       # k
                pl.BlockSpec((None, chunk, hb * e), tokens),       # v
                pl.BlockSpec((None, hb, e, e), state),             # s0
            ],
            out_specs=(pl.BlockSpec((None, chunk, hb * e), tokens),
                       pl.BlockSpec((None, hb, e, e), state)),
        ),
        # operand 6 (n_valid, held and slopes are 0, 1, 2) is the state,
        # rewritten in place
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a free row's steps lean on the block the row before
            # it left in VMEM
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name=KERNEL_NAME,
    )(n_valid.astype(jnp.int32), held, slopes.astype(f32), flat(q), flat(k),
      flat(v), s0.astype(f32))
    return o[:, :L].reshape(B, L, H, e), s


def lightning_attention(q, k, v, slopes, s0, n_valid, interpret=None):
    """(o [B, L, H, e], the state [B, H, e, e] after each row's last real
    token), float32: the kernel where `kernel_chunk` says so, else the
    `lax.scan`."""
    chunk = kernel_chunk(q.shape[1], q.shape[2], q.shape[3], interpret)
    if chunk is None:
        return lightning_attention_reference(q, k, v, slopes, s0, n_valid)
    return _attn_pallas(
        q, k, v, slopes, s0, n_valid, chunk=chunk,
        interpret=compat.pallas_mode(interpret) == "interpret",
        vmem_bytes=compat.vmem_budget_bytes())
