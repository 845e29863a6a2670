"""The selective scan of a state-space mixer (Mamba-1), state in, state out.

`selective_scan(x, delta, a, b, c, h0, n_valid)` walks L tokens of each of B
rows through the recurrence, for channel d and state index n,

    h_t[n, d] = exp(delta_t[d] * a[n, d]) * h_{t-1}[n, d]
                + delta_t[d] * x_t[d] * b_t[n]
    y_t[d]    = sum_n h_t[n, d] * c_t[n]

from the state `h0` a row brings, and hands back (y, the state after the
row's last real token).  x, delta [B, L, D] (delta after its softplus),
a [N, D] (negative), b, c [B, L, N], h0 [B, N, D], n_valid [B] int32: a
row's tokens at positions >= n_valid[b] are not real (the right padding of
a prefill bucket, or every token of a serving slot that holds no request,
n_valid 0): they leave the state as it is and their y is zero.  Everything
is float32 whatever dtype x arrives in; y [B, L, D] and the state are
float32.  The skip term D * x and the gate are the caller's.

The state is kept TRANSPOSED, [N, D] with the channels on the lanes: N is
16, and a [D, 16] float32 array is stored on the TPU in tiles of 128 lanes
of which 16 hold a number.  `a` is stored the same way by the model.

Both shapes the serving engine brings run the one kernel: a prefill
(B = 1, L = the bucket, n_valid = the prompt's real tokens) and a decode
step (B = slots, L = 1, n_valid = 1 for a busy slot, 0 for a free one).

`selective_scan_reference` is the definition: a `lax.scan` over the
tokens.  It is what runs off TPU (`compat.pallas_mode() == "off"`), what a
shape the kernel does not take runs, and what the training-mode model
calls (it has a gradient; the kernel has none).

On TPU the same contract is a Mosaic kernel the trace names
`kft_selective_scan`; under KFT_PALLAS=interpret its body runs in the
Pallas interpreter.  The grid walks (row, chunk of tokens).  A grid step
holds every channel of a chunk of tokens in VMEM and the row's state
[N, D] in its output block, which stays there from the row's first chunk
to its last (the block index does not move along the chunk axis) and is
the input state's own buffer (`input_output_aliases`): a decode step
rewrites the slot cache's state leaf in place.  Inside a grid step the
channels are walked in sub-tiles of `_LANES` lanes so that a sub-tile's
state [N, _LANES] lives in registers across its tokens; tokens are read
eight rows at a time.  A chunk that lies wholly in the padding is skipped,
and a row with no real token at all (a free serving slot) moves no state:
its steps point the state's blocks at a neighbouring row's, already in
VMEM, so a decode step reads and writes the states of its BUSY slots only.
b and c arrive as [B, L, N, 1]: a token's [N, 1] column is then one plain
load that the multiply broadcasts over the lanes (a column of a [N, L]
matrix at a dynamic lane is not).

The projections, the convolution, the three norms and the gate stay with
XLA (models/transformer.py `Mamba`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compat

#: the kernel's name in a device trace (benchmark/layer_metrics/ssm_*)
KERNEL_NAME = "kft_selective_scan"

#: channels of one register-resident sub-tile: [16, 512] float32 is 8 vregs
#: of state, and as many again for each of exp(delta a) and the products
_LANES = 512
#: tokens of one grid step of a long call: [128, 5120] float32 blocks of
#: x, delta and y (2.6 MB each, double-buffered) and 128 padded b and c
#: columns (1 MB each)
_CHUNK = 128
_ROWS = 8  # tokens read at once: one float32 sublane tile


def selective_scan_reference(x, delta, a, b, c, h0, n_valid):
    """The recurrence as a `lax.scan` over the tokens (the definition)."""
    B, L, _ = x.shape
    f32 = jnp.float32
    valid = jnp.arange(L)[None, :] < n_valid[:, None]            # [B, L]
    delta = jnp.where(valid[..., None], delta.astype(f32), 0.0)

    def step(h, inp):
        x_t, d_t, b_t, c_t = inp            # [B, D], [B, D], [B, N], [B, N]
        h = (jnp.exp(d_t[:, None, :] * a[None]) * h
             + (d_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    tokens_first = lambda t: jnp.moveaxis(t.astype(f32), 1, 0)  # noqa: E731
    h, y = jax.lax.scan(step, h0.astype(f32),
                        (tokens_first(x), tokens_first(delta),
                         tokens_first(b), tokens_first(c)))
    y = jnp.where(valid[..., None], jnp.moveaxis(y, 0, 1), 0.0)
    return y, h


def kernel_chunk(tokens: int, channels: int, interpret=None) -> Optional[int]:
    """Tokens of one grid step when the kernel takes a call of `tokens`
    tokens a row over `channels` channels here, None when the `lax.scan`
    form does: the one question every call site asks (as
    `decode_attn.kernel_block` is for attention)."""
    mode = compat.pallas_mode(interpret)
    lanes = min(_LANES, channels)
    if mode == "off" or channels % lanes or (mode == "compiled" and lanes % 128):
        return None  # compiled, a sub-tile is whole lanes
    if tokens <= _ROWS:
        return tokens                      # a decode or verify step: whole
    if tokens % _ROWS:
        return None
    chunk = min(_CHUNK, tokens)
    return chunk if tokens % chunk == 0 else None


# One cached trace a shape, as ops/flash.py keeps its kernels: `pl.pallas_call`
# traces its body anew at every call, and this body unrolls eight tokens in
# each of ten sub-tiles; 26 mixers un-jitted traced and lowered it 26 times a
# program (0.9 s a call on the chip's host, 296 s over a worker's nine
# programs: PERF.md section 6, PR 42).  Under `jax.jit` with everything that
# is not an array static, mixers 2..26 hit jit's trace cache and the lowered
# program holds the kernel once, called 26 times.
@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "vmem_bytes"))
def _scan_pallas(x, delta, a, b, c, h0, n_valid, *, chunk: int,
                 interpret: bool, vmem_bytes: int):
    B, L, D = x.shape
    N = a.shape[0]
    lanes = min(_LANES, D)
    rows = min(_ROWS, chunk)
    assert L % chunk == 0 and chunk % rows == 0 and D % lanes == 0, (L, D, chunk)
    f32 = jnp.float32

    # a row with no real token (a free serving slot) neither reads nor
    # writes its state: its grid steps point the state's blocks at the
    # nearest row that has one (`held`: the last such row at or before it,
    # else the first after it), whose block is then already in VMEM, and
    # touch nothing.  Only when no row has a token does a row point at
    # itself, and carries its state through unchanged
    has_token = n_valid > 0
    index = jnp.arange(B, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(has_token, index, -1))
    first = jnp.argmax(has_token).astype(jnp.int32)
    held = jnp.where(last >= 0, last,
                     jnp.where(has_token.any(), first, index))

    def kernel(n_ref, held_ref, x_ref, d_ref, a_ref, b_ref, c_ref, h0_ref,
               y_ref, h_ref):
        row, step = pl.program_id(0), pl.program_id(1)

        @pl.when(jnp.logical_and(step == 0, held_ref[row] == row))
        def _():
            h_ref[...] = h0_ref[...]

        n, base = n_ref[row], step * chunk

        @pl.when(base >= n)
        def _():                           # the whole chunk is padding
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(base < n)
        def _():
            for lo in range(0, D, lanes):
                at = pl.ds(lo, lanes)
                a_t = a_ref[:, at]                             # [N, lanes]

                def eight(i, h, at=at, a_t=a_t):
                    r0 = pl.multiple_of(i * rows, rows)
                    real = (base + r0 + jax.lax.broadcasted_iota(
                        jnp.int32, (rows, 1), 0)) < n           # [rows, 1]
                    d8 = jnp.where(real, d_ref[pl.ds(r0, rows), at], 0.0)
                    dx8 = d8 * x_ref[pl.ds(r0, rows), at]
                    ys = []
                    for j in range(rows):
                        d_t = d8[j:j + 1]                       # [1, lanes]
                        h = (jnp.exp(d_t * a_t) * h
                             + dx8[j:j + 1] * b_ref[r0 + j])    # [N, lanes]
                        ys.append(jnp.sum(h * c_ref[r0 + j], axis=0,
                                          keepdims=True))
                    y8 = ys[0] if rows == 1 else jnp.concatenate(ys, axis=0)
                    y_ref[pl.ds(r0, rows), at] = jnp.where(real, y8, 0.0)
                    return h

                h_ref[:, at] = jax.lax.fori_loop(
                    0, chunk // rows, eight, h_ref[:, at])

    tokens = lambda r, s, n, held: (r, s, 0)              # noqa: E731
    columns = lambda r, s, n, held: (r, s, 0, 0)          # noqa: E731
    state = lambda r, s, n, held: (held[r], 0, 0)         # noqa: E731
    y, h = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, L, D), f32),
                   jax.ShapeDtypeStruct((B, N, D), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, L // chunk),
            in_specs=[
                pl.BlockSpec((None, chunk, D), tokens),        # x
                pl.BlockSpec((None, chunk, D), tokens),        # delta
                pl.BlockSpec((N, D), lambda r, s, n, held: (0, 0)),  # a
                pl.BlockSpec((None, chunk, N, 1), columns),    # b
                pl.BlockSpec((None, chunk, N, 1), columns),    # c
                pl.BlockSpec((None, N, D), state),             # h0
            ],
            out_specs=(pl.BlockSpec((None, chunk, D), tokens),
                       pl.BlockSpec((None, N, D), state)),
        ),
        # operand 7 (n_valid and held are 0 and 1) is the state, rewritten
        # in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            # rows in order too: a free row's steps lean on the block the
            # row before it left in VMEM
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name=KERNEL_NAME,
    )(n_valid.astype(jnp.int32), held, x.astype(f32), delta.astype(f32),
      a.astype(f32), b.astype(f32)[..., None], c.astype(f32)[..., None],
      h0.astype(f32))
    return y, h


def selective_scan(x, delta, a, b, c, h0, n_valid, interpret=None):
    """(y [B, L, D], the state [B, N, D] after each row's last real token),
    float32: the kernel where `kernel_chunk` says so, else the `lax.scan`."""
    chunk = kernel_chunk(x.shape[1], x.shape[2], interpret)
    if chunk is None:
        return selective_scan_reference(x, delta, a, b, c, h0, n_valid)
    return _scan_pallas(
        x, delta, a, b, c, h0, n_valid, chunk=chunk,
        interpret=compat.pallas_mode(interpret) == "interpret",
        vmem_bytes=compat.vmem_budget_bytes())
