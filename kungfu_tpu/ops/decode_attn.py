"""Decode-step attention over the slot cache, by the rows a slot has written.

`decode_attention(q, cache_k, cache_v, q_pos, window)`: q [B, L, H, D] holds
the few query rows each of B slots brings to a step (a decode step's one, a
speculative verify's k), cache_k / cache_v [B, max_len, Hkv, D] the slot
cache with this step's rows already stored, q_pos [B, L] each query row's
position.  Query row (b, l) attends the cache rows m <= q_pos[b, l] of slot
b (and q_pos - m < window when the model has one).  The result is float32
[B, L, H, D].  Grouped queries read the un-repeated cache: G = H // Hkv
query heads against one KV head.

`decode_attention_reference` is the definition: the dense einsum over all
max_len rows under a mask, K and V operands in the cache dtype, scores,
softmax and accumulation in float32, probabilities cast to the cache dtype
before the product over V.  It reads the whole cache whatever the cursors
say.  It is what every shape the kernel does not take runs (a prefill
bucket, an int8 cache after its dequantisation), what the model keeps for
a cache sharded over a mesh (a Mosaic call is not partitioned by GSPMD)
and what runs off TPU (`compat.pallas_mode() == "off"`).

On TPU the same contract is a Mosaic kernel the trace names
`kft_decode_attn`; under KFT_PALLAS=interpret its body runs in the Pallas
interpreter.  The grid walks a LIST of visits, (slot, KV block) pairs
ordered by slot, and is as long as the list: its one dimension is the
traced count.  `visits` makes the list from each slot's first and last
live block (`live_blocks`) and the step's `live` mask: a slot at cursor
300 of 2,048 is two visits of 256 rows, not eight, and a slot that is not
live (it holds no request, or one whose last token is in flight) is none,
so a call's cost follows the rows busy slots have written and not the
number of slots.  List, first and last are scalar-prefetched; a visit's
index maps read its slot and block from the list, the running max, sum
and output are reset at a slot's first visit and the output written at
its last.  No grid step maps the output rows of a slot with no visit:
they are set to zero outside the kernel (`_walked`).  With every slot
live the list is every slot's run, and at max_len the whole
(slot, block) rectangle the grid once was.  Blocks are combined by online
softmax, in the einsum's arithmetic.  A K block [block, Hkv, D] is read as
the matrix [block x Hkv, D] it already is in memory: one matmul gives every query
head's score against every (row, KV head) pair, the pairs of another KV
head are masked like the rows beyond the cursor, and the probabilities,
zero there, multiply the V block the same way.  That spends Hkv times the
multiplications the scores need, on a step whose matmul unit is idle
(16 query rows), and never moves a cache block out of the layout it is
stored in.

Latent attention (MLA, models/transformer.py `MLA`) keeps ONE row a token:
`cache` [B, max_len, rank + rope] holds the normed latent c and, after it,
the rotary key part all heads share.  In the absorbed form a query head is
q [rank + rope] = [q_nope W_uk^T | q_rope], its score against a row is one
dot product over the whole row, and its value is the row's first `rank`
numbers: `mla_decode_attention(q [B, L, H, rank + rope], cache, q_pos,
rank, scale)` -> float32 [B, L, H, rank], every head against the same
rows, so a written row is read ONCE for scores and values alike and no
per-head K or V exists.  `mla_decode_attention_reference` is the dense
definition and the off-TPU path; the Mosaic kernel is `kft_mla_decode_attn`,
the same walk (one `Walk` serves both: `slot_walk`), prefetch and online
softmax over [block, rank + rope] blocks.  `kernel_block` answers for a
three-dimensional leaf as it does for a four-dimensional one.

Attention over SELECTED blocks (models/transformer.py `SparseAttention`)
is the third form, at the end of this file: `select_blocks` chooses, a
query row and a KV head, which blocks of rows to read, and
`kft_sparse_decode_attn` reads that list of chosen blocks on a static
grid where `kft_decode_attn` walks each live slot's one contiguous run
(`live_blocks`).  A prefill bucket's rows each
choose too, and `kft_sparse_prefill_attn` attends them all under the
choices' bitmap, flash-style: scores in VMEM, key tiles up to a query
tile's last position.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compat

#: the kernel's name in a device trace (benchmark/layer_metrics/decode_attn_*)
KERNEL_NAME = "kft_decode_attn"

#: the latent-attention kernel's name in a device trace
#: (benchmark/layer_metrics/mla_decode_attn_*)
MLA_KERNEL_NAME = "kft_mla_decode_attn"

#: query rows a slot the kernel takes: a decode step's 1, a verify's k.
#: The smallest prefill bucket is 16 (serving/engine.py `default_buckets`)
MAX_QUERY_ROWS = 8
#: bytes of one K (or V) block: 256 rows of 16 x 128 bf16.  Two operands,
#: double-buffered, are 4 MiB of VMEM; a DMA of 1 MiB is long enough for
#: the bandwidth-bound read, and 256 rows bound what rounding a cursor up
#: to the block fetches for nothing
_BLOCK_BYTES = 1 << 20
_MASKED = -1e30


def decode_attention_reference(q, k, v, q_pos, window: int = 0):
    """The dense form: every query row against all max_len rows of its
    slot, masked.  k, v [B, max_len, Hkv, D] in any float dtype (an int8
    cache arrives dequantised; the product fuses into the operand read)."""
    B, L, H, D = q.shape
    max_len, Hkv = k.shape[1], k.shape[2]
    # grouped-query einsum against the UN-repeated cache: decode is
    # cache-read-bound, so neither a jnp.repeat materialization
    # (x H/Hkv bytes under GQA) nor an f32 cast (x2 bytes) of the
    # cache is acceptable — group the query heads instead and keep
    # operands in the cache dtype with f32 accumulation
    qg = q.reshape(B, L, Hkv, H // Hkv, D)
    s = jnp.einsum(
        "blkgd,bmkd->bkglm", qg, k,
        preferred_element_type=jnp.float32,
    ) * (1.0 / (D ** 0.5))
    q_pos = q_pos[:, :, None]                       # [B, L, 1]
    c_pos = jnp.arange(max_len)[None, None, :]      # [1, 1, max_len]
    valid = c_pos <= q_pos                          # [B, L, max_len]
    if window:  # sliding-window models decode windowed too
        valid = jnp.logical_and(valid, q_pos - c_pos < window)
    s = jnp.where(valid[:, None, None], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bkglm,bmkd->blkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).reshape(B, L, H, D)


def kernel_block(query_rows: int, cache_shape, cache_dtype,
                 interpret=None) -> Optional[int]:
    """Rows of one KV block when the kernel takes this call here, None when
    the reference does.  From what a caller can see of the call: how many
    query rows a slot brings, the cache leaf's shape and dtype, and the
    Pallas gate.  The engine asks the same question to count the rows a
    step fetches.  (A cache sharded over a mesh is the caller's to keep
    away: models/transformer.py, attention="full".)"""
    if compat.pallas_mode(interpret) == "off":
        return None
    dtype = jnp.dtype(cache_dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None  # an int8 cache is read through its scales
    if len(cache_shape) == 3:
        # a latent leaf [B, max_len, rank + rope]: a block is the matrix
        # [block, rank + rope] as it lies, whatever its width
        (_, max_len, head_dim), kv_heads = cache_shape, 1
        if query_rows > MAX_QUERY_ROWS:
            return None
    else:
        _, max_len, kv_heads, head_dim = cache_shape
        # a block is read as [block x Hkv, D] with no relayout only when the
        # KV heads fill whole sublane tiles (8 rows of 32 bits) and D whole
        # lanes
        if (query_rows > MAX_QUERY_ROWS or head_dim % 128
                or kv_heads % (32 // dtype.itemsize)):
            return None
    block = 1 << ((_BLOCK_BYTES // (kv_heads * head_dim * dtype.itemsize))
                  .bit_length() - 1)
    while block > 8 and (block > max_len or max_len % block):
        block //= 2
    return block if max_len % block == 0 else None


def live_blocks(xp, q_lo, q_hi, block: int, max_len: int, window: int = 0):
    """(first, last) KV block a slot's query rows at positions q_lo..q_hi
    can see: rows 0..q_hi, less the rows every query's window has left.
    `xp` is numpy on the host (the engine's count of rows fetched) and
    jax.numpy in the program, so the two cannot drift apart."""
    last = xp.minimum(q_hi, max_len - 1) // block
    if window:
        first = xp.maximum(q_lo - window + 1, 0) // block
    else:
        first = xp.zeros_like(last)
    return xp.minimum(first, last), last


def visits(xp, first, last, live, length: int):
    """The (slot, block) pairs a step's attention reads, ordered by slot:
    block first[b] .. last[b] of every LIVE slot b (`live` [B] bool; None:
    every slot).  A slot that is not live holds no request, or one whose
    last token is in flight: it contributes no visit.  -> (slot [length],
    block [length], how many of them are visits): `length` is the static
    slots x blocks a slot; the places past the count hold (0, 0) and are
    never walked.  `xp` as `live_blocks` takes it: the engine counts the
    rows a step fetches from the list the program walks."""
    n = last - first + 1
    if live is not None:
        n = xp.where(live, n, 0)
    ends = xp.cumsum(n)
    starts = ends - n
    # place i of the list is slot b's where starts[b] <= i < ends[b]: a
    # [length, B] mask and two sums over it, which the compiler fuses (the
    # same list by gathers, first[slot], took 11 us at 8 slots and 39 us
    # at 32 on the chip; this takes under 2: PERF.md section 6, PR 49)
    at = xp.arange(length)[:, None]
    mine = xp.logical_and(at >= starts[None, :], at < ends[None, :])
    slot = xp.where(mine, xp.arange(n.shape[0])[None, :], 0).sum(axis=1)
    block = xp.where(mine, first[None, :] + at - starts[None, :], 0).sum(axis=1)
    return slot.astype(xp.int32), block.astype(xp.int32), ends[-1]


class Walk(NamedTuple):
    """What both kernels walk in one step: `live_blocks` of the slots'
    query rows, `visits` of those under the step's `live` mask (kept, so
    that a slot with no visit reads as zeros).  A function of the cursors
    and the mask alone, so a model builds it once a step for all its
    layers (models/transformer.py `TransformerLM`)."""
    first: jax.Array   # [B]
    last: jax.Array    # [B]
    slot: jax.Array    # [B x blocks a slot]
    block: jax.Array   # [B x blocks a slot]
    count: jax.Array   # []
    live: Optional[jax.Array]


def slot_walk(q_pos, live, block: int, max_len: int, window: int = 0) -> Walk:
    """The `Walk` of query rows at q_pos [B, L] over blocks of `block`
    rows (`kernel_block`)."""
    q_pos = q_pos.astype(jnp.int32)
    first, last = live_blocks(jnp, q_pos.min(axis=1), q_pos.max(axis=1),
                              block, max_len, window)
    return Walk(first, last, *visits(
        jnp, first, last, live, q_pos.shape[0] * (max_len // block)), live)


def _walked(call, walk: Walk, q_pos, *operands):
    """`call` (a `pl.pallas_call` over the grid `(walk.count,)`) on the
    walk's scalars and `operands`; the rows of a slot the walk does not
    visit, which no grid step wrote, read as zeros."""
    out = call(walk.slot, walk.block, walk.first, walk.last,
               q_pos.astype(jnp.int32), *operands)
    if walk.live is None:
        return out
    return jnp.where(walk.live[:, None, None], out, 0.0)


def _attn_pallas(q, cache_k, cache_v, q_pos, window: int, block: int,
                 interpret: bool, walk: Walk):
    B, L, H, D = q.shape
    max_len, Hkv = cache_k.shape[1], cache_k.shape[2]
    assert H % Hkv == 0 and max_len % block == 0, (H, Hkv, max_len, block)
    G, R, lanes = H // Hkv, L * H, block * Hkv
    scale = 1.0 / (D ** 0.5)
    assert walk.slot.shape == (B * (max_len // block),), (walk.slot.shape, B)

    def kernel(slot, block_of, first, last, pos, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref):
        i = pl.program_id(0)
        b, blk = slot[i], block_of[i]

        @pl.when(blk == first[b])
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        lo = hi = pos[b, 0]  # the slot's lowest and highest query position
        for l in range(1, L):
            lo, hi = jnp.minimum(lo, pos[b, l]), jnp.maximum(hi, pos[b, l])
        # an inner block holds only rows every query of the slot attends: no
        # row of it is beyond a cursor or out of a window
        inner = (blk + 1) * block - 1 <= lo
        if window:
            inner = jnp.logical_and(inner, blk * block > hi - window)

        def attend(edge: bool):
            # row r of the scores is query (l, h) = (r // H, r % H); lane c
            # is cache (row, KV head) = (blk * block + c // Hkv, c % Hkv)
            row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
            lane = jax.lax.broadcasted_iota(jnp.int32, (R, lanes), 1)
            valid = (jax.lax.rem(lane, Hkv)
                     == jax.lax.div(jax.lax.rem(row, H), G))
            v = v_ref[...].reshape(lanes, D)
            if edge:
                at = jnp.full((R, 1), pos[b, 0], jnp.int32)
                for l in range(1, L):
                    at = jnp.where(row >= l * H, pos[b, l], at)
                at = at - blk * block  # query position, block-relative
                valid = jnp.logical_and(valid, lane < (at + 1) * Hkv)
                if window:
                    valid = jnp.logical_and(
                        valid, lane >= (at - window + 1) * Hkv)
                # rows no query of the slot attends meet a probability of 0,
                # and 0 x NaN is NaN: whatever lies beyond the cursor (the
                # last request's rows, a prefill's padding) reads as 0
                c = jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0)
                v = jnp.where(c < (hi - blk * block + 1) * Hkv, v,
                              jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q_ref[...], k_ref[...].reshape(lanes, D),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, _MASKED)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        pl.when(inner)(lambda: attend(False))
        pl.when(jnp.logical_not(inner))(lambda: attend(True))

        @pl.when(blk == last[b])
        def _():
            norm = l_ref[...]
            o_ref[...] = acc_ref[...] / jnp.where(norm == 0.0, 1.0, norm)

    def kv_index(i, slot, block_of, first, last, pos):
        return slot[i], block_of[i], 0, 0

    def row_index(i, slot, block_of, first, last, pos):
        return slot[i], 0, 0

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, R, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(walk.count,),
            in_specs=[
                pl.BlockSpec((None, R, D), row_index),
                pl.BlockSpec((None, block, Hkv, D), kv_index),
                pl.BlockSpec((None, block, Hkv, D), kv_index),
            ],
            out_specs=pl.BlockSpec((None, R, D), row_index),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, D), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=compat.vmem_budget_bytes()),
        interpret=interpret,
        name=KERNEL_NAME,
    )
    out = _walked(call, walk, q_pos, q.astype(cache_k.dtype).reshape(B, R, D),
                  cache_k, cache_v)
    return out.reshape(B, L, H, D)


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     q_pos: jax.Array, window: int = 0, interpret=None,
                     live=None, walk: Optional[Walk] = None) -> jax.Array:
    """q [B, L, H, D] against the slot cache [B, max_len, Hkv, D] -> float32
    [B, L, H, D]: the kernel where `kernel_block` says it takes the call,
    the reference einsum elsewhere.  `live` [B] bool (None: every slot):
    the kernel visits no block of a slot that is not live and returns
    zeros for it; the einsum, which reads the whole cache anyway, takes no
    notice.  `walk`: the step's `slot_walk` of these positions, this mask
    and the kernel's block where the caller has built it already."""
    block = kernel_block(q.shape[1], cache_k.shape, cache_k.dtype, interpret)
    if block is None:
        return decode_attention_reference(q, cache_k, cache_v, q_pos, window)
    if walk is None:
        walk = slot_walk(q_pos, live, block, cache_k.shape[1], window)
    return _attn_pallas(q, cache_k, cache_v, q_pos, window, block,
                        compat.pallas_mode(interpret) == "interpret", walk)


# -- latent attention: one shared row a token ------------------------------------------


def mla_decode_attention_reference(q, cache, q_pos, rank: int, scale: float):
    """The dense absorbed form: q [B, L, H, W] against every row of
    cache [B, max_len, W] under the causal mask, values the rows' first
    `rank` numbers.  Operands in the cache dtype, scores, softmax and
    accumulation in float32, probabilities cast to the cache dtype before
    the product over the values (as `decode_attention_reference`)."""
    B, L, H, W = q.shape
    max_len = cache.shape[1]
    # the per-head reference's einsums with one KV head and H grouped
    # queries: the same contraction, in the form every backend multiplies
    rows = cache[:, :, None, :]                     # [B, max_len, 1, W]
    s = jnp.einsum("blkgd,bmkd->bkglm",
                   q.astype(cache.dtype).reshape(B, L, 1, H, W), rows,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(max_len)[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(valid[:, None, None], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkglm,bmkd->blkgd", p.astype(cache.dtype),
                      rows[..., :rank], preferred_element_type=jnp.float32
                      ).reshape(B, L, H, rank)


def _mla_attn_pallas(q, cache, q_pos, rank: int, scale: float, block: int,
                     interpret: bool, walk: Walk):
    """The kernel reads the cache feature-major, [B, W, max_len]: that is
    how the chip lays a [B, max_len, W] array out when W fills no whole
    lane tile (576 = 4.5 x 128: the compiler makes max_len the minor
    dimension rather than pad every row to 640), so the `swapaxes` below
    is a change of name and not of place, and a block [W, block] arrives
    with its positions on the lanes: the scores are a plain product
    q [R, W] x block, the values the same block contracted over its
    lanes.  Handed to the kernel position-major, the whole leaf was copied
    ahead of every call (150 MB a sublayer at the cell's shape)."""
    B, L, H, W = q.shape
    max_len = cache.shape[1]
    assert cache.shape[2] == W and max_len % block == 0, (cache.shape, W, block)
    R = L * H
    assert walk.slot.shape == (B * (max_len // block),), (walk.slot.shape, B)

    def kernel(slot, block_of, first, last, pos, q_ref, c_ref, o_ref, m_ref,
               l_ref, acc_ref):
        i = pl.program_id(0)
        b, blk = slot[i], block_of[i]

        @pl.when(blk == first[b])
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        lo = hi = pos[b, 0]  # the slot's lowest and highest query position
        for l in range(1, L):
            lo, hi = jnp.minimum(lo, pos[b, l]), jnp.maximum(hi, pos[b, l])
        # an inner block holds only rows every query of the slot attends
        inner = (blk + 1) * block - 1 <= lo

        def attend(edge: bool):
            cols = c_ref[...]                       # [W, block]: a row a lane
            if edge:
                # row r of the scores is query (l, h) = (r // H, r % H)
                row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
                lane = jax.lax.broadcasted_iota(jnp.int32, (R, block), 1)
                at = jnp.full((R, 1), pos[b, 0], jnp.int32)
                for l in range(1, L):
                    at = jnp.where(row >= l * H, pos[b, l], at)
                valid = lane <= at - blk * block
                # whatever lies beyond the cursor (the last request's rows,
                # a prefill's padding, NaN) reads as 0: a row is key and
                # value at once, and 0 x NaN is NaN
                c = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
                cols = jnp.where(c <= hi - blk * block, cols,
                                 jnp.zeros_like(cols))
            s = jnp.dot(q_ref[...], cols,
                        preferred_element_type=jnp.float32) * scale
            if edge:
                s = jnp.where(valid, s, _MASKED)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if edge:
                p = jnp.where(valid, p, 0.0)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
            # the product over the whole row: its first `rank` numbers are
            # the values, the rest is sliced off outside (the matmul unit
            # is idle at 16 query rows; the block is never cut)
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p.astype(cols.dtype), cols, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        pl.when(inner)(lambda: attend(False))
        pl.when(jnp.logical_not(inner))(lambda: attend(True))

        @pl.when(blk == last[b])
        def _():
            norm = l_ref[...]
            o_ref[...] = acc_ref[...] / jnp.where(norm == 0.0, 1.0, norm)

    def col_index(i, slot, block_of, first, last, pos):
        return slot[i], 0, block_of[i]

    def q_index(i, slot, block_of, first, last, pos):
        return slot[i], 0, 0

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, R, W), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(walk.count,),
            in_specs=[
                pl.BlockSpec((None, R, W), q_index),
                pl.BlockSpec((None, W, block), col_index),
            ],
            out_specs=pl.BlockSpec((None, R, W), q_index),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, W), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=compat.vmem_budget_bytes()),
        interpret=interpret,
        name=MLA_KERNEL_NAME,
    )
    out = _walked(call, walk, q_pos, q.astype(cache.dtype).reshape(B, R, W),
                  jnp.swapaxes(cache, 1, 2))
    return out.reshape(B, L, H, W)[..., :rank]


def mla_decode_attention(q: jax.Array, cache: jax.Array, q_pos: jax.Array,
                         rank: int, scale: float, interpret=None, live=None,
                         walk: Optional[Walk] = None) -> jax.Array:
    """q [B, L, H, rank + rope] against the latent slot cache
    [B, max_len, rank + rope] -> float32 [B, L, H, rank]: the kernel where
    `kernel_block` says it takes the call, the reference einsum elsewhere;
    `live` and `walk` as `decode_attention` takes them."""
    block = kernel_block(q.shape[1], cache.shape, cache.dtype, interpret)
    if block is None:
        return mla_decode_attention_reference(q, cache, q_pos, rank, scale)
    if walk is None:
        walk = slot_walk(q_pos, live, block, cache.shape[1])
    return _mla_attn_pallas(q, cache, q_pos, rank, scale, block,
                            compat.pallas_mode(interpret) == "interpret", walk)


# -- attention over selected blocks: a visit list a (slot, KV head) --------------------
#
# Block-selected sparse attention (models/transformer.py `SparseAttention`,
# the `minicpm4` mixer): the position axis is cut into blocks of `block`
# rows, and a query attends the rows at or before its own position of at
# most `topk` blocks, chosen for each (query row, KV head) by the query's
# scores against COMPRESSED keys (the mean of `2 x stride` keys, one every
# `stride` rows).  The leaves are [B, max_len, Hkv x D] (K, V) and
# [B, max_len / stride, Hkv x D] (the compressed keys): a KV head is a run
# of D lanes of a row, so a block of one KV head's rows is a [block, D]
# window of whole tiles whatever Hkv is (2 here: a [.., 2, D] plane has no
# such window).
#
# `select_blocks` is the choice, in XLA; `sparse_decode_attention` reads the
# chosen blocks for a decode step (the Mosaic kernel `kft_sparse_decode_attn`,
# or `sparse_decode_attention_reference`, a gather and an einsum);
# `sparse_prefill_attention` is the same choice for every row of a long call,
# made in chunks of query rows and attended in one Mosaic kernel
# (`kft_sparse_prefill_attn`) under the choices' bitmap, or, where the kernel
# does not run and in training mode, chunk by chunk as a dense product under
# the chosen blocks' mask (`sparse_prefill_attention_reference`).

#: the block-selected kernel's name in a device trace
#: (benchmark/layer_metrics/sparse_attn_*)
SPARSE_KERNEL_NAME = "kft_sparse_decode_attn"
#: chosen blocks of one grid step of that kernel: each is one [block, D]
#: DMA a K and a V (16 KB at 64 rows of 128 bf16), so a step's fixed cost
#: is shared by eight of them and its scores are one [G, 8 x block] matmul
_SPARSE_BLOCKS_A_STEP = 8
#: query rows of one chunk of a long call's selection (`select_blocks` under
#: `lax.map`: [H, 128, max_len / stride] float32 scores, 12.6 MB at 32 heads
#: and 12,288 rows).  Where the XLA form attends as well, a chunk's scores
#: over the rows are [H, 128, max_len] float32, 200 MB there, four passes
#: through HBM; where the kernel runs they do not exist
_SPARSE_QUERY_CHUNK = 128
#: the prefill kernel's name in a device trace
#: (benchmark/layer_metrics/sparse_prefill_attn_share.json); it may not
#: contain the decode kernel's, which `sparse_attn_share` buckets by
SPARSE_PREFILL_KERNEL_NAME = "kft_sparse_prefill_attn"
#: (query rows, key rows) of one of its tiles.  At 32 heads on 2 KV heads a
#: tile is 2,048 rows of scores; the key rows a step amortise its fixed work
#: (the running max, sum and output rescaled, the mask built) against a last
#: tile half beyond the cursor: 12,288 causal rows take 33.5, 19.3 and
#: 12.7 ms at 256, 512 and 1,024 key rows (PERF.md section 6, PR 48)
_SPARSE_PREFILL_TILES = (128, 1024)
_FORCED = 1e30


def visible_kernels(xp, q_pos, kernel_size: int, stride: int):
    """How many compressed keys lie wholly at or before position `q_pos`
    (key m covers rows stride x m .. stride x m + kernel_size - 1).  `xp`
    is numpy on the host (the engine's count) and jax.numpy in the program."""
    return xp.maximum((q_pos - kernel_size + 1) // stride + 1, 0)


def block_scores(q, k_cmp, q_pos, *, block: int, stride: int,
                 init_blocks: int, window: int):
    """What a query row ranks the blocks by, a KV head: q [B, L, H, D],
    k_cmp [B, Mk, Hkv x D] (compressed key m = the mean of rows
    stride x m .. stride x (m + 2) - 1 of that KV head), q_pos [B, L] ->
    float32 [B, L, Hkv, blocks]:

        p^h     = softmax_m(q_h . kbar_m / sqrt(D)) over the compressed keys
                  wholly at or before the query's position, a query head
        P_m     = the sum of p^h_m over the query heads of the KV head
        score_b = the largest P_m of the keys that overlap block b

    with `_FORCED` for the blocks always read (the first `init_blocks` and
    those that hold the `window` newest rows) and -1 for a block beyond
    the query's own."""
    B, L, H, D = q.shape
    Mk = k_cmp.shape[1]
    Hkv = k_cmp.shape[2] // D
    ratio = block // stride
    nb = Mk // ratio
    kbar = k_cmp.reshape(B, Mk, Hkv, D)
    s = jnp.einsum("blkgd,bmkd->blkgm",
                   q.astype(kbar.dtype).reshape(B, L, Hkv, H // Hkv, D), kbar,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    seen = (jnp.arange(Mk) < visible_kernels(
        jnp, q_pos, 2 * stride, stride)[..., None])[:, :, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, _MASKED), axis=-1) * seen
    p = p.sum(axis=3)                                   # [B, L, Hkv, Mk]
    # the keys that overlap block b: ratio x b - 1 (its second half lies in
    # the block's first `stride` rows) .. ratio x b + ratio - 1
    before = jnp.pad(p, ((0, 0),) * 3 + ((1, 0),))[..., :Mk]
    score = jnp.maximum(p.reshape(B, L, Hkv, nb, ratio).max(-1),
                        before.reshape(B, L, Hkv, nb, ratio)[..., 0])
    at = (q_pos // block)[..., None, None]              # the query's block
    b = jnp.arange(nb)
    forced = jnp.logical_or(b < init_blocks, b > at - window // block)
    return jnp.where(b <= at, jnp.where(forced, _FORCED, score), -1.0)


def select_blocks(q, k_cmp, q_pos, *, topk: int, **scoring):
    """The blocks each query row attends, a KV head (`block_scores`'
    arguments) -> (ids [B, L, Hkv, K] int32, n [B, L, Hkv] int32), K =
    min(topk, blocks): the first n[b, l, k] of ids[b, l, k] are the chosen
    blocks (the forced ones first, then by falling score), the rest are not
    to be read.  So: the forced blocks always, then by score, K in all;
    every block at or before the query's while those are at most K."""
    score = block_scores(q, k_cmp, q_pos, **scoring)
    vals, ids = jax.lax.top_k(score, min(topk, score.shape[-1]))
    return ids.astype(jnp.int32), (vals >= 0).sum(-1).astype(jnp.int32)


def sparse_decode_attention_reference(q, cache_k, cache_v, ids, n, q_pos,
                                      block: int):
    """The definition: q [B, L, H, D] against the rows at or before
    q_pos [B, L] of the blocks ids[b, l, k, :n[b, l, k]] of KV head k of
    cache_k / cache_v [B, max_len, Hkv x D] -> float32 [B, L, H, D].  The
    chosen blocks gathered, then `decode_attention_reference`'s arithmetic:
    operands in the cache dtype, scores, softmax and accumulation float32."""
    B, L, H, D = q.shape
    max_len, Hkv = cache_k.shape[1], cache_k.shape[2] // D
    K = ids.shape[-1]
    pick = jnp.moveaxis(ids, 1, 2)[..., None, None]     # [B, Hkv, L, K, 1, 1]

    def chosen(cache):                                  # [B, Hkv, L, K, block, D]
        blocks = cache.reshape(B, max_len // block, block, Hkv, D)
        return jnp.take_along_axis(
            jnp.transpose(blocks, (0, 3, 1, 2, 4))[:, :, None], pick, axis=3)

    k, v = chosen(cache_k), chosen(cache_v)
    s = jnp.einsum("blkgd,bkljrd->bklgjr",
                   q.astype(k.dtype).reshape(B, L, Hkv, H // Hkv, D), k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    rows = pick[..., 0] * block + jnp.arange(block)     # [B, Hkv, L, K, block]
    valid = jnp.logical_and(
        rows <= q_pos[:, None, :, None, None],
        (jnp.arange(K) < jnp.moveaxis(n, 1, 2)[..., None])[..., None])
    s = jnp.where(valid[:, :, :, None], s, _MASKED)
    p = jax.nn.softmax(s.reshape(s.shape[:4] + (-1,)), axis=-1)
    o = jnp.einsum("bklgjr,bkljrd->blkgd",
                   p.reshape(s.shape).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, L, H, D)


def sparse_kernel_takes(query_rows: int, head_dim: int, cache_dtype,
                        interpret=None) -> bool:
    """Whether `kft_sparse_decode_attn` takes this call here: one query row
    a slot, K and V in bf16 or float32, a head of whole lane tiles."""
    mode = compat.pallas_mode(interpret)
    return (mode != "off" and query_rows == 1
            and jnp.dtype(cache_dtype) in (jnp.dtype(jnp.bfloat16),
                                           jnp.dtype(jnp.float32))
            and (mode == "interpret" or head_dim % 128 == 0))


def _sparse_attn_pallas(q, cache_k, cache_v, ids, n, q_pos, block: int,
                        interpret: bool):
    """q [B, H, D], ids [B, Hkv, K], n [B, Hkv], q_pos [B].  The grid walks
    (slot, KV head, `_SPARSE_BLOCKS_A_STEP` of the listed blocks): the list
    is scalar-prefetched and each of a step's blocks is an operand of its
    own, whose index map reads its place in the list; a place past n repeats
    block n - 1, so no new DMA is issued for it, and is not computed.  The
    KV head's G = H / Hkv query heads are the rows of the tile: one
    [G, blocks x block] matmul scores them all against rows each of them
    attends (16 rows fill a bf16 tile; a tile a query head would hold one)."""
    B, H, D = q.shape
    Hkv, K = ids.shape[1], ids.shape[2]
    G = H // Hkv
    per = next(u for u in (_SPARSE_BLOCKS_A_STEP, 4, 2, 1) if K % u == 0)
    width = per * block
    scale = D ** -0.5

    def kernel(ids_ref, n_ref, pos_ref, q_ref, *refs):
        k_refs, v_refs = refs[:per], refs[per:2 * per]
        o_ref, m_ref, l_ref, acc_ref = refs[2 * per:]
        b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(j * per < n_ref[b, h])
        def _():
            # the cache row under each lane of the scores and each sublane
            # of V; a place past the list's end holds no row (-1 > no cursor)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
            sub = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
            row_l = jnp.full((1, width), jnp.iinfo(jnp.int32).max, jnp.int32)
            row_s = jnp.full((width, 1), jnp.iinfo(jnp.int32).max, jnp.int32)
            for u in range(per):
                place = j * per + u
                first = jnp.where(place < n_ref[b, h],
                                  ids_ref[b, h * K + place] * block,
                                  jnp.iinfo(jnp.int32).max - width)
                row_l = jnp.where(lane // block == u, first + lane % block, row_l)
                row_s = jnp.where(sub // block == u, first + sub % block, row_s)
            t = pos_ref[b]
            valid = row_l <= t
            k = jnp.concatenate([r[...] for r in k_refs], axis=0)
            v = jnp.concatenate([r[...] for r in v_refs], axis=0)
            # rows no query attends meet a probability of 0, and 0 x NaN is
            # NaN: whatever lies beyond the cursor reads as 0
            v = jnp.where(row_s <= t, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, _MASKED)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            norm = l_ref[...]
            o_ref[...] = acc_ref[...] / jnp.where(norm == 0.0, 1.0, norm)

    def listed(u):
        def index(b, h, j, ids, n, pos):
            place = jnp.minimum(j * per + u, n[b, h] - 1)
            return b, ids[b, h * K + place], h
        return pl.BlockSpec((None, block, D), index)

    def group(b, h, j, ids, n, pos):
        return b, h, 0, 0

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, Hkv, K // per),
            in_specs=[pl.BlockSpec((None, None, G, D), group)]
            + [listed(u) for u in range(per)] * 2,
            out_specs=pl.BlockSpec((None, None, G, D), group),
            scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, D), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=compat.vmem_budget_bytes()),
        interpret=interpret,
        name=SPARSE_KERNEL_NAME,
    )(ids.reshape(B, Hkv * K), n, q_pos.astype(jnp.int32),
      q.astype(cache_k.dtype).reshape(B, Hkv, G, D),
      *([cache_k] * per), *([cache_v] * per))
    return out.reshape(B, H, D)


def sparse_decode_attention(q, cache_k, cache_v, ids, n, q_pos, block: int,
                            interpret=None) -> jax.Array:
    """q [B, L, H, D] against the listed blocks of the slot cache
    [B, max_len, Hkv x D] -> float32 [B, L, H, D]: the kernel where
    `sparse_kernel_takes` says so, the gather and einsum elsewhere."""
    if not sparse_kernel_takes(q.shape[1], q.shape[3], cache_k.dtype, interpret):
        return sparse_decode_attention_reference(
            q, cache_k, cache_v, ids, n, q_pos, block)
    return _sparse_attn_pallas(
        q[:, 0], cache_k, cache_v, ids[:, 0], n[:, 0], q_pos[:, 0], block,
        compat.pallas_mode(interpret) == "interpret")[:, None]


def _chosen_bitmap(q, k_cmp, q_pos, *, block: int, stride: int, **choice):
    """`select_blocks`' lists as a bitmap: bool [B, L, Hkv, blocks], set
    where block b is among the first n of a (row, KV head)'s list."""
    ids, n = select_blocks(q, k_cmp, q_pos, block=block, stride=stride, **choice)
    listed = jnp.arange(ids.shape[-1]) < n[..., None]
    nb = k_cmp.shape[1] * stride // block
    return jnp.logical_and(ids[..., None] == jnp.arange(nb),
                           listed[..., None]).any(-2)


def _pad_to_chunks(q, q_pos):
    """(rows of a chunk, q, q_pos): the query rows padded to whole chunks of
    at most `_SPARSE_QUERY_CHUNK`, the padding at position 0."""
    L = q_pos.shape[1]
    chunk = min(_SPARSE_QUERY_CHUNK, L)
    pad = -L % chunk
    return (chunk, jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(q_pos, ((0, 0), (0, pad))))


def _chunked(t, chunk: int):
    """[B, L, ...] -> [L / chunk, B, chunk, ...]: what `lax.map` walks."""
    return jnp.moveaxis(
        t.reshape((t.shape[0], t.shape[1] // chunk, chunk) + t.shape[2:]), 1, 0)


def sparse_prefill_attention_reference(q, k, v, k_cmp, q_pos, *, block: int,
                                       **choice):
    """The XLA form of `sparse_prefill_attention`, and what training mode
    runs (it has a gradient): in chunks of `_SPARSE_QUERY_CHUNK` query rows,
    one after another (`lax.map`), a chunk chooses its blocks
    (`select_blocks`, with `choice`), spreads the choice to a [chunk, M] mask
    a KV head and takes the dense masked product, so no [L, M] tensor exists
    whole.  M x chunk x H float32 scores pass through memory a chunk, four
    times over, where topk x block x chunk x H are needed: what the kernel
    below is for."""
    B, L, H, D = q.shape
    M, Hkv = k.shape[1], k.shape[2] // D
    chunk, q, q_pos = _pad_to_chunks(q, q_pos)
    keys, values = k.reshape(B, M, Hkv, D), v.reshape(B, M, Hkv, D)

    def one(args):
        q_c, pos_c = args                           # [B, chunk, H, D], [B, chunk]
        with jax.named_scope("sparse.select"):
            hit = _chosen_bitmap(q_c, k_cmp, pos_c, block=block, **choice)
        with jax.named_scope("sparse.attend"):
            valid = jnp.logical_and(
                jnp.repeat(hit, block, axis=-1),
                (jnp.arange(M) <= pos_c[..., None])[:, :, None])
            s = jnp.einsum(
                "blkgd,bmkd->bkglm",
                q_c.astype(keys.dtype).reshape(B, chunk, Hkv, H // Hkv, D), keys,
                preferred_element_type=jnp.float32) * (D ** -0.5)
            valid = jnp.moveaxis(valid, 2, 1)[:, :, None]   # [B, Hkv, 1, c, M]
            p = jax.nn.softmax(jnp.where(valid, s, _MASKED), axis=-1)
            return jnp.einsum("bkglm,bmkd->blkgd", p.astype(values.dtype),
                              values, preferred_element_type=jnp.float32
                              ).reshape(B, chunk, H, D)

    o = jax.lax.map(one, (_chunked(q, chunk), _chunked(q_pos, chunk)))
    return jnp.moveaxis(o, 0, 1).reshape(B, -1, H, D)[:, :L]


def _prefill_bitmap(q, k_cmp, q_pos, **choice):
    """`_chosen_bitmap` of every row of a long call, chunk by chunk."""
    B, L = q_pos.shape
    chunk, q, q_pos = _pad_to_chunks(q, q_pos)
    hit = jax.lax.map(lambda a: _chosen_bitmap(a[0], k_cmp, a[1], **choice),
                      (_chunked(q, chunk), _chunked(q_pos, chunk)))
    return jnp.moveaxis(hit, 0, 1).reshape((B, -1) + hit.shape[3:])[:, :L]


def sparse_prefill_tiles(query_rows: int, rows: int, head_dim: int,
                         block: int, cache_dtype,
                         interpret=None) -> Optional[Tuple[int, int]]:
    """(query rows, key rows) of a tile when `kft_sparse_prefill_attn` takes
    this call here, None when the XLA chunks do.  From what a caller can see
    of the call: a prefill bucket's rows (more than `MAX_QUERY_ROWS`), the
    rows of a K leaf, a head of whole lane tiles, K and V in bf16 or
    float32, a key tile that holds whole blocks and divides the leaf."""
    mode = compat.pallas_mode(interpret)
    if (mode == "off" or query_rows <= MAX_QUERY_ROWS
            or jnp.dtype(cache_dtype) not in (jnp.dtype(jnp.bfloat16),
                                              jnp.dtype(jnp.float32))
            or (mode != "interpret" and head_dim % 128)):
        return None
    tile_q, tile_k = _SPARSE_PREFILL_TILES
    while tile_k > block and (rows % tile_k or tile_k % block):
        tile_k //= 2
    if rows % tile_k or tile_k % block or (mode != "interpret" and tile_k % 128):
        return None
    # a short bucket is one tile of its own rows, in whole sublane tiles
    return min(tile_q, -(-query_rows // 32) * 32), tile_k


@functools.partial(jax.jit, static_argnames=(
    "block", "tile_q", "tile_k", "interpret", "vmem_bytes"))
def _sparse_prefill_pallas(q, k, v, hit, q_pos, *, block: int, tile_q: int,
                           tile_k: int, interpret: bool, vmem_bytes: int):
    """q [B, L, H, D] in the leaves' dtype, k, v [B, M, Hkv x D], hit
    [B, L, Hkv, nb] bool, q_pos [B, L] -> float32 [B, L, H, D].  One cached
    program a shape (ops/flash.py `_fwd_pallas`: a model's two block-selected
    layers trace this body once).

    The grid walks (slot, KV head, query tile, key tile); the key-tile index
    of a step past the tile of the query tile's last position repeats that
    tile, so no new DMA is issued for it, and its body is skipped: a causal
    call reads and multiplies half the rows.  The G = H / Hkv query heads of
    a KV head are rows of the tile, head-major ([G x tile_q, D]: q and the
    output are read and written as they lie, [B, L, H x D], a head a run of
    D lanes, like a KV head of a K row), so one mask [tile_q, tile_k] serves
    all G.  That mask is the bitmap's row spread over `block` rows a column
    (a [tile_q, nb] x [nb, tile_k] product of zeros and ones: the matmul unit
    is the cheap place to repeat a lane) AND row <= q_pos.  Scores, running
    max, sum and output are float32 in VMEM; no score reaches HBM."""
    B, L, H, D = q.shape
    M, Hkv = k.shape[1], k.shape[2] // D
    G, nb = H // Hkv, M // block
    assert M % tile_k == 0 and tile_k % block == 0, (M, tile_k, block)
    pad, nb_pad = -L % tile_q, -nb % 128
    Lp, nq, nk = L + pad, (L + pad) // tile_q, M // tile_k
    scale = D ** -0.5
    q = jnp.pad(q.reshape(B, L, H * D), ((0, 0), (0, pad), (0, 0)))
    q_pos = jnp.pad(q_pos.astype(jnp.int32), ((0, 0), (0, pad)))
    bitmap = jnp.pad(jnp.moveaxis(hit, 2, 1).astype(jnp.int8),
                     ((0, 0), (0, 0), (0, pad), (0, nb_pad)))
    # the last position a query tile can see: the walk's end, and the row
    # past which V reads as 0
    hi = jnp.clip(q_pos.reshape(B, nq, tile_q).max(-1), 0, M - 1)

    def kernel(hi_ref, q_ref, pos_ref, hit_ref, k_ref, v_ref, o_ref,
               q_s, m_s, l_s, acc_s):
        b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        last = hi_ref[b, i]

        @pl.when(j == 0)
        def _():
            for g in range(G):
                q_s[g * tile_q:(g + 1) * tile_q, :] = q_ref[:, g * D:(g + 1) * D]
            m_s[...] = jnp.full_like(m_s, _MASKED)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        @pl.when(j * tile_k <= last)
        def _():
            first = j * tile_k
            col = first + jax.lax.broadcasted_iota(jnp.int32, (1, tile_k), 1)
            sub = first + jax.lax.broadcasted_iota(jnp.int32, (tile_k, 1), 0)
            # rows no query of the tile attends meet a probability of 0, and
            # 0 x NaN is NaN: whatever lies beyond the tile's last position
            # (the last request's rows, a free slot's) reads as 0
            v_t = v_ref[...]
            v_t = jnp.where(sub <= last, v_t, jnp.zeros_like(v_t))
            of = jax.lax.broadcasted_iota(jnp.int32, (nb + nb_pad, tile_k), 0)
            spread = (of == col // block).astype(jnp.bfloat16)
            chosen = jnp.dot(hit_ref[...].astype(jnp.bfloat16), spread,
                             preferred_element_type=jnp.float32)
            valid = jnp.logical_and(chosen > 0.5, col <= pos_ref[...])[None]
            s = jax.lax.dot_general(
                q_s[...], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s.reshape(G, tile_q, tile_k), _MASKED)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a masked place is exactly 0: exp(-1e30 - m) is, unless every
            # place of the row so far is masked and m itself is -1e30
            p = jnp.exp(s - jnp.where(m_new == _MASKED, 0.0, m_new))
            l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=2, keepdims=True)
            pv = jnp.dot(p.astype(v_t.dtype).reshape(G * tile_q, tile_k), v_t,
                         preferred_element_type=jnp.float32)
            acc_s[...] = alpha * acc_s[...] + pv.reshape(G, tile_q, D)
            m_s[...] = m_new

        @pl.when(j == nk - 1)
        def _():
            norm = l_s[...]
            o = acc_s[...] / jnp.where(norm == 0.0, 1.0, norm)
            for g in range(G):
                o_ref[:, g * D:(g + 1) * D] = o[g]

    def rows_index(b, h, i, j, hi):
        return b, i, h

    def kv_index(b, h, i, j, hi):
        return b, jnp.minimum(j, hi[b, i] // tile_k), h

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Lp, H * D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nq, nk),
            in_specs=[
                pl.BlockSpec((None, tile_q, G * D), rows_index),
                pl.BlockSpec((None, tile_q, 1),
                             lambda b, h, i, j, hi: (b, i, 0)),
                pl.BlockSpec((None, None, tile_q, nb + nb_pad),
                             lambda b, h, i, j, hi: (b, h, i, 0)),
                pl.BlockSpec((None, tile_k, D), kv_index),
                pl.BlockSpec((None, tile_k, D), kv_index),
            ],
            out_specs=pl.BlockSpec((None, tile_q, G * D), rows_index),
            scratch_shapes=[pltpu.VMEM((G * tile_q, D), k.dtype),
                            pltpu.VMEM((G, tile_q, 1), jnp.float32),
                            pltpu.VMEM((G, tile_q, 1), jnp.float32),
                            pltpu.VMEM((G, tile_q, D), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name=SPARSE_PREFILL_KERNEL_NAME,
    )(hi, q, q_pos[..., None], bitmap, k, v)
    return out[:, :L].reshape(B, L, H, D)


def sparse_prefill_attention(q, k, v, k_cmp, q_pos, *, block: int,
                             interpret=None, **choice):
    """Every row of a long call under its own choice of blocks:
    q [B, L, H, D] at q_pos [B, L] against k, v [B, M, Hkv x D] and
    k_cmp [B, M / stride, Hkv x D] -> float32 [B, L, H, D].  Where
    `sparse_prefill_tiles` says the kernel takes the call: the choice still
    chunk by chunk in XLA (`select_blocks`, with `choice`, under
    `sparse.select`), handed on as a bitmap, a byte a (row, KV head, block),
    and the attention of all rows in one `kft_sparse_prefill_attn`.  Elsewhere
    `sparse_prefill_attention_reference`.  No gradient: training mode calls
    the reference."""
    tiles = sparse_prefill_tiles(q.shape[1], k.shape[1], q.shape[3], block,
                                 k.dtype, interpret)
    if tiles is None:
        return sparse_prefill_attention_reference(
            q, k, v, k_cmp, q_pos, block=block, **choice)
    with jax.named_scope("sparse.select"):
        hit = _prefill_bitmap(q, k_cmp, q_pos, block=block, **choice)
    with jax.named_scope("sparse.attend"):
        return _sparse_prefill_pallas(
            q.astype(k.dtype), k, v, hit, q_pos, block=block,
            tile_q=tiles[0], tile_k=tiles[1],
            interpret=compat.pallas_mode(interpret) == "interpret",
            vmem_bytes=compat.vmem_budget_bytes())
