"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has NO long-context support (SURVEY.md §5: no ring attention,
no sequence parallelism anywhere in the tree); this module is the TPU-native
capability the reference lacks, built the way the hardware wants it: the
sequence is sharded over the `sp` mesh axis, K/V blocks rotate around the
ring (`lax.ppermute`; see `_rotate_kv`), and each
device folds one block per hop into a flash-style online-softmax
accumulator (fp32), so the full sequence never materializes on any chip.
Peak memory per chip is O(L/n), compute overlaps communication hop by hop
(hop h+1's transfer streams while the block math for hop h runs).

Use under shard_map with q/k/v sharded on the sequence dim:

    out = shard_map(lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
                    mesh=mesh, in_specs=P(None, "sp", None, None), ...)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import pallas_mode

NEG_INF = -1e30


def _rotate_kv(k, v, axis_name):
    """One ring hop of the K/V blocks.

    `lax.ppermute` by default.  Under KFT_PALLAS=interpret the hop rides
    `ops.fused_matmul.ring_shift` (one remote DMA per block, bit-identical
    to the ppermute, differentiable) so the tier-1 tests keep exercising
    that kernel; on a TPU it stays off this path until the remote-DMA
    kernels have compiled on a chip (ROADMAP S8).
    """
    if pallas_mode() == "interpret":
        from ..ops.fused_matmul import ring_shift

        return ring_shift(k, axis_name, 1), ring_shift(v, axis_name, 1)
    n = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return lax.ppermute(k, axis_name, perm), lax.ppermute(v, axis_name, perm)


def _block_attn(q, k, v, m, l, o, q_off, k_off, causal: bool, scale: float):
    """Fold one K/V block into the online-softmax accumulator.

    q: [B, Lq, H, D]   k,v: [B, Lk, Hkv, D] (Hkv divides H; grouped-query
    einsums against the UN-repeated k/v — under GQA the rotated ring
    payload and the block operands stay Hkv-sized, H/Hkv times smaller)
    m,l: [B, H, Lq]    o: [B, Lq, H, D] (fp32)
    q_off/k_off: absolute position offsets of the q and k blocks.
    """
    B, Lq, H, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Lq, Hkv, G, D)
    # query head h = khv * G + g — the same grouping order GQA models use
    s = jnp.einsum(
        "bqkgd,bmkd->bkgqm", qg, k, preferred_element_type=jnp.float32
    ).reshape(B, H, Lq, Lk) * scale
    if causal:
        q_pos = q_off + jnp.arange(Lq)
        k_pos = k_off + jnp.arange(Lk)
        mask = q_pos[:, None] >= k_pos[None, :]  # [Lq, Lk]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)  # [B, H, Lq]
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - m_new[..., None])  # [B, H, Lq, Lk]
    corr = jnp.exp(m - m_new)  # [B, H, Lq]
    l_new = l * corr + jnp.sum(p, axis=-1)
    # operands in v's dtype, f32 accumulation: an f32-cast v would force
    # the slow multi-pass MXU mode (same contract as ops/flash.py)
    pv = jnp.einsum(
        "bkgqm,bmkd->bqkgd",
        p.reshape(B, Hkv, G, Lq, Lk).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).reshape(B, Lq, H, D)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _merge_blocks(o1, lse1, o2, lse2):
    """Combine two normalized attention outputs via their log-sum-exps.

    o: [B, L, H, D] fp32 (already normalized per block); lse: [B, H, L].
    """
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(lse2 - lse).transpose(0, 2, 1)[..., None]
    return o1 * w1 + o2 * w2, lse


def _block_attn_flash(q, k, v, mode, scale):
    """Per-hop block compute on the Pallas flash kernel (ops/flash.py).

    Ring blocks are all L_chunk long, so the causal structure per hop is one
    of three whole-block cases decided by device index, never a dynamic
    offset inside the kernel: `mode` 0 = fully masked (skip), 1 = fully
    visible (non-causal kernel), 2 = diagonal (causal kernel).
    Returns (o [B, Lq, H, D] fp32 normalized, lse [B, H, Lq]).
    """
    from ..ops.flash import flash_attention_with_lse

    B, Lq, H, D = q.shape

    def skip(q, k, v):
        # derive from the operands so every switch branch agrees on vma
        # types; reduce k/v to size-1 dims so the broadcast also works for
        # GQA operands (Hkv < H)
        z = jnp.zeros_like(q, jnp.float32) + (
            k[:, :1, :1, :1] * 0 + v[:, :1, :1, :1] * 0
        ).astype(jnp.float32)
        return z, z[:, :, :, 0].transpose(0, 2, 1) + NEG_INF

    def full_blk(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=False, scale=scale)
        return o.astype(jnp.float32), lse

    def diag_blk(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True, scale=scale)
        return o.astype(jnp.float32), lse

    return lax.switch(mode, (skip, full_blk, diag_blk), q, k, v)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on `axis_name`.

    Shapes (per device): q: [B, L_chunk, H, D]; k, v: [B, L_chunk, Hkv, D]
    with Hkv dividing H (GQA kv rotates un-repeated — H/Hkv times less ICI
    traffic per hop); returns [B, L_chunk, H, D] in q's dtype.  Must be
    called inside shard_map with `axis_name` in scope.

    `impl` selects the per-block compute: "flash" streams each hop's block
    through the Pallas kernel (default wherever compat.pallas_mode lets the
    kernels run), "einsum" is the plain-XLA path (default when it is "off").
    """
    if impl is None:
        impl = "flash" if pallas_mode() != "off" else "einsum"
    if impl == "flash":
        return _ring_attention_flash(q, k, v, axis_name, causal, scale)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, Lc, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    q_off = idx * Lc

    # derive accumulators from q so they inherit q's varying-axes type (the
    # shard_map region may be manual over dp/tp as well as the sp ring axis)
    o0 = jnp.zeros_like(q, jnp.float32)
    zhl = o0[:, :, :, 0].transpose(0, 2, 1)  # [B, H, Lc] zeros
    m0 = zhl + NEG_INF
    l0 = zhl

    if n == 1:
        m, l, o = _block_attn(q, k, v, m0, l0, o0, q_off, 0, causal, scale)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    def hop(carry, s):
        k_cur, v_cur, m, l, o = carry
        # the block currently held arrived from device (idx - s) mod n
        k_off = ((idx - s) % n) * Lc
        m, l, o = _block_attn(q, k_cur, v_cur, m, l, o, q_off, k_off, causal, scale)
        k_nxt, v_nxt = _rotate_kv(k_cur, v_cur, axis_name)
        return (k_nxt, v_nxt, m, l, o), None

    # n-1 rotated hops, then fold the final block without a wasted rotation
    (k_f, v_f, m, l, o), _ = lax.scan(hop, (k, v, m0, l0, o0), jnp.arange(n - 1))
    k_off_last = ((idx - (n - 1)) % n) * Lc
    m, l, o = _block_attn(q, k_f, v_f, m, l, o, q_off, k_off_last, causal, scale)
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (padding) stay 0
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, causal, scale):
    """Ring rotation with the flash kernel as per-block compute: each hop's
    normalized (o, lse) pair merges into the running pair (logaddexp), so
    the accumulator math stays out of the kernel and stays differentiable
    (the kernel's VJP handles the lse cotangent)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, Lc, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    def mode_for(s):
        if not causal:
            return jnp.int32(1)
        src = (idx - s) % n  # device the held block originated from
        return jnp.where(src < idx, 1, jnp.where(src == idx, 2, 0)).astype(jnp.int32)

    if n == 1:
        o, lse = _block_attn_flash(q, k, v, mode_for(0), scale)
        return o.astype(q.dtype)

    # derive accumulators from q so they inherit its varying-axes type
    o0 = jnp.zeros_like(q, jnp.float32)
    lse0 = o0[:, :, :, 0].transpose(0, 2, 1) + NEG_INF  # [B, H, Lc]

    def hop(carry, s):
        k_cur, v_cur, o, lse = carry
        o_blk, lse_blk = _block_attn_flash(q, k_cur, v_cur, mode_for(s), scale)
        o, lse = _merge_blocks(o, lse, o_blk, lse_blk)
        k_nxt, v_nxt = _rotate_kv(k_cur, v_cur, axis_name)
        return (k_nxt, v_nxt, o, lse), None

    (k_f, v_f, o, lse), _ = lax.scan(hop, (k, v, o0, lse0), jnp.arange(n - 1))
    o_blk, lse_blk = _block_attn_flash(q, k_f, v_f, mode_for(n - 1), scale)
    o, _ = _merge_blocks(o, lse, o_blk, lse_blk)
    return o.astype(q.dtype)


def full_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                   window: Optional[int] = None):
    """Single-device reference implementation (for tests and small models).

    GQA-native: k/v may carry Hkv < H heads (H % Hkv == 0); the grouped
    einsums contract against the un-repeated k/v, so no head-broadcast
    copy exists in HBM.  `window` (requires causal): sliding-window mask —
    each query sees only the last `window` positions (masked here; the
    flash kernels also SKIP the dead blocks)."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qg = q.reshape(B, L, Hkv, G, D)
    s = jnp.einsum(
        "bqkgd,bmkd->bkgqm", qg, k, preferred_element_type=jnp.float32
    ) * scale  # [B, Hkv, G, Lq, Lk]
    pos = jnp.arange(L)
    if causal:
        s = jnp.where(
            (pos[:, None] >= pos[None, :])[None, None, None], s, NEG_INF
        )
    if window:
        assert window > 0, "window must be positive (None/0 = unlimited)"
        assert causal, "sliding window requires causal attention"
        s = jnp.where(
            (pos[:, None] - pos[None, :] < window)[None, None, None], s,
            NEG_INF,
        )
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bkgqm,bmkd->bqkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).reshape(B, L, H, D).astype(q.dtype)
