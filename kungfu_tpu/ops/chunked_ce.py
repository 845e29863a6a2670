"""Chunked lm-head cross-entropy: loss without materializing [N, V] logits.

At GPT scales the logits tensor dominates activation memory and HBM
traffic: batch 8 x seq 2048 x 32k vocab in f32 is ~2 GB forward plus the
same again for its cotangent — often more than the whole transformer
stack.  XLA cannot fuse away a tensor that crosses the loss boundary, so
this op streams the head matmul + online log-softmax over vocab blocks
(the same running-max/running-sum refactoring flash attention uses along
the sequence axis, applied to the vocab axis), and the custom VJP
recomputes each block's logits in backward instead of saving them.

Peak extra memory drops from O(N*V) to O(N*block); the weight gradient is
still O(D*V) (unavoidable — it is the gradient).

No reference analog (the reference ships no model/loss code); this is a
beyond-parity TPU memory/bandwidth optimization in the spirit of its
perf-first benchmark culture (README.md:203-219).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def resolve_ce_block(block: Optional[int], n_tokens: Optional[int] = None,
                     vocab: Optional[int] = None) -> int:
    """The vocab chunk size the streaming head actually runs with.

    An explicit int always wins; None asks, in order: the KFT_CE_BLOCK
    env knob, then `default_ce_block` (streams ~64 MiB logit blocks,
    clamped to [512, 8192]).
    Malformed env values fall through rather than wedge a trace.
    """
    if block:
        return int(block)
    env = os.environ.get("KFT_CE_BLOCK", "").strip()
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    return default_ce_block(n_tokens, vocab)


def default_ce_block(n_tokens: Optional[int] = None,
                     vocab: Optional[int] = None) -> int:
    """Shape-conditional chunked-CE block default: stream ~64 MiB logit
    blocks (f32), clamped to [512, 8192] powers of two.  With no token
    count known, 2048 (the historical default)."""
    if not n_tokens or n_tokens <= 0:
        return 2048
    target = (64 << 20) // (4 * n_tokens)
    block = 512
    while block * 2 <= target and block < 8192:
        block *= 2
    if vocab:
        while block > vocab and block > 512:
            block //= 2
    return block


def _pad_w(w: jax.Array, block: int):
    d, v = w.shape
    nb = -(-v // block)
    pad = nb * block - v
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    return w, nb, v


def chunked_lm_head_ll(h, w, targets, block: Optional[int] = None):
    """Streaming log-likelihood of `targets` under softmax(h @ w).

    h: [N, D] (any float dtype; matmul runs in f32 like the dense head),
    w: [D, V], targets: [N] int32.  `block=None` resolves the vocab chunk
    through `resolve_ce_block` (env, then the shape default).
    Returns (ll [N] f32, log_z [N] f32) — log-probability of the target
    and the log-normalizer (for PaLM z-loss), matching the dense
    `_token_ll` contract.
    """
    return _chunked_lm_head_ll(
        h, w, targets, resolve_ce_block(block, int(h.shape[0]),
                                        int(w.shape[1])))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_lm_head_ll(h, w, targets, block: int):
    ll, log_z, _ = _forward(h, w, targets, block)
    return ll, log_z


def _forward(h, w, targets, block):
    n, d = h.shape
    hf = h.astype(jnp.float32)
    w_pad, nb, v = _pad_w(w.astype(jnp.float32), block)

    def body(carry, j):
        m, s, tl = carry
        w_j = lax.dynamic_slice_in_dim(w_pad, j * block, block, axis=1)
        logits = hf @ w_j  # [N, block] f32
        col = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        logits = jnp.where(col < v, logits, NEG_INF)
        bm = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m, bm)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1
        )
        in_blk = (targets >= j * block) & (targets < (j + 1) * block)
        idx = jnp.clip(targets - j * block, 0, block - 1)
        picked = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        tl = jnp.where(in_blk, picked, tl)
        return (m_new, s, tl), None

    init = (
        jnp.full((n,), NEG_INF, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.full((n,), NEG_INF, jnp.float32),
    )
    (m, s, tl), _ = lax.scan(body, init, jnp.arange(nb))
    log_z = m + jnp.log(s)
    return tl - log_z, log_z, (m, s)


def _fwd_vjp(h, w, targets, block):
    ll, log_z, _ = _forward(h, w, targets, block)
    return (ll, log_z), (h, w, targets, log_z)


def _bwd_vjp(block, res, cts):
    h, w, targets, log_z = res
    d_ll, d_logz = cts
    n, d = h.shape
    hf = h.astype(jnp.float32)
    w_pad, nb, v = _pad_w(w.astype(jnp.float32), block)

    # d logits = d_ll * (onehot - p) + d_logz * p, streamed per block
    def body(carry, j):
        dh, dw = carry
        w_j = lax.dynamic_slice_in_dim(w_pad, j * block, block, axis=1)
        logits = hf @ w_j
        col = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        logits = jnp.where(col < v, logits, NEG_INF)
        p = jnp.exp(logits - log_z[:, None])  # [N, block]
        onehot = (col == targets[:, None]).astype(jnp.float32)  # [N, block]
        # ll = tl - log_z:  d ll / d logits    = onehot - p
        #                   d log_z / d logits = p
        # => dlogits = d_ll * (onehot - p) + d_logz * p
        #            = d_ll * onehot + (d_logz - d_ll) * p
        dlogits = d_ll[:, None] * onehot + (d_logz - d_ll)[:, None] * p
        dh = dh + dlogits @ w_j.T
        dw = lax.dynamic_update_slice_in_dim(
            dw, hf.T @ dlogits, j * block, axis=1
        )
        return (dh, dw), None

    init = (
        jnp.zeros((n, d), jnp.float32),
        jnp.zeros_like(w_pad),
    )
    (dh, dw_pad), _ = lax.scan(body, init, jnp.arange(nb))
    dw = dw_pad[:, :v]
    return dh.astype(h.dtype), dw.astype(w.dtype), None


_chunked_lm_head_ll.defvjp(_fwd_vjp, _bwd_vjp)
