"""Ulysses-style sequence parallelism — all_to_all head<->sequence reshard.

The second long-context strategy (DeepSpeed-Ulysses pattern), complementing
parallel/ring_attention.py.  The reference has neither (SURVEY.md §5).

Ring attention keeps the sequence sharded and rotates K/V around the ring:
communication O(L*D) per hop, n-1 hops, compute fully local.  Ulysses
instead re-shards twice with all_to_all:

    [B, L/n, H,  D]  --all_to_all-->  [B, L, H/n, D]
        attention over the FULL sequence for this device's head group
    [B, L, H/n, D]   --all_to_all-->  [B, L/n, H,  D]

Two collectives total (plus two for K/V), each moving only 1/n of the
tensor per device — cheaper than the ring when heads >= n and the per-chip
memory can hold L * H/n * D (the full-sequence slice).  Inside the head
group the attention is plain full/flash attention, so causal masking needs
no offset bookkeeping at all.

Trade-off table (both under shard_map, q/k/v sharded on seq dim):
  ring:    memory O(L/n * H * D) per chip — longest contexts; n-1 hops
  ulysses: memory O(L * H/n * D) per chip — fewer, bigger collectives;
           requires n_heads % axis_size == 0

Use under shard_map exactly like ring_attention:

    out = shard_map(lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
                    mesh=mesh, in_specs=P(None, "sp", None, None), ...)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import pallas_mode


def _seq_to_heads(x, axis_name: str):
    """[B, L/n, H, D] (per device) -> [B, L, H/n, D]: gather seq, split heads."""
    # all_to_all: concat over the gathered axis (seq), split the head axis
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)


def _heads_to_seq(x, axis_name: str):
    """[B, L, H/n, D] -> [B, L/n, H, D]: the inverse reshard."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    attn_fn=None,
) -> jax.Array:
    """Sequence-parallel attention via head-dimension all_to_all.

    q: [B, L/n, H, D] per device (seq sharded over `axis_name`);
    k, v: [B, L/n, Hkv, D] with Hkv dividing H (GQA).  Returns
    [B, L/n, H, D].  The axis size must divide H.  When it also divides
    Hkv, the K/V all_to_alls move the UN-repeated Hkv-sized payload and
    each chip attends its query-head chunk against the matching kv-head
    chunk (contiguous-chunk grouping aligns: global q head i*H/n + j maps
    to kv head (i*H/n + j)//G = i*Hkv/n + j//G, which is exactly chip i's
    kv chunk); otherwise kv heads are broadcast up to H first (correct
    everywhere, costs the repeat).  `attn_fn(q, k, v, causal=, scale=)`
    computes attention on the full-sequence head-slice; defaults to the
    flash kernel on TPU, plain einsum elsewhere (models/transformer.py's
    "auto" rule) — both are GQA-native.
    """
    n = lax.axis_size(axis_name)
    b, l_shard, h, d = q.shape
    hkv = k.shape[2]
    if h % n:
        raise ValueError(
            f"{axis_name} axis size {n} must divide n_heads={h}"
        )
    if attn_fn is None:
        if pallas_mode() != "off":
            from ..ops.flash import flash_attention as attn_fn
        else:
            from .ring_attention import full_attention as attn_fn

    if hkv != h and hkv % n:
        # kv heads not splittable over the axis: fall back to broadcast
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    qh = _seq_to_heads(q, axis_name)  # [B, L, H/n, D]
    kh = _seq_to_heads(k, axis_name)  # [B, L, Hkv/n, D] when GQA-split
    vh = _seq_to_heads(v, axis_name)
    oh = attn_fn(qh, kh, vh, causal=causal, scale=scale)
    return _heads_to_seq(oh, axis_name)  # [B, L/n, H, D]
