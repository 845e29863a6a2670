"""Compressed variants of the collective primitives (ops/collective.py).

The uncompressed primitives let XLA move fp32/bf16 bytes; these move *codes*.
The quantized allreduce is the EQuARX schedule re-expressed with portable
collectives:

  RS leg   each peer blocks+quantizes the shard destined for every other
           peer, `all_to_all` moves int8/fp8 codes + per-block scales, and
           the receiver dequantizes and accumulates **in fp32** — so the
           reduction itself is exact given the quantized inputs (no code-
           space wraparound, no double-quantization of partial sums).
  AG leg   the reduced fp32 shard is requantized once and `all_gather`
           moves codes again.

Bytes on the wire per peer: 2·(n-1)/n·N codes + scales instead of
2·(n-1)/n·N·4 bytes — ~3.9x fewer for int8 at block=256.  Error: one
quantization on each leg, so |err| <= absmax_block/127 per element ("scale-
dependent tolerance" — see docs/compression.md for the exact bound).

All functions are pure and must run under shard_map/pjit with the axis in
scope, exactly like ops/collective.py.  `config` is static (hashable
dataclass): switching bit-width = tracing/compiling the other program,
which is the same cost model as a strategy swap.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import collective as C
from .config import AxisCompression, CompressionConfig, resolve, resolve_for_axis
from .quant import QTensor, dequantize, pad_to_block, quantize, sparsify

AxisName = Union[str, Tuple[str, ...]]


def _leg_keys(key: Optional[jax.Array], axis_name: AxisName, cfg: CompressionConfig):
    """Two per-peer-decorrelated keys (RS leg, AG leg) for stochastic
    rounding; (None, None) when the config doesn't dither."""
    if not (cfg.is_quantized and cfg.stochastic):
        return None, None
    if key is None:
        key = jax.random.PRNGKey(0)
    idx = C._flat_axis_index(axis_name)
    key = jax.random.fold_in(key, idx)
    k1, k2 = jax.random.split(key)
    return k1, k2


def all_reduce(
    x: jax.Array,
    axis_name: AxisName,
    config: Union[None, str, CompressionConfig] = None,
    op: str = "sum",
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Allreduce with a compressed wire format.

    none -> ops.collective.all_reduce; bf16 -> cast/psum/cast; int8/fp8 ->
    quantized reduce-scatter + all-gather.  Non-additive ops (min/max/prod)
    fall back to the uncompressed path: quantized code spaces don't compose
    with them blockwise.
    """
    cfg = resolve(config)
    if cfg.is_sparse:
        raise ValueError(
            f"{cfg.scheme} is a sparsifier for pair exchange, not an "
            "allreduce wire format; use topk/randk with sparse_pair_exchange"
        )
    if cfg.scheme == "none" or op not in ("sum", "mean"):
        return C.all_reduce(x, axis_name, op)
    if cfg.scheme == "bf16":
        out = C.all_reduce(x.astype(jnp.bfloat16), axis_name, "sum").astype(x.dtype)
        if op == "mean":
            out = out / C._axis_size(axis_name)
        return out
    return _quantized_rs_ag(x, axis_name, cfg, op, key)


def _quantized_rs_ag(
    x: jax.Array,
    axis_name: AxisName,
    cfg: CompressionConfig,
    op: str,
    key: Optional[jax.Array],
) -> jax.Array:
    n = C._axis_size(axis_name)
    if n == 1:
        return x
    k_rs, k_ag = _leg_keys(key, axis_name, cfg)
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    # pad so every peer's shard is a whole number of quantization blocks
    pad = (-flat.size) % (n * cfg.block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shards = flat.reshape(n, -1)  # row d = the shard destined for peer d

    # RS leg: quantize per-destination shards, all_to_all the codes, then
    # dequantize each peer's contribution and accumulate in fp32
    qt = quantize(shards, cfg, k_rs)
    data = lax.all_to_all(qt.data, axis_name, split_axis=0, concat_axis=0)
    scale = lax.all_to_all(qt.scale, axis_name, split_axis=0, concat_axis=0)
    acc = jnp.sum(dequantize(QTensor(data, scale)), axis=0)  # (shard_len,) f32
    if op == "mean":
        acc = acc / n

    # AG leg: requantize the reduced shard once, gather codes, dequantize
    qt2 = quantize(acc, cfg, k_ag)
    data2 = lax.all_gather(qt2.data, axis_name)
    scale2 = lax.all_gather(qt2.scale, axis_name)
    out = dequantize(QTensor(data2, scale2)).reshape(-1)
    return out[: x.size].reshape(x.shape).astype(orig_dtype)


def cross_all_reduce(
    x: jax.Array,
    dcn_axis: str,
    config: Union[None, str, CompressionConfig] = None,
    op: str = "sum",
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Compressed CrossAllReduce (reference session/allreduce.go:38): reduce
    over the slow DCN axis only, quantized on the wire.  This is the highest-
    value placement for compression — DCN bandwidth is the bottleneck the
    hierarchical strategies exist to protect."""
    return all_reduce(x, dcn_axis, config, op=op, key=key)


def hierarchical_all_reduce(
    x: jax.Array,
    ici_axis: str,
    dcn_axis: str,
    ici_config: Union[None, str, CompressionConfig] = None,
    dcn_config: Union[None, str, CompressionConfig] = None,
    op: str = "sum",
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Two-level allreduce with per-axis wire formats.

    ici reduce-scatter -> compressed dcn allreduce -> ici all-gather.  The
    canonical config is ici_config=None (ICI is fast and short), dcn_config=
    int8 (DCN is the slow leg); both legs accept any dense config.
    """
    ici_cfg = resolve(ici_config)
    dcn_cfg = resolve(dcn_config)
    if op not in ("sum", "mean"):
        return C.all_reduce(C.all_reduce(x, ici_axis, op), dcn_axis, op)
    n = C._axis_size(ici_axis)
    world = n * C._axis_size(dcn_axis)
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    # shard length must block-align for BOTH legs' quantizers
    import math

    blk = math.lcm(ici_cfg.block if ici_cfg.is_quantized else 1,
                   dcn_cfg.block if dcn_cfg.is_quantized else 1)
    pad = (-flat.size) % (n * blk)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shards = flat.reshape(n, -1)

    if ici_cfg.is_quantized:
        k_rs, k_ag = _leg_keys(key, ici_axis, ici_cfg)
        qt = quantize(shards, ici_cfg, k_rs)
        data = lax.all_to_all(qt.data, ici_axis, split_axis=0, concat_axis=0)
        scale = lax.all_to_all(qt.scale, ici_axis, split_axis=0, concat_axis=0)
        scat = jnp.sum(dequantize(QTensor(data, scale)), axis=0)
    else:
        k_ag = _leg_keys(key, ici_axis, ici_cfg)[1]
        # tiled=False: the scatter dim (== axis size) is squeezed -> (shard_len,)
        scat = lax.psum_scatter(shards, ici_axis, scatter_dimension=0, tiled=False)

    # cross-host leg: every local rank reduces its shard over DCN, compressed
    scat = all_reduce(scat, dcn_axis, dcn_cfg, op="sum", key=key)
    if op == "mean":
        scat = scat / world

    if ici_cfg.is_quantized:
        qt2 = quantize(scat, ici_cfg, k_ag)
        out = dequantize(
            QTensor(lax.all_gather(qt2.data, ici_axis),
                    lax.all_gather(qt2.scale, ici_axis))
        ).reshape(-1)
    else:
        out = lax.all_gather(scat, ici_axis, tiled=True)
    return out[: x.size].reshape(x.shape).astype(orig_dtype)


def group_all_reduce(
    xs: Sequence[jax.Array],
    axis_name: AxisName,
    config: Union[None, str, CompressionConfig] = None,
    op: str = "sum",
    key: Optional[jax.Array] = None,
):
    """Compressed allreduce over a tensor list (one program when jitted
    together — the group/fuse discussion in Session.group_all_reduce)."""
    if key is not None:
        keys = jax.random.split(key, len(list(xs)))
    else:
        keys = [None] * len(list(xs))
    return [all_reduce(x, axis_name, config, op=op, key=k)
            for x, k in zip(xs, keys)]


def sparse_pair_exchange(
    x: jax.Array,
    axis_name: str,
    perm: Sequence[Tuple[int, int]],
    config: Union[str, CompressionConfig],
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Sparsified directed pair averaging (the gossip path's wire diet).

    Each peer sends only the top-k (or a random-k subset) of its tensor's
    coordinates along the pairing permutation; the receiver averages the
    exchanged coordinates and keeps the rest of its own tensor unchanged:

        x_i[idx_j] <- (x_i[idx_j] + vals_j) / 2,   everything else untouched

    Wire bytes: k·n·8 (f32 value + i32 index) instead of n·4 — at k=1% a
    ~50x thinner pull than the dense ppermute exchange, with gossip's usual
    tolerance for partial mixing (AD-PSGD converges under stale/partial
    pulls by design).
    """
    cfg = resolve(config)
    if not cfg.is_sparse:
        raise ValueError(f"sparse_pair_exchange needs topk/randk, got {cfg.scheme!r}")
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    vals, idx = sparsify(flat, cfg, key)
    recv_vals = lax.ppermute(vals, axis_name, list(perm))
    recv_idx = lax.ppermute(idx, axis_name, list(perm))
    mixed = flat.at[recv_idx].set(0.5 * (flat[recv_idx] + recv_vals))
    return mixed.reshape(x.shape).astype(orig_dtype)


def compressed_pair_average(
    x: jax.Array,
    axis_name: str,
    perm: Sequence[Tuple[int, int]],
    config: Union[None, str, CompressionConfig] = None,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Directed pair averaging with a selectable wire format — the gossip
    pull (optimizers/gossip.py) with its bytes dieted.

    Dense schemes (bf16/int8/fp8) quantize the pulled model: the partner's
    tensor crosses the wire as codes and the average runs in fp32.  Sparse
    schemes exchange only k·n coordinates (sparse_pair_exchange).  none is
    the plain dense exchange.
    """
    cfg = resolve(config)
    if cfg.is_sparse:
        return sparse_pair_exchange(x, axis_name, perm, cfg, key)
    if cfg.scheme == "none":
        other = lax.ppermute(x, axis_name, list(perm))
        return (x + other) * 0.5
    orig_dtype = x.dtype
    flat = pad_to_block(x.astype(jnp.float32).reshape(-1), cfg.block)
    qt = quantize(flat, cfg, key)
    other = dequantize(
        QTensor(
            lax.ppermute(qt.data, axis_name, list(perm)),
            lax.ppermute(qt.scale, axis_name, list(perm)),
        )
    )[: x.size].reshape(x.shape)
    return (0.5 * (x.astype(jnp.float32) + other)).astype(orig_dtype)
