"""Grouped matmul — the expert matmuls of a sparse-expert layer.

`grouped_matmul(lhs, rhs, group_sizes)`: lhs [m, k] holds rows sorted by
group (expert), rhs [groups, k, n] one matrix a group, group_sizes [groups]
how many consecutive rows each group owns.  Row r of the result is
lhs[r] @ rhs[group of r].  No capacity, no dropped row: a group may own
every row or none.

The group sizes sum to m, or, when the caller says `leftover=True`, to
less (to nothing, even): the rows after the last group's belong to no
group and come back as zeros, whatever lhs holds there (a NaN too), and
take no gradient.  The layer routes the rows of free serving slots there
(parallel/moe.py), so the experts read are those live rows hit.  The
kernel never stores such a row (no visit owns it), so the zero is a
select on the [m, n] result, the same on every path below; a caller whose
sizes sum to m does not ask for it and gets the program it always got.

On TPU this is a Mosaic kernel the trace names `kft_moe_gmm`.  The grid
walks (n tile, visit, k tile); a *visit* is one (row tile, group) pair that
share a row, so only the groups that own rows are ever touched: a decode
step of 64 rows over 35 experts reads 35 experts' weights, not 64.  The
number of visits is a dynamic grid bound (row tiles <= visits < row tiles +
groups).  A row tile that several groups share is visited once for each,
consecutively, and each visit stores only its own rows.  The rhs tile
crosses HBM as stored (float32 from the serving worker) and is cast to the
lhs dtype in VMEM: no whole-table cast ahead of the kernel.  Accumulation
is float32.

Off TPU (`compat.pallas_mode() == "off"`) the same contract is
`jax.lax.ragged_dot`; under KFT_PALLAS=interpret the kernel body runs in
the Pallas interpreter.  The backward pass is `ragged_dot`'s own transpose
rules on every backend (the training path; PERF.md section 7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compat

#: the kernel's name in a device trace (benchmark/layer_metrics/moe_*.{json,py})
KERNEL_NAME = "kft_moe_gmm"


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _tiles(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn).  A float32 [tk, tn] rhs tile is 4 MiB at the largest
    (8 MiB double-buffered), long DMAs for the memory-bound decode step; tm
    covers a decode or verify step's rows in one tile (16 rows is the bf16
    sublane packing) and is 256 for prefills and training batches, where a
    visit's wasted rows (a tile shared by several groups is multiplied once
    for each) and the weight re-reads (once a visit) balance."""
    tm = min(256, _round_up(m, 16))
    return tm, _tile(k), _tile(n)


def _tile(d: int) -> int:
    """A dimension of the rhs whole when it is at most 1024, else the
    largest multiple of 128 (whole lanes) up to 1024 that divides it: 1024
    for 2048 and 6144, 768 for 7680 = 60 x 128."""
    if d <= 1024:
        return d
    return max((t for t in range(128, 1025, 128) if d % t == 0), default=1024)


def visit_metadata(group_sizes: jax.Array, m: int, tm: int):
    """(group_offsets [G+1], group_ids [V], m_tile_ids [V], num_visits) for
    V = m // tm + G - 1 static slots, of which the first `num_visits` are
    real.  Visits are ordered by group, hence by row tile."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first_tile = starts // tm
    visits = jnp.where(group_sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    slots = m // tm + groups - 1
    group_ids = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), visits,
                           total_repeat_length=slots)
    visit_start = jnp.cumsum(visits) - visits
    m_tile_ids = (first_tile[group_ids]
                  + jnp.arange(slots, dtype=jnp.int32) - visit_start[group_ids])
    m_tile_ids = jnp.clip(m_tile_ids, 0, m // tm - 1).astype(jnp.int32)
    return offsets, group_ids, m_tile_ids, jnp.sum(visits).astype(jnp.int32)


def _gmm_pallas(lhs, rhs, group_sizes, out_dtype, interpret: bool):
    m0, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = _tiles(m0, k, n)
    assert k % tk == 0 and n % tn == 0, (k, n, tk, tn)
    m = _round_up(m0, tm)
    if m != m0:  # padding rows belong to no group: never stored, sliced off
        lhs = jnp.pad(lhs, ((0, m - m0), (0, 0)))
    offsets, group_ids, m_tile_ids, num_visits = visit_metadata(
        group_sizes.astype(jnp.int32), m, tm)
    tiles_k = k // tk

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref, acc):
        visit, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(lhs_ref[...], rhs_ref[...].astype(lhs_ref.dtype),
                            preferred_element_type=jnp.float32)

        @pl.when(ki == tiles_k - 1)
        def _():
            g = group_ids[visit]
            row = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                   + m_tile_ids[visit] * tm)
            mine = jnp.logical_and(row >= offsets[g], row < offsets[g + 1])
            out_ref[...] = jnp.where(
                mine, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, num_visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, off, gid, mid:
                             (mid[v], ki)),
                pl.BlockSpec((None, tk, tn), lambda ni, v, ki, off, gid, mid:
                             (gid[v], ki, ni)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, v, ki, off, gid, mid:
                                   (mid[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=compat.vmem_budget_bytes()),
        interpret=interpret,
        name=KERNEL_NAME,
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
    return out[:m0]


def _ragged(lhs, rhs, group_sizes, out_dtype):
    return jax.lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32).astype(out_dtype)


def _owned(x, group_sizes):
    """x [m, n] with the rows that belong to no group set to zero."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < jnp.sum(group_sizes, dtype=jnp.int32), x,
                     jnp.zeros((), x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, out_dtype, mode, leftover):
    if mode == "off":
        out = _ragged(lhs, rhs, group_sizes, out_dtype)
    else:
        out = _gmm_pallas(lhs, rhs, group_sizes, out_dtype,
                          mode == "interpret")
    return _owned(out, group_sizes) if leftover else out


def _gmm_fwd(lhs, rhs, group_sizes, out_dtype, mode, leftover):
    return (_gmm(lhs, rhs, group_sizes, out_dtype, mode, leftover),
            (lhs, rhs, group_sizes))


def _gmm_bwd(out_dtype, mode, leftover, res, g):
    lhs, rhs, group_sizes = res
    if leftover:
        g = _owned(g, group_sizes)
    _, vjp = jax.vjp(lambda a, b: _ragged(a, b, group_sizes, out_dtype), lhs, rhs)
    d_lhs, d_rhs = vjp(g)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   out_dtype=None, interpret=None,
                   leftover: bool = False) -> jax.Array:
    """lhs [m, k] (rows sorted by group) x rhs [groups, k, n] -> [m, n].
    `leftover`: the group sizes may sum to less than m; the rows after the
    last group's come back as zeros."""
    return _gmm(lhs, rhs, group_sizes, jnp.dtype(out_dtype or lhs.dtype),
                compat.pallas_mode(interpret), bool(leftover))
