"""Fused computation-collective matmuls — public wrappers over ring_kernels.

A sharded step pays its collectives as separate XLA ops that serialize
against the matmuls producing/consuming them: the forward unshard
(`lax.all_gather` then `jnp.dot`), the backward epilogue (`jnp.dot` then
`lax.psum_scatter`), and ring attention's per-hop `lax.ppermute` KV
rotation.  This module exposes the fused alternatives (arXiv 2305.06942
on the ops/ring_kernels.py DMA machinery):

  all_gather_matmul
      y = x @ concat_rows(all_gather(w_shard)) with the weight shards
      rotating hop by hop: the MXU consumes hop h's shard while hop
      h+1's remote DMA is in flight, and the gathered weight never
      materializes.  Layout-matched to
      `lax.all_gather(w, axis, tiled=True)` + `jnp.dot(..., f32)`.
  matmul_reduce_scatter
      reduce_scatter(x @ w_partial) with each row chunk's matmul
      computed directly into the outbound ring slot.  Layout-matched to
      `jnp.dot(..., f32)` + `lax.psum_scatter(..., scatter_dimension=0,
      tiled=True)`.
  ring_shift
      single-hop ring rotation (`ppermute (i -> i+shift)`) as one
      remote DMA — what ring attention's blockwise KV rotation rides
      (parallel/ring_attention.py).  Differentiable: the VJP rotates
      the cotangent backwards.

Every entry point resolves `compat.pallas_mode(interpret)` first —
compiled on TPU, the Pallas interpreter under KFT_PALLAS=interpret (the
tier-1 CPU parity path), and automatic `lax.*` fallback otherwise — and
additionally falls back per call when shapes don't fit the
KFT_PALLAS_VMEM_MIB scratch budget, the dtype is unsupported, or n == 1:
no entry point ever fails where the XLA path would have worked.
`python -m kungfu_tpu.ops.fused_matmul --smoke` is the scripts/check.sh
stage proving both the interpret path and the clean fallback on a
2-rank CPU mesh.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compat
from . import collective as C
from . import pallas_collectives as PC
from . import ring_kernels as RK

LANES = PC.LANES

_ANY = pl.ANY


def _sublanes(dtype) -> int:
    """Second-minor padding unit per dtype (TPU tiling: f32 8, bf16 16)."""
    return 16 if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16) else 8


def _pad_up(v: int, unit: int) -> int:
    return -(-max(int(v), 1) // unit) * unit


def effective_impl(requested: str = "pallas_fused_matmul",
                   interpret: Optional[bool] = None) -> str:
    """Fallback-aware telemetry tag (ops.pallas_collectives contract)."""
    return PC.effective_impl(requested, interpret)


def _pad2(a, rows: int, cols: int):
    pr, pc = rows - a.shape[-2], cols - a.shape[-1]
    if pr or pc:
        pad = [(0, 0)] * (a.ndim - 2) + [(0, pr), (0, pc)]
        a = jnp.pad(a, pad)
    return a


# --- all-gather-matmul -----------------------------------------------------------------


def all_gather_matmul(
    x: jax.Array,
    w_shard: jax.Array,
    axis_name: str,
    interpret: Optional[bool] = None,
    block_m: int = 0,
    block_n: int = 0,
) -> jax.Array:
    """y = x @ W where W = concat_rows of every rank's `w_shard`.

    x: [M, K] (local activation, full contraction dim), w_shard:
    [K/n, N] (this rank's row shard).  Returns [M, N] in x's dtype,
    fp32-accumulated.  The fused kernel never materializes W: shard c
    feeds the MXU while the next shard's DMA is in flight.  Falls back
    to `lax.all_gather(tiled=True)` + `jnp.dot` whenever the kernel
    can't run here — semantics preserved, only the schedule changes.

    block_m/block_n: MXU tile split of each per-hop dot (0 = whole
    block), against the shared VMEM budget.
    """
    n = C._axis_size(axis_name)
    mode = PC.pallas_mode(interpret)
    m, k = x.shape
    ks, nn = w_shard.shape
    if k != n * ks:
        raise ValueError(
            f"all_gather_matmul: x contraction dim {k} != n*shard rows "
            f"{n}*{ks} on axis {axis_name!r}")

    def fallback():
        w_full = lax.all_gather(w_shard, axis_name, tiled=True)
        return jnp.dot(x, w_full,
                       preferred_element_type=jnp.float32).astype(x.dtype)

    if (mode == "off" or n <= 1 or not PC._sole_named_axis(axis_name)
            or not PC._supported_dtype(x.dtype)
            or not PC._supported_dtype(w_shard.dtype)):
        return fallback()
    sub = _sublanes(w_shard.dtype)
    kp = _pad_up(ks, max(sub, LANES))  # lanes of x AND sublanes of w
    np_ = _pad_up(nn, LANES)
    mp = _pad_up(m, _sublanes(x.dtype))
    itemsize = jnp.dtype(w_shard.dtype).itemsize
    if RK.ag_matmul_scratch_bytes(n, kp, np_, mp, itemsize) \
            > compat.vmem_budget_bytes():
        return fallback()
    # x blocked by contraction chunk: block c multiplies shard W_c
    xb = _pad2(x.reshape(m, n, ks).transpose(1, 0, 2), mp, kp)
    wb = _pad2(w_shard, kp, np_)
    interp = mode == "interpret"
    out = pl.pallas_call(
        RK.make_ag_matmul_kernel(n, axis_name, pipelined=not interp,
                                 block_m=int(block_m), block_n=int(block_n)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=_ANY),
                  pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.VMEM((n, kp, np_), w_shard.dtype),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
        ],
        interpret=interp,
    )(xb, wb)
    return out[:m, :nn].astype(x.dtype)


# --- matmul-reduce-scatter -------------------------------------------------------------


def matmul_reduce_scatter(
    x: jax.Array,
    w: jax.Array,
    axis_name: str,
    interpret: Optional[bool] = None,
    block_m: int = 0,
    block_n: int = 0,
) -> jax.Array:
    """reduce_scatter over `axis_name` of the partial product x @ w.

    x: [M, K] with M divisible by n, w: [K, N] (this rank's partial
    operands).  Rank d returns rows [d·M/n, (d+1)·M/n) of the
    cross-rank sum — the ownership of `lax.psum_scatter(x @ w,
    scatter_dimension=0, tiled=True)`.  The fused kernel computes each
    row chunk's matmul directly into the outbound ring slot (partials
    travel fp32); the MXU fills the DMA drain time.  Falls back to the
    unfused dot + psum_scatter whenever the kernel can't run here.
    """
    n = C._axis_size(axis_name)
    m, k = x.shape
    nn = w.shape[1]

    def fallback():
        part = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return lax.psum_scatter(part, axis_name, scatter_dimension=0,
                                tiled=True).astype(x.dtype)

    mode = PC.pallas_mode(interpret)
    if (mode == "off" or n <= 1 or m % n != 0
            or not PC._sole_named_axis(axis_name)
            or not PC._supported_dtype(x.dtype)
            or not PC._supported_dtype(w.dtype)):
        return fallback()
    mc = m // n
    mcp = _pad_up(mc, _sublanes(x.dtype))
    kp = _pad_up(k, LANES)  # lanes of x and sublanes of w; lcm-safe
    np_ = _pad_up(nn, LANES)
    if RK.matmul_rs_scratch_bytes(n, mcp, np_) > compat.vmem_budget_bytes():
        return fallback()
    xb = _pad2(x.reshape(n, mc, k), mcp, kp)
    wb = _pad2(w, kp, np_)
    interp = mode == "interpret"
    out = pl.pallas_call(
        RK.make_matmul_rs_kernel(n, axis_name, pipelined=not interp,
                                 block_m=int(block_m), block_n=int(block_n)),
        out_shape=jax.ShapeDtypeStruct((mcp, np_), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=_ANY),
                  pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.VMEM((n + 1, mcp, np_), jnp.float32),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
        ],
        interpret=interp,
    )(xb, wb)
    return out[:mc, :nn].astype(x.dtype)


# --- single-hop ring rotation (ring attention's KV hop) --------------------------------


def _shift_impl(x, axis_name, shift, interpret):
    n = C._axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    mode = PC.pallas_mode(interpret)
    elems = int(x.size)
    rows = _pad_up(elems, _sublanes(x.dtype) * LANES) // LANES
    if (mode == "off" or n <= 1 or not PC._sole_named_axis(axis_name)
            or not PC._supported_dtype(x.dtype)
            or 2 * rows * LANES * jnp.dtype(x.dtype).itemsize
            > compat.vmem_budget_bytes()):
        return lax.ppermute(x, axis_name, perm)
    flat = x.reshape(-1)
    pad = rows * LANES - elems
    if pad:
        flat = jnp.pad(flat, (0, pad))
    interp = mode == "interpret"
    out = pl.pallas_call(
        RK.make_shift_kernel(n, axis_name, shift=shift % n),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interp,
    )(flat.reshape(rows, LANES))
    return out.reshape(-1)[:elems].reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def ring_shift(x: jax.Array, axis_name: str, shift: int = 1,
               interpret: Optional[bool] = None) -> jax.Array:
    """`lax.ppermute(x, axis, [(i, (i+shift) % n)])` as one remote DMA
    on the data plane — the hop ring attention's blockwise KV rotation
    rides.  Differentiable (the VJP rotates the cotangent by -shift);
    falls back to the ppermute lowering whenever the kernel can't run."""
    return _shift_impl(x, axis_name, shift, interpret)


def _shift_fwd(x, axis_name, shift, interpret):
    return _shift_impl(x, axis_name, shift, interpret), None


def _shift_bwd(axis_name, shift, interpret, _res, g):
    return (_shift_impl(g, axis_name, -shift, interpret),)


ring_shift.defvjp(_shift_fwd, _shift_bwd)


# --- smoke drill (scripts/check.sh stage) ----------------------------------------------


def _smoke(np_ranks: int) -> int:
    """2-rank CPU drill mirroring pallas_collectives --smoke: (1) with
    the pallas gate off every fused entry point must produce the exact
    lax result through the clean fallback; (2) under KFT_PALLAS=interpret
    the real kernel bodies must be bit-identical on integer-valued
    payloads (all-gather-matmul, matmul-reduce-scatter and the
    ring-shift hop); (3) gradients flow through the custom-VJP wrapper
    and match the XLA transpose."""
    import numpy as np

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    assert PC.pallas_mode() == "off", (
        "smoke must start with the pallas gate off (no KFT_PALLAS in env)")
    n = np_ranks
    mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
    rng = np.random.RandomState(0)
    m, ks, nn = 24, 40, 72  # deliberately non-tiling shapes
    x = rng.randint(-8, 8, size=(m, n * ks)).astype(np.float32)
    w = rng.randint(-8, 8, size=(n, ks, nn)).astype(np.float32)

    def shmap(fn, in_specs, out_specs):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    xs = np.broadcast_to(x, (n,) + x.shape)
    spec = P("dp")
    ag_fn = shmap(lambda xx, ww: all_gather_matmul(xx[0], ww[0], "dp"),
                  (spec, spec), spec)
    want_ag = x @ w.reshape(n * ks, nn)

    got = np.asarray(ag_fn(xs, w))[:m]
    assert np.array_equal(got, want_ag), "fallback all_gather_matmul wrong"
    assert effective_impl() == "xla"
    print(f"RESULT: fused-matmul smoke fallback ok (np={n}, impl=xla)")

    os.environ["KFT_PALLAS"] = "interpret"
    try:
        assert effective_impl() == "pallas_fused_matmul"
        got = np.asarray(ag_fn(xs, w))[:m]
        assert np.array_equal(got, want_ag), \
            "interpret all_gather_matmul != unfused reference"

        # matmul-reduce-scatter vs dot + psum_scatter
        m2 = 4 * n
        x2 = rng.randint(-8, 8, size=(n, m2, ks)).astype(np.float32)
        rs_fn = shmap(lambda xx, ww: matmul_reduce_scatter(
            xx[0], ww[0], "dp"), (spec, spec), spec)
        got2 = np.asarray(rs_fn(x2, w))
        want2 = np.add.reduce([x2[i] @ w[i] for i in range(n)])
        want2 = want2.reshape(n, m2 // n, nn)
        assert np.array_equal(got2.reshape(want2.shape), want2), \
            "interpret matmul_reduce_scatter != unfused reference"

        # ring shift parity vs the lax lowering
        v = rng.randint(-8, 8, size=(n, 48)).astype(np.float32)
        sh = shmap(lambda vv: ring_shift(vv[0], "dp"), spec, spec)
        got4 = np.asarray(sh(v)).reshape(n, -1)
        assert np.array_equal(got4, np.roll(v, 1, axis=0)), "ring_shift wrong"
        print(f"RESULT: fused-matmul smoke interpret kernels ok (np={n})")

        # gradients flow through the custom VJP (the cotangent rotates back)
        def loss(vv):
            return jnp.sum(ring_shift(vv[0], "dp") ** 2)

        g = shmap(jax.grad(loss), spec, spec)(jnp.asarray(v))
        assert np.allclose(np.asarray(g).reshape(n, -1), 2.0 * v), \
            "ring_shift VJP wrong"
        print("RESULT: fused-matmul smoke custom-VJP gradients ok")
    finally:
        os.environ.pop("KFT_PALLAS", None)
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="kungfu_tpu.ops.fused_matmul")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--np", type=int, default=2)
    args = ap.parse_args(argv)
    if not args.smoke:
        ap.error("nothing to do (pass --smoke)")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.np}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
    return _smoke(args.np)


if __name__ == "__main__":
    import sys

    sys.exit(main())
