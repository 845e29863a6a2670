"""Pallas-native overlapped collectives — public wrappers over ring_kernels.

The hot collectives in ops/collective.py lower through `lax.psum` /
`ppermute` / `psum_scatter`, which XLA schedules as opaque blocks; the
int8/fp8 wire additionally pays three separate XLA ops (dequantize -> fp32
accumulate -> requantize) around each exchange.  This module exposes the
hand-scheduled alternatives:

  ring_reduce_scatter / ring_all_gather
      the RS/AG pair as double-buffered Pallas DMA kernels, layout-matched
      to `lax.psum_scatter(..., scatter_dimension=0, tiled=False)` /
      `lax.all_gather(..., tiled=False)` so interpret-mode parity against
      the XLA lowerings is a plain array compare.
  ring_all_reduce
      RS then AG — the drop-in for ops.collective.ring_all_reduce.
  fused_ring_all_reduce
      the compressed wire with the codec fused INTO the ring step: int8 /
      fp8 dequantize -> fp32 accumulate -> requantize on the VMEM-resident
      block, one kernel per leg instead of three XLA ops around an
      all_to_all (compression/collectives.py).

Every entry point resolves `compat.pallas_mode(interpret)` first:

  compiled    TPU backend — real DMA kernels on ICI.
  interpret   the Pallas interpreter (KFT_PALLAS=interpret or an explicit
              interpret=True) — the tier-1 CPU parity path: same kernel
              bodies, conservative per-hop sync.
  off         automatic fallback to the existing lax.* / compression.*
              lowerings — every training path stays green off-TPU.

Fallback also engages per call when shapes don't tile (payload exceeds the
KFT_PALLAS_VMEM_MIB scratch budget, op is not a sum/mean, a sparse or
stochastic wire config, n == 1): the wrappers never fail where the XLA
path would have worked.  `python -m kungfu_tpu.ops.pallas_collectives
--smoke` is the scripts/check.sh stage proving both the interpret path and
the clean fallback on a 2-rank CPU mesh.
"""
from __future__ import annotations

import math
import os
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import compat
from ..compression.config import CompressionConfig, resolve
from . import collective as C
from . import ring_kernels as RK

#: TPU vector lane count; chunks are shaped (rows, LANES)
LANES = 128

#: fp32 tile = 8 sublanes x 128 lanes; per-chunk padding unit
TILE = 8 * LANES

_ANY = pl.ANY


def pallas_mode(interpret: Optional[bool] = None) -> str:
    """"compiled" | "interpret" | "off" — see compat.pallas_mode."""
    return compat.pallas_mode(interpret)


def effective_impl(requested: str, interpret: Optional[bool] = None) -> str:
    """The telemetry tag a requested pallas impl resolves to here: the
    request ("pallas" | "pallas_fused") when the kernels can run, "xla"
    when the fallback will engage — so A/B attribution in spans/counters
    reflects what actually executed, not what was asked for."""
    return requested if pallas_mode(interpret) != "off" else "xla"


def _chunk_elems(total: int, n: int, multiple: int = TILE) -> int:
    """Per-chunk element count: ceil(total/n) padded up to `multiple`."""
    per = -(-total // n)
    return -(-per // multiple) * multiple


def _supported_dtype(dtype) -> bool:
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _sole_named_axis(axis_name) -> bool:
    """True when `axis_name` is the ONLY named mesh axis in scope.

    The ring kernels address their neighbor with a scalar LOGICAL
    device_id, which is only well-defined (and only implemented by the
    Pallas DMA lowering/discharge) for a single named axis — the same
    condition under which Session routes a pallas strategy to the
    kernels (`len(self._axes) == 1`).  On a multi-axis manual region
    (e.g. an fsdp ring inside a dp×fsdp shard_map) the wrappers fall
    back to the lax lowering instead of building an untraceable kernel.
    """
    from jax._src import core as _jcore

    return tuple(_jcore.get_axis_env().axis_sizes) == (axis_name,)


def _ring_ok(n: int, chunk: int, dtype, axis_name,
             cfg: Optional[CompressionConfig] = None) -> bool:
    if n <= 1 or not _sole_named_axis(axis_name):
        return False
    if cfg is None and not _supported_dtype(dtype):
        return False
    return RK.scratch_bytes(n, chunk, cfg) <= compat.vmem_budget_bytes()


# --- plain ring primitives -------------------------------------------------------------


def _rs_call(shards, axis_name: str, n: int, mode: str):
    """(n, rows, LANES) per rank -> this rank's reduced (rows, LANES)."""
    interpret = mode == "interpret"
    rows = shards.shape[1]
    kernel = RK.make_rs_kernel(n, axis_name, pipelined=not interpret)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), shards.dtype),
        in_specs=[pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.VMEM((n + 1, rows, LANES), shards.dtype),
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        interpret=interpret,
    )(shards)


def _ag_call(chunk, axis_name: str, n: int, mode: str):
    """(rows, LANES) per rank -> (n, rows, LANES) on every rank."""
    interpret = mode == "interpret"
    rows = chunk.shape[0]
    kernel = RK.make_ag_kernel(n, axis_name, pipelined=not interpret)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), chunk.dtype),
        in_specs=[pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        interpret=interpret,
    )(chunk)


def ring_reduce_scatter(x: jax.Array, axis_name: str,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Ring reduce-scatter, layout-matched to
    `lax.psum_scatter(x, axis, scatter_dimension=0, tiled=False)`: x is
    (n, ...) per rank, rank d returns row d summed across ranks."""
    n = C._axis_size(axis_name)
    mode = pallas_mode(interpret)
    row_elems = int(math.prod(x.shape[1:])) if x.ndim > 1 else 1
    chunk = -(-row_elems // TILE) * TILE
    if mode == "off" or not _ring_ok(n, chunk, x.dtype, axis_name):
        return lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=False)
    flat = x.reshape(n, row_elems)
    pad = chunk - row_elems
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    shards = flat.reshape(n, chunk // LANES, LANES)
    out = _rs_call(shards, axis_name, n, mode)
    return out.reshape(-1)[:row_elems].reshape(x.shape[1:])


def ring_all_gather(x: jax.Array, axis_name: str,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Ring all-gather, layout-matched to `lax.all_gather(x, axis,
    tiled=False)`: every rank returns (n, *x.shape)."""
    n = C._axis_size(axis_name)
    mode = pallas_mode(interpret)
    elems = int(x.size)
    chunk = -(-max(elems, 1) // TILE) * TILE
    if mode == "off" or not _ring_ok(n, chunk, x.dtype, axis_name):
        return lax.all_gather(x, axis_name, tiled=False)
    flat = x.reshape(-1)
    pad = chunk - elems
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = _ag_call(flat.reshape(chunk // LANES, LANES), axis_name, n, mode)
    return out.reshape(n, -1)[:, :elems].reshape((n,) + x.shape)


def ring_all_reduce(x: jax.Array, axis_name: str, op: str = "sum",
                    interpret: Optional[bool] = None) -> jax.Array:
    """Hand-scheduled ring allreduce: Pallas RS then AG, chunk ownership
    identical to ops.collective.ring_all_reduce's 2(n-1) schedule.  Falls
    back to that lax lowering whenever the kernels can't run here."""
    n = C._axis_size(axis_name)
    mode = pallas_mode(interpret)
    chunk = _chunk_elems(int(x.size), n)
    if (mode == "off" or op not in ("sum", "mean")
            or not _ring_ok(n, chunk, x.dtype, axis_name)):
        out = C.ring_all_reduce(x, axis_name, "sum" if op == "mean" else op)
        return out / n if op == "mean" else out
    flat = x.reshape(-1)
    pad = n * chunk - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shards = flat.reshape(n, chunk // LANES, LANES)
    mine = _rs_call(shards, axis_name, n, mode)
    full = _ag_call(mine, axis_name, n, mode)
    out = full.reshape(-1)[: x.size].reshape(x.shape)
    return out / n if op == "mean" else out


# --- fused-codec ring allreduce --------------------------------------------------------


def _fused_ok(n: int, cfg: CompressionConfig, chunk: int,
              axis_name) -> bool:
    if n <= 1 or not cfg.is_quantized or cfg.stochastic \
            or not _sole_named_axis(axis_name):
        return False
    if cfg.scheme == "fp8" and RK.FP8_DTYPE is None:
        return False
    return RK.scratch_bytes(n, chunk, cfg) <= compat.vmem_budget_bytes()


def fused_ring_all_reduce(
    x: jax.Array,
    axis_name: str,
    config: Union[None, str, CompressionConfig],
    op: str = "sum",
    interpret: Optional[bool] = None,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Quantized ring allreduce with the codec fused into the kernel body.

    Wire bytes match compression.all_reduce's RS->AG schedule (2(n-1)/n
    code-chunks + scales per peer); the difference is WHERE the codec
    runs: inside the ring step on the resident block, not as three XLA
    ops around an all_to_all.  bf16 configs run the plain ring kernel on
    bf16 data (a cast wire needs no codec).  Falls back to
    compression.all_reduce for sparse/stochastic configs, non-additive
    ops, oversized payloads, or when the Pallas gate is off — semantics
    are preserved everywhere, only the schedule changes.
    """
    from ..compression import collectives as Comp

    cfg = resolve(config)
    mode = pallas_mode(interpret)
    if cfg.scheme == "none":
        return ring_all_reduce(x, axis_name, op, interpret)
    n = C._axis_size(axis_name)
    if mode == "off" or op not in ("sum", "mean") or cfg.is_sparse:
        return Comp.all_reduce(x, axis_name, cfg, op=op, key=key)
    if cfg.scheme == "bf16":
        out = ring_all_reduce(
            x.astype(jnp.bfloat16), axis_name, "sum", interpret
        ).astype(x.dtype)
        return out / n if op == "mean" else out
    # per-chunk length must block-align for the in-kernel codec AND tile
    unit = math.lcm(cfg.block, TILE)
    chunk = _chunk_elems(int(x.size), n, multiple=unit)
    if not _fused_ok(n, cfg, chunk, axis_name):
        return Comp.all_reduce(x, axis_name, cfg, op=op, key=key)
    interp = mode == "interpret"
    orig_dtype = x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    pad = n * chunk - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nblocks = chunk // cfg.block
    shards = flat.reshape(n, nblocks, cfg.block)
    wire = RK.wire_dtype(cfg)
    sems = lambda: pltpu.SemaphoreType.DMA((n - 1,))

    mine = pl.pallas_call(
        RK.make_fused_rs_kernel(n, axis_name, cfg, pipelined=not interp),
        out_shape=jax.ShapeDtypeStruct((nblocks, cfg.block), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.VMEM((n + 1, nblocks, cfg.block), wire),
            pltpu.VMEM((n + 1, nblocks, 1), jnp.float32),
            sems(), sems(), sems(), sems(),
        ],
        interpret=interp,
    )(shards)
    if op == "mean":
        mine = mine / n
    full = pl.pallas_call(
        RK.make_fused_ag_kernel(n, axis_name, cfg, pipelined=not interp),
        out_shape=jax.ShapeDtypeStruct((n, nblocks, cfg.block), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=_ANY)],
        out_specs=pl.BlockSpec(memory_space=_ANY),
        scratch_shapes=[
            pltpu.VMEM((n, nblocks, cfg.block), wire),
            pltpu.VMEM((n, nblocks, 1), jnp.float32),
            sems(), sems(), sems(), sems(),
        ],
        interpret=interp,
    )(mine)
    return full.reshape(-1)[: x.size].reshape(x.shape).astype(orig_dtype)


# --- smoke drill (scripts/check.sh stage) ----------------------------------------------


def _smoke(np_ranks: int) -> int:
    """2-rank CPU drill: (1) Session.set_strategy(PALLAS_RING) off-TPU
    must fall back to the lax ring and still sum correctly with the span
    tag reporting "xla"; (2) under KFT_PALLAS=interpret the same session
    must run the real kernel bodies (interpret mode) bit-identically; (3)
    the fused int8 path must agree with the XLA three-op path within the
    documented quantization tolerance."""
    import numpy as np

    from ..plan import Strategy, make_mesh
    from ..session import Session

    assert pallas_mode() == "off", (
        "smoke must start with the pallas gate off (no KFT_PALLAS in env)")
    sess = Session(make_mesh(dp=np_ranks), strategy=Strategy.PALLAS_RING)
    rng = np.random.RandomState(0)
    v = rng.randint(-32, 32, size=(2048,)).astype(np.float32)
    want = np_ranks * v  # every rank lifts the same value
    got = Session.local_row(sess.all_reduce(sess.lift(v), name="smoke-fallback"))
    assert np.array_equal(got, want), "fallback ring allreduce wrong"
    assert effective_impl("pallas") == "xla"
    print(f"RESULT: pallas-smoke fallback ok (np={np_ranks}, impl=xla)")

    os.environ["KFT_PALLAS"] = "interpret"
    try:
        assert effective_impl("pallas") == "pallas"
        sess2 = Session(make_mesh(dp=np_ranks), strategy=Strategy.PALLAS_RING)
        got2 = Session.local_row(
            sess2.all_reduce(sess2.lift(v), name="smoke-interpret"))
        assert np.array_equal(got2, want), "interpret ring kernel wrong"
        print(f"RESULT: pallas-smoke interpret kernels ok (np={np_ranks})")

        sess2.set_strategy(Strategy.PALLAS_RING_FUSED)
        sess2.set_compression("int8")
        got3 = Session.local_row(
            sess2.all_reduce(sess2.lift(v), name="smoke-fused"))
        tol = (np_ranks + 1) * float(np.abs(want).max()) / 127.0
        err = float(np.abs(got3 - want).max())
        assert err <= tol, f"fused int8 error {err} > tolerance {tol}"
        print(f"RESULT: pallas-smoke fused int8 ok (max_err={err:.4f} "
              f"<= {tol:.4f})")
    finally:
        os.environ.pop("KFT_PALLAS", None)
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="kungfu_tpu.ops.pallas_collectives")
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
