#!/usr/bin/env python3
"""Time the decode step's two attention kernels alone on the chip
(`kft_decode_attn`, `kft_mla_decode_attn`: ops/decode_attn.py), at the
serving cells' shapes, over (busy slots, their cursor): the other slots are
free, at cursor 0 and not live, as the engine leaves them.

    chiprun -- python scripts/bench_decode_attn.py --out chiprun_out/decode_attn.json

One JSON line a (shape, busy, cursor): microseconds a call of the kernel
(median duration of its events in a device trace of `--iters` calls),
microseconds of the whole program around it (the output's select and
slice), microseconds of the program that builds the step's visit list
(`slot_walk`: once a step in a model, whatever its layers), rows fetched a
call by the kernel's own arithmetic, the bytes the busy slots' rows are
over the kernel's time, the largest difference from the dense einsum over
the busy slots, and whether every free slot's rows came back zero.  Runs
on any commit that has the kernels: where `decode_attention` takes no
`walk`, the call is made without one.  A CPU run says so and times nothing
worth keeping.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: name -> (slots, max_len, query heads, leaf's trailing dimensions, cases)
SHAPES = {
    # olmo-1b-serve, olmoe-1b-7b-serve: 8 slots of 2,048 rows, 16 KV heads of 128
    "dense": (8, 2048, 16, (16, 128),
              "0x0,1x300,1x1500,2x300,4x600,8x300,8x2047"),
    # longcat-flash-omni-serve: 32 slots of 4,096 latent rows of 576, 16 heads
    "latent16": (32, 4096, 16, (576,),
                 "0x0,1x600,16x600,20x1000,20x1300,32x600,32x4095"),
    # openpangu-ultra-moe-serve: the same leaf under 32 heads
    "latent32": (32, 4096, 32, (576,), "1x600,16x600,20x1300,32x4095"),
}
RANK = 512


def _traced(fn, args, iters: int, kernel: str = ""):
    """(median microseconds of the events named `kernel`, of the whole
    program) over `iters` calls of jitted `fn` in a device trace; None
    where the trace holds none (a CPU run has no device plane)."""
    import jax

    from benchmark.lib.xplane import load_trace

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        devices = load_trace(tmp)["devices"] or [{"ops": [], "modules": []}]
    median = lambda d: statistics.median(d) * 1e6 if d else None  # noqa: E731
    return (median([d for name, _, d in devices[0]["ops"]
                    if kernel and kernel in name]),
            median([d for _, _, d in devices[0]["modules"]]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--cases", default="",
                    help="comma list of BUSYxCURSOR for every shape "
                         "(default: each shape's own)")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--dtype", default="bfloat16",
                    help="the leaves' dtype (the cells': bfloat16; the CPU's "
                         "rehearsal multiplies float32 only)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import numpy as np
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.ops import decode_attn as da

    dev, dtype = jax.devices()[0], jnp.dtype(args.dtype)
    takes_walk = "walk" in inspect.signature(da.decode_attention).parameters
    given = (lambda walk: {"walk": walk}) if takes_walk else (lambda walk: {})
    rows = []
    for shape in args.shapes.split(","):
        B, max_len, H, tail, cases = SHAPES[shape]
        latent = len(tail) == 1
        r = np.random.default_rng(len(shape))
        draw = lambda *s: jnp.asarray(  # noqa: E731
            r.standard_normal(s, np.float32)).astype(dtype)
        q = draw(B, 1, H, tail[-1])
        if latent:
            # handed over feature-major, as the chip lays the leaf out in the
            # engine's program: the kernel's own swapaxes then moves nothing
            leaves = (draw(B, tail[0], max_len),)
            block = da.kernel_block(1, (B, max_len) + tail, dtype)
            name = da.MLA_KERNEL_NAME

            def kernel(q, c, pos, walk):
                return da.mla_decode_attention(
                    q, jnp.swapaxes(c, 1, 2), pos, RANK, 192 ** -0.5,
                    **given(walk))

            def einsum(q, c, pos):
                return da.mla_decode_attention_reference(
                    q, jnp.swapaxes(c, 1, 2), pos, RANK, 192 ** -0.5)
        else:
            leaves = (draw(B, max_len, *tail), draw(B, max_len, *tail))
            block = da.kernel_block(1, leaves[0].shape, dtype)
            name = da.KERNEL_NAME

            def kernel(q, k, v, pos, walk):
                return da.decode_attention(q, k, v, pos, **given(walk))

            einsum = da.decode_attention_reference
        row_bytes = int(np.prod(tail)) * dtype.itemsize * len(leaves)
        kernel, einsum = jax.jit(kernel), jax.jit(einsum)
        build, walk_us = None, None
        if takes_walk:  # one program whatever the cursors: timed once
            build = jax.jit(lambda pos, live: da.slot_walk(
                pos, live, block, max_len))
            _, walk_us = _traced(build, (jnp.zeros((B, 1), jnp.int32),
                                         jnp.ones(B, bool)), args.iters)
        for case in (args.cases or cases).split(","):
            busy, cursor = (int(v) for v in case.split("x"))
            live = np.arange(B) < busy
            pos = jnp.asarray(np.where(live, cursor, 0)[:, None], jnp.int32)
            walk = build(pos, jnp.asarray(live)) if takes_walk else None
            got = np.asarray(jax.block_until_ready(
                kernel(q, *leaves, pos, walk)))
            want = np.asarray(einsum(q, *leaves, pos))
            us, program_us = _traced(kernel, (q, *leaves, pos, walk),
                                     args.iters, name)
            needed = busy * (cursor + 1) * row_bytes
            first, last = da.live_blocks(np, np.asarray(pos)[:, 0],
                                         np.asarray(pos)[:, 0], block, max_len)
            fetched = (last - first + 1) * block
            row = {"shape": shape, "busy": busy, "cursor": cursor,
                   "platform": dev.platform, "device_kind": dev.device_kind,
                   "dtype": dtype.name, "takes_walk": takes_walk, "block": block,
                   "kernel_us": us, "program_us": program_us,
                   "walk_us": walk_us,
                   "rows_fetched": int(fetched[live].sum() if takes_walk
                                       else fetched.sum()),
                   "needed_bytes": needed,
                   "needed_gb_per_s": needed / us / 1e3 if us else None,
                   "max_abs_diff_busy": float(np.abs(
                       got[live] - want[live]).max()) if busy else 0.0,
                   "free_rows_zero": bool((got[~live] == 0).all()),
                   "finite": bool(np.isfinite(got).all())}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
