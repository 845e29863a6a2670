#!/usr/bin/env python3
"""Time a block-selected layer's prefill attention alone, on the chip, at a
model's own widths: the XLA chunks (`sparse_prefill_attention_reference`)
against the selection plus the kernel (`sparse_prefill_attention`), and the
kernel by itself over tile sizes.

    chiprun -- python scripts/bench_sparse_prefill.py --out chiprun_out/sparse_prefill.json

Prints one JSON line a form: device, milliseconds a call (median of `--iters`
calls, each ended by block_until_ready), the largest difference from the XLA
chunks' output, and the kernel's share of the bf16 peak by the products of a
causal call.  A CPU run says so and times nothing worth keeping.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=12288,
                    help="query rows of the call and rows of the leaves")
    ap.add_argument("--cursor", type=int, default=0,
                    help="position of the call's first row")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--stride", type=int, default=16)
    ap.add_argument("--topk", type=int, default=64)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--tiles", default="128x512,128x256,64x512,256x512",
                    help="comma list of query rows x key rows of a tile")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from kungfu_tpu import compat
    from kungfu_tpu.models.transformer import _compressed_keys
    from kungfu_tpu.ops import decode_attn as da

    dev = jax.devices()[0]
    L, H, Hkv, D = args.rows, args.heads, args.kv_heads, args.head_dim
    M = L + args.cursor
    M += -M % args.block
    choice = dict(block=args.block, stride=args.stride, topk=args.topk,
                  init_blocks=1, window=args.window)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (1, L, H, D), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (1, M, Hkv * D), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (1, M, Hkv * D), jnp.float32).astype(dt)
    k_cmp = _compressed_keys(k, args.stride).astype(dt)
    pos = args.cursor + jnp.arange(L)[None, :]

    def timed(fn, *xs):
        out = jax.block_until_ready(fn(*xs))
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            times.append((time.perf_counter() - t0) * 1e3)
        return out, statistics.median(times)

    base = {"rows": L, "leaf_rows": M, "cursor": args.cursor, "heads": H,
            "kv_heads": Hkv, "head_dim": D, "platform": dev.platform,
            "device_kind": dev.device_kind}
    rows = []

    def report(**row):
        rows.append(dict(base, **row))
        print(json.dumps(rows[-1]), flush=True)

    want, ms = timed(jax.jit(functools.partial(
        da.sparse_prefill_attention_reference, **choice)), q, k, v, k_cmp, pos)
    report(form="xla_chunks", ms=ms)
    got, ms = timed(jax.jit(functools.partial(
        da.sparse_prefill_attention, **choice)), q, k, v, k_cmp, pos)
    report(form="select+kernel", ms=ms,
           tiles=da.sparse_prefill_tiles(L, M, D, args.block, dt),
           max_abs_diff=float(jnp.abs(got - want).max()))

    hit, ms = timed(jax.jit(functools.partial(da._prefill_bitmap, **choice)),
                    q, k_cmp, pos)
    report(form="select", ms=ms)
    # the products of the rows at or before each query's, both matmuls
    flop = 4.0 * H * D * sum(min(int(p), M - 1) + 1 for p in pos[0])
    for tile in args.tiles.split(","):
        tq, tk = (int(x) for x in tile.split("x"))
        fn = functools.partial(
            da._sparse_prefill_pallas, block=args.block, tile_q=tq, tile_k=tk,
            interpret=compat.pallas_mode() == "interpret",
            vmem_bytes=compat.vmem_budget_bytes())
        try:
            got, ms = timed(fn, q, k, v, hit, pos)
        except Exception as e:  # a tile the compiler refuses is a finding
            report(form="kernel", tile=tile, error=str(e)[-400:])
            continue
        report(form="kernel", tile=tile, ms=ms,
               max_abs_diff=float(jnp.abs(got - want).max()),
               causal_tflop=flop / 1e12,
               share_of_bf16_peak=flop / (ms * 1e-3) / 197e12)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
