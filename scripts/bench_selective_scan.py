#!/usr/bin/env python3
"""Time the selective-scan kernel alone against the `lax.scan` form, on the
chip, at a model's own widths: a decode step (B = slots, L = 1) and
prefills (B = 1, L = a bucket).

    chiprun -- python scripts/bench_selective_scan.py --out chiprun_out/scan.json

Prints one JSON line a shape: device, the two forms' milliseconds a call
(median of `--iters` calls, each ended by block_until_ready), the largest
difference between their outputs, and the bytes a call must move over the
kernel's time.  A CPU run says so and times nothing worth keeping.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--state", type=int, default=16)
    ap.add_argument("--shapes", default="64x1,32x1,1x16,1x512,1x2048",
                    help="comma list of BxL")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import numpy as np
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.ops.selective_scan import (
        kernel_chunk, selective_scan, selective_scan_reference)

    dev = jax.devices()[0]
    D, N = args.channels, args.state
    rows = []
    for shape in args.shapes.split(","):
        B, L = (int(v) for v in shape.split("x"))
        r = np.random.default_rng(B * 10000 + L)
        f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        x = jnp.asarray(r.normal(size=(B, L, D)), jnp.bfloat16)
        delta = f(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (B, L, D))))
        a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                              (N, D))
        b, c = f(r.normal(size=(B, L, N))), f(r.normal(size=(B, L, N)))
        h0 = f(r.normal(size=(B, N, D)))
        n = jnp.full((B,), L, jnp.int32)
        forms = {"kernel": jax.jit(selective_scan),
                 "lax_scan": jax.jit(selective_scan_reference)}
        ms, outs = {}, {}
        for name, fn in forms.items():
            outs[name] = jax.block_until_ready(fn(x, delta, a, b, c, h0, n))
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, delta, a, b, c, h0, n))
                times.append((time.perf_counter() - t0) * 1e3)
            ms[name] = statistics.median(times)
        moved = (2 * B * N * D * 4 + B * L * (D * (4 + 4 + 4) + 2 * N * 4))
        row = {"shape": shape, "channels": D, "state": N,
               "platform": dev.platform, "device_kind": dev.device_kind,
               "kernel_chunk": kernel_chunk(L, D), "ms": ms,
               "max_abs_diff_y": float(jnp.abs(
                   outs["kernel"][0] - outs["lax_scan"][0]).max()),
               "max_abs_diff_h": float(jnp.abs(
                   outs["kernel"][1] - outs["lax_scan"][1]).max()),
               "bytes_moved_f32": moved,
               "kernel_gb_per_s": moved / ms["kernel"] / 1e6}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
