"""Compressed-allreduce A/B benchmark: fp32 vs bf16 vs int8 (vs fp8).

The EQuARX-style claim this repo needs a number for: how many bytes does a
gradient allreduce put on the wire per scheme, what does the quantized
schedule cost in step time on this backend, and how large is the error.
One JSON line (BENCH-parseable) + grep-able RESULT lines:

    python -m kungfu_tpu.benchmarks --bench compression [--size 4194304]

On the CPU host the wall-clock column measures the schedule's overhead, not
real wire time (virtual devices share memory); bytes-on-wire is computed
from the wire format (config.wire_bytes) and is exact on any backend —
that is the column the BENCH record keys on.  On a real multi-host slice
the time column becomes the DCN win.
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

GiB = float(1 << 30)

#: scheme sweep: registered CompressionConfig names (fp32 == none)
DEFAULT_SCHEMES = ("fp32", "bf16", "int8", "int8-sr", "fp8")


def _cfg_of(scheme: str):
    from .. import compression as Comp

    return Comp.resolve("none" if scheme == "fp32" else scheme)


def bench_compression(
    size: int = 1 << 22,
    schemes: Sequence[str] = DEFAULT_SCHEMES,
    steps: int = 10,
    warmup: int = 2,
    out: Optional[str] = None,
) -> List[Dict]:
    """Time `steps` allreduces of a `size`-element f32 tensor per scheme.

    Returns one record per scheme: wire bytes per peer per leg, achieved
    rate, and max relative error vs the fp32 reduction.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .. import compression as Comp
    from jax import shard_map
    from ..plan import make_mesh

    mesh = make_mesh(dp=-1)
    n = mesh.shape["dp"]
    rng = np.random.RandomState(0)
    full = rng.randn(n, size).astype(np.float32)
    stacked = jax.device_put(
        full[:, None, :],
        jax.sharding.NamedSharding(mesh, P("dp")),
    )
    want = full.sum(axis=0)

    results: List[Dict] = []
    for scheme in schemes:
        cfg = _cfg_of(scheme)
        if cfg.scheme == "fp8" and getattr(jnp, "float8_e4m3fn", None) is None:
            continue  # pragma: no cover - old ml_dtypes build

        def body(y, cfg=cfg):
            return Comp.all_reduce(jnp.squeeze(y, 0), "dp", cfg, op="sum")[None]

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        ))
        for _ in range(warmup):
            fn(stacked).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            o = fn(stacked)
        o.block_until_ready()
        dt = (time.perf_counter() - t0) / steps

        got = np.asarray(o)[0, 0]
        rel_err = float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))
        logical = size * 4
        wire = cfg.wire_bytes(size, 4)
        results.append({
            "scheme": scheme,
            "wire_format": cfg.describe(),
            "elements": size,
            "logical_bytes": logical,
            "wire_bytes": wire,
            "compression_ratio": round(logical / wire, 3),
            "step_ms": round(dt * 1e3, 3),
            "data_gibps": round(logical / dt / GiB, 3),
            "max_rel_error": rel_err,
            "np": n,
        })
        print(
            f"RESULT: bench=compression scheme={scheme} np={n} "
            f"payload={logical} B wire={wire} B "
            f"ratio={logical / wire:.2f}x step={dt * 1e3:.3f} ms "
            f"rel_err={rel_err:.2e}",
            flush=True,
        )

    fp32 = next((r for r in results if r["scheme"] == "fp32"), None)
    int8 = next((r for r in results if r["scheme"] == "int8"), None)
    # engine A/B: the same wire formats moved by the lax lowerings vs the
    # hand-scheduled Pallas ring kernels (xla | pallas | pallas_fused),
    # with honest effective-impl stamps when the off-TPU fallback engages
    from .pallas import _bench_impl_ab

    impl_ab = _bench_impl_ab(min(size, 1 << 20), steps, warmup)
    record = {
        "bench": "compression_allreduce",
        "backend": jax.default_backend(),
        "np": n,
        "elements": size,
        "results": results,
        "impl_ab": impl_ab,
        # the headline the BENCH json keys on: int8 moves >= 3x fewer bytes
        "int8_vs_fp32_wire_ratio": (
            round(fp32["wire_bytes"] / int8["wire_bytes"], 3)
            if fp32 and int8 else None
        ),
    }
    print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
    return results
