#!/usr/bin/env python3
"""Time the engine's decode step on the chip under each way of handing a
step's new cache rows to the compiler (`models/transformer.py::_store_rows`
and the forms it was chosen over), at the serving cells' attention widths:
the dense cell's K/V leaves, the latent leaf, a one-KV-head leaf at 64 slots.

    chiprun -- python scripts/bench_store_rows.py --out chiprun_out/store_rows.json

One JSON line a (model, form): milliseconds a `_decode` (`--iters` steps
dispatched back to back on the donated cache, ended by one
block_until_ready), the host's milliseconds to dispatch one (a step that
takes no longer than that is the host's time, not the device's), the
`while` loops of the compiled program, and whether the cache after those
steps is the vmapped form's bit for bit.  A CPU run says so and times
nothing worth keeping.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _forms():
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models import transformer as T

    def vmapped(cache, rows, idx0):
        tail = (0,) * (cache.ndim - 2)
        return jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i,) + tail))(cache, rows, idx0)

    def start(cache, rows, idx0):
        return jnp.clip(idx0, 0, cache.shape[1] - rows.shape[1])

    def written_out(cache, rows, idx0):
        return T._write_each_slot(cache, rows, start(cache, rows, idx0))

    def scatter(cache, rows, idx0):
        return T._scatter_rows(cache, rows, start(cache, rows, idx0))

    return {"helper": T._store_rows, "vmapped": vmapped,
            "written_out": written_out, "scatter": scatter}


def _models(layers: int):
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    common = dict(vocab_size=512, rope=True, attention="auto",
                  dtype=jnp.bfloat16, ffn="swiglu")
    return {
        # olmo-1b-serve's block whole: the step the claimed cell runs
        "dense": (TransformerConfig(d_model=2048, n_layers=layers, n_heads=16,
                                    d_ff=8192, max_len=2048, **common), 8),
        # longcat-flash-omni-serve's attention sublayers, two a layer
        "latent": (TransformerConfig(
            d_model=6144, n_layers=max(1, layers // 4), n_heads=16, d_ff=256,
            max_len=4096, rope_theta=1e7, norm="rms", block="shortcut_moe",
            kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, mla_scale_q_lora=True,
            mla_scale_kv_lora=True, d_ff_expert=256, n_experts=512,
            n_zero_experts=256, experts_per_token=12, moe_every=1,
            routed_scaling_factor=6.0, router_bias=True, experts_held=8,
            **common), 32),
        # jamba2-3b-serve's attention layers' leaf: one KV head, 64 slots
        "one_kv_head": (TransformerConfig(
            d_model=2560, n_layers=max(1, layers // 4), n_heads=20,
            n_kv_heads=1, d_ff=8192, max_len=4096, norm="rms",
            pos_table=False, **dict(common, rope=False)), 64),
    }


CHECK_LEAVES = [  # (leaf shape, rows a slot, dtype): the cells' leaves and GQA's
    ((8, 2048, 16, 128), 1, "bfloat16"), ((8, 2048, 16, 128), 4, "bfloat16"),
    ((8, 2048, 16, 128), 1, "int8"), ((8, 2048, 16, 128), 1, "float32"),
    ((8, 2048, 8, 128), 1, "bfloat16"), ((8, 2048, 4, 128), 1, "bfloat16"),
    ((8, 2048, 2, 128), 1, "bfloat16"), ((8, 2048, 2, 128), 1, "float32"),
    ((64, 4096, 1, 128), 1, "bfloat16"), ((64, 4096, 1, 128), 4, "bfloat16"),
    ((64, 4096, 1, 128), 1, "float32"),
    ((32, 4096, 576), 1, "bfloat16"), ((32, 4096, 576), 4, "bfloat16"),
    ((8, 2048, 16), 1, "float32"),
]


def check_leaves(forms) -> list:
    """Each form against numpy on one leaf alone, jitted and donated, bit
    for bit: cursors spread over the axis, the last start that fits, two
    past it, two at 0."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    lines = []
    for shape, L, dtype in CHECK_LEAVES:
        B, M = shape[:2]
        rng = np.random.default_rng(B + L)
        draw = lambda s: (  # noqa: E731
            jnp.asarray(rng.integers(-127, 128, s), jnp.int8) if dtype == "int8"
            else jnp.asarray(rng.normal(size=s), jnp.float32).astype(dtype))
        rows = draw((B, L) + shape[2:])
        idx = rng.integers(0, M - L, B)
        idx[:2], idx[-3:] = 0, (M - L, M - L + 1, M + 5)
        want = np.array(draw(shape))
        cache0 = jnp.asarray(want)
        for b, i in enumerate(np.clip(idx, 0, M - L)):
            want[b, i:i + L] = np.asarray(rows)[b]
        for form, fn in forms.items():
            got = np.asarray(jax.jit(fn, donate_argnums=0)(
                cache0 + 0, rows, jnp.asarray(idx, jnp.int32)))
            bad = np.argwhere((got != want).reshape(B, M, -1).any(-1))
            line = {"check": list(shape), "L": L, "dtype": dtype, "form": form,
                    "rows_wrong": int(len(bad)),
                    "first_wrong": bad[:4].tolist(),
                    "cursors_head": np.clip(idx, 0, M - L)[:4].tolist()}
            print(json.dumps(line), flush=True)
            lines.append(line)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--models", default="dense,latent,one_kv_head")
    ap.add_argument("--forms", default="helper,vmapped,written_out,scatter")
    ap.add_argument("--layers", type=int, default=16,
                    help="the dense model's depth; the other two take a quarter")
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--out", default="")
    ap.add_argument("--check", action="store_true",
                    help="first each form on one leaf alone against numpy")
    args = ap.parse_args(argv)
    import numpy as np
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    from kungfu_tpu.models import transformer as T
    from kungfu_tpu.models.transformer import TransformerLM
    from kungfu_tpu.serving import ServingEngine

    dev = jax.devices()[0]
    forms, models = _forms(), _models(args.layers)
    lines = check_leaves(forms) if args.check else []
    for name in [m for m in args.models.split(",") if m]:
        cfg, slots = models[name]
        params = jax.jit(lambda: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
            nn.meta.unbox(TransformerLM(cfg).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])))()
        toks = jnp.ones((slots, 1), jnp.int32)
        want = None
        for form in ["vmapped"] + [f for f in args.forms.split(",") if f != "vmapped"]:
            T._store_rows = forms[form]
            eng = ServingEngine(cfg, params, slots=slots)
            compiled = eng._decode.lower(
                eng.params, eng.cache, eng._dev_counters, toks).compile()
            text = compiled.as_text()
            cache, counters = eng.cache, eng._dev_counters
            # slots at distinct cursors, as a serving batch is
            cache = jax.tree_util.tree_map_with_path(
                lambda p, a: (jnp.arange(slots, dtype=a.dtype) * 7
                              if getattr(p[-1], "key", "") == "idx" else a), cache)
            for _ in range(10):
                _, _, cache, counters = eng._decode(eng.params, cache, counters, toks)
            jax.block_until_ready(cache)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                _, _, cache, counters = eng._decode(eng.params, cache, counters, toks)
            t1 = time.perf_counter()
            jax.block_until_ready(cache)
            t2 = time.perf_counter()
            got = [np.asarray(a) for a in jax.tree.leaves(cache)]
            if want is None:
                want = got
            line = {
                "device": dev.device_kind, "platform": dev.platform,
                "model": name, "layers": cfg.n_layers, "slots": slots,
                "form": form,
                "step_ms": round((t2 - t0) / args.iters * 1e3, 4),
                "host_dispatch_ms": round((t1 - t0) / args.iters * 1e3, 4),
                "whiles": text.count(" while("),
                "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
                "same_as_vmapped": all(
                    a.tobytes() == b.tobytes() for a, b in zip(got, want)),
                "largest_difference": max(float(np.max(np.abs(
                    a.astype(np.float32) - b.astype(np.float32))))
                    for a, b in zip(got, want)),
            }
            print(json.dumps(line), flush=True)
            lines.append(line)
            del eng, cache, counters, compiled
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
