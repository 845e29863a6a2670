#!/usr/bin/env python3
"""How often does the bf16 system route a token to other experts than the
float32 reference, and what does that do to the logits?

    python benchmark/routing_flips.py --config benchmark/configs/<name>.json \
        --seeds 1,2 --tokens 512 --out chiprun_out/flips.json

On the chip, at the configuration's own widths and depth: seeded weights
(the worker's `seed_params`), one sequence of seeded tokens, the program's
`TransformerLM` in the configuration's dtype against the configuration's
plain reference in float32.  An 8th and a 9th expert a rounding apart swap
between the two, and the swap is a discrete change of the output, so the
tolerance of `correct` (benchmark/lib/serve_check.py) has to be read beside
these numbers (PERF.md section 6).  For each seed: the share of (token,
layer) pairs whose expert set differs, by layer; the largest logit error and
the largest reference-logit deficit of the system's argmax over tokens with
no flipped layer and over tokens with one.  Not a benchmark cell: no window,
no traffic; a measurement a builder repeats when the routing changes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def measure(config: dict, seed: int, n_tokens: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.configs import load_reference, transformer_config
    from kungfu_tpu.models.transformer import TransformerLM
    from kungfu_tpu.serving.worker import seed_params

    cfg = transformer_config(config, attention="full")
    ref = load_reference(config)
    params = jax.jit(lambda: seed_params(cfg, seed))()
    toks = jnp.asarray(np.random.default_rng([seed, 25]).integers(
        0, config["vocab_size"], size=(1, n_tokens)), jnp.int32)
    model = TransformerLM(cfg)
    got, state = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"]))(params, toks)
    want, chosen, _, _ = jax.jit(
        lambda p, t: ref.forward_with_routing(p, t, config))(params, toks)
    got, want, chosen = np.asarray(got, np.float32)[0], np.asarray(want)[0], \
        np.asarray(chosen)[:, 0]
    layers, E = chosen.shape[0], chosen.shape[-1]
    flipped = np.zeros((layers, n_tokens), bool)
    for layer in range(layers):
        mine = np.asarray(state["intermediates"][f"block_{layer}"]["moe"]
                          ["moe_experts"][0])[0]
        sets = np.zeros((n_tokens, E), bool)
        np.put_along_axis(sets, mine, True, axis=-1)
        flipped[layer] = (sets != chosen[layer]).any(-1)
    err = np.abs(got - want).max(-1)
    deficit = want.max(-1) - want[np.arange(n_tokens), got.argmax(-1)]
    some = flipped.any(0)
    part = lambda x, m: float(x[m].max()) if m.any() else None  # noqa: E731
    return {
        "seed": seed, "tokens": n_tokens, "layers": layers,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "flipped_pair_share": float(flipped.mean()),
        "flipped_pair_share_by_layer": flipped.mean(1).tolist(),
        "tokens_with_a_flip_share": float(some.mean()),
        "logit_abs_max": float(np.abs(want).max()),
        "logit_std": float(want.std()),
        "logit_err_max_no_flip": part(err, ~some),
        "logit_err_max_flip": part(err, some),
        "logit_err_rms": float(np.sqrt(np.mean((got - want) ** 2))),
        "argmax_deficit_max_no_flip": part(deficit, ~some),
        "argmax_deficit_max_flip": part(deficit, some),
        "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--tokens", type=int, default=512)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()
    with open(args.config) as f:
        config = json.load(f)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(measure(config, seed, args.tokens))
        print("FLIPS: " + json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"config": config["name"], "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
