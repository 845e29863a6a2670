#!/usr/bin/env python3
"""Checker child for serving cells: served tokens against the plain reference.

Runs after the fleet has stopped, so the chip is free.  It makes the same
weights the worker made (`seed_params`, the program's own seeding) and
teacher-forces each served request (prompt + the tokens the fleet returned)
through the configuration's float32 reference.  With random weights the
argmax turns on rounding, so the comparison is on logits: each served token's
reference logit must lie within `LOGIT_DEFICIT_TOL` of the reference maximum
at its position.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: reference-logit deficit allowed for a served token.  Logits of the seeded
#: model have a standard deviation near 0.9 and the served path computes in
#: bf16, which moves a logit by about 0.02 (PERF.md, Findings); a token
#: chosen by anything coarser, or from a wrong position or cache row, lies
#: whole units below the maximum.  A run's largest deficit over its ~300
#: checked tokens was 0.044-0.107 in ten chip runs (mean 0.067, PR 22): a
#: largest-of-many, so it has a tail, and a limit of 0.15 would fail about
#: one sound run in several hundred; 0.2 fails none in ten thousand and is
#: still five times under what an 8-bit float (16 times coarser) would show.
LOGIT_DEFICIT_TOL = 0.2


def main(argv=None) -> int:
    job = json.load(open(sys.argv[1]))
    from kungfu_tpu.env import apply_platform_override, enable_compile_cache

    apply_platform_override()
    enable_compile_cache()
    import numpy as np
    import jax
    import jax.numpy as jnp

    from benchmark.lib.configs import load_json, load_reference, transformer_config
    from kungfu_tpu.serving.worker import seed_params

    config = load_json(job["config"])
    cfg = transformer_config(config, attention="full")
    ref = load_reference(config)
    params = jax.jit(lambda: seed_params(cfg, job["seed"]))()
    fwd = jax.jit(lambda p, t: ref.forward(p, t, config))
    worst, checked, out = 0.0, 0, []
    for item in job["served"]:
        toks = np.asarray(item["tokens"], np.int32)
        n_prompt = item["prompt_len"]
        pad = -len(toks) % 64  # few shapes: right padding is causally invisible
        padded = np.concatenate([toks, np.zeros(pad, np.int32)])[None]
        logits = np.asarray(fwd(params, jnp.asarray(padded)))[0]
        rows = logits[n_prompt - 1:len(toks) - 1]
        served = toks[n_prompt:]
        deficit = rows.max(-1) - rows[np.arange(len(served)), served]
        worst = max(worst, float(deficit.max()))
        checked += len(served)
        out.append({"id": item["id"], "tokens": int(len(served)),
                    "max_deficit": float(deficit.max()),
                    "argmax_agree": float(np.mean(rows.argmax(-1) == served))})
    result = {"ok": bool(checked > 0 and worst <= LOGIT_DEFICIT_TOL),
              "checked_tokens": checked, "max_deficit": worst,
              "tol": LOGIT_DEFICIT_TOL, "requests": out,
              "platform": jax.devices()[0].platform}
    with open(job["out"], "w") as f:
        json.dump(result, f)
    print("SERVE_CHECK: " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
