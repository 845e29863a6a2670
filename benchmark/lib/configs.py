"""Configuration files -> what the program is started with.

A configuration is `benchmark/configs/<name>.json`: the model's published
`config.json` keys under their own names (so a reader can diff it against
`source`), then `reduced` and `assumed` (what differs and why), `program`
(the repo's `TransformerConfig` fields the published keys do not decide),
`deployment` (chips, mesh, batch, slots, optimizer) and `reference` (the
plain reference under `benchmark/references/`).  Nothing here knows a
configuration's name.
"""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

#: published key -> TransformerConfig field
_PUBLISHED = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "intermediate_size": "d_ff",
    "max_position_embeddings": "max_len",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attention_bias",
    "sliding_window": "window",
}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def program_fields(config: dict) -> dict:
    """JSON-able `TransformerConfig` fields of a configuration: what
    `--model-json` carries to a serving worker and what the training worker
    builds its model from.  `dtype` stays a string here."""
    out = {field: config[key] for key, field in _PUBLISHED.items()
           if config.get(key) is not None}
    heads, kv = config["num_attention_heads"], config.get("num_key_value_heads")
    out["n_kv_heads"] = 0 if kv in (None, heads) else kv
    out["rope"] = True
    out.update(config.get("program", {}))
    return out


def transformer_config(config: dict, **overrides):
    """The program's `TransformerConfig` for a configuration (imports JAX)."""
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import TransformerConfig

    kw = program_fields(config)
    kw.update(overrides)
    if isinstance(kw.get("dtype"), str):
        kw["dtype"] = jnp.dtype(kw["dtype"]).type
    return TransformerConfig(**kw)


def load_reference(config: dict):
    """The configuration's plain reference module: `forward(params, tokens,
    config) -> logits` in float32 with no kernels."""
    import importlib.util

    path = os.path.join(BENCH, "references", config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + config["reference"].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
