"""Latent attention in the plain block beside a held share of sigmoid-routed
experts and a shared expert: what a decode step needs, under the key names
of a DeepSeek-V3-family `config.json` (`num_hidden_layers`,
`first_k_dense_replace`, `moe_intermediate_size`).

`mla_costs.py` reckons the same two kernels for a block with two attention
sublayers a layer and an expert branch in every layer, under another
model's key names (`num_layers`, `expert_ffn_hidden_size`), and is not
edited: what is generic there (the latent row's bytes, the counters'
deltas, the kernels' events, one layer call's FLOPs and bytes) is imported,
and the counts that differ are taken here from this file's keys: ONE
attention sublayer a layer, in every layer, the leading dense ones too;
expert layers are the layers after the `first_k_dense_replace` leading
ones; an expert is `moe_intermediate_size` wide.

The shared expert is not in these counts: it is `nn.Dense` matmuls that XLA
fuses, not `kft_moe_gmm` rows, and the device trace keeps an operation's
instruction name only (`fusion.N`), so nothing read here can name it.

A program without a counter or a kernel leaves every function here with
nothing, and it says so with None.
"""
from __future__ import annotations

from . import mla_costs as _L
from .mla_costs import (  # noqa: F401 - what a reader of this cell needs, in one place
    assignment_deltas, gmm_kernel_events, latent_row_bytes, mla_kernel_events,
    needed_rows)
from .moe_costs import capture_counters

ATTENTION_SUBLAYERS_A_LAYER = 1


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def bytes_per_row(config: dict) -> int:
    """HBM bytes the attention of one decode step must read for one written
    row of one slot: its latent row in every layer's attention sublayer."""
    return (config["num_hidden_layers"] * ATTENTION_SUBLAYERS_A_LAYER
            * latent_row_bytes(config))


def _as_mla_costs_reads(config: dict) -> dict:
    """This file's counts under the key names `mla_costs.py` reads."""
    return dict(config, num_layers=expert_layers(config),
                expert_ffn_hidden_size=config["moe_intermediate_size"])


def expert_params_held(config: dict) -> int:
    """Parameters of the held routed experts of every expert layer."""
    return _L.expert_params_held(_as_mla_costs_reads(config))


def expert_weight_bytes(ctx: dict):
    """Bytes one stored routed-expert weight takes in the program of this
    capture (4 when its float32 parameters hold the held experts, else 2),
    from `kft_serve_param_bytes{dtype}`; None without it."""
    counters = capture_counters(ctx)
    held = counters and counters[1].get("kft_serve_param_bytes")
    if not held:
        return None
    f32 = held.get('dtype="float32"', 0)
    return 4 if f32 >= 4 * expert_params_held(ctx["config"]) else 2


def held_expert_layer_call(config: dict, rows: float, experts_hit: float,
                           weight_bytes: int) -> dict:
    """Required FLOPs and HBM bytes of the three grouped matmuls of one
    expert layer call (`mla_costs.held_expert_layer_call` at this file's
    expert width)."""
    return _L.held_expert_layer_call(_as_mla_costs_reads(config), rows,
                                     experts_hit, weight_bytes)
