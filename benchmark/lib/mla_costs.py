"""Latent attention and a held share of the experts: what a decode step
needs, and what the program counted.

Bytes and operations from shapes alone, as `decode_attn_costs.py` and
`moe_costs.py` have them for the per-head cache and for a layer that holds
every expert: what the algorithm requires, not what a kernel moved.

Latent attention (MLA) keeps ONE row a token a sublayer: `kv_lora_rank`
numbers of latent and `qk_rope_head_dim` of shared rotary key, in the
program's dtype.  The absorbed decode step must read each row its busy
slots have written once, for scores and values alike, in every attention
sublayer (two a layer).  The program counts rows on the host, a sublayer
(`kft_serve_decode_attn_rows_total`, the five kinds `decode_attn_costs.py`
describes), and names its kernel `kft_mla_decode_attn`.

A layer that holds a share of its experts (`program.experts_held` of
`n_routed_experts` published) routes over the whole router and multiplies
only the rows its held experts own.  The program counts, on the device and
for live rows only, the assignments to each held expert
(`kft_moe_assignments_total{layer,expert}`), to identity experts
(`kft_moe_zero_assignments_total`) and to routed experts held elsewhere
(`kft_moe_absent_assignments_total`), the distinct held experts a call hit
(`kft_moe_experts_hit_total`) and the calls
(`kft_moe_decode_layer_calls_total`).  How wide the held experts are stored
is the program's to say, not the configuration file's: `expert_weight_bytes`
reads it from `kft_serve_param_bytes{dtype}` of the same capture.

A program without a counter or a kernel (the parent of the PR that brought
them) leaves every function here with nothing, and it says so with None.
"""
from __future__ import annotations

from .decode_attn_costs import kernel_events_in_program, rows_delta
from .moe_costs import DTYPE_BYTES, capture_counters, family_delta

#: the kernels' names in the device trace (kungfu_tpu/ops/decode_attn.py,
#: kungfu_tpu/ops/gmm.py) and the program whose events the shares cover
MLA_KERNEL, GMM_KERNEL, PROGRAM = "kft_mla_decode_attn", "kft_moe_gmm", "jit__decode"
ATTENTION_SUBLAYERS_A_LAYER = 2


def latent_row_bytes(config: dict) -> int:
    """HBM bytes of one token's row in one attention sublayer."""
    width = DTYPE_BYTES[config["program"]["dtype"]]
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * width


def bytes_per_row(config: dict) -> int:
    """HBM bytes the attention of one decode step must read for one written
    row of one slot: its latent row in every attention sublayer."""
    return (config["num_layers"] * ATTENTION_SUBLAYERS_A_LAYER
            * latent_row_bytes(config))


def needed_rows(ctx: dict):
    """Rows the busy slots had written, summed over the decode and verify
    steps of the capture, a sublayer; None without the counter."""
    rows = rows_delta(ctx)
    if not rows:
        return None
    return rows["written"] - rows["written_free"]


def mla_kernel_events(trace: dict):
    return kernel_events_in_program(trace, MLA_KERNEL, PROGRAM)


def gmm_kernel_events(trace: dict):
    return kernel_events_in_program(trace, GMM_KERNEL, PROGRAM)


def expert_params_held(config: dict) -> int:
    """Parameters of the held experts of every layer: gate, up and down."""
    held = int(config["program"]["experts_held"])
    return (config["num_layers"] * held * 3 * config["hidden_size"]
            * config["expert_ffn_hidden_size"])


def expert_weight_bytes(ctx: dict):
    """Bytes one stored expert weight takes in the program of this capture:
    4 when its float32 parameters hold the held experts (they are most of
    them), else 2.  From `kft_serve_param_bytes{dtype}` at the capture's
    end; None without it."""
    counters = capture_counters(ctx)
    if counters is None:
        return None
    held = counters[1].get("kft_serve_param_bytes")
    if not held:
        return None
    f32 = held.get('dtype="float32"', 0)
    return 4 if f32 >= 4 * expert_params_held(ctx["config"]) else 2


def assignment_deltas(ctx: dict):
    """{"held", "zero", "absent", "hit", "calls"} over the capture, None
    when the program has no such counters."""
    c = capture_counters(ctx)
    held = family_delta(c, "kft_moe_assignments_total")
    zero = family_delta(c, "kft_moe_zero_assignments_total")
    absent = family_delta(c, "kft_moe_absent_assignments_total")
    hit = family_delta(c, "kft_moe_experts_hit_total")
    calls = family_delta(c, "kft_moe_decode_layer_calls_total")
    if not held or zero is None or absent is None or not hit or not calls:
        return None
    return {"held": sum(held.values()), "zero": zero[""], "absent": absent[""],
            "hit": hit[""], "calls": calls[""]}


def held_expert_layer_call(config: dict, rows: float, experts_hit: float,
                           weight_bytes: int, act_bytes: int = 2) -> dict:
    """Required FLOPs and HBM bytes of the three grouped matmuls of one
    layer call in which `rows` assignment rows hit `experts_hit` distinct
    held experts: 2 x rows x hidden x width for each of gate, up and down;
    3 x hidden x width weights of each expert hit, as stored; the rows in,
    gate and up out and their product back in, the down projection out."""
    d, w = config["hidden_size"], config["expert_ffn_hidden_size"]
    acts = rows * (d * act_bytes + 2 * w * 4 + w * act_bytes + d * 4)
    return {"flops": 2.0 * rows * d * w * 3,
            "bytes": experts_hit * 3 * d * w * weight_bytes + acts}
