"""Operations and bytes a call needs, from its shapes alone.

The yardstick for `mfu` and for a kernel's roofline share: what the
algorithm requires, not what a compiler counted (XLA's count includes
recomputation and sees no FLOPs inside a Mosaic call).  A multiply-add is 2
FLOPs.  Causal attention is counted once: the half of the score matrix above
the diagonal is not required work.
"""
from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    the layers' projections and the output head (the input embedding is a
    lookup)."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    h = config["num_attention_heads"]
    kv = config.get("num_key_value_heads") or h
    hd = config.get("head_dim") or d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp_mats = 3 if config.get("hidden_act", "silu") in ("silu", "swiglu") else 2
    return config["num_hidden_layers"] * (attn + mlp_mats * d * ff) \
        + d * config["vocab_size"]


def attention_flops_per_token_fwd(config: dict, seq_len: int) -> float:
    """QK^T and PV for one token of a causal sequence of `seq_len`, averaged
    over positions (a token at position p attends p + 1 keys)."""
    h = config["num_attention_heads"]
    hd = config.get("head_dim") or config["hidden_size"] // h
    window = config.get("sliding_window") or seq_len
    keys = min((seq_len + 1) / 2.0, window)
    return config["num_hidden_layers"] * 2 * 2 * h * hd * keys


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    return 2.0 * matmul_params(config) + attention_flops_per_token_fwd(config, seq_len)


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward: the backward pass needs twice the forward's
    multiplications (one for the input's gradient, one for the weight's);
    nothing recomputed is counted."""
    return 3.0 * forward_flops_per_token(config, seq_len)


def flash_attention_call(batch: int, heads: int, kv_heads: int, seq_len: int,
                         head_dim: int, dtype_bytes: int = 2,
                         backward: bool = True) -> dict:
    """Required FLOPs and HBM bytes of causal flash attention over one
    [batch, seq_len, heads, head_dim] call, forward (and backward).

    Forward: QK^T and PV over the lower triangle.  Backward: dV, dP, dQ, dK
    and the recomputed scores, 5 multiplications of the same size against
    the forward's 2 (the recomputation is part of the algorithm here, as the
    scores are never stored).  Bytes: q, k, v read and o written once;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    tri = seq_len * (seq_len + 1) / 2.0
    mm = 2.0 * batch * heads * tri * head_dim  # one [L, L] x head_dim product
    q = batch * seq_len * heads * head_dim * dtype_bytes
    kv = batch * seq_len * kv_heads * head_dim * dtype_bytes
    fwd = {"flops": 2 * mm, "bytes": 2 * q + 2 * kv}
    if not backward:
        return fwd
    return {"flops": fwd["flops"] + 5 * mm,
            "bytes": fwd["bytes"] + (3 * q + 2 * kv) + (q + 2 * kv)}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}
