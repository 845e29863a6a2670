"""flops.py against a hand count for OLMo-1B as published (16 layers)."""
import json
import os

import pytest

from benchmark.lib import flops
from benchmark.lib.configs import ROOT
from benchmark.lib.metrics import load_peaks


@pytest.fixture(scope="module")
def olmo():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "olmo-1b-serve.json")))
    assert cfg["num_hidden_layers"] == 16
    return cfg


def test_matmul_parameters_by_hand(olmo):
    # a layer: q, k, v, out 4 x 2048^2; gate, up, down 3 x 2048 x 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert layer == 67_108_864
    head = 2048 * 50304  # the tied head multiplies; the lookup does not
    assert flops.matmul_params(olmo) == 16 * layer + head == 1_176_764_416


def test_train_flops_per_token_by_hand(olmo):
    matmul = 6 * 1_176_764_416  # 2 forward + 4 backward a parameter
    assert abs(matmul / 1e9 - 7.06) < 0.005  # "7.06 GFLOP a token in matmuls"
    # causal attention, counted once: a token sees (2048 + 1) / 2 keys on
    # average; QK^T and PV, 2 FLOPs a multiply-add, 16 heads x 128, 16 layers
    attn_fwd = 16 * 2 * 2 * 16 * 128 * 1024.5
    assert flops.attention_flops_per_token_fwd(olmo, 2048) == attn_fwd
    assert flops.train_flops_per_token(olmo, 2048) == matmul + 3 * attn_fwd


def test_flash_call_counts_the_triangle_once():
    c = flops.flash_attention_call(batch=4, heads=16, kv_heads=16, seq_len=2048,
                                   head_dim=128, backward=False)
    tri = 2048 * 2049 / 2
    assert c["flops"] == 2 * (2 * 4 * 16 * tri * 128)
    assert c["bytes"] == 4 * (4 * 2048 * 16 * 128 * 2)  # q, k, v in; o out
    both = flops.flash_attention_call(4, 16, 16, 2048, 128, backward=True)
    assert both["flops"] == 3.5 * c["flops"]  # 2 forward + 5 backward products


def test_roofline_says_which_bound():
    peak = load_peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    r = flops.roofline_seconds(197e12, 1.0, peak)
    assert r == {"seconds": 1.0, "bound": "compute"}
    assert flops.roofline_seconds(1.0, 819e9, peak)["bound"] == "memory"
    with pytest.raises(KeyError):
        load_peaks("TPU v9 imaginary")
