"""Model FLOP/s utilisation of a training cell, in percent.

Required forward+backward FLOPs per token from the configuration's shapes
(benchmark/lib/flops.py: causal attention counted once, nothing recomputed)
times the tokens per second per chip the run completed, over the chip's
bf16 peak (benchmark/lib/peaks.json, keyed by device kind).  An end-to-end
utilisation: it is not a kernel's roofline share and says nothing of idle time.
"""
from benchmark.lib.flops import train_flops_per_token


def read(ctx):
    rate = ctx["values"].get("train_tokens_per_s_chip")
    if rate is None or ctx["peaks"] is None:
        return None
    per_token = train_flops_per_token(ctx["config"], ctx["traffic"]["seq_len"])
    return 100.0 * rate * per_token / ctx["peaks"]["bf16_flops_per_s"]
