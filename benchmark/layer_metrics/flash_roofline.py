"""The flash attention kernel's share of its roofline, in percent.

The least time the chip could take for the traced steps' attention forward
calls (benchmark/lib/flops.py `flash_attention_call`: the larger of FLOPs
over the bf16 peak and HBM bytes over the bandwidth peak; at head_dim 128
and 2,048 tokens it is the compute bound) over the device time of the
Mosaic kernel events in the trace.  Forward only: below 4,096 tokens the
program's backward is blocked XLA (`while` loops, `attn_bwd_xla_share`), not
a kernel.  A PR that makes the backward a kernel adds a metric for it.
"""
from benchmark.lib.flops import flash_attention_call, roofline_seconds

#: the Mosaic kernels as the reduced trace names them (xplane.short_name)
TRACE_BUCKETS = {"mosaic_kernels": {"match": r"\[tpu_custom_call\]",
                                    "line": "XLA Ops"}}


def read(ctx):
    trace, steps = ctx.get("trace"), ctx["values"].get("traced_steps")
    if not trace or not steps or ctx["peaks"] is None:
        return None
    b = trace["buckets"].get("mosaic_kernels")
    if not b or not b["op_seconds"]:
        return None
    c, v = ctx["config"], ctx["values"]
    heads = c["num_attention_heads"]
    call = flash_attention_call(
        batch=int(v["batch"]) // int(v["chips"]), heads=heads,
        kv_heads=c.get("num_key_value_heads") or heads, seq_len=int(v["seq_len"]),
        head_dim=c["hidden_size"] // heads, backward=False)
    least = roofline_seconds(call["flops"], call["bytes"], ctx["peaks"])["seconds"]
    return 100.0 * least * c["num_hidden_layers"] * steps / b["op_seconds"]
