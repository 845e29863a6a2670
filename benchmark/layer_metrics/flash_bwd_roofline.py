"""The flash attention backward kernels' share of their roofline, in percent.

The least time the chip could take for the traced steps' attention backward
calls (benchmark/lib/flops.py `flash_attention_call`: the call with its
backward less the forward alone, so dV, dP, dQ, dK and the recomputed
scores, 5 multiplications of the lower triangle, against q, k, v, o, do
read and dq, dk, dv written; the larger of FLOPs over the bf16 peak and HBM
bytes over the bandwidth peak, the compute bound at head_dim 128 and 2,048
tokens) over the device time of the backward's Mosaic kernel events in the
trace (`kft_flash_bwd*`).  A kernel that recomputes more than the algorithm
needs pays for it here: its extra multiplications are time and not work.
Where the program's backward is blocked XLA there is no such event and
nothing to read.
"""
from benchmark.lib.flops import flash_attention_call, roofline_seconds

#: the backward kernels as the reduced trace names them (xplane.short_name)
TRACE_BUCKETS = {"flash_bwd_kernels": {
    "match": r"kft_flash_bwd.*\[tpu_custom_call\]", "line": "XLA Ops"}}


def read(ctx):
    trace, steps = ctx.get("trace"), ctx["values"].get("traced_steps")
    if not trace or not steps or ctx["peaks"] is None:
        return None
    b = trace["buckets"].get("flash_bwd_kernels")
    if not b or not b["op_seconds"]:
        return None
    c, v = ctx["config"], ctx["values"]
    heads = c["num_attention_heads"]
    shape = dict(
        batch=int(v["batch"]) // int(v["chips"]), heads=heads,
        kv_heads=c.get("num_key_value_heads") or heads, seq_len=int(v["seq_len"]),
        head_dim=c["hidden_size"] // heads)
    both = flash_attention_call(backward=True, **shape)
    fwd = flash_attention_call(backward=False, **shape)
    least = roofline_seconds(both["flops"] - fwd["flops"], both["bytes"] - fwd["bytes"],
                             ctx["peaks"])["seconds"]
    return 100.0 * least * c["num_hidden_layers"] * steps / b["op_seconds"]
