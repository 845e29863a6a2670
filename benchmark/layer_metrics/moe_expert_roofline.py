"""The expert matmuls' share of their roofline in decode steps, in percent.

Numerator: the least time the chip could take for the grouped matmuls of
the traced decode steps (benchmark/lib/moe_costs.py `expert_layer_call`:
the larger of FLOPs over the bf16 peak and HBM bytes over the bandwidth
peak; at 64 rows a call it is the memory bound: the weights of the distinct
experts hit, as stored).  Denominator: the device time of the
`kft_moe_gmm` kernel events that start inside a `jit__decode` program of
the capture.  Restricted to decode steps: the program counts the distinct
experts a call hit only in its slot-cache programs, so a prefill's least
time cannot be stated; a prefill's kernel time is in `moe_expert_share`.

A layer call is three kernel events (gate, up, down).  Rows a call:
slots x experts per token (a free slot's ride-along row is multiplied like
any other).  Distinct experts a call: the capture's mean from the program's
counters (`moe_experts_hit_mean`), so the bytes are those of the calls the
kernel time covers up to the steps at the capture's two edges.
"""
import os

from benchmark.lib import xplane as X
from benchmark.lib.flops import roofline_seconds
from benchmark.lib.moe_costs import expert_layer_call, experts_hit_mean, run_dir

KERNEL, PROGRAM = "kft_moe_gmm", "jit__decode"


def decode_kernel_events(trace: dict):
    """(count, seconds) of device 0's kernel events that start inside one of
    its decode programs."""
    dev = trace["devices"][0]
    spans = sorted((s, s + d) for n, s, d in dev["modules"] if n.startswith(PROGRAM))
    count, seconds, i = 0, 0.0, 0
    for name, start, dur in sorted((e for e in dev["ops"] if KERNEL in e[0]),
                                   key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if i < len(spans) and spans[i][0] <= start:
            count, seconds = count + 1, seconds + dur
    return count, seconds


def read(ctx):
    path = os.path.join(run_dir(ctx), "events.json.gz")
    hit = experts_hit_mean(ctx)
    if hit is None or ctx["peaks"] is None or not os.path.exists(path):
        return None
    trace = X.read_trace(path)
    if not trace.get("devices"):
        return None
    count, seconds = decode_kernel_events(trace)
    if not count or not seconds:
        return None
    c = ctx["config"]
    rows = int(c["deployment"]["slots"]) * int(c["num_experts_per_tok"])
    call = expert_layer_call(c, rows, hit)
    least = roofline_seconds(call["flops"], call["bytes"], ctx["peaks"])["seconds"]
    return 100.0 * least * (count / 3.0) / seconds
