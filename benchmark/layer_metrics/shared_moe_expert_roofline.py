"""The held routed experts' grouped matmuls' share of their roofline in
decode steps, in percent, for expert layers that carry a shared expert
beside them (the shared expert itself is dense matmuls under XLA's own
names and is not in this share).

Numerator: the least time the chip could take for the grouped matmuls of
the traced decode steps (benchmark/lib/latent_moe_costs.py
`held_expert_layer_call`: the larger of FLOPs over the bf16 peak and HBM
bytes over the bandwidth peak; at a handful of rows a call it is the memory
bound: the weights of the distinct held experts hit, AS THE PROGRAM STORES
THEM, read from `kft_serve_param_bytes{dtype}` of the same capture).  Rows
and distinct experts a call: the capture's means from the program's device
counters (assignments to held experts and experts hit over layer calls;
live rows only).  Denominator: the device time of the `kft_moe_gmm` kernel
events that start inside a `jit__decode` program of the capture; a layer
call is three kernel events (gate, up, down).
"""
import os

from benchmark.lib import xplane as X
from benchmark.lib.flops import roofline_seconds
from benchmark.lib.latent_moe_costs import (
    assignment_deltas, expert_weight_bytes, gmm_kernel_events,
    held_expert_layer_call)
from benchmark.lib.moe_costs import run_dir


def read(ctx):
    path = os.path.join(run_dir(ctx), "events.json.gz")
    if ctx["peaks"] is None or not os.path.exists(path):
        return None
    d, wb = assignment_deltas(ctx), expert_weight_bytes(ctx)
    if d is None or wb is None or not d["calls"]:
        return None
    trace = X.read_trace(path)
    if not trace.get("devices"):
        return None
    count, seconds = gmm_kernel_events(trace)
    if not count or not seconds:
        return None
    call = held_expert_layer_call(ctx["config"], d["held"] / d["calls"],
                                  d["hit"] / d["calls"], wb)
    least = roofline_seconds(call["flops"], call["bytes"], ctx["peaks"])["seconds"]
    return 100.0 * least * (count / 3.0) / seconds
