"""Distinct experts a decode step reads a layer: the capture's mean.

From the program's device counters over the traced window (the difference
of `kft_moe_experts_hit_total` over that of
`kft_moe_decode_layer_calls_total` between the two ends of the capture,
benchmark/lib/moe_costs.py).  Of 64 experts, 8 slots x 8 draws reach 41 at
most on average when every slot is busy and routing is uniform; a free
slot's ride-along row counts, as the device reads its experts too.  It is
what the expert matmuls' bytes, and so a decode step's floor, turn on.
"""
from benchmark.lib.moe_costs import experts_hit_mean


def read(ctx):
    return experts_hit_mean(ctx)
