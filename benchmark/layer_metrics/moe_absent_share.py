"""Assignments to routed experts held elsewhere, in percent of all live
assignments, over the traced window.

From the program's device counters between the two ends of the capture
(benchmark/lib/latent_moe_costs.py `assignment_deltas`): held elsewhere
over held + identity + held elsewhere.  With 8 of 256 routed experts held
and no identity experts, balanced routing gives 96.9%: such an assignment
owns no grouped-matmul row and reads no weight here, as on any one rank of
the deployment before the combine.  A lower share is more work on this
chip than its share of the deployment's.
"""
from benchmark.lib.latent_moe_costs import assignment_deltas


def read(ctx):
    d = assignment_deltas(ctx)
    total = d and d["held"] + d["zero"] + d["absent"]
    if not total:
        return None
    return 100.0 * d["absent"] / total
