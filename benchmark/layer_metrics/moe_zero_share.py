"""Assignments to identity (zero-compute) experts, in percent of all live
assignments, over the traced window.

From the program's device counters between the two ends of the capture
(benchmark/lib/mla_costs.py `assignment_deltas`): identity over identity +
held + held elsewhere.  256 of 768 router outputs are identity experts, so
balanced routing gives 33%; each such assignment costs one scaled add of
the token and no matmul row, here as in any deployment.
"""
from benchmark.lib.mla_costs import assignment_deltas


def read(ctx):
    d = assignment_deltas(ctx)
    total = d and d["held"] + d["zero"] + d["absent"]
    if not total:
        return None
    return 100.0 * d["zero"] / total
