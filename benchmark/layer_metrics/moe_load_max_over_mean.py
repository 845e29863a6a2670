"""The busiest expert's assignments over the mean, over the traced window.

From `kft_moe_assignments_total{layer, expert}` between the two ends of the
capture (decode steps' rows; benchmark/lib/moe_costs.py): the largest
(layer, expert) count over the mean count.  1 is perfectly balanced
routing; random weights from a seed give a router with no learned balance,
and a large value here says a few experts' weights are read by most steps.
"""
from benchmark.lib.moe_costs import by_layer_and_expert, capture_counters, family_delta


def read(ctx):
    delta = family_delta(capture_counters(ctx), "kft_moe_assignments_total")
    cells = by_layer_and_expert(delta or {})
    total = sum(cells.values())
    if not total:
        return None
    return max(cells.values()) / (total / len(cells))
