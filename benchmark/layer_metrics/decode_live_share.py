"""Slot-steps of the decode step that held a request, in percent of all.

`live / (live + free)` of `kft_serve_decode_rows_total{kind}` between the
two ends of the capture (benchmark/lib/moe_costs.py `capture_counters`):
the batch occupancy of the decode step.  A free slot's row does no work
since PR 31, so 100 less this is the share of the step's rows that ride
along empty.
"""
from benchmark.lib.moe_costs import capture_counters, family_delta


def read(ctx):
    rows = family_delta(capture_counters(ctx), "kft_serve_decode_rows_total")
    if not rows:
        return None
    live, free = rows.get('kind="live"', 0), rows.get('kind="free"', 0)
    return 100.0 * live / (live + free) if live + free else None
