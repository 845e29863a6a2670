"""Decode steps dispatched with the step before them unread, in percent of all.

`ahead / (ahead + synced)` of `kft_serve_decode_steps_total{kind}` between
the two ends of the capture (benchmark/lib/moe_costs.py `capture_counters`):
how often the engine's decode loop ran one step ahead of the host, so that
the chip worked on step N+1 while the host read step N.  A step is `synced`
when the host had to see the last token first: after an admission, a
preemption, a speculative round, or while a request samples.  A program
from before the counter has no such family and the metric is left out.
"""
from benchmark.lib.moe_costs import capture_counters, family_delta


def read(ctx):
    steps = family_delta(capture_counters(ctx), "kft_serve_decode_steps_total")
    if not steps:
        return None
    ahead, synced = steps.get('kind="ahead"', 0), steps.get('kind="synced"', 0)
    return 100.0 * ahead / (ahead + synced) if ahead + synced else None
