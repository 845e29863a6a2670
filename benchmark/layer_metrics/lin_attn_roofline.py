"""The decode step's lightning recurrence: its share of its roofline, in percent.

Numerator: the least time the chip could take to move what the lightning
layers of the traced decode steps need: the live slot-steps of the capture
(the program's counter `kft_serve_scan_tokens_total{kind="decode"}`, counted
a layer) times the bytes one busy slot's step must move in every lightning
layer (benchmark/lib/sala_costs.py: the float32 state [heads, e, e] read and
written, 2 x 2.10 MB at the published widths, q, k and v in, o out), over
the bandwidth peak.  The step is memory-bound: a state of 2 MB is read and
written for one token's arithmetic.  Counted from live slots, so the same
work whatever implements it.

Denominator: the device time of the `kft_lightning_attn` events that start
inside a `jit__decode` program of the capture.  The counter is read after
the trace starts and before it stops, so the slot-steps cover at most the
steps the kernel time covers: the share errs low and cannot pass 100%.
"""
from benchmark.lib.sala_costs import lightning_roofline


def read(ctx):
    return lightning_roofline(ctx, "decode")
