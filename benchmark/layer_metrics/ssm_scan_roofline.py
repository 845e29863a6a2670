"""The decode step's selective scan: its share of its roofline, in percent.

Numerator: the least time the chip could take to move what the scans of
the traced decode steps need: the live slot-steps of the capture (the
program's counter `kft_serve_scan_tokens_total{kind="decode"}`, counted a
layer) times the bytes one busy slot's step must move in every mixer
(benchmark/lib/ssm_costs.py: the float32 state read and written, x, Delta,
B and C in, y out), over the bandwidth peak.  The step is memory-bound: a
state of 327,680 bytes is read and written for one token's arithmetic.
Counted from live slots, so the same work whatever implements it: a free
slot's state that the kernel carries through the step is the kernel's cost,
not its work.

Denominator: the device time of the `kft_selective_scan` events that start
inside a `jit__decode` program of the capture.  The counter is read after
the trace starts and before it stops, so the slot-steps cover at most the
steps the kernel time covers: the share errs low and cannot pass 100%.
"""
from benchmark.lib.ssm_costs import scan_roofline


def read(ctx):
    return scan_roofline(ctx, "decode")
