"""The prefill's selective scan: its share of its roofline, in percent.

Numerator: the real tokens the prefills of the capture walked (the
program's counter `kft_serve_scan_tokens_total{kind="prefill"}`, counted a
layer: a prompt's tokens, not its bucket's padding) times the bytes one
token must move in every mixer (benchmark/lib/ssm_costs.py: x, Delta, B and
C in, y out; the state stays on the chip across a prefill's tokens), over
the bandwidth peak.  The bound that binds the kernel here is not this one:
a token is some 150 vector operations a 512-channel tile against 51 KB
moved, so the vector unit sets its time and this share reads low.  That is
the finding, not a fault: it says how far the prefill's scan is from being
paid for by its bytes.

Denominator: the device time of the `kft_selective_scan` events that start
inside a `jit__prefill` program of the capture.  The counter moves when a
prefill has been read, so a prefill under way when the trace starts is
counted whole against the part of its kernel time the trace holds.
"""
from benchmark.lib.ssm_costs import scan_roofline


def read(ctx):
    return scan_roofline(ctx, "prefill")
