"""A prefill's lightning recurrence: its share of its roofline, in percent.

Numerator: the least time the chip could take for the real tokens one of
the capture's prefills walked (`kft_serve_scan_tokens_total{kind="prefill"}`,
a layer: a prompt's tokens, not its bucket's padding) in every lightning
layer: for a token the larger of its rows over the bandwidth peak (q, k, v
in, o out; the state stays on the chip across the tokens) and the
recurrence's own 5 e^2 operations a head over the bf16 peak
(benchmark/lib/sala_costs.py).  At the published widths the rows bound it:
40,960 bytes against 2.6 M operations a token and layer.  The chunked
kernel's extra matmuls, its float32 operands and the padding it walks are
its cost, not its work.

Denominator: the kernel's device time in one prefill: a call a lightning
layer, so the layers times the mean device time of a `kft_lightning_attn`
event inside a `jit__prefill` program.

Both sides are a prefill's, not a capture's.  The counter moves when a
prefill has been read, and a prefill is a sixth of a capture: summed over
the capture, one cut by its start would be counted whole against the part
of its kernel time the trace holds, and the share could pass 100%.  So the
tokens are divided by the executions that END inside the capture (every
counted prefill is one of them: the counter is read after the trace starts
and before it stops, so the mean errs low at worst) and the kernel time is
the mean event's (benchmark/lib/sala_costs.py `prefills_in_capture`).
"""
from benchmark.lib.sala_costs import lightning_roofline


def read(ctx):
    return lightning_roofline(ctx, "prefill")
