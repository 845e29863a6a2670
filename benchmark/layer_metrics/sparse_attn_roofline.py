"""The decode step's block-selected attention: its share of its roofline, in
percent.

Numerator: the least time the chip could take to read K and V of the rows
of the chosen blocks: `kft_serve_sparse_rows_total{kind="fetched"}` over the
capture (a layer and a KV head: for a live slot at position t, 64 rows for
each of min(t // 64 + 1, 64) blocks) times the bytes of a row's K and V over
all minicpm4 layers and KV heads (benchmark/lib/sala_costs.py), over the
bandwidth peak.  The kernel is memory-bound: 16 query rows a KV head against
4,096 rows.  The compressed keys the selector scores are XLA's read and not
in this count.

Denominator: the device time of the `kft_sparse_decode_attn` events that
start inside a `jit__decode` program of the capture.  The counter is read
after the trace starts and before it stops, so the rows cover at most the
steps the kernel time covers: the share errs low and cannot pass 100%.
"""
from benchmark.lib.sala_costs import sparse_roofline


def read(ctx):
    return sparse_roofline(ctx)
