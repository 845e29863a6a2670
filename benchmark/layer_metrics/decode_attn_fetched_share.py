"""Cache rows the decode-step attention fetches, in percent of the rows the
cache holds: how far the length-aware kernel engages.

From the program's counter over the traced window (the difference of
`kft_serve_decode_attn_rows_total{kind="fetched"}` over that of
`{kind="cache"}` between the two ends of the capture,
benchmark/lib/decode_attn_costs.py).  `cache` is slots x max_len a step;
`fetched` each slot's live blocks of the kernel (its cursor rounded up to
the block), free slots' ride-along rows among them.  100 means the program
was built with the dense einsum and reads every row; the floor is the share
of rows written.
"""
from benchmark.lib.decode_attn_costs import rows_delta


def read(ctx):
    rows = rows_delta(ctx)
    if not rows or not rows.get("cache"):
        return None
    return 100.0 * rows["fetched"] / rows["cache"]
