"""Cache rows the block-selected attention fetches, in percent of the rows
its busy slots hold: how far the selection cuts the read.

`fetched` over `written` of `kft_serve_sparse_rows_total` between the two
ends of the capture (benchmark/lib/sala_costs.py).  `written` is a live
slot's position + 1 a step, `fetched` the rows of the blocks chosen for it:
every block at or before the query while those are at most 64, then 64
blocks of 64 rows.  About 40 at 10,000 rows; 100 (a little over: the last
block counts whole) means nothing was selected away.
"""
from benchmark.lib.sala_costs import sparse_fetched_share


def read(ctx):
    return sparse_fetched_share(ctx)
