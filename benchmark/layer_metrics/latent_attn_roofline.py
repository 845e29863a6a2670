"""The absorbed latent-attention read's share of its roofline, in percent,
for a block with ONE attention sublayer a layer.

Numerator: the least time the chip could take to read what the attention
of the traced decode steps needs: the rows its BUSY slots had written,
summed over the steps of the capture (the program's counter
`kft_serve_decode_attn_rows_total`: `{kind="written"}` less
`{kind="written_free"}`, counted a sublayer), times the bytes of one row in
every layer (benchmark/lib/latent_moe_costs.py: 576 bf16 numbers, one
sublayer in each of `num_hidden_layers` layers), over the bandwidth peak.
Rows a request wrote, not rows fetched: a block fetched for the 40 rows it
holds is the kernel's cost, not its work.

Denominator: the device time of the `kft_mla_decode_attn` events that start
inside a `jit__decode` program of the capture.  The counter is read after
the trace starts and before it stops, so the rows cover at most the steps
the kernel time covers: the share errs low and cannot pass 100%.
"""
import os

from benchmark.lib import xplane as X
from benchmark.lib.latent_moe_costs import (
    bytes_per_row, mla_kernel_events, needed_rows)
from benchmark.lib.moe_costs import run_dir


def read(ctx):
    path = os.path.join(run_dir(ctx), "events.json.gz")
    rows = needed_rows(ctx)
    if rows is None or ctx["peaks"] is None or not os.path.exists(path):
        return None
    trace = X.read_trace(path)
    if not trace.get("devices"):
        return None
    count, seconds = mla_kernel_events(trace)
    if not count or not seconds:
        return None
    least = rows * bytes_per_row(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
