"""Device idle while the host fetches results, in percent of the traced window.

Device 0's idle time whose innermost program span is a `serve:*.fetch`: the
device is done and the host is still copying or transposing what it made.
"""
from benchmark.lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "in_fetch")
