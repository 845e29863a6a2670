"""Device idle for lack of load, in percent of the traced window.

Device 0's idle time lying under `serve:idle`, the worker loop's span for a
stretch with no queue and no active slot.  With `serve_idle_in_fetch`,
`serve_idle_in_host` and `serve_idle_unnamed` it sums to `serve_device_idle`.
"""
from benchmark.lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "unloaded")
