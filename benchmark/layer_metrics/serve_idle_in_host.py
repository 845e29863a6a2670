"""Device idle under the engine's other host work, in percent of the traced window.

Device 0's idle time whose innermost program span is any other `serve:` or
`programs:` span: upload, dispatch, sampling, admission, slot programs'
dispatch, the rest of a `serve:step`.
"""
from benchmark.lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "in_host")
