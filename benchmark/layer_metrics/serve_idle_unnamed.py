"""Device idle under no program span, in percent of the traced window.

The coverage check of the other three: idle time the program's spans do not
reach (between two iterations of the worker's loop, or a span cut by the
capture's edge).  It should stay small.
"""
from benchmark.lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "unnamed")
