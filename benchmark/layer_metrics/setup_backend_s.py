"""The worker's entry point to devices ready, in seconds.

`boot:imports` (jax and the package), `boot:backend` (the TPU runtime
coming up at the first question about devices) and, where the worker is
the caller's script and asks for devices itself, the gap between the two
that the record names (benchmark/lib/start_record.py).
"""
from benchmark.lib.start_record import stretch_seconds


def read(ctx):
    return stretch_seconds(ctx, "backend")
