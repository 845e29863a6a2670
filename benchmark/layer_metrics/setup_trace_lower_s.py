"""Python tracing and lowering of the worker's programs, in seconds.

The compile ledger's `trace_ms` + `lower_ms` in the worker's start record
(kungfu_tpu/monitor/programs.py: the union of JAX's own trace and lowering
time spans, a nested trace counted once, a trace inside a lowering counted
as tracing).  It cuts across the phase metrics (an op-by-op init traces and
lowers inside `setup_weights_s`) and is never added to them.
"""
from benchmark.lib.start_record import ledger_seconds


def read(ctx):
    return ledger_seconds(ctx, "trace_ms", "lower_ms")
