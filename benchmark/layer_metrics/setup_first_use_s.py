"""First use of the programs, in seconds.

`train:lower` and every `boot:first_call` of the worker's start record
(trace + lower + load-or-compile of one program until its first call
returns; a trainer's first `train:step` is one), as their union on the job
clock (benchmark/lib/start_record.py).  A serving worker's lie in the
driver's warm-up requests, after `SERVE_WORKER_READY:`; a `correct` run has
none inside the window (`compiles_in_window` is 0).
"""
from benchmark.lib.start_record import stretch_seconds


def read(ctx):
    return stretch_seconds(ctx, "first_use")
