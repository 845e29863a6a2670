"""Job start to the worker's entry point, in seconds.

The first stretch of the start's timeline, from the worker's own start
record (benchmark/lib/start_record.py): the launcher's or the supervisor's
share (its imports, its configuration, the spawn; its own record says which)
and then `boot:interpreter`, the spawn to the first statement of the
program in the worker.  The job clock starts with the launcher's process,
so the benchmark's own work before it spawns the launcher is not in here
but in `setup_unnamed_s`.
"""
from benchmark.lib.start_record import stretch_seconds


def read(ctx):
    return stretch_seconds(ctx, "launch")
