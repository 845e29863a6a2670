"""`setup_s` under none of the four phase metrics, in seconds.

The coverage check of `setup_launch_s`, `setup_backend_s`,
`setup_weights_s` and `setup_first_use_s`, as `serve_idle_unnamed` is for
idle: the benchmark's own work before it spawns the launcher, gaps the
program cannot span, the driver's warm-up requests beyond the programs'
first calls, a training worker's second warm-up step.
"""
from benchmark.lib.start_record import unnamed_seconds


def read(ctx):
    return unnamed_seconds(ctx)
