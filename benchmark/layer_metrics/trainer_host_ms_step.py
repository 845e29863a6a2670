"""The trainer's host time for a step, in ms a step.

(`train:shard_batch` + `train:step`) per `train:step`: batch placement,
then the rng fold and the jitted call until it returns, as `MeshTrainer`
spans them itself.  `host_step_ms` times the same two calls from outside,
on the benchmark's clock, over the whole window; this is the mean over the
traced steps, which run under the benchmark's own capture (Python tracer
on), so it may read higher.
"""
from benchmark.lib.host_spans import ms_per, of_run


def read(ctx):
    return ms_per(of_run(ctx), ["train:shard_batch", "train:step"], per="train:step")
