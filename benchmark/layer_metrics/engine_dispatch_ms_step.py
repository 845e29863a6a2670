"""Host time of a decode step's program call, in ms a step.

Mean `serve:decode.dispatch` per `serve:decode` in the traced run's
capture: from the call of the decode program until it returns (argument
handling, the observatory's `programs:digest`, the enqueue); the device runs
on after it.  With `engine_fetch_ms_step` it makes up what the engine's
clock calls a step (`decode_step_ms_mean`, the window's mean of the
`tok_latency_ms` histogram; this is the capture's).
"""
from benchmark.lib.host_spans import ms_per, of_run


def read(ctx):
    return ms_per(of_run(ctx), ["serve:decode.dispatch"], per="serve:decode")
