"""Host time of per-slot sampling after a decode step, in ms a step.

Mean `serve:decode.sample` per `serve:decode`: the loop over the active
slots after the logits are on the host (`_pick`, `_push_token`, a finished
slot's reset, `_finish` and the reply's wake-up).
"""
from benchmark.lib.host_spans import ms_per, of_run


def read(ctx):
    return ms_per(of_run(ctx), ["serve:decode.sample"], per="serve:decode")
