"""Host time waiting for and copying a decode step's logits, in ms a step.

Mean `serve:decode.fetch` per `serve:decode`: `np.asarray(logits)`, which
waits for the device to finish the step and then copies (and transposes)
the logits to the host.  The part of it with the device idle is
`serve_idle_in_fetch`.
"""
from benchmark.lib.host_spans import ms_per, of_run


def read(ctx):
    return ms_per(of_run(ctx), ["serve:decode.fetch"], per="serve:decode")
