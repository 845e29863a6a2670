"""The program observatory's own cost on the serving hot path, in ms a step.

Summed `programs:digest` (the signature digest and `note_call` of
`monitor/programs.py:_Tracked.__call__`, paid on every call of a tracked
program, prefills too) per `serve:decode`.
"""
from benchmark.lib.host_spans import ms_per, of_run


def read(ctx):
    return ms_per(of_run(ctx), ["programs:digest"], per="serve:decode")
