"""Weights and state onto the device, in seconds.

A trainer's `train:init` (parameter and optimizer-state initialisation
until the state is placed), or a serving worker's `boot:weights` (the
ladder: seed, file or buddy) + `boot:resident` (the resident form) +
`boot:engine` (the slot cache and the engine's programs), from the worker's
start record (benchmark/lib/start_record.py).
"""
from benchmark.lib.start_record import stretch_seconds


def read(ctx):
    return stretch_seconds(ctx, "weights")
