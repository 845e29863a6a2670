"""Recurrent state's share of the slot cache's bytes, in percent.

`state / (state + rows)` of the gauge `kft_serve_cache_bytes{kind}` at the
capture's end (kungfu_tpu/serving/engine.py `cache_bytes`, from the cache's
own shapes; benchmark/lib/ssm_costs.py `cache_bytes`): how much of what a
slot holds has no position axis, and so cannot be reused by prefix, shipped
to another rank or rolled back by a cursor.  A decode step reads and
writes every byte of a busy slot's state and reads its rows up to a cursor
(the whole axis where the dense einsum runs).
"""
from benchmark.lib.ssm_costs import cache_bytes


def read(ctx):
    held = cache_bytes(ctx)
    if not held or not held.get("state", 0) + held.get("rows", 0):
        return None
    return 100.0 * held["state"] / (held["state"] + held["rows"])
