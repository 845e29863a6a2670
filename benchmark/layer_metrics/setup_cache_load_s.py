"""Executables read from the persistent compile cache, in seconds.

The compile ledger's `cache_load_ms` in the worker's start record: JAX's
`cache_retrieval_time_sec` summed over the hits (file read, decompression,
deserialisation onto the device).  On a warm start it is what `compile_s`
mostly holds; on a first start with an empty cache it is near 0.
"""
from benchmark.lib.start_record import ledger_seconds


def read(ctx):
    return ledger_seconds(ctx, "cache_load_ms")
