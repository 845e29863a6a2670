"""Decode-step attention over the slot cache: what a step needs, and what the
program counted.

Bytes from shapes alone, as `flops.py` and `moe_costs.py` have them for the
other kernels: what the algorithm requires, not what a compiler or a kernel
moved.  A decode step's attention must read, in every layer, the K and the V
row of every position its slots have written: `kv_heads x head_dim` elements
each, in the cache's dtype.  It need not read a row beyond a cursor; the
dense einsum read all `slots x max_len` of them.

The program counts rows on the host (`kft_serve_decode_attn_rows_total`,
kungfu_tpu/serving/engine.py `decode_attn_rows`: `cache`, `written`,
`written_free`, `fetched`, `fetched_free`, summed over steps, a layer; the
`_free` kinds are the part under free slots' ride-along cursors, so
`written` less `written_free` is what requests wrote) and a profile capture
writes them at both ends into `<capture>/counters.json`
(`moe_costs.capture_counters`).  A program without the counter (the parent
of PR 28) leaves every reader with nothing.
"""
from __future__ import annotations

import re

from .moe_costs import DTYPE_BYTES, capture_counters, family_delta

FAMILY = "kft_serve_decode_attn_rows_total"
#: the kernel's name in the device trace (kungfu_tpu/ops/decode_attn.py) and
#: the program whose events the roofline share covers
KERNEL, PROGRAM = "kft_decode_attn", "jit__decode"


def bytes_per_row(config: dict) -> int:
    """HBM bytes the attention of one decode step must read for one written
    row of one slot: its K and its V in every layer, as the cache stores
    them (the program's dtype)."""
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads") or heads
    head_dim = config["hidden_size"] // heads
    width = DTYPE_BYTES[config["program"]["dtype"]]
    return config["num_hidden_layers"] * 2 * kv_heads * head_dim * width


def rows_delta(ctx: dict):
    """{kind: rows over the capture} of the program's counter, None when the
    run was not traced or the program has no such counter."""
    delta = family_delta(capture_counters(ctx), FAMILY)
    if not delta:
        return None
    return {m.group(1): n for labels, n in delta.items()
            if (m := re.fullmatch(r'kind="(\w+)"', labels))}


def kernel_events_in_program(trace: dict, kernel: str = KERNEL,
                             program: str = PROGRAM):
    """(count, seconds) of device 0's events named `kernel` that start
    inside one of its `program` executions."""
    dev = trace["devices"][0]
    spans = sorted((s, s + d) for n, s, d in dev["modules"]
                   if n.startswith(program))
    count, seconds, i = 0, 0.0, 0
    for _, start, dur in sorted((e for e in dev["ops"] if kernel in e[0]),
                                key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= start:
            i += 1
        if i < len(spans) and spans[i][0] <= start:
            count, seconds = count + 1, seconds + dur
    return count, seconds
