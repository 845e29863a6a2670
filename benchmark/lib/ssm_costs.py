"""State-space (Mamba-1) mixers beside an attention layer a period: what the
selective scan of a step needs, and what the program counted, under the key
names of a Jamba `config.json` (`mamba_d_state`, `mamba_expand`,
`attn_layer_period`, `attn_layer_offset`).

Bytes from shapes alone, as `decode_attn_costs.py` and `mla_costs.py` have
them for the attention kernels: what the recurrence requires, not what a
kernel moved.  For one token of one row in one mixer the scan must read the
row's state [mamba_d_state, d_inner] and write it back (float32: the
configuration's `assumed.state_dtype`), read x (the program's dtype), Delta
(float32) and the B and C rows (float32, `mamba_d_state` each), and write y
(float32).  A decode step does that once for every BUSY slot; a prefill
keeps the state on the chip across its tokens, so its tokens move only the
rows.  The projections, the convolution, the norms and the gate are XLA's
and are not in these counts.

The program counts the tokens its scan walked on the host, a layer
(`kft_serve_scan_tokens_total{kind="prefill"|"decode"}`,
kungfu_tpu/serving/engine.py `scan_tokens`: the real tokens of a prefill,
not its bucket's padding, and the live slot-steps of a decode), the bytes of
its slot cache by kind (`kft_serve_cache_bytes{kind="rows"|"state"}`), and
names its kernel `kft_selective_scan` in the device trace.  A profile
capture writes the counters at both ends into `<capture>/counters.json`
(`moe_costs.capture_counters`).

A program without a counter or the kernel (the parent of the PR that brought
them) leaves every function here with nothing, and it says so with None.
"""
from __future__ import annotations

import os
import re

from . import xplane as X
from .decode_attn_costs import kernel_events_in_program
from .moe_costs import DTYPE_BYTES, capture_counters, family_delta, run_dir

#: the kernel's name in the device trace (kungfu_tpu/ops/selective_scan.py)
#: and the programs whose events the two roofline shares cover
KERNEL, DECODE_PROGRAM, PREFILL_PROGRAM = (
    "kft_selective_scan", "jit__decode", "jit__prefill")
TOKENS, CACHE = "kft_serve_scan_tokens_total", "kft_serve_cache_bytes"
STATE_BYTES = 4  # float32, whatever the program's dtype


def ssm_layers(config: dict) -> int:
    """Layers whose mixer is the state-space one: all but one a period."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return sum(1 for i in range(config["num_hidden_layers"])
               if i % period != offset)


def d_inner(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def row_bytes(config: dict) -> int:
    """HBM bytes one token of one row moves through one mixer's scan, the
    state aside: x in, Delta in, B and C in, y out."""
    x = DTYPE_BYTES[config["program"]["dtype"]]
    return (d_inner(config) * (x + 4 + 4)
            + 2 * config["mamba_d_state"] * 4)


def state_bytes(config: dict) -> int:
    """Bytes of one row's state in one mixer."""
    return config["mamba_d_state"] * d_inner(config) * STATE_BYTES


def decode_step_bytes(config: dict) -> int:
    """HBM bytes the scans of one decode step must move for one busy slot:
    in every mixer the state read and written and the token's rows."""
    return ssm_layers(config) * (2 * state_bytes(config) + row_bytes(config))


def prefill_token_bytes(config: dict) -> int:
    """HBM bytes the scans of a prefill must move for one real token: its
    rows in every mixer (the state stays on the chip across the tokens; its
    one read and one write a prefill are left out, so the share errs low)."""
    return ssm_layers(config) * row_bytes(config)


def _by_kind(family):
    """{kind: value} of a family labelled `kind="..."`; None when empty."""
    if not family:
        return None
    return {m.group(1): n for labels, n in family.items()
            if (m := re.fullmatch(r'kind="(\w+)"', labels))}


def tokens_delta(ctx: dict):
    """{"prefill", "decode"}: tokens the scan walked over the capture, a
    layer; None when the run was not traced or the program has no such
    counter."""
    return _by_kind(family_delta(capture_counters(ctx), TOKENS))


def cache_bytes(ctx: dict):
    """{"rows", "state"} of the program's slot cache at the capture's end;
    None without the gauge."""
    counters = capture_counters(ctx)
    return _by_kind(counters and counters[1].get(CACHE))


def scan_events(trace: dict, program: str):
    """(count, seconds) of device 0's `kft_selective_scan` events that start
    inside one of its `program` executions."""
    return kernel_events_in_program(trace, KERNEL, program)


def scan_roofline(ctx: dict, kind: str):
    """The scan's share of its bandwidth roofline in the `kind` ("decode" |
    "prefill") programs of the capture, in percent: the tokens the program
    counted times the bytes each must move, over the bandwidth peak, over
    the kernel's device time in those programs.  None when any of the
    counter, the peaks, the kept events or the kernel is missing."""
    program, bytes_a_token = {
        "decode": (DECODE_PROGRAM, decode_step_bytes),
        "prefill": (PREFILL_PROGRAM, prefill_token_bytes)}[kind]
    path = os.path.join(run_dir(ctx), "events.json.gz")
    tokens = tokens_delta(ctx)
    if not tokens or not tokens.get(kind) or ctx["peaks"] is None \
            or not os.path.exists(path):
        return None
    trace = X.read_trace(path)
    if not trace.get("devices"):
        return None
    count, seconds = scan_events(trace, program)
    if not count or not seconds:
        return None
    least = (tokens[kind] * bytes_a_token(ctx["config"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
