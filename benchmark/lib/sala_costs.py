"""Lightning linear-attention layers beside block-selected sparse attention:
what their kernels need of a step, and what the program counted, under the
key names of a MiniCPM-SALA `config.json` (`mixer_types`, `lightning_nh`,
`lightning_head_dim`, `num_key_value_heads`, `head_dim`).

Bytes and operations from shapes alone, as `ssm_costs.py` and
`decode_attn_costs.py` have them: what the algorithm requires, not what a
kernel moved.

Lightning (`kft_lightning_attn`, kungfu_tpu/ops/lightning_attn.py).  For one
token of one row in one layer the recurrence must read the row's state
[heads, e, e] and write it back (float32: the configuration's
`assumed.state_dtype`), read q, k and v (the program's dtype) and write o
(float32).  A decode step does that once for every BUSY slot; a prefill
keeps the state on the chip across its tokens, so its tokens move only the
rows, and must do the recurrence's own arithmetic: the state's decay and
its update k^T v (3 e^2 operations a head) and the output q S (2 e^2), which
the chunked form doubles and is not credited for.

Sparse (`kft_sparse_decode_attn`, kungfu_tpu/ops/decode_attn.py).  A decode
step must read K and V of the rows of the blocks chosen for each busy slot,
in every minicpm4 layer and for each KV head: `fetched` of the program's
counter counts those rows a layer and a KV head.

The program counts on the host (kungfu_tpu/serving/engine.py):
`kft_serve_scan_tokens_total{kind="prefill"|"decode"}` the tokens its
recurrent layers walked, a layer (the real tokens of a prefill, the live
slot-steps of a decode), and
`kft_serve_sparse_rows_total{kind="written"|"fetched"|"kernels"}` the rows
live slots held, the rows of the blocks chosen for them and the compressed
keys scored, a layer and a KV head.  A profile capture writes both ends
into `<capture>/counters.json` (`moe_costs.capture_counters`).

A program without a counter or a kernel (the parent of the PR that brought
them) leaves every function here with nothing, and it says so with None.
"""
from __future__ import annotations

import os

from . import xplane as X
from .decode_attn_costs import kernel_events_in_program
from .moe_costs import DTYPE_BYTES, capture_counters, family_delta, run_dir
from .ssm_costs import _by_kind

#: the kernels' names in the device trace and the programs whose events the
#: roofline shares cover
LIGHTNING_KERNEL, SPARSE_KERNEL = "kft_lightning_attn", "kft_sparse_decode_attn"
DECODE_PROGRAM, PREFILL_PROGRAM = "jit__decode", "jit__prefill"
TOKENS, SPARSE_ROWS = "kft_serve_scan_tokens_total", "kft_serve_sparse_rows_total"
STATE_BYTES = 4  # float32, whatever the program's dtype


def layers(config: dict, kind: str) -> int:
    """Layers of `mixer_types` whose mixer is `kind`."""
    return sum(1 for m in config.get("mixer_types", ()) if m == kind)


def lightning_width(config: dict) -> int:
    return config["lightning_nh"] * config["lightning_head_dim"]


def lightning_state_bytes(config: dict) -> int:
    """Bytes of one row's state in one lightning layer."""
    return lightning_width(config) * config["lightning_head_dim"] * STATE_BYTES


def lightning_row_bytes(config: dict) -> int:
    """HBM bytes one token of one row moves through one lightning layer's
    recurrence, the state aside: q, k and v in, o out."""
    x = DTYPE_BYTES[config["program"]["dtype"]]
    return lightning_width(config) * (3 * x + 4)


def lightning_decode_step_bytes(config: dict) -> int:
    """HBM bytes the lightning layers of one decode step must move for one
    busy slot: in every layer the state read and written and the token's
    rows."""
    return layers(config, "lightning-attn") * (
        2 * lightning_state_bytes(config) + lightning_row_bytes(config))


def lightning_prefill_token_seconds(config: dict, peaks: dict) -> float:
    """The least time one real token of a prefill takes in the lightning
    layers: the larger of its rows over the bandwidth peak (the state stays
    on the chip; its one read and write a prefill are left out, so the
    share errs low) and the recurrence's 5 e^2 operations a head over the
    bf16 peak."""
    n, e = layers(config, "lightning-attn"), config["lightning_head_dim"]
    flops = 5 * e * e * config["lightning_nh"]
    return n * max(lightning_row_bytes(config) / peaks["hbm_bytes_per_s"],
                   flops / peaks["bf16_flops_per_s"])


def sparse_row_bytes(config: dict) -> int:
    """HBM bytes of one fetched row (a layer and a KV head, as the counter
    counts) over all minicpm4 layers and KV heads: its K and its V."""
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads") or heads
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return (layers(config, "minicpm4") * kv_heads * 2 * head_dim
            * DTYPE_BYTES[config["program"]["dtype"]])


def tokens_delta(ctx: dict):
    """{"prefill", "decode"}: tokens the recurrent layers walked over the
    capture, a layer; None when the run was not traced or the program has
    no such counter."""
    return _by_kind(family_delta(capture_counters(ctx), TOKENS))


def sparse_rows_delta(ctx: dict):
    """{"written", "fetched", "kernels"} over the capture, a layer and a KV
    head; None without the counter."""
    return _by_kind(family_delta(capture_counters(ctx), SPARSE_ROWS))


def _kernel_seconds(ctx: dict, kernel: str, program: str):
    """Device seconds of `kernel`'s events inside `program` executions of
    the capture; None when the events were not kept or hold none."""
    trace = _read_events(ctx)
    if trace is None:
        return None
    count, seconds = kernel_events_in_program(trace, kernel, program)
    return seconds if count and seconds else None


def _read_events(ctx: dict):
    """The capture's kept events; None when the run kept none."""
    path = os.path.join(run_dir(ctx), "events.json.gz")
    if ctx.get("peaks") is None or not os.path.exists(path):
        return None
    trace = X.read_trace(path)
    return trace if trace.get("devices") else None


def prefills_in_capture(trace: dict, kernel: str = LIGHTNING_KERNEL,
                        program: str = PREFILL_PROGRAM):
    """(executions, events, seconds) of device 0: the `program` executions
    that END inside the capture (one cut by the capture's start does; one
    cut by its stop, whose span reaches the device's last event, does not),
    and the count and device seconds of the `kernel` events that start
    inside one of those."""
    dev = trace["devices"][0]
    last = max((s + d for _, s, d in dev["ops"]), default=0.0)
    spans = sorted((s, s + d) for n, s, d in dev["modules"]
                   if n.startswith(program) and s + d < last - 1e-6)
    events = [(s, d) for n, s, d in dev["ops"] if kernel in n
              and any(a <= s < b for a, b in spans)]
    return len(spans), len(events), sum(d for _, d in events)


def lightning_roofline(ctx: dict, kind: str):
    """The lightning kernel's share of its roofline in the `kind` ("decode"
    | "prefill") programs of the capture, in percent.  Only for a
    configuration with lightning layers.

    Decode: the slot-steps the program counted times the least time each
    needs, over the kernel's device time in the decode programs (the
    counter is read inside the capture, so it covers at most the steps the
    kernel time covers).

    Prefill: a prefill is a large share of a capture, so one cut by the
    capture's start is counted whole (the counter moves when a prefill has
    been read) against the part of its kernel time the trace holds.  The
    two sides are therefore taken a prefill: the real tokens counted over
    the executions that END inside the capture (every counted prefill is
    one of them, so the mean errs low at worst) times the least time a
    token needs in every layer, over the kernel time of one execution (a
    call a layer: the layers times the mean device time of a kernel event
    inside those executions)."""
    config = ctx["config"]
    n_layers = layers(config, "lightning-attn")
    if not n_layers:
        return None
    tokens = tokens_delta(ctx)
    if not tokens or not tokens.get(kind):
        return None
    if kind == "decode":
        seconds = _kernel_seconds(ctx, LIGHTNING_KERNEL, DECODE_PROGRAM)
        if not seconds:
            return None
        return 100.0 * tokens[kind] * lightning_decode_step_bytes(config) \
            / ctx["peaks"]["hbm_bytes_per_s"] / seconds
    trace = _read_events(ctx)
    if trace is None:
        return None
    prefills, events, seconds = prefills_in_capture(trace)
    if not prefills or not seconds:
        return None
    least = tokens[kind] / prefills * lightning_prefill_token_seconds(
        config, ctx["peaks"])
    return 100.0 * least / (n_layers * seconds / events)


def sparse_roofline(ctx: dict):
    """The sparse decode kernel's share of its bandwidth roofline, in
    percent: K and V of the rows the chosen blocks hold, over the bandwidth
    peak, over the kernel's device time in the decode programs."""
    config = ctx["config"]
    if not layers(config, "minicpm4"):
        return None
    rows = sparse_rows_delta(ctx)
    seconds = _kernel_seconds(ctx, SPARSE_KERNEL, DECODE_PROGRAM)
    if not rows or not rows.get("fetched") or not seconds:
        return None
    least = rows["fetched"] * sparse_row_bytes(config) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def sparse_fetched_share(ctx: dict):
    """Rows of the chosen blocks over the rows busy slots held, in percent."""
    rows = sparse_rows_delta(ctx)
    if not rows or not rows.get("written"):
        return None
    return 100.0 * rows["fetched"] / rows["written"]
