"""Sparse-expert layers: what a call needs, and what the program counted.

Operations and bytes from shapes alone, as `flops.py` has them for the dense
kernels: what the algorithm requires, not what a compiler counted.  A layer
call of `rows` assignment rows (tokens x experts per token) multiplies each
row by three [hidden, width] matrices of its expert (gate, up, down); the
weights it must read are those of the *distinct* experts that own a row, as
the worker stores them.

The program's counters (`kft_moe_*`, kungfu_tpu/parallel/moe.py) live on the
device and are written to `<capture>/counters.json` at both ends of a
profile capture (kungfu_tpu/monitor/programs.py `capture_profile`);
`capture_counters` reads that file of a traced run.
"""
from __future__ import annotations

import glob
import json
import os
import re

from .configs import ROOT

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def expert_layer_call(config: dict, rows: int, experts_hit: float,
                      act_bytes: int = 2) -> dict:
    """Required FLOPs and HBM bytes of the three grouped matmuls of one
    expert layer over `rows` rows that hit `experts_hit` distinct experts.

    FLOPs: 2 x rows x hidden x width for each of gate, up and down.  Bytes:
    3 x hidden x width weights of each expert hit, in the dtype the
    deployment keeps them in (`deployment.resident_weight_dtype`); the rows
    in (bf16, once for gate and up together), gate and up out and the
    hidden product back in, the down projection out in float32."""
    d, w = config["hidden_size"], config["intermediate_size"]
    wb = DTYPE_BYTES[config["deployment"].get("resident_weight_dtype", "float32")]
    acts = rows * (d * act_bytes + 2 * w * 4 + w * act_bytes + d * 4)
    return {"flops": 2.0 * rows * d * w * 3,
            "bytes": experts_hit * 3 * d * w * wb + acts}


def run_dir(ctx: dict) -> str:
    """Where the harness keeps a run's files (run.py: `.bench_out/<cell>`)."""
    return os.path.join(ROOT, ".bench_out", ctx["cell"]["name"])


def capture_counters(ctx: dict):
    """(start, end) of the traced run's `counters.json`, each
    {family: {label text: value}}; None when the run was not traced or the
    program wrote none (a program without the counters)."""
    if not ctx.get("trace"):
        return None
    found = sorted(glob.glob(os.path.join(run_dir(ctx), "**", "counters.json"),
                             recursive=True))
    if not found:
        return None
    with open(found[-1]) as f:
        doc = json.load(f)
    return doc.get("start") or {}, doc.get("end") or {}


def family_delta(counters, family: str):
    """{label text: end - start} of one family over the capture; None when
    the family is missing."""
    if counters is None or family not in counters[1]:
        return None
    start, end = counters[0].get(family, {}), counters[1][family]
    return {k: v - start.get(k, 0) for k, v in end.items()}


def experts_hit_mean(ctx: dict):
    """Distinct experts a slot-cache (decode) call read a layer, the mean
    over the capture; None without the counters."""
    c = capture_counters(ctx)
    hit = family_delta(c, "kft_moe_experts_hit_total")
    calls = family_delta(c, "kft_moe_decode_layer_calls_total")
    if not hit or not calls or not calls[""]:
        return None
    return hit[""] / calls[""]


def by_layer_and_expert(delta: dict) -> dict:
    """{(layer, expert): n} from the labels of `kft_moe_assignments_total`."""
    out = {}
    for labels, n in delta.items():
        m = re.fullmatch(r'layer="(\d+)",expert="(\d+)"', labels)
        if m:
            out[int(m.group(1)), int(m.group(2))] = n
    return out
