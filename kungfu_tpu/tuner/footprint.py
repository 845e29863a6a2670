"""VMEM/HBM footprint model + analytic step-time predictor.

The pruning half of the tuner's candidate/cost/runoff skeleton (the
compute analog of planner/cost.py): before anything is measured, every
`StepConfig` is checked against

  VMEM   the flash kernels keep one q tile plus the FULL padded K/V rows
         resident per grid step (ops/flash.py BlockSpecs) — a tile choice
         that blows the `KFT_PALLAS_VMEM_MIB` scratch budget (the same
         knob the Pallas ring collectives honor) is rejected before it
         can wedge a chip;
  HBM    parameters + optimizer state (+ a non-donated double buffer),
         saved activations under the chosen remat policy, and the logits
         tensor (dense head) vs one streamed chunk (chunked CE), against
         `KFT_TUNER_HBM_GIB` (default 16, the v5e budget).

Survivors are ranked by `predict_step_ms` — a roofline (max of MXU time
at a layout-dependent efficiency and HBM time at the modeled traffic).
The constants are priors, not truth: the measured runoff decides, and
the bench reports predicted-vs-measured rel_err so the model's honesty
stays visible (the planner's contract).
"""
from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

from ..compat import VMEM_ENV, vmem_budget_bytes
from ..ops import flash
from .space import ShapeKey, StepConfig

#: HBM budget (GiB) for the footprint gate
HBM_ENV = "KFT_TUNER_HBM_GIB"
DEFAULT_HBM_GIB = 16.0

#: peak dense bf16 FLOP/s and HBM B/s per chip by device_kind prefix
#: (longest prefix wins at lookup); the benchmark keeps its own table,
#: benchmark/lib/peaks.json, and the library must not import it
PEAK_SPECS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}

#: MXU efficiency prior by head_dim: 64 half-fills the 128-lane
#: contraction (RESULTS.md r4 timing decomposition), 128 is MXU-native.
#: Calibrated so the flagship 16×64 arm lands near its measured 0.27 MFU.
_HEAD_DIM_EFF = {64: 0.45, 128: 0.62}


def hbm_budget_bytes() -> int:
    try:
        return int(float(os.environ.get(HBM_ENV, str(DEFAULT_HBM_GIB)))
                   * (1 << 30))
    except ValueError:
        return int(DEFAULT_HBM_GIB * (1 << 30))


def peak_specs(device_kind: str) -> Tuple[Optional[float], Optional[float]]:
    for k in sorted(PEAK_SPECS, key=len, reverse=True):
        if device_kind and device_kind.startswith(k):
            return PEAK_SPECS[k]
    return (None, None)


def _dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}.get(dtype, 2)


def flash_vmem_bytes(cfg: StepConfig, shape: ShapeKey) -> int:
    """Resident VMEM of one flash fwd grid step under this tiling
    (ops/flash.py keeps the model beside the kernels' BlockSpecs)."""
    return flash.flash_vmem_bytes(cfg.block_q, cfg.block_k, cfg.head_dim,
                                  shape.seq_len, _dtype_bytes(shape.dtype))


def step_hbm_bytes(cfg: StepConfig, shape: ShapeKey) -> Dict[str, int]:
    """Modeled HBM high-water mark of one train step, by component."""
    n = shape.n_params()
    b, l, dm, v = (shape.batch_per_chip, shape.seq_len, shape.d_model,
                   shape.vocab_size)
    db = _dtype_bytes(shape.dtype)
    # fp32 master params + adam m/v
    state = 12 * n
    if not cfg.donate:
        state *= 2  # un-donated steps double-buffer params + opt state
    if cfg.remat:
        if cfg.remat_policy == "dots":
            # dots_saveable keeps the matmul outputs per block:
            # q/k/v/attn-out/mlp-out (~5·d) plus the d_ff hidden
            per_layer = b * l * (5 * dm + shape.d_ff) * db
        else:
            per_layer = b * l * dm * db  # block inputs only
    else:
        # every intermediate saved: ~10 activation-sized tensors per block
        per_layer = 10 * b * l * dm * db
    acts = shape.n_layers * per_layer
    if cfg.ce_chunk:
        # streamed head: one [N, block] logits block live at a time
        # (recomputed in backward), plus the [N] running stats
        logits = b * l * (cfg.ce_chunk + 3) * 4
    else:
        logits = 2 * b * l * v * 4  # f32 logits + their cotangent
    # PR-9 bucketed sync stages one flat bucket buffer
    bucket = cfg.bucket_bytes if cfg.bucket_bytes else 0
    total = state + acts + logits + bucket
    return {"state": state, "activations": acts, "logits": logits,
            "bucket": bucket, "total": total}


def check_fit(cfg: StepConfig, shape: ShapeKey) -> Optional[str]:
    """None when the config fits both budgets, else the rejection reason
    (the footprint gate's single entry point — rejected configs journal
    `tuner_rejected` and can never rank)."""
    vmem = flash_vmem_bytes(cfg, shape)
    if vmem > vmem_budget_bytes():
        return (f"flash tile {cfg.block_q}x{cfg.block_k} needs "
                f"{vmem >> 20} MiB VMEM > {VMEM_ENV}="
                f"{vmem_budget_bytes() >> 20} MiB")
    hbm = step_hbm_bytes(cfg, shape)
    if hbm["total"] > hbm_budget_bytes():
        return (f"step footprint {hbm['total'] >> 30} GiB > {HBM_ENV}="
                f"{hbm_budget_bytes() >> 30} GiB "
                f"(state {hbm['state'] >> 20} MiB, activations "
                f"{hbm['activations'] >> 20} MiB, logits "
                f"{hbm['logits'] >> 20} MiB)")
    return None


def predict_step_ms(cfg: StepConfig, shape: ShapeKey,
                    peak_flops: Optional[float] = None,
                    peak_hbm: Optional[float] = None) -> float:
    """Roofline estimate of one step: max(MXU time, HBM time) in ms.

    Absolute accuracy is not the point (the runoff measures); the model
    only has to ORDER candidates well enough that the top-k contains the
    winner.  Known effects encoded: head_dim lane fill, tile-bookkeeping
    amortization (larger tiles spend fewer VPU passes per element), remat
    recompute factors, the chunked head's extra logit pass, un-donated
    state copies.
    """
    if peak_flops is None or peak_hbm is None:
        tpk, hpk = _device_peaks()
        peak_flops = peak_flops if peak_flops is not None else tpk
        peak_hbm = peak_hbm if peak_hbm is not None else hpk
    flops = float(shape.flops_per_token()) * shape.tokens_per_step
    eff = _HEAD_DIM_EFF.get(cfg.head_dim, 0.5)
    # larger tiles amortize the per-block online-softmax bookkeeping
    # (~2%/doubling vs the 128x128 baseline, the hunt's observed slope)
    tile_factor = 1.0 + 0.02 * math.log2(
        max(cfg.block_q * cfg.block_k, 1) / float(128 * 128))
    eff = min(eff * max(tile_factor, 0.5), 0.95)
    if cfg.remat:
        flops *= (7.0 / 6.0) if cfg.remat_policy == "dots" else (4.0 / 3.0)
    if cfg.ce_chunk:
        # one extra streamed head matmul in backward
        flops += 2.0 * shape.tokens_per_step * shape.d_model * shape.vocab_size
    compute_ms = flops / (peak_flops * eff) * 1e3
    hbm = step_hbm_bytes(cfg, shape)
    # traffic ~ 3 passes over state (read, grad write, update) + the
    # activation working set twice (save + backward read)
    traffic = 3 * hbm["state"] + 2 * (hbm["activations"] + hbm["logits"])
    hbm_ms = traffic / peak_hbm * 1e3
    return max(compute_ms, hbm_ms)


def _device_peaks() -> Tuple[float, float]:
    """Peaks for the live device, with a CPU-host floor so ranking still
    works (and stays deterministic) off-TPU."""
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:
        kind = ""
    flops, hbm = peak_specs(kind)
    return (flops or 1e12, hbm or 50e9)
