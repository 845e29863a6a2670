"""The runtime gate and the VMEM budget of the Pallas kernels.

`pallas_mode` is the one place that decides whether a Pallas kernel (the
flash attention kernels, the ring collectives, the fused matmuls) runs
compiled, interpreted or not at all: compiled on TPU, interpreter under
KFT_PALLAS=interpret (the CPU test path), and "off" everywhere else so
callers take the lax.* / plain-XLA lowerings.
"""
from __future__ import annotations

import os

import jax

#: override (MiB) of the VMEM budget below — a deployment setting, and how
#: the tests shrink the budget
VMEM_ENV = "KFT_PALLAS_VMEM_MIB"


def vmem_budget_bytes() -> int:
    """VMEM one Pallas kernel may use.  Stated once: the tile gates
    (tuner/footprint.py, ops/pallas_collectives.py) check candidates
    against this number, and the flash kernels hand the same number to
    Mosaic as `vmem_limit_bytes`, so a tile the gate lets through is not
    then refused under Mosaic's smaller default.

    Derived from the device: half the core's VMEM (64 MiB on a v5e), the
    other half left to the compiler.  Off TPU there is no device to ask and
    the v5e figure stands in, so the gates decide as they would there.
    """
    env = os.environ.get(VMEM_ENV, "")
    if env:
        return int(env) << 20
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.get_tpu_info().vmem_capacity_bytes // 2
    return 64 << 20


def pallas_mode(interpret=None) -> str:
    """How a Pallas kernel should run here: "compiled" | "interpret" | "off".

      interpret=True   force the Pallas interpreter — the tier-1-testable
                       path: kernel *semantics* (DMA schedule, in-kernel
                       codec) run on CPU against the XLA lowerings.
      interpret=False  force a compiled kernel (TPU only; caller's promise).
      None             TPU backend -> "compiled"; otherwise KFT_PALLAS=
                       interpret (or KFT_PALLAS_INTERPRET=1) -> "interpret",
                       else "off" — callers fall back to the lax.* lowering,
                       so every training path stays green off-TPU without
                       paying the interpreter's per-op cost.
    """
    if interpret is True:
        return "interpret"
    if interpret is False:
        return "compiled"
    if jax.default_backend() == "tpu":
        return "compiled"
    env = os.environ.get("KFT_PALLAS", "")
    if env == "interpret" or os.environ.get("KFT_PALLAS_INTERPRET") == "1":
        return "interpret"
    return "off"
