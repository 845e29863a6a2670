"""Compute autotuner — the MFU chase as a subsystem (ROADMAP item 5a).

Per (model shape × backend × batch), searches step-graph configurations
— flash tiles + backward arm, head layout, remat policy, chunked-CE
chunk, donation and gradient-sync buckets — prunes with a VMEM/HBM
footprint model, runs a measured runoff (the hand-tuned default always a
control), and persists winners in a JSON prior cache keyed
(shape digest | backend | jax version).  The shape defaults a model runs
with untuned live beside their kernels (`default_flash_blocks` in
ops/flash.py, `default_ce_block` in ops/chunked_ce.py,
`default_bucket_bytes` in optimizers/sync.py) and are re-exported here;
a winner reaches a model through `ComputeTuner.apply`.  See
docs/tuning.md.
"""
from ..ops.chunked_ce import default_ce_block
from ..ops.flash import default_flash_blocks
from ..optimizers.sync import default_bucket_bytes
from .cache import PriorCache, backend_name, default_cache_path, jax_version
from .core import ComputeTuner, resolve_flash_blocks
from .footprint import (
    check_fit,
    flash_vmem_bytes,
    predict_step_ms,
    step_hbm_bytes,
)
from .measure import flash_sweep, measure_step, probe_peak
from .space import ShapeKey, StepConfig, default_config, enumerate_configs

__all__ = [
    "ComputeTuner",
    "PriorCache",
    "ShapeKey",
    "StepConfig",
    "backend_name",
    "check_fit",
    "default_bucket_bytes",
    "default_cache_path",
    "default_ce_block",
    "default_config",
    "default_flash_blocks",
    "enumerate_configs",
    "flash_sweep",
    "flash_vmem_bytes",
    "jax_version",
    "measure_step",
    "predict_step_ms",
    "probe_peak",
    "resolve_flash_blocks",
    "step_hbm_bytes",
]
