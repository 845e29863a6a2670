"""Compressed collectives: quantized AllReduce + error feedback.

KungFu's thesis is that communication strategy is a tunable of training
(ROADMAP north star; plan/strategy.py routes); this subsystem extends the
tunable from *route* to *representation*: what bytes the collective moves.
EQuARX (PAPERS.md) shows block-quantized AllReduce inside XLA gives near-2x
collective speedups at negligible quality cost; GC3 argues such transforms
should be first-class programmable constructs.  Layout:

  config.py          CompressionConfig (frozen/hashable), named registry,
                     per-axis selection ({"ici": None, "dcn": INT8})
  quant.py           block-wise int8/fp8 quantize/dequantize (per-block f32
                     scales, optional stochastic rounding) — pure JAX,
                     lowers on TPU, nests in shard_map
  collectives.py     compressed primitives: quantized RS->AG allreduce
                     (fp32 accumulators), compressed cross_all_reduce,
                     per-axis hierarchical allreduce, top-k/random-k
                     sparsified pair exchange for the gossip path
  error_feedback.py  EF residual pytree so compression error feeds back
                     into the next step's gradients

Consumers: optimizers/sync.py (compression= on the gradient allreduce),
optimizers/gossip.py (sparse pair exchange), fsdp.py (compressed dp leg),
optimizers/adaptive.py (GNS-driven bit-width switching in-program),
policy.py (host-side switching), Session.all_reduce(compression=...),
and monitor/counters.py (bytes-on-wire + quantization-error gauges).
"""
from .config import (
    AxisCompression,
    AxisConfig,
    CompressionConfig,
    BF16,
    FP8,
    INT8,
    INT8_SR,
    NONE,
    RANDK_1PCT,
    TOPK_1PCT,
    register,
    registered,
    resolve,
    resolve_for_axis,
    validate_axis_keys,
)
from .quant import (
    QTensor,
    dequantize,
    pad_to_block,
    quantization_error,
    quantize,
    roundtrip,
    sparsify,
)
from .collectives import (
    all_reduce,
    compressed_pair_average,
    cross_all_reduce,
    group_all_reduce,
    hierarchical_all_reduce,
    sparse_pair_exchange,
)
from . import error_feedback
from .error_feedback import EFState

__all__ = [
    "AxisCompression", "AxisConfig", "CompressionConfig",
    "NONE", "BF16", "INT8", "INT8_SR", "FP8", "TOPK_1PCT", "RANDK_1PCT",
    "register", "registered", "resolve", "resolve_for_axis",
    "validate_axis_keys",
    "QTensor", "quantize", "dequantize", "roundtrip", "pad_to_block",
    "quantization_error", "sparsify",
    "all_reduce", "cross_all_reduce", "hierarchical_all_reduce",
    "group_all_reduce", "sparse_pair_exchange", "compressed_pair_average",
    "error_feedback", "EFState",
]
