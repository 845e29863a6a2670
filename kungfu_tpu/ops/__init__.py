from .collective import (
    all_reduce,
    psum_all_reduce,
    rs_ag_all_reduce,
    ring_all_reduce,
    hierarchical_all_reduce,
    broadcast,
    all_gather,
    reduce_scatter,
    reduce,
    barrier,
    consensus,
    group_all_reduce,
    ppermute_pair_exchange,
)

# Pallas DMA collective entry points (ops/pallas_collectives.py) — exported
# so callers stop deep-importing the module.  `ring_all_reduce` above stays
# the lax ring (the historical binding); the hand-scheduled kernel wrappers
# carry the pallas_ prefix.
from .pallas_collectives import (
    fused_ring_all_reduce,
    ring_all_gather as pallas_ring_all_gather,
    ring_all_reduce as pallas_ring_all_reduce,
    ring_reduce_scatter as pallas_ring_reduce_scatter,
)

# Fused computation-collective matmuls (ops/fused_matmul.py): a sharded
# matmul's unshard/epilogue and ring attention's KV hop on the DMA data plane.
from .fused_matmul import (
    all_gather_matmul,
    matmul_reduce_scatter,
    ring_shift,
)

# Decode-step attention over the slot cache (ops/decode_attn.py): the
# length-aware kernel on TPU, the dense einsum everywhere else.
from .decode_attn import (decode_attention, decode_attention_reference,
                          mla_decode_attention,
                          mla_decode_attention_reference)

__all__ = [
    "all_reduce", "psum_all_reduce", "rs_ag_all_reduce", "ring_all_reduce",
    "hierarchical_all_reduce", "broadcast", "all_gather", "reduce_scatter",
    "reduce", "barrier", "consensus", "group_all_reduce", "ppermute_pair_exchange",
    "pallas_ring_all_reduce", "fused_ring_all_reduce",
    "pallas_ring_reduce_scatter", "pallas_ring_all_gather",
    "all_gather_matmul", "matmul_reduce_scatter",
    "ring_shift",
    "decode_attention", "decode_attention_reference",
]
