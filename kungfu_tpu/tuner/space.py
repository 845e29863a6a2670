"""Compute-tuner search space: per-(shape × backend × batch) step configs.

The collective planner (kungfu_tpu/planner) searches over how gradients
move; this space describes how the *step itself* computes.  One
`StepConfig` is a full step-graph configuration:

  flash tiling    (block_q, block_k) of the Pallas flash kernels plus the
                  backward arm ("pallas" two-kernel split vs "xla" blocked
                  scan);
  head layout     head_dim factorization of d_model for MHA models
                  (16×64 vs 8×128 at d_model 1024): the parameter count
                  and math are identical, but head_dim 64 half-fills the
                  MXU's 128-lane contraction (RESULTS.md r4 timing
                  decomposition) while 128 is MXU-native;
  remat           per-block rematerialization off/on plus the
                  jax.checkpoint policy ("none" = save everything,
                  "full" = recompute everything, "dots" =
                  checkpoint_policies.dots_saveable: keep matmul outputs,
                  recompute the cheap elementwise tail);
  chunked CE      the streaming lm-head chunk size (0 = dense [B, L, V]
                  logits; >0 = ops/chunked_ce with that vocab block);
  donation        donate the train-step params/opt buffers (halves the
                  state's HBM high-water mark) — plus the PR-9 bucketed
                  gradient-sync layout (bucket_bytes, 0 = XLA's single
                  fused tree).

A `ShapeKey` pins the identity the tuning is valid for — model dims, seq,
per-chip batch, dtype — and digests to the prior-cache key together with
the backend and jax version (tuner/cache.py).

Configs are frozen, hashable and JSON round-trippable (the cache format).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import List, Optional, Sequence, Tuple

#: flash tile sweep (measure.flash_sweep's arms)
DEFAULT_BLOCKS: Tuple[Tuple[int, int], ...] = (
    (128, 128), (256, 256), (512, 512), (256, 512), (512, 1024),
)

#: head_dim layouts worth trying for MHA models (must divide d_model)
HEAD_DIMS: Tuple[int, ...] = (64, 128)

#: remat arms: (remat on/off, jax.checkpoint policy name)
REMAT_ARMS: Tuple[Tuple[bool, str], ...] = (
    (False, "none"), (True, "full"), (True, "dots"),
)

#: chunked-CE vocab block sizes (0 = dense logits)
DEFAULT_CE_CHUNKS: Tuple[int, ...] = (0, 2048, 8192)

#: PR-9 gradient-sync bucket sizes (0 = single fused tree)
DEFAULT_BUCKET_BYTES: Tuple[int, ...] = (0, 4 << 20)


@dataclasses.dataclass(frozen=True)
class ShapeKey:
    """What a tuned config is valid for: model shape × seq × batch × dtype.

    `n_heads` is part of the identity (a user who *declares* 8 heads is
    tuning a different model object than one who declares 16, even when
    the head-layout search can reach the same math)."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int  # 0 = MHA
    d_ff: int
    seq_len: int
    batch_per_chip: int
    dtype: str = "bfloat16"
    causal: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def tokens_per_step(self) -> int:
        return self.batch_per_chip * self.seq_len

    def n_params(self) -> int:
        """Analytic parameter count (gelu 2-matmul FFN, untied head) —
        the 6N FLOP accounting's N, good to ~1% for the flagship."""
        per_layer = 4 * self.d_model * self.d_model + 2 * self.d_model * self.d_ff
        return self.n_layers * per_layer + 2 * self.vocab_size * self.d_model

    def flops_per_token(self) -> int:
        """Standard 6N + attention-matrix accounting."""
        attn = 12 * self.n_layers * self.seq_len * self.d_model
        if self.causal:
            attn //= 2
        return 6 * self.n_params() + attn

    def digest(self) -> str:
        return hashlib.sha256(
            json.dumps(dataclasses.asdict(self), sort_keys=True).encode()
        ).hexdigest()[:16]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "ShapeKey":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})

    @classmethod
    def of(cls, cfg, batch_per_chip: int,
           seq_len: Optional[int] = None) -> "ShapeKey":
        """Build from a TransformerConfig-like object (duck-typed, so the
        tuner never imports models.transformer at module load)."""
        import jax.numpy as jnp

        return cls(
            vocab_size=int(cfg.vocab_size), d_model=int(cfg.d_model),
            n_layers=int(cfg.n_layers), n_heads=int(cfg.n_heads),
            n_kv_heads=int(getattr(cfg, "n_kv_heads", 0) or 0),
            d_ff=int(cfg.d_ff),
            seq_len=int(seq_len if seq_len is not None else cfg.max_len),
            batch_per_chip=int(batch_per_chip),
            dtype=jnp.dtype(cfg.dtype).name,
            causal=bool(cfg.causal),
        )


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """One candidate step-graph configuration (frozen, JSON-stable)."""

    block_q: int = 128
    block_k: int = 128
    backward: str = "auto"       # "auto" | "pallas" | "xla"
    head_dim: int = 64           # MHA layout choice; == shape head_dim when kept
    remat: bool = False
    remat_policy: str = "none"   # "none" | "full" | "dots"
    ce_chunk: int = 0            # 0 = dense logits
    donate: bool = True
    bucket_bytes: int = 0        # 0 = single fused gradient tree

    def describe(self) -> str:
        remat = self.remat_policy if self.remat else "off"
        ce = str(self.ce_chunk) if self.ce_chunk else "dense"
        return (f"flash{self.block_q}x{self.block_k}/{self.backward}"
                f"|h{self.head_dim}|remat:{remat}|ce:{ce}"
                f"|donate:{int(self.donate)}|bucket:{self.bucket_bytes}")

    def n_heads_for(self, shape: ShapeKey) -> int:
        return shape.d_model // self.head_dim

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "StepConfig":
        return cls(**{f.name: d[f.name]
                      for f in dataclasses.fields(cls) if f.name in d})


def default_config(shape: ShapeKey) -> StepConfig:
    """The hand-tuned baseline a step runs with before any tuning: 128×128
    flash tiles, auto backward, the declared head layout, no remat, dense
    head, donated buffers, XLA's fused gradient tree.  Always a runoff
    control (planner-style) — the tuned winner can never lose to it."""
    return StepConfig(head_dim=shape.head_dim)


def head_dim_choices(shape: ShapeKey) -> Tuple[int, ...]:
    """Layouts the search may re-factor d_model into.  Only MHA models
    (n_kv_heads 0): under GQA the kv-head count is a *model* property the
    tuner must not silently change.  RoPE needs an even head_dim."""
    dims = [shape.head_dim]
    if shape.n_kv_heads == 0:
        for d in HEAD_DIMS:
            if d != shape.head_dim and shape.d_model % d == 0 and d % 2 == 0:
                dims.append(d)
    return tuple(dims)


def enumerate_configs(
    shape: ShapeKey,
    blocks: Sequence[Tuple[int, int]] = DEFAULT_BLOCKS,
    ce_chunks: Sequence[int] = DEFAULT_CE_CHUNKS,
    bucket_bytes: Sequence[int] = DEFAULT_BUCKET_BYTES,
    backwards: Sequence[str] = ("pallas", "xla"),
    remat_arms: Sequence[Tuple[bool, str]] = REMAT_ARMS,
    donations: Sequence[bool] = (True, False),
) -> List[StepConfig]:
    """The full candidate set for one shape.

    Structurally invalid points are never emitted (tiles larger than the
    padded sequence collapse to the same kernel; CE chunks beyond the
    vocab are the dense head in disguise); the footprint model prunes the
    rest (tuner/footprint.py)."""
    seen = set()
    out: List[StepConfig] = []
    for hd in head_dim_choices(shape):
        for bq, bk in blocks:
            # tiles clamp to the sequence inside flash_attention; emitting
            # both a clamped and an unclamped spelling would just measure
            # the same kernel twice
            cbq = min(bq, max(8, shape.seq_len))
            cbk = min(bk, max(8, shape.seq_len))
            for bwd in backwards:
                for remat, policy in remat_arms:
                    for ce in ce_chunks:
                        if ce and ce >= shape.vocab_size:
                            continue  # dense head in disguise
                        for bb in bucket_bytes:
                            for donate in donations:
                                cfg = StepConfig(
                                    block_q=cbq, block_k=cbk, backward=bwd,
                                    head_dim=hd, remat=remat,
                                    remat_policy=policy if remat else "none",
                                    ce_chunk=int(ce), donate=bool(donate),
                                    bucket_bytes=int(bb),
                                )
                                if cfg not in seen:
                                    seen.add(cfg)
                                    out.append(cfg)
    return out
