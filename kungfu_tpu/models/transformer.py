"""Flagship decoder-only transformer LM — TP/SP/DP-shardable, ring-attention
capable, optional MoE layers, GQA/MQA (n_kv_heads), RoPE, SwiGLU.

The reference is model-agnostic DP (it ships no transformer); this is the
TPU-first flagship exercising every parallelism axis the framework offers:

  dp/fsdp  batch via the trainer (data axis)
  tp       Megatron-style column/row-parallel QKV/MLP via logical axes
           ("heads", "mlp", "vocab" -> tp); XLA inserts the psums
  sp       ring attention over the "sp" axis (parallel/ring_attention.py) —
           the sequence never materializes on one chip
  ep       sparse-expert blocks, experts sharded over ep (parallel/moe.py)

Params carry flax logical-axis metadata; map them onto a mesh with
parallel/sharding.py's rules.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.chunked_ce import chunked_lm_head_ll
from ..parallel.sharding import logical_constraint
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import pallas_mode
from ..parallel.ring_attention import full_attention, ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    d_ff: int = 2048
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    # "auto" = flash kernel on TPU, plain einsum elsewhere (the Pallas
    # kernel would run interpreted off-TPU); "ring"/"ulysses" =
    # sequence-parallel (K/V rotation vs head all_to_all; parallel/
    # ring_attention.py and parallel/ulysses.py document the trade-off)
    attention: str = "auto"  # "auto" | "flash" | "full" | "ring" | "ulysses"
    causal: bool = True
    # grouped-query attention: number of K/V heads (0 = n_heads, i.e. MHA;
    # 1 = MQA).  Every attention path is GQA-native when tp divides
    # n_kv_heads: flash index-maps the shared kv heads, full/ring use
    # grouped einsums on the un-repeated kv (the ring's rotating payload
    # stays Hkv-sized), ulysses all_to_alls the Hkv-sized payload when sp
    # also divides the per-shard kv heads (internal broadcast otherwise),
    # and decode groups queries against the un-repeated cache.
    n_kv_heads: int = 0
    # rotary position embeddings instead of the learned pos_embed table.
    # Applied to q/k on the GLOBAL sequence positions before any
    # sequence-parallel region, so ring/ulysses shards see correct offsets.
    rope: bool = False
    rope_theta: float = 10000.0
    # sliding-window (local) attention: 0 = unlimited; >0 = each query
    # attends only the last `window` positions (flash kernels skip the
    # dead blocks).  Supported by the "flash"/"full" paths; requires causal
    window: int = 0
    # flash-kernel tile sizes (q rows / k columns per block).  None =
    # "ask the compute tuner": the prior cache's measured winner for this
    # exact (shape, backend, jax version) when one exists, else the
    # shape-conditional hunt-winner defaults, clamped to the VMEM budget
    # (kungfu_tpu/tuner/core.resolve_flash_blocks — the round-5
    # scripts/mfu_hunt.py sweep landed in-library).  Explicit ints always
    # win.  Only the "flash" path reads them.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # flash backward arm: None = per-shape auto (ops/flash.py), "pallas"
    # or "xla" pin one — the tuner installs the arm its runoff measured
    flash_backward: Optional[str] = None
    # feed-forward flavor: "gelu" (2-matmul) or "swiglu" (gated, 3-matmul)
    ffn: str = "gelu"
    # normalization flavor: "layer" (LayerNorm, no bias) or "rms"
    # (RMSNorm) — rms + rope + GQA + swiglu is the Llama-class recipe
    # (models/hf.py loads HF Llama checkpoints into exactly that config).
    # Both store a single "scale" param, so the tree shape is identical.
    norm: str = "layer"  # "layer" | "rms"
    norm_eps: float = 1e-6
    # bias vectors on the q/k/v projections only (Qwen2-style; the output
    # projection and MLP stay bias-free).  Default False keeps the
    # historical param tree.
    attention_bias: bool = False
    # dropout on embeddings and each residual branch, active only when the
    # model is applied with train=True and an rngs={"dropout": key}
    # (MeshTrainer threads a per-step key to 4-arg loss functions)
    dropout: float = 0.0
    # share the input embedding matrix with the lm_head (logits = x @ E^T)
    tie_embeddings: bool = False
    # checkpoint each transformer block: trade ~1/3 extra forward FLOPs
    # for not storing per-layer activations — the standard long-sequence
    # memory lever (jax.checkpoint / nn.remat per block)
    remat: bool = False
    # remat policy (remat=True only): "full" (= "none" here, recompute
    # everything — jax.checkpoint's default) or "dots" =
    # jax.checkpoint_policies.dots_saveable: keep the MXU matmul outputs,
    # recompute only the cheap elementwise tail — ~1/6 extra FLOPs
    # instead of ~1/3 for most of the memory win.  A tuner search axis.
    remat_policy: str = "none"  # "none" | "full" | "dots"
    # "dense" returns [B, L, V] logits; "hidden" returns the final hidden
    # states and defers the head to a streaming loss (lm_loss_chunked /
    # ops/chunked_ce) that never materializes the logits tensor — the
    # large-vocab memory/HBM lever.  The param tree is identical either
    # way (the head kernel is created at init in both modes).
    head: str = "dense"
    # sparse experts (parallel/moe.py): every `moe_every`-th block routes
    # each token to `experts_per_token` of `n_experts` SwiGLU experts of
    # width d_ff, dropless (0 experts = dense model; moe_every=1 = every
    # block).  `norm_topk_prob`: the chosen gate weights renormalised to
    # sum to 1 (the published key of the same name)
    n_experts: int = 0
    moe_every: int = 2
    experts_per_token: int = 1
    norm_topk_prob: bool = False
    # QK-norm: a norm of the config's flavor over the WHOLE q and k
    # projections (own scales, norm_eps), before the head split and rope
    qk_norm: bool = False
    # standard deviation the token embedding is initialised with (every
    # other matrix: 0.02).  It decides what seeded stand-in weights route
    # by: at 0.02 an attention layer's context average outweighs the
    # token in the residual stream, so a router picks one expert set for a
    # whole sequence and greedy decoding repeats one token; at 1.0 the
    # token leads and routing changes with it, as in a trained model
    # (PERF.md section 6, PR 25).  A checkpoint overwrites it
    embed_init_std: float = 0.02
    # mesh is needed for attention="ring"/"ulysses" (shard_map region)
    mesh: Optional[Mesh] = None
    sp_axis: str = "sp"
    # autoregressive decode mode: attention keeps a KV cache ("cache"
    # collection) of max_len positions and consumes 1..n new tokens per
    # call.  Training parallelism axes don't apply; requires rope (the
    # cache index supplies absolute positions).  See `generate`.
    #
    # verify-k contract (speculative serving, serving/spec.py): a decode
    # call with L = k tokens is EXACTLY k chained 1-token calls — per-slot
    # cursors place each token at its own absolute position, the causal
    # mask (`c_pos <= q_pos`) lets position j attend the k/v written at
    # positions <= j within the same call, and every position's logits
    # come back.  That makes one [slots, k] apply a batched verify step
    # whose greedy argmax run is bit-identical to k sequential [slots, 1]
    # steps — the property the serving engine's ONE extra compiled
    # signature (and its in-program acceptance) is built on.
    decode: bool = False
    # KV-cache storage dtype (decode only): "model" stores cfg.dtype;
    # "int8" stores per-(position, kv-head) symmetric-quantized int8 plus
    # f32 scales — half the cache-read HBM traffic (decode's bottleneck)
    # and twice the context per chip.  The dequantize (int8 -> bf16 *
    # scale) fuses into the attention einsum's operand read, so the
    # full-precision cache never materializes in HBM.
    kv_cache_dtype: str = "model"  # "model" | "int8"

    def __post_init__(self):
        assert self.d_model % self.n_heads == 0
        if self.decode:
            assert self.rope, "decode mode requires rope positions"
        if self.n_kv_heads:
            assert self.n_heads % self.n_kv_heads == 0, (
                "query heads must be a multiple of kv heads"
            )
        if self.rope:
            assert (self.d_model // self.n_heads) % 2 == 0, (
                "rope rotates feature pairs: head_dim must be even"
            )
        if self.window:
            assert self.window > 0, "window must be positive (0 = unlimited)"
            assert self.causal, "sliding window requires causal attention"
            assert self.attention in ("auto", "flash", "full"), (
                "sliding window is supported on the flash/full paths"
            )
        assert self.ffn in ("gelu", "swiglu"), self.ffn
        if self.n_experts:
            assert 1 <= self.experts_per_token <= self.n_experts, (
                "experts per token must lie in 1..n_experts"
            )
        assert self.norm in ("layer", "rms"), self.norm
        assert self.remat_policy in ("none", "full", "dots"), self.remat_policy
        assert self.flash_backward in (None, "pallas", "xla"), (
            self.flash_backward
        )
        assert self.head in ("dense", "hidden"), self.head
        assert self.kv_cache_dtype in ("model", "int8"), self.kv_cache_dtype
        if self.decode:
            assert self.head == "dense", "decode/generation needs logits"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads


def _attention_kind(cfg: TransformerConfig) -> str:
    """Resolve attention="auto" through the SAME gate the Pallas kernels
    use (compat.pallas_mode): flash when the kernels can run — compiled on
    TPU, interpreted under KFT_PALLAS=interpret — plain einsum when they
    are off.  Deciding off `jax.default_backend() == "tpu"` directly (the
    old rule) meant interpret-mode CI silently exercised the full-einsum
    path while claiming to test the flash path the tuner tunes."""
    if cfg.attention != "auto":
        return cfg.attention
    return "flash" if pallas_mode() != "off" else "full"


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding on [B, L, H, D] with positions [L] or [B, L].

    Rotates pairs (x[..., :D/2], x[..., D/2:]) in fp32, casts back.  Called
    with GLOBAL positions before any sequence-parallel sharding region, so
    each sp shard's rows carry their true absolute position.  Per-row [B, L]
    positions are the continuous-batching decode shape: every serving slot
    sits at its own cache cursor (serving/engine.py).
    """
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., L, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [(B,) L, 1, half] — bcasts over H
    sin = jnp.sin(ang)[..., :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _dense(features, name, kernel_axes, dtype, use_bias: bool = False):
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=dtype,
        name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), kernel_axes
        ),
        # bias shards with the projection's OUTPUT dim (kernel_axes[-1]):
        # under tp the q/k/v outputs are head-sharded, so the bias is too
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros, (kernel_axes[-1],)
        ),
    )


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        H, D = cfg.n_heads, cfg.d_model // cfg.n_heads
        Hkv = cfg.kv_heads
        B, L, _ = x.shape
        qkv_axes = ("embed", "heads")
        ab = cfg.attention_bias
        def project(name, heads):
            y = _dense(heads * D, name, qkv_axes, cfg.dtype, ab)(x)
            if cfg.qk_norm and name != "v":
                y = _norm(cfg, name + "_norm")(y).astype(cfg.dtype)
            return y.reshape(B, L, heads, D)

        q, k, v = project("q", H), project("k", Hkv), project("v", Hkv)

        if cfg.decode:
            # KV-cache decode: write this call's k/v at the cache cursor,
            # attend q against the whole cache, advance the cursor
            quant = cfg.kv_cache_dtype == "int8"
            cdtype = jnp.int8 if quant else cfg.dtype
            cache_k = self.variable(
                "cache", "cached_k", jnp.zeros, (B, cfg.max_len, Hkv, D), cdtype
            )
            cache_v = self.variable(
                "cache", "cached_v", jnp.zeros, (B, cfg.max_len, Hkv, D), cdtype
            )
            if quant:  # per-(position, kv-head) symmetric scales
                kscale = self.variable(
                    "cache", "scale_k", jnp.zeros, (B, cfg.max_len, Hkv),
                    jnp.float32,
                )
                vscale = self.variable(
                    "cache", "scale_v", jnp.zeros, (B, cfg.max_len, Hkv),
                    jnp.float32,
                )
            else:
                kscale = vscale = None
            # PER-SLOT cursors [B]: every batch row is an independent serving
            # slot with its own write position — the enabler for continuous
            # batching (serving/engine.py packs requests of different ages
            # into one fixed-shape decode batch).  generate() keeps all rows
            # in lockstep, so the [B] shape is invisible to the train path.
            cache_idx = self.variable(
                "cache", "idx", lambda: jnp.zeros((B,), jnp.int32)
            )
            # sticky PER-SLOT overflow flags: once a row's write ran past
            # max_len the clamped dynamic_update_slice has clobbered that
            # row's older slots, so EVERY later output of that row is
            # suspect, not just out-of-range positions.  Cleared per slot
            # when the serving engine re-prefills it.
            cache_ovf = self.variable(
                "cache", "overflowed", lambda: jnp.zeros((B,), jnp.bool_)
            )
            idx0 = cache_idx.value                      # [B]
            pos = idx0[:, None] + jnp.arange(L)[None, :]  # [B, L]
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)

            def quantize(x):
                """[B, L, Hkv, D] -> (int8 values, f32 scales [B, L, Hkv])."""
                xf = x.astype(jnp.float32)
                sc = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
                qx = jnp.clip(
                    jnp.round(xf / sc[..., None]), -127, 127
                ).astype(jnp.int8)
                return qx, sc

            def store(cache_var, scale_var, x):
                """Write x at each slot's own cursor (quantizing + scale
                write if int8).  vmapped over the batch dim: rows land at
                per-slot positions, the continuous-batching write shape."""
                if quant:
                    x, sc = quantize(x)
                    scale_var.value = jax.vmap(
                        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0))
                    )(scale_var.value, sc, idx0)
                else:
                    x = x.astype(cache_var.value.dtype)
                cache_var.value = jax.vmap(
                    lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
                )(cache_var.value, x, idx0)

            def load(cache_var, scale_var):
                """Full cache in the model dtype.  int8: the dequant (exact
                for magnitudes <= 127 in bf16) fuses into the attention
                einsum's operand read, so the cache crosses HBM as int8
                bytes."""
                if not quant:
                    return cache_var.value
                return cache_var.value.astype(cfg.dtype) * (
                    scale_var.value.astype(cfg.dtype)[..., None]
                )

            if not self.is_initializing():
                # init() traces the module once to create the cache — it
                # must not write tokens or advance the cursors
                store(cache_k, kscale, k)
                store(cache_v, vscale, v)
                cache_idx.value = idx0 + L
                cache_ovf.value = jnp.logical_or(
                    cache_ovf.value, idx0 + L > cfg.max_len
                )
            kf = load(cache_k, kscale)
            vf = load(cache_v, vscale)
            scale = 1.0 / (D ** 0.5)
            # grouped-query einsum against the UN-repeated cache: decode is
            # cache-read-bound, so neither a jnp.repeat materialization
            # (x H/Hkv bytes under GQA) nor an f32 cast (x2 bytes) of the
            # cache is acceptable — group the query heads instead and keep
            # operands in the cache dtype with f32 accumulation
            G = H // Hkv
            qg = q.reshape(B, L, Hkv, G, D)
            s = jnp.einsum(
                "blkgd,bmkd->bkglm", qg, kf,
                preferred_element_type=jnp.float32,
            ) * scale
            q_pos = pos[:, :, None]                        # [B, L, 1]
            c_pos = jnp.arange(cfg.max_len)[None, None, :]  # [1, 1, max_len]
            valid = c_pos <= q_pos                          # [B, L, max_len]
            if cfg.window:  # sliding-window models decode windowed too
                valid = jnp.logical_and(valid, q_pos - c_pos < cfg.window)
            s = jnp.where(valid[:, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum(
                "bkglm,bmkd->blkgd", p.astype(vf.dtype), vf,
                preferred_element_type=jnp.float32,
            ).reshape(B, L, H, D)
            # a cursor past max_len clamps that row's cache write and
            # clobbers its older slots — poison the ROW with NaN so overflow
            # is LOUD instead of silently-wrong logits (generate() bounds
            # the total; this guards the raw decode apply() surface).  The
            # sticky per-slot flag poisons in-range outputs of overflowing
            # and LATER calls of that slot too: they attend to corrupted
            # K/V.  Other slots stay clean — the serving engine relies on
            # overflow being contained to the offending slot.
            poison = jnp.logical_or(
                (pos >= cfg.max_len)[:, :, None, None],
                cache_ovf.value[:, None, None, None],
            )
            o = jnp.where(poison, jnp.nan, o)
            o = o.astype(cfg.dtype).reshape(B, L, cfg.d_model)
            return _dense(cfg.d_model, "out", ("heads", "embed"), cfg.dtype)(o)

        if cfg.rope:
            # global positions: L here is the full (logical) sequence even
            # when seq is sharded — the constraint below keeps the sharding
            pos = jnp.arange(L)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
        kind = _attention_kind(cfg)
        if Hkv != H:
            # flash (index-mapped kv), full, ring (grouped einsums on the
            # un-repeated kv — the rotated ring payload stays Hkv-sized),
            # and ulysses (kv all_to_all moves the Hkv-sized payload when
            # the sp axis divides the PER-SHARD kv head count, Hkv/tp —
            # it falls back to broadcasting internally otherwise) are all
            # GQA-native, as long as any tp sharding still divides the
            # kv-head axis
            tp = cfg.mesh.shape.get("tp", 1) if cfg.mesh is not None else 1
            if Hkv % tp != 0:
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
        q = logical_constraint(q, ("batch", "seq", "heads", "kv"), cfg.mesh)
        k = logical_constraint(k, ("batch", "seq", "heads", "kv"), cfg.mesh)
        v = logical_constraint(v, ("batch", "seq", "heads", "kv"), cfg.mesh)

        if (
            kind in ("ring", "ulysses")
            and cfg.mesh is not None
            and cfg.sp_axis in cfg.mesh.axis_names
        ):
            names = cfg.mesh.axis_names
            # keep batch on dp (and fsdp) and heads on tp inside the manual
            # region — omitting them would all-gather those dims onto every
            # device
            spec = P(
                tuple(a for a in ("dp", "fsdp") if a in names) or None,
                cfg.sp_axis,
                "tp" if "tp" in names else None,
                None,
            )
            if kind == "ulysses":
                from ..parallel.ulysses import ulysses_attention

                fn = partial(
                    ulysses_attention, axis_name=cfg.sp_axis, causal=cfg.causal
                )
            else:
                fn = partial(ring_attention, axis_name=cfg.sp_axis, causal=cfg.causal)
            # the DMA KV rotation (ops.fused_matmul.ring_shift) traces a
            # pallas_call, which has no replication rule: opt out of the
            # rep/vma check exactly when it engages (Session precedent).
            attn = jax.shard_map(
                fn,
                mesh=cfg.mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=pallas_mode() == "off",
            )
            o = attn(q, k, v)
        elif kind == "flash":
            from ..ops.flash import flash_attention

            # tile resolution: explicit config ints win; None asks the
            # compute tuner's prior cache / shape-conditional defaults
            # (kungfu_tpu/tuner), clamped to the VMEM budget
            from ..tuner import resolve_flash_blocks

            bq, bk = resolve_flash_blocks(cfg, batch=B, seq_len=L)
            if cfg.mesh is not None:
                # pjit path with sharded q/k/v: a pallas_call is not GSPMD-
                # partitionable, so enter a manual region over the batch/head
                # axes (seq stays whole per device — sharded seq is "ring")
                names = cfg.mesh.axis_names
                spec = P(
                    tuple(a for a in ("dp", "fsdp") if a in names) or None,
                    None,
                    "tp" if "tp" in names else None,
                    None,
                )
                # a pallas_call has no replication rule: opt out of the
                # rep/vma check exactly when the flash kernels engage
                # (compiled on TPU or KFT_PALLAS=interpret; the XLA
                # reference path keeps the check)
                attn = jax.shard_map(
                    partial(flash_attention, causal=cfg.causal,
                            window=cfg.window or None,
                            block_q=bq, block_k=bk,
                            backward=cfg.flash_backward),
                    mesh=cfg.mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    check_vma=pallas_mode() == "off",
                )
                o = attn(q, k, v)
            else:
                o = flash_attention(q, k, v, causal=cfg.causal,
                                    window=cfg.window or None,
                                    block_q=bq, block_k=bk,
                                    backward=cfg.flash_backward)
        else:
            o = full_attention(q, k, v, causal=cfg.causal,
                               window=cfg.window or None)

        o = o.reshape(B, L, cfg.d_model)
        return _dense(cfg.d_model, "out", ("heads", "embed"), cfg.dtype)(o)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = _dense(cfg.d_ff, "in", ("embed", "mlp"), cfg.dtype)(x)
        if cfg.ffn == "swiglu":
            gate = _dense(cfg.d_ff, "gate", ("embed", "mlp"), cfg.dtype)(x)
            h = nn.silu(gate) * h
        else:
            h = nn.gelu(h)
        h = logical_constraint(h, ("batch", "seq", "mlp"), cfg.mesh)
        return _dense(cfg.d_model, "out", ("mlp", "embed"), cfg.dtype)(h)


def _norm(cfg, name: str):
    """The config's norm flavor; both flavors store one "scale" param, so
    layer/rms configs share a param-tree shape."""
    kw = dict(
        dtype=jnp.float32, epsilon=cfg.norm_eps, name=name,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones, ("norm",)
        ),
    )
    if cfg.norm == "rms":
        return nn.RMSNorm(**kw)
    return nn.LayerNorm(use_bias=False, **kw)


class Block(nn.Module):
    cfg: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg
        ln = partial(_norm, cfg)
        drop = nn.Dropout(cfg.dropout, deterministic=not train)
        x = x + drop(Attention(cfg, name="attn")(ln(name="ln1")(x)))
        if self.use_moe:
            from ..parallel.moe import MoE

            x = x + drop(MoE(cfg, name="moe")(ln(name="ln2")(x)))
        else:
            x = x + drop(MLP(cfg, name="mlp")(ln(name="ln2")(x)))
        return logical_constraint(x, ("batch", "seq", "act_embed"), cfg.mesh)


class _Head(nn.Module):
    """lm_head projection with a use-site-gathered kernel.

    Same param tree as the nn.Dense it replaces (params["lm_head"]
    ["kernel"]).  The kernel is STORED under the rules' sharding (fsdp
    shards it) but GATHERED at use: without the constraint, the backward
    dot that produces the sharded kernel grad makes the partitioner
    reshard the batch-sharded logits cotangent (B, L, V) to the kernel's
    layout — an involuntary full remat of an activation-sized tensor.
    Gathered, the grad is computed partial+psum then sliced: weight-sized
    traffic, the ZeRO-3 contract.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        w = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("embed", "vocab")
            ),
            (cfg.d_model, cfg.vocab_size),
            jnp.float32,
        )
        # "act_vocab" (not "vocab"): keeps the kernel tp-sharded on tp
        # meshes (Megatron vocab-parallel logits) while gathering the
        # fsdp storage dims
        w = logical_constraint(w, (None, "act_vocab"), cfg.mesh)
        return jnp.einsum("bld,dv->blv", x.astype(jnp.float32), w)


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        cfg = self.cfg
        B, L = tokens.shape
        emb = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed",
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=cfg.embed_init_std),
                ("vocab", "embed")
            ),
        )
        # pin the lookup output to the activation layout immediately: the
        # table's embed dim may be fsdp-sharded (ZeRO-3), and without the
        # constraint the gather output inherits that feature-dim sharding
        x = logical_constraint(
            emb(tokens), ("batch", "seq", "act_embed"), cfg.mesh
        )
        if not cfg.rope:  # rope applies per-layer in Attention instead
            pos = self.param(
                "pos_embed",
                nn.with_logical_partitioning(nn.initializers.normal(stddev=0.02), ("seq", "embed")),
                (cfg.max_len, cfg.d_model),
                jnp.float32,
            )
            # use-site gather: pos_embed's PARAM embed dim may be
            # fsdp-sharded (ZeRO-3); adding it raw would make the
            # partitioner reshard the batch-sharded activation to the
            # table's layout (observed: involuntary full remat in the
            # dp x fsdp dryrun).  Constraining the use to the activation
            # layout all-gathers the small table instead.
            p = logical_constraint(
                pos[None, :L].astype(cfg.dtype), (None, "seq", "act_embed"),
                cfg.mesh,
            )
            x = x + p
        x = nn.Dropout(cfg.dropout, deterministic=not train)(x)
        x = logical_constraint(x, ("batch", "seq", "act_embed"), cfg.mesh)
        # per-block remat: backward recomputes each block's forward
        # instead of reading every intermediate from HBM — at seq 2048+
        # the saved activations (~O(10 * B*L*D) bf16 per layer) dominate
        # HBM, and recompute costs ~1/3 extra forward FLOPs (or ~1/6
        # under remat_policy="dots", which keeps the matmul outputs and
        # recomputes only the elementwise tail — the tuner's middle
        # ground).  Stable block_{i} names keep the param tree identical
        # across the flags.
        if cfg.remat:
            remat_kw = {}
            if cfg.remat_policy == "dots":
                remat_kw["policy"] = jax.checkpoint_policies.dots_saveable
            block_cls = nn.remat(Block, static_argnums=(2,), **remat_kw)
        else:
            block_cls = Block
        for i in range(cfg.n_layers):
            use_moe = cfg.n_experts > 0 and (i % cfg.moe_every == cfg.moe_every - 1)
            x = block_cls(cfg, use_moe=use_moe, name=f"block_{i}")(x, train)
        x = _norm(cfg, "ln_f")(x)
        if cfg.head == "hidden":
            # deferred head: the streaming loss (lm_loss_chunked) consumes
            # hidden states + the head kernel directly.  Touch the head at
            # init so the param tree matches head="dense" exactly.
            if not cfg.tie_embeddings and self.is_initializing():
                _Head(cfg, name="lm_head")(x[:, :1])
            return x
        if cfg.tie_embeddings:
            # logits = x @ E^T with the INPUT embedding, in f32 to match
            # the untied lm_head's precision (bf16 logits would noisily
            # round the loss over a large vocab)
            e = nn.meta.unbox(emb.variables["params"]["embedding"])
            # use-site gather (ZeRO-3): the stored table may be
            # fsdp-sharded; used raw, the partitioner reshards the big
            # batch-sharded logits cotangent to the table's layout in
            # backward (involuntary full remat).  Constrained replicated,
            # forward all-gathers the table and backward computes the
            # table grad as partial+psum then slices — weight-sized
            # traffic instead of activation-sized.
            e = logical_constraint(e, ("act_vocab", None), cfg.mesh)
            logits = jnp.einsum(
                "bld,vd->blv", x.astype(jnp.float32), e.astype(jnp.float32)
            )
        else:
            logits = _Head(cfg, name="lm_head")(x)
        # batch-sharded logits ("act_vocab" keeps tp vocab-parallelism,
        # resolves to None under fsdp): without this the partitioner may
        # shard the head matmul over the kernel's fsdp storage dims,
        # resharding the whole activation (involuntary full remat).
        # Plain "vocab" would be wrong here — under fsdp rules it outranks
        # "batch" for the fsdp axis and would shard logits feature-wise.
        return logical_constraint(
            logits, ("batch", "seq", "act_vocab"), cfg.mesh
        )


def generate(
    cfg: TransformerConfig,
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    rules=None,
) -> jax.Array:
    """Autoregressive generation with a KV cache (prefill + jitted scan).

    `params` are ordinary trained TransformerLM params (rope configs carry
    no position table, so train and decode share them verbatim).  Greedy at
    temperature 0, categorical sampling otherwise.  Returns
    [B, prompt_len + max_new_tokens] tokens.  Beyond-parity capability: the
    reference is training-only.

    `mesh`: tensor-parallel serving — params are placed under the rules
    table (q/k/v/mlp kernels shard over tp, Megatron-style) and GSPMD
    propagates the sharding through the decode scan, KV cache included
    (the cache inherits the head sharding from the sharded k/v writes).
    Serves models whose weights exceed one chip.  Numerics match the
    single-device path up to reduction-order ULPs (the tp psum sums
    partials in a different order), so greedy tokens agree except at
    exact logit near-ties.
    """
    assert prompt.ndim == 2
    b, prompt_len = prompt.shape
    assert cfg.rope, (
        "generate() requires a rope-trained model: a learned pos_embed "
        "table has no decode-cursor equivalent here"
    )
    assert prompt_len + max_new_tokens <= cfg.max_len, (
        f"{prompt_len}+{max_new_tokens} exceeds max_len={cfg.max_len}"
    )
    # decode overrides: full attention on the cache, no shard_map region
    # (under `mesh`, sharding is GSPMD-propagated instead), and a dense
    # head (a head="hidden"-trained config shares the same param tree, so
    # its params decode unchanged)
    dcfg = dataclasses.replace(
        cfg, decode=True, attention="full", mesh=None, head="dense"
    )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    run = _generate_compiled(dcfg, b, prompt_len, max_new_tokens, temperature)
    model = TransformerLM(dcfg)
    variables = model.init(jax.random.PRNGKey(0), prompt[:, :1])
    cache = variables["cache"]
    if mesh is not None:
        from ..parallel.sharding import param_shardings

        # the init above already carries the partition metadata — no
        # second trace needed
        params = jax.device_put(
            params, param_shardings(mesh, variables["params"], rules)
        )
    return run(params, cache, prompt, rng)


@functools.lru_cache(maxsize=64)
def _generate_compiled(dcfg: TransformerConfig, b: int, prompt_len: int,
                       max_new_tokens: int, temperature: float):
    """One jitted prefill+scan program per (config, shape) — repeat
    generate() calls with the same shapes hit the jit cache instead of
    retracing."""
    model = TransformerLM(dcfg)

    def pick(logits, r):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            r, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)

    @jax.jit
    def run(params, cache, prompt, rng):
        logits, st = model.apply(
            {"params": params, "cache": cache}, prompt, mutable=["cache"]
        )
        rng, r0 = jax.random.split(rng)
        tok = pick(logits[:, -1], r0)

        def step(carry, _):
            cache, tok, rng = carry
            logits, st = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                mutable=["cache"],
            )
            rng, r = jax.random.split(rng)
            nxt = pick(logits[:, -1], r)
            return (st["cache"], nxt, rng), tok

        (_, last, _), toks = jax.lax.scan(
            step, (st["cache"], tok, rng), None, length=max_new_tokens - 1
        )
        return jnp.concatenate(
            [prompt.astype(jnp.int32), jnp.moveaxis(toks, 0, 1),
             last[:, None]], axis=1
        )

    return run


def _token_ll(logits: jax.Array, targets: jax.Array):
    """Per-token log-likelihood (fp32) and the log normalizer log Z."""
    lg = logits.astype(jnp.float32)
    log_z = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0] - log_z
    return ll, log_z


def lm_loss(
    logits: jax.Array, tokens: jax.Array, z_loss: float = 0.0
) -> jax.Array:
    """Next-token cross entropy, mean over all positions.

    `z_loss`: PaLM-style stabilizer `z_loss * mean(log Z^2)` keeping the
    softmax normalizer near 1 (typ. 1e-4) — prevents logit drift in long
    bf16 pretraining runs.
    """
    ll, log_z = _token_ll(logits[:, :-1], tokens[:, 1:])
    loss = -jnp.mean(ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(log_z ** 2)
    return loss


def lm_loss_chunked(
    model: "TransformerLM", params, tokens: jax.Array,
    block: Optional[int] = None, z_loss: float = 0.0,
) -> jax.Array:
    """`lm_loss` without materializing [B, L, V] logits.

    Requires a model configured with head="hidden": the forward returns
    final hidden states and the head matmul + log-softmax stream over
    vocab blocks (ops/chunked_ce — recomputed in backward).  At GPT scale
    the logits tensor is the single largest activation; this removes it.
    `block=None` resolves the chunk size through the tuner's defaults
    (KFT_CE_BLOCK env, then the footprint table — ops/chunked_ce).
    """
    cfg = model.cfg
    assert cfg.head == "hidden", 'lm_loss_chunked needs TransformerConfig(head="hidden")'
    h = model.apply({"params": params}, tokens)  # [B, L, D] f32 (ln_f)
    if cfg.tie_embeddings:
        w = params["embed"]["embedding"].astype(jnp.float32).T
    else:
        w = params["lm_head"]["kernel"]
    # same use-site gather contract as _Head: keep tp vocab-parallelism,
    # gather fsdp storage dims so the streamed matmuls never pull the
    # activations onto the kernel's layout (the involuntary-remat
    # pathology _Head documents)
    w = logical_constraint(w, (None, "act_vocab"), cfg.mesh)
    b, l, d = h.shape
    ll, log_z = chunked_lm_head_ll(
        h[:, :-1].reshape(-1, d), w, tokens[:, 1:].reshape(-1), block
    )
    loss = -jnp.mean(ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(log_z ** 2)
    return loss


def mlm_loss(
    logits: jax.Array, targets: jax.Array, mask: jax.Array,
    z_loss: float = 0.0,
) -> jax.Array:
    """Masked-LM (BERT-style) cross entropy: mean over MASKED positions.

    `targets` are the ORIGINAL token ids, `mask` is 1 where the input was
    corrupted (the model sees the corrupted tokens; the loss reads only the
    masked slots).  Use with a bidirectional config (causal=False).
    """
    ll, log_z = _token_ll(logits, targets)
    m = mask.astype(jnp.float32)
    denom = jnp.maximum(m.sum(), 1.0)
    loss = -(ll * m).sum() / denom
    if z_loss:
        loss = loss + z_loss * ((log_z ** 2) * m).sum() / denom
    return loss


def mlm_corrupt(
    rng: jax.Array, tokens: jax.Array, vocab_size: int, mask_id: int,
    mask_rate: float = 0.15,
) -> Tuple[jax.Array, jax.Array]:
    """BERT's 80/10/10 corruption: select `mask_rate` of positions; of those
    80% -> mask_id, 10% -> random token, 10% unchanged.  Returns
    (corrupted_tokens, selected_mask)."""
    r_sel, r_kind, r_tok = jax.random.split(rng, 3)
    sel = jax.random.uniform(r_sel, tokens.shape) < mask_rate
    kind = jax.random.uniform(r_kind, tokens.shape)
    rand_tok = jax.random.randint(r_tok, tokens.shape, 0, vocab_size)
    corrupted = jnp.where(kind < 0.8, mask_id,
                          jnp.where(kind < 0.9, rand_tok, tokens))
    return jnp.where(sel, corrupted, tokens).astype(tokens.dtype), sel


def lm_loss_with_aux(
    model: TransformerLM, params, tokens: jax.Array, aux_weight: float = 0.01,
    z_loss: float = 0.0, router_z_weight: float = 0.001,
) -> jax.Array:
    """LM loss + the experts' two auxiliary losses, each a mean over the
    expert layers (parallel/moe.py sows them): `aux_weight` x the
    load-balancing loss in its top-k form (without it the router collapses
    onto few experts) and `router_z_weight` x the router z-loss.  The
    defaults are OLMoE's published coefficients."""
    logits, state = model.apply({"params": params}, tokens, mutable=["intermediates"])
    loss = lm_loss(logits, tokens, z_loss=z_loss)
    sown = _iter_sown(state.get("intermediates", {}))
    for name, weight in (("moe_aux_loss", aux_weight),
                         ("moe_router_z", router_z_weight)):
        terms = [jnp.asarray(l, jnp.float32) for path, leaves in sown
                 if path.endswith(name) for l in leaves]
        if terms and weight:
            loss = loss + weight * sum(terms) / len(terms)
    return loss


def _iter_sown(tree, prefix=""):
    out = []
    if isinstance(tree, dict):
        for k, v in tree.items():
            out += _iter_sown(v, f"{prefix}/{k}")
    else:
        out.append((prefix, tree))
    return out
