"""PipelinedLM — pipeline-parallel transformer on the pp (x dp) mesh.

Takes the flagship TransformerLM (models/transformer.py) and runs its
block stack through the circular/GPipe ring schedule (parallel/pp.py):

  embed + positions        computed outside the pipeline (pjit land; the
                           dp axis shards the batch, pp replicates)
  n_layers blocks          cut into S*R layer-groups; device s on the pp
                           axis holds groups {r*S + s}, stacked [S, R, Lg]
                           per param leaf and sharded P("pp")
  final norm + lm head     outside the pipeline again

This is the "distinct embed/head stages" design: embed/head are their own
(small) computations with their own parameters, not forced through the
identical-activation-shape constraint of the ring — only the homogeneous
block stack is pipelined, which is exactly the part whose weights dominate.

Duck-typed like a flax module (init/apply returning/taking {"params": ...})
so MeshTrainer drives it unmodified:

    model = PipelinedLM(cfg, repeats=2, microbatches=8)
    trainer = MeshTrainer(model, loss_fn, optax.adamw(1e-3), mesh=mesh)

The stacked block leaves carry logical axes ("stage", None, None, *orig) —
sharding.DEFAULT_RULES maps "stage" -> "pp".

Constraints: cfg.n_layers % (S*R) == 0; dense blocks only (no MoE — EP's
all_to_all would nest a second manual region); attention "flash"/"full"
(ring attention = its own shard_map, same nesting limit); microbatches >= S
when repeats > 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
from flax.linen import spmd as flax_spmd
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map
from ..models.transformer import Block, TransformerConfig, TransformerLM
from .pp import pipeline_spmd


class PipelinedLM:
    """Pipeline-parallel TransformerLM (see module docstring)."""

    def __init__(
        self,
        cfg: TransformerConfig,
        stages: Optional[int] = None,
        repeats: int = 1,
        microbatches: int = 4,
        remat: bool = True,
        pp_axis: str = "pp",
    ):
        if cfg.mesh is None or pp_axis not in cfg.mesh.axis_names:
            raise ValueError(f"PipelinedLM needs a mesh with a {pp_axis!r} axis")
        if cfg.n_experts > 0:
            raise ValueError("PipelinedLM supports dense blocks only (no MoE)")
        if cfg.attention in ("ring", "ulysses"):
            raise ValueError(
                f"{cfg.attention} attention opens its own shard_map and "
                "cannot nest inside the pipeline's manual region; use "
                "attention='auto'/'flash'/'full'"
            )
        self.mesh: Mesh = cfg.mesh
        self.pp_axis = pp_axis
        self.S = stages if stages is not None else self.mesh.shape[pp_axis]
        if self.S != self.mesh.shape[pp_axis]:
            raise ValueError(
                f"stages={self.S} must equal the mesh's {pp_axis} size "
                f"({self.mesh.shape[pp_axis]})"
            )
        self.R = repeats
        self.M = microbatches
        self.remat = remat
        groups = self.S * self.R
        if cfg.n_layers % groups != 0:
            raise ValueError(
                f"n_layers={cfg.n_layers} must divide into S*R={groups} groups"
            )
        if cfg.tie_embeddings:
            raise ValueError(
                "tie_embeddings is not supported under pipeline parallelism: "
                "the embedding lives on the first stage and the head on the "
                "last; use an untied lm_head"
            )
        self.layers_per_group = cfg.n_layers // groups
        self.cfg = cfg
        # blocks run inside the manual pp region: their internal attention
        # must not open a second shard_map (mesh=None => flash/full direct)
        self._block_cfg = dataclasses.replace(cfg, mesh=None)
        self._block = Block(self._block_cfg)

    # -- params -----------------------------------------------------------------------

    def init(self, rng, tokens) -> Any:
        """Init via TransformerLM (same shapes/metadata), repacked:

        {"embed", "ln_f", "lm_head"} (+ "pos_embed" for non-rope
        configs; rope models carry no position table) kept as-is;
        {"blocks": ...} leaves stacked [S, R, Lg, ...] with logical axis
        "stage" on the pp dim.
        """
        full = TransformerLM(self._block_cfg).init(rng, tokens)["params"]
        Lg, S, R = self.layers_per_group, self.S, self.R

        # device s, round r, in-group layer j <- model layer (r*S + s)*Lg + j
        order = [
            full[f"block_{(r * S + s) * Lg + j}"]
            for s in range(S)
            for r in range(R)
            for j in range(Lg)
        ]

        def stk(*leaves):
            first = leaves[0]
            if isinstance(first, nn.Partitioned):
                v = jnp.stack([l.value for l in leaves])
                v = v.reshape((S, R, Lg) + first.value.shape)
                return nn.Partitioned(
                    v, names=("stage", None, None) + tuple(first.names)
                )
            v = jnp.stack(leaves)
            return v.reshape((S, R, Lg) + first.shape)

        blocks = jax.tree.map(
            stk, order[0], *order[1:],
            is_leaf=lambda x: isinstance(x, nn.Partitioned),
        )
        params = {
            k: v
            for k, v in full.items()
            if not k.startswith("block_")
        }
        params["blocks"] = blocks
        return {"params": params}

    # -- apply ------------------------------------------------------------------------

    def apply(self, variables, tokens) -> jax.Array:
        p = nn.meta.unbox(variables["params"])
        cfg = self.cfg
        B, L = tokens.shape
        dp_size = self.mesh.shape.get("dp", 1)
        b_shard = B // dp_size
        if B % dp_size or b_shard % self.M or b_shard < self.M:
            raise ValueError(
                f"per-dp-shard batch {B}/{dp_size} must be a (nonzero) "
                f"multiple of microbatches={self.M}"
            )

        # embed (outside the pipe).  rope configs carry no pos_embed table:
        # each Block applies rotary positions to q/k internally, and every
        # microbatch holds the full sequence, so positions need no
        # pipeline-stage bookkeeping here
        x = jnp.take(p["embed"]["embedding"], tokens, axis=0).astype(cfg.dtype)
        if not cfg.rope:
            x = x + p["pos_embed"][None, :L].astype(cfg.dtype)

        # pipelined block stack
        block, remat, R, pp_axis = self._block, self.remat, self.R, self.pp_axis

        def group_fn(gp, h):
            # gp leaves [Lg, ...]: apply the group's blocks in sequence.
            # Empty logical rules => the blocks' with_logical_constraint
            # calls no-op inside the manual region.
            def body(h, lp):
                with nn.logical_axis_rules(()):
                    return block.apply({"params": lp}, h), None

            h, _ = jax.lax.scan(body, h, gp)
            return h

        names = self.mesh.axis_names
        dp = "dp" if "dp" in names else None
        M = self.M

        def pipe(blocks_p, xx):
            blocks_p = jax.tree.map(lambda q: jnp.squeeze(q, 0), blocks_p)
            b_loc = xx.shape[0]
            xs = xx.reshape((M, b_loc // M) + xx.shape[1:])
            out = pipeline_spmd(
                group_fn, blocks_p, xs, axis_name=pp_axis, repeats=R,
                remat=remat,
            )
            return out.reshape(xx.shape)

        x = _shard_map(
            pipe,
            mesh=self.mesh,
            in_specs=(P(self.pp_axis), P(dp)),
            out_specs=P(dp),
            # the pipeline's switch-over-shifts cond mixes pp-varying and
            # replicated carries; replication checking rejects it on both
            # JAX generations (check_rep / check_vma)
            check_vma=False,
        )(p["blocks"], x)

        # final norm + head (outside the pipe)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mean) / jnp.sqrt(var + 1e-6) * p["ln_f"]["scale"]
        return xf.astype(jnp.float32) @ p["lm_head"]["kernel"].astype(jnp.float32)

    # flax-module duck-typing for MeshTrainer
    def __call__(self, *a, **k):  # pragma: no cover
        raise TypeError("PipelinedLM is applied via .apply(variables, tokens)")
