"""Logical-axis sharding rules — the TP/SP/EP wiring for pjit models.

The scaling-book recipe: annotate params/activations with *logical* axis
names, map logical names to mesh axes with one rules table, and let XLA
insert the collectives (the entire Megatron-style TP comm pattern — psum
after row-parallel matmuls, all-gather where needed — falls out of the
sharding propagation).  This replaces nothing in the reference (it is
DP-only); it is the TPU-first capability layer.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import flax.linen as nn
from flax.linen import spmd as flax_spmd
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis. None = replicated.
DEFAULT_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", "dp"),
    ("seq", "sp"),
    # "embed" names PARAMETER embed dims (fsdp shards them); activations
    # use "act_embed" so the fsdp rule never forces activation resharding
    ("embed", None),
    ("act_embed", None),
    # norm scales/biases: a few dozen floats — sharding them over fsdp
    # saves nothing and their annotation makes the partitioner reshard the
    # big activations they multiply (observed involuntary full remat), so
    # they stay replicated even under ZeRO
    ("norm", None),
    ("heads", "tp"),
    ("kv", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    # activation/use-site vocab dim: tp-sharded when tp exists (Megatron
    # vocab-parallel logits), NEVER rewritten to fsdp — use-site gathers
    # name this so ZeRO storage sharding doesn't leak onto activations
    ("act_vocab", "tp"),
    ("expert", "ep"),
    ("stage", "pp"),
)


def rules_for_mesh(mesh: Mesh, rules=DEFAULT_RULES) -> Tuple[Tuple[str, Optional[str]], ...]:
    """Drop rules whose mesh axis does not exist (e.g. no 'ep' axis).

    An `fsdp` mesh axis activates GSPMD-style fully-sharded data
    parallelism inside MeshTrainer: parameter *embed* dims shard over
    fsdp (XLA inserts the per-layer all-gathers — ZeRO-3 semantics by
    sharding propagation) and the batch shards over BOTH dp and fsdp
    (fsdp groups are data-parallel).
    """
    names = set(mesh.axis_names)
    fsdp_defaults = rules is DEFAULT_RULES and "fsdp" in names
    out = []
    for l, m in rules:
        if l == "batch" and fsdp_defaults:
            axes = tuple(a for a in ("dp", "fsdp") if a in names)
            out.append((l, axes if len(axes) > 1 else axes[0]))
        elif l == "embed" and fsdp_defaults:
            out.append((l, "fsdp"))
        elif l == "vocab" and fsdp_defaults and "tp" not in names:
            out.append((l, "fsdp"))
        elif isinstance(m, tuple):
            # tuple-valued mapping (e.g. batch -> ("dp","fsdp")): keep the
            # axes this mesh actually has
            axes = tuple(a for a in m if a in names)
            out.append(
                (l, axes if len(axes) > 1 else (axes[0] if axes else None))
            )
        else:
            out.append((l, m if (m in names) else None))
    if fsdp_defaults and "tp" not in names:
        # vocab must OUTRANK embed for the fsdp axis: flax gives a mesh
        # axis to the FIRST rule claiming it, so listing vocab first
        # shards the embedding table and lm_head on their VOCAB dim and
        # leaves their embed dim whole.  Sharding those tables on the
        # embed (feature) dim instead makes the table-gradient scatter
        # demand feature-sharded updates, which forces the partitioner to
        # fully rematerialize the batch-sharded activations (observed in
        # the dp x fsdp dryrun).
        out.sort(key=lambda r: 0 if r[0] == "vocab" else 1)
    if "fsdp" in names and not fsdp_defaults:
        # custom rules on an fsdp mesh: the ZeRO rewrite above is
        # identity-gated on DEFAULT_RULES, so a caller passing their own
        # table (even a copied default) must map the fsdp axis themselves
        # — otherwise params silently replicate.  Surface it.
        used = set()
        for _, m in out:
            used.update(m if isinstance(m, tuple) else (m,))
        if "fsdp" not in used:
            from ..utils import get_logger

            get_logger("kungfu.sharding").warning(
                "mesh has an 'fsdp' axis but the custom rules table never "
                "maps it: parameters will be fully replicated.  Map a "
                "logical dim to 'fsdp' (DEFAULT_RULES does this "
                "automatically) or drop the axis."
            )
    return tuple(out)


def logical_constraint(x, names: Sequence[Optional[str]], mesh: Optional[Mesh] = None, rules=None):
    """with_sharding_constraint by logical names (no-op without a mesh).

    The mesh MUST be passed explicitly: flax's with_logical_constraint
    no-ops unless flax.core.meta.global_mesh_defined() is true, and on the
    pinned jax/flax versions `with mesh:` does not satisfy that check
    (verified empirically — constraints were absent from the lowered HLO
    until the mesh was passed here, observed as an involuntary full remat
    in the dp x fsdp dryrun).

    Rules come from the ambient nn.logical_axis_rules context.  An EMPTY
    context no-ops, preserving flax's contract — manual shard_map regions
    (e.g. pipeline stages) set `nn.logical_axis_rules(())` exactly to
    disable constraints; substituting defaults there would inject
    with_sharding_constraint inside a manual region.  Callers without a
    rules context can pass `rules=` explicitly (MeshTrainer always traces
    under its rules, so the training path never hits the empty case).
    """
    if mesh is None or not mesh.axis_names:
        return x
    if rules is None:
        rules = flax_spmd.get_logical_axis_rules()
        if not rules:
            return x
    return flax_spmd.with_logical_constraint(
        x, tuple(names), rules=rules, mesh=mesh
    )


def param_shardings(mesh: Mesh, abstract_params: Any, rules=None) -> Any:
    """NamedShardings for a flax param tree annotated with logical axes."""
    rules = rules if rules is not None else rules_for_mesh(mesh)
    specs = nn.get_partition_spec(abstract_params)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, flax_spmd.logical_to_mesh_axes(s, rules))
        if isinstance(s, P)
        else NamedSharding(mesh, P()),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def decode_cache_shardings(mesh: Mesh, cache: Any) -> Any:
    """NamedShardings pinning a decode KV cache onto a serving mesh.

    The cache tree (models/transformer.py decode mode) has per-layer leaves
    `cached_k`/`cached_v` [slots, max_len, kv_heads, head_dim], int8 scales
    `scale_k`/`scale_v` [slots, max_len, kv_heads], and per-slot
    `idx`/`overflowed` [slots].  Serving shards the SLOT axis over "dp"
    (independent requests — every decode step is collective-free on that
    axis) and the kv-head axis over "tp" to match the Megatron q/k/v kernel
    sharding, so the tp psums of the attention output are the only decode
    collectives.  Sequence-parallel serving (sharding max_len over "sp", the
    ring-attention layout) is a per-call shard_map decision, not a storage
    pin — see docs/serving.md.

    A tp degree that does not divide kv_heads leaves the head axis
    replicated (GQA caches can have fewer kv heads than tp shards).
    """
    names = set(mesh.axis_names)
    dp = "dp" if "dp" in names else None
    tp = "tp" if "tp" in names else None

    def spec_for(path, leaf) -> NamedSharding:
        name = getattr(path[-1], "key", "")
        row_dp = dp
        if dp is not None and leaf.shape[0] % mesh.shape["dp"] != 0:
            row_dp = None
        row_tp = tp
        if tp is not None and leaf.ndim >= 3:
            if leaf.shape[2] % mesh.shape["tp"] != 0:
                row_tp = None
        if name in ("cached_k", "cached_v") and leaf.ndim == 4:
            return NamedSharding(mesh, P(row_dp, None, row_tp, None))
        if name in ("scale_k", "scale_v") and leaf.ndim == 3:
            return NamedSharding(mesh, P(row_dp, None, row_tp))
        if name in ("idx", "overflowed") and leaf.ndim == 1:
            return NamedSharding(mesh, P(row_dp))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, cache)
