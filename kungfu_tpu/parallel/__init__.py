"""Parallelism beyond DP: TP sharding rules, SP ring attention, PP, EP MoE."""
from .ring_attention import ring_attention, full_attention
from .ulysses import ulysses_attention
from .sharding import DEFAULT_RULES, rules_for_mesh, param_shardings, logical_constraint
from .pp import (
    pipeline_apply,
    pipeline_apply_grouped,
    pipeline_spmd,
    stack_group_params,
    stack_stage_params,
)
from .moe import MoE

__all__ = [
    "ring_attention", "full_attention", "ulysses_attention",
    "DEFAULT_RULES", "rules_for_mesh", "param_shardings", "logical_constraint",
    "pipeline_apply", "pipeline_apply_grouped", "pipeline_spmd",
    "stack_stage_params", "stack_group_params", "PipelinedLM",
    "MoE",
]


def __getattr__(name):
    # lazy: pp_transformer imports models.transformer, which imports this
    # package (ring_attention) — an eager import here would be circular
    if name == "PipelinedLM":
        from .pp_transformer import PipelinedLM

        return PipelinedLM
    raise AttributeError(name)
