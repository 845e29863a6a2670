"""Sparse-expert feed-forward layer: top-k routing, dropless, SwiGLU experts.

    p      = softmax(u W_r)                  float32, over all experts
    top-k  = the k largest p (lower index first on a tie)
    g_e    = p_e, or p_e / sum of the chosen p when `norm_topk_prob`
    MoE(u) = sum over the chosen e of g_e * W_down,e (silu(W_gate,e u) * W_up,e u)

No capacity and no dropped token.  One algorithm for a decode step's
[slots, 1], a 1,536-token prefill and a training batch: the tokens x k
assignments are sorted by expert and each projection is one grouped matmul
over those rows (ops/gmm.py: the Mosaic kernel `kft_moe_gmm` on TPU,
`jax.lax.ragged_dot` elsewhere), combined by the gate weights in float32.
Only experts that own rows are read, and they are read as stored.

`MoE(cfg)(u, live)`: `live` [B] bool says which batch rows hold a request
(the serving engine's slot-cache programs pass it; None, everywhere else,
is every row and the program as it always was).  The tokens of a row that
is not live are routed to nobody: their k assignments take the expert id
`n_experts`, which the stable sort puts after every real one and the count
drops, so the grouped matmuls get group sizes that sum to k x live tokens
(ops/gmm.py: the rows left over come back as zeros) and read only the
experts live rows hit.  Such a row's output is zero, and finite whatever
its input.

Experts carry the logical axes ("expert", "embed", "mlp"), so an `ep` mesh
axis shards them; GSPMD then partitions the grouped matmul as it sees fit
(PERF.md section 7: an expert-parallel form is open).

Sown for the trainer's loss (`lm_loss_with_aux`), harmless when
"intermediates" is not mutable: `moe_aux_loss`, the load-balancing loss in
its top-k form (E * sum_e f_e P_e, f_e the share of tokens that chose e,
P_e the mean router probability: k when balanced), `moe_router_z`,
mean(logsumexp(router logits)^2), and `moe_experts` [B, L, k], the chosen
experts (what the routing-flip measurement compares with the reference's).

Counted on the device in decode mode, in the "moe_stats" collection
(declared only there, updated only when the caller makes it mutable: the
serving engine's slot-cache programs): `assignments` [experts], rows routed
to each expert; `experts_hit`, distinct experts that owned a row, summed
over calls; `calls`.  Live rows only: a free slot's row is routed to
nobody and counts nowhere.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.gmm import grouped_matmul

STATS = "moe_stats"


def route(probs: jax.Array, k: int, renormalise: bool):
    """(gates [T, k] float32, experts [T, k] int32) from probs [T, E].
    `lax.top_k` is by value, and puts the lower index first on a tie."""
    gates, experts = jax.lax.top_k(probs, k)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


class MoE(nn.Module):
    cfg: Any  # TransformerConfig

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        B, L, Dm = x.shape
        E, k, width = cfg.n_experts, cfg.experts_per_token, cfg.d_ff
        T = B * L
        init = nn.initializers.normal(stddev=0.02)

        def expert_param(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(init, axes),
                              shape, jnp.float32)

        router = self.param(
            "router", nn.with_logical_partitioning(init, ("embed", "expert")),
            (Dm, E), jnp.float32)
        w_gate = expert_param("w_gate", (E, Dm, width), ("expert", "embed", "mlp"))
        w_up = expert_param("w_up", (E, Dm, width), ("expert", "embed", "mlp"))
        w_down = expert_param("w_down", (E, width, Dm), ("expert", "mlp", "embed"))

        with jax.named_scope("moe"):
            flat = x.reshape(T, Dm)
            with jax.named_scope("moe.router"):
                # float32 in full: a TPU otherwise multiplies float32
                # matrices in bf16 passes, and a rounding here is a
                # different expert, a discrete change of the output
                logits = jnp.dot(flat.astype(jnp.float32), router,
                                 precision=jax.lax.Precision.HIGHEST)
                probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
                gates, experts = route(probs, k, cfg.norm_topk_prob)
                if live is not None:
                    # a token of a row that is not live is nobody's: expert
                    # id E sorts last and is not counted, gate weight 0
                    mine = jnp.repeat(live, L)[:, None]               # [T, 1]
                    gates = jnp.where(mine, gates, 0.0)
                    experts = jnp.where(mine, experts, E)
                # the T*k assignments sorted by expert (stable: by token
                # within an expert); row r of the grouped matmuls is token
                # order[r] // k
                order = jnp.argsort(experts.reshape(-1), stable=True)
                counts = jnp.bincount(experts.reshape(-1), length=E)  # [E]
            with jax.named_scope("moe.experts"):
                gmm = partial(grouped_matmul, group_sizes=counts,
                              out_dtype=jnp.float32,
                              leftover=live is not None)
                rows = flat.astype(cfg.dtype)[order // k]             # [T*k, Dm]
                gate = gmm(rows, w_gate)
                up = gmm(rows, w_up)
                h = (nn.silu(gate) * up).astype(cfg.dtype)
                y = gmm(h, w_down)                                    # [T*k, Dm]
                unsort = jnp.argsort(order)
                y = y[unsort].reshape(T, k, Dm)
                out = jnp.einsum("tkd,tk->td", y, gates).astype(cfg.dtype)

        frac_tokens = counts.astype(jnp.float32) / T
        self.sow("intermediates", "moe_aux_loss",
                 E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)))
        self.sow("intermediates", "moe_router_z",
                 jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2))
        self.sow("intermediates", "moe_experts", experts.reshape(B, L, k))
        if cfg.decode and (self.is_initializing()
                           or self.is_mutable_collection(STATS)):
            zero = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
            assignments = self.variable(STATS, "assignments", zero, E)
            hit = self.variable(STATS, "experts_hit", zero)
            calls = self.variable(STATS, "calls", zero)
            if not self.is_initializing():
                assignments.value = assignments.value + counts
                hit.value = hit.value + jnp.sum(counts > 0, dtype=jnp.int32)
                calls.value = calls.value + 1
        return out.reshape(B, L, Dm)


def stats_totals(stats) -> dict:
    """The "moe_stats" collection of a decode-mode model (host copy) as
    totals: {"assignments": int [layers, experts], "experts_hit": int,
    "layer_calls": int}, layers in block order; None for a dense model."""
    import numpy as np

    layers = sorted((int(name.rsplit("_", 1)[1]), block["moe"])
                    for name, block in (stats or {}).items())
    if not layers:
        return None
    return {
        "assignments": np.stack([np.asarray(m["assignments"]) for _, m in layers]),
        "experts_hit": int(sum(int(m["experts_hit"]) for _, m in layers)),
        "layer_calls": int(sum(int(m["calls"]) for _, m in layers)),
    }


def stats_health(stats):
    """The same as a small block for /healthz; None for a dense model."""
    t = stats_totals(stats)
    if t is None:
        return None
    return {"assignments_total": int(t["assignments"].sum()),
            "assignments_by_expert_max": int(t["assignments"].sum(0).max()),
            "experts_hit_total": t["experts_hit"],
            "decode_layer_calls_total": t["layer_calls"]}


def stats_families(stats) -> dict:
    """The same as Prometheus families for `Counters.add_source`:
    {family: {label text: value}}."""
    t = stats_totals(stats)
    if t is None:
        return {}
    return {
        "kft_moe_assignments_total": {
            f'layer="{layer}",expert="{e}"': int(n)
            for layer, row in enumerate(t["assignments"])
            for e, n in enumerate(row)},
        "kft_moe_experts_hit_total": {"": t["experts_hit"]},
        "kft_moe_decode_layer_calls_total": {"": t["layer_calls"]},
    }
