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
experts live rows hit.  Such a row's output is zero (beside a shared
expert's term, which is per row and nobody else's), and finite whatever
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

A share of the experts (`cfg.experts_held` of `cfg.n_experts` from
`cfg.expert_offset`: one rank of an expert-parallel deployment), identity
experts and a biased choice, each selected by its `TransformerConfig`
field and absent from the program without it:

    p      = softmax(u W_r)          over n_experts + n_zero_experts
    top-k  = the k largest p + b     (`router_bias`; b starts at 0)
    g_e    = routed_scaling_factor * p_e
    MoE(u) = sum over chosen HELD e of g_e * expert_e(u)
             + (sum over chosen e >= n_experts of g_e) * u

The layer routes over the whole width.  An assignment to a routed expert
that is not held here is another rank's: it takes the id of nobody, as a
free row's does, so it owns no grouped-matmul row and reads no weight, and
its part of the result is left out, here as in the deployment before the
combine.  An identity expert's part is computed here in full (it needs no
exchange anywhere) and owns no grouped-matmul row either.  The weights
are [held, ...]; local id = e - expert_offset.

Sigmoid scores and a shared expert (`cfg.router_scores == "sigmoid"`,
`cfg.n_shared_experts`; DeepSeek-V3's published layer, absent from the
program without their fields):

    s      = sigmoid(u W_r)          float32, each output on its own
    top-k  = the k largest s
    g_e    = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
             (`norm_topk_prob`: renormalised first, then scaled)
    MoE(u) = FFN_shared(u) + sum over chosen HELD e of g_e * expert_e(u)

The shared expert is a dense FFN of width n_shared_experts x the experts'
width (`shared`: `nn.Dense` kernels like the dense `MLP`'s) that every
token passes through, unweighted.  It needs no exchange, so every rank of
a deployment computes it in full and it is no part of the share: the
parts all the shares give add up to the uncut layer with it counted once.

Counted on the device in decode mode, in the "moe_stats" collection
(declared only there, updated only when the caller makes it mutable: the
serving engine's slot-cache programs): `assignments` [experts], rows routed
to each expert; `experts_hit`, distinct experts that owned a row, summed
over calls; `calls`.  Live rows only: a free slot's row is routed to
nobody and counts nowhere.  A layer with a share or with identity experts
also counts `zero_assignments` and `absent_assignments`, so that held +
zero + absent = k x live tokens.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.gmm import grouped_matmul

STATS = "moe_stats"


def route(probs: jax.Array, k: int, renormalise: bool, bias=None,
          eps: float = 0.0):
    """(gates [T, k] float32, experts [T, k] int32) from probs [T, E].
    `lax.top_k` is by value, and puts the lower index first on a tie.
    With `bias` [E] the choice is by probs + bias and the gates are the
    chosen experts' probs themselves.  `eps` is added to the sum the
    chosen weights are renormalised by (sigmoid scores, whose sum no
    softmax bounds away from 0: the published 1e-20)."""
    if bias is None:
        gates, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + bias, k)
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalise:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + eps if eps else total)
    return gates, experts.astype(jnp.int32)


class MoE(nn.Module):
    cfg: Any  # TransformerConfig
    #: the dense FFN module a shared expert is built from
    #: (models/transformer.py `MLP`), handed in by the block that builds the
    #: layer: this package imports nothing of `models`
    shared_ffn: Any = None

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        B, L, Dm = x.shape
        k, width = cfg.experts_per_token, cfg.expert_width
        # E experts' weights are here; the router is `wide`: every routed
        # expert, held or not, then the identity experts
        E, routed = cfg.local_experts, cfg.n_experts
        wide = routed + cfg.n_zero_experts
        partial_share = E != routed or cfg.n_zero_experts > 0
        T = B * L
        init = nn.initializers.normal(stddev=0.02)

        def expert_param(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(init, axes),
                              shape, jnp.float32)

        router = self.param(
            "router", nn.with_logical_partitioning(init, ("embed", "expert")),
            (Dm, wide), jnp.float32)
        bias = self.param(
            "router_bias", nn.with_logical_partitioning(
                nn.initializers.zeros, ("expert",)), (wide,), jnp.float32
        ) if cfg.router_bias else None
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
                sigmoid = cfg.router_scores == "sigmoid"
                probs = (jax.nn.sigmoid(logits) if sigmoid
                         else jax.nn.softmax(logits, axis=-1))  # [T, wide]
                gates, experts = route(probs, k, cfg.norm_topk_prob, bias,
                                       eps=1e-20 if sigmoid else 0.0)
                if cfg.routed_scaling_factor != 1.0:
                    gates = gates * cfg.routed_scaling_factor
                chosen = experts
                if live is not None:
                    # a token of a row that is not live is nobody's: expert
                    # id E sorts last and is not counted, gate weight 0
                    mine = jnp.repeat(live, L)[:, None]               # [T, 1]
                    gates = jnp.where(mine, gates, 0.0)
                    experts = jnp.where(mine, experts, E)
                if partial_share:
                    # what is left of the choice here: the held experts by
                    # their local id; an identity expert's weight on the
                    # token itself; another rank's expert is nobody's
                    local = chosen - cfg.expert_offset
                    held = jnp.logical_and(local >= 0, local < E)
                    zero = chosen >= routed
                    if live is not None:
                        held = jnp.logical_and(held, mine)
                        zero = jnp.logical_and(zero, mine)
                    zero_gate = jnp.sum(jnp.where(zero, gates, 0.0), axis=-1)
                    gates = jnp.where(held, gates, 0.0)
                    experts = jnp.where(held, local, E)
                # the T*k assignments sorted by expert (stable: by token
                # within an expert); row r of the grouped matmuls is token
                # order[r] // k
                order = jnp.argsort(experts.reshape(-1), stable=True)
                counts = jnp.bincount(experts.reshape(-1), length=E)  # [E]
            with jax.named_scope("moe.experts"):
                gmm = partial(grouped_matmul, group_sizes=counts,
                              out_dtype=jnp.float32,
                              leftover=live is not None or partial_share)
                rows = flat.astype(cfg.dtype)[order // k]             # [T*k, Dm]
                gate = gmm(rows, w_gate)
                up = gmm(rows, w_up)
                h = (nn.silu(gate) * up).astype(cfg.dtype)
                y = gmm(h, w_down)                                    # [T*k, Dm]
                unsort = jnp.argsort(order)
                y = y[unsort].reshape(T, k, Dm)
                out = jnp.einsum("tkd,tk->td", y, gates)
                if not cfg.n_zero_experts:
                    out = out.astype(cfg.dtype)
            if cfg.n_zero_experts:
                with jax.named_scope("moe.zero"):
                    out = (out + zero_gate[:, None]
                           * flat.astype(jnp.float32)).astype(cfg.dtype)
            if cfg.n_shared_experts:
                assert self.shared_ffn is not None, (
                    "a shared expert needs the block's dense FFN module")
                with jax.named_scope("moe.shared"):
                    wide_cfg = dataclasses.replace(
                        cfg, d_ff=cfg.n_shared_experts * width)
                    out = out + self.shared_ffn(wide_cfg, name="shared")(
                        x).reshape(T, Dm)

        if not partial_share:
            # the load-balancing loss is over all the experts: a share of
            # them has no such loss of its own (and is not trained here)
            frac_tokens = counts.astype(jnp.float32) / T
            self.sow("intermediates", "moe_aux_loss",
                     E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)))
        self.sow("intermediates", "moe_router_z",
                 jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2))
        self.sow("intermediates", "moe_experts",
                 (chosen if partial_share else experts).reshape(B, L, k))
        if cfg.decode and (self.is_initializing()
                           or self.is_mutable_collection(STATS)):
            zeros = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
            assignments = self.variable(STATS, "assignments", zeros, E)
            hit = self.variable(STATS, "experts_hit", zeros)
            calls = self.variable(STATS, "calls", zeros)
            if partial_share:
                to_zero = self.variable(STATS, "zero_assignments", zeros)
                to_absent = self.variable(STATS, "absent_assignments", zeros)
            if not self.is_initializing():
                assignments.value = assignments.value + counts
                hit.value = hit.value + jnp.sum(counts > 0, dtype=jnp.int32)
                calls.value = calls.value + 1
                if partial_share:
                    n_zero = jnp.sum(zero, dtype=jnp.int32)
                    n_live = k * (T if live is None
                                  else L * jnp.sum(live, dtype=jnp.int32))
                    to_zero.value = to_zero.value + n_zero
                    to_absent.value = to_absent.value + (
                        n_live - n_zero - jnp.sum(counts, dtype=jnp.int32))
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
    out = {
        "assignments": np.stack([np.asarray(m["assignments"]) for _, m in layers]),
        "experts_hit": int(sum(int(m["experts_hit"]) for _, m in layers)),
        "layer_calls": int(sum(int(m["calls"]) for _, m in layers)),
    }
    # a layer that holds a share of its experts, or has identity experts
    for kind in ("zero", "absent"):
        if all(f"{kind}_assignments" in m for _, m in layers):
            out[f"{kind}_assignments"] = int(sum(
                int(m[f"{kind}_assignments"]) for _, m in layers))
    return out


def stats_health(stats):
    """The same as a small block for /healthz; None for a dense model."""
    t = stats_totals(stats)
    if t is None:
        return None
    out = {"assignments_total": int(t["assignments"].sum()),
           "assignments_by_expert_max": int(t["assignments"].sum(0).max()),
           "experts_hit_total": t["experts_hit"],
           "decode_layer_calls_total": t["layer_calls"]}
    for kind in ("zero", "absent"):
        if f"{kind}_assignments" in t:
            out[f"{kind}_assignments_total"] = t[f"{kind}_assignments"]
    return out


def stats_families(stats) -> dict:
    """The same as Prometheus families for `Counters.add_source`:
    {family: {label text: value}}."""
    t = stats_totals(stats)
    if t is None:
        return {}
    out = {
        "kft_moe_assignments_total": {
            f'layer="{layer}",expert="{e}"': int(n)
            for layer, row in enumerate(t["assignments"])
            for e, n in enumerate(row)},
        "kft_moe_experts_hit_total": {"": t["experts_hit"]},
        "kft_moe_decode_layer_calls_total": {"": t["layer_calls"]},
    }
    for kind in ("zero", "absent"):
        if f"{kind}_assignments" in t:
            out[f"kft_moe_{kind}_assignments_total"] = {
                "": t[f"{kind}_assignments"]}
    return out
