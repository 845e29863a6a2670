"""Plain reference: OLMoE decoder (allenai/OLMoE-1B-7B-0125-Instruct, `modeling_olmoe`).

Straightforward float32 `jax.numpy`, no kernels, no cache, no sort, no
batching tricks: every expert is computed for every token and masked by the
top-k.  `jax.default_matmul_precision("highest")` because a TPU otherwise
multiplies float32 matrices in bf16 passes.  It reads the program's own
parameter tree (embed/embedding, block_i/{ln1, attn/{q,k,v,out,q_norm,
k_norm}, ln2, moe/{router,w_gate,w_up,w_down}}, ln_f, lm_head/kernel), so
system and reference run on the same weights.

The published layer, in order (RMSNorm: x * rsqrt(mean(x^2) + eps) * scale,
eps `rms_norm_eps`):

    h = x + Attn(RMSNorm(x))            y = h + MoE(RMSNorm(h))

    Attn(u): q = RMSNorm_q(u W_q), k = RMSNorm_k(u W_k), v = u W_v, the two
      norms over the WHOLE projection (hidden_size wide, own scales), before
      the split into heads and before RoPE; rotary embedding on q and k over
      the two halves of each head (theta `rope_theta`); causal softmax
      attention scaled by 1/sqrt(head_dim); output projection; no biases;
      `clip_qkv` is null.
    MoE(u): p = softmax(u W_r) in float32 over all `num_experts`; the
      `num_experts_per_tok` largest p are chosen; their weights are the p as
      they are (`norm_topk_prob` false: not renormalised; true: divided by
      their sum); MoE(u) = sum over the chosen e of
      p_e * W_down,e (silu(W_gate,e u) * W_up,e u).  No shared expert, no
      capacity, no dropped token.

then a final RMSNorm and the untied head (`tie_word_embeddings` false).

Training losses: next-token cross entropy, plus `AUX_COEF` x the
load-balancing loss and `ROUTER_Z_COEF` x the router z-loss, each a mean
over the layers: E * sum_e f_e P_e (f_e the share of tokens that chose
expert e, so the f_e sum to k; P_e the mean router probability; f_e carries
no gradient) and mean(logsumexp(u W_r)^2).

Departures from `modeling_olmoe.py`, each noted in the configuration's
`assumed`: a tie among router probabilities goes to the lower expert index
(`torch.topk` leaves it open; the program's `lax.top_k` does the same and
the test holds both to it); the load-balancing loss is computed a layer and
averaged, where the published code concatenates the layers' router logits
first (the same number when every layer sees the same tokens); the router
z-loss is the OLMoE paper's (coefficient 0.001), which `modeling_olmoe.py`
does not carry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

AUX_COEF = 0.01
ROUTER_Z_COEF = 0.001


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, L, H, D]: rotate the pairs (x[..., i], x[..., i + D/2])."""
    L, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def top_k_mask(p, k):
    """[..., E] bool: the k largest of p, the lower index first on a tie.
    Expert e is chosen when fewer than k experts rank before it."""
    idx = jnp.arange(p.shape[-1])
    before = (p[..., None, :] > p[..., :, None]) | (
        (p[..., None, :] == p[..., :, None]) & (idx[None, :] < idx[:, None]))
    return jnp.sum(before, axis=-1) < k


def _moe(u, p_moe, config):
    """(MoE(u) [B, L, d], chosen [B, L, E] bool, load-balancing loss, z-loss)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    E, k = config["num_experts"], config["num_experts_per_tok"]
    logits = u @ f32(p_moe["router"])                       # [B, L, E]
    p = jax.nn.softmax(logits, axis=-1)
    chosen = jax.lax.stop_gradient(top_k_mask(p, k))
    w = jnp.where(chosen, p, 0.0)
    if config.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    gate = jnp.einsum("bld,edf->blef", u, f32(p_moe["w_gate"]))
    up = jnp.einsum("bld,edf->blef", u, f32(p_moe["w_up"]))
    every = jnp.einsum("blef,efd->bled", jax.nn.silu(gate) * up,
                       f32(p_moe["w_down"]))                # every expert
    out = jnp.einsum("bled,ble->bld", every, w)
    share = jnp.mean(chosen.astype(jnp.float32), axis=(0, 1))  # f_e, sums to k
    balance = E * jnp.sum(share * jnp.mean(p, axis=(0, 1)))
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return out, chosen, balance, z


def forward_with_routing(params, tokens, config):
    """(logits [B, L, vocab] float32, chosen [layers, B, L, E] bool,
    load-balancing loss, router z-loss; the last two means over layers)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    H = config["num_attention_heads"]
    Hkv = config.get("num_key_value_heads") or H
    theta = float(config.get("rope_theta", 10000.0))
    eps = float(config["rms_norm_eps"])
    n_layers = config["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["embedding"])[tokens]
        B, L, d = x.shape
        D = d // H
        causal = jnp.tril(jnp.ones((L, L), bool))
        routing, balance, z = [], 0.0, 0.0
        for i in range(n_layers):
            p = params[f"block_{i}"]
            a = p["attn"]
            u = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
            q = _rms_norm(u @ f32(a["q"]["kernel"]), f32(a["q_norm"]["scale"]), eps)
            k = _rms_norm(u @ f32(a["k"]["kernel"]), f32(a["k_norm"]["scale"]), eps)
            v = (u @ f32(a["v"]["kernel"])).reshape(B, L, Hkv, D)
            q = _rope(q.reshape(B, L, H, D), theta)
            k = _rope(k.reshape(B, L, Hkv, D), theta)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(jnp.float32(D))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1), v)
            x = x + o.reshape(B, L, d) @ f32(a["out"]["kernel"])
            out, chosen, bal_i, z_i = _moe(
                _rms_norm(x, f32(p["ln2"]["scale"]), eps), p["moe"], config)
            x = x + out
            routing.append(chosen)
            balance, z = balance + bal_i / n_layers, z + z_i / n_layers
        x = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        if config.get("tie_word_embeddings"):
            logits = x @ f32(params["embed"]["embedding"]).T
        else:
            logits = x @ f32(params["lm_head"]["kernel"])
    return logits, jnp.stack(routing), balance, z


def forward(params, tokens, config):
    """Logits [B, L, vocab] in float32 for int tokens [B, L]."""
    return forward_with_routing(params, tokens, config)[0]


def loss(params, tokens, config):
    """Mean next-token cross entropy + the two router losses."""
    logits, _, balance, z = forward_with_routing(params, tokens, config)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    return ce + AUX_COEF * balance + ROUTER_Z_COEF * z


def loss_and_grads(params, tokens, config):
    """(loss, d loss / d params) by plain autodiff of the plain forward."""
    return jax.value_and_grad(loss)(params, tokens, config)
