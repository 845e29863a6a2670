"""Plain reference: the LongCat-Flash language model (meituan-longcat/LongCat-Flash-Omni,
`config.json`; the layer as `modeling_longcat_flash` in transformers has it).

Straightforward float32 `jax.numpy`, no kernels, no cache, no sort, no
absorbed attention: every key and value is materialised for every position,
the experts are a Python loop.  `jax.default_matmul_precision("highest")`
because a TPU otherwise multiplies float32 matrices in bf16 passes.  It
reads the program's own parameter tree (embed/embedding, block_i/{ln_attn_0,
attn_0/{q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, out}, ln_ffn_0, mlp_0/{in,
gate, out}, moe/{router, router_bias, w_gate, w_up, w_down}, ln_attn_1,
attn_1, ln_ffn_1, mlp_1}, ln_f, lm_head/kernel), so system and reference
run on the same weights.

The published layer (N = RMSNorm: x * rsqrt(mean(x^2) + eps) * scale, eps
`rms_norm_eps`), two attention and two dense sublayers with an expert
branch that leaves after the first attention and rejoins after the second
FFN:

    x1 = x + MLA_0(N(x))      h = N(x1)      s = MoE(h)
    x2 = x1 + FFN_0(h)        x3 = x2 + MLA_1(N(x2))
    x4 = x3 + FFN_1(N(x3)) + s

    FFN(u) = W_out (silu(W_gate u) * W_in u), width `ffn_hidden_size`.
    MLA(u): c_q = N_q(u W_qa) [`q_lora_rank`]; q = s_q c_q W_qb, a head
      [q_nope `qk_nope_head_dim` | q_rope `qk_rope_head_dim`], s_q =
      (hidden_size / q_lora_rank)^0.5 when `mla_scale_q_lora`;
      [c | k_r] = u W_kva [`kv_lora_rank` + `qk_rope_head_dim`];
      c = s_kv N_kv(c), s_kv = (hidden_size / kv_lora_rank)^0.5 when
      `mla_scale_kv_lora`; rotary embedding (theta `rope_theta`) on q_rope
      and on k_r, which all heads share; [k_nope | v] = c W_kvb, a head
      [`qk_nope_head_dim` | `v_head_dim`]; score = (q_nope k_nope + q_rope
      k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim), causal softmax;
      out = (sum p v) W_o.  No biases.
    MoE(u): p = softmax(u W_r) in float32 over `n_routed_experts` +
      `zero_expert_num` outputs; the `moe_topk` largest p + b are chosen (b
      the router's correction bias); their weights are
      `routed_scaling_factor` x p, not renormalised; a routed expert e gives
      W_down,e (silu(W_gate,e u) * W_up,e u), width `expert_ffn_hidden_size`;
      a zero-compute expert (`zero_expert_type` identity) gives u itself.

then a final RMSNorm and the untied head.

The share.  The reference computes what the parameters it is given hold:
the number of heads is read from the output projection's rows, the routed
experts held from `w_gate`'s first dimension (they are the consecutive
experts from `program.expert_offset`), the router's width from `router`,
the vocabulary from the embedding.  Given everything, it is the whole
model.  Given one chip's share of a deployment (16 of 64 heads, 8 of 512
routed experts, 16,384 of 131,072 ids) it computes that chip's part: the
partial sum of the output projection over the heads held, the held
experts' terms of the expert sum beside the identity experts' term (which
every chip computes alike, in full: it needs no exchange), logits over the
slice; an assignment to a routed expert held elsewhere adds nothing here.
No exchange is simulated and nothing stands in for the other chips.

Departures from the published model, each noted in the configuration's
`assumed`: the rotary pairing is the program's (the two halves of the
rotary part; the published code interleaves, a fixed permutation of the
columns of W_qb and W_kva that seeded weights do not tell apart); a tie
among p + b goes to the lower index; the audio and vision encoders and the
codec decoder of the Omni model are not part of the language model's step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


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


def _ffn(u, p):
    return (jax.nn.silu(u @ _f32(p["gate"]["kernel"])) * (u @ _f32(p["in"]["kernel"]))
            ) @ _f32(p["out"]["kernel"])


def mla(u, p, config):
    """Latent attention of the heads `p` holds, materialised: [B, L, hidden]."""
    d = config["hidden_size"]
    r, rq = config["kv_lora_rank"], config["q_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    B, L, _ = u.shape
    w_o = _f32(p["out"]["kernel"])
    H = w_o.shape[0] // dv
    c_q = _rms_norm(u @ _f32(p["q_a"]["kernel"]), _f32(p["q_a_norm"]["scale"]), eps)
    q = (c_q @ _f32(p["q_b"]["kernel"])).reshape(B, L, H, dn + dr)
    if config.get("mla_scale_q_lora"):
        q = q * (d / rq) ** 0.5
    kv_a = u @ _f32(p["kv_a"]["kernel"])
    c = _rms_norm(kv_a[..., :r], _f32(p["kv_a_norm"]["scale"]), eps)
    if config.get("mla_scale_kv_lora"):
        c = c * (d / r) ** 0.5
    k_r = _rope(kv_a[..., None, r:], theta)                         # [B, L, 1, dr]
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], theta)
    kv = (c @ _f32(p["kv_b"]["kernel"])).reshape(B, L, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("blhd,bmhd->bhlm", q_nope, k_nope)
         + jnp.einsum("blhd,bmd->bhlm", q_rope, k_r[:, :, 0])) / (dn + dr) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, L, H * dv) @ w_o


def route(u, p_moe, config):
    """(weights [B, L, wide] float32, zero outside the chosen; chosen
    [B, L, wide] bool) over the router's whole width."""
    k = config["moe_topk"]
    p = jax.nn.softmax(u @ _f32(p_moe["router"]), axis=-1)
    choice = p + _f32(p_moe["router_bias"]) if "router_bias" in p_moe else p
    # the k largest, the lower index first on a tie (a stable sort)
    top = jnp.argsort(-choice, axis=-1, stable=True)[..., :k]
    chosen = jnp.sum(jax.nn.one_hot(top, p.shape[-1], dtype=jnp.int32), -2) > 0
    w = jnp.where(chosen, p, 0.0) * float(config.get("routed_scaling_factor", 1.0))
    if config.get("norm_topk_prob"):
        w = w / jnp.sum(jnp.where(chosen, p, 0.0), axis=-1, keepdims=True)
    return w, chosen


def moe(u, p_moe, config):
    """The held experts' terms and the identity experts' term: [B, L, hidden]."""
    w, _ = route(u, p_moe, config)
    routed = w.shape[-1] - int(config.get("zero_expert_num", 0))
    first = int(config.get("program", {}).get("expert_offset", 0))
    out = jnp.sum(w[..., routed:], axis=-1, keepdims=True) * u      # identity experts
    for j in range(p_moe["w_gate"].shape[0]):                       # experts held
        y = (jax.nn.silu(u @ _f32(p_moe["w_gate"][j])) * (u @ _f32(p_moe["w_up"][j]))
             ) @ _f32(p_moe["w_down"][j])
        out = out + w[..., first + j, None] * y
    return out


def block(x, p, config):
    eps = float(config["rms_norm_eps"])
    norm = lambda name, y: _rms_norm(y, _f32(p[name]["scale"]), eps)  # noqa: E731
    x = x + mla(norm("ln_attn_0", x), p["attn_0"], config)
    h = norm("ln_ffn_0", x)
    shortcut = moe(h, p["moe"], config)
    x = x + _ffn(h, p["mlp_0"])
    x = x + mla(norm("ln_attn_1", x), p["attn_1"], config)
    return x + _ffn(norm("ln_ffn_1", x), p["mlp_1"]) + shortcut


def forward(params, tokens, config):
    """Logits [B, L, vocabulary held] in float32 for int tokens [B, L]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"])[tokens]
        for i in range(config["num_layers"]):
            x = block(x, params[f"block_{i}"], config)
        x = _rms_norm(x, _f32(params["ln_f"]["scale"]), float(config["rms_norm_eps"]))
        return x @ _f32(params["lm_head"]["kernel"])
