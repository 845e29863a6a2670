"""Plain reference: openPangu-Ultra-MoE-718B (FreedomIntelligence/openPangu-Ultra-MoE-718B,
`config.json`, `model_type: pangu_ultra_moe`): DeepSeek-V3's block under
sandwich norms.

Straightforward float32 `jax.numpy`, no kernels, no cache, no sort, no
absorbed attention: every key and value is materialised for every position,
the experts are a Python loop.  `jax.default_matmul_precision("highest")`
because a TPU otherwise multiplies float32 matrices in bf16 passes.  It
reads the program's own parameter tree (embed/embedding, block_i/{ln1,
attn/{q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, out}, ln1_post, ln2,
mlp/{in, gate, out} or moe/{router, w_gate, w_up, w_down, shared/{in, gate,
out}}, ln2_post}, ln_f, lm_head/kernel, and for the prediction module
mtp_0/{enorm, hnorm, eh_proj, block, ln_f}), so system and reference run on
the same weights.

The published layer (N = RMSNorm: x * rsqrt(mean(x^2) + eps) * scale, eps
`rms_norm_eps`; `sandwich_norm`: four norms a layer):

    a = x + N2(MLA(N1(x)))        y = a + N4(F(N3(a)))

    F is the dense SwiGLU FFN(u) = W_out (silu(W_gate u) * W_in u) of width
      `intermediate_size` in the first `first_k_dense_replace` layers and
      the expert layer in the rest.
    MLA(u): c_q = N_q(u W_qa) [`q_lora_rank`]; q = c_q W_qb, a head
      [q_nope `qk_nope_head_dim` | q_rope `qk_rope_head_dim`];
      [c | k_r] = u W_kva [`kv_lora_rank` + `qk_rope_head_dim`]; c = N_kv(c);
      rotary embedding (theta `rope_theta`) on q_rope and on k_r, which all
      heads share; [k_nope | v] = c W_kvb, a head [`qk_nope_head_dim` |
      `v_head_dim`]; score = (q_nope k_nope + q_rope k_r) /
      sqrt(qk_nope_head_dim + qk_rope_head_dim), causal softmax;
      out = (sum p v) W_o.  No biases.
    Expert layer: s = sigmoid(u W_r) in float32 over `n_routed_experts`
      outputs (the published count); the `num_experts_per_tok` largest s;
      g_e = `routed_scaling_factor` * s_e / (sum of the chosen s + 1e-20)
      (`norm_topk_prob`); F(u) = FFN_shared(u) + sum over the chosen e of
      g_e * FFN_e(u), experts of width `moe_intermediate_size`, the shared
      expert of `n_shared_experts` times that.

then a final RMSNorm and the untied head.

The prediction module (`num_nextn_predict_layers` 1), for position i with
the final-normed hidden h_i of the model above and the next token t_{i+1}:

    z = [N_e(Emb(t_{i+1})) ; N_h(h_i)] W_eh    (2 hidden -> hidden)
    logits for t_{i+2} = Head(N_f(Layer(z)))

one expert layer as above with its own weights, its own norms N_e, N_h,
N_f, the model's embedding and head (`forward_mtp`).

The share.  The reference computes what the parameters it is given hold:
the number of heads is read from the output projection's rows, the routed
experts held from `w_gate`'s first dimension (they are the consecutive
experts from `program.expert_offset`), the router's width from `router`,
the vocabulary from the embedding.  Given everything, it is the whole
model.  Given one chip's share of a deployment (32 of 128 heads, 8 of 256
routed experts, 19,200 of 153,600 ids) it computes that chip's part: the
partial sum of the output projection over the heads held (and N2 of that
partial sum: what this chip's stream carries on), the held experts' terms
of the expert sum beside the shared expert's term (which every chip
computes alike, in full: it needs no exchange), logits over the slice; an
assignment to a routed expert held elsewhere adds nothing here.  No
exchange is simulated and nothing stands in for the other chips.

Departures from the published model, each noted in the configuration's
`assumed`: the config gives no `scoring_func`, `n_group`, `topk_group` or
correction bias, so the router is the family's plain sigmoid top-k with no
group limit and no bias; the rotary pairing is the program's (the two
halves of the rotary part; the published code interleaves, a fixed
permutation of the columns of W_qb and W_kva that seeded weights do not
tell apart); no `rope_scaling`; a tie among s goes to the lower index; the
module's order of concatenation, that h is taken after the final norm and
that embedding and head are shared follow DeepSeek-V3's published module
(the config gives only the count).
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
    r = config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    B, L, _ = u.shape
    w_o = _f32(p["out"]["kernel"])
    H = w_o.shape[0] // dv
    c_q = _rms_norm(u @ _f32(p["q_a"]["kernel"]), _f32(p["q_a_norm"]["scale"]), eps)
    q = (c_q @ _f32(p["q_b"]["kernel"])).reshape(B, L, H, dn + dr)
    kv_a = u @ _f32(p["kv_a"]["kernel"])
    c = _rms_norm(kv_a[..., :r], _f32(p["kv_a_norm"]["scale"]), eps)
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
    """Gate weights [B, L, router width] float32, zero outside the chosen."""
    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ _f32(p_moe["router"]))
    # the k largest, the lower index first on a tie (a stable sort)
    top = jnp.argsort(-s, axis=-1, stable=True)[..., :k]
    chosen = jnp.sum(jax.nn.one_hot(top, s.shape[-1], dtype=jnp.int32), -2) > 0
    w = jnp.where(chosen, s, 0.0)
    if config.get("norm_topk_prob"):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * float(config.get("routed_scaling_factor", 1.0))


def moe(u, p_moe, config):
    """The shared expert's term and the held experts' terms: [B, L, hidden]."""
    w = route(u, p_moe, config)
    first = int(config.get("program", {}).get("expert_offset", 0))
    out = _ffn(u, p_moe["shared"])
    for j in range(p_moe["w_gate"].shape[0]):                       # experts held
        y = (jax.nn.silu(u @ _f32(p_moe["w_gate"][j])) * (u @ _f32(p_moe["w_up"][j]))
             ) @ _f32(p_moe["w_down"][j])
        out = out + w[..., first + j, None] * y
    return out


def block(x, p, config):
    """One layer under sandwich norms; dense or expert by what `p` holds."""
    eps = float(config["rms_norm_eps"])
    norm = lambda name, y: _rms_norm(y, _f32(p[name]["scale"]), eps)  # noqa: E731
    a = x + norm("ln1_post", mla(norm("ln1", x), p["attn"], config))
    u = norm("ln2", a)
    f = moe(u, p["moe"], config) if "moe" in p else _ffn(u, p["mlp"])
    return a + norm("ln2_post", f)


def hidden_states(params, tokens, config):
    """The final-normed hidden states [B, L, hidden] the head reads."""
    x = _f32(params["embed"]["embedding"])[tokens]
    dense = int(config["first_k_dense_replace"])
    for i in range(config["num_hidden_layers"]):
        p = params[f"block_{i}"]
        assert ("mlp" in p) == (i < dense), "leading dense layers, then experts"
        x = block(x, p, config)
    return _rms_norm(x, _f32(params["ln_f"]["scale"]), float(config["rms_norm_eps"]))


def forward(params, tokens, config):
    """Logits [B, L, vocabulary held] in float32 for int tokens [B, L]."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, tokens, config) @ _f32(
            params["lm_head"]["kernel"])


def forward_mtp(params, tokens, config):
    """The prediction module's logits [B, L - 1, vocabulary held]: row i,
    from the model's hidden state at position i and token i + 1, scores
    token i + 2."""
    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, tokens, config)[:, :-1]
        m = params["mtp_0"]
        e = _f32(params["embed"]["embedding"])[tokens[:, 1:]]
        z = jnp.concatenate([_rms_norm(e, _f32(m["enorm"]["scale"]), eps),
                             _rms_norm(h, _f32(m["hnorm"]["scale"]), eps)], -1)
        x = block(z @ _f32(m["eh_proj"]["kernel"]), m["block"], config)
        x = _rms_norm(x, _f32(m["ln_f"]["scale"]), eps)
        return x @ _f32(params["lm_head"]["kernel"])
