"""Plain reference: OLMo-style decoder (allenai/OLMo-1B-hf, `modeling_olmo`).

Straightforward float32 `jax.numpy`, no kernels, no cache, no batching
tricks; `jax.default_matmul_precision("highest")` because a TPU otherwise
multiplies float32 matrices in bf16 passes.  It reads the program's own
parameter tree (embed/embedding, block_i/{ln1,attn/{q,k,v,out},ln2,
mlp/{in,gate,out}}, ln_f), so system and reference run on the same weights.

The published block, in order: x + Attn(LN(x)), then x + MLP(LN(x)); LN is
LayerNorm over the hidden size with eps 1e-5; q, k, v, out are bias-free
linear maps; rotary embedding on q and k over the two halves of each head
(theta from the config); causal softmax attention scaled by
1/sqrt(head_dim); MLP is down(silu(gate(x)) * up(x)); a final LN; logits
through the transposed input embedding when `tie_word_embeddings`.

Departure from the published model (listed under `assumed` in the
configuration files): OLMo's LayerNorm has no parameters, the program's
carries a scale initialised to 1; the reference multiplies by that scale,
so it follows the program's tree whatever the scale holds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layer_norm(x, scale):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale


def _rope(x, theta):
    """x [B, L, H, D]: rotate the pairs (x[..., i], x[..., i + D/2])."""
    L, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(params, tokens, config):
    """Logits [B, L, vocab] in float32 for int tokens [B, L]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    H = config["num_attention_heads"]
    Hkv = config.get("num_key_value_heads") or H
    theta = float(config.get("rope_theta", 10000.0))
    with jax.default_matmul_precision("highest"):
        emb = f32(params["embed"]["embedding"])
        x = emb[tokens]
        B, L, d = x.shape
        D = d // H
        causal = jnp.tril(jnp.ones((L, L), bool))
        for i in range(config["num_hidden_layers"]):
            p = params[f"block_{i}"]
            h = _layer_norm(x, f32(p["ln1"]["scale"]))
            q = (h @ f32(p["attn"]["q"]["kernel"])).reshape(B, L, H, D)
            k = (h @ f32(p["attn"]["k"]["kernel"])).reshape(B, L, Hkv, D)
            v = (h @ f32(p["attn"]["v"]["kernel"])).reshape(B, L, Hkv, D)
            q, k = _rope(q, theta), _rope(k, theta)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(jnp.float32(D))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1), v)
            x = x + o.reshape(B, L, d) @ f32(p["attn"]["out"]["kernel"])
            h = _layer_norm(x, f32(p["ln2"]["scale"]))
            up = h @ f32(p["mlp"]["in"]["kernel"])
            gate = h @ f32(p["mlp"]["gate"]["kernel"])
            x = x + (jax.nn.silu(gate) * up) @ f32(p["mlp"]["out"]["kernel"])
        x = _layer_norm(x, f32(params["ln_f"]["scale"]))
        if config.get("tie_word_embeddings"):
            return x @ emb.T
        return x @ f32(params["lm_head"]["kernel"])
