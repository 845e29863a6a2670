"""Plain reference: Jamba (ai21labs/AI21-Jamba2-3B, `config.json`,
`model_type: jamba`): Mamba-1 mixers with an attention layer a period, every
feed-forward part the dense SwiGLU (`num_experts` 1).

Straightforward float32 `jax.numpy`, no kernels, no cache, no batching: the
recurrence is a `lax.scan` over the tokens of a whole sequence from a zero
state, the convolution a sum over its taps on a zero-padded copy, attention
the full masked softmax.  `jax.default_matmul_precision("highest")` because
a TPU otherwise multiplies float32 matrices in bf16 passes.  It reads the
program's own parameter tree (embed/embedding, block_i/{ln1, attn/{q, k, v,
out} or mamba/{in_proj, conv_w, conv_b, x_proj, dt_norm, b_norm, c_norm,
dt_proj, dt_bias, A_log, D, out_proj}, ln2, mlp/{in, gate, out}}, ln_f), so
system and reference run on the same weights.

The published layer i, N = RMSNorm (x * rsqrt(mean(x^2) + `rms_norm_eps`) *
scale), d = `hidden_size`, d_inner = `mamba_expand` x d, n = `mamba_d_state`,
r = `mamba_dt_rank`, k = `mamba_d_conv`:

    h = x + Mixer_i(N(x))          out = h + MLP(N(h))
    Mixer_i = attention where i % `attn_layer_period` == `attn_layer_offset`,
              else the Mamba mixer
    MLP(u)  = W_down (silu(W_gate u) * W_up u), width `intermediate_size`
    attention(u): `num_attention_heads` query heads and
      `num_key_value_heads` key/value heads of d / heads, no bias, NO
      rotary embedding and no position term of any kind; causal
      softmax(q k^T / sqrt(head)) v; W_o
    Mamba(u):
      [x, z]     = u W_in                       (d -> 2 d_inner, no bias)
      x_t        = silu(sum_{j<k} w[j] * x_{t-k+1+j} + b_conv)   (depthwise,
                   causal, zeros before the sequence starts)
      [dt, B, C] = x_t W_x                      (d_inner -> r + n + n)
      dt, B, C   = N_dt(dt), N_B(B), N_C(C)     (Jamba's addition to Mamba-1)
      Delta_t    = softplus(dt W_dt + b_dt)     (r -> d_inner)
      A          = -exp(A_log)
      h_t        = exp(Delta_t A) * h_{t-1} + (Delta_t * x_t) B_t
      y_t        = h_t C_t + D * x_t
      out        = (y_t * silu(z_t)) W_out      (d_inner -> d, no bias)

then a final RMSNorm and logits through the transposed embedding
(`tie_word_embeddings`).

Departures from the published modelling code, each listed under `assumed`
in the configuration's file:

* which layers are attention is the rule above (the config carries the
  period and the offset; the catalog does not give the order);
* `A_log` and the state are read TRANSPOSED, [n, d_inner], as the program
  stores them (the published tensors are [d_inner, n]): the same numbers;
* the convolution's weight is `conv_w` [k, d_inner] (published:
  [d_inner, 1, k]);
* `mamba_proj_bias` is false in the published config and no projection
  bias is read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def is_attention_layer(config: dict, i: int) -> bool:
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def _attention(p, u, config):
    H = config["num_attention_heads"]
    Hkv = config.get("num_key_value_heads") or H
    B, L, d = u.shape
    D = d // H
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    q = (u @ f32(p["q"]["kernel"])).reshape(B, L, H, D)
    k = (u @ f32(p["k"]["kernel"])).reshape(B, L, Hkv, D)
    v = (u @ f32(p["v"]["kernel"])).reshape(B, L, Hkv, D)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, L, d) @ f32(p["out"]["kernel"])


def _mamba(p, u, config):
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    eps = config["rms_norm_eps"]
    n, r, k = (config["mamba_d_state"], config["mamba_dt_rank"],
               config["mamba_d_conv"])
    B, L, d = u.shape
    di = config["mamba_expand"] * d
    xz = u @ f32(p["in_proj"]["kernel"])
    x, z = xz[..., :di], xz[..., di:]
    w = f32(p["conv_w"])                                          # [k, di]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = sum(padded[:, j:j + L] * w[j] for j in range(k))
    if config.get("mamba_conv_bias", True):
        x = x + f32(p["conv_b"])
    x = jax.nn.silu(x)
    dbc = x @ f32(p["x_proj"]["kernel"])
    dt = _rms_norm(dbc[..., :r], f32(p["dt_norm"]["scale"]), eps)
    b = _rms_norm(dbc[..., r:r + n], f32(p["b_norm"]["scale"]), eps)
    c = _rms_norm(dbc[..., r + n:], f32(p["c_norm"]["scale"]), eps)
    delta = jax.nn.softplus(dt @ f32(p["dt_proj"]["kernel"]) + f32(p["dt_bias"]))
    a = -jnp.exp(f32(p["A_log"]))                                 # [n, di]

    def token(h, inp):
        x_t, delta_t, b_t, c_t = inp            # [B, di], [B, di], [B, n] x 2
        h = (jnp.exp(delta_t[:, None, :] * a) * h
             + (delta_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.einsum("bnd,bn->bd", h, c_t)

    first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(token, jnp.zeros((B, n, di), jnp.float32),
                        (first(x), first(delta), first(b), first(c)))
    y = jnp.moveaxis(y, 0, 1) + f32(p["D"]) * x
    return (y * jax.nn.silu(z)) @ f32(p["out_proj"]["kernel"])


def forward(params, tokens, config):
    """Logits [B, L, vocab] in float32 for int tokens [B, L]."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        emb = f32(params["embed"]["embedding"])
        x = emb[tokens]
        for i in range(config["num_hidden_layers"]):
            p = params[f"block_{i}"]
            u = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
            if is_attention_layer(config, i):
                x = x + _attention(p["attn"], u, config)
            else:
                x = x + _mamba(p["mamba"], u, config)
            u = _rms_norm(x, f32(p["ln2"]["scale"]), eps)
            up = u @ f32(p["mlp"]["in"]["kernel"])
            gate = u @ f32(p["mlp"]["gate"]["kernel"])
            x = x + (jax.nn.silu(gate) * up) @ f32(p["mlp"]["out"]["kernel"])
        x = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        return x @ emb.T
