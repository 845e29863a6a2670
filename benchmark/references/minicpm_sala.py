"""Plain reference: MiniCPM-SALA (openbmb/MiniCPM-SALA, `config.json`,
`model_type: minicpm_sala`): lightning linear-attention layers 3:1 with
block-selected sparse attention (`mixer_types`), every feed-forward part the
dense SwiGLU, the MiniCPM family's three scale constants.

Straightforward float32 `jax.numpy`, no kernels, no cache, no batching of
requests: the linear-attention recurrence is a `lax.scan` over the tokens of
a whole sequence from a zero state, the selection and the sparse softmax are
written from the equations below for every query position, in blocks of
query rows (and the SwiGLU in blocks of rows) so that a 9,000-token request
fits beside the float32 tree on one chip.
`jax.default_matmul_precision("highest")` because a TPU otherwise multiplies
float32 matrices in bf16 passes.  It reads the program's own parameter tree
(embed/embedding, block_i/{ln1, lin/{q, k, v, gate, out, q_norm, k_norm,
o_norm} or attn/{q, k, v, gate, out, q_norm, k_norm}, ln2, mlp/{in, gate,
out}}, ln_f, lm_head/kernel), so system and reference run on the same
weights.

d = `hidden_size`, N = RMSNorm (x * rsqrt(mean(x^2) + `rms_norm_eps`) *
scale), s = `scale_depth` / sqrt(the PUBLISHED `num_hidden_layers`, 32: a
trained constant, also at a cut depth):

    x0     = E[token] * `scale_emb`
    h      = x + s * Mixer_i(N(x));   y = h + s * MLP(N(h))
    MLP(u) = W_down(silu(W_gate u) * W_up u), width `intermediate_size`
    logits = W_head( N_f(y) / (`hidden_size` / `dim_model_base`) )   (untied)
    Mixer_i by `mixer_types`[i]: "lightning-attn" | "minicpm4"

`lightning-attn` (H = `lightning_nh` = `lightning_nkv`, e = `lightning_head_dim`):

    q, k, v = W_q u, W_k u, W_v u          (d -> H e each, no bias, no activation)
    q, k    = rope(N_q(q)), rope(N_k(k))   (`qk_norm`: RMSNorm over e a head, scale [e];
                                            `lightning_use_rope`, `rope_theta`, pairs
                                            (x[:e/2], x[e/2:]) as the program rotates them)
    S_t     = lam_h * S_{t-1} + k_t^T v_t  (S in R^{e x e} a head, float32, S_{-1} = 0)
    o_t     = (q_t S_t) / sqrt(e)          (`lightning_scale` "1/sqrt(d)")
    lam_h   = exp(-2^(-8 (h+1) / H)),  h = 0..H-1     (not learned, the same in every layer)
    out     = W_o( N_o(o) * sigmoid(W_g u) )          (`use_output_norm`: RMSNorm over all H e;
                                                       `use_output_gate`: W_g d -> H e, no bias)

`minicpm4` (`num_attention_heads` query heads on `num_key_value_heads` KV
heads of `head_dim`, group G; `attn_use_rope` false: no positional term;
the sizes below are `sparse_config`'s):

    q, k, v = W_q u, W_k u, W_v u;  q, k = N_q(q), N_k(k) a head
    kbar_m  = mean(k_j, j = stride m .. stride m + kernel_size - 1)  a KV head,
              for every such kernel wholly inside 0..t
    p^h     = softmax_m( q_h . kbar_m / sqrt(head_dim) )   over those kernels, a query head
    P_g,m   = sum over the G heads h of group g of p^h_m
    score_b = max( P_g,m : kernel m overlaps block b = rows block_size b .. block_size (b+1) - 1 )
    chosen  = the first `init_blocks` blocks and the blocks of the `window_size` newest
              rows always, then the highest score_b, `topk` blocks in all; every block at or
              before the query's while those are <= `topk`
    o_h     = softmax over the rows j <= t of the chosen blocks ( q_h . k_j / sqrt(head_dim) ) v_j
    out     = W_o( o * sigmoid(W_g u) )                    (`attn_use_output_gate`)

So a query with at most `topk` blocks at or before it is plain causal
attention exactly.

Departures and fillings-in, each listed under `assumed` in the
configuration's file: the seven sparse sizes (the catalog's `config` carries
none); `dense_len` not modelled (selection at every position); the decay
schedule; no activation on q, k, v; `qk_norm` in both layer kinds, a head;
the output norm over the whole width, the gate after it; `mup_denominator`
read by nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows (and SwiGLU rows) handled at once: [heads, 256, 9,600] float32
#: scores are 315 MB
ROWS = 256


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _in_row_blocks(fn, *rows):
    """fn over blocks of `ROWS` rows of [L, ...] arrays, one after another;
    the rows are padded with zeros to whole blocks and cut again."""
    L = rows[0].shape[0]
    pad = -L % ROWS
    blocks = [jnp.pad(r, ((0, pad),) + ((0, 0),) * (r.ndim - 1)).reshape(
        (-1, ROWS) + r.shape[1:]) for r in rows]
    out = jax.lax.map(lambda b: fn(*b), tuple(blocks))
    return out.reshape((-1,) + out.shape[2:])[:L]


def _rope(x, theta):
    """x [L, H, e] at positions 0..L-1: pairs (x[:e/2], x[e/2:]) rotated."""
    L, _, e = x.shape
    half = e // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _lightning(p, u, config):
    H, e = config["lightning_nh"], config["lightning_head_dim"]
    eps, L = config["rms_norm_eps"], u.shape[0]
    q = (u @ _f32(p["q"]["kernel"])).reshape(L, H, e)
    k = (u @ _f32(p["k"]["kernel"])).reshape(L, H, e)
    v = (u @ _f32(p["v"]["kernel"])).reshape(L, H, e)
    q = _rms_norm(q, _f32(p["q_norm"]["scale"]), eps)
    k = _rms_norm(k, _f32(p["k_norm"]["scale"]), eps)
    if config.get("lightning_use_rope", True):
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    lam = jnp.exp(-jnp.exp2(
        -8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H))[:, None, None]

    def token(s, qkv):
        q_t, k_t, v_t = qkv                                   # [H, e] each
        s = lam * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hd,hde->he", q_t, s)

    _, o = jax.lax.scan(token, jnp.zeros((H, e, e), jnp.float32), (q, k, v))
    o = o.reshape(L, H * e) / jnp.sqrt(jnp.float32(e))
    o = _rms_norm(o, _f32(p["o_norm"]["scale"]), eps)
    return (o * jax.nn.sigmoid(u @ _f32(p["gate"]["kernel"]))) \
        @ _f32(p["out"]["kernel"])


def chosen_blocks(q, k, t, sparse):
    """[rows, Hkv, blocks] bool: the blocks the queries q [rows, H, D] at
    positions t [rows] attend, a KV head, against the keys k [L, Hkv, D] of
    the whole sequence, by `sparse` (the configuration's `sparse_config`)."""
    size, stride = sparse["kernel_size"], sparse["kernel_stride"]
    block, topk = sparse["block_size"], sparse["topk"]
    L, Hkv, D = k.shape
    G = q.shape[1] // Hkv
    nb = -(-L // block)
    nk = max((L - size) // stride + 1, 0)
    at = t // block                                           # the query's block
    b = jnp.arange(nb)
    score = jnp.zeros((q.shape[0], Hkv, nb), jnp.float32)
    if nk:
        first = stride * jnp.arange(nk)                       # a kernel's first row
        kbar = k[first[:, None] + jnp.arange(size)].mean(1)   # [nk, Hkv, D]
        seen = (first + size - 1)[None, :] <= t[:, None]      # [rows, nk]
        s = jnp.einsum("lkgd,mkd->lkgm", q.reshape(-1, Hkv, G, D), kbar) \
            / jnp.sqrt(jnp.float32(D))
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        p = jnp.where(seen[:, None, None],
                      jax.nn.softmax(s, axis=-1), 0.0)        # no kernel seen: 0
        p = p.sum(2)                                          # [rows, Hkv, nk]
        overlaps = jnp.logical_and(
            first[:, None] <= (block * b + block - 1)[None, :],
            (first + size - 1)[:, None] >= (block * b)[None, :])  # [nk, nb]
        score = jnp.where(overlaps[None, None], p[..., None], 0.0).max(2)
    forced = jnp.logical_or(
        b[None, :] < sparse["init_blocks"],
        at[:, None] - b[None, :] < sparse["window_size"] // block)
    reachable = b[None, :] <= at[:, None]
    rank = jnp.where(forced[:, None], jnp.inf, score)
    rank = jnp.where(reachable[:, None], rank, -jnp.inf)
    order = jnp.argsort(-rank, axis=-1, stable=True)[..., :topk]
    picked = jnp.zeros(rank.shape, bool).at[
        jnp.arange(rank.shape[0])[:, None, None],
        jnp.arange(Hkv)[None, :, None], order].set(True)
    return jnp.logical_and(picked, reachable[:, None])


def _sparse(p, u, config):
    H = config["num_attention_heads"]
    Hkv = config.get("num_key_value_heads") or H
    D = config.get("head_dim") or config["hidden_size"] // H
    eps, L = config["rms_norm_eps"], u.shape[0]
    sparse = config["sparse_config"]
    block = sparse["block_size"]
    q = _rms_norm((u @ _f32(p["q"]["kernel"])).reshape(L, H, D),
                  _f32(p["q_norm"]["scale"]), eps)
    k = _rms_norm((u @ _f32(p["k"]["kernel"])).reshape(L, Hkv, D),
                  _f32(p["k_norm"]["scale"]), eps)
    v = (u @ _f32(p["v"]["kernel"])).reshape(L, Hkv, D)

    def rows(q_b, t_b):
        blocks = chosen_blocks(q_b, k, t_b, sparse)           # [rows, Hkv, nb]
        mask = jnp.logical_and(
            jnp.repeat(blocks, block, axis=-1)[..., :L],
            (jnp.arange(L)[None, :] <= t_b[:, None])[:, None])
        s = jnp.einsum("lkgd,mkd->lkgm", q_b.reshape(-1, Hkv, H // Hkv, D), k) \
            / jnp.sqrt(jnp.float32(D))
        s = jnp.where(mask[:, :, None], s, -jnp.inf)
        return jnp.einsum("lkgm,mkd->lkgd", jax.nn.softmax(s, axis=-1), v
                          ).reshape(-1, H * D)

    o = _in_row_blocks(rows, q, jnp.arange(L))
    return (o * jax.nn.sigmoid(u @ _f32(p["gate"]["kernel"]))) \
        @ _f32(p["out"]["kernel"])


def _mlp(p, u):
    def rows(u_b):
        up = u_b @ _f32(p["in"]["kernel"])
        gate = u_b @ _f32(p["gate"]["kernel"])
        return (jax.nn.silu(gate) * up) @ _f32(p["out"]["kernel"])

    return _in_row_blocks(rows, u)


def forward(params, tokens, config):
    """Logits [B, L, vocab] in float32 for int tokens [B, L], a row at a
    time."""
    eps = config["rms_norm_eps"]
    depth = config.get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    s = config["scale_depth"] / jnp.sqrt(jnp.float32(depth))
    mixers = {"lightning-attn": ("lin", _lightning), "minicpm4": ("attn", _sparse)}

    def row(toks):
        x = _f32(params["embed"]["embedding"])[toks] * config["scale_emb"]
        for i, kind in enumerate(config["mixer_types"]):
            p = params[f"block_{i}"]
            name, mixer = mixers[kind]
            x = x + s * mixer(p[name], _rms_norm(x, _f32(p["ln1"]["scale"]), eps),
                              config)
            x = x + s * _mlp(p["mlp"], _rms_norm(x, _f32(p["ln2"]["scale"]), eps))
        x = _rms_norm(x, _f32(params["ln_f"]["scale"]), eps)
        x = x / (config["hidden_size"] / config["dim_model_base"])
        return x @ _f32(params["lm_head"]["kernel"])

    with jax.default_matmul_precision("highest"):
        return jnp.stack([row(t) for t in tokens])
