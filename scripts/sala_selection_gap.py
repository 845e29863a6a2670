#!/usr/bin/env python3
"""How sure is the block selection of `minicpm-sala-serve` on seeded weights?

For one prompt of 10,240 random tokens and the FIRST block-selected layer
(published layer 9: its input is the scaled embedding alone, so no other
layer has to be run), at the published widths: the float32 reference's
choice of 64 blocks for the prompt's last positions against the served
path's (bf16 projections, bf16 compressed keys, `select_blocks`), and the
gap between the 64th and the 65th block score beside what bf16 rounding
moves a score by; then what the layer adds to the residual stream at the
configuration's `sparse_v_init_std`, and how far the served path's blocks,
and the forced blocks alone, move that.  Arithmetic only: it runs on the CPU and says nothing of
time (PERF.md section 6, PR 47; finding 11e's question for this model).

    JAX_PLATFORMS=cpu python scripts/sala_selection_gap.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4700000047)
    ap.add_argument("--tokens", type=int, default=10240)
    ap.add_argument("--rows", type=int, default=256, help="last positions looked at")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.configs import ROOT, load_json, load_reference
    from kungfu_tpu.models.transformer import _compressed_keys
    from kungfu_tpu.ops.decode_attn import block_scores, select_blocks

    config = load_json(os.path.join(ROOT, "benchmark", "configs",
                                    "minicpm-sala-serve.json"))
    ref = load_reference(config)
    sparse, prog = config["sparse_config"], config["program"]
    d, H, Hkv, D = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    L, rows = args.tokens, args.rows
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    # the seeded weights' distributions (configuration `assumed.weights`)
    x0 = jax.random.normal(keys[0], (L, d)) * prog["embed_init_std"] \
        * config["scale_emb"]
    w_q = jax.random.normal(keys[1], (d, H * D)) * 0.02
    w_k = jax.random.normal(keys[2], (d, Hkv * D)) * 0.02
    norm = lambda x: x * jax.lax.rsqrt(  # noqa: E731  (scales are 1)
        jnp.mean(jnp.square(x), -1, keepdims=True) + config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        u = norm(x0)
        q32 = norm((u[-rows:] @ w_q).reshape(rows, H, D))
        k32 = norm((u @ w_k).reshape(L, Hkv, D))
    t = jnp.arange(L - rows, L)
    want = np.asarray(ref.chosen_blocks(q32, k32, t, sparse))    # [rows, Hkv, nb]
    # the served path: bf16 operands and stores
    bf = jnp.bfloat16
    ub = u.astype(bf)
    qb = norm((ub[-rows:] @ w_q.astype(bf)).astype(jnp.float32).reshape(
        rows, H, D)).astype(bf)
    kb = norm((ub @ w_k.astype(bf)).astype(jnp.float32).reshape(
        L, Hkv, D)).astype(bf)
    pad = -L % sparse["block_size"]
    k_rows = jnp.pad(kb.reshape(1, L, Hkv * D), ((0, 0), (0, pad), (0, 0)))
    k_cmp = _compressed_keys(k_rows, sparse["kernel_stride"]).astype(bf)
    choice = dict(block=sparse["block_size"], stride=sparse["kernel_stride"],
                  topk=sparse["topk"], init_blocks=sparse["init_blocks"],
                  window=sparse["window_size"])
    ids, n = select_blocks(qb[None], k_cmp, t[None], **choice)
    ids, n = np.asarray(ids[0]), np.asarray(n[0])
    nb = want.shape[-1]
    alike, free = [], sparse["topk"] - sparse["init_blocks"] \
        - sparse["window_size"] // sparse["block_size"]
    for r in range(rows):
        for h in range(Hkv):
            got = np.zeros(nb, bool)
            got[ids[r, h, :n[r, h]]] = True
            alike.append(int((got[:nb] & want[r, h]).sum()))
    # the float32 scores' own margin at the cut: block scores of the
    # candidates (neither forced nor beyond the query), sorted
    scoring = {k: v for k, v in choice.items() if k != "topk"}

    def scores(q, kc):
        return np.asarray(block_scores(q[None], kc, t[None], **scoring)[0])

    s32 = scores(q32, _compressed_keys(jnp.pad(
        k32.reshape(1, L, Hkv * D), ((0, 0), (0, pad), (0, 0))),
        sparse["kernel_stride"]))
    sbf = scores(qb, k_cmp)
    cand = (s32 >= 0) & (s32 < 1e29)
    gaps, moved = [], []
    for r in range(rows):
        for h in range(Hkv):
            c = np.sort(s32[r, h][cand[r, h]])[::-1]
            gaps.append(float(c[free - 1] - c[free]))
            moved.append(float(np.abs(s32[r, h] - sbf[r, h])[cand[r, h]].max()))
    # what the layer adds to the residual stream (unit RMS here: the scaled
    # embedding alone), and how far a wrong choice of blocks moves it: the
    # float32 arithmetic under the reference's blocks, under the served
    # path's, and under the forced blocks alone (block 0 and the window:
    # a selector that chooses nothing by score)
    w_v = jax.random.normal(keys[4], (d, Hkv * D)) * prog["sparse_v_init_std"]
    w_g = jax.random.normal(keys[5], (d, H * D)) * 0.02
    w_o = jax.random.normal(keys[6], (H * D, d)) * 0.02
    depth = config["scale_depth"] / config["published"]["num_hidden_layers"] ** 0.5
    got_blocks = np.zeros(want.shape, bool)
    r_, h_ = np.meshgrid(np.arange(rows), np.arange(Hkv), indexing="ij")
    for j in range(ids.shape[-1]):
        listed = j < n
        got_blocks[r_[listed], h_[listed], ids[..., j][listed]] = True
    b = np.arange(nb)
    at = np.asarray(t)[:, None] // sparse["block_size"]
    forced = np.broadcast_to(((b[None] < sparse["init_blocks"]) | (
        (at - b[None] < sparse["window_size"] // sparse["block_size"])
        & (b[None] <= at)))[:, None], want.shape)

    with jax.default_matmul_precision("highest"):
        v32 = (u @ w_v).reshape(L, Hkv, D)
        gate = jax.nn.sigmoid(u[-rows:] @ w_g)
        sc = jnp.einsum("lkgd,mkd->lkgm", q32.reshape(rows, Hkv, H // Hkv, D),
                        k32) / jnp.sqrt(jnp.float32(D))

        def added(blocks):                                    # [rows, d]
            mask = jnp.logical_and(
                jnp.repeat(jnp.asarray(blocks), sparse["block_size"],
                           axis=-1)[..., :L],
                (jnp.arange(L)[None, :] <= t[:, None])[:, None])
            p = jax.nn.softmax(jnp.where(mask[:, :, None], sc, -jnp.inf), -1)
            o = jnp.einsum("lkgm,mkd->lkgd", p, v32).reshape(rows, H * D)
            return depth * ((o * gate) @ w_o)

        ref_out, got_out, forced_out = (
            np.asarray(added(m)) for m in (want, got_blocks, forced))
    rms = lambda a: float(np.sqrt(np.mean(np.square(a))))    # noqa: E731
    row_rms = lambda a: np.sqrt(np.mean(np.square(a), -1))   # noqa: E731
    out = {"tokens": L, "rows": rows, "kv_heads": Hkv, "blocks_reachable": int(nb),
           "sparse_v_init_std": prog["sparse_v_init_std"],
           "added_to_unit_stream_rms": rms(ref_out),
           "served_blocks_move_it_by_rms": rms(got_out - ref_out),
           "served_blocks_move_it_by_worst_row": float(
               row_rms(got_out - ref_out).max()),
           "forced_blocks_alone_move_it_by_rms": rms(forced_out - ref_out),
           "chosen": sparse["topk"], "by_score": free,
           "alike_of_64_mean": float(np.mean(alike)),
           "alike_of_64_min": int(np.min(alike)),
           "gap_64th_65th_median": float(np.median(gaps)),
           "gap_64th_65th_min": float(np.min(gaps)),
           "score_moved_by_bf16_median_of_max": float(np.median(moved)),
           "candidate_score_median": float(np.median(s32[cand])),
           "platform": jax.devices()[0].platform, "seed": args.seed}
    print("SALA_SELECTION: " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
