"""The dense and the sparse-expert shapes lower to the programs they lowered
to before the latent-attention block existed (ISSUE 35).

ISSUE 35 adds a second attention formulation, a second block and an expert
layer that holds a share of its experts, all selected by fields of
`TransformerConfig` whose defaults select what was there.  The four
configurations the benchmark already measures must not move, so their
programs are held here letter for letter: the slot-cache programs of
`ServingEngine` (`_decode`, `_verify_accept`, a `_prefill` bucket) and a
training step, for a dense model (OLMo's shape: MHA, SwiGLU, RMSNorm, tied
head) and a sparse-expert one (OLMoE's: experts in every block, QK-norm),
through `ragged_dot` / the einsum and through the Pallas bodies.  GOLDEN
holds the SHA-256 of the StableHLO text commit e225a5d (PR 31, the parent
of ISSUE 35) lowered to, made by running this file as a script in a
checkout of it
(`JAX_PLATFORMS=cpu PYTHONPATH=. python tests/unit/test_parent_programs.py`).

`slots.py` and `prefix.py` are held by what they did on the host for a
`[B, max_len, Hkv, D]` cache: the rows they extract, the warm cache they
build from them and the block the engine counts fetched rows by.

A later PR that changes one of these programs on purpose runs the script
on its own tree, replaces the digest and says so.

ISSUE 42 adds the "hybrid" shape (state-space mixers beside position-free
multi-query attention, state leaves in the slot cache) so that the next PR
is held to its programs too: `_decode`, a `_prefill` bucket (which hands
the model its count of real tokens) and a training step, through the
`lax.scan` and through the selective-scan kernel's body.  Their digests are
of commit-of-PR-42's own lowering; a model with recurrent state serves
without speculation, so it has no verify program.

ISSUE 44 changes every slot-cache program on purpose: a call's new rows
reach the cache through `_store_rows` (one batched scatter of points a K/V
leaf, one `dynamic_update_slice` at batch 1) where a vmapped
`dynamic_update_slice` wrote them.  The decode, verify and prefill digests
are of PR 44's own lowering; the three training steps are the digests they
were (training never enters decode mode).

ISSUE 47 adds the "sala" shape (a block-selected layer beside a lightning
linear-attention layer, from `mixer_types`, under the MiniCPM family's three
scale constants; a matrix state and compressed keys in the slot cache) so
that the next PR is held to its programs too: `_decode`, a `_prefill`
bucket and a training step, through the einsum / `lax.scan` forms and
through the two kernels' bodies.  Their digests are of PR 47's own
lowering; like the hybrid it has no verify program.  Every digest above it
is the one it was: the new fields default to what was there.

ISSUE 48 changes `sala.prefill.interpret` on purpose: the block-selected
layer's prefill bucket (16 rows: more than `MAX_QUERY_ROWS`) chooses its
blocks chunk by chunk as before and attends them in the body of
`kft_sparse_prefill_attn` where it took a dense product under the chosen
blocks' mask.  That digest is of PR 48's own lowering.  `sala.prefill.off`
(the XLA chunks), `sala.decode.*`, `sala.train_step.off` (training mode
keeps the XLA chunks: it needs their gradient) and every digest of the
dense, experts and hybrid shapes are the ones they were.

ISSUE 49 changes `dense.decode.interpret`, `dense.verify.interpret`,
`experts.decode.interpret` and `experts.verify.interpret` on purpose: the
body of `kft_decode_attn` walks the list of the live (slot, block) pairs,
built once a step from the first layer's cursors and the step's `live`
mask, on a grid as long as the list, where it walked slots x blocks a slot.
Those four digests are of PR 49's own lowering.  Every `.off` digest (the
einsum takes no notice of the mask and the model builds no list for it),
every prefill and training step, and the hybrid and sala shapes (one and
two KV heads: the einsum; a kernel of their own) are the ones they were.
"""
import dataclasses
import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

GOLDEN = {
    "dense.decode.interpret":
        "9dab7c1f80bd486800c6148ed60d7e4f1756f2f0a117393b7b17600686f1c2c6",
    "dense.decode.off":
        "dff2ce6cdb42d7a30f2a889fbfb5faf134e6e6380deac0c15cf70a0fce7fd22a",
    "dense.prefill.interpret":
        "afa5e34826a3f8e241292009d0b30e48b6f11b002f43e8b75034079e12ed72f8",
    "dense.prefill.off":
        "afa5e34826a3f8e241292009d0b30e48b6f11b002f43e8b75034079e12ed72f8",
    "dense.train_step.off":
        "e1bf78d520ffde169777bb0f2d5d35a364d77ff15c831504558858f11f774798",
    "dense.verify.interpret":
        "2b368bff0bfbff0248510b8bea763090c4c85bff3cbbb69f68f1a1dd5acc7782",
    "dense.verify.off":
        "049418a09b22d252913f08dccdf0dded884ca7d1065eaf9da61b74db7337f634",
    "experts.decode.interpret":
        "cc1c0bc3168a05c7e7bbab306551152d4284aa869d0a2606196a82d3f279ba4f",
    "experts.decode.off":
        "11d682522325e29e834f1883f018d2b0fee92a45f91f306e53c19309860b9802",
    "experts.prefill.interpret":
        "f2a213abd2a6a52ccb8847aab92eb81daa2853af47a68c766c532f1334a3f0b0",
    "experts.prefill.off":
        "220191571cc751386c3b28ebc5ecc5b4e01736577f92aa169abd5d7cd1eb1081",
    "experts.train_step.off":
        "6dc0c6c1db163676f6b77ff85271948f55fec02d7f5edfba18c605f64d2c7c15",
    "experts.verify.interpret":
        "117a8c80b92a80cabd590d9d84956c685ae1155fe08753714e619dbd588ddcb1",
    "experts.verify.off":
        "881c93b4acbb03f19a25fa36efc330a0bf64c8d45a5c83b7fa488fc10e703fad",
    "hybrid.decode.interpret":
        "01e2c48cdae41eb5795757afb53c2dac09203f96d693aba981e487382bb1e8ab",
    "hybrid.decode.off":
        "b8d7614e871a6f730e8aca68efdbd4e26b91b2c0cbed1f7e0e39920855320c53",
    "hybrid.prefill.interpret":
        "c0af09bf77dd244570dc0f065dcfdd59bd84094cc0f815945c1a43ecff35cffa",
    "hybrid.prefill.off":
        "1ad15325891f75f2c0c3de60a98cf17de03d3fccbe78689d2aec78b74c700d01",
    "hybrid.train_step.off":
        "a25b9f333846a5147707ded4446f86ccb38868497edd35f7b302ffbb16f5d75f",
    "sala.decode.interpret":
        "2500f4e269c92816d1483ec99cf2854f8d2771aca81b671df807daaccaad0352",
    "sala.decode.off":
        "b5d0acea40ddc98ef1491b54e1efc428d532b47e3c56b3c65daf30cc3e053be5",
    "sala.prefill.interpret":
        "6e7bd0951ab96736647166fb8dbea175b9e12279952b0501730bae6f2ebe737d",
    "sala.prefill.off":
        "0ec3d8dad8945143c6a40b58928889468caeabd3ea31b67c72d44f08afa95d27",
    "sala.train_step.off":
        "cc0771dbf157ee59a31e6aa33909c3220e366631f7845c24de8087e7157a4529",
}

SLOTS, K = 4, 3


def _config(shape: str):
    from kungfu_tpu.models.transformer import TransformerConfig

    # head_dim 128 over 8 float32 KV heads: the shape the decode attention
    # kernel takes, so the interpreted programs hold its body
    base = dict(vocab_size=64, d_model=1024, n_layers=2, n_heads=8, d_ff=64,
                max_len=64, rope=True, ffn="swiglu", norm="rms",
                dtype=jnp.float32)
    if shape == "dense":
        return TransformerConfig(tie_embeddings=True, **base)
    if shape == "hybrid":
        # layer 1 of 2 attention (one KV head: the dense einsum), layer 0 a
        # state-space mixer of inner width 2048: four sub-tiles of the kernel
        return TransformerConfig(**dict(
            base, rope=False, pos_table=False, tie_embeddings=True,
            n_kv_heads=1, mamba_d_state=16, mamba_dt_rank=64,
            attn_layer_period=2, attn_layer_offset=1))
    if shape == "sala":
        # layer 0 block-selected (8 query heads on 2 KV heads, blocks of 8
        # rows, 4 of 8 read), layer 1 lightning (8 heads of 128: one block
        # of heads of its kernel)
        return TransformerConfig(**dict(
            base, n_kv_heads=2, attn_use_rope=False,
            mixer_types=("minicpm4", "lightning-attn"), sparse_block_size=8,
            sparse_topk=4, sparse_kernel_stride=2,
            sparse_window_size=16, scale_emb=12.0, scale_depth=1.4,
            scale_depth_layers=32, dim_model_base=256))
    return TransformerConfig(qk_norm=True, n_experts=4, experts_per_token=2,
                             moe_every=1, embed_init_std=1.0, **base)


def _params(cfg):
    from kungfu_tpu.models.transformer import TransformerLM

    return nn.meta.unbox(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"])


def _engine(shape: str):
    from kungfu_tpu.serving import ServingEngine

    cfg = _config(shape)
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), _params(cfg))
    return ServingEngine(cfg, zeros, slots=SLOTS, prefill_buckets=(16,))


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _decode(shape):
    eng = _engine(shape)
    return eng._decode.lower(eng.params, eng.cache, eng._dev_counters,
                             _i32(SLOTS, 1))


def _verify(shape):
    eng = _engine(shape)
    return eng._verify.lower(eng.params, eng.cache, eng._dev_counters,
                             _i32(SLOTS, K), _i32(SLOTS, K - 1))


def _prefill(shape):
    eng = _engine(shape)
    return eng._prefill.lower(eng.params, eng._small_cache0, _i32(1, 16), 5, 5)


def _train_step(shape):
    from kungfu_tpu.models.transformer import (
        TransformerLM, lm_loss, lm_loss_with_aux)

    cfg = dataclasses.replace(_config(shape), remat=True)
    model = TransformerLM(cfg)
    if cfg.n_experts:
        loss = lambda p, t: lm_loss_with_aux(model, p, t)  # noqa: E731
    else:
        loss = lambda p, t: lm_loss(model.apply({"params": p}, t), t)  # noqa: E731
    return jax.jit(jax.value_and_grad(loss)).lower(_params(cfg), _i32(2, 16))


PROGRAMS = {
    f"{shape}.{name}.{mode}": (mode, lower, shape)
    for shape in ("dense", "experts", "hybrid", "sala")
    for name, lower in (("decode", _decode), ("verify", _verify),
                        ("prefill", _prefill), ("train_step", _train_step))
    if (shape, name) not in (("hybrid", "verify"), ("sala", "verify"))
    # the Pallas bodies are what a TPU runs: the decode attention kernel,
    # the grouped matmul.  The training step has no kernel of its own here
    for mode in (("off",) if name == "train_step" else ("off", "interpret"))
}


def _digest(lower, shape) -> str:
    return hashlib.sha256(lower(shape).as_text().encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_program_is_the_parents(name, monkeypatch):
    mode, lower, shape = PROGRAMS[name]
    monkeypatch.setenv("KFT_PALLAS", mode)
    assert _digest(lower, shape) == GOLDEN[name], (
        f"{name} no longer lowers to what commit e225a5d lowered to: if "
        "that is meant, run this file as a script and replace GOLDEN")


# -- the host work of slots.py and prefix.py -------------------------------------------


def _small_with_rows(eng, n):
    """A batch-1 cache whose position-indexed leaves hold distinct numbers."""
    def fill(path, leaf):
        if getattr(path[-1], "key", None) in ("idx", "overflowed"):
            return leaf
        return jnp.arange(leaf.size, dtype=jnp.float32).reshape(
            leaf.shape).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(fill, eng._small_cache0)


@pytest.mark.parametrize("shape", ["dense", "experts"])
def test_the_row_helpers_do_the_host_work_they_did(shape, monkeypatch):
    """`extract_rows` gives one `[n, Hkv, D]` block a K or V leaf under the
    flattened path, `warm_small_cache` puts them back under a cursor at n,
    a prefix-cache lease returns the same rows, and the engine counts the
    rows a step fetches by the dense kernel's block (or the whole cache off
    TPU)."""
    from kungfu_tpu.serving.prefix import PrefixCache
    from kungfu_tpu.serving.slots import extract_rows, warm_small_cache

    monkeypatch.setenv("KFT_PALLAS", "off")
    eng = _engine(shape)
    cfg = eng.dcfg
    assert eng._attn_block == {1: None}
    n = 7
    small = _small_with_rows(eng, n)
    rows = extract_rows(small, n)
    head_dim = cfg.d_model // cfg.n_heads
    assert sorted(rows) == sorted(
        (f"['block_{i}']", "['attn']", f"['cached_{kv}']")
        for i in range(cfg.n_layers) for kv in "kv")
    for key, block in rows.items():
        assert block.shape == (n, cfg.n_heads, head_dim)
        assert block.dtype == np.float32 and block.flags["C_CONTIGUOUS"]
    warm = warm_small_cache(eng._small_cache0, rows, n)
    assert jax.tree.structure(warm) == jax.tree.structure(small)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(warm), jax.tree.leaves(small)):
        name = path[-1].key
        if name == "idx":
            assert np.asarray(got).tolist() == [n]
        elif name == "overflowed":
            assert np.asarray(got).tolist() == [False]
        else:
            assert got.shape == want.shape and got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got)[0, :n],
                                          np.asarray(want)[0, :n])
            assert not np.asarray(got)[0, n:].any()
    cache = PrefixCache(budget_bytes=1 << 20)
    tokens = tuple(range(1, n + 1))
    cache.insert(tokens, rows)
    hit, lease = cache.match(tokens + (9,))
    assert hit == n
    for key, block in lease.rows().items():
        np.testing.assert_array_equal(block, rows[key])
    lease.release()
    assert cache.total_bytes == sum(b.nbytes for b in rows.values())


def test_the_engine_counts_fetched_rows_by_the_dense_kernels_block(monkeypatch):
    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM
    from kungfu_tpu.serving import ServingEngine

    monkeypatch.setenv("KFT_PALLAS", "interpret")
    cfg = TransformerConfig(vocab_size=32, d_model=1024, n_layers=1, n_heads=8,
                            d_ff=32, max_len=512, rope=True, dtype=jnp.float32)
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        nn.meta.unbox(jax.eval_shape(
            TransformerLM(cfg).init, jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32))["params"]))
    assert ServingEngine(cfg, params, slots=2)._attn_block == {1: 256}


if __name__ == "__main__":
    for name, (mode, lower, shape) in sorted(PROGRAMS.items()):
        os.environ["KFT_PALLAS"] = mode
        print(f'    "{name}":\n        "{_digest(lower, shape)}",')
