"""MiniCPM-SALA on the normal path (ISSUE 47): a mixer kind a layer from
`mixer_types`, `LightningAttention` (a matrix state a slot), `SparseAttention`
(block selection, compressed keys beside the rows), the family's three scale
constants, against the plain reference `benchmark/references/minicpm_sala.py`
at tiny widths in float32, through the slot cache, and through
`ServingEngine` with what it refuses for such a model.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib.configs import load_reference
from kungfu_tpu.models.transformer import (LISTED_MIXERS, TransformerConfig,
                                           TransformerLM, _compressed_keys)
from kungfu_tpu.ops import decode_attn as da
from kungfu_tpu.serving.slots import STATE_LEAVES, STRIDED_LEAVES
from kungfu_tpu.serving.worker import build_config, seed_params

MIXERS = ("minicpm4", "lightning-attn", "lightning-attn", "minicpm4")
VOCAB, MAX_LEN, BUCKET, SLOTS = 96, 128, 64, 3
#: float32 on both sides: summation order only
F32_TOL = 1e-4

SPARSE = dict(kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8,
              window_size=16, topk=4)
#: the tiny model as `--model-json` carries it (a list, strings for dtypes)
PROGRAM = dict(
    vocab_size=VOCAB, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=96,
    max_len=MAX_LEN, dtype="float32", rope=True, attn_use_rope=False,
    norm="rms", ffn="swiglu", mixer_types=list(MIXERS),
    sparse_block_size=8, sparse_topk=4,
    sparse_kernel_stride=2, sparse_init_blocks=1,
    sparse_window_size=16, scale_emb=12.0, scale_depth=1.4,
    scale_depth_layers=32, dim_model_base=16, embed_init_std=1 / 12,
    head_init_std=0.45)
#: the same model under the published keys the reference reads
CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_head_dim=16, lightning_use_rope=True,
    rms_norm_eps=1e-6, rope_theta=10000.0, scale_emb=12, scale_depth=1.4,
    dim_model_base=16, num_hidden_layers=4, mixer_types=list(MIXERS),
    published={"num_hidden_layers": 32}, reference="minicpm_sala",
    sparse_config=SPARSE)


@pytest.fixture(scope="module")
def cfg():
    return build_config("tiny", json.dumps(PROGRAM))


@pytest.fixture(scope="module")
def params(cfg):
    return seed_params(cfg, 3)


@pytest.fixture(scope="module")
def reference():
    ref = load_reference(CONFIG)
    fwd = jax.jit(lambda p, t: ref.forward(p, t, CONFIG))
    fwd.module = ref
    return fwd


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(5), (2, 100), 0, VOCAB)


def worst(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


# -- the configuration ----------------------------------------------------------------


def test_a_list_from_json_names_every_layers_mixer(cfg):
    assert cfg.mixer_types == MIXERS and isinstance(cfg.mixer_types, tuple)
    assert [cfg.layer_kind(i) for i in range(4)] == list(MIXERS)
    assert not any(cfg.layer_is_ssm(i) for i in range(4))
    assert cfg.has_mixer("minicpm4") and not cfg.has_mixer("ssm", "attention")
    assert cfg.keeps_state
    hash(cfg)                       # flax and lru_cache key on it
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, mixer_types=MIXERS[:3])
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, mixer_types=("mamba",) * 4)
    with pytest.raises(AssertionError):
        # plain attention is what a model WITHOUT a list has: no
        # configuration lists it, so the list does not take it
        dataclasses.replace(cfg, mixer_types=("attention",) + MIXERS[1:])
    with pytest.raises(AssertionError, match="whole strides a block"):
        dataclasses.replace(cfg, sparse_kernel_stride=3)


@pytest.mark.parametrize("kind", LISTED_MIXERS)
def test_every_kind_a_list_may_name_keeps_state(kind):
    """The supervisor refuses a model with ANY list what it refuses a
    stateful one, from the JSON alone (it loads no jax): that rule is this
    property of `LISTED_MIXERS`."""
    only = TransformerConfig(vocab_size=32, d_model=32, n_layers=2, n_heads=4,
                             d_ff=32, max_len=16, mixer_types=[kind] * 2)
    assert only.keeps_state


def test_the_value_projections_seeding_is_the_block_selected_layers_alone(cfg):
    """`sparse_v_init_std` gives a layer without an output norm its share
    of the stream on stand-in weights (the benchmark's configuration names
    the value); every other matrix, a lightning layer's v too, stays 0.02."""
    params = seed_params(dataclasses.replace(cfg, sparse_v_init_std=0.5), 3)
    std = lambda leaf: float(jnp.std(leaf["kernel"]))   # noqa: E731
    for i, kind in enumerate(MIXERS):
        if kind == "minicpm4":
            mixer = params[f"block_{i}"]["attn"]
            assert std(mixer["v"]) == pytest.approx(0.5, rel=0.1)
            assert std(mixer["k"]) == pytest.approx(0.02, rel=0.1)
        else:
            assert std(params[f"block_{i}"]["lin"]["v"]) == pytest.approx(
                0.02, rel=0.1)


@pytest.mark.parametrize("rows", [2, 8])
def test_a_verify_steps_rows_are_refused(cfg, params, tokens, rows):
    """Speculation is not served for this model, so no decode-mode call has
    2 to MAX_QUERY_ROWS rows: a block-selected layer says so when traced."""
    model = _decode_model(cfg)
    with pytest.raises(AssertionError, match="no verify step"):
        model.apply({"params": params, "cache": _empty(model, 2)},
                    tokens[:, :rows], mutable=["cache"])


def test_layer_is_ssm_is_a_view_of_layer_kind():
    hybrid = TransformerConfig(
        vocab_size=32, d_model=32, n_layers=4, n_heads=4, d_ff=32, max_len=16,
        rope=False, pos_table=False, mamba_d_state=4, mamba_dt_rank=4,
        attn_layer_period=2, attn_layer_offset=1)
    assert [hybrid.layer_kind(i) for i in range(4)] == [
        "ssm", "attention", "ssm", "attention"]
    assert [hybrid.layer_is_ssm(i) for i in range(4)] == [True, False] * 2
    assert hybrid.keeps_state
    plain = TransformerConfig(vocab_size=32, d_model=32, n_layers=2, n_heads=4,
                              d_ff=32, max_len=16)
    assert [plain.layer_kind(i) for i in range(2)] == ["attention"] * 2
    assert not plain.keeps_state and plain.mixer_types == ()


def test_the_parameter_tree_is_what_the_reference_reads(cfg, params):
    assert sorted(params["block_0"]["attn"]) == [
        "gate", "k", "k_norm", "out", "q", "q_norm", "v"]
    assert sorted(params["block_1"]["lin"]) == [
        "gate", "k", "k_norm", "o_norm", "out", "q", "q_norm", "v"]
    assert params["block_0"]["attn"]["k"]["kernel"].shape == (64, 32)
    assert params["block_0"]["attn"]["q_norm"]["scale"].shape == (16,)
    assert params["block_1"]["lin"]["o_norm"]["scale"].shape == (64,)
    assert "pos_embed" not in params and "lm_head" in params


def test_every_projection_is_resident_in_the_models_dtype(cfg):
    from kungfu_tpu.models.transformer import resident_params

    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: seed_params(bf16, 0))
    res = resident_params(bf16, shapes)
    for block, mixer in (("block_0", "attn"), ("block_1", "lin")):
        for name in ("q", "k", "v", "gate", "out"):
            assert res[block][mixer][name]["kernel"].dtype == jnp.bfloat16
        assert res[block][mixer]["q_norm"]["scale"].dtype == jnp.float32
    assert res["lm_head"]["kernel"].dtype == jnp.float32
    assert res["embed"]["embedding"].dtype == jnp.bfloat16


# -- system = reference ---------------------------------------------------------------


def test_the_system_is_the_reference_in_float32(cfg, params, reference, tokens):
    want = reference(params, tokens)
    got = TransformerLM(cfg).apply({"params": params}, tokens)
    assert got.shape == (2, 100, VOCAB)
    assert worst(got, want) < F32_TOL
    # seeded logits keep the scale the checker's tolerance was set for
    assert 0.6 < float(want.std()) < 1.3


def test_the_three_scale_constants_each_matter(cfg, params, reference, tokens):
    want = reference(params, tokens)
    for field, value in (("scale_emb", 1.0), ("scale_depth", 0.0),
                         ("dim_model_base", 0), ("scale_depth_layers", 0)):
        moved = dataclasses.replace(cfg, **{field: value})
        got = TransformerLM(moved).apply({"params": params}, tokens)
        assert worst(got, want) > 100 * F32_TOL, field


def test_a_selection_over_fewer_blocks_is_another_model(cfg, params, reference,
                                                        tokens):
    ref = reference.module
    fewer = dict(CONFIG, sparse_config=dict(SPARSE, topk=3))
    got = TransformerLM(cfg).apply({"params": params}, tokens)
    assert worst(got, ref.forward(params, tokens, fewer)) > 20 * F32_TOL
    # ... while rows that reach at most topk blocks do not see the difference
    assert worst(got[:, :3 * 8], ref.forward(params, tokens, fewer)[:, :3 * 8]) \
        < F32_TOL


def test_the_chosen_blocks_are_the_references(reference):
    """`select_blocks` against the reference's `chosen_blocks` on random
    queries and keys, whose scores separate: the same blocks for every
    query row and KV head."""
    ref = reference.module
    L, H, Hkv, D = 120, 4, 2, 16
    kq, kk = jax.random.split(jax.random.PRNGKey(9))
    q = 3 * jax.random.normal(kq, (L, H, D), jnp.float32)
    k = jax.random.normal(kk, (MAX_LEN, Hkv, D), jnp.float32)
    k_cmp = _compressed_keys(k.reshape(1, MAX_LEN, Hkv * D), 2)
    t = jnp.arange(L)
    ids, n = da.select_blocks(q[None], k_cmp, t[None], block=8, stride=2, topk=4,
                              init_blocks=1, window=16)
    got = np.zeros((L, Hkv, MAX_LEN // 8), bool)
    ids, n = np.asarray(ids[0]), np.asarray(n[0])
    for l in range(L):
        for h in range(Hkv):
            got[l, h, ids[l, h, :n[l, h]]] = True
    want = np.asarray(ref.chosen_blocks(q, k, t, SPARSE))
    assert (got.sum(-1) == np.minimum(np.arange(L) // 8 + 1, 4)[:, None]).all()
    np.testing.assert_array_equal(got, want)


# -- prefill, then decoding through the slot cache ------------------------------------


def _fix_cursor(cache, n):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full_like(leaf, n)
        if path[-1].key == "idx" else leaf, cache)


def _decode_model(cfg):
    return TransformerLM(dataclasses.replace(cfg, decode=True))


def _empty(model, batch):
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((batch, 1), jnp.int32))["cache"]


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_prefill_then_decode_is_the_references_full_forward(
        cfg, params, reference, tokens, mode, monkeypatch):
    monkeypatch.setenv("KFT_PALLAS", mode)
    model = _decode_model(cfg)
    want = reference(params, tokens)
    n0 = 64
    logits, st = model.apply({"params": params, "cache": _empty(model, 2)},
                             tokens[:, :n0], mutable=["cache"])
    assert worst(logits, want[:, :n0]) < F32_TOL
    step = jax.jit(lambda c, t: model.apply(
        {"params": params, "cache": c}, t, mutable=["cache"]))
    cache, errs = st["cache"], []
    for t in range(n0, 100):
        logits, st = step(cache, tokens[:, t:t + 1])
        cache = st["cache"]
        errs.append(worst(logits[:, 0], want[:, t]))
    assert max(errs) < F32_TOL
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if path[-1].key == "idx":
            assert np.asarray(leaf).tolist() == [100, 100]


@pytest.mark.parametrize("n_real", [5, 37, BUCKET])
def test_a_padded_bucket_is_the_unpadded_prompt(cfg, params, reference, tokens,
                                                n_real):
    """A prefill bucket tells the model how many of its tokens are real
    (`n_new`): the state and the compressed keys it hands on are those of
    the prompt alone, so the next tokens decode as the reference's."""
    model = _decode_model(cfg)
    want = reference(params, tokens[:1])
    padded = jnp.zeros((1, BUCKET), jnp.int32).at[:, :n_real].set(
        tokens[:1, :n_real]).at[:, n_real:].set(7)
    logits, st = model.apply(
        {"params": params, "cache": _empty(model, 1)}, padded,
        n_new=jnp.asarray([n_real]), mutable=["cache"])
    assert worst(logits[:, :n_real], want[:, :n_real]) < F32_TOL
    cache = _fix_cursor(st["cache"], n_real)
    for t in range(n_real, n_real + 20):
        logits, st = model.apply({"params": params, "cache": cache},
                                 tokens[:1, t:t + 1], mutable=["cache"])
        cache = st["cache"]
        assert worst(logits[:, 0], want[:, t]) < F32_TOL, t
    # without the count the padding runs through the recurrence
    _, st = model.apply({"params": params, "cache": _empty(model, 1)}, padded,
                        mutable=["cache"])
    if n_real < BUCKET:
        moved = worst(st["cache"]["block_1"]["lin"]["lin_state"],
                      cache["block_1"]["lin"]["lin_state"])
        assert moved > 100 * F32_TOL


def test_a_call_of_l_tokens_is_l_chained_calls(cfg, params, tokens):
    """The chunked recurrence and the token-by-token one, the bucket's
    compressed keys and the step's: one state, one cache."""
    model = _decode_model(cfg)
    whole, st_whole = model.apply(
        {"params": params, "cache": _empty(model, 2)}, tokens[:, :48],
        mutable=["cache"])
    cache, rows = _empty(model, 2), []
    for t in range(48):
        logits, st = model.apply({"params": params, "cache": cache},
                                 tokens[:, t:t + 1], mutable=["cache"])
        cache = st["cache"]
        rows.append(logits[:, 0])
    assert worst(jnp.stack(rows, 1), whole) < F32_TOL
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(cache),
                                 jax.tree.leaves(st_whole["cache"])):
        name = path[-1].key
        if name == "k_cmp":      # entries whose last row has landed: (48 - 4) / 2 + 1
            got, want = got[:, :23], want[:, :23]
        assert worst(got.astype(jnp.float32), want.astype(jnp.float32)) \
            < F32_TOL, name


def test_a_free_slots_state_and_compressed_keys_are_unmoved(cfg, params, tokens):
    model = _decode_model(cfg)
    _, st = model.apply({"params": params, "cache": _empty(model, 2)},
                        tokens[:, :40], mutable=["cache"])
    before = st["cache"]
    live = jnp.asarray([True, False])
    # position 41 completes a compressed key ((41 - 3) % 2 == 0): the live
    # slot writes it, the free one does not
    for t in (40, 41):
        _, st = model.apply({"params": params, "cache": st["cache"]},
                            tokens[:, t:t + 1], live=live, mutable=["cache"])
    kept = STATE_LEAVES + STRIDED_LEAVES + ("idx", "overflowed")
    seen = set()
    for (path, was), now in zip(jax.tree_util.tree_leaves_with_path(before),
                                jax.tree.leaves(st["cache"])):
        name = path[-1].key
        if name in kept:
            seen.add(name)
            np.testing.assert_array_equal(np.asarray(now[1]), np.asarray(was[1]))
            assert name == "overflowed" or worst(now[0], was[0]) > 0, name
    assert {"lin_state", "k_cmp", "idx"} <= seen


def test_a_bf16_state_fails(cfg, params, reference, tokens):
    """The matrix state rounded to bf16 between steps leaves the reference
    by far more than the float32 path's tolerance."""
    model = _decode_model(cfg)
    want = reference(params, tokens)
    _, st = model.apply({"params": params, "cache": _empty(model, 2)},
                        tokens[:, :64], mutable=["cache"])

    def rounded(cache):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf.astype(jnp.bfloat16).astype(leaf.dtype)
            if path[-1].key == "lin_state" else leaf, cache)

    cache, errs = st["cache"], []
    for t in range(64, 80):
        logits, st = model.apply({"params": params, "cache": rounded(cache)},
                                 tokens[:, t:t + 1], mutable=["cache"])
        cache = st["cache"]
        errs.append(worst(logits[:, 0], want[:, t]))
    assert max(errs) > 5 * F32_TOL


def test_the_cache_declares_its_leaves(cfg):
    from kungfu_tpu.serving.slots import cache_bytes, has_state

    model = _decode_model(cfg)
    cache = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                           jnp.zeros((SLOTS, 1), jnp.int32))["cache"]
    sparse, lin = cache["block_0"]["attn"], cache["block_1"]["lin"]
    assert sorted(sparse) == ["cached_k", "cached_v", "idx", "k_cmp", "overflowed"]
    assert sorted(lin) == ["idx", "lin_state", "overflowed"]
    assert sparse["cached_k"].shape == (SLOTS, MAX_LEN, 32)
    assert sparse["k_cmp"].shape == (SLOTS, MAX_LEN // 2, 32)
    assert lin["lin_state"].shape == (SLOTS, 4, 16, 16)
    assert lin["lin_state"].dtype == jnp.float32
    assert has_state(cache)
    held = cache_bytes(cache)
    assert held["state"] == 2 * SLOTS * 4 * 16 * 16 * 4
    # K, V and the compressed keys count as rows (cursors beside them)
    assert held["rows"] >= 2 * SLOTS * (2 * MAX_LEN + MAX_LEN // 2) * 32 * 4


# -- the serving engine ---------------------------------------------------------------


def _engine(cfg, params, **kw):
    from kungfu_tpu.serving import ServingEngine

    return ServingEngine(cfg, params, slots=2, prefill_buckets=(16, 64, MAX_LEN),
                         **kw)


def test_the_engine_serves_it_end_to_end_with_two_slots(cfg, params, reference):
    from kungfu_tpu.serving.request import Request

    eng = _engine(cfg, params)
    prompts = [list(range(3, 3 + n)) for n in (50, 9, 70)]
    handles = [eng.submit(Request(req_id=f"r{i}", prompt=p, max_new_tokens=12))
               for i, p in enumerate(prompts)]
    eng.run_until_idle(timeout_s=300)
    for handle, prompt in zip(handles, prompts):
        result = handle.wait(0)
        assert result is not None and result.status == "ok"
        toks = np.asarray(result.tokens)
        assert len(toks) == len(prompt) + 12
        logits = np.asarray(reference(params, jnp.asarray(toks[None])))[0]
        rows = logits[len(prompt) - 1:len(toks) - 1]
        served = toks[len(prompt):]
        deficit = rows.max(-1) - rows[np.arange(12), served]
        assert deficit.max() < F32_TOL, deficit
    stats = eng.stats()
    assert stats["scan_tokens"]["prefill"] == 50 + 9 + 70
    assert stats["scan_tokens"]["decode"] == stats["decode_rows"]["live"]
    sparse = stats["sparse_rows"]
    assert 0 < sparse["fetched"] and 0 < sparse["kernels"]
    # four blocks of eight rows at most, of up to 82 rows written
    assert sparse["fetched"] <= 32 * stats["decode_rows"]["live"]
    assert sparse["fetched"] < sparse["written"] + 8 * stats["decode_rows"]["live"]
    assert stats["cache_bytes"]["state"] > 0
    # the dense decode-attention kernel is not what runs: it reads nothing
    assert stats["decode_attn_rows"]["fetched"] == stats["decode_attn_rows"]["cache"]


def test_the_engine_counts_sparse_rows_as_the_selector_reads(cfg, params):
    eng = _engine(cfg, params)
    eng._cursor = np.asarray([41, 7], np.int64)
    eng._count_step(np.asarray([40, 7], np.int64), 1, np.asarray([True, False]))
    # position 40: 41 rows held, blocks 0..5 reachable of which 4 are read,
    # compressed keys 0..18 end at or before it ((40 - 3) // 2 + 1)
    assert eng.sparse_rows() == {"written": 41, "fetched": 32, "kernels": 19}
    assert eng.scan_tokens()["decode"] == 1
    from kungfu_tpu.monitor.counters import METRIC_HELP

    assert "compressed keys" in METRIC_HELP["kft_serve_sparse_rows_total"]


def test_the_engine_refuses_what_cuts_a_cache_by_position(cfg, params):
    from kungfu_tpu.serving.prefix import PrefixCache
    from kungfu_tpu.serving.request import Request
    from kungfu_tpu.serving.spec import SpecDecoder

    with pytest.raises(ValueError, match="recurrent state"):
        _engine(cfg, params, prefix_cache=PrefixCache(budget_bytes=1 << 20))
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(cfg, params, spec=SpecDecoder(cfg, params, slots=2, k=3))
    eng = _engine(cfg, params)
    req = Request(req_id="shipped", prompt=[1, 2, 3], max_new_tokens=4)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.submit_prefilled(req, {"n": 3}, {})
    with pytest.raises(ValueError, match="recurrent state"):
        eng.prefill_only(req)


def test_a_block_selected_model_alone_is_refused_the_same_things(cfg):
    """No recurrent layer, yet compressed keys at a stride of their own:
    the row helpers slice every leaf at one length."""
    from kungfu_tpu.serving.prefix import PrefixCache
    from kungfu_tpu.serving.slots import extract_rows

    only = dataclasses.replace(cfg, n_layers=2,
                               mixer_types=("minicpm4", "minicpm4"))
    assert only.keeps_state
    params = seed_params(only, 0)
    with pytest.raises(ValueError, match="stride of their own"):
        _engine(only, params, prefix_cache=PrefixCache(budget_bytes=1 << 20))
    with pytest.raises(ValueError, match="stride of their own"):
        extract_rows(_engine(only, params)._small_cache0, 4)


@pytest.mark.parametrize("flags", [
    ["--prefix-cache", "on"], ["--spec-draft", "same"], ["--prefill-ranks", "1"]])
def test_the_supervisor_refuses_the_flags_before_it_spawns(flags, capsys):
    from kungfu_tpu.serving.__main__ import main

    with pytest.raises(SystemExit) as exit_:
        main(["-np", "2", "--platform", "cpu", "--model-json",
              json.dumps(PROGRAM), *flags])
    assert exit_.value.code == 2
    assert "recurrent state" in capsys.readouterr().err


@pytest.mark.parametrize("flag, value", [
    ("prefix_cache", "on"), ("spec_draft", "same"), ("tier", "prefill")])
def test_the_worker_refuses_the_flags_at_boot_with_the_reason(flag, value):
    import argparse

    from kungfu_tpu.serving.worker import ServingWorker

    args = dict(host="127.0.0.1", port=0, launch_rank=0, incarnation=0,
                config_server="", preset="tiny", model_json=json.dumps(PROGRAM),
                tier="", prefix_cache="auto", spec_draft="", spec_k=4, slots=2,
                queue_capacity=8, seed=3, weights_file="", warm_ship_s=0.15,
                buddy_timeout_s=3.0, request_timeout_s=30.0)
    with pytest.raises(SystemExit, match="recurrent state"):
        ServingWorker(argparse.Namespace(**dict(args, **{flag: value})))
