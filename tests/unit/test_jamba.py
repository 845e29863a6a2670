"""State-space mixers beside position-free multi-query attention
(models/transformer.py `Mamba`, `Block(ssm=True)`, `pos_table`), the state
leaves of the slot cache (serving/slots.py `STATE_LEAVES`) and the engine
over them, against the plain reference `benchmark/references/jamba.py`, at
a Jamba-shaped tiny size on the CPU: hidden 64, inner 128, state 16, step
rank 8, 4 taps, 3 layers of which layer 1 is attention (period 3,
offset 1) with 4 query heads on 1 KV head, SwiGLU 128, a tied head, seeded
weights, float32."""
import argparse
import dataclasses
import functools
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from benchmark.lib.configs import load_reference, program_fields, transformer_config
from kungfu_tpu.models.transformer import TransformerLM, generate, resident_params
from kungfu_tpu.serving import Request, ServingEngine
from kungfu_tpu.serving.slots import (
    STATE_LEAVES, cache_bytes, extract_rows, extract_slot_rows, has_state,
    reset_slot, set_cursors, warm_small_cache, write_slot)

pytestmark = pytest.mark.serving

VOCAB, LAYERS, INNER, STATE = 96, 3, 128, 16
CONFIG = {
    "vocab_size": VOCAB, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": LAYERS, "num_attention_heads": 4,
    "num_key_value_heads": 1, "attn_layer_period": 3, "attn_layer_offset": 1,
    "mamba_d_state": STATE, "mamba_dt_rank": 8, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_conv_bias": True, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 64, "tie_word_embeddings": True,
    "reference": "jamba",
    "program": {"rope": False, "pos_table": False, "norm": "rms",
                "norm_eps": 1e-6, "ffn": "swiglu", "attention": "full",
                "dtype": "float32", "mamba_d_state": STATE,
                "mamba_dt_rank": 8, "mamba_d_conv": 4, "mamba_expand": 2,
                "mamba_conv_bias": True, "attn_layer_period": 3,
                "attn_layer_offset": 1, "embed_init_std": 0.15},
}
REF = load_reference(CONFIG)
MAMBA_LAYERS = [i for i in range(LAYERS) if i % 3 != 1]

#: float32 system against the float32 reference: the same sums in the same
#: order but for the convolution (the program adds the window's rows to the
#: call's, the reference pads with zeros) and the reduction over the state
#: (a sum over an axis against an einsum).  Logits of standard deviation
#: 1.2 differ by 4.9e-6 at most over the cases below (a few float32
#: roundings through three layers).  A leaf of one mixer off by a tenth
#: moves them by 1e-3 or more, and a scan that ran through a bucket's
#: padding moves the next token's logits by 2.6.
F32_TOL = 3e-5


def build(seed=1, **program):
    return _build(seed, tuple(sorted(program.items())))


@functools.lru_cache(maxsize=None)
def _build(seed, program):
    config = dict(CONFIG, program=dict(CONFIG["program"], **dict(program)))
    cfg = transformer_config(config)
    model = TransformerLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32))["params"])
    # matrices six times the seeded 0.02, so that at this width every
    # sublayer weighs in the residual stream beside the token's own
    # embedding (the tied head otherwise answers every token with itself)
    # and a wrong state shows in the logits; norm scales off 1, so that a
    # norm taken for another one shows too
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 6.0 * a if path[-1].key == "kernel" else a, params)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 50), 256))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.2 * jax.random.normal(next(keys), a.shape)
        if path[-1].key == "scale" else a, params)
    return config, cfg, model, params


def tokens(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, VOCAB, shape),
                       jnp.int32)


_reference = jax.jit(lambda p, t: REF.forward(p, t, CONFIG))


def reference(params, toks):
    return _reference(params, toks)


def worst(got, want):
    return float(jnp.abs(jnp.asarray(got) - jnp.asarray(want)).max())


# -- the whole sequence ----------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2])
def test_forward_matches_reference(seed):
    config, cfg, model, params = build(seed)
    toks = tokens((2, 24), seed)
    got = jax.jit(model.apply)({"params": params}, toks)
    assert got.shape == (2, 24, VOCAB)
    assert worst(got, reference(params, toks)) < F32_TOL
    assert float(jnp.std(got)) > 0.5          # logits worth comparing
    # attention where i % 3 == 1, the mixer elsewhere; no position table
    assert [i for i in range(LAYERS) if cfg.layer_is_ssm(i)] == MAMBA_LAYERS
    for i in range(LAYERS):
        kinds = set(params[f"block_{i}"]) - {"ln1", "ln2", "mlp"}
        assert kinds == ({"mamba"} if i in MAMBA_LAYERS else {"attn"})
    assert "pos_embed" not in params and "lm_head" not in params
    mixer = params["block_0"]["mamba"]
    assert mixer["A_log"].shape == (STATE, INNER)      # channels on the lanes
    np.testing.assert_allclose(np.exp(mixer["A_log"][:, 0]),
                               np.arange(1, STATE + 1), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    assert np.all(np.asarray(mixer["D"]) == 1.0)


MAMBA_LEAVES = ("A_log", "D", "conv_w", "conv_b", "dt_bias", "in_proj/kernel",
                "x_proj/kernel", "dt_proj/kernel", "out_proj/kernel",
                "dt_norm/scale", "b_norm/scale", "c_norm/scale")


@pytest.mark.parametrize("leaf", MAMBA_LEAVES)
def test_every_leaf_of_the_mixer_weighs_in_the_comparison(leaf):
    """The reference on weights whose one leaf of one mixer is off by a
    tenth no longer agrees: the comparison reads every leaf."""
    config, cfg, model, params = build()
    toks = tokens((1, 24), 3)
    got = jax.jit(model.apply)({"params": params}, toks)

    def off(path, a):
        name = "/".join(k.key for k in path)
        if name != "block_2/mamba/" + leaf:
            return a
        # a tenth of its neighbour's row added: no norm after it undoes
        # that, as the three inner norms undo a scaling of W_x
        return 1.05 * a + 0.1 * jnp.roll(a, 1, axis=0)

    moved = jax.tree_util.tree_map_with_path(off, params)
    assert worst(got, reference(params, toks)) < F32_TOL
    assert worst(got, reference(moved, toks)) > 30 * F32_TOL


def test_a_model_without_the_fields_is_the_model_it_was():
    """The new fields default to what was there: attention in every layer,
    the learned table when rope is off."""
    from kungfu_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=32, d_model=32, n_layers=2, n_heads=4,
                            d_ff=32, max_len=16)
    assert not any(cfg.layer_is_ssm(i) for i in range(2)) and cfg.pos_table
    params = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))["params"]
    assert "pos_embed" in params and "attn" in params["block_0"]
    with pytest.raises(AssertionError, match="rope positions, or none"):
        dataclasses.replace(cfg, decode=True)
    with pytest.raises(AssertionError, match="attention layer a period"):
        dataclasses.replace(cfg, mamba_d_state=16, mamba_dt_rank=4)


# -- prefill, then decoding through the slot cache -------------------------------------

BUCKET, SLOTS = 16, 3


def _fix_cursor(cache, n):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full_like(leaf, n)
        if path[-1].key == "idx" else leaf, cache)


def _state_of(cache, slot):
    return [np.asarray(leaf[slot]) for path, leaf
            in jax.tree_util.tree_leaves_with_path(cache)
            if path[-1].key in STATE_LEAVES]


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("n_new", [1, 2, 3, BUCKET - 1, BUCKET])
def test_prefill_then_decode_through_the_slot_cache(n_new, mode, monkeypatch):
    """A prompt of n_new tokens right-padded to the bucket, prefilled with
    `n_new` handed down, grafted into slot 1 of three, then decoded token
    by token beside a free slot and a busy one: every step's logits are the
    reference's for the whole sequence, whatever the bucket's padding holds
    (prompts shorter than the convolution's window included), and the free
    slot's state stands still."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    config, cfg, _, params = build()
    dcfg = dataclasses.replace(cfg, decode=True)
    model = TransformerLM(dcfg)
    steps = 6
    seq = tokens((1, n_new + steps), 10 + n_new)
    other = tokens((1, 4 + steps), 99)
    want = reference(params, seq)

    def zeros(batch):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                jnp.zeros((batch, 1), jnp.int32))["cache"]
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def prefill(prompt):
        n = prompt.shape[1]
        # the padding is not zeros: a recurrence that ran through it shows
        padded = jnp.concatenate(
            [prompt, tokens((1, BUCKET - n), 7) + 1], axis=1) % VOCAB
        logits, st = jax.jit(
            lambda p, c, t, k: model.apply(
                {"params": p, "cache": c}, t, mutable=["cache"], n_new=k)
        )(params, zeros(1), padded, jnp.full((1,), n, jnp.int32))
        return logits[0, :n], _fix_cursor(st["cache"], n)

    logits, small = prefill(seq[:, :n_new])
    assert worst(logits, want[0, :n_new]) < F32_TOL
    big = zeros(SLOTS)
    # slot 0 stays free and holds a state of its own that no step may touch
    big = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf.at[0].set(3.0)
        if path[-1].key in STATE_LEAVES else leaf, big)
    big = write_slot(big, small, 1)
    big = write_slot(big, prefill(other[:, :4])[1], 2)
    before = _state_of(big, 0)
    step = jax.jit(lambda p, c, t, live: model.apply(
        {"params": p, "cache": c}, t, live=live, mutable=["cache"]))
    live = jnp.asarray([False, True, True])
    for j in range(steps):
        toks = jnp.stack([jnp.zeros(1, jnp.int32), seq[0, n_new + j][None],
                          other[0, 4 + j][None]])
        logits, st = step(params, big, toks, live)
        big = st["cache"]
        assert worst(logits[1, 0], want[0, n_new + j]) < F32_TOL, j
    for a, b in zip(before, _state_of(big, 0)):
        np.testing.assert_array_equal(a, b)
    assert worst(logits[2, 0], reference(params, other)[0, -1]) < F32_TOL


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_a_decode_call_of_l_tokens_is_l_chained_calls(mode, monkeypatch):
    """Four tokens in one call against four one-token calls: the same
    logits at every position and the same state and window after."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    _, cfg, _, params = build()
    model = TransformerLM(dataclasses.replace(cfg, decode=True))
    cache0 = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((2, 1), jnp.int32))["cache"])
    apply = jax.jit(lambda c, t: model.apply(
        {"params": params, "cache": c}, t, mutable=["cache"]))
    toks = tokens((2, 7), 5)
    _, st = apply(cache0, toks[:, :3])
    whole, st_whole = apply(st["cache"], toks[:, 3:])
    cache, one = st["cache"], []
    for j in range(3, 7):
        logits, st = apply(cache, toks[:, j:j + 1])
        cache = st["cache"]
        one.append(logits[:, 0])
    assert worst(whole, jnp.stack(one, axis=1)) < F32_TOL
    for a, b in zip(jax.tree.leaves(st_whole["cache"]), jax.tree.leaves(cache)):
        assert worst(a.astype(jnp.float32), b.astype(jnp.float32)) < F32_TOL


# -- the slot cache's helpers -----------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _alone_engine():
    return _engine(slots=1)


def _engine(slots=2, buckets=(8, 16, 32), **kw):
    _, cfg, _, params = build()
    return ServingEngine(cfg, params, slots=slots, prefill_buckets=buckets, **kw)


def test_the_cache_names_its_state_leaves_and_the_row_helpers_refuse_them():
    eng = _engine()
    names = {path[-1].key for path, _ in
             jax.tree_util.tree_leaves_with_path(eng.cache)}
    # this model's two of the declared state leaves (`lin_state` is a
    # lightning layer's: tests/unit/test_minicpm_sala.py)
    assert names == {"cached_k", "cached_v", "idx", "overflowed",
                     "ssm_state", "conv_state"} <= {
                         "cached_k", "cached_v", "idx", "overflowed", *STATE_LEAVES}
    assert has_state(eng.cache) and has_state(eng._small_cache0)
    state = len(MAMBA_LAYERS) * 2 * (STATE * INNER * 4 + 3 * INNER * 4)
    rows = 2 * (2 * 64 * 16 * 4 + 4 + 1)
    assert eng.cache_bytes == cache_bytes(eng.cache) == {
        "rows": rows, "state": state}
    assert eng.stats()["cache_bytes"] == eng.cache_bytes
    for call in (lambda: extract_rows(eng._small_cache0, 3),
                 lambda: extract_slot_rows(eng.cache, 0, 3),
                 lambda: warm_small_cache(eng._small_cache0, {}, 3),
                 lambda: set_cursors(eng.cache, jnp.zeros(2, jnp.int32))):
        with pytest.raises(ValueError, match="recurrent state"):
            call()
    # what stays: a whole slot replaced, a cursor reset with the state left
    marked = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 2 if path[-1].key in STATE_LEAVES else leaf,
        eng._small_cache0)
    big = write_slot(eng.cache, marked, 1)
    assert all(np.all(s == 2) for s in _state_of(big, 1))
    assert all(np.all(s == 0) for s in _state_of(big, 0))
    big = reset_slot(big, 1)
    assert all(np.all(s == 2) for s in _state_of(big, 1))


def test_a_cache_of_rows_alone_has_no_state():
    from kungfu_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=32, d_model=32, n_layers=1, n_heads=4,
                            d_ff=32, max_len=16, rope=True, dtype=jnp.float32)
    params = nn.meta.unbox(TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,))
    assert not has_state(eng.cache) and not eng._stateful
    assert eng.cache_bytes["state"] == 0 and eng.cache_bytes["rows"] > 0
    assert "scan_tokens" not in eng.stats()
    assert eng.scan_tokens() == {"prefill": 0, "decode": 0}
    assert extract_rows(eng._small_cache0, 2)      # still cut by position


# -- the engine --------------------------------------------------------------------------


def _prompts(lens, seed=4):
    rs = np.random.RandomState(seed)
    return [tuple(int(t) for t in rs.randint(0, VOCAB, n)) for n in lens]


@functools.lru_cache(maxsize=None)
def _alone(prompt, new):
    """What an engine serves for one request by itself."""
    eng = _alone_engine()
    pending = eng.submit(Request(prompt=prompt, max_new_tokens=new))
    eng.run_until_idle()
    return tuple(pending.result.tokens)


def _deficit(params, served, n_prompt):
    """The checker's number (benchmark/lib/serve_check.py): how far below
    the reference's maximum each served token's reference logit lies."""
    rows = np.asarray(reference(params, jnp.asarray(served)[None]))[0]
    rows = rows[n_prompt - 1:len(served) - 1]
    new = np.asarray(served[n_prompt:])
    return float((rows.max(-1) - rows[np.arange(len(new)), new]).max())


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_engine_serves_what_the_reference_chooses(mode, monkeypatch):
    """Seven requests over three slots, prompts on both sides of every
    bucket's end and shorter than the window: each served token is the
    reference's choice for the whole sequence (float32: no deficit beyond
    rounding), slots are released and admitted again, and the counters say
    what the scan walked."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    _, _, _, params = build()
    eng = _engine(slots=3)
    lens = [1, 2, 3, 8, 9, 16, 17]
    pend = [eng.submit(Request(prompt=p, max_new_tokens=6 + i))
            for i, p in enumerate(_prompts(lens))]
    eng.run_until_idle()
    seen = set()
    for n, p in zip(lens, pend):
        served = p.result.tokens
        assert p.result.status == "ok" and len(served) == n + p.request.max_new_tokens
        assert _deficit(params, served, n) < 1e-4
        seen.update(served[n:])
    assert len(seen) > 8            # no one token answering everything
    generated = sum(6 + i for i in range(7))
    assert eng.scan_tokens() == {"prefill": sum(lens),
                                 "decode": generated - len(lens)}
    assert eng.decode_rows()["live"] == generated - len(lens)
    assert eng.stats()["scan_tokens"] == eng.scan_tokens()
    assert eng.decode_steps()["ahead"] > eng.decode_steps()["synced"] > 0


def test_a_slot_admitted_again_serves_what_a_fresh_engine_serves():
    """The second tenant of a slot starts from the prefill's state, not
    from what the first left there."""
    eng = _engine(slots=1)
    first, second = _prompts([11, 5], seed=8)
    a = eng.submit(Request(prompt=first, max_new_tokens=9))
    b = eng.submit(Request(prompt=second, max_new_tokens=7))
    eng.run_until_idle()
    assert tuple(a.result.tokens) == _alone(first, 9)
    assert tuple(b.result.tokens) == _alone(second, 7)
    assert len(set(b.result.tokens[5:])) > 2


def test_the_loop_one_step_ahead_serves_what_the_synced_loop_serves():
    prompts = _prompts([4, 13, 7, 20])
    out = {}
    for ahead in (True, False):
        eng = _engine(slots=2)
        if not ahead:
            eng._decode_step = lambda e=eng: e._read_step(run_ahead=False)
        pend = [eng.submit(Request(prompt=p, max_new_tokens=10)) for p in prompts]
        eng.run_until_idle()
        out[ahead] = [tuple(p.result.tokens) for p in pend]
        assert (eng.decode_steps()["ahead"] > 0) == ahead
    assert out[True] == out[False]


def test_a_preempted_request_resumes_to_the_same_tokens():
    """No prefix cache holds the victim's rows (a state cannot be cut at a
    position): it resumes through a cold prefill of its folded tokens, to
    the tokens it would have been served undisturbed."""
    from kungfu_tpu.serving.tenancy import TenantRegistry, TenantSpec

    reg = TenantRegistry(specs={
        "bulk": TenantSpec(name="bulk", priority=0),
        "gold": TenantSpec(name="gold", priority=2)})
    eng = _engine(slots=1, tenants=reg)
    bulk, gold = _prompts([6, 3], seed=12)
    a = eng.submit(Request(prompt=bulk, max_new_tokens=12, tenant="bulk"))
    for _ in range(4):
        eng.step()
    b = eng.submit(Request(prompt=gold, max_new_tokens=5, tenant="gold"))
    eng.run_until_idle()
    assert eng.preemptions == 1 and eng.prefix is None
    assert tuple(a.result.tokens) == _alone(bulk, 12)
    assert tuple(b.result.tokens) == _alone(gold, 5)
    # the resume prefilled the prompt and what had been generated
    assert eng.scan_tokens()["prefill"] > len(bulk) + len(gold)


def test_generate_serves_what_the_engine_serves():
    _, cfg, _, params = build()
    prompt = _prompts([9], seed=2)[0]
    out = generate(cfg, params, jnp.asarray(prompt)[None], 8)
    assert tuple(int(t) for t in np.asarray(out)[0]) == _alone(prompt, 8)


# -- what a model with state refuses ----------------------------------------------------


@pytest.mark.parametrize("what", ["prefix_cache", "spec"])
def test_the_engine_refuses_what_cuts_a_cache_by_position(what):
    from kungfu_tpu.serving.prefix import PrefixCache

    with pytest.raises(ValueError, match="no prefix cache and no speculation"):
        _engine(**{what: PrefixCache(1 << 20) if what == "prefix_cache"
                   else object()})


def test_the_engine_refuses_shipped_rows_and_a_prefill_tier():
    eng = _engine()
    req = Request(prompt=(1, 2, 3), max_new_tokens=4)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.submit_prefilled(req, {"cursor": 3, "first_token": 1}, {})
    with pytest.raises(ValueError, match="recurrent state"):
        eng.prefill_only(req)
    assert not eng._grafts and not eng._pending


def _model_json():
    fields = program_fields(CONFIG)
    return json.dumps(fields)


def _worker_args(**over):
    base = dict(host="127.0.0.1", port=0, launch_rank=0, incarnation=0,
                config_server="", preset="tiny", model_json=_model_json(),
                tier="", prefix_cache="auto", spec_draft="", spec_k=4, slots=2,
                queue_capacity=8, seed=3, weights_file="", warm_ship_s=0.15,
                buddy_timeout_s=3.0, request_timeout_s=30.0)
    return argparse.Namespace(**dict(base, **over))


@pytest.mark.parametrize("flag, value", [
    ("prefix_cache", "on"), ("spec_draft", "same"), ("tier", "prefill"),
    ("tier", "decode")])
def test_the_worker_refuses_the_flags_at_boot_with_the_reason(flag, value):
    from kungfu_tpu.serving.worker import ServingWorker

    with pytest.raises(SystemExit, match="recurrent state"):
        ServingWorker(_worker_args(**{flag: value}))


@pytest.mark.parametrize("flags", [
    ["--prefix-cache", "on"], ["--spec-draft", "same"], ["--prefill-ranks", "1"]])
def test_the_supervisor_refuses_the_flags_before_it_spawns(flags, capsys):
    from kungfu_tpu.serving.__main__ import main

    with pytest.raises(SystemExit) as exit_:
        main(["-np", "2", "--platform", "cpu", "--model-json", _model_json(),
              *flags])
    assert exit_.value.code == 2
    assert "recurrent state" in capsys.readouterr().err


def test_the_worker_serves_with_no_prefix_cache_and_shows_its_counters(
        monkeypatch):
    """Under `--prefix-cache auto` with a budget in the environment the
    worker of a model with state builds none; `/metrics` carries the cache's
    bytes by kind and the tokens the scan walked, the same numbers as
    `stats()`; the resident tree keeps the mixer's projections in cfg.dtype
    and its other leaves in float32."""
    from kungfu_tpu.monitor import counters as C
    from kungfu_tpu.serving.worker import ServingWorker

    counters = C.Counters()
    monkeypatch.setattr(C, "counters_if_enabled", lambda: counters)
    monkeypatch.setenv("KFT_PREFIX_CACHE_MB", "64")
    over = dict(json.loads(_model_json()), dtype="bfloat16")
    w = ServingWorker(_worker_args(model_json=json.dumps(over)))
    eng = w.engine
    assert eng.prefix is None and eng.spec is None
    pending = eng.submit(Request(prompt=(5, 17, 42, 7, 9), max_new_tokens=6))
    eng.run_until_idle()
    assert len(pending.result.tokens) == 11
    text = counters.prometheus_text()
    assert "# TYPE kft_serve_cache_bytes gauge" in text
    assert "# TYPE kft_serve_scan_tokens_total counter" in text
    for kind, n in eng.cache_bytes.items():
        assert f'kft_serve_cache_bytes{{kind="{kind}"}} {n}' in text
    assert 'kft_serve_scan_tokens_total{kind="prefill"} 5' in text
    assert 'kft_serve_scan_tokens_total{kind="decode"} 5' in text
    mixer = eng.params["block_0"]["mamba"]
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj"):
        assert mixer[name]["kernel"].dtype == jnp.bfloat16
    for name in ("A_log", "D", "conv_w", "conv_b", "dt_bias"):
        assert mixer[name].dtype == jnp.float32
    for name in ("dt_norm", "b_norm", "c_norm"):
        assert mixer[name]["scale"].dtype == jnp.float32
    assert eng.params["embed"]["embedding"].dtype == jnp.float32   # tied
    state = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(eng.cache)
             if path[-1].key == "ssm_state"]
    assert state and all(s.dtype == jnp.float32 for s in state)


def test_bf16_serving_stays_within_the_checkers_reach():
    """The served path in bf16 (float32 state) against the float32
    reference, by the checker's number.  At this width and with matrices
    six times the seeded size a served token lies at most 0.016 below the
    reference's maximum (three requests, 36 tokens: a bf16 rounding moves a
    logit of standard deviation 1.2 by about 0.02, and only near-ties
    flip); a wrong state or window moves a logit by whole units (2.6 for
    the padded prefill above).  0.2, the benchmark checker's own limit,
    lies between."""
    config, cfg, _, params = build(dtype="bfloat16")
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16, 32))
    lens = [3, 12, 17]
    pend = [eng.submit(Request(prompt=p, max_new_tokens=12))
            for p in _prompts(lens, seed=6)]
    eng.run_until_idle()
    for n, p in zip(lens, pend):
        assert _deficit(resident_params(cfg, params), p.result.tokens, n) < 0.2


def test_float32_between_the_matmuls_brings_bf16_nearer_the_reference():
    """`fp32_activations`: the residual stream, the projections' outputs and
    the gates in float32, matmul operands and resident kernels bf16 as
    before.  A bf16 model's logits then lie nearer the float32
    reference's: by a quarter at this depth of three layers (0.0202 ->
    0.0154 RMS of logits of deviation 1.2), by three tenths at 14 layers of
    the published widths (PERF.md section 6, PR 42); what is left is the
    bf16 weights and the operand casts.  The parameter tree and the cache's dtypes are the
    same, and the default leaves every program as it was
    (tests/unit/test_parent_programs.py)."""
    toks = tokens((2, 24), 4)
    away = {}
    for wide in (False, True):
        _, cfg, model, params = build(dtype="bfloat16", fp32_activations=wide)
        resident = resident_params(cfg, params)
        got = jax.jit(model.apply)({"params": resident}, toks)
        assert got.dtype == jnp.float32
        away[wide] = float(jnp.sqrt(jnp.mean(
            (got - reference(params, toks)) ** 2)))
        assert resident["block_0"]["mamba"]["in_proj"]["kernel"].dtype \
            == jnp.bfloat16
    assert away[True] < 0.85 * away[False], away
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,))
    dtypes = {path[-1].key: leaf.dtype for path, leaf
              in jax.tree_util.tree_leaves_with_path(eng.cache)}
    assert dtypes["conv_state"] == dtypes["cached_k"] == jnp.bfloat16
    assert dtypes["ssm_state"] == jnp.float32
    p = eng.submit(Request(prompt=(3, 1, 4, 1, 5), max_new_tokens=6))
    eng.run_until_idle()
    assert _deficit(resident, p.result.tokens, 5) < 0.2
