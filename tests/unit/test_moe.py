"""Sparse experts (parallel/moe.py, ops/gmm.py) against the plain reference
`benchmark/references/olmoe.py`, at an OLMoE-shaped tiny size on the CPU:
d_model 64, 4 heads, 8 experts of width 32, 2 a token, 2 layers, QK-norm,
seeded weights."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from benchmark.lib.configs import load_reference, transformer_config
from kungfu_tpu.models.transformer import TransformerLM, lm_loss_with_aux
from kungfu_tpu.ops.gmm import grouped_matmul
from kungfu_tpu.parallel.moe import STATS, MoE, route, stats_families, stats_totals
from kungfu_tpu.serving import Request, ServingEngine

pytestmark = pytest.mark.serving

CONFIG = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "max_position_embeddings": 64,
    "tie_word_embeddings": False, "reference": "olmoe",
    "program": {"norm": "rms", "norm_eps": 1e-5, "ffn": "swiglu",
                "attention": "full", "dtype": "float32", "n_experts": 8,
                "experts_per_token": 2, "moe_every": 1, "qk_norm": True},
}
REF = load_reference(CONFIG)

#: float32 system against the float32 reference.  Both sum the same products
#: in another order (sorted rows against a dense [T, E, width] einsum), so
#: logits of size 0.6 differ by a few float32 roundings: 2e-7 measured.  A
#: dropped token, a renormalised gate (weights 0.13 -> 0.5) or a missing
#: QK-norm moves logits by 1e-2 to 1e-1; one expert computed in bf16 by 1e-3.
F32_TOL = 5e-6


def build(seed=1, **program):
    config = dict(CONFIG, program=dict(CONFIG["program"], **program))
    cfg = transformer_config(config)
    model = TransformerLM(cfg)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32))["params"])
    return config, cfg, model, params


def tokens(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 96, shape), jnp.int32)


@pytest.mark.parametrize("renorm", [False, True], ids=["as_published", "norm_topk_prob"])
def test_forward_matches_reference(renorm):
    config, cfg, model, params = build(norm_topk_prob=renorm)
    config["norm_topk_prob"] = renorm
    toks = tokens((2, 24))
    got = model.apply({"params": params}, toks)
    want = REF.forward(params, toks, config)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    # the tolerance tells the variants apart: the other gate rule, no QK-norm
    other = REF.forward(params, toks, dict(config, norm_topk_prob=not renorm))
    assert float(jnp.abs(got - other).max()) > 100 * F32_TOL
    plain = dataclasses.replace(cfg, qk_norm=False)
    no_norm = TransformerLM(plain).apply({"params": params}, toks)
    assert float(jnp.abs(no_norm - want).max()) > 100 * F32_TOL


def test_bf16_system_routing_flips_and_logit_error():
    """The system in bf16 against the float32 reference: how often a (token,
    layer) pair picks another expert set, and what that does to the logits.
    Measured here (CPU, this size, 4 x 48 tokens, 4 seeds of weights and
    tokens): 0.26-1.3% of the 384 pairs differ; the largest logit error is
    0.0034-0.0042 over tokens with no flipped layer and 0.0062-0.0097 over
    those with one, of logits up to 0.60-0.66: a swap costs about twice
    bf16's own error, because the two experts a rounding apart carry nearly
    the same gate weight.  The limits below are three times those readings.
    On the chip at the published widths: PERF.md section 6, PR 25."""
    config, cfg, _, params = build()
    model = TransformerLM(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    toks = tokens((4, 48), seed=3)
    got, state = model.apply({"params": params}, toks, mutable=["intermediates"])
    want, chosen, _, _ = REF.forward_with_routing(params, toks, config)
    flipped = np.zeros((2, 4, 48), bool)
    for layer in range(2):
        mine = np.asarray(
            state["intermediates"][f"block_{layer}"]["moe"]["moe_experts"][0])
        sets = np.zeros((4, 48, 8), bool)
        np.put_along_axis(sets, mine, True, axis=-1)
        flipped[layer] = (sets != np.asarray(chosen[layer])).any(-1)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want)).max(-1)
    token_flipped = flipped.any(0)
    assert flipped.mean() < 0.04              # a rounding apart: rare
    assert err[~token_flipped].max() < 0.0126  # bf16's own error
    assert err.max() < 0.029                  # a swapped expert: twice that, no more


def engine_logits(eng, prompts, steps):
    """Prefill each prompt into its slot, then `steps` decode steps fed the
    reference continuation `cont`; the logits each call returned."""
    from kungfu_tpu.serving.slots import write_slot

    out = {s: [] for s in range(len(prompts))}
    cache = eng.cache
    for slot, (prompt, _) in enumerate(prompts):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :len(prompt)] = prompt
        _, last, small = eng._prefill(eng.params, eng._small_cache0,
                                      jnp.asarray(padded), len(prompt), len(prompt))
        cache = write_slot(cache, small, slot)
        out[slot].append(np.asarray(last))
    counters = eng._dev_counters
    for t in range(steps):
        toks = jnp.asarray([[cont[t]] for _, cont in prompts], jnp.int32)
        _, logits, cache, counters = eng._decode(eng.params, cache, counters, toks)
        for slot in out:
            out[slot].append(np.asarray(logits)[slot])
    return out, counters


def test_engine_prefill_then_decode_matches_reference_logits():
    """Two slots at different cursors through the slot cache, logits against
    the reference's full forward over prompt + continuation."""
    config, cfg, _, params = build()
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    rs = np.random.RandomState(5)
    prompts = [(rs.randint(0, 96, 11).tolist(), rs.randint(0, 96, 6).tolist()),
               (rs.randint(0, 96, 5).tolist(), rs.randint(0, 96, 6).tolist())]
    got, counters = engine_logits(eng, prompts, steps=6)
    for slot, (prompt, cont) in enumerate(prompts):
        full = jnp.asarray([prompt + cont], jnp.int32)
        want = np.asarray(REF.forward(params, full, config))[0]
        for t, logits in enumerate(got[slot]):
            np.testing.assert_allclose(logits, want[len(prompt) - 1 + t],
                                       atol=F32_TOL, rtol=0)
    # the device counters counted the 6 decode steps of 2 rows, not the prefills
    totals = stats_totals(jax.device_get(counters)[STATS])
    assert totals["layer_calls"] == 6 * 2
    assert totals["assignments"].shape == (2, 8)
    assert totals["assignments"].sum() == 6 * 2 * 2 * 2  # steps, layers, rows, k
    assert 2 * 12 <= totals["experts_hit"] <= 4 * 12
    fam = stats_families(jax.device_get(counters)[STATS])
    assert fam["kft_moe_decode_layer_calls_total"][""] == 12
    assert len(fam["kft_moe_assignments_total"]) == 16


def test_engine_serves_requests_and_reports_counters():
    config, cfg, _, params = build()
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    pend = [eng.submit(Request(prompt=(3, 1, 4, 1, 5), max_new_tokens=5)),
            eng.submit(Request(prompt=(9, 2, 6), max_new_tokens=7))]
    eng.run_until_idle()
    for p in pend:
        assert p.result.status == "ok"
        toks = np.asarray(p.result.tokens)
        ref_logits = np.asarray(REF.forward(params, jnp.asarray(toks[None]), config))[0]
        n = len(p.request.prompt)
        rows = ref_logits[n - 1:len(toks) - 1]
        deficit = rows.max(-1) - rows[np.arange(len(toks) - n), toks[n:]]
        assert deficit.max() < F32_TOL  # the served token is the reference's argmax
    totals = stats_totals(eng.device_counters()[STATS])
    assert totals["layer_calls"] == 2 * 6  # 6 decode steps (the longer answer), 2 layers


def test_verify_k_equals_chained_calls():
    """The engine's verify-k contract with experts: one [slots, k] call is k
    chained [slots, 1] calls, logits and cache."""
    config, cfg, _, params = build()
    dcfg = dataclasses.replace(cfg, decode=True)
    model = TransformerLM(dcfg)
    B, k = 2, 4
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((B, 1), jnp.int32))["cache"])
    toks = tokens((B, 3 + k), seed=9)
    _, st = model.apply({"params": params, "cache": cache}, toks[:, :3],
                        mutable=["cache"])
    wide, st_wide = model.apply({"params": params, "cache": st["cache"]},
                                toks[:, 3:], mutable=["cache"])
    chained, c = [], st["cache"]
    for j in range(k):
        lg, stj = model.apply({"params": params, "cache": c},
                              toks[:, 3 + j:4 + j], mutable=["cache"])
        chained.append(lg[:, 0])
        c = stj["cache"]
    np.testing.assert_allclose(np.asarray(wide), np.stack(chained, 1),
                               atol=F32_TOL, rtol=0)
    for a, b in zip(jax.tree.leaves(st_wide["cache"]), jax.tree.leaves(c)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=F32_TOL)


def test_loss_and_gradients_match_reference():
    config, cfg, model, params = build()
    toks = tokens((2, 20), seed=4)
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss_with_aux(model, p, toks))(params)
    want_loss, want_grads = REF.loss_and_grads(params, toks, config)
    # a loss of 4.6 and gradients up to 2e-2, float32 both sides
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        np.testing.assert_allclose(np.asarray(g), np.asarray(want[path]),
                                   atol=2e-6, rtol=1e-4, err_msg=str(path))
    # the router learns from both auxiliary losses: without them it moves
    bare = jax.grad(lambda p: lm_loss_with_aux(
        model, p, toks, aux_weight=0.0, router_z_weight=0.0))(params)
    r = lambda g: np.asarray(g["block_0"]["moe"]["router"])  # noqa: E731
    assert np.abs(r(grads) - r(bare)).max() > 1e-6


def moe_layer(E=4, k=2, width=16, d=8):
    cfg = transformer_config(dict(CONFIG, hidden_size=d, intermediate_size=width,
                                  num_attention_heads=2, num_key_value_heads=2,
                                  program=dict(CONFIG["program"], n_experts=E,
                                               experts_per_token=k)))
    layer = MoE(cfg)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 6, d), jnp.float32)
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(0), x)["params"])
    config = {"num_experts": E, "num_experts_per_tok": k}
    return layer, params, x, config


@pytest.mark.parametrize("case", ["all_to_one_expert", "an_expert_with_no_token"])
def test_dropless_under_skew(case):
    """No capacity: an expert may own every row or none, and every token
    still gets all k of its experts."""
    layer, params, x, config = moe_layer(k=1 if case == "all_to_one_expert" else 2)
    router = np.zeros((8, 4), np.float32)
    if case == "all_to_one_expert":
        router[:, 2] = 0.0
        bias = np.asarray([0, 0, 50.0, 0], np.float32)
    else:
        bias = np.asarray([5.0, 4.0, -50.0, 3.0], np.float32)  # expert 2 never
    # a router that ignores its input: logits = |x| . 0 + bias through one
    # constant feature
    x = x.at[..., 0].set(1.0)
    router[0] = bias
    params = dict(params, router=jnp.asarray(router))
    got, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    want, chosen, _, _ = REF._moe(x, params, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    picked = np.asarray(state["intermediates"]["moe_experts"][0])
    counts = np.bincount(picked.reshape(-1), minlength=4)
    if case == "all_to_one_expert":
        assert counts.tolist() == [0, 0, 12, 0]
    else:
        assert counts[2] == 0 and counts.sum() == 12 * 2
    assert np.abs(np.asarray(got)).min() > 0  # no token came back empty


@pytest.mark.parametrize("k", [1, 2, 3])
def test_top_k_ties_resolve_as_the_reference(k):
    """Equal probabilities: the lower expert index wins, in `route` and in
    the reference's independent rank-count."""
    p = jnp.asarray([[0.2, 0.2, 0.2, 0.2, 0.2],
                     [0.1, 0.3, 0.3, 0.2, 0.1],
                     [0.25, 0.25, 0.1, 0.25, 0.15]], jnp.float32)
    _, experts = route(p, k, False)
    mine = np.zeros(p.shape, bool)
    np.put_along_axis(mine, np.asarray(experts), True, axis=-1)
    np.testing.assert_array_equal(mine, np.asarray(REF.top_k_mask(p, k)))
    assert np.asarray(experts)[0].tolist() == list(range(k))


@pytest.mark.parametrize("sizes", [[0, 10, 0, 20, 1, 33, 0, 0], [64, 0, 0, 0, 0, 0, 0, 0],
                                   [8] * 8], ids=["ragged", "one_group", "even"])
def test_gmm_kernel_interpreted_matches_ragged_dot(sizes):
    """The Mosaic kernel's body in the Pallas interpreter (bf16 rows, float32
    weights cast in the kernel) against `lax.ragged_dot` and a plain gather."""
    rs = np.random.RandomState(0)
    lhs = jnp.asarray(rs.randn(64, 128), jnp.bfloat16)
    rhs = jnp.asarray(rs.randn(8, 128, 256), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, gs, jnp.float32, interpret=True)
    off = grouped_matmul(lhs, rhs, gs, jnp.float32)
    group = np.repeat(np.arange(8), sizes)
    want = np.einsum("mk,mkn->mn", np.asarray(lhs, np.float32),
                     np.asarray(rhs.astype(jnp.bfloat16), np.float32)[group])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(off), want, atol=1e-3, rtol=1e-3)


def test_gmm_kernel_lowers_for_tpu_at_published_widths():
    """A decode step's 64 rows and a prefill's 2,048 over 64 experts of
    [2048, 1024] float32: the kernel lowers to a Mosaic call named
    `kft_moe_gmm` (jax.export, no chip)."""
    from kungfu_tpu.ops.gmm import KERNEL_NAME

    for m in (64, 2048):
        exp = jax.export.export(
            jax.jit(lambda a, b, g: grouped_matmul(a, b, g, jnp.float32,
                                                   interpret=False)),
            platforms=["tpu"])(
            jax.ShapeDtypeStruct((m, 2048), jnp.bfloat16),
            jax.ShapeDtypeStruct((64, 2048, 1024), jnp.float32),
            jax.ShapeDtypeStruct((64,), jnp.int32))
        text = exp.mlir_module()
        assert "tpu_custom_call" in text and KERNEL_NAME in text


def test_engine_boot_holds_one_set_of_parameters():
    """ServingEngine takes its cache from an abstract init: after a boot
    exactly one array of each parameter's shape is live (finding 2 of
    PERF.md: a second `model.init` once doubled them)."""
    config, cfg, _, params = build(seed=7)
    shape = params["block_0"]["moe"]["w_gate"].shape
    count = lambda: sum(a.shape == shape for a in jax.live_arrays())  # noqa: E731
    before = count()
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    assert count() == before  # w_gate, w_up of 2 layers were there already
    assert jax.tree.leaves(eng.cache)[0].shape[0] == 2
    assert set(eng._dev_counters) == {STATS}


@pytest.mark.parametrize("std", [0.02, 1.0], ids=["default", "token_led"])
def test_embed_init_std_scales_the_embedding_alone(std):
    """`embed_init_std` is the token embedding's initial scale and nothing
    else: the same key draws the same normals, so the table is the default's
    times std / 0.02 and every other leaf is the default's bit for bit (the
    dense configurations, which leave the field alone, keep their weights)."""
    _, _, _, base = build(seed=5)
    _, cfg, _, params = build(seed=5, embed_init_std=std)
    assert cfg.embed_init_std == std
    table = np.asarray(params["embed"]["embedding"])
    np.testing.assert_allclose(table.std(), std, rtol=0.05)
    np.testing.assert_allclose(
        table, np.asarray(base["embed"]["embedding"]) * (std / 0.02), rtol=1e-6)
    rest = lambda p: {k: v for k, v in p.items() if k != "embed"}  # noqa: E731
    for a, b in zip(jax.tree.leaves(rest(params)), jax.tree.leaves(rest(base))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_health_probe_reads_no_device_counter():
    """`device_counters(refresh=False)`, what /healthz shows, is the last
    copy: the router probes it four times a second, and a read waits for
    the step in flight under the lock the step programs dispatch under."""
    _, cfg, _, params = build()
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    eng.submit(Request(prompt=(3, 1, 4), max_new_tokens=4))
    eng.run_until_idle()
    assert stats_totals(eng.device_counters(refresh=False)[STATS])["layer_calls"] == 0
    assert stats_totals(eng.device_counters()[STATS])["layer_calls"] == 2 * 3
    assert stats_totals(eng.device_counters(refresh=False)[STATS])["layer_calls"] == 6
