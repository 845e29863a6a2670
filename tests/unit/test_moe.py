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
from kungfu_tpu.models.transformer import (
    TransformerLM,
    lm_loss_with_aux,
    resident_params,
)
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
    # the shorter answer's slot is free in the last two steps, and a free
    # row is routed to nobody: 2 experts a layer for each LIVE slot-step
    assert eng.decode_rows() == {"live": 4 + 6, "free": 2}
    assert totals["assignments"].sum() == 2 * 2 * (4 + 6)
    assert totals["experts_hit"] <= 2 * 2 * (4 + 6)


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


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("sizes", [[0, 10, 0, 20, 1, 9, 0, 0], [3] + [0] * 7, [0] * 8,
                                   [8] * 8],
                         ids=["ragged", "one_group", "no_group", "every_row"])
def test_gmm_rows_of_no_group_come_back_as_zeros(sizes, mode, monkeypatch):
    """`leftover=True`: the group sizes sum to less than m (to 0, even) and
    the rows after the last group's are zeros, with NaN in lhs there, from
    `ragged_dot` and from the kernel's body alike; the rows that have a
    group are what they are when the sizes sum to m; no gradient reaches
    a row of no group."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    rs = np.random.RandomState(1)
    owned = sum(sizes)
    lhs = jnp.asarray(rs.randn(64, 128), jnp.bfloat16)
    rhs = jnp.asarray(rs.randn(8, 128, 256), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs.at[owned:].set(jnp.nan), rhs, gs, jnp.float32,
                         leftover=True)
    assert not np.asarray(got[owned:]).any()
    # the same rows with every row owned: the last group takes the rest
    full = grouped_matmul(lhs, rhs, gs.at[-1].add(64 - owned), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got[:owned]), np.asarray(full[:owned]))
    d_lhs = jax.grad(lambda a: grouped_matmul(
        a, rhs, gs, jnp.float32, leftover=True).sum())(lhs.astype(jnp.float32))
    assert not np.asarray(d_lhs[owned:]).any()
    assert owned == 0 or np.asarray(d_lhs[:owned]).any()


def _decode_layer(live_rows=(True, False, True, False), L=3):
    """The layer in decode mode (it counts), its parameters, zeroed
    counters, x [4, L, d] and the mask."""
    layer, params, _, _ = moe_layer()
    layer = MoE(dataclasses.replace(layer.cfg, decode=True, rope=True))
    x = jnp.asarray(np.random.RandomState(7).randn(4, L, 8), jnp.float32)
    stats = jax.tree.map(jnp.zeros_like, layer.init(jax.random.PRNGKey(0), x)[STATS])
    return layer, params, stats, x, jnp.asarray(live_rows)


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_a_row_that_is_not_live_is_routed_to_nobody(mode, monkeypatch):
    """`MoE(cfg)(x, live)`: a live row's output is bit for bit the unmasked
    call's; a row that is not live comes back exactly zero with NaN for its
    input; the counters count the live rows' assignments and experts only."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    layer, params, stats, x, live = _decode_layer()
    L, k = x.shape[1], layer.cfg.experts_per_token
    run = lambda x, *mask: layer.apply(  # noqa: E731
        {"params": params, STATS: stats}, x, *mask, mutable=[STATS, "intermediates"])
    want, plain = run(x)
    got, masked = run(jnp.where(live[:, None, None], x, jnp.nan), live)
    keep = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got)[keep], np.asarray(want)[keep])
    assert not np.asarray(got)[~keep].any()
    assert np.isfinite(np.asarray(got)).all()
    assert int(masked[STATS]["assignments"].sum()) == k * keep.sum() * L
    assert int(plain[STATS]["assignments"].sum()) == k * len(keep) * L
    # the experts the live rows chose in the unmasked call, and no other
    chosen = np.asarray(plain["intermediates"]["moe_experts"][0])[keep]
    np.testing.assert_array_equal(
        np.asarray(masked[STATS]["assignments"]),
        np.bincount(chosen.reshape(-1), minlength=layer.cfg.n_experts))
    assert int(masked[STATS]["experts_hit"]) == len(np.unique(chosen))
    assert int(masked[STATS]["calls"]) == 1


@pytest.mark.parametrize("live_rows", [(True,) * 4, (False,) * 4],
                         ids=["every_row_live", "no_row_live"])
def test_mask_at_its_two_ends(live_rows):
    """Every row live reproduces the call without a mask, counters too; no
    row live reads nothing and returns zeros."""
    layer, params, stats, x, live = _decode_layer(live_rows)
    run = lambda *mask: layer.apply(  # noqa: E731
        {"params": params, STATS: stats}, x, *mask, mutable=[STATS])
    (want, plain), (got, masked) = run(), run(live)
    if all(live_rows):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for name in ("assignments", "experts_hit", "calls"):
            np.testing.assert_array_equal(np.asarray(masked[STATS][name]),
                                          np.asarray(plain[STATS][name]))
    else:
        assert not np.asarray(got).any()
        assert int(masked[STATS]["assignments"].sum()) == 0
        assert int(masked[STATS]["experts_hit"]) == 0


@pytest.mark.parametrize("leftover", [False, True],
                         ids=["sizes_sum_to_m", "rows_of_no_group"])
def test_gmm_kernel_lowers_for_tpu_at_published_widths(leftover):
    """A decode step's 64 rows and a prefill's 2,048 over 64 experts of
    [2048, 1024] float32: the kernel lowers to a Mosaic call named
    `kft_moe_gmm` (jax.export, no chip), with and without the select that
    zeroes the rows of no group."""
    from kungfu_tpu.ops.gmm import KERNEL_NAME

    for m in (64, 2048):
        exp = jax.export.export(
            jax.jit(lambda a, b, g: grouped_matmul(
                a, b, g, jnp.float32, interpret=False, leftover=leftover)),
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


# -- resident parameters: the experts' model, untied head, QK-norm, bf16 ---------------


def dtypes(tree):
    return {jax.tree_util.keystr(path): str(leaf.dtype)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_resident_params_leave_what_is_read_in_float32():
    """Stored in bf16: the attention kernels and, the head being its own
    matrix, the embedding table.  Float32 as they came: router, every
    scale, `lm_head`, and for now the experts (their byte count is the
    benchmark's: PERF.md section 7)."""
    _, cfg, _, params = build(dtype="bfloat16")
    resident = resident_params(cfg, params)
    narrowed = {name for name, d in dtypes(resident).items() if d == "bfloat16"}
    assert narrowed == {"['embed']['embedding']"} | {
        f"['block_{i}']['attn']['{m}']['kernel']"
        for i in range(2) for m in ("q", "k", "v", "out")}
    for name in ("['block_0']['moe']['router']", "['block_1']['moe']['w_gate']",
                 "['block_0']['moe']['w_up']", "['block_0']['moe']['w_down']",
                 "['block_0']['attn']['q_norm']['scale']", "['ln_f']['scale']",
                 "['lm_head']['kernel']"):
        assert dtypes(resident)[name] == "float32"
    again = resident_params(cfg, resident)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(resident)))
    _, f32_cfg, _, _ = build()
    assert resident_params(f32_cfg, params) is params


@pytest.mark.parametrize("program", ["forward", "prefill", "decode_step"])
def test_resident_tree_gives_the_same_logits_bit_for_bit(program):
    """On the CPU a float32 matmul is a float32 matmul, so a leaf narrowed
    that the router, a norm or the head reads in float32 shows here, as
    another logit or another expert."""
    _, cfg, _, params = build(dtype="bfloat16")
    resident = resident_params(cfg, params)
    toks = tokens((3, 9), seed=8)

    def run(p):
        if program == "forward":
            return TransformerLM(cfg).apply({"params": p}, toks)
        model = TransformerLM(dataclasses.replace(cfg, decode=True))
        state = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks[:, :1])
        state = {col: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
                 for col, tree in state.items() if col != "params"}
        prefill, st = model.apply({"params": p, **state}, toks[:, :8],
                                  mutable=list(state))
        step, _ = model.apply({"params": p, **st}, toks[:, 8:9],
                              mutable=list(state))
        return prefill if program == "prefill" else step

    np.testing.assert_array_equal(np.asarray(run(resident)), np.asarray(run(params)))


def test_engine_holds_the_resident_tree_and_counts_its_bytes_on_the_host():
    """The engine installs through `resident_params`, and `param_bytes`
    (what /healthz and `kft_serve_param_bytes` show) comes from the leaves'
    shapes: /healthz stays off the device."""
    _, cfg, _, params = build(dtype="bfloat16")
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    assert dtypes(eng.params) == dtypes(resident_params(cfg, params))
    for leaf in jax.tree.leaves(eng.params):
        leaf.delete()  # whatever read an array now would raise
    held = eng.stats()["param_bytes"]
    d, width, E, V = 64, 32, 8, 96
    assert held["bfloat16"] == 2 * (V * d + 2 * 4 * d * d)
    assert held["float32"] == 4 * (2 * (3 * E * d * width + d * E + 4 * d) + d + d * V)
