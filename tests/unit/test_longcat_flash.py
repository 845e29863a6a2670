"""The latent-attention block with a shortcut expert branch (models/
transformer.py `MLA`, `ShortcutMoEBlock`; parallel/moe.py's share of the
experts and identity experts) against the plain reference
`benchmark/references/longcat_flash.py`, at a LongCat-Flash-shaped tiny size
on the CPU: hidden 64, 4 heads of 16 + 8 (keys) and 16 (values), latent 32,
query rank 24, dense FFN 96, 16 routed experts of width 48 + 8 identity
experts, 4 a token, factor 6, 2 layers, seeded weights, float32."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from benchmark.lib.configs import load_reference, transformer_config
from kungfu_tpu.models.transformer import (
    MLA,
    MLP,
    TransformerLM,
    _Head,
    _norm,
    resident_params,
)
from kungfu_tpu.parallel.moe import STATS, MoE, stats_families, stats_health
from kungfu_tpu.serving import Request, ServingEngine

pytestmark = pytest.mark.serving

VOCAB, HEADS, ROUTED, ZERO, TOPK = 96, 4, 16, 8, 4
CONFIG = {
    "vocab_size": VOCAB, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 48, "num_layers": 2, "num_attention_heads": HEADS,
    "kv_lora_rank": 32, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": ROUTED, "max_position_embeddings": 64,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "zero_expert_num": ZERO,
    "zero_expert_type": "identity", "moe_topk": TOPK, "attention_bias": False,
    "reference": "longcat_flash",
    "program": {"n_layers": 2, "d_ff": 96, "d_ff_expert": 48, "norm": "rms",
                "norm_eps": 1e-5, "ffn": "swiglu", "attention": "full",
                "dtype": "float32", "block": "shortcut_moe", "kv_lora_rank": 32,
                "q_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16, "mla_scale_q_lora": True,
                "mla_scale_kv_lora": True, "n_experts": ROUTED,
                "n_zero_experts": ZERO, "experts_per_token": TOPK,
                "moe_every": 1, "routed_scaling_factor": 6.0,
                "router_bias": True, "embed_init_std": 1.0},
}
REF = load_reference(CONFIG)

#: float32 system against the float32 reference.  Both sum the same products
#: in another order (sorted rows against a loop over experts, the absorbed
#: score against the materialised one), so logits of standard deviation 0.16
#: differ by a few float32 roundings: 1.5e-7 measured, whole and through the
#: cache.  The mutations below move them by 1.9e-2 (the shortcut branch
#: rejoining early) to 1.2e-1 (identity experts dropped).
F32_TOL = 3e-6


def build(seed=1, layers=2, **program):
    config = dict(CONFIG, num_layers=layers,
                  program=dict(CONFIG["program"], n_layers=layers, **program))
    cfg = transformer_config(config)
    model = TransformerLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32))["params"])
    # matrices four times the seeded 0.02 (lm_head and router as seeded), so
    # that every sublayer weighs in the residual stream and a sublayer moved
    # or left out shows in the logits
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 4.0 * a if path[-1].key in (
            "kernel", "w_gate", "w_up", "w_down") and path[0].key != "lm_head"
        else a, params)
    # a correction bias that matters: the choice is by p + b, the gate by p
    for name, block in params.items():
        if name.startswith("block_"):
            key = jax.random.PRNGKey(seed + 100 + int(name[6:]))
            block["moe"]["router_bias"] = 0.02 * jax.random.normal(
                key, block["moe"]["router_bias"].shape)
    return config, cfg, model, params


def tokens(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, VOCAB, shape),
                       jnp.int32)


def reference(params, toks, config):
    """The reference's logits, traced as one program (eager, every small
    operation of the loop over experts is compiled on its own)."""
    return jax.jit(lambda p, t: REF.forward(p, t, config))(params, toks)


def close(got, want, tol=F32_TOL):
    return float(jnp.abs(jnp.asarray(got) - jnp.asarray(want)).max()) < tol


# -- the whole sequence ----------------------------------------------------------------


@pytest.mark.parametrize("share", [{}, {"experts_held": 4, "expert_offset": 8}],
                         ids=["every_expert_held", "experts_8_to_11_held"])
def test_forward_matches_reference(share):
    config, cfg, model, params = build(**share)
    toks = tokens((2, 24))
    got = jax.jit(model.apply)({"params": params}, toks)
    assert got.shape == (2, 24, VOCAB)
    assert close(got, reference(params, toks, config))
    assert params["block_0"]["moe"]["w_gate"].shape[0] == cfg.local_experts
    assert params["block_0"]["moe"]["router"].shape == (64, ROUTED + ZERO)


def _mutations():
    """name -> a reference that differs from the published layer in one way
    a wrong implementation could."""
    true_moe = REF.moe

    def no_identity(u, p_moe, config):
        w, _ = REF.route(u, p_moe, config)
        return true_moe(u, p_moe, config) - jnp.sum(
            w[..., ROUTED:], axis=-1, keepdims=True) * u

    def no_rotary(x, theta):
        return jnp.zeros_like(x)  # q_rope . k_r contributes nothing

    def shortcut_early(x, p, config):
        eps = float(config["rms_norm_eps"])
        norm = lambda name, y: REF._rms_norm(  # noqa: E731
            y, jnp.asarray(p[name]["scale"]), eps)
        x = x + REF.mla(norm("ln_attn_0", x), p["attn_0"], config)
        h = norm("ln_ffn_0", x)
        x = x + REF._ffn(h, p["mlp_0"]) + REF.moe(h, p["moe"], config)
        x = x + REF.mla(norm("ln_attn_1", x), p["attn_1"], config)
        return x + REF._ffn(norm("ln_ffn_1", x), p["mlp_1"])

    return {
        "identity_experts_dropped": ("moe", no_identity, {}),
        "routed_scaling_factor_1": (None, None, {"routed_scaling_factor": 1}),
        "rotary_part_left_out_of_the_score": ("_rope", no_rotary, {}),
        "shortcut_added_after_the_first_sublayer": ("block", shortcut_early, {}),
    }


@pytest.mark.parametrize("name", sorted(_mutations()))
def test_a_mutated_layer_fails_the_comparison(name, monkeypatch):
    """The comparison tells the published layer from its near misses: the
    system agrees with the reference to F32_TOL, whole and through the
    absorbed decode steps, and is 1000 x that or more from each mutation."""
    attr, fn, change = _mutations()[name]
    config, cfg, model, params = build()
    toks = tokens((2, 24), seed=4)
    whole = jax.jit(model.apply)({"params": params}, toks)
    stepped = _prefill_then_decode(cfg, params, toks, prefill=16)
    true = reference(params, toks, config)
    assert close(whole, true) and close(stepped, true)
    if attr:
        monkeypatch.setattr(REF, attr, fn)
    wrong = reference(params, toks, dict(config, **change))
    for got in (whole, stepped):
        assert float(jnp.abs(got - wrong).max()) > 1000 * F32_TOL


# -- through the cache -----------------------------------------------------------------


def _prefill_then_decode(cfg, params, toks, prefill, chunk=1):
    """Logits of a decode-mode model: one call over `prefill` tokens (the
    materialised form), then calls of `chunk` tokens (the absorbed form)."""
    model = TransformerLM(dataclasses.replace(cfg, decode=True))
    cache = jax.jit(model.init)(jax.random.PRNGKey(0), toks[:, :1])["cache"]
    step = jax.jit(lambda cache, t: model.apply(  # one trace a shape
        {"params": params, "cache": cache}, t, mutable=["cache"]))
    out, at = [], 0
    for n in [prefill] + [chunk] * ((toks.shape[1] - prefill) // chunk):
        logits, st = step(cache, toks[:, at:at + n])
        cache, at = st["cache"], at + n
        out.append(logits)
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("chunk", [1, 4], ids=["decode", "verify_k4"])
def test_absorbed_decode_equals_materialised_attention(chunk, mode, monkeypatch):
    """Prefill (K and V built from the stored latent), then decode or verify
    steps in the absorbed form, through the dense einsum and through the
    kernel's body: the reference's materialised attention over the whole
    sequence.  The cache is one [B, max_len, 32 + 8] leaf a sublayer."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    config, cfg, model, params = build()
    cfg = dataclasses.replace(cfg, attention="auto")
    toks = tokens((3, 28), seed=2)
    got = _prefill_then_decode(cfg, params, toks, prefill=16, chunk=chunk)
    assert close(got, reference(params, toks, config))
    dm = TransformerLM(dataclasses.replace(cfg, decode=True))
    cache = jax.eval_shape(dm.init, jax.random.PRNGKey(0), toks[:, :1])["cache"]
    leaves = {"/".join(str(k.key) for k in path): leaf.shape
              for path, leaf in jax.tree_util.tree_leaves_with_path(cache)}
    assert leaves == {
        f"block_{i}/attn_{j}/{name}": shape for i in range(2) for j in range(2)
        for name, shape in (("cached_latent", (3, 64, 40)), ("idx", (3,)),
                            ("overflowed", (3,)))}


def test_the_absorbed_step_builds_no_key_or_value_of_a_cached_row(monkeypatch):
    """The jaxpr of a decode step holds nothing of shape [B, max_len, H, .]:
    the score is against the latent row itself."""
    monkeypatch.setenv("KFT_PALLAS", "off")
    _, cfg, _, params = build()
    model = TransformerLM(dataclasses.replace(cfg, decode=True))
    toks = tokens((2, 1))
    variables = {"params": params,
                 "cache": model.init(jax.random.PRNGKey(0), toks)["cache"]}
    text = lambda t: str(jax.make_jaxpr(  # noqa: E731
        lambda v, t: model.apply(v, t, mutable=["cache"]))(variables, t))
    per_head = f"[2,{cfg.max_len},{HEADS},"
    assert per_head not in text(toks)
    assert per_head in text(tokens((2, 16)))  # a prefill bucket materialises


def test_overflow_poisons_its_own_slot_only():
    _, cfg, _, params = build()
    model = TransformerLM(dataclasses.replace(cfg, decode=True, max_len=8))
    toks = tokens((2, 6))
    cache = jax.jit(model.init)(jax.random.PRNGKey(0), toks[:, :1])["cache"]
    live = jnp.asarray([True, False])
    step = jax.jit(lambda cache, t: model.apply(
        {"params": params, "cache": cache}, t, live=live, mutable=["cache"]))
    _, st = step(cache, toks)
    logits, st = step(st["cache"], toks[:, :4])
    idx = st["cache"]["block_0"]["attn_1"]["idx"]
    assert idx.tolist() == [10, 0]              # the free row's cursor stayed
    assert bool(jnp.isnan(logits[0]).all()) and bool(jnp.isfinite(logits[1]).all())


# -- the engine ------------------------------------------------------------------------


def engine_logits(eng, prompts, steps, free=()):
    from kungfu_tpu.serving.engine import FREE
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
        col = [[cont[t]] for _, cont in prompts] + [[FREE]] * len(free)
        _, logits, cache, counters = eng._decode(
            eng.params, cache, counters, jnp.asarray(col, jnp.int32))
        for slot in out:
            out[slot].append(np.asarray(logits)[slot])
    return out, cache, counters


@pytest.mark.parametrize("share", [{}, {"experts_held": 4, "expert_offset": 4}],
                         ids=["every_expert_held", "experts_4_to_7_held"])
def test_engine_with_a_free_slot_matches_reference_logits(share):
    """Two requests at different cursors and a free slot through the slot
    cache: logits against the reference's full forward over prompt +
    continuation, the free slot's cursor at 0, and every live assignment
    counted once: held + identity + absent = k x live tokens."""
    config, cfg, _, params = build(**share)
    eng = ServingEngine(cfg, params, slots=3, prefill_buckets=(16,))
    rs = np.random.RandomState(5)
    prompts = [(rs.randint(0, VOCAB, 11).tolist(), rs.randint(0, VOCAB, 6).tolist()),
               (rs.randint(0, VOCAB, 5).tolist(), rs.randint(0, VOCAB, 6).tolist())]
    got, cache, counters = engine_logits(eng, prompts, steps=6, free=(2,))
    for slot, (prompt, cont) in enumerate(prompts):
        full = jnp.asarray([prompt + cont], jnp.int32)
        want = np.asarray(reference(params, full, config))[0]
        for t, logits in enumerate(got[slot]):
            assert close(logits, want[len(prompt) - 1 + t])
    for i in range(2):
        for j in range(2):
            assert cache[f"block_{i}"][f"attn_{j}"]["idx"].tolist() == [17, 11, 0]
    stats = jax.device_get(counters[STATS])
    for i in range(2):
        m = stats[f"block_{i}"]["moe"]
        assert m["assignments"].shape == (cfg.local_experts,)
        assert int(m["calls"]) == 6
        assert (int(m["assignments"].sum()) + int(m["zero_assignments"])
                + int(m["absent_assignments"])) == 6 * 2 * TOPK
        if not share:
            assert int(m["absent_assignments"]) == 0
    fam = stats_families(stats)
    assert set(fam) == {"kft_moe_assignments_total", "kft_moe_experts_hit_total",
                        "kft_moe_decode_layer_calls_total",
                        "kft_moe_zero_assignments_total",
                        "kft_moe_absent_assignments_total"}
    assert fam["kft_moe_zero_assignments_total"][""] > 0
    health = stats_health(stats)
    assert health["zero_assignments_total"] == fam[
        "kft_moe_zero_assignments_total"][""]


def test_engine_serves_requests_and_counts_latent_rows():
    """Requests through `submit` / `step`: greedy tokens are those of the
    reference's argmax, the replay is identical, the five kinds of
    `decode_attn_rows` count latent rows, and the resident tree keeps the
    new nn.Dense kernels in the compute dtype."""
    config, cfg, _, params = build()
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    prompt = tokens((1, 9), seed=7)[0].tolist()
    outs = []
    for rid in ("a", "b"):
        pending = eng.submit(Request(req_id=rid, prompt=tuple(prompt),
                                     max_new_tokens=5))
        eng.run_until_idle()
        outs.append(list(pending.result.tokens))
    assert outs[0] == outs[1] and len(outs[0]) == 14
    want = reference(params, jnp.asarray([outs[0]], jnp.int32), config)[0]
    assert np.asarray(want)[8:13].argmax(-1).tolist() == outs[0][9:]
    rows = eng.decode_attn_rows()
    assert set(rows) == {"cache", "written", "written_free", "fetched",
                         "fetched_free"}
    assert rows["written"] == 2 * sum(range(10, 14)) and rows["written_free"] == 0
    assert rows["cache"] == rows["fetched"] == 2 * 4 * 2 * cfg.max_len
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    res = resident_params(bf16, params)
    attn = res["block_0"]["attn_1"]
    assert {attn[k]["kernel"].dtype for k in ("q_a", "q_b", "kv_a", "kv_b", "out")
            } == {jnp.dtype(jnp.bfloat16)}
    assert res["block_0"]["mlp_1"]["gate"]["kernel"].dtype == jnp.bfloat16
    moe = res["block_0"]["moe"]
    assert {moe[k].dtype for k in ("router", "router_bias", "w_gate", "w_up",
                                   "w_down")} == {jnp.dtype(jnp.float32)}
    assert attn["kv_a_norm"]["scale"].dtype == jnp.float32


def test_the_prefix_cache_and_row_helpers_take_the_latent_leaf():
    """A warm prefill from cached latent rows is the cold prefill, and the
    rows are one [n, 40] block a sublayer."""
    from kungfu_tpu.serving.prefix import PrefixCache
    from kungfu_tpu.serving.slots import extract_rows

    _, cfg, _, params = build()
    prefix = PrefixCache(budget_bytes=1 << 22)
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,),
                        prefix_cache=prefix)
    shared = tokens((1, 12), seed=8)[0].tolist()
    first, small, total, hit = eng._run_prefill(tuple(shared + [3, 4]), 0.0)
    assert hit == 0
    rows = extract_rows(small, total)
    assert sorted(rows) == sorted(
        (f"['block_{i}']", f"['attn_{j}']", "['cached_latent']")
        for i in range(2) for j in range(2))
    assert {block.shape for block in rows.values()} == {(14, 40)}
    cold = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    want, _, _, _ = cold._run_prefill(tuple(shared + [5, 6, 7]), 0.0)
    got, _, _, hit = eng._run_prefill(tuple(shared + [5, 6, 7]), 0.0)
    assert hit == 12 and got == want


# -- the shares add up -----------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_and_logits():
    """One layer, cut as a deployment cuts it: 4 expert shares of 4 routed
    experts, 2 head shares of 2 heads, 2 vocabulary slices of 48 ids.  The
    parts the shares give (the system's modules on sliced weights), the
    identity experts' part counted once, add up to the uncut reference's
    layer output, and the slices' logits side by side are its logits."""
    config, cfg, _, params = build(layers=1)
    p = params["block_0"]
    toks = tokens((2, 20), seed=9)
    x = jnp.asarray(params["embed"]["embedding"])[toks]
    eps = cfg.norm_eps
    norm = lambda name, y: REF._rms_norm(y, p[name]["scale"], eps)  # noqa: E731

    def head_share(a, first, n):
        cols = lambda w, per: w.reshape(w.shape[0], HEADS, per)[  # noqa: E731
            :, first:first + n].reshape(w.shape[0], n * per)
        return dict(a, q_b={"kernel": cols(a["q_b"]["kernel"], 16 + 8)},
                    kv_b={"kernel": cols(a["kv_b"]["kernel"], 16 + 16)},
                    out={"kernel": a["out"]["kernel"].reshape(HEADS, 16, 64)[
                        first:first + n].reshape(n * 16, 64)})

    def attention(name, u):
        share_cfg = dataclasses.replace(cfg, n_heads=2)
        return sum(MLA(share_cfg).apply(
            {"params": head_share(p[name], first, 2)}, u) for first in (0, 2))

    def experts(u):
        parts = []
        for first in range(0, ROUTED, 4):
            share_cfg = dataclasses.replace(cfg, experts_held=4,
                                            expert_offset=first)
            m = dict(p["moe"], **{k: p["moe"][k][first:first + 4]
                                  for k in ("w_gate", "w_up", "w_down")})
            parts.append(MoE(share_cfg).apply({"params": m}, u))
        w, _ = REF.route(u, p["moe"], config)
        identity = jnp.sum(w[..., ROUTED:], axis=-1, keepdims=True) * u
        return sum(parts) - (len(parts) - 1) * identity

    ffn = lambda name, u: MLP(cfg).apply({"params": p[name]}, u)  # noqa: E731
    x1 = x + attention("attn_0", norm("ln_attn_0", x))
    h = norm("ln_ffn_0", x1)
    s = experts(h)
    assert close(s, REF.moe(h, p["moe"], config))
    x2 = x1 + ffn("mlp_0", h)
    x3 = x2 + attention("attn_1", norm("ln_attn_1", x2))
    x4 = x3 + ffn("mlp_1", norm("ln_ffn_1", x3)) + s
    assert close(x4, REF.block(x, p, config))
    final = _norm(cfg, "ln_f").apply({"params": params["ln_f"]}, x4)
    logits = jnp.concatenate([
        _Head(dataclasses.replace(cfg, vocab_size=48)).apply(
            {"params": {"kernel": params["lm_head"]["kernel"][:, v:v + 48]}},
            final) for v in (0, 48)], axis=-1)
    assert close(logits, reference(params, toks, config))
