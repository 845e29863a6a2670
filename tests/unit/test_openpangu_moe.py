"""Latent attention in the plain block under sandwich norms, a leading dense
layer, sigmoid top-k experts beside a shared expert and the prediction
module as the engine's drafter (models/transformer.py `Block`, `MTPModule`;
parallel/moe.py; serving/spec.py `MTPDrafter`) against the plain reference
`benchmark/references/openpangu_moe.py`, at an openPangu-Ultra-MoE-shaped
tiny size on the CPU: hidden 64, 4 heads of 16 + 8 (keys) and 16 (values),
latent 32, query rank 24, one dense layer of width 96 then two expert layers
of 16 routed experts of width 48, 4 a token, factor 2.5, one shared expert,
one prediction module, seeded weights, float32."""
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
    MTPModule,
    TransformerLM,
    _Head,
    _norm,
    resident_params,
)
from kungfu_tpu.parallel.moe import STATS, MoE
from kungfu_tpu.serving import Request, ServingEngine
from kungfu_tpu.serving.spec import MTPDrafter

pytestmark = pytest.mark.serving

VOCAB, HEADS, ROUTED, TOPK = 96, 4, 16, 4
CONFIG = {
    "vocab_size": VOCAB, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 48, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": HEADS,
    "kv_lora_rank": 32, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "routed_scaling_factor": 2.5,
    "n_routed_experts": ROUTED, "n_shared_experts": 1,
    "num_experts_per_tok": TOPK, "norm_topk_prob": True,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "attention_bias": False, "sandwich_norm": True,
    "num_nextn_predict_layers": 1, "reference": "openpangu_moe",
    "program": {"d_ff_expert": 48, "norm": "rms", "norm_eps": 1e-5,
                "ffn": "swiglu", "attention": "full", "dtype": "float32",
                "kv_lora_rank": 32, "q_lora_rank": 24, "qk_nope_head_dim": 16,
                "qk_rope_head_dim": 8, "v_head_dim": 16, "n_experts": ROUTED,
                "experts_per_token": TOPK, "moe_every": 1,
                "norm_topk_prob": True, "routed_scaling_factor": 2.5,
                "sandwich_norm": True, "first_dense_layers": 1,
                "n_shared_experts": 1, "router_scores": "sigmoid",
                "mtp_layers": 1, "embed_init_std": 1.0},
}
REF = load_reference(CONFIG)

#: float32 system against the float32 reference.  Both sum the same products
#: in another order (sorted rows against a loop over experts, the absorbed
#: score against the materialised one), so logits of standard deviation 0.16
#: differ by a few float32 roundings: 2.7e-7 measured for the model, 3.4e-7
#: for the prediction module, whole and through the cache.  The mutations
#: below move them by 5e-3 (the 1e-20 aside, the smallest term: sigmoid for
#: softmax under renormalisation) to 2e-1 (a post-sublayer norm left out).
F32_TOL = 3e-6


def build(seed=1, **program):
    config = dict(CONFIG, program=dict(CONFIG["program"], **program))
    cfg = transformer_config(config)
    model = TransformerLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32))["params"])
    # matrices four times the seeded 0.02 (lm_head as seeded), so that every
    # sublayer weighs in the residual stream and a term moved or left out
    # shows in the logits; norm scales off 1, so that a norm taken for
    # another one shows too
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 4.0 * a if path[-1].key in (
            "kernel", "router", "w_gate", "w_up", "w_down")
        and path[0].key != "lm_head" else a, params)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 50), 256))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.2 * jax.random.normal(next(keys), a.shape)
        if path[-1].key == "scale" else a, params)
    return config, cfg, model, params


def tokens(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, VOCAB, shape),
                       jnp.int32)


def reference(params, toks, config, fn="forward"):
    """The reference's logits, traced as one program (eager, every small
    operation of the loop over experts is compiled on its own)."""
    return jax.jit(lambda p, t: getattr(REF, fn)(p, t, config))(params, toks)


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
    # one leading dense layer, then expert layers with a shared expert
    assert "mlp" in params["block_0"] and "moe" not in params["block_0"]
    for i in (1, 2):
        moe = params[f"block_{i}"]["moe"]
        assert moe["w_gate"].shape[0] == cfg.local_experts
        assert moe["router"].shape == (64, ROUTED)
        assert moe["shared"]["gate"]["kernel"].shape == (64, 48)
    assert set(params["block_1"]) == {"attn", "ln1", "ln1_post", "ln2",
                                      "ln2_post", "moe"}


def test_the_main_model_is_seeded_alike_with_and_without_the_module():
    """`seed_params` of the cell's file (`mtp_layers` 0: what the timed
    worker and the checker hold) and of the drafting engine (`mtp_layers`
    1) give the main model the same weights: the module draws from keys of
    its own path."""
    from kungfu_tpu.serving.worker import seed_params

    _, cfg, _, _ = build()
    with_module = jax.jit(lambda: seed_params(cfg, 7))()
    without = jax.jit(lambda: seed_params(
        dataclasses.replace(cfg, mtp_layers=0), 7))()
    assert set(with_module) == set(without) | {"mtp_0"}
    assert set(with_module["mtp_0"]) == {"enorm", "hnorm", "eh_proj", "block",
                                         "ln_f"}
    assert with_module["mtp_0"]["eh_proj"]["kernel"].shape == (128, 64)
    for a, b in zip(jax.tree.leaves(without), jax.tree.leaves(
            {k: v for k, v in with_module.items() if k != "mtp_0"})):
        assert a.shape == b.shape and bool((a == b).all())


def _mutations():
    """name -> a reference that differs from the published layer in one way
    a wrong implementation could."""

    def no_post_norm(x, p, config):
        eps = float(config["rms_norm_eps"])
        norm = lambda name, y: REF._rms_norm(  # noqa: E731
            y, jnp.asarray(p[name]["scale"]), eps)
        a = x + REF.mla(norm("ln1", x), p["attn"], config)   # N2 left out
        u = norm("ln2", a)
        f = REF.moe(u, p["moe"], config) if "moe" in p else REF._ffn(u, p["mlp"])
        return a + norm("ln2_post", f)

    def no_shared(u, p_moe, config):
        return true_moe(u, p_moe, config) - REF._ffn(u, p_moe["shared"])

    def softmax_scores(u, p_moe, config):
        k = config["num_experts_per_tok"]
        s = jax.nn.softmax(u @ jnp.asarray(p_moe["router"]), axis=-1)
        top = jnp.argsort(-s, axis=-1, stable=True)[..., :k]
        chosen = jnp.sum(jax.nn.one_hot(top, s.shape[-1], dtype=jnp.int32), -2) > 0
        w = jnp.where(chosen, s, 0.0)
        return w / jnp.sum(w, -1, keepdims=True) * config["routed_scaling_factor"]

    def dense_everywhere(x, p, config):
        if "moe" not in p:
            return true_block(x, p, config)
        q = {k: v for k, v in p.items() if k != "moe"}
        return true_block(x, dict(q, mlp=p["moe"]["shared"]), config)

    true_moe, true_block = REF.moe, REF.block
    return {
        "post_attention_norm_left_out": ("block", no_post_norm, {}),
        "shared_expert_left_out": ("moe", no_shared, {}),
        "gates_not_renormalised": (None, None, {"norm_topk_prob": False}),
        "routed_scaling_factor_1": (None, None, {"routed_scaling_factor": 1}),
        "softmax_scores_for_sigmoid": ("route", softmax_scores, {}),
        "expert_layers_run_dense": ("block", dense_everywhere, {}),
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
@pytest.mark.parametrize("chunk", [1, 2], ids=["decode", "verify_k2"])
def test_prefill_then_decode_through_the_slot_cache(chunk, mode, monkeypatch):
    """Prefill, then decode steps or the drafter's two-row verify steps in
    the absorbed form, through the dense einsum and through the kernel's
    body: the reference's forward over the whole sequence.  The cache is
    one [B, max_len, 32 + 8] leaf a layer and nothing of the module's."""
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
        f"block_{i}/attn/{name}": shape for i in range(3)
        for name, shape in (("cached_latent", (3, 64, 40)), ("idx", (3,)),
                            ("overflowed", (3,)))}


# -- the prediction module -------------------------------------------------------------


def _module(cfg, params, cache=None):
    """The module over (hidden, next tokens), on the target's embedding and
    head: logits, and the cache it updated when it was given one."""
    m = MTPModule(cfg)
    variables = {"params": params["mtp_0"]}
    if cache is not None:
        variables["cache"] = cache
    return jax.jit(lambda v, h, t: m.apply(
        v, h, t, params["embed"]["embedding"], params["lm_head"]["kernel"],
        mutable=["cache"] if cache is not None else False))


def test_the_prediction_module_matches_forward_mtp():
    """Row i of the module, from the model's hidden state at i and token
    i + 1, against `forward_mtp`: whole, and through its own latent cache
    (a prefill of 16 rows, then the drafter's two-row steps, the second
    row of one step written again as the first of the next)."""
    config, cfg, model, params = build()
    toks = tokens((2, 25), seed=3)
    _, hidden = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True))(params, toks)
    want = reference(params, toks, config, "forward_mtp")       # [2, 24, V]
    h, nxt = hidden[:, :-1], toks[:, 1:]
    assert close(_module(cfg, params)(
        {"params": params["mtp_0"]}, h, nxt), want)

    dcfg = dataclasses.replace(cfg, decode=True)
    zeros = jnp.zeros
    cache = jax.tree.map(lambda s: zeros(s.shape, s.dtype), jax.eval_shape(
        MTPModule(dcfg).init, jax.random.PRNGKey(0), h[:, :1], nxt[:, :1],
        zeros((VOCAB, 64)), zeros((64, VOCAB)))["cache"])
    assert {"/".join(str(k.key) for k in path) for path, _ in
            jax.tree_util.tree_leaves_with_path(cache)} == {
        "block/attn/cached_latent", "block/attn/idx", "block/attn/overflowed"}
    step = _module(dcfg, params, cache)
    logits, st = step({"params": params["mtp_0"], "cache": cache},
                      h[:, :16], nxt[:, :16])
    assert close(logits, want[:, :16])
    cache = st["cache"]
    for at in range(15, 23):  # rows (at, at + 1): row `at` is written again
        cache = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.full_like(leaf, at)
            if path[-1].key == "idx" else leaf, cache)
        logits, st = step({"params": params["mtp_0"], "cache": cache},
                          h[:, at:at + 2], nxt[:, at:at + 2])
        cache = st["cache"]
        assert close(logits, want[:, at:at + 2])


def test_a_mutated_module_fails_the_comparison(monkeypatch):
    """`forward_mtp` with the halves of the concatenation exchanged, or fed
    the hidden state before the final norm, is 1000 x the limit away."""
    config, cfg, model, params = build()
    toks = tokens((2, 20), seed=6)
    _, hidden = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, return_hidden=True))(params, toks)
    got = _module(cfg, params)({"params": params["mtp_0"]}, hidden[:, :-1],
                               toks[:, 1:])
    assert close(got, reference(params, toks, config, "forward_mtp"))
    w = params["mtp_0"]["eh_proj"]["kernel"]
    exchanged = jax.tree_util.tree_map(lambda a: a, params)
    exchanged["mtp_0"] = dict(params["mtp_0"], eh_proj={
        "kernel": jnp.concatenate([w[64:], w[:64]])})
    wrong = reference(exchanged, toks, config, "forward_mtp")
    assert float(jnp.abs(got - wrong).max()) > 1000 * F32_TOL

    def before_the_final_norm(params, tokens, config):
        x = jnp.asarray(params["embed"]["embedding"])[tokens]
        for i in range(config["num_hidden_layers"]):
            x = REF.block(x, params[f"block_{i}"], config)
        return x

    monkeypatch.setattr(REF, "hidden_states", before_the_final_norm)
    unnormed = reference(params, toks, config, "forward_mtp")
    assert float(jnp.abs(got - unnormed).max()) > 1000 * F32_TOL


# -- the engine ------------------------------------------------------------------------


def test_engine_serves_requests_and_counts():
    """Requests through `submit` / `step`: greedy tokens are those of the
    reference's argmax, the replay is identical, every live assignment is
    counted once (held + absent = k x live tokens, no identity experts),
    and the resident tree keeps the shared expert in the compute dtype
    beside float32 routed experts."""
    config, cfg, _, params = build(experts_held=4, expert_offset=4)
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
    stats = eng.device_counters()[STATS]
    assert set(stats) == {"block_1", "block_2"}       # the dense layer has none
    for m in (b["moe"] for b in stats.values()):
        assert int(m["calls"]) == 8 and int(m["zero_assignments"]) == 0
        assert (int(m["assignments"].sum()) + int(m["absent_assignments"])
                == 8 * TOPK)
    rows = eng.decode_attn_rows()
    assert rows["written"] == 2 * sum(range(10, 14)) and rows["written_free"] == 0
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    res = resident_params(bf16, params)
    moe = res["block_1"]["moe"]
    assert {moe["shared"][k]["kernel"].dtype for k in ("in", "gate", "out")
            } == {jnp.dtype(jnp.bfloat16)}
    assert {moe[k].dtype for k in ("router", "w_gate", "w_up", "w_down")
            } == {jnp.dtype(jnp.float32)}
    module = res["mtp_0"]
    assert module["eh_proj"]["kernel"].dtype == jnp.bfloat16
    assert module["block"]["attn"]["kv_b"]["kernel"].dtype == jnp.bfloat16
    assert module["block"]["moe"]["w_up"].dtype == jnp.float32
    assert res["block_1"]["ln1_post"]["scale"].dtype == jnp.float32


def test_the_resident_form_is_found_with_the_kernels_on(monkeypatch):
    """`resident_params` finds the module's `nn.Dense` leaves by an abstract
    init over ONE token; with the kernels on (as on the chip) that init
    must not hand the module an empty sequence (the grouped matmul tiles
    its rows: no rows, no tile)."""
    monkeypatch.setenv("KFT_PALLAS", "interpret")
    _, cfg, _, params = build()
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16, max_len=48)  # no cached answer
    res = resident_params(bf16, params)
    assert res["mtp_0"]["block"]["moe"]["shared"]["in"]["kernel"].dtype == jnp.bfloat16
    assert res["mtp_0"]["enorm"]["scale"].dtype == jnp.float32


PROMPTS = [(11, 9), (5, 12), (14, 7)]   # (prompt length, new tokens) a slot


def _serve(eng, seed=11):
    rs = np.random.RandomState(seed)
    pending = [eng.submit(Request(
        req_id=f"r{i}", prompt=tuple(rs.randint(0, VOCAB, n).tolist()),
        max_new_tokens=new)) for i, (n, new) in enumerate(PROMPTS)]
    eng.run_until_idle()
    return [list(p.result.tokens) for p in pending]


def _inject(drafter, plain, plan):
    """Wrap `drafter.propose`: the module still runs (its cache and state
    stay what they would be), but what goes to the verify step is decided
    by `plan(round, slot)`: True the token the plain engine chose next
    (accepted), False another one (rejected), None the module's own."""
    real, rounds = drafter.propose, []

    def propose(next_tok, cursor):
        own = real(next_tok, cursor)
        out = own.copy()
        for slot, (n, _) in enumerate(PROMPTS):
            done = int(cursor[slot]) + 1      # tokens of the stream so far
            choice = plan(len(rounds), slot)
            if choice is None or cursor[slot] == 0 or done >= len(plain[slot]):
                continue
            truth = plain[slot][done]
            out[slot, 0] = truth if choice else (truth + 1) % VOCAB
        rounds.append(out.copy())
        return out

    drafter.propose = propose
    return rounds


@pytest.mark.parametrize("plan", ["reject_all", "accept_all", "mixed", "own"])
def test_the_engine_with_the_drafter_returns_the_plain_engines_tokens(plan):
    """Greedy output with the target-resident drafter is the plain
    engine's, token for token: with every draft wrong (1 token a round),
    every draft right (2 a round), both kinds across the slots of one
    round and changing from round to round, and the module's own drafts."""
    _, cfg, _, params = build(experts_held=8, expert_offset=0)
    plain = _serve(ServingEngine(cfg, params, slots=3, prefill_buckets=(16,)))
    drafter = MTPDrafter(cfg, params, slots=3, prefill_buckets=(16,),
                         disable_below=0.0)
    eng = ServingEngine(cfg, params, slots=3, prefill_buckets=(16,),
                        spec=drafter)
    rounds = _inject(drafter, plain, {
        "reject_all": lambda r, s: False, "accept_all": lambda r, s: True,
        "mixed": lambda r, s: (r + s) % 2 == 0, "own": lambda r, s: None,
    }[plan])
    assert _serve(eng) == plain
    st = drafter.stats()
    assert st["k"] == 2 and st["rounds"] > 0 and len(rounds) > 0
    new = sum(n for _, n in PROMPTS) - len(PROMPTS)   # the first is prefill's
    assert st["committed_tokens"] == new
    if plan == "reject_all":
        assert st["accepted_tokens"] == 0 and st["rounds"] == new
    if plan == "accept_all":
        # two tokens a round but where a request's budget cut the last one
        assert st["accepted_tokens"] >= (new - len(PROMPTS)) // 2
        assert st["rounds"] <= (new + len(PROMPTS)) // 2 + 1
    if plan == "mixed":
        assert 0 < st["accepted_tokens"] < st["rounds"]
    assert drafter.attn_rows()["draft_written"] > 0
    assert eng.stats()["spec"]["committed_tokens"] == new


def test_the_drafters_own_draft_is_the_modules_argmax():
    """What `propose` returns for a slot is the argmax of `forward_mtp` at
    the slot's last committed position: the drafter's prefill, its state
    and its two-row step compute the module."""
    config, cfg, _, params = build()
    drafter = MTPDrafter(cfg, params, slots=2, prefill_buckets=(16,),
                         disable_below=0.0)
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,),
                        spec=drafter)
    seen, real = [], drafter.propose

    def propose(next_tok, cursor):
        out = real(next_tok, cursor)
        seen.append((next_tok.copy(), cursor.copy(), out.copy()))
        return out

    drafter.propose = propose
    prompt = tokens((1, 10), seed=12)[0].tolist()
    pending = eng.submit(Request(req_id="a", prompt=tuple(prompt),
                                 max_new_tokens=8))
    eng.run_until_idle()
    stream = list(pending.result.tokens)
    assert len(seen) >= 4
    for next_tok, cursor, out in seen:
        c = int(cursor[0])                    # rows 0..c-1 cached, t_c pending
        assert stream[c] == int(next_tok[0])
        want = reference(params, jnp.asarray([stream[:c + 1]], jnp.int32),
                         config, "forward_mtp")[0, c - 1]
        assert int(out[0, 0]) == int(np.asarray(want).argmax())


def test_a_prefix_hit_leaves_the_slot_to_the_target():
    """A warm prefill computes the suffix's hidden states only, so the
    drafter leaves that slot stale and the output is still the plain
    engine's."""
    from kungfu_tpu.serving.prefix import PrefixCache

    _, cfg, _, params = build()
    drafter = MTPDrafter(cfg, params, slots=2, prefill_buckets=(16,),
                         disable_below=0.0)
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,),
                        spec=drafter, prefix_cache=PrefixCache(1 << 22))
    plain = ServingEngine(cfg, params, slots=2, prefill_buckets=(16,))
    shared = tokens((1, 12), seed=8)[0].tolist()
    outs = {}
    for name, e in (("spec", eng), ("plain", plain)):
        outs[name] = []
        for rid, tail in (("a", [3, 4]), ("b", [5, 6, 7])):
            p = e.submit(Request(req_id=rid, prompt=tuple(shared + tail),
                                 max_new_tokens=6))
            e.run_until_idle()
            outs[name].append(list(p.result.tokens))
    assert outs["spec"] == outs["plain"]
    assert eng.prefix.stats()["hit_tokens"] >= 12
    assert drafter.stats()["rounds"] == 5    # the cold request's rounds alone


def test_the_worker_arms_the_drafter_and_shows_its_counters(monkeypatch):
    """`--spec-draft mtp` over a model JSON with `mtp_layers` 1: the worker
    builds the drafter on the engine's own resident tree (nothing copied),
    `/metrics` carries the counters `SpecDecoder` keeps under the same
    names and `spec_committed_tokens`, the module's latent rows stand in
    `kft_serve_decode_attn_rows_total` under a kind of their own, and
    without `mtp_layers` the flag's value is refused."""
    import argparse
    import json

    from kungfu_tpu.monitor import counters as C
    from kungfu_tpu.serving.worker import ServingWorker

    counters = C.Counters()
    monkeypatch.setattr(C, "counters_if_enabled", lambda: counters)
    program = dict(CONFIG["program"], vocab_size=VOCAB, d_model=64, n_layers=3,
                   n_heads=HEADS, d_ff=96, max_len=64, rope_theta=10000.0,
                   n_kv_heads=0, experts_held=4)

    def worker(**over):
        return ServingWorker(argparse.Namespace(
            host="127.0.0.1", port=0, launch_rank=0, incarnation=0,
            config_server="", preset="tiny",
            model_json=json.dumps(dict(program, **over)), tier="",
            prefix_cache="off", spec_draft="mtp", spec_k=4, slots=2,
            queue_capacity=8, seed=3, weights_file="", warm_ship_s=0.15,
            buddy_timeout_s=3.0, request_timeout_s=30.0))

    w = worker()
    eng, drafter = w.engine, w.engine.spec
    assert isinstance(drafter, MTPDrafter) and drafter.k == 2
    assert all(a is b for a, b in zip(jax.tree.leaves(drafter.params),
                                      jax.tree.leaves(eng.params)))
    drafter.disable_below = 0.0
    pending = eng.submit(Request(prompt=(5, 17, 42, 7, 9), max_new_tokens=9))
    eng.run_until_idle()
    assert len(pending.result.tokens) == 14
    events = counters.events()
    assert events["spec_rounds"] >= 4
    assert events["spec_committed_tokens"] == 8      # the first is prefill's
    assert events.get("spec_accepted_tokens", 0) == drafter.stats()[
        "accepted_tokens"]
    text = counters.prometheus_text()
    assert 'kft_serve_decode_attn_rows_total{kind="draft_written"}' in text
    assert 'kft_serve_decode_attn_rows_total{kind="written"}' in text
    assert "spec_accept_rate" in text
    assert eng.stats()["spec"]["rounds"] == events["spec_rounds"]
    with pytest.raises(AssertionError, match="prediction module"):
        worker(mtp_layers=0)


# -- the shares add up -----------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_and_logits():
    """One expert layer, cut as a deployment cuts it: 4 expert shares of 4
    routed experts, 2 head shares of 2 heads, 2 vocabulary slices of 48
    ids.  The parts the shares give (the system's modules on sliced
    weights), the shared expert's part counted once, add up to the uncut
    reference's sublayers, and the slices' logits side by side are its
    logits.  (Under sandwich norms the post-norm is of the SUM: it is taken
    here after the parts are added, as a deployment takes it after its
    reduction.)"""
    config, cfg, _, params = build()
    p = params["block_1"]
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

    def attention(u):
        share_cfg = dataclasses.replace(cfg, n_heads=2)
        return sum(MLA(share_cfg).apply(
            {"params": head_share(p["attn"], first, 2)}, u) for first in (0, 2))

    def experts(u):
        parts = []
        for first in range(0, ROUTED, 4):
            share_cfg = dataclasses.replace(cfg, experts_held=4,
                                            expert_offset=first)
            m = dict(p["moe"], **{k: p["moe"][k][first:first + 4]
                                  for k in ("w_gate", "w_up", "w_down")})
            parts.append(MoE(share_cfg, shared_ffn=MLP).apply({"params": m}, u))
        shared = MLP(dataclasses.replace(cfg, d_ff=48)).apply(
            {"params": p["moe"]["shared"]}, u)
        return sum(parts) - (len(parts) - 1) * shared

    u = norm("ln1", x)
    assert close(attention(u), REF.mla(u, p["attn"], config))
    a = x + norm("ln1_post", attention(u))
    u = norm("ln2", a)
    assert close(experts(u), REF.moe(u, p["moe"], config))
    y = a + norm("ln2_post", experts(u))
    assert close(y, REF.block(x, p, config))
    final = _norm(cfg, "ln_f").apply({"params": params["ln_f"]}, y)
    logits = jnp.concatenate([
        _Head(dataclasses.replace(cfg, vocab_size=48)).apply(
            {"params": {"kernel": params["lm_head"]["kernel"][:, v:v + 48]}},
            final) for v in (0, 48)], axis=-1)
    want = REF._rms_norm(REF.block(x, p, config), params["ln_f"]["scale"], eps
                         ) @ params["lm_head"]["kernel"]
    assert close(logits, want)


# -- the kernels at this model's widths ------------------------------------------------


def test_gmm_tiles_divide_a_width_that_1024_does_not():
    """7680 = 60 x 128 is no multiple of 1024: the grouped matmul tiles it
    by 768, the widths it had keep their tiles, and the kernel's body in
    the interpreter agrees with `ragged_dot` at such a width (1920 = 15 x
    128, tiled by 640) on both sides of the matmul."""
    from kungfu_tpu.ops.gmm import _tile, grouped_matmul

    assert [_tile(d) for d in (64, 1024, 2048, 6144, 7680, 1920)] == [
        64, 1024, 1024, 1024, 768, 640]
    rs = np.random.RandomState(0)
    sizes = [0, 10, 0, 20, 1, 9, 0, 0]
    gs = jnp.asarray(sizes, jnp.int32)
    for k, n in ((1920, 256), (256, 1920)):
        lhs = jnp.asarray(rs.randn(64, k), jnp.bfloat16)
        rhs = jnp.asarray(rs.randn(8, k, n) / k ** 0.5, jnp.float32)
        got = grouped_matmul(lhs, rhs, gs, jnp.float32, interpret=True,
                             leftover=True)
        want = grouped_matmul(lhs, rhs, gs, jnp.float32, leftover=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=2e-3)
        assert not np.asarray(got)[sum(sizes):].any()


def test_the_kernels_lower_for_tpu_at_the_published_widths():
    """No chip: `jax.export` for the TPU platform.  The grouped matmul over
    8 held experts of [7680, 2048] and [2048, 7680] float32 at a decode
    step's 256 assignment rows, and the latent-attention kernel at 32
    heads for a decode step's one query row and the drafter's two, over 32
    slots of 4,096 rows of 576 bf16 numbers."""
    from kungfu_tpu.ops.decode_attn import MLA_KERNEL_NAME, mla_decode_attention
    from kungfu_tpu.ops.gmm import KERNEL_NAME, grouped_matmul

    for k, n in ((7680, 2048), (2048, 7680)):
        text = jax.export.export(
            jax.jit(lambda a, b, g: grouped_matmul(
                a, b, g, jnp.float32, interpret=False, leftover=True)),
            platforms=["tpu"])(
            jax.ShapeDtypeStruct((256, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((8, k, n), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.int32)).mlir_module()
        assert "tpu_custom_call" in text and KERNEL_NAME in text
    for rows in (1, 2):
        text = jax.export.export(
            jax.jit(lambda q, c, p: mla_decode_attention(
                q, c, p, 512, 192 ** -0.5, interpret=False)),
            platforms=["tpu"])(
            jax.ShapeDtypeStruct((32, rows, 32, 576), jnp.bfloat16),
            jax.ShapeDtypeStruct((32, 4096, 576), jnp.bfloat16),
            jax.ShapeDtypeStruct((32, rows), jnp.int32)).mlir_module()
        assert "tpu_custom_call" in text and MLA_KERNEL_NAME in text
