"""A caller that passes no `live` mask traces the program it traced before
the mask existed (ISSUE 31: a free serving slot does no work).

`TransformerLM.__call__(tokens, train, live=None)`: only the serving
engine's slot-cache programs (`_decode`, `_verify_accept`) say which rows
are live.  A training step with experts, a prefill bucket and `generate()`
pass nothing, and what they lower to is, letter for letter, what commit
08f088d (PR 28, the parent of the PR that brought the mask) lowered to:
GOLDEN holds the SHA-256 of that commit's StableHLO text, made by running
this file as a script in a checkout of it
(`JAX_PLATFORMS=cpu PYTHONPATH=. python tests/unit/test_no_mask_programs.py`).

A later PR that changes what one of these programs computes on purpose
runs the script on its own tree, replaces the digest and says so; a PR
that only threads another optional argument through the model should
find them unchanged, which is the point.

ISSUE 44 changed the three decode-mode programs on purpose (a call's new
cache rows go through `_store_rows`: one `dynamic_update_slice` at batch 1,
one batched scatter of points a K/V leaf at `generate()`'s batch 2, where a
vmapped `dynamic_update_slice` wrote them); their digests are of PR 44's
own lowering.  The training step is the digest it was.
"""
import dataclasses
import hashlib
import os

import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

GOLDEN = {
    "train_step_with_experts":
        "ebc329ba1510025fe611c8b8e5ce9fb563a66f1d67980b2935baa9d7dea90db5",
    "prefill_bucket":
        "0c5d85c8ead13d7f3ac2becb0ae9a11b3f57858ba74a3ad6bd7d1936866a57c6",
    "prefill_bucket_through_the_kernels":
        "c1cef00bd4b56709842b12aaccff913d2cd253df855638734298c8f3dd5c4deb",
    "generate":
        "6ca608813c552414ec683421465656adcc8738953178038d75a0f65c0f80e8ce",
}


def _config():
    from kungfu_tpu.models.transformer import TransformerConfig

    # a dense block and an expert block, QK-norm, float32 so that the CPU
    # and the interpreter run what they are given
    return TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_len=48, rope=True, ffn="swiglu", norm="rms",
        qk_norm=True, n_experts=4, experts_per_token=2, moe_every=2,
        dtype=jnp.float32)


def _params(cfg):
    from kungfu_tpu.models.transformer import TransformerLM

    return nn.meta.unbox(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"])


def _train_step_with_experts():
    from kungfu_tpu.models.transformer import TransformerLM, lm_loss_with_aux

    cfg = dataclasses.replace(_config(), remat=True)
    model = TransformerLM(cfg)
    step = jax.jit(jax.value_and_grad(
        lambda p, t: lm_loss_with_aux(model, p, t)))
    return step.lower(_params(cfg), jax.ShapeDtypeStruct((2, 16), jnp.int32))


def _prefill_bucket():
    from kungfu_tpu.serving import ServingEngine

    cfg = _config()
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), _params(cfg))
    eng = ServingEngine(cfg, zeros, slots=2, prefill_buckets=(16,))
    return eng._prefill.lower(eng.params, eng._small_cache0,
                              jax.ShapeDtypeStruct((1, 16), jnp.int32), 5, 5)


def _generate():
    from kungfu_tpu.models.transformer import TransformerLM, _generate_compiled

    dcfg = dataclasses.replace(_config(), decode=True, attention="auto",
                               head="dense")
    prompt = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    cache = jax.eval_shape(TransformerLM(dcfg).init, jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))["cache"]
    return _generate_compiled(dcfg, 2, 4, 6, 0.0).lower(
        _params(dcfg), cache, prompt, jax.random.PRNGKey(0))


PROGRAMS = {
    "train_step_with_experts": ("off", _train_step_with_experts),
    "prefill_bucket": ("off", _prefill_bucket),
    # the grouped matmul and the attention as Pallas bodies: the path a
    # TPU takes, where a select after the kernel would show
    "prefill_bucket_through_the_kernels": ("interpret", _prefill_bucket),
    "generate": ("off", _generate),
}


def _digest(lower) -> str:
    return hashlib.sha256(lower().as_text().encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_without_a_mask_the_program_is_the_parents(name, monkeypatch):
    mode, lower = PROGRAMS[name]
    monkeypatch.setenv("KFT_PALLAS", mode)
    assert _digest(lower) == GOLDEN[name], (
        f"{name} no longer lowers to what commit 08f088d lowered to: if "
        "that is meant, run this file as a script and replace GOLDEN")


if __name__ == "__main__":
    for name, (mode, lower) in PROGRAMS.items():
        os.environ["KFT_PALLAS"] = mode
        print(f'    "{name}":\n        "{_digest(lower)}",')
