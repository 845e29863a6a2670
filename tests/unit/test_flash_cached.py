"""The flash kernels as cached programs, and the backward arm's rule (tier-1).

`ops/flash.py` keeps each kernel call (`_fwd_pallas`, `_bwd_pallas`) under a
module-level `jax.jit` whose static arguments carry everything that is not an
array, so a model's N layers trace each kernel body once and the lowered step
holds each Mosaic kernel once.  What the environment decides (`KFT_PALLAS`,
`KFT_PALLAS_VMEM_MIB`) is resolved outside the cached program and is part of
its key.  The backward arm is chosen by `pallas_mode` alone: the kernels
wherever Pallas runs, blocked XLA where it does not.
"""
import re

import numpy as np
import pytest

import jax
import jax.export  # noqa: F401  (see test_flash_lowering.py)
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import kungfu_tpu.ops.flash as F
from kungfu_tpu.ops.flash import flash_attention, flash_attention_with_lse

#: MHA: the forward and the one-pass backward; GQA keeps the dq + dk/dv pair
MHA_KERNELS = ["kft_flash_bwd", "kft_flash_fwd"]
GQA_KERNELS = ["kft_flash_bwd_dkdv", "kft_flash_bwd_dq", "kft_flash_fwd"]


def _rand(b, l, h, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b, l, h, d), dtype) for k in ks]


def _grads(fn, *args):
    return jax.grad(fn, argnums=(0, 1, 2))(*args)


def _tpu_module(fn, *args) -> str:
    """The StableHLO of `fn` lowered for a TPU from the CPU (Mosaic runs)."""
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args).mlir_module()


def _stacked(n, lse_too=False):
    def loss(q, k, v):
        x = q
        for _ in range(n):
            x = flash_attention(x, k, v, causal=True, interpret=False)
        if lse_too:  # the ring merge's entry point shares the same programs
            o, lse = flash_attention_with_lse(x, k, v, causal=True,
                                              interpret=False)
            x = o + jnp.sin(lse).transpose(0, 2, 1)[..., None].astype(o.dtype)
        return jnp.sum(x.astype(jnp.float32) ** 2)

    return loss


@pytest.mark.parametrize("hkv,lse_too,kernels", [
    (2, False, MHA_KERNELS), (2, True, MHA_KERNELS), (1, False, GQA_KERNELS)])
def test_stacked_layers_hold_each_kernel_once(hkv, lse_too, kernels):
    """4 layers forward and backward: each Mosaic kernel once in the module
    (2 calls for MHA, 3 under GQA — not 8 or 12), under the kernels' own
    names, whichever entry point calls them."""
    q = jnp.zeros((1, 512, 2, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 512, hkv, 64), jnp.bfloat16)
    txt = _tpu_module(
        lambda q, k, v: _grads(_stacked(4, lse_too), q, k, v), q, kv, kv)
    assert txt.count("stablehlo.custom_call @tpu_custom_call") == len(kernels)
    assert sorted(re.findall(r'kernel_name = "([^"]+)"', txt)) == kernels


def test_pallas_mode_change_is_a_new_program(monkeypatch):
    """KFT_PALLAS is resolved outside the cached callable: flipping it
    between two traces of the same entry point takes effect both ways."""
    q = jnp.zeros((1, 64, 1, 16), jnp.float32)

    def traced_kernels():
        text = str(jax.make_jaxpr(
            lambda q, k, v: _grads(
                lambda q, k, v: jnp.sum(flash_attention(q, k, v)), q, k, v)
        )(q, q, q))
        return sorted(set(re.findall(r"kft_flash_\w+", text)))

    monkeypatch.delenv("KFT_PALLAS", raising=False)
    monkeypatch.delenv("KFT_PALLAS_INTERPRET", raising=False)
    assert traced_kernels() == []
    monkeypatch.setenv("KFT_PALLAS", "interpret")
    assert traced_kernels() == MHA_KERNELS
    monkeypatch.delenv("KFT_PALLAS")
    assert traced_kernels() == []


def test_vmem_budget_change_is_a_new_program(monkeypatch):
    """KFT_PALLAS_VMEM_MIB reaches Mosaic through a static argument: the
    lowered kernel carries the budget of the trace that asked, not the
    first one's."""
    q = jnp.zeros((1, 256, 1, 64), jnp.bfloat16)

    def limits():
        txt = _tpu_module(
            lambda q, k, v: flash_attention(q, k, v, interpret=False), q, q, q)
        return re.findall(r'\\22size\\22: (\d+)', txt)

    monkeypatch.delenv("KFT_PALLAS_VMEM_MIB", raising=False)
    assert limits() == [str(64 << 20)]
    monkeypatch.setenv("KFT_PALLAS_VMEM_MIB", "24")
    assert limits() == [str(24 << 20)]
    monkeypatch.delenv("KFT_PALLAS_VMEM_MIB")
    assert limits() == [str(64 << 20)]


def _two_layer_loss(arm, **kw):
    def f(q, k, v):
        x = q
        for _ in range(2):  # the second layer hits the cache
            x = flash_attention(x, k, v, causal=True, block_q=32, block_k=32,
                                interpret=True, backward=arm, **kw)
        return jnp.sum(x ** 2)

    return f


@pytest.mark.parametrize("hkv,l,window", [(2, 96, None), (1, 96, None),
                                          (2, 100, 40)])
def test_cached_kernel_grads_match_xla_arm(hkv, l, window):
    """The kernel backward under the interpreter (one pass for MHA, the
    pair for GQA; an unpadded length under a window) against blocked XLA."""
    q, _, _ = _rand(1, l, 2, 16, seed=3)
    _, k, v = _rand(1, l, hkv, 16, seed=4)
    got = _grads(_two_layer_loss(None, window=window), q, k, v)
    want = _grads(_two_layer_loss("xla", window=window), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_one_pass_backward_matches_the_pair(monkeypatch):
    """MHA rows too long for the one-pass kernel's VMEM residents fall back
    to the dq + dk/dv pair: same gradients, the other kernels."""
    assert F._fused_bwd_fits(28672, 128, jnp.bfloat16, 64 << 20)
    assert not F._fused_bwd_fits(32768, 128, jnp.bfloat16, 64 << 20)
    # a 64-wide row fills 128 lanes: no more positions fit than at 128
    assert not F._fused_bwd_fits(32768, 64, jnp.bfloat16, 64 << 20)
    assert not F._fused_bwd_fits(20480, 128, jnp.float32, 64 << 20)
    q, k, v = _rand(1, 96, 2, 16, seed=11)
    one = _grads(_two_layer_loss(None), q, k, v)
    monkeypatch.setattr(F, "_fused_bwd_fits", lambda *a: False)
    # the patch is no part of the cached program's key: a new budget is
    monkeypatch.setenv("KFT_PALLAS_VMEM_MIB", "63")
    text = str(jax.make_jaxpr(
        lambda *a: _grads(_two_layer_loss(None), *a))(q, k, v))
    assert sorted(set(re.findall(r"kft_flash_\w+", text))) == GQA_KERNELS
    for a, b in zip(one, _grads(_two_layer_loss(None), q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize(
    "mode,l,backward,expect",
    [
        ("off", 96, None, "xla"),            # plain CPU: lowers anywhere
        ("interpret", 96, None, "pallas"),   # short MHA: no threshold
        ("compiled", 2048, None, "pallas"),  # the training cells' shape
        ("compiled", 2048, "xla", "xla"),    # explicit argument wins
        ("compiled", 96, "xla", "xla"),      # the A/B arm, at any length
        ("interpret", 96, "xla", "xla"),
        ("off", 96, "pallas", "pallas"),     # ... over the mode too
        ("interpret", 96, "pallas", "pallas"),
        ("off", 96, "xla", "xla"),
    ],
)
def test_bwd_auto_selection(monkeypatch, mode, l, backward, expect):
    """The arm is chosen by `pallas_mode` (what the code can see) and the
    explicit argument — by no length and no environment variable.  Both
    arms and the forward are stubbed: this is the rule, not the kernels."""
    calls = []

    def recorder(name):
        def fake(q, k, v, *a, **kw):
            calls.append(name)
            return jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)

        return fake

    def fake_fwd(q, k, v, scale, causal, *a):
        return F._fwd_reference(q, k, v, scale, causal)

    monkeypatch.setattr(F, "_mode", lambda interpret=None: mode)
    monkeypatch.setattr(F, "_bwd_pallas", recorder("pallas"))
    monkeypatch.setattr(F, "_bwd_blocked", recorder("xla"))
    monkeypatch.setattr(F, "_flash_fwd", fake_fwd)

    q, k, v = _rand(1, l, 2, 16, seed=5)
    _grads(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, backward=backward) ** 2), q, k, v)
    assert calls == [expect]


def test_kernels_inside_shard_map(monkeypatch):
    """Forward and gradient of the cached kernels inside a manual region
    over a 2-device CPU mesh (the four-chip cell's path, `cfg.mesh`; the
    model opts out of the vma check where the kernels engage, as here)
    against the unsharded call."""
    monkeypatch.setenv("KFT_PALLAS", "interpret")
    mesh = Mesh(np.array(jax.devices()[:2]), ("fsdp",))
    q, k, v = _rand(2, 64, 2, 16, seed=9)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32)

    spec = P("fsdp")
    sharded = jax.shard_map(attn, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=spec, check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(sharded)(q, k, v)),
                               np.asarray(attn(q, k, v)), atol=1e-6)
    got = jax.jit(lambda *a: _grads(lambda *b: jnp.sum(sharded(*b) ** 2), *a))(
        q, k, v)
    want = _grads(lambda *b: jnp.sum(attn(*b) ** 2), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
