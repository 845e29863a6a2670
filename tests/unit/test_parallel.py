"""Parallelism tests: ring attention (SP), TP transformer sharding, MoE (EP),
pipeline (PP) — all on the 8-virtual-device CPU mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
import flax.linen as nn
from flax.linen import spmd as flax_spmd
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from kungfu_tpu.parallel.ring_attention import full_attention, ring_attention
from kungfu_tpu.parallel.sharding import rules_for_mesh
from kungfu_tpu.parallel.pp import pipeline_apply, stack_stage_params
from kungfu_tpu.plan import make_mesh

# compile-heavy: excluded from the fast dev loop (pytest -m 'not slow');
# CI runs the full suite unfiltered
pytestmark = pytest.mark.slow


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
    def test_matches_full_attention(self, causal):
        mesh = make_mesh(sp=8)
        B, L, H, D = 2, 64, 4, 16
        rng = np.random.RandomState(0)
        q, k, v = (rng.randn(B, L, H, D).astype(np.float32) * 0.5 for _ in range(3))

        spec = P(None, "sp", None, None)
        ring = jax.jit(
            shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )
        )
        got = np.asarray(ring(q, k, v))
        want = np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_gqa_matches_repeated_kv(self):
        """GQA ring (un-repeated rotating kv) == ring over manually
        repeated kv heads — the grouped einsums must reproduce the
        broadcast semantics exactly while moving H/Hkv times less data
        per hop."""
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        B, L, H, Hkv, D = 2, 32, 4, 2, 8
        rng = np.random.RandomState(5)
        q = rng.randn(B, L, H, D).astype(np.float32) * 0.5
        k = rng.randn(B, L, Hkv, D).astype(np.float32) * 0.5
        v = rng.randn(B, L, Hkv, D).astype(np.float32) * 0.5
        k_rep = np.repeat(k, H // Hkv, axis=2)
        v_rep = np.repeat(v, H // Hkv, axis=2)

        spec = P(None, "sp", None, None)

        def run(kk, vv, impl=None):
            return np.asarray(jax.jit(shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                               impl=impl),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            ))(q, kk, vv))

        np.testing.assert_allclose(
            run(k, v), run(k_rep, v_rep), rtol=2e-4, atol=2e-5
        )
        # the TPU-default flash impl too (off-TPU it runs the XLA
        # reference per block, but the GQA plumbing — un-repeated kv
        # through lax.switch incl. the skip() branch — is the same code)
        np.testing.assert_allclose(
            run(k, v, impl="flash"), run(k_rep, v_rep), rtol=2e-4,
            atol=2e-5,
        )
        # and the single-device reference agrees with ITS repeated form
        np.testing.assert_allclose(
            np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v))),
            np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k_rep),
                                      jnp.asarray(v_rep))),
            rtol=2e-4, atol=2e-5,
        )

    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
    def test_dma_rotation_matches_ppermute(self, causal, monkeypatch):
        """KV rotation on the Pallas DMA plane (ops.fused_matmul.ring_shift
        under KFT_PALLAS=interpret) is pure data movement: the ring output
        must be BIT-IDENTICAL to the ppermute fallback and match the
        single-device reference.  The enclosing shard_map opts out of the
        rep check (pallas_call has no replication rule — docs/pallas.md)."""

        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        B, L, H, D = 2, 64, 4, 16
        rng = np.random.RandomState(7)
        q, k, v = (rng.randn(B, L, H, D).astype(np.float32) * 0.5
                   for _ in range(3))
        spec = P(None, "sp", None, None)

        def run():
            return np.asarray(jax.jit(shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                               causal=causal),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False))(q, k, v))

        monkeypatch.delenv("KFT_PALLAS", raising=False)
        base = run()  # gate off -> the ppermute fallback
        monkeypatch.setenv("KFT_PALLAS", "interpret")
        dma = run()   # the DMA shift kernels under the interpreter
        assert np.array_equal(base, dma)
        want = np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=causal))
        np.testing.assert_allclose(dma, want, rtol=2e-4, atol=2e-5)

    def test_dma_rotation_grad_flows(self, monkeypatch):
        """Gradients through the scan + custom-VJP rotation (the VJP
        rotates the cotangent backwards) must match the single-device
        reference when the DMA hop is engaged."""

        monkeypatch.setenv("KFT_PALLAS", "interpret")
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        B, L, H, D = 1, 32, 2, 8
        rng = np.random.RandomState(8)
        q, k, v = (rng.randn(B, L, H, D).astype(np.float32) * 0.5
                   for _ in range(3))
        spec = P(None, "sp", None, None)

        def loss_ring(q, k, v):
            o = shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)(q, k, v)
            return jnp.sum(o ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_full = jax.grad(
            lambda q, k, v: jnp.sum(full_attention(q, k, v) ** 2),
            argnums=(0, 1, 2),
        )(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_grad_flows(self):
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        B, L, H, D = 1, 32, 2, 8
        rng = np.random.RandomState(1)
        q, k, v = (rng.randn(B, L, H, D).astype(np.float32) * 0.5 for _ in range(3))
        spec = P(None, "sp", None, None)

        def loss_ring(q, k, v):
            o = shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )(q, k, v)
            return jnp.sum(o ** 2)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)

    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
    def test_flash_impl_matches_full(self, causal):
        """Ring with the Pallas kernel as per-block compute (interpret mode
        on CPU) must equal single-device full attention."""
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        B, L, H, D = 1, 64, 2, 16
        rng = np.random.RandomState(2)
        q, k, v = (rng.randn(B, L, H, D).astype(np.float32) * 0.5 for _ in range(3))
        spec = P(None, "sp", None, None)

        ring = jax.jit(
            shard_map(
                lambda q, k, v: ring_attention(
                    q, k, v, axis_name="sp", causal=causal, impl="flash"),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )
        )
        got = np.asarray(ring(q, k, v))
        want = np.asarray(full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_flash_impl_grad_flows(self):
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        B, L, H, D = 1, 32, 2, 8
        rng = np.random.RandomState(3)
        q, k, v = (rng.randn(B, L, H, D).astype(np.float32) * 0.5 for _ in range(3))
        spec = P(None, "sp", None, None)

        def loss_ring(q, k, v):
            o = shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="sp", impl="flash"),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )(q, k, v)
            return jnp.sum(o ** 2)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_full):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


class TestTransformerTP:
    def _build(self, mesh, attention="full", n_experts=0):
        from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM

        cfg = TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_len=64, dtype=jnp.float32, attention=attention,
            n_experts=n_experts, experts_per_token=min(2, n_experts),
            mesh=mesh,
        )
        return TransformerLM(cfg), cfg

    def test_tp_sharded_train_step(self):
        """dp x tp mesh: logits identical to unsharded; params actually sharded."""
        from kungfu_tpu.models.transformer import lm_loss

        mesh = make_mesh(dp=2, tp=4)
        rules = rules_for_mesh(mesh)
        model, cfg = self._build(mesh)
        tokens = np.random.RandomState(0).randint(0, 128, size=(4, 32)).astype(np.int32)

        with nn.logical_axis_rules(rules):
            params = model.init(jax.random.PRNGKey(0), tokens)["params"]

            from kungfu_tpu.parallel.sharding import param_shardings

            shardings = param_shardings(mesh, params)
            params_arr = nn.meta.unbox(params)
            with mesh:
                placed = jax.jit(lambda p: p, out_shardings=shardings)(params_arr)

                def loss_fn(p, t):
                    return lm_loss(model.apply({"params": p}, t), t)

                step = jax.jit(jax.value_and_grad(loss_fn))
                loss, grads = step(placed, tokens)
                loss = float(loss)

        # sharded heads axis: q kernel [embed, d_model] split over tp on dim 1
        q_kernel = placed["block_0"]["attn"]["q"]["kernel"]
        assert q_kernel.sharding.spec == P(None, "tp"), q_kernel.sharding
        # unsharded reference
        loss_ref = float(lm_loss(model.apply({"params": params_arr}, tokens), tokens))
        assert np.isfinite(loss) and abs(loss - loss_ref) < 1e-3

    def test_ring_attention_inside_model(self):
        """sp mesh: model with ring attention == model with full attention."""
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        rules = rules_for_mesh(mesh)
        model_r, cfg = self._build(mesh, attention="ring")
        model_f, _ = self._build(mesh, attention="full")
        tokens = np.random.RandomState(1).randint(0, 128, size=(2, 32)).astype(np.int32)

        with nn.logical_axis_rules(rules):
            params = nn.meta.unbox(model_f.init(jax.random.PRNGKey(0), tokens)["params"])
            with mesh:
                logits_f = np.asarray(model_f.apply({"params": params}, tokens))
                logits_r = np.asarray(jax.jit(lambda p, t: model_r.apply({"params": p}, t))(params, tokens))
        np.testing.assert_allclose(logits_r, logits_f, rtol=2e-3, atol=2e-4)

    def test_moe_model_runs(self):
        """dp x ep mesh, experts placed over ep: the loss with its two router
        terms and its gradients equal the unsharded model's."""
        from kungfu_tpu.models.transformer import lm_loss_with_aux
        from kungfu_tpu.parallel.sharding import param_shardings

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "ep"))
        rules = rules_for_mesh(mesh)
        model, cfg = self._build(mesh, n_experts=4)
        tokens = np.random.RandomState(2).randint(0, 128, size=(4, 16)).astype(np.int32)
        step = jax.jit(jax.value_and_grad(
            lambda p, t: lm_loss_with_aux(model, p, t)))
        with nn.logical_axis_rules(rules):
            boxed = model.init(jax.random.PRNGKey(0), tokens)["params"]
            params = nn.meta.unbox(boxed)
            with mesh:
                placed = jax.device_put(params, param_shardings(mesh, boxed))
                loss, grads = step(placed, tokens)
        w_gate = placed["block_1"]["moe"]["w_gate"]
        assert w_gate.shape == (4, 64, 128)
        assert w_gate.sharding.spec[0] == "ep", w_gate.sharding
        plain, _ = self._build(None, n_experts=4)
        want, want_grads = jax.value_and_grad(
            lambda p, t: lm_loss_with_aux(plain, p, t))(params, tokens)
        assert abs(float(loss) - float(want)) < 1e-5
        for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-5, rtol=1e-4)


class TestPipeline:
    def test_matches_sequential(self):
        mesh = make_mesh(pp=4, devices=jax.devices()[:4])
        S, M, mb, d = 4, 8, 4, 16
        rng = np.random.RandomState(4)
        ws = [rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(S)]
        x = rng.randn(M, mb, d).astype(np.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        stacked = stack_stage_params([{"w": w} for w in ws])
        got = np.asarray(
            jax.jit(lambda p, xx: pipeline_apply(lambda pw, h: stage_fn(pw["w"], h), p, xx, mesh))(
                stacked, x
            )
        )
        want = x
        for w in ws:
            want = np.tanh(want @ w)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_pipeline_grad(self):
        mesh = make_mesh(pp=2, devices=jax.devices()[:2])
        S, M, mb, d = 2, 4, 2, 8
        rng = np.random.RandomState(5)
        ws = [rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(S)]
        x = rng.randn(M, mb, d).astype(np.float32)
        stacked = stack_stage_params([{"w": w} for w in ws])

        def loss_pp(p, xx):
            out = pipeline_apply(lambda pw, h: jnp.tanh(h @ pw["w"]), p, xx, mesh)
            return jnp.sum(out ** 2)

        def loss_seq(ws_, xx):
            h = xx
            for w in ws_:
                h = jnp.tanh(h @ w)
            return jnp.sum(h ** 2)

        g_pp = jax.jit(jax.grad(loss_pp))(stacked, x)["w"]
        g_seq = jax.grad(lambda ws_: loss_seq(ws_, jnp.asarray(x)))(
            [jnp.asarray(w) for w in ws]
        )
        for i in range(S):
            np.testing.assert_allclose(
                np.asarray(g_pp[i]), np.asarray(g_seq[i]), rtol=1e-3, atol=1e-4
            )
