"""Ulysses (all_to_all head<->seq) sequence parallelism: must equal
single-device full attention, gradients included, and train end-to-end via
TransformerConfig(attention="ulysses")."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from kungfu_tpu.parallel.ring_attention import full_attention
from kungfu_tpu.parallel.ulysses import ulysses_attention
from kungfu_tpu.plan import make_mesh

# compile-heavy: excluded from the fast dev loop (pytest -m 'not slow');
# CI runs the full suite unfiltered
pytestmark = pytest.mark.slow

SPEC = P(None, "sp", None, None)


def _qkv(B=2, L=64, H=8, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(B, L, H, D).astype(np.float32) * 0.5 for _ in range(3))


class TestUlysses:
    @pytest.mark.parametrize("sp,hkv", [(4, 8), (4, 2)],
                             ids=["kv-split", "kv-fallback"])
    def test_gqa_matches_repeated_kv(self, sp, hkv):
        """GQA kv through ulysses: when sp divides Hkv the all_to_all
        moves the un-repeated payload; otherwise it falls back to the
        internal broadcast — both must equal attention over manually
        repeated kv heads."""
        mesh = make_mesh(sp=sp, devices=jax.devices()[:sp])
        B, L, H, D = 2, 32, 8, 16
        rng = np.random.RandomState(7)
        q = rng.randn(B, L, H, D).astype(np.float32) * 0.5
        k = rng.randn(B, L, hkv, D).astype(np.float32) * 0.5
        v = rng.randn(B, L, hkv, D).astype(np.float32) * 0.5
        k_rep = np.repeat(k, H // hkv, axis=2)
        v_rep = np.repeat(v, H // hkv, axis=2)

        def run(kk, vv):
            return np.asarray(jax.jit(shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
                mesh=mesh, in_specs=(SPEC, SPEC, SPEC), out_specs=SPEC,
            ))(q, kk, vv))

        np.testing.assert_allclose(
            run(k, v), run(k_rep, v_rep), rtol=2e-4, atol=2e-5
        )

        # gradients through the kv-split path must also match the
        # repeated-kv oracle (group-summed over each kv head's queries)
        def loss(kk, vv):
            o = shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
                mesh=mesh, in_specs=(SPEC, SPEC, SPEC), out_specs=SPEC,
            )(q, kk, vv)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        gk, gv = jax.grad(loss, argnums=(0, 1))(jnp.asarray(k),
                                                jnp.asarray(v))
        gk_rep, gv_rep = jax.grad(loss, argnums=(0, 1))(
            jnp.asarray(k_rep), jnp.asarray(v_rep)
        )
        G = H // hkv
        B2, L2 = k.shape[:2]
        fold = lambda g: np.asarray(g).reshape(B2, L2, hkv, G, -1).sum(3)
        np.testing.assert_allclose(np.asarray(gk), fold(gk_rep),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gv), fold(gv_rep),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
    def test_matches_full_attention(self, causal):
        mesh = make_mesh(sp=8)
        q, k, v = _qkv()
        uly = jax.jit(
            shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp", causal=causal),
                mesh=mesh, in_specs=(SPEC, SPEC, SPEC), out_specs=SPEC,
            )
        )
        got = np.asarray(uly(q, k, v))
        want = np.asarray(
            full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_grad_matches_full(self):
        mesh = make_mesh(sp=4, devices=jax.devices()[:4])
        q, k, v = _qkv(B=1, L=32, H=4, D=8, seed=1)

        def loss_uly(q, k, v):
            o = shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
                mesh=mesh, in_specs=(SPEC, SPEC, SPEC), out_specs=SPEC,
            )(q, k, v)
            return jnp.sum(o ** 2)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v) ** 2)

        g_u = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
        g_f = jax.grad(loss_full, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
        )
        for a, b in zip(g_u, g_f):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)

    def test_rejects_indivisible_heads(self):
        mesh = make_mesh(sp=8)
        q, k, v = _qkv(H=4)  # 4 heads on sp=8
        uly = shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(SPEC, SPEC, SPEC), out_specs=SPEC,
        )
        with pytest.raises(ValueError, match="divide"):
            jax.jit(uly)(q, k, v)

    def test_transformer_trains_with_ulysses(self):
        """MeshTrainer + attention='ulysses' on dp x sp matches unsharded."""
        import optax

        from kungfu_tpu.models.transformer import (
            TransformerConfig, TransformerLM, lm_loss,
        )
        from kungfu_tpu.plan import MeshSpec
        from kungfu_tpu.trainer import MeshTrainer

        tokens = np.random.RandomState(0).randint(0, 64, size=(8, 32)).astype(np.int32)
        mesh = make_mesh(MeshSpec.make(dp=4, sp=2))
        base = dict(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            max_len=32, dtype=jnp.float32,
        )

        def loss_fn(model, params, toks):
            return lm_loss(model.apply({"params": params}, toks), toks)

        model = TransformerLM(
            TransformerConfig(mesh=mesh, attention="ulysses", **base)
        )
        trainer = MeshTrainer(model, loss_fn, optax.sgd(0.05), mesh=mesh)
        state = trainer.init(jax.random.PRNGKey(0), tokens)
        batch = trainer.shard_batch(tokens)
        for _ in range(2):
            state, metrics = trainer.train_step(state, batch)
        got = float(np.asarray(metrics["loss"]))

        # unsharded reference
        import flax.linen as nn

        plain = TransformerLM(TransformerConfig(**base))
        params = nn.meta.unbox(plain.init(jax.random.PRNGKey(0), tokens)["params"])
        tx = optax.sgd(0.05)
        opt = tx.init(params)

        @jax.jit
        def step(p, s):
            loss, g = jax.value_and_grad(
                lambda pp: lm_loss(plain.apply({"params": pp}, tokens), tokens)
            )(p)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s, loss

        for _ in range(2):
            params, opt, want = step(params, opt)
        assert np.isclose(got, float(want), rtol=2e-4), (got, float(want))
