"""Optimizer algebra tests (reference: tests/python/integration/test_optimizers.py
+ test_mnist_slp.py convergence check, run on the 8-virtual-device CPU mesh)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from kungfu_tpu.plan import make_mesh
from kungfu_tpu.optimizers import (
    synchronous_sgd,
    synchronous_averaging,
    pair_averaging,
    adaptive_sgd,
    gradient_noise_scale,
    gradient_variance,
    get_noise_scale,
    get_gradient_variance,
)
from kungfu_tpu.initializer import broadcast_params, sync_check

from jax import shard_map

N = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(dp=-1)


def run_spmd(mesh, fn, *args, specs=P("dp")):
    f = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs)
    return jax.jit(f)(*args)


def quad_grads(params, data):
    """grad of 0.5*|w - data|^2 per replica: (w - data)."""
    return params - data


class TestSynchronousSGD:
    def test_replicas_stay_identical(self, mesh):
        tx = synchronous_sgd(optax.sgd(0.5), axis_name="dp")
        w0 = np.zeros((N, 4), np.float32)
        data = np.random.RandomState(0).randn(N, 4).astype(np.float32)

        def step(w, d):
            state = tx.init(w[0])
            g = quad_grads(w[0], d[0])
            u, _ = tx.update(g, state, w[0])
            return (w[0] + u)[None]

        w1 = np.asarray(run_spmd(mesh, step, w0, data))
        # all replicas identical == averaged gradient applied
        want = -0.5 * (0.0 - data.mean(axis=0))
        for r in range(N):
            np.testing.assert_allclose(w1[r], 0.0 - 0.5 * (0.0 - data.mean(0)), rtol=1e-5)

    def test_converges_to_mean(self, mesh):
        """S-SGD on 0.5|w-d_i|^2 converges to mean(d_i): the distributed
        consensus sanity check from the reference's MNIST SLP test."""
        tx = synchronous_sgd(optax.sgd(0.3), axis_name="dp")
        data = np.random.RandomState(1).randn(N, 3).astype(np.float32)

        def train(w, d):
            state = tx.init(w[0])

            def body(carry, _):
                w, s = carry
                g = quad_grads(w, d[0])
                u, s = tx.update(g, s, w)
                return (w + u, s), None

            (wf, _), _ = jax.lax.scan(body, (w[0], state), None, length=50)
            return wf[None]

        wf = np.asarray(run_spmd(mesh, train, np.zeros((N, 3), np.float32), data))
        np.testing.assert_allclose(wf[0], data.mean(axis=0), rtol=1e-4, atol=1e-5)


class TestReduceImpls:
    """Every strategy-selected reduction schedule equals plain pmean
    (the in-step analog of the reference's swappable allreduce strategies)."""

    @pytest.mark.parametrize("impl", ["rs_ag", "ring"])
    def test_flat_axis_impls(self, mesh, impl):
        tx = synchronous_sgd(optax.sgd(0.5), axis_name="dp", impl=impl)
        ref = synchronous_sgd(optax.sgd(0.5), axis_name="dp", impl="pmean")
        data = np.random.RandomState(1).randn(N, 5).astype(np.float32)
        w0 = np.zeros((N, 5), np.float32)

        def step(t):
            def body(w, d):
                state = t.init(w[0])
                g = quad_grads(w[0], d[0])
                u, _ = t.update(g, state, w[0])
                return (w[0] + u)[None]

            return np.asarray(run_spmd(mesh, body, w0, data))

        np.testing.assert_allclose(step(tx), step(ref), rtol=1e-5)

    def test_hierarchical_on_dcn_ici(self):
        from kungfu_tpu.plan import make_hierarchical_mesh

        hmesh = make_hierarchical_mesh(2)
        axes = ("dcn", "ici")
        tx = synchronous_sgd(optax.sgd(0.5), axis_name=axes, impl="hierarchical")
        ref = synchronous_sgd(optax.sgd(0.5), axis_name=axes, impl="pmean")
        data = np.random.RandomState(2).randn(N, 5).astype(np.float32)
        w0 = np.zeros((N, 5), np.float32)

        def step(t):
            def body(w, d):
                state = t.init(w[0])
                g = quad_grads(w[0], d[0])
                u, _ = t.update(g, state, w[0])
                return (w[0] + u)[None]

            f = shard_map(body, mesh=hmesh, in_specs=P(axes), out_specs=P(axes))
            return np.asarray(jax.jit(f)(w0, data))

        np.testing.assert_allclose(step(tx), step(ref), rtol=1e-5)

    def test_bad_impl_raises(self):
        with pytest.raises(ValueError):
            synchronous_sgd(optax.sgd(0.1), axis_name="dp", impl="bogus")
        with pytest.raises(ValueError):
            synchronous_sgd(optax.sgd(0.1), axis_name="dp", impl="hierarchical")


class TestSMA:
    def test_pulls_toward_average(self, mesh):
        tx = synchronous_averaging(optax.sgd(0.0), axis_name="dp", alpha=0.1)
        w0 = np.random.RandomState(2).randn(N, 4).astype(np.float32)

        def step(w):
            state = tx.init(w[0])
            u, _ = tx.update(jnp.zeros_like(w[0]), state, w[0])
            return (w[0] + u)[None]

        w1 = np.asarray(run_spmd(mesh, step, w0))
        want = (1 - 0.1) * w0 + 0.1 * w0.mean(axis=0, keepdims=True)
        np.testing.assert_allclose(w1, want, rtol=1e-5)

    def test_models_converge_over_steps(self, mesh):
        tx = synchronous_averaging(optax.sgd(0.0), axis_name="dp", alpha=0.5)
        w0 = np.random.RandomState(3).randn(N, 2).astype(np.float32)

        def train(w):
            state = tx.init(w[0])

            def body(carry, _):
                w, s = carry
                u, s = tx.update(jnp.zeros_like(w), s, w)
                return (w + u, s), None

            (wf, _), _ = jax.lax.scan(body, (w[0], state), None, length=30)
            return wf[None]

        wf = np.asarray(run_spmd(mesh, train, w0))
        spread = wf.std(axis=0).max()
        assert spread < 1e-4, f"SMA replicas did not converge, spread={spread}"
        np.testing.assert_allclose(wf[0], w0.mean(axis=0), rtol=1e-3, atol=1e-4)


class TestPairAveraging:
    def test_mass_conserved_and_mixing(self, mesh):
        """Directed gossip preserves the mean and shrinks the spread."""
        tx = pair_averaging(optax.sgd(0.0), axis_name="dp", axis_size=N, seed=4)
        w0 = np.random.RandomState(4).randn(N, 3).astype(np.float32)

        def train(w):
            state = tx.init(w[0])

            def body(carry, _):
                w, s = carry
                u, s = tx.update(jnp.zeros_like(w), s, w)
                return (w + u, s), None

            (wf, _), _ = jax.lax.scan(body, (w[0], state), None, length=40)
            return wf[None]

        wf = np.asarray(run_spmd(mesh, train, w0))
        # directed ring gossip with uniform shifts preserves the global mean
        np.testing.assert_allclose(wf.mean(axis=0), w0.mean(axis=0), rtol=1e-3, atol=1e-4)
        assert wf.std(axis=0).max() < 0.2 * w0.std(axis=0).max()

    def test_roundrobin_selector(self, mesh):
        tx = pair_averaging(
            optax.sgd(0.1), axis_name="dp", axis_size=N, selector="roundrobin"
        )
        w0 = np.random.RandomState(5).randn(N, 2).astype(np.float32)
        d = np.random.RandomState(6).randn(N, 2).astype(np.float32)

        def step(w, dd):
            state = tx.init(w[0])
            g = quad_grads(w[0], dd[0])
            u, _ = tx.update(g, state, w[0])
            return (w[0] + u)[None]

        w1 = np.asarray(run_spmd(mesh, step, w0, d))
        assert np.isfinite(w1).all()
        # step 0 roundrobin shift=1: replica i mixed with i+1, plus the local
        # gradient update (grad was evaluated at w0 here)
        mixed = 0.5 * (w0 + np.roll(w0, -1, axis=0))
        want = mixed - 0.1 * (w0 - d)
        np.testing.assert_allclose(w1, want, rtol=1e-4, atol=1e-5)


class TestAdaptiveSGD:
    def test_switch_unifies_models(self, mesh):
        tx = adaptive_sgd(optax.sgd(0.0), switch_step=3, axis_name="dp", alpha=0.0)
        w0 = np.random.RandomState(7).randn(N, 2).astype(np.float32)

        def train(w, steps):
            state = tx.init(w[0])

            def body(carry, _):
                w, s = carry
                u, s = tx.update(jnp.zeros_like(w), s, w)
                return (w + u, s), None

            (wf, _), _ = jax.lax.scan(body, (w[0], state), None, length=steps)
            return wf[None]

        # before switch (alpha=0, lr=0): models stay distinct
        w_before = np.asarray(run_spmd(mesh, functools.partial(train, steps=3), w0))
        assert w_before.std(axis=0).max() > 1e-3
        # after the switch step ran: everyone snapped to rank 0's model
        w_after = np.asarray(run_spmd(mesh, functools.partial(train, steps=4), w0))
        np.testing.assert_allclose(w_after, np.tile(w0[0], (N, 1)), rtol=1e-5)


class TestMonitors:
    def test_noise_scale_positive_for_noisy_grads(self, mesh):
        tx = gradient_noise_scale(
            synchronous_sgd(optax.sgd(0.1)), local_batch_size=32, axis_name="dp", axis_size=N
        )
        d = 4096  # large enough that the single-step estimator is stable
        g = np.random.RandomState(8).randn(N, d).astype(np.float32) + 0.3

        def step(gg):
            state = tx.init(jnp.zeros(d))
            u, state = tx.update(gg[0], state, jnp.zeros(d))
            return get_noise_scale(state)[None].astype(jnp.float32)

        gns = np.asarray(run_spmd(mesh, step, g))
        assert np.isfinite(gns).all()
        # per-replica estimates vary (each uses its own local grad norm, as in
        # the reference); the cluster-mean estimate must be positive
        assert gns.mean() > 0

    def test_noise_scale_zero_for_identical_grads(self, mesh):
        tx = gradient_noise_scale(
            synchronous_sgd(optax.sgd(0.1)), local_batch_size=32, axis_name="dp", axis_size=N
        )
        g = np.tile(np.random.RandomState(9).randn(16).astype(np.float32), (N, 1))

        def step(gg):
            state = tx.init(jnp.zeros(16))
            u, state = tx.update(gg[0], state, jnp.zeros(16))
            return get_noise_scale(state)[None].astype(jnp.float32)

        gns = np.asarray(run_spmd(mesh, step, g))
        np.testing.assert_allclose(gns, 0.0, atol=1e-4)

    def test_grad_variance(self, mesh):
        tx = gradient_variance(optax.sgd(0.1), axis_name="dp")
        g = np.random.RandomState(10).randn(N, 8).astype(np.float32)

        def step(gg):
            state = tx.init(jnp.zeros(8))
            u, state = tx.update(gg[0], state, jnp.zeros(8))
            return get_gradient_variance(state)[None].astype(jnp.float32)

        var = np.asarray(run_spmd(mesh, step, g))
        # E|g|^2 - |Eg|^2 computed in numpy
        want = (g ** 2).sum(axis=1).mean() - (g.mean(axis=0) ** 2).sum()
        np.testing.assert_allclose(var[0], want, rtol=1e-4)


class TestInitializer:
    def test_broadcast_params(self, mesh):
        w0 = np.random.RandomState(11).randn(N, 4).astype(np.float32)

        def step(w):
            return broadcast_params(w[0], axis_name="dp")[None]

        w1 = np.asarray(run_spmd(mesh, step, w0))
        np.testing.assert_allclose(w1, np.tile(w0[0], (N, 1)), rtol=1e-6)

    def test_sync_check(self, mesh):
        same = np.tile(np.arange(4, dtype=np.float32), (N, 1))
        diff = same.copy()
        diff[5] += 1

        def step(w):
            return sync_check(w[0], axis_name="dp")[None].astype(jnp.int32)

        assert np.asarray(run_spmd(mesh, step, same)).all()
        assert not np.asarray(run_spmd(mesh, step, diff)).any()


def test_lm_adamw_preset():
    """Warmup->cosine schedule, rank>=2 weight-decay mask, global clip."""
    import optax

    from kungfu_tpu.optimizers import lm_adamw

    params = {"w": jnp.ones((4, 4)), "scale": jnp.ones((4,))}
    tx = lm_adamw(1e-2, warmup_steps=2, total_steps=10)
    st = tx.init(params)
    g = {"w": jnp.ones((4, 4)), "scale": jnp.ones((4,))}
    p = params
    for _ in range(3):
        upd, st = tx.update(g, st, p)
        p = optax.apply_updates(p, upd)
    # matrices decayed toward zero faster than the (undecayed) vector moved
    assert float(p["w"].mean()) < 1.0
    # the vector saw NO weight decay: with constant grads its update is the
    # pure adam step; verify by comparing against weight_decay=0
    tx0 = lm_adamw(1e-2, warmup_steps=2, total_steps=10, weight_decay=0.0)
    st0 = tx0.init(params)
    p0 = params
    for _ in range(3):
        upd, st0 = tx0.update(g, st0, p0)
        p0 = optax.apply_updates(p0, upd)
    np.testing.assert_allclose(np.asarray(p["scale"]), np.asarray(p0["scale"]),
                               atol=1e-7)
    assert not np.allclose(np.asarray(p["w"]), np.asarray(p0["w"]))
