"""Where `MeshTrainer.init` puts the optimizer state: every sub-tree that
mirrors the parameters under the parameters' shardings, every other leaf
replicated on the mesh, every leaf committed, so that under fsdp a chip
holds and updates its share of Adam's moments and the first and second
step of a state run one compiled program.

`tests/unit/test_trainer.py` holds the trainer's steps against one
device's, layout by layout.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from kungfu_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss,
)
from kungfu_tpu.monitor import programs
from kungfu_tpu.plan import make_mesh
from kungfu_tpu.trainer import MeshTrainer

TOKENS = np.random.RandomState(0).randint(0, 64, (8, 32)).astype(np.int32)


def _trainer(mesh, tx):
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_len=32, dtype=jnp.float32, attention="full", mesh=mesh,
    )
    return MeshTrainer(
        TransformerLM(cfg),
        lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
        tx, mesh=mesh,
    )


def _four(**axes):
    return make_mesh(devices=jax.devices()[:4], **axes)


def _adam_states(opt_state):
    return [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]


def _specs(tree):
    return jax.tree.map(lambda x: x.sharding.spec, tree)


def _run(mesh, tx, steps):
    tr = _trainer(mesh, tx)
    st = tr.init(jax.random.PRNGKey(0), TOKENS)
    batch, losses = tr.shard_batch(TOKENS), []
    for _ in range(steps):
        st, m = tr.train_step(st, batch)
        losses.append(float(m["loss"]))
    return st, losses


@pytest.mark.parametrize("tx", [
    optax.adamw(1e-3),
    optax.chain(optax.clip_by_global_norm(1.0),
                optax.adamw(1e-3, mask=lambda p: jax.tree.map(
                    lambda x: x.ndim >= 2, p))),
], ids=["adamw", "clip_then_masked_adamw"])
def test_adams_moments_take_their_parameters_shardings(tx):
    programs.maybe_install()
    mesh = make_mesh(dp=2, fsdp=4)
    tr = _trainer(mesh, tx)
    st = tr.init(jax.random.PRNGKey(0), TOKENS)

    (adam,) = _adam_states(st.opt_state)
    want = _specs(st.params)
    assert _specs(adam.mu) == want and _specs(adam.nu) == want
    assert P("fsdp", None) in jax.tree.leaves(
        want, is_leaf=lambda x: isinstance(x, P))  # the rules did shard
    assert adam.count.sharding == NamedSharding(mesh, P())
    on_mesh = set(mesh.devices.flat)
    for leaf in jax.tree.leaves(st.opt_state):
        assert leaf.committed and set(leaf.sharding.device_set) == on_mesh
    for moments in (adam.mu, adam.nu):
        q = moments["block_0"]["attn"]["q"]["kernel"]
        assert q.sharding.spec == P("fsdp", None)
        assert q.addressable_shards[0].data.shape[0] * 4 == q.shape[0]

    placed = jax.tree.map(lambda x: x.sharding, st.opt_state)
    batch = tr.shard_batch(TOKENS)
    st, _ = tr.train_step(st, batch)
    compiled = programs.compile_watch_state()["compiles"]
    st, _ = tr.train_step(st, batch)
    assert programs.compile_watch_state()["compiles"] == compiled
    assert tr._step_fn._cache_size() == 1  # one program for both steps
    assert jax.tree.map(lambda x: x.sharding, st.opt_state) == placed


def test_the_update_is_the_same_mathematics_wherever_it_runs():
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-2))
    st_f, loss_f = _run(_four(fsdp=4), tx, 3)
    st_d, loss_d = _run(_four(dp=4), tx, 3)
    (adam_f,), (adam_d,) = _adam_states(st_f.opt_state), _adam_states(st_d.opt_state)
    assert not adam_f.mu["block_0"]["attn"]["q"]["kernel"].sharding.is_fully_replicated
    assert all(x.sharding.is_fully_replicated for x in jax.tree.leaves(adam_d))
    np.testing.assert_allclose(loss_f, loss_d, rtol=0, atol=1e-5)
    assert loss_f[-1] < loss_f[0]
    for got, ref in zip(jax.tree.leaves(st_f.params), jax.tree.leaves(st_d.params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=0, atol=1e-5)


def _row_scaled_sgd(lr):
    """A transformation whose state has the parameters' TREE but not their
    shapes: one running mean square a row (a factored statistic)."""
    def rest(x):
        return tuple(range(1, x.ndim))

    def init(params):
        return {"rows": jax.tree.map(lambda p: jnp.zeros(p.shape[:1], p.dtype), params),
                "count": jnp.zeros([], jnp.int32)}

    def update(grads, state, params=None):
        rows = jax.tree.map(
            lambda r, g: 0.9 * r + 0.1 * jnp.mean(jnp.square(g), axis=rest(g)),
            state["rows"], grads)
        updates = jax.tree.map(
            lambda g, r: -lr * g / jnp.expand_dims(1e-3 + jnp.sqrt(r), rest(g)),
            grads, rows)
        return updates, {"rows": rows, "count": state["count"] + 1}

    return optax.GradientTransformation(init, update)


def test_a_state_of_another_shape_than_its_parameter_is_replicated_and_trains():
    mesh = _four(fsdp=4)
    st, losses = _run(mesh, _row_scaled_sgd(1e-2), 3)
    replicated = NamedSharding(mesh, P())
    for leaf in jax.tree.leaves(st.opt_state):
        assert leaf.committed and leaf.sharding == replicated
    assert int(st.opt_state["count"]) == 3
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    _, losses_dp = _run(_four(dp=4), _row_scaled_sgd(1e-2), 3)
    np.testing.assert_allclose(losses, losses_dp, rtol=0, atol=1e-5)
