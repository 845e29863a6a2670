"""MeshTrainer (public multi-axis trainer): sharded steps must match the
unsharded single-device computation, across dp x tp, dp x sp, ep and fsdp
meshes; under fsdp parameters and moments are stored one share a device and
a checkpoint restores under another layout."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from kungfu_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss,
)
from kungfu_tpu.plan import MeshSpec, make_mesh
from kungfu_tpu.trainer import MeshTrainer


def _loss_fn(model, params, toks):
    return lm_loss(model.apply({"params": params}, toks), toks)


def _cfg(mesh=None, **kw):
    base = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_len=32, dtype=jnp.float32, mesh=mesh,
    )
    base.update(kw)
    return TransformerConfig(**base)


def _tokens(batch=4):
    return np.random.RandomState(0).randint(0, 64, size=(batch, 32)).astype(np.int32)


def _single_device_run(tokens, tx, steps, **cfg_kw):
    """(losses, host params) of `steps` steps on one device, no mesh."""
    import flax.linen as nn

    model = TransformerLM(_cfg(**cfg_kw))
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])
    opt = tx.init(params)

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(lambda pp: _loss_fn(model, pp, tokens))(p)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    losses = []
    for _ in range(steps):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    return losses, jax.tree.map(np.asarray, params)


def _baseline(cfg_kw, tokens, steps=2):
    """Last loss of the unsharded single-device reference run."""
    return _single_device_run(tokens, optax.sgd(0.05), steps, **cfg_kw)[0][-1]


#: the two sharded layouts: every device a shard, and two replicas of four
FSDP_MESHES = [dict(fsdp=8), dict(dp=2, fsdp=4)]


def _mesh_id(axes):
    return "x".join(f"{k}{v}" for k, v in axes.items())


def _fsdp_trainer(mesh, tx, tokens):
    model = TransformerLM(_cfg(mesh=mesh, attention="full"))
    trainer = MeshTrainer(model, _loss_fn, tx, mesh=mesh)
    return trainer, trainer.init(jax.random.PRNGKey(0), tokens)


@pytest.mark.parametrize(
    "spec", [dict(dp=2, tp=4), dict(dp=4, sp=2), dict(dp=8)],
    ids=["dp2xtp4", "dp4xsp2", "dp8"],
)
def test_matches_unsharded(spec):
    tokens = _tokens(8)
    mesh = make_mesh(MeshSpec.make(**spec))
    kw = {}
    if spec.get("sp", 1) > 1:
        kw["attention"] = "ring"
    model = TransformerLM(_cfg(mesh=mesh, **kw))
    trainer = MeshTrainer(model, _loss_fn, optax.sgd(0.05), mesh=mesh)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    batch = trainer.shard_batch(tokens)
    for _ in range(2):
        state, metrics = trainer.train_step(state, batch)
    got = float(np.asarray(metrics["loss"]))
    want = _baseline(kw, tokens, steps=2)
    assert np.isclose(got, want, rtol=2e-4), (got, want)


def test_params_actually_sharded_on_tp():
    tokens = _tokens(4)
    mesh = make_mesh(MeshSpec.make(dp=2, tp=4))
    model = TransformerLM(_cfg(mesh=mesh))
    trainer = MeshTrainer(model, _loss_fn, optax.sgd(0.05), mesh=mesh)
    state = trainer.init(jax.random.PRNGKey(0), tokens)
    # at least one param leaf (mlp/vocab kernels) is split over tp
    sharded = [
        l for l in jax.tree.leaves(state.params)
        if l.addressable_shards[0].data.size < l.size
    ]
    assert sharded, "expected tp-sharded kernels"
    # optimizer state (momentum-free sgd has none) still placed fine
    state, metrics = trainer.train_step(state, trainer.shard_batch(tokens))
    assert np.isfinite(float(np.asarray(metrics["loss"])))


def test_requires_init_before_step():
    mesh = make_mesh(MeshSpec.make(dp=8))
    model = TransformerLM(_cfg(mesh=mesh))
    trainer = MeshTrainer(model, _loss_fn, optax.sgd(0.05), mesh=mesh)
    with pytest.raises(RuntimeError):
        trainer.train_step(None, None)


# -- DataParallelTrainer has_aux (mutable model state, e.g. BatchNorm) ----------------


class _BNModel:
    """Tiny dense+BN flax model used to exercise model_state threading."""

    def __new__(cls):
        import flax.linen as nn

        class M(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = True):
                x = nn.Dense(8)(x)
                x = nn.BatchNorm(use_running_average=not train, momentum=0.5)(x)
                return nn.Dense(1)(x)

        return M()


def _bn_setup(per_replica=False, donate=True):
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.train import DataParallelTrainer

    model = _BNModel()
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    y = np.random.RandomState(1).randn(16, 1).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), x[:1], train=False)

    def loss_fn(params, model_state, batch):
        xb, yb = batch
        out, mutated = model.apply(
            {"params": params, **model_state}, xb, train=True,
            mutable=["batch_stats"],
        )
        return jnp.mean((out - yb) ** 2), mutated

    trainer = DataParallelTrainer(
        loss_fn, synchronous_sgd(optax.sgd(0.05)),
        per_replica_params=per_replica, has_aux=True, donate=donate,
    )
    state = trainer.init(
        variables["params"], model_state={"batch_stats": variables["batch_stats"]}
    )
    return trainer, state, (x, y), variables


@pytest.mark.parametrize("per_replica", [False, True], ids=["replicated", "per_replica"])
def test_bn_stats_train_through_state(per_replica):
    trainer, state, (x, y), variables = _bn_setup(per_replica=per_replica)
    batch = trainer.shard_batch((x, y))
    before = np.asarray(
        jax.tree.leaves(trainer.eval_model_state(state))[0]
    ).copy()
    state, metrics = trainer.train_step(state, batch)
    # scan path must thread the stats identically
    state, metrics = trainer.train_steps(state, batch, n=3)
    assert state.step == 4
    after = np.asarray(jax.tree.leaves(trainer.eval_model_state(state))[0])
    assert not np.allclose(before, after), "BN running stats never updated"
    assert np.isfinite(float(np.asarray(metrics["loss"])))


def test_bn_replicated_matches_single_device():
    """Replicated-mode BN sync (pmean of per-shard stats) must equal the
    single-device full-batch computation: mean of shard-means == full mean."""
    trainer, state, (x, y), variables = _bn_setup()
    batch = trainer.shard_batch((x, y))
    state, _ = trainer.train_step(state, batch)

    # single-device reference
    model = _BNModel()
    params, bstats = variables["params"], variables["batch_stats"]

    def loss(p, ms):
        out, mut = model.apply(
            {"params": p, **ms}, x, train=True, mutable=["batch_stats"]
        )
        return jnp.mean((out - y) ** 2), mut

    (_, mutated), grads = jax.value_and_grad(loss, has_aux=True)(
        params, {"batch_stats": bstats}
    )
    want_mean = np.asarray(mutated["batch_stats"]["BatchNorm_0"]["mean"])
    got_mean = np.asarray(
        state.model_state["batch_stats"]["BatchNorm_0"]["mean"]
    )
    assert np.allclose(got_mean, want_mean, atol=1e-5), (got_mean, want_mean)


def test_has_aux_requires_model_state():
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.train import DataParallelTrainer

    trainer = DataParallelTrainer(
        lambda p, m, b: (0.0, m), synchronous_sgd(optax.sgd(0.1)), has_aux=True
    )
    with pytest.raises(ValueError, match="model_state"):
        trainer.init({"w": np.zeros(2, np.float32)})


def test_mesh_trainer_train_steps_matches_single_steps():
    tokens = _tokens(8)
    mesh = make_mesh(MeshSpec.make(dp=8))
    model = TransformerLM(_cfg(mesh=mesh))
    a = MeshTrainer(model, _loss_fn, optax.sgd(0.05), mesh=mesh)
    sa = a.init(jax.random.PRNGKey(0), tokens)
    b = MeshTrainer(model, _loss_fn, optax.sgd(0.05), mesh=mesh)
    sb = b.init(jax.random.PRNGKey(0), tokens)
    batch_a = a.shard_batch(tokens)
    batch_b = b.shard_batch(tokens)
    for _ in range(3):
        sa, ma = a.train_step(sa, batch_a)
    sb, mb = b.train_steps(sb, batch_b, n=3)
    assert sb.step == 3
    la, lb = float(np.asarray(ma["loss"])), float(np.asarray(mb["loss"]))
    assert np.isclose(la, lb, rtol=1e-5), (la, lb)


class TestGradAccumulation:
    def _data(self, n=64, seed=0):
        rng = np.random.RandomState(seed)
        return (rng.randn(n, 8, 8, 1).astype(np.float32),
                rng.randint(0, 10, size=n).astype(np.int32))

    def test_accum_matches_single_step(self):
        """accum_steps=4 on one batch == accum_steps=1 (mean-based loss)."""
        import optax

        from kungfu_tpu.models.slp import MLP, softmax_cross_entropy
        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.train import DataParallelTrainer

        model = MLP(hidden=(16,), num_classes=10)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 1)))["params"]

        def loss_fn(p, batch):
            images, labels = batch
            return softmax_cross_entropy(model.apply({"params": p}, images), labels)

        def run(accum):
            tr = DataParallelTrainer(
                loss_fn, synchronous_sgd(optax.sgd(0.1)), accum_steps=accum
            )
            st = tr.init(jax.tree.map(jnp.array, params))
            for seed in range(3):
                st, m = tr.train_step(st, tr.shard_batch(self._data(seed=seed)))
            return jax.tree.map(np.asarray, st.params), float(np.asarray(m["loss"]))

        p1, l1 = run(1)
        p4, l4 = run(4)
        assert abs(l1 - l4) < 1e-5
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_accum_threads_model_state(self):
        """has_aux path: BN-style state threads through the microbatch scan."""
        import optax

        from kungfu_tpu.train import DataParallelTrainer

        def loss_fn(p, state, batch):
            x, _ = batch
            mean = jnp.mean(x)
            new_state = {"count": state["count"] + 1.0,
                         "running": 0.9 * state["running"] + 0.1 * mean}
            return jnp.mean((x * p["w"]) ** 2), new_state

        tr = DataParallelTrainer(
            loss_fn, optax.sgd(0.01), has_aux=True, accum_steps=4
        )
        st = tr.init({"w": jnp.ones(())}, model_state={"count": jnp.zeros(()),
                                                       "running": jnp.zeros(())})
        st, _ = tr.train_step(st, tr.shard_batch(self._data()))
        # the counter advanced once per MICROBATCH, not once per step
        assert float(np.asarray(st.model_state["count"])) == 4.0

    def test_accum_indivisible_raises(self):
        import optax

        from kungfu_tpu.train import DataParallelTrainer

        tr = DataParallelTrainer(
            lambda p, b: jnp.sum(p["w"] * jnp.mean(b[0])), optax.sgd(0.1),
            accum_steps=3,
        )
        st = tr.init({"w": jnp.ones(())})
        with pytest.raises(ValueError, match="not divisible"):
            tr.train_step(st, tr.shard_batch(self._data(n=64)))


class TestMeshTrainerFSDP:
    def test_fsdp_rules_shard_params_and_match_dp(self):
        """MeshTrainer on a dp x fsdp mesh: embed dims of params shard over
        fsdp (GSPMD ZeRO-3), batch shards over both axes, and one train
        step's loss equals dp-only training."""
        import optax
        from jax.sharding import PartitionSpec as P

        from kungfu_tpu.models.transformer import (
            TransformerConfig, TransformerLM, lm_loss,
        )
        from kungfu_tpu.plan import make_mesh
        from kungfu_tpu.trainer import MeshTrainer

        tokens = np.random.RandomState(0).randint(0, 64, (8, 32)).astype(np.int32)

        def run(mesh):
            cfg = TransformerConfig(
                vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                max_len=32, dtype=jnp.float32, attention="full", mesh=mesh,
            )
            tr = MeshTrainer(
                TransformerLM(cfg),
                lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
                optax.sgd(0.05), mesh=mesh,
            )
            st = tr.init(jax.random.PRNGKey(0), tokens)
            st, m = tr.train_step(st, tr.shard_batch(tokens))
            return st, float(np.asarray(m["loss"]))

        st_f, loss_f = run(make_mesh(dp=2, fsdp=4))
        # qkv kernels are (embed, heads)-partitioned: dim 0 over fsdp
        qk = st_f.params["block_0"]["attn"]["q"]["kernel"]
        assert qk.sharding.spec == P("fsdp", None), qk.sharding.spec
        shard_rows = qk.addressable_shards[0].data.shape[0]
        assert shard_rows * 4 == qk.shape[0]

        st_d, loss_d = run(make_mesh(dp=8))
        assert abs(loss_f - loss_d) < 1e-4, (loss_f, loss_d)

    # Adam divides by sqrt(v): where a gradient is small, float32's
    # reduction order moves a step by a fraction of lr (1e-2 of it allowed)
    @pytest.mark.parametrize("tx,atol", [(optax.sgd(0.1, momentum=0.9), 2e-5),
                                         (optax.adam(1e-2), 1e-4)],
                             ids=["sgd_momentum", "adam"])
    @pytest.mark.parametrize("axes", FSDP_MESHES, ids=_mesh_id)
    def test_steps_equal_the_single_device_steps(self, axes, tx, atol):
        """Three steps under fsdp (and dp x fsdp) against the same three
        on one device with no mesh: every loss, then every parameter."""
        tokens = _tokens(8)
        trainer, state = _fsdp_trainer(make_mesh(**axes), tx, tokens)
        batch, losses = trainer.shard_batch(tokens), []
        for _ in range(3):
            state, m = trainer.train_step(state, batch)
            losses.append(float(np.asarray(m["loss"])))
        want_losses, want = _single_device_run(tokens, tx, steps=3,
                                               attention="full")
        np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
        assert losses[-1] < losses[0]
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=0, atol=atol),
            trainer.eval_params(state), want)

    @pytest.mark.parametrize("axes", FSDP_MESHES, ids=_mesh_id)
    def test_parameters_and_moments_stored_one_share_a_device(self, axes):
        """Every matrix, its momentum trace and both of Adam's moments:
        a device holds 1/n_fsdp of each, before and after a step."""
        tokens = _tokens(8)
        n = axes["fsdp"]
        tx = optax.chain(optax.trace(0.9), optax.adam(1e-2))
        trainer, state = _fsdp_trainer(make_mesh(**axes), tx, tokens)

        def check(state):
            trace, adam = state.opt_state[0], state.opt_state[1][0]
            for tree in (state.params, trace.trace, adam.mu, adam.nu):
                matrices = [x for x in jax.tree.leaves(tree) if x.ndim >= 2]
                assert len(matrices) >= 10
                for x in matrices:
                    assert x.addressable_shards[0].data.size * n == x.size, (
                        x.shape, x.sharding.spec)
            held = sum(x.addressable_shards[0].data.nbytes
                       for x in jax.tree.leaves((state.params, state.opt_state)))
            whole = sum(x.nbytes for x in jax.tree.leaves(
                (state.params, state.opt_state)))
            # the norm scales (vectors) and two counts stay whole
            assert held < whole / n * 1.1, (held, whole)

        check(state)
        state, _ = trainer.train_step(state, trainer.shard_batch(tokens))
        check(state)

    @pytest.mark.parametrize("axes", FSDP_MESHES, ids=_mesh_id)
    def test_checkpoint_restores_under_another_layout(self, axes, tmp_path):
        """Two steps under one fsdp layout, saved; restored onto the OTHER
        layout's freshly placed state: the same values under the other
        layout's shardings, and the third step reads the same loss."""
        from kungfu_tpu.checkpoint import CheckpointManager

        (other,) = [m for m in FSDP_MESHES if m != axes]
        tokens = _tokens(8)
        tx = optax.adam(1e-2)
        a, sa = _fsdp_trainer(make_mesh(**axes), tx, tokens)
        for _ in range(2):
            sa, _ = a.train_step(sa, a.shard_batch(tokens))
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        try:
            assert mgr.save(2, {"params": sa.params, "opt": sa.opt_state})
            mgr.wait()
            b, sb = _fsdp_trainer(make_mesh(**other), tx, tokens)
            got, _ = mgr.restore(like={"params": sb.params,
                                       "opt": sb.opt_state})
        finally:
            mgr.close()
        for new, placed, saved in zip(
                jax.tree.leaves(got),
                jax.tree.leaves({"params": sb.params, "opt": sb.opt_state}),
                jax.tree.leaves({"params": sa.params, "opt": sa.opt_state})):
            assert new.sharding == placed.sharding
            np.testing.assert_array_equal(np.asarray(new), np.asarray(saved))
        sb = type(sb)(params=got["params"], opt_state=got["opt"], step=2)
        sa, ma = a.train_step(sa, a.shard_batch(tokens))
        sb, mb = b.train_step(sb, b.shard_batch(tokens))
        np.testing.assert_allclose(float(np.asarray(mb["loss"])),
                                   float(np.asarray(ma["loss"])), rtol=2e-5)


def test_loss_arity_detection_ignores_defaults():
    """A 3-required-arg loss with optional kwargs (lm_loss_with_aux shape)
    must NOT be treated as rng-taking."""
    import optax

    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM
    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_layers=1, n_heads=2,
                            d_ff=32, max_len=16, dtype=jnp.float32,
                            attention="full")

    def loss3(m, p, b, aux_weight=0.01, z_loss=0.0):
        from kungfu_tpu.models.transformer import lm_loss

        return lm_loss(m.apply({"params": p}, b), b, z_loss=z_loss)

    tr = MeshTrainer(TransformerLM(cfg), loss3, optax.sgd(0.1),
                     mesh=make_mesh(dp=8))
    assert not tr._loss_takes_rng
    toks = np.random.RandomState(0).randint(0, 32, (8, 16)).astype(np.int32)
    st = tr.init(jax.random.PRNGKey(0), toks)
    st, m = tr.train_step(st, tr.shard_batch(toks))
    assert np.isfinite(float(np.asarray(m["loss"])))

    def loss4(m, p, b, rng):
        return jax.random.uniform(rng, ()) + 0.0 * sum(
            jnp.sum(x) for x in jax.tree.leaves(p)
        )

    tr4 = MeshTrainer(TransformerLM(cfg), loss4, optax.sgd(0.1),
                      mesh=make_mesh(dp=8))
    assert tr4._loss_takes_rng


def test_rng_paths_agree():
    """train_step at step s and train_steps(n=1) starting at step s use the
    SAME per-step key (restart determinism across both paths)."""
    import optax

    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer
    from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_layers=1, n_heads=2,
                            d_ff=32, max_len=16, dtype=jnp.float32,
                            attention="full")

    def probe(m, p, b, rng):
        return jax.random.uniform(rng, ()) + 0.0 * sum(
            jnp.sum(x) for x in jax.tree.leaves(p)
        )

    toks = np.random.RandomState(0).randint(0, 32, (8, 16)).astype(np.int32)

    def run(single):
        # fresh trainer/state per path: the step donates its buffers
        tr = MeshTrainer(TransformerLM(cfg), probe, optax.sgd(0.1),
                         mesh=make_mesh(dp=8))
        st = tr.init(jax.random.PRNGKey(3), toks)
        if single:
            _, m = tr.train_step(st, tr.shard_batch(toks))
        else:
            _, m = tr.train_steps(st, tr.shard_batch(toks), n=1)
        return float(np.asarray(m["loss"]))

    assert abs(run(True) - run(False)) < 1e-7
