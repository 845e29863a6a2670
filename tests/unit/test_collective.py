"""Collective op correctness on an 8-virtual-device CPU mesh.

Mirrors the reference op tests (tests/python/integration/test_operators.py)
and the np x strategy CI sweep (scripts/tests/run-integration-tests.sh:30-38).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.plan import Strategy, make_mesh, make_hierarchical_mesh
from kungfu_tpu.session import Session

ALL_STRATEGIES = [s for s in Strategy if s is not Strategy.AUTO] + [Strategy.AUTO]


def per_peer_values(n, shape=(5,), dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(n, *shape).astype(dtype)


@pytest.fixture(scope="module")
def sess():
    return Session(make_mesh(dp=-1))


@pytest.fixture(scope="module")
def hier_sess():
    # 2 "hosts" x 4 "chips": dcn x ici axes
    return Session(make_hierarchical_mesh(2), strategy=Strategy.BINARY_TREE_STAR, host_count=2)


class TestAllReduce:
    @pytest.mark.parametrize("strategy", ALL_STRATEGIES, ids=lambda s: s.name)
    def test_sum_all_strategies(self, sess, strategy):
        x = per_peer_values(sess.size)
        out = np.asarray(sess.all_reduce(x, strategy=strategy))
        want = np.tile(x.sum(axis=0), (sess.size, 1))
        np.testing.assert_allclose(out, want, rtol=1e-5)

    @pytest.mark.parametrize("op", ["sum", "min", "max", "mean", "prod"])
    def test_ops(self, sess, op):
        x = per_peer_values(sess.size, seed=1)
        out = np.asarray(sess.all_reduce(x, op=op))
        red = {"sum": np.sum, "min": np.min, "max": np.max,
               "mean": np.mean, "prod": np.prod}[op](x, axis=0)
        np.testing.assert_allclose(out[0], red, rtol=1e-5)

    def test_odd_sizes_ring(self, sess):
        # tensor size not divisible by world size exercises chunk padding
        x = per_peer_values(sess.size, shape=(13,), seed=2)
        out = np.asarray(sess.all_reduce(x, strategy=Strategy.RING))
        np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)

    def test_2d_tensors(self, sess):
        x = per_peer_values(sess.size, shape=(3, 7), seed=3)
        out = np.asarray(sess.all_reduce(x))
        np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)

    def test_hierarchical_mesh(self, hier_sess):
        x = per_peer_values(hier_sess.size, shape=(11,), seed=4)
        out = np.asarray(hier_sess.all_reduce(x))
        np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)

    def test_group(self, sess):
        xs = [per_peer_values(sess.size, shape=(k + 1,), seed=k) for k in range(3)]
        outs = sess.group_all_reduce(xs)
        for x, o in zip(xs, outs):
            np.testing.assert_allclose(np.asarray(o)[0], x.sum(axis=0), rtol=1e-5)

    @pytest.mark.parametrize(
        "strategy",
        [Strategy.AUTO, Strategy.STAR, Strategy.RING, Strategy.CLIQUE,
         Strategy.BINARY_TREE_STAR],
        ids=lambda s: s.name)
    def test_group_fused_matches_unfused(self, sess, strategy):
        """Fused (one compiled program) == per-tensor dispatch == numpy,
        for each lowering family (auto, psum, ring, rs+ag, hierarchical),
        over mixed dtypes and shapes."""
        rng = np.random.RandomState(7)
        n = sess.size
        xs_np = [
            rng.randn(n, 5).astype(np.float32),
            rng.randn(n, 3, 4).astype(np.float64),
            rng.randint(0, 100, size=(n, 7)).astype(np.int32),
            rng.randn(n).astype(np.float32),
        ]
        fused = sess.group_all_reduce(xs_np, fuse=True, strategy=strategy)
        unfused = sess.group_all_reduce(xs_np, fuse=False, strategy=strategy)
        for x_np, f, u in zip(xs_np, fused, unfused):
            want = np.broadcast_to(x_np.sum(axis=0, keepdims=True), x_np.shape)
            np.testing.assert_allclose(np.asarray(f), want, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(f), np.asarray(u), rtol=1e-6)

    def test_group_fused_non_sum_op(self, sess):
        rng = np.random.RandomState(7)
        xs_np = [rng.randn(sess.size, 5).astype(np.float32),
                 rng.randn(sess.size, 3, 4).astype(np.float64)]
        mx = sess.group_all_reduce(xs_np, op="max", fuse=True)
        np.testing.assert_allclose(
            np.asarray(mx[0]),
            np.broadcast_to(xs_np[0].max(axis=0, keepdims=True), xs_np[0].shape),
            rtol=1e-6,
        )


class TestOtherCollectives:
    def test_broadcast(self, sess):
        x = per_peer_values(sess.size, seed=5)
        for root in (0, 3):
            out = np.asarray(sess.broadcast(x, root=root))
            np.testing.assert_allclose(out, np.tile(x[root], (sess.size, 1)), rtol=1e-6)

    def test_reduce_root_only(self, sess):
        x = per_peer_values(sess.size, seed=6)
        out = np.asarray(sess.reduce(x, root=2))
        np.testing.assert_allclose(out[2], x.sum(axis=0), rtol=1e-5)
        assert np.all(out[0] == 0) and np.all(out[7] == 0)

    def test_all_gather(self, sess):
        x = per_peer_values(sess.size, shape=(3,), seed=7)
        out = np.asarray(sess.all_gather(x))
        assert out.shape == (sess.size, sess.size, 3)
        for r in range(sess.size):
            np.testing.assert_allclose(out[r], x, rtol=1e-6)

    def test_gather_root_only(self, sess):
        # reference root-gather (session/session.go:185-207): root holds the
        # stack, non-roots zeros
        x = per_peer_values(sess.size, shape=(3,), seed=11)
        out = np.asarray(sess.gather(x, root=2))
        assert out.shape == (sess.size, sess.size, 3)
        np.testing.assert_allclose(out[2], x, rtol=1e-6)
        assert np.all(out[0] == 0) and np.all(out[7] == 0)

    def test_cross_all_reduce_hierarchical(self, hier_sess):
        # reference CrossAllReduce (session/allreduce.go:38): reduce over
        # hosts only — each (host h, local l) slot sums with the same local
        # slot on every other host
        n = hier_sess.size
        hosts = hier_sess.mesh.shape["dcn"]
        local = n // hosts
        x = per_peer_values(n, shape=(4,), seed=12)
        out = np.asarray(hier_sess.cross_all_reduce(x))
        grid = x.reshape(hosts, local, 4)
        want = np.broadcast_to(grid.sum(axis=0), (hosts, local, 4)).reshape(n, 4)
        np.testing.assert_allclose(out, want, rtol=1e-5)

    def test_cross_all_reduce_single_host_identity(self, sess):
        x = per_peer_values(sess.size, seed=13)
        out = np.asarray(sess.cross_all_reduce(x))
        np.testing.assert_allclose(out, x, rtol=1e-6)
        with pytest.raises(ValueError):  # same shape contract as every op
            sess.cross_all_reduce(x[:3])

    def test_cross_all_reduce_multi_host_flat_mesh_rejected(self):
        # silently skipping the cross reduction would change semantics
        sess = Session(make_mesh(dp=-1), host_count=4)
        with pytest.raises(ValueError, match="ici×dcn"):
            sess.cross_all_reduce(per_peer_values(sess.size, seed=14))

    def test_barrier(self, sess):
        sess.barrier()  # completes

    def test_consensus_agree(self, sess):
        x = np.tile(np.arange(4, dtype=np.float32), (sess.size, 1))
        assert sess.consensus(x) is True

    def test_consensus_disagree(self, sess):
        x = np.tile(np.arange(4, dtype=np.float32), (sess.size, 1))
        x[3, 0] = 99.0
        assert sess.consensus(x) is False

    def test_consensus_int(self, sess):
        x = np.ones((sess.size, 2), np.int32)
        assert sess.consensus(x) is True


class TestSessionMechanics:
    def test_strategy_swap(self, sess):
        x = per_peer_values(sess.size, seed=8)
        a = np.asarray(sess.all_reduce(x))
        sess.set_strategy(Strategy.RING)
        b = np.asarray(sess.all_reduce(x))
        sess.set_strategy(Strategy.AUTO)
        np.testing.assert_allclose(a, b, rtol=1e-5)

    def test_per_op_tree(self, sess):
        """all_reduce(tree=...) picks the impl for one op without touching
        the session default (reference MonitoredAllReduce's tree input)."""
        x = per_peer_values(sess.size, seed=21)
        default = sess.strategy
        a = np.asarray(sess.all_reduce(x))
        # a star rooted at 0 (father array: everyone's father is 0)
        b = np.asarray(sess.all_reduce(x, tree=[0] * sess.size))
        np.testing.assert_allclose(a, b, rtol=1e-5)
        assert sess.strategy is default

    def test_stats_recorded(self, sess):
        sess.stats.reset()
        x = per_peer_values(sess.size, seed=9)
        sess.all_reduce(x, name="grad0")  # warmup call: excluded (compile time)
        assert "grad0" not in sess.calc_stats()
        sess.all_reduce(x, name="grad0")
        assert "grad0" in sess.calc_stats()
        assert sess.throughput() > 0

    def test_leading_dim_check(self, sess):
        with pytest.raises(ValueError):
            sess.all_reduce(np.zeros((3, 5), np.float32))

    def test_bf16(self, sess):
        x = jnp.asarray(per_peer_values(sess.size, seed=10), dtype=jnp.bfloat16)
        out = np.asarray(sess.all_reduce(x).astype(jnp.float32))
        want = np.asarray(jnp.sum(x, axis=0).astype(jnp.float32))
        np.testing.assert_allclose(out[0], want, rtol=2e-2)
