"""Fused computation-collective matmuls: interpret-mode parity vs XLA.

The kernel bodies (ops/ring_kernels.py make_ag_matmul_kernel /
make_matmul_rs_kernel / make_shift_kernel) run under the Pallas
interpreter on the CPU mesh — same DMA schedule, same MXU interleaving,
conservative per-hop sync — so these tests pin kernel *semantics*
against the exact unfused lax programs the off-TPU fallback uses:

  bit-exactness   with integer-valued fp32/bf16 payloads every addition
                  and every partial product is exact, so any correct
                  fused schedule must match `lax.all_gather` +
                  `jnp.dot` / `jnp.dot` + `lax.psum_scatter` BITWISE —
                  no tolerance can hide a misrouted shard or a
                  mis-accumulated hop.
  fallback        with the pallas gate off (the default off-TPU), every
                  entry point must produce the lax lowering's result
                  exactly — routing a step through the fused ops is
                  always safe.
  differentiation ring_shift's custom VJP (the cotangent rotates
                  backwards) must match the lax transpose, so ring
                  attention stays correct when its KV hop moves to the
                  DMA plane.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from kungfu_tpu.ops import fused_matmul as FM

pytestmark = pytest.mark.pallas


def _mesh(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _ints(shape, lo=-8, hi=8, seed=0, dtype=np.float32):
    """Integer-valued floats: partial products and ring sums stay exact
    in fp32 (and bf16 for small magnitudes), so parity is bitwise."""
    return np.random.RandomState(seed).randint(lo, hi, size=shape).astype(dtype)


def _shmap(fn, mesh, in_specs, out_specs=P("dp")):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


@pytest.fixture
def interpret_gate(monkeypatch):
    monkeypatch.setenv("KFT_PALLAS", "interpret")


# -- all-gather-matmul vs lax.all_gather + jnp.dot ------------------------------------


class TestAllGatherMatmul:
    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_bit_exact_vs_unfused(self, n, dtype, interpret_gate):
        mesh = _mesh(n)
        m, ks, nn = 24, 40, 72  # deliberately non-tiling shapes
        x = jnp.broadcast_to(
            jnp.asarray(_ints((m, n * ks)), dtype), (n, m, n * ks))
        w = jnp.asarray(_ints((n, ks, nn), seed=1), dtype)

        fused = _shmap(
            lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        unfused = _shmap(
            lambda xx, ww: jnp.dot(
                xx[0], lax.all_gather(ww[0], "dp", tiled=True),
                preferred_element_type=jnp.float32).astype(dtype),
            mesh, (P("dp"), P("dp")))(x, w)
        assert fused.dtype == unfused.dtype == dtype
        assert np.array_equal(
            np.asarray(fused.astype(jnp.float32)),
            np.asarray(unfused.astype(jnp.float32)))

    def test_tile_split_bit_exact(self, interpret_gate):
        """MXU tile splits (fused_block_m/n) are a pure scheduling knob:
        same math, same bits."""
        n = 2
        mesh = _mesh(n)
        x = jnp.broadcast_to(jnp.asarray(_ints((16, n * 32))), (n, 16, n * 32))
        w = jnp.asarray(_ints((n, 32, 256), seed=2))

        whole = _shmap(
            lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        tiled = _shmap(
            lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp",
                                                block_m=8, block_n=128),
            mesh, (P("dp"), P("dp")))(x, w)
        assert np.array_equal(np.asarray(whole), np.asarray(tiled))

    def test_fallback_identity_gate_off(self, monkeypatch):
        monkeypatch.delenv("KFT_PALLAS", raising=False)
        n = 2
        mesh = _mesh(n)
        x = jnp.broadcast_to(jnp.asarray(_ints((8, n * 16))), (n, 8, n * 16))
        w = jnp.asarray(_ints((n, 16, 24), seed=3))
        fused = _shmap(
            lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        want = np.asarray(x[0]) @ np.asarray(w).reshape(n * 16, 24)
        assert np.array_equal(np.asarray(fused)[:8], want)
        assert FM.effective_impl() == "xla"

    def test_oversized_payload_falls_back(self, interpret_gate, monkeypatch):
        """Past the VMEM scratch budget the wrapper must take the lax
        path (and still be correct), never build an unloadable kernel."""
        monkeypatch.setenv("KFT_PALLAS_VMEM_MIB", "0")
        n = 2
        mesh = _mesh(n)
        x = jnp.broadcast_to(jnp.asarray(_ints((8, n * 16))), (n, 8, n * 16))
        w = jnp.asarray(_ints((n, 16, 24), seed=4))
        fused = _shmap(
            lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        want = np.asarray(x[0]) @ np.asarray(w).reshape(n * 16, 24)
        assert np.array_equal(np.asarray(fused)[:8], want)

    def test_shape_mismatch_raises(self, interpret_gate):
        n = 2
        mesh = _mesh(n)
        x = jnp.zeros((n, 8, 30))  # 30 != n * 16
        w = jnp.zeros((n, 16, 24))
        with pytest.raises(ValueError, match="contraction dim"):
            _shmap(lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp"),
                   mesh, (P("dp"), P("dp")))(x, w)

    def test_float_payload_close(self, interpret_gate):
        """Non-integer floats: per-rank accumulation order differs from
        the one-dot reference, so parity is allclose, not bitwise."""
        n = 4
        mesh = _mesh(n)
        rng = np.random.RandomState(5)
        x = jnp.broadcast_to(
            jnp.asarray(rng.randn(16, n * 24).astype(np.float32)),
            (n, 16, n * 24))
        w = jnp.asarray(rng.randn(n, 24, 40).astype(np.float32))
        fused = _shmap(
            lambda xx, ww: FM.all_gather_matmul(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        want = np.asarray(x[0]) @ np.asarray(w).reshape(n * 24, 40)
        np.testing.assert_allclose(np.asarray(fused)[:16], want,
                                   rtol=1e-5, atol=1e-4)


# -- matmul-reduce-scatter vs jnp.dot + lax.psum_scatter ------------------------------


class TestMatmulReduceScatter:
    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_bit_exact_vs_unfused(self, n, dtype, interpret_gate):
        mesh = _mesh(n)
        m, k, nn = 8 * n, 24, 56  # non-tiling N/K
        x = jnp.asarray(_ints((n, m, k)), dtype)
        w = jnp.asarray(_ints((n, k, nn), seed=1), dtype)

        fused = _shmap(
            lambda xx, ww: FM.matmul_reduce_scatter(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        unfused = _shmap(
            lambda xx, ww: lax.psum_scatter(
                jnp.dot(xx[0], ww[0], preferred_element_type=jnp.float32),
                "dp", scatter_dimension=0, tiled=True).astype(dtype),
            mesh, (P("dp"), P("dp")))(x, w)
        assert fused.dtype == unfused.dtype == dtype
        assert np.array_equal(
            np.asarray(fused.astype(jnp.float32)),
            np.asarray(unfused.astype(jnp.float32)))

    def test_true_sum_ownership(self, interpret_gate):
        """Rank d must hold rows [d*M/n, (d+1)*M/n) of the cross-rank
        sum — the psum_scatter(scatter_dimension=0) ownership."""
        n = 4
        mesh = _mesh(n)
        m, k, nn = 4 * n, 16, 32
        x = _ints((n, m, k), seed=2)
        w = _ints((n, k, nn), seed=3)
        got = np.asarray(_shmap(
            lambda xx, ww: FM.matmul_reduce_scatter(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(jnp.asarray(x), jnp.asarray(w)))
        want = np.add.reduce([x[i] @ w[i] for i in range(n)])
        assert np.array_equal(got.reshape(n, m // n, nn),
                              want.reshape(n, m // n, nn))

    def test_indivisible_rows_fall_back_semantics(self, interpret_gate):
        """M not divisible by n routes to the lax fallback — which has
        the same divisibility contract — so the fused wrapper never
        errors where the XLA path would have worked (both require
        divisibility; the gate itself must not add new failures)."""
        n = 2
        mesh = _mesh(n)
        x = jnp.asarray(_ints((n, 6, 16)))  # 6 % 2 == 0: kernel path
        w = jnp.asarray(_ints((n, 16, 24), seed=4))
        got = _shmap(
            lambda xx, ww: FM.matmul_reduce_scatter(xx[0], ww[0], "dp"),
            mesh, (P("dp"), P("dp")))(x, w)
        want = _shmap(
            lambda xx, ww: lax.psum_scatter(
                jnp.dot(xx[0], ww[0], preferred_element_type=jnp.float32),
                "dp", scatter_dimension=0, tiled=True),
            mesh, (P("dp"), P("dp")))(x, w)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# -- ring shift ------------------------------------------------------------------------


class TestRingShift:
    @pytest.mark.parametrize("n", [2, 4])
    def test_matches_ppermute(self, n, interpret_gate):
        mesh = _mesh(n)
        v = jnp.asarray(_ints((n, 3, 17), seed=11))  # non-tiling payload
        got = _shmap(lambda x: FM.ring_shift(x[0], "dp"), mesh, P("dp"))(v)
        perm = [(i, (i + 1) % n) for i in range(n)]
        want = _shmap(lambda x: lax.ppermute(x[0], "dp", perm),
                      mesh, P("dp"))(v)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_grad_rotates_backwards(self, interpret_gate):
        n = 4
        mesh = _mesh(n)
        v = jnp.asarray(_ints((n, 32), seed=12))
        c = jnp.asarray(_ints((n, 32), seed=13))

        def g(fn):
            return np.asarray(_shmap(
                lambda x, cc: jax.grad(
                    lambda xx: jnp.sum(fn(xx[0]) * cc[0]))(x),
                mesh, (P("dp"), P("dp")))(v, c))

        perm = [(i, (i + 1) % n) for i in range(n)]
        g_dma = g(lambda x: FM.ring_shift(x, "dp"))
        g_lax = g(lambda x: lax.ppermute(x, "dp", perm))
        assert np.array_equal(g_dma, g_lax)


    def test_multi_axis_mesh_falls_back(self, interpret_gate):
        """A ring on one axis of a MULTI-axis manual region must take
        the lax path: a scalar LOGICAL device_id is only well-defined
        for a sole named axis (the Pallas DMA discharge raises
        NotImplementedError otherwise — found driving the dp×sp×tp
        dryrun).  Correctness, not an error, is the contract."""
        if len(jax.devices()) < 4:
            pytest.skip("needs a 2x2 mesh")
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "fsdp"))
        v = jnp.asarray(_ints((2, 2, 24), seed=16))
        got = jax.jit(shard_map(
            lambda x: FM.ring_shift(x[0, 0], "fsdp")[None, None],
            mesh=mesh, in_specs=P("dp", "fsdp"),
            out_specs=P("dp", "fsdp"), check_vma=False))(v)
        perm = [(0, 1), (1, 0)]
        want = jax.jit(shard_map(
            lambda x: lax.ppermute(x[0, 0], "fsdp", perm)[None, None],
            mesh=mesh, in_specs=P("dp", "fsdp"),
            out_specs=P("dp", "fsdp"), check_vma=False))(v)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# -- planner + strategy registration --------------------------------------------------


class TestPlannerFused:
    def test_fused_plans_enumerated_and_lint_clean(self):
        from kungfu_tpu.planner.candidates import (
            FUSED_MATMUL_ALGORITHMS, default_buckets, enumerate_plans,
            hosts_for,
        )
        from kungfu_tpu.planner.validate import validate_plan

        for world, hc in ((2, 1), (4, 1), (8, 2)):
            hosts = hosts_for(world, hc)
            plans = enumerate_plans(world, hosts, default_buckets()[0])
            fused = [p for p in plans
                     if p.algorithm in FUSED_MATMUL_ALGORITHMS]
            assert {p.algorithm for p in fused} == {"ag_matmul", "matmul_rs"}
            # full-precision wire only: installing a fused plan must not
            # flip the session's allreduce compression as a side effect
            wires = {p.wire_scheme(p.legs[0]) for p in fused}
            assert wires == {"none"}
            for p in fused:
                assert validate_plan(p, hosts) == [], p.describe()

    def test_fused_plan_json_roundtrip(self):
        from kungfu_tpu.planner.candidates import Plan

        p = Plan(algorithm="ag_matmul", strategy_name="PALLAS_FUSED_MATMUL",
                 wire=(("ici", "none"),), bucket="small", world=4)
        assert Plan.from_json(p.to_json()) == p
        assert p.compression() is None

    def test_cost_fused_below_pallas_ring(self):
        """A single overlapped leg must price below the 2(n-1)-round
        pallas ring at equal wire bytes — that ordering is what puts the
        fused candidates into the measured runoff."""
        from kungfu_tpu.planner.candidates import Plan, default_buckets, hosts_for
        from kungfu_tpu.planner.cost import predict_ms
        from kungfu_tpu.planner.model import CostModel, LinkModel

        model = CostModel(links={"ici": LinkModel(alpha_ms=0.1,
                                                  beta_ms_per_mib=1.0)})
        hosts = hosts_for(4, 1)
        b = default_buckets()[1]
        mk = lambda alg, strat: Plan(algorithm=alg, strategy_name=strat,
                                     wire=(("ici", "none"),), bucket=b.id,
                                     world=4)
        ring = predict_ms(mk("pallas_ring", "PALLAS_RING"), b.rep_bytes,
                          model, hosts)
        ag = predict_ms(mk("ag_matmul", "PALLAS_FUSED_MATMUL"), b.rep_bytes,
                        model, hosts)
        rs = predict_ms(mk("matmul_rs", "PALLAS_FUSED_MATMUL"), b.rep_bytes,
                        model, hosts)
        assert ag < ring and rs < ring

    def test_strategy_registration(self):
        from kungfu_tpu.plan import Impl, Strategy, impl_of, strategy_graphs

        s = Strategy.parse("pallas_fused_matmul")
        assert s is Strategy.PALLAS_FUSED_MATMUL
        assert impl_of(s) is Impl.PALLAS_FUSED_MATMUL
        # shares RING's circular reference graphs for digests + kf-lint
        pairs = strategy_graphs(s, [[0, 1, 2, 3]])
        assert pairs and all(len(pair) == 2 for pair in pairs)

    def test_session_allreduce_under_fused_strategy(self, interpret_gate):
        from kungfu_tpu.plan import Strategy, make_mesh
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1),
                       strategy=Strategy.PALLAS_FUSED_MATMUL)
        v = _ints((513,), seed=14)
        out = Session.local_row(sess.all_reduce(sess.lift(v)))
        assert np.array_equal(out, sess.size * v)

    def test_session_fallback_off_tpu(self, monkeypatch):
        monkeypatch.delenv("KFT_PALLAS", raising=False)
        from kungfu_tpu.plan import Impl, Strategy, make_mesh
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1),
                       strategy=Strategy.PALLAS_FUSED_MATMUL)
        v = _ints((64,), seed=15)
        out = Session.local_row(sess.all_reduce(sess.lift(v)))
        assert np.array_equal(out, sess.size * v)
        assert Session._impl_tag(Impl.PALLAS_FUSED_MATMUL) == "xla"
        monkeypatch.setenv("KFT_PALLAS", "interpret")
        assert Session._impl_tag(
            Impl.PALLAS_FUSED_MATMUL) == "pallas_fused_matmul"
