"""Pallas ring collectives: interpret-mode parity vs the XLA lowerings.

The kernel bodies (ops/ring_kernels.py) run under the Pallas interpreter
on the CPU mesh — same DMA schedule, same in-kernel codec, conservative
per-hop sync — so these tests pin kernel *semantics* against the exact
lax.* programs the off-TPU fallback uses:

  bit-exactness   the plain ring RS/AG move bytes; with integer-valued
                  fp32/bf16 payloads every addition is exact, so any
                  correct schedule must match lax.psum_scatter /
                  lax.all_gather BITWISE — no tolerance can hide a
                  misrouted chunk.
  quant tolerance the fused int8/fp8 ring requantizes the traveling
                  partial sum at each hop, so its error bound is the sum
                  over hops of (partial absmax)/(2*codemax) — computed
                  from the data here, like test_compression.py's bounds.
  fallback        with the pallas gate off (the default off-TPU), every
                  entry point must produce the lax lowering's result
                  exactly — installing a pallas strategy is always safe.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kungfu_tpu import compression as comp
from jax import shard_map
from kungfu_tpu.ops import collective as C
from kungfu_tpu.ops import pallas_collectives as PC

pytestmark = pytest.mark.pallas

_HAS_FP8 = getattr(jnp, "float8_e4m3fn", None) is not None


def _mesh(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _ints(shape, lo=-31, hi=32, seed=0, dtype=np.float32):
    """Integer-valued floats: exact in fp32 and (for |sums| < 256) bf16,
    so data-movement parity can be asserted bitwise."""
    return np.random.RandomState(seed).randint(lo, hi, size=shape).astype(dtype)


def _shmap(fn, mesh, in_specs=P("dp"), out_specs=P("dp")):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


@pytest.fixture
def interpret_gate(monkeypatch):
    monkeypatch.setenv("KFT_PALLAS", "interpret")


# -- ring RS / AG vs the XLA lowerings ------------------------------------------------


class TestRingParity:
    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_reduce_scatter_bit_exact(self, n, dtype, interpret_gate):
        mesh = _mesh(n)
        x = jnp.asarray(_ints((n * n, 40, 9))).astype(dtype)

        pallas = _shmap(lambda v: PC.ring_reduce_scatter(v, "dp"), mesh)(x)
        xla = _shmap(
            lambda v: lax.psum_scatter(v, "dp", scatter_dimension=0,
                                       tiled=False), mesh)(x)
        assert pallas.dtype == xla.dtype == dtype
        assert np.array_equal(
            np.asarray(pallas.astype(jnp.float32)),
            np.asarray(xla.astype(jnp.float32)))

    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_all_gather_bit_exact(self, n, dtype, interpret_gate):
        mesh = _mesh(n)
        x = jnp.asarray(_ints((n * 11, 13))).astype(dtype)

        pallas = _shmap(lambda v: PC.ring_all_gather(v, "dp"), mesh)(x)
        xla = _shmap(lambda v: lax.all_gather(v, "dp", tiled=False), mesh)(x)
        assert pallas.shape == xla.shape
        assert np.array_equal(
            np.asarray(pallas.astype(jnp.float32)),
            np.asarray(xla.astype(jnp.float32)))

    @pytest.mark.parametrize("n", [2, 3, 4])
    def test_all_reduce_bit_exact_vs_xla_ring(self, n, interpret_gate):
        mesh = _mesh(n)
        full = _ints((n, 2000), seed=3)
        x = jnp.asarray(full.reshape(-1))

        pallas = _shmap(lambda v: PC.ring_all_reduce(v, "dp"), mesh)(x)
        xla = _shmap(lambda v: C.ring_all_reduce(v, "dp"), mesh)(x)
        assert np.array_equal(np.asarray(pallas), np.asarray(xla))
        # and both equal the true sum, replicated to every shard
        want = np.tile(full.sum(axis=0), n)
        assert np.array_equal(np.asarray(pallas), want)

    def test_all_reduce_float_close_to_psum(self, interpret_gate):
        n = 4
        mesh = _mesh(n)
        x = jnp.asarray(np.random.RandomState(1).randn(n * 500).astype(np.float32))
        pallas = _shmap(lambda v: PC.ring_all_reduce(v, "dp"), mesh)(x)
        psum = _shmap(lambda v: lax.psum(v, "dp"), mesh)(x)
        np.testing.assert_allclose(np.asarray(pallas), np.asarray(psum),
                                   rtol=1e-5, atol=1e-4)

    def test_mean_op(self, interpret_gate):
        n = 4
        mesh = _mesh(n)
        x = jnp.asarray(_ints((n * 256,), seed=5) * float(n))
        out = _shmap(lambda v: PC.ring_all_reduce(v, "dp", op="mean"), mesh)(x)
        want = np.asarray(_shmap(lambda v: lax.pmean(v, "dp"), mesh)(x))
        assert np.array_equal(np.asarray(out), want)


# -- fused codec vs the three-op XLA path ---------------------------------------------


def _fused_tolerance(full: np.ndarray, n: int, codemax: float) -> float:
    """Sum-over-hops requantization bound: every hop rounds the traveling
    partial by at most its absmax/(2*codemax); partial absmax is bounded
    by the running cumulative-abs-sum.  Plus one AG-leg quantization of
    the final sum.  Computed from the data, not a magic rtol."""
    partial_max = np.abs(np.cumsum(full, axis=0)).max()
    rs_err = (n - 1) * partial_max / (2 * codemax)
    ag_err = np.abs(full.sum(axis=0)).max() / (2 * codemax)
    return 2.0 * (rs_err + ag_err)  # 2x: rounding-mode slack at block edges


class TestFusedCodec:
    @pytest.mark.parametrize("n", [2, 4])
    def test_int8_within_quant_tolerance(self, n, interpret_gate):
        mesh = _mesh(n)
        rng = np.random.RandomState(0)
        full = (rng.randn(n, 3000) * np.exp(rng.randn(n, 1))).astype(np.float32)
        x = jnp.asarray(full.reshape(-1))
        cfg = comp.resolve("int8")

        fused = _shmap(
            lambda v: PC.fused_ring_all_reduce(v, "dp", cfg), mesh)(x)
        want_rows = np.concatenate([full.sum(axis=0)] * n)[: x.size]
        tol = _fused_tolerance(full, n, 127.0)
        err = np.abs(np.asarray(fused) - want_rows).max()
        assert err <= tol, (err, tol)

        # and it agrees with the existing three-op XLA schedule within the
        # combined tolerance of the two (different) quantization orders
        xla = _shmap(
            lambda v: comp.all_reduce(v, "dp", cfg), mesh)(x)
        xla_tol = (np.abs(full).max() * n + np.abs(full.sum(0)).max()) / 254.0
        assert np.abs(np.asarray(fused) - np.asarray(xla)).max() <= tol + xla_tol

    @pytest.mark.skipif(not _HAS_FP8, reason="no float8_e4m3fn in this build")
    def test_fp8_within_quant_tolerance(self, interpret_gate):
        n = 4
        mesh = _mesh(n)
        rng = np.random.RandomState(2)
        full = rng.randn(n, 2048).astype(np.float32)
        x = jnp.asarray(full.reshape(-1))
        cfg = comp.resolve("fp8")
        fused = _shmap(
            lambda v: PC.fused_ring_all_reduce(v, "dp", cfg), mesh)(x)
        want_rows = np.concatenate([full.sum(axis=0)] * n)[: x.size]
        # fp8 e4m3 relative spacing is 2^-3 of the block scale envelope
        partial_max = np.abs(np.cumsum(full, axis=0)).max()
        tol = 2.0 * n * partial_max * (2 ** -3)
        assert np.abs(np.asarray(fused) - want_rows).max() <= tol

    def test_bf16_scheme_is_cast_ring(self, interpret_gate):
        n = 4
        mesh = _mesh(n)
        x = jnp.asarray(_ints((n * 512,), seed=7))
        out = _shmap(
            lambda v: PC.fused_ring_all_reduce(v, "dp", "bf16"), mesh)(x)
        want = _shmap(
            lambda v: comp.all_reduce(v, "dp", "bf16"), mesh)(x)
        assert np.array_equal(np.asarray(out), np.asarray(want))

    def test_stochastic_config_falls_back(self, interpret_gate):
        """int8-sr has no fused kernel: the wrapper must route to the XLA
        schedule (whose dither needs per-peer keys), not silently drop
        the stochastic rounding."""
        n = 2
        mesh = _mesh(n)
        x = jnp.asarray(np.random.RandomState(3).randn(n * 512).astype(np.float32))
        cfg = comp.resolve("int8-sr")
        out = _shmap(
            lambda v: PC.fused_ring_all_reduce(v, "dp", cfg), mesh)(x)
        # sanity: still an allreduce (close to the fp32 sum)
        want = np.asarray(_shmap(lambda v: lax.psum(v, "dp"), mesh)(x))
        tol = 4 * np.abs(want).max() / 127.0
        assert np.abs(np.asarray(out) - want).max() <= tol


# -- error feedback with the fused reducer --------------------------------------------


class TestErrorFeedback:
    def test_residual_equivalence_across_impls(self, interpret_gate):
        """The EF residual is the LOCAL roundtrip error of the corrected
        gradient — independent of which engine moved the bytes.  The
        pallas_ring compressed reducer must leave the EF state identical
        to the xla ring's (same seed, same leaves)."""
        from kungfu_tpu.optimizers.sync import all_reduce_gradients

        n = 2
        mesh = _mesh(n)
        cfg = comp.CompressionConfig(scheme="int8", error_feedback=True)
        grads = {"w": jnp.asarray(
            np.random.RandomState(0).randn(n, 700).astype(np.float32))}

        def run(impl):
            tx = all_reduce_gradients("dp", impl=impl, compression=cfg)

            def body(g):
                st = tx.init(g)
                u, st2 = tx.update(g, st)
                return u, st2.ef

            return _shmap(body, mesh,
                          out_specs=(P("dp"), P("dp")))(grads)

        u_ring, ef_ring = run("ring")
        u_pallas, ef_pallas = run("pallas_ring")
        for k in ef_ring.residual:
            assert np.array_equal(np.asarray(ef_ring.residual[k]),
                                  np.asarray(ef_pallas.residual[k]))
        # reduced outputs agree within one extra hop-requant of each other
        scale = np.abs(np.asarray(grads["w"])).max()
        assert np.abs(np.asarray(u_ring["w"]) -
                      np.asarray(u_pallas["w"])).max() <= 4 * n * scale / 254.0


# -- bucketed gradient sync -----------------------------------------------------------


class TestBucketedSync:
    @pytest.mark.parametrize("n", [2, 4])
    def test_bucketed_identity_pmean(self, n):
        from kungfu_tpu.optimizers.sync import all_reduce_gradients

        mesh = _mesh(n)
        rng = np.random.RandomState(0)
        grads = {
            "a": jnp.asarray(rng.randn(n, 1000).astype(np.float32)),
            "b": jnp.asarray(rng.randn(n, 37).astype(np.float32)),
            "c": jnp.asarray(rng.randn(n, 8, 11).astype(np.float32)),
            "d": jnp.asarray(rng.randn(n, 5).astype(np.float32)),
        }

        def run(bucket_bytes):
            tx = all_reduce_gradients("dp", bucket_bytes=bucket_bytes)

            def body(g):
                import optax

                u, _ = tx.update(g, optax.EmptyState())
                return u

            return _shmap(body, mesh)(grads)

        base = run(None)
        for bb in (512, 4096, 1 << 20):
            got = run(bb)
            for k in base:
                assert np.array_equal(np.asarray(base[k]), np.asarray(got[k])), (
                    k, bb)

    def test_mixed_dtype_buckets_never_mix(self):
        from kungfu_tpu.optimizers.sync import _pack_buckets

        leaves = [jnp.zeros(10, jnp.float32), jnp.zeros(10, jnp.bfloat16),
                  jnp.zeros(10, jnp.float32)]
        buckets = _pack_buckets(leaves, 1 << 20)
        for idxs in buckets:
            dts = {leaves[i].dtype for i in idxs}
            assert len(dts) == 1
        assert [i for b in buckets for i in b] == [0, 1, 2]

    def test_oversized_leaf_gets_own_bucket(self):
        from kungfu_tpu.optimizers.sync import _pack_buckets

        leaves = [jnp.zeros(4, jnp.float32), jnp.zeros(10_000, jnp.float32),
                  jnp.zeros(4, jnp.float32)]
        buckets = _pack_buckets(leaves, 1024)
        assert buckets == [[0], [1], [2]]

    def test_session_group_bucketed(self):
        from kungfu_tpu.plan import make_mesh
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1))
        xs = [sess.lift(np.full(sz, 2.0, np.float32)) for sz in (100, 300, 50)]
        outs = sess.group_all_reduce(xs, bucket_bytes=1 << 11)
        for o, sz in zip(outs, (100, 300, 50)):
            row = Session.local_row(o)
            assert row.shape == (sz,)
            assert np.all(row == 2.0 * sess.size)

    def test_pack_buckets_static(self):
        from kungfu_tpu.session import Session

        assert Session.pack_buckets([10, 10, 10], 25) == [[0, 1], [2]]
        assert Session.pack_buckets([100], 10) == [[0]]
        assert Session.pack_buckets([], 10) == []


# -- Session strategies + fallback ----------------------------------------------------


class TestSessionIntegration:
    def test_pallas_strategy_fallback_off_tpu(self, monkeypatch):
        monkeypatch.delenv("KFT_PALLAS", raising=False)
        from kungfu_tpu.plan import Strategy, make_mesh
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1), strategy=Strategy.PALLAS_RING)
        v = _ints((513,), seed=11)
        out = Session.local_row(sess.all_reduce(sess.lift(v)))
        assert np.array_equal(out, sess.size * v)
        assert PC.effective_impl("pallas") == "xla"

    def test_pallas_strategy_interpret(self, interpret_gate):
        from kungfu_tpu.plan import Strategy, make_mesh
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1), strategy=Strategy.PALLAS_RING)
        v = _ints((513,), seed=12)
        out = Session.local_row(sess.all_reduce(sess.lift(v)))
        assert np.array_equal(out, sess.size * v)
        assert PC.effective_impl("pallas") == "pallas"

    def test_fused_strategy_with_session_compression(self, interpret_gate):
        from kungfu_tpu.plan import Strategy, make_mesh
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1), strategy=Strategy.PALLAS_RING_FUSED)
        sess.set_compression("int8")
        v = _ints((2048,), seed=13)
        out = Session.local_row(sess.all_reduce(sess.lift(v)))
        want = sess.size * v
        tol = (sess.size + 1) * np.abs(want).max() / 127.0
        assert np.abs(out - want).max() <= tol

    def test_impl_tag_fallback_aware(self, monkeypatch):
        from kungfu_tpu.plan import Impl
        from kungfu_tpu.session import Session

        monkeypatch.delenv("KFT_PALLAS", raising=False)
        assert Session._impl_tag(Impl.PSUM) == "xla"
        assert Session._impl_tag(Impl.PALLAS_RING) == "xla"  # gate off
        monkeypatch.setenv("KFT_PALLAS", "interpret")
        assert Session._impl_tag(Impl.PALLAS_RING) == "pallas"
        cfg = comp.resolve("int8")
        assert Session._impl_tag(Impl.PALLAS_RING_FUSED, cfg) == "pallas_fused"
        assert Session._impl_tag(Impl.PALLAS_RING_FUSED) == "pallas"

    def test_oversized_payload_falls_back(self, interpret_gate, monkeypatch):
        """A payload past the VMEM scratch budget must take the lax path
        (and still be correct) instead of building an unloadable kernel."""
        monkeypatch.setenv("KFT_PALLAS_VMEM_MIB", "0")
        n = 2
        mesh = _mesh(n)
        x = jnp.asarray(_ints((n * 256,), seed=14))
        out = _shmap(lambda v: PC.ring_all_reduce(v, "dp"), mesh)(x)
        want = _shmap(lambda v: C.ring_all_reduce(v, "dp"), mesh)(x)
        assert np.array_equal(np.asarray(out), np.asarray(want))


# -- planner registration -------------------------------------------------------------


class TestPlannerRegistration:
    def test_pallas_plans_enumerated_and_lint_clean(self):
        from kungfu_tpu.planner.candidates import (
            default_buckets, enumerate_plans, hosts_for,
        )
        from kungfu_tpu.planner.validate import validate_plan

        for world, hc in ((2, 1), (4, 1), (8, 2)):
            hosts = hosts_for(world, hc)
            plans = enumerate_plans(world, hosts, default_buckets()[0])
            pallas = [p for p in plans if p.algorithm.startswith("pallas")]
            assert {p.algorithm for p in pallas} == {
                "pallas_ring", "pallas_ring_fused"}
            fused_wires = {p.wire_scheme(p.legs[0]) for p in pallas
                           if p.algorithm == "pallas_ring_fused"}
            assert fused_wires == {"int8", "fp8"}
            for p in pallas:
                assert validate_plan(p, hosts) == []

    def test_pallas_plan_json_roundtrip(self):
        from kungfu_tpu.planner.candidates import Plan

        p = Plan(algorithm="pallas_ring_fused",
                 strategy_name="PALLAS_RING_FUSED",
                 wire=(("ici", "int8"),), bucket="small", world=4)
        assert Plan.from_json(p.to_json()) == p
        assert p.compression() == "int8"

    def test_pallas_program_lint_clean_on_live_session(self, interpret_gate):
        from kungfu_tpu.planner.candidates import (
            default_buckets, enumerate_plans, hosts_for,
        )
        from kungfu_tpu.planner.validate import validate_plan
        from kungfu_tpu.session import Session

        n = min(4, len(jax.devices()))
        sess = Session(_mesh(n))
        hosts = hosts_for(n, 1)
        for p in enumerate_plans(n, hosts, default_buckets()[0]):
            if p.algorithm.startswith("pallas"):
                assert validate_plan(p, hosts, session=sess) == [], p.describe()

    def test_cost_model_alpha_discount(self):
        """The pallas ring pays α once per kernel, the lax ring per round
        — so in an α-dominated regime the planner must price pallas_ring
        below ring at equal wire bytes."""
        from kungfu_tpu.planner.candidates import Plan, default_buckets, hosts_for
        from kungfu_tpu.planner.cost import predict_ms
        from kungfu_tpu.planner.model import CostModel, LinkModel

        model = CostModel(links={"ici": LinkModel(alpha_ms=1.0,
                                                  beta_ms_per_mib=0.001)})
        hosts = hosts_for(4, 1)
        b = default_buckets()[0]
        mk = lambda alg, strat: Plan(algorithm=alg, strategy_name=strat,
                                     wire=(("ici", "none"),), bucket=b.id,
                                     world=4)
        ring = predict_ms(mk("ring", "RING"), b.rep_bytes, model, hosts)
        pallas = predict_ms(mk("pallas_ring", "PALLAS_RING"), b.rep_bytes,
                            model, hosts)
        assert pallas < ring

    def test_fused_cost_includes_codec(self):
        from kungfu_tpu.planner.candidates import Plan, default_buckets, hosts_for
        from kungfu_tpu.planner.cost import predict_ms
        from kungfu_tpu.planner.model import CostModel, LinkModel

        model = CostModel(
            links={"ici": LinkModel(alpha_ms=0.0, beta_ms_per_mib=1.0)},
            codecs={"int8": 5.0})
        hosts = hosts_for(4, 1)
        b = default_buckets()[1]
        plain = Plan(algorithm="pallas_ring", strategy_name="PALLAS_RING",
                     wire=(("ici", "none"),), bucket=b.id, world=4)
        fused = Plan(algorithm="pallas_ring_fused",
                     strategy_name="PALLAS_RING_FUSED",
                     wire=(("ici", "int8"),), bucket=b.id, world=4)
        p_plain = predict_ms(plain, b.rep_bytes, model, hosts)
        p_fused = predict_ms(fused, b.rep_bytes, model, hosts)
        # int8 moves ~4x fewer wire bytes but pays γ: with γ this large the
        # codec term must dominate the saving
        assert p_fused > p_plain / 3.9


# -- telemetry ------------------------------------------------------------------------


class TestTelemetry:
    def test_collective_impl_counter(self):
        from kungfu_tpu.monitor.counters import Counters

        c = Counters()
        c.record_collective_impl("pallas")
        c.record_collective_impl("pallas")
        c.record_collective_impl("xla")
        ev = c.events()
        assert ev["collective_impl_pallas"] == 2
        assert ev["collective_impl_xla"] == 1

    def test_span_carries_collective_impl(self, monkeypatch):
        from kungfu_tpu.plan import Strategy, make_mesh
        from kungfu_tpu.session import Session
        from kungfu_tpu.utils import trace as T

        monkeypatch.setenv(T.ENABLE_ENV, "1")
        monkeypatch.delenv("KFT_PALLAS", raising=False)
        T.global_trace_buffer().clear()
        try:
            sess = Session(make_mesh(dp=-1), strategy=Strategy.PALLAS_RING)
            sess.all_reduce(sess.lift(np.ones(64, np.float32)),
                            name="tag-probe")
            spans = [s for s in T.global_trace_buffer().spans()
                     if s.name == "collective:tag-probe"]
            assert spans, "collective span missing"
            assert spans[-1].args.get("collective_impl") == "xla"  # gate off
        finally:
            T.global_trace_buffer().clear()

    def test_bucket_layout_recorded(self, monkeypatch):
        from kungfu_tpu.monitor import counters as mc
        from kungfu_tpu.optimizers.sync import (
            _pack_buckets, _record_bucket_layout,
        )

        c = mc.Counters()
        monkeypatch.setattr(mc, "counters_if_enabled", lambda: c)
        leaves = [jnp.zeros(1000, jnp.float32), jnp.zeros(10, jnp.float32)]
        buckets = _pack_buckets(leaves, 2048)
        _record_bucket_layout(leaves, buckets)
        assert c.gauges()["grad_sync_buckets"] == len(buckets)
        hist = c.hist_summaries()["collective_overlap"]["grad_sync_mib"]
        assert hist["count"] == len(buckets)
