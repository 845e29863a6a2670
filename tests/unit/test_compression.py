"""Compression subsystem: quantizer bounds, compressed collectives under
jit/shard_map, error feedback on a toy quadratic, and the monitor wiring.

Tolerances are scale-dependent by construction: one int8 quantization of a
block with absolute max M rounds each element by at most M/(2*127); the
quantized allreduce pays one such error per peer on the RS leg plus one on
the requantized AG leg, so

    |err| <= (sum_i M_i + M_sum) / 254        per element (deterministic)

The tests assert this exact bound (computed from the data) rather than a
magic rtol.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from kungfu_tpu import compression as comp
from jax import shard_map
from kungfu_tpu.plan import make_mesh, make_hierarchical_mesh

pytestmark = pytest.mark.compression


def _mesh_dp(n: int):
    """n-device 1-D dp mesh (make_mesh insists on using every device)."""
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("dp",))


# -- quantizer ------------------------------------------------------------------------


class TestQuantRoundtrip:
    @pytest.mark.parametrize("scheme,block", [("int8", 64), ("int8", 256), ("fp8", 128)])
    def test_blockwise_error_bound(self, scheme, block):
        rng = np.random.RandomState(0)
        x = (rng.randn(7, 1000) * np.exp(rng.randn(7, 1))).astype(np.float32)
        cfg = comp.CompressionConfig(scheme=scheme, block=block)
        rt = np.asarray(comp.roundtrip(jnp.asarray(x), cfg))
        # per-block bound: |x - Q(x)| <= absmax_block / codemax (fp8 mantissa
        # gives a relative bound; absmax/codemax covers both conservatively
        # only for int8, so fp8 uses its max relative spacing 2^-2)
        flat = x.reshape(-1)
        pad = (-flat.size) % block
        flat = np.pad(flat, (0, pad))
        blocks = flat.reshape(-1, block)
        err = np.pad((x - rt).reshape(-1), (0, pad)).reshape(-1, block)
        absmax = np.abs(blocks).max(axis=1, keepdims=True)
        if scheme == "int8":
            bound = absmax / 254 + 1e-7  # round-to-nearest: scale/2
        else:
            bound = np.maximum(np.abs(blocks) * 0.125, absmax / 448) + 1e-7
        assert (np.abs(err) <= bound).all()

    def test_zero_block_is_exact(self):
        x = jnp.zeros((512,), jnp.float32)
        for name in ("int8", "fp8", "bf16"):
            rt = comp.roundtrip(x, comp.resolve(name))
            np.testing.assert_array_equal(np.asarray(rt), 0.0)

    def test_stochastic_rounding_is_unbiased(self):
        # E[Q(x)] == x: average many independently-dithered roundtrips
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(256).astype(np.float32))
        cfg = comp.resolve("int8-sr")
        n = 400
        acc = np.zeros(256, np.float64)
        for i in range(n):
            acc += np.asarray(comp.roundtrip(x, cfg, key=jax.random.PRNGKey(i)))
        scale = float(jnp.max(jnp.abs(x))) / 127
        # mean converges to x at sigma ~ scale/sqrt(12 n); 6 sigma margin
        assert np.abs(acc / n - np.asarray(x)).max() < 6 * scale / np.sqrt(12 * n)

    def test_sparsify_topk_picks_largest(self):
        x = jnp.asarray(np.arange(100, dtype=np.float32) - 50)
        cfg = comp.CompressionConfig(scheme="topk", k=0.1)
        vals, idx = comp.sparsify(x, cfg)
        # x holds -50..49: the 10 largest magnitudes are 50, ±49..±46, -45
        assert set(int(v) for v in np.abs(np.asarray(vals))) == {50, 49, 48, 47, 46, 45}

    def test_wire_bytes_ratios(self):
        n = 1 << 20
        assert comp.resolve("none").wire_bytes(n) == 4 * n
        assert comp.resolve("bf16").wire_bytes(n) == 2 * n
        # int8 at block 256: 1 byte/elem + 4/256 scale overhead -> ~3.94x
        assert comp.resolve("int8").compression_ratio(n) > 3.9
        assert comp.resolve("fp8").compression_ratio(n) > 3.9
        # sparse at 1%: ~50x
        assert comp.resolve("topk").compression_ratio(n) > 40

    def test_registry_resolve(self):
        assert comp.resolve(None).scheme == "none"
        assert comp.resolve("INT8") is comp.INT8
        assert comp.resolve(comp.FP8) is comp.FP8
        with pytest.raises(ValueError):
            comp.resolve("int3")
        with pytest.raises(ValueError):
            comp.CompressionConfig(scheme="huffman")
        # per-axis dict: missing axis = uncompressed
        assert comp.resolve_for_axis({"dcn": "int8"}, "ici").scheme == "none"
        assert comp.resolve_for_axis({"dcn": "int8"}, "dcn").scheme == "int8"


# -- compressed collectives under jit/shard_map ---------------------------------------


def _stacked(mesh, vals):
    return jax.device_put(vals[:, None, :], NamedSharding(mesh, P("dp")))


class TestCompressedAllReduce:
    @pytest.fixture(scope="class")
    def mesh4(self):
        # acceptance: >= 4 CPU devices (conftest forces 8; use 4 of them)
        return _mesh_dp(4)

    @pytest.mark.parametrize("scheme", ["int8", "fp8", "bf16"])
    def test_matches_fp32_within_scale_bound(self, mesh4, scheme):
        n = mesh4.shape["dp"]
        rng = np.random.RandomState(2)
        vals = rng.randn(n, 1337).astype(np.float32)
        cfg = comp.resolve(scheme)

        fn = jax.jit(shard_map(
            lambda y: comp.all_reduce(jnp.squeeze(y, 0), "dp", cfg, op="sum")[None],
            mesh=mesh4, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
        ))
        out = np.asarray(fn(_stacked(mesh4, vals)))
        want = vals.sum(axis=0)
        # every peer ends with the identical reduced tensor
        np.testing.assert_array_equal(out[:, 0], np.broadcast_to(out[0, 0], (n, 1337)))
        err = np.abs(out[0, 0] - want)
        if scheme == "int8":
            # scale-dependent bound: one quant per peer (RS) + one on AG
            bound = (np.abs(vals).max(axis=0).sum() + np.abs(want).max()) / 254 + 1e-6
            assert err.max() <= bound
        else:
            assert err.max() / (np.abs(want).max() + 1e-9) < 0.06

    def test_mean_and_dtype_preserved(self, mesh4):
        n = mesh4.shape["dp"]
        vals = np.random.RandomState(3).randn(n, 96).astype(np.float32)
        fn = jax.jit(shard_map(
            lambda y: comp.all_reduce(
                jnp.squeeze(y, 0).astype(jnp.bfloat16), "dp", "int8", op="mean"
            )[None],
            mesh=mesh4, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
        ))
        out = fn(_stacked(mesh4, vals))
        assert out.dtype == jnp.bfloat16
        got = np.asarray(out.astype(jnp.float32))[0, 0]
        want = vals.astype(np.float32).mean(axis=0)
        assert np.abs(got - want).max() / (np.abs(want).max() + 1e-9) < 0.05

    def test_non_sum_op_falls_back_uncompressed(self, mesh4):
        n = mesh4.shape["dp"]
        vals = np.random.RandomState(4).randn(n, 64).astype(np.float32)
        fn = jax.jit(shard_map(
            lambda y: comp.all_reduce(jnp.squeeze(y, 0), "dp", "int8", op="max")[None],
            mesh=mesh4, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
        ))
        out = np.asarray(fn(_stacked(mesh4, vals)))
        np.testing.assert_allclose(out[0, 0], vals.max(axis=0), rtol=1e-6)

    def test_sparse_scheme_rejected_for_allreduce(self):
        with pytest.raises(ValueError, match="sparsifier"):
            comp.all_reduce(jnp.zeros(8), "dp", "topk")

    def test_hierarchical_per_axis(self):
        mesh = make_hierarchical_mesh(2)  # 2 hosts x 4 chips
        vals = np.random.RandomState(5).randn(8, 555).astype(np.float32)
        fn = jax.jit(shard_map(
            lambda y: comp.hierarchical_all_reduce(
                jnp.squeeze(y, 0), "ici", "dcn",
                ici_config=None, dcn_config="int8", op="sum",
            )[None],
            mesh=mesh, in_specs=P(("dcn", "ici")), out_specs=P(("dcn", "ici")),
            check_vma=False,
        ))
        stacked = jax.device_put(
            vals[:, None, :], NamedSharding(mesh, P(("dcn", "ici")))
        )
        out = np.asarray(fn(stacked))
        want = vals.sum(axis=0)
        assert np.abs(out[0, 0] - want).max() / np.abs(want).max() < 0.02

    def test_sparse_pair_exchange_mixes_only_k(self):
        mesh = _mesh_dp(8)
        n = 8
        vals = np.random.RandomState(6).randn(n, 200).astype(np.float32)
        perm = [((i + 1) % n, i) for i in range(n)]
        cfg = comp.CompressionConfig(scheme="topk", k=0.05)
        fn = jax.jit(shard_map(
            lambda y: comp.sparse_pair_exchange(
                jnp.squeeze(y, 0), "dp", perm, cfg
            )[None],
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
        ))
        out = np.asarray(fn(_stacked(mesh, vals)))
        k = 10  # 5% of 200
        for i in range(n):
            changed = np.nonzero(out[i, 0] != vals[i])[0]
            assert len(changed) <= k
            src = (i + 1) % n  # i pulls from i+1
            np.testing.assert_allclose(
                out[i, 0, changed],
                0.5 * (vals[i, changed] + vals[src, changed]),
                rtol=1e-6,
            )


# -- error feedback -------------------------------------------------------------------


class TestErrorFeedback:
    def test_residual_is_local_quant_error(self):
        rng = np.random.RandomState(7)
        g = {"w": jnp.asarray(rng.randn(300).astype(np.float32))}
        cfg = comp.resolve("int8")
        ef = comp.error_feedback.init(g)
        corrected, ef2 = comp.error_feedback.apply(g, ef, cfg)
        np.testing.assert_array_equal(np.asarray(corrected["w"]), np.asarray(g["w"]))
        want = np.asarray(g["w"]) - np.asarray(comp.roundtrip(g["w"], cfg))
        np.testing.assert_allclose(np.asarray(ef2.residual["w"]), want, atol=1e-7)

    def test_ef_sgd_matches_uncompressed_on_quadratic(self):
        """Compressed S-SGD with EF tracks uncompressed SGD on
        f(w) = mean_i 0.5||w - t_i||^2 (minimizer: mean of the targets)."""
        import optax
        from kungfu_tpu.optimizers import synchronous_sgd

        mesh = _mesh_dp(4)
        n, d, lr, steps = 4, 64, 0.3, 60
        rng = np.random.RandomState(8)
        targets = (rng.randn(n, d) * np.array([1.0, 5.0, 0.1, 2.0])[:, None]).astype(
            np.float32
        )
        w_star = targets.mean(axis=0)

        # coarse quantizer (one block across the vector) makes EF matter
        cfg = comp.CompressionConfig(scheme="int8", block=d, error_feedback=True)

        def run(tx):
            def body(t):
                t = t.reshape(-1)  # per-device (1, 1, d) -> (d,)
                w = jnp.zeros((d,), jnp.float32)
                state = tx.init(w)

                def step(carry, _):
                    w, state = carry
                    u, state = tx.update(w - t, state, w)
                    return (w + u, state), None

                (w, _), _ = jax.lax.scan(step, (w, state), None, length=steps)
                return w[None]

            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                check_vma=False,
            ))
            return np.asarray(fn(targets[:, None, :]))[0]  # (n, d) -> device 0's w

        w_plain = run(synchronous_sgd(optax.sgd(lr)))
        w_comp = run(synchronous_sgd(optax.sgd(lr), compression=cfg))
        # uncompressed converges to w* geometrically; EF-compressed must
        # land within quantization resolution of the same point
        assert np.abs(w_plain - w_star).max() < 1e-3
        tol = np.abs(targets).max() / 127 + 1e-3
        assert np.abs(w_comp - w_star).max() < tol
        assert np.abs(w_comp - w_plain).max() < tol

    def test_gossip_compressed_pull_runs(self):
        import optax
        from kungfu_tpu.optimizers import pair_averaging

        mesh = _mesh_dp(8)
        tx = pair_averaging(
            optax.sgd(0.1), axis_size=8,
            compression=comp.CompressionConfig(scheme="topk", k=0.2),
        )
        vals = np.random.RandomState(9).randn(8, 40).astype(np.float32)

        def body(p):
            p = jnp.squeeze(p, 0)
            state = tx.init(p)
            u, _ = tx.update(jnp.zeros_like(p), state, p)
            return (p + u)[None]

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
        ))
        out = np.asarray(fn(vals[:, None, :]))
        # zero grads: the update is pure mixing -> values move toward peers
        assert np.isfinite(out).all()
        assert (out[:, 0] != vals).any()


# -- adaptive bit-width + policy ------------------------------------------------------


class TestAdaptiveCompression:
    def test_noise_adaptive_runs_and_reduces(self):
        import optax
        from kungfu_tpu.optimizers import noise_adaptive_compression

        mesh = _mesh_dp(4)
        tx = noise_adaptive_compression(
            optax.sgd(0.1), local_batch_size=32, gns_threshold=0.0,
        )
        vals = np.random.RandomState(10).randn(4, 128).astype(np.float32)

        def body(g):
            g = jnp.squeeze(g, 0)
            state = tx.init(g)
            u, state = tx.update(g, state, g)
            return u[None], state.compressed, state.noise_scale

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("dp"),
            out_specs=(P("dp"), P(), P()), check_vma=False,
        ))
        u, compressed, gns = fn(_stacked(mesh, vals))
        want = -0.1 * vals.mean(axis=0)
        got = np.asarray(u)[0, 0]
        assert bool(compressed)  # threshold 0: compressed from step 0
        assert np.abs(got - want).max() / np.abs(want).max() < 0.02

    def test_compression_policy_hysteresis(self):
        from kungfu_tpu.policy import CompressionPolicy

        switched = []
        pol = CompressionPolicy(
            switch=switched.append, threshold=100.0, hysteresis=0.5
        )
        pol.after_step({"noise_scale": 10.0})
        assert switched == [] and pol.active.scheme == "none"
        pol.after_step({"noise_scale": 150.0})
        assert pol.active.scheme == "int8" and len(switched) == 1
        # inside the hysteresis band: no flapping
        pol.after_step({"noise_scale": 80.0})
        assert pol.active.scheme == "int8" and len(switched) == 1
        pol.after_step({"noise_scale": 40.0})
        assert pol.active.scheme == "none" and len(switched) == 2


# -- monitor wiring -------------------------------------------------------------------


class TestCounters:
    def test_wire_and_quant_error_counters(self):
        from kungfu_tpu.monitor.counters import Counters

        c = Counters()
        c.add_wire("grads", logical_bytes=4000, wire_bytes=1016)
        c.add_wire("grads", logical_bytes=4000, wire_bytes=1016)
        c.record_quant_error("grads", 0.007)
        logical, wire = c.wire_totals()
        assert logical["grads"] == 8000 and wire["grads"] == 2032
        assert abs(c.compression_ratios()["grads"] - 8000 / 2032) < 1e-9
        text = c.prometheus_text()
        assert 'collective_wire_total_bytes{op="grads"} 2032' in text
        assert 'collective_quantization_error{op="grads"} 0.007' in text

    def test_session_records_compressed_bytes(self, monkeypatch):
        monkeypatch.setenv("KFT_CONFIG_ENABLE_MONITORING", "1")
        from kungfu_tpu.monitor.counters import global_counters
        from kungfu_tpu.session import Session

        sess = Session(make_mesh(dp=-1))
        x = np.random.RandomState(11).randn(sess.size, 64).astype(np.float32)
        a = np.asarray(sess.all_reduce(x, name="c8"))
        b = np.asarray(sess.all_reduce(x, compression="int8", name="c8"))
        assert np.abs(a - b).max() / np.abs(a).max() < 0.05
        ratios = global_counters().compression_ratios()
        assert ratios.get("c8", 0) > 3.0  # acceptance: >= 3x fewer bytes
        assert 0 < global_counters().quant_errors()["c8"] < 0.1
