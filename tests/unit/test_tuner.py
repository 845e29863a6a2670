"""Tests for the compute autotuner (kungfu_tpu.tuner).

Covers the subsystem's contract end to end: the search space enumerates
every tuned axis (tiles, head layout, backward arm, remat policy, CE
chunk, donation/buckets) and stays JSON round-trippable; the footprint
gate rejects configs that blow KFT_PALLAS_VMEM_MIB / the HBM budget; the
prior cache round-trips, misses on any stale key component and drops
stale entries; tile resolution (flash_block=None) is the shape table
beside the kernels, clamped to VMEM, explicit ints win, and a cached
winner reaches a model through `ComputeTuner.apply` alone; the measured
runoff always keeps the hand-tuned default as a
control (the tuned config of record never loses to it) and a cache hit
skips measurement; tuned-vs-default numerics: the resolution path and the
remat policies are bit-identical on the forward pass and grad-close on
the backward; and bucket_bytes="auto" / chunked-CE block resolution feed
the optimizer and loss layers.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import kungfu_tpu.tuner as T
from kungfu_tpu.tuner import cache as tuner_cache
from kungfu_tpu.tuner import footprint as F

pytestmark = pytest.mark.tuner


def flagship(batch=4):
    return T.ShapeKey(vocab_size=32000, d_model=1024, n_layers=24,
                      n_heads=16, n_kv_heads=0, d_ff=4096, seq_len=2048,
                      batch_per_chip=batch, dtype="bfloat16", causal=True)


def tiny(**kw):
    base = dict(vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                n_kv_heads=0, d_ff=32, seq_len=16, batch_per_chip=2,
                dtype="float32", causal=True)
    base.update(kw)
    return T.ShapeKey(**base)


class TestSpace:
    def test_enumeration_covers_every_axis(self):
        cands = T.enumerate_configs(flagship())
        assert {c.head_dim for c in cands} == {64, 128}
        assert {(c.block_q, c.block_k) for c in cands} >= {
            (128, 128), (256, 512), (512, 1024)}
        assert {c.backward for c in cands} == {"pallas", "xla"}
        assert {(c.remat, c.remat_policy) for c in cands} == {
            (False, "none"), (True, "full"), (True, "dots")}
        assert {c.ce_chunk for c in cands} == {0, 2048, 8192}
        assert {c.bucket_bytes for c in cands} == {0, 4 << 20}
        assert {c.donate for c in cands} == {True, False}

    def test_gqa_keeps_declared_layout(self):
        cands = T.enumerate_configs(flagship().__class__(
            **{**flagship().to_json(), "n_kv_heads": 4}))
        # the kv-head count is a model property: no head re-factoring
        assert {c.head_dim for c in cands} == {64}

    def test_tiles_clamp_to_sequence(self):
        cands = T.enumerate_configs(tiny())
        assert {(c.block_q, c.block_k) for c in cands} == {(16, 16)}

    def test_ce_chunks_beyond_vocab_are_dense(self):
        assert {c.ce_chunk for c in T.enumerate_configs(tiny())} == {0}

    def test_config_json_roundtrip(self):
        cfg = T.StepConfig(block_q=256, block_k=512, backward="pallas",
                           head_dim=128, remat=True, remat_policy="dots",
                           ce_chunk=4096, donate=False,
                           bucket_bytes=4 << 20)
        assert T.StepConfig.from_json(
            json.loads(json.dumps(cfg.to_json()))) == cfg

    def test_shape_digest_sensitivity(self):
        a = flagship()
        assert a.digest() == flagship().digest()
        for field, val in (("batch_per_chip", 8), ("seq_len", 4096),
                           ("n_heads", 8), ("dtype", "float32")):
            b = T.ShapeKey(**{**a.to_json(), field: val})
            assert b.digest() != a.digest(), field

    def test_shape_of_transformer_config(self):
        from kungfu_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(vocab_size=64, d_model=16, n_layers=1,
                                n_heads=2, d_ff=32, max_len=16,
                                dtype=jnp.float32)
        shape = T.ShapeKey.of(cfg, batch_per_chip=2)
        assert shape == tiny()


class TestFootprint:
    def test_vmem_gate_rejects_oversized_tiles(self, monkeypatch):
        shape = flagship()
        big = T.StepConfig(block_q=8192, block_k=8192, head_dim=64)
        reason = T.check_fit(big, shape)
        assert reason is not None and "VMEM" in reason
        assert T.check_fit(T.StepConfig(head_dim=64), shape) is None

    def test_vmem_budget_env_tightens_the_gate(self, monkeypatch):
        shape = flagship()
        ok = T.StepConfig(block_q=512, block_k=1024, head_dim=64)
        assert T.check_fit(ok, shape) is None
        monkeypatch.setenv(F.VMEM_ENV, "1")
        assert "VMEM" in T.check_fit(ok, shape)

    def test_hbm_gate_and_levers(self, monkeypatch):
        monkeypatch.setenv(F.HBM_ENV, "2")
        shape = flagship(batch=8)
        dense = T.StepConfig(head_dim=64)
        assert "footprint" in (T.check_fit(dense, shape) or "")
        lean = T.StepConfig(head_dim=64, remat=True, remat_policy="full",
                            ce_chunk=2048)
        assert T.step_hbm_bytes(lean, shape)["total"] < \
            T.step_hbm_bytes(dense, shape)["total"]

    def test_donation_halves_state_footprint(self):
        shape = flagship()
        kept = T.step_hbm_bytes(T.StepConfig(donate=True), shape)["state"]
        copied = T.step_hbm_bytes(T.StepConfig(donate=False), shape)["state"]
        assert copied == 2 * kept

    def test_predictor_prefers_mxu_native_head_dim(self):
        shape = flagship()
        ms64 = T.predict_step_ms(T.StepConfig(head_dim=64), shape,
                                 peak_flops=197e12, peak_hbm=819e9)
        ms128 = T.predict_step_ms(T.StepConfig(head_dim=128), shape,
                                  peak_flops=197e12, peak_hbm=819e9)
        assert ms128 < ms64

    def test_remat_costs_predicted_flops(self):
        shape = flagship()
        base = T.predict_step_ms(T.StepConfig(head_dim=128), shape,
                                 peak_flops=197e12, peak_hbm=819e9)
        dots = T.predict_step_ms(
            T.StepConfig(head_dim=128, remat=True, remat_policy="dots"),
            shape, peak_flops=197e12, peak_hbm=819e9)
        full = T.predict_step_ms(
            T.StepConfig(head_dim=128, remat=True, remat_policy="full"),
            shape, peak_flops=197e12, peak_hbm=819e9)
        assert base < dots < full

    def test_default_bucket_bytes_table(self):
        assert T.default_bucket_bytes(1 << 20) is None
        assert T.default_bucket_bytes(64 << 20) == 4 << 20

    def test_default_ce_block_streams_bounded_blocks(self):
        assert T.default_ce_block() == 2048
        assert T.default_ce_block(16384, 32000) == 1024
        assert 512 <= T.default_ce_block(10 ** 6, 32000) <= 8192
        # tiny vocab clamps down
        assert T.default_ce_block(128, 1024) <= 1024


class TestPriorCache:
    def test_round_trip_and_stale_key_miss(self, tmp_path):
        path = str(tmp_path / "prior.json")
        shape = tiny()
        cfg = T.StepConfig(block_q=256, block_k=512)
        c = T.PriorCache(path)
        c.put(shape, "cpu", "0.4.37", cfg, measured_ms=1.0)
        # fresh load round-trips (restart persistence)
        again = T.PriorCache(path)
        assert again.get_config(shape.digest(), "cpu", "0.4.37") == cfg
        # any stale key component misses
        assert again.get_config(shape.digest(), "tpu", "0.4.37") is None
        assert again.get_config(shape.digest(), "cpu", "0.5.0") is None
        assert again.get_config(tiny(seq_len=32).digest(), "cpu",
                                "0.4.37") is None

    def test_invalidate_stale_drops_other_versions(self, tmp_path):
        path = str(tmp_path / "prior.json")
        c = T.PriorCache(path)
        c.put(tiny(), "cpu", "0.4.37", T.StepConfig())
        c.put(tiny(seq_len=32), "cpu", "0.4.37", T.StepConfig())
        c.put(tiny(), "cpu", "0.3.0", T.StepConfig())
        c.put(tiny(), "tpu", "0.4.37", T.StepConfig())
        assert c.invalidate_stale("cpu", "0.4.37") == 2
        assert len(c) == 2  # both shapes on the live key survive

    def test_corrupt_file_is_empty_not_fatal(self, tmp_path):
        path = str(tmp_path / "prior.json")
        with open(path, "w") as f:
            f.write("{not json")
        c = T.PriorCache(path)
        assert len(c) == 0 and c.load_error

    def test_no_file_no_answer_on_any_backend(self):
        """The cache holds what a runoff on this stack wrote and nothing
        else: no record ships with the code."""
        for path in ("", "/nonexistent/never-created.json"):
            c = T.PriorCache(path)
            assert len(c) == 0
            for backend in ("tpu", "cpu"):
                assert c.get_config(flagship().digest(), backend,
                                    "whatever-version") is None

    def test_entries_of_an_earlier_version_still_load(self, tmp_path):
        """A file written when StepConfig had more axes (the fused-matmul
        keys, PR 45) loads: unknown keys are dropped, known ones kept."""
        path = str(tmp_path / "prior.json")
        mine = T.StepConfig(block_q=512, block_k=512, head_dim=64)
        c = T.PriorCache(path)
        c.put(flagship(), "tpu", "0.4.37", mine)
        with open(path) as f:
            d = json.load(f)
        for e in d["entries"].values():
            e["config"].update(fused_matmul=True, fused_block_m=128,
                               fused_block_n=128)
        with open(path, "w") as f:
            json.dump(d, f)
        assert T.PriorCache(path).get_config(
            flagship().digest(), "tpu", "0.4.37") == mine


class TestResolution:
    def _cfg(self, **kw):
        from kungfu_tpu.models.transformer import TransformerConfig

        base = dict(vocab_size=32000, d_model=1024, n_layers=24, n_heads=16,
                    d_ff=4096, max_len=2048, rope=True)
        base.update(kw)
        return TransformerConfig(**base)

    def _traced_tiles(self, cfg, monkeypatch, seq_len=2048):
        """(block_q, block_k) `Attention` hands the flash kernel when a
        one-layer model of `cfg`'s attention shape is traced."""
        from kungfu_tpu.models.transformer import TransformerLM
        from kungfu_tpu.ops import flash

        seen = []
        real = flash.flash_attention

        def spy(q, k, v, **kw):
            seen.append((kw["block_q"], kw["block_k"]))
            return real(q, k, v, **kw)

        monkeypatch.setattr(flash, "flash_attention", spy)
        small = dataclasses.replace(cfg, vocab_size=64, n_layers=1, d_ff=64,
                                    attention="flash")
        model = TransformerLM(small)
        jax.eval_shape(lambda t: model.init(jax.random.PRNGKey(0), t),
                       jax.ShapeDtypeStruct((1, seq_len), jnp.int32))
        assert len(set(seen)) == 1, seen
        return seen[0]

    def test_explicit_ints_always_win(self, monkeypatch):
        cfg = self._cfg(flash_block_q=64, flash_block_k=96)
        assert T.resolve_flash_blocks(cfg, seq_len=2048) == (64, 96)
        assert self._traced_tiles(cfg, monkeypatch) == (64, 96)
        # one explicit int wins on its own axis, the table fills the other
        one = self._cfg(flash_block_q=128)
        assert T.resolve_flash_blocks(one, seq_len=2048) == (128, 1024)

    def test_shape_conditional_hunt_defaults(self, monkeypatch):
        # head_dim 64 at seq 2048: the 16×64 sweep winner
        assert T.resolve_flash_blocks(
            self._cfg(), seq_len=2048) == (512, 1024)
        # head_dim 128: the MXU-native winner
        assert T.resolve_flash_blocks(
            self._cfg(n_heads=8), seq_len=2048) == (256, 512)
        assert self._traced_tiles(self._cfg(n_heads=8),
                                  monkeypatch) == (256, 512)
        # short sequences stay safe
        assert T.default_flash_blocks(64, 512) == (128, 128)
        assert T.default_flash_blocks(64, 1024) == (256, 256)

    def test_installed_winner_is_what_attention_traces(self, tmp_path,
                                                       monkeypatch):
        path = str(tmp_path / "prior.json")
        cfg = self._cfg()
        shape = T.ShapeKey.of(cfg, batch_per_chip=4, seq_len=2048)
        cache = T.PriorCache(path)
        cache.put(shape, T.backend_name(), T.jax_version(),
                  T.StepConfig(block_q=256, block_k=256, head_dim=64))
        tuned, _ = T.ComputeTuner(shape, cache=cache).apply(cfg)
        assert (tuned.flash_block_q, tuned.flash_block_k) == (256, 256)
        assert self._traced_tiles(tuned, monkeypatch) == (256, 256)
        # the model itself reads no file: untuned, it traces the table
        # even where KFT_TUNER_CACHE names the winner's file
        monkeypatch.setenv(tuner_cache.CACHE_ENV, path)
        assert self._traced_tiles(cfg, monkeypatch) == (512, 1024)
        # a winner for ANOTHER layout installs that layout with its
        # tiles; they never land on the declared head_dim alone
        cache.put(shape, T.backend_name(), T.jax_version(),
                  T.StepConfig(block_q=256, block_k=512, head_dim=128))
        tuned, _ = T.ComputeTuner(shape, cache=cache).apply(cfg)
        assert tuned.d_model // tuned.n_heads == 128
        assert self._traced_tiles(tuned, monkeypatch) == (256, 512)

    def test_vmem_clamp_degrades_instead_of_wedging(self, monkeypatch):
        monkeypatch.setenv(F.VMEM_ENV, "2")
        bq, bk = T.resolve_flash_blocks(self._cfg(), seq_len=2048)
        probe = T.StepConfig(block_q=bq, block_k=bk, head_dim=64)
        assert F.flash_vmem_bytes(
            probe, flagship()) <= F.vmem_budget_bytes()
        assert (bq, bk) != (512, 1024)
        # the same clamp halves an oversize winner on its way in
        cfg = self._cfg()
        tuner = T.ComputeTuner(T.ShapeKey.of(cfg, 4, seq_len=2048),
                               cache=None)
        tuned, _ = tuner.apply(cfg, T.StepConfig(block_q=512, block_k=1024,
                                                 head_dim=64))
        assert (tuned.flash_block_q, tuned.flash_block_k) == (bq, bk)
        assert self._traced_tiles(tuned, monkeypatch) == (bq, bk)


class TestTuneRunoff:
    def _fake_measure(self, times):
        calls = []

        def measure(shape, cfg, steps):
            calls.append(cfg)
            return {"step_ms": times(cfg), "mfu": None}

        return measure, calls

    def test_default_is_always_a_control_and_never_wins_late(self, tmp_path):
        shape = tiny()
        default = T.default_config(shape)

        # every non-default config measures faster: winner is tuned
        measure, calls = self._fake_measure(
            lambda cfg: 5.0 if cfg == default else 1.0)
        tuner = T.ComputeTuner(shape, cache=str(tmp_path / "c.json"),
                               measure_fn=measure)
        rec = tuner.tune(steps=1, measure_top=2)
        assert default in calls  # the control ran
        assert rec["default_ms"] == 5.0
        assert rec["measured_ms"] == 1.0
        assert rec["speedup_vs_default"] == 5.0
        assert T.StepConfig.from_json(rec["config"]) != default

    def test_tuned_config_never_loses_to_default(self, tmp_path):
        shape = tiny()
        default = T.default_config(shape)
        # the default measures FASTEST: it must be the config of record
        measure, _ = self._fake_measure(
            lambda cfg: 1.0 if cfg == default else 9.0)
        tuner = T.ComputeTuner(shape, cache=str(tmp_path / "c.json"),
                               measure_fn=measure)
        rec = tuner.tune(steps=1, measure_top=2)
        assert T.StepConfig.from_json(rec["config"]) == default
        assert rec["measured_ms"] <= rec["default_ms"]

    def test_cache_hit_skips_measurement(self, tmp_path):
        shape = tiny()
        measure, calls = self._fake_measure(lambda cfg: 1.0)
        tuner = T.ComputeTuner(shape, cache=str(tmp_path / "c.json"),
                               measure_fn=measure)
        first = tuner.tune(steps=1, measure_top=1)
        assert not first["cache_hit"] and first["measured_this_run"]
        n = len(calls)
        second = tuner.tune(steps=1, measure_top=1)
        assert second["cache_hit"] and not second["measured_this_run"]
        assert len(calls) == n  # nothing re-measured

    def test_unfit_cached_prior_retunes(self, tmp_path, monkeypatch):
        shape = tiny()
        measure, calls = self._fake_measure(lambda cfg: 1.0)
        cache = T.PriorCache(str(tmp_path / "c.json"))
        # seed a prior whose tiles blow the (tightened) VMEM budget
        cache.put(shape, T.backend_name(), T.jax_version(),
                  T.StepConfig(block_q=8192, block_k=8192,
                               head_dim=shape.head_dim))
        monkeypatch.setenv(F.VMEM_ENV, "8")
        tuner = T.ComputeTuner(shape, cache=cache, measure_fn=measure)
        rec = tuner.tune(steps=1, measure_top=1)
        assert not rec["cache_hit"] and calls

    def test_rejections_and_selection_are_journaled(self, tmp_path,
                                                    monkeypatch):
        from kungfu_tpu.monitor import journal as J

        jpath = str(tmp_path / "journal.jsonl")
        monkeypatch.setenv(J.JOURNAL_FILE_ENV, jpath)
        J._reset_for_tests()
        try:
            shape = tiny()
            measure, _ = self._fake_measure(lambda cfg: 1.0)
            tuner = T.ComputeTuner(shape, cache=None, measure_fn=measure)
            seeded = T.StepConfig(block_q=8192, block_k=8192,
                                  head_dim=shape.head_dim)
            search = tuner.search(candidates=tuner.candidates() + [seeded])
            assert any(c == seeded for c, _ in search["rejected"])
            assert all(c != seeded for c, _ in search["ranked"])
            tuner.tune(steps=1, measure_top=1)
            J._reset_for_tests()  # close the writer: flush to disk
            events = [e["event"] for e in J.read_journal(jpath)]
            assert "tuner_selected" in events
        finally:
            J._reset_for_tests()

    def test_broken_runoff_arm_is_skipped_not_fatal(self, tmp_path):
        shape = tiny()
        default = T.default_config(shape)

        def measure(s, cfg, steps):
            if cfg != default:
                raise RuntimeError("arm wedged")
            return {"step_ms": 2.0, "mfu": None}

        tuner = T.ComputeTuner(shape, cache=None, measure_fn=measure)
        rec = tuner.tune(steps=1, measure_top=2)
        assert T.StepConfig.from_json(rec["config"]) == default


class TestApply:
    def test_apply_lands_every_knob(self):
        from kungfu_tpu.models.transformer import TransformerConfig

        base = TransformerConfig(vocab_size=32000, d_model=1024, n_layers=24,
                                 n_heads=16, d_ff=4096, max_len=2048,
                                 rope=True)
        winner = T.StepConfig(block_q=256, block_k=512, backward="pallas",
                              head_dim=128, remat=True, remat_policy="dots",
                              ce_chunk=4096, donate=False,
                              bucket_bytes=4 << 20)
        tuner = T.ComputeTuner(T.ShapeKey.of(base, 4), cache=None)
        cfg, extras = tuner.apply(base, winner)
        assert (cfg.flash_block_q, cfg.flash_block_k) == (256, 512)
        assert cfg.flash_backward == "pallas"
        assert cfg.n_heads == 8  # 1024 // 128: the MHA layout re-factor
        assert cfg.remat and cfg.remat_policy == "dots"
        assert cfg.head == "hidden"
        assert extras == {"ce_chunk": 4096, "donate": False,
                          "bucket_bytes": 4 << 20}

    def test_apply_never_refactors_gqa_heads(self):
        from kungfu_tpu.models.transformer import TransformerConfig

        base = TransformerConfig(vocab_size=32000, d_model=1024, n_layers=2,
                                 n_heads=16, n_kv_heads=4, d_ff=4096,
                                 max_len=2048, rope=True)
        winner = T.StepConfig(block_q=256, block_k=512, head_dim=128)
        tuner = T.ComputeTuner(T.ShapeKey.of(base, 4), cache=None)
        cfg, _ = tuner.apply(base, winner)
        assert cfg.n_heads == 16


class TestNumericalParity:
    def _toks(self, shape):
        return jnp.asarray(np.random.RandomState(0).randint(
            0, shape.vocab_size,
            size=(shape.batch_per_chip, shape.seq_len)), jnp.int32)

    def _model_out(self, cfg, params, toks):
        from kungfu_tpu.models.transformer import TransformerLM

        return np.asarray(TransformerLM(cfg).apply({"params": params}, toks))

    def test_tile_resolution_is_bit_identical(self):
        from kungfu_tpu.models.transformer import TransformerConfig, \
            TransformerLM

        shape = tiny()
        base = TransformerConfig(vocab_size=64, d_model=16, n_layers=1,
                                 n_heads=2, d_ff=32, max_len=16,
                                 dtype=jnp.float32, rope=True)
        assert base.flash_block_q is None  # None IS the default now
        toks = self._toks(shape)
        params = TransformerLM(base).init(jax.random.PRNGKey(0),
                                          toks)["params"]
        bq, bk = T.resolve_flash_blocks(base, seq_len=16)
        explicit = dataclasses.replace(base, flash_block_q=bq,
                                       flash_block_k=bk)
        np.testing.assert_array_equal(
            self._model_out(base, params, toks),
            self._model_out(explicit, params, toks))

    def test_remat_policies_bit_identical_fwd_grad_close_bwd(self):
        from kungfu_tpu.models.transformer import (
            TransformerConfig, TransformerLM, lm_loss,
        )

        shape = tiny()
        toks = self._toks(shape)
        cfgs = {}
        for remat, policy in ((False, "none"), (True, "full"),
                              (True, "dots")):
            cfgs[(remat, policy)] = TransformerConfig(
                vocab_size=64, d_model=16, n_layers=2, n_heads=2, d_ff=32,
                max_len=16, dtype=jnp.float32, rope=True, remat=remat,
                remat_policy=policy)
        base_cfg = cfgs[(False, "none")]
        params = TransformerLM(base_cfg).init(jax.random.PRNGKey(0),
                                              toks)["params"]
        outs, grads = {}, {}
        for key, cfg in cfgs.items():
            model = TransformerLM(cfg)
            outs[key] = np.asarray(model.apply({"params": params}, toks))

            def loss(p):
                return lm_loss(model.apply({"params": p}, toks), toks)

            grads[key] = jax.grad(loss)(params)
        base = outs[(False, "none")]
        for key, out in outs.items():
            np.testing.assert_array_equal(base, out, err_msg=str(key))
        gbase = jax.tree.leaves(grads[(False, "none")])
        for key in cfgs:
            for a, b in zip(gbase, jax.tree.leaves(grads[key])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6,
                                           err_msg=str(key))

    def test_flash_tile_choice_parity_through_the_kernel(self):
        """Interpreted kernels: tile choice must not change the math —
        fwd within fp tolerance of the reference and of each other, grads
        close across the tuner's candidate tiles."""
        from kungfu_tpu.ops.flash import flash_attention

        rng = np.random.RandomState(1)
        q, k, v = (jnp.asarray(rng.randn(1, 64, 2, 8), jnp.float32)
                   for _ in range(3))

        def grad_of(bq, bk):
            def loss(q, k, v):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk,
                    interpret=True) ** 2)

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        o32 = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
        o16 = flash_attention(q, k, v, causal=True, block_q=16, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(o32), np.asarray(o16),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(grad_of(32, 32), grad_of(16, 64)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestGateUnification:
    def test_attention_auto_consults_pallas_mode(self, monkeypatch):
        from kungfu_tpu.models.transformer import (
            TransformerConfig, _attention_kind,
        )

        cfg = TransformerConfig(vocab_size=64, d_model=16, n_layers=1,
                                n_heads=2, d_ff=32, max_len=16)
        monkeypatch.delenv("KFT_PALLAS", raising=False)
        monkeypatch.delenv("KFT_PALLAS_INTERPRET", raising=False)
        assert _attention_kind(cfg) == "full"  # CPU, kernels off
        monkeypatch.setenv("KFT_PALLAS", "interpret")
        assert _attention_kind(cfg) == "flash"  # interpret CI runs flash
        # explicit kinds are never overridden
        ring = dataclasses.replace(cfg, attention="ring")
        assert _attention_kind(ring) == "ring"

    def test_flash_interpret_env_drives_the_kernel_gate(self, monkeypatch):
        """KFT_PALLAS=interpret must route the flash fwd through the
        interpreted kernel (identical numerics to interpret=True), not
        the XLA reference."""
        from kungfu_tpu.ops.flash import flash_attention

        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
                   for _ in range(3))
        monkeypatch.setenv("KFT_PALLAS", "interpret")
        auto = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        forced = flash_attention(q, k, v, causal=True, block_q=16,
                                 block_k=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))
        monkeypatch.delenv("KFT_PALLAS", raising=False)
        ref = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


class TestLayerWiring:
    def test_bucket_bytes_auto_resolution(self):
        from kungfu_tpu.optimizers.sync import _resolve_bucket_bytes

        small = [np.zeros(1024, np.float32)]
        big = [np.zeros(4 << 20, np.float32), np.zeros(4 << 20, np.float32)]
        assert _resolve_bucket_bytes("auto", small) == 0
        assert _resolve_bucket_bytes("auto", big) == 4 << 20
        assert _resolve_bucket_bytes(123, small) == 123
        assert _resolve_bucket_bytes(None, small) == 0

    def test_synchronous_sgd_accepts_auto(self):
        import optax

        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.plan import make_mesh
        from kungfu_tpu.train import DataParallelTrainer

        tx = synchronous_sgd(optax.sgd(0.1), bucket_bytes="auto")
        trainer = DataParallelTrainer(
            lambda p, b: jnp.mean((b @ p["w"]) ** 2), tx,
            mesh=make_mesh(dp=-1))
        state = trainer.init({"w": np.ones((4, 2), np.float32)})
        batch = trainer.shard_batch(
            np.ones((len(jax.devices()), 4), np.float32))
        state, m = trainer.train_step(state, batch)
        assert np.isfinite(float(np.asarray(m["loss"])))

    def test_chunked_ce_block_resolution(self, monkeypatch):
        from kungfu_tpu.ops.chunked_ce import (
            chunked_lm_head_ll, resolve_ce_block,
        )

        monkeypatch.delenv("KFT_CE_BLOCK", raising=False)
        assert resolve_ce_block(512) == 512
        monkeypatch.setenv("KFT_CE_BLOCK", "1024")
        assert resolve_ce_block(None) == 1024
        monkeypatch.setenv("KFT_CE_BLOCK", "not-a-number")
        assert resolve_ce_block(None, 128, 64) == \
            T.default_ce_block(128, 64)
        monkeypatch.delenv("KFT_CE_BLOCK", raising=False)
        rng = np.random.RandomState(0)
        h = jnp.asarray(rng.randn(8, 4), jnp.float32)
        w = jnp.asarray(rng.randn(4, 40), jnp.float32)
        t = jnp.asarray(rng.randint(0, 40, 8), jnp.int32)
        ll_auto, _ = chunked_lm_head_ll(h, w, t)
        ll_expl, _ = chunked_lm_head_ll(h, w, t,
                                        resolve_ce_block(None, 8, 40))
        np.testing.assert_array_equal(np.asarray(ll_auto),
                                      np.asarray(ll_expl))


@pytest.mark.slow
class TestSmokeDrill:
    def test_smoke_cli_cold_then_cache_hit(self, tmp_path):
        import subprocess
        import sys

        cache = str(tmp_path / "prior.json")
        env = {"JAX_PLATFORMS": "cpu"}
        import os

        env = {**os.environ, **env}
        for extra in ([], ["--expect-cache-hit"]):
            r = subprocess.run(
                [sys.executable, "-m", "kungfu_tpu.tuner", "--smoke",
                 "--cache", cache, "--steps", "1"] + extra,
                capture_output=True, text=True, timeout=420, env=env)
            assert r.returncode == 0, r.stdout + r.stderr
        assert "cache hit" in r.stdout
