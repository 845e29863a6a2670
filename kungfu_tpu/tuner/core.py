"""The compute autotuner: enumerate -> prune -> cost -> measure -> install.

The planner's candidate/cost/runoff skeleton (kungfu_tpu/planner/core.py)
applied to the step graph itself.  One `ComputeTuner` binds a `ShapeKey`
to the search machinery:

  1. enumerate   candidate `StepConfig`s — flash (block_q, block_k) tiles
                 and backward arm, head layout, per-block remat +
                 jax.checkpoint policy, chunked-CE chunk size, donation
                 and gradient-sync bucket layout (space.py);
  2. prune       every candidate through the VMEM/HBM footprint model
                 (footprint.check_fit); rejections journal
                 `tuner_rejected` and can never rank;
  3. cost        survivors ranked by the analytic roofline
                 (footprint.predict_step_ms) — the model's only job is to
                 put the winner in the top-k;
  4. measure     the top predicted finalists — plus the hand-tuned
                 default as a control — with a real train-step A/B
                 (measure.measure_step); the measured winner, never the
                 merely-predicted one, becomes the config of record, so
                 the tuned config can never lose the runoff to the
                 default;
  5. install     `apply()` lands the winner on a TransformerConfig
                 (tiles clamped to the VMEM budget, backward arm, head
                 layout, remat policy, head mode) and reports the
                 step-level knobs (ce_chunk, donate, bucket_bytes); the
                 decision journals
                 `tuner_selected` and persists to the prior cache keyed
                 (shape digest | backend | jax version) — tuning survives
                 restarts.

A model asks the tuner nothing at trace time: `flash_block_q/k=None` is
the shape table beside the kernels (ops/flash.py `flash_blocks`), and a
measured winner reaches a model through `apply()` alone.
`resolve_flash_blocks` answers "what would this config run with".
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..monitor.journal import journal_event
from ..ops.flash import fit_blocks_to_vmem, flash_blocks
from ..utils import get_logger
from . import footprint, measure
from .cache import PriorCache, backend_name, jax_version
from .space import ShapeKey, StepConfig, default_config, enumerate_configs

log = get_logger("kungfu.tuner")


class ComputeTuner:
    """Compute autotuner over one (model shape × backend × batch).

    Args:
      shape: the ShapeKey tuning is valid for.
      cache: a PriorCache, a path, or None (no persistence).
      measure_fn: (shape, config, steps) -> {"step_ms", ...} — injectable
        for tests; defaults to the real train-step measurement.
    """

    def __init__(self, shape: ShapeKey, cache=None,
                 measure_fn: Optional[Callable] = None):
        self.shape = shape
        if isinstance(cache, str):
            cache = PriorCache(cache)
        self.cache: Optional[PriorCache] = cache
        self.measure_fn = measure_fn or (
            lambda shape, cfg, steps: measure.measure_step(
                shape, cfg, steps=steps))

    # -- identity ---------------------------------------------------------------------

    def key(self) -> Tuple[str, str, str]:
        return (self.shape.digest(), backend_name(), jax_version())

    def default(self) -> StepConfig:
        return default_config(self.shape)

    # -- search -----------------------------------------------------------------------

    def candidates(self, **kw) -> List[StepConfig]:
        return enumerate_configs(self.shape, **kw)

    def search(self, candidates: Optional[Sequence[StepConfig]] = None) -> Dict:
        """Footprint-prune + cost every candidate; returns {"ranked":
        [(config, predicted_ms)...best-first], "rejected": [(config,
        reason)...]}.  Every rejection is journaled — a config the
        footprint model kills must leave a trace, not just disappear."""
        cands = list(candidates if candidates is not None
                     else self.candidates())
        ranked, rejected = [], []
        digest = self.shape.digest()
        for cfg in cands:
            reason = footprint.check_fit(cfg, self.shape)
            if reason:
                rejected.append((cfg, reason))
                journal_event("tuner_rejected", config=cfg.describe(),
                              shape=digest, reason=reason)
                continue
            ranked.append(
                (cfg, footprint.predict_step_ms(cfg, self.shape)))
        ranked.sort(key=lambda t: t[1])
        return {"ranked": ranked, "rejected": rejected}

    # -- tune -------------------------------------------------------------------------

    def tune(self, steps: int = 4, measure_top: int = 3,
             use_cache: bool = True, source: str = "runoff") -> Dict:
        """Full pipeline; returns the tuning record.

        A cache hit (same shape digest/backend/jax version) skips the
        runoff entirely and reuses the persisted winner.  A miss runs
        search, measures the `measure_top` best-predicted configs plus
        the hand-tuned default as a control, and records the measured
        winner — the default is always in the runoff, so the tuned
        config of record never loses to it.
        """
        digest, backend, jaxv = self.key()
        if use_cache and self.cache is not None:
            entry = self.cache.get(digest, backend, jaxv)
            cfg = self.cache.get_config(digest, backend, jaxv)
            if cfg is not None:
                reason = footprint.check_fit(cfg, self.shape)
                if reason is None:
                    journal_event(
                        "tuner_selected", config=cfg.describe(),
                        shape=digest, backend=backend,
                        source=f"cache:{entry.get('source', '?')}",
                        predicted_ms=entry.get("predicted_ms"),
                        measured_ms=entry.get("measured_ms"),
                        measured_this_run=False,
                    )
                    return {
                        "shape": digest, "cache_hit": True,
                        "config": cfg.to_json(), "describe": cfg.describe(),
                        "predicted_ms": entry.get("predicted_ms"),
                        "measured_ms": entry.get("measured_ms"),
                        "default_ms": entry.get("default_ms"),
                        "source": f"cache:{entry.get('source', '?')}",
                        "rejected": 0, "measured": 0,
                        "measured_this_run": False,
                    }
                # a prior that no longer fits (smaller VMEM budget, new
                # HBM ceiling) must re-tune, not install blind
                journal_event("tuner_rejected", config=cfg.describe(),
                              shape=digest, stage="cached-prior",
                              reason=reason)
        result = self.search()
        ranked = result["ranked"]
        if not ranked:
            raise RuntimeError(
                f"every step config for shape {digest} was rejected")
        default = self.default()
        finalists = [c for c, _ in ranked[:max(measure_top, 1)]]
        if default not in finalists:
            finalists.append(default)
        predicted = {c: ms for c, ms in ranked}
        if default not in predicted:
            predicted[default] = footprint.predict_step_ms(
                default, self.shape)
        measured: Dict[StepConfig, float] = {}
        records: Dict[StepConfig, Dict] = {}
        for cfg in finalists:
            try:
                rec = self.measure_fn(self.shape, cfg, steps)
            except Exception as e:  # one broken arm must not sink the runoff
                journal_event("tuner_measure_failed", config=cfg.describe(),
                              shape=digest,
                              error=f"{type(e).__name__}: {e}"[:200])
                log.warning("runoff arm %s failed: %s", cfg.describe(), e)
                continue
            measured[cfg] = float(rec["step_ms"])
            records[cfg] = rec
        if not measured:
            raise RuntimeError(
                f"no runoff finalist for shape {digest} produced a time")
        winner = min(measured, key=lambda c: measured[c])
        pred = predicted.get(winner)
        meas = measured[winner]
        rel_err = (abs(pred - meas) / meas
                   if (pred is not None and meas > 0) else None)
        default_ms = measured.get(default)
        record = {
            "shape": digest, "cache_hit": False,
            "config": winner.to_json(), "describe": winner.describe(),
            "predicted_ms": round(pred, 4) if pred is not None else None,
            "measured_ms": round(meas, 4),
            "rel_err": round(rel_err, 4) if rel_err is not None else None,
            "default_ms": (round(default_ms, 4)
                           if default_ms is not None else None),
            "speedup_vs_default": (round(default_ms / meas, 4)
                                   if default_ms and meas > 0 else None),
            "mfu": records[winner].get("mfu"),
            "default_mfu": records.get(default, {}).get("mfu"),
            "finalists": [
                {"config": c.describe(),
                 "predicted_ms": round(predicted.get(c, float("nan")), 4),
                 "measured_ms": round(measured[c], 4),
                 "mfu": records[c].get("mfu")}
                for c in measured
            ],
            "rejected": len(result["rejected"]),
            "measured": len(measured),
            "source": source,
            "measured_this_run": True,
        }
        if self.cache is not None:
            self.cache.put(self.shape, backend, jaxv, winner,
                           predicted_ms=record["predicted_ms"],
                           measured_ms=record["measured_ms"],
                           default_ms=record["default_ms"], source=source)
        journal_event(
            "tuner_selected", config=winner.describe(), shape=digest,
            backend=backend, source=source,
            predicted_ms=record["predicted_ms"],
            measured_ms=record["measured_ms"],
            default_ms=record["default_ms"],
            speedup_vs_default=record["speedup_vs_default"],
            measured_this_run=True,
        )
        log.info("tuner selected %s (measured %.4g ms, default %.4g ms)",
                 winner.describe(), meas, default_ms or float("nan"))
        return record

    # -- install ----------------------------------------------------------------------

    def apply(self, model_cfg, config: Optional[StepConfig] = None):
        """Land a winning StepConfig on a TransformerConfig.

        Returns (new_config, extras): the replaced TransformerConfig
        (tiles, backward arm, head layout, remat policy, head mode) and
        the step-level knobs that live outside the model config —
        {"ce_chunk", "donate", "bucket_bytes"} — for the trainer/loss
        wiring.  With `config=None` the shape's cached winner is used
        (the default config when there is none).
        """
        if config is None:
            digest, backend, jaxv = self.key()
            config = (self.cache.get_config(digest, backend, jaxv)
                      if self.cache is not None else None)
            if config is None:
                config = self.default()
        # a winner recorded under a bigger VMEM budget degrades here: the
        # ints it installs are taken as given by the model
        bq, bk = fit_blocks_to_vmem(
            config.block_q, config.block_k, config.head_dim,
            self.shape.seq_len, footprint._dtype_bytes(self.shape.dtype))
        kw = dict(
            flash_block_q=bq, flash_block_k=bk,
            flash_backward=(config.backward
                            if config.backward != "auto" else None),
            remat=config.remat,
            remat_policy=config.remat_policy if config.remat else "none",
            head="hidden" if config.ce_chunk else "dense",
        )
        if (model_cfg.n_kv_heads or 0) == 0 and \
                model_cfg.d_model % config.head_dim == 0:
            kw["n_heads"] = model_cfg.d_model // config.head_dim
        new_cfg = dataclasses.replace(model_cfg, **kw)
        extras = {"ce_chunk": config.ce_chunk, "donate": config.donate,
                  "bucket_bytes": config.bucket_bytes}
        return new_cfg, extras


# -- what a config would run with ------------------------------------------------------


def resolve_flash_blocks(cfg, seq_len: int) -> Tuple[int, int]:
    """The flash tile sizes a model config runs with at `seq_len`: what
    `Attention` asks ops.flash.flash_blocks for (explicit
    `flash_block_q/k` win on their own axis, `None` is the shape table,
    clamped to the VMEM budget)."""
    import jax.numpy as jnp

    return flash_blocks(cfg.flash_block_q, cfg.flash_block_k,
                        head_dim=cfg.d_model // cfg.n_heads, seq_len=seq_len,
                        dtype_bytes=jnp.dtype(cfg.dtype).itemsize)
