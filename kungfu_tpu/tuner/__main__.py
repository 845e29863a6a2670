"""``python -m kungfu_tpu.tuner`` — compute-autotuner smoke drill + probes.

Modes::

    # end-to-end CPU drill (a scripts/check.sh stage): enumerate -> the
    # footprint gate rejects + journals a seeded oversized tiling ->
    # cost -> measured runoff on REAL tiny train steps (default always a
    # control) -> apply() onto a TransformerConfig -> prior cache
    # persists -> tuned-vs-default forward parity is bit-identical.
    python -m kungfu_tpu.tuner --smoke [--cache PATH] [--steps 2]

    # second run against the same cache must skip the runoff entirely:
    python -m kungfu_tpu.tuner --smoke --cache PATH --expect-cache-hit

    # the on-chip measurement probes (one `HUNT:` JSON line per record,
    # TPU required):
    python -m kungfu_tpu.tuner --probe peak|flash|all
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def _probe(which: str) -> int:
    """The probe contract: HUNT: lines, nonzero off-TPU."""
    import jax

    from . import measure

    print(f"# tuner probe: backend={jax.default_backend()} "
          f"devices={jax.devices()}", flush=True)
    if jax.default_backend() != "tpu":
        print("HUNT: " + json.dumps({"error": "not on tpu"}), flush=True)
        return 1
    if which in ("peak", "all"):
        print("HUNT: " + json.dumps(measure.probe_peak()), flush=True)
    if which in ("flash", "all"):
        rec = measure.flash_sweep(on_row=lambda row: print(
            "HUNT: " + json.dumps({"probe": "flash", "row": row}),
            flush=True))
        print("HUNT: " + json.dumps(rec), flush=True)
    return 0


def _smoke(args) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the drill must be able to verify its own journal trail
    owns_journal = not (os.environ.get("KFT_JOURNAL_FILE")
                        or os.environ.get("KFT_JOURNAL_DIR"))
    tmp_journal = None
    if owns_journal:
        fd, tmp_journal = tempfile.mkstemp(prefix="kft-tuner-smoke-",
                                           suffix=".jsonl")
        os.close(fd)
        os.environ["KFT_JOURNAL_FILE"] = tmp_journal
        from ..monitor.journal import _reset_for_tests

        _reset_for_tests()

    import dataclasses

    import numpy as np

    from ..monitor.journal import read_journal
    from .cache import PriorCache, backend_name, jax_version
    from .core import ComputeTuner, resolve_flash_blocks
    from .space import ShapeKey, StepConfig

    failures = []
    shape = ShapeKey(vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                     n_kv_heads=0, d_ff=32, seq_len=16, batch_per_chip=2,
                     dtype="float32", causal=True)
    cache_path = args.cache or os.path.join(
        tempfile.mkdtemp(prefix="kft-tuner-cache-"), "prior_cache.json")
    tuner = ComputeTuner(shape, cache=PriorCache(cache_path))

    # 1. enumeration + footprint gate: every emitted candidate fits the
    #    default budgets; a seeded oversized tiling is rejected + journaled
    cands = tuner.candidates()
    search = tuner.search(
        candidates=cands + [StepConfig(block_q=8192, block_k=8192,
                                       head_dim=shape.head_dim)])
    legal_rejected = [c for c, _ in search["rejected"] if c.block_q <= 1024]
    if legal_rejected:
        failures.append(f"legal candidates rejected: "
                        f"{[c.describe() for c in legal_rejected]}")
    if not any(c.block_q == 8192 for c, _ in search["rejected"]):
        failures.append("seeded oversized tiling was NOT rejected by the "
                        "footprint gate")
    if any(c.block_q == 8192 for c, _ in search["ranked"]):
        failures.append("seeded oversized tiling entered the ranking")
    print(f"# enumerated {len(cands)} candidates; footprint gate rejected "
          f"the seeded oversized tiling")

    # 2. cache state decides the path: hit = reuse, miss = measured runoff
    had_prior = tuner.cache.get_config(shape.digest(), backend_name(),
                                       jax_version()) is not None
    record = tuner.tune(steps=args.steps, measure_top=2, use_cache=True)
    if args.expect_cache_hit and not record["cache_hit"]:
        failures.append("--expect-cache-hit: the runoff ran anyway")
    if had_prior and not record["cache_hit"]:
        failures.append("prior existed but tune() re-measured")
    if not record["cache_hit"]:
        # 3. the default is always a runoff control and never beats the
        #    winner (the measured winner IS the min, planner-style)
        if record["default_ms"] is None:
            failures.append("default control missing from the runoff")
        elif record["measured_ms"] > record["default_ms"] + 1e-9:
            failures.append(
                f"tuned config lost the runoff to the default: "
                f"{record['measured_ms']} > {record['default_ms']}")

    winner = StepConfig.from_json(record["config"])

    # 4. apply() must land the winner on a TransformerConfig
    from ..models.transformer import TransformerConfig, TransformerLM

    base = TransformerConfig(
        vocab_size=shape.vocab_size, d_model=shape.d_model,
        n_layers=shape.n_layers, n_heads=shape.n_heads, d_ff=shape.d_ff,
        max_len=shape.seq_len, dtype=np.float32, causal=True, rope=True,
        flash_block_q=None, flash_block_k=None,
    )
    tuned_cfg, extras = tuner.apply(base, winner)
    if (tuned_cfg.flash_block_q, tuned_cfg.flash_block_k) != \
            (winner.block_q, winner.block_k):
        failures.append("apply() did not install the winner's flash tiles")
    if tuned_cfg.remat != winner.remat:
        failures.append("apply() did not install the winner's remat choice")
    if extras.get("donate") != winner.donate:
        failures.append("apply() lost the donation knob")

    # 5. tuned-vs-default parity: the resolution path (flash_block=None)
    #    must be bit-identical to the same tiles passed explicitly, and
    #    remat on/off must not change the forward
    import jax
    import jax.numpy as jnp

    bq, bk = resolve_flash_blocks(base, seq_len=shape.seq_len)
    explicit = dataclasses.replace(base, flash_block_q=bq, flash_block_k=bk)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, shape.vocab_size, size=(shape.batch_per_chip, shape.seq_len)),
        jnp.int32)
    model_none = TransformerLM(base)
    params = model_none.init(jax.random.PRNGKey(0), toks)["params"]
    out_none = np.asarray(model_none.apply({"params": params}, toks))
    out_expl = np.asarray(
        TransformerLM(explicit).apply({"params": params}, toks))
    if not np.array_equal(out_none, out_expl):
        failures.append("flash_block=None resolution is not bit-identical "
                        "to the resolved explicit tiles")
    remat_cfg = dataclasses.replace(base, remat=True, remat_policy="dots")
    out_remat = np.asarray(
        TransformerLM(remat_cfg).apply({"params": params}, toks))
    if not np.array_equal(out_none, out_remat):
        failures.append("remat(dots) forward is not bit-identical")

    # 6. cache must round-trip through a fresh load (restart persistence)
    reloaded = PriorCache(cache_path)
    if reloaded.get_config(shape.digest(), backend_name(),
                           jax_version()) is None:
        failures.append("prior cache round-trip lost the winner")

    # 7. the journal must carry the rejection + selection trail
    from ..monitor.journal import _reset_for_tests as _flush

    journal_path = os.environ.get("KFT_JOURNAL_FILE", "")
    events = []
    if journal_path and os.path.exists(journal_path):
        _flush()  # close the writer so every line is on disk
        events = [e.get("event") for e in read_journal(journal_path)]
    if "tuner_rejected" not in events:
        failures.append("no tuner_rejected event journaled for the seeded "
                        "oversized tiling")
    if "tuner_selected" not in events:
        failures.append("no tuner_selected event journaled")

    summary = {
        "shape": shape.digest(),
        "candidates": len(cands),
        "cache_hit": record["cache_hit"],
        "cache_path": cache_path,
        "selected": record["describe"],
        "predicted_ms": record.get("predicted_ms"),
        "measured_ms": record.get("measured_ms"),
        "default_ms": record.get("default_ms"),
        "speedup_vs_default": record.get("speedup_vs_default"),
        "resolved_blocks": [bq, bk],
        "failures": failures,
    }
    print("TUNER-SMOKE: " + json.dumps(summary))
    if tmp_journal and not args.keep_journal:
        try:
            os.unlink(tmp_journal)
        except OSError:
            pass
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(f"ok: tuner smoke passed "
          f"({'cache hit' if record['cache_hit'] else 'cold runoff'})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kungfu_tpu.tuner")
    ap.add_argument("--smoke", action="store_true",
                    help="end-to-end CPU drill")
    ap.add_argument("--cache", default=None,
                    help="prior cache path (default: fresh temp dir)")
    ap.add_argument("--expect-cache-hit", action="store_true",
                    help="fail unless the winner came from the cache")
    ap.add_argument("--steps", type=int, default=2,
                    help="train steps per runoff measurement in --smoke")
    ap.add_argument("--keep-journal", action="store_true")
    ap.add_argument("--probe", default=None, metavar="peak|flash|all",
                    help="on-chip measurement probes (HUNT: line contract)")
    args = ap.parse_args(argv)

    if args.probe:
        if args.probe not in ("peak", "flash", "all"):
            print(f"# tuner: unknown probe {args.probe!r} "
                  "(expected peak|flash|all)", file=sys.stderr)
            return 2
        return _probe(args.probe)
    if args.smoke:
        return _smoke(args)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
