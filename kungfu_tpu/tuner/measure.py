"""The tuner's measurement primitives.

Three probes, each returning a plain record (callers decide how to print;
the CLI prints one `HUNT:` JSON line per record):

  probe_peak     true MXU rate per (m, k, n) via a dependent matmul chain —
                 every iteration's output feeds the next input, so XLA can
                 neither hoist the matmul nor slice through an unused
                 output (both happened with naive timing loops).  The
                 measured peak seeds the footprint model's
                 roofline instead of the spec-sheet number.
  flash_sweep    the Pallas flash fwd+grad at a given attention shape,
                 swept over (block_q, block_k) tiles, head layout (16×64
                 vs 8×128) and backward arm, vs jax.experimental's
                 reference TPU kernel.
  measure_step   one REAL train step (TransformerLM + synchronous_sgd
                 under the DataParallelTrainer) built from a (ShapeKey,
                 StepConfig) — the runoff's ground truth: step_ms, 6ND
                 tokens/sec and MFU where the chip's peak is known.

Every number here is measured in-process by the caller.  No cell of the
benchmark runs these probes: their records are the tuner's own, not
measured on this stack until ROADMAP S7 takes them to the chip.
"""
from __future__ import annotations

import functools
import statistics
import time
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from .footprint import peak_specs
from .space import ShapeKey, StepConfig

#: (m, k, n, iters[, dtype-name]) rows the peak probe times by default —
#: the flagship GPT step's matmul shapes (lm head, mlp, qkv/out proj)
DEFAULT_PEAK_SHAPES: Tuple[Tuple, ...] = (
    (4096, 4096, 4096, 100),
    (8192, 1024, 32000, 40),
    (8192, 1024, 4096, 100),
    (8192, 1024, 1024, 100),
    (8192, 1024, 1024, 100, "float32"),
)


def sync_result(x) -> None:
    """End a timed region: wait until the device has produced `x`."""
    import jax

    jax.block_until_ready(x)


def probe_peak(shapes: Iterable[Tuple] = DEFAULT_PEAK_SHAPES) -> Dict:
    """Dependent-chain MXU peak probe; returns {"probe": "peak", "rows"}."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))

    def bench(m, k_, n, iters, dtype=jnp.bfloat16):
        x = jax.random.normal(k1, (m, k_), dtype) * 0.01
        w = jax.random.normal(k2, (k_, n), dtype) * 0.01

        @jax.jit
        def run(x, w):
            def body(x, _):
                y = x @ w  # [m, n]
                # fold a NONLINEAR reduction of the WHOLE output back into
                # the next input: abs blocks the algebraic rewrite
                # sum(dot(x, w)) -> dot(x, sum(w)) (and any slice-through),
                # so every output element is live and the matmul cannot be
                # hoisted or shrunk.  Costs one VPU pass over y (~10% on
                # the widest shape) — accepted, and in the safe direction
                # (reported peak is a slight UNDERestimate).
                feedback = jnp.sum(jnp.abs(y), axis=1, keepdims=True)
                return (x + feedback * 1e-6).astype(dtype) * 0.5, ()

            x, _ = lax.scan(body, x, None, length=iters)
            return x

        sync_result(run(x, w))  # compile + warm
        t0 = time.perf_counter()
        sync_result(run(x, w))
        dt = (time.perf_counter() - t0) / iters
        return {
            "shape": [m, k_, n],
            "ms": round(dt * 1e3, 4),
            "tflops": round(2 * m * k_ * n / dt / 1e12, 1),
        }

    rows = []
    for row in shapes:
        m, k_, n, iters = row[:4]
        dtype = jnp.dtype(row[4]).type if len(row) > 4 else jnp.bfloat16
        rows.append(bench(m, k_, n, iters, dtype))
    return {"probe": "peak", "rows": rows}


def default_flash_arms(heads_dims: Tuple[Tuple[int, int], ...] = ((16, 64), (8, 128))):
    """The hunt's sweep: our kernel over tiles × layouts × backward arms,
    plus jax.experimental's reference kernel per layout."""
    for heads, dim in heads_dims:
        for bq, bk in ((128, 128), (256, 256), (512, 512), (256, 512),
                       (512, 1024)):
            yield ("ours", heads, dim, bq, bk)
    # the blocked-XLA backward (never the auto choice where Pallas runs
    # since PR 39: the kernels lead at every swept length, docs/KERNELS.md
    # "Backward choice"; kept as the A/B arm) reads block_k as its scan
    # granularity — sweep it too
    for heads, dim in heads_dims:
        for bq, bk in ((128, 128), (128, 512)):
            yield ("ours_xla_bwd", heads, dim, bq, bk)
    for heads, dim in heads_dims:
        yield ("jax_ref", heads, dim, 0, 0)


def time_flash_arm(kind: str, heads: int, dim: int, bq: int, bk: int,
                   batch: int = 4, seq_len: int = 2048,
                   steps: int = 10) -> Dict:
    """Time one fwd+grad arm of the flash sweep; returns its record row."""
    import jax
    import jax.numpy as jnp

    from ..ops.flash import flash_attention

    rng = np.random.RandomState(0)
    shape = (batch, seq_len, heads, dim)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
               for _ in range(3))
    if kind in ("ours", "ours_xla_bwd"):
        fn = functools.partial(
            flash_attention, causal=True, block_q=bq, block_k=bk,
            backward="pallas" if kind == "ours" else "xla",
        )
    else:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )

        def fn(q, k, v):
            # jax ref kernel wants [B, H, L, D]
            t = lambda x: x.transpose(0, 2, 1, 3)
            return t(jax_flash(t(q), t(k), t(v), causal=True))

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    sync_result(g(q, k, v))
    t0 = time.perf_counter()
    r = None
    for _ in range(steps):
        r = g(q, k, v)
    sync_result(r)
    dt = (time.perf_counter() - t0) / steps
    return {
        "impl": kind, "heads": heads, "head_dim": dim,
        "block_q": bq, "block_k": bk, "ms": round(dt * 1e3, 3),
    }


def flash_sweep(batch: int = 4, seq_len: int = 2048, steps: int = 10,
                arms=None, on_row=None) -> Dict:
    """Run the flash tile/layout/backward sweep; returns
    {"probe": "flash", "rows": [...], "best": row|None}.  `on_row` is
    called after every arm (the CLI streams HUNT: lines through it, so an
    unattended queue's log survives a mid-sweep wedge)."""
    rows: List[Dict] = []
    for arm in (arms if arms is not None else default_flash_arms()):
        try:
            rows.append(time_flash_arm(*arm, batch=batch, seq_len=seq_len,
                                       steps=steps))
        except Exception as e:  # one bad tiling must not sink the sweep
            rows.append({"impl": arm[0], "heads": arm[1], "head_dim": arm[2],
                         "block_q": arm[3], "block_k": arm[4],
                         "error": f"{type(e).__name__}: {e}"[:200]})
        if on_row is not None:
            on_row(rows[-1])
    best = min((r for r in rows if "ms" in r), key=lambda r: r["ms"],
               default=None)
    return {"probe": "flash", "rows": rows, "best": best}


def build_transformer_config(shape: ShapeKey, cfg: StepConfig):
    """The TransformerConfig a (shape, config) pair describes.

    The head-layout choice re-factors d_model into config.head_dim-wide
    heads (MHA only — space.head_dim_choices guards); chunked CE flips the
    model to head="hidden" so the streaming loss owns the head matmul.
    """
    import jax.numpy as jnp

    from ..models.transformer import TransformerConfig

    n_heads = cfg.n_heads_for(shape)
    return TransformerConfig(
        vocab_size=shape.vocab_size, d_model=shape.d_model,
        n_layers=shape.n_layers, n_heads=n_heads,
        n_kv_heads=shape.n_kv_heads, d_ff=shape.d_ff,
        max_len=shape.seq_len, dtype=jnp.dtype(shape.dtype).type,
        causal=shape.causal, rope=True, attention="auto",
        flash_block_q=cfg.block_q, flash_block_k=cfg.block_k,
        flash_backward=cfg.backward if cfg.backward != "auto" else None,
        remat=cfg.remat,
        remat_policy=cfg.remat_policy if cfg.remat else "none",
        head="hidden" if cfg.ce_chunk else "dense",
    )


def measure_step(shape: ShapeKey, cfg: StepConfig, steps: int = 4,
                 reps: int = 1, tx=None) -> Dict:
    """Measured wall time of one real train step under this config.

    Builds the full stack — TransformerLM(config) + synchronous_sgd +
    DataParallelTrainer(donate=cfg.donate, bucket_bytes from the config)
    — and times `steps` compiled scan steps, `reps` times, keeping the
    median.  Returns {"step_ms", "tokens_per_sec_per_chip", "mfu",
    "backend"}; mfu is None off-TPU (a host MFU would be noise).
    """
    import jax
    import optax

    from ..models.transformer import TransformerLM, lm_loss, lm_loss_chunked
    from ..optimizers import synchronous_sgd
    from ..train import DataParallelTrainer

    tcfg = build_transformer_config(shape, cfg)
    model = TransformerLM(tcfg)
    if cfg.ce_chunk:
        def loss_fn(params, batch):
            return lm_loss_chunked(model, params, batch, block=cfg.ce_chunk)
    else:
        def loss_fn(params, batch):
            return lm_loss(model.apply({"params": params}, batch), batch)

    import flax.linen as nn
    import jax.numpy as jnp

    n_chips = len(jax.devices())
    global_batch = shape.batch_per_chip * n_chips
    tokens0 = jnp.zeros((1, shape.seq_len), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), tokens0)["params"])
    if tx is None:
        tx = synchronous_sgd(
            optax.adamw(3e-4, b1=0.9, b2=0.95),
            bucket_bytes=cfg.bucket_bytes or None,
        )
    trainer = DataParallelTrainer(loss_fn, tx, donate=cfg.donate)
    state = trainer.init(params)
    rng = np.random.RandomState(0)
    batch = trainer.shard_batch(
        rng.randint(0, shape.vocab_size,
                    size=(global_batch, shape.seq_len)).astype(np.int32))

    state, m = trainer.train_steps(state, batch, n=steps)
    sync_result(m["loss"])  # compile + warm
    # warm state is the honest census moment (params + opt state + batch +
    # activations' workspace all live): journal the footprint model's
    # prediction against the measured bytes so the gate's error stays
    # visible (hbm_footprint, monitor/programs.py)
    from ..monitor.programs import journal_footprint, programs_enabled

    if programs_enabled():
        from .footprint import step_hbm_bytes

        journal_footprint(f"train_step[{shape.digest()}]",
                          step_hbm_bytes(cfg, shape)["total"])
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        state, m = trainer.train_steps(state, batch, n=steps)
        sync_result(m["loss"])
        times.append((time.perf_counter() - t0) / steps * 1e3)
    step_ms = statistics.median(times)
    toks = global_batch * shape.seq_len / (step_ms / 1e3)
    mfu = None
    if jax.default_backend() == "tpu":
        peak, _ = peak_specs(jax.devices()[0].device_kind)
        if peak:
            mfu = round(toks / n_chips * shape.flops_per_token() / peak, 4)
    return {
        "step_ms": round(step_ms, 3),
        "tokens_per_sec_per_chip": round(toks / n_chips, 1),
        "mfu": mfu,
        "backend": jax.default_backend(),
    }
