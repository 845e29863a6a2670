"""Fused computation-collective A/B (ROADMAP item 3's success metric).

Two measurements the BENCH json's `fused` section keys on:

  ops        `all_gather_matmul` / `matmul_reduce_scatter`
             (ops/fused_matmul.py) vs their unfused XLA references
             (`lax.all_gather` + `jnp.dot` / `jnp.dot` +
             `lax.psum_scatter`) at a fixed shape, each row stamped with
             the EFFECTIVE impl (off-TPU the fused arms honestly report
             the engaged fallback) and the straggler observatory's
             compute/collective-wait decomposition
             (benchmarks.scaling.step_attribution) — computed against a
             pure-compute (zero-collective) matmul at the same shape, so
             the collective_wait_frac is exactly the exposed
             communication each schedule pays.
  fsdp_step  a real FSDP-transformer train step, fused
             (`FSDPTrainer(dma_collectives=True)`: the unshard and the
             gradient reduce-scatter ride the DMA kernels) vs unfused
             (False: the legacy lax program), with the same attribution
             attached.  On the CPU host this measures the wrapper
             overhead floor; on a TPU slice the same bench is the real
             overlap win.

    python -m kungfu_tpu.benchmarks --bench fused [--steps 8]
"""
from __future__ import annotations

import json
import statistics
import time
from typing import Dict, List, Optional


def _p50(times_ms: List[float]) -> float:
    return statistics.median(times_ms)


def _timed(fn, args, steps: int, warmup: int) -> List[float]:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return times


def _bench_ops(steps: int, warmup: int) -> List[Dict]:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from ..ops import fused_matmul as FM
    from .scaling import step_attribution

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
    spec = P("dp")

    def shmap(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=False))

    rng = np.random.RandomState(0)
    m, ks, nn = 256, 256, 512
    w = jnp.asarray(rng.randn(n, ks, nn).astype(np.float32))
    rows: List[Dict] = []

    # all-gather-matmul: fused vs gather-then-dot vs pure compute
    x = jnp.asarray(
        np.broadcast_to(rng.randn(m, n * ks).astype(np.float32),
                        (n, m, n * ks)))
    arms = {
        "fused": shmap(lambda xx, ww: FM.all_gather_matmul(
            xx[0], ww[0], "dp")),
        "unfused": shmap(lambda xx, ww: jnp.dot(
            xx[0], lax.all_gather(ww[0], "dp", tiled=True),
            preferred_element_type=jnp.float32)),
        # zero-collective control: same MXU work on a resident weight
        "compute": shmap(lambda xx, ww: jnp.dot(
            xx[0], jnp.concatenate([ww[0]] * n, axis=0),
            preferred_element_type=jnp.float32)),
    }
    rows.append(_op_row("all_gather_matmul", arms, (x, w), n, steps,
                        warmup, step_attribution))

    # matmul-reduce-scatter: fused vs dot-then-scatter vs pure compute
    x2 = jnp.asarray(rng.randn(n, m * n, ks).astype(np.float32))
    arms = {
        "fused": shmap(lambda xx, ww: FM.matmul_reduce_scatter(
            xx[0], ww[0], "dp")),
        "unfused": shmap(lambda xx, ww: lax.psum_scatter(
            jnp.dot(xx[0], ww[0], preferred_element_type=jnp.float32),
            "dp", scatter_dimension=0, tiled=True)),
        "compute": shmap(lambda xx, ww: jnp.dot(
            xx[0], ww[0], preferred_element_type=jnp.float32)),
    }
    rows.append(_op_row("matmul_reduce_scatter", arms, (x2, w), n, steps,
                        warmup, step_attribution))
    return rows


def _op_row(op: str, arms: Dict, args, n: int, steps: int, warmup: int,
            step_attribution) -> Dict:
    from ..ops import fused_matmul as FM

    p50 = {name: round(_p50(_timed(fn, args, steps, warmup)), 3)
           for name, fn in arms.items()}
    effective = FM.effective_impl()
    row = {
        "op": op,
        "np": n,
        "fused_ms_p50": p50["fused"],
        "unfused_ms_p50": p50["unfused"],
        "compute_ms_p50": p50["compute"],
        "speedup": (round(p50["unfused"] / p50["fused"], 3)
                    if p50["fused"] > 0 else None),
        "effective_impl": effective,
        "fallback_engaged": effective == "xla",
        # PR-8 decomposition vs the zero-collective control: the lost
        # fraction IS the exposed communication each schedule pays
        "attribution": {
            "fused": step_attribution(p50["fused"], p50["compute"]),
            "unfused": step_attribution(p50["unfused"], p50["compute"]),
        },
    }
    print(
        f"RESULT: bench=fused op={op} effective={effective} np={n} "
        f"fused_p50={p50['fused']} ms unfused_p50={p50['unfused']} ms "
        f"wait_frac_fused="
        f"{row['attribution']['fused']['collective_wait_frac']} "
        f"wait_frac_unfused="
        f"{row['attribution']['unfused']['collective_wait_frac']}",
        flush=True,
    )
    return row


def _bench_fsdp_step(steps: int, warmup: int) -> Optional[Dict]:
    """FSDP-transformer step_ms, dma_collectives on vs off, with the
    compute baseline measured as the same model's zero-communication
    single-device step."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from ..fsdp import FSDPTrainer
    from ..models.transformer import TransformerConfig, TransformerLM, lm_loss
    from ..ops import fused_matmul as FM
    from .scaling import step_attribution

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return None
    mesh = Mesh(np.array(devs[:n]), ("fsdp",))
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            d_ff=256, max_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)

    def loss_fn(params, tokens):
        return lm_loss(model.apply({"params": params}, tokens), tokens)

    import flax.linen as nn

    tokens0 = jnp.zeros((1, 32), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), tokens0)["params"])
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2 * n, 32)).astype(np.int32)

    def run(dma) -> float:
        trainer = FSDPTrainer(loss_fn, optax.adam(1e-3), mesh=mesh,
                              dma_collectives=dma)
        state = trainer.init(params)
        batch = trainer.shard_batch(tokens)
        for _ in range(warmup):
            state, _ = trainer.train_step(state, batch)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, m = trainer.train_step(state, batch)
            jax.block_until_ready(m["loss"])
            times.append((time.perf_counter() - t0) * 1e3)
        return _p50(times)

    # zero-communication ideal: the same per-device work on one device
    tx = optax.adam(1e-3)
    opt0 = tx.init(params)
    local = jnp.asarray(tokens[: 2 * n // n])

    @jax.jit
    def one_step(p, o, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    p, o = params, opt0
    for _ in range(warmup):
        p, o, loss = one_step(p, o, local)
    comp = []
    for _ in range(steps):
        t0 = time.perf_counter()
        p, o, loss = one_step(p, o, local)
        jax.block_until_ready(loss)
        comp.append((time.perf_counter() - t0) * 1e3)
    compute_ms = _p50(comp)

    unfused = run(False)
    fused = run(True)
    effective = FM.effective_impl()
    rec = {
        "np": n,
        "unfused_step_ms_p50": round(unfused, 3),
        "fused_step_ms_p50": round(fused, 3),
        "compute_ms_p50": round(compute_ms, 3),
        "speedup": round(unfused / fused, 3) if fused > 0 else None,
        "effective_impl": effective,
        "fallback_engaged": effective == "xla",
        "attribution": {
            "fused": step_attribution(fused, compute_ms),
            "unfused": step_attribution(unfused, compute_ms),
        },
    }
    print(
        f"RESULT: bench=fused sweep=fsdp_step np={n} "
        f"fused_p50={rec['fused_step_ms_p50']} ms "
        f"unfused_p50={rec['unfused_step_ms_p50']} ms "
        f"speedup={rec['speedup']}",
        flush=True,
    )
    return rec


def bench_fused(steps: int = 8, warmup: int = 2,
                out: Optional[str] = None) -> Dict:
    import jax

    ops = _bench_ops(steps, warmup)
    fsdp_step = _bench_fsdp_step(max(steps // 2, 3), warmup)
    speedups = [r["speedup"] for r in ops if r.get("speedup")]
    record = {
        "bench": "fused_matmul",
        "backend": jax.default_backend(),
        "np": ops[0]["np"] if ops else None,
        "ops": ops,
        "fsdp_step": fsdp_step,
        # the headline ratio; > 1.0 means the fused schedule won.  Off-TPU
        # the fused arms are the engaged fallback, so ~1.0 is the honest
        # answer — on a TPU slice this becomes the real overlap number
        "fused_speedup_vs_unfused": (
            round(min(speedups), 3) if speedups else None),
        "fused_fallback_engaged": bool(ops and ops[0]["fallback_engaged"]),
    }
    print(json.dumps(record), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
    return record
