"""All-reduce / p2p microbenchmarks over fake-model gradient lists.

TPU re-design of the reference benchmark harness
(srcs/python/kungfu/tensorflow/v1/benchmarks/__main__.py:1-188): the
reference sweeps allreduce *methods* (CPU | NCCL | NCCL+CPU | HOROVOD) over
synthetic per-tensor gradient lists for ResNet50/VGG16/BERT and prints
``RESULT:`` lines with achieved rates.  Here the methods are XLA collective
*strategies* (psum | ring | rs_ag | hierarchical), run over the session mesh
— real ICI on TPU, virtual devices on CPU — and the same fake-model lists
come from :mod:`kungfu_tpu.models.fakemodel`.

Reported numbers:
  * ``data`` GiB/s — payload bytes / wall time (the reference's rate).
  * ``busbw`` GiB/s — algorithmic bus bandwidth, data × 2(n-1)/n, the
    standard cross-framework comparison figure for allreduce.
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from ..models import fakemodel
from ..plan import Strategy
from ..session import Session

GiB = float(1 << 30)

#: strategy sweep exposed as benchmark "methods" (reference --method flag)
METHODS: Dict[str, Strategy] = {
    "auto": Strategy.AUTO,
    "psum": Strategy.STAR,          # single-pass XLA all-reduce
    "ring": Strategy.RING,          # explicit ppermute ring
    "rs_ag": Strategy.CLIQUE,       # reduce_scatter + all_gather phases
    "hierarchical": Strategy.BINARY_TREE_STAR,  # ici-then-dcn two-level
}


@dataclass
class BenchResult:
    model: str
    method: str
    fuse: bool
    steps: int
    payload_bytes: int
    seconds_per_step: float

    @property
    def data_gibps(self) -> float:
        return self.payload_bytes / self.seconds_per_step / GiB

    def busbw_gibps(self, n: int) -> float:
        return self.data_gibps * (2.0 * (n - 1) / n if n > 1 else 1.0)

    def line(self, n: int) -> str:
        # RESULT: prefix mirrors the reference's grep-able output contract
        # (benchmarks/__main__.py:112-120).
        return (
            f"RESULT: model={self.model} method={self.method} fuse={int(self.fuse)} "
            f"np={n} payload={self.payload_bytes} B "
            f"step={self.seconds_per_step * 1e3:.3f} ms "
            f"data={self.data_gibps:.3f} GiB/s busbw={self.busbw_gibps(n):.3f} GiB/s"
        )


def _payloads(session: Session, model: str, dtype=np.float32) -> List[jnp.ndarray]:
    sizes = fakemodel.get_sizes(model)
    rng = np.random.RandomState(0)
    # Session.lift places per-peer rows correctly in BOTH single-controller
    # and multi-controller (launcher) runs — a plain jnp.asarray of the
    # global shape would break under jax.process_count() > 1
    return [session.lift(rng.randn(s).astype(dtype)) for s in sizes]


def bench_all_reduce(
    session: Session,
    model: str = "resnet50-imagenet",
    method: str = "auto",
    fuse: bool = True,
    steps: int = 10,
    warmup: int = 2,
    dtype=np.float32,
) -> BenchResult:
    """Time `steps` group-all-reduces of the model's gradient list.

    fuse selects Session.group_all_reduce's path: True = the whole list is
    concatenated and reduced by one compiled program (the reference NCCL
    fuse, sync_sgd.py:81-112); False = one dispatched collective per tensor.
    The A/B between the two is this benchmark's reason to exist.
    """
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; one of {sorted(METHODS)}")
    strategy = METHODS[method]
    xs = _payloads(session, model, dtype)
    payload = sum(int(x.nbytes) // session.size for x in xs)

    def one_step():
        session.group_all_reduce(
            xs, name=f"bench/{model}", fuse=fuse, strategy=strategy
        )

    for _ in range(warmup):
        one_step()
    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    dt = (time.perf_counter() - t0) / steps
    return BenchResult(model, method, fuse, steps, payload, dt)


def bench_p2p(
    store_size: int = 1 << 20,
    steps: int = 50,
    versioned: bool = True,
) -> float:
    """Save/request round-trips through the blob store (kungfu-bench-p2p
    analog, tests/go/cmd/kungfu-bench-p2p).  Returns GiB/s."""
    from ..store import VersionedStore, Store, Blob

    arr = np.random.RandomState(0).randint(0, 255, store_size, dtype=np.uint8)
    store = VersionedStore() if versioned else Store()
    t0 = time.perf_counter()
    for i in range(steps):
        blob = Blob.from_array(arr)
        if versioned:
            store.save(str(i), "bench", blob)
            out = store.get(str(i), "bench")
        else:
            store.save("bench", blob)
            out = store.get("bench")
        assert out is not None
    dt = time.perf_counter() - t0
    return 2 * store_size * steps / dt / GiB


def bench_attention(
    batch: int = 8,
    seq_len: int = 2048,
    heads: int = 16,
    head_dim: int = 64,
    causal: bool = True,
    steps: int = 20,
    warmup: int = 3,
    dtype=jnp.bfloat16,
    grad: bool = True,
) -> Dict[str, float]:
    """Flash (Pallas) vs full (einsum) attention on one chip.

    Returns {impl: seconds_per_step} and prints RESULT lines with achieved
    attention TFLOP/s (4*B*L^2*H*D matmul flops fwd, x2.5 with backward —
    the standard flash-attention accounting, halved for causal).
    """
    import jax

    from ..ops.flash import flash_attention
    from ..parallel.ring_attention import full_attention

    rng = np.random.RandomState(0)
    shape = (batch, seq_len, heads, head_dim)
    q, k, v = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))

    flops = 4.0 * batch * seq_len * seq_len * heads * head_dim
    if causal:
        flops /= 2
    if grad:
        flops *= 2.5

    def make(fn):
        if grad:
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v, causal=causal).astype(jnp.float32) ** 2)

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return jax.jit(lambda q, k, v: fn(q, k, v, causal=causal))

    # device programs run in dispatch order, so waiting on the LAST
    # result bounds all prior steps
    sync = jax.block_until_ready

    steps = max(1, steps)
    warmup = max(1, warmup)  # first call is compile; timing it is never wanted
    # "flash" is the shipping default (auto backward selection); the forced
    # pallas/xla arms expose the A/B the auto heuristic is calibrated on
    impls = [("flash", flash_attention, None), ("full", full_attention, None)]
    if grad:
        if jax.default_backend() == "tpu":
            # forced-pallas off-TPU would run the interpreter on real bench
            # shapes (effectively a hang) — the compiled-kernel arm is
            # TPU-only, matching flash.py's own env-knob guard
            impls.append(("flash_pallas_bwd", flash_attention, "pallas"))
        impls.append(("flash_xla_bwd", flash_attention, "xla"))
    out: Dict[str, float] = {}
    for name, fn, bwd in impls:
        # stray KFT_FLASH_BWD / KFT_FLASH_BWD_AUTO_SEQ exports would
        # silently skew the default arm's auto selection and void the A/B
        # — pin both off for all arms
        prev = os.environ.pop("KFT_FLASH_BWD", None)
        prev_seq = os.environ.pop("KFT_FLASH_BWD_AUTO_SEQ", None)
        try:
            f = make(
                functools.partial(fn, backward=bwd)
                if fn is flash_attention else fn
            )
            for _ in range(warmup):
                r = f(q, k, v)
            sync(r)
            t0 = time.perf_counter()
            for _ in range(steps):
                r = f(q, k, v)
            sync(r)
        finally:
            if prev is not None:
                os.environ["KFT_FLASH_BWD"] = prev
            if prev_seq is not None:
                os.environ["KFT_FLASH_BWD_AUTO_SEQ"] = prev_seq
        dt = (time.perf_counter() - t0) / steps
        out[name] = dt
        print(
            f"RESULT: bench=attention impl={name} shape={shape} causal={int(causal)} "
            f"grad={int(grad)} step={dt * 1e3:.3f} ms tflops={flops / dt / 1e12:.2f}",
            flush=True,
        )
    return out


def run_sweep(
    session: Session,
    models: Sequence[str] = ("resnet50-imagenet",),
    methods: Sequence[str] = ("auto",),
    fuse: bool = True,
    steps: int = 10,
    warmup: int = 2,
) -> List[BenchResult]:
    results = []
    for m in models:
        for meth in methods:
            r = bench_all_reduce(session, m, meth, fuse=fuse, steps=steps, warmup=warmup)
            print(r.line(session.size), flush=True)
            results.append(r)
    return results
