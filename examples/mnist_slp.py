"""MNIST SLP under the launcher — the reference's first end-to-end example.

Reference: examples/tf2_mnist_gradient_tape.py + tests/python/integration/
test_mnist_slp.py.  Run standalone:

    python examples/mnist_slp.py --steps 100

or distributed (4 workers on this machine, CPU backend):

    python -m kungfu_tpu.run -np 4 -platform cpu -- python examples/mnist_slp.py

Prints `RESULT: acc=<...> loss=<...>` at the end (the reference's RESULT-line
convention for CI greps).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import kungfu_tpu
from kungfu_tpu.datasets import ElasticDataAdaptor, synthetic_mnist
from kungfu_tpu.env import enable_compile_cache
from kungfu_tpu.models.slp import SLP, accuracy, softmax_cross_entropy
from kungfu_tpu.optimizers import (
    adaptive_sgd,
    pair_averaging,
    synchronous_averaging,
    synchronous_sgd,
)
from kungfu_tpu.train import DataParallelTrainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=32, help="per-worker batch")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument(
        "--optimizer", default="ssgd", choices=["ssgd", "sma", "gossip", "ada"]
    )
    args = ap.parse_args()

    enable_compile_cache()
    peer = kungfu_tpu.init()
    rank, size = peer.rank, peer.size

    import jax
    import jax.numpy as jnp
    import optax

    n_replicas = len(jax.devices())
    tx, per_replica = {
        "ssgd": (synchronous_sgd(optax.sgd(args.lr)), False),
        "sma": (synchronous_averaging(optax.sgd(args.lr)), True),
        "gossip": (pair_averaging(optax.sgd(args.lr), axis_size=n_replicas), True),
        "ada": (adaptive_sgd(optax.sgd(args.lr), switch_step=args.steps // 2), True),
    }[args.optimizer]

    model = SLP()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))["params"]

    def loss_fn(p, batch):
        images, labels = batch
        return softmax_cross_entropy(model.apply({"params": p}, images), labels)

    trainer = DataParallelTrainer(loss_fn, tx, per_replica_params=per_replica)
    state = trainer.init(params)

    images, labels = synthetic_mnist(n=4096, noise=0.5)
    # each process feeds its local devices' share of the global batch
    local_devices = jax.local_device_count()
    data = iter(
        ElasticDataAdaptor(
            images, labels,
            batch_size=args.batch_size * local_devices,
            rank=rank, size=size,
        )
    )
    # the first step compiles: time it as set-up, apart from the steady rate
    t0 = time.perf_counter()
    state, metrics = trainer.fit(state, data, steps=1, log_every=0)
    setup_s = time.perf_counter() - t0
    state, metrics = trainer.fit(state, data, steps=args.steps - 1, log_every=25)
    step_ms = args.batch_size * local_devices / metrics["samples_per_sec"] * 1e3

    final = trainer.eval_params(state)
    logits = model.apply({"params": final}, images[:1024])
    acc = float(accuracy(logits, labels[:1024]))
    dev = jax.devices()[0]
    print(
        f"RESULT: rank={rank}/{size} acc={acc:.4f} "
        f"loss={float(metrics['loss']):.4f} "
        f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"devices={jax.device_count()} local_devices={local_devices} "
        f"setup_s={setup_s:.1f} step_ms={step_ms:.2f} "
        f"throughput={metrics['samples_per_sec']:.0f} samples/s"
    )


if __name__ == "__main__":
    main()
