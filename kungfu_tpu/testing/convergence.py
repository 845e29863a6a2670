"""Optimizer convergence comparison — the framework's analog of the
reference's headline convergence table (README.md:191-197: at 16 workers
Horovod/S-SGD drop to 59% ImageNet top-1 while SMA and PairAveraging hold
75%).  One command trains the same synthetic task with every distributed
optimizer family on the 8-virtual-device CPU mesh and prints a table of
final loss and eval accuracy (`--out F` also writes the loss curves as JSON,
`--markdown F` the table as a file; neither is written unless asked for):

    python -m kungfu_tpu.testing.convergence --steps 60

Configs:
  ssgd              synchronous_sgd          (replicated params)
  sma               synchronous_averaging    (per-replica, pull-to-mean)
  gossip-random     pair_averaging selector=random      (SPMD ppermute)
  gossip-roundrobin pair_averaging selector=roundrobin  (SPMD ppermute)
  ada               adaptive_sgd             (SMA -> S-SGD switch)
  gossip-host       HostPairAveraging        (true async p2p blob store) —
                    run as 4 separate worker processes under the launcher,
                    i.e. the reference's actual AD-PSGD deployment shape.
  gossip-host-overlapped  OverlappedHostPairAveraging — same deployment
                    shape with store I/O on a worker thread; its arm
                    MEASURES the one-extra-step-staleness cost instead of
                    asserting it harmless.

The task is datasets.synthetic_mnist (deterministic, linearly separable
with noise): every optimizer must beat chance by a wide margin, and the
table shows how far each family closed the gap.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _force_cpu_mesh(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def _data(batch_per_replica: int, world: int):
    import numpy as np

    from ..datasets import synthetic_mnist
    from ..native import BatchLoader

    images, labels = synthetic_mnist(n=8192, noise=2.5)
    n_eval = 1024
    train = (images[:-n_eval], labels[:-n_eval])
    evals = (images[-n_eval:], labels[-n_eval:])
    loader = BatchLoader(
        train[0], train[1], batch_size=batch_per_replica * world, seed=7
    )
    return loader, evals


def _accuracy(model, params, images, labels) -> float:
    import jax.numpy as jnp
    import numpy as np

    logits = model.apply({"params": params}, jnp.asarray(images))
    return float(np.mean(np.argmax(np.asarray(logits), axis=-1) == labels))


def run_in_process(name: str, steps: int, batch: int, lr: float, log_every: int):
    """Train one optimizer family on the 8-virtual-device mesh."""
    import numpy as np
    import jax
    import optax

    from ..models.slp import SLP, softmax_cross_entropy
    from ..optimizers import (
        adaptive_sgd,
        pair_averaging,
        synchronous_averaging,
        synchronous_sgd,
    )
    from ..train import DataParallelTrainer

    world = len(jax.devices())
    tx, per_replica = {
        "ssgd": (synchronous_sgd(optax.sgd(lr)), False),
        "sma": (synchronous_averaging(optax.sgd(lr)), True),
        "gossip-random": (
            pair_averaging(optax.sgd(lr), axis_size=world, selector="random"),
            True,
        ),
        "gossip-roundrobin": (
            pair_averaging(optax.sgd(lr), axis_size=world, selector="roundrobin"),
            True,
        ),
        "ada": (adaptive_sgd(optax.sgd(lr), switch_step=steps // 2), True),
    }[name]

    model = SLP()
    import jax.numpy as jnp

    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))["params"]

    def loss_fn(p, b):
        images, labels = b
        return softmax_cross_entropy(model.apply({"params": p}, images), labels)

    trainer = DataParallelTrainer(loss_fn, tx, per_replica_params=per_replica)
    state = trainer.init(params)
    loader, (eval_x, eval_y) = _data(batch, world)

    curve = []
    t0 = time.perf_counter()
    for step in range(steps):
        d, l = next(loader)
        state, metrics = trainer.train_step(
            state, trainer.shard_batch((d.reshape(-1, 28, 28, 1), l))
        )
        if step % log_every == 0 or step == steps - 1:
            curve.append([step, round(float(np.asarray(metrics["loss"])), 4)])
    dt = time.perf_counter() - t0

    final = trainer.eval_params(state)  # replica 0 in per-replica families
    acc = _accuracy(model, final, eval_x.reshape(-1, 28, 28, 1), eval_y)
    loader.close()
    return {
        "optimizer": name,
        "world": world,
        "steps": steps,
        "final_loss": curve[-1][1],
        "eval_accuracy": round(acc, 4),
        "seconds": round(dt, 1),
        "loss_curve": curve,
    }


def run_host_gossip(steps: int, batch: int, lr: float, log_every: int = 50,
                    np_workers: int = 4, overlapped: bool = False):
    """True-async AD-PSGD: np separate worker processes under the launcher,
    gossiping through their TCP blob stores (the reference deployment
    shape).  Returns rank 0's RESULT line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 device per worker process
    cmd = [
        sys.executable, "-m", "kungfu_tpu.run", "-np", str(np_workers),
        sys.executable, "-m", "kungfu_tpu.testing.convergence",
        "--host-gossip-worker",
        "--steps", str(steps), "--batch", str(batch), "--lr", str(lr),
        "--log-every", str(log_every),
    ] + (["--overlapped"] if overlapped else [])
    r = subprocess.run(
        cmd, capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    )
    for line in (r.stdout + r.stderr).splitlines():
        marker = "CONVERGENCE-RESULT: "
        if marker in line:
            return json.loads(line.split(marker, 1)[1])
    raise RuntimeError(
        f"host-gossip run produced no result (rc={r.returncode}):\n"
        f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    )


def host_gossip_worker(steps: int, batch: int, lr: float,
                       log_every: int = 50, overlapped: bool = False) -> None:
    """One AD-PSGD worker: local SGD + HostPairAveraging.mix() per step.

    overlapped=True swaps in OverlappedHostPairAveraging — same gossip
    semantics with store I/O on a worker thread (one extra step of pull
    staleness).  Recorded as its own convergence arm so the overlap's
    staleness cost is measured, not asserted."""
    import kungfu_tpu
    from ..env import apply_platform_override

    apply_platform_override()
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from ..models.slp import SLP, softmax_cross_entropy
    from ..optimizers.gossip import (
        HostPairAveraging,
        OverlappedHostPairAveraging,
    )

    peer = kungfu_tpu.init()
    model = SLP()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))["params"]
    tx = optax.sgd(lr)
    opt = tx.init(params)
    cls = OverlappedHostPairAveraging if overlapped else HostPairAveraging
    hpa = cls(peer, seed=42)

    def loss_fn(p, b):
        images, labels = b
        return softmax_cross_entropy(model.apply({"params": p}, images), labels)

    step_fn = jax.jit(
        lambda p, o, b: _sgd_step(loss_fn, tx, p, o, b)
    )

    loader, (eval_x, eval_y) = _data(batch, 1)
    loader.reshard(peer.rank, peer.size)  # each worker trains its shard
    curve = []
    for step in range(steps):
        d, l = next(loader)
        # reference order (async_sgd.py:127-140): average, apply local
        # grads, THEN publish — peers pull a model with the latest step
        params = hpa.mix(params)
        params, opt, loss = step_fn(params, opt, (d.reshape(-1, 28, 28, 1), l))
        hpa.publish(params)
        if step % log_every == 0 or step == steps - 1:
            curve.append([step, round(float(loss), 4)])
    if overlapped:
        # the last publish must land before peers stop pulling
        if not hpa.flush():
            print("# WARN: final gossip publish did not land", file=sys.stderr)
    kungfu_tpu.run_barrier()
    if overlapped:
        hpa.close()
    if peer.rank == 0:
        acc = _accuracy(model, params, eval_x.reshape(-1, 28, 28, 1), eval_y)
        print(
            "CONVERGENCE-RESULT: "
            + json.dumps(
                {
                    "optimizer": "gossip-host-overlapped"
                    if overlapped else "gossip-host",
                    "world": peer.size,
                    "steps": steps,
                    "final_loss": curve[-1][1],
                    "eval_accuracy": round(acc, 4),
                    "loss_curve": curve,
                }
            ),
            flush=True,
        )
    kungfu_tpu.finalize()


def _sgd_step(loss_fn, tx, params, opt, batch):
    import jax
    import optax

    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    updates, opt = tx.update(grads, opt, params)
    return optax.apply_updates(params, updates), opt, loss


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kungfu_tpu.testing.convergence")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--batch", type=int, default=32, help="per-replica batch")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--log-every", type=int, default=50)
    ap.add_argument("--out", default=None,
                    help="also write results and loss curves here as JSON")
    ap.add_argument("--markdown", default=None,
                    help="also write the table here")
    ap.add_argument("--skip-host-gossip", action="store_true")
    ap.add_argument("--host-gossip-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--overlapped", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.host_gossip_worker:
        host_gossip_worker(args.steps, args.batch, args.lr, args.log_every,
                           overlapped=args.overlapped)
        return 0

    _force_cpu_mesh(8)

    results = []
    for name in ("ssgd", "sma", "gossip-random", "gossip-roundrobin", "ada"):
        r = run_in_process(name, args.steps, args.batch, args.lr, args.log_every)
        print(f"# {name}: loss {r['final_loss']} acc {r['eval_accuracy']}",
              file=sys.stderr)
        results.append(r)
    if not args.skip_host_gossip:
        for overlapped in (False, True):
            arm = "gossip-host-overlapped" if overlapped else "gossip-host"
            try:
                r = run_host_gossip(args.steps, args.batch, args.lr,
                                    args.log_every, overlapped=overlapped)
                print(f"# {arm}: loss {r['final_loss']} acc "
                      f"{r['eval_accuracy']}", file=sys.stderr)
            except Exception as e:  # never lose the finished runs
                r = {"optimizer": arm, "error": f"{type(e).__name__}: {e}"}
                print(f"# {arm} FAILED: {r['error']}", file=sys.stderr)
            results.append(r)

    table = (
        "# Optimizer convergence — synthetic MNIST, 8-replica mesh\n\n"
        "Reference analog: the reference's README.md:191-197 (S-SGD vs SMA vs "
        "PairAveraging ImageNet convergence).\n\n"
        "| optimizer | world | steps | final loss | eval accuracy |\n"
        "|---|---|---|---|---|\n"
    )
    for r in results:
        if "error" in r:
            table += f"| {r['optimizer']} | - | - | FAILED | FAILED |\n"
            continue
        table += (
            f"| {r['optimizer']} | {r['world']} | {r['steps']} "
            f"| {r['final_loss']} | {r['eval_accuracy']} |\n"
        )
    print(table, end="", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"task": "synthetic_mnist", "results": results}, f, indent=1)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(table)
    return 0


if __name__ == "__main__":
    sys.exit(main())
