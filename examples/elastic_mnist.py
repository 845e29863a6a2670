"""Elastic MNIST training — the resize-mid-training drill.

Reference: tests/python/integration/test_tensorflow_resize.py:31-79 (schedule
of cluster sizes, resize asserted mid-run, detached workers exit) under
kungfu-run watch mode.  Run:

    python -m kungfu_tpu.run -w -np 2 -platform cpu -- \
        python examples/elastic_mnist.py --schedule 2:20,3:20,2:10 --total-samples 6400

Each surviving worker prints `RESULT: ... resizes=N`; detached workers print
`DETACHED: ...` and exit 0.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from kungfu_tpu.elastic.trainer import ElasticConfig, run_elastic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--total-samples", type=int, default=6400)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--schedule", default="", help="size:steps,... resize schedule")
    ap.add_argument("--check-every", type=int, default=2)
    ap.add_argument("--checkpoint-dir", default="", help="durable resume dir")
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--gns", action="store_true",
                    help="chain the gradient-noise-scale monitor into the step")
    args = ap.parse_args()

    from kungfu_tpu.env import enable_compile_cache

    # a resize back to a mesh size already seen then skips XLA compilation
    enable_compile_cache()

    def make_loss():
        import jax

        from kungfu_tpu.models.slp import SLP, softmax_cross_entropy

        model = SLP()

        def loss_fn(params, batch):
            images, labels = batch
            return softmax_cross_entropy(model.apply({"params": params}, images), labels)

        return loss_fn

    def init_params():
        import jax
        import jax.numpy as jnp

        from kungfu_tpu.models.slp import SLP

        return SLP().init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))["params"]

    def make_tx(axes="dp", impl="pmean"):
        import optax

        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.optimizers.monitor import gradient_noise_scale

        tx = synchronous_sgd(optax.sgd(args.lr), axis_name=axes, impl=impl)
        if args.gns:
            tx = gradient_noise_scale(
                tx, local_batch_size=args.batch_size, axis_name=axes
            )
        return tx

    def make_data(rank, size, offset):
        import jax

        from kungfu_tpu.datasets import ElasticDataAdaptor, synthetic_mnist

        images, labels = synthetic_mnist(n=4096, noise=0.5)
        return iter(
            ElasticDataAdaptor(
                images, labels,
                batch_size=args.batch_size * jax.local_device_count(),
                rank=rank, size=size, offset=offset,
            )
        )

    out = run_elastic(
        make_loss, init_params, make_tx, make_data,
        ElasticConfig(
            total_samples=args.total_samples,
            batch_size=args.batch_size,
            schedule=args.schedule,
            check_every=args.check_every,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
        ),
    )
    gns = ""
    if args.gns:
        import numpy as np

        from kungfu_tpu.optimizers.monitor import get_noise_scale

        gns = f" gns={float(np.asarray(get_noise_scale(out['state'].opt_state))):.4f}"
    lat = ""
    if out["resize_p50_s"] is not None:
        lat = (f" resize_p50_s={out['resize_p50_s']} "
               f"resize_p95_s={out['resize_p95_s']}")
    heals = f" heals={out['heals']}" if out["heals"] else ""
    print(
        f"RESULT: loss={out['loss']:.4f} trained={out['trained_samples']} "
        f"resizes={out['resizes']} final_size={out['final_size']} "
        f"seconds={out['seconds']:.1f}{lat}{gns}{heals}",
        flush=True,
    )
    if out["resize_events"]:
        import json

        print("RESIZE_EVENTS: " + json.dumps(out["resize_events"]), flush=True)
    if out["heal_events"]:
        import json

        print("HEAL_EVENTS: " + json.dumps(out["heal_events"]), flush=True)


if __name__ == "__main__":
    main()
