#!/usr/bin/env python3
"""Compile-only rehearsal: does a training configuration's step fit the chip?

Lowers each named training configuration's `MeshTrainer` step for a
described (not attached) `v5e:2x2` and prints the compiler's
`memory_analysis()`, the collectives it inserted and the Mosaic calls.  No
chip, no chip time; nothing runs, so it says nothing about speed.  It is how
the depth of a one-chip configuration and the per-chip batch of a four-chip
one are chosen; the result goes into the configuration's `reduced` /
`assumed`.

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py \
        --config benchmark/configs/<name>.json [--layers 4,6,8] [--seqs 4,2,1]

The program decides "flash or not" from `jax.default_backend()`, which is
the CPU here; this script (not the program) steers it onto the kernel path.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16 * 1024 ** 3  # v5e; the runtime keeps some of it for itself

COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all")


def rehearse(config: dict, seq_len: int, seqs_per_chip: int) -> dict:
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import kungfu_tpu.compat as compat
    import kungfu_tpu.models.transformer as tr
    from benchmark.lib.configs import transformer_config
    from benchmark.lib.train_worker import make_optimizer
    from kungfu_tpu.models.transformer import TransformerLM, lm_loss
    from kungfu_tpu.parallel.sharding import param_shardings
    from kungfu_tpu.plan import make_mesh
    from kungfu_tpu.trainer import MeshTrainer

    compat.pallas_mode = tr.pallas_mode = lambda interpret=None: "compiled"

    dep = config["deployment"]
    chips = dep["chips"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(devices=list(topo.devices)[:chips], **dep["mesh"])
    cfg = transformer_config(config, mesh=mesh)
    model = TransformerLM(cfg)
    trainer = MeshTrainer(
        model, lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
        make_optimizer(dep), mesh=mesh)
    batch = seqs_per_chip * chips
    tokens = jax.ShapeDtypeStruct(
        (batch, seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P(trainer.batch_axes or None)))
    with nn.logical_axis_rules(trainer.rules):
        boxed = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((batch, seq_len), jnp.int32))["params"])
    shardings = param_shardings(mesh, boxed, trainer.rules)
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        nn.meta.unbox(boxed), shardings)
    with mesh:
        init = jax.jit(trainer.tx.init).lower(params).compile()
    # as MeshTrainer.init does: leaves tx.init makes fresh (step counters)
    # are pinned replicated on the mesh
    mesh_devs = set(mesh.devices.flat)
    opt = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=s if set(s.device_set) == mesh_devs
            else NamedSharding(mesh, P())),
        jax.eval_shape(trainer.tx.init, params), init.output_shardings)
    state_sh = jax.tree.map(lambda x: x.sharding, (params, opt))

    def step(params, opt_state, batch, rng):
        params, opt_state, loss = trainer._step_body(params, opt_state, batch, rng)
        return params, opt_state, {"loss": loss}

    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    with mesh:
        lowered = jax.jit(step, donate_argnums=(0, 1),
                          out_shardings=(*state_sh, None)).lower(
            params, opt, tokens, rng)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return {
        "config": config["name"], "layers": cfg.n_layers, "chips": chips,
        "mesh": dict(mesh.shape), "seq_len": seq_len,
        "sequences_per_chip": seqs_per_chip, "params": n_params,
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "peak_bytes_per_chip": peak,
        "peak_share_of_hbm": round(peak / HBM_BYTES, 4),
        "mosaic_calls": text.count("tpu_custom_call"),
        "collectives": {c: len(re.findall(rf"= \S+ {c}(?:-start)?\(", text))
                        for c in COLLECTIVES},
        "compile_s": round(time.perf_counter() - t0, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a training configuration file")
    ap.add_argument("--layers", default="",
                    help="comma list of depths to try (default: the file's)")
    ap.add_argument("--seqs", default="",
                    help="comma list of sequences per chip (default: the file's)")
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--program", default="{}",
                    help="JSON of TransformerConfig fields to try over the "
                         "file's `program` (e.g. remat)")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    config["program"] = dict(config.get("program", {}), **json.loads(args.program))
    layers = [int(x) for x in args.layers.split(",") if x] or [
        config["num_hidden_layers"]]
    seqs = [int(x) for x in args.seqs.split(",") if x] or [
        config["deployment"]["sequences_per_chip"]]
    for n in layers:
        for s in seqs:
            try:
                out = rehearse(dict(config, num_hidden_layers=n), args.seq_len, s)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal is the result
                out = {"config": config["name"], "layers": n,
                       "sequences_per_chip": s,
                       "refused": f"{type(e).__name__}: {str(e)[:600]}"}
            print("REHEARSAL: " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
