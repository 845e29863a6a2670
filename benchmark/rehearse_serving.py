#!/usr/bin/env python3
"""Compile-only rehearsal: does a serving configuration's worker fit the chip?

Lowers the `ServingEngine`'s decode step and its largest prefill, and the
configuration's plain reference as the checker child runs it, for a
described (not attached) `v5e:2x2`, and prints the compiler's
`memory_analysis()` beside what the process holds anyway (weights, slot
cache).  No chip, no chip time; nothing runs, so it says nothing about
speed.  It is how the depth of a serving configuration is chosen; the
result goes into the configuration's `reduced`.

    JAX_PLATFORMS=cpu python benchmark/rehearse_serving.py \
        --config benchmark/configs/<name>.json [--layers 6,7] [--check-tokens 512]

The compiler counts one program at a time: `resident` below is what the
worker keeps on the device between programs (parameters + slot cache), and
a program fits when resident + its temporaries + its outputs that are not
donated stay under the chip's memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16 * 1024 ** 3  # v5e; the runtime keeps some of it for itself


def rehearse(config: dict, check_tokens: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import kungfu_tpu.compat as compat
    from benchmark.lib.configs import load_reference, transformer_config
    from kungfu_tpu.serving.engine import ServingEngine
    from kungfu_tpu.serving.worker import seed_params

    # the program asks jax.default_backend(), the CPU here; this script (not
    # the program) steers it onto the kernel path the chip takes
    compat.pallas_mode = lambda interpret=None: "compiled"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    cfg = transformer_config(config)
    params = described(jax.eval_shape(lambda: seed_params(cfg, 0)))
    slots = int(config["deployment"]["slots"])
    eng = ServingEngine(cfg, params, slots=slots)
    nbytes = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    out = {"layers": cfg.n_layers, "param_bytes": nbytes(params),
           "cache_bytes": nbytes(eng.cache), "programs": {}}
    out["resident_bytes"] = out["param_bytes"] + out["cache_bytes"]

    def analyse(name, lowered):
        try:
            m = lowered.compile().memory_analysis()
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is the finding
            out["programs"][name] = {"refused": str(e).splitlines()[0][:300]}
            return
        out["programs"][name] = {
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes}

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)  # noqa: E731
    analyse("decode", eng._decode.lower(
        params, described(eng.cache), described(eng._dev_counters), i32(slots, 1)))
    bucket = eng.buckets[-1]
    analyse(f"prefill_{bucket}", eng._prefill.lower(
        params, described(eng._small_cache0), i32(1, bucket), 1, 1))
    ref = load_reference(config)
    compat.pallas_mode = lambda interpret=None: "off"
    analyse(f"reference_{check_tokens}", jax.jit(
        lambda p, t: ref.forward(p, t, config)).lower(params, i32(1, check_tokens)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", default="", help="comma list; default: the file's")
    ap.add_argument("--check-tokens", type=int, default=512,
                    help="padded length of the longest request the checker holds")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    depths = [int(x) for x in args.layers.split(",") if x] or [config["num_hidden_layers"]]
    for n in depths:
        res = rehearse(dict(config, num_hidden_layers=n), args.check_tokens)
        res["hbm_bytes"] = HBM_BYTES
        print("REHEARSAL: " + json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
