"""The flash backward kernels' two readers (benchmark/layer_metrics/flash_bwd_*):
on a capture made by hand with known answers, and on the trace recorded on the
chip from a program whose backward is blocked XLA (tests/data/train-1chip),
where both find nothing and say so."""
import os

import pytest

from benchmark.lib import metrics as M
from benchmark.lib import xplane as X
from benchmark.lib.configs import ROOT, load_json
from benchmark.lib.flops import flash_attention_call
from benchmark.lib.manifest import Manifest, check_manifest

CELLS = ["train-olmo1b-1chip", "train-olmo1b-4chip-fsdp"]
NEW = ("flash_bwd_share", "flash_bwd_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = load_json(os.path.join(ROOT, "benchmark", "configs", "olmo-1b-train-1chip.json"))
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "train-1chip.events.json.gz")


@pytest.fixture(scope="module")
def readers():
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    out = {}
    for cell in CELLS:
        found = dict((m["name"], (m, path))
                     for m, path in man.metrics_for(man.cell(cell), "per_layer"))
        for n in NEW:
            m = found[n][0]
            assert m["workloads"] == CELLS and m["layer"] == "kernels"
            assert m["moves"] == "train_tokens_per_s_chip" and m["unit"] == "%"
            out[n] = M.Reader(n, found[n][1])
    return out


def ctx_of(trace, readers, steps, chips=1):
    spec = {"buckets": {}}
    for r in readers.values():
        spec["buckets"].update(r.trace_buckets())
    return {"values": {"traced_steps": steps, "batch": 4 * chips, "chips": chips,
                       "seq_len": 2048},
            "trace": X.reduce_trace(trace, spec), "cell": {"name": CELLS[0]},
            "config": CONFIG, "traffic": {}, "device": {}, "peaks": PEAKS}


@pytest.mark.parametrize("names", [("kft_flash_bwd",),
                                   ("kft_flash_bwd_dq", "kft_flash_bwd_dkdv")])
def test_readers_on_a_capture_made_by_hand(readers, names):
    """Two steps of 8 layers: a forward kernel of 1 ms and 2 ms of backward
    kernels a layer (one fused event, or the dq and dk/dv pair), and 10 ms
    of fusions a step.  The forward's events are not the backward's."""
    ops, t = [], 0.0
    for step in range(2):
        for layer in range(8):
            n = step * 8 + layer
            ops.append([f"kft_flash_fwd.{n} [tpu_custom_call]", t, 1e-3])
            t += 1e-3
            for name in names:
                ops.append([f"{name}.{n} [tpu_custom_call]", t, 2e-3 / len(names)])
                t += 2e-3 / len(names)
        ops.append([f"fusion.{step}", t, 10e-3])
        t += 10e-3
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
             "host": [], "lines": {}}
    got = {n: readers[n].read(ctx_of(trace, readers, steps=2)) for n in NEW}
    busy = 2 * (8 * 3e-3 + 10e-3)
    assert got["flash_bwd_share"] == pytest.approx(100 * 2 * 8 * 2e-3 / busy)
    shape = dict(batch=4, heads=16, kv_heads=16, seq_len=2048, head_dim=128)
    flops = (flash_attention_call(backward=True, **shape)["flops"]
             - flash_attention_call(backward=False, **shape)["flops"])
    # compute-bound at this shape: 5 of the call's 7 triangle products
    assert flops == pytest.approx(5 * 2 * 4 * 16 * (2048 * 2049 / 2) * 128)
    least = flops / 197e12
    assert got["flash_bwd_roofline"] == pytest.approx(100 * least * 16 / (16 * 2e-3))
    assert 0 < got["flash_bwd_roofline"] < 100
    # four chips hold four times the batch, a chip's share is the same
    four = readers["flash_bwd_roofline"].read(ctx_of(trace, readers, 2, chips=4))
    assert four == pytest.approx(got["flash_bwd_roofline"])


def test_readers_find_nothing_where_the_backward_is_blocked_xla(readers):
    """The recorded trace of the parent's program: `while` loops, no backward
    kernel.  The share reads 0 (the bucket is empty), the roofline nothing."""
    ctx = ctx_of(X.read_trace(RECORDED), readers, steps=4)
    assert ctx["trace"]["devices"] and ctx["trace"]["busy_s"] > 0
    assert readers["flash_bwd_share"].read(ctx) == 0.0
    assert readers["flash_bwd_roofline"].read(ctx) is None
    assert readers["flash_bwd_roofline"].read(dict(ctx, trace=None)) is None
