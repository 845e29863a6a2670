"""The decode-attention metrics' readers (benchmark/layer_metrics/decode_attn_*)
and their arithmetic (benchmark/lib/decode_attn_costs.py): on a capture made
by hand with known answers, and on captures of a program without the kernel
and the counter (the parent of PR 28), where the share of busy time reads 0
and the two counter readers find nothing and say so."""
import json
import os

import pytest

from benchmark.lib import decode_attn_costs as D
from benchmark.lib import metrics as M
from benchmark.lib import moe_costs as C
from benchmark.lib import xplane as X
from benchmark.lib.configs import ROOT, load_json
from benchmark.lib.manifest import Manifest, check_manifest

CELLS = ["serve-olmo1b-chat-r80", "serve-olmoe-chat-r80"]
NEW = ("decode_attn_share", "decode_attn_fetched_share", "decode_attn_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DENSE = load_json(os.path.join(ROOT, "benchmark", "configs", "olmo-1b-serve.json"))
EXPERT = load_json(os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b-serve.json"))


@pytest.fixture(scope="module")
def readers():
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    out = {}
    for cell in CELLS:
        found = dict((m["name"], (m, path))
                     for m, path in man.metrics_for(man.cell(cell), "per_layer"))
        assert set(NEW) <= set(found)
        for n in NEW:
            m = found[n][0]
            assert m["workloads"] == CELLS and m["moves"] == "tpot_p50_ms"
            assert m["layer"] == "kernels" and m["unit"] == "%"
            out[n] = M.Reader(n, found[n][1])
    # new entries stand at the end of the list, in this order
    assert [m["name"] for m in man.doc["per_layer"][-3:]] == list(NEW)
    return out


@pytest.fixture()
def as_run(monkeypatch, tmp_path, readers):
    """Lay a capture's files where a traced run of the cell leaves its own."""
    monkeypatch.setattr(C, "ROOT", str(tmp_path))

    def lay(trace, counters, config=DENSE):
        out = tmp_path / ".bench_out" / CELLS[0]
        (out / "profile-serve-0-1").mkdir(parents=True, exist_ok=True)
        X.save_trace(trace, str(out / "events.json.gz"))
        if counters is not None:
            with open(out / "profile-serve-0-1" / "counters.json", "w") as f:
                json.dump(counters, f)
        spec = {"buckets": {}}
        for r in readers.values():
            spec["buckets"].update(r.trace_buckets())
        return {"values": {}, "trace": X.reduce_trace(trace, spec),
                "cell": {"name": CELLS[0]}, "config": config, "traffic": {},
                "device": {}, "peaks": PEAKS}

    return lay


def family(cache, written, fetched, fetched_free, written_free=0):
    return {D.FAMILY: {'kind="cache"': cache, 'kind="written"': written,
                       'kind="written_free"': written_free,
                       'kind="fetched"': fetched,
                       'kind="fetched_free"': fetched_free},
            "kft_serve_param_bytes": {'dtype="bfloat16"': 1}}


def test_bytes_of_a_written_row_from_shapes():
    # 16 layers x (K + V) x 16 heads x 128 x bf16: PERF.md's 131,072 bytes a token
    assert D.bytes_per_row(DENSE) == 16 * 2 * 16 * 128 * 2 == 131072
    assert D.bytes_per_row(EXPERT) == 7 * 2 * 16 * 128 * 2
    gqa = dict(DENSE, num_key_value_heads=4, program=dict(DENSE["program"],
                                                         dtype="float32"))
    assert D.bytes_per_row(gqa) == 16 * 2 * 4 * 128 * 4
    assert D.bytes_per_row(dict(DENSE, num_key_value_heads=None)) == 131072


def test_readers_on_a_capture_made_by_hand(readers, as_run):
    """Two decode programs of two layers (one kernel event a layer, 50 us
    each) and a verify program whose kernel events are not the roofline's;
    the counter says the two steps spanned 2 x 16,384 rows, held 3,000 +
    3,008 written (900 of them under free slots' cursors) and fetched
    4,096 + 4,352 (1,024 of them for free slots)."""
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 0.010)):
        modules.append(["jit__decode(123)", t0, 0.004])
        for layer in range(2):
            ops.append([f"kft_decode_attn.{layer} [tpu_custom_call]",
                        t0 + 0.001 * layer, 50e-6])
        ops.append([f"fusion.{step}", t0 + 0.002, 0.001])
    modules.append(["jit__verify_accept(9)", 0.020, 0.002])
    ops.append(["kft_decode_attn.7 [tpu_custom_call]", 0.020, 100e-6])
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
             "host": [], "lines": {}}
    start = family(100 * 16384, 50_000, 300_000, 70_000, 20_000)
    end = family(102 * 16384, 56_008, 308_448, 71_024, 20_900)
    ctx = as_run(trace, {"start": start, "end": end})
    got = {n: readers[n].read(ctx) for n in NEW}
    assert D.rows_delta(ctx) == {"cache": 32768, "written": 6008,
                                 "written_free": 900, "fetched": 8448,
                                 "fetched_free": 1024}
    assert got["decode_attn_fetched_share"] == pytest.approx(100 * 8448 / 32768)
    # busy: 4 x 50 us + 2 x 1 ms + 100 us; the kernel's 300 us of it
    assert got["decode_attn_share"] == pytest.approx(100 * 300e-6 / 2300e-6)
    assert D.kernel_events_in_program(trace) == (4, pytest.approx(200e-6))
    least = (6008 - 900) * 131072 / 819e9  # the busy slots' rows alone
    assert got["decode_attn_roofline"] == pytest.approx(100 * least / 200e-6)
    # the expert configuration's 7 layers need 7/16 of the bytes
    expert = readers["decode_attn_roofline"].read(dict(ctx, config=EXPERT))
    assert expert == pytest.approx(got["decode_attn_roofline"] * 7 / 16)


def test_readers_find_nothing_in_a_program_that_reads_the_whole_cache(
        readers, as_run):
    """The parent of PR 28: no kernel event, no counter family (its
    counters.json holds the parameter bytes alone, or nothing is written):
    a share of nothing, no number, no exception."""
    trace = {"devices": [{"name": "/device:TPU:0",
                          "ops": [["multiply_reduce_fusion.1", 0.0, 0.004]],
                          "modules": [["jit__decode(1)", 0.0, 0.004]]}],
             "host": [], "lines": {}}
    ctx = as_run(trace, None)
    assert readers["decode_attn_share"].read(ctx) == 0.0
    for n in NEW[1:]:
        assert readers[n].read(ctx) is None
    params_only = {"kft_serve_param_bytes": {'dtype="bfloat16"': 1}}
    ctx = as_run(trace, {"start": params_only, "end": params_only})
    assert [readers[n].read(ctx) for n in NEW[1:]] == [None, None]
    assert all(readers[n].read(dict(ctx, trace=None)) is None for n in NEW)
    # the counter without the kernel (the einsum path of this PR's program):
    # the fetched share says 100, the roofline has no kernel time to divide by
    ctx = as_run(trace, {"start": family(0, 0, 0, 0),
                         "end": family(16384, 3000, 16384, 2048)})
    assert readers["decode_attn_fetched_share"].read(ctx) == 100.0
    assert readers["decode_attn_roofline"].read(ctx) is None
    # nothing decoded during the capture
    ctx = as_run(trace, {"start": family(5, 1, 5, 0), "end": family(5, 1, 5, 0)})
    assert readers["decode_attn_fetched_share"].read(ctx) is None
