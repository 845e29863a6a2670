"""The latent-attention and held-expert metrics' readers
(benchmark/layer_metrics/mla_decode_attn_*, moe_held_expert_roofline,
moe_zero_share) and their arithmetic (benchmark/lib/mla_costs.py), with the
two accepted counter readers the cell is also listed under
(moe_experts_hit_mean, moe_load_max_over_mean: a layer exports its held
experts' counts under the names a whole layer does): on a capture made by
hand with known answers, on the counters of a traced run of the cell on
the chip (tests/data/longcat/), and on captures of a program without the
kernel and the counters (the parent of PR 35), where the share of busy
time reads 0 and the other readers find nothing and say so."""
import json
import os

import pytest

from benchmark.lib import metrics as M
from benchmark.lib import mla_costs as L
from benchmark.lib import moe_costs as C
from benchmark.lib import xplane as X
from benchmark.lib.configs import ROOT, load_json
from benchmark.lib.manifest import Manifest, check_manifest

CELL = "serve-longcat-reason-r80"
NEW = ("mla_decode_attn_share", "mla_decode_attn_roofline",
       "moe_held_expert_roofline", "moe_zero_share")
#: accepted metrics that read only counters this layer exports too
ACCEPTED = ("moe_experts_hit_mean", "moe_load_max_over_mean")
ALL = NEW + ACCEPTED
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "longcat", "serve-longcat-reason.counters.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = load_json(os.path.join(ROOT, "benchmark", "configs",
                                "longcat-flash-omni-serve.json"))
EXPERT = 3 * 6144 * 2048  # parameters of one expert: gate, up, down


@pytest.fixture(scope="module")
def readers():
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    found = dict((m["name"], (m, path))
                 for m, path in man.metrics_for(man.cell(CELL), "per_layer"))
    assert set(ALL) <= set(found)
    # (no test of where in the list they stand or of which other cells list
    # them: later PRs append, and may not edit this file)
    for n in ALL:
        m = found[n][0]
        assert CELL in m["workloads"] and m["moves"] == "tpot_p50_ms"
    assert all(found[n][0]["workloads"] == [CELL] for n in NEW)
    # the accepted readers whose reckoning assumes per-head K/V rows or
    # `intermediate_size` are not asked of this cell
    for n in ("decode_attn_roofline", "decode_attn_fetched_share",
              "moe_expert_roofline"):
        assert n not in found
    return {n: M.Reader(n, found[n][1]) for n in ALL}


@pytest.fixture()
def as_run(monkeypatch, tmp_path, readers):
    """Lay a capture's files where a traced run of the cell leaves its own."""
    monkeypatch.setattr(C, "ROOT", str(tmp_path))

    def lay(trace, counters, config=CONFIG):
        out = tmp_path / ".bench_out" / CELL
        (out / "profile-serve-0-1").mkdir(parents=True, exist_ok=True)
        X.save_trace(trace, str(out / "events.json.gz"))
        path = out / "profile-serve-0-1" / "counters.json"
        if counters is not None:
            with open(path, "w") as f:
                json.dump(counters, f)
        elif path.exists():
            path.unlink()
        spec = {"buckets": {}}
        for r in readers.values():
            spec["buckets"].update(r.trace_buckets())
        return {"values": {}, "trace": X.reduce_trace(trace, spec),
                "cell": {"name": CELL}, "config": config, "traffic": {},
                "device": {}, "peaks": PEAKS}

    return lay


def counters(written, written_free, held, zero, absent, hit, calls,
             f32_bytes=5310484480):
    return {
        "kft_serve_decode_attn_rows_total": {
            'kind="cache"': 0, 'kind="written"': written,
            'kind="written_free"': written_free, 'kind="fetched"': 0,
            'kind="fetched_free"': 0},
        "kft_moe_assignments_total": {
            'layer="0",expert="0"': held // 2, 'layer="3",expert="7"': held - held // 2},
        "kft_moe_zero_assignments_total": {"": zero},
        "kft_moe_absent_assignments_total": {"": absent},
        "kft_moe_experts_hit_total": {"": hit},
        "kft_moe_decode_layer_calls_total": {"": calls},
        "kft_serve_param_bytes": {'dtype="bfloat16"': 4343201792,
                                  'dtype="float32"': f32_bytes}}


def test_bytes_from_shapes():
    # 576 bf16 numbers a token a sublayer, two sublayers a layer, 4 layers
    assert L.latent_row_bytes(CONFIG) == (512 + 64) * 2 == 1152
    assert L.bytes_per_row(CONFIG) == 4 * 2 * 1152 == 9216
    f32 = dict(CONFIG, program=dict(CONFIG["program"], dtype="float32"))
    assert L.bytes_per_row(f32) == 2 * 9216
    assert L.expert_params_held(CONFIG) == 4 * 8 * EXPERT == 1207959552
    call = L.held_expert_layer_call(CONFIG, rows=4, experts_hit=3, weight_bytes=4)
    assert call["flops"] == 2.0 * 4 * EXPERT
    acts = 4 * (6144 * 2 + 2 * 2048 * 4 + 2048 * 2 + 6144 * 4)
    assert call["bytes"] == 3 * EXPERT * 4 + acts
    half = L.held_expert_layer_call(CONFIG, rows=4, experts_hit=3, weight_bytes=2)
    assert half["bytes"] == 3 * EXPERT * 2 + acts


def test_readers_on_a_capture_made_by_hand(readers, as_run):
    """Two decode programs of one layer (two latent-attention kernel events
    of 60 us and three grouped-matmul events of 200 us each) and a prefill
    whose grouped matmuls are not the roofline's.  The counters say the
    busy slots' cursors stood at 40,000 + 40,032 rows over the two steps
    (none under a free slot), and that the two layer calls routed 700 live
    assignments: 8 to held experts (5 distinct over the two calls), 232 to
    identity experts, 460 to experts held elsewhere."""
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 0.010)):
        modules.append(["jit__decode(123)", t0, 0.004])
        for sub in range(2):
            ops.append([f"kft_mla_decode_attn.{sub} [tpu_custom_call]",
                        t0 + 0.0005 * sub, 60e-6])
        for part in range(3):
            ops.append([f"kft_moe_gmm.{part} [tpu_custom_call]",
                        t0 + 0.001 + 0.0003 * part, 200e-6])
        ops.append([f"fusion.{step}", t0 + 0.002, 0.001])
    modules.append(["jit__prefill(9)", 0.020, 0.003])
    ops.append(["kft_moe_gmm.9 [tpu_custom_call]", 0.020, 0.001])
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
             "host": [], "lines": {}}
    start = counters(1_000_000, 0, 100, 3_000, 6_000, 80, 40)
    end = counters(1_080_032, 0, 108, 3_232, 6_460, 85, 42)
    ctx = as_run(trace, {"start": start, "end": end})
    got = {n: readers[n].read(ctx) for n in ALL}
    assert L.needed_rows(ctx) == 80_032
    assert L.assignment_deltas(ctx) == {"held": 8, "zero": 232, "absent": 460,
                                        "hit": 5, "calls": 2}
    assert L.expert_weight_bytes(ctx) == 4
    # busy: 4 x 60 us + 6 x 200 us + 2 x 1 ms + 1 ms
    busy = 4 * 60e-6 + 6 * 200e-6 + 2 * 1e-3 + 1e-3
    assert got["mla_decode_attn_share"] == pytest.approx(100 * 240e-6 / busy)
    assert L.mla_kernel_events(trace) == (4, pytest.approx(240e-6))
    assert L.gmm_kernel_events(trace) == (6, pytest.approx(1200e-6))
    # one layer in this hand-made program, the configuration says four:
    # the reader goes by the configuration, as the cell's program does
    least = 80_032 * 9216 / 819e9
    assert got["mla_decode_attn_roofline"] == pytest.approx(100 * least / 240e-6)
    call = L.held_expert_layer_call(CONFIG, 4.0, 2.5, 4)
    floor = max(call["flops"] / 197e12, call["bytes"] / 819e9)
    assert got["moe_held_expert_roofline"] == pytest.approx(
        100 * floor * 2 / 1200e-6)
    assert 0 < got["moe_held_expert_roofline"] < 100
    assert got["moe_zero_share"] == pytest.approx(100 * 232 / 700)
    assert got["moe_experts_hit_mean"] == pytest.approx(2.5)
    # 8 assignments over the two (layer, expert) counts the capture names: 4, 4
    assert got["moe_load_max_over_mean"] == pytest.approx(1.0)
    # held experts stored in bf16 (ROADMAP S13b): the program says so and
    # the floor halves with it; the configuration file is not asked
    small = 5310484480 - 4 * 1207959552 + 2 * 1207959552
    ctx = as_run(trace, {"start": dict(start), "end": counters(
        1_080_032, 0, 108, 3_232, 6_460, 85, 42, f32_bytes=small - 2 * 1207959552)})
    assert L.expert_weight_bytes(ctx) == 2
    assert readers["moe_held_expert_roofline"].read(ctx) < got[
        "moe_held_expert_roofline"] * 0.51


def test_readers_find_nothing_in_a_program_without_the_block(readers, as_run):
    """The parent of PR 35 cannot run the configuration at all; whatever
    program leaves a capture without the kernel and the counters gets a
    share of nothing, no number, no exception."""
    trace = {"devices": [{"name": "/device:TPU:0",
                          "ops": [["fusion.1", 0.0, 0.004],
                                  ["kft_decode_attn.1 [tpu_custom_call]", 0.004, 1e-4]],
                          "modules": [["jit__decode(1)", 0.0, 0.005]]}],
             "host": [], "lines": {}}
    ctx = as_run(trace, None)
    assert readers["mla_decode_attn_share"].read(ctx) == 0.0
    for n in ALL[1:]:
        assert readers[n].read(ctx) is None
    params_only = {"kft_serve_param_bytes": {'dtype="float32"': 1}}
    ctx = as_run(trace, {"start": params_only, "end": params_only})
    assert [readers[n].read(ctx) for n in ALL[1:]] == [None] * 5
    assert all(readers[n].read(dict(ctx, trace=None)) is None for n in NEW)
    # the counters of a layer that holds every expert (no identity or absent
    # families): the zero share and the held roofline have nothing to read
    whole = {"kft_moe_assignments_total": {'layer="0",expert="0"': 5},
             "kft_moe_experts_hit_total": {"": 3},
             "kft_moe_decode_layer_calls_total": {"": 2}}
    ctx = as_run(trace, {"start": {}, "end": whole})
    assert readers["moe_zero_share"].read(ctx) is None
    assert readers["moe_held_expert_roofline"].read(ctx) is None
    assert readers["moe_experts_hit_mean"].read(ctx) == pytest.approx(1.5)


def test_counter_readers_on_the_recorded_capture(readers, as_run):
    """The counters.json of a traced run of the cell on the chip (my chip
    run, PR 35, chiprun_out/pr35d, seed 3400000059: 201 decode steps and 5
    prefills in the capture).  Its result line read `moe_zero_share`
    33.3555 and 2.23125 distinct held experts a layer call; the two
    accepted readers find the same families a whole layer exports."""
    empty = {"devices": [], "host": [], "lines": {}}
    ctx = as_run(empty, load_json(RECORDED))
    assert L.assignment_deltas(ctx) == {
        "held": 2070, "zero": 67613, "absent": 133021, "hit": 1785, "calls": 800}
    assert L.needed_rows(ctx) == 11981774 - 7097520
    assert L.expert_weight_bytes(ctx) == 4
    assert readers["moe_zero_share"].read(ctx) == pytest.approx(33.3555, abs=1e-4)
    assert readers["moe_experts_hit_mean"].read(ctx) == pytest.approx(2.23125)
    # 2,070 assignments over 4 layers x 8 held experts, the busiest took 85
    assert readers["moe_load_max_over_mean"].read(ctx) == pytest.approx(85 / (2070 / 32))
    # the kernels' events were not kept with it: the rooflines say nothing
    assert readers["mla_decode_attn_roofline"].read(ctx) is None
    assert readers["moe_held_expert_roofline"].read(ctx) is None
