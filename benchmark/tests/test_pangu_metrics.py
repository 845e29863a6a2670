"""The readers this cell brings (benchmark/layer_metrics/latent_attn_roofline,
shared_moe_expert_roofline, moe_absent_share) and their arithmetic
(benchmark/lib/latent_moe_costs.py), with the accepted readers the cell is
also listed under (mla_decode_attn_share, moe_expert_share,
moe_experts_hit_mean, moe_load_max_over_mean: trace and counter readers that
know no configuration key): on a capture made by hand with known answers,
on the counters of a traced run of the cell on the chip (tests/data/pangu/),
and on captures of a program without the kernel and the counters, where the
shares of busy time read 0 and the other readers find nothing and say so.

Since this cell is appended to `mla_decode_attn_share`'s `workloads`, the
module fixture of test_longcat_metrics.py (which pins that list to its own
cell alone, and may not be edited here) fails before its three cases run:
the last test calls those cases, from that file and on its captures, with
readers found by name, as test_accepted_readers.py does for the two files
PR 35 left in that state."""
import importlib.util
import inspect
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import latent_moe_costs as L
from benchmark.lib import metrics as M
from benchmark.lib import moe_costs as C
from benchmark.lib import xplane as X
from benchmark.lib.configs import ROOT, load_json
from benchmark.lib.manifest import Manifest, check_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-pangu-reason-r80"
NEW = ("latent_attn_roofline", "shared_moe_expert_roofline", "moe_absent_share")
#: accepted metrics whose readers take this program's trace and counters as
#: they are
ACCEPTED = ("mla_decode_attn_share", "moe_expert_share", "moe_experts_hit_mean",
            "moe_load_max_over_mean")
ALL = NEW + ACCEPTED
RECORDED = os.path.join(HERE, "data", "pangu", "serve-pangu-reason.counters.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = load_json(os.path.join(ROOT, "benchmark", "configs",
                                "openpangu-ultra-moe-serve.json"))
EXPERT = 3 * 7680 * 2048  # parameters of one routed expert: gate, up, down
F32_BYTES = CONFIG["bytes"]["resident_bytes"]["float32"]
BF16_BYTES = CONFIG["bytes"]["resident_bytes"]["bfloat16"]


def _readers(cell, names):
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    found = dict((m["name"], (m, path))
                 for m, path in man.metrics_for(man.cell(cell), "per_layer"))
    assert set(names) <= set(found)
    for n in names:
        assert cell in found[n][0]["workloads"]
        assert found[n][0]["moves"] == "tpot_p50_ms"
    return found, {n: M.Reader(n, found[n][1]) for n in names}


@pytest.fixture(scope="module")
def readers():
    found, readers = _readers(CELL, ALL)
    assert all(found[n][0]["workloads"] == [CELL] for n in NEW)
    # the readers that reckon from another block's key names or counts, or
    # from identity experts, are not asked of this cell
    for n in ("mla_decode_attn_roofline", "moe_held_expert_roofline",
              "moe_zero_share", "moe_expert_roofline", "decode_attn_roofline"):
        assert n not in found
    # every per-layer metric that moves tpot_p50_ms and lists no cells would
    # have to be reported here; there is none
    man = Manifest(ROOT)
    assert all("workloads" in m for m in man.doc["per_layer"]
               if m["moves"] == "tpot_p50_ms")
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "tpot_p50_ms")["workloads"]
    return readers


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


def counters(written, written_free, held, absent, hit, calls,
             f32_bytes=F32_BYTES):
    return {
        "kft_serve_decode_attn_rows_total": {
            'kind="cache"': 0, 'kind="written"': written,
            'kind="written_free"': written_free, 'kind="fetched"': 0,
            'kind="fetched_free"': 0},
        "kft_moe_assignments_total": {
            'layer="0",expert="0"': held // 2, 'layer="3",expert="7"': held - held // 2},
        "kft_moe_zero_assignments_total": {"": 0},
        "kft_moe_absent_assignments_total": {"": absent},
        "kft_moe_experts_hit_total": {"": hit},
        "kft_moe_decode_layer_calls_total": {"": calls},
        "kft_serve_param_bytes": {'dtype="bfloat16"': BF16_BYTES,
                                  'dtype="float32"': f32_bytes}}


def test_bytes_from_shapes():
    # 576 bf16 numbers a token a layer, one attention sublayer in each of
    # the 5 layers (the leading dense one too); 4 of them are expert layers
    assert L.latent_row_bytes(CONFIG) == (512 + 64) * 2 == 1152
    assert L.bytes_per_row(CONFIG) == 5 * 1152 == 5760
    assert L.expert_layers(CONFIG) == 4
    f32 = dict(CONFIG, program=dict(CONFIG["program"], dtype="float32"))
    assert L.bytes_per_row(f32) == 2 * 5760
    assert L.expert_params_held(CONFIG) == 4 * 8 * EXPERT == 1509949440
    # what the file's own `bytes` block says of one expert and of the cache
    assert CONFIG["bytes"]["a_layer"]["one_routed_expert"] == EXPERT
    assert CONFIG["bytes"]["cache_bytes"] == 32 * 4096 * L.bytes_per_row(CONFIG)
    call = L.held_expert_layer_call(CONFIG, rows=6, experts_hit=4, weight_bytes=4)
    assert call["flops"] == 2.0 * 6 * EXPERT
    acts = 6 * (7680 * 2 + 2 * 2048 * 4 + 2048 * 2 + 7680 * 4)
    assert call["bytes"] == 4 * EXPERT * 4 + acts
    half = L.held_expert_layer_call(CONFIG, rows=6, experts_hit=4, weight_bytes=2)
    assert half["bytes"] == 4 * EXPERT * 2 + acts


def test_readers_on_a_capture_made_by_hand(readers, as_run):
    """Two decode programs (five latent-attention kernel events of 120 us,
    one a layer, and three grouped-matmul events of 400 us each) and a prefill
    whose grouped matmuls are not the roofline's.  The counters say the
    busy slots' cursors stood at 30,000 + 30,023 rows over the two steps
    (none under a free slot), and that the two layer calls routed 368 live
    assignments: 12 to held experts (7 distinct over the two calls), 356 to
    experts held elsewhere, none to identity experts (there are none)."""
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 0.010)):
        modules.append(["jit__decode(123)", t0, 0.004])
        for layer in range(5):
            ops.append([f"kft_mla_decode_attn.{layer} [tpu_custom_call]",
                        t0 + 0.00015 * layer, 120e-6])
        for part in range(3):
            ops.append([f"kft_moe_gmm.{part} [tpu_custom_call]",
                        t0 + 0.001 + 0.0005 * part, 400e-6])
        ops.append([f"fusion.{step}", t0 + 0.0026, 0.001])
    modules.append(["jit__prefill(9)", 0.020, 0.003])
    ops.append(["kft_moe_gmm.9 [tpu_custom_call]", 0.020, 0.001])
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
             "host": [], "lines": {}}
    start = counters(1_000_000, 0, 100, 9_000, 80, 40)
    end = counters(1_060_023, 0, 112, 9_356, 87, 42)
    ctx = as_run(trace, {"start": start, "end": end})
    got = {n: readers[n].read(ctx) for n in ALL}
    assert L.needed_rows(ctx) == 60_023
    assert L.assignment_deltas(ctx) == {"held": 12, "zero": 0, "absent": 356,
                                        "hit": 7, "calls": 2}
    assert L.expert_weight_bytes(ctx) == 4
    busy = 10 * 120e-6 + 6 * 400e-6 + 2 * 1e-3 + 1e-3
    assert got["mla_decode_attn_share"] == pytest.approx(100 * 1200e-6 / busy)
    # the share of busy time counts the prefill's grouped matmul too
    assert got["moe_expert_share"] == pytest.approx(100 * (2400e-6 + 1e-3) / busy)
    assert L.mla_kernel_events(trace) == (10, pytest.approx(1200e-6))
    assert L.gmm_kernel_events(trace) == (6, pytest.approx(2400e-6))
    # the rows are counted a sublayer; the bytes are of all five layers'
    least = 60_023 * 5760 / 819e9
    assert got["latent_attn_roofline"] == pytest.approx(100 * least / 1200e-6)
    call = L.held_expert_layer_call(CONFIG, 6.0, 3.5, 4)
    floor = max(call["flops"] / 197e12, call["bytes"] / 819e9)
    assert got["shared_moe_expert_roofline"] == pytest.approx(
        100 * floor * 2 / 2400e-6)
    assert 0 < got["shared_moe_expert_roofline"] < 100
    assert 0 < got["latent_attn_roofline"] < 100
    assert got["moe_absent_share"] == pytest.approx(100 * 356 / 368)
    assert got["moe_experts_hit_mean"] == pytest.approx(3.5)
    assert got["moe_load_max_over_mean"] == pytest.approx(1.0)
    # held experts stored in bf16 (ROADMAP S13b): the program says so and
    # the floor halves with it; the configuration file is not asked
    held = L.expert_params_held(CONFIG)
    ctx = as_run(trace, {"start": dict(start), "end": counters(
        1_060_023, 0, 112, 9_356, 87, 42, f32_bytes=F32_BYTES - 4 * held)})
    assert L.expert_weight_bytes(ctx) == 2
    assert readers["shared_moe_expert_roofline"].read(ctx) < got[
        "shared_moe_expert_roofline"] * 0.51


def test_readers_find_nothing_in_a_program_without_the_block(readers, as_run):
    """The parent of this PR cannot run the configuration at all; whatever
    program leaves a capture without the kernels and the counters gets a
    share of nothing, no number, no exception."""
    trace = {"devices": [{"name": "/device:TPU:0",
                          "ops": [["fusion.1", 0.0, 0.004],
                                  ["kft_decode_attn.1 [tpu_custom_call]", 0.004, 1e-4]],
                          "modules": [["jit__decode(1)", 0.0, 0.005]]}],
             "host": [], "lines": {}}
    ctx = as_run(trace, None)
    assert readers["mla_decode_attn_share"].read(ctx) == 0.0
    assert readers["moe_expert_share"].read(ctx) == 0.0
    rest = [n for n in ALL if n not in ("mla_decode_attn_share", "moe_expert_share")]
    for n in rest:
        assert readers[n].read(ctx) is None
    params_only = {"kft_serve_param_bytes": {'dtype="float32"': 1}}
    ctx = as_run(trace, {"start": params_only, "end": params_only})
    assert [readers[n].read(ctx) for n in rest] == [None] * len(rest)
    assert all(readers[n].read(dict(ctx, trace=None)) is None for n in NEW)
    # the counters of a layer that holds every expert (no identity or absent
    # families): the absent share and the held roofline have nothing to read
    whole = {"kft_moe_assignments_total": {'layer="0",expert="0"': 5},
             "kft_moe_experts_hit_total": {"": 3},
             "kft_moe_decode_layer_calls_total": {"": 2}}
    ctx = as_run(trace, {"start": {}, "end": whole})
    assert readers["moe_absent_share"].read(ctx) is None
    assert readers["shared_moe_expert_roofline"].read(ctx) is None
    assert readers["moe_experts_hit_mean"].read(ctx) == pytest.approx(1.5)


def test_counter_readers_on_the_recorded_capture(readers, as_run):
    """The counters.json of a traced run of the cell on the chip (my chip
    run, PR 40; the docstring of the data file's directory says which).
    The kernels' events were not kept with it, so the rooflines say
    nothing; the counter readers give what that run's result line read."""
    empty = {"devices": [], "host": [], "lines": {}}
    doc = load_json(RECORDED)
    ctx = as_run(empty, doc["counters"])
    d = L.assignment_deltas(ctx)
    assert d == doc["assignment_deltas"] and d["zero"] == 0
    assert L.needed_rows(ctx) == doc["needed_rows"] > 0
    assert L.expert_weight_bytes(ctx) == 4
    line = doc["result_line"]
    assert readers["moe_absent_share"].read(ctx) == pytest.approx(
        line["moe_absent_share"], abs=1e-4)
    assert readers["moe_experts_hit_mean"].read(ctx) == pytest.approx(
        line["moe_experts_hit_mean"], abs=1e-4)
    assert readers["moe_load_max_over_mean"].read(ctx) == pytest.approx(
        line["moe_load_max_over_mean"], abs=1e-4)
    assert readers["latent_attn_roofline"].read(ctx) is None
    assert readers["shared_moe_expert_roofline"].read(ctx) is None
    # the rooflines of that run, reckoned again from its kernel seconds
    k = doc["kernel_events"]
    least = doc["needed_rows"] * L.bytes_per_row(CONFIG) / 819e9
    assert 100 * least / k["mla_seconds"] == pytest.approx(
        line["latent_attn_roofline"], rel=1e-6)
    call = L.held_expert_layer_call(CONFIG, d["held"] / d["calls"],
                                    d["hit"] / d["calls"], 4)
    floor = max(call["flops"] / 197e12, call["bytes"] / 819e9)
    assert 100 * floor * (k["gmm_count"] / 3.0) / k["gmm_seconds"] == pytest.approx(
        line["shared_moe_expert_roofline"], rel=1e-6)
    assert line["latent_attn_roofline"] < 100
    assert line["shared_moe_expert_roofline"] < 100


def test_the_forced_drafting_run_rehearses_on_the_cpu(tmp_path):
    """benchmark/rehearse_mtp.py end to end at a tiny size: the engine from
    the cell's file with the module built and the drafter held on, the
    checker on what it served, the break-even acceptance from the two
    passes' step times."""
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "layer_metrics", "e2e_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        root / "benchmark" / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = root / "benchmark" / "configs" / "openpangu-ultra-moe-serve.json"
    config = load_json(path)
    config.update(vocab_size=512, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=48, num_hidden_layers=3,
                  num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
                  q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=16,
                  qk_nope_head_dim=16, max_position_embeddings=128,
                  num_experts_per_tok=4)
    config["program"].update(
        d_ff_expert=48, kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_experts=16, experts_per_token=4,
        experts_held=4, dtype="float32", head_init_std=0.02)
    config["deployment"]["slots"] = 4
    with open(path, "w") as f:
        json.dump(config, f)
    path = root / "benchmark" / "traffic" / "reason-open-pangu.json"
    traffic = load_json(path)
    traffic.update(rate_per_s=4.0, answer_len={"dist": "uniform", "min": 4, "max": 12},
                   prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.5,
                               "min": 4, "max": 60})
    with open(path, "w") as f:
        json.dump(traffic, f)
    env = dict(os.environ, KFT_BENCH_REHEARSE="cpu", JAX_PLATFORMS="cpu")
    out = tmp_path / "mtp.json"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse_mtp.py"),
         "--bench-root", str(root), "--workload", CELL, "--seed", "3000000123",
         "--seconds", "3", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = load_json(out)
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["check"]["ok"] and doc["check"]["checked_tokens"] > 0
    spec = doc["spec"]
    assert spec["k"] == 2 and spec["rounds"] > 10 and spec["disabled_slots"] == 0
    assert spec["committed_tokens"] >= spec["rounds"]
    assert doc["drafting"]["spec_steps"] > 0 and doc["plain"]["plain_steps"] > 0
    assert doc["plain"]["spec_steps"] == 0
    # float32 on the CPU: the verify-2 round is the plain step twice over
    assert doc["requests_with_the_plain_passes_tokens"] == doc["requests"]
    assert doc["break_even_acceptance"] == pytest.approx(
        doc["round_ms"] / doc["plain_ms"] - 1.0)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name + "_cases", os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LONGCAT = _load("test_longcat_metrics")
LONGCAT_CASES = [n for n, f in vars(LONGCAT).items() if n.startswith("test_")
                 and "readers" in inspect.signature(f).parameters]


def test_every_erroring_case_is_found():
    assert len(LONGCAT_CASES) == 3


@pytest.mark.parametrize("case", LONGCAT_CASES)
def test_longcat_case_with_readers_found_by_name(case, monkeypatch, tmp_path):
    _, found = _readers(LONGCAT.CELL, LONGCAT.ALL)
    lay = LONGCAT.as_run.__wrapped__  # the fixture's own function
    wants = [{"monkeypatch": monkeypatch, "tmp_path": tmp_path,
              "readers": found}[p] for p in inspect.signature(lay).parameters]
    getattr(LONGCAT, case)(found, lay(*wants))
