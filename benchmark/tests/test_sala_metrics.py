"""The readers this cell brings (benchmark/layer_metrics/lin_attn_share,
lin_attn_roofline, lin_attn_prefill_roofline, sparse_attn_share,
sparse_attn_roofline, sparse_attn_fetched_share) and their arithmetic
(benchmark/lib/sala_costs.py): on a capture made by hand with known answers,
on the counters of a traced run of the cell on the chip (tests/data/sala/),
and on captures of a program without the kernels and the counters, where
the shares of busy time read 0 and the other readers find nothing and say
so.  The manifest's entries are looked up by name: a later PR may put its
own after them."""
import json
import os

import pytest

from benchmark.lib import metrics as M
from benchmark.lib import moe_costs as C
from benchmark.lib import sala_costs as S
from benchmark.lib import xplane as X
from benchmark.lib.configs import ROOT, load_json
from benchmark.lib.manifest import Manifest, check_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-sala-docs-r80"
NEW = ("lin_attn_share", "lin_attn_roofline", "lin_attn_prefill_roofline",
       "sparse_attn_share", "sparse_attn_roofline", "sparse_attn_fetched_share")
SHARES = ("lin_attn_share", "sparse_attn_share")
RECORDED = os.path.join(HERE, "data", "sala", "serve-sala-docs.counters.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = load_json(os.path.join(ROOT, "benchmark", "configs",
                                "minicpm-sala-serve.json"))


@pytest.fixture(scope="module")
def readers():
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "docs-open-sala"
    entry = man.config_entry(cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types",
                                "max_position_embeddings"]
    found = dict((m["name"], (m, path))
                 for m, path in man.metrics_for(cell, "per_layer"))
    for n in NEW:
        m = found[n][0]
        assert CELL in m["workloads"] and m["layer"] == "kernels"
        assert (m["moves"], m["unit"]) == ("tpot_p50_ms", "%")
    # the kernels this model does not run are not asked of the cell
    for n in ("decode_attn_share", "decode_attn_roofline", "mla_decode_attn_share",
              "moe_expert_share", "ssm_scan_share", "ssm_scan_roofline"):
        assert n not in found
    # the engine's, the router's and the device's serving metrics are
    assert {"decode_step_ms_mean", "prefill_ms_mean", "decode_live_share",
            "decode_ahead_share", "router_overhead_ms_p50", "serve_device_idle",
            "serve_peak_hbm_gib", "serve_state_share", "setup_weights_s"} \
        <= set(found)
    e2e = {m["name"] for m, _ in man.metrics_for(cell, "end_to_end")}
    assert e2e == {"tpot_p50_ms", "setup_s"}
    return {n: M.Reader(n, found[n][1]) for n in NEW}


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


def counters(prefill, decode, written, fetched, kernels):
    return {"kft_serve_scan_tokens_total": {'kind="prefill"': prefill,
                                            'kind="decode"': decode},
            "kft_serve_sparse_rows_total": {'kind="written"': written,
                                            'kind="fetched"': fetched,
                                            'kind="kernels"': kernels}}


def test_the_configuration_is_the_catalogs_cut_as_written():
    catalog = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"MiniCPM-SALA"' in line] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    reduced = set(CONFIG["reduced"])
    for entry in catalog:
        for key, value in entry["config"].items():
            if key not in reduced:
                assert CONFIG[key] == value, key
        assert CONFIG["mixer_types"] == entry["config"]["mixer_types"][9:17]
        assert CONFIG["source"] == entry["source_url"]
    assert CONFIG["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 + [
        "minicpm4"]
    assert CONFIG["num_hidden_layers"] == 8 == len(CONFIG["program"]["mixer_types"])
    assert CONFIG["max_position_embeddings"] == 12288
    assert CONFIG["published"]["num_hidden_layers"] == 32 \
        == CONFIG["program"]["scale_depth_layers"]
    assert set(CONFIG["sparse_config"]) <= set(CONFIG["assumed"]) | {
        "kernel_size", "kernel_stride", "init_blocks", "block_size",
        "window_size", "topk"}
    held = CONFIG["bytes"]
    assert held["float32_bytes_checker_and_boot"] == 4 * held["parameters"]
    assert held["resident_bytes"]["total"] == sum(
        held["resident_bytes"][k] for k in ("bfloat16", "float32"))


def test_bytes_and_operations_from_shapes():
    assert S.layers(CONFIG, "lightning-attn") == 6 and S.layers(CONFIG, "minicpm4") == 2
    assert S.lightning_width(CONFIG) == 4096
    assert S.lightning_state_bytes(CONFIG) == 32 * 128 * 128 * 4 == 2097152
    # a token's rows in one layer: q, k, v bf16 in, o float32 out
    assert S.lightning_row_bytes(CONFIG) == 4096 * (3 * 2 + 4) == 40960
    assert S.lightning_decode_step_bytes(CONFIG) == 6 * (2 * 2097152 + 40960)
    # a prefill token: the rows bind it (50 ns against 13 ns of operations)
    rows, flops = 40960 / 819e9, 5 * 128 * 128 * 32 / 197e12
    assert rows > flops
    assert S.lightning_prefill_token_seconds(CONFIG, PEAKS) == pytest.approx(6 * rows)
    # a fetched row over both layers and KV heads: K and V of 128 bf16 each
    assert S.sparse_row_bytes(CONFIG) == 2 * 2 * 2 * 128 * 2 == 2048
    # what the file's own `bytes` block says of the cache at 16 slots
    held = CONFIG["bytes"]["cache_bytes"]
    assert held["state"] == 16 * 6 * 2097152
    assert held["rows"] == 16 * (2 * (2 * 12288 + 768) * 256 * 2 + 8 * (4 + 1))
    f32 = dict(CONFIG, program=dict(CONFIG["program"], dtype="float32"))
    assert S.lightning_row_bytes(f32) == 4096 * 16
    assert S.sparse_row_bytes(f32) == 4096


def test_readers_on_a_capture_made_by_hand(readers, as_run):
    """Two decode programs, each six lightning events of 80 us and two
    sparse events of 100 us, and three prefill programs whose lightning
    events take 5 ms: one cut by the capture's start (its last three layers
    are in the trace), one whole, one cut by the capture's stop (its first
    layer).  The counters say 20 live slot-steps over the two decode steps,
    19,000 real tokens in the two prefills read inside the capture, 200,000
    rows written of which 81,920 fetched."""
    ops, modules = [], []
    modules.append(["jit__prefill(9)", 0.0, 0.020])
    for layer in range(3, 6):
        ops.append([f"kft_lightning_attn.{layer} [tpu_custom_call]",
                    0.007 * (layer - 3), 5e-3])
    for step, t0 in enumerate((0.021, 0.033)):
        modules.append(["jit__decode(123)", t0, 0.010])
        for layer in range(6):
            ops.append([f"kft_lightning_attn.{layer} [tpu_custom_call]",
                        t0 + 0.001 * layer, 80e-6])
        for layer in range(2):
            ops.append([f"kft_sparse_decode_attn.{layer} [tpu_custom_call]",
                        t0 + 0.007 + 0.001 * layer, 100e-6])
        ops.append([f"fusion.{step}", t0 + 0.009, 0.001])
    modules.append(["jit__prefill(9)", 0.045, 0.050])
    for layer in range(6):
        ops.append([f"kft_lightning_attn.{layer} [tpu_custom_call]",
                    0.045 + 0.007 * layer, 5e-3])
    modules.append(["jit__prefill(9)", 0.100, 0.006])
    ops.append(["kft_lightning_attn.0 [tpu_custom_call]", 0.101, 5e-3])
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
             "host": [], "lines": {}}
    assert S.prefills_in_capture(trace) == (2, 9, pytest.approx(9 * 5e-3))
    ctx = as_run(trace, {"start": counters(50_000, 9_000, 10**7, 4 * 10**6, 10**6),
                         "end": counters(69_000, 9_020, 10**7 + 200_000,
                                         4 * 10**6 + 81_920, 10**6 + 12_000)})
    got = {n: readers[n].read(ctx) for n in NEW}
    assert S.tokens_delta(ctx) == {"prefill": 19_000, "decode": 20}
    assert S.sparse_rows_delta(ctx) == {"written": 200_000, "fetched": 81_920,
                                        "kernels": 12_000}
    lin = 12 * 80e-6 + 10 * 5e-3
    busy = lin + 4 * 100e-6 + 2 * 1e-3
    assert got["lin_attn_share"] == pytest.approx(100 * lin / busy)
    assert got["sparse_attn_share"] == pytest.approx(100 * 4 * 100e-6 / busy)
    least = 20 * 6 * (2 * 2097152 + 40960) / 819e9
    assert got["lin_attn_roofline"] == pytest.approx(100 * least / (12 * 80e-6))
    # a prefill's: 9,500 tokens of the two that ended inside the capture
    # against six events of 5 ms; summed over the capture it would be
    # 19,000 tokens against nine events and a half, a third too high
    least = 9_500 * 6 * 40960 / 819e9
    assert got["lin_attn_prefill_roofline"] == pytest.approx(
        100 * least / (6 * 5e-3))
    least = 81_920 * 2048 / 819e9
    assert got["sparse_attn_roofline"] == pytest.approx(100 * least / (4 * 100e-6))
    assert got["sparse_attn_fetched_share"] == pytest.approx(40.96)
    for n in NEW:
        assert 0 < got[n] < 100, n
    # no prefill read inside the capture: that share has nothing to read
    ctx = as_run(trace, {"start": counters(50_000, 9_000, 0, 0, 0),
                         "end": counters(50_000, 9_020, 200_000, 81_920, 1)})
    assert readers["lin_attn_prefill_roofline"].read(ctx) is None
    assert readers["lin_attn_roofline"].read(ctx) == pytest.approx(
        got["lin_attn_roofline"])


def test_a_prefill_cut_by_the_captures_start_cannot_take_the_share_past_100(
        readers, as_run):
    """The kernel at its roofline for a full bucket: every event takes
    exactly the least time 12,288 tokens need in a layer.  Two prefills of
    12,288 real tokens are read inside the capture, and the trace holds the
    last layer of the first and the whole second: the share reads 100, where
    the tokens summed over the kernel seconds summed would read 171."""
    a_layer = 12_288 * 40960 / 819e9
    ops = [["kft_lightning_attn.5 [tpu_custom_call]", 0.0, a_layer]]
    modules = [["jit__prefill(9)", 0.0, 0.001], ["jit__prefill(9)", 0.5, 0.5],
               ["jit__decode(1)", 1.0, 0.01]]
    ops += [[f"kft_lightning_attn.{layer} [tpu_custom_call]", 0.5 + 0.05 * layer,
             a_layer] for layer in range(6)]
    ops.append(["fusion.1", 1.0, 0.01])
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
             "host": [], "lines": {}}
    ctx = as_run(trace, {"start": counters(0, 0, 0, 0, 0),
                         "end": counters(2 * 12_288, 0, 0, 0, 0)})
    assert readers["lin_attn_prefill_roofline"].read(ctx) == pytest.approx(100.0)
    summed = 2 * 12_288 * 6 * 40960 / 819e9 / (7 * a_layer)
    assert 100 * summed == pytest.approx(1200 / 7)


def test_readers_find_nothing_in_a_program_without_the_layers(readers, as_run):
    """The parent of this PR cannot run the configuration at all; whatever
    program leaves a capture without the kernels and the counters gets a
    share of nothing, no number, no exception: also under another
    configuration's file, which has no `mixer_types`."""
    trace = {"devices": [{"name": "/device:TPU:0",
                          "ops": [["fusion.1", 0.0, 0.004],
                                  ["kft_decode_attn.1 [tpu_custom_call]", 0.004, 1e-4]],
                          "modules": [["jit__decode(1)", 0.0, 0.005]]}],
             "host": [], "lines": {}}
    rest = [n for n in NEW if n not in SHARES]
    ctx = as_run(trace, None)
    assert [readers[n].read(ctx) for n in SHARES] == [0.0, 0.0]
    assert [readers[n].read(ctx) for n in rest] == [None] * 4
    other = {"kft_serve_param_bytes": {'dtype="float32"': 1},
             "kft_serve_scan_tokens_total": {'kind="prefill"': 5, 'kind="decode"': 7}}
    ctx = as_run(trace, {"start": other, "end": other})
    assert [readers[n].read(ctx) for n in rest] == [None] * 4
    assert all(readers[n].read(dict(ctx, trace=None)) is None for n in NEW)
    jamba = load_json(os.path.join(ROOT, "benchmark", "configs",
                                   "jamba2-3b-serve.json"))
    moving = {"start": counters(0, 0, 0, 0, 0), "end": counters(9, 9, 0, 0, 0)}
    ctx = as_run(trace, moving, config=jamba)
    assert [readers[n].read(ctx) for n in rest] == [None] * 4


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no capture of the cell was recorded")
def test_counter_readers_on_the_recorded_capture(readers, as_run):
    """The counters.json of a traced run of the cell on the chip (the data
    file says which).  The kernels' events were not kept with it, so the
    rooflines are reckoned again here from that run's kernel seconds and
    must give what its result line read."""
    empty = {"devices": [], "host": [], "lines": {}}
    doc = load_json(RECORDED)
    ctx = as_run(empty, doc["counters"])
    assert S.tokens_delta(ctx) == doc["tokens_delta"]
    assert S.sparse_rows_delta(ctx) == doc["sparse_rows_delta"]
    line, k = doc["result_line"], doc["kernel_events"]
    assert readers["sparse_attn_fetched_share"].read(ctx) == pytest.approx(
        line["sparse_attn_fetched_share"], abs=1e-6)
    assert 35 < line["sparse_attn_fetched_share"] < 50     # 4,096 of ~10,000 rows
    assert readers["lin_attn_roofline"].read(ctx) is None   # no events kept
    least = doc["tokens_delta"]["decode"] * S.lightning_decode_step_bytes(CONFIG) / 819e9
    assert 100 * least / k["lightning_decode_seconds"] == pytest.approx(
        line["lin_attn_roofline"], rel=1e-6)
    least = doc["sparse_rows_delta"]["fetched"] * S.sparse_row_bytes(CONFIG) / 819e9
    assert 100 * least / k["sparse_decode_seconds"] == pytest.approx(
        line["sparse_attn_roofline"], rel=1e-6)
    if "lin_attn_prefill_roofline" in line:
        least = doc["tokens_delta"]["prefill"] / k["prefills_ending_inside"] \
            * S.lightning_prefill_token_seconds(CONFIG, PEAKS)
        a_prefill = 6 * k["lightning_prefill_seconds"] / k["lightning_prefill_events"]
        assert 100 * least / a_prefill == pytest.approx(
            line["lin_attn_prefill_roofline"], rel=1e-6)
    for n in NEW:
        if n in line:
            assert 0 <= line[n] <= 100, n
