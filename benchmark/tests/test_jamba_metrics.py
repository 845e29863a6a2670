"""The readers this cell brings (benchmark/layer_metrics/ssm_scan_share,
ssm_scan_roofline, ssm_prefill_scan_roofline, serve_state_share) and their
arithmetic (benchmark/lib/ssm_costs.py): on a capture made by hand with
known answers, on the counters of a traced run of the cell on the chip
(tests/data/jamba/), and on captures of a program without the kernel and
the counters, where the share of busy time reads 0 and the other readers
find nothing and say so."""
import json
import os

import pytest

from benchmark.lib import metrics as M
from benchmark.lib import moe_costs as C
from benchmark.lib import ssm_costs as S
from benchmark.lib import xplane as X
from benchmark.lib.configs import ROOT, load_json
from benchmark.lib.manifest import Manifest, check_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "serve-jamba-reason-r80"
NEW = ("ssm_scan_share", "ssm_scan_roofline", "ssm_prefill_scan_roofline",
       "serve_state_share")
RECORDED = os.path.join(HERE, "data", "jamba", "serve-jamba-reason.counters.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = load_json(os.path.join(ROOT, "benchmark", "configs",
                                "jamba2-3b-serve.json"))


@pytest.fixture(scope="module")
def readers():
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    found = dict((m["name"], (m, path))
                 for m, path in man.metrics_for(man.cell(CELL), "per_layer"))
    for n in NEW:
        entry = found[n][0]
        assert entry["workloads"] == [CELL] and entry["layer"] == "kernels"
        assert entry["moves"] == "tpot_p50_ms"
    # the kernels this model does not run are not asked of the cell
    for n in ("decode_attn_share", "decode_attn_roofline", "mla_decode_attn_share",
              "moe_expert_share", "latent_attn_roofline"):
        assert n not in found
    # the engine's, the router's and the device's serving metrics are
    assert {"decode_step_ms_mean", "decode_live_share", "decode_ahead_share",
            "router_overhead_ms_p50", "serve_device_idle",
            "serve_peak_hbm_gib"} <= set(found)
    assert CELL in next(m for m in man.doc["end_to_end"]
                        if m["name"] == "tpot_p50_ms")["workloads"]
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


def counters(prefill, decode, rows=268436096, state=596377600):
    return {"kft_serve_scan_tokens_total": {'kind="prefill"': prefill,
                                            'kind="decode"': decode},
            "kft_serve_cache_bytes": {'kind="rows"': rows, 'kind="state"': state}}


def test_bytes_from_shapes():
    assert S.ssm_layers(CONFIG) == 26 and S.d_inner(CONFIG) == 5120
    # a token's rows in one mixer: x bf16, Delta and y float32, B and C
    assert S.row_bytes(CONFIG) == 5120 * (2 + 4 + 4) + 2 * 16 * 4 == 51328
    assert S.state_bytes(CONFIG) == 16 * 5120 * 4 == 327680
    assert S.decode_step_bytes(CONFIG) == 26 * (2 * 327680 + 51328) == 18373888
    assert S.prefill_token_bytes(CONFIG) == 26 * 51328
    f32 = dict(CONFIG, program=dict(CONFIG["program"], dtype="float32"))
    assert S.row_bytes(f32) == 5120 * 12 + 128
    # what the file's own `bytes` block says of the cache at 64 slots
    held = CONFIG["bytes"]["cache_bytes"]
    assert held["state"] == 64 * 26 * (327680 + 3 * 5120 * 2)
    assert held["rows"] == 64 * (2 * 2 * 4096 * 128 * 2 + 2 * (4 + 1))
    every = dict(CONFIG, attn_layer_period=28, attn_layer_offset=27)
    assert S.ssm_layers(every) == 27


def test_readers_on_a_capture_made_by_hand(readers, as_run):
    """Two decode programs of 26 scan events of 50 us each and a prefill
    program of 26 events of 400 us.  The counters say 70 live slot-steps
    over the two decode steps and 600 real tokens in the prefill."""
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 0.012)):
        modules.append(["jit__decode(123)", t0, 0.010])
        for layer in range(26):
            ops.append([f"kft_selective_scan.{layer} [tpu_custom_call]",
                        t0 + 0.0003 * layer, 50e-6])
        ops.append([f"fusion.{step}", t0 + 0.0085, 0.001])
    modules.append(["jit__prefill(9)", 0.030, 0.020])
    for layer in range(26):
        ops.append([f"kft_selective_scan.{layer} [tpu_custom_call]",
                    0.030 + 0.0007 * layer, 400e-6])
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": modules}],
             "host": [], "lines": {}}
    ctx = as_run(trace, {"start": counters(10_000, 50_000),
                         "end": counters(10_600, 50_070)})
    got = {n: readers[n].read(ctx) for n in NEW}
    assert S.tokens_delta(ctx) == {"prefill": 600, "decode": 70}
    assert S.scan_events(trace, S.DECODE_PROGRAM) == (52, pytest.approx(52 * 50e-6))
    assert S.scan_events(trace, S.PREFILL_PROGRAM) == (26, pytest.approx(26 * 400e-6))
    busy = 52 * 50e-6 + 26 * 400e-6 + 2 * 1e-3
    assert got["ssm_scan_share"] == pytest.approx(
        100 * (52 * 50e-6 + 26 * 400e-6) / busy)
    least = 70 * 18373888 / 819e9
    assert got["ssm_scan_roofline"] == pytest.approx(100 * least / (52 * 50e-6))
    least = 600 * 26 * 51328 / 819e9
    assert got["ssm_prefill_scan_roofline"] == pytest.approx(
        100 * least / (26 * 400e-6))
    assert 0 < got["ssm_prefill_scan_roofline"] < got["ssm_scan_roofline"] < 100
    assert got["serve_state_share"] == pytest.approx(
        100 * 596377600 / (596377600 + 268436096))
    # no prefill inside the capture: that share has nothing to read
    ctx = as_run(trace, {"start": counters(10_000, 50_000),
                         "end": counters(10_000, 50_070)})
    assert readers["ssm_prefill_scan_roofline"].read(ctx) is None
    assert readers["ssm_scan_roofline"].read(ctx) == pytest.approx(
        got["ssm_scan_roofline"])


def test_readers_find_nothing_in_a_program_without_the_mixer(readers, as_run):
    """The parent of this PR cannot run the configuration at all; whatever
    program leaves a capture without the kernel and the counters gets a
    share of nothing, no number, no exception."""
    trace = {"devices": [{"name": "/device:TPU:0",
                          "ops": [["fusion.1", 0.0, 0.004],
                                  ["kft_decode_attn.1 [tpu_custom_call]", 0.004, 1e-4]],
                          "modules": [["jit__decode(1)", 0.0, 0.005]]}],
             "host": [], "lines": {}}
    rest = [n for n in NEW if n != "ssm_scan_share"]
    ctx = as_run(trace, None)
    assert readers["ssm_scan_share"].read(ctx) == 0.0
    assert [readers[n].read(ctx) for n in rest] == [None] * 3
    other = {"kft_serve_param_bytes": {'dtype="float32"': 1}}
    ctx = as_run(trace, {"start": other, "end": other})
    assert [readers[n].read(ctx) for n in rest] == [None] * 3
    assert all(readers[n].read(dict(ctx, trace=None)) is None for n in NEW)
    # a model of rows alone under this PR's program: the gauge says so, the
    # scan walked nothing and the kernel is not in the trace
    plain = {"kft_serve_scan_tokens_total": {'kind="prefill"': 0, 'kind="decode"': 0},
             "kft_serve_cache_bytes": {'kind="rows"': 1000, 'kind="state"': 0}}
    ctx = as_run(trace, {"start": plain, "end": plain})
    assert readers["serve_state_share"].read(ctx) == 0.0
    assert readers["ssm_scan_roofline"].read(ctx) is None
    assert readers["ssm_prefill_scan_roofline"].read(ctx) is None


def test_counter_readers_on_the_recorded_capture(readers, as_run):
    """The counters.json of a traced run of the cell on the chip (my chip
    run, PR 42; the data file says which).  The kernel's events were not
    kept with it, so the rooflines are reckoned again here from that run's
    kernel seconds and must give what its result line read."""
    empty = {"devices": [], "host": [], "lines": {}}
    doc = load_json(RECORDED)
    ctx = as_run(empty, doc["counters"])
    assert S.tokens_delta(ctx) == doc["tokens_delta"]
    # the program's gauge reads what the configuration's file reckoned
    assert S.cache_bytes(ctx) == {
        k: CONFIG["bytes"]["cache_bytes"][k] for k in ("rows", "state")}
    line = doc["result_line"]
    assert readers["serve_state_share"].read(ctx) == pytest.approx(
        line["serve_state_share"], abs=1e-6)
    assert readers["ssm_scan_roofline"].read(ctx) is None     # no events kept
    k = doc["kernel_events"]
    least = doc["tokens_delta"]["decode"] * S.decode_step_bytes(CONFIG) / 819e9
    assert 100 * least / k["decode_seconds"] == pytest.approx(
        line["ssm_scan_roofline"], rel=1e-6)
    least = doc["tokens_delta"]["prefill"] * S.prefill_token_bytes(CONFIG) / 819e9
    assert 100 * least / k["prefill_seconds"] == pytest.approx(
        line["ssm_prefill_scan_roofline"], rel=1e-6)
    assert 0 < line["ssm_prefill_scan_roofline"] < 100
    assert 0 < line["ssm_scan_roofline"] < 100
    assert 0 < line["ssm_scan_share"] < 100
