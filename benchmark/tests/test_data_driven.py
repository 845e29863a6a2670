"""A configuration, a traffic mix, a cell and a per-layer metric added as new
files and a new `workloads` entry alone are found and run: rehearsals 1 and 2
of the on-chip-measurement guide, on the CPU at a tiny size.  Nothing under
benchmark/lib or run.py knows the names used here."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib.configs import ROOT

RUN = os.path.join(ROOT, "benchmark", "run.py")
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=128)


def _write(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """A copy of the benchmark's data files, plus files that only add."""
    root = tmp_path_factory.mktemp("bench")
    dst = root / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics", "e2e_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), dst / sub)
    before = {p: open(p, "rb").read() for p in dst.rglob("*") if p.is_file()}
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    train = json.load(open(dst / "configs" / "olmo-1b-train-1chip.json"))
    train.update(TINY, name="added-train")
    train["program"]["dtype"] = "float32"
    train["deployment"].update(chips=4, mesh={"dp": 2, "fsdp": 2},
                               sequences_per_chip=1)
    _write(dst / "configs" / "added-train.json", train)
    serve = json.load(open(dst / "configs" / "olmo-1b-serve.json"))
    serve.update(TINY, name="added-serve")
    serve["deployment"]["slots"] = 4
    _write(dst / "configs" / "added-serve.json", serve)
    _write(dst / "traffic" / "added-seq64.json", {
        "kind": "train", "seq_len": 64, "pool_batches": 2, "trace_steps": 2,
        "trace_after_steps": 2, "check_positions": 32})
    _write(dst / "traffic" / "added-bursty.json", {
        "kind": "open", "rate_per_s": 8.0, "bursts": {"size_min": 2, "size_max": 4},
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 4, "max": 60},
        "answer_len": {"dist": "uniform", "min": 4, "max": 8},
        "drain_s": 30, "trace_seconds": 1.0, "trace_at_share": 0.3})
    _write(dst / "layer_metrics" / "added_init_s.json",
           {"kind": "value", "key": "setup_init_s"})
    _write(dst / "layer_metrics" / "added_copy_share.json", {
        "kind": "trace", "bucket": {"match": "^copy", "line": "XLA Ops"},
        "reduce": "share_of_busy"})
    with open(dst / "layer_metrics" / "added_steps_per_s.py", "w") as f:
        f.write("def read(ctx):\n"
                "    v = ctx['values']\n"
                "    return v['steps'] / v['window_s']\n")
    for name, file in (("added-train", "added-train"), ("added-serve", "added-serve")):
        doc["configs"].append({"name": name, "source": "none: a test",
                               "file": f"benchmark/configs/{file}.json",
                               "reduced": [], "why": "a test"})
    doc["workloads"] += [
        {"name": "added-cell", "config": "added-train", "traffic": "added-seq64",
         "chips": 4, "why": "a test"},
        {"name": "added-chat", "config": "added-serve", "traffic": "added-bursty",
         "chips": 1, "why": "a test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:  # a new cell lists itself on the metrics it reports
            old = " ".join(m["workloads"])
            if "train" in old:
                m["workloads"] = m["workloads"] + ["added-cell"]
            elif "chat" in old:  # what the open-loop cell reports
                m["workloads"] = m["workloads"] + ["added-chat"]
    for name, unit in (("added_init_s", "s"), ("added_copy_share", "%"),
                       ("added_steps_per_s", "steps/s")):
        doc["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": "host_clock",
            "layer": "trainer", "moves": "train_tokens_per_s_chip",
            "workloads": ["added-cell"]})
    _write(root / "BENCHMARK.json", doc)
    for p, content in before.items():  # nothing that was there was edited
        assert open(p, "rb").read() == content
    return str(root)


def _run(bench_root, workload, trace, seconds, env_extra, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "KFT_BENCH_REHEARSE"}
    env.update(env_extra, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, RUN, "--bench-root", bench_root, "--workload", workload,
         "--seed", "3", "--seconds", str(seconds), "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=timeout)


def test_added_training_cell_runs_on_four_virtual_devices(bench_root):
    p = _run(bench_root, "added-cell", 1, 2, {
        "KFT_BENCH_REHEARSE": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device",
                        "breakdown"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 2
    assert out["device"]["count"] == 4 and out["rehearsal"] == "cpu"
    m = out["metrics"]
    assert m["added_init_s"]["value"] > 0 and m["added_init_s"]["unit"] == "s"
    assert m["added_steps_per_s"]["value"] > 0
    assert m["compiles_in_window"]["value"] == 0
    # a reader that finds nothing to read (no device plane on the CPU)
    # returns nothing, and the metric is left out of the line
    assert "added_copy_share" not in m and "flash_share" not in m
    assert "train_tokens_per_s_chip" not in m  # --trace 1 reports per-layer


def test_added_serving_cell_runs_through_router_and_worker(bench_root):
    p = _run(bench_root, "added-chat", 0, 4, {"KFT_BENCH_REHEARSE": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 5
    assert set(out["metrics"]) == {"tpot_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert "breakdown" not in out


def test_no_accelerator_is_a_failure_not_a_cpu_run(bench_root):
    p = _run(bench_root, "added-cell", 0, 1, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "needs a TPU" in p.stderr


def test_unknown_cell_is_refused(bench_root):
    p = _run(bench_root, "no-such-cell", 0, 1, {"KFT_BENCH_REHEARSE": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
