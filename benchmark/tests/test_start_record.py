"""The `setup_*` readers (benchmark/lib/start_record.py) and `decode_live_share`:
on start records recorded from a launched trainer and a serving fleet
(tests/data/start_records/, written by kungfu_tpu/monitor/boot.py on the CPU at
a tiny size: their seconds are no device numbers), on a stale record and on a
program that writes none, through a rehearsed run of each kind of cell, and the
manifest's limits with the eight entries."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import metrics as M
from benchmark.lib import start_record as S
from benchmark.lib.configs import ROOT
from benchmark.lib.manifest import Manifest, check_manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "start_records")
PHASES = ("setup_launch_s", "setup_backend_s", "setup_weights_s", "setup_first_use_s")
SEVEN = PHASES + ("setup_trace_lower_s", "setup_cache_load_s", "setup_unnamed_s")
#: recorded: (the pid that spawned the launcher, the job's start)
JOBS = {"train": (28164, 1790807411.7948706), "serve": (28171, 1790807380.2148712)}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture()
def readers(man):
    cell = man.cell("train-olmo1b-1chip")
    found = {m["name"]: M.Reader(m["name"], path)
             for m, path in man.metrics_for(cell, "per_layer")}
    return {n: found[n] for n in SEVEN}


def as_run(monkeypatch, kind, setup_s):
    """The context of a run whose records are the recorded ones."""
    run = S.find([os.path.join(DATA, kind)], *JOBS[kind])
    assert run is not None
    monkeypatch.setattr(S, "of_run", lambda ctx: run)
    return {"values": {"setup_s": setup_s}, "cell": {"name": kind}, "trace": None}


def test_the_eight_entries_meet_the_contract(man):
    assert check_manifest(man.doc) == []
    cells = [w["name"] for w in man.doc["workloads"]]
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    assert [m["name"] for m in man.doc["per_layer"][-8:]] == list(
        PHASES[:3]) + ["setup_first_use_s", "setup_trace_lower_s",
                       "setup_cache_load_s", "setup_unnamed_s", "decode_live_share"]
    for n in SEVEN:
        m = by_name[n]
        assert (m["moves"], m["unit"], m["better"]) == ("setup_s", "s", "lower")
        assert m["workloads"] == cells  # every cell, said outright
        counter = n in ("setup_trace_lower_s", "setup_cache_load_s")
        assert m["source"] == ("program_counter" if counter else "program_span")
        compile_cache = counter or n == "setup_first_use_s"
        assert m["layer"] == ("compile cache" if compile_cache
                              else "launcher and worker runtime")
    live = by_name["decode_live_share"]
    assert live["workloads"] == [c for c in cells if c.startswith("serve-")]
    assert (live["moves"], live["unit"], live["better"], live["source"], live["layer"]) == (
        "tpot_p50_ms", "%", "higher", "program_counter", "serving engine")
    layers = {m["layer"] for m in man.doc["per_layer"][:-8]}
    assert {m["layer"] for m in man.doc["per_layer"][-8:]} <= layers  # no new layer


def test_readers_on_a_recorded_trainer(monkeypatch, readers):
    ctx = as_run(monkeypatch, "train", setup_s=8.5)
    got = {n: r.read(ctx) for n, r in readers.items()}
    # job start -> spawn (3.01) -> the package's first statement (+0.0271)
    assert got["setup_launch_s"] == pytest.approx(3.01 + 0.0271, abs=2e-4)
    # -> boot:backend's end: the package, the script's own imports, its devices
    assert got["setup_backend_s"] == pytest.approx(6.822 - 3.0371, abs=2e-4)
    assert got["setup_weights_s"] == pytest.approx(0.258, abs=2e-4)       # train:init
    assert got["setup_first_use_s"] == pytest.approx(0.1082 + 0.0072, abs=2e-4)
    assert got["setup_trace_lower_s"] == pytest.approx((85.2277 + 167.8643) / 1e3, abs=1e-4)
    assert got["setup_cache_load_s"] == pytest.approx(0.05656, abs=1e-4)
    assert got["setup_unnamed_s"] == pytest.approx(
        8.5 - sum(got[n] for n in PHASES), abs=1e-9)
    assert got["setup_unnamed_s"] > 0


def test_readers_on_a_recorded_serving_worker(monkeypatch, readers):
    ctx = as_run(monkeypatch, "serve", setup_s=22.0)
    got = {n: r.read(ctx) for n, r in readers.items()}
    assert got["setup_launch_s"] == pytest.approx(3.68 + 0.0228, abs=2e-4)
    assert got["setup_backend_s"] == pytest.approx(7.3235 + 0.0307 - 3.7028, abs=2e-4)
    # boot:weights + boot:resident + boot:engine
    assert got["setup_weights_s"] == pytest.approx(3.5248 + 0.0001 + 0.3993, abs=3e-4)
    # the two first calls lie in the warm-up requests, after boot complete
    assert got["setup_first_use_s"] == pytest.approx(0.4586 + 0.503, abs=2e-4)
    assert got["setup_cache_load_s"] == 0.0  # an empty cache: nothing loaded
    assert got["setup_trace_lower_s"] > 0.5
    assert got["setup_unnamed_s"] == pytest.approx(
        22.0 - sum(got[n] for n in PHASES), abs=1e-9)


@pytest.mark.parametrize("kind", sorted(JOBS))
def test_the_four_phase_stretches_never_overlap(kind):
    worker = S.find([os.path.join(DATA, kind)], *JOBS[kind])["worker"]
    parts = S.stretches(worker)
    flat = sorted(iv for ivs in parts.values() for iv in ivs)
    assert len(flat) >= 4 and flat[0][0] == 0.0
    for (_, e), (s, _) in zip(flat, flat[1:]):
        assert e <= s + 1e-9
    assert S.measure(S.union(flat)) == pytest.approx(sum(S.measure(v) for v in parts.values()))


def test_a_first_call_inside_an_earlier_stretch_counts_once():
    worker = {"phases": [
        {"name": "boot:interpreter", "t": 1.0, "s": 0.5},
        {"name": "boot:backend", "t": 2.0, "s": 3.0},
        {"name": "train:init", "t": 5.0, "s": 4.0},
        {"name": "boot:first_call", "t": 6.0, "s": 1.0},    # inside the init
        {"name": "boot:first_call", "t": 8.5, "s": 1.5},    # half inside it
        {"name": "train:lower", "t": 9.5, "s": 1.0},        # overlaps the call
    ]}
    parts = S.stretches(worker)
    assert parts["launch"] == [[0.0, 1.5]] and parts["backend"] == [[1.5, 5.0]]
    assert parts["weights"] == [[5.0, 9.0]]
    assert parts["first_use"] == [[9.0, 10.5]]


@pytest.mark.parametrize("parent_pid,not_before,found", [
    (28164, 1790807411.79, True),      # this run
    (28164, 1790807411.79 + 0.9, True),  # the clocks' slack
    (28164, 1790807500.0, False),      # an earlier run's records, found again
    (99999, 1790807411.79, False),     # some other process's job
])
def test_only_this_runs_record_is_read(parent_pid, not_before, found):
    run = S.find([os.path.join(DATA, "train")], parent_pid, not_before)
    assert (run is not None) is found
    if found:
        assert run["worker"]["role"] == "trainer"
        assert run["worker"]["ppid"] == run["launcher"]["pid"]
        assert run["worker"]["job_start_wall"] == run["launcher"]["job_start_wall"]


def test_the_run_directory_is_read_before_the_cache(tmp_path):
    first, second = tmp_path / "run", tmp_path / "cache"
    shutil.copytree(os.path.join(DATA, "serve"), first)
    shutil.copytree(os.path.join(DATA, "train"), second)
    assert S.find([str(first), str(second)], *JOBS["serve"])["worker"]["role"] == "serve-worker"
    assert S.find([str(first), str(second)], *JOBS["train"])["worker"]["role"] == "trainer"
    assert S.find([str(tmp_path / "nowhere")], *JOBS["train"]) is None


def test_a_worker_whose_boot_never_completed_is_no_record(tmp_path):
    for name in os.listdir(os.path.join(DATA, "train")):
        rec = json.load(open(os.path.join(DATA, "train", name)))
        if rec["role"] == "trainer":
            rec["boot_complete"] = None
        json.dump(rec, open(tmp_path / name, "w"))
    (tmp_path / "start-trainer-torn.json").write_text('{"role": "trai')
    assert S.find([str(tmp_path)], *JOBS["train"]) is None


def test_on_the_parent_every_reader_returns_none(monkeypatch, tmp_path, readers):
    """A program from before the records (the parent commit, this PR's
    benchmark files laid over it) writes none: nothing is read, nothing
    raises, and the seven metrics are left out of the line."""
    monkeypatch.setattr(S, "ROOT", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(S, "_memo", {})
    ctx = {"values": {"setup_s": 31.0}, "cell": {"name": "train-olmo1b-1chip"},
           "trace": None}
    assert S.record_dirs(ctx) == [
        str(tmp_path / ".bench_out" / "train-olmo1b-1chip"),
        str(tmp_path / "cache" / "starts")]
    assert {n: r.read(ctx) for n, r in readers.items()} == dict.fromkeys(SEVEN)
    # and a stale pair in the cache's starts/ (an earlier run of a later
    # commit, the directory shared) is not this run's either
    shutil.copytree(os.path.join(DATA, "train"), tmp_path / "cache" / "starts")
    monkeypatch.setattr(S, "_memo", {})
    assert {n: r.read(ctx) for n, r in readers.items()} == dict.fromkeys(SEVEN)


def test_decode_live_share_from_a_captures_counters(man, monkeypatch, tmp_path):
    from benchmark.lib import moe_costs

    cell = man.cell("serve-olmo1b-chat-r80")
    reader = dict((m["name"], M.Reader(m["name"], p))
                  for m, p in man.metrics_for(cell, "per_layer"))["decode_live_share"]
    monkeypatch.setattr(moe_costs, "ROOT", str(tmp_path))
    ctx = {"cell": cell, "trace": {"devices": [1]}, "values": {}}
    assert reader.read(ctx) is None  # a capture with no counters.json
    cap = tmp_path / ".bench_out" / cell["name"] / "profile-1"
    cap.mkdir(parents=True)
    family = "kft_serve_decode_rows_total"
    (cap / "counters.json").write_text(json.dumps({
        "start": {family: {'kind="live"': 1000, 'kind="free"': 600}},
        "end": {family: {'kind="live"': 1600, 'kind="free"': 800}}}))
    assert reader.read(ctx) == pytest.approx(75.0)  # 600 live of 800 slot-steps
    (cap / "counters.json").write_text(json.dumps({"start": {}, "end": {"other": {}}}))
    assert reader.read(ctx) is None  # a program from before the counter
    assert reader.read(dict(ctx, trace=None)) is None  # an untraced run


# -- a rehearsed run of each kind of cell ----------------------------------------------

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=128)


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """The benchmark's data files and two tiny cells that list the metrics."""
    root = tmp_path_factory.mktemp("bench")
    dst = root / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics", "e2e_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), dst / sub)
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    train = json.load(open(dst / "configs" / "olmo-1b-train-1chip.json"))
    train.update(TINY, name="tiny-train")
    train["program"]["dtype"] = "float32"
    train["deployment"].update(chips=1, mesh={"dp": 1}, sequences_per_chip=2)
    json.dump(train, open(dst / "configs" / "tiny-train.json", "w"))
    serve = json.load(open(dst / "configs" / "olmo-1b-serve.json"))
    serve.update(TINY, name="tiny-serve")
    serve["deployment"]["slots"] = 4
    json.dump(serve, open(dst / "configs" / "tiny-serve.json", "w"))
    json.dump({"kind": "train", "seq_len": 64, "pool_batches": 2, "trace_steps": 2,
               "trace_after_steps": 2, "check_positions": 32},
              open(dst / "traffic" / "tiny-seq64.json", "w"))
    json.dump({"kind": "open", "rate_per_s": 6.0,
               "prompt_len": {"dist": "uniform", "min": 4, "max": 40},
               "answer_len": {"dist": "uniform", "min": 4, "max": 8},
               "drain_s": 30, "trace_seconds": 1.0, "trace_at_share": 0.3},
              open(dst / "traffic" / "tiny-open.json", "w"))
    for name in ("tiny-train", "tiny-serve"):
        doc["configs"].append({"name": name, "source": "none: a test",
                               "file": f"benchmark/configs/{name}.json",
                               "reduced": [], "why": "a test"})
    doc["workloads"] += [
        {"name": "tiny-train-cell", "config": "tiny-train", "traffic": "tiny-seq64",
         "chips": 1, "why": "a test"},
        {"name": "tiny-serve-cell", "config": "tiny-serve", "traffic": "tiny-open",
         "chips": 1, "why": "a test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            if "train-olmo1b-1chip" in m["workloads"]:
                m["workloads"] = m["workloads"] + ["tiny-train-cell"]
            if "serve-olmo1b-chat-r80" in m["workloads"]:
                m["workloads"] = m["workloads"] + ["tiny-serve-cell"]
    json.dump(doc, open(root / "BENCHMARK.json", "w"))
    return str(root)


def rehearse(bench_root, workload, seconds, cache):
    env = dict(os.environ, KFT_BENCH_REHEARSE="cpu", JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    for k in ("KFT_JOB_START", "KFT_PROC_START", "KFT_TRACE_DUMP_DIR"):
        env.pop(k, None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--bench-root",
         bench_root, "--workload", workload, "--seed", "2147483777", "--seconds",
         str(seconds), "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    values = json.loads(next(line.split("values: ", 1)[1]
                             for line in p.stderr.splitlines() if "] values: " in line))
    return json.loads(p.stdout.strip().splitlines()[-1]), values


def check_seven(out, values):
    m = {n: out["metrics"][n]["value"] for n in SEVEN}
    assert all(out["metrics"][n]["unit"] == "s" for n in SEVEN)
    assert all(m[n] > 0 for n in PHASES) and m["setup_trace_lower_s"] > 0
    assert m["setup_cache_load_s"] >= 0
    # the four stretches and what they leave are the benchmark's setup_s
    assert sum(m[n] for n in PHASES) + m["setup_unnamed_s"] == pytest.approx(
        values["setup_s"], abs=1e-6)
    assert 0 <= m["setup_unnamed_s"] < values["setup_s"]
    return m


def test_a_rehearsed_training_cell_prints_the_seven(bench_root, tmp_path):
    out, values = rehearse(bench_root, "tiny-train-cell", 2, str(tmp_path / "cache"))
    assert out["correct"] is True and out["rehearsal"] == "cpu"
    m = check_seven(out, values)
    # against the worker's own clocks of the same run (train_worker.py)
    assert m["setup_launch_s"] == pytest.approx(values["setup_launch_to_worker_s"], abs=0.5)
    assert m["setup_backend_s"] == pytest.approx(values["setup_worker_to_device_s"], abs=0.5)
    assert m["setup_weights_s"] == pytest.approx(values["setup_init_s"], abs=0.5)
    assert m["setup_first_use_s"] <= (values["setup_lower_s"]
                                      + values["setup_first_steps_s"] + 0.05)
    assert "decode_live_share" not in out["metrics"]
    assert os.listdir(tmp_path / "cache" / "starts")  # a trainer's go beside the cache


def test_a_rehearsed_serving_cell_prints_the_seven_and_the_live_share(bench_root, tmp_path):
    out, values = rehearse(bench_root, "tiny-serve-cell", 4, str(tmp_path / "cache"))
    assert out["correct"] is True
    m = check_seven(out, values)
    # job start -> READY, told by the worker; the benchmark times the same from outside
    assert m["setup_launch_s"] + m["setup_backend_s"] + m["setup_weights_s"] == pytest.approx(
        values["worker_boot_s"], abs=1.0)
    assert 0 < out["metrics"]["decode_live_share"]["value"] <= 100
    assert not os.path.exists(tmp_path / "cache" / "starts")  # the run directory took them
