"""Benchmark harness tests (reference bench-as-test, SURVEY.md §4)."""
import re

import numpy as np
import pytest

from kungfu_tpu.benchmarks import (
    METHODS,
    bench_all_reduce,
    bench_p2p,
    run_sweep,
)
from kungfu_tpu.session import Session


@pytest.fixture(scope="module")
def session():
    return Session()


def test_bench_all_reduce_slp(session):
    r = bench_all_reduce(session, "slp-mnist", "auto", steps=2, warmup=1)
    assert r.payload_bytes == (784 * 10 + 10) * 4
    assert r.seconds_per_step > 0
    assert r.data_gibps > 0
    line = r.line(session.size)
    assert re.match(r"RESULT: model=slp-mnist method=auto .* GiB/s", line)


@pytest.mark.parametrize("method", sorted(METHODS))
def test_bench_methods(session, method):
    r = bench_all_reduce(session, "slp-mnist", method, steps=1, warmup=1)
    assert r.data_gibps > 0


def test_bench_unfused(session):
    r = bench_all_reduce(session, "slp-mnist", "auto", fuse=False, steps=1, warmup=1)
    assert r.payload_bytes == (784 * 10 + 10) * 4


def test_busbw_scaling():
    from kungfu_tpu.benchmarks import BenchResult

    r = BenchResult("m", "auto", True, 1, 1 << 30, 1.0)
    assert r.data_gibps == pytest.approx(1.0)
    assert r.busbw_gibps(8) == pytest.approx(2 * 7 / 8)
    assert r.busbw_gibps(1) == pytest.approx(1.0)


def test_run_sweep_prints(session, capsys):
    run_sweep(session, models=["slp-mnist"], methods=["auto", "psum"], steps=1, warmup=1)
    out = capsys.readouterr().out
    assert out.count("RESULT:") == 2


def test_bench_p2p():
    rate = bench_p2p(store_size=1 << 12, steps=5)
    assert rate > 0


def test_unknown_method(session):
    with pytest.raises(ValueError):
        bench_all_reduce(session, "slp-mnist", "nccl")


def test_cli_main(capsys):
    from kungfu_tpu.benchmarks.__main__ import main

    rc = main(["--model", "slp-mnist", "--method", "auto", "--steps", "1", "--warmup", "1"])
    assert rc == 0
    assert "RESULT:" in capsys.readouterr().out

    rc = main(["--bench", "p2p", "--p2p-size", "4096", "--steps", "5"])
    assert rc == 0
    assert "bench=p2p" in capsys.readouterr().out


def test_baseline_matrix_merge(tmp_path):
    """_merge_into keys records by config name and survives a corrupt file."""
    from kungfu_tpu.benchmarks import baseline_matrix as bm

    out = str(tmp_path / "m.json")
    bm._merge_into(out, {"config": "a", "value": 1})
    bm._merge_into(out, {"config": "b", "value": 2})
    bm._merge_into(out, {"config": "a", "value": 3})  # overwrite, not append
    import json

    with open(out) as f:
        recs = {r["config"]: r for r in json.load(f)["results"]}
    assert recs["a"]["value"] == 3 and recs["b"]["value"] == 2

    # writes are atomic (temp + os.replace), so our own kills can never
    # truncate the file; an EXTERNALLY corrupted file degrades to fresh
    with open(out, "w") as f:
        f.write("{corrupt")
    bm._merge_into(out, {"config": "c", "value": 4})
    with open(out) as f:
        assert [r["config"] for r in json.load(f)["results"]] == ["c"]
    assert not [p for p in tmp_path.iterdir() if p.suffix == ".tmp"]


def test_gpt_decode_config_tiny():
    """Config 12's measurement mechanics end-to-end on CPU: two-point
    marginal-cost timing, per-batch rows, best-row headline.  A noisy CPU
    may yield the documented degenerate-timing row; what must NOT appear
    is an exception-shaped error (mechanics breakage)."""
    from kungfu_tpu.benchmarks.baseline_matrix import config_gpt_decode

    r = config_gpt_decode(new_tokens=32, tiny=True)
    rows = r.get("rows", [])
    for row in rows:
        if "error" in row:  # only the documented degenerate case is OK
            assert "marginal decode time" in row["error"], row
    ok = [row for row in rows if "tokens_per_sec" in row]
    if ok:  # the normal outcome
        assert "error" not in r and r["value"] > 0
        assert all(row["tokens_per_sec"] > 0 for row in ok)
        assert all("fixed_overhead_ms" in row for row in ok)
