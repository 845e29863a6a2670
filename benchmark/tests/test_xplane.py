"""The trace reduction: on intervals made by hand, on a trace recorded on
the chip (tests/data/*.events.json.gz: stage 1's output for a few steps,
cut down), checked against a brute-force rasterisation, and stage 1 itself
on a trace captured here."""
import glob
import os
import re

import numpy as np
import pytest

from benchmark.lib import xplane as X

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_subtract_leaves_by_hand():
    assert X.union([[0, 2], [1, 3], [5, 6], [6, 7], [9, 9]]) == [[0, 3], [5, 7]]
    assert X.length(X.union([[0, 2], [1, 3], [5, 6]])) == 4
    a, b = X.union([[0, 10], [20, 30]]), X.union([[2, 4], [8, 22], [29, 40]])
    assert X.subtract(a, b) == [[0, 2], [4, 8], [22, 29]]
    ev = [["while", 0.0, 10.0], ["fusion.1", 1.0, 2.0], ["fusion.2", 4.0, 5.0],
          ["copy.3", 12.0, 1.0]]
    assert [n for n, _, _ in X.leaves(ev)] == ["fusion.1", "fusion.2", "copy.3"]
    assert X.base_name("fusion.123") == "fusion" and X.base_name("copy") == "copy"


def _hand_trace():
    ops = [["fusion.1", 0.0, 1.0], ["all-gather-start.1", 1.0, 0.1],
           ["fusion.2", 1.1, 1.0], ["all-gather-done.1", 2.1, 0.4],
           ["flash_fwd.1", 2.5, 0.5], ["all-reduce.7", 4.0, 1.0]]
    host = [["bench:step_dispatch", 2.9, 0.5], ["$some.py:1 f", 3.0, 0.2],
            ["bench:loss_fetch", 3.5, 0.6]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": [
        ["jit_step(1)", 0.0, 5.0]]}], "host": host, "lines": {}}


def test_reduction_by_hand():
    coll = "all-gather|all-reduce"
    out = X.reduce_trace(_hand_trace(), {"buckets": {
        "coll": {"match": coll, "line": "XLA Ops", "async_pairs": True},
        "flash": {"match": "flash", "line": "XLA Ops"},
        "step": {"match": "jit_step", "line": "XLA Modules"}}})
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(5.0)
    assert out["busy_s"] == pytest.approx(4.0)  # idle only in [3.0, 4.0]
    b = out["buckets"]["coll"]
    # in flight from the start op's begin to the done op's end, plus the
    # synchronous all-reduce: [1.0, 2.5] and [4.0, 5.0]
    assert b["seconds"] == pytest.approx(2.5)
    # fusion.2 runs during [1.1, 2.1] of it: the rest is exposed
    assert b["exposed_s"] == pytest.approx(1.5)
    assert b["op_seconds"] == pytest.approx(1.5) and b["events"] == 3
    assert out["buckets"]["flash"]["op_seconds"] == pytest.approx(0.5)
    assert out["buckets"]["step"]["op_seconds"] == pytest.approx(5.0)
    assert out["device_ops"][0] == ["fusion", pytest.approx(2.0)]
    # the one gap, [3.0, 4.0], has its middle inside bench:loss_fetch; the
    # benchmark's own span wins over a python frame
    assert out["idle_gaps"] == [["bench:loss_fetch", pytest.approx(1.0)]]
    assert out["modules"]["jit_step"]["count"] == 1


def _raster(intervals, lo, hi, n):
    grid = np.zeros(n, bool)
    for s, e in intervals:
        a = int(np.floor((s - lo) / (hi - lo) * n))
        b = int(np.ceil((e - lo) / (hi - lo) * n))
        grid[max(a, 0):min(b, n)] = True
    return grid


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.events.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_reduction_on_a_recorded_trace_against_brute_force(path):
    trace = X.read_trace(path)
    assert trace["devices"], "a trace recorded on the chip has device planes"
    # a bucket of leaf events that overlap little with the rest, and one of
    # containers (the attention backward's `while` loops)
    spec = {"buckets": {
        "moves": {"match": "^(copy|slice|all-gather|all-reduce|reduce-scatter)",
                  "line": "XLA Ops"},
        "loops": {"match": "^while", "line": "XLA Ops"},
        "kernels": {"match": r"\[tpu_custom_call\]", "line": "XLA Ops"}}}
    out = X.reduce_trace(trace, spec)
    assert out["devices"] == len(trace["devices"])
    assert 0 < out["busy_s"] <= out["window_s"]
    dev = trace["devices"][0]
    first = X.reduce_trace({"devices": [dev], "host": trace["host"]}, spec)
    ops = X.leaves(dev["ops"])
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    n = 2_000_000
    cell = (hi - lo) / n
    # the raster overcounts by at most a cell at each edge of each event
    slack = 2 * cell * len(dev["ops"])
    busy = _raster([[s, s + d] for _, s, d in ops], lo, hi, n)
    assert first["busy_s"] == pytest.approx(busy.sum() * cell, abs=slack)
    assert first["window_s"] == pytest.approx(hi - lo)
    for name, b in spec["buckets"].items():
        rx = re.compile(b["match"])
        mine = _raster([[s, s + d] for n_, s, d in dev["ops"] if rx.search(n_)],
                       lo, hi, n)
        other = _raster([[s, s + d] for n_, s, d in ops if not rx.search(n_)],
                        lo, hi, n)
        got = first["buckets"][name]
        assert got["events"] == sum(1 for n_, _, _ in dev["ops"] if rx.search(n_))
        assert got["events"] > 0, name
        assert got["seconds"] == pytest.approx(mine.sum() * cell, abs=slack)
        assert got["exposed_s"] == pytest.approx((mine & ~other).sum() * cell,
                                                 abs=slack)
        assert got["op_seconds"] == pytest.approx(
            sum(d for n_, _, d in dev["ops"] if rx.search(n_)))
    # a loop's time is its body's: nearly none of it is exposed
    assert first["buckets"]["loops"]["exposed_s"] < 0.01 * first["buckets"]["loops"]["seconds"]
    # every named gap is idle time: together no more than window - busy
    gaps = sum(v for _, v in first["idle_gaps"])
    assert 0 < gaps <= first["window_s"] - first["busy_s"] + 1e-9
    assert any(k.startswith("bench:") for k, _ in first["idle_gaps"])
    assert len(first["device_ops"]) <= 10 and len(first["idle_gaps"]) <= 10
    assert first["device_ops"][0][1] == max(v for _, v in first["device_ops"])
    assert not any(k.startswith("while") for k, _ in first["device_ops"])


def test_recorded_traces_are_there():
    assert RECORDED, "benchmark/tests/data holds the recorded trace"


def test_stage_one_reads_what_the_profiler_writes(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:probe"):
        jax.block_until_ready(jax.jit(lambda x: x @ x)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()
    trace = X.load_trace(str(tmp_path))
    assert any(n == "bench:probe" for n, _, _ in trace["host"])
    assert trace["devices"] == []  # the CPU has no device plane
    out = X.reduce_trace(trace, {"buckets": {}})
    assert out["devices"] == 0 and out["busy_s"] == 0.0
