"""The span reductions (benchmark/lib/host_spans.py) and the readers built on
them: on six events made by hand with known answers, on a serving capture
recorded on the chip (tests/data/host_spans/serve-chat.events.json.gz: stage
1's output for 3 s of `serve-olmo1b-chat-r80`, cut down to the op line's
leaves, the program's spans and the runtime's longer host events), and on the
two training traces recorded before the program had spans, where every new
reader finds nothing and says so."""
import glob
import os
import shutil

import pytest

from benchmark.lib import host_spans as H
from benchmark.lib import metrics as M
from benchmark.lib import xplane as X
from benchmark.lib.manifest import Manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SERVE = os.path.join(DATA, "host_spans", "serve-chat.events.json.gz")
NEW = ("engine_dispatch_ms_step", "engine_fetch_ms_step", "engine_sample_ms_step",
       "observatory_ms_step", "serve_idle_unloaded", "serve_idle_in_fetch",
       "serve_idle_in_host", "serve_idle_unnamed", "trainer_host_ms_step")
IDLE_PARTS = ("serve_idle_unloaded", "serve_idle_in_fetch", "serve_idle_in_host",
              "serve_idle_unnamed")


def _hand_trace():
    """Two decode steps by hand.  The device runs [0, 4], [6, 9] and
    [13, 14]: idle [4, 6] and [9, 13], 6 of a window of 14."""
    ops = [["fusion.1", 0.0, 4.0], ["fusion.2", 6.0, 3.0], ["copy.3", 13.0, 1.0]]
    host = [
        ["serve:step", 0.5, 6.0],             # [0.5, 6.5]
        ["serve:decode", 1.0, 4.0],           # [1, 5]
        ["serve:decode.dispatch", 1.0, 1.0],  # [1, 2]
        ["programs:digest", 1.2, 0.5],        # [1.2, 1.7]
        ["serve:decode.fetch", 2.0, 3.0],     # [2, 5]: idle [4, 5] under it
        ["serve:decode.sample", 5.0, 1.0],    # [5, 6]: idle [5, 6] under it
        ["$engine.py:1 step", 0.5, 6.0],      # a python frame: not a program span
        ["serve:step", 6.5, 3.5],             # [6.5, 10]: idle [9, 10] under it alone
        ["serve:decode", 7.0, 2.0],           # [7, 9]
        ["serve:decode.dispatch", 7.0, 0.5],
        ["serve:decode.fetch", 7.5, 1.5],     # ends as the device does: no idle
        ["serve:idle", 10.5, 2.0],            # [10.5, 12.5]: all of it idle
    ]                                         # [10, 10.5] and [12.5, 13]: no span
    return {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
            "host": host, "lines": {}}


def test_the_two_reductions_by_hand():
    red = H.reduce_spans(_hand_trace())
    assert red["spans"]["serve:decode"] == {"count": 2, "seconds": pytest.approx(6.0)}
    assert "$engine.py:1 step" not in red["spans"]
    # mean of a named span per occurrence of a counting span
    assert H.ms_per(red, ["serve:decode.dispatch"], per="serve:decode") == \
        pytest.approx(1e3 * 1.5 / 2)
    assert H.ms_per(red, ["serve:decode.fetch"], per="serve:decode") == \
        pytest.approx(1e3 * 4.5 / 2)
    assert H.ms_per(red, ["serve:decode.sample", "programs:digest"],
                    per="serve:decode") == pytest.approx(1e3 * 1.5 / 2)
    assert H.ms_per(red, ["serve:verify.fetch"], per="serve:decode") == 0.0
    assert H.ms_per(red, ["serve:decode.fetch"], per="serve:verify") is None
    # device idle under the innermost span open at each instant
    idle = red["idle"]
    assert idle["window_s"] == pytest.approx(14.0)
    assert idle["idle_s"] == pytest.approx(6.0)
    under = {k: v for k, v in idle["under"].items() if v > 1e-12}
    assert under == {"serve:decode.fetch": pytest.approx(1.0),
                     "serve:decode.sample": pytest.approx(1.0),
                     "serve:step": pytest.approx(1.0),
                     "serve:idle": pytest.approx(2.0),
                     None: pytest.approx(1.0)}
    parts = {k: H.idle_seconds(red, pick) for k, pick in H.SERVE_IDLE.items()}
    assert parts == {"unloaded": pytest.approx(2.0), "in_fetch": pytest.approx(1.0),
                     "in_host": pytest.approx(2.0), "unnamed": pytest.approx(1.0)}
    assert sum(parts.values()) == pytest.approx(idle["idle_s"])


def test_innermost_is_the_span_that_started_last():
    spans = [["a", 0.0, 10.0], ["b", 2.0, 2.0], ["c", 6.0, 6.0], ["d", 20.0, 1.0]]
    assert H.innermost(spans) == [
        [0.0, 2.0, "a"], [2.0, 4.0, "b"], [4.0, 6.0, "a"],
        [6.0, 12.0, "c"],  # c outlives a, which started before it: c to its end
        [20.0, 21.0, "d"]]
    assert H.innermost([]) == []


def test_no_device_plane_or_no_span_is_nothing_to_read():
    host_only = dict(_hand_trace(), devices=[])
    red = H.reduce_spans(host_only)
    assert red["idle"] is None and red["spans"]["serve:idle"]["count"] == 1
    assert H.idle_seconds(red, H.SERVE_IDLE["unloaded"]) is None
    assert H.ms_per(red, ["serve:decode.fetch"], per="serve:decode") is not None
    no_spans = dict(_hand_trace(), host=[["$engine.py:1 step", 0.5, 6.0]])
    red = H.reduce_spans(no_spans)
    assert red["spans"] == {} and red["idle"]["idle_s"] == pytest.approx(6.0)
    assert H.idle_seconds(red, H.SERVE_IDLE["unnamed"]) is None
    assert H.ms_per(None, ["x"], per="y") is None


# -- the readers, through the harness's own loader -------------------------------------


@pytest.fixture(scope="module")
def readers():
    man = Manifest(H.ROOT)
    by_name = {m["name"]: (m, path) for group in ("per_layer",)
               for cell in man.doc["workloads"]
               for m, path in man.metrics_for(cell, group)}
    assert set(NEW) <= set(by_name), "BENCHMARK.json lists the nine new metrics"
    return {n: M.Reader(n, by_name[n][1]) for n in NEW}, \
        {n: by_name[n][0] for n in NEW}


def _ctx(cell_name, trace=True):
    return {"values": {}, "trace": {"devices": 1} if trace else None,
            "cell": {"name": cell_name}, "config": {}, "traffic": {},
            "device": {}, "peaks": None}


@pytest.fixture()
def as_run(monkeypatch, tmp_path):
    """Lay a recorded capture where a traced run of a cell leaves its own."""
    monkeypatch.setattr(H, "ROOT", str(tmp_path))

    def lay(path, cell):
        out = tmp_path / ".bench_out" / cell
        out.mkdir(parents=True)
        shutil.copy(path, out / "events.json.gz")
        return _ctx(cell)

    return lay


def test_manifest_entries_of_the_new_metrics(readers):
    _, entries = readers
    for name, m in entries.items():
        assert m["source"] == "program_span" and m["better"] == "lower"
        train = name == "trainer_host_ms_step"
        assert m["moves"] == ("train_tokens_per_s_chip" if train else "tpot_p50_ms")
        assert m["layer"] == ("trainer" if train else
                              "device" if name in IDLE_PARTS else "serving engine")
        assert len(m["workloads"]) == (2 if train else 1)


def test_readers_on_the_recorded_serving_capture(readers, as_run):
    rd, _ = readers
    ctx = as_run(SERVE, "serve-olmo1b-chat-r80")
    got = {n: rd[n].read(ctx) for n in NEW}
    assert got["trainer_host_ms_step"] is None  # no train: span in a serving capture
    trace = X.read_trace(SERVE)
    red = X.reduce_trace(trace, {"buckets": {}})
    idle_share = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    # the four parts are the idle share of the accepted reader, split
    assert sum(got[n] for n in IDLE_PARTS) == pytest.approx(idle_share, abs=1e-6)
    assert all(got[n] >= -1e-9 for n in IDLE_PARTS)
    assert got["serve_idle_unnamed"] <= 2.0
    assert got["serve_idle_in_fetch"] > 0 and got["serve_idle_in_host"] > 0
    # a step by the engine's clock is dispatch + fetch: a decode step of a
    # billion-parameter model on one chip, tens of milliseconds
    step = got["engine_dispatch_ms_step"] + got["engine_fetch_ms_step"]
    decodes = [d for n, _, d in trace["host"] if n == "serve:decode"]
    assert step == pytest.approx(1e3 * sum(decodes) / len(decodes), rel=0.01)
    assert 10.0 < step < 60.0
    assert 0 < got["observatory_ms_step"] < got["engine_dispatch_ms_step"]
    assert 0 < got["engine_sample_ms_step"] < step
    # an untraced run has no capture to read
    assert all(rd[n].read(dict(ctx, trace=None)) is None for n in NEW)


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.events.json.gz"))),
                         ids=os.path.basename)
def test_every_new_reader_finds_nothing_in_a_trace_from_before_the_spans(
        readers, as_run, path):
    rd, _ = readers
    assert not H.program_spans(X.read_trace(path))
    ctx = as_run(path, "train-olmo1b-1chip")
    assert {n: rd[n].read(ctx) for n in NEW} == dict.fromkeys(NEW)


def test_no_capture_kept_is_nothing_to_read(readers, as_run, tmp_path):
    rd, _ = readers
    as_run(SERVE, "some-cell")
    assert all(rd[n].read(_ctx("another-cell")) is None for n in NEW)
