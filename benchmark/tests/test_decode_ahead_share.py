"""`decode_ahead_share` from a capture's `counters.json`: the share of decode
steps the engine dispatched with the step before unread, left out where the
program has no `kft_serve_decode_steps_total` (a parent commit) or the run
was not traced; and its entry in the manifest."""
import json

import pytest

from benchmark.lib import metrics as M
from benchmark.lib import moe_costs
from benchmark.lib.configs import ROOT
from benchmark.lib.manifest import Manifest, check_manifest

SERVING = ["serve-olmo1b-chat-r80", "serve-olmoe-chat-r80",
           "serve-longcat-reason-r80", "serve-pangu-reason-r80"]
FAMILY = "kft_serve_decode_steps_total"


def test_the_entry_meets_the_contract_and_lists_the_serving_cells():
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    entry = man.doc["per_layer"][-1]
    assert entry == {"name": "decode_ahead_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "serving engine",
                     "moves": "tpot_p50_ms", "workloads": SERVING}
    for cell in man.doc["workloads"]:
        names = [m["name"] for m, _ in man.metrics_for(cell, "per_layer")]
        assert ("decode_ahead_share" in names) == (cell["name"] in SERVING)


@pytest.mark.parametrize("name", SERVING)
def test_share_of_steps_ahead_between_the_ends_of_a_capture(name, monkeypatch, tmp_path):
    man = Manifest(ROOT)
    cell = man.cell(name)
    reader = dict((m["name"], M.Reader(m["name"], p))
                  for m, p in man.metrics_for(cell, "per_layer"))["decode_ahead_share"]
    monkeypatch.setattr(moe_costs, "ROOT", str(tmp_path))
    ctx = {"cell": cell, "trace": {"devices": [1]}, "values": {}}
    assert reader.read(ctx) is None  # a capture with no counters.json
    cap = tmp_path / ".bench_out" / cell["name"] / "profile-1"
    cap.mkdir(parents=True)

    def counters(start, end):
        (cap / "counters.json").write_text(json.dumps({"start": start, "end": end}))

    kinds = lambda a, s, w: {FAMILY: {  # noqa: E731
        'kind="ahead"': a, 'kind="synced"': s, 'kind="wasted_rows"': w}}
    counters(kinds(100, 40, 3), kinds(1090, 50, 500))
    assert reader.read(ctx) == pytest.approx(99.0)  # 990 of 1,000 steps; rows are no steps
    counters(kinds(100, 40, 0), kinds(100, 90, 0))
    assert reader.read(ctx) == 0.0                  # every step waited for the host
    counters(kinds(100, 40, 0), kinds(100, 40, 0))
    assert reader.read(ctx) is None                 # no decode step in the window
    counters({}, {"kft_serve_decode_rows_total": {'kind="live"': 5}})
    assert reader.read(ctx) is None                 # a program from before the counter
    assert reader.read(dict(ctx, trace=None)) is None  # an untraced run
