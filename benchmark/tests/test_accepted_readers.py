"""The reader cases of test_moe_metrics.py and test_decode_attn_metrics.py,
run with readers found by name.

Those files' module fixtures pin which cells list their metrics
(`workloads == [CELL]`, `== CELLS`) and that the decode-attention entries
are the last three of `per_layer`.  A PR may only append to BENCHMARK.json
and may not edit a benchmark file that is there, so since PR 35 appended a
cell and its metrics the fixtures fail and five cases error before they
run.  Until a `benchmark` issue makes those fixtures test membership, this
file calls the same case functions, from those files, on the same captures
(made by hand, and tests/data/moe/ recorded on the chip), with the readers
the manifest lists for the cell, wherever in the list they stand."""
import importlib.util
import inspect
import os

import pytest

from benchmark.lib import metrics as M
from benchmark.lib.configs import ROOT
from benchmark.lib.manifest import Manifest, check_manifest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name + "_cases", os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cases(mod):
    return [(mod, n) for n, f in vars(mod).items()
            if n.startswith("test_") and "readers" in inspect.signature(f).parameters]


MOE, ATTN = _load("test_moe_metrics"), _load("test_decode_attn_metrics")
CELL_OF = {MOE: MOE.CELL, ATTN: ATTN.CELLS[0]}
CASES = _cases(MOE) + _cases(ATTN)


def _readers(mod):
    man = Manifest(ROOT)
    assert check_manifest(man.doc) == []
    cell = CELL_OF[mod]
    found = dict((m["name"], (m, path))
                 for m, path in man.metrics_for(man.cell(cell), "per_layer"))
    for n in mod.NEW:
        assert cell in found[n][0]["workloads"]
        assert found[n][0]["moves"] == "tpot_p50_ms"
    return {n: M.Reader(n, found[n][1]) for n in mod.NEW}


def test_every_erroring_case_is_found():
    assert len(CASES) == 5


@pytest.mark.parametrize(
    "mod,case", CASES, ids=[f"{m.__name__}.{n}" for m, n in CASES])
def test_case_with_readers_found_by_name(mod, case, monkeypatch, tmp_path):
    readers = _readers(mod)
    lay = mod.as_run.__wrapped__  # the fixture's own function
    wants = [{"monkeypatch": monkeypatch, "tmp_path": tmp_path,
              "readers": readers}[p] for p in inspect.signature(lay).parameters]
    getattr(mod, case)(readers, lay(*wants))
