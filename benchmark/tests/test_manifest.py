"""BENCHMARK.json against the contract's limits, and the files it names."""
import json
import os

import pytest

from benchmark.lib.configs import ROOT, load_json, program_fields
from benchmark.lib.manifest import NAME, UNIT, Manifest, check_manifest


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def test_manifest_meets_the_contract(man):
    assert check_manifest(man.doc) == []


def test_names_and_units_hold_only_allowed_characters(man):
    doc = man.doc
    names = [c["name"] for c in doc["configs"]]
    for w in doc["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for m in doc["end_to_end"] + doc["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert all(ord(ch) < 128 for ch in m["unit"])
    for c in doc["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.match(n), n
    assert len(set(m["name"] for m in doc["end_to_end"] + doc["per_layer"])) == \
        len(doc["end_to_end"] + doc["per_layer"])


def test_check_manifest_refuses_what_the_contract_refuses(man):
    doc = json.loads(json.dumps(man.doc))
    doc["end_to_end"][0]["unit"] = "tokens per second"
    doc["workloads"][0]["name"] = "has space"
    doc["per_layer"][0]["why"] = "no such key"
    doc["workloads"][1]["chips"] = 2
    bad = "\n".join(check_manifest(doc))
    for piece in ("unit", "bad name", "per_layer keys", "chips 2"):
        assert piece in bad, (piece, bad)


def test_every_cell_finds_its_files_by_name(man):
    for cell in man.doc["workloads"]:
        assert os.path.exists(man.config_file(cell))
        traffic = json.load(open(man.traffic_file(cell)))
        assert traffic["kind"] in ("train", "open", "closed")
        e2e = [m["name"] for m, _ in man.metrics_for(cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, (cell["name"], e2e)
        layer = man.metrics_for(cell, "per_layer")
        assert layer, cell["name"]
        for m, _ in layer:  # a per-layer metric only where what it moves is
            assert m["moves"] in e2e, (cell["name"], m["name"])


def test_one_four_chip_cell(man):
    four = [w["name"] for w in man.doc["workloads"] if w["chips"] == 4]
    # a quarter of the cells, rounded down, and one always may
    assert len(four) == 1 <= max(1, len(man.doc["workloads"]) // 4)


PUBLISHED = {  # allenai/OLMo-1B-hf config.json
    "vocab_size": 50304, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 16, "num_attention_heads": 16,
    "num_key_value_heads": 16, "max_position_embeddings": 2048,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
}


def test_configurations_keep_every_published_width(man):
    for entry in man.doc["configs"]:
        cfg = load_json(os.path.join(ROOT, entry["file"]))
        differs = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
        assert differs == sorted(entry["reduced"]), (entry["name"], differs)
        assert set(entry["reduced"]) <= {"num_hidden_layers"}  # never a width
        assert set(cfg["reduced"]) == set(entry["reduced"])
        fields = program_fields(cfg)
        assert fields["d_model"] == 2048 and fields["d_ff"] == 8192
        assert fields["n_heads"] == 16 and fields["n_kv_heads"] == 0
        assert fields["vocab_size"] == 50304 and fields["tie_embeddings"] is True
        assert fields["norm"] == "layer" and fields["ffn"] == "swiglu"
        assert cfg["deployment"]["chips"] in (1, 4) and "assumed" in cfg
