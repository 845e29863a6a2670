"""`BENCHMARK.json` and the data files it names.

A cell is an entry of `workloads`; its configuration is the entry of
`configs` with that name (a file of sizes); its traffic mix is
`<bench>/traffic/<traffic>.json`; a metric's reader is
`<bench>/e2e_metrics/<name>.{json,py}` or `<bench>/layer_metrics/<name>.{json,py}`.
The harness finds all of them by name, so a later PR adds files and entries
and edits nothing.  `check_manifest` holds the manifest to the contract's
limits on names, units and sizes.
"""
from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


class Manifest:
    def __init__(self, root: str):
        """`root` holds BENCHMARK.json; data files are looked up under each
        of its `paths`."""
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.paths = [os.path.join(self.root, p) for p in self.doc["paths"]]

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config_entry(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def find(self, *relative: str) -> str:
        """The first existing `<path>/<relative>` over `paths`."""
        for base in self.paths:
            for rel in relative:
                p = os.path.join(base, rel)
                if os.path.exists(p):
                    return p
        raise FileNotFoundError(
            f"none of {list(relative)} under {self.doc['paths']}")

    def config_file(self, cell: dict) -> str:
        return os.path.join(self.root, self.config_entry(cell["config"])["file"])

    def traffic_file(self, cell: dict) -> str:
        return self.find(os.path.join("traffic", cell["traffic"] + ".json"))

    def metrics_for(self, cell: dict, group: str):
        """The metrics of `end_to_end` or `per_layer` this cell reports, each
        with the path of its reader."""
        sub = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}[group]
        out = []
        for m in self.doc[group]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            reader = self.find(os.path.join(sub, m["name"] + ".json"),
                               os.path.join(sub, m["name"] + ".py"))
            out.append((m, reader))
        return out


def check_manifest(doc: dict) -> list:
    """Every breach of the contract's limits found in `doc`, as text."""
    bad = []
    if set(doc) != KEYS:
        bad.append(f"keys {sorted(doc)} != {sorted(KEYS)}")
        return bad
    if len(json.dumps(doc)) > 64 * 1024:
        bad.append("manifest over 64 KiB")
    if not (1 <= len(doc["paths"]) <= 16 and all(PATH.match(p) and not p.startswith("/")
                                                  and ".." not in p.split("/")
                                                  for p in doc["paths"])):
        bad.append(f"paths {doc['paths']}")
    if not (1 <= len(doc["command"]) <= 32):
        bad.append("command length")
    if not (isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51):
        bad.append(f"run_seconds {doc['run_seconds']}")

    def line(s, what):
        if not (isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
                and "\t" not in s):
            bad.append(f"{what}: not one line of 1..200 characters")

    def name(s, what):
        if not (isinstance(s, str) and NAME.match(s)):
            bad.append(f"{what}: bad name {s!r}")

    for w in doc["command"]:
        line(w, "command word")
    seen = set()
    for c in doc["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
            continue
        name(c["name"], "config")
        line(c["source"], "source")
        line(c["why"], "why")
        if not any(c["file"].startswith(p + "/") for p in doc["paths"]):
            bad.append(f"config file {c['file']} outside paths")
        if len(c["reduced"]) > 16:
            bad.append("reduced over 16 keys")
        for k in c["reduced"]:
            name(k, "reduced key")
        if c["name"] in seen:
            bad.append(f"duplicate config {c['name']}")
        seen.add(c["name"])
    if len({c["file"] for c in doc["configs"]}) != len(doc["configs"]):
        bad.append("two configurations share a file")
    cells, pairs = set(), set()
    for w in doc["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name(w[k], f"workload {k}")
        line(w["why"], "why")
        if w["chips"] not in (1, 4):
            bad.append(f"chips {w['chips']}")
        if w["config"] not in seen:
            bad.append(f"workload {w['name']} names no configuration")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            bad.append(f"duplicate workload {w['name']}")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    if not 2 <= len(doc["workloads"]) <= 24:
        bad.append("2 to 24 workloads")
    four = sum(1 for w in doc["workloads"] if w.get("chips") == 4)
    if four > max(1, len(doc["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(doc['workloads'])}")
    used = {w.get("config") for w in doc["workloads"]}
    for c in seen - used:
        bad.append(f"configuration {c} is used by no cell")
    metrics = set()
    e2e = {m.get("name") for m in doc["end_to_end"]}
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound", "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in doc[group]:
            if set(m) - {"workloads"} != keys:
                bad.append(f"{group} keys {sorted(m)}")
                continue
            name(m["name"], "metric")
            if not UNIT.match(m["unit"]):
                bad.append(f"unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"source {m['source']!r}")
            if m["name"] in metrics:
                bad.append(f"duplicate metric {m['name']}")
            metrics.add(m["name"])
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"metric {m['name']} lists unknown cell {w}")
            if group == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"end-to-end {m['name']} source {m['source']}")
                if not 0.01 <= m["bound"] <= 0.1:
                    bad.append(f"bound of {m['name']}: {m['bound']}")
            else:
                line(m["layer"], "layer")
                if m["moves"] not in e2e:
                    bad.append(f"{m['name']} moves unknown {m['moves']}")
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    return bad
