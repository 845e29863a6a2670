"""The package graph of kungfu_tpu/ as a table and a test.

Every file under kungfu_tpu/ is parsed with `ast` (nothing is imported),
mapped to its unit (its package directory, or the top-level module it is)
and every `import` it holds, at module level or inside a function, to the
unit it names.  RANK puts the units in layers.  An import must go DOWN: to
a unit of a strictly lower rank (so two units of one rank may not import
each other, and a cycle cannot hide inside a layer).  An import that goes
sideways or up fails unless the pair (file, target unit) is in
KNOWN_UPWARD with the name of its debt; an entry there that no longer
occurs fails too, so the list only shrinks.  ROADMAP D12 lists every
entry with the decision it hides.

Adding a unit: give it a rank.  Adding an upward arrow: don't — move the
code, or inject the dependency from the caller that is above both.
"""
import ast
import functools
import os

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
PKG = os.path.join(REPO, "kungfu_tpu")
#: the root package's own `__init__.py` (what `import kungfu_tpu` names)
FACADE = "__init__"
#: parametrize id of the case that checks the top-level modules
TOP_LEVEL = "top-level modules"

RANK = {
    # leaves: no import of the package
    "compat": 0, "datasets": 0, "plan": 0, "utils": 0,
    # process plumbing and telemetry
    "env": 1, "monitor": 1, "native": 1, "platforms": 1,
    # kernels, collectives, host-side stores
    "data_files": 2, "distributed": 2, "info": 2, "ops": 2, "store": 2,
    # what is built from kernels and stores
    "compression": 3, "initializer": 3, "parallel": 3, "resilience": 3,
    "models": 4, "session": 4,
    "optimizers": 5, "peer": 5,
    "variables": 6,
    "policy": 7,
    # trainers and what measures a step
    "train": 8,
    "checkpoint": 9, "trainer": 9, "tuner": 9,
    # whole-program tools and the elastic runtime
    "analysis": 10, "elastic": 10,
    "api": 11, "planner": 11, "run": 11,
    # entry points
    FACADE: 12, "serving": 12,
    "testing": 13, "torch": 13,
    "chaos": 14,
}

#: (file relative to kungfu_tpu/, target unit) -> the debt it stands for
KNOWN_UPWARD = {
    ("utils/stall.py", "monitor"): "utils journals: the stall watchdog emits journal events",
    ("utils/trace.py", "monitor"): "utils counts: dropped spans bump monitor's counters",
    ("ops/pallas_collectives.py", "compression"): "compression <-> ops: the fused-codec ring reads wire configs",
    ("ops/ring_kernels.py", "compression"): "compression <-> ops: codec constants live above the kernels",
    ("ops/pallas_collectives.py", "session"): "the --smoke main of a kernel module builds a Session",
    ("ops/kv_ship.py", "serving"): "kv_ship is serving code filed under ops",
    ("monitor/__main__.py", "models"): "the compile drill builds a model inside monitor's CLI",
    ("monitor/__main__.py", "serving"): "the compile drill builds an engine inside monitor's CLI",
    ("parallel/pp_transformer.py", "models"): "parallel <-> models: PipelinedLM reuses the LM's blocks",
    ("run/__main__.py", "serving"): "run <-> serving: `kungfu-run -serve` forwards to the supervisor",
    ("optimizers/gossip.py", "analysis"): "analyze= hook",
    ("optimizers/sync.py", "analysis"): "analyze= hook",
    ("session.py", "analysis"): "analyze= hook",
    ("checkpoint.py", "chaos"): "injection hook: crash-in-save",
    ("elastic/config_server.py", "chaos"): "injection hook: server chaos from the environment",
    ("elastic/trainer.py", "chaos"): "injection hook: the step loop's fault injector",
    ("serving/drill.py", "chaos"): "the serve drill parses the fault-plan grammar",
    ("serving/worker.py", "chaos"): "injection hook: the worker's fault injector",
    ("elastic/trainer.py", FACADE): "run_elastic calls kungfu_tpu.init/finalize through the facade",
}


def _units():
    out = {FACADE}
    for name in os.listdir(PKG):
        path = os.path.join(PKG, name)
        if name.startswith("_"):
            continue  # __pycache__, the built _lib/
        if os.path.isdir(path):
            out.add(name)
        elif name.endswith(".py"):
            out.add(name[:-3])
    return out


UNITS = _units()
PACKAGE_DIRS = sorted(u for u in UNITS if os.path.isdir(os.path.join(PKG, u)))


def _files(unit):
    """Paths (relative to kungfu_tpu/) of the files a case checks."""
    if unit == TOP_LEVEL:
        return sorted(n for n in os.listdir(PKG) if n.endswith(".py"))
    out = []
    for dirpath, dirnames, filenames in os.walk(os.path.join(PKG, unit)):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        out.extend(os.path.relpath(os.path.join(dirpath, f), PKG)
                   for f in filenames if f.endswith(".py"))
    return sorted(out)


def _unit_of(rel):
    parts = rel.split(os.sep)
    return parts[0][:-3] if len(parts) == 1 else parts[0]


def _imports(rel):
    """(target unit, line) for every import of the package in one file."""
    with open(os.path.join(PKG, rel), encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=rel)
    here = ["kungfu_tpu"] + rel[:-3].split(os.sep)
    here = here[:-1]  # the package a relative import starts from
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                parts = alias.name.split(".")
                if parts[0] == "kungfu_tpu":
                    out.append((parts[1] if len(parts) > 1 else FACADE, node.lineno))
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = here[:len(here) - (node.level - 1)]
                base = base + (node.module.split(".") if node.module else [])
            else:
                base = (node.module or "").split(".")
                if base[0] != "kungfu_tpu":
                    continue
            if len(base) > 1:
                out.append((base[1], node.lineno))
            else:  # `from kungfu_tpu import x` / `from .. import x`
                out.extend((a.name if a.name in UNITS else FACADE, node.lineno)
                           for a in node.names)
    return out


@functools.lru_cache(maxsize=None)  # each case and the shrink test read one parse
def _upward(unit):
    """Imports of `unit`'s files that do not go down: {(file, target): line}."""
    out = {}
    for rel in _files(unit):
        src = _unit_of(rel)
        for target, line in _imports(rel):
            assert target in UNITS, f"{rel}:{line} imports unknown unit {target!r}"
            if target != src and RANK[target] >= RANK[src]:
                out.setdefault((rel.replace(os.sep, "/"), target), line)
    return out


def test_every_unit_has_a_rank():
    assert set(RANK) == UNITS, (
        f"no rank: {sorted(UNITS - set(RANK))}; gone: {sorted(set(RANK) - UNITS)}")


@pytest.mark.parametrize("unit", PACKAGE_DIRS + [TOP_LEVEL])
def test_imports_go_down(unit):
    new = {k: v for k, v in _upward(unit).items() if k not in KNOWN_UPWARD}
    assert not new, "imports that go sideways or up (move the code, or inject from above): " + \
        ", ".join(f"{f}:{line} -> {t} (rank {RANK[_unit_of(f)]} -> {RANK[t]})"
                  for (f, t), line in sorted(new.items()))


def test_known_upward_only_shrinks():
    seen = set()
    for unit in PACKAGE_DIRS + [TOP_LEVEL]:
        seen.update(_upward(unit))
    stale = sorted(set(KNOWN_UPWARD) - seen)
    assert not stale, f"KNOWN_UPWARD entries that no longer occur (delete them): {stale}"
    assert all(debt for debt in KNOWN_UPWARD.values())
