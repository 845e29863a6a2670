"""KFT_* environment-variable drift audit.

The env surface is the de-facto public API of the launcher/trainer stack
— and the one that rots fastest: a variable renamed in code but not in
docs ships a knob nobody can find, and a doc row for a variable nothing
reads is worse (operators set it and believe it worked).  This audit
greps both sides and reports the difference:

  * read in code but documented nowhere and not allowlisted as internal
    plumbing -> `env-drift` finding (undocumented knob);
  * documented but never read anywhere in code -> `env-drift` finding
    (dead doc row).

"Internal" variables — the launcher->worker private wire protocol the
user never sets — live in INTERNAL_ENV with a one-line justification
each; they are exempt from the docs requirement but still checked for
deadness (an internal var nobody reads is a removed feature's fossil).
"""
from __future__ import annotations

import os
import re
from typing import Dict, Iterable, List, Optional, Set

from .findings import ERROR, Finding, RULE_ENV_DRIFT

_ENV_RE = re.compile(r"\bKFT_[A-Z0-9_]+\b")

#: internal wire-protocol variables: set by the launcher (or test
#: harness) for its children, never by an operator — exempt from docs.
INTERNAL_ENV: Dict[str, str] = {
    "KFT_SELF_SPEC": "launcher->worker: this process's peer identity",
    "KFT_SELF_RANK": "launcher->worker: this process's rank",
    "KFT_SELF_HOST": "launcher->worker: this process's host id",
    "KFT_PARENT_ID": "launcher->worker: parent launcher id for orphan "
                     "detection",
    "KFT_PROC_START": "launcher->worker: wall-clock stamp of this spawn; "
                      "the worker's boot:interpreter phase starts there",
    "KFT_INIT_CLUSTER": "launcher->worker: serialized initial cluster "
                        "document",
    "KFT_INIT_VERSION": "launcher->worker: initial cluster doc version",
    "KFT_HEARTBEAT_FILE": "launcher->worker: heartbeat file path the "
                          "healer watches",
    "KFT_INCARNATION": "launcher->worker: restart counter of this rank",
    "KFT_LAUNCH_RANK": "launcher->worker: rank at launch (chaos targeting "
                       "stays stable across elastic renumbering)",
    "KFT_INIT_PEERS": "launcher->worker: comma-separated worker list at "
                      "spawn (env.py)",
    "KFT_INIT_RUNNERS": "launcher->worker: comma-separated runner list at "
                        "spawn (env.py)",
    "KFT_INIT_CLUSTER_VERSION": "launcher->worker: config version at "
                                "spawn (env.py)",
    "KFT_DIST_HOST": "distribute.py->remote shell: the host id it "
                     "exported itself to",
    "KFT_PROGRESS_BEACON": "test harness (testing/pod.py)->trainer: arm "
                           "the per-step progress beacon the pod drills "
                           "assert on",
}

#: directories (relative to repo root) whose source counts as "code"
CODE_DIRS = ("kungfu_tpu", "scripts", "examples")
#: docs scanned for the documented set
DOC_DIRS = ("docs",)
DOC_FILES = ("README.md",)


def _repo_root(root: Optional[str] = None) -> str:
    return os.path.abspath(
        root or os.path.join(os.path.dirname(__file__), "..", ".."))


def _scan(paths: Iterable[str], exts: tuple) -> Set[str]:
    out: Set[str] = set()
    for path in paths:
        if os.path.isfile(path):
            files = [path]
        else:
            files = []
            for dirpath, dirnames, filenames in os.walk(path):
                if "torch" in dirpath.split(os.sep):
                    dirnames[:] = []
                    continue
                files.extend(os.path.join(dirpath, f) for f in filenames
                             if f.endswith(exts))
        for f in sorted(files):
            try:
                with open(f, encoding="utf-8", errors="replace") as fh:
                    text = fh.read()
            except OSError:
                continue
            for name in _ENV_RE.findall(text):
                # an f-string prefix like `f"KFT_CONFIG_{key}"` captures
                # a trailing-underscore stem: treat it as a family prefix,
                # matched by prefix below, not as a variable of its own
                out.add(name)
    return out


def code_env(root: Optional[str] = None) -> Set[str]:
    root = _repo_root(root)
    paths = [os.path.join(root, d) for d in CODE_DIRS]
    return _scan([p for p in paths if os.path.exists(p)],
                 (".py", ".sh"))


def docs_env(root: Optional[str] = None) -> Set[str]:
    root = _repo_root(root)
    paths = [os.path.join(root, d) for d in DOC_DIRS]
    paths += [os.path.join(root, f) for f in DOC_FILES]
    return _scan([p for p in paths if os.path.exists(p)], (".md",))


def _match(name: str, pool: Set[str]) -> bool:
    """Exact membership, or family-prefix membership: a stem ending in
    `_` (from an f-string) matches any pool entry it prefixes, and vice
    versa."""
    if name in pool:
        return True
    if name.endswith("_"):
        return any(p.startswith(name) for p in pool)
    return any(p.endswith("_") and name.startswith(p) for p in pool)


def env_findings(root: Optional[str] = None) -> List[Finding]:
    root = _repo_root(root)
    code = code_env(root)
    docs = docs_env(root)
    out: List[Finding] = []
    for name in sorted(code):
        if name in INTERNAL_ENV or _match(name, docs):
            continue
        out.append(Finding(
            rule=RULE_ENV_DRIFT, severity=ERROR,
            message=(f"{name} is read in code but documented nowhere "
                     "under docs/ or README.md — document it or add it "
                     "to envaudit.INTERNAL_ENV with a justification"),
            path=("env", name), source=name))
    for name in sorted(docs):
        if _match(name, code):
            continue
        out.append(Finding(
            rule=RULE_ENV_DRIFT, severity=ERROR,
            message=(f"{name} is documented but nothing in the code "
                     "reads it — a dead doc row operators will set and "
                     "trust; delete the row or restore the reader"),
            path=("env", name), source=name))
    for name in sorted(INTERNAL_ENV):
        if not _match(name, code):
            out.append(Finding(
                rule=RULE_ENV_DRIFT, severity=ERROR,
                message=(f"{name} is allowlisted as internal but nothing "
                         "reads it any more — remove the allowlist entry"),
                path=("env", name), source=name))
    return out
