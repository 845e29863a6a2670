"""The documents name only what exists.

One case per document (README.md and each docs/*.md), per CI workflow
(.github/workflows/*.yaml) and per gate script (scripts/*.sh).  Text only:
nothing is imported and no process is started.

  * every `python -m kungfu_tpu.<module>` a document prints resolves to a
    module, or to a package with a `__main__`;
  * every back-quoted path under kungfu_tpu/, scripts/, tests/, examples/,
    benchmark/ or docs/ exists.  A path may carry a `:line`, a `::test` or a
    `: name` suffix and may be followed by arguments; a span with a
    placeholder (`<name>`, `*`, `{a,b}`, `…`) is a pattern, not a path.

A workflow or a script has no back-quotes, so outside its comments every
word is held to the same: a `kungfu_tpu.<dotted>` name is a module or a
package, a word under one of the roots or ending in `.py` / `.sh` is a file or
a directory, and what `ruff check` is given exists.
"""
import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
ROOTS = ("kungfu_tpu/", "scripts/", "tests/", "examples/", "benchmark/", "docs/")

DOCUMENTS = ["README.md"] + sorted(
    "docs/" + n for n in os.listdir(os.path.join(REPO, "docs")) if n.endswith(".md"))

WORKFLOWS = sorted(
    ".github/workflows/" + n
    for n in os.listdir(os.path.join(REPO, ".github", "workflows"))
    if n.endswith((".yaml", ".yml"))) + sorted(
    "scripts/" + n for n in os.listdir(os.path.join(REPO, "scripts")) if n.endswith(".sh"))

_MODULE = re.compile(r"python3? -m (kungfu_tpu(?:\.[A-Za-z_][A-Za-z0-9_]*)+)")
_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_SPAN = re.compile(r"`([^`\n]+)`")
_PLACEHOLDER = re.compile(r"[<>*{}…]")
_DOTTED = re.compile(r"(?<![\w.])(kungfu_tpu(?:\.[A-Za-z_][A-Za-z0-9_]*)+)")
_WORD_PATH = re.compile(
    r"(?<![\w./-])((?:%s)[\w./-]*|[\w-]+\.(?:py|sh))(?![\w/-])"
    % "|".join(re.escape(r) for r in ROOTS))
_RUFF = re.compile(r"ruff check ([^\n|;&]+)")


def _read(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        return f.read()


def _module_exists(dotted):
    base = os.path.join(REPO, *dotted.split("."))
    return os.path.isfile(base + ".py") or os.path.isfile(os.path.join(base, "__main__.py"))


def _paths(text):
    """Back-quoted paths under ROOTS, outside fenced blocks, suffixes cut."""
    for span in _SPAN.findall(_FENCE.sub("", text)):
        if not span.startswith(ROOTS):
            continue
        path = span.split()[0]
        if _PLACEHOLDER.search(path):
            continue
        path = re.sub(r":.*$", "", path).rstrip(".,;)")
        yield path


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_exists(doc):
    text = _read(doc)
    modules = sorted({m for m in _MODULE.findall(text) if not _module_exists(m)})
    paths = sorted({p for p in _paths(text) if not os.path.exists(os.path.join(REPO, p))})
    assert not modules and not paths, (
        f"{doc} names what is not there: modules {modules}, paths {paths}")


def _importable(dotted):
    base = os.path.join(REPO, *dotted.split("."))
    return os.path.isfile(base + ".py") or os.path.isfile(os.path.join(base, "__init__.py"))


@pytest.mark.parametrize("workflow", WORKFLOWS)
def test_workflow_runs_only_what_exists(workflow):
    text = "\n".join(
        line for line in _read(workflow).splitlines() if not line.lstrip().startswith("#"))
    modules = {m for m in _MODULE.findall(text) if not _module_exists(m)}
    modules |= {m for m in _DOTTED.findall(text) if not _importable(m)}
    words = {p.rstrip(".") for p in _WORD_PATH.findall(text)}
    for args in _RUFF.findall(text):
        words |= {w for w in args.split() if not w.startswith("-")}
    paths = sorted(p for p in words if not os.path.exists(os.path.join(REPO, p)))
    assert not modules and not paths, (
        f"{workflow} runs what is not there: modules {sorted(modules)}, paths {paths}")
