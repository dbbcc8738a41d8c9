"""The documents a user reads name things that exist.

One case a file: README.md, COMPONENTS.md and every docs/*.md. PERF.md,
ROADMAP.md and CHANGES.md are history (they speak of what has gone) and are
left out.
"""
import glob
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "COMPONENTS.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_TREES = ("distar_tpu/", "tools/", "configs/", "benchmark/", "tests/")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_NOT_A_NAME = re.compile(r"[*?\[\]<>{}]|\.\.\.|…")  # globs and placeholders
_PYTHON = re.compile(r"\bpython3?\s+(?:-u\s+)?(?:-m\s+([\w.]+)|([\w./-]+\.py)\b)")


def _stale_paths(text):
    """(a) backticked tokens under the repo's trees, cut at the first blank
    or ``:`` (so ``file.py:12`` and ``file.py::test`` name the file)."""
    stale = []
    for token in _BACKTICKED.findall(text):
        path = re.split(r"[\s:]", token.strip(), maxsplit=1)[0].rstrip(",.;)")
        if not path.startswith(_TREES) or _NOT_A_NAME.search(path):
            continue
        if not os.path.exists(os.path.join(REPO, path)):
            stale.append(token)
    return stale


def _module_exists(name):
    parts = name.split(".")
    if not os.path.isdir(os.path.join(REPO, parts[0])):  # not the repo's: installed?
        return importlib.util.find_spec(parts[0]) is not None
    path = os.path.join(REPO, *parts)
    return os.path.isfile(path + ".py") or os.path.isdir(path)


def _stale_commands(text):
    """(b) every ``python[3] <file>.py`` and ``python[3] -m <module>``."""
    stale = []
    for match in _PYTHON.finditer(text):
        module, script = match.groups()
        if module is not None:
            ok = _module_exists(module)
        else:
            ok = _NOT_A_NAME.search(script) or os.path.isfile(os.path.join(REPO, script))
        if not ok:
            stale.append(match.group(0))
    return stale


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_things_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    stale = _stale_paths(text) + _stale_commands(text)
    assert stale == [], f"{doc} names what the tree does not have: {stale}"
