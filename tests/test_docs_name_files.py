"""The documents name files that exist: every `*.py` / `*.json` path that
README.md, the CI workflow and the verify skill spell under `tools/`,
`tests/`, `trino_tpu/` or `benchmark/`, and every script they name bare
(`chip_smoke.py`), is in the checkout.  A deleted tool that a sentence still sends the reader to fails
here, not in the reader's shell."""

import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    "README.md",
    ".github/workflows/ci.yml",
    ".claude/skills/verify/SKILL.md",
)

#: a path under one of the tree's four code directories
_ROOTED = re.compile(
    r"(?<![\w/.*-])((?:tools|tests|trino_tpu|benchmark)/[\w./-]*\.(?:py|json))\b"
)
#: a script or module named bare
_BARE_PY = re.compile(r"(?<![\w/.*<{-])([A-Za-z_]\w*\.py)\b")


def _tree(root: str) -> set:
    """Relative paths of the checkout's files; scratch directories (a
    leading `_` or `.`, `chiprun_out`) are not part of it."""
    found = set()
    for base, dirs, files in os.walk(root):
        dirs[:] = [
            d for d in dirs if d[0] not in "._" and d != "chiprun_out"
        ]
        for name in files:
            found.add(os.path.relpath(os.path.join(base, name), root))
    return found


def missing_paths(document: str, root: str = REPO_ROOT) -> list:
    with open(os.path.join(root, document), encoding="utf-8") as fh:
        text = fh.read()
    tree = _tree(root)
    names = {os.path.basename(p) for p in tree}
    absent = {p for p in _ROOTED.findall(text) if p not in tree}
    # a bare `runner.py` is some module of the tree by its short name; a
    # bare script that is nowhere in the tree is what this test is for
    absent |= {n for n in _BARE_PY.findall(text) if n not in names}
    return sorted(absent)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document):
    assert missing_paths(document) == []
