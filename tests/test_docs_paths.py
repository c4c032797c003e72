"""Every repo path a document names in backticks exists.

A pointer is the first word of a backticked token that starts with one
of the repo's top directories and ends in a file extension, with a
``:line`` or ``::name`` tail cut; globs and ``<placeholders>`` are not
pointers, and neither is a bare basename such as ``engine.py``.  The
documents are the ones a reader is sent to; ``docs/perf.md`` is left out
by name because it is a dated record (its first lines say which scripts
it names have left the tree, and where in git they are).
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "docs/serving.md", "docs/observability.md",
        "docs/build.md", "docs/analysis.md", "docs/design.md")
_ROOTS = ("scripts/", "tests/", "benchmarks/", "triton_dist_tpu/",
          "examples/", "docs/")
_TICKED = re.compile(r"`([^`\n]+)`")
_EXT = re.compile(r"\.[A-Za-z0-9]+$")


def pointers(text):
    out = []
    for token in _TICKED.findall(text):
        word = (token.split() or [""])[0]
        path = re.split(r"::|:(?=\d)", word, maxsplit=1)[0]
        if (path.startswith(_ROOTS) and _EXT.search(path)
                and not set(path) & set("*<>{}[]")):
            out.append(path)
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_paths_a_document_names_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        found = pointers(f.read())
    assert found, f"{doc} names no repo path: the pattern has rotted"
    missing = sorted({p for p in found
                      if not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{doc} names files that do not exist: {missing}"
