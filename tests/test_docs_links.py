"""The documents cite files that exist.

One case a document: every relative markdown link, and every backticked
path under the repo's code directories, names a file in the checkout. A
PR that deletes a file and still cites it fails here. PERF.md, ROADMAP.md
and CHANGES.md are plans and history that rightly name files that are
gone, and are not in the list.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PARITY.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_TICKED = re.compile(r"`([^`\s]+)`")
_DIRS = ("paddle_tpu/", "tools/", "scripts/", "tests/", "benchmark/",
         "docs/")
_ENDS = (".py", ".sh", ".json")


def cited_paths(document):
    """(what the document wrote, the path it names from the repo's root)
    for every citation the rule covers."""
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    here = os.path.dirname(document)
    for target in _LINK.findall(text):
        if re.match(r"[a-z]+:", target) or target.startswith("#"):
            continue
        yield target, os.path.normpath(
            os.path.join(here, target.split("#")[0]))
    for ticked in _TICKED.findall(text):
        path = re.sub(r":[\d,\-]+$", "", ticked)
        if (path.startswith(_DIRS) and path.endswith(_ENDS)
                and not set(path) & set("*{<")):
            yield ticked, path


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_cites_files_that_exist(document):
    missing = sorted({wrote for wrote, path in cited_paths(document)
                      if not os.path.exists(os.path.join(ROOT, path))})
    assert not missing, f"{document} cites files that do not exist: {missing}"
