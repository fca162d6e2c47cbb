"""The documents a user reads first cite only files that exist.

One case a document. Checked in each: every repo-relative Markdown
link, and every back-quoted path that ends in ``.py``, ``.json`` or
``.md`` and starts at a top-level name of the repo (with the test it
names after ``::``, where it names one). It is what keeps a
deleted record (a benchmark script, a results file) from being cited
again. The history-bearing files (``CHANGES.md``, ``PERF.md``,
``ROADMAP.md``) name files that are gone on purpose and are not
scanned.
"""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "examples/README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_QUOTED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w./-]+\.(?:py|json|md)$")
#: a path with one of these in it is a pattern, not a file
_PATTERN_MARKS = ("<", "*", "...")


def _cited(text, doc_dir):
    """(how it was cited, path from the repo's root, test named after
    ``::`` or "") of every checked citation in ``text``."""
    top = set(os.listdir(REPO))
    for target in _LINK.findall(text):
        if re.match(r"^[a-z][a-z0-9+.-]*:", target) \
                or target.startswith("#"):
            continue                      # a URL, or this page's anchor
        path = target.split("#")[0]
        yield f"link ({target})", os.path.normpath(
            os.path.join(doc_dir, path)), ""
    for quoted in _QUOTED.findall(text):
        if any(mark in quoted for mark in _PATTERN_MARKS):
            continue
        path, _, case = quoted.partition("::")  # tests/t.py::test_case
        if _PATH.match(path) and path.split("/")[0] in top:
            yield f"`{quoted}`", os.path.normpath(path), case


def _holds_case(path, case):
    """Whether the test file at ``path`` defines the cited test."""
    with open(os.path.join(REPO, path)) as f:
        return re.search(rf"^\s*def {re.escape(case)}\(", f.read(),
                         re.M) is not None


@pytest.mark.parametrize("doc", DOCS)
def test_document_cites_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = sorted({
        f"{how} -> {path}" for how, path, case in
        _cited(text, os.path.dirname(doc))
        if not os.path.exists(os.path.join(REPO, path))
        or (case and not _holds_case(path, case))})
    assert not missing, f"{doc} cites what does not exist: {missing}"
