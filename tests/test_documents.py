"""The documents a user reads first name only what the tree has: every
repository path and every `make <target>` in them exists. (A quick-start
that outlives the file it tells the user to run is found here, not by the
user.)"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ("README.md", "DEPLOY.md", "perf/README.md")

# A path under one of the tree's directories, or any *.py file name.
_PATH = re.compile(
    r"(?<![\w/.*-])("
    r"(?:scripts|perf|native|perfbench|polykey_tpu|tests|protos|assets)"
    r"/[\w./*-]*[\w*]"
    r"|[\w/.*-]*[\w*]\.py"
    r")(?![\w/-])"
)
# A bare *.json name: a record, in a document that describes its own
# folder (elsewhere such a name is a file a run writes).
_RECORD = re.compile(r"(?<![\w/.*-])([\w.*-]*[\w*]\.json)(?![\w/-])")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_MAKE = re.compile(r"\bmake ([a-z][a-z0-9-]*)")


@functools.cache
def _tracked_names() -> frozenset:
    names = set()
    for folder, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "build")]
        names.update(files)
    return frozenset(names)


def _exists(path: str, beside: str) -> bool:
    """As the documents write paths: from the root, from the document's
    own folder, from the package, or a bare file name somewhere in the
    tree; `*` is a glob that must match."""
    for base in (ROOT, os.path.join(ROOT, beside),
                 os.path.join(ROOT, "polykey_tpu")):
        if glob.glob(os.path.join(base, path)):
            return True
    return "/" not in path and path in _tracked_names()


def _make_targets() -> frozenset:
    with open(os.path.join(ROOT, "Makefile")) as f:
        return frozenset(re.findall(r"(?m)^([a-z][a-z0-9-]*):", f.read()))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    beside = os.path.dirname(document)
    paths = {m.group(1) for m in _PATH.finditer(text)}
    if beside:
        paths |= set(_RECORD.findall(text))
    assert paths, f"{document}: the path pattern found nothing to check"
    missing = sorted(p for p in paths if not _exists(p, beside))
    assert not missing, f"{document} names paths the tree lacks: {missing}"

    targets = _make_targets()
    code = "\n".join(_CODE.findall(text))
    unknown = sorted(set(_MAKE.findall(code)) - targets)
    assert not unknown, (
        f"{document} names make targets the Makefile lacks: {unknown}")
