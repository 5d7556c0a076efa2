"""The documents name only what exists: every repo path a document puts in
back quotes is a file (or directory, or glob with a match) of the checkout,
every ``path.py::test_name`` names a test that file defines, and every
``DSTPU_*`` variable it mentions is read somewhere in the program, its tools
or its tests.  One case a document."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DOCS = (["README.md", "examples/README.md", ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, REPO)
                 for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))

#: what looks like a path of this repo inside a back-quoted span
_PATH = re.compile(
    r"(?<![\w/.-])"
    r"((?:tools|deepspeed_tpu|benchmark|tests|examples|docs)/[\w./*-]*"
    r"|bench\w*\.py|chip_smoke\.py)"
    r"(?:::(\w+))?")
_ENV = re.compile(r"DSTPU_[A-Z0-9_]+")
#: where a DSTPU_* variable has to be read for a document to name it
_ENV_ROOTS = ("deepspeed_tpu", "tools", "tests", "csrc", "chip_smoke.py")


def _spans(text):
    """Back-quoted spans, fenced blocks included; a path wrapped after a
    slash is joined again."""
    for span in re.findall(r"```.*?```|`[^`]+`", text, flags=re.S):
        yield re.sub(r"/\n\s*", "/", span.strip("`"))


@functools.lru_cache(maxsize=None)
def _env_names():
    """Every DSTPU_* name in the sources under ``_ENV_ROOTS``; read once
    for all the documents."""
    names = set()
    for root in _ENV_ROOTS:
        top = os.path.join(REPO, root)
        files = [top] if os.path.isfile(top) else [
            os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs
            if f.endswith((".py", ".cc", ".cpp", ".h", ".sh"))]
        for path in files:
            with open(path, encoding="utf-8", errors="replace") as f:
                names.update(_ENV.findall(f.read()))
    return names


def _missing_paths(text):
    missing = []
    for span in _spans(text):
        for path, test in _PATH.findall(span):
            path = path.rstrip(".,:;")
            full = os.path.join(REPO, path)
            if "*" in path:
                if not glob.glob(full):
                    missing.append(path)
            elif not os.path.exists(full):
                missing.append(path)
            elif test:
                with open(full, encoding="utf-8") as f:
                    if not re.search(rf"^\s*def {test}\b", f.read(), re.M):
                        missing.append(f"{path}::{test}")
    return sorted(set(missing))


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    known = _env_names()
    unread = sorted({n for n in _ENV.findall(text)
                     if not (n in known or (n.endswith("_") and any(
                         k.startswith(n) for k in known)))})
    assert not _missing_paths(text), f"{doc} names paths that do not exist"
    assert not unread, f"{doc} names DSTPU_* variables nothing reads"
