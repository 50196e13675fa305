"""The documents a new owner reads first name only files that are there.

``README.md``, the builders' notes (``.claude/skills/verify/SKILL.md``) and
``PARITY.md`` point at modules, tests, tools and records by path. A path in
backticks that no longer exists sends the reader to a file that is gone (the
README named a CPU-era benchmark and its records for 28 PRs after the
ledger's benchmark replaced them), so every such token is held to the tree.
"""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIXES = ("delta_tpu/", "tests/", "tools/", "benchmark/")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_BARE_FILE = re.compile(r"[\w.\-]+\.(?:py|json)")
_GLOB = set("*?[]{}<>…")


def _tree_file_names():
    names = set()
    for _dir, dirs, files in os.walk(ROOT):
        # caches, scratch copies of other commits and chip outputs are not
        # the tree a reader is sent to
        dirs[:] = [d for d in dirs if d == ".claude" or not (
            d.startswith(".") or d in ("__pycache__", "chiprun_out"))]
        names.update(files)
    return names


def _tokens(text):
    """(token, kind) for every backticked word that names a file: `path`
    for one under a source directory, `bare` for a bare *.py / *.json."""
    for quoted in _BACKTICKED.findall(text):
        for word in quoted.split():
            word = word.split("::")[0]
            word = re.sub(r":\d+(?:[-,]\d+)*$", "", word).rstrip(".,;:)")
            if not word or _GLOB & set(word):
                continue
            if word.startswith(PREFIXES):
                yield word, "path"
            elif _BARE_FILE.fullmatch(word):
                yield word, "bare"


@pytest.mark.parametrize("document", [
    "README.md", ".claude/skills/verify/SKILL.md", "PARITY.md"])
def test_documents_name_files_that_exist(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as fh:
        tokens = sorted(set(_tokens(fh.read())))
    assert tokens, f"{document} names no file: the pattern has rotted"
    names = _tree_file_names()
    missing = [
        word for word, kind in tokens
        if not (os.path.exists(os.path.join(ROOT, word)) if kind == "path"
                else word in names)
    ]
    assert missing == [], f"{document} names files that are not in the tree"
