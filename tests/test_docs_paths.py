"""The documents name files that exist.

A back-quoted path ending in ``.py`` or ``.json`` in a document the next
builder reads (README, PERF, MIGRATION, COMPONENTS, the verify skill) is
an instruction to open or run that file. Files get deleted; this guard
makes the deletion find its mentions.
"""

import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCS = ("README.md", "PERF.md", "MIGRATION.md", "COMPONENTS.md",
        ".claude/skills/verify/SKILL.md")

# not files of this tree: the reference framework's own paths, files a
# run writes, a model's published config
ELSEWHERE = {
    "fleet/utils/fs.py", "fluid/io.py", "fluid/reader.py",
    "tools/timeline.py", "timeline.py",
    "MANIFEST.json", "fleet.json", "chiprun_out/.last_call.json",
    "config.json",
}

_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"[\w./<>*$-]+\.(?:py|json)\b")


@pytest.fixture(scope="module")
def tree() -> list[str]:
    out = []
    for base, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in ("chiprun_out",
                                                          "__pycache__")]
        rel = pathlib.Path(base).relative_to(REPO).as_posix()
        out += [f if rel == "." else f"{rel}/{f}" for f in files]
    return out


def _named(text: str) -> set[str]:
    return {tok for span in _SPAN.findall(text)
            for tok in _PATH.findall(span)
            if not set(tok) & set("*<>$") and not tok.startswith("/")}


@pytest.mark.parametrize("doc", DOCS)
def test_documents_name_files_that_exist(doc, tree):
    names = {t.rsplit("/", 1)[-1] for t in tree}

    def exists(tok: str) -> bool:
        if "/" not in tok:
            return tok in names
        # written from the root or from inside a package
        # (``serving/engine.py``, ``lib/flops.py``)
        return any(t == tok or t.endswith("/" + tok) for t in tree)

    missing = sorted(t for t in _named((REPO / doc).read_text())
                     if t not in ELSEWHERE and not exists(t))
    assert not missing, f"{doc} names files not in the tree: {missing}"


def test_the_guard_sees_a_missing_file():
    assert _named("run `python tools/gone.py --x` then `a/b.json`:") == {
        "tools/gone.py", "a/b.json"}
    assert _named("`/root/x.json`, `BENCH_*.json`, `<dir>/y.py`") == set()
