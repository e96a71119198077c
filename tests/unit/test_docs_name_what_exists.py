"""A document or a docstring that sends its reader to a file names one the
tree holds: every back-quoted repo path of ``README.md`` and ``docs/*.md``,
and every ``tools/`` script or run recipe a source file names."""

import glob
import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

FENCED = re.compile(r"```.*?```", re.S)
INLINE = re.compile(r"`[^`\n]+`")
# a path with a directory in it that ends in .py / .md / .json; a trailing
# ``:line`` is not part of the match
REPO_PATH = re.compile(
    r"(?<![\w./-])((?:[\w.-]+/)+[\w.-]+\.(?:py|md|json))(?![\w/-])")
# where a document's short spelling is looked up
PREFIXES = ("", "deepspeed_tpu", "docs", "perfbench")
TOOL_PATH = re.compile(r"(?<![\w./-])(tools/\w+\.py)\b")
ROOT_RECIPE = re.compile(r"\bpython3? (\w+\.py)\b")


def _ignored_dirs():
    with open(os.path.join(REPO, ".gitignore")) as f:
        return tuple(line.strip() for line in f
                     if line.strip().endswith("/"))


def _backquoted(text):
    return FENCED.findall(text) + INLINE.findall(FENCED.sub("", text))


@pytest.mark.parametrize("doc", DOCS)
def test_every_backquoted_path_resolves(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    ignored = _ignored_dirs()
    dangling = []
    for span in _backquoted(text):
        for path in REPO_PATH.findall(span):
            # the reference's own tree, and what a run leaves behind
            if path.startswith(("deepspeed/", ) + ignored):
                continue
            if not any(os.path.exists(os.path.join(REPO, pre, path))
                       for pre in PREFIXES):
                dangling.append(path)
    assert not dangling, f"{doc} names files the tree does not hold"


def test_no_source_file_names_a_script_that_is_gone():
    sources = [os.path.join(REPO, "chip_smoke.py")]
    for top in ("deepspeed_tpu", "tools"):
        sources += glob.glob(os.path.join(REPO, top, "**", "*.py"),
                             recursive=True)
    dangling = []
    for src in sources:
        with open(src) as f:
            text = f.read()
        for path in TOOL_PATH.findall(text) + ROOT_RECIPE.findall(text):
            if not os.path.exists(os.path.join(REPO, path)):
                dangling.append((os.path.relpath(src, REPO), path))
    assert not dangling
