"""Every repository path a document quotes exists.

A back-quoted token of ``README.md``, ``examples/README.md`` or a file
under ``doc/`` counts as a repository path when its first component is a
top-level directory of this repository and it ends in a source or
document suffix (a trailing ``:line`` or ``::test`` stripped), or when it
is a root-level name such as ``PERF.md`` or ``BENCH_X_r07.json``. It is
looked up from the repository root. Tokens with ``*``, ``<``, ``{`` or
``...`` are patterns and are skipped, and so is everything else (module
shorthand such as ``ops/nn.py``, the reference's ``src/...`` paths).
``PERF.md``, ``CHANGES.md``, ``ROADMAP.md`` and ``SURVEY.md`` are history
and name what was removed: they are not checked."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOP_DIRS = ("mxnet_tpu", "tests", "tools", "examples", "benchmark", "doc",
            "predict", "amalgamation", "R-package")
SUFFIXES = (".py", ".md", ".json", ".cc", ".h", ".R")
ROOT_NAME = re.compile(r"[A-Z][A-Z0-9_]*(_r\d+)?\.(json|md|jsonl)")
PATTERN_MARKS = ("*", "<", "{", "...")

DOCUMENTS = sorted(
    ["README.md", "examples/README.md"]
    + [os.path.relpath(p, REPO) for p in
       glob.glob(os.path.join(REPO, "doc", "**", "*.md"), recursive=True)])


def quoted_paths(text):
    """The back-quoted tokens of ``text`` that the rule above takes for
    repository paths, each stripped of its ``:line`` or ``::test``."""
    found = []
    for token in re.findall(r"`([^`\n]+)`", text):
        if any(mark in token for mark in PATTERN_MARKS):
            continue
        path = re.sub(r"(::.*|:\d+(-\d+)?)$", "", token.strip())
        first, _, rest = path.partition("/")
        if (rest and first in TOP_DIRS and path.endswith(SUFFIXES)) \
                or ROOT_NAME.fullmatch(path):
            found.append(path)
    return found


def test_the_rule_takes_paths_and_leaves_the_rest():
    text = ("`tests/test_comm.py::test_x` `mxnet_tpu/model.py:621` "
            "`mxnet_tpu/model.py:1086-2832` `PERF.md` `BENCH_X_r07.json` "
            "`ops/nn.py` `src/io/iter_mnist.cc` `tools/bench_*.py` "
            "`benchmark/configs/<name>.py` `doc/{a,b}.md` `fit.epoch` "
            "`MXNET_TPU_LEDGER_DIR` `python tools/launch.py -n 4`")
    assert quoted_paths(text) == [
        "tests/test_comm.py", "mxnet_tpu/model.py", "mxnet_tpu/model.py",
        "PERF.md", "BENCH_X_r07.json"]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_quoted_repository_paths_exist(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        paths = quoted_paths(f.read())
    missing = sorted({p for p in paths
                      if not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{document} quotes paths that do not exist: {missing}"
