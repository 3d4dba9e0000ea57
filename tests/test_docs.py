"""The documents name what exists.

``README.md`` and ``PERF.md`` are read by every later session as the
account of what the repository holds and how fast it is. Two things made
the README wrong for many PRs: it named scripts and records as the way to
regenerate its numbers after they had stopped being that, and it described
workloads no cell runs. So:

- a name in back-ticks (or a word of a fenced command) that looks like a
  file — it ends in ``.py``, ``.json``, ``.md`` or ``.jsonl`` — or like a
  path — it has a slash and either ends in one, ends in a file extension
  or starts at a top-level directory — is a claim that the file exists: it
  resolves in the tree (as a path from the root or as the tail of one), or
  it is one of the run-time outputs listed here. A file that was removed
  is named without back-ticks. Gauge names (``replay/fill``), phase scopes
  (``sgd/psum``) and anything with a placeholder or a glob are not paths;
- every cell of ``BENCHMARK.json`` has its line in the README and in
  ``PERF.md`` §4.

The tests read the benchmark's files and the documents; they edit none.
"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FILE_EXTS = (".py", ".json", ".md", ".jsonl")
# what a run writes (under a session folder, the checkout or the sandbox)
# and the documents describe: not in the tree, by design
_OUTPUT_PATHS = {
    "/root/TESTS_LAST_RUN.json",          # the driver's record of its test run
    "/tmp/campaign.json",                 # `surreal_tpu chaos --out` example
    # under a session's --folder
    "config.json", "checkpoints/", "checkpoints/run_meta.json", "extra/",
    "tb/", "telemetry/events.jsonl", "exemplars.jsonl",
    "ops.json",                           # beside a profiler capture
    ".jax_cache/",                        # the checkout's compile cache
}
_SKIP_DIRS = {
    ".git", "__pycache__", ".pytest_cache", ".jax_cache", ".hypothesis",
    "chip_scratch", "chiprun_out",  # git-ignored: a builder's scratch
}
_NOT_A_PATH = re.compile(r"[\s<>*{}$|=,()\[\]\"'…]")
_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_TICKED = re.compile(r"`([^`]+)`")  # inline code may wrap over a line end


def _tree():
    files, dirs = set(), set()
    for root, subdirs, names in os.walk(REPO):
        subdirs[:] = [d for d in subdirs if d not in _SKIP_DIRS]
        rel = os.path.relpath(root, REPO)
        rel = "" if rel == "." else rel + "/"
        files.update(rel + n for n in names)
        dirs.update(rel + d + "/" for d in subdirs)
    return files, dirs


def _candidates(text):
    """Every back-ticked span outside fenced blocks, and every word of a
    fenced block."""
    for block in _FENCE.findall(text):
        yield from block.split()
    yield from _TICKED.findall(_FENCE.sub("", text))


def _as_path(token, top_dirs):
    """The file or directory a token claims, or None where it claims none."""
    token = token.strip().rstrip(".,;:")
    token = token.split("::", 1)[0]                 # file.py::name
    token = re.sub(r":\d+(-\d+)?$", "", token)      # file.py:12-34
    if token.startswith("./"):
        token = token[2:]
    if (
        not re.search(r"\w", token)
        or _NOT_A_PATH.search(token)
        or token.startswith(("-", "http"))
    ):
        return None
    if token.endswith(_FILE_EXTS):
        return token
    if "/" not in token:
        return None
    last = token.rstrip("/").rsplit("/", 1)[-1]
    if (
        token.endswith("/")
        or re.search(r"\.[A-Za-z][A-Za-z0-9]{1,5}$", last)
        or token.split("/", 1)[0] in top_dirs
    ):
        return token
    return None


def _resolves(path, files, dirs):
    if path in _OUTPUT_PATHS:
        return True
    if path.startswith("/"):
        return False  # outside the checkout, and not a listed output
    as_dir = path if path.endswith("/") else path + "/"
    return (
        path in files
        or any(f.endswith("/" + path) for f in files)
        or as_dir in dirs
        or any(d.endswith("/" + as_dir) for d in dirs)
    )


@pytest.mark.parametrize("document", ["README.md", "PERF.md"])
def test_document_names_only_files_that_exist(document):
    files, dirs = _tree()
    top_dirs = {d.rstrip("/") for d in dirs if d.count("/") == 1}
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    claimed = {
        p for p in (_as_path(t, top_dirs) for t in _candidates(text))
        if p is not None
    }
    assert len(claimed) > 20, f"the walk found only {sorted(claimed)}"
    missing = sorted(p for p in claimed if not _resolves(p, files, dirs))
    assert not missing, (
        f"{document} names files or directories that are not in the tree "
        "(name a removed file without back-ticks; list a run-time output in "
        f"_OUTPUT_PATHS): {missing}"
    )


def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_every_cell_has_its_line_in_the_documents(cell):
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert f"`{cell}`" in readme, f"README.md does not name the cell {cell}"
    m = re.search(r"^## 4\. Cells\n(.*?)(?=^## \d)", perf, re.M | re.S)
    assert m, "PERF.md has no section '## 4. Cells'"
    assert f"`{cell}`" in m.group(1), f"PERF.md §4 has no line for {cell}"
