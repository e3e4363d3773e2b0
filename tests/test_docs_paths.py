"""Every file a document names exists.

``README.md``, ``PERF.md``, ``ROADMAP.md`` and the files of ``docs/`` name
source files, tests, records and directories in backticks and links.  A
name that no longer resolves is a document that drifted from the tree:
PR 32 deleted a measurement stack that a dozen documents still pointed
into.  One case a document; nothing is generated.

A name resolves when it is the tail of a path in the tree (prose
abbreviates: ``optimizers.py`` for ``chainermn_tpu/optimizers.py``,
``readers/_xplane.py`` for ``cellbench/readers/_xplane.py``); a glob must
match something.  Absolute paths, URLs, placeholders and the reference
project's own files (``chainermn/...``) are not the repo's to guarantee.
"""

import fnmatch
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PERF.md", "ROADMAP.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md")
)
#: not part of the tree: scratch, caches, what runs leave behind
_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".jax_cache",
              "_archive", "_scratch", "_parent", "chiprun_out"}
#: directories a program makes when it runs (and the builders' git-
#: ignored scratch), named as such
RUNTIME = {"kv_handoff/", "result/", "checkpoints/", "profile/",
           "_scratch/"}

#: names the two records may use for what is gone, because their
#: history paragraphs say so (PR 24's, PR 32's pre-chip measurement
#: stack, PR 46's hand-set count).  No other document may.  Keep it short; a name leaves with
#: the last sentence that needs it.
_GONE = {
    "BENCH_r*.json", "VERDICT.md", "_compat.py",
    "bench.py", "docs/performance.md", "MULTICHIP_r0*.json",
    "benchmarks/perf_history.py", "tests/test_readme_count.py",
}
DELETED = {"PERF.md": _GONE, "ROADMAP.md": _GONE}

_SPAN = re.compile(r"`([^`\n]+)`")
_LINK = re.compile(r"\]\(([^)\s]+)\)")
#: a file with one of the extensions documents cite, anywhere in a
#: span (``:line`` / ``::name`` may follow it) ...
_FILE = re.compile(
    r"(?<![\w./*<>~-])((?:[\w.*-]+/)*[\w.*-]+\.(?:py|md|json))\b")
#: ... or a span that is nothing but a directory, written ``name/``
_DIRECTORY = re.compile(r"(?:[\w.-]+/)+")


def _named_paths(text):
    found = set()
    for span in _SPAN.findall(text):
        if "://" in span:
            continue
        if _DIRECTORY.fullmatch(span):
            found.add(span)
        found.update(_FILE.findall(span))
    for target in _LINK.findall(text):
        if "://" not in target and not target.startswith("#"):
            found.add(target.split("#")[0])
    # the reference project's files are upstream's
    return {name for name in found if not name.startswith("chainermn/")}


def _tree():
    paths = []
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
        rel = os.path.relpath(dirpath, REPO)
        for name in filenames + [d + "/" for d in dirnames]:
            paths.append("/" + os.path.normpath(os.path.join(rel, name))
                         + ("/" if name.endswith("/") else ""))
    return paths


def _resolves(name, tree):
    if "*" in name:
        return any(fnmatch.fnmatch(path, "*/" + name) for path in tree)
    return any(path.endswith("/" + name) for path in tree)


@pytest.fixture(scope="module")
def tree():
    return _tree()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_path_exists(document, tree):
    with open(os.path.join(REPO, document)) as f:
        named = _named_paths(f.read())
    missing = sorted(
        name for name in named - DELETED.get(document, set()) - RUNTIME
        if not _resolves(name, tree)
    )
    assert not missing, (
        f"{document} names paths that do not exist: {missing} (fix the "
        "document, or, if it names them as deleted, add them to DELETED)"
    )


def test_extractor_and_allow_list(tree):
    assert _named_paths(
        "see `ops/x.py:12`, `a/b.json` and `docs/`; not `/root/x.json`, "
        "`<checkout>/y.py`, prose like `p.send/recv/…` or "
        "`chainermn/link.py`; a [link](docs/z.md#top) and "
        "[one](https://h/w.md)"
    ) == {"ops/x.py", "a/b.json", "docs/", "docs/z.md"}
    assert _resolves("optimizers.py", tree) and _resolves("tests/", tree)
    assert _resolves("benchmarks/*.py", tree)
    assert not _resolves("ptimizers.py", tree)  # whole components only
    still_there = sorted(n for n in _GONE if _resolves(n, tree))
    assert not still_there, f"DELETED lists existing paths: {still_there}"
