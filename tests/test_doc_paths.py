"""Every repo path a user-facing document names exists in the work tree.

A document that tells its reader to open or run a file that is gone is
worse than no document. One case per document, so a deletion that leaves
a dangling name fails on exactly the documents that still carry it.
PERF.md, ROADMAP.md, CHANGES.md and ADVICE.md are history — they name
what was deleted on purpose — and are not checked.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md", "PARITY.md", ".claude/skills/verify/SKILL.md"]
    + sorted(os.path.join("docs", name)
             for name in os.listdir(os.path.join(REPO, "docs"))
             if name.endswith(".md")))

# Where a bare or partial path may be rooted, besides the document's own
# directory.
ROOTS = ("", "byteps_tpu", "byteps_tpu/core", "byteps_tpu/core/csrc")
SUFFIXES = (".py", ".json", ".md", ".cc", ".h", ".sh", ".toml")
SKIP_DIRS = {".git", "__pycache__", ".jax_cache", ".pytest_cache",
             "chiprun_out", "chiprun_archive", ".benchmark_out"}

# Names that are not files of this repo.
NOT_OURS = {
    # the reference implementation's sources, named for parity
    # (PARITY.md, docs/rationale.md)
    "common.cc", "communicator.cc", "core_loops.cc", "global.cc",
    "logging.cc", "nccl_manager.cc", "operations.cc", "ready_table.cc",
    "scheduled_queue.cc", "shared_memory.cc",
    # what a documented command reads or writes: the user's own script
    # (docs/step-by-step-tutorial.md) and the merged dumps the monitor
    # tools are told to write (docs/timeline.md, docs/troubleshooting.md)
    "train.py", "fleet.json", "flight_fleet.json",
}


def _named_paths(text):
    """Back-quoted tokens that end in a source or data suffix, with a
    trailing ``:line`` or ``::name`` stripped. Globs and <placeholders>
    name no one file; a token with a space is a command, and its words
    are looked at one by one."""
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for token in quoted.split():
            token = re.sub(r"(::[\w\[\]\-.]+|:\d+(-\d+)?)+$", "", token)
            token = token.strip("()[],;'\"")
            if any(c in token for c in "*<>{}$") or "://" in token:
                continue
            if token.endswith(SUFFIXES):
                yield token


def _exists(token, doc_dir, tree_names):
    if any(os.path.exists(os.path.join(REPO, root, token))
           for root in (*ROOTS, doc_dir)):
        return True
    return "/" not in token and token in tree_names


@pytest.fixture(scope="module")
def tree_names():
    """Every file name in the work tree, for tokens that name a file
    without its directory."""
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        names.update(files)
    return names


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_every_named_path_exists(doc, tree_names):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    named = set(_named_paths(text))
    missing = sorted(t for t in named
                     if os.path.basename(t) not in NOT_OURS
                     and not _exists(t, os.path.dirname(doc), tree_names))
    assert not missing, (
        f"{doc} names files that are not in the tree: {missing} — fix the "
        f"document, or list a name that is not this repo's in NOT_OURS")
