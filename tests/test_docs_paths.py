"""The documents describe the tree as it is, and every config flag does
something.

1. Every repo path or file name a document puts in backticks exists: a
   README that sends its reader to a deleted script is worse than none.
2. Every key of ``_FLAG_DEFS`` is read by some module of ``ray_tpu/``:
   each flag is an ``RAY_TPU_<NAME>`` an operator can set, and one that
   nothing reads is set to no effect.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: a backticked token is held to the tree when it starts in one of the
#: repo's directories, or is a bare file name (a ``.json`` apart: a run-time
#: file such as a checkpoint's manifest is written the same way)
_ROOTED = re.compile(r"^(ray_tpu|cells|benchmarks|tests|docs|examples)/")
_BARE = re.compile(r"^[A-Za-z_][\w.-]*\.(py|md|jsonl|toml)$")


def _repo_paths(text):
    for token in re.findall(r"`([^`\n]+)`", text):
        token = re.sub(r"(::[\w.\[\]-]+|:[\d,:-]+)$", "", token.strip())
        if " " in token or re.search(r"[*?<>{}$]|\.\.\.|…", token):
            continue  # a command, a glob or a placeholder
        if _ROOTED.match(token) or _BARE.match(token):
            yield token


@functools.lru_cache(maxsize=None)
def _file_names():
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        names.update(files)
    return names


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_paths_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        named = sorted(set(_repo_paths(f.read())))
    assert named, f"{doc} names no path of the repo: the reader has no way in"
    # a path is written from the repo's root or, in docs/, from beside the
    # document; a bare name stands for a file somewhere in the tree
    bases = (REPO, os.path.dirname(os.path.join(REPO, doc)))
    names = _file_names()
    missing = [p for p in named
               if not (p in names if "/" not in p else any(
                   os.path.exists(os.path.join(b, p)) for b in bases))]
    assert not missing, f"{doc} names paths that do not exist: {missing}"


def test_every_flag_is_read():
    from ray_tpu._private.config import _FLAG_DEFS

    sources = []
    for root, _, files in os.walk(os.path.join(REPO, "ray_tpu")):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and not path.endswith(
                    os.path.join("_private", "config.py")):
                with open(path) as f:
                    sources.append(f.read())
    text = "\n".join(sources)
    unread = [k for k in _FLAG_DEFS
              if not re.search(rf"\b{re.escape(k)}\b", text)]
    assert not unread, f"flags nothing in ray_tpu/ reads: {unread}"
