"""README.md and docs/ name only files that exist.

Every repository path a document names in backticks (`scripts/...`,
`docs/...`, `tests/...`, `etcd_tpu/...`, `benchmark/...`, a top-level
`*.py` / `*.json` / `*.md`) must be in the tree: a document that points a
new reader at a deleted script or record costs them the time to find out.
"""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_TICKED = re.compile(r"`([^`\n]+)`")
# a path under one of the repository's directories, or a top-level file
_PATH = re.compile(
    r"^(?:\./)?((?:scripts|docs|tests|etcd_tpu|benchmark)/[\w./\-]+"
    r"|[\w\-]+\.(?:py|json|jsonl|md))$")


def named_paths(text):
    """The repository paths `text` names in backticks, word by word (so
    `python x.py --flag` names x.py), without a `::test` suffix; globs
    and placeholders are left out."""
    out = set()
    for run in _TICKED.findall(text):
        for word in run.split():
            word = word.split("::")[0].rstrip(".,;:")
            m = _PATH.match(word)
            if m and not re.search(r"[*<>{}]|\.\.\.", word):
                out.add(m.group(1))
    return sorted(out)


def _basenames():
    """File names anywhere under the directories a document may name: a
    bare `tenants.py` is shorthand for the one file of that name."""
    names = set(os.listdir(REPO))
    for top in ("scripts", "docs", "tests", "etcd_tpu", "benchmark"):
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    return names


def test_the_extractor_finds_paths_in_commands_and_prose():
    assert named_paths("run `python gone.py --x`, see `docs/gone.md`, "
                       "`tests/test_a.py::test_b` and `a/b.py`") == [
        "docs/gone.md", "gone.py", "tests/test_a.py"]


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_the_document_names_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        paths = named_paths(f.read())
    known = _basenames()
    missing = [p for p in paths
               if not (os.path.exists(os.path.join(REPO, p))
                       or ("/" not in p and p in known))]
    assert not missing, f"{doc} names files that do not exist: {missing}"
