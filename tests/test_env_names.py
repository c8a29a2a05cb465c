"""The ``DLROVER_TPU_*`` name space, held to three rules.

- A name that was deleted stays deleted: the ten names PR 47 removed
  (and its launcher flag) occur nowhere in the tree but in the
  records of their removal.
- A name a document's table row (or the README) gives an operator is
  still read by the library — a row for a name nothing reads is a
  switch that does nothing (``docs/flywheel.md`` carried one).
- The count of distinct names is a ratchet: a PR that adds a name has
  to edit ``NAME_COUNT`` here, where a reviewer sees it.

The names are taken the way ``ROADMAP.md`` takes them:
``grep -rhoE "DLROVER_TPU_[A-Z0-9_]+" dlrover_tpu | sort -u``.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"DLROVER_TPU_[A-Z0-9_]+")

#: distinct names under ``dlrover_tpu/`` (132 before PR 47)
NAME_COUNT = 122

#: removed by PR 47 with the older implementation each one selected
DELETED = (
    "DLROVER_TPU_INPUT_PIPELINE",
    "DLROVER_TPU_CONTROL_LONGPOLL",
    "DLROVER_TPU_CONTROL_BATCH",
    "DLROVER_TPU_DATASTORE_SYNC",
    "DLROVER_TPU_OBSERVATORY",
    "DLROVER_TPU_RESHARD",
    "DLROVER_TPU_SELF_OBS",
    "DLROVER_TPU_MASTER_FAILOVER",
    "DLROVER_TPU_RESTART_OVERLAP",
    "DLROVER_TPU_FLYWHEEL_DRAFT",
    "no_restart_overlap",
)

#: where a deleted name may still stand: the records of the removal,
#: this list, and the driver's own ledger (rewritten every session)
HISTORY = {
    "CHANGES.md",
    "ROADMAP.md",
    "ISSUE.md",
    "PERF_LEDGER.jsonl",
    os.path.join("tests", "test_env_names.py"),
}

#: what building, testing and running leave behind (``.gitignore``)
SKIP_DIRS = {
    ".git", "__pycache__", ".cache", ".pytest_cache", ".hypothesis",
    "chiprun_out", "parent_checkout", "parent_overlay",
    "proof_checkout", "build",
}

#: documented names that a harness outside the library reads
READ_OUTSIDE_THE_LIBRARY = {
    "DLROVER_TPU_BENCH_BUDGET_S": "bench.py",
}


def _read(path: str) -> str:
    with open(path, errors="ignore") as f:
        return f.read()


def _files(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        for name in filenames:
            if not name.endswith(".pyc"):
                yield os.path.join(dirpath, name)


@functools.lru_cache(maxsize=None)
def _tree_text():
    """{path relative to the repo: text} for every file git would
    commit (and whatever else lies about outside the skipped dirs)."""
    return {
        os.path.relpath(path, REPO): _read(path)
        for path in _files(REPO)
        if os.path.getsize(path) < 4 * 1024 * 1024
    }


@functools.lru_cache(maxsize=None)
def _library_names():
    found = set()
    for rel, text in _tree_text().items():
        if rel.startswith("dlrover_tpu" + os.sep):
            found.update(NAME.findall(text))
    return frozenset(found)


def _documented_names():
    """Names an operator is handed: every ``DLROVER_TPU_*`` in a table
    row of ``docs/*.md`` and anywhere in the README."""
    found = set()
    for path in glob.glob(os.path.join(REPO, "docs", "*.md")):
        for line in _read(path).splitlines():
            if line.lstrip().startswith("|"):
                found.update(NAME.findall(line))
    found.update(NAME.findall(_read(os.path.join(REPO, "README.md"))))
    return sorted(found)


@pytest.mark.parametrize("name", DELETED)
def test_deleted_name_stays_deleted(name):
    word = re.compile(r"(?<![A-Za-z0-9_])" + name + r"(?![A-Z0-9a-z_])")
    hits = sorted(
        rel
        for rel, text in _tree_text().items()
        if rel not in HISTORY and word.search(text)
    )
    assert hits == [], f"{name} is back in {hits}"


@pytest.mark.parametrize("name", _documented_names())
def test_documented_name_is_read(name):
    if name in READ_OUTSIDE_THE_LIBRARY:
        reader = READ_OUTSIDE_THE_LIBRARY[name]
        assert name in _tree_text()[reader]
        return
    names = _library_names()
    if name.endswith("_"):
        # a row for a family, written `DLROVER_TPU_FLEET_*`
        assert any(n.startswith(name) and n != name for n in names), (
            f"docs name the family {name}* and nothing reads a member"
        )
        return
    assert name in names, (
        f"docs hand an operator {name}; nothing under dlrover_tpu/ "
        "reads it"
    )


@pytest.mark.parametrize("root, count", [("dlrover_tpu", NAME_COUNT)])
def test_name_count_is_a_ratchet(root, count):
    names = _library_names()
    assert len(names) == count, (
        f"{len(names)} distinct DLROVER_TPU_* names under {root}/, "
        f"{count} on record: a new name edits NAME_COUNT in this file "
        "(and says why in CHANGES.md); a removed one lowers it"
    )
