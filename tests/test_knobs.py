"""docs/knobs.md lists every KSPEC_* environment variable the tree reads.

Jax-free.  One case per variable the code names (the package,
chip_smoke.py, scripts/): it has a row in the table.  One case the other
way round: the table has no row for a variable nothing names.  A PR that
adds a switch therefore says so in a file a reviewer reads, and a PR that
deletes one takes its row along.
"""

import functools
import re
from pathlib import Path

import pytest

_REPO = Path(__file__).resolve().parent.parent
_NAME = re.compile(r"KSPEC_[A-Z0-9_]+")
_ROW = re.compile(r"^\| `(KSPEC_[A-Z0-9_]+)` \|", re.M)


def _sources():
    yield _REPO / "chip_smoke.py"
    for top in ("kafka_specification_tpu", "scripts"):
        for path in sorted((_REPO / top).rglob("*")):
            if path.suffix in (".py", ".sh", ".cpp", ".h"):
                yield path


@functools.cache
def _names_in_code() -> list:
    return sorted({
        name
        for path in _sources()
        for name in _NAME.findall(path.read_text(errors="replace"))
    })


@functools.cache
def _rows() -> list:
    return _ROW.findall((_REPO / "docs" / "knobs.md").read_text())


@pytest.mark.parametrize("name", _names_in_code())
def test_variable_has_a_row_in_docs_knobs(name):
    assert name in _rows(), (
        f"{name} is read by the code and has no row in docs/knobs.md: "
        "say what it sets, where it is read and its kind there"
    )


def test_docs_knobs_has_no_stale_or_double_row():
    rows = _rows()
    assert sorted(set(rows)) == sorted(rows), "a variable has two rows"
    stale = sorted(set(rows) - set(_names_in_code()))
    assert not stale, f"docs/knobs.md rows for variables nothing reads: {stale}"
