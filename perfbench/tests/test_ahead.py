"""`ahead_share` (PR 42) on fixture level records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader takes the level record's `chunks_ahead` (the fused chunks of a
level whose guard launch went out before the previous chunk's successor
launch) and `chunks`, sums each over a pass's committed levels and takes
the median of the passes' ratios; a program whose records lack the field
(the parent of PR 42) reads nothing and raises nothing.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
ONE_CHIP = ["kip320-3b-notrace", "kip320-3b-trace", "kip320-5b-notrace",
            "firsttry-3b-cex", "asyncisr-4b-constraint",
            "kip320-5b-symmetry-notrace", "kip279-4b-cex"]


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _pass(*levels):
    """A pass reduced to what the reader reads: one record a level,
    (chunks_ahead, chunks), or an int (chunks alone: a record without the
    field)."""
    return {"level_records": [
        {"depth": d, "chunks": lv} if isinstance(lv, int) else
        {"depth": d, "chunks_ahead": lv[0], "chunks": lv[1]}
        for d, lv in enumerate(levels, 1)]}


# the committed levels of `kip279-4b-cex` (six one-chunk levels, then 2 / 3
# / 7 / 14 / 26 chunks) and of `asyncisr-4b-constraint` at depth 14
KIP279 = [(0, 1)] * 6 + [(1, 2), (2, 3), (6, 7), (13, 14), (25, 26)]
ASYNCISR = [(0, 1)] * 10 + [(1, 2), (3, 4), (6, 7), (9, 10)]


@pytest.mark.parametrize("passes,share", [
    # hand-worked: n - 1 of n in every multi-chunk level, 0 of 1 elsewhere
    ([_pass(*KIP279)], 100.0 * 47 / 58),
    ([_pass(*ASYNCISR)], 100.0 * 19 / 33),
    # the median over passes of each pass's own ratio: 50, 25, 40 %
    ([_pass((1, 2)), _pass((1, 4)), _pass((2, 5))], 40.0),
    # one-chunk levels and whole-level programs: nothing is ahead
    ([_pass((0, 1), (0, 1), (0, 3))], 0.0),
    # the parent's records: `chunks` and no `chunks_ahead`
    ([_pass(1, 2, 3), _pass(1)], None),
    # a record without it anywhere in a pass: that pass reads nothing
    ([_pass((1, 2), 3), _pass((1, 2))], 50.0),
    # a pass that streamed no chunk at all
    ([_pass((0, 0))], None),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, share, harness):
    reader = harness.load_metric_readers()["ahead_share"]
    got = reader.read({"passes": passes})
    assert got == (None if share is None else pytest.approx(share))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()["ahead_share"].META
    # found by name: an entry appended after this one must not move it
    (entry,) = [e for e in bench["per_layer"] if e["name"] == "ahead_share"]
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] != "ahead_share"}
    # the one-chip cells, in BENCHMARK.json's order; the sharded engine has
    # a loop of its own and no such field
    assert entry["workloads"] == ONE_CHIP
    cells = {w["name"]: w for w in bench["workloads"]}
    assert all(cells[c]["chips"] == 1 for c in entry["workloads"])
    verdict_s = next(e for e in bench["end_to_end"]
                     if e["name"] == "verdict_s")
    assert set(entry["workloads"]) <= set(
        verdict_s.get("workloads", list(cells)))
