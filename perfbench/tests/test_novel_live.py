"""`novel_live_share` (PR 37) on fixture level records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader takes the two level-record fields the compaction's counter
fills (`novel_rows`, `novel_rows_plain`), sums each over a pass's levels
and takes the median of the passes' ratios; a program whose records lack
the fields (the parent of PR 37) reads nothing and raises nothing.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CELLS = ["kip320-3b-notrace", "kip320-3b-trace", "kip320-5b-notrace",
         "kip320-5b-x4", "firsttry-3b-cex", "asyncisr-4b-constraint"]


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _pass(*levels):
    """A pass reduced to what the reader reads: one record a level,
    (novel_rows, novel_rows_plain), or None for a record without the
    fields."""
    return {"level_records": [
        {"depth": d} if lv is None else
        {"depth": d, "novel_rows": lv[0], "novel_rows_plain": lv[1]}
        for d, lv in enumerate(levels, 1)]}


T, B = 475136, 16384


@pytest.mark.parametrize("passes,share", [
    # hand-worked: a chunk of 475,136 lanes whose live prefix fills 13
    # blocks and whose new states fill 4, and one that keeps a single
    # state (a block a loop): (17 + 2) x 16,384 of 2 x 475,136 rows
    ([_pass((17 * B, T), (2 * B, T))], 100.0 * 19 * B / (2 * T)),
    # the median over passes of each pass's own ratio: 25, 50, 30 %
    ([_pass((5, 20)), _pass((10, 20)), _pass((3, 10), (3, 10))], 30.0),
    # loops that touched as many rows as the full-width compaction
    ([_pass((T, T), (T, T))], 100.0),
    # every lane live and new: both loops run the whole width
    ([_pass((2 * T, T))], 200.0),
    # the parent's records: no such fields, nothing to read
    ([_pass(None, None), _pass(None)], None),
    # a record without them anywhere in a pass: that pass reads nothing
    ([_pass((1, 4), None), _pass((1, 4))], 25.0),
    # a host-backend pass on the fused path compacts nothing on the device
    ([_pass((0, 0), (0, 0))], None),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, share, harness):
    reader = harness.load_metric_readers()["novel_live_share"]
    got = reader.read({"passes": passes})
    assert got == (None if share is None else pytest.approx(share))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()["novel_live_share"].META
    # found by name: an entry appended after this one must not move it
    (entry,) = [e for e in bench["per_layer"] if e["name"] == "novel_live_share"]
    assert entry["workloads"] == CELLS
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] != "novel_live_share"}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(entry["workloads"]) <= {c["name"] for c in bench["workloads"]}
    # every cell it lists reports the end-to-end metric it moves
    for e2e in bench["end_to_end"]:
        if e2e["name"] == entry["moves"]:
            assert set(entry["workloads"]) <= set(e2e.get("workloads", CELLS))
