"""`probe_window_share` (PR 45) on fixture level records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader takes the two level-record fields the level loop fills from what
it holds before a dispatch (`probes`, `probes_windowed`), sums each over a
pass's levels and takes the median of the passes' ratios; a program whose
records lack the fields (the parent of PR 45) reads nothing and raises
nothing.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
# the cell whose pinned capacity is above the window, and the one whose
# capacity IS the window (bypassed: 0), in BENCHMARK.json's order
LISTED = ["kip279-4b-cex", "kip320-5b-3p-notrace"]


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _pass(*levels):
    """A pass reduced to what the reader reads: one record a level,
    (probes_windowed, probes), or None for a record without the fields."""
    return {"level_records": [
        {"depth": d} if lv is None else
        {"depth": d, "probes_windowed": lv[0], "probes": lv[1]}
        for d, lv in enumerate(levels, 1)]}


# a warm pass of `kip320-5b-3p-notrace`: a `step` chunk in each of levels
# 1-3, a fused chunk in level 4, four in level 5, one probe a chunk, the
# capacity pinned at 16,777,216 over a set of at most 1,189,826
PRODUCT = [(1, 1)] * 4 + [(4, 4)]


@pytest.mark.parametrize("passes,share", [
    ([_pass(*PRODUCT)], 100.0),
    # no capacity above the window (`kip279-4b-cex`: 8,388,608): bypassed
    ([_pass(*[(0, n) for n in (1,) * 6 + (2, 3, 7, 14, 26)])], 0.0),
    # a set that outgrows the window inside a pass: the chunks after it
    # search the whole capacity (depth 6 of the product)
    ([_pass((1, 1), (4, 4), (150, 296))], 100.0 * 155 / 301),
    # the median over passes of each pass's own ratio: 0 (a cold pass, the
    # capacity still growing), 60, 100 %
    ([_pass((0, 5)), _pass((3, 5)), _pass((5, 5))], 60.0),
    # the parent's records: no such fields, nothing to read
    ([_pass(None, None), _pass(None)], None),
    # a record without them anywhere in a pass: that pass reads nothing
    ([_pass((1, 4), None), _pass((1, 4))], 25.0),
    # nothing probed on the device (a host backend's per-chunk path)
    ([_pass((0, 0), (0, 0))], None),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, share, harness):
    reader = harness.load_metric_readers()["probe_window_share"]
    got = reader.read({"passes": passes})
    assert got == (None if share is None else pytest.approx(share))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()["probe_window_share"].META
    # found by name: an entry appended after this one must not move it
    (entry,) = [e for e in bench["per_layer"]
                if e["name"] == "probe_window_share"]
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] != "probe_window_share"}
    assert entry["workloads"] == LISTED
    cells = {w["name"]: w for w in bench["workloads"]}
    assert all(cells[c]["chips"] == 1 for c in entry["workloads"])
    (moved,) = [e for e in bench["end_to_end"] if e["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved.get("workloads", list(cells)))
