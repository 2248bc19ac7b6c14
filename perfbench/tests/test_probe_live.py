"""`probe_live_share` (PR 41) on fixture level records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader takes the two level-record fields the probe's counter fills
(`probe_lanes`, `probe_lanes_plain`), sums each over a pass's levels and
takes the median of the passes' ratios; a program whose records lack the
fields (the parent of PR 41) reads nothing and raises nothing.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _pass(*levels):
    """A pass reduced to what the reader reads: one record a level,
    (probe_lanes, probe_lanes_plain), or None for a record without the
    fields."""
    return {"level_records": [
        {"depth": d} if lv is None else
        {"depth": d, "probe_lanes": lv[0], "probe_lanes_plain": lv[1]}
        for d, lv in enumerate(levels, 1)]}


T, B = 475136, 8192


@pytest.mark.parametrize("passes,share", [
    # hand-worked: a chunk of 475,136 lanes whose live prefix fills 26
    # blocks, and one that holds a single candidate (one block):
    # (26 + 1) x 8,192 of 2 x 475,136 lanes
    ([_pass((26 * B, T), (B, T))], 100.0 * 27 * B / (2 * T)),
    # the median over passes of each pass's own ratio: 25, 50, 30 %
    ([_pass((5, 20)), _pass((10, 20)), _pass((3, 10), (3, 10))], 30.0),
    # every lane live: the full-width search's count
    ([_pass((T, T), (T, T))], 100.0),
    # a width that is no multiple of its block, every lane live: the last
    # block starts early and searches its overlap twice
    ([_pass((13 * 7693, 100000))], 100.009),
    # a whole-level chunk's two probes and the level-new rank
    ([_pass((2 * 3 * B + B, 2 * T + 65536))], 100.0 * 7 * B / (2 * T + 65536)),
    # the parent's records: no such fields, nothing to read
    ([_pass(None, None), _pass(None)], None),
    # a record without them anywhere in a pass: that pass reads nothing
    ([_pass((1, 4), None), _pass((1, 4))], 25.0),
    # nothing probed on the device (a host backend): 0 of 0
    ([_pass((0, 0), (0, 0))], None),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, share, harness):
    reader = harness.load_metric_readers()["probe_live_share"]
    got = reader.read({"passes": passes})
    assert got == (None if share is None else pytest.approx(share))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()["probe_live_share"].META
    # found by name: an entry appended after this one must not move it
    (entry,) = [e for e in bench["per_layer"]
                if e["name"] == "probe_live_share"]
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] != "probe_live_share"}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    # every cell the benchmark had when the metric joined, and every one
    # it lists still exists and reports the end-to-end metric it moves
    cells = [c["name"] for c in bench["workloads"]]
    assert entry["workloads"] == cells[:len(entry["workloads"])]
    assert len(entry["workloads"]) >= 8
    for e2e in bench["end_to_end"]:
        if e2e["name"] == entry["moves"]:
            assert set(entry["workloads"]) <= set(e2e.get("workloads", cells))
