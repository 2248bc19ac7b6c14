"""`merge_live_share` (PR 33) on fixture level records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader takes the two level-record fields the merge's counter fills
(`merge_slots`, `merge_slots_plain`), sums each over a pass's levels and
takes the median of the passes' ratios; a program whose records lack the
fields (the parent of PR 33) reads nothing and raises nothing.
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
    (merge_slots, merge_slots_plain), or None for a record without the
    fields."""
    return {"level_records": [
        {"depth": d} if lv is None else
        {"depth": d, "merge_slots": lv[0], "merge_slots_plain": lv[1]}
        for d, lv in enumerate(levels, 1)]}


CAP, M, B = 4194304, 475136, 65536


@pytest.mark.parametrize("passes,share", [
    # hand-worked: three one-block merges and one of three blocks + one,
    # (1 + 1 + 1 + 4) x 65,536 of 4 x 4,669,440 slots: 2.4561...%
    ([_pass((B, CAP + M), (B, CAP + M), (B, CAP + M), (4 * B, CAP + M))],
     100.0 * 7 * B / (4 * (CAP + M))),
    # the median over passes of each pass's own ratio: 25, 50, 30 %
    ([_pass((5, 20)), _pass((10, 20)), _pass((3, 10), (3, 10))], 30.0),
    # one-block sets with a full block of new entries: the capacity-wide form
    ([_pass((2 * B, 2 * B), (4 * B, 4 * B))], 100.0),
    # the parent's records: no such fields, nothing to read
    ([_pass(None, None), _pass(None)], None),
    # a record without them anywhere in a pass: that pass reads nothing
    ([_pass((1, 4), None), _pass((1, 4))], 25.0),
    # a host-backend pass on the fused path merges nothing on the device
    ([_pass((0, 0), (0, 0))], None),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, share, harness):
    reader = harness.load_metric_readers()["merge_live_share"]
    got = reader.read({"passes": passes})
    assert got == (None if share is None else pytest.approx(share))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()["merge_live_share"].META
    # found by name: an entry appended after this one must not move it
    (entry,) = [e for e in bench["per_layer"] if e["name"] == "merge_live_share"]
    assert entry["workloads"] == CELLS
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(entry["workloads"]) <= {c["name"] for c in bench["workloads"]}
    # every cell it lists reports the end-to-end metric it moves
    for e2e in bench["end_to_end"]:
        if e2e["name"] == entry["moves"]:
            assert set(entry["workloads"]) <= set(e2e.get("workloads", CELLS))
