"""`probe_rounds_share` (PR 31) on fixture level records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader takes the two level-record fields the probe's counter fills
(`probe_rounds`, `probe_rounds_plain`), sums each over a pass's levels and
takes the median of the passes' ratios; a program whose records lack the
fields (the parent of PR 31) reads nothing and raises nothing.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CELLS = ["kip320-3b-notrace", "kip320-3b-trace", "kip320-5b-notrace",
         "kip320-5b-x4", "firsttry-3b-cex"]


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _pass(*levels):
    """A pass reduced to what the reader reads: one record a level,
    (probe_rounds, probe_rounds_plain), or None for a record without the
    fields."""
    return {"level_records": [
        {"depth": d} if lv is None else
        {"depth": d, "probe_rounds": lv[0], "probe_rounds_plain": lv[1]}
        for d, lv in enumerate(levels, 1)]}


@pytest.mark.parametrize("passes,share", [
    # hand-worked: 0 + 2 + 6 + 14 = 22 rounds of 4 x 22 = 88: 25%
    ([_pass((0, 22), (2, 22), (6, 22), (14, 22))], 25.0),
    # the median over passes of each pass's own ratio: 25, 50, 30 %
    ([_pass((5, 20)), _pass((10, 20)), _pass((3, 10), (3, 10))], 30.0),
    # a set in one bucket: the fixed-count search's rounds, 100%
    ([_pass((22, 22), (44, 44))], 100.0),
    # the parent's records: no such fields, nothing to read
    ([_pass(None, None), _pass(None)], None),
    # a record without them anywhere in a pass: that pass reads nothing
    ([_pass((1, 4), None), _pass((1, 4))], 25.0),
    # a host-backend pass probes nothing on the device: 0 of 0
    ([_pass((0, 0), (0, 0))], None),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, share, harness):
    reader = harness.load_metric_readers()["probe_rounds_share"]
    got = reader.read({"passes": passes})
    assert got == (None if share is None else pytest.approx(share))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()["probe_rounds_share"].META
    entry = bench["per_layer"][-1]  # appended, nothing before it moved
    assert entry["name"] == "probe_rounds_share"
    assert entry["workloads"] == CELLS
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
