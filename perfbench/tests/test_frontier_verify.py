"""`frontier_verify_ms` (PR 46) on fixture spans.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

The reader sums a pass's `frontier-verify` spans (one a level boundary, as
`perfbench/adapter.py` `_read_spans` hands them on: kind, start, seconds,
depth) and takes the median of the passes' sums; a program with no such span
(the parent of PR 46, or any job under SYMMETRY) reads nothing and raises
nothing.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
NAME = "frontier_verify_ms"
# the cells whose boundaries re-read at least a million rows a pass, and the
# sharded engine's, in BENCHMARK.json's order
LISTED = ["kip320-5b-x4", "asyncisr-4b-constraint", "kip279-4b-cex",
          "kip320-5b-3p-notrace"]


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _pass(*spans):
    """A pass reduced to what the reader reads: (kind, seconds) a span."""
    return {"spans": {"spans": [[kind, 0.0, s, None] for kind, s in spans],
                      "events": []}}


V, L = "frontier-verify", "level"


@pytest.mark.parametrize("passes,ms", [
    # two boundaries a pass, summed; the spans between them are not its
    ([_pass((V, 0.002), (L, 0.5), (V, 0.025), ("host-invariants", 0.01))],
     27.0),
    # the median over passes of each pass's own sum: 5, 30, 250 ms
    ([_pass((V, 0.25)), _pass((V, 0.004), (V, 0.001)),
      _pass((V, 0.01), (V, 0.02))], 30.0),
    # the parent's spans, or a job under SYMMETRY: nothing to read
    ([_pass((L, 0.5), ("check-open", 0.01)), _pass((L, 0.4))], None),
    # a pass without the span beside one with it: that pass reads nothing
    ([_pass((L, 0.5)), _pass((V, 0.003))], 3.0),
    ([_pass()], None),
    ([], None),
])
def test_reader(passes, ms, harness):
    reader = harness.load_metric_readers()[NAME]
    got = reader.read({"passes": passes})
    assert got == (None if ms is None else pytest.approx(ms))


def test_reader_says_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()[NAME].META
    # found by name: an entry appended after this one must not move it
    (entry,) = [e for e in bench["per_layer"] if e["name"] == NAME]
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] != NAME}
    assert entry["workloads"] == LISTED
    cells = {w["name"] for w in bench["workloads"]}
    (moved,) = [e for e in bench["end_to_end"] if e["name"] == entry["moves"]]
    assert set(LISTED) <= set(moved.get("workloads", cells))
