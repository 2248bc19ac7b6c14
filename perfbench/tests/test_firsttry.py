"""The counterexample cell's own files (PR 30).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

The golden of `firsttry-3b` against its oracle derivation, what an uncut
pass owes of it, and the two readers the cell brings (`cex_ms`,
`cut_level_share`) on fixture records: a hand-worked value where the program
wrote the span and the record, nothing where it did not (a pass with no
verdict, or the parent's program, which has neither).
"""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
LEVELS = [1, 6, 36, 207, 837, 2244, 4563, 8991, 17307, 30030, 48150, 71769]
VIOLATION = {"invariant": "WeakIsr", "depth": 11, "trace_len": 12,
             "rendered_chars": 6728}


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _golden(name="firsttry-3b.json"):
    with open(os.path.join(PERFBENCH, "golden", name)) as fh:
        return json.load(fh)


def test_golden_equals_its_derivation():
    golden, derived = _golden(), _golden("firsttry-3b.derived.json")
    assert derived["equal_to_golden"] is True
    assert derived["levels"] == golden["levels"] == LEVELS
    assert derived["total"] == golden["total"] == sum(LEVELS) == 184141
    assert derived["violation"] == golden["violation"]["invariant"]
    assert len(derived["levels"]) - 1 == golden["violation"]["depth"]
    assert derived["invariants"] == ["TypeOk", "WeakIsr", "StrongIsr"]
    assert golden["exhaustive"] is False and golden["violation"] == VIOLATION


def test_configuration_is_the_uncut_job(harness):
    bench, cell, config, traffic, golden = harness.load_cell("firsttry-3b-cex")
    assert config["max_depth"] is None and config["reduced"] == []
    assert (config["cfg"], config["module"], config["engine"],
            config["kernel_source"], config["options"]) == (
        "configs/Kip320FirstTry.cfg", "Kip320FirstTry", "single", "hand", {})
    assert traffic["options"] == {"store_trace": True} and cell["chips"] == 1
    opts = harness.pass_options(config, traffic, traffic["jobs"][0], 4)
    assert opts == {"store_trace": True, "max_depth": None}  # rehearsed whole


def test_an_uncut_pass_owes_the_whole_golden(harness):
    want = harness.golden_for(_golden(), None)
    assert want == {"levels": LEVELS, "total": 184141, "diameter": 11,
                    "violation": VIOLATION}


@pytest.mark.parametrize("got,fails", [
    (VIOLATION, False),
    (dict(VIOLATION, rendered_chars=6700), True),   # another 12-state trace
    (dict(VIOLATION, invariant="StrongIsr"), True),
    (None, True),
])
def test_a_pass_is_held_to_every_key_of_the_violation(got, fails, harness):
    rec = {"levels": LEVELS, "total": 184141, "diameter": 11,
           "violation": got, "spans": {"spans": [], "events": []},
           "manifest": {}, "stats": {}, "jax": {"backend_compiles": 0}}
    why = harness.judge_pass(rec, harness.golden_for(_golden(), None))
    assert bool(why) == fails and all(k == "answer" for k, _ in why)


# --- the two readers ---------------------------------------------------------

def _pass(check_s, cex_s=None, cut_level_ms=None):
    """A pass as `adapter.run_pass` returns it, reduced to what the two
    readers read: spans as [kind, t0, seconds, depth]."""
    spans = [["check", 100.0, check_s, None], ["level", 100.1, 0.5, 1]]
    stats = {"visited_capacity": 4194304}
    if cex_s is not None:
        spans.append(["counterexample", 102.0, cex_s, 11])
    if cut_level_ms is not None:
        stats["cut_level"] = {"depth": 12, "frontier": 71769,
                              "level_ms": cut_level_ms}
    return {"spans": {"spans": spans, "events": []}, "stats": stats}


@pytest.mark.parametrize("passes,cex_ms,share", [
    # hand-worked: counterexample spans of 100, 300 and 200 ms; cut levels of
    # 500, 900 and 600 ms in checks of 2.0, 3.0 and 2.5 s: 25, 30 and 24 %
    ([_pass(2.0, 0.1, 500.0), _pass(3.0, 0.3, 900.0), _pass(2.5, 0.2, 600.0)],
     200.0, 25.0),
    ([_pass(2.0, 0.25, 400.0)], 250.0, 20.0),
    # no verdict in any pass, or the parent's program: nothing to read
    ([_pass(2.0), _pass(2.1)], None, None),
    # the passes that have the records are the ones read
    ([_pass(2.0), _pass(4.0, 0.5, 1000.0)], 500.0, 25.0),
    ([], None, None),
])
def test_readers(passes, cex_ms, share, harness):
    readers = harness.load_metric_readers()
    ctx = {"passes": passes}
    got_cex = readers["cex_ms"].read(ctx)
    got_share = readers["cut_level_share"].read(ctx)
    assert got_cex == (None if cex_ms is None else pytest.approx(cex_ms))
    assert got_share == (None if share is None else pytest.approx(share))


def test_readers_say_what_benchmark_json_says(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    readers = harness.load_metric_readers()
    for name in ("cex_ms", "cut_level_share"):
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert "firsttry-3b-cex" in entry["workloads"]
        assert entry["moves"] == "verdict_s"
        assert {k: readers[name].META[k] for k in entry
                if k not in ("workloads",)} == {
            k: v for k, v in entry.items() if k != "workloads"}
