"""The Kip279 4-broker cell's own files (PR 40).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

Tier-1 runs this file too (`tests/test_kip279_bench.py` loads it).  The
golden of `kip279-4b` against its oracle derivation; the configuration
against the cfg a user runs, which is read from `configs/` (no copy under
`perfbench/configs/`); the configuration, the cell and the two per-layer
entries this cell brought, each found BY NAME (an entry a later PR appends
must not move them), each entry held to its reader's META; the two readers
on synthetic records; the cell on no `workloads` list that was there.
"""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CONFIG = "kip279-4b"
CELL = "kip279-4b-cex"
LEVELS = [1, 8, 68, 572, 3276, 12796, 36560, 89344, 203892, 431340, 842240,
          1527204]
# name -> (unit, better, source, layer, moves): what BENCHMARK.json must say
NEW = {
    "cut_chunks_committed": ("count", "lower", "program_counter",
                             "level loop on the host", "verdict_s"),
    "level_programs": ("count", "lower", "program_counter",
                       "compile and shape ladder", "setup_s"),
}
# the ten accepted metrics that list no cells: every cell reports them
UNLISTED = {"host_share", "ms_per_level", "launches_per_level",
            "step_us_per_state", "bytes_roofline_share", "device_idle_share",
            "peak_hbm_MiB", "programs", "program_load_s", "window_retrace_s"}


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


@pytest.fixture
def bench(harness):
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture
def readers(harness):
    return harness.load_metric_readers()


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _golden(suffix=".json"):
    return _json(PERFBENCH, "golden", CONFIG + suffix)


def _by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


# --- the golden and its derivation -------------------------------------------

def test_golden_equals_its_derivation():
    golden, derived = _golden(), _golden(".derived.json")
    assert derived["equal_to_golden"] is True
    assert derived["levels"] == golden["levels"] == LEVELS
    assert derived["total"] == golden["total"] == sum(LEVELS) == 3147301
    assert derived["invariants"] == ["TypeOk", "WeakIsr", "StrongIsr"]
    # the search ends at its first violation, in the level the golden ends at
    assert golden["exhaustive"] is False
    assert golden["diameter"] == len(LEVELS) - 1 == 11
    assert golden["violation"] == {"invariant": "WeakIsr", "depth": 11,
                                   "trace_len": 12, "rendered_chars": 7763}
    assert derived["violation"] == golden["violation"]["invariant"]
    assert derived["command"].endswith("--derive kip279-4b 99")


def test_golden_is_what_a_whole_pass_owes(harness):
    golden = _golden()
    want = harness.golden_for(golden, None)
    assert want["levels"] == LEVELS and want["diameter"] == 11
    assert want["violation"]["rendered_chars"] == 7763
    rec = {"levels": LEVELS, "total": sum(LEVELS), "diameter": 11,
           "violation": dict(golden["violation"]),
           "spans": {"spans": [], "events": []}, "manifest": {}, "stats": {},
           "jax": {"backend_compiles": 0}}
    assert harness.judge_pass(rec, want) == []
    # another trace of the same length is another answer
    rec["violation"]["rendered_chars"] -= 1
    assert [w[0] for w in harness.judge_pass(rec, want)] == ["answer"]


# --- the configuration, the cell and the cfg a user runs ---------------------

def test_configuration_is_the_cfg_a_user_runs(harness):
    bench, cell, config, traffic, golden = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "exhaustive-trace", 1)
    assert (config["module"], config["engine"], config["kernel_source"],
            config["options"], config["reduced"], config["max_depth"],
            config["chips"]) == ("Kip279", "single", "hand", {}, [], None, 1)
    # read where a user reads it: no second copy under perfbench/configs/
    assert config["cfg"] == "configs/Kip279FourBroker.cfg"
    assert not os.path.exists(
        os.path.join(PERFBENCH, "configs", "Kip279FourBroker.cfg"))
    from kafka_specification_tpu.utils.cfg import (
        CFG_MODULE_ALIASES, parse_cfg)

    tlc = parse_cfg(os.path.join(ROOT, config["cfg"]))
    assert {k: (len(v) if isinstance(v, list) else v)
            for k, v in tlc.constants.items()} == config["constants"]
    assert tlc.invariants == config["invariants"]
    assert CFG_MODULE_ALIASES["Kip279FourBroker"] == config["module"]
    with open(os.path.join(ROOT, config["cfg"])) as fh:
        assert "check configs/Kip279FourBroker.cfg" in fh.read()
    assert traffic["options"] == {"store_trace": True}
    assert set(config["cut"]) == {"nothing"}
    assert set(config["assumed"]) == {"Replicas", "kernel_source",
                                      "warm_protocol"}
    assert set(config["guarantees"]) == {"search", "invariants", "counts",
                                         "counterexample", "degradations"}
    assert golden["config"] == CONFIG


def test_configuration_and_cell_are_found_by_name(bench):
    conf = _by_name(bench["configs"], CONFIG)
    assert conf["file"] == "perfbench/configs/kip279-4b.json"
    assert conf["reduced"] == []
    on_file = _json(ROOT, conf["file"])
    assert (on_file["name"], on_file["source"], on_file["reduced"]) == (
        CONFIG, conf["source"], [])
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert "Kip279.tla:53-62" in conf["source"]
    cell = _by_name(bench["workloads"], CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200
    # one cell of this configuration, and no other configuration's file
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in bench["configs"]
            if c["file"] == conf["file"]] == [CONFIG]


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_says_what_its_reader_says(name, readers, bench):
    meta = readers[name].META
    entry = _by_name(bench["per_layer"], name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [CELL]
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == NEW[name]
    # a layer BENCHMARK.json already named, letter for letter
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] not in NEW}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert meta["what"]


def test_the_cell_is_on_no_list_that_was_there(bench):
    listed = [e["name"] for e in bench["per_layer"]
              if CELL in e.get("workloads", [])]
    assert listed == sorted(NEW)
    # and it reports the accepted metrics that list no cells
    assert {e["name"] for e in bench["per_layer"]
            if "workloads" not in e} == UNLISTED


def test_what_a_traced_run_of_the_cell_is_asked_for(readers, bench):
    """`Cell.per_layer_metrics` asks a reader when its entry lists the cell
    or lists none: the ten accepted metrics with no list, and the two."""
    asked = [e["name"] for e in bench["per_layer"]
             if e["name"] in readers
             and CELL in e.get("workloads", [CELL])]
    assert set(asked) == UNLISTED | set(NEW) and len(asked) == 12
    assert {n: readers[n].read(_ctx()) for n in NEW} == {
        "cut_chunks_committed": 4, "level_programs": 30}


# --- the two readers on synthetic records ------------------------------------

def _pass(chunks_committed):
    stats = {}
    if chunks_committed is not None:
        stats["cut_level"] = {"chunks_committed": chunks_committed,
                              "chunks_discarded": 1, "frontier": 1527204}
    return {"stats": stats, "spans": {"spans": [], "events": []}}


def _ctx(passes=(4, 4, 4), setup=None):
    return {"passes": [_pass(n) for n in passes],
            "setup": {"compile_spans": 19, "rewarmed_variants": 11}
            if setup is None else setup}


@pytest.mark.parametrize("passes,want", [
    ((4, 4, 4), 4),          # the cell: the verdict's chunk is the fourth
    ((1, 1, 1, 1), 1),       # firsttry-3b-cex: the row is in chunk 0
    ((4, 4, 5), 4),          # a median over the passes
    ((4, None, 4), 4),       # a pass with no verdict owes nothing
    ((None, None), None),    # no verdict in any pass: nothing to read
    ((), None),
], ids=["cell", "chunk-0", "median", "skips-a-pass", "no-verdict", "empty"])
def test_cut_chunks_committed(readers, passes, want):
    assert readers["cut_chunks_committed"].read(_ctx(passes)) == want


def test_cut_chunks_committed_reads_nothing_on_a_record_without_the_field(
        readers):
    ctx = _ctx()
    for p in ctx["passes"]:
        del p["stats"]["cut_level"]["chunks_committed"]
    assert readers["cut_chunks_committed"].read(ctx) is None
    ctx["passes"][0]["stats"]["cut_level"] = None
    assert readers["cut_chunks_committed"].read(ctx) is None


@pytest.mark.parametrize("setup,want", [
    ({"compile_spans": 19, "rewarmed_variants": 11}, 30),   # this cell
    ({"compile_spans": 18, "rewarmed_variants": 9}, 27),    # asyncisr-4b
    ({"compile_spans": 0, "rewarmed_variants": 0}, 0),
    ({"compile_spans": 19}, None),
    ({"rewarmed_variants": 11}, None),
    ({}, None),
], ids=["cell", "asyncisr-4b", "zero", "no-rewarm-field", "no-span-field",
        "empty"])
def test_level_programs(readers, setup, want):
    assert readers["level_programs"].read(_ctx(setup=setup)) == want
