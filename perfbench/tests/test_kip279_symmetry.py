"""The 5-broker Kip279 cell under SYMMETRY (PR 47).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

Tier-1 runs this file too (`tests/test_kip279_symmetry_bench.py` loads it).
The golden of `kip279-5b-symmetry` against its oracle derivation and against
the orbit derivation (`.orbits.json`: the oracle's own orbit sizes, levels
0-11, sum to the unreduced job's 33,620,741 states); the configuration
against the cfg a user runs, read from `configs/` (no copy under
`perfbench/configs/`); the configuration, the cell and the two per-layer
entries this PR brought, each found BY NAME (an entry a later PR appends must
not move them), each entry held to its reader's META; the two readers on
synthetic records and a synthetic trace.
"""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CONFIG = "kip279-5b-symmetry"
CELL = "kip279-5b-symmetry-cex"
LEVELS = [1, 2, 7, 36, 167, 638, 2075, 5981, 15605, 37827, 85366, 178902,
          349573]
# the unreduced states levels 0-11 stand for (the oracle's orbit sizes)
UNREDUCED = [1, 10, 110, 1220, 9000, 46140, 173465, 537555, 1489900, 3772630,
             8765995, 18824715]
# name -> (unit, better, source, layer, moves, cells): what BENCHMARK.json
# must say
NEW = {
    "canon_us_per_candidate": (
        "us", "lower", "device_trace", "kernels", "states_per_s",
        [CELL, "kip320-5b-symmetry-notrace"]),
    "cut_candidates_share": (
        "%", "lower", "program_counter", "level loop on the host",
        "verdict_s", ["firsttry-3b-cex", "kip279-4b-cex", CELL]),
}
# the successors a pass generates into levels 1-12 (ISSUE 47's table)
GENERATED = [10, 22, 98, 487, 2139, 7620, 23395, 65299, 167765, 399558,
             881768, 1806867]


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


# --- the golden, its derivation and the orbit sizes --------------------------

def test_golden_equals_its_derivation():
    golden, derived = _golden(), _golden(".derived.json")
    assert derived["equal_to_golden"] is True
    assert derived["levels"] == golden["levels"] == LEVELS
    assert derived["total"] == golden["total"] == sum(LEVELS) == 676180
    assert derived["invariants"] == ["TypeOk", "WeakIsr", "StrongIsr"]
    # the search ends at its first violation, in the level the golden ends at
    assert golden["exhaustive"] is False
    assert golden["diameter"] == len(LEVELS) - 1 == 12
    assert golden["violation"] == {"invariant": "WeakIsr", "depth": 12,
                                   "trace_len": 13, "rendered_chars": 9716}
    assert derived["violation"] == golden["violation"]["invariant"]
    assert derived["command"].endswith("--derive kip279-5b-symmetry 99")


def test_the_orbit_derivation_counts_the_goldens_orbits():
    """`orbitderive.py` stops before a violating level: levels 0-11."""
    orbits = _golden(".orbits.json")
    assert orbits["symmetry"] == {"set": "Replicas", "order": 120}
    assert orbits["levels"] == LEVELS[:12]
    assert orbits["orbit_states"] == UNREDUCED
    assert sum(orbits["orbit_states"]) == 33620741
    assert orbits["command"].endswith("orbitderive.py kip279-5b-symmetry 11")
    # an orbit holds between 1 and 120 states
    assert all(o <= s <= 120 * o for o, s in
               zip(orbits["levels"], orbits["orbit_states"]))
    # the first levels are whole orbits of an initial state's kind: one state
    # alone, then the ten choices of a leader and an ISR member
    assert orbits["orbit_states"][:3] == [1, 10, 110]


def test_golden_is_what_a_whole_pass_owes(harness):
    golden = _golden()
    want = harness.golden_for(golden, None)
    assert want["levels"] == LEVELS and want["diameter"] == 12
    assert want["violation"]["rendered_chars"] == 9716
    rec = {"levels": LEVELS, "total": sum(LEVELS), "diameter": 12,
           "violation": dict(golden["violation"]),
           "spans": {"spans": [], "events": []}, "manifest": {}, "stats": {},
           "jax": {"backend_compiles": 0}}
    assert harness.judge_pass(rec, want) == []
    # another trace of the same length is another answer
    rec["violation"]["rendered_chars"] -= 1
    assert [w[0] for w in harness.judge_pass(rec, want)] == ["answer"]
    # and so is a pass that stored one orbit twice
    rec["violation"] = dict(golden["violation"])
    rec["levels"] = LEVELS[:12] + [349574]
    assert [w[0] for w in harness.judge_pass(rec, want)] == ["answer"]


# --- the configuration, the cell and the cfg a user runs ---------------------

def test_configuration_is_the_cfg_a_user_runs(harness):
    bench, cell, config, traffic, golden = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "exhaustive-trace", 1)
    assert (config["module"], config["engine"], config["kernel_source"],
            config["options"], config["reduced"], config["max_depth"],
            config["chips"]) == ("MCKip279", "single", "hand", {}, [], None,
                                 1)
    # read where a user reads it: no second copy under perfbench/configs/
    assert config["cfg"] == "configs/MCKip279FiveBroker.cfg"
    assert not os.path.exists(
        os.path.join(PERFBENCH, "configs", "MCKip279FiveBroker.cfg"))
    from kafka_specification_tpu.utils.cfg import (
        CFG_MODULE_ALIASES, parse_cfg)

    tlc = parse_cfg(os.path.join(ROOT, config["cfg"]))
    assert tlc.symmetry == "Symm" == config["symmetry"]["operator"]
    assert config["symmetry"] == {"operator": "Symm", "set": "Replicas",
                                  "order": 120}
    assert {k: (len(v) if isinstance(v, list) else v)
            for k, v in tlc.constants.items()} == config["constants"]
    assert tlc.invariants == config["invariants"]
    assert CFG_MODULE_ALIASES["MCKip279FiveBroker"] == config["module"]
    with open(os.path.join(ROOT, config["cfg"])) as fh:
        header = fh.read()
    assert "check configs/MCKip279FiveBroker.cfg" in header
    assert "--module MCKip279\n" in header and "MODULE MCKip279" in header
    # kip279-4b's job with one more replica and the stanza's consequences
    four = _json(PERFBENCH, "configs", "kip279-4b.json")
    assert four["constants"] == dict(config["constants"], Replicas=4)
    assert four["invariants"] == config["invariants"]
    assert four["options"] == config["options"] == {}
    assert traffic["options"] == {"store_trace": True}
    # Partitions is no constant of this cfg: said, not listed as a cut
    assert set(config["cut"]) == {"nothing", "Partitions"}
    assert "not a constant of this cfg" in config["cut"]["Partitions"]
    assert "Partitions" not in tlc.constants
    assert set(config["assumed"]) == {"Replicas", "module", "kernel_source",
                                      "warm_protocol"}
    assert set(config["guarantees"]) == {"search", "invariants", "counts",
                                         "counterexample", "degradations"}
    assert "orbits under the full group, exact" in \
        config["guarantees"]["counts"]
    assert "UNREDUCED spec" in config["guarantees"]["counterexample"]
    assert golden["config"] == CONFIG


def test_configuration_and_cell_are_found_by_name(bench):
    conf = _by_name(bench["configs"], CONFIG)
    assert conf["file"] == "perfbench/configs/kip279-5b-symmetry.json"
    assert conf["reduced"] == []
    on_file = _json(ROOT, conf["file"])
    assert (on_file["name"], on_file["source"], on_file["reduced"]) == (
        CONFIG, conf["source"], [])
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    for part in ("Kip279.tla:53-62", "MCKip279", "Specifying Systems 14.3.4",
                 "configs/MCKip279FiveBroker.cfg"):
        assert part in conf["source"], part
    cell = _by_name(bench["workloads"], CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and "orbits" in cell["why"]
    # one cell of this configuration, and no other configuration's file
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in bench["configs"]
            if c["file"] == conf["file"]] == [CONFIG]
    # the other two halves exist: the symmetry with no verdict, the verdict
    # with no symmetry
    names = {w["name"] for w in bench["workloads"]}
    assert {"kip320-5b-symmetry-notrace", "kip279-4b-cex"} <= names


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_says_what_its_reader_says(name, readers, bench):
    meta = readers[name].META
    entry = _by_name(bench["per_layer"], name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cells this PR listed, in its order (a later PR may append)
    assert entry["workloads"][:len(NEW[name][5])] == NEW[name][5]
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == NEW[name][:5]
    # a layer BENCHMARK.json already named, letter for letter
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] not in NEW}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert meta["what"]


def test_what_a_traced_run_of_the_cell_is_asked_for(readers, bench):
    """`Cell.per_layer_metrics` asks a reader when its entry lists the cell
    or lists none: the two this PR brought and the accepted metrics with no
    list."""
    asked = {e["name"] for e in bench["per_layer"]
             if e["name"] in readers and CELL in e.get("workloads", [CELL])}
    assert set(NEW) <= asked
    assert {"host_share", "ms_per_level", "launches_per_level",
            "step_us_per_state", "bytes_roofline_share", "device_idle_share",
            "peak_hbm_MiB", "programs", "program_load_s",
            "window_retrace_s"} <= asked
    # every cell reports setup_s, one more end-to-end metric, one per-layer
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])} >= {
        "setup_s", "verdict_s", "states_per_s"}


# --- cut_candidates_share on synthetic records -------------------------------

def _pass(levels, cut):
    """Level records of `levels` candidates each; `cut`: the cut level's
    candidates, None for a pass with no verdict, "old" for a record without
    the field (the parent of PR 47)."""
    stats = {}
    if cut == "old":
        stats["cut_level"] = {"chunks_committed": 2, "canon_rows": 652720}
    elif cut is not None:
        stats["cut_level"] = {"chunks_committed": 2,
                              "enabled_candidates": cut}
    return {"level_records": [{"depth": d, "enabled_candidates": n}
                              for d, n in enumerate(levels, 1)],
            "stats": stats}


# the cell's pass: 3,355,028 candidates into levels 1-12, and the two chunks
# the cut level ran
CELL_PASS = _pass(GENERATED, 644375)


@pytest.mark.parametrize("passes,want", [
    ([CELL_PASS] * 3, 100.0 * 644375 / (3355028 + 644375)),
    ([_pass([10, 30], 60)], 60.0),
    # the median over passes of each pass's own share: 50, 25, 20 %
    ([_pass([5], 5), _pass([30], 10), _pass([8], 2)], 25.0),
    # a pass with no verdict owes nothing
    ([_pass([5], 5), _pass([30], None)], 50.0),
    ([_pass([30], None)], None),
    # the parent's record: no such field, nothing to read
    ([_pass(GENERATED, "old")] * 3, None),
    # nothing generated anywhere: 0 of 0 is nothing to read
    ([_pass([0], 0)], None),
    ([], None),
], ids=["cell", "one", "median", "skips-a-pass", "no-verdict", "parent",
        "zero", "empty"])
def test_cut_candidates_share(readers, passes, want):
    got = readers["cut_candidates_share"].read({"passes": passes})
    assert got == pytest.approx(want) if want is not None else got is None


def test_cut_candidates_share_of_the_cells_pass(readers):
    assert sum(GENERATED) == 3355028
    # 644,375 candidates in the two chunks the cut level ran (its canon_rows,
    # 652,720, are blocks run x block size: ISSUE 47 reckoned with those)
    assert 100.0 * 644375 / 3999403 == pytest.approx(16.1118, abs=1e-4)
    # a level record without the count: that pass reads nothing
    p = _pass([10, 30], 60)
    del p["level_records"][0]["enabled_candidates"]
    assert readers["cut_candidates_share"].read({"passes": [p]}) is None


# --- canon_us_per_candidate on a synthetic trace ------------------------------

def _op(name, kind, shape="u32[8]{0}"):
    return f"%{name} = {shape} {kind}({shape} %p), calls=%c"


def _trace(canon=True):
    """A pass of two levels: 1,500 ns of leaf operations under
    `kspec.canon` inside a fused successor program, 1,000 ns elsewhere;
    `canon` False: a program with no symmetry."""
    scope = "kspec.canon" if canon else "kspec.fingerprint"
    dev = [
        ["%while.1 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), "
         "condition=%c, body=%b", 1000, 3000, f"jit(fsc_n2)/{scope}/while:"],
        [_op("gather.1", "gather"), 1100, 400,
         f"jit(fsc_n2)/{scope}/while/body/gather:"],
        [_op("fusion.2", "fusion"), 1600, 800,
         f"jit(fsc_n2)/{scope}/while/body/while/body/or:"],
        [_op("fusion.3", "fusion"), 4200, 300, f"jit(fsc_n2)/{scope}/xor:"],
        [_op("fusion.6", "fusion"), 4800, 900,
         "jit(fsc_n2)/kspec.dedup_probe/while/body/gather:"],
        [_op("fusion.7", "fusion"), 5800, 100,
         "jit(fsc_n2)/kspec.fingerprint/xor:"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["perfbench.pass", 1000, 8000, ""],
            ["kspec.level d=3", 1050, 2950, ""],
            ["kspec.level d=4", 4100, 3900, ""]]}]}]}


@pytest.mark.parametrize("cut,want_candidates", [
    (None, 500),                         # kip320-5b-symmetry-notrace: no cut
    ({"enabled_candidates": 250}, 750),  # the cut level's are in the sum
    ({"canon_rows": 256}, None),         # the parent's record: nothing
], ids=["no-verdict", "cut-level-included", "parent-record"])
def test_canon_us_per_candidate_on_the_synthetic_trace(
        harness, readers, tmp_path, monkeypatch, cut, want_candidates):
    import canonreduce
    import stagereduce

    run_dir = tmp_path / "run"
    (tmp_path / "trace").mkdir()
    xplane = str(tmp_path / "trace" / "t.xplane.pb")
    records = [{"enabled_candidates": 100}, {"enabled_candidates": 400}]
    ctx = {"traced": {"manifest": {"dir": str(run_dir)}, "total": 500,
                      "spans": {"spans": []}, "level_records": records,
                      "stats": {"cut_level": cut} if cut else {}},
           "lanes": 5, "peaks": {"hbm_bytes_per_s": 819e9}}
    for canon in (True, False):
        stagereduce._CACHE.clear()
        canonreduce._CACHE.clear()
        monkeypatch.setattr(stagereduce, "find_xplane", lambda d: xplane)
        monkeypatch.setattr(canonreduce, "find_xplane", lambda d: xplane)
        monkeypatch.setattr(stagereduce, "load_xplane",
                            lambda p, c=canon: _trace(c))
        got = readers["canon_us_per_candidate"].read(ctx)
        if not canon or want_candidates is None:
            assert got is None
        else:
            assert got == pytest.approx(1500e-9 * 1e6 / want_candidates)
    stagereduce._CACHE.clear()
    canonreduce._CACHE.clear()


def test_canon_us_per_candidate_reads_nothing_without_a_traced_pass(readers):
    assert readers["canon_us_per_candidate"].read(
        {"traced": None, "rehearsal": False}) is None
    assert readers["canon_us_per_candidate"].read(
        {"traced": {"manifest": {}}, "rehearsal": True}) is None
