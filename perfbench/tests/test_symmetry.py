"""The SYMMETRY cell's own files (PR 38).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

Tier-1 runs this file too (`tests/test_symmetry_cell.py` loads it).  The
golden of `kip320-5b-symmetry` against its oracle derivation; the oracle's
orbit sizes against the UNREDUCED job's counts, level by level (the
oracle-derived `kip320-5b.json` for levels 0-10, the banked
`RUN5R_r5_stats.jsonl` for 11-15); the configuration against the cfg a user
runs, and the two copies of that cfg; each of the five per-layer entries,
found BY NAME, against its reader's META; `canonreduce` on a synthetic trace
and `canonroof` on a hand-made level; the two counter readers on fixture
records.

The trace, hand-worked.  One device.  The pass runs 1,000-9,000 ns, level 3
1,050-4,000, level 4 4,100-8,000:

    dvl while (container, under kspec.canon: not a leaf)   1,000 + 6,000
    dvl canon gather                     level 3           1,100 +   400
    dvl canon fusion, inner loop         level 3           1,600 +   800
    fsc canon fusion                     level 4           4,200 +   300
    dvl probe (another stage)            level 4           4,800 +   900
    dvl fingerprint (another stage)      level 4           5,800 +   100
    dvl canon fusion before the pass     -                   100 +   200

`canon` is 400 + 800 + 300 = 1,500 ns of 2,500 leaf ns: 60%.  Over 500
stored states 1,500 ns are 0.003 us a state.
"""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CONFIG = "kip320-5b-symmetry"
CELL = "kip320-5b-symmetry-notrace"
# name -> (unit, better, source): what BENCHMARK.json must say
NEW = {
    "stage_canon_us_per_state": ("us", "lower", "device_trace"),
    "canon_share": ("%", "lower", "device_trace"),
    "canon_roofline_share": ("%", "higher", "device_trace"),
    "canon_rows_per_candidate": ("ratio", "lower", "program_counter"),
    "symmetry_reduction": ("ratio", "higher", "program_counter"),
}


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


@pytest.fixture
def canonreduce(harness):
    return importlib.import_module("canonreduce")


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _golden(suffix=".json"):
    return _json(PERFBENCH, "golden", CONFIG + suffix)


# --- the golden, its derivation, and the unreduced job ----------------------

def test_golden_equals_its_derivation():
    golden, derived = _golden(), _golden(".derived.json")
    assert derived["equal_to_golden"] is True and derived["violation"] is None
    n = len(derived["levels"])
    assert derived["levels"] == golden["levels"][:n]
    assert derived["total"] == sum(derived["levels"])
    assert golden["total_so_far"] == sum(golden["levels"])
    assert golden["diameter_so_far"] == len(golden["levels"]) - 1
    assert derived["invariants"] == ["TypeOk", "LeaderInIsr", "WeakIsr",
                                     "StrongIsr"]
    assert golden["exhaustive"] is False and golden["violation"] is None
    # every level of the cell's depth is oracle-derived
    assert n > _json(PERFBENCH, "configs", CONFIG + ".json")["max_depth"]


def test_the_orbit_derivation_counts_the_goldens_orbits():
    golden, orbits = _golden(), _golden(".orbits.json")
    assert orbits["symmetry"] == {"set": "Replicas", "order": 120}
    n = min(len(orbits["levels"]), len(golden["levels"]))
    assert orbits["levels"][:n] == golden["levels"][:n]
    assert len(orbits["levels"]) == len(orbits["orbit_states"])
    assert orbits["levels"][:6] == [1, 2, 6, 24, 63, 251]
    # an orbit holds between 1 and 120 states
    assert all(o <= s <= 120 * o for o, s in
               zip(orbits["levels"], orbits["orbit_states"]))


def _unreduced_levels():
    """The unreduced 5-broker job's level counts and where each is from."""
    derived = _json(PERFBENCH, "golden", "kip320-5b.json")["levels"]
    banked = {}
    with open(os.path.join(ROOT, "RUN5R_r5_stats.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            banked[rec["depth"]] = rec["new"]
    levels = list(derived)
    assert len(levels) == 11 and all(
        banked[d] == levels[d] for d in range(1, 11))
    levels += [banked[d] for d in range(11, 16)]
    return levels


@pytest.mark.parametrize("depth", range(16))
def test_orbit_sizes_sum_to_the_unreduced_level(depth):
    orbits = _golden(".orbits.json")
    if depth >= len(orbits["orbit_states"]):
        pytest.skip(f"the orbit derivation stopped at depth "
                    f"{len(orbits['orbit_states']) - 1}")
    want = _unreduced_levels()[depth]
    assert orbits["orbit_states"][depth] == want
    assert want == [1, 10, 90, 770, 2370, 14635, 58100, 195095, 597860,
                    1650700, 4071215, 8996245, 17852020, 31931205, 51994450,
                    78163550][depth]


# --- the configuration -------------------------------------------------------

def test_the_two_copies_of_the_cfg_are_equal():
    with open(os.path.join(ROOT, "configs", "MCKip320FiveBroker.cfg"),
              "rb") as fh:
        user = fh.read()
    with open(os.path.join(PERFBENCH, "configs", "MCKip320FiveBroker.cfg"),
              "rb") as fh:
        assert fh.read() == user
    assert b"\nSYMMETRY Symm\n" in user
    assert b"--module MCKip320 --pipeline device --no-trace" in user


def test_configuration_is_the_cfg_a_user_runs(harness):
    bench, cell, config, traffic, golden = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "exhaustive-notrace", 1)
    assert (config["module"], config["engine"], config["kernel_source"],
            config["options"], config["reduced"]) == (
        "MCKip320", "single", "hand", {"pipeline": "device"},
        ["max_depth", "Partitions"])
    assert config["cfg"] == "perfbench/configs/MCKip320FiveBroker.cfg"
    from kafka_specification_tpu.utils.cfg import (
        CFG_MODULE_ALIASES, parse_cfg)

    tlc = parse_cfg(os.path.join(ROOT, config["cfg"]))
    assert tlc.symmetry == "Symm" == config["symmetry"]["operator"]
    assert {k: (len(v) if isinstance(v, list) else v)
            for k, v in tlc.constants.items()} == config["constants"]
    assert tlc.invariants == config["invariants"]
    assert CFG_MODULE_ALIASES["MCKip320FiveBroker"] == "MCKip320"
    # the unreduced cell's file, but for the stanza's consequences
    plain = _json(PERFBENCH, "configs", "kip320-5b.json")
    assert plain["constants"] == config["constants"]
    assert plain["invariants"] == config["invariants"]
    assert plain["options"] == config["options"]
    assert "orbits under the full group, exact" in \
        config["guarantees"]["counts"]
    assert len(golden["levels"]) > config["max_depth"]
    assert traffic["options"] == {"store_trace": False}


def test_the_cell_is_an_addition(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["configs"][-1]["reduced"] == ["max_depth", "Partitions"]
    assert [e["name"] for e in bench["per_layer"][-5:]] == list(NEW)
    # appended to no entry that was there
    assert all(CELL not in e.get("workloads", [])
               for e in bench["per_layer"][:-5])


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_says_what_its_reader_says(name, harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()[name].META
    # found by name: an entry appended after these must not move them
    (entry,) = [e for e in bench["per_layer"] if e["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [CELL]
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert (entry["unit"], entry["better"], entry["source"]) == NEW[name]
    assert (entry["layer"], entry["moves"]) == ("kernels", "states_per_s")
    # the cells it lists report the end-to-end metric it moves
    assert set(entry["workloads"]) <= {c["name"] for c in bench["workloads"]}
    assert meta["what"]


# --- canonreduce --------------------------------------------------------------

def _op(name, kind, shape="u32[8]{0}"):
    return f"%{name} = {shape} {kind}({shape} %p), calls=%c"


def _trace(canon=True):
    """The trace of the module docstring; `canon` False: a program with no
    symmetry (the same operations under `kspec.fingerprint`)."""
    scope = "kspec.canon" if canon else "kspec.fingerprint"
    dev = [
        ["%while.1 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), "
         "condition=%c, body=%b", 1000, 6000,
         f"jit(dvl_n2)/while/body/{scope}/while:"],
        [_op("gather.1", "gather"), 1100, 400,
         f"jit(dvl_n2)/while/body/{scope}/while/body/gather:"],
        [_op("fusion.2", "fusion"), 1600, 800,
         f"jit(dvl_n2)/while/body/{scope}/while/body/while/body/or:"],
        [_op("fusion.3", "fusion"), 4200, 300, f"jit(fsc_n2)/{scope}/xor:"],
        [_op("fusion.6", "fusion"), 4800, 900,
         "jit(dvl_n2)/while/body/kspec.dedup_probe/while/body/gather:"],
        [_op("fusion.7", "fusion"), 5800, 100,
         "jit(dvl_n2)/while/body/kspec.fingerprint/xor:"],
        [_op("fusion.2", "fusion"), 100, 200,
         f"jit(dvl_n2)/while/body/{scope}/while/body/or:"],
    ]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["perfbench.pass", 1000, 8000, ""],
            ["kspec.level d=3", 1050, 2950, ""],
            ["kspec.level d=4", 4100, 3900, ""]]}]}]}


@pytest.mark.parametrize("path,want", [
    ("jit(dvl_n2)/while/body/kspec.canon/while/body/gather:", True),
    ("jit(fsc_n2)/kspec.canon:", True),
    # the innermost kspec component decides, as in stagereduce.stage_of
    ("jit(x)/kspec.canon/kspec.fingerprint/xor:", False),
    ("jit(x)/kspec.compact/part.novel/kspec.canon/or:", True),
    ("jit(x)/kspec.fingerprint/xor:", False),
    ("jit(x)/canon/or:", False),
    ("", False),
])
def test_is_canon(path, want, canonreduce):
    assert canonreduce.is_canon(path) is want


def test_reduce_canon_on_the_synthetic_trace(canonreduce):
    import stagereduce

    trace = _trace()
    stages = stagereduce.reduce_stages(trace)
    got = canonreduce.reduce_canon(trace, stages)
    assert got["plane"] == "/device:TPU:0"
    assert round(got["canon_s"] * 1e9, 6) == 1500.0
    assert round(got["leaf_s"] * 1e9, 6) == 2500.0
    assert {d: round(v * 1e9, 6) for d, v in got["by_level"].items()} == {
        3: 1200.0, 4: 300.0}
    assert {p: round(v * 1e9, 6) for p, v in got["by_program"].items()} == {
        "dvl_n2": 1200.0, "fsc_n2": 300.0}
    # the benchmark's own stage list books the scope as unnamed until a
    # `benchmark` PR adds it: the two readings are the same seconds
    assert round(stages["stage_s"]["unnamed"] * 1e9, 6) == 1500.0


def test_a_program_without_the_scope_reads_nothing(canonreduce):
    import stagereduce

    trace = _trace(canon=False)
    assert canonreduce.reduce_canon(
        trace, stagereduce.reduce_stages(trace)) is None
    assert canonreduce.reduce_canon(
        {"planes": []}, {"plane": "/device:TPU:0", "levels": []}) is None


def test_the_trace_readers_on_the_synthetic_trace(
        harness, canonreduce, tmp_path, monkeypatch):
    import stagereduce

    run_dir = tmp_path / "run"
    (tmp_path / "trace").mkdir()
    xplane = str(tmp_path / "trace" / "t.xplane.pb")
    records = [{"enabled_candidates": 100}, {"enabled_candidates": 400}]
    ctx = {"traced": {"manifest": {"dir": str(run_dir)}, "total": 500,
                      "spans": {"spans": []}, "level_records": records},
           "lanes": 5, "peaks": {"hbm_bytes_per_s": 819e9}}
    readers = harness.load_metric_readers()
    for canon in (True, False):
        stagereduce._CACHE.clear()
        canonreduce._CACHE.clear()
        monkeypatch.setattr(stagereduce, "find_xplane", lambda d: xplane)
        monkeypatch.setattr(canonreduce, "find_xplane", lambda d: xplane)
        monkeypatch.setattr(stagereduce, "load_xplane",
                            lambda p, c=canon: _trace(c))
        us = readers["stage_canon_us_per_state"].read(ctx)
        share = readers["canon_share"].read(ctx)
        roof = readers["canon_roofline_share"].read(ctx)
        if not canon:
            assert (us, share, roof) == (None, None, None)
            continue
        assert us == pytest.approx(1500e-9 * 1e6 / 500)
        assert share == pytest.approx(60.0)
        # 500 candidates x (5 lanes x 4 B + 8 B) = 14,000 B
        assert roof == pytest.approx(
            100.0 * (14000 / 819e9) / 1500e-9)
        assert os.path.exists(tmp_path / "trace_canon.json")
    # no traced pass: nothing to read
    for name in ("stage_canon_us_per_state", "canon_share",
                 "canon_roofline_share"):
        assert readers[name].read({"traced": None, "peaks": {}}) is None


# --- canonroof ----------------------------------------------------------------

def test_the_roofline_bytes_of_a_hand_made_level(harness):
    canonroof = importlib.import_module("canonroof")
    # 1,000 candidates of 5 lanes: 20 B read and 8 B written each
    assert canonroof.level_min_bytes(1000, 5) == 28000
    assert canonroof.level_min_bytes(0, 5) == 0
    assert canonroof.pass_min_bytes(
        [{"enabled_candidates": 10}, {"enabled_candidates": 681}], 5
    ) == 691 * 28
    with pytest.raises(ValueError):
        canonroof.level_min_bytes(-1, 5)


# --- the counter readers ------------------------------------------------------

@pytest.mark.parametrize("name,passes,want", [
    # 4,096 + 8,192 rows over 1,000 + 7,192 candidates: 1.5
    ("canon_rows_per_candidate",
     [[{"canon_rows": 4096, "enabled_candidates": 1000},
       {"canon_rows": 8192, "enabled_candidates": 7192}]], 1.5),
    # the median over the passes
    ("canon_rows_per_candidate",
     [[{"canon_rows": 10, "enabled_candidates": 10}],
      [{"canon_rows": 30, "enabled_candidates": 10}],
      [{"canon_rows": 20, "enabled_candidates": 10}]], 2.0),
    # records without the field (no symmetry; the parent): nothing
    ("canon_rows_per_candidate", [[{"enabled_candidates": 10}]], None),
    ("canon_rows_per_candidate", [[]], None),
    ("canon_rows_per_candidate",
     [[{"canon_rows": 0, "enabled_candidates": 0}]], None),
    # 10 + 90 + 770 unreduced states over 2 + 6 + 24 stored
    ("symmetry_reduction",
     [[{"orbit_states": 10, "new": 2}, {"orbit_states": 90, "new": 6},
       {"orbit_states": 770, "new": 24}]], 870 / 32),
    ("symmetry_reduction", [[{"new": 2}]], None),
    ("symmetry_reduction", [[{"orbit_states": 0, "new": 0}]], None),
])
def test_the_counter_readers(name, passes, want, harness):
    reader = harness.load_metric_readers()[name]
    got = reader.read({"passes": [{"level_records": p} for p in passes]})
    assert got == (None if want is None else pytest.approx(want))
