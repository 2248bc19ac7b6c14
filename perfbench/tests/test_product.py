"""The 5-broker x 3-partition cell's own files (PR 44).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

Tier-1 runs this file too (`tests/test_product_bench.py` loads it).  The
golden of `kip320-5b-3p` against its oracle derivation AND against the
threefold convolution of the one-partition golden (two independent ways to
the same seven levels); the configuration against the cfg a user runs
(`configs/Kip320Stretch.cfg`, `Partitions` uncut); the configuration, the
cell and the two per-layer entries this PR brought, each found BY NAME (an
entry a later PR appends must not move them), each entry held to its
reader's META; the two readers on synthetic records; the least-bytes count
at the job's 15 lanes.  The one `slow` case rehearses the cell.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CONFIG = "kip320-5b-3p"
CELL = "kip320-5b-3p-notrace"
TWIN = "kip320-5b-notrace"  # the same job at one partition
LEVELS = [1, 30, 570, 8710, 104610, 1075905, 9708900]
# name -> (unit, better, source, layer, moves, cells): what BENCHMARK.json
# must say
NEW = {
    "guard_live_share": ("%", "higher", "program_counter", "level programs",
                         "states_per_s", [TWIN, CELL]),
    "expansion_share": ("%", "lower", "device_trace", "level programs",
                        "states_per_s", [TWIN, "kip279-4b-cex", CELL]),
}


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


def _golden(config=CONFIG, suffix=".json"):
    return _json(PERFBENCH, "golden", config + suffix)


def _by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def _convolve(levels, k, depth):
    out = [1]
    for _ in range(k):
        out = [sum(out[i] * levels[d - i] for i in range(d + 1)
                   if i < len(out) and d - i < len(levels))
               for d in range(depth + 1)]
    return out


# --- the golden, its derivation and the closed form --------------------------

def test_golden_equals_its_derivation():
    golden, derived = _golden(), _golden(suffix=".derived.json")
    assert derived["equal_to_golden"] is True
    assert derived["levels"] == golden["levels"] == LEVELS
    assert derived["total"] == golden["total_so_far"] == sum(LEVELS) \
        == 10898726
    assert derived["invariants"] == ["TypeOk", "LeaderInIsr", "WeakIsr",
                                     "StrongIsr"]
    assert derived["violation"] is None and golden["violation"] is None
    # the space does not end at depth 6: the golden says so
    assert golden["exhaustive"] is False and golden["diameter_so_far"] == 6
    assert derived["command"].endswith("--derive kip320-5b-3p 6")


def test_golden_is_the_threefold_convolution_of_the_one_partition_golden():
    """Independent partitions with one initial state each: a product
    state's depth is the sum of its parts', so level d of the product is
    the sum over a + b + c = d of l(a) l(b) l(c).  The one-partition
    golden was derived by another run of the oracle, for PR 22."""
    one = _golden("kip320-5b")["levels"]
    assert _convolve(one, 3, 6) == _golden()["levels"]
    # and beyond what the oracle derived: the sizes PERF.md section 7 names
    deeper = _convolve(one, 3, 7)
    assert deeper[7] == 76610085 and sum(deeper) == 87508811
    assert sum(deeper[:6]) == 1189826


def test_golden_is_what_a_pass_to_depth_five_owes(harness):
    want = harness.golden_for(_golden(), 5)
    assert want == {"levels": LEVELS[:6], "total": 1189826, "diameter": 5,
                    "violation": None}
    rec = {"levels": LEVELS[:6], "total": 1189826, "diameter": 5,
           "violation": None, "spans": {"spans": [], "events": []},
           "manifest": {}, "stats": {}, "jax": {"backend_compiles": 0}}
    assert harness.judge_pass(rec, want) == []
    rec["levels"] = LEVELS[:5] + [1075904]
    assert [w[0] for w in harness.judge_pass(rec, want)] == ["answer"]
    # the banked depth: a benchmark PR may deepen the job to 6, no further
    assert harness.golden_for(_golden(), 6)["total"] == 10898726
    with pytest.raises(SystemExit):
        harness.golden_for(_golden(), 7)


# --- the configuration, the cell and the cfg a user runs ---------------------

def test_configuration_is_the_cfg_a_user_runs(harness):
    bench, cell, config, traffic, golden = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "exhaustive-notrace", 1)
    assert (config["module"], config["engine"], config["kernel_source"],
            config["options"], config["reduced"], config["max_depth"],
            config["chips"]) == ("Kip320", "single", "hand", {},
                                 ["max_depth"], 5, 1)
    # read where a user reads it, the file the seed rounds wrote
    assert config["cfg"] == "configs/Kip320Stretch.cfg"
    from kafka_specification_tpu.utils.cfg import parse_cfg

    tlc = parse_cfg(os.path.join(ROOT, config["cfg"]))
    assert {k: (len(v) if isinstance(v, list) else v)
            for k, v in tlc.constants.items()} == config["constants"]
    assert config["constants"]["Partitions"] == 3
    assert tlc.invariants == config["invariants"]
    with open(os.path.join(ROOT, config["cfg"])) as fh:
        header = fh.read()
    # the header gives the cell's own command line
    assert "--module Kip320 --no-trace --max-depth 5" in header
    assert traffic["options"] == {"store_trace": False}
    # Partitions is a width of this job and is not cut
    assert set(config["cut"]) == {"max_depth"}
    assert set(config["assumed"]) == {"Partitions", "visited_backend",
                                      "kernel_source", "warm_protocol"}
    assert set(config["guarantees"]) == {"search", "invariants", "counts",
                                         "degradations"}
    assert golden["config"] == CONFIG


def test_configuration_and_cell_are_found_by_name(bench):
    conf = _by_name(bench["configs"], CONFIG)
    assert conf["file"] == "perfbench/configs/kip320-5b-3p.json"
    assert conf["reduced"] == ["max_depth"]
    on_file = _json(ROOT, conf["file"])
    assert (on_file["name"], on_file["source"], on_file["reduced"]) == (
        CONFIG, conf["source"], ["max_depth"])
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert "BASELINE.json" in conf["source"]
    assert "configs/Kip320Stretch.cfg" in conf["source"]
    cell = _by_name(bench["workloads"], CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200
    # one cell of this configuration, and no other configuration's file
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert [c["name"] for c in bench["configs"]
            if c["file"] == conf["file"]] == [CONFIG]
    # the first 5-broker configuration that does not cut `Partitions`
    assert [c["name"] for c in bench["configs"]
            if c["name"].startswith("kip320-5b")
            and "Partitions" not in c["reduced"]] == [CONFIG]


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_says_what_its_reader_says(name, readers, bench):
    meta = readers[name].META
    entry = _by_name(bench["per_layer"], name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cells this PR listed, in its order (a later PR may append)
    assert entry["workloads"][:len(NEW[name][5])] == NEW[name][5]
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
    or lists none: the two this PR brought are among them, and so is the
    least-bytes share, which takes the job's lanes."""
    asked = {e["name"] for e in bench["per_layer"]
             if e["name"] in readers and CELL in e.get("workloads", [CELL])}
    assert set(NEW) <= asked
    assert {"bytes_roofline_share", "device_idle_share", "peak_hbm_MiB",
            "programs", "launches_per_level"} <= asked
    # every cell reports setup_s, one more end-to-end metric, one per-layer
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])} >= {
        "setup_s", "verdict_s", "states_per_s"}


# --- guard_live_share on synthetic records -----------------------------------

def _pass(*levels):
    """One record a level: (enabled_candidates, guard_lanes), or None for a
    record without the new field (the parent of PR 44)."""
    return {"level_records": [
        {"depth": d, "enabled_candidates": 7} if lv is None else
        {"depth": d, "enabled_candidates": lv[0], "guard_lanes": lv[1]}
        for d, lv in enumerate(levels, 1)]}


# the cell's depth-5 pass on the CPU (ISSUE 44's table; buckets of at least
# 256 rows below the compact gate, 32,768-row chunks and an 8,192-row tail
# at level 5)
CELL_PASS = _pass((30, 256 * 339), (870, 256 * 339), (16245, 1024 * 339),
                  (235545, 16384 * 339), (2723430, 106496 * 339))


@pytest.mark.parametrize("passes,share", [
    ([CELL_PASS, CELL_PASS, CELL_PASS],
     100.0 * 2976120 / ((256 + 256 + 1024 + 16384 + 106496) * 339)),
    # no width padded: enabled over frontier x fanout
    ([_pass((26, 339), (52, 678))], 100.0 * 78 / 1017),
    # the median over passes of each pass's own ratio: 25, 50, 30 %
    ([_pass((5, 20)), _pass((10, 20)), _pass((3, 10), (3, 10))], 30.0),
    # the parent's records: no such field, nothing to read
    ([_pass(None, None), _pass(None)], None),
    # a record without it anywhere in a pass: that pass reads nothing
    ([_pass((1, 4), None), _pass((1, 4))], 25.0),
    # nothing evaluated: 0 of 0 is nothing to read
    ([_pass((0, 0))], None),
    ([], None),
], ids=["cell", "unpadded", "median", "parent", "skips-a-pass", "zero",
        "empty"])
def test_guard_live_share(readers, passes, share):
    got = readers["guard_live_share"].read({"passes": passes})
    assert got == pytest.approx(share) if share is not None else got is None


def test_guard_live_share_of_the_cells_pass_is_what_the_issue_counted():
    """7.71% of 113,921 x 339 lanes unpadded; the padded widths the engine
    hands its guard side make it 7.05%."""
    enabled = sum(r["enabled_candidates"]
                  for r in CELL_PASS["level_records"])
    assert enabled == 2976120
    assert 100.0 * enabled / (113921 * 339) == pytest.approx(7.7063, abs=1e-4)
    lanes = sum(r["guard_lanes"] for r in CELL_PASS["level_records"])
    assert lanes == 124416 * 339
    assert 100.0 * enabled / lanes == pytest.approx(7.0563, abs=1e-4)


# --- expansion_share on a synthetic stage reduction --------------------------

def _reduced(**stage_s):
    import stagereduce

    full = {s: 0.0 for s in stagereduce.STAGES + (stagereduce.UNNAMED,)}
    full.update(stage_s)
    return {"stage_s": full, "leaf_s": sum(full.values())}


@pytest.mark.parametrize("reduced,share", [
    (dict(guard=0.2, expand=0.3, compact=0.1, dedup_sort=0.3,
          dedup_probe=0.1), 60.0),
    (dict(guard=0.0, expand=0.0, compact=0.0, fingerprint=1.0), 0.0),
    (dict(guard=0.5, expand=0.25, compact=0.25), 100.0),
    # seconds under no stage are in the denominator
    (dict(guard=0.5, unnamed=0.5), 50.0),
], ids=["mixed", "none", "all", "unnamed-counts"])
def test_expansion_share(harness, readers, monkeypatch, reduced, share):
    import stagereduce

    monkeypatch.setattr(stagereduce, "for_ctx",
                        lambda ctx: _reduced(**reduced))
    assert readers["expansion_share"].read({}) == pytest.approx(share)


@pytest.mark.parametrize("reduced", [None, {"stage_s": {}, "leaf_s": 0.0}],
                         ids=["no-trace", "no-leaf-seconds"])
def test_expansion_share_reads_nothing_without_a_stage_reduction(
        harness, readers, monkeypatch, reduced):
    import stagereduce

    monkeypatch.setattr(stagereduce, "for_ctx", lambda ctx: reduced)
    assert readers["expansion_share"].read({}) is None
    # and through the real loader: a rehearsal, or no traced pass
    monkeypatch.undo()
    importlib.import_module("run")
    assert readers["expansion_share"].read(
        {"traced": None, "rehearsal": False}) is None
    assert readers["expansion_share"].read(
        {"traced": {"manifest": {}}, "rehearsal": True}) is None


# --- the least-bytes count at the job's lanes --------------------------------

def test_least_bytes_of_the_cells_pass_at_fifteen_lanes(harness):
    """`bytes_roofline_share` has no list and reports here: its floor takes
    `lanes` from the job, 15 here (60 B a row where no other cell has more
    than 20)."""
    import roofline

    recs = [dict(frontier=f, enabled_candidates=e, new=n, total=t)
            for f, e, n, t in [
                (1, 30, 30, 31), (30, 870, 570, 601),
                (570, 16245, 8710, 9311), (8710, 235545, 104610, 113921),
                (104610, 2723430, 1075905, 1189826)]]
    got = roofline.pass_min_bytes(recs, 15)
    rows = 113921 + 1189825  # frontier rows read, new rows written
    assert got == 60 * rows + 16 * 2976120 + 8 * 1189825 == 135361280
    assert got - roofline.pass_min_bytes(recs, 5) == 40 * rows


# --- the cell, rehearsed -----------------------------------------------------

@pytest.mark.slow
def test_the_cell_rehearses_correct():
    """`perfbench/run.py --workload kip320-5b-3p-notrace --rehearse`: the
    harness's control flow on the CPU at depth 4 (113,921 states; a minute
    here, most of it compiles, so `slow`)."""
    p = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "run.py"), "--workload",
         CELL, "--rehearse", "--seed", "2147483659"],
        cwd=ROOT, capture_output=True, text=True, timeout=1800)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert (line["attempted"], line["failed"], line["problems"]) == (3, 0, [])
    assert line["compared"]["window_passes"]["value"] == 3
