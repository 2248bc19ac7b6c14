"""The AsyncIsr cell's own files (PR 32).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

The golden of `asyncisr-4b` against its oracle derivation (all 31 levels of
the bounded space), what a pass cut at the cell's depth owes of it, the
configuration against the cfg a user runs, and the two readers the cell
brings (`chunk_ms`, `dup_share`) on fixture records: a hand-worked value
each, and nothing where the record is absent (the parent's program writes
no `chunks`; a pass with no level of two chunks has no chunk to price).
"""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CELL = "asyncisr-4b-constraint"
LEVELS = [1, 7, 31, 116, 377, 1082, 2819, 6829, 15413, 32324, 63333, 115993,
          197528, 312282, 458565, 623812, 783474, 907380, 967941, 947673,
          847266, 687960, 503619, 328506, 187557, 91359, 36627, 11493, 2622,
          384, 27]


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


def _golden(name="asyncisr-4b.json"):
    with open(os.path.join(PERFBENCH, "golden", name)) as fh:
        return json.load(fh)


def test_golden_equals_its_derivation():
    golden, derived = _golden(), _golden("asyncisr-4b.derived.json")
    assert derived["equal_to_golden"] is True and derived["violation"] is None
    assert derived["levels"] == golden["levels"] == LEVELS
    assert derived["total"] == golden["total"] == sum(LEVELS) == 8134400
    assert derived["invariants"] == ["TypeOk", "ValidHighWatermark"]
    # the derivation ran until the frontier emptied: the space is whole
    assert golden["exhaustive"] is True and golden["violation"] is None
    assert golden["diameter"] == len(LEVELS) - 1 == 30


def test_configuration_is_the_cfg_a_user_runs(harness):
    bench, cell, config, traffic, golden = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "asyncisr-4b", "exhaustive-trace", 1)
    assert (config["module"], config["engine"], config["kernel_source"],
            config["options"], config["reduced"]) == (
        "AsyncIsr", "single", "hand", {}, ["max_depth"])
    # the harness reads a copy (the parent's checkout has no such cfg);
    # it is the file `cli check configs/AsyncIsrFourBroker.cfg` reads
    with open(os.path.join(ROOT, config["cfg"]), "rb") as a, open(
            os.path.join(ROOT, "configs", "AsyncIsrFourBroker.cfg"), "rb") as b:
        assert a.read() == b.read()
    from kafka_specification_tpu.utils.cfg import parse_cfg

    tlc = parse_cfg(os.path.join(ROOT, config["cfg"]))
    assert len(tlc.constants["Replicas"]) == config["constants"]["Replicas"] == 4
    assert {k: tlc.constants[k] for k in ("Leader", "MaxOffset", "MaxVersion")} \
        == {k: config["constants"][k] for k in ("Leader", "MaxOffset", "MaxVersion")}
    assert tlc.invariants == config["invariants"]
    assert tlc.constraints == [config["constraint"]]
    assert config["whole_space"]["states"] == golden["total"]
    assert config["whole_space"]["diameter"] == golden["diameter"]
    opts = harness.pass_options(config, traffic, traffic["jobs"][0], None)
    assert opts == {"store_trace": True, "max_depth": config["max_depth"]}


def test_a_pass_at_the_cells_depth_owes_its_prefix_of_the_golden(harness):
    depth = harness.load_cell(CELL)[2]["max_depth"]
    want = harness.golden_for(_golden(), depth)
    assert want == {"levels": LEVELS[: depth + 1],
                    "total": sum(LEVELS[: depth + 1]), "diameter": depth,
                    "violation": None}
    # the bracket's far side has a golden too, and so has the whole job
    assert harness.golden_for(_golden(), depth + 1)["total"] == sum(
        LEVELS[: depth + 2])
    assert harness.golden_for(_golden(), None)["total"] == 8134400
    assert harness.golden_for(_golden(), 14)["total"] == 1206700


def test_least_bytes_at_four_lanes(harness):
    """`bytes_roofline_share` reports here as everywhere; its floor model at
    this model's 4 packed lanes, hand-worked for level 14 (312,282 frontier
    rows, 1,983,744 enabled candidates, 458,565 new): 16 B x 312,282 +
    16 B x 458,565 + 2 x 8 B x 1,983,744 + 8 B x 458,565 = 47,741,976."""
    import roofline

    assert roofline.level_min_bytes(312282, 1983744, 458565, 1206700, 4) \
        == 47741976


# --- the two readers ---------------------------------------------------------

def _pass(*levels):
    """A pass reduced to what the two readers read: one record a level,
    (level_ms, chunks, enabled_candidates, duplicates); `chunks` None is a
    record without the field (the parent's program)."""
    recs = []
    for d, (ms, chunks, enabled, dup) in enumerate(levels, 1):
        rec = {"depth": d, "level_ms": ms, "enabled_candidates": enabled,
               "duplicates": dup}
        if chunks is not None:
            rec["chunks"] = chunks
        recs.append(rec)
    return {"level_records": recs}


@pytest.mark.parametrize("passes,chunk_ms,dup_share", [
    # hand-worked: levels of 2 and 4 chunks cost 300 + 900 = 1,200 ms for 6
    # chunks: 200 ms a chunk, the one-chunk level left out; 10 + 300 + 590 =
    # 900 duplicates of 100 + 400 + 700 = 1,200 enabled: 75%
    ([_pass((50.0, 1, 100, 10), (300.0, 2, 400, 300), (900.0, 4, 700, 590))],
     200.0, 75.0),
    # the median over passes of each pass's own ratio: 100, 150, 110 ms a
    # chunk; 50, 60, 70 %
    ([_pass((200.0, 2, 10, 5)), _pass((450.0, 3, 10, 6)),
      _pass((220.0, 2, 10, 7))], 110.0, 60.0),
    # no level of two chunks: no chunk to price; the counts still read
    ([_pass((50.0, 1, 8, 2), (60.0, 1, 8, 4))], None, 37.5),
    # the parent's records: no `chunks`, nothing for chunk_ms, no error
    ([_pass((300.0, None, 400, 300)), _pass((310.0, None, 400, 300))],
     None, 75.0),
    # a record without it anywhere in a pass: that pass reads nothing
    ([_pass((300.0, 2, 4, 1), (50.0, None, 4, 1)), _pass((500.0, 2, 4, 3))],
     250.0, 50.0),
    # nothing enabled (an empty pass), no passes
    ([_pass((1.0, 1, 0, 0))], None, None),
    ([], None, None),
])
def test_readers(passes, chunk_ms, dup_share, harness):
    readers = harness.load_metric_readers()
    got_ms = readers["chunk_ms"].read({"passes": passes})
    got_dup = readers["dup_share"].read({"passes": passes})
    assert got_ms == (None if chunk_ms is None else pytest.approx(chunk_ms))
    assert got_dup == (None if dup_share is None
                       else pytest.approx(dup_share))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _by_name(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


ONE_CHIP_TRACED = ["kip320-3b-notrace", "kip320-3b-trace",
                   "kip320-5b-notrace"]
# the cells each listed metric reports in, in the order they were appended:
# what PR 31's test held for `probe_rounds_share` at `per_layer[-1]`, by name
# here and for every entry (conftest.py says why)
LISTED = {
    **{name: ONE_CHIP_TRACED + ["kip320-5b-x4", CELL] for name in (
        "stage_guard_us_per_state", "stage_expand_us_per_state",
        "stage_compact_us_per_state", "stage_fingerprint_us_per_state",
        "stage_dedup_sort_us_per_state", "stage_dedup_probe_us_per_state",
        "stage_dedup_merge_us_per_state", "stage_invariants_us_per_state",
        "stage_digest_us_per_state", "stage_unnamed_share",
        "discarded_dispatch_share", "d2h_bytes_per_state",
        "h2d_bytes_per_state", "fetches_per_level", "pass_overhead_ms")},
    "store_share": ONE_CHIP_TRACED + [CELL],
    **{name: ["kip320-5b-x4"] for name in (
        "exchange_bytes_per_state", "collective_share", "shard_imbalance",
        "exchange_ici_share", "stage_exchange_us_per_state")},
    "cex_ms": ["firsttry-3b-cex"], "cut_level_share": ["firsttry-3b-cex"],
    "probe_rounds_share": ONE_CHIP_TRACED + [
        "kip320-5b-x4", "firsttry-3b-cex", CELL],
    "chunk_ms": [CELL], "dup_share": [CELL],
}


@pytest.mark.parametrize("name", [m["name"] for m in _bench()["per_layer"]])
def test_an_entry_says_what_its_reader_says(name, harness):
    """Every per-layer entry, found by name: its cells, its keys against its
    reader's META, its `moves` among the end-to-end metrics that its cells
    report."""
    bench = _bench()
    entry = _by_name(bench["per_layer"], name)
    meta = harness.load_metric_readers()[name].META
    assert entry["name"] == meta["name"] == name
    assert entry.get("workloads") == LISTED.get(name)
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert set(entry.get("workloads", [])) <= {
        c["name"] for c in bench["workloads"]}


def test_what_the_cell_brought_to_benchmark_json():
    bench = _bench()
    config = _by_name(bench["configs"], "asyncisr-4b")
    assert (config["file"], config["reduced"]) == (
        "perfbench/configs/asyncisr-4b.json", ["max_depth"])
    cell = _by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "asyncisr-4b", "exhaustive-trace", 1)
    for name in ("chunk_ms", "dup_share"):
        entry = _by_name(bench["per_layer"], name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "states_per_s"
    # every listed metric with something to read here lists the cell: all
    # but the exchange layer's five and the counterexample's two
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", [])) == sorted(
        n for n, cells in LISTED.items() if CELL in cells)
    assert len([n for n, cells in LISTED.items() if CELL in cells]) == 19


def test_the_cell_rehearses_on_the_cpu(harness):
    """`run.py --rehearse`: the harness's whole control flow at depth 4
    (532 states), counts only: set-up clean, three golden passes, `dup_share`
    and the listed record metrics read from the program's records, `chunk_ms`
    left out (no level of two chunks at that depth)."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "run.py"), "--workload",
         CELL, "--seed", "2147484399", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] >= 3
    assert last["failed"] == 0 and last["problems"] == []
    assert "chunk_ms" not in last["metric_names"]
    # the record metrics that list the cell find their records (the ten
    # `stage_*` read a device trace, which a CPU has none of)
    assert {"dup_share", "probe_rounds_share", "d2h_bytes_per_state",
            "h2d_bytes_per_state", "fetches_per_level", "store_share",
            "pass_overhead_ms", "discarded_dispatch_share"} <= set(
        last["metric_names"])
