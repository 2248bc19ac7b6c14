"""`setup_s` by part: the eight readers of the program's process ledger
(PR 49).  CPU, no chip, seconds.

    python3 -m pytest perfbench/tests -q

Tier-1 runs this file too (`tests/test_setup_parts.py` loads it).  Each
reader on a synthetic ledger (the documented number), on a record without
`process` (the parent's: nothing, and no raise) and in a rehearsal (a CPU
run counts and never times); the parts and `setup_unbooked_share` account
for `setup_s` by construction; each of the eight `BENCHMARK.json` entries,
found BY NAME, against its reader's META as `selfcheck.py` holds them.  No
position and no list of cells is pinned: a later PR appends entries and
cells.
"""

import importlib
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
LAYER = "compile and shape ladder"
# name -> (unit, source): what BENCHMARK.json must say of each
NEW = {
    "setup_start_s": ("s", "program_span"),
    "setup_model_s": ("s", "program_span"),
    "setup_trace_s": ("s", "program_span"),
    "setup_lower_s": ("s", "program_span"),
    "setup_passes_s": ("s", "program_span"),
    "setup_rewarm_s": ("s", "program_span"),
    "program_cache_miss_share": ("%", "program_counter"),
    "setup_unbooked_share": ("%", "program_span"),
}
SECONDS = [n for n in NEW if n.endswith("_s")]


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


def _ledger(**over):
    """A ledger snapshot as `obs/ledger.py` writes it, of a process that
    started at 1000.0 and whose first window pass took 0.5 s."""
    p = {
        "start_unix": 1000.0, "jax_unix": 1004.0,
        "backend_ready_unix": 1010.0, "model_s": 2.0, "models": 3,
        "programs": {"trace_s": 6.0, "lower_s": 3.0, "backend_s": 4.0,
                     "cache_hits": 27, "cache_misses": 3, "retrieval_s": 3.5,
                     "built": 30, "call_s": 14.0, "slowest": []},
        "helpers": {"trace_s": 1.0, "lower_s": 0.5, "backend_s": 2.0,
                    "cache_hits": 270, "cache_misses": 0, "retrieval_s": 1.5,
                    "built": 281, "by_name": {"jit(dynamic_slice)": 188}},
        "rewarm": {"calls": 1, "s": 5.0, "built": 11},
        "checks": {"calls": 3, "s": 30.5, "last_s": 0.5},
    }
    p.update(over)
    return p


def _ctx(process=None, setup_s=50.0, rehearsal=False, later=None):
    """A context as `run.py` hands it to the readers: the first window pass
    carries `process`; `later` is what the passes after it carry."""
    def one(p):
        return {"stats": {"pipeline": "fused",
                          **({"process": p} if p is not None else {})}}

    setup = {"passes": 2, "jax": {"trace_s": 21.0}}
    if setup_s is not None:
        setup["setup_s"] = setup_s
    return {"passes": [one(process), one(later or process)], "setup": setup,
            "rehearsal": rehearsal}


WANT = {
    "setup_start_s": 10.0,     # 1010.0 - 1000.0
    "setup_model_s": 2.0,
    "setup_trace_s": 7.0,      # 6.0 + 1.0: outermost traces, wall seconds
    "setup_lower_s": 3.5,      # 3.0 + 0.5
    "setup_passes_s": 30.0,    # 30.5 - 0.5: the window pass taken out
    "setup_rewarm_s": 5.0,
    "program_cache_miss_share": 1.0,   # 3 of 300
    "setup_unbooked_share": 6.0,       # 1 - (10 + 2 + 30 + 5) / 50
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_on_a_synthetic_ledger(name, readers):
    assert readers[name].read(_ctx(_ledger())) == pytest.approx(WANT[name])
    # the first window pass is read: what later passes add is the window's
    later = _ledger(checks={"calls": 9, "s": 33.5, "last_s": 0.5})
    assert readers[name].read(_ctx(_ledger(), later=later)) == pytest.approx(
        WANT[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_finds_nothing_on_the_parents_record(name, readers):
    """The parent's results carry no `process`; a set-up that failed has no
    window pass: nothing to read, and nothing raised."""
    assert readers[name].read(_ctx(None)) is None
    empty = {"passes": [], "setup": {"problems": ["x"]}, "rehearsal": False}
    assert readers[name].read(empty) is None
    assert readers[name].read({"setup": {}, "rehearsal": False}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_rehearsal_times_nothing(name, readers):
    """A CPU run counts and never times: the counter reads, the rest not
    (`setup_s` itself is absent from a rehearsal's set-up record)."""
    got = readers[name].read(_ctx(_ledger(), setup_s=None, rehearsal=True))
    if name == "program_cache_miss_share":
        assert got == pytest.approx(1.0)
    else:
        assert got is None


def test_the_parts_account_for_setup_s(readers):
    """start + model + passes + rewarm and the unbooked share are `setup_s`,
    whatever the ledger holds."""
    for setup_s, p in ((50.0, _ledger()),
                       (812.25, _ledger(model_s=3.25, rewarm={
                           "calls": 1, "s": 321.5, "built": 11}))):
        ctx = _ctx(p, setup_s=setup_s)
        booked = sum(readers[n].read(ctx) for n in (
            "setup_start_s", "setup_model_s", "setup_passes_s",
            "setup_rewarm_s"))
        unbooked = readers["setup_unbooked_share"].read(ctx)
        assert booked + unbooked / 100.0 * setup_s == pytest.approx(setup_s)


def test_edges_of_the_two_shares(readers):
    # a cache that answered nothing (off): no share, not a division by zero
    quiet = _ledger()
    for part in ("programs", "helpers"):
        quiet[part] = dict(quiet[part], cache_hits=0, cache_misses=0)
    assert readers["program_cache_miss_share"].read(_ctx(quiet)) is None
    cold = _ledger()
    cold["programs"] = dict(cold["programs"], cache_hits=0, cache_misses=30)
    cold["helpers"] = dict(cold["helpers"], cache_hits=0, cache_misses=270)
    assert readers["program_cache_miss_share"].read(_ctx(cold)) == 100.0
    # a program that never marked its backend has no start, so no share
    unmarked = _ctx(_ledger(backend_ready_unix=None))
    assert readers["setup_start_s"].read(unmarked) is None
    assert readers["setup_unbooked_share"].read(unmarked) is None
    assert readers["setup_model_s"].read(unmarked) == 2.0


def test_the_harness_leaves_them_out_of_the_parents_line(harness, bench):
    """`Cell.per_layer_metrics` on a record without `process`: the line
    lacks the eight and carries the rest, as the driver accepts of the
    parent for a metric new in this PR."""
    cell = next(iter(bench["workloads"]))

    class Stub:
        pass

    stub = Stub()
    stub.bench, stub.cell = bench, cell
    rec = {"stats": {}, "level_records": [], "wall_s": 1.0, "total": 1,
           "spans": {"spans": [], "events": []}, "manifest": {},
           "jax": {"trace_s": 0.0, "lowering_s": 0.0,
                   "backend_compile_s": 0.0}}
    ctx = {"cell": cell, "config": {}, "lanes": 3, "passes": [rec, rec],
           "setup": {"jax": {"backend_compiles": 7, "backend_compile_s": 1.0},
                     "setup_s": 40.0},
           "traced": None, "trace": None, "device_kind": "TPU v5 lite",
           "peaks": None, "chips": 1, "memory_peak_bytes": 0,
           "rehearsal": False}
    got = harness.Cell.per_layer_metrics(stub, ctx)
    assert not set(NEW) & set(got)
    assert got["programs"]["value"] == 7
    ctx["passes"] = [dict(rec, stats={"process": _ledger()}), rec]
    got = harness.Cell.per_layer_metrics(stub, ctx)
    assert set(NEW) <= set(got)
    assert {n: got[n]["unit"] for n in NEW} == {n: u for n, (u, _) in
                                                NEW.items()}
    assert got["setup_unbooked_share"]["value"] == pytest.approx(-17.5)


# --- BENCHMARK.json ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_says_what_its_reader_says(name, readers, bench):
    """What `selfcheck.py` holds of every per-layer entry, for these eight,
    each found by name."""
    meta = readers[name].META
    (entry,) = [e for e in bench["per_layer"] if e["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert all(meta[k] == entry[k] for k in ("name", "unit", "better",
                                             "source", "layer", "moves"))
    assert (entry["unit"], entry["source"]) == NEW[name]
    assert (entry["better"], entry["layer"], entry["moves"]) == (
        "lower", LAYER, "setup_s")
    # a layer BENCHMARK.json already named, letter for letter, and an
    # end-to-end metric every listed cell reports
    assert LAYER in {e["layer"] for e in bench["per_layer"]
                     if e["name"] not in NEW}
    (setup_s,) = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup_s
    assert entry["workloads"] and set(entry["workloads"]) <= {
        w["name"] for w in bench["workloads"]}
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    assert meta["what"]


def test_the_eight_list_the_same_cells(bench):
    lists = [e["workloads"] for e in bench["per_layer"] if e["name"] in NEW]
    assert len(lists) == len(NEW)
    assert all(cells == lists[0] for cells in lists)
