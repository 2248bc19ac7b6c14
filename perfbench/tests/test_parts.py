"""The parts of `compact`, the lanes a chunk pads and the host's blocked
time (PR 34): `partreduce` on a synthetic trace and the nine readers on
fixture records.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

Tier-1 runs this file too (`tests/test_parts.py` loads it), so the repo's
count holds the yardstick of the parts to its arithmetic.

The trace, hand-worked.  Two devices; device 1 is the busiest (its `while`
alone is 6,000 ns), so device 0's 5,000 ns squeeze is read by nobody.  The
pass runs 1,000-9,000 ns, level 3 1,050-4,000, level 4 4,100-8,000.  On
device 1:

    while (container, under part.append: not a leaf)      1,000 + 6,000
    fsc squeeze scatter                  level 3          1,100 +   300
    fsc novel gather                     level 3          1,500 +   700
    fsc cumsum under compact, no part    level 3          2,300 +    40
    step select                          between levels   4,020 +    60
    dvl append inside the while          level 4          4,200 +   500
    dvl probe (another stage)            level 4          4,800 +   900
    dvl fingerprint inside part.novel    level 4          5,800 +   100
    fsc squeeze before the pass          -                  100 +   200

`compact` is 300 + 700 + 40 + 60 + 500 = 1,600 ns, 40 of them under no
part: 2.5%.  Over 1,000 states 1,600 ns are 0.0016 us a state.
"""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
CELLS = ["kip320-3b-notrace", "kip320-3b-trace", "kip320-5b-notrace",
         "kip320-5b-x4", "firsttry-3b-cex", "asyncisr-4b-constraint"]
# name -> (unit, better, source, layer): what BENCHMARK.json must say
NEW = {
    "compact_select_us_per_state":
        ("us", "lower", "device_trace", "level programs"),
    "compact_squeeze_us_per_state":
        ("us", "lower", "device_trace", "level programs"),
    "compact_novel_us_per_state":
        ("us", "lower", "device_trace", "level programs"),
    "compact_append_us_per_state":
        ("us", "lower", "device_trace", "level programs"),
    "compact_unparted_share":
        ("%", "lower", "device_trace", "level programs"),
    "lane_live_share": ("%", "higher", "program_counter", "kernels"),
    "host_wait_share":
        ("%", "lower", "program_counter", "level loop on the host"),
    "host_work_ms_per_chunk":
        ("ms", "lower", "program_counter", "level loop on the host"),
    "idle_ms_per_dispatch": ("ms", "lower", "device_trace", "device"),
}


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


@pytest.fixture
def partreduce(harness):
    return importlib.import_module("partreduce")


def _op(name, kind, shape="u32[8]{0}"):
    return f"%{name} = {shape} {kind}({shape} %p), calls=%c"


def _trace(parts=True):
    """The trace of the module docstring; `parts` False: the same program
    from before the parts (no `part.*` component anywhere)."""
    def path(text):
        if parts:
            return text
        return "/".join(c for c in text.split("/")
                        if not c.startswith("part."))

    dev1 = [
        ["%while.1 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), "
         "condition=%c, body=%b", 1000, 6000,
         path("jit(dvl_n2)/kspec.compact/part.append/while:")],
        [_op("scatter.1", "scatter"), 1100, 300,
         path("jit(fsc_n2)/kspec.compact/part.squeeze/scatter:")],
        [_op("gather.2", "gather"), 1500, 700,
         path("jit(fsc_n2)/kspec.compact/part.novel/gather:")],
        [_op("fusion.3", "fusion"), 2300, 40,
         "jit(fsc_n2)/kspec.compact/jit(cumsum)/add:"],
        [_op("fusion.4", "fusion"), 4020, 60,
         path("jit(step_n2)/kspec.compact/part.select/jit(cumsum)/add:")],
        [_op("fusion.5", "fusion"), 4200, 500,
         path("jit(dvl_n2)/while/body/kspec.compact/part.append/"
              "dynamic_update_slice:")],
        [_op("fusion.6", "fusion"), 4800, 900,
         "jit(dvl_n2)/while/body/kspec.dedup_probe/while/body/gather:"],
        [_op("fusion.7", "fusion"), 5800, 100,
         path("jit(dvl_n2)/while/body/kspec.compact/part.novel/"
              "kspec.fingerprint/xor:")],
        [_op("scatter.1", "scatter"), 100, 200,
         path("jit(fsc_n2)/kspec.compact/part.squeeze/scatter:")],
    ]
    dev0 = [[_op("scatter.1", "scatter"), 1100, 5000,
             path("jit(fsc_n2)/kspec.compact/part.squeeze/scatter:")]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev0}]},
        {"name": "/device:TPU:1",
         "lines": [{"name": "XLA Ops", "events": dev1},
                   # a second line of the plane is not the op line
                   {"name": "XLA Modules", "events": [
                       ["jit_fsc_n2", 1100, 5000,
                        "jit(fsc_n2)/kspec.compact/part.squeeze/x:"]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["perfbench.pass", 1000, 8000, ""],
            ["kspec.level d=3", 1050, 2950, ""],
            ["kspec.level d=4", 4100, 3900, ""]]}]}]}


def _ns(seconds):
    return {k: round(v * 1e9, 6) for k, v in seconds.items() if v}


@pytest.mark.parametrize("path,part", [
    ("jit(fsc_n2)/kspec.compact/part.squeeze/scatter:", "squeeze"),
    ("jit(dvl_n2)/while/body/kspec.compact/part.append/while/body/"
     "dynamic_update_slice:", "append"),
    ("jit(step_n2)/kspec.compact/part.select:", "select"),
    # `compact` with no part, a part outside the vocabulary, a part that
    # is not inside the stage scope: all `unparted`, the check's numerator
    ("jit(fsc_n1)/kspec.compact/jit(cumsum)/add:", "unparted"),
    ("jit(x)/kspec.compact/part.bogus/add:", "unparted"),
    ("jit(x)/part.novel/kspec.compact/add:", "unparted"),
    # the innermost `kspec.*` component decides the stage, as in stage_of
    ("jit(x)/kspec.compact/part.novel/kspec.fingerprint/xor:", None),
    ("jit(x)/kspec.dedup_probe/gather:", None),
    ("jit(x)/while:", None),
    ("", None),
])
def test_part_of(path, part, partreduce):
    import stagereduce

    assert partreduce.part_of(path) == part
    # the stage split never meets a part: it reads what it read before
    stage = stagereduce.stage_of(path)
    assert (stage == "compact") == (part is not None)
    assert stage in stagereduce.STAGES + (stagereduce.UNNAMED,)


def test_parts_on_the_synthetic_trace(partreduce):
    import stagereduce

    trace = _trace()
    stages = stagereduce.reduce_stages(trace)
    assert stages["plane"] == "/device:TPU:1"
    got = partreduce.reduce_parts(trace, stages)
    assert got["plane"] == "/device:TPU:1"
    assert _ns(got["part_s"]) == {"select": 60.0, "squeeze": 300.0,
                                  "novel": 700.0, "append": 500.0,
                                  "unparted": 40.0}
    # the five are the stage's own seconds, to the nanosecond
    assert got["compact_s"] == pytest.approx(stages["stage_s"]["compact"],
                                             abs=1e-18)
    assert round(got["compact_s"] * 1e9, 6) == 1600.0
    assert {d: _ns(v) for d, v in got["by_level"].items()} == {
        3: {"squeeze": 300.0, "novel": 700.0, "unparted": 40.0},
        4: {"append": 500.0}}  # the select ran between two levels
    assert {p: _ns(v) for p, v in got["by_program"].items()} == {
        "fsc_n2": {"squeeze": 300.0, "novel": 700.0, "unparted": 40.0},
        "step_n2": {"select": 60.0}, "dvl_n2": {"append": 500.0}}
    # the stage reduction is the same with and without the parts
    before = stagereduce.reduce_stages(_trace(parts=False))
    assert before["stage_s"] == stages["stage_s"]
    assert before["by_level"] == stages["by_level"]


def test_a_program_without_parts_reads_nothing(partreduce):
    import stagereduce

    trace = _trace(parts=False)
    assert partreduce.reduce_parts(
        trace, stagereduce.reduce_stages(trace)) is None
    stages = stagereduce.reduce_stages(_trace())
    assert partreduce.reduce_parts({"planes": []}, stages) is None


@pytest.fixture
def traced_ctx(partreduce, monkeypatch, tmp_path):
    """A context whose traced pass's profile is the synthetic trace:
    `stagereduce.for_ctx` and `partreduce.for_ctx` find it with no
    `.xplane.pb` on disk."""
    import stagereduce

    def ctx_for(trace, states=1000):
        xplane = str(tmp_path / f"{id(trace)}.xplane.pb")
        monkeypatch.setattr(stagereduce, "find_xplane", lambda d: xplane)
        monkeypatch.setattr(partreduce, "find_xplane", lambda d: xplane)
        monkeypatch.setattr(stagereduce, "load_xplane", lambda p: trace)
        run_dir = tmp_path / "traced.0"
        return {"rehearsal": False, "traced": {
            "manifest": {"dir": str(run_dir)}, "total": states,
            "t0_unix": None, "spans": {"spans": [], "events": []}}}

    return ctx_for


def test_readers_of_the_parts(harness, traced_ctx, tmp_path):
    readers = harness.load_metric_readers()
    ctx = traced_ctx(_trace())
    got = {p: readers[f"compact_{p}_us_per_state"].read(ctx)
           for p in ("select", "squeeze", "novel", "append")}
    assert got == pytest.approx(
        {"select": 6e-5, "squeeze": 3e-4, "novel": 7e-4, "append": 5e-4})
    share = readers["compact_unparted_share"].read(ctx)
    assert share == pytest.approx(2.5)
    # the acceptance identity: the four sum to the stage less the share
    stage = readers["stage_compact_us_per_state"].read(ctx)
    assert stage == pytest.approx(1.6e-3)
    assert sum(got.values()) == pytest.approx(stage * (1 - share / 100))
    with open(tmp_path / "trace_parts.json") as fh:
        left = json.load(fh)
    assert left["states"] == 1000 and left["reduce_s"] >= 0
    assert set(left["by_level"]) == {"3", "4"}
    assert set(left["by_program"]) == {"fsc_n2", "step_n2", "dvl_n2"}
    assert os.path.exists(tmp_path / "trace_stages.json")


def test_readers_of_the_parts_read_nothing_of_the_parent(harness, traced_ctx,
                                                         tmp_path):
    readers = harness.load_metric_readers()
    ctx = traced_ctx(_trace(parts=False))
    names = [n for n in NEW if n.startswith("compact_")]
    assert len(names) == 5
    assert [readers[n].read(ctx) for n in names] == [None] * 5
    assert readers["stage_compact_us_per_state"].read(ctx) \
        == pytest.approx(1.6e-3)
    assert not os.path.exists(tmp_path / "trace_parts.json")
    # no traced pass, a rehearsal, a manifest without `dir`: nothing
    for ctx in ({"traced": None}, dict(ctx, rehearsal=True),
                {"traced": {"manifest": {}}, "rehearsal": False}):
        assert [readers[n].read(ctx) for n in names] == [None] * 5


# --- the three readers of the level records ---------------------------------

def _pass(*levels):
    """A pass reduced to what the readers read: one record a level,
    (level_ms, fetch_ms, chunks, enabled_candidates, dedup_lanes); None in
    a place is a record without that field (the parent's program)."""
    keys = ("level_ms", "fetch_ms", "chunks", "enabled_candidates",
            "dedup_lanes")
    return {"level_records": [
        dict({"depth": d}, **{k: v for k, v in zip(keys, lv)
                              if v is not None})
        for d, lv in enumerate(levels, 1)]}


@pytest.mark.parametrize("passes,want", [
    # hand-worked: a one-chunk level and a ten-chunk one.  Blocked 40 +
    # 1,500 of 100 + 1,900 ms: 77%; the host's own 60 + 400 ms over 11
    # chunks: 41.81...; 1,000 + 2,000,000 candidates in 4,096 + 4,751,360
    # lanes: 42.07...%
    ([_pass((100.0, 40.0, 1, 1000, 4096),
            (1900.0, 1500.0, 10, 2000000, 4751360))],
     (77.0, 460.0 / 11, 100.0 * 2001000 / 4755456)),
    # the median over passes of each pass's own ratio
    ([_pass((10.0, 1.0, 1, 1, 10)), _pass((10.0, 3.0, 2, 5, 10)),
      _pass((10.0, 2.0, 1, 2, 10), (10.0, 2.0, 3, 2, 10))],
     (20.0, 4.0, 20.0)),
    # every lane live, no blocked time
    ([_pass((5.0, 0.0, 1, 64, 64))], (0.0, 5.0, 100.0)),
    # the parent's records: none of the fields, nothing to read
    ([_pass((10.0, None, 1, 5, None)), _pass((10.0, None, 1, 5, None))],
     (None, None, None)),
    # the sharded parent's: no `chunks` either
    ([_pass((10.0, None, None, 5, None))], (None, None, None)),
    # a record without them anywhere in a pass: that pass reads nothing
    ([_pass((10.0, 5.0, 1, 5, 10), (10.0, None, 1, 5, None)),
      _pass((10.0, 5.0, 2, 5, 10))], (50.0, 2.5, 50.0)),
    # `fetch_ms` without `chunks`: the share reads, the per-chunk does not
    ([_pass((10.0, 5.0, None, 5, 10))], (50.0, None, 50.0)),
    ([_pass()], (None, None, None)),
    ([], (None, None, None)),
])
def test_record_readers(passes, want, harness):
    readers = harness.load_metric_readers()
    ctx = {"passes": passes}
    got = tuple(readers[n].read(ctx) for n in (
        "host_wait_share", "host_work_ms_per_chunk", "lane_live_share"))
    assert got == tuple(None if w is None else pytest.approx(w)
                        for w in want)


@pytest.mark.parametrize("trace,records,want", [
    # 0.25 + 0.15 + 0.1 s of gaps over (6 - 1) + 5 committed dispatches
    ({"idle_by": {"host-assembly": 0.25, "compact-host": 0.15,
                  "dispatch": 0.1}},
     [{"dispatches": 6, "discarded_dispatches": 1},
      {"dispatches": 5, "discarded_dispatches": 0}], 50.0),
    # a device that never idles
    ({"idle_by": {}}, [{"dispatches": 2, "discarded_dispatches": 0}], 0.0),
    # no trace reduction; records without the counters; nothing committed
    (None, [{"dispatches": 2, "discarded_dispatches": 0}], None),
    ({"idle_by": {"step": 1.0}}, [{"level_ms": 1.0}], None),
    ({"idle_by": {"step": 1.0}}, [], None),
    ({"idle_by": {"step": 1.0}},
     [{"dispatches": 1, "discarded_dispatches": 1}], None),
])
def test_idle_ms_per_dispatch(trace, records, want, harness):
    reader = harness.load_metric_readers()["idle_ms_per_dispatch"]
    got = reader.read({"trace": trace,
                       "traced": {"level_records": records}})
    assert got == (None if want is None else pytest.approx(want))
    assert reader.read({"trace": trace, "traced": None}) is None


# --- BENCHMARK.json ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_says_what_its_reader_says(name, harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    meta = harness.load_metric_readers()[name].META
    # found by name: an entry appended after these must not move them
    (entry,) = [e for e in bench["per_layer"] if e["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == CELLS
    assert {k: meta[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == NEW[name]
    assert entry["moves"] == "states_per_s"
    assert set(entry["workloads"]) == {c["name"] for c in bench["workloads"]}
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"]
                              if e["name"] not in NEW}
    assert meta["what"]


def test_the_nine_entries_end_the_list_in_the_issues_order(harness):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [e["name"] for e in bench["per_layer"]]
    first = names.index("compact_select_us_per_state")
    assert names[first: first + len(NEW)] == list(NEW)
    assert names[first - 1] == "merge_live_share"
