"""Observability subsystem: run dirs, spans, metrics, report, CLI.

Tier-1 contracts (ISSUE 3):
- a crashed-mid-level run directory still renders a report;
- span JSONL lines are untearable (a torn FINAL line is tolerated, exactly
  like the mosaic ladder's append-only banking);
- the `stats_path` shim emits records identical to the pre-obs stream on a
  known model (volatile wall-clock fields aside);
- `cli check --run-dir` + `cli report` works on both engines, including
  with a forced tiny `--mem-budget` (spill accounting) and under the
  `KSPEC_FAULT=crash@level` injector.
"""

import json
import os

import pytest

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.obs import (
    MetricsRegistry,
    RunContext,
    SpanTracer,
    read_jsonl_tolerant,
    render_report,
    report_data,
)
from kafka_specification_tpu.obs.report import eta
from kafka_specification_tpu.obs.tracer import set_tracer
from kafka_specification_tpu.resilience.faults import InjectedCrash
from kafka_specification_tpu.utils.cli import main as cli_main

pytestmark = pytest.mark.obs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MINI_RUN = os.path.join(_REPO, "tests", "data", "mini_run")

# volatile fields: wall-clock and run-correlation stamps; everything else
# in a level record is deterministic for a fixed model
_VOLATILE = ("ts", "unix", "level_ms", "step_ms", "host_ms", "run_id")


def _strip(rec):
    return {k: v for k, v in rec.items() if k not in _VOLATILE}


def _records(path):
    return [json.loads(l) for l in open(path).read().splitlines()]


# --- run context ---------------------------------------------------------


def test_run_context_manifest_and_resume_lineage(tmp_path):
    d = str(tmp_path / "r")
    run = RunContext(d)
    man = json.load(open(run.manifest_path))
    assert man["run_id"] == run.run_id
    assert man["status"] == "running"
    assert man["lineage"][0]["event"] == "open"
    run.record_config(module="Toy", engine="bfs")
    run.finish("complete", distinct_states=42)
    man = json.load(open(run.manifest_path))
    assert man["status"] == "complete"
    assert man["result"]["distinct_states"] == 42
    assert man["config"]["module"] == "Toy"
    # reopening the same directory resumes the SAME run_id and appends to
    # the lineage (supervised restarts correlate under one run)
    run2 = RunContext(d)
    assert run2.run_id == run.run_id
    man = json.load(open(run.manifest_path))
    assert [e["event"] for e in man["lineage"]][-1] == "reopen"
    assert man["status"] == "running"


def test_default_run_dir_honors_runs_root(tmp_path, monkeypatch):
    monkeypatch.setenv("KSPEC_RUNS_ROOT", str(tmp_path / "allruns"))
    run = RunContext()
    assert run.dir.startswith(str(tmp_path / "allruns"))
    assert os.path.isfile(run.manifest_path)


# --- span tracer ---------------------------------------------------------


def test_tracer_nesting_and_event(tmp_path):
    p = str(tmp_path / "spans.jsonl")
    tr = SpanTracer(p, "run-x")
    with tr.span("outer", depth=3):
        with tr.span("inner", item=1):
            pass
        tr.event("retry", attempt=1)
    tr.close()
    recs = read_jsonl_tolerant(p)
    assert [r.get("span", r.get("event")) for r in recs] == [
        "inner", "retry", "outer",
    ]
    inner, ev, outer = recs
    assert inner["parent_id"] == outer["span_id"] != inner["span_id"]
    assert all(r["run_id"] == "run-x" for r in recs)
    # the envelope stamps the end to the millisecond, t0 is the start to
    # the microsecond
    assert all(r["unix"] + 0.0005 >= r["t0"] for r in (inner, outer))
    assert ev["kind"] == "event" and ev["attempt"] == 1


def test_span_jsonl_untearable_torn_lines(tmp_path):
    """Mirror of the ladder fix: a hard kill can tear at most the final
    appended line.  A supervised restart then appends PAST the tear (one
    shared file per run dir), so the reader must skip torn lines anywhere
    and keep every intact record around them."""
    p = str(tmp_path / "spans.jsonl")
    tr = SpanTracer(p, "run-x")
    for i in range(5):
        with tr.span("level", depth=i):
            pass
    tr.close()
    whole = open(p, "rb").read()
    torn = whole[: len(whole) - 17]  # rip through the last record
    open(p, "wb").write(torn)
    recs = read_jsonl_tolerant(p)
    assert len(recs) == 4 and recs[-1]["depth"] == 3
    # a tear mid-file (kill, then restart appended after it): the records
    # on both sides survive, only the torn line is dropped
    lines = whole.split(b"\n")
    lines[1] = lines[1][:10]
    open(p, "wb").write(b"\n".join(lines))
    recs = read_jsonl_tolerant(p)
    assert [r["depth"] for r in recs] == [0, 2, 3, 4]


def test_metrics_registry_and_prom_export(tmp_path):
    m = MetricsRegistry("run-y")
    m.inc("kspec_states_total", 10)
    m.inc("kspec_states_total", 5)
    m.set_gauge("kspec_frontier", 123)
    m.set_gauge("kspec_shard_new", 7, shard=1)
    m.observe("kspec_level_ms", 42.0)
    m.observe("kspec_level_ms", 9000.0)
    snap = m.snapshot()
    assert snap["counters"]["kspec_states_total"] == 15
    assert snap["gauges"]['kspec_shard_new{shard="1"}'] == 7
    assert snap["histograms"]["kspec_level_ms"]["count"] == 2
    prom = str(tmp_path / "m.prom")
    m.write_prom(prom)
    text = open(prom).read()
    assert "# TYPE kspec_states_total counter" in text
    assert 'kspec_states_total{run_id="run-y"} 15' in text
    assert "# TYPE kspec_frontier gauge" in text
    assert 'kspec_shard_new{shard="1",run_id="run-y"} 7' in text
    # histogram: cumulative buckets + sum + count, all run_id-labelled
    assert 'kspec_level_ms_bucket{le="50",run_id="run-y"} 1' in text
    assert 'kspec_level_ms_bucket{le="+Inf",run_id="run-y"} 2' in text
    assert 'kspec_level_ms_count{run_id="run-y"} 2' in text
    jl = str(tmp_path / "m.jsonl")
    m.write_jsonl(jl)
    rec = _records(jl)[0]
    assert rec["kind"] == "metrics" and rec["run_id"] == "run-y"


# --- stats shim equivalence ---------------------------------------------


def test_stats_shim_record_for_record_identical(tmp_path):
    """The legacy stats_path stream must be unchanged by the obs refactor:
    same record set with and without a run context (minus the volatile
    wall-clock fields and the run_id stamp), no run_id on the bare path,
    and file records == result.stats['levels']."""
    bare = str(tmp_path / "bare.jsonl")
    r1 = check(frl.make_model(2, 2, 2), min_bucket=32, stats_path=bare)
    run = RunContext(str(tmp_path / "run"))
    r2 = check(frl.make_model(2, 2, 2), min_bucket=32, run=run)
    assert r1.total == r2.total == 49
    recs_bare = _records(bare)
    recs_run = _records(run.stats_path)
    assert [_strip(r) for r in recs_bare] == [_strip(r) for r in recs_run]
    # legacy schema exactly: envelope + historical fields, nothing else
    assert list(recs_bare[0]) == [
        "kind", "ts", "unix", "depth", "frontier", "enabled_candidates",
        "new", "duplicates", "total", "level_ms", "step_ms", "host_ms",
        "action_enablement",
    ]
    assert all("run_id" not in r for r in recs_bare)
    assert all(r["run_id"] == run.run_id for r in recs_run)
    # result.stats['levels'] additionally carries the engine-local
    # successor-launch accounting (engine/pipeline.py) and the PR 10
    # overlap attribution, and the dispatch / transfer / store counters
    # (engine/hostio.py) — in-memory only, never in the pinned stream
    from kafka_specification_tpu.engine.hostio import LEVEL_COUNTERS
    from kafka_specification_tpu.engine.pipeline import WORK_FIELDS

    assert [
        {k: v for k, v in r.items()
         if k not in ("successor_launches", "launches_per_chunk_max",
                      "io_hidden_ms", "io_exposed_ms",
                      "overlap_efficiency", "host_probe_ms",
                      "store_ms", "chunks", "chunks_ahead", "dedup_lanes",
                      "guard_lanes", "probes", "probes_windowed")
         + WORK_FIELDS + LEVEL_COUNTERS}
        for r in r1.stats["levels"]
    ] == recs_bare


# --- the probe's round counts (level records, both engines) -------------

KIP320_LEVELS_TO_8 = [1, 6, 30, 138, 366, 1170, 2715, 5673, 10836]


def _assert_probe_rounds(records):
    """Every record: 0 <= probe_rounds <= probe_rounds_plain, and a level
    that dispatched a probing program counts the capacity's rounds (a probe
    of an empty set runs none of its own)."""
    for rec in records:
        assert 0 <= rec["probe_rounds"] <= rec["probe_rounds_plain"], rec
        if rec["dispatches"]:
            assert rec["probe_rounds_plain"] > 0, rec


@pytest.mark.parametrize("pipeline", [None, "device"],
                         ids=["fused", "whole-level"])
def test_level_records_carry_the_probe_rounds(tmp_path, pipeline):
    """configs/Kip320.cfg cut to depth 8: every level record says how many
    search rounds its probes ran and how many a search of the whole
    capacity would have; the counts are the golden's either way, and the
    emitted stream does not gain the fields."""
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    run = RunContext(str(tmp_path / "run"))
    res = check(model, max_depth=8, run=run, pipeline=pipeline)
    assert res.ok and res.levels == KIP320_LEVELS_TO_8
    records = res.stats["levels"]
    assert len(records) == 8
    _assert_probe_rounds(records)
    # hashed fingerprints: the directory does the work, far under the
    # fixed count, and the late levels do search (the set is not empty)
    assert records[-1]["probe_rounds"] > 0
    assert sum(r["probe_rounds"] for r in records) < \
        0.6 * sum(r["probe_rounds_plain"] for r in records)
    assert all("probe_rounds" not in r for r in _records(run.stats_path))


def test_cut_level_carries_the_probe_rounds(tmp_path):
    """The level a verdict cuts (the rejected KIP-320 design at three
    replicas and one record a log, the smallest constants at which it
    fails: configs/Kip320FirstTry.cfg's own job is the slow set's) has the
    probe's four fields too; verdict and counts are the ones tests/test_cex_cell.py
    replays through the oracle."""
    from kafka_specification_tpu.models import kip320
    from kafka_specification_tpu.models.kafka_replication import Config
    from kafka_specification_tpu.ops import dedup

    model = kip320.make_first_try_model(Config(3, 1, 1, 2))
    res = check(model, min_bucket=1024, run=RunContext(str(tmp_path / "r")))
    v = res.violation
    assert (v.invariant, v.depth, len(v.trace), res.total) == \
        ("WeakIsr", 11, 12, 78832)
    cut = res.stats["cut_level"]
    _assert_probe_rounds(res.stats["levels"] + [cut])
    assert cut["probe_rounds_plain"] > 0 and cut["dispatches"] > 0
    _assert_merge_slots(res.stats["levels"] + [cut])
    assert cut["merge_slots"] > 0
    _assert_probe_lanes(res.stats["levels"] + [cut], dedup.PROBE_BLOCK)
    assert 0 < cut["probe_lanes"] <= cut["probe_lanes_plain"] \
        == cut["dedup_lanes"]
    # and the new states' compaction's rows, over its committed chunks
    for rec in res.stats["levels"] + [cut]:
        assert 0 < rec["novel_rows"] and \
            rec["novel_rows_plain"] == rec["dedup_lanes"], rec


# --- the probe's lane counts (level records, both engines) --------------

def _assert_probe_lanes(records, block):
    """Every record: whole blocks of query lanes searched, no more than
    the lanes the probes were handed plus a block's rounding a probe (a
    width that is no multiple of its block searches its overlap twice),
    and at least the candidates the level's dispatches held."""
    for rec in records:
        assert 0 <= rec["probe_lanes"] <= \
            rec["probe_lanes_plain"] + 3 * block * rec["dispatches"], rec
        if rec["dispatches"]:
            assert rec["probe_lanes_plain"] >= rec["dedup_lanes"] > 0, rec
            # (the cut level's record has no candidate count)
            assert rec["probe_lanes"] >= rec.get("enabled_candidates", 0), rec


@pytest.mark.parametrize("pipeline", [None, "device"],
                         ids=["fused", "whole-level"])
def test_level_records_carry_the_probe_lanes(tmp_path, monkeypatch, pipeline):
    """configs/Kip320.cfg cut to depth 7 at a probe block of 256 lanes,
    twice: every level record says how many query lanes its probes searched
    (blocks of the live prefix of each probe's sorted queries) beside the
    lanes they were handed; one probe a dispatch on the fused path, so
    handed == `dedup_lanes` there, and two a chunk and the level-new rank
    in a whole-level program; the two runs agree to the lane, the probes
    search a fraction of the layout where a level is many blocks wide, the
    counts are the golden's, and the emitted stream does not gain the
    fields."""
    from kafka_specification_tpu.ops import dedup
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    monkeypatch.setattr(dedup, "PROBE_BLOCK", 256)
    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    runs = []
    for i in range(2):
        run = RunContext(str(tmp_path / f"run{i}"))
        res = check(model, max_depth=7, run=run, pipeline=pipeline)
        assert res.ok and res.levels == KIP320_LEVELS_TO_8[:8]
        _assert_probe_lanes(res.stats["levels"], 256)
        runs.append([(r["probe_lanes"], r["probe_lanes_plain"],
                      r["dedup_lanes"]) for r in res.stats["levels"]])
        assert all("probe_lanes" not in r for r in _records(run.stats_path))
    assert runs[0] == runs[1]
    for _lanes, plain, handed in runs[0]:
        assert plain == handed if pipeline is None else plain >= handed
    if pipeline == "device":  # the last level ran the whole-level program
        assert runs[0][-1][1] > 2 * runs[0][-1][2]
    assert sum(r[0] for r in runs[0]) < 0.6 * sum(r[1] for r in runs[0])


# --- the merge's slot counts (level records, both engines) --------------


def _assert_merge_slots(records):
    """Every record: 0 <= merge_slots <= merge_slots_plain, and a level
    that dispatched a merging program counts the capacity's slots."""
    for rec in records:
        assert 0 <= rec["merge_slots"] <= rec["merge_slots_plain"], rec
        if rec["dispatches"]:
            assert rec["merge_slots_plain"] > 0, rec


def _merge_slots(records):
    return [(r["merge_slots"], r["merge_slots_plain"]) for r in records]


@pytest.mark.parametrize("block", [None, 256],
                         ids=["engine-block", "block-256"])
@pytest.mark.parametrize("pipeline", [None, "device"],
                         ids=["fused", "whole-level"])
def test_level_records_carry_the_merge_slots(tmp_path, monkeypatch,
                                             pipeline, block):
    """configs/Kip320.cfg cut to depth 7, twice: every level record says
    how many slots its merges' loops touched and how many merges over the
    whole capacity touch; the two runs agree to the slot, the counts are
    the golden's whatever the block (at 256 slots the loops run several
    blocks a merge and touch a fraction of the capacity), and the emitted
    stream does not gain the fields."""
    from kafka_specification_tpu.ops import dedup
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    if block:
        monkeypatch.setattr(dedup, "MERGE_BLOCK", block)
    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    runs = []
    for i in range(2):
        run = RunContext(str(tmp_path / f"run{i}"))
        res = check(model, max_depth=7, run=run, pipeline=pipeline)
        assert res.ok and res.levels == KIP320_LEVELS_TO_8[:8]
        runs.append(res.stats["levels"])
        _assert_merge_slots(runs[-1])
        assert all("merge_slots" not in r for r in _records(run.stats_path))
    assert _merge_slots(runs[0]) == _merge_slots(runs[1])
    assert runs[0][-1]["merge_slots"] > 0
    if block:
        assert all(r["merge_slots"] % block == 0 for r in runs[0])
        assert sum(r["merge_slots"] for r in runs[0]) < \
            0.5 * sum(r["merge_slots_plain"] for r in runs[0])


# --- the new states' compaction's row counts (level records) -------------


@pytest.mark.parametrize("pipeline", [None, "device"],
                         ids=["fused", "whole-level"])
def test_level_records_carry_the_novel_rows(tmp_path, monkeypatch, pipeline):
    """configs/Kip320.cfg cut to depth 7 at a compaction block of 256 rows,
    twice: every level record says how many rows the loops of the new
    states' compaction touched (blocks of the live prefix + blocks of new
    rows) beside the rows a full-width compaction touches, which is the
    width dedup was handed (`dedup_lanes`); the two runs agree to the
    row, the loops touch a fraction of the width where a level is many
    blocks wide, and the emitted stream does not gain the fields."""
    from kafka_specification_tpu.engine import pipeline as pl
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    monkeypatch.setattr(pl, "NOVEL_BLOCK", 256)
    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    runs = []
    for i in range(2):
        run = RunContext(str(tmp_path / f"run{i}"))
        res = check(model, max_depth=7, run=run, pipeline=pipeline)
        assert res.ok and res.levels == KIP320_LEVELS_TO_8[:8]
        runs.append([(r["novel_rows"], r["novel_rows_plain"], r["dedup_lanes"])
                     for r in res.stats["levels"]])
        assert all("novel_rows" not in r for r in _records(run.stats_path))
    assert runs[0] == runs[1]
    for rows, plain, lanes in runs[0]:
        assert 0 < rows and rows % 256 == 0 and plain == lanes
    assert sum(r[0] for r in runs[0]) < 0.5 * sum(r[1] for r in runs[0])


# --- engine-threaded run dirs -------------------------------------------


def test_run_dir_artifacts_single_device(tmp_path):
    run = RunContext(str(tmp_path / "run"))
    res = check(frl.make_model(2, 2, 2), min_bucket=32, run=run)
    assert res.total == 49
    man = json.load(open(run.manifest_path))
    assert man["status"] == "complete"
    assert man["result"]["distinct_states"] == 49
    assert man["config"]["engine"] == "bfs"
    spans = read_jsonl_tolerant(run.spans_path)
    kinds = {(s.get("span"), s.get("ph")) for s in spans}
    assert ("level", "B") in kinds and ("level", "E") in kinds
    assert ("step", "E") in kinds and ("host-assembly", "E") in kinds
    prom = open(run.metrics_prom).read()
    assert f'kspec_states_total{{run_id="{run.run_id}"}} 48' in prom
    assert "kspec_level_ms_bucket" in prom
    report = render_report(run.dir)
    assert "COMPLETE" in report and "Action enablement" in report


def test_sharded_per_shard_breakdowns_and_imbalance(tmp_path):
    from kafka_specification_tpu.parallel.sharded import check_sharded

    run = RunContext(str(tmp_path / "run"))
    res = check_sharded(frl.make_model(2, 2, 2), min_bucket=32, run=run)
    assert res.total == 49
    recs = _records(run.stats_path)
    import jax

    D = len(jax.devices())
    for rec in recs:
        # satellite: per-shard breakdowns ride every level record so
        # exchange imbalance is visible without re-running
        assert len(rec["shard_new"]) == D
        assert len(rec["shard_frontier"]) == D
        assert len(rec["shard_enabled"]) == D
        assert sum(rec["shard_new"]) == rec["new"]
        assert sum(rec["shard_frontier"]) == rec["frontier"]
        assert sum(rec["shard_enabled"]) == rec["enabled_candidates"]
    # result.stats['levels'] additionally carries the PR 10 exchange/
    # overlap accounting and the host/device split and transfer counters
    # of engine/hostio.py — in-memory only, never in the pinned stream
    from kafka_specification_tpu.engine.hostio import LEVEL_COUNTERS
    from kafka_specification_tpu.engine.pipeline import WORK_FIELDS

    assert [
        {k: v for k, v in r.items()
         if k not in ("exch_bytes", "exch_raw_bytes", "io_hidden_ms",
                      "io_exposed_ms", "shard_launches",
                      "host_probe_ms", "step_ms", "host_ms", "chunks",
                      "dedup_lanes", "guard_lanes") + WORK_FIELDS
         + LEVEL_COUNTERS}
        for r in res.stats["levels"]
    ] == recs
    prom = open(run.metrics_prom).read()
    assert "kspec_shard_imbalance" in prom
    assert f'kspec_shard_new{{shard="0",run_id="{run.run_id}"}}' in prom
    spans = read_jsonl_tolerant(run.spans_path)
    assert any(s.get("span") == "exchange" for s in spans)


def test_sharded_host_backend_shard_duplicates(tmp_path):
    from kafka_specification_tpu.parallel.sharded import check_sharded

    run = RunContext(str(tmp_path / "run"))
    res = check_sharded(
        frl.make_model(2, 2, 2), min_bucket=32, visited_backend="host",
        run=run,
    )
    assert res.total == 49
    recs = _records(run.stats_path)
    # host backend: the coordinator sees the novelty masks, so per-owner
    # duplicate counts are exact and present
    assert all("shard_duplicates" in r for r in recs)
    assert all(
        all(d >= 0 for d in r["shard_duplicates"]) for r in recs
    )


# --- crash + report (acceptance criterion) ------------------------------


def test_crashed_mid_level_run_dir_still_renders(tmp_path, monkeypatch):
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    run = RunContext(str(tmp_path / "run"))
    with pytest.raises(InjectedCrash):
        check(frl.make_model(2, 2, 2), min_bucket=32, run=run)
    set_tracer(None)  # the crash skipped the observer's teardown
    # manifest still says "running" (nobody finalized it) + dead pid in a
    # subprocess world; in-process the pid is alive, so force the verdict
    # path that only depends on heartbeat age by rendering "now" far ahead
    rep = render_report(run.dir, now=__import__("time").time() + 10_000)
    assert "Run " + run.run_id in rep
    assert "Per-level throughput" in rep
    assert ("STALLED" in rep) or ("CRASHED" in rep)
    data = report_data(run.dir, now=__import__("time").time() + 10_000)
    assert data["verdict"]["status"] in ("stalled", "crashed")
    assert len(data["levels"]) >= 1  # the levels before the crash survive


def test_report_on_empty_and_partial_dirs(tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    rep = render_report(str(empty))
    assert "No per-level stats" in rep
    # stats only, no manifest — e.g. artifacts copied off a dead box
    part = tmp_path / "part"
    part.mkdir()
    (part / "stats.jsonl").write_text(
        json.dumps({"kind": "level", "unix": 1.0, "depth": 1, "frontier": 1,
                    "new": 3, "enabled_candidates": 4, "duplicates": 1,
                    "total": 4, "level_ms": 10.0}) + "\n"
    )
    rep = render_report(str(part))
    assert "Per-level throughput" in rep


def test_eta_fit_directions():
    def lv(depth, new):
        return {"kind": "level", "depth": depth, "new": new,
                "level_ms": 1000.0, "total": 0}

    shrink = [lv(i, int(1e6 * 0.5 ** i)) for i in range(1, 8)]
    e = eta(shrink)
    assert e["status"] == "fit" and e["growth_ratio"] < 1
    assert e["est_remaining_states"] > 0 and "eta_seconds" in e
    grow = [lv(i, 10 * 2 ** i) for i in range(1, 8)]
    e = eta(grow)
    assert e["growth_ratio"] > 1 and "eta_seconds" not in e
    assert eta([lv(1, 5)])["status"] == "insufficient-data"


# --- CLI -----------------------------------------------------------------


def test_cli_check_run_dir_then_report(tmp_path, capsys):
    d = str(tmp_path / "run")
    rc = cli_main(
        ["check", os.path.join(_REPO, "configs", "IdSequence.cfg"),
         "--hand", "--run-dir", d, "--json"]
    )
    assert rc == 0
    capsys.readouterr()
    rc = cli_main(["report", d])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[COMPLETE]" in out
    assert "Per-level throughput" in out
    assert "Action enablement" in out
    assert "NextId" in out
    assert "Stall verdict: complete" in out
    rc = cli_main(["report", d, "--json"])
    data = json.loads(capsys.readouterr().out)
    assert data["verdict"]["status"] == "complete"
    assert data["manifest"]["config"]["module"] == "IdSequence"


@pytest.mark.spill
def test_cli_spill_run_dir_report_both_engines(tmp_path, capsys):
    """Acceptance criterion: --mem-budget spill accounting shows up in
    `cli report` on both engines (the forced tiny budget spills runs)."""
    for tag, extra in (("b", []), ("s", ["--sharded"])):
        d = str(tmp_path / f"run{tag}")
        rc = cli_main(
            ["check", os.path.join(_REPO, "configs", "IdSequence.cfg"),
             "--hand", "--run-dir", d, "--mem-budget", "1K", "--json"]
            + extra
        )
        assert rc == 0
        capsys.readouterr()
        assert cli_main(["report", d]) == 0
        out = capsys.readouterr().out
        assert "spill" in out.lower(), out
        assert "kspec_spill_runs" in out


def test_cli_report_mini_run_smoke(capsys):
    """Fast-suite smoke over the checked-in miniature run directory: a
    supervised sharded spill run killed mid-level (the post-mortem case
    the report exists for)."""
    assert cli_main(["report", _MINI_RUN]) == 0
    out = capsys.readouterr().out
    assert "[CRASHED]" in out or "[STALLED]" in out
    assert "died mid-level: level 9" in out
    assert "Per-level throughput" in out
    assert "imbalance max/mean" in out
    assert "LeaderWrite" in out
    assert "kspec_spill_disk_fps" in out
    assert "stall-kill" in out and "restart" in out and "retry" in out
    assert "ETA: frontier decaying" in out
    # torn-final-line tolerance end to end: report survives a ripped tail
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp()
    dst = os.path.join(tmp, "mini")
    shutil.copytree(_MINI_RUN, dst)
    with open(os.path.join(dst, "stats.jsonl"), "ab") as fh:
        fh.write(b'{"kind": "level", "torn": tr')
    assert cli_main(["report", dst]) == 0
    assert "Per-level throughput" in capsys.readouterr().out
    shutil.rmtree(tmp, ignore_errors=True)


def test_supervisor_events_run_id_stamped(tmp_path):
    from kafka_specification_tpu.resilience.supervisor import (
        SupervisorConfig,
        supervise,
    )

    ev = str(tmp_path / "events.jsonl")
    cfg = SupervisorConfig(
        cmd=["true"], events=ev, max_restarts=0, run_id="run-z"
    )
    assert supervise(cfg) == 0
    events = _records(ev)
    assert [e["event"] for e in events] == ["start", "exit", "complete"]
    assert all(e["run_id"] == "run-z" for e in events)
    assert all(e["kind"] == "supervisor" for e in events)


# --- concurrency: multiple in-process jobs (the serving daemon's regime) --


def test_concurrent_tracers_no_tearing_no_cross_stamping(tmp_path):
    """Two jobs in one process, each with its own RunContext, writing
    spans CONCURRENTLY: every line parses strictly (no tearing), and each
    file carries only its own run_id (the thread-local active tracer
    cannot cross-stamp)."""
    import threading

    ctxs = [RunContext(str(tmp_path / f"run{i}")) for i in range(2)]
    n_spans = 300
    errs = []

    def job(ctx):
        try:
            from kafka_specification_tpu.obs import tracer as tr

            ctx.activate()
            for i in range(n_spans):
                with tr.span("work", i=i):
                    pass
                tr.event("tick", i=i)
        except Exception as e:  # pragma: no cover - surfaced by assert
            errs.append(e)

    threads = [threading.Thread(target=job, args=(c,)) for c in ctxs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for ctx in ctxs:
        ctx.tracer.close()
        with open(ctx.spans_path) as fh:
            lines = fh.read().splitlines()
        recs = [json.loads(line) for line in lines]  # STRICT: no tears
        # (the run directory's own run-open span aside)
        assert sum(r.get("span") == "work" or r.get("event") == "tick"
                   for r in recs) == 2 * n_spans
        assert {r["run_id"] for r in recs} == {ctx.run_id}  # no cross-stamp


def test_shared_tracer_concurrent_writers_whole_lines(tmp_path):
    """One tracer shared by many threads (a batched group's workers):
    every record lands whole and span ids stay unique."""
    import threading

    tracer = SpanTracer(str(tmp_path / "spans.jsonl"), "run-shared")
    n, per = 4, 200

    def worker(k):
        for i in range(per):
            tracer.emit_span("w", 0.0, 0.001, worker=k, i=i)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tracer.close()
    with open(tmp_path / "spans.jsonl") as fh:
        recs = [json.loads(line) for line in fh.read().splitlines()]
    assert len(recs) == n * per
    ids = [r["span_id"] for r in recs]
    assert len(set(ids)) == len(ids)  # locked seq: no duplicate ids


def test_concurrent_metrics_registries_and_shared_counters(tmp_path):
    """Thread-local active registries keep jobs' metrics apart; a SHARED
    registry under concurrent increments loses none (locked RMW)."""
    import threading

    from kafka_specification_tpu.obs import metrics as met

    regs = [MetricsRegistry(run_id=f"r{i}") for i in range(2)]
    per = 500

    def job(reg):
        met.set_registry(reg)
        for _ in range(per):
            met.inc("kspec_test_total")
            met.set_gauge("kspec_test_gauge", 1)
        met.set_registry(None)

    threads = [threading.Thread(target=job, args=(r,)) for r in regs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for reg in regs:
        assert reg.counters["kspec_test_total"] == per  # no cross-counting

    shared = MetricsRegistry(run_id="shared")

    def pound():
        for _ in range(per):
            shared.inc("kspec_pound_total")
            shared.observe("kspec_pound_ms", 1.0)

    threads = [threading.Thread(target=pound) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert shared.counters["kspec_pound_total"] == 4 * per
    assert shared.hists["kspec_pound_ms"]["count"] == 4 * per
    # exports stay coherent under a concurrent writer
    writer = threading.Thread(target=pound)
    writer.start()
    for _ in range(20):
        shared.write_prom(str(tmp_path / "m.prom"))
    writer.join()
    assert "kspec_pound_total" in (tmp_path / "m.prom").read_text()
