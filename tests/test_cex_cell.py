"""A violating job on the warm served-job path, and the records of the level
its verdict cuts (ISSUE 30; docs/observability.md § The level a verdict cuts).

CPU, small sizes: `configs/Kip101.cfg` (5,491 states, WeakIsr at depth 11, a
12-state trace) for the verdict path, Kip320FirstTry at the TINY constants
(337 states, no violation: the design needs three replicas to fail) for a
pass that cuts nothing, and Kip320FirstTry at three replicas with one record
(78,832 states, WeakIsr at depth 11) for the replay through its oracle.  The
corpus-size job (184,141 states) stays in tests/test_kip320.py and
tests/test_oracle_replay.py, marked slow."""

import functools
import json

import jax
import numpy as np
import pytest

from kafka_specification_tpu.engine import hostio
from kafka_specification_tpu.engine.bfs import check, prepare
from kafka_specification_tpu.engine.pipeline import WORK_FIELDS
from kafka_specification_tpu.models import kip320
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg
from kafka_specification_tpu.utils.pretty import render_trace

from test_oracle_replay import replay_through_oracle

TINY = Config(2, 2, 1, 1)
KIP101_LEVELS = [1, 4, 14, 44, 100, 166, 268, 456, 684, 976, 1292, 1486]
CUT_KEYS = {"depth", "frontier", "enabled_candidates", "new", "duplicates",
            "rows_committed", "chunks_committed",
            "chunks_discarded", "chunks", "chunks_ahead", "dedup_lanes",
            "guard_lanes", "probes", "probes_windowed", "level_ms",
            "step_ms", "host_ms",
            "successor_launches"} | set(WORK_FIELDS) | set(hostio.LEVEL_COUNTERS)
# the fused path from 64 rows up, so a small chunk leaves launch 2 in flight
FUSED = dict(min_bucket=64, compact_gate=64)


def _kip101():
    cfg = parse_cfg("configs/Kip101.cfg")
    return build_model("Kip101", cfg), cfg


@functools.lru_cache(maxsize=None)
def _kip101_shared():
    """One model object for the cases that do not count compiles: its step
    cache keeps the programs from one case to the next."""
    return _kip101()


def _spans(run_dir):
    return [r for r in read_jsonl_tolerant(str(run_dir / "spans.jsonl"))
            if r.get("kind") == "span"]


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """The serving daemon's protocol on a job that ends in a violation:
    cold `check(prepared=)`, `note_result`, `rewarm`, then two calls at the
    capacity fixed point.  -> (model, [results], [run dirs])."""
    base = tmp_path_factory.mktemp("warm")
    model, cfg = _kip101()
    pk = prepare(model)
    kw = dict(prepared=pk, check_deadlock=cfg.check_deadlock, min_bucket=64)
    results = [check(model, run=RunContext(str(base / "cold")), **kw)]
    pk.note_result(results[0])
    pk.rewarm()
    for tag in ("warm1", "warm2"):
        results.append(check(model, run=RunContext(str(base / tag)),
                             visited_capacity_exact=pk.capacity_hint, **kw))
    return model, results, [base / t for t in ("cold", "warm1", "warm2")]


# --- (a) the warm protocol on a violating job ------------------------------

def test_warm_passes_repeat_the_violation_levels_and_rendered_trace(warm):
    model, results, _ = warm
    texts = []
    for res in results:
        assert res.levels == KIP101_LEVELS and res.total == 5491
        v = res.violation
        assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 11, 12)
        texts.append(render_trace(model.meta, v.trace).encode())
    assert texts[0] == texts[1] == texts[2] and len(texts[0]) > 0
    # the capacity is a fixed point of the protocol on a violating result too
    caps = [r.stats["visited_capacity"] for r in results]
    assert caps[1] == caps[2] >= caps[0]


def test_last_warm_pass_builds_no_program(warm):
    _, _, dirs = warm
    kinds = [s["span"] for s in _spans(dirs[0])]
    assert "compile" in kinds  # the cold pass is what compiles
    assert "compile" not in [s["span"] for s in _spans(dirs[2])]


# --- (b) stats["cut_level"] -------------------------------------------------

def _first_try_tiny():
    return kip320.make_first_try_model(TINY), {}


def _kip101_verdict():
    model, cfg = _kip101_shared()
    return model, dict(check_deadlock=cfg.check_deadlock)


def _kip101_bound():
    # the depth bound stops the loop at the violating frontier: the verdict
    # comes from the invariant pass over the unexpanded rows, no level is cut
    model, kw = _kip101_verdict()
    return model, dict(kw, max_depth=11)


@pytest.mark.parametrize("build,violates,cut", [
    (_kip101_verdict, True, True),
    (_kip101_bound, True, False),
    (_first_try_tiny, False, False),
], ids=["verdict-in-the-loop", "verdict-at-the-depth-bound", "no-verdict"])
def test_cut_level_present_exactly_when_a_verdict_cut_a_level(
        tmp_path, build, violates, cut):
    model, kw = build()
    res = check(model, run=RunContext(str(tmp_path / "run")), min_bucket=64,
                **kw)
    assert (res.violation is not None) == violates
    assert ("cut_level" in res.stats) == cut
    # one record per committed level, whatever ended the run (an exhausted
    # space also commits the empty level that ends it)
    assert len(res.stats["levels"]) == len(res.levels) - (1 if violates else 0)
    manifest = json.load(open(tmp_path / "run" / "manifest.json"))
    assert manifest["result"].get("cut_level") == res.stats.get("cut_level")
    if cut:
        rec = res.stats["cut_level"]
        assert set(rec) == CUT_KEYS
        assert rec["depth"] == res.violation.depth + 1
        assert rec["frontier"] == res.levels[-1]
        assert rec["chunks_committed"] >= 1 and rec["level_ms"] > 0
    # nothing of it reaches the emitted stream
    emitted = read_jsonl_tolerant(str(tmp_path / "run" / "stats.jsonl"))
    assert all(r.get("kind") == "level" and "rows_committed" not in r
               for r in emitted)
    # a violation found with a trace store has its counterexample span,
    # cut level or not
    cex = [s for s in _spans(tmp_path / "run")
           if s["span"] == "counterexample"]
    assert len(cex) == (1 if violates else 0)


def test_cut_level_and_level_records_sum_to_the_run_totals(
        tmp_path, monkeypatch):
    """Counted a second time, outside `HostIO.take`: every fetch, upload and
    dispatch of the run is in a level record or in the cut level's."""
    seen = {"d2h_fetches": 0, "d2h_bytes": 0, "h2d_puts": 0, "h2d_bytes": 0,
            "dispatches": 0}
    fetch, put, dispatch = (hostio.HostIO.fetch, hostio.HostIO.put,
                            hostio.HostIO.dispatch)

    def counted_fetch(self, x, dtype=None):
        if isinstance(x, jax.Array):
            seen["d2h_fetches"] += 1
            seen["d2h_bytes"] += x.nbytes
        return fetch(self, x, dtype)

    def counted_put(self, x, *where):
        if isinstance(x, np.ndarray):
            seen["h2d_puts"] += 1
            seen["h2d_bytes"] += x.nbytes
        return put(self, x, *where)

    def counted_dispatch(self, program, **attrs):
        seen["dispatches"] += 1
        return dispatch(self, program, **attrs)

    monkeypatch.setattr(hostio.HostIO, "fetch", counted_fetch)
    monkeypatch.setattr(hostio.HostIO, "put", counted_put)
    monkeypatch.setattr(hostio.HostIO, "dispatch", counted_dispatch)
    model, kw = _kip101_verdict()
    res = check(model, run=RunContext(str(tmp_path / "run")), chunk_size=256,
                **FUSED, **kw)
    records = res.stats["levels"] + [res.stats["cut_level"]]
    for key, total in seen.items():
        assert sum(r[key] for r in records) == total, key
    assert res.stats["cut_level"]["d2h_fetches"] > 0


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "serial"])
def test_multi_chunk_cut_reports_the_chunk_it_dropped(tmp_path, overlap):
    """Level 11 of Kip101 in chunks of 256 rows: the violating row sits in
    the fourth of six.  With overlap on the fifth chunk's guard stage has run
    ahead (ISSUE 42) when the verdict is read with the fourth's counts,
    before the fifth's successor launch is queued: that stage is all that is
    dropped, and nothing in flight is discarded.  The serial path never
    dispatched it.  Same verdict, same trace."""
    model, kw = _kip101_verdict()
    res = check(model, run=RunContext(str(tmp_path / "run")), chunk_size=256,
                overlap=overlap, **FUSED, **kw)
    v = res.violation
    assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 11, 12)
    assert res.levels == KIP101_LEVELS
    rec = res.stats["cut_level"]
    assert (rec["rows_committed"], rec["chunks_committed"]) == (1024, 4)
    assert rec["frontier"] == 1486
    dropped = 1 if overlap else 0
    assert rec["chunks_discarded"] == dropped
    assert rec["chunks"] == 4 + dropped  # every chunk the level dispatched
    assert (rec["discarded_dispatches"], rec["discarded_ms"]) == (0, 0)
    # of the four committed chunks, all but the level's first went out ahead
    assert rec["chunks_ahead"] == (3 if overlap else 0)
    # the dropped chunk's guard launch is a dispatch, not a committed
    # launch: two fused launches a committed chunk, launch 1 of the fifth
    assert rec["successor_launches"] == 2 * 4
    assert rec["dispatches"] == 2 * 4 + dropped
    # the probe's lanes are the committed chunks' too: one probe a chunk
    # over the layout dedup was handed, its live prefix searched
    assert 0 < rec["probe_lanes"] <= rec["probe_lanes_plain"] \
        == rec["dedup_lanes"]
    discarded = [s for s in _spans(tmp_path / "run")
                 if s["span"] == "dispatch" and s.get("discarded")]
    assert discarded == []
    ref = check(model, **FUSED, **kw)  # the same path, one chunk a level
    assert render_trace(model.meta, ref.violation.trace) == \
        render_trace(model.meta, v.trace)


def test_whole_level_program_cut_books_the_chunks_it_ran(tmp_path):
    """`pipeline="device"`: one program runs the level's chunks and stops at
    the verdict's, so the cut level holds the four chunks it ran of six, as
    the per-chunk path does, and nothing was in flight to drop."""
    model, kw = _kip101_verdict()
    res = check(model, run=RunContext(str(tmp_path / "run")), chunk_size=256,
                pipeline="device", **FUSED, **kw)
    assert res.levels == KIP101_LEVELS
    assert res.stats["device"]["levels"] == len(KIP101_LEVELS)
    rec = res.stats["cut_level"]
    assert (rec["rows_committed"], rec["chunks_committed"]) == (1024, 4)
    assert (rec["chunks_discarded"], rec["discarded_dispatches"]) == (0, 0)
    assert (rec["successor_launches"], rec["dispatches"]) == (1, 1)
    # two probes a chunk it ran and the level-new rank
    assert 0 < rec["probe_lanes"] < rec["probe_lanes_plain"]
    assert rec["probe_lanes_plain"] > 2 * rec["dedup_lanes"]


# --- (c) the spans ----------------------------------------------------------

def test_counterexample_span_and_its_split(warm):
    _, _, dirs = warm
    spans = _spans(dirs[2])
    root = next(s for s in spans if s["span"] == "check" and s["ph"] == "E")
    (cex,) = [s for s in spans if s["span"] == "counterexample"]
    assert cex["parent_id"] == root["span_id"]
    assert (cex["invariant"], cex["depth"], cex["trace_len"],
            cex["source"]) == ("WeakIsr", 11, 12, "ram")
    assert cex["walk_ms"] >= 0 and cex["decode_ms"] > 0
    # every state of the trace decoded in one host call (PR 48)
    assert (cex["decode"], cex["decoded_rows"]) == ("host", 12)
    assert cex["walk_ms"] + cex["decode_ms"] <= cex["ms"] + 0.01


def test_cut_level_span_is_completed_not_left_unmatched(warm):
    _, results, dirs = warm
    spans = _spans(dirs[2])
    begun = {s["span_id"] for s in spans if s["ph"] == "B"}
    ended = {s["span_id"] for s in spans if s["ph"] == "E"}
    assert begun <= ended  # every begin marker has its completed span
    levels = [s for s in spans if s["span"] == "level" and s["ph"] == "E"]
    assert len(levels) == len(KIP101_LEVELS)  # 11 committed + the cut one
    (cut,) = [s for s in levels if s.get("cut")]
    rec = results[2].stats["cut_level"]
    assert (cut["depth"], cut["frontier"]) == (rec["depth"], rec["frontier"])
    assert cut["rows_committed"] == rec["rows_committed"]
    assert cut["chunks_discarded"] == rec["chunks_discarded"] == 0
    # the counterexample is built after the cut level has ended
    (cex,) = [s for s in spans if s["span"] == "counterexample"]
    assert cex["t0"] >= cut["t0"] + cut["ms"] / 1e3 - 1e-3
    # the chunk that held the verdict has its step span
    steps = [s for s in spans if s["span"] == "step"
             and s["parent_id"] == cut["span_id"]]
    assert [s.get("verdict") for s in steps] == ["invariant"]


# --- (d) the traces replay through the plain reference ----------------------

def test_warm_pass_trace_replays_through_the_kip101_oracle(warm):
    _, results, _ = warm
    _, cfg = _kip101()
    replay_through_oracle(results[2].violation.trace,
                          build_model("Kip101", cfg, oracle=True), "WeakIsr")


def test_first_try_trace_replays_through_make_first_try_oracle():
    """Kip320FirstTry's own six kernels to a verdict, at three replicas with
    one record a log (the smallest constants at which the design fails):
    the counterexample is a legal path of `make_first_try_oracle`."""
    small3 = Config(3, 1, 1, 2)
    res = check(kip320.make_first_try_model(small3), min_bucket=1024)
    v = res.violation
    assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 11, 12)
    assert res.total == 78832 and "cut_level" not in res.stats  # no run, no record
    replay_through_oracle(v.trace, kip320.make_first_try_oracle(small3),
                          "WeakIsr")
