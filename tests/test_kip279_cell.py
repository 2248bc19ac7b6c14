"""Kip279 at the benchmark cell's constants, on the path the cell takes
(ISSUE 40; perfbench/configs/kip279-4b.json).

CPU, small sizes: the engine against `variants.make_oracle("Kip279")` level
by level, whole at the corpus's own `configs/Kip279.cfg` (3 brokers: 113,212
states, WeakIsr at depth 10) and to depth 7 at the cell's constants,
`configs/Kip279FourBroker.cfg` (142,625 states), fused path from 64 rows up;
the serving daemon's warm protocol on the violating 3-broker job, in chunks
small enough that the verdict is not in the cut level's first chunk; the
trace replayed through the oracle twin.  The cell's own job (3,147,301
states to WeakIsr at depth 11, the verdict in the fourth chunk of 47) is the
one `slow` case; its golden is perfbench/golden/kip279-4b.json."""

import functools

import pytest

from kafka_specification_tpu.engine.bfs import check, prepare
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.oracle.interp import oracle_bfs
from kafka_specification_tpu.utils.cfg import (
    CFG_MODULE_ALIASES,
    build_model,
    parse_cfg,
)
from kafka_specification_tpu.utils.pretty import render_trace

from test_oracle_replay import replay_through_oracle

CELL_CFG = "configs/Kip279FourBroker.cfg"
CORPUS_CFG = "configs/Kip279.cfg"
# the golden's twelve levels (oracle-derived; the depth-7 case derives the
# first eight again, the slow case holds the engine to all of them)
CELL_LEVELS = [1, 8, 68, 572, 3276, 12796, 36560, 89344, 203892, 431340,
               842240, 1527204]
CORPUS_LEVELS = [1, 6, 36, 207, 837, 2244, 4557, 8937, 17181, 30102, 49104]
# the fused path from 64 rows up, so the small levels take it too
FUSED = dict(min_bucket=64, compact_gate=64)
# level 10 of the 3-broker job (49,104 rows) in chunks of 4,096: the
# violating row is not in the first
CHUNK = 4096
# what a cut level is made of (every pass of one job and chunk size), and the
# host's counters (two warm passes)
CUT_SHAPE = ("depth", "frontier", "rows_committed", "chunks_committed",
             "chunks_discarded", "chunks", "dedup_lanes")
CUT_COUNTERS = ("successor_launches", "dispatches", "discarded_dispatches",
                "d2h_bytes", "d2h_fetches", "h2d_bytes", "h2d_puts")
# job -> (cfg, max_depth)
JOBS = {"3b-whole": (CORPUS_CFG, None), "4b-depth7": (CELL_CFG, 7)}


@functools.lru_cache(maxsize=None)
def _job(name):
    """(model, oracle model, the oracle's result, max_depth): one model
    object a job, so its step cache serves every case of the job."""
    cfg, max_depth = JOBS[name]
    tlc = parse_cfg(cfg)
    oracle = build_model("Kip279", tlc, oracle=True)
    return (build_model("Kip279", tlc), oracle,
            oracle_bfs(oracle, max_depth=max_depth, keep_level_sets=False),
            max_depth)


@pytest.mark.parametrize("store_trace", [True, False], ids=["trace", "notrace"])
@pytest.mark.parametrize("name", sorted(JOBS))
def test_engine_equals_oracle_level_by_level(name, store_trace):
    model, _, ores, max_depth = _job(name)
    res = check(model, max_depth=max_depth, store_trace=store_trace,
                check_deadlock=False, **FUSED)
    assert res.levels == ores.levels and res.total == sum(ores.levels)
    assert res.stats["pipeline"] == "fused"
    assert not res.stats.get("degradations")
    if name == "4b-depth7":
        assert ores.levels == CELL_LEVELS[:8] and res.total == 142625
        assert res.violation is None and ores.violation is None
    else:
        assert ores.levels == CORPUS_LEVELS and res.total == 113212
        assert ores.violation[:2] == ("WeakIsr", 10)
        v = res.violation
        assert (v.invariant, v.depth) == ("WeakIsr", 10)
        # only a trace store can say how the state was reached
        assert len(v.trace) == (11 if store_trace else 0)


def test_cell_cfg_is_the_corpus_cfg_at_four_brokers():
    assert CFG_MODULE_ALIASES["Kip279FourBroker"] == "Kip279"
    tlc, corpus = parse_cfg(CELL_CFG), parse_cfg(CORPUS_CFG)
    assert tlc.constants == dict(corpus.constants,
                                 Replicas=["b1", "b2", "b3", "b4"])
    assert len(corpus.constants["Replicas"]) == 3
    assert tlc.invariants == corpus.invariants == [
        "TypeOk", "WeakIsr", "StrongIsr"]
    assert tlc.check_deadlock is corpus.check_deadlock is False
    assert tlc.symmetry is None and not tlc.constraints
    model = build_model("Kip279", tlc)
    assert model.meta["cfg"] == Config(4, 2, 2, 2)
    # the operating point the cell puts on the shared path: 4 packed lanes,
    # 79 choice slots over the L3 core's nine actions, four of them over a
    # 4-wide replica axis squared or times the epochs
    assert model.spec.num_lanes == 4
    widths = {a.name: a.n_choices for a in model.actions}
    assert sum(widths.values()) == 79 and len(widths) == 9
    assert (widths["LeaderExpandIsr"], widths["LeaderShrinkIsr"],
            widths["FollowerReplicate"],
            widths["BecomeFollowerTruncateKip279"]) == (16, 16, 16, 12)
    # the same builders the variant registry hands out
    assert [a.name for a in variants.make_model(
        "Kip279", Config(4, 2, 2, 2)).actions] == list(widths)


# --- the warm protocol, on a level the verdict cuts mid-way ------------------

def _spans(run_dir, kind):
    return [r for r in read_jsonl_tolerant(str(run_dir / "spans.jsonl"))
            if r.get("kind") == "span" and r.get("ph") == "E"
            and r.get("span") == kind]


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """What `service/daemon.py` does for the jobs of one shape, on the
    violating 3-broker job: a cold `check(prepared=)` that climbs the
    capacity ladder, `note_result`, `rewarm`, then two calls at the capacity
    fixed point.  A model of its own: the compile spans of its cold pass are
    counted.  -> (model, [results], [run dirs])."""
    base = tmp_path_factory.mktemp("warm")
    tlc = parse_cfg(CORPUS_CFG)
    model = build_model("Kip279", tlc)
    pk = prepare(model)
    kw = dict(prepared=pk, check_deadlock=tlc.check_deadlock,
              chunk_size=CHUNK, **FUSED)
    results = [check(model, run=RunContext(str(base / "cold")), **kw)]
    pk.note_result(results[0])
    pk.rewarm()
    for tag in ("warm1", "warm2"):
        results.append(check(model, run=RunContext(str(base / tag)),
                             visited_capacity_exact=pk.capacity_hint, **kw))
    return model, results, [base / t for t in ("cold", "warm1", "warm2")]


def test_warm_passes_repeat_the_violation_levels_and_rendered_trace(warm):
    model, results, _ = warm
    texts = []
    for res in results:
        assert res.levels == CORPUS_LEVELS and res.total == 113212
        v = res.violation
        assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 10, 11)
        assert not res.stats.get("degradations")
        texts.append(render_trace(model.meta, v.trace))
    assert texts[0] == texts[1] == texts[2] and len(texts[0]) > 0
    caps = [r.stats["visited_capacity"] for r in results]
    assert caps[1] == caps[2] >= caps[0]


def test_last_warm_pass_builds_no_program(warm):
    _, _, dirs = warm
    assert _spans(dirs[0], "compile")  # the cold pass is what compiles
    assert not _spans(dirs[2], "compile")


def test_verdict_is_not_in_the_cut_levels_first_chunk_and_its_counts_repeat(
        warm):
    """The cell's verdict lies four chunks into a 47-chunk frontier; here it
    lies several chunks of 4,096 into level 10's twelve.  The chunks before
    it are committed at full width, the one in flight behind it is dropped,
    and every count of the cut level is the same in every pass."""
    _, results, dirs = warm
    cuts = [r.stats["cut_level"] for r in results]
    rec = cuts[2]
    assert rec["chunks_committed"] >= 2
    assert rec["frontier"] == CORPUS_LEVELS[-1] and rec["depth"] == 11
    assert rec["rows_committed"] == rec["chunks_committed"] * CHUNK
    assert rec["rows_committed"] < rec["frontier"]
    # overlap is on: the guard stage of the chunk behind the verdict's ran
    # ahead and is dropped; the verdict is read before that chunk's successor
    # launch is queued, so nothing in flight is discarded
    assert (rec["chunks_discarded"], rec["discarded_dispatches"]) == (1, 0)
    assert rec["chunks"] == rec["chunks_committed"] + 1
    assert rec["chunks_ahead"] == rec["chunks_committed"] - 1
    assert rec["dedup_lanes"] > 0
    for other in cuts[:2]:
        assert [other[k] for k in CUT_SHAPE] == [rec[k] for k in CUT_SHAPE]
    # two warm passes are the same search to the last counter
    assert [cuts[1][k] for k in CUT_COUNTERS] == [
        rec[k] for k in CUT_COUNTERS]
    keys = ("frontier", "enabled_candidates", "new", "duplicates", "chunks",
            "successor_launches", "dedup_lanes")
    assert [[lv[k] for k in keys] for lv in results[1].stats["levels"]] == [
        [lv[k] for k in keys] for lv in results[2].stats["levels"]]
    # the spans say the same: one cut level, the verdict's step, the drop
    (cut,) = [s for s in _spans(dirs[2], "level") if s.get("cut")]
    assert cut["rows_committed"] == rec["rows_committed"]
    dropped = [s for s in _spans(dirs[2], "dispatch") if s.get("discarded")]
    assert dropped == []
    (cex,) = _spans(dirs[2], "counterexample")
    assert (cex["invariant"], cex["depth"], cex["trace_len"],
            cex["source"]) == ("WeakIsr", 10, 11, "ram")


def test_warm_pass_trace_replays_through_the_oracle_twin(warm):
    _, results, _ = warm
    _, oracle, _, _ = _job("3b-whole")
    replay_through_oracle(results[2].violation.trace, oracle, "WeakIsr")


# --- the cell's own job ------------------------------------------------------

@pytest.mark.slow
def test_four_broker_job_whole_to_its_counterexample(tmp_path):
    """`cli check configs/Kip279FourBroker.cfg --module Kip279` as the
    engine runs it, CLI defaults: every golden level, the verdict in the
    fourth chunk of level 11's 47, a trace of the oracle twin."""
    tlc = parse_cfg(CELL_CFG)
    model = build_model("Kip279", tlc)
    res = check(model, run=RunContext(str(tmp_path / "run")),
                check_deadlock=tlc.check_deadlock)
    assert res.levels == CELL_LEVELS and res.total == 3147301
    v = res.violation
    assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 11, 12)
    assert len(render_trace(model.meta, v.trace)) == 7763
    assert not res.stats.get("degradations")
    assert res.stats["visited_capacity"] == 8388608
    rec = res.stats["cut_level"]
    assert (rec["frontier"], rec["chunks_committed"], rec["rows_committed"],
            rec["chunks_discarded"], rec["chunks"], rec["dedup_lanes"]) == (
        1527204, 4, 131072, 1, 5, 2605056)
    assert [lv["chunks_ahead"] for lv in res.stats["levels"][6:]] == [
        1, 2, 6, 13, 25]
    assert [lv["chunks"] for lv in res.stats["levels"][6:]] == [
        2, 3, 7, 14, 26]
    replay_through_oracle(v.trace, build_model("Kip279", tlc, oracle=True),
                          "WeakIsr")
