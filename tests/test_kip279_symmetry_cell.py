"""Kip279 at 5 brokers under `SYMMETRY Symm`, on the path the benchmark cell
takes (ISSUE 47; perfbench/configs/kip279-5b-symmetry.json): the `canon`
stage inside the fused multi-chunk programs, the trace store beside orbit
keys, and a verdict that cuts a level whose keys are orbits.

CPU; the search is exact, so nothing is seeded.  The engine against the
oracle twin (`build_model(..., oracle=True)`: `variants`' oracle under
`oracle/interp.py` `reduce_by_symmetry`) on the cell's own cfg,
`configs/MCKip279FiveBroker.cfg`, to depth 8 (24,512 orbits: every level, its
candidates, and `orbit_states` equal to the UNREDUCED job's level counts); and
to the verdict on `MCKip279` at 3 brokers (`configs/Kip279.cfg` with the
stanza: 18,957 orbits, WeakIsr at depth 10) as the serving daemon's warm
protocol runs it, in chunks of 1,024 rows, so that levels 8-10 stream 2-9
chunks and the verdict lies in the fifth chunk of the level it cuts: the
violation, a trace that replays through the UNREDUCED oracle, a warm pass that
builds no program and repeats every count, and the cut level's
`enabled_candidates`, `new` and `duplicates`, which with `stats["levels"]`
are the oracle's successor counts up to the end of the verdict's chunk.  The
cell's own job (676,180 orbits for 71,087,196 states, WeakIsr at depth 12, the
verdict in the second chunk of eleven) is the one `slow` case, four minutes
here; its golden is perfbench/golden/kip279-5b-symmetry.json."""

import functools

import jax
import pytest

from kafka_specification_tpu.engine.bfs import check, prepare
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg
from kafka_specification_tpu.utils.pretty import render_trace

from helpers import decode_rows
from test_oracle_replay import replay_through_oracle

CELL_CFG = "configs/MCKip279FiveBroker.cfg"
INVARIANTS = ("TypeOk", "WeakIsr", "StrongIsr")
# the golden's thirteen levels of orbits, the unreduced states they stand for
# and the successors generated into each level (ISSUE 47's table; the depth-8
# case derives the first nine again, the slow case holds the engine to all)
CELL_LEVELS = [1, 2, 7, 36, 167, 638, 2075, 5981, 15605, 37827, 85366,
               178902, 349573]
CELL_UNREDUCED = [1, 10, 110, 1220, 9000, 46140, 173465, 537555, 1489900,
                  3772630, 8765995, 18824715, 37466455]
CELL_GENERATED = [10, 22, 98, 487, 2139, 7620, 23395, 65299, 167765, 399558,
                  881768, 1806867]
# MCKip279 at 3 brokers: orbits, and the unreduced job's levels
# (tests/test_kip279_cell.py CORPUS_LEVELS: 113,212 states to the violation)
SMALL_LEVELS = [1, 2, 7, 36, 142, 377, 765, 1499, 2879, 5038, 8211]
SMALL_UNREDUCED = [1, 6, 36, 207, 837, 2244, 4557, 8937, 17181, 30102, 49104]
# the fused path from 64 rows up, so the small levels take it too, and chunks
# of 1,024 rows: two or more a level from level 8 on (tests/test_symmetry.py)
SMALL = dict(min_bucket=64, compact_gate=64, chunk_size=1024)
EXACT = ("frontier", "enabled_candidates", "new", "duplicates", "chunks",
         "chunks_ahead", "successor_launches", "dedup_lanes", "guard_lanes",
         "canon_rows", "orbit_states")
CUT_EXACT = EXACT + ("depth", "rows_committed", "chunks_committed",
                     "chunks_discarded")


def _small_cfg():
    with open("configs/Kip279.cfg") as fh:
        return parse_cfg(fh.read() + "SYMMETRY Symm\n")


def _searched(om, depth):
    """The benchmark's own loop (perfbench/orbitderive.py) over the reduced
    oracle `om`, with the successors counted: -> (levels, orbit_states,
    generated, violated invariant or None), to `depth` or the first
    violating level."""
    size = om.symmetry.canonical
    frontier = list(dict.fromkeys(om.init_states()))
    visited = set(frontier)
    levels, weights, generated = [len(frontier)], [sum(
        size(s)[1] for s in frontier)], []
    while frontier and (depth is None or len(levels) <= depth):
        nxt, weight, made = [], 0, 0
        for s in frontier:
            for a in om.actions:
                for t in a.successors(s):
                    made += 1
                    if t not in visited:
                        visited.add(t)
                        nxt.append(t)
                        weight += size(t)[1]
        levels.append(len(nxt))
        weights.append(weight)
        generated.append(made)
        bad = [name for name, pred in om.invariants
               if not all(pred(s) for s in nxt)]
        if bad:
            return levels, weights, generated, bad[0]
        frontier = nxt
    return levels, weights, generated, None


def _spans(run_dir, kind):
    return [r for r in read_jsonl_tolerant(str(run_dir / "spans.jsonl"))
            if r.get("kind") == "span" and r.get("ph") == "E"
            and r.get("span") == kind]


# --- the cell's own cfg, to depth 8 ------------------------------------------

def test_cell_cfg_is_the_four_broker_cfg_with_one_more_replica_and_the_stanza():
    tlc, four = parse_cfg(CELL_CFG), parse_cfg("configs/Kip279FourBroker.cfg")
    assert tlc.constants == dict(
        four.constants, Replicas=["b1", "b2", "b3", "b4", "b5"])
    assert tlc.invariants == four.invariants == list(INVARIANTS)
    assert tlc.check_deadlock is four.check_deadlock is False
    assert (tlc.symmetry, four.symmetry) == ("Symm", None)
    model = build_model("MCKip279", tlc)
    assert model.meta["cfg"] == Config(5, 2, 2, 2)
    assert (model.symmetry.operator, model.symmetry.set_name,
            model.symmetry.order) == ("Symm", "Replicas", 120)
    # the operating point the cell puts on the fused path: 5 packed lanes,
    # 113 choice slots over the L3 core's nine actions
    assert model.spec.num_lanes == 5
    widths = {a.name: a.n_choices for a in model.actions}
    assert sum(widths.values()) == 113 and len(widths) == 9
    assert [a.name for a in variants.make_model(
        "Kip279", Config(5, 2, 2, 2)).actions] == list(widths)
    # the stanza needs the wrapper module: the corpus declares no symmetry set
    with pytest.raises(ValueError, match="pass --module MCKip279"):
        build_model("Kip279", tlc)


def test_engine_equals_oracle_to_depth_eight_on_the_cells_cfg(tmp_path):
    tlc = parse_cfg(CELL_CFG)
    levels, weights, generated, violated = _searched(
        build_model("MCKip279", tlc, oracle=True), 8)
    assert violated is None
    assert levels == CELL_LEVELS[:9] and sum(levels) == 24512
    assert weights == CELL_UNREDUCED[:9] and generated == CELL_GENERATED[:8]
    res = check(build_model("MCKip279", tlc), max_depth=8,
                check_deadlock=tlc.check_deadlock,
                run=RunContext(str(tmp_path / "run")),
                min_bucket=64, compact_gate=64)
    assert res.levels == levels and res.total == 24512
    assert res.violation is None and not res.stats.get("degradations")
    assert (res.stats["pipeline"], res.stats["visited_backend"]) == (
        "fused", "device")
    assert res.stats["symmetry"] == {"set": "Replicas", "order": 120}
    recs = res.stats["levels"]
    assert [r["new"] for r in recs] == levels[1:]
    assert [r["enabled_candidates"] for r in recs] == generated
    # what ties the reduction to the model: each level's orbit sizes sum to
    # the unreduced job's count of that level
    assert [r["orbit_states"] for r in recs] == weights[1:]
    assert all(r["canon_rows"] >= r["enabled_candidates"] for r in recs)


# --- the warm protocol at 3 brokers, to the verdict --------------------------

@functools.lru_cache(maxsize=None)
def _small_oracle():
    om = build_model("MCKip279", _small_cfg(), oracle=True)
    return om, _searched(om, None)


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """What `service/daemon.py` does for the jobs of one shape (the
    benchmark's warm protocol): a cold `check(prepared=)` that climbs the
    capacity ladder, `note_result`, `rewarm`, then two calls at the capacity
    fixed point.  The last pass also hands out its levels' rows.
    -> (model, [results], [run dirs], the last pass's levels)."""
    base = tmp_path_factory.mktemp("warm")
    tlc = _small_cfg()
    model = build_model("MCKip279", tlc)
    pk = prepare(model)
    kw = dict(prepared=pk, check_deadlock=tlc.check_deadlock, **SMALL)
    results = [check(model, run=RunContext(str(base / "cold")), **kw)]
    pk.note_result(results[0])
    pk.rewarm()
    results.append(check(model, run=RunContext(str(base / "warm1")),
                         visited_capacity_exact=pk.capacity_hint, **kw))
    rows = []
    results.append(check(model, run=RunContext(str(base / "warm2")),
                         visited_capacity_exact=pk.capacity_hint,
                         collect_levels=rows, **kw))
    return model, results, [base / t for t in ("cold", "warm1", "warm2")], rows


def test_every_pass_finds_the_violation_at_the_oracles_depth(warm):
    model, results, _, _ = warm
    _, (levels, _, _, violated) = _small_oracle()
    assert levels == SMALL_LEVELS and violated == "WeakIsr"
    texts = []
    for res in results:
        assert res.levels == SMALL_LEVELS and res.total == 18957
        v = res.violation
        assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 10, 11)
        assert not res.stats.get("degradations")
        assert res.stats["pipeline"] == "fused"
        texts.append(render_trace(model.meta, v.trace))
    assert texts[0] == texts[1] == texts[2] and "b3" in texts[0]
    # fewer states than the unreduced job stores before it answers
    assert results[0].total < sum(SMALL_UNREDUCED) // 5


def test_the_trace_is_a_behaviour_of_the_unreduced_spec(warm):
    """Rows are stored as found, keyed by their orbits: the walk returns
    states of the spec, not canonical members, and every step is a `Next`
    step of the plain oracle, which knows no symmetry."""
    _, results, _, _ = warm
    plain = variants.make_oracle("Kip279", Config(3, 2, 2, 2), INVARIANTS)
    assert plain.symmetry is None
    for res in results[1:]:
        replay_through_oracle(res.violation.trace, plain, "WeakIsr")


def test_a_warm_pass_builds_no_program_and_repeats_every_count(warm):
    _, results, dirs, _ = warm
    assert _spans(dirs[0], "compile")  # the cold pass is what compiles
    assert not _spans(dirs[2], "compile")
    caps = [r.stats["visited_capacity"] for r in results]
    assert caps[1] == caps[2] >= caps[0]
    a, b = results[1].stats, results[2].stats
    assert [[lv[k] for k in EXACT] for lv in a["levels"]] == [
        [lv[k] for k in EXACT] for lv in b["levels"]]
    assert [a["cut_level"][k] for k in CUT_EXACT] == [
        b["cut_level"][k] for k in CUT_EXACT]
    # and the cold pass's: the same search at another capacity
    assert [results[0].stats["cut_level"][k] for k in CUT_EXACT] == [
        b["cut_level"][k] for k in CUT_EXACT]
    # canon ran inside multi-chunk levels, on orbit keys across chunks
    assert [lv["chunks"] for lv in b["levels"]][-3:] == [2, 3, 5]


def test_committed_levels_count_what_the_oracle_counts(warm):
    _, results, _, _ = warm
    _, (levels, weights, generated, _) = _small_oracle()
    recs = results[2].stats["levels"]
    assert len(recs) == 10
    assert [r["new"] for r in recs] == levels[1:]
    assert [r["enabled_candidates"] for r in recs] == generated[:10]
    assert [r["duplicates"] for r in recs] == [
        g - n for g, n in zip(generated, levels[1:])]
    assert [r["orbit_states"] for r in recs] == weights[1:] \
        == SMALL_UNREDUCED[1:]


def test_the_cut_level_counts_the_oracles_successors_up_to_the_verdicts_chunk(
        warm):
    """The level the verdict cuts is in no level record: its own record
    holds the candidates of the chunks it ran, the verdict's included, so
    that summed with `stats["levels"]` they are every successor the pass
    generated.  Held to the reduced oracle run over the ENGINE's own frontier
    (its rows in its discovery order, each mapped to its orbit's canonical
    member), up to the end of the verdict's chunk."""
    model, results, dirs, rows = warm
    om, (levels, _, generated, _) = _small_oracle()
    cut = results[2].stats["cut_level"]
    assert (cut["depth"], cut["frontier"]) == (11, SMALL_LEVELS[-1])
    assert cut["chunks_committed"] == 5 and cut["rows_committed"] == 5 * 1024
    assert (cut["chunks_discarded"], cut["chunks"]) == (1, 6)
    canon = om.symmetry.canonical
    unpack = jax.jit(jax.vmap(model.spec.unpack))
    assert [r.shape[0] for r in rows] == SMALL_LEVELS
    members = [[canon(s)[0] for s in decode_rows(model, packed, unpack)]
               for packed in rows]
    visited = {s for level in members for s in level}
    assert len(visited) == sum(SMALL_LEVELS)  # one row an orbit
    made = new = weight = 0
    for s in members[-1][:cut["rows_committed"]]:
        for a in om.actions:
            for t in a.successors(s):
                made += 1
                if t not in visited:
                    visited.add(t)
                    new += 1
                    weight += canon(t)[1]
    assert (cut["enabled_candidates"], cut["new"], cut["duplicates"]) == (
        made, new, made - new)
    assert cut["orbit_states"] == weight
    assert cut["canon_rows"] >= made
    # with the level records: every successor of the pass
    recs = results[2].stats["levels"]
    assert sum(r["enabled_candidates"] for r in recs + [cut]) == sum(
        generated[:10]) + made
    assert sum(r["new"] for r in recs + [cut]) == sum(levels[1:]) + new
    # the spans say the same: the cut level's, then the counterexample's
    (span,) = [s for s in _spans(dirs[2], "level") if s.get("cut")]
    assert [span[k] for k in ("enabled_candidates", "new", "duplicates",
                              "rows_committed", "chunks_committed")] == [
        made, new, made - new, cut["rows_committed"], 5]
    (cex,) = _spans(dirs[2], "counterexample")
    assert (cex["invariant"], cex["depth"], cex["trace_len"], cex["source"],
            cex["symmetry"], cex["symmetry_order"]) == (
        "WeakIsr", 10, 11, "ram", "Symm", 6)


def test_a_model_without_symmetry_says_nothing_of_one_in_its_span(tmp_path):
    model = variants.make_model("KafkaTruncateToHighWatermark",
                                Config(2, 2, 1, 1), ("TypeOk", "WeakIsr"))
    res = check(model, run=RunContext(str(tmp_path / "viol")), min_bucket=32)
    (cex,) = _spans(tmp_path / "viol", "counterexample")
    assert res.violation.depth == 8
    assert "symmetry" not in cex and "symmetry_order" not in cex
    cut = res.stats["cut_level"]
    assert cut["duplicates"] == cut["enabled_candidates"] - cut["new"] >= 0


# --- the cell's own job ------------------------------------------------------

@pytest.mark.slow
def test_five_broker_job_whole_to_its_counterexample(tmp_path):
    """`cli check configs/MCKip279FiveBroker.cfg --module MCKip279` as the
    engine runs it, CLI defaults: every golden level, each level's orbit
    sizes summing to the unreduced job's count, the verdict in the second
    chunk of level 12's eleven, a trace of the unreduced oracle."""
    tlc = parse_cfg(CELL_CFG)
    model = build_model("MCKip279", tlc)
    res = check(model, run=RunContext(str(tmp_path / "run")),
                check_deadlock=tlc.check_deadlock)
    assert res.levels == CELL_LEVELS and res.total == 676180
    v = res.violation
    assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 12, 13)
    assert len(render_trace(model.meta, v.trace)) == 9716
    assert not res.stats.get("degradations")
    assert res.stats["visited_capacity"] == 8388608
    recs = res.stats["levels"]
    # twelve committed levels (1-12); the verdict cuts the expansion of 12
    assert [r["orbit_states"] for r in recs] == CELL_UNREDUCED[1:]
    assert [r["enabled_candidates"] for r in recs] == CELL_GENERATED
    assert [r["chunks"] for r in recs][-4:] == [1, 2, 3, 6]
    cut = res.stats["cut_level"]
    assert (cut["frontier"], cut["chunks_committed"], cut["rows_committed"],
            cut["chunks_discarded"], cut["chunks"]) == (
        349573, 2, 65536, 1, 3)
    assert (cut["enabled_candidates"], cut["new"], cut["duplicates"],
            cut["canon_rows"], cut["orbit_states"]) == (
        644375, 291925, 352450, 652720, 32428645)
    replay_through_oracle(
        v.trace, variants.make_oracle("Kip279", Config(5, 2, 2, 2),
                                      INVARIANTS), "WeakIsr")
