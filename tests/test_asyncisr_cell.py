"""AsyncIsr at the benchmark cell's constants, on the path the cell takes
(ISSUE 32; perfbench/configs/asyncisr-4b.json).

CPU, small sizes: the engine against `make_oracle` level by level, whole at
3 brokers 2 / 2 (4,088 states, diameter 16) and to depth 8 at the cell's own
constants, `configs/AsyncIsrFourBroker.cfg` (4 brokers, MaxOffset 3,
MaxVersion 3: 26,675 states), fused pipeline, trace store on and off; the
serving daemon's warm protocol on it; the `chunks` field of the level
records; the encoding's limit of 4 brokers.  The cell's depth (14, 1,206,700
states) is the chip's; its golden is perfbench/golden/asyncisr-4b.json."""

import functools

import pytest

from kafka_specification_tpu.analysis.encoding import EncodingUnsound
from kafka_specification_tpu.engine.bfs import check, prepare
from kafka_specification_tpu.models import async_isr
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.oracle.interp import oracle_bfs
from kafka_specification_tpu.utils.cfg import (
    CFG_MODULE_ALIASES,
    build_model,
    parse_cfg,
)

CELL_CFG = "configs/AsyncIsrFourBroker.cfg"
# the golden's first nine levels (oracle-derived; the test derives them again)
CELL_LEVELS_D8 = [1, 7, 31, 116, 377, 1082, 2819, 6829, 15413]
# the fused path from 64 rows up, so a 4,088-state job takes it too
FUSED = dict(min_bucket=64, compact_gate=64)


def _three_brokers():
    cfg = async_isr.AsyncIsrConfig(n_replicas=3, max_offset=2, max_version=2)
    return async_isr.make_model(cfg), async_isr.make_oracle(cfg), None, FUSED


def _cell_constants():
    tlc = parse_cfg(CELL_CFG)
    # engine options none below: the cell's (fused from 4,096 rows up)
    return (build_model("AsyncIsr", tlc), build_model("AsyncIsr", tlc, oracle=True),
            8, {})


JOBS = {"3b-2-2-whole": _three_brokers, "4b-3-3-depth8": _cell_constants}


@functools.lru_cache(maxsize=None)
def _job(name):
    """(model, oracle's per-level counts, max_depth, engine keywords): one
    model object a job, so its step cache serves both trace cases."""
    model, oracle, max_depth, kw = JOBS[name]()
    ores = oracle_bfs(oracle, max_depth=max_depth, keep_level_sets=False)
    assert ores.violation is None
    return model, ores.levels, max_depth, kw


@pytest.mark.parametrize("store_trace", [True, False], ids=["trace", "notrace"])
@pytest.mark.parametrize("name", sorted(JOBS))
def test_engine_equals_oracle_level_by_level(name, store_trace):
    model, want, max_depth, kw = _job(name)
    res = check(model, max_depth=max_depth, store_trace=store_trace,
                check_deadlock=False, **kw)
    assert res.violation is None
    assert res.levels == want and res.total == sum(want)
    if name == "3b-2-2-whole":
        assert (res.total, res.diameter) == (4088, 16)
    else:
        assert want == CELL_LEVELS_D8 and res.total == 26675
        # the wide levels took the fused per-chunk path, not a fallback
        assert res.stats["pipeline"] == "fused"
        assert not res.stats.get("degradations")


def test_cell_cfg_is_the_spec_at_the_encodings_limit():
    tlc = parse_cfg(CELL_CFG)
    assert CFG_MODULE_ALIASES["AsyncIsrFourBroker"] == "AsyncIsr"
    assert tlc.constants == {"Replicas": ["b1", "b2", "b3", "b4"],
                             "Leader": "b1", "MaxOffset": 3, "MaxVersion": 3}
    assert tlc.invariants == ["TypeOk", "ValidHighWatermark"]
    assert tlc.constraints == ["Bounded"] and tlc.check_deadlock is False
    # the benchmark reads a copy under perfbench/ (the parent commit of the
    # PR that brought the cell has no such cfg): the same bytes
    with open(CELL_CFG, "rb") as a, open("perfbench/" + CELL_CFG, "rb") as b:
        assert a.read() == b.read()
    model = build_model("AsyncIsr", tlc)
    assert model.meta["cfg"] == async_isr.AsyncIsrConfig(4, 3, 3)
    # the operating point the cell puts on the shared path: 4 packed lanes,
    # 37 choice slots (4 + 16 + 4 + 4 + 1 + 4 + 4 over the seven actions)
    assert model.spec.num_lanes == 4
    assert sum(a.n_choices for a in model.actions) == 37


@pytest.mark.parametrize("oracle", [False, True], ids=["model", "oracle"])
def test_five_brokers_are_refused(oracle):
    tlc = parse_cfg(open(CELL_CFG).read().replace("b4}", "b4, b5}"))
    assert len(tlc.constants["Replicas"]) == 5
    with pytest.raises(EncodingUnsound, match="at most 4 replicas"):
        build_model("AsyncIsr", tlc, oracle=oracle)


# --- the warm protocol -------------------------------------------------------

def _spans(run_dir, kind):
    return [r for r in read_jsonl_tolerant(str(run_dir / "spans.jsonl"))
            if r.get("kind") == "span" and r.get("ph") == "E"
            and r.get("span") == kind]


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """What `service/daemon.py` does for the jobs of one shape: a cold
    `check(prepared=)` that climbs the capacity ladder, `note_result`,
    `rewarm`, then two calls at the capacity fixed point.  A model of its
    own: the compile spans of its cold pass are counted."""
    base = tmp_path_factory.mktemp("warm")
    model = build_model("AsyncIsr", parse_cfg(CELL_CFG))
    pk = prepare(model)
    kw = dict(prepared=pk, check_deadlock=False, max_depth=8)
    results = [check(model, run=RunContext(str(base / "cold")), **kw)]
    pk.note_result(results[0])
    pk.rewarm()
    for tag in ("warm1", "warm2"):
        results.append(check(model, run=RunContext(str(base / tag)),
                             visited_capacity_exact=pk.capacity_hint, **kw))
    return results, [base / t for t in ("cold", "warm1", "warm2")]


def test_warm_passes_repeat_every_level(warm):
    results, _ = warm
    for res in results:
        assert res.levels == CELL_LEVELS_D8 and res.violation is None
        assert not res.stats.get("degradations")
    caps = [r.stats["visited_capacity"] for r in results]
    assert caps[1] == caps[2] >= caps[0]
    # a warm pass is the same search: every count of every level record
    keys = ("frontier", "enabled_candidates", "new", "duplicates", "chunks",
            "successor_launches")
    assert [[lv[k] for k in keys] for lv in results[1].stats["levels"]] == [
        [lv[k] for k in keys] for lv in results[2].stats["levels"]]


def test_last_warm_pass_builds_no_program(warm):
    _, dirs = warm
    assert _spans(dirs[0], "compile")  # the cold pass is what compiles
    assert not _spans(dirs[2], "compile")


# --- `chunks` on the level records ---------------------------------------------

@pytest.mark.parametrize("pipeline", ["fused", "device"])
def test_chunks_on_every_level_record_sum_to_what_the_spans_show(
        tmp_path, pipeline):
    """A chunk of 1,024 rows makes levels 6-8 (frontiers of 1,082 / 2,819 /
    6,829 rows) stream 2, 3 and 7 chunks: each record's `chunks` is what the level's `step`
    spans show (one span a chunk on the fused path; one a level with
    its own `chunks` where a whole-level program ran)."""
    model, want, max_depth, _ = _job("4b-3-3-depth8")
    res = check(model, max_depth=max_depth, check_deadlock=False,
                chunk_size=1024, pipeline=pipeline, min_bucket=64,
                compact_gate=64, run=RunContext(str(tmp_path / "run")))
    assert res.levels == want
    recs = res.stats["levels"]
    assert len(recs) == max_depth and all("chunks" in r for r in recs)
    by_depth = {}
    for s in _spans(tmp_path / "run", "step"):
        d = s["depth"] + 1  # a span names the frontier's depth, a record the level built
        by_depth[d] = by_depth.get(d, 0) + s.get("chunks", 1)
    assert {r["depth"]: r["chunks"] for r in recs} == by_depth
    # ceil(frontier / chunk): the frontier of level d is level d-1's new
    assert [r["chunks"] for r in recs] == [-(-n // 1024) for n in want[:-1]]
    assert sum(r["chunks"] for r in recs) == 17
    assert "cut_level" not in res.stats
