"""Kip320 at 5 brokers x 3 partitions, the benchmark cell's job, on the path
the cell takes (ISSUE 44; perfbench/configs/kip320-5b-3p.json).

CPU, small sizes.  The cell's own constants (`configs/Kip320Stretch.cfg`)
through the harness's one door (`perfbench/adapter.py` `Job` + `run_pass`) to
depth 3 (9,311 states): every level and every action's enablement against
`product_oracle` over `models/kip320.py` `make_oracle`, the three partitions
equal; the serving daemon's warm protocol on that job (cold pass,
`note_result`, `rewarm`, a pass that builds nothing); two partitions of the
3-broker job in chunks small enough that a level streams several; a product
state in a counterexample (walked, decoded to a tuple a partition, rendered,
replayed through the oracle twin); and what PR 44 added to the records: the
`check-open` span's and the manifest's `partitions` / `base_fanout`, and
every level record's `guard_lanes` (under each pipeline and visited
backend: tests/test_run_phases.py; here the cell's, a streamed level's and
the mesh's); and what PR 45 added: `probes` / `probes_windowed`, with
`dedup.PROBE_WINDOW` patched under the job's capacity and at its default.

The violating job is KafkaTruncateToHighWatermark at 2 brokers x 2
partitions (WeakIsr at depth 8, 15,997 product states at most), not
Kip320FirstTry as ISSUE 44 named: FirstTry's smallest violating constants
(3 brokers, MaxLeaderEpoch 2) reach WeakIsr at depth 11, where the
two-partition product holds 60,085,963 states (the convolution of its
levels), which no CPU test can run."""

import functools
import importlib.util
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import kip320
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.models.product import product_models
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.oracle.interp import oracle_bfs
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg
from kafka_specification_tpu.utils.pretty import render_trace

from test_oracle_replay import replay_through_oracle
from test_product import convolve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CFG = "configs/Kip320Stretch.cfg"
CONFIG_FILE = os.path.join(ROOT, "perfbench", "configs", "kip320-5b-3p.json")
# the one-partition job's levels (perfbench/golden/kip320-5b.json) and the
# product's, their threefold convolution (perfbench/golden/kip320-5b-3p.json)
ONE_PARTITION = [1, 10, 90, 770, 2370, 14635, 58100]
CELL_LEVELS = [1, 30, 570, 8710, 104610, 1075905, 9708900]
DEPTH = 3
BASE_ACTIONS = [
    "ControllerElectLeader", "ControllerShrinkIsr", "BecomeLeader",
    "FencedLeaderExpandIsr", "FencedLeaderShrinkIsr", "LeaderWrite",
    "FencedLeaderIncHighWatermark", "FencedBecomeFollowerAndTruncate",
    "FencedFollowerFetch"]
THREE_BROKER_X2 = """CONSTANTS
    Replicas = {b1, b2, b3}
    LogSize = 2
    MaxRecords = 2
    MaxLeaderEpoch = 2
    Partitions = 2
INVARIANTS TypeOk LeaderInIsr WeakIsr StrongIsr
CHECK_DEADLOCK FALSE
"""
VIOLATING_X2 = """CONSTANTS
    Replicas = {b1, b2}
    LogSize = 2
    MaxRecords = 1
    MaxLeaderEpoch = 1
    Partitions = 2
INVARIANTS TypeOk WeakIsr
CHECK_DEADLOCK FALSE
"""


def _spans(run_dir, kind):
    return [r for r in read_jsonl_tolerant(
        os.path.join(str(run_dir), "spans.jsonl"))
        if r.get("kind") == "span" and r.get("ph") == "E"
        and r.get("span") == kind]


# --- the cell's cfg is the product, at its published widths ------------------

def test_the_closed_form_of_the_cells_levels():
    assert convolve(ONE_PARTITION, 3, 6) == CELL_LEVELS
    assert [sum(CELL_LEVELS[:d + 1]) for d in (3, 4, 5, 6)] == [
        9311, 113921, 1189826, 10898726]


def test_cell_cfg_builds_the_three_partition_product():
    tlc = parse_cfg(CELL_CFG)
    assert tlc.constants["Partitions"] == 3
    assert len(tlc.constants["Replicas"]) == 5
    assert tlc.invariants == ["TypeOk", "LeaderInIsr", "WeakIsr", "StrongIsr"]
    model = build_model("Kip320", tlc)
    base = kip320.make_model(Config(5, 2, 2, 2), tlc.invariants)
    assert (model.meta["partitions"], model.meta["base_fanout"]) == (3, 113)
    assert base.total_fanout == 113 and model.total_fanout == 339
    assert [a.name for a in model.actions] == [
        f"p{p}.{name}" for p in range(3) for name in BASE_ACTIONS]
    assert [a.n_choices for a in model.actions] == [
        a.n_choices for a in base.actions] * 3
    widths = {a.name: a.n_choices for a in base.actions}
    assert (widths["FencedLeaderExpandIsr"], widths["FencedLeaderShrinkIsr"],
            widths["FencedFollowerFetch"],
            widths["FencedBecomeFollowerAndTruncate"]) == (25, 25, 25, 15)
    # 14 fields and 5 packed lanes a partition
    assert len(base.spec.fields) == 14 and base.spec.num_lanes == 5
    assert len(model.spec.fields) == 42 and model.spec.num_lanes == 15
    assert [i.name for i in model.invariants] == tlc.invariants
    # a product state decodes to one base state a partition
    (init,) = model.init_states()
    decoded = model.decode({k: np.asarray(v) for k, v in init.items()})
    (oinit,) = build_model("Kip320", tlc, oracle=True).init_states()
    assert decoded == oinit and len(decoded) == 3
    assert decoded[0] == decoded[1] == decoded[2]


def test_a_heterogeneous_product_says_each_partitions_fanout():
    a = kip320.make_model(Config(2, 2, 1, 1), invariants=("TypeOk",))
    b = kip320.make_model(Config(3, 2, 1, 1), invariants=("TypeOk",))
    mixed = product_models([a, b])
    assert mixed.meta["partitions"] == 2
    assert mixed.meta["base_fanout"] == [a.total_fanout, b.total_fanout]
    assert sum(mixed.meta["base_fanout"]) == mixed.total_fanout


# --- the cell's job through the harness's door, and its warm protocol --------

def _serve(base):
    """The cell's configuration at depth 3, as `perfbench/run.py` `set_up`
    drives it: a cold pass, `after_setup_pass` (`note_result` + `rewarm`),
    two passes at the capacity fixed point.  -> (job, [pass records],
    [the engine's level records of each pass])."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_adapter", os.path.join(ROOT, "perfbench", "adapter.py"))
    adapter = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adapter)
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    assert config["cfg"] == CELL_CFG and config["options"] == {}
    job = adapter.Job(config, ROOT)
    opts = {"store_trace": False, "max_depth": DEPTH}
    passes, records, rewarmed = [], [], None
    for tag in ("cold", "warm1", "warm2"):
        passes.append(job.run_pass(str(base / tag), opts))
        records.append(job._last_result.stats["levels"])
        if tag == "cold":
            rewarmed = job.after_setup_pass()
    return job, passes, records, rewarmed


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return _serve(tmp_path_factory.mktemp("served"))


# under the capacity the job pins at depth 3 on the CPU (524,288 slots) and
# over the 9,311 states its set ever holds: what 8,388,608 is to the cell's
# 16,777,216 slots and 1,189,826 states on the chip
SMALL_WINDOW = 65_536


@pytest.fixture(scope="module")
def served_windowed(tmp_path_factory):
    """:func:`served` with `dedup.PROBE_WINDOW` under the job's capacity
    (the model, its step cache and every program are this job's own, traced
    while the patch holds)."""
    from kafka_specification_tpu.ops import dedup

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dedup, "PROBE_WINDOW", SMALL_WINDOW)
        return _serve(tmp_path_factory.mktemp("served_windowed"))


@functools.lru_cache(maxsize=None)
def _oracle_to_depth():
    oracle = build_model("Kip320", parse_cfg(CELL_CFG), oracle=True)
    return oracle, oracle_bfs(oracle, max_depth=DEPTH)


def test_every_level_equals_the_oracle_and_the_closed_form(served):
    job, passes, _, _ = served
    _, ores = _oracle_to_depth()
    assert job.lanes == 15
    for rec in passes:
        assert rec["levels"] == ores.levels == CELL_LEVELS[:DEPTH + 1]
        assert rec["total"] == 9311 and rec["violation"] is None
        assert not rec["stats"].get("degradations")
        assert rec["stats"]["fanout"] == 339 and rec["stats"]["lanes"] == 15


def test_action_enablement_equals_the_oracles_in_every_partition(served):
    """What each of the 27 lifted kernels enabled a level, against the
    oracle twin's successors of that level's states; a partition's nine
    counts are its fellows' (independent partitions, one initial state)."""
    _, _, records, _ = served
    oracle, ores = _oracle_to_depth()
    for depth, rec in enumerate(records[2]):
        want = {a.name: sum(sum(1 for _ in a.successors(s))
                            for s in ores.level_sets[depth])
                for a in oracle.actions}
        got = rec["action_enablement"]
        assert got == want, f"level {depth + 1}"
        assert sum(got.values()) == rec["enabled_candidates"]
        for name in BASE_ACTIONS:
            assert (got[f"p0.{name}"] == got[f"p1.{name}"]
                    == got[f"p2.{name}"])
    assert records[0] and [r["action_enablement"] for r in records[0]] == [
        r["action_enablement"] for r in records[2]]


def test_a_warm_pass_builds_no_program_and_repeats_every_count(served):
    job, passes, records, rewarmed = served

    def compiles(rec):
        return sum(1 for s in rec["spans"]["spans"] if s[0] == "compile")

    assert compiles(passes[0]) > 0  # the cold pass is what compiles
    assert rewarmed >= 0
    assert compiles(passes[1]) == 0 and compiles(passes[2]) == 0
    caps = [p["stats"]["visited_capacity"] for p in passes]
    assert caps[1] == caps[2] == job.prepared.capacity_hint >= caps[0]
    keys = ("frontier", "enabled_candidates", "new", "duplicates", "chunks",
            "successor_launches", "dedup_lanes", "guard_lanes", "d2h_bytes")
    assert [[lv[k] for k in keys] for lv in records[1]] == [
        [lv[k] for k in keys] for lv in records[2]]
    for events in (p["spans"]["events"] for p in passes):
        assert not set(events) & {"pipeline-fallback", "compile-fallback",
                                  "chunk-degrade", "retry"}


def test_guard_lanes_of_the_cells_levels(served):
    """Below the compact gate a level is one `step` chunk of 27 per-action
    programs over a bucket of at least 256 rows: its guard side evaluates
    bucket x 339 lanes, which is also what its dedup side is handed."""
    _, passes, records, _ = served
    for rec in records[2]:
        bucket = max(256, 1 << (rec["frontier"] - 1).bit_length())
        assert rec["guard_lanes"] == bucket * 339 == rec["dedup_lanes"]
        assert rec["guard_lanes"] >= rec["frontier"] * 339
        assert rec["enabled_candidates"] <= rec["guard_lanes"]
    # the harness's records keep the field (run.py drops only
    # `action_enablement`)
    assert [r["guard_lanes"] for r in passes[2]["level_records"]] == [
        86784, 86784, 347136]


def test_check_open_and_the_manifest_say_what_the_engine_was_handed(served):
    _, passes, _, _ = served
    want = {"fanout": 339, "lanes": 15, "partitions": 3, "base_fanout": 113}
    config = passes[2]["manifest"]["config"]
    assert {k: config[k] for k in want} == want
    (span,) = _spans(passes[2]["manifest"]["dir"], "check-open")
    assert {k: span[k] for k in want} == want


def test_a_one_partition_model_says_one_partition(tmp_path):
    model = kip320.make_model(Config(2, 2, 1, 1))
    res = check(model, run=RunContext(str(tmp_path / "run")), max_depth=2)
    assert res.ok
    want = {"fanout": model.total_fanout, "lanes": model.spec.num_lanes,
            "partitions": 1, "base_fanout": model.total_fanout}
    (span,) = _spans(tmp_path / "run", "check-open")
    assert {k: span[k] for k in want} == want
    with open(tmp_path / "run" / "manifest.json") as fh:
        config = json.load(fh)["config"]
    assert {k: config[k] for k in want} == want


def test_warm_passes_upload_one_host_image_of_the_visited_set(served):
    """`PreparedKernels.initial_visited`: the jobs of a shape open at one
    capacity with the same initial states, so the sentinel-filled image is
    built once (128 MiB at the cell's 16,777,216 slots), kept read-only
    and uploaded by every pass: the same bytes, no fresh fill a pass."""
    from kafka_specification_tpu.engine.bfs import prepare, sentinel_set

    job, passes, records, _ = served
    pk = job.prepared
    (vcap, hi_b, lo_b), (vhi, vlo) = pk._visited0
    assert vcap == pk.capacity_hint == passes[2]["stats"]["visited_capacity"]
    assert not vhi.flags.writeable and not vlo.flags.writeable
    hi, lo = (np.frombuffer(b, np.uint32) for b in (hi_b, lo_b))
    assert hi.shape == (1,)  # one initial state
    want = sentinel_set(vcap, hi, lo)
    assert np.array_equal(vhi, want[0]) and np.array_equal(vlo, want[1])
    assert (vhi[1:] == 0xFFFFFFFF).all() and vhi.shape == (vcap,)
    # the same object again at the same key, a new one at another
    assert pk.initial_visited(vcap, hi, lo)[0] is vhi
    other = pk.initial_visited(2 * vcap, hi, lo)
    assert other[0] is not vhi and other[0].shape == (2 * vcap,)
    assert pk.initial_visited(vcap, hi, lo)[0] is not vhi  # one kept
    # the bytes a pass uploads before level 1 are what they were: the
    # set's two lanes, the initial state's fields and its padded row
    warm = records[2][0]["h2d_bytes"]
    assert records[1][0]["h2d_bytes"] == warm > 2 * 4 * vcap
    assert warm - 2 * 4 * vcap < 1 << 20  # the rest: a state and a bucket
    assert prepare(job.model)._visited0 is None


# --- the probe's window (ISSUE 45) --------------------------------------------

def test_every_probe_of_a_pinned_pass_searches_the_window(served_windowed):
    """With the window under the pinned capacity and over the set, every
    sorted-set probe of the two passes at the fixed point is one the host
    booked to the window (`probes_windowed == probes > 0` a level); every
    level is golden (the counters: the test below)."""
    job, passes, records, _ = served_windowed
    assert job.prepared.capacity_hint > SMALL_WINDOW > passes[2]["total"]
    for rec in passes:
        assert rec["levels"] == CELL_LEVELS[:DEPTH + 1]
        assert rec["total"] == 9311 and rec["violation"] is None
        assert not rec["stats"].get("degradations")
    # (the cold pass too: its growth ladder starts at 131,072 slots, level
    # 1's bucket of 256 rows x 339, already above the window)
    for recs in records:
        assert [r["probes_windowed"] for r in recs] == [
            r["probes"] for r in recs]
        # one `step` chunk a level, one probe a chunk
        assert [r["probes"] for r in recs] == [1] * DEPTH
    # the harness's records keep both fields
    assert [(r["probes_windowed"], r["probes"])
            for r in passes[2]["level_records"]] == [(1, 1)] * DEPTH


def test_the_window_changes_no_count(served, served_windowed):
    keys = ("frontier", "enabled_candidates", "new", "duplicates", "chunks",
            "successor_launches", "dedup_lanes", "guard_lanes", "probes",
            "probe_rounds", "probe_rounds_plain", "probe_lanes",
            "probe_lanes_plain", "merge_slots", "merge_slots_plain",
            "novel_rows", "novel_rows_plain", "d2h_bytes", "h2d_bytes",
            "d2h_fetches", "action_enablement")
    for a, b in zip(served[2], served_windowed[2]):
        assert [[lv[k] for k in keys] for lv in a] == [
            [lv[k] for k in keys] for lv in b]
    assert [p["stats"]["visited_capacity"] for p in served[1]] == [
        p["stats"]["visited_capacity"] for p in served_windowed[1]]


def test_no_probe_is_windowed_at_the_default_window(served):
    """The CPU's capacities are under `dedup.PROBE_WINDOW`: the probe is
    the parent's program and the counter says so."""
    from kafka_specification_tpu.ops import dedup

    job, passes, records, _ = served
    assert job.prepared.capacity_hint <= dedup.PROBE_WINDOW
    for recs in records:
        assert [r["probes_windowed"] for r in recs] == [0] * DEPTH
        assert [r["probes"] for r in recs] == [1] * DEPTH


# --- two partitions, a level in several chunks -------------------------------

def test_two_partitions_of_the_three_broker_job_stream_chunks(tmp_path):
    """Two partitions of `configs/Kip320.cfg`'s constants to depth 4 in
    chunks of 512 rows (one bucket, so one shape a program): the last
    level streams two chunks through the fused path, so the duplicates
    that interleaving makes (`a` in partition 0 then `b` in partition 1 is
    `b` then `a`) meet across chunks."""
    tlc = parse_cfg(THREE_BROKER_X2)
    model = build_model("Kip320", tlc)
    one = oracle_bfs(build_model("Kip320", parse_cfg(
        THREE_BROKER_X2.replace("    Partitions = 2\n", "")), oracle=True),
        max_depth=4, keep_level_sets=False).levels
    ores = oracle_bfs(build_model("Kip320", tlc, oracle=True), max_depth=4,
                      keep_level_sets=False)
    res = check(model, max_depth=4, store_trace=False, check_deadlock=False,
                run=RunContext(str(tmp_path / "run")), chunk_size=512,
                min_bucket=512, compact_gate=64,
                visited_capacity_hint=1 << 20)
    assert res.levels == ores.levels == convolve(one, 2, 4) == [1, 12, 96, 636, 3288]
    assert res.violation is None and ores.violation is None
    assert res.stats["pipeline"] == "fused"
    assert not res.stats.get("degradations")
    recs = res.stats["levels"]
    assert [r["chunks"] for r in recs] == [
        -(-n // 512) for n in res.levels[:-1]]
    assert recs[-1]["chunks"] >= 2
    assert recs[-1]["chunks_ahead"] == recs[-1]["chunks"] - 1
    assert recs[-1]["duplicates"] > 0
    fanout = model.total_fanout
    for r in recs:
        assert r["guard_lanes"] == r["chunks"] * 512 * fanout
        assert r["enabled_candidates"] <= r["guard_lanes"]
    # the frontier the depth cut leaves gets its invariant pass a chunk a
    # launch, never as one launch of its whole padded length (in the cell
    # 1,075,905 rows: 33 launches of 32,768, not one of 2,097,152); seven
    # pieces here, more than `_Step.PIECES_AHEAD`
    hinv = [d["bucket"] for d in _spans(tmp_path / "run", "dispatch")
            if d["program"] == "hinv"]
    assert hinv == [512] * (1 + -(-res.levels[-1] // 512))
    # ... of ONE program, compiled once
    assert [c["bucket"] for c in _spans(tmp_path / "run", "compile")
            if c["program"] == "hinv"] == [512]
    cut, = [h for h in _spans(tmp_path / "run", "host-invariants")
            if h["rows"] > 1]
    assert cut["rows"] == res.levels[-1] > 512


# --- guard_lanes on the mesh (each pipeline: tests/test_run_phases.py) --------

def test_guard_lanes_on_the_mesh(tmp_path):
    """The sharded record: every shard's padded rows x the fanout, so at
    least the level's rows x the fanout and a multiple of D x fanout."""
    model = kip320.make_model(Config(2, 2, 1, 1))
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    res = check_sharded(model, mesh=mesh, min_bucket=64, max_depth=8,
                        stats_path=str(tmp_path / "stats.jsonl"))
    fanout = model.total_fanout
    for r in res.stats["levels"]:
        assert r["guard_lanes"] % (2 * fanout) == 0
        assert r["guard_lanes"] >= r["frontier"] * fanout
        assert r["enabled_candidates"] <= r["guard_lanes"]


# --- a product state in a counterexample -------------------------------------

def test_a_violating_products_trace_decodes_renders_and_replays():
    tlc = parse_cfg(VIOLATING_X2)
    module = "KafkaTruncateToHighWatermark"
    model = build_model(module, tlc)
    oracle = build_model(module, tlc, oracle=True)
    assert model.meta["partitions"] == 2
    # one bucket of 8,192 rows a level, full-width compaction buffers and
    # a capacity that never grows: one shape a program (the pooled widths
    # of nine levels are nine successor programs, 47 s of compiles; the
    # subject here is the trace, not the compaction)
    res = check(model, check_deadlock=False, min_bucket=8192,
                compact_shift=0, visited_capacity_hint=1 << 20)
    one = oracle_bfs(build_model(module, parse_cfg(
        VIOLATING_X2.replace("    Partitions = 2\n", "")), oracle=True),
        keep_level_sets=False)
    assert one.violation[:2] == ("WeakIsr", 8)
    v = res.violation
    # the product violates where its first partition to get there does
    assert (v.invariant, v.depth, len(v.trace)) == ("WeakIsr", 8, 9)
    assert res.levels[:8] == convolve(one.levels, 2, 7)
    # each step one partition's, named for it, the other's state untouched
    for (_, before), (action, after) in zip(v.trace, v.trace[1:]):
        p = int(action[1])
        assert action.startswith(f"p{p}.") and len(after) == 2
        assert after[1 - p] == before[1 - p] and after[p] != before[p]
    assert v.trace[0][0] == "<init>" and v.state == v.trace[-1][1]
    replay_through_oracle(v.trace, oracle, "WeakIsr")
    text = render_trace(model.meta, v.trace)
    # the structured rendering a partition, never the repr fallback
    assert text.count("  partition 0:") == text.count("  partition 1:") == 9
    assert "frozenset" not in text and "p0." in text or "p1." in text
    sub_meta = {k: x for k, x in model.meta.items() if k != "partitions"}
    from kafka_specification_tpu.utils.pretty import render_state

    last = v.trace[-1][1]
    for p in (0, 1):
        assert render_state(sub_meta, last[p]) in text


# --- one partition and three never share a cached program or verdict ---------

def test_the_caches_keys_tell_one_partition_from_three():
    from kafka_specification_tpu.service.kernel_cache import (
        model_key,
        shape_key,
    )
    from kafka_specification_tpu.service.state_cache import key_for_job

    three = parse_cfg(CELL_CFG)
    one = parse_cfg("configs/Kip320FiveBroker.cfg")
    assert {k: v for k, v in three.constants.items()
            if k != "Partitions"} == one.constants
    invs = tuple(three.invariants)
    assert shape_key("Kip320", three, False, invs) != shape_key(
        "Kip320", one, False, invs)
    assert model_key("Kip320", three, False) != model_key(
        "Kip320", one, False)
    spec = {"module": "Kip320", "max_depth": 5}
    k3, k1 = (key_for_job(spec, c, False, invs) for c in (three, one))
    assert k3 != k1 and k3.base_digest() != k1.base_digest()
    assert ("Partitions", 3) in k3.constants
