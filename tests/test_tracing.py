"""Stage names on the device, and the host's dispatch / transfer / store
records (docs/observability.md § Stage vocabulary, § Spans; ISSUE 23).

CPU, toy models, each case a few seconds: what a profile of the chip reads
by stage is decided by what these find in the lowered programs."""

import functools
import json
import re

import jax.numpy as jnp
import numpy as np
import pytest

from kafka_specification_tpu.engine import check
from kafka_specification_tpu.engine import pipeline as pl
from kafka_specification_tpu.engine.bfs import _Step
from kafka_specification_tpu.engine.hostio import (
    LEVEL_COUNTERS,
    LEVEL_TIMINGS,
)
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant

KW = dict(min_bucket=64, compact_gate=64)
BUCKET, VCAP = 64, 2048


def _model():
    return frl.make_model(3, 4, 1)


# --- (a) every stage of the vocabulary lands in the lowered programs -------

# (`canon`, PR 38, is in the programs of a model with a `symmetry` alone:
# the toy model has none, and tests/test_symmetry.py holds both halves)
ALL = set(pl.STAGES) - {"canon"}
DEDUP = {"dedup_sort", "dedup_probe", "dedup_merge"}
PROGRAMS = {
    # tag: (visited backend, the stages the program contains)
    "fgd": ("device", {"guard", "invariants"}),
    "fsc": ("device", {"expand", "compact", "fingerprint"} | DEDUP),
    "dvl": ("device", ALL),
    "dvh": ("host", ALL - {"digest"}),
    "step": ("device", ALL - {"digest"}),
    # the engine's start and finish (ISSUE 26)
    "hinv": ("device", {"invariants"}),
    "init": ("device", {"expand", "fingerprint"}),
}


# the parts of `compact` each program must hold (pl.COMPACT_PARTS): the
# fused path selects on the host (`compact-host`) and hands its pooled
# layout to the sorted dedup unsqueezed (a squeeze at the width it is
# handed narrows nothing), the whole-level programs alone append
PARTS = {
    "fgd": set(), "hinv": set(), "init": set(),
    "fsc": {"novel"},
    "step": {"select", "squeeze", "novel"},
    "dvl": set(pl.COMPACT_PARTS),
    "dvh": set(pl.COMPACT_PARTS),
}


@functools.lru_cache(maxsize=None)
def _lower(tag):
    """The lowered text of one `tag` program of the toy model."""
    m = _model()
    sb = _Step(m)
    backend = PROGRAMS[tag][0]
    K = m.spec.num_lanes
    u32 = jnp.uint32
    rows = jnp.zeros((BUCKET, K), u32)
    visited = (jnp.zeros((VCAP,), u32), jnp.zeros((VCAP,), u32), jnp.int32(0))
    if tag == "step":
        fn = sb.get(BUCKET, VCAP, True, with_merge=True, compact=2)
        args = (rows, jnp.zeros((BUCKET,), bool)) + visited
    elif tag in ("hinv", "init"):
        assert check(m, max_depth=0, min_bucket=BUCKET).ok
        fn = sb._cache[{"hinv": ("hinv", BUCKET, sb.inv_sig(True)),
                        "init": ("init", 1)}[tag]]
        args = (rows, np.int32(1)) if tag == "hinv" else ({
            k: np.asarray(v, np.int32)[None]
            for k, v in m.init_states()[0].items()},)
    elif tag in ("fgd", "fsc"):
        fused = pl.FusedPipeline(sb, m, None, None, None, True, backend,
                                 None, 2, BUCKET)
        if tag == "fgd":
            fn = fused.guard_step(BUCKET)
            args = (rows, jnp.zeros((BUCKET,), bool))
        else:
            widths = (256,) * len(m.actions)
            W = sum(widths)
            fn = fused.succ_step(BUCKET, widths, VCAP)
            args = (rows, jnp.zeros((W,), jnp.int32),
                    jnp.zeros((W,), jnp.int32), jnp.zeros((W,), bool)
                    ) + visited
    else:
        dev = pl.DevicePipeline(sb, m, None, None, None, True, backend,
                                None, 2, BUCKET)
        widths = sb.norm_widths(BUCKET, (256,) * len(m.actions))
        fn = dev._level_program(BUCKET, 1, VCAP, widths, 1024)
        args = (rows, jnp.int32(0), jnp.int32(0))
        if tag == "dvl":
            args += visited
    fn = getattr(fn, "fn", fn)  # a first-call wrapper, or the jit
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("tag", sorted(PROGRAMS))
def test_program_carries_its_stages_and_its_name(tag):
    text = _lower(tag)
    found = set(re.findall(r"kspec\.([a-z_]+)", text))
    assert found <= ALL, f"scopes outside the vocabulary: {found - ALL}"
    assert found == PROGRAMS[tag][1], (tag, found ^ PROGRAMS[tag][1])
    # the module name says which program it is and which naming version:
    # the compile cache hashes the name, not the scopes
    assert pl.program_name(tag) == f"{tag}_n{pl.NAMING_VERSION}"
    assert re.search(rf"module @jit_{tag}_n{pl.NAMING_VERSION}\b", text)


def compact_parts_of(text):
    """The `part.<name>` scopes a lowered program holds, each held to its
    place: a name of the vocabulary directly under `kspec.compact`, one a
    path, and no operation of `kspec.compact` outside one:
    `jit(step_n2)/kspec.compact/part.squeeze/scatter`."""
    found = set()
    compact = pl.STAGE_PREFIX + "compact"
    for path in set(re.findall(r'"([^"]*(?:kspec\.compact|part\.)[^"]*)"',
                               text)):
        comps = path.split("/")
        for i, comp in enumerate(comps):
            if comp.startswith(pl.PART_PREFIX):
                name = comp[len(pl.PART_PREFIX):]
                assert name in pl.COMPACT_PARTS, path
                assert comps[i - 1] == compact, path
                found.add(name)
            elif comp == compact:
                assert comps[i + 1].startswith(pl.PART_PREFIX), path
        assert sum(c.startswith(pl.PART_PREFIX) for c in comps) <= 1, path
    return found


@pytest.mark.parametrize("tag", sorted(PROGRAMS))
def test_compact_runs_under_exactly_one_part(tag):
    found = compact_parts_of(_lower(tag))
    assert found == PARTS[tag], (tag, found ^ PARTS[tag])
    assert ("compact" in PROGRAMS[tag][1]) == bool(found)
    # a reader of the stage takes the innermost `kspec.*` component and
    # must not meet the parts there
    assert not pl.PART_PREFIX.startswith(pl.STAGE_PREFIX)


# --- (b) spans: true starts, one root, a cause on every span ---------------

def _spans(run):
    recs = read_jsonl_tolerant(run.spans_path)
    return [r for r in recs if r.get("kind") == "span"]


@pytest.mark.parametrize("pipeline", ["fused", "device"])
def test_every_engine_span_reaches_check(tmp_path, pipeline):
    run = RunContext(str(tmp_path / "run"))
    res = check(_model(), pipeline=pipeline, run=run, **KW)
    assert res.total == 125
    spans = _spans(run)
    done = {s["span_id"]: s for s in spans if s["ph"] == "E"}
    roots = [s for s in done.values() if s["span"] == "check"]
    assert len(roots) == 1
    root = roots[0]
    outside = {"run-open"}  # RunContext's own, before the check began
    for s in done.values():
        if s is root:
            continue
        if s["parent_id"] is None:
            assert s["span"] in outside, s
            continue
        # walk to the root; every child lies inside its parent to 1 ms
        node, hops = s, 0
        while node["parent_id"] is not None:
            parent = done[node["parent_id"]]
            assert node["t0"] >= parent["t0"] - 1e-3, (node, parent)
            assert (node["t0"] + node["ms"] / 1e3
                    <= parent["t0"] + parent["ms"] / 1e3 + 1e-3), (node, parent)
            node, hops = parent, hops + 1
            assert hops < 8
        assert node is root, s
    kinds = {s["span"] for s in done.values()}
    assert {"check", "check-open", "check-close", "run-open", "init-states",
            "host-invariants", "frontier-verify", "level", "dispatch", "step",
            "host-assembly", "store"} <= kinds
    if pipeline == "fused":
        assert "compact-host" in kinds
    # the blocked part of the two host spans between launches: the fetches
    # (and, selecting on the host, the uploads) inside them
    for s in done.values():
        if s["span"] == "compact-host":
            assert 0 <= s["fetch_ms"] and 0 <= s["put_ms"]
            assert s["fetch_ms"] + s["put_ms"] <= s["ms"] + 1e-2, s
        elif s["span"] == "host-assembly" and pipeline == "fused":
            assert 0 <= s["fetch_ms"] <= s["ms"] + 1e-2, s
    levels = [s for s in done.values() if s["span"] == "level"]
    assert all(s["parent_id"] == root["span_id"] for s in levels)
    t0s = [s["t0"] for s in sorted(levels, key=lambda s: s["depth"])]
    assert t0s == sorted(t0s) and len(set(t0s)) == len(t0s)
    # a begin marker and its completed span share one id
    begun = {s["span_id"] for s in spans if s["ph"] == "B"}
    assert begun == {s["span_id"] for s in levels} | {root["span_id"]}
    # microsecond records
    assert any(round(s["t0"], 3) != s["t0"] for s in done.values())
    programs = {s["program"] for s in done.values() if s["span"] == "dispatch"}
    # the engine's start and finish (ISSUE 26), then the level programs
    assert programs == {"init", "hinv"} | (
        {"fgd", "fsc"} if pipeline == "fused" else {"dvl"})
    man = json.load(open(run.manifest_path))
    assert man["dir"] == str(tmp_path / "run")


# --- (c) level records carry the counters, and the counts repeat -----------

@pytest.mark.parametrize("pipeline", ["fused", "device"])
def test_level_records_carry_repeatable_counters(tmp_path, pipeline):
    runs = []
    for i in range(2):
        res = check(_model(), pipeline=pipeline,
                    run=RunContext(str(tmp_path / f"run{i}")), **KW)
        runs.append(res.stats["levels"])
    exact = [k for k in LEVEL_COUNTERS if k not in LEVEL_TIMINGS] + [
        "chunks", "dedup_lanes", "guard_lanes"]
    for rec in runs[0]:
        assert set(LEVEL_COUNTERS) | {"store_ms", "dedup_lanes",
                                      "guard_lanes"} <= set(rec)
        # every candidate a level enabled lay in a lane its dedup sides
        # were handed; the host's blocked time is part of the level's
        assert rec["dedup_lanes"] >= rec["enabled_candidates"]
        assert rec["dedup_lanes"] > 0 and rec["chunks"] >= 1
        assert 0 <= rec["fetch_ms"] <= rec["level_ms"] + 0.1
        assert 0 <= rec["put_ms"] <= rec["level_ms"] + 0.1
        assert rec["dispatches"] >= 1 and rec["d2h_fetches"] >= 1
        assert rec["d2h_bytes"] > 0 and rec["h2d_bytes"] > 0
        assert rec["discarded_dispatches"] == 0 == rec["discarded_ms"]
    assert [[r[k] for k in exact] for r in runs[0]] == \
        [[r[k] for k in exact] for r in runs[1]]
    # level 1 carries what crossed before it: the initial state's fields
    # (`init`), its row padded to the bucket floor (`hinv`), and the visited
    # set's first upload, two u32 lanes of the initial capacity, min_bucket
    # x fanout rounded up to a power of two (levels 1 and 2 are otherwise
    # one chunk of the same bucket)
    m = _model()
    vcap0 = 1 << (KW["min_bucket"] * _Step(m).C - 1).bit_length()
    start = sum(np.asarray(v, np.int32).nbytes
                for v in m.init_states()[0].values())
    start += KW["min_bucket"] * m.spec.num_lanes * 4
    assert (runs[0][0]["h2d_bytes"] - runs[0][1]["h2d_bytes"]
            == start + 2 * 4 * vcap0)
    # the emitted stream stays historical: none of it reaches stats.jsonl
    emitted = read_jsonl_tolerant(str(tmp_path / "run0" / "stats.jsonl"))
    assert emitted and not any(
        set(LEVEL_COUNTERS) & set(r) or "store_ms" in r
        or "dedup_lanes" in r or "guard_lanes" in r for r in emitted)


# --- (d) a discarded dispatch is counted and marked ------------------------

def test_forced_overflow_discards_one_dispatch(tmp_path, monkeypatch):
    from kafka_specification_tpu.ops import devlevel

    ref = check(_model(), pipeline="device", **KW)
    # shrink the level-new ladder: every level of more than 8 new states
    # overflows it and re-runs at the safe bound
    monkeypatch.setattr(devlevel, "level_new_capacity",
                        lambda T, hw, worst: 8)
    run = RunContext(str(tmp_path / "run"))
    res = check(_model(), pipeline="device", run=run, **KW)
    assert (res.total, res.levels) == (ref.total, ref.levels)
    redone = [r for r in res.stats["levels"] if r["successor_launches"] == 2]
    assert redone and len(redone) < len(res.stats["levels"])
    for rec in res.stats["levels"]:
        again = rec["successor_launches"] == 2
        assert rec["discarded_dispatches"] == (1 if again else 0)
        assert rec["dispatches"] == rec["successor_launches"]
        assert (rec["discarded_ms"] > 0) == again
        assert rec["discarded_ms"] <= rec["level_ms"] + 1.0
    dispatches = [s for s in _spans(run) if s["span"] == "dispatch"]
    thrown = [s for s in dispatches if s.get("discarded")]
    assert len(thrown) == len(redone)
    for s in thrown:
        assert s["discarded"] is True and s["attempt"] == 0
        again = [d for d in dispatches if d.get("depth") == s["depth"]
                 and d["attempt"] == 1]
        assert len(again) == 1 and "discarded" not in again[0]
        assert again[0]["level_new_cap"] > s["level_new_cap"] == 8


# --- (e) the store span exists exactly when something is stored ------------

@pytest.mark.parametrize("store_trace", [False, True])
def test_store_span_only_when_storing(tmp_path, store_trace):
    run = RunContext(str(tmp_path / "run"))
    res = check(_model(), run=run, store_trace=store_trace, **KW)
    stores = [s for s in _spans(run) if s["span"] == "store"]
    recs = res.stats["levels"]
    if not store_trace:
        assert not stores
        assert all(r["store_ms"] == 0 for r in recs)
        return
    assert len(stores) == len(recs)
    assert all(r["store_ms"] > 0 for r in recs)
    by_depth = {s["depth"]: s for s in stores}
    for r in recs:
        s = by_depth[r["depth"]]
        assert s["rows"] == r["new"]
        assert (s["bytes"] > 0) == (r["new"] > 0)


# --- the gauge is the run's rate, not the last level's ----------------------

def test_states_per_sec_gauge_is_the_runs_rate(tmp_path):
    run = RunContext(str(tmp_path / "run"))
    res = check(_model(), run=run, **KW)
    gauge = run.metrics.gauges["kspec_states_per_sec"]
    last = res.stats["levels"][-1]
    assert last["new"] == 0  # the last level's own rate would read 0
    assert 0 < gauge <= res.total / (sum(
        r["level_ms"] for r in res.stats["levels"]) / 1e3)
