"""The sharded engine's programs outlive a `check_sharded` call, and the
engine writes the single-device engine's records (ISSUE 25;
`check_sharded`'s docstring, docs/observability.md § Sharded engine).

CPU, four of the suite's virtual devices, 2-broker constants: a second call
on the same model, mesh and options must trace, lower, compile and load
nothing and return bit-identical counts, digest chain and exchange bytes; a
call with another mesh or option builds its own programs and still agrees
with the plain oracle; a resume in a warm process agrees with an
uninterrupted run; the level records, spans and the benchmark's new cell
read what they should."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax import monitoring
from jax.sharding import Mesh

from kafka_specification_tpu.engine.hostio import (
    LEVEL_COUNTERS,
    LEVEL_TIMINGS,
)
from kafka_specification_tpu.models import kip320
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.oracle.interp import oracle_bfs
from kafka_specification_tpu.parallel import sharded
from kafka_specification_tpu.parallel.sharded import check_sharded
from test_tracing import compact_parts_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = Config(2, 2, 1, 1)  # 277 states, diameter 11
# small gated chunks, so the whole-level programs serve multi-chunk levels
KW = dict(min_bucket=8, compact_gate=8, chunk_size=64, store_trace=False)

# what JAX itself reports of building a program (perfbench/run.py JaxEvents)
BUILD_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_hits",
    "/jax/compilation_cache/cache_misses",
)


@pytest.fixture(scope="module")
def events():
    """Every event name JAX reports while this file's tests run.

    The file starts from empty JAX caches, whatever its worker ran before
    it.  JAX reports `jaxpr_trace_duration` whenever a jitted call misses
    the C++ fast path, also where its trace is cached and nothing is
    built; and an eager primitive whose FIRST call with some signature ran
    inside an eager transformation (`tests/test_differential_walk.py`
    runs the kernels under an eager `jax.vmap`: the trace state is not
    clean, so JAX binds instead of compiling and hands the C++ cache no
    executable) stays off the fast path for that signature for the life
    of the process.  The sharded level loop's one eager slice a level
    (scalar `convert_element_type`) then reported eleven "traces" in every
    later call of a four-device run: the nine cases that were red in
    whole runs since PR 37 and green alone (PR 43)."""
    from jax._src import monitoring as m

    jax.clear_caches()
    seen = []

    def on_duration(name, secs, **kw):
        seen.append(name)

    def on_event(name, **kw):
        seen.append(name)

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    yield seen
    m.unregister_event_duration_listener(on_duration)
    m.unregister_event_listener(on_event)


def _built_since(events, mark):
    """{event: count} of the build events JAX reported since `mark`."""
    seen = events[mark:]
    return {e: seen.count(e) for e in BUILD_EVENTS if e in seen}


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("d",))


def _spans(run_dir):
    recs = read_jsonl_tolerant(os.path.join(run_dir, "spans.jsonl"))
    return [r for r in recs if r.get("kind") == "span" and r["ph"] == "E"]


def _chain(ckpt_dir):
    with np.load(os.path.join(ckpt_dir, "sharded_checkpoint.npz")) as z:
        return np.array(z["digest_chain"])


def _oracle_levels(max_depth=None):
    return oracle_bfs(kip320.make_oracle(CFG), max_depth=max_depth).levels


VARIANTS = {
    # (a) the benchmark's path, and the per-chunk path beside it
    "device-a2a-level": dict(pipeline="device"),
    "device-a2a-chunk": dict(pipeline="legacy"),
    # (b) the other visited backends and the other exchange
    "hash-a2a-chunk": dict(visited_backend="device-hash"),
    "host-a2a-level": dict(visited_backend="host", pipeline="device"),
    "device-gather-level": dict(exchange="all_gather", pipeline="device"),
    "device-a2a-codec-level": dict(pipeline="device", overlap=True),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_second_call_builds_nothing_and_repeats_bit_for_bit(
        tmp_path, monkeypatch, events, variant):
    if "codec" in variant:  # the compressed exchange, off on a CPU mesh
        monkeypatch.setenv("KSPEC_EXCHANGE_COMPRESS", "1")
    model = kip320.make_model(CFG)
    opts = dict(KW, mesh=_mesh(4), **VARIANTS[variant])
    runs = []
    for i in (1, 2):
        run_dir, ck = str(tmp_path / f"run{i}"), str(tmp_path / f"ck{i}")
        mark = len(events)
        res = check_sharded(model, run=RunContext(run_dir),
                            checkpoint_dir=ck, **opts)
        runs.append((res, _built_since(events, mark), _spans(run_dir),
                     _chain(ck)))
    (r1, built1, spans1, chain1), (r2, built2, spans2, chain2) = runs
    assert r1.levels == _oracle_levels() and r1.total == 277
    assert built1, "the first call on a new model builds its programs"
    assert built2 == {}, f"the second call built programs: {built2}"
    assert any(s["span"] == "compile" for s in spans1)
    assert not any(s["span"] == "compile" for s in spans2)
    assert r2.levels == r1.levels and r2.total == r1.total
    assert np.array_equal(chain1, chain2)
    for key in ("exchange_bytes_total", "exchange_raw_bytes_total",
                "visited_capacity_per_shard", "exchange_compressed"):
        assert r2.stats[key] == r1.stats[key], key
    if "codec" in variant:
        assert r1.stats["exchange_compressed"] is True
        assert (r1.stats["exchange_bytes_total"]
                < r1.stats["exchange_raw_bytes_total"])
    if "level" in variant:
        assert r2.stats["device"] == r1.stats["device"]
        assert r1.stats["device"]["fallback"] is None
        assert r1.stats["device"]["levels"] > 0
    drop = ("ts", "unix", "run_id", "level_ms", "step_ms", "host_ms",
            "io_hidden_ms", "io_exposed_ms", "host_probe_ms") + LEVEL_TIMINGS
    for a, b in zip(r1.stats["levels"], r2.stats["levels"]):
        assert ({k: v for k, v in a.items() if k not in drop}
                == {k: v for k, v in b.items() if k not in drop})


@pytest.mark.parametrize("change", ["mesh-2", "all-gather", "legacy"])
def test_another_mesh_or_option_builds_its_own_and_agrees_with_oracle(
        events, change):
    """(c) a warm cache must not serve a call it was not built for."""
    model = kip320.make_model(CFG)
    base = dict(KW, mesh=_mesh(4), pipeline="device")
    warm = check_sharded(model, **base)
    other = dict(base, **{
        "mesh-2": dict(mesh=_mesh(2)),
        "all-gather": dict(exchange="all_gather"),
        "legacy": dict(pipeline="legacy"),
    }[change])
    mark = len(events)
    res = check_sharded(model, **other)
    built = _built_since(events, mark)
    assert built.get("/jax/core/compile/jaxpr_to_mlir_module_duration"), (
        "a call with another mesh or option lowers its own programs", built)
    assert res.levels == _oracle_levels() == warm.levels
    assert res.stats["devices"] == other["mesh"].devices.size
    # ... and the first set is still there for the call it was built for
    mark = len(events)
    again = check_sharded(model, **base)
    assert _built_since(events, mark) == {} and again.levels == warm.levels


@pytest.mark.parametrize("pipeline", ["device", "legacy"])
def test_resume_in_a_warm_process_agrees_with_an_uninterrupted_run(
        tmp_path, pipeline):
    """(d) a resumed call starts mid-ladder: it may build programs the
    cache lacks, and lands on the same counts and the same chain."""
    model = kip320.make_model(CFG)
    opts = dict(KW, mesh=_mesh(4), pipeline=pipeline)
    full_ck = str(tmp_path / "full")
    full = check_sharded(model, checkpoint_dir=full_ck, **opts)
    ck = str(tmp_path / "ck")
    cut = check_sharded(model, checkpoint_dir=ck, max_depth=7, **opts)
    assert cut.diameter == 7 and cut.levels == full.levels[:8]
    resumed = check_sharded(model, checkpoint_dir=ck, **opts)
    assert resumed.levels == full.levels and resumed.total == full.total
    assert np.array_equal(_chain(ck), _chain(full_ck))


@pytest.mark.parametrize("pipeline", ["device", "legacy"])
def test_level_records_and_spans_of_a_sharded_run(tmp_path, pipeline):
    """(e) the single-device engine's records, under the same names."""
    model = kip320.make_model(CFG)
    opts = dict(KW, mesh=_mesh(4), pipeline=pipeline)
    dirs = [str(tmp_path / "run1"), str(tmp_path / "run2")]
    res = [check_sharded(model, run=RunContext(d), **opts) for d in dirs][1]
    recs = res.stats["levels"]
    # (one record per expanded level: the last finds nothing new)
    assert len(recs) == res.diameter + 1 and recs[-1]["new"] == 0
    for rec in recs:
        for key in ("step_ms", "host_ms", "level_ms", "shard_new",
                    "exch_bytes", "shard_launches", "chunks",
                    "dedup_lanes", "guard_lanes") + LEVEL_COUNTERS:
            assert key in rec, key
        # the lanes the shards' dedup sides ran hold every candidate, and
        # the host's blocked time is part of the level's
        assert rec["dedup_lanes"] >= rec["enabled_candidates"]
        assert rec["chunks"] >= 1
        assert rec["fetch_ms"] >= 0 and rec["put_ms"] >= 0
        assert rec["dispatches"] >= rec["shard_launches"] >= 1
        assert rec["d2h_fetches"] > 0 and rec["d2h_bytes"] > 0
        assert rec["h2d_puts"] > 0 and rec["h2d_bytes"] > 0
        assert rec["discarded_dispatches"] <= rec["dispatches"]
        assert sum(rec["shard_new"]) == rec["new"]
    # the blocked time is part of the run's: a record holds what crossed
    # since the last one, so level 1's holds the uploads made before its
    # span began, and a per-level bound (`fetch_ms <= level_ms + 0.1`, until
    # PR 38) compared host clocks of different windows: it failed once in
    # the driver's loaded run of PR 37's tree and once in five here
    assert sum(r["fetch_ms"] + r["put_ms"] for r in recs) <= \
        res.seconds * 1e3 + 1.0
    assert sum(r["exch_bytes"] for r in recs) == \
        res.stats["exchange_bytes_total"] > 0
    first, second = (_spans(d) for d in dirs)
    for spans in (first, second):
        kinds = [s["span"] for s in spans]
        for kind in ("check", "check-open", "check-close", "dispatch",
                     "init-states", "host-invariants", "frontier-verify",
                     "level"):
            assert kind in kinds, kind
        assert kinds.count("check") == 1
        root = next(s for s in spans if s["span"] == "check")
        assert all(s["parent_id"] == root["span_id"]
                   for s in spans if s["span"] == "level")
        programs = {s["program"] for s in spans if s["span"] == "dispatch"}
        level_tag = {"device": sharded.LEVEL_TAG}.get(pipeline)
        assert sharded.INVARIANT_TAG in programs
        assert programs <= {sharded.STEP_TAG, sharded.LEVEL_TAG,
                            sharded.INVARIANT_TAG}
        assert level_tag is None or level_tag in programs
        n_disp = sum(1 for s in spans if s["span"] == "dispatch"
                     and s["program"] != sharded.INVARIANT_TAG)
        assert n_disp == sum(r["dispatches"] for r in recs)
        assert (sum(1 for s in spans if s["span"] == "dispatch"
                    and s.get("discarded"))
                == sum(r["discarded_dispatches"] for r in recs))
    compiled = [s for s in first if s["span"] == "compile"]
    assert compiled and {s["program"] for s in compiled} <= {
        sharded.STEP_TAG, sharded.LEVEL_TAG, sharded.INVARIANT_TAG}
    assert not [s for s in second if s["span"] == "compile"]


def test_sharded_programs_carry_their_names_and_the_exchange_scope():
    """Module names say which sharded program ran; the exchange body has
    its own scope beside the shared stages' (trace_stages.json reads it)."""
    import re

    from kafka_specification_tpu.engine import pipeline as pl
    from kafka_specification_tpu.engine.bfs import _Step

    model = kip320.make_model(CFG)
    mesh = _mesh(4)
    check_sharded(model, mesh=mesh, pipeline="device", max_depth=5, **KW)
    check_sharded(model, mesh=mesh, pipeline="legacy", max_depth=3, **KW)
    cache = _Step(model)._cache
    seen = {}
    for key, fn in cache.items():
        if key[0] in (sharded.STEP_TAG, sharded.LEVEL_TAG) \
                and key[0] not in seen:
            seen[key[0]] = (key, fn)
    assert set(seen) == {sharded.STEP_TAG, sharded.LEVEL_TAG}
    D, K = 4, model.spec.num_lanes
    u32 = jax.numpy.uint32
    for tag, (key, fn) in seen.items():
        if tag == sharded.STEP_TAG:
            bucket, vcap = key[2], key[3]
            args = (jax.ShapeDtypeStruct((D * bucket, K), u32),
                    jax.ShapeDtypeStruct((D * bucket,), bool))
        else:
            B, NCp, vcap = key[2], key[3], key[4]
            args = (jax.ShapeDtypeStruct((D * NCp * B, K), u32),
                    jax.ShapeDtypeStruct((D,), jax.numpy.int32),
                    jax.ShapeDtypeStruct((D,), jax.numpy.int32))
        args += (jax.ShapeDtypeStruct((D, vcap), u32),
                 jax.ShapeDtypeStruct((D, vcap), u32),
                 jax.ShapeDtypeStruct((D,), jax.numpy.int32))
        fn = getattr(fn, "fn", fn)  # a first-call wrapper, or the jit
        text = fn.lower(*args).as_text(debug_info=True)
        assert re.search(rf"module @jit_{tag}_n{pl.NAMING_VERSION}\b", text)
        found = set(re.findall(r"kspec\.([a-z_]+)", text))
        assert "exchange" in found
        assert found - {"exchange"} <= set(pl.STAGES), found
        assert {"guard", "expand", "fingerprint", "dedup_probe",
                "dedup_merge", "invariants", "digest"} <= found, (tag, found)
        # the parts of `compact` (pipeline.COMPACT_PARTS), each directly
        # under the stage scope: the guard matrix's index compaction and
        # the new states' compaction; the sharded level program appends
        # outside the stage scope, as it did before the parts
        parts = compact_parts_of(text)
        assert parts == {"select", "novel"}, (tag, parts)


def test_benchmark_cell_rehearses_on_four_virtual_devices():
    """(f) `kip320-5b-x4` through the harness: CPU, depth 4, counts only.
    A rehearsal has no device trace, so of the four exchange metrics it
    reports the two that read counters; the two that read the trace are
    held to a recorded context instead."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "kip320-5b-x4", "--seed", "2147483659",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] >= 3
    assert last["failed"] == 0 and last["problems"] == []
    assert last["device"]["count"] == 4
    names = set(last["metric_names"])
    assert {"exchange_bytes_per_state", "shard_imbalance"} <= names
    assert {"host_share", "ms_per_level", "launches_per_level",
            "step_us_per_state", "programs"} <= names
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        import exchange
        import run as harness
        readers = harness.load_metric_readers()
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("exchange_bytes_per_state", "collective_share",
                 "shard_imbalance", "exchange_ici_share"):
        assert entries[name]["workloads"] == ["kip320-5b-x4"]
        assert entries[name]["layer"] == "exchange"
        assert all(readers[name].META[k] == entries[name][k] for k in
                   ("unit", "better", "source", "layer", "moves"))
    # 4 shards, 1.6e9 bytes counted: a quarter of it is each chip's, three
    # quarters of that leave it: 3e8 bytes, 1.5 ms of 1,600 Gbit/s
    assert exchange.bytes_leaving_one_chip(1.6e9, 4) == 3e8
    ctx = {"trace": {"collective_s_mean": 0.006, "busy_s_mean": 0.6},
           "traced": {"manifest": {"result":
                                   {"exchange_bytes_total": 1.6e9}}},
           "peaks": {"ici_bits_per_s": 1600e9}, "chips": 4}
    assert readers["collective_share"].read(ctx) == pytest.approx(1.0)
    assert readers["exchange_ici_share"].read(ctx) == pytest.approx(25.0)
    assert readers["exchange_ici_share"].read(dict(ctx, trace=None)) is None
