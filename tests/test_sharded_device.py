"""Sharded device-resident level pipeline (`--pipeline device` +
`--sharded`): per-shard one-dispatch level programs with the exchange
inside the loop.

Pins the PR's contracts:
- bit-identity with the per-chunk sharded path (`pipeline="legacy"`, the
  oracle): counts, levels, duplicate accounting, first-violation rule,
  trace VALUES and digest chains — across violating/clean models, both
  exchange modes, the compressed exchange, and multi-chunk levels;
- O(1) collective-bearing launches per level per shard, span-tracer- and
  gauge-pinned, with a >1-chunk single-dispatch proven and the <=2-launch
  bound holding through the forced level-new-overflow exact re-dispatch;
- cross-pipeline sharded checkpoint resume (sharded-device <->
  sharded-legacy) and an elastic 4->2 reshard under the device pipeline;
- the degradation ladder (non-device backend / injected compile failure
  -> per-chunk, sticky, reason recorded) and loud rejection of unknown
  pipeline names;
- the EXPLICIT mesh-axis layouts (mesh_layouts): every placed tensor
  class carries the named PartitionSpec, asserted on real committed
  arrays and recorded in stats.
"""

import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import kip320, variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs.runctx import RunContext
from kafka_specification_tpu.parallel.sharded import (
    check_sharded,
    mesh_layouts,
)

pytestmark = pytest.mark.sharded_device

# small gated chunks: the serial sharded path compacts at these sizes,
# so the device program covers the same chunks it mirrors
KW = dict(min_bucket=8, compact_gate=8, chunk_size=64)


def _mk_violating():
    return variants.make_model(
        "KafkaTruncateToHighWatermark", Config(2, 2, 1, 1),
        ("TypeOk", "WeakIsr"),
    )


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("d",))


def _verdict(res):
    v = res.violation
    return (
        res.levels,
        res.total,
        None if v is None else (v.invariant, v.depth, v.state),
    )


def test_sharded_device_bit_identity_violating_model():
    """Counts, levels, first-violation rule and trace VALUES equal the
    per-chunk oracle on the violating workload."""
    ref = check_sharded(_mk_violating(), pipeline="legacy", **KW)
    res = check_sharded(_mk_violating(), pipeline="device", **KW)
    assert res.stats["device"]["levels"] > 0
    assert res.stats["device"]["fallback"] is None
    assert res.stats["pipeline"] == "device"
    assert _verdict(res) == _verdict(ref)
    assert res.violation.trace == ref.violation.trace
    assert res.violation.depth == 8 and res.violation.invariant == "WeakIsr"


@pytest.mark.slow
def test_sharded_device_bit_identity_all_gather():
    """The all_gather exchange mode inside the level loop is exact too."""
    m = kip320.make_model(Config(2, 2, 1, 1))
    ref = check_sharded(m, pipeline="legacy", exchange="all_gather", **KW)
    res = check_sharded(m, pipeline="device", exchange="all_gather", **KW)
    assert res.stats["device"]["levels"] > 0
    assert (res.total, res.levels) == (ref.total, ref.levels) == (277, ref.levels)


@pytest.mark.slow
def test_sharded_device_compressed_exchange(monkeypatch):
    """The PR 10 compression codec rides INSIDE the while_loop:
    bit-identical results, strictly fewer wire bytes than the raw
    layout at the same widths."""
    monkeypatch.setenv("KSPEC_EXCHANGE_COMPRESS", "1")
    m = kip320.make_model(Config(2, 2, 1, 1))
    ref = check_sharded(m, pipeline="legacy", **KW)
    res = check_sharded(m, pipeline="device", **KW)
    assert res.stats["device"]["levels"] > 0
    assert (res.total, res.levels) == (ref.total, ref.levels)
    assert res.stats["exchange_compressed"] is True
    assert 0 < res.stats["exchange_bytes_total"] < \
        res.stats["exchange_raw_bytes_total"]


@pytest.mark.parametrize("pipeline", ["legacy", "device"],
                         ids=["per-chunk", "whole-level"])
def test_sharded_level_records_carry_the_probe_rounds(tmp_path, pipeline):
    """The sharded twin of tests/test_obs.py's: configs/Kip320.cfg cut to
    depth 8 on the mesh, every level record with the rounds its shards'
    probes ran (each shard its own count: the loop holds no collective)
    and the rounds whole-capacity searches would have run; the counts
    are the golden's."""
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    res = check_sharded(model, max_depth=8, pipeline=pipeline,
                        store_trace=False,
                        run=RunContext(str(tmp_path / "run")))
    assert res.ok
    assert res.levels == [1, 6, 30, 138, 366, 1170, 2715, 5673, 10836]
    records = res.stats["levels"]
    assert len(records) == 8
    for rec in records:
        assert 0 <= rec["probe_rounds"] <= rec["probe_rounds_plain"], rec
        assert rec["probe_rounds_plain"] > 0, rec
    assert records[-1]["probe_rounds"] > 0
    assert sum(r["probe_rounds"] for r in records) < \
        0.6 * sum(r["probe_rounds_plain"] for r in records)


@pytest.mark.parametrize("pipeline", ["legacy", "device"],
                         ids=["per-chunk", "whole-level"])
def test_sharded_level_records_carry_the_probe_lanes(tmp_path, monkeypatch,
                                                     pipeline):
    """The sharded twin of tests/test_obs.py's, at a probe block of 256
    lanes, twice: every level record with the query lanes its shards'
    probes searched (each shard its own live prefix: the loop holds no
    collective) beside the lanes they were handed: `dedup_lanes` where a
    shard probes once a chunk (`shs`), more than twice that in the
    whole-level program (`shl`: two probes a chunk and the level-new
    rank); whole blocks, the two runs equal to the lane, a fraction of the
    layout over the run; the counts are the golden's."""
    from kafka_specification_tpu.ops import dedup
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    monkeypatch.setattr(dedup, "PROBE_BLOCK", 256)
    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    runs = []
    for i in range(2):
        res = check_sharded(model, max_depth=8, pipeline=pipeline,
                            store_trace=False,
                            run=RunContext(str(tmp_path / f"run{i}")))
        assert res.ok
        assert res.levels == [1, 6, 30, 138, 366, 1170, 2715, 5673, 10836]
        runs.append(res.stats["levels"])
    for rec in runs[0]:
        assert 0 <= rec["probe_lanes"] <= rec["probe_lanes_plain"], rec
        assert rec["probe_lanes"] >= rec["enabled_candidates"], rec
        if pipeline == "legacy":
            assert rec["probe_lanes_plain"] == rec["dedup_lanes"], rec
        else:  # (the narrow levels run `shs` under the gate)
            assert rec["probe_lanes_plain"] >= rec["dedup_lanes"], rec
    if pipeline == "device":
        assert runs[0][-1]["probe_lanes_plain"] > \
            2 * runs[0][-1]["dedup_lanes"]
    assert [(r["probe_lanes"], r["probe_lanes_plain"]) for r in runs[0]] \
        == [(r["probe_lanes"], r["probe_lanes_plain"]) for r in runs[1]]
    assert sum(r["probe_lanes"] for r in runs[0]) < \
        0.6 * sum(r["probe_lanes_plain"] for r in runs[0])


@pytest.mark.parametrize("pipeline", ["legacy", "device"],
                         ids=["per-chunk", "whole-level"])
def test_sharded_level_records_carry_the_merge_slots(tmp_path, monkeypatch,
                                                     pipeline):
    """configs/Kip320.cfg cut to depth 7 on the mesh at a merge block of
    256 slots, twice: every level record holds the slots its shards'
    merges touched (each shard its own count, no collective in the loops)
    beside the slots capacity-wide merges touch, summed over the shards
    (every shard runs the same shapes); the two runs agree to the slot
    and the counts are the golden's."""
    from kafka_specification_tpu.ops import dedup
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    monkeypatch.setattr(dedup, "MERGE_BLOCK", 256)
    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    runs = []
    for i in range(2):
        res = check_sharded(model, max_depth=7, pipeline=pipeline,
                            store_trace=False,
                            run=RunContext(str(tmp_path / f"run{i}")))
        assert res.ok
        assert res.levels == [1, 6, 30, 138, 366, 1170, 2715, 5673]
        runs.append(res.stats["levels"])
    for rec in runs[0]:
        shards = len(rec["shard_new"])
        assert 0 < rec["merge_slots"] <= rec["merge_slots_plain"], rec
        assert rec["merge_slots"] % 256 == 0, rec
        assert rec["merge_slots_plain"] % shards == 0, rec
    assert [(r["merge_slots"], r["merge_slots_plain"]) for r in runs[0]] \
        == [(r["merge_slots"], r["merge_slots_plain"]) for r in runs[1]]
    assert sum(r["merge_slots"] for r in runs[0]) < \
        0.5 * sum(r["merge_slots_plain"] for r in runs[0])


@pytest.mark.parametrize("pipeline", ["legacy", "device"],
                         ids=["per-chunk", "whole-level"])
def test_sharded_level_records_carry_the_novel_rows(tmp_path, monkeypatch,
                                                    pipeline):
    """configs/Kip320.cfg cut to depth 7 on four devices at a compaction
    block of 256 rows, twice: every level record holds the rows its
    shards' `novel_stage` loops touched beside the rows full-width
    compactions of the same dedup stages touch (`R` a shard), summed over
    the shards.  The whole-level program compacts through the shared
    dedup stage, whose loops follow each shard's own live prefix and new
    states (no collective in them); the per-chunk step keeps its own
    full-width compaction, which is not `novel_stage` and counts nothing.
    The two runs agree to the row and the counts are the golden's."""
    from kafka_specification_tpu.engine import pipeline as pl
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    monkeypatch.setattr(pl, "NOVEL_BLOCK", 256)
    model = build_model("Kip320", parse_cfg("configs/Kip320.cfg"))
    runs = []
    for i in range(2):
        res = check_sharded(model, max_depth=7, pipeline=pipeline,
                            store_trace=False, mesh=_mesh(4),
                            run=RunContext(str(tmp_path / f"run{i}")))
        assert res.ok
        assert res.levels == [1, 6, 30, 138, 366, 1170, 2715, 5673]
        runs.append(res.stats["levels"])
    for rec in runs[0]:
        assert len(rec["shard_new"]) == 4
        assert 0 <= rec["novel_rows"] <= rec["novel_rows_plain"], rec
    assert [(r["novel_rows"], r["novel_rows_plain"]) for r in runs[0]] \
        == [(r["novel_rows"], r["novel_rows_plain"]) for r in runs[1]]
    # levels 1-6 run the per-chunk step under the compact gate on either
    # pipeline; level 7 (13,164 candidates for 5,673 states in 98,304
    # lanes) is the whole-level program's where the pipeline has one
    assert all(r["novel_rows"] == r["novel_rows_plain"] == 0
               for r in runs[0][:6])
    last = runs[0][6]
    if pipeline == "legacy":
        assert last["novel_rows"] == last["novel_rows_plain"] == 0
    else:
        assert last["novel_rows_plain"] == last["dedup_lanes"]
        assert last["novel_rows"] % 256 == 0
        assert 0 < last["novel_rows"] < 0.25 * last["novel_rows_plain"], last


@pytest.mark.perf
def test_sharded_device_launches_per_level(tmp_path):
    """The O(1)-launches/level/shard contract, span-tracer-verified:
    every level — including MULTI-CHUNK levels — dispatches at most 2
    collective-bearing programs per shard (one steady-state; two only
    on the exact-bound overflow re-dispatch), where the per-chunk path
    dispatches one per chunk.  chunk_size 128 forces several levels of
    FRL(3,3,2) through multiple chunks, so the test proves the
    while_loop really covers the chunk loop AND the exchange."""
    m = frl.make_model(3, 3, 2)
    kw = dict(min_bucket=64, compact_gate=32, chunk_size=128,
              store_trace=False)
    run = RunContext(str(tmp_path / "dev"))
    res = check_sharded(m, pipeline="device", run=run, **kw)
    run.deactivate()
    assert res.ok and res.total == 3375
    assert res.stats["device"]["levels"] > 0
    assert res.stats["device"]["fallback"] is None
    for lvl in res.stats["levels"]:
        assert lvl["shard_launches"] <= 2, lvl
    with open(os.path.join(run.dir, "spans.jsonl")) as fh:
        spans = [json.loads(line) for line in fh]
    lv = [s for s in spans
          if s.get("span") == "exchange-level" and s.get("ph") != "B"]
    assert lv, "no exchange-level spans recorded"
    assert all(s["launches"] <= 2 for s in lv)
    # the multi-chunk proof: at least one single-dispatch span covered
    # more than one serial chunk
    assert any(s.get("chunks", 1) > 1 for s in lv), \
        [s.get("chunks") for s in lv]
    # the per-chunk oracle run shows O(chunks) launches on the same
    # config (and pins bit-identity at this chunking)
    r_leg = check_sharded(m, pipeline="legacy", **kw)
    assert r_leg.levels == res.levels and r_leg.total == res.total


@pytest.mark.slow
def test_sharded_device_ln_overflow_redispatch(monkeypatch):
    """A level-new-set overflow costs exactly one exact-bound
    re-dispatch (<=2 launches/level/shard even then) and stays
    bit-identical: shrink the shared LN ladder so every multi-state
    level overflows."""
    from kafka_specification_tpu.ops import devlevel

    m = kip320.make_model(Config(2, 2, 1, 1))
    ref = check_sharded(m, pipeline="legacy", **KW)
    monkeypatch.setattr(devlevel, "level_new_capacity",
                        lambda T, hw, worst: 8)
    res = check_sharded(m, pipeline="device", stats_path=os.devnull, **KW)
    assert res.stats["device"]["levels"] > 0
    assert (res.total, res.levels) == (ref.total, ref.levels)
    launches = [l["shard_launches"] for l in res.stats["levels"]]
    assert any(n == 2 for n in launches), launches  # re-dispatch happened
    assert all(n <= 2 for n in launches), launches


@pytest.mark.slow
def test_sharded_device_cross_pipeline_resume(tmp_path):
    """A sharded checkpoint written under one pipeline resumes under the
    other, bit-identical on counts, levels AND the digest chain (the
    checkpoint format is pipeline-independent by construction)."""
    m = kip320.make_model(Config(2, 2, 1, 1))
    full = check_sharded(m, pipeline="legacy", **KW)
    chains = {}
    for first, second in (("device", "legacy"), ("legacy", "device")):
        ck = str(tmp_path / f"ck-{first}")
        cut = check_sharded(m, pipeline=first, checkpoint_dir=ck,
                            checkpoint_every=1, max_depth=6, **KW)
        assert cut.diameter == 6
        resumed = check_sharded(m, pipeline=second, checkpoint_dir=ck,
                                checkpoint_every=1, **KW)
        assert resumed.total == full.total
        assert resumed.levels == full.levels
        with np.load(os.path.join(ck, "sharded_checkpoint.npz")) as z:
            chains[(first, second)] = np.array(z["digest_chain"])
    # the two resume orders sealed the identical chain
    a, b = chains.values()
    assert np.array_equal(a, b)


def test_sharded_device_elastic_4_to_2(tmp_path, monkeypatch):
    """Elastic reshard UNDER the device pipeline: a 4-shard device-run
    checkpoint resumed on 2 shards (still --pipeline device) re-buckets
    ownership and completes bit-identical to the oracle."""
    from kafka_specification_tpu.resilience.faults import InjectedCrash

    model = frl.make_model(2, 2, 2)
    kw = dict(min_bucket=8, compact_gate=8)
    golden = check_sharded(model, mesh=_mesh(4), pipeline="legacy", **kw)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check_sharded(model, mesh=_mesh(4), pipeline="device",
                      checkpoint_dir=ck, **kw)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(model, mesh=_mesh(2), pipeline="device",
                            checkpoint_dir=ck, **kw)
    assert resumed.ok and resumed.total == 49
    assert _verdict(resumed) == _verdict(golden)


def test_sharded_device_fallback_non_device_backend():
    """The degradation ladder: the device-hash backend (no whole-level
    program) records the sticky fallback reason NAMING the backend and
    the per-chunk path serves the run — results identical to the
    oracle."""
    m = frl.make_model(3, 4, 1)
    ref = check_sharded(m, pipeline="legacy", min_bucket=64,
                        visited_backend="device-hash")
    res = check_sharded(m, pipeline="device", min_bucket=64,
                        visited_backend="device-hash")
    assert res.total == ref.total == 125
    assert res.stats["device"]["levels"] == 0
    assert "device-hash" in res.stats["device"]["fallback"]


@pytest.mark.device_host
def test_sharded_device_host_backend_bit_identity():
    """`--sharded --pipeline device` on the HOST backend (the deferred
    per-shard probe): each shard's level runs as ONE dispatched program
    with NO visited shards on device, and each owner shard's FpSet
    takes one batched insert per level — bit-identical to the per-chunk
    sharded oracle on the violating workload (counts, levels,
    first-violation rule, trace VALUES), device path proven engaged,
    probe attribution recorded."""
    ref = check_sharded(_mk_violating(), pipeline="legacy",
                        visited_backend="host", **KW)
    res = check_sharded(_mk_violating(), pipeline="device",
                        visited_backend="host",
                        stats_path=os.devnull, **KW)
    assert res.stats["device"]["levels"] > 0
    assert res.stats["device"]["fallback"] is None
    assert _verdict(res) == _verdict(ref)
    assert res.violation.trace == ref.violation.trace
    assert res.violation.depth == 8 and \
        res.violation.invariant == "WeakIsr"
    assert any(
        lvl.get("host_probe_ms") is not None
        for lvl in res.stats.get("levels", [])
    )


@pytest.mark.slow
@pytest.mark.device_host
def test_sharded_device_host_backend_clean_model():
    """Deferred per-shard probe on a passing workload (multi-chunk
    levels): counts/levels equal the per-chunk sharded host oracle."""
    m = kip320.make_model(Config(2, 2, 1, 1))
    ref = check_sharded(m, pipeline="legacy", visited_backend="host",
                        **KW)
    res = check_sharded(m, pipeline="device", visited_backend="host",
                        **KW)
    assert res.stats["device"]["levels"] > 0
    assert (res.total, res.levels) == (ref.total, ref.levels) == \
        (277, ref.levels)


@pytest.mark.fault
def test_sharded_device_compile_failure_degrades(monkeypatch):
    """Injected compile-OOM on the level program degrades the run to the
    per-chunk ladder (sticky, reason recorded) with identical results."""
    m = frl.make_model(2, 2, 2)
    kw = dict(min_bucket=8, compact_gate=8)
    ref = check_sharded(m, pipeline="legacy", **kw)
    monkeypatch.setenv("KSPEC_FAULT", "compile_oom")
    res = check_sharded(m, pipeline="device", **kw)
    assert res.total == ref.total and res.levels == ref.levels
    assert res.stats["device"]["levels"] == 0
    assert res.stats["device"]["fallback"] is not None


def test_sharded_unknown_pipeline_rejected():
    """The sharded engine no longer silently ignores --pipeline: a typo
    is rejected loudly naming the valid set (registry contract)."""
    with pytest.raises(ValueError, match="unknown pipeline"):
        check_sharded(frl.make_model(2, 2, 1), pipeline="devcie")


def test_mesh_layouts_are_explicit_and_recorded():
    """The explicit mesh-axis layouts (SNIPPETS.md sharding-rule
    pattern): the named PartitionSpecs are what they claim, committed
    device arrays actually carry them, and the run stats record them."""
    from kafka_specification_tpu.parallel.multihost import put_global

    mesh = _mesh(8)
    L = mesh_layouts(mesh)
    assert L["frontier"].spec == P("d", None)
    assert L["fpset"].spec == P("d", None)
    assert L["fvalid"].spec == P("d")
    assert L["pershard"].spec == P("d")
    assert L["exchange"].spec == P("d", None)
    # a placed per-shard table really carries the named layout
    arr = put_global(np.zeros((8, 64), np.uint32), L["fpset"])
    assert arr.sharding.spec == L["fpset"].spec
    # ... and the engine records the layout map in its stats
    res = check_sharded(frl.make_model(2, 2, 1), min_bucket=32)
    assert res.stats["mesh_layouts"] == {
        k: str(v.spec) for k, v in L.items()
    }


def test_registry_sharded_engine_matrix():
    """Satellite: the per-engine support matrix is the single queryable
    source for which pipelines each engine serves and why a combination
    degrades (jax-free registry)."""
    from kafka_specification_tpu.pipeline_registry import (
        ENGINES,
        engine_support,
        list_pipelines,
    )

    assert ENGINES == ("single-device", "sharded")
    assert engine_support("device", "sharded")["supported"] is True
    assert "level program" in engine_support("device", "sharded")["detail"]
    assert engine_support("fused", "sharded")["supported"] is False
    assert engine_support("legacy", "sharded")["supported"] is True
    with pytest.raises(ValueError, match="unknown engine"):
        engine_support("device", "gpu-cluster")
    for e in list_pipelines():
        assert set(e["engines"]) == set(ENGINES)
        for cell in e["engines"].values():
            assert isinstance(cell["supported"], bool) and cell["detail"]
