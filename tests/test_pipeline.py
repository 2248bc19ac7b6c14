"""Level-pipeline parity: fused successor mega-kernels vs the legacy
per-action path (engine/pipeline.py).

The fused pipeline's contract is BIT-IDENTITY with the legacy path —
same level counts, duplicate accounting, first-violation rule, and trace
values — plus the perf contract the span tracer can observe: at most 2
successor launches per chunk (one guard-matrix program + one
update-skeleton program) where the legacy path dispatches one
successor-kernel pass per action.

Tiny configs + compact_gate=32 push the fused path into play at
test-sized buckets (the production gate of 4096 would leave these
frontiers on the shared full-lattice path and test nothing).

Tier budget: the violating TruncateToHW case (richest assertions: trace
values) plus the perf smokes and units run in tier-1; the rest of the
model matrix, the extra backends and the cross-pipeline resume ride the
`slow` tier (they re-run the same parity predicate on more models).
Models are memoized per module — the two pipelines SHARE one Model (and
hence one step cache), exactly like a CLI pipeline switch on a warm
model; key tags keep their programs separate.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest
from conftest import needs_reference

from kafka_specification_tpu.engine import check, prepare
from kafka_specification_tpu.engine.pipeline import (
    PooledWidths,
    resolve_pipeline,
)
from kafka_specification_tpu.models import async_isr, kip320, variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs.runctx import RunContext

TINY = Config(2, 2, 1, 1)

# fused engages at bucket >= compact_gate; 32 puts every level of these
# tiny models on it (min_bucket 32 -> buckets 32..256)
KW = dict(min_bucket=32, chunk_size=256, compact_gate=32,
          store_trace=True, stats_path=os.devnull)

_MODELS: dict = {}


def _model(module):
    """One shared Model per module (jit tracing is the dominant test
    cost; the pipelines' step-cache keys are tagged, so sharing is the
    same contract a CLI `--pipeline` switch on a warm model gets)."""
    if module not in _MODELS:
        if module == "Kip320":
            _MODELS[module] = kip320.make_model(TINY)
        elif module == "AsyncIsr":
            _MODELS[module] = async_isr.make_model(
                async_isr.AsyncIsrConfig(2, 2, 2)
            )
        else:
            _MODELS[module] = variants.make_model(
                module, TINY, invariants=("TypeOk", "WeakIsr")
            )
    return _MODELS[module]


def _assert_parity(module, pipeline="fused", **extra_kw):
    kw = {**KW, **extra_kw}
    m = _model(module)
    r_leg = check(m, pipeline="legacy", **kw)
    r_fus = check(m, pipeline=pipeline, **kw)
    assert r_fus.stats["pipeline"] == pipeline
    assert r_fus.stats["pipeline_fallback"] is False
    if pipeline == "device" and kw.get("visited_backend", "device") in \
            ("device", "host"):
        # the device path must actually ENGAGE (a silent fused
        # delegation would vacuously pass every parity assertion) —
        # on BOTH native backends: the sorted device set and the
        # deferred-probe host FpSet
        assert r_fus.stats["device"]["levels"] > 0, r_fus.stats["device"]
    assert r_leg.levels == r_fus.levels
    assert r_leg.total == r_fus.total
    for a, b in zip(r_leg.stats["levels"], r_fus.stats["levels"]):
        assert a["new"] == b["new"]
        assert a["duplicates"] == b["duplicates"]
        assert a["enabled_candidates"] == b["enabled_candidates"]
        assert a["action_enablement"] == b["action_enablement"]
    assert (r_leg.violation is None) == (r_fus.violation is None)
    if r_leg.violation is not None and kw.get("store_trace"):
        assert r_leg.violation.invariant == r_fus.violation.invariant
        assert r_leg.violation.depth == r_fus.violation.depth
        t_leg = [(a, repr(s)) for a, s in r_leg.violation.trace]
        t_fus = [(a, repr(s)) for a, s in r_fus.violation.trace]
        assert t_leg == t_fus  # trace VALUES, transition for transition
    return r_leg, r_fus


def test_fused_vs_legacy_bit_identity_violating_model():
    """Tier-1 anchor case: TruncateToHW violates WeakIsr at depth 8
    (tests/test_variants.py's pinned answer) — counts, per-level
    duplicate accounting, the per-action enablement histogram, the
    first-violation verdict, and the trace VALUES all bit-identical
    between the two pipelines."""
    r_leg, _ = _assert_parity("KafkaTruncateToHighWatermark")
    assert r_leg.violation is not None  # the case actually violates


def test_fused_vs_legacy_async_isr_under_constraint_with_trace():
    """AsyncIsr at 3 brokers under an explicit CONSTRAINT
    (helpers.async_isr_under_constraint: a fused chunk's segments have
    holes inside their enabled prefixes and hand them to the sorted dedup
    unsqueezed), cut at depth 9, trace store on: per-level counts as
    `_assert_parity` holds them (the programs are warm by then), and
    every level's rows, parents and action ids equal in discovery
    order."""
    from helpers import async_isr_under_constraint

    _MODELS["AsyncIsr3Constraint"] = async_isr_under_constraint()
    bufs = {}
    for pipeline in ("legacy", "fused"):
        bufs[pipeline] = []
        res = check(_MODELS["AsyncIsr3Constraint"], pipeline=pipeline,
                    max_depth=9, collect_trace=bufs[pipeline], **KW)
        assert res.stats["pipeline"] == pipeline
        assert res.stats["pipeline_fallback"] is False
        assert res.levels == [1, 5, 16, 42, 92, 171, 282, 414, 535, 614]
    assert len(bufs["legacy"]) == len(bufs["fused"]) == 10
    for depth, (leg, fus) in enumerate(zip(bufs["legacy"], bufs["fused"])):
        for what, a, b in zip(("rows", "parents", "action ids"), leg, fus):
            if a is None or b is None:
                assert a is b, (depth, what)
                continue
            np.testing.assert_array_equal(
                np.asarray(a, np.int64), np.asarray(b, np.int64),
                err_msg=f"level {depth} {what}")
    _assert_parity("AsyncIsr3Constraint", max_depth=9)


@pytest.mark.slow
@pytest.mark.parametrize("module", ["Kip101", "Kip320", "AsyncIsr"])
def test_fused_vs_legacy_bit_identity_matrix(module):
    """The rest of the model matrix (passing runs, constraint pruning
    on AsyncIsr) — same parity predicate."""
    _assert_parity(module)


def test_device_vs_legacy_bit_identity_violating_model():
    """Tier-1 anchor for the device-resident pipeline: the violating
    TruncateToHW case (richest assertions: trace VALUES) run as whole-
    level device programs is bit-identical to the legacy oracle —
    counts, duplicate accounting, enablement histograms, the first-
    violation verdict and the trace, with the device path proven
    engaged."""
    r_leg, _ = _assert_parity("KafkaTruncateToHighWatermark",
                              pipeline="device")
    assert r_leg.violation is not None


@pytest.mark.slow
@pytest.mark.parametrize("module", ["Kip101", "Kip320", "AsyncIsr"])
def test_device_vs_legacy_bit_identity_matrix(module):
    """Device-pipeline parity over the rest of the model matrix
    (passing runs, constraint pruning on AsyncIsr)."""
    _assert_parity(module, pipeline="device")


def test_device_pipeline_ungated_tail_chunk():
    """A trailing partial chunk BELOW the compact gate stays on the
    per-chunk ladder (legacy full-lattice candidate order) while the
    gated prefix runs device-resident — the split must be bit-identical
    and must slice the device buffer to the handled prefix (regression:
    padding the full frontier into a prefix-sized buffer raised).
    min_bucket 16 < gate 32 makes every level's remainder chunk
    un-gated."""
    kw = {**KW, "min_bucket": 16, "chunk_size": 32}
    m = _model("KafkaTruncateToHighWatermark")
    r_leg = check(m, pipeline="legacy", **kw)
    r_dev = check(m, pipeline="device", **kw)
    assert r_dev.stats["device"]["levels"] > 0
    assert r_dev.stats["device"]["fallback"] is None
    assert r_leg.levels == r_dev.levels
    assert r_leg.total == r_dev.total
    for a, b in zip(r_leg.stats["levels"], r_dev.stats["levels"]):
        assert a["duplicates"] == b["duplicates"]
        assert a["action_enablement"] == b["action_enablement"]
    t_leg = [(a, repr(s)) for a, s in r_leg.violation.trace]
    t_dev = [(a, repr(s)) for a, s in r_dev.violation.trace]
    assert t_leg == t_dev


def test_device_pipeline_hash_backend_falls_back():
    """The degradation ladder's first rung: the device-hash backend has
    no whole-level program (the table mutates in place per probe), so
    --pipeline device runs the fused per-chunk path — same results,
    zero device levels, and the reason recorded NAMING the backend
    (stats['device']['fallback'], from the registry's per-backend
    matrix)."""
    m = _model("Kip101")
    r_dev = check(m, pipeline="device", visited_backend="device-hash",
                  **KW)
    assert r_dev.stats["device"]["levels"] == 0
    assert r_dev.stats["device"]["fallback"] is not None
    assert "device-hash" in r_dev.stats["device"]["fallback"]
    r_ref = check(m, pipeline="fused", visited_backend="device-hash",
                  **KW)
    assert r_dev.levels == r_ref.levels
    assert r_dev.total == r_ref.total


@pytest.mark.device_host
def test_device_host_backend_bit_identity_violating_model():
    """Tier-1 anchor for the DEFERRED-PROBE host backend (the tentpole
    of the host-backend device path): the violating TruncateToHW case
    run as whole-level device programs with intra-level dedup on device
    and ONE batched C-arena FpSet probe per level is bit-identical to
    the legacy per-chunk oracle — counts, duplicate accounting,
    enablement histograms, the first-violation verdict and the trace
    VALUES, with the device path proven engaged."""
    r_leg, r_dev = _assert_parity(
        "KafkaTruncateToHighWatermark", pipeline="device",
        visited_backend="host",
    )
    assert r_leg.violation is not None
    # the probe attribution rides the in-memory level records
    assert any(
        lvl.get("host_probe_ms") is not None
        for lvl in r_dev.stats["levels"]
    )


@pytest.mark.slow
@pytest.mark.device_host
@pytest.mark.parametrize("module", ["Kip101", "Kip320", "AsyncIsr"])
def test_device_host_backend_bit_identity_matrix(module):
    """Deferred-probe parity over the rest of the model matrix (passing
    runs, constraint pruning on AsyncIsr)."""
    _assert_parity(module, pipeline="device", visited_backend="host")


@pytest.mark.device_host
def test_device_host_backend_ungated_tail_chunk():
    """Host-backend twin of the ungated-tail case: a sub-gate trailing
    partial chunk stays on the fused per-chunk ladder (its host FpSet
    insert runs per chunk, AFTER the level's batched probe committed)
    while the gated prefix runs device-resident — the split must be
    bit-identical, which pins the probe/tail commit ordering."""
    kw = {**KW, "min_bucket": 16, "chunk_size": 32,
          "visited_backend": "host"}
    m = _model("KafkaTruncateToHighWatermark")
    r_leg = check(m, pipeline="legacy", **kw)
    r_dev = check(m, pipeline="device", **kw)
    assert r_dev.stats["device"]["levels"] > 0
    assert r_dev.stats["device"]["fallback"] is None
    assert r_leg.levels == r_dev.levels
    assert r_leg.total == r_dev.total
    for a, b in zip(r_leg.stats["levels"], r_dev.stats["levels"]):
        assert a["duplicates"] == b["duplicates"]
        assert a["action_enablement"] == b["action_enablement"]
    t_leg = [(a, repr(s)) for a, s in r_leg.violation.trace]
    t_dev = [(a, repr(s)) for a, s in r_dev.violation.trace]
    assert t_leg == t_dev


@pytest.mark.device_host
def test_device_host_backend_disk_tier_bit_identity(tmp_path):
    """Disk tier (forced tiny budget, real spills + batched sorted run
    probes) under the device pipeline: bit-identical to legacy on the
    same store — the deferred probe makes the disk tier FASTER, never
    excluded (one sorted batch probe per run per level)."""
    kw = {**KW, "store_trace": False, "store": "disk",
          "mem_budget": 4096}
    m = _model("KafkaTruncateToHighWatermark")
    r_leg = check(m, pipeline="legacy",
                  spill_dir=str(tmp_path / "leg"), **kw)
    r_dev = check(m, pipeline="device",
                  spill_dir=str(tmp_path / "dev"), **kw)
    assert r_dev.stats["device"]["levels"] > 0
    assert r_dev.stats["device"]["fallback"] is None
    assert r_dev.stats["spill"]["spills"] > 0  # the tier really spilled
    assert r_leg.levels == r_dev.levels
    assert r_leg.total == r_dev.total
    assert (r_leg.violation is None) == (r_dev.violation is None)
    assert r_dev.violation.depth == r_leg.violation.depth
    # traces reconstruct from the on-disk parent log under BOTH
    t_leg = [(a, repr(s)) for a, s in r_leg.violation.trace]
    t_dev = [(a, repr(s)) for a, s in r_dev.violation.trace]
    assert t_leg == t_dev
    # ... and with SUB-GATE TAIL chunks on the spilled frontier (the
    # tail runs per-chunk AFTER the device span — from the already-
    # materialized rows, at the serial offsets, without re-reading the
    # handled prefix from disk)
    kw.update(min_bucket=16, chunk_size=32)
    r_leg2 = check(m, pipeline="legacy",
                   spill_dir=str(tmp_path / "leg2"), **kw)
    r_dev2 = check(m, pipeline="device",
                   spill_dir=str(tmp_path / "dev2"), **kw)
    assert r_dev2.stats["device"]["levels"] > 0
    assert r_leg2.levels == r_dev2.levels
    assert r_leg2.total == r_dev2.total
    assert r_dev2.violation.depth == r_leg2.violation.depth


@pytest.mark.slow
@pytest.mark.device_host
def test_resume_cross_pipeline_host_backend_chain_equality(tmp_path):
    """Cross-pipeline checkpoint resume on the HOST backend, both
    orders, with digest-chain equality: a checkpoint written under the
    deferred-probe device path resumes bit-identical under legacy and
    vice versa, and both orders seal the IDENTICAL digest chain (the
    PR 12 matrix pinned this for the device backend only; slow tier
    like its device-backend predecessor test_resume_cross_pipeline)."""
    import numpy as np

    from kafka_specification_tpu.resilience.checkpoints import (
        verify_file,
    )

    kw = {**KW, "store_trace": False, "visited_backend": "host"}
    ref = check(_model("Kip101"), pipeline="fused", **kw)
    chains = {}
    for first, second in (("device", "legacy"), ("legacy", "device")):
        ck = tmp_path / f"{first}-{second}"
        cut = check(
            _model("Kip101"), pipeline=first, checkpoint_dir=str(ck),
            max_depth=5, **kw,
        )
        assert cut.diameter == 5
        resumed = check(
            _model("Kip101"), pipeline=second, checkpoint_dir=str(ck),
            **kw,
        )
        assert resumed.levels == ref.levels
        assert resumed.total == ref.total
        arrays = verify_file(str(ck / "bfs_checkpoint.npz"))
        chains[(first, second)] = np.asarray(arrays["digest_chain"])
    a, b = chains.values()
    assert np.array_equal(a, b)


@pytest.mark.device_host
def test_seed_composed_with_device_pipeline_host_backend():
    """check(seed=) (the PR 14 state-cache delta seeding) composed with
    --pipeline device on the host backend: counts/levels/verdicts
    bit-identical to a cold seeded legacy run, with the device path
    proven engaged past the seed boundary."""
    from kafka_specification_tpu.resilience.integrity import (
        LevelDigestChain,
        fingerprint_rows,
    )

    m = _model("Kip101")
    buf: list = []
    kw = {k: v for k, v in KW.items() if k != "store_trace"}
    bounded = check(m, max_depth=3, store_trace=True, collect_trace=buf,
                    **kw)
    assert bounded.violation is None and bounded.diameter == 3
    rows = [t[0] for t in buf]
    chain = LevelDigestChain()
    fps_all = []
    for d in range(len(bounded.levels)):
        fps = fingerprint_rows(rows[d], m.spec.exact64)
        chain.fold(fps)
        chain.seal(d, bounded.levels[d])
        fps_all.append(fps)
    import numpy as np

    seed = {
        "visited_fps": np.sort(np.concatenate(fps_all)),
        "frontier": rows[-1],
        "levels": list(bounded.levels),
        "total": bounded.total,
        "depth": len(bounded.levels) - 1,
        "digest_chain": chain.to_array(),
    }
    kw = {**kw, "store_trace": False, "visited_backend": "host"}
    cold = check(m, pipeline="legacy", **kw)
    seeded = check(m, pipeline="device", seed=dict(seed), **kw)
    assert seeded.stats["device"]["levels"] > 0
    assert seeded.stats["device"]["fallback"] is None
    assert seeded.stats["seeded_from_depth"] == 3
    assert seeded.levels == cold.levels
    assert seeded.total == cold.total
    assert (seeded.violation is None) == (cold.violation is None)


@pytest.mark.perf
@pytest.mark.device_host
def test_device_host_backend_one_probe_per_level(tmp_path):
    """The tentpole's sync contract, span-proven: on the host backend
    the device pipeline makes exactly ONE batched host-probe call per
    device-resident level (host syncs O(1)/level, vs one FpSet insert
    per chunk on the fused path) and dispatches <=2 successor programs
    per level — including MULTI-CHUNK levels (chunk_size 32)."""
    m = _model("Kip101")
    run = RunContext(str(tmp_path / "devhost"))
    kw = {k: v for k, v in KW.items() if k != "stats_path"}
    kw.update(chunk_size=32, visited_backend="host")
    res = check(m, pipeline="device", run=run, **kw)
    run.deactivate()
    assert res.stats["device"]["levels"] > 0
    assert res.stats["device"]["fallback"] is None
    for lvl in res.stats["levels"]:
        assert lvl["successor_launches"] <= 2, lvl
    with open(os.path.join(run.dir, "spans.jsonl")) as fh:
        spans = [json.loads(line) for line in fh]
    dev = [s for s in spans
           if s.get("span") == "step" and s.get("ph") != "B"
           and s.get("pipeline") == "device"]
    assert dev, "no device-level step spans recorded"
    assert all(s["launches"] <= 2 for s in dev)
    assert any(s.get("chunks", 1) > 1 for s in dev)
    probes = [s for s in spans
              if s.get("span") == "host-probe" and s.get("ph") != "B"]
    # exactly one batched probe per device-resident level
    assert len(probes) == res.stats["device"]["levels"]
    assert all(p.get("batched") == "level" for p in probes)
    # bit-identity cross-check at this chunking
    r_leg = check(m, pipeline="legacy", **kw)
    assert r_leg.levels == res.levels
    assert r_leg.total == res.total


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["host", "device-hash"])
def test_fused_vs_legacy_backends(backend):
    """Same parity on the non-default visited backends (the sorted
    device set is the default exercised above)."""
    _assert_parity("Kip101", visited_backend=backend)


@pytest.mark.slow
@needs_reference
def test_fused_vs_legacy_emitted_kernels():
    """The same parity holds on the mechanically emitted kernels (the
    CLI default path when the reference corpus is present)."""
    from kafka_specification_tpu.models.emitted import make_emitted_model

    r = {}
    for pipe in ("legacy", "fused"):
        m = make_emitted_model("Kip101", TINY,
                               invariants=("TypeOk", "WeakIsr"))
        r[pipe] = check(m, pipeline=pipe, **KW)
    assert r["legacy"].levels == r["fused"].levels
    assert r["legacy"].total == r["fused"].total
    for a, b in zip(r["legacy"].stats["levels"],
                    r["fused"].stats["levels"]):
        assert a["duplicates"] == b["duplicates"]


@pytest.mark.slow
def test_resume_cross_pipeline(tmp_path):
    """A checkpoint taken under one pipeline resumes bit-identical under
    the other — checkpoints carry no pipeline-specific state, which is
    what makes the CLI default switch safe for in-flight runs."""
    kw = {**KW, "store_trace": False}
    ref = check(_model("Kip101"), pipeline="fused", **kw)
    for first, second in (("legacy", "fused"), ("fused", "legacy"),
                          ("device", "legacy"), ("fused", "device")):
        ckpt = tmp_path / f"{first}-{second}"
        cut = check(
            _model("Kip101"), pipeline=first, checkpoint_dir=str(ckpt),
            max_depth=5, **kw,
        )
        assert cut.diameter == 5
        resumed = check(
            _model("Kip101"), pipeline=second, checkpoint_dir=str(ckpt),
            **kw,
        )
        assert resumed.levels == ref.levels
        assert resumed.total == ref.total


@pytest.mark.perf
def test_fused_two_launches_per_chunk(tmp_path):
    """The launch-count contract, asserted via the span tracer: every
    fused chunk dispatches exactly 2 successor programs (guard matrix +
    update skeleton) where the legacy path runs one successor-kernel
    pass per action.  Single-chunk levels here, so the per-level count
    equals the per-chunk count."""
    m = _model("KafkaTruncateToHighWatermark")
    n_actions = len(m.actions)

    def check_counts(pipe, pred):
        run = RunContext(str(tmp_path / pipe))
        res = check(m, pipeline=pipe, run=run,
                    **{k: v for k, v in KW.items() if k != "stats_path"})
        run.deactivate()
        assert res.stats["pipeline_fallback"] is False
        for lvl in res.stats["levels"]:
            assert pred(lvl["launches_per_chunk_max"]), (pipe, lvl)
        with open(os.path.join(run.dir, "spans.jsonl")) as fh:
            spans = [json.loads(line) for line in fh]
        steps = [s for s in spans
                 if s.get("span") == "step" and s.get("ph") != "B"]
        assert steps, "no step spans recorded"
        assert all(pred(s["launches"]) for s in steps), pipe

    # fused: EXACTLY 2 — exact pre-dispatch counts mean no retry can
    # ever re-dispatch.  legacy: one pass per action per dispatch, and
    # overflow retries re-dispatch the whole per-action step (a multiple
    # of n_actions; at these tiny buckets the uniform buffers overflow
    # and escalate, which is exactly the retry cost fused eliminates)
    check_counts("fused", lambda n: n == 2)
    check_counts("legacy",
                 lambda n: n >= n_actions and n % n_actions == 0)
    # the bit-identity case above already pins fused == legacy results;
    # this test is ONLY the launch-count contract


@pytest.mark.perf
def test_device_two_launches_per_level(tmp_path):
    """The device pipeline's launch contract, span-tracer-verified: a
    whole level — including MULTI-CHUNK levels — dispatches at most 2
    successor programs (one steady-state; two only when a segment-width
    overflow forces the exact-width re-dispatch).  chunk_size 32 forces
    several levels of this model through multiple chunks, so the test
    proves the while_loop really covers the chunk loop (a per-chunk
    dispatcher would show 2 x chunks here, like fused does)."""
    m = _model("Kip101")
    run = RunContext(str(tmp_path / "dev"))
    kw = {k: v for k, v in KW.items() if k != "stats_path"}
    kw["chunk_size"] = 32
    res = check(m, pipeline="device", run=run, **kw)
    run.deactivate()
    assert res.stats["device"]["levels"] > 0
    assert res.stats["device"]["fallback"] is None
    for lvl in res.stats["levels"]:
        assert lvl["successor_launches"] <= 2, lvl
    with open(os.path.join(run.dir, "spans.jsonl")) as fh:
        spans = [json.loads(line) for line in fh]
    steps = [s for s in spans
             if s.get("span") == "step" and s.get("ph") != "B"]
    dev = [s for s in steps if s.get("pipeline") == "device"]
    assert dev, "no device-level step spans recorded"
    assert all(s["launches"] <= 2 for s in dev)
    # the multi-chunk proof: at least one single-dispatch span covered
    # more than one serial chunk
    assert any(s.get("chunks", 1) > 1 for s in dev), \
        [s.get("chunks") for s in dev]
    # same run, bit-identical to the oracle (cheap cross-check at this
    # chunking — the anchor test covers the violating case)
    r_leg = check(m, pipeline="legacy", **kw)
    assert r_leg.levels == res.levels
    assert r_leg.total == res.total


@pytest.mark.slow
def test_device_rewarm_replays_level_keys(tmp_path):
    """PreparedKernels.rewarm re-compiles DEVICE level-program keys at a
    new visited-capacity fixed point (the serving post-growth warm
    contract covers the 'dvl' tag like 'step'/'fsc')."""
    model = variants.make_model("Kip101", TINY,
                                invariants=("TypeOk", "WeakIsr"))
    pk = prepare(model)
    kw = {**KW, "store_trace": False}
    r = check(model, pipeline="device", prepared=pk,
              visited_backend="device", **kw)
    assert r.stats["device"]["levels"] > 0
    pk.note_result(r)
    pk.capacity_hint = int(r.stats["visited_capacity"]) * 2
    pk._hint_is_capacity = True
    assert pk.rewarm() > 0
    from kafka_specification_tpu.engine.pipeline import key_vcap

    caps = {key_vcap(k) for k in model._step_compiled_log
            if k[0] == "dvl"}
    assert pk.capacity_hint in caps


@pytest.mark.perf
def test_warm_prepared_fused_zero_compiles(tmp_path):
    """The serving warm-path contract survives the fused default: the
    second check() over one PreparedKernels replays every fused program
    from the step cache — zero compile spans in its trace.  Needs a
    FRESH model (the shared memo would arrive pre-warmed)."""
    model = variants.make_model("Kip101", TINY,
                                invariants=("TypeOk", "WeakIsr"))
    pk = prepare(model)
    kw = {k: v for k, v in KW.items() if k != "stats_path"}
    run1 = RunContext(str(tmp_path / "cold"))
    r1 = check(model, pipeline="fused", prepared=pk, run=run1, **kw)
    run1.deactivate()
    assert r1.stats["pipeline_fallback"] is False
    pk.note_result(r1)
    run2 = RunContext(str(tmp_path / "warm"))
    check(model, pipeline="fused", prepared=pk, run=run2,
          visited_capacity_exact=pk.capacity_hint, **kw)
    run2.deactivate()

    def compiles(run):
        with open(os.path.join(run.dir, "spans.jsonl")) as fh:
            spans = [json.loads(line) for line in fh]
        return [s for s in spans if s.get("span") == "compile"]

    assert len(compiles(run1)) > 0  # cold: the fused programs compile
    assert compiles(run2) == []  # warm: every one replayed from cache


@pytest.mark.slow
def test_rewarm_replays_fused_keys(tmp_path):
    """PreparedKernels.rewarm re-compiles FUSED step-cache keys at a new
    visited-capacity fixed point (the serving daemon's post-growth warm
    contract now covers the fused default, not just legacy 'step' keys)."""
    model = variants.make_model("Kip101", TINY,
                                invariants=("TypeOk", "WeakIsr"))
    pk = prepare(model)
    kw = {**KW, "store_trace": False}
    r = check(model, pipeline="fused", prepared=pk,
              visited_backend="device", **kw)
    pk.note_result(r)
    # simulate a growth run: pretend the fixed point is one doubling up
    pk.capacity_hint = int(r.stats["visited_capacity"]) * 2
    pk._hint_is_capacity = True
    warmed = pk.rewarm()
    assert warmed > 0
    # the replayed fused keys exist at the new capacity
    from kafka_specification_tpu.engine.pipeline import key_vcap

    caps = {key_vcap(k) for k in model._step_compiled_log
            if k[0] == "fsc"}
    assert pk.capacity_hint in caps
    # and a run at the new capacity is compile-free (all replayed)
    run = RunContext(str(tmp_path / "warm"))
    check(model, pipeline="fused", prepared=pk, visited_backend="device",
          visited_capacity_exact=pk.capacity_hint,
          **{k: v for k, v in kw.items() if k != "stats_path"}, run=run)
    run.deactivate()
    with open(os.path.join(run.dir, "spans.jsonl")) as fh:
        spans = [json.loads(line) for line in fh]
    assert [s for s in spans if s.get("span") == "compile"] == []


def test_injected_compile_oom_degrades_fused_to_legacy(monkeypatch):
    """KSPEC_FAULT=compile_oom rehearses the fused failure ladder: the
    fused programs are the escalated-shape family, so the injected OOM
    fires on them and the run degrades to the legacy pipeline — same
    results, stats['pipeline_fallback'] records it."""
    monkeypatch.setenv("KSPEC_FAULT", "compile_oom")
    r_fall = check(_model("KafkaTruncateToHighWatermark"),
                   pipeline="fused", **KW)
    monkeypatch.delenv("KSPEC_FAULT")
    r_ref = check(_model("KafkaTruncateToHighWatermark"),
                  pipeline="fused", **KW)
    assert r_fall.stats["pipeline_fallback"] is True
    assert any(d["kind"] == "compile_fallback"
               for d in r_fall.stats["degradations"])
    assert r_fall.levels == r_ref.levels  # degraded run, exact results
    assert r_fall.violation.depth == r_ref.violation.depth
    # and the degraded run's chunks ran the per-action path (a multiple
    # of n_actions: overflow retries re-dispatch the whole step)
    n_actions = len(_model("KafkaTruncateToHighWatermark").actions)
    assert r_fall.stats["launches_per_chunk_max"] % n_actions == 0
    assert r_fall.stats["launches_per_chunk_max"] >= n_actions
    assert r_ref.stats["launches_per_chunk_max"] == 2


def test_injected_compile_oom_degrades_device_to_fused(monkeypatch):
    """KSPEC_FAULT=compile_oom rehearses the device failure ladder: the
    level dispatch is the escalated-shape family, so the injected OOM
    fires there and the run degrades to the fused per-chunk ladder —
    same results, stats['device']['fallback'] records why."""
    monkeypatch.setenv("KSPEC_FAULT", "compile_oom")
    r_fall = check(_model("KafkaTruncateToHighWatermark"),
                   pipeline="device", **KW)
    monkeypatch.delenv("KSPEC_FAULT")
    r_ref = check(_model("KafkaTruncateToHighWatermark"),
                  pipeline="device", **KW)
    assert r_fall.stats["device"]["levels"] == 0
    assert r_fall.stats["device"]["fallback"] is not None
    assert r_ref.stats["device"]["levels"] > 0
    assert r_fall.levels == r_ref.levels  # degraded run, exact results
    assert r_fall.violation.depth == r_ref.violation.depth


def test_pooled_widths_ladder():
    """Unit: pooled segment widths cover the exact counts, stay
    256-aligned (the fingerprint-block invariant), never exceed the
    action's full lattice width, and only grow (the monotone ladder is
    what bounds compiled width vectors and keeps warm runs replayable)."""
    m = _model("Kip101")
    pool = PooledWidths(m.actions)
    bucket = 4096
    w1 = pool.widths_for(
        bucket, np.asarray([5.0] * len(m.actions)), fp_n=1000
    )
    assert all(w >= 256 for w in w1)
    assert all(w % 256 == 0 for w in w1)
    counts = np.asarray(
        [300.0 * (i + 1) for i in range(len(m.actions))]
    )
    w2 = pool.widths_for(bucket, counts, fp_n=1000)
    assert all(w >= c for w, c in zip(w2, counts))
    assert all(b >= a for a, b in zip(w1, w2))  # monotone
    # cap: never wider than the full lattice for the action
    huge = np.asarray([1e9] * len(m.actions))
    w3 = pool.widths_for(bucket, huge, fp_n=1)
    for w, a in zip(w3, m.actions):
        assert w <= -(-bucket * a.n_choices // 256) * 256


def test_resolve_pipeline_env(monkeypatch):
    assert resolve_pipeline(None) == "fused"
    assert resolve_pipeline("legacy") == "legacy"
    assert resolve_pipeline("device") == "device"
    monkeypatch.setenv("KSPEC_PIPELINE", "legacy")
    assert resolve_pipeline(None) == "legacy"
    monkeypatch.setenv("KSPEC_PIPELINE", "device")
    assert resolve_pipeline(None) == "device"
    with pytest.raises(ValueError):
        resolve_pipeline("bogus")
    # a typo'd ENV value must be rejected just as loudly as a typo'd
    # arg (the silent-fallback class the registry exists to kill), and
    # the error must NAME the valid set
    monkeypatch.setenv("KSPEC_PIPELINE", "fusedd")
    with pytest.raises(ValueError, match="device.*fused.*legacy"):
        resolve_pipeline(None)


def test_cli_pipelines_list_is_jax_free_registry_dump(capsys):
    """`cli pipelines --list` mirrors `cli faults --list`: a pure dump
    of the jax-free registry with the launch contracts and the
    degradation ladder — and the machine-readable --json twin."""
    from kafka_specification_tpu.utils.cli import main as cli_main

    assert cli_main(["pipelines", "--json"]) == 0
    entries = json.loads(capsys.readouterr().out)
    assert [e["name"] for e in entries] == ["device", "fused", "legacy"]
    assert all("description" in e and "launches" in e for e in entries)
    assert cli_main(["pipelines"]) == 0
    out = capsys.readouterr().out
    assert "device" in out and "degrades to 'fused'" in out
    assert "bit-identity oracle" in out
    # the per-backend cells render too (which visited backends each
    # pipeline serves natively vs degrades from)
    assert "[backend host] native" in out
    assert "[backend device-hash] degrades" in out


def test_pipeline_registry_backend_matrix():
    """Satellite: the per-BACKEND support matrix is the single queryable
    source for which visited backends each pipeline serves natively,
    and the unsupported cells' details ARE the fallback reasons the
    engines stamp (backend_fallback_reason names the backend)."""
    from kafka_specification_tpu.pipeline_registry import (
        BACKENDS,
        backend_fallback_reason,
        backend_support,
        list_pipelines,
    )

    assert BACKENDS == ("device", "device-hash", "host")
    assert backend_support("device", "device")["supported"] is True
    assert backend_support("device", "host")["supported"] is True
    assert "batched" in backend_support("device", "host")["detail"]
    assert backend_support("device", "device-hash")["supported"] is False
    # fused and legacy serve every backend natively
    for name in ("fused", "legacy"):
        for be in BACKENDS:
            assert backend_support(name, be)["supported"] is True
            assert backend_fallback_reason(name, be) is None
    reason = backend_fallback_reason("device", "device-hash")
    assert reason is not None and "device-hash" in reason
    assert backend_fallback_reason("device", "host") is None
    with pytest.raises(ValueError, match="unknown visited backend"):
        backend_support("device", "redis")
    for e in list_pipelines():
        assert set(e["backends"]) == set(BACKENDS)
        for cell in e["backends"].values():
            assert isinstance(cell["supported"], bool) and cell["detail"]


def test_pipeline_registry_is_the_single_source():
    """The jax-free registry (pipeline_registry.py), the engine's
    PIPELINES tuple, and the factory agree on the name set — the CLI
    parser builds its choices from the same registry."""
    from kafka_specification_tpu.pipeline_registry import (
        PIPELINE_REGISTRY,
        list_pipelines,
        pipeline_names,
    )
    from kafka_specification_tpu.engine.pipeline import PIPELINES

    assert set(PIPELINES) == set(pipeline_names())
    assert set(PIPELINE_REGISTRY) == {"device", "fused", "legacy"}
    entries = {e["name"]: e for e in list_pipelines()}
    assert entries["fused"]["default"] is True
    assert entries["device"]["fallback"] == "fused"
    assert entries["fused"]["fallback"] == "legacy"
    assert entries["legacy"]["fallback"] is None


@pytest.mark.parametrize("pipeline,tag", [
    ("legacy", "step"), ("fused", "fsc"), ("device", "dvl"),
])
def test_warm_key_round_trips_every_capacity_key(pipeline, tag):
    """Each step-cache key layout is written once, in its builder
    (_Step.get, FusedPipeline.succ_step, DevicePipeline._level_program);
    pipeline.warm_key reads a logged key apart and gets the rebuilt key
    back from that builder.  So warming a key at ITS OWN capacity must
    give the key itself: a builder whose layout drifts from what
    warm_key takes apart (an element added, dropped or reordered) fails
    here, not as a silent cache miss in a served job's warm pass."""
    from kafka_specification_tpu.engine.pipeline import key_vcap, warm_key

    model = variants.make_model("Kip101", TINY,
                                invariants=("TypeOk", "WeakIsr"))
    pk = prepare(model)
    check(model, pipeline=pipeline, prepared=pk, visited_backend="device",
          **{**KW, "store_trace": False})
    keyed = [k for k in model._step_cache if key_vcap(k) is not None]
    assert tag in {k[0] for k in keyed}, sorted(k[0] for k in keyed)
    for key in keyed:
        assert warm_key(pk.step, model, key, key_vcap(key)) == key


# --- the level programs' high waters outlive a check call ------------------
#
# A whole-level program's first dispatch is sized from high waters that a
# cold call starts at zero; PreparedKernels keeps what a device-pipeline
# run measured (note_result) and the next check(prepared=pk) starts each
# level there, so it dispatches every level program once.  Two scenarios,
# each run once per session and asserted on by the cases below:
#
# - "natural": Kip320 at (2, 2, 2, 2) to depth 9 under the real sizing
#   policy, sorted device backend.  The level of 510 rows overflows an
#   action segment (floor 256) on a cold call: the density high water.
# - "ladder": tiny Kip101 in 32-row chunks on the host backend (dvh), the
#   level-new ladder scaled to test size (floor 8 for T, no
#   LN_SAFE_SMALL shortcut; otherwise level_new_capacity's own formula):
#   every level that grows by more than the headroom overflows the
#   level-new set on a cold call, and a seeded multi-chunk level asks for
#   a capacity under the re-dispatch's safe bound, a program only rewarm
#   can have built: the level-new high water and rewarm's second half.

_WARM: dict = {}


def _level_dispatches(run):
    """The level programs' dispatch spans of a run, in order, as
    (depth, bucket, vcap, level_new_cap, attempt, discarded)."""
    with open(os.path.join(run.dir, "spans.jsonl")) as fh:
        spans = [json.loads(line) for line in fh]
    compiles = [s for s in spans
                if s.get("span") == "compile" and s.get("ph") != "B"]
    keys = [(s["depth"], s["bucket"], s.get("vcap"), s["level_new_cap"],
             s["attempt"], bool(s.get("discarded")))
            for s in spans
            if s.get("span") == "dispatch" and s.get("ph") != "B"
            and s.get("program") in ("dvl", "dvh")]
    return keys, compiles


def _trace_digest(buf):
    """sha256 over a run's trace store (rows, parents, action ids, level
    by level, in discovery order): stricter than the digest chain, which
    is order-free."""
    import hashlib

    h = hashlib.sha256()
    for level in buf:
        for a in level:  # the pipelines differ in index dtypes, not values
            if a is not None:
                h.update(np.ascontiguousarray(a, np.int64).tobytes())
    return h.hexdigest()


def _scaled_level_new_capacity(T, ln_hw, worst):
    from kafka_specification_tpu.engine.bfs import _next_pow2
    from kafka_specification_tpu.ops import devlevel

    return min(_next_pow2(max(8, int(devlevel.LN_HEADROOM * ln_hw) + 1)),
               _next_pow2(worst))


def _warm_scenario(name, tmp_root):
    if name in _WARM:
        return _WARM[name]
    from kafka_specification_tpu.ops import devlevel

    if name == "natural":
        model = kip320.make_model(Config(2, 2, 2, 2))
        kw = {**KW, "chunk_size": 4096, "max_depth": 9,
              "visited_backend": "device"}
        patch = None
    else:
        model = variants.make_model("Kip101", TINY,
                                    invariants=("TypeOk", "WeakIsr"))
        kw = {**KW, "chunk_size": 32, "visited_backend": "host"}
        patch = _scaled_level_new_capacity
    kw.pop("stats_path")
    out: dict = {}

    def one(tag, pipeline="device", pk=None, vcap=None, **extra):
        run = RunContext(os.path.join(tmp_root, f"{name}-{tag}"))
        buf: list = []
        res = check(
            model, pipeline=pipeline, prepared=pk, run=run,
            collect_trace=buf,
            visited_capacity_exact=vcap or (pk and pk.capacity_hint),
            **{**kw, **extra},
        )
        run.deactivate()
        keys, compiles = _level_dispatches(run)
        return {"res": res, "keys": keys, "compiles": compiles,
                "digest": _trace_digest(buf)}

    with pytest.MonkeyPatch.context() as mp:
        if patch is not None:
            mp.setattr(devlevel, "level_new_capacity", patch)
        pk = prepare(model)
        out["cold"] = one("cold", pk=pk)
        pk.note_result(out["cold"]["res"])
        out["rewarmed"] = pk.rewarm()
        out["warm"] = one("warm", pk=pk)
        pk.note_result(out["warm"]["res"])  # as the daemon does after
        out["rewarmed_again"] = pk.rewarm()  # every job
        out["third"] = one("third", pk=pk)
        out["legacy"] = one("legacy", pipeline="legacy")
        if name == "ladder":
            # rewarm's second half alone: drop every level program and
            # let it build what a seeded call asks for
            cache = model._step_cache
            for key in [k for k in cache if k[0] in ("dvl", "dvh")]:
                del cache[key]
            out["rebuilt"] = pk.rewarm()
            out["fourth"] = one("fourth", pk=pk)
        else:
            # the calls below start at the capacity the cold call ended
            # at, so they find its programs where rewarm put them
            cap = pk.capacity_hint
            # (c) a seed from a shallower run, then the deeper call
            shallow_pk = prepare(model)
            out["shallow"] = one("shallow", pk=shallow_pk, vcap=cap,
                                 max_depth=8)
            shallow_pk.note_result(out["shallow"]["res"])
            out["deeper"] = one("deeper", pk=shallow_pk, vcap=cap)
            # (e) no prepared; and a prepared fed by a fused result
            out["bare"] = one("bare", vcap=cap)
            fused_pk = prepare(model)
            fused = one("fused", pipeline="fused", pk=fused_pk,
                        max_depth=3)
            fused_pk.note_result(fused["res"])
            out["fused_pk"] = fused_pk
            out["after_fused"] = one("after-fused", pk=fused_pk, vcap=cap)
    _WARM[name] = out
    return out


@pytest.fixture(scope="module")
def warm_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("warm"))


def _discarded_levels(res):
    return [r["depth"] for r in res.stats["levels"]
            if r["discarded_dispatches"]]


@pytest.mark.perf
@pytest.mark.parametrize("scenario", [
    "natural",
    pytest.param("ladder", marks=pytest.mark.device_host),
])
def test_warm_device_pass_dispatches_each_level_once(scenario, warm_root):
    """(a), and (d) on the host backend: after note_result + rewarm the
    second check(prepared=pk) discards nothing, launches each level the
    first call re-dispatched once, compiles nothing, and equals the
    first call and the legacy pipeline in levels, total and trace."""
    sc = _warm_scenario(scenario, warm_root)
    cold, warm, legacy = sc["cold"], sc["warm"], sc["legacy"]
    redone = _discarded_levels(cold["res"])
    assert redone, "the cold call never overflowed: nothing to show"
    assert cold["res"].stats["device"]["seeded"] is False
    assert warm["res"].stats["device"]["seeded"] is True
    assert warm["res"].stats["device"]["fallback"] is None
    assert _discarded_levels(warm["res"]) == []
    for rec in warm["res"].stats["levels"]:
        if rec["depth"] in redone:
            assert rec["successor_launches"] <= 1, rec
    assert not any(k[-1] for k in warm["keys"])  # no discarded span
    assert all(k[4] == 0 for k in warm["keys"])  # every attempt the first
    assert warm["compiles"] == []
    for other in (cold, legacy):
        assert warm["res"].levels == other["res"].levels
        assert warm["res"].total == other["res"].total
        assert warm["digest"] == other["digest"]
    # the records are counts, not shapes: a seeded run reports what the
    # run that seeded it did
    assert (warm["res"].stats["device"]["high_waters"]
            == cold["res"].stats["device"]["high_waters"])
    if scenario == "ladder":
        # a multi-chunk level's seeded level-new capacity is under the
        # re-dispatch's safe bound: a program rewarm had to build
        committed = {(k[0], k[3]) for k in cold["keys"] if not k[-1]}
        assert {(k[0], k[3]) for k in warm["keys"]} - committed


@pytest.mark.perf
@pytest.mark.parametrize("scenario", [
    "natural",
    pytest.param("ladder", marks=pytest.mark.device_host),
])
def test_warm_device_pass_is_a_fixed_point(scenario, warm_root):
    """(b): the third call dispatches the key sequence of the second
    (bucket, vcap, level_new_cap per level), builds nothing, and leaves
    rewarm nothing to do; the visited capacity stays where it was."""
    sc = _warm_scenario(scenario, warm_root)
    assert sc["third"]["keys"] == sc["warm"]["keys"]
    assert sc["third"]["compiles"] == []
    assert sc["rewarmed_again"] == 0
    assert sc["third"]["digest"] == sc["cold"]["digest"]
    if scenario == "ladder":
        # rewarm builds the programs a seeded call asks for (here: after
        # every level program was dropped) and the call then builds none
        # (at least one per (bucket, level_new_cap) it dispatched; the
        # padded chunk count, which no span holds, tells more apart)
        assert sc["rebuilt"] >= len({k[1:] for k in sc["warm"]["keys"]})
        assert sc["fourth"]["keys"] == sc["warm"]["keys"]
        assert sc["fourth"]["compiles"] == []
    caps = [sc[k]["res"].stats["visited_capacity"]
            for k in ("cold", "warm", "third")]
    assert caps[0] == caps[1] == caps[2]


def test_seed_from_a_shallower_run_redispatches_once(warm_root):
    """(c): a seed that is too small (fed by a max_depth 8 run) costs the
    deeper call the one re-dispatch a cold call pays at the level the
    seed never saw, and the counts are the golden's."""
    sc = _warm_scenario("natural", warm_root)
    cold, deeper = sc["cold"], sc["deeper"]
    assert _discarded_levels(sc["shallow"]["res"]) == []
    assert sc["shallow"]["res"].stats["device"]["seeded"] is False
    assert max(r["depth"] for r in sc["shallow"]["res"].stats["levels"]) \
        < min(_discarded_levels(cold["res"]))
    assert deeper["res"].stats["device"]["seeded"] is True
    assert _discarded_levels(deeper["res"]) == _discarded_levels(
        cold["res"])
    for rec in deeper["res"].stats["levels"]:
        assert rec["discarded_dispatches"] <= 1
        assert rec["successor_launches"] <= 2
    assert deeper["res"].levels == cold["res"].levels
    assert deeper["res"].total == cold["res"].total
    assert deeper["digest"] == cold["digest"]


@pytest.mark.parametrize("which", ["bare", "after_fused"])
def test_unseeded_device_call_dispatches_as_before(which, warm_root):
    """(e): a call without `prepared`, and a `prepared` fed by a
    fused-pipeline result, dispatch exactly as a cold call does."""
    sc = _warm_scenario("natural", warm_root)
    cold, got = sc["cold"], sc[which]
    assert sc["fused_pk"].level_high_waters == {}
    assert got["res"].stats["device"]["seeded"] is False
    # (its capacity is the one the cold call ended at, handed in)
    assert [k[:2] + k[3:] for k in got["keys"]] == [
        k[:2] + k[3:] for k in cold["keys"]]
    assert _discarded_levels(got["res"]) == _discarded_levels(cold["res"])
    assert got["res"].levels == cold["res"].levels
    assert got["digest"] == cold["digest"]
