"""Mesh-sharded BFS over the virtual 8-device CPU mesh: counts must equal the
single-device engine / oracle golden values, violations must be detected."""

import jax
import pytest
import numpy as np
from jax.sharding import Mesh

from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.models import async_isr
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import id_sequence, kip320, variants
from kafka_specification_tpu.models.kafka_replication import Config


def test_sharded_frl_exact_count():
    res = check_sharded(frl.make_model(3, 4, 1), min_bucket=64)
    assert res.ok
    assert res.total == 125
    assert res.diameter == 12
    assert res.stats["devices"] == 8


def test_sharded_kip320_tiny_exact_count():
    res = check_sharded(kip320.make_model(Config(2, 2, 1, 1)), min_bucket=64)
    assert res.ok
    assert res.total == 277
    assert res.diameter == 11


def test_sharded_detects_violation():
    m = variants.make_model(
        "KafkaTruncateToHighWatermark", Config(2, 2, 1, 1), ("TypeOk", "WeakIsr")
    )
    res = check_sharded(m, min_bucket=64)
    assert res.violation is not None
    assert res.violation.invariant == "WeakIsr"
    assert res.violation.depth == 8  # same depth as single-device/oracle


def test_sharded_on_mesh_subset():
    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
    res = check_sharded(frl.make_model(2, 2, 2), mesh=mesh, min_bucket=32)
    assert res.ok
    assert res.total == 49
    assert res.stats["devices"] == 4


def test_sharded_chunked_levels_exact_count():
    """chunk_size well below the peak per-shard frontier forces several
    step calls per level; counts must still be exact (cross-chunk dedup
    via the per-shard visited sets).  FRL(3,3,2) = 15^3 = 3,375 closed
    form; the 29,791 version runs as slow below."""
    res = check_sharded(
        frl.make_model(3, 3, 2), min_bucket=8, chunk_size=128, store_trace=False
    )
    assert res.ok
    assert res.total == 3375
    assert res.diameter == 9


@pytest.mark.slow
def test_sharded_chunked_levels_exact_count_29791():
    res = check_sharded(
        frl.make_model(3, 4, 2), min_bucket=8, chunk_size=128, store_trace=False
    )
    assert res.ok
    assert res.total == 29791
    assert res.diameter == 12


def test_sharded_violation_trace_is_valid_path():
    """The sharded engine reconstructs full counterexample traces across
    chunks and shards; the trace must replay through the oracle semantics
    and end in the violating state."""
    m = variants.make_model(
        "KafkaTruncateToHighWatermark", Config(2, 2, 1, 1), ("TypeOk", "WeakIsr")
    )
    res = check_sharded(m, min_bucket=8, chunk_size=8)
    v = res.violation
    assert v is not None and v.invariant == "WeakIsr" and v.depth == 8
    assert len(v.trace) == 9
    assert v.trace[0][0] == "<init>"
    # replay: every step of the trace must be a legal oracle transition
    o = variants.make_oracle(
        "KafkaTruncateToHighWatermark", Config(2, 2, 1, 1), ("TypeOk",)
    )
    actions = {a.name: a for a in o.actions}
    cur = o.init_states()[0]
    assert v.trace[0][1] == cur
    for name, nxt in v.trace[1:]:
        assert nxt in set(actions[name].successors(cur)), name
        cur = nxt


def test_sharded_checkpoint_resume(tmp_path):
    ckdir = str(tmp_path / "sck")
    m = frl.make_model(2, 2, 2)
    partial = check_sharded(m, max_depth=2, min_bucket=32, checkpoint_dir=ckdir)
    assert partial.total < 49
    resumed = check_sharded(m, min_bucket=32, checkpoint_dir=ckdir)
    assert resumed.ok
    assert resumed.total == 49


def test_sharded_checkpoint_rejects_other_model_but_resharding_mesh(tmp_path):
    """A different model/constants still refuses to resume; a different
    MESH SIZE is no longer a mismatch — it takes the elastic re-shard
    path and completes exactly (tests/test_sharded_resilience.py has the
    full elastic matrix)."""
    import pytest as _pytest

    ckdir = str(tmp_path / "sck")
    check_sharded(frl.make_model(2, 2, 2), max_depth=1, min_bucket=32, checkpoint_dir=ckdir)
    with _pytest.raises(ValueError, match="different"):
        check_sharded(frl.make_model(2, 3, 2), min_bucket=32, checkpoint_dir=ckdir)
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("d",))
    res = check_sharded(
        frl.make_model(2, 2, 2), mesh=mesh4, min_bucket=32, checkpoint_dir=ckdir
    )
    assert res.ok and res.total == 49


def test_sharded_exchange_modes_agree():
    """all_to_all (bucket-by-owner routing) and all_gather (broadcast +
    ownership filter) must produce identical exact counts; chunking forces
    multiple exchanges per level."""
    m = kip320.make_model(Config(2, 2, 1, 1))
    for exchange in ("all_to_all", "all_gather"):
        res = check_sharded(m, min_bucket=32, chunk_size=128, exchange=exchange)
        assert res.ok, exchange
        assert res.total == 277, (exchange, res.total)
        assert res.stats["exchange"] == exchange


def test_sharded_host_fpset_backend_exact_count():
    """Per-shard host FpSet spill (the >HBM mode): counts must match the
    device-resident visited sets, and the per-shard set sizes must sum to
    the distinct-state total."""
    res = check_sharded(
        frl.make_model(3, 3, 2),
        min_bucket=8,
        chunk_size=128,
        store_trace=False,
        visited_backend="host",
    )
    assert res.ok
    assert res.total == 3375
    assert sum(res.stats["host_fpset_sizes"]) == 3375


@pytest.mark.slow
def test_sharded_host_fpset_backend_exact_count_29791():
    res = check_sharded(
        frl.make_model(3, 4, 2),
        min_bucket=8,
        chunk_size=128,
        store_trace=False,
        visited_backend="host",
    )
    assert res.ok
    assert res.total == 29791
    assert sum(res.stats["host_fpset_sizes"]) == 29791


def test_sharded_host_backend_violation_trace():
    m = variants.make_model(
        "KafkaTruncateToHighWatermark", Config(2, 2, 1, 1), ("TypeOk", "WeakIsr")
    )
    res = check_sharded(m, min_bucket=8, chunk_size=8, visited_backend="host")
    assert res.violation is not None
    assert res.violation.invariant == "WeakIsr"
    assert res.violation.depth == 8
    assert len(res.violation.trace) == 9


@pytest.mark.slow  # round-5 fast-suite budget (<=300s): cheaper siblings keep the
# fast-path coverage; this full variant runs in the slow set
def test_sharded_async_isr_constraint_model():
    """AsyncIsr carries the corpus's only state CONSTRAINT
    (AsyncIsr.tla:117-119 is unguarded); the sharded engine must apply it
    identically to engine.check — 4,088 states at (3r, M2, V2)."""
    cfg = async_isr.AsyncIsrConfig(n_replicas=3, max_offset=2, max_version=2)
    res = check_sharded(
        async_isr.make_model(cfg), min_bucket=64, chunk_size=1024, store_trace=False
    )
    assert res.ok
    assert res.total == 4088
    assert res.diameter == 16


def test_sharded_deadlock_detection():
    res = check_sharded(id_sequence.make_model(3), min_bucket=32, check_deadlock=True)
    assert res.violation is not None
    assert res.violation.invariant == "Deadlock"
    assert res.violation.depth == 4
    assert [s for _, s in res.violation.trace] == [0, 1, 2, 3, 4]


@pytest.mark.slow  # the RESULTS.md flagship claim, regression-pinned
@pytest.mark.parametrize("exchange", ["all_to_all", "all_gather"])
def test_sharded_kip320_flagship_full_workload(exchange):
    """The full 737,794-state Kip320 3-broker exhaustive pass through the
    8-device mesh — the flagship workload the bench runs single-device —
    in BOTH exchange modes (bucket-by-owner all_to_all and the all_gather
    broadcast fallback), with all four invariants (VERDICT r3 item 4b)."""
    m = kip320.make_model(Config(3, 2, 2, 2))
    res = check_sharded(
        m,
        min_bucket=4096,
        chunk_size=16384,
        store_trace=False,
        exchange=exchange,
        visited_backend="device-hash",
    )
    assert res.ok, exchange
    assert res.total == 737_794, (exchange, res.total)
    assert res.diameter == 25, (exchange, res.diameter)
    assert res.stats["devices"] == 8


def test_adaptive_compact_policy_unit():
    """The shared sizing policy (engine.bfs.AdaptiveCompact): uniform
    shift until a uniform overflow, then measured widths with learned
    floors — pure host logic, no devices."""
    import numpy as np

    from kafka_specification_tpu.engine.bfs import AdaptiveCompact

    class A:  # minimal action stub
        def __init__(self, n):
            self.n_choices = n

    acts = [A(4), A(16)]
    ad = AdaptiveCompact(acts, compact_shift=2, bucket_gate=1024)
    assert ad.widths_for(512) is None  # below gate -> full path
    assert ad.widths_for(4096) == 2  # uniform until escalation
    # uniform overflow escalates using the attempt's guard densities
    nxt = ad.escalate(2, np.array([True, False]), 4096,
                      np.array([1.0, 0.01]))
    assert ad.active and isinstance(nxt, tuple) and len(nxt) == 2
    # dense action ~1.35*1.0*4096 pow2 -> 8192, clamped to 4*4096=16384 cap
    assert nxt[0] == 8192 and nxt[1] == 256
    # per-action overflow doubles the offender and floors it
    nxt2 = ad.escalate(nxt, np.array([True, False]), 4096,
                       np.array([1.0, 0.01]))
    assert nxt2[0] == 16384 == ad.floor[0] and nxt2[1] == 256
    # widths_for now reflects the floor
    assert ad.widths_for(4096)[0] == 16384


def test_adaptive_compact_wide_model_hybrid_unit():
    """Wide-model guard (KSPEC_ADAPTIVE_MAX_PIPE): above the pipeline
    cap, escalation widens only the actions whose measured need exceeds
    their uniform buffer and pins every other action at the 256-rounded
    uniform width, keeping the program's shapes close to the
    known-compiling uniform one (round-5 LLVM-OOM finding)."""
    import numpy as np

    from kafka_specification_tpu.engine.bfs import AdaptiveCompact

    class A:  # minimal action stub
        def __init__(self, n):
            self.n_choices = n

    acts = [A(4) for _ in range(3)]
    ad = AdaptiveCompact(acts, compact_shift=2, bucket_gate=1024)
    ad.max_pipe = 2  # force wide-model mode for the 3-action stub
    nxt = ad.escalate(2, np.array([True, False, False]), 4096,
                      np.array([1.0, 0.01, 0.01]))
    # dense action escalates past its uniform width (4096>>2)*4 = 4096
    assert nxt[0] == 8192
    # sparse actions: measured need (256) <= uniform width -> pinned at
    # uniform 4096 (shape adjacency over padding savings in this mode)
    assert nxt[1] == nxt[2] == 4096
    # under the cap the round-5 behavior is unchanged: sparse actions
    # shrink to their measured pow2 width
    ad2 = AdaptiveCompact(acts, compact_shift=2, bucket_gate=1024)
    nxt2 = ad2.escalate(2, np.array([True, False, False]), 4096,
                        np.array([1.0, 0.01, 0.01]))
    assert nxt2[0] == 8192 and nxt2[1] == nxt2[2] == 256


@pytest.mark.slow
@pytest.mark.parametrize("exchange", ["all_to_all", "all_gather"])
def test_sharded_adaptive_escalation_exact(exchange):
    """Round-5 verdict item 2: the sharded engine escalates to per-action
    adaptive widths (same policy object as the single-device engine) and
    stays exact.  A deliberately undersized uniform shift forces the
    uniform attempt to overflow at the first compact-eligible bucket."""
    from kafka_specification_tpu.models import kip320
    from kafka_specification_tpu.models.kafka_replication import Config

    model = kip320.make_model(Config(2, 2, 2, 2))
    res = check_sharded(
        model,
        min_bucket=8192,  # per-shard bucket 1024 -> compact active
        chunk_size=2048,
        store_trace=False,
        compact_shift=6,  # 1024>>6 = 16 rows/action-choice: overflows
        exchange=exchange,
    )
    assert res.ok and res.total == 5973
    assert res.stats["adaptive_active"] is True


@pytest.mark.slow
def test_sharded_adaptive_compile_fallback_exact(monkeypatch):
    """Sharded twin of test_engine.test_adaptive_compile_fallback_exact:
    a failing escalated step pins adaptation off and the run completes
    exactly on the uniform path.  Escalated state is injected via
    widths_for (same rationale as the engine test)."""
    from kafka_specification_tpu.engine import bfs as bfs_mod
    from kafka_specification_tpu.parallel import sharded as sh_mod

    orig_make = sh_mod._make_sharded_step
    orig_wf = bfs_mod.AdaptiveCompact.widths_for

    def tuple_widths(self, bucket):
        if self.on:  # pre-fallback: pretend a prior chunk escalated
            return tuple(256 for _ in self.actions)
        return orig_wf(self, bucket)

    def failing_make(model, mesh, bucket, vcap, compact=None, **kw):
        if isinstance(compact, (list, tuple)):
            raise RuntimeError("synthetic XLA compile failure")
        return orig_make(model, mesh, bucket, vcap, compact=compact, **kw)

    monkeypatch.setattr(bfs_mod.AdaptiveCompact, "widths_for", tuple_widths)
    monkeypatch.setattr(sh_mod, "_make_sharded_step", failing_make)
    model = kip320.make_model(Config(2, 2, 1, 1))
    res = check_sharded(
        model,
        min_bucket=8192,  # per-shard bucket 1024 -> compact active
        chunk_size=2048,
        store_trace=False,
        exchange="all_to_all",
    )
    assert res.ok and res.total == 277
    assert res.stats["adaptive_compile_fallback"] is True
    assert res.stats["adaptive_active"] is False
