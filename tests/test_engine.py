"""Engine behaviors: violation traces, hashed-fingerprint dedup mode,
invariant checking at init, depth cutoffs."""

import numpy as np

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import finite_replicated_log, id_sequence
from kafka_specification_tpu.models.base import Invariant, Model

from helpers import assert_matches_oracle


def _with_invariants(base, invariants):
    return Model(
        name=base.name,
        spec=base.spec,
        init_states=base.init_states,
        actions=base.actions,
        invariants=invariants,
        constraint=base.constraint,
        decode=base.decode,
    )


def test_violation_trace_is_valid_action_path():
    """Falsify an invariant at the end of the IdSequence chain; the
    reconstructed trace must be a valid path init -> violation."""
    max_id = 5
    base = id_sequence.make_model(max_id)
    model = _with_invariants(
        base, [Invariant("BelowBound", lambda s: s["nextId"] <= 3)]
    )
    res = check(model, min_bucket=32)
    assert res.violation is not None
    v = res.violation
    assert v.invariant == "BelowBound"
    assert v.depth == 4 and v.state == 4
    # the trace replays as a real action path: 0 ->NextId-> 1 ... -> 4
    assert [s for _, s in v.trace] == [0, 1, 2, 3, 4]
    assert v.trace[0][0] == "<init>"
    assert all(a == "NextId" for a, _ in v.trace[1:])


def test_violation_at_init():
    base = id_sequence.make_model(3)
    model = _with_invariants(base, [Invariant("NotZero", lambda s: s["nextId"] != 0)])
    res = check(model)
    assert res.violation is not None
    assert res.violation.depth == 0
    assert res.violation.trace == [("<init>", 0)]


def test_hashed_fingerprint_mode_full_bfs():
    """Same model checked in exact64 and forced-hashed dedup mode must agree
    with the oracle state-for-state (exercises murmur3 path through the
    whole sort/member/merge pipeline)."""
    model = finite_replicated_log.make_model(2, 2, 2, force_hashed=True)
    assert not model.spec.exact64
    oracle = finite_replicated_log.make_oracle(2, 2, 2)
    res, _ = assert_matches_oracle(model, oracle)
    assert res.total == 7**2


def test_invariants_checked_on_new_states_each_level():
    """A violation deep in FRL: no log may reach length 2 — found at depth 2."""
    base = finite_replicated_log.make_model(2, 2, 1)
    model = _with_invariants(
        base,
        [Invariant("ShortLogs", lambda s: (s["end"] < 2).all())],
    )
    res = check(model, min_bucket=32)
    assert res.violation is not None
    assert res.violation.invariant == "ShortLogs"
    assert res.violation.depth == 2
    # trace is a valid path of length depth+1
    assert len(res.violation.trace) == 3
    assert res.violation.trace[0][0] == "<init>"


def test_chunked_frontier_matches_golden():
    """Tiny chunk_size forces multi-chunk levels; counts must be identical
    (cross-chunk dedup rides the shared visited set)."""
    model = finite_replicated_log.make_model(3, 4, 2)
    res = check(model, min_bucket=32, chunk_size=32, store_trace=False)
    assert res.ok
    assert res.total == 29791
    assert res.diameter == 12


def test_chunked_violation_depth_stable():
    base = finite_replicated_log.make_model(2, 2, 1)
    model = Model(
        name=base.name,
        spec=base.spec,
        init_states=base.init_states,
        actions=base.actions,
        invariants=[Invariant("ShortLogs", lambda s: (s["end"] < 2).all())],
        decode=base.decode,
    )
    res = check(model, min_bucket=32, chunk_size=32)
    assert res.violation is not None and res.violation.depth == 2
    assert len(res.violation.trace) == 3


def test_multiple_initial_states():
    """TLC enumerates all Init states; the engine must seed BFS with the
    whole (deduplicated) init set and count level 0 accordingly."""
    base = id_sequence.make_model(6)

    def inits():
        return [{"nextId": 0}, {"nextId": 3}, {"nextId": 3}, {"nextId": 5}]

    model = Model(
        name="IdSeq-multi-init",
        spec=base.spec,
        init_states=inits,
        actions=base.actions,
        invariants=base.invariants,
        decode=base.decode,
    )
    res = check(model, min_bucket=32)
    assert res.levels[0] == 3  # deduplicated init set
    # reachable: 0..7 from the three seeds
    assert res.total == 8
    assert res.ok


def test_adaptive_compile_fallback_exact(monkeypatch):
    """An escalated per-action compact program that fails to compile must
    not kill the run: the engine falls back loudly to the uniform path
    and stays exact (XLA:CPU's LLVM has been seen OOMing on the 27-action
    mixed product's escalated step — a known gap, now handled).

    The escalated state is injected (widths_for returns a per-action
    tuple while adaptation is on) so the test doesn't depend on a model
    dense enough to overflow organically; the organic uniform-overflow ->
    escalate path is covered by tests/test_sharded.py's escalation test
    and the policy unit test."""
    from kafka_specification_tpu.engine import bfs as bfs_mod
    from kafka_specification_tpu.models import finite_replicated_log as frl

    orig_get = bfs_mod._Step.get
    orig_wf = bfs_mod.AdaptiveCompact.widths_for

    def tuple_widths(self, bucket):
        if self.on:  # pre-fallback: pretend a prior chunk escalated
            return tuple(256 for _ in self.actions)
        return orig_wf(self, bucket)

    def failing_get(self, bucket, vcap, *args, **kw):
        if isinstance(kw.get("compact"), (list, tuple)):
            raise RuntimeError("synthetic XLA compile failure")
        return orig_get(self, bucket, vcap, *args, **kw)

    monkeypatch.setattr(bfs_mod.AdaptiveCompact, "widths_for", tuple_widths)
    monkeypatch.setattr(bfs_mod._Step, "get", failing_get)
    model = frl.make_model(2, 2, 2)
    res = check(
        model, store_trace=False, compact_shift=2, visited_backend="host"
    )
    assert res.ok and res.total == 49
    assert res.stats["adaptive_compile_fallback"] is True
    assert res.stats["adaptive_active"] is False
