"""Whole kernels with `models.base.read` / `write` in their select form
against the same kernels with the helper forced to the plain indexing it
replaced (ISSUE 39): for every action of every hand model, every state
reachable to a small depth and EVERY choice, enabled or not, the enabled bit
and the packed successor are equal.  Disabled rows run the kernels too (the
engine masks them afterwards), so the bits of a disabled row are part of what
a program computes.  And one fused-path run of each claimed cell's model
against the legacy pipeline: rows, parents and action ids in discovery order.

Forcing plain indexing is a test fixture (``SELECT_MAX`` = 0 for one call),
not an option of the program."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import hand_models

from kafka_specification_tpu.engine import check
from kafka_specification_tpu.models import base, finite_replicated_log as frl

# model -> (factory, depth the reachable states are taken to)
MODELS = {
    name: (factory, 3 if name.endswith("/5") else 5)
    for name, factory in hand_models().items()
    if name != "MCKip320/5"  # Kip320/5's kernels under another state key
}
MODELS["FiniteReplicatedLog"] = (lambda: frl.make_model(3, 2, 2), 4)
CELLS_DEPTH = 6


@functools.lru_cache(maxsize=None)
def _reachable(name):
    """(model, packed rows of every state to the model's depth, plus rows
    of noise: any bit pattern is a legal input of a kernel)."""
    factory, depth = MODELS[name]
    model = factory()
    levels: list = []
    check(model, max_depth=depth, min_bucket=32, collect_levels=levels,
          check_deadlock=False)
    rows = np.concatenate([np.asarray(lv) for lv in levels])
    rng = np.random.default_rng(39)
    noise = rng.integers(0, 1 << 32, size=(64, rows.shape[1]),
                         dtype=np.uint64).astype(np.uint32)
    return model, jnp.asarray(np.concatenate([rows, noise]))


def _cases():
    for name, (factory, _depth) in sorted(MODELS.items()):
        for i, a in enumerate(factory().actions):
            yield pytest.param(name, i, id=f"{name}-{a.name}")


def _successors(model, action, rows):
    """(ok[S, C], packed[S, C, K]) of `action` over every row and choice."""
    spec = model.spec
    choices = jnp.arange(action.n_choices, dtype=jnp.int32)

    def one(row):
        state = spec.unpack(row)
        ok, nxt = jax.vmap(lambda c: action.kernel(state, c))(choices)
        return ok, jax.vmap(spec.pack)(nxt)

    ok, packed = jax.vmap(one)(rows)  # traced anew at every call
    return np.asarray(ok), np.asarray(packed)


@pytest.mark.parametrize("name,index", _cases())
def test_select_form_equals_plain_indexing(name, index, monkeypatch):
    model, rows = _reachable(name)
    action = model.actions[index]
    ok, packed = _successors(model, action, rows)
    monkeypatch.setattr(base, "SELECT_MAX", 0)  # plain x[i] / x.at[i].set
    ok_plain, packed_plain = _successors(model, action, rows)
    assert ok.dtype == ok_plain.dtype and packed.dtype == packed_plain.dtype
    np.testing.assert_array_equal(ok, ok_plain)
    np.testing.assert_array_equal(packed, packed_plain)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_predicates_equal_under_plain_indexing(name, monkeypatch):
    model, rows = _reachable(name)
    preds = [i.pred for i in model.invariants]
    if model.constraint is not None:
        preds.append(model.constraint)

    def run():
        states = jax.vmap(model.spec.unpack)(rows)
        return [np.asarray(jax.vmap(p)(states)) for p in preds]

    got = run()
    monkeypatch.setattr(base, "SELECT_MAX", 0)
    for a, b in zip(got, run()):
        np.testing.assert_array_equal(a, b)


# the claimed cells' models, cut to a depth the CPU walks in seconds; the
# gate at 64 rows puts their wide levels on the fused per-chunk path
CELLS = {"kip320-3b": "Kip320/3", "firsttry-3b": "Kip320FirstTry/3",
         "asyncisr-4b": "AsyncIsr/4"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fused_path_equals_legacy_rows_parents_and_action_ids(cell):
    model, depth = hand_models()[CELLS[cell]](), CELLS_DEPTH
    runs = {}
    for pipeline in ("legacy", "fused"):
        trace: list = []
        res = check(model, pipeline=pipeline, max_depth=depth, min_bucket=64,
                    chunk_size=1024, compact_gate=64, store_trace=True,
                    collect_trace=trace, check_deadlock=False,
                    stats_path=os.devnull)
        assert res.stats["pipeline"] == pipeline
        assert res.stats["pipeline_fallback"] is False
        runs[pipeline] = (res, trace)
    (leg, t_leg), (fus, t_fus) = runs["legacy"], runs["fused"]
    assert leg.levels == fus.levels and len(leg.levels) == depth + 1
    for a, b in zip(leg.stats["levels"], fus.stats["levels"]):
        for key in ("new", "duplicates", "enabled_candidates",
                    "action_enablement"):
            assert a[key] == b[key], key
    assert len(t_leg) == len(t_fus) == depth + 1
    for d, (lv_leg, lv_fus) in enumerate(zip(t_leg, t_fus)):
        for what, a, b in zip(("rows", "parents", "action ids"),
                              lv_leg, lv_fus):
            if a is None or b is None:
                assert a is b, (d, what)
                continue
            np.testing.assert_array_equal(
                np.asarray(a, np.int64), np.asarray(b, np.int64),
                err_msg=f"level {d} {what}")
