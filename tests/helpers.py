"""Shared test helpers: engine-vs-oracle cross validation."""

from __future__ import annotations

import importlib.util
import os

import jax
import numpy as np

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.oracle.interp import oracle_bfs


def enumerate_states(model, max_depth=None, min_bucket=32):
    """Run the engine BFS and decode every level's states to canonical python
    values. Returns (CheckResult, list of per-level state sets)."""
    spec = model.spec
    collected: list = []
    res = check(
        model,
        max_depth=max_depth,
        store_trace=True,
        min_bucket=min_bucket,
        collect_levels=collected,
    )
    unpack = jax.jit(jax.vmap(spec.unpack))
    return res, [set(decode_rows(model, packed, unpack))
                 for packed in collected]


def decode_rows(model, packed, unpack=None):
    """Packed rows (a level as `collect_levels` hands it out) -> the
    model's decoded canonical states, in the rows' order."""
    unpack = unpack or jax.jit(jax.vmap(model.spec.unpack))
    batch = {k: np.asarray(v) for k, v in unpack(packed).items()}
    return [model.decode({k: v[i] for k, v in batch.items()})
            for i in range(packed.shape[0])]


def assert_matches_oracle(model, oracle, max_depth=None, min_bucket=32):
    """BFS both the JAX kernels and the Python oracle; require identical
    per-level distinct-state *sets* (strongest possible equivalence), and the
    same verdict (violation of the same invariant at the same depth, or an
    exhaustive pass with identical counts)."""
    ores = oracle_bfs(oracle, max_depth=max_depth)
    res, engine_levels = enumerate_states(model, max_depth=max_depth, min_bucket=min_bucket)

    if ores.violation is None:
        assert res.violation is None, res.violation
        assert res.levels == ores.levels, (res.levels, ores.levels)
        assert res.total == ores.total
        assert len(engine_levels) == len(ores.level_sets)
        for d, (eng, orc) in enumerate(zip(engine_levels, ores.level_sets)):
            assert eng == orc, (
                f"level {d}: engine-only={list(eng - orc)[:3]} "
                f"oracle-only={list(orc - eng)[:3]}"
            )
    else:
        # Both stop at the violation level; the explored prefix must agree.
        assert res.violation is not None, f"oracle found {ores.violation}, engine none"
        assert res.violation.invariant == ores.violation[0]
        assert res.violation.depth == ores.violation[1]
        for d in range(ores.violation[1] + 1):
            assert engine_levels[d] == ores.level_sets[d], f"level {d} diff"
    return res, ores


def async_isr_under_constraint():
    """AsyncIsr at 3 brokers with its folded bounds at 3 / 3 and an
    explicit CONSTRAINT at 2: successors are pruned AFTER the guards, so a
    compacted chunk has holes inside its segments' enabled prefixes (the
    hand model alone folds its bounds into the guards and has none).
    Levels to depth 9: 1, 5, 16, 42, 92, 171, 282, 414, 535, 614."""
    import dataclasses

    import jax.numpy as jnp

    from kafka_specification_tpu.models import async_isr

    def bounded(s):
        return ((jnp.max(s["offs"]) <= 2) & (s["c_ver"] <= 2)
                & (s["l_ver"] <= 2))

    return dataclasses.replace(
        async_isr.make_model(async_isr.AsyncIsrConfig(3, 3, 3)),
        constraint=bounded)


def hand_models() -> dict:
    """name -> factory of every hand model at its benchmark cell's constants
    (3 and 5 brokers, LogSize / MaxRecords / MaxLeaderEpoch 2; AsyncIsr at 4
    brokers, MaxOffset / MaxVersion 3) and the three historical variants."""
    from kafka_specification_tpu.models import async_isr, kip320, variants
    from kafka_specification_tpu.models.kafka_replication import Config

    c3, c5 = Config(3, 2, 2, 2), Config(5, 2, 2, 2)
    return {
        "Kip320/3": lambda: kip320.make_model(c3),
        "Kip320/5": lambda: kip320.make_model(c5),
        "Kip320FirstTry/3": lambda: kip320.make_first_try_model(c3),
        "AsyncIsr/4": lambda: async_isr.make_model(
            async_isr.AsyncIsrConfig(4, 3, 3)),
        "MCKip320/5": lambda: kip320.make_model(c5, symmetric=True),
        "Kip101/3": lambda: variants.make_model("Kip101", c3),
        "Kip279/3": lambda: variants.make_model("Kip279", c3),
        "KafkaTruncateToHighWatermark/3": lambda: variants.make_model(
            "KafkaTruncateToHighWatermark", c3),
    }


def perfbench_tests(name: str) -> dict:
    """The tests and fixtures of `perfbench/tests/<name>.py`, for a tier-1
    wrapper to take as its own (`globals().update(...)`): `pytest
    perfbench/tests` is the harness's own judgement of itself, and
    `perfbench/` is a directory of scripts and no package, so the file is
    loaded by path."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "tests", name + ".py")
    spec = importlib.util.spec_from_file_location("perfbench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {k: v for k, v in vars(mod).items()
            if not k.startswith("_") and k != "pytest"}
