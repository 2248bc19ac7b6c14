"""Product-space combinator (the multi-partition stretch definition)."""

import functools

import pytest

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import id_sequence, kip320
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.models.product import product_model, product_oracle
from kafka_specification_tpu.oracle.interp import oracle_bfs

from helpers import assert_matches_oracle


def test_product_idsequence_matches_generic_oracle():
    k = 3
    base = id_sequence.make_model(2)
    model = product_model(base, k)
    obase = id_sequence.make_oracle(2)
    oracle = product_oracle(obase, k)
    res, ores = assert_matches_oracle(model, oracle)
    assert res.ok
    assert res.total == 4**k  # |base|^k reachable product states


def convolve(levels, k, depth):
    """Levels of the k-fold product of independent partitions with one
    initial state each: a product state's depth is the sum of its parts',
    so level d is the sum over d1 + ... + dk = d of the parts' levels."""
    out = [1]
    for _ in range(k):
        out = [sum(out[i] * levels[d - i] for i in range(d + 1)
                   if i < len(out) and d - i < len(levels))
               for d in range(depth + 1)]
    return out


@functools.lru_cache(maxsize=None)
def _two_partitions_to_depth_three():
    base = kip320.make_model(Config(2, 2, 1, 1), invariants=("TypeOk",))
    return check(product_model(base, 2), max_depth=3, min_bucket=64)


@pytest.mark.parametrize("held_to", ["count", "convolution"])
def test_product_kip320_two_partitions_smoke(held_to):
    res = _two_partitions_to_depth_three()
    assert res.ok
    if held_to == "count":
        # level 1 of the product = 2 x level 1 of the base (one partition
        # steps)
        assert res.levels[1] == 2 * 4
    else:
        one = oracle_bfs(
            kip320.make_oracle(Config(2, 2, 1, 1), invariants=("TypeOk",)),
            max_depth=3, keep_level_sets=False).levels
        assert one == [1, 4, 12, 18]
        assert res.levels == convolve(one, 2, 3) == [1, 8, 40, 132]


@pytest.mark.slow
def test_product_kafka_variant_matches_oracle():
    """Two-partition product of a full Kafka variant, cross-checked against
    the oracle product state-for-state (validates the per-partition kernel
    slicing at full model complexity): 353^2 = 124,609 reachable states."""
    from kafka_specification_tpu.models import variants

    cfg = Config(2, 2, 1, 1)
    base = variants.make_model("KafkaTruncateToHighWatermark", cfg, ("TypeOk",))
    obase = variants.make_oracle("KafkaTruncateToHighWatermark", cfg, ("TypeOk",))
    model = product_model(base, 2)
    oracle = product_oracle(obase, 2)
    res, _ = assert_matches_oracle(model, oracle, min_bucket=1024)
    assert res.ok
    assert res.total == 353 * 353


@pytest.mark.slow
def test_wide_product_hybrid_escalation_exact():
    """Wide-model escalation guard (round-5 LLVM-OOM finding): a product
    model with more actions than KSPEC_ADAPTIVE_MAX_PIPE escalates in
    hybrid mode — only needy actions leave the uniform width — and the
    count stays exact.  18 actions (2 x Kip320 tiny) > the default cap
    of 16; an undersized shift forces the uniform attempt to overflow."""
    base = kip320.make_model(Config(2, 2, 1, 1), invariants=("TypeOk",))
    model = product_model(base, 2)
    assert len(model.actions) == 18
    res = check(
        model,
        min_bucket=8192,  # >= the 4096 compact gate from level 1
        compact_shift=6,  # 8192>>6 = 128 rows/action-choice: overflows
        store_trace=False,
        visited_backend="host",
    )
    assert res.ok
    assert res.total == 277 * 277
    assert res.stats["adaptive_active"] is True  # escalation really fired


def test_mixed_base_product_closed_form():
    """product_models (heterogeneous partitions, round-5): the reachable
    set of Kip320-tiny x IdSequence is exactly 277 * 4 — partitions with
    entirely different specs, fanouts and kernels interleaved in one
    model (the shape the 277^2 x 5,973 half-billion run relies on)."""
    from kafka_specification_tpu.models.product import product_models

    a = kip320.make_model(Config(2, 2, 1, 1), invariants=("TypeOk",))
    b = id_sequence.make_model(2)  # 4 states; TypeOk only
    assert [i.name for i in a.invariants] == [i.name for i in b.invariants]
    m = product_models([a, b])
    r = check(m, min_bucket=256, store_trace=False, visited_backend="host")
    assert r.ok
    assert r.total == 277 * 4
