"""Slow known-answer checks (deselect with -m "not slow").

These pin the remaining rows of the reference's expected-outcome matrix
(SURVEY.md §4) that need 3 replicas to manifest.
"""

import pytest

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import kip320, variants
from kafka_specification_tpu.models.kafka_replication import Config

THREE = Config(3, 2, 2, 2)

pytestmark = pytest.mark.slow


def test_kip279_strong_isr_violated_at_three_replicas():
    """Kip279's truncation is sound but its fetch path is unfenced; with a
    third replica the stale-leader interleavings break the ISR contract
    (Kip320.tla:21-35).  Golden depth pinned by the oracle."""
    m = variants.make_model("Kip279", THREE, invariants=("TypeOk", "WeakIsr", "StrongIsr"))
    res = check(m, min_bucket=2048, chunk_size=16384)
    assert res.violation is not None
    assert res.violation.invariant in ("WeakIsr", "StrongIsr")
    assert res.violation.depth == 10
    assert len(res.violation.trace) == 11


def test_kip320_three_broker_exhaustive_pass():
    """The THEOREM workload (Kip320.tla:168-171) at 3 brokers: all four
    invariants hold across all 737,794 states (count pinned by the oracle —
    also the job perfbench's kip320-3b cells cut to depth 10)."""
    m = kip320.make_model(THREE)
    res = check(
        m,
        store_trace=False,
        min_bucket=4096,
        chunk_size=32768,
        visited_capacity_hint=800_000,
    )
    assert res.ok
    assert res.total == 737_794
    assert res.diameter == 25


def test_kip320_first_try_strong_isr_only():
    """The canonical rejected-design claim (Kip320FirstTry.tla:27-39): with
    only StrongIsr checked, the violation surfaces at depth 12 after 284,803
    states (oracle-pinned)."""
    m = kip320.make_first_try_model(THREE, invariants=("StrongIsr",))
    res = check(m, min_bucket=2048, chunk_size=16384, store_trace=False)
    assert res.violation is not None
    assert res.violation.invariant == "StrongIsr"
    assert res.violation.depth == 12
    assert res.total == 284_803
