"""Shared dedup-probe verification fixture.

One definition of "same winners as the jnp path" for every consumer that
validates a Pallas probe kernel against hashset.probe_insert: the
interpret-mode bit-identity tests (tests/test_pallas.py) and the on-chip
ladder (scripts/tpu_mosaic_ladder.py).  The fixture bakes in the
awkward cases — in-batch duplicates (winner identity matters: the lowest
-index row carries parent/action attribution for traces), rows colliding
with pre-seeded table entries, and invalid rows.
"""

from __future__ import annotations

import numpy as np

from . import hashset


def make_probe_case(seed: int = 5, cap: int = 1 << 12, m: int = 1024):
    """Build (t_hi0, t_lo0, q_hi, q_lo, valid) plus the jnp-path
    reference (ref_new, ref_n, ref_hi, ref_lo): ~25% in-batch
    duplicates, the first m/8 rows pre-seeded in the table, ~10%
    invalid rows."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    base = rng.integers(0, 2**32, size=(m, 2), dtype=np.uint32)
    dup_idx = rng.integers(0, m // 2, size=m // 4)
    base[m // 2 : m // 2 + m // 4] = base[dup_idx]
    seeded = base[: m // 8]
    valid = rng.random(m) < 0.9

    t_hi0, t_lo0 = hashset.table_from_pairs(
        seeded[:, 0], seeded[:, 1], min_cap=cap
    )
    q_hi = jnp.asarray(base[:, 0])
    q_lo = jnp.asarray(base[:, 1])
    v = jnp.asarray(valid)
    ref_hi, ref_lo, _claim, ref_new, ref_n, ref_ovf = hashset.probe_insert(
        t_hi0, t_lo0, q_hi, q_lo, v
    )
    assert not bool(ref_ovf)
    return {
        "t_hi0": t_hi0,
        "t_lo0": t_lo0,
        "q_hi": q_hi,
        "q_lo": q_lo,
        "valid": v,
        "ref_new": np.asarray(ref_new),
        "ref_n": int(ref_n),
        "ref_hi": ref_hi,
        "ref_lo": ref_lo,
    }


def live_set(h, l):
    """The set of live fingerprint pairs in a table — membership
    comparison that ignores slot layout (collision chains may legally
    place entries differently across kernel formulations)."""
    h, l = np.asarray(h), np.asarray(l)
    keep = ~((h == hashset.SENT) & (l == hashset.SENT))
    return set(zip(h[keep].tolist(), l[keep].tolist()))


def assert_same_winners(case, p_hi, p_lo, p_new, p_n):
    """Winners bit-identical to the jnp path, count equal, membership
    equal.  Raises AssertionError with context on any mismatch."""
    got = np.asarray(p_new)
    assert np.array_equal(got, case["ref_new"]), (
        "is_new winners differ from the jnp path "
        f"({int(got.sum())} vs {int(case['ref_new'].sum())} new)"
    )
    assert int(p_n) == case["ref_n"], (int(p_n), case["ref_n"])
    assert live_set(p_hi, p_lo) == live_set(case["ref_hi"], case["ref_lo"])
