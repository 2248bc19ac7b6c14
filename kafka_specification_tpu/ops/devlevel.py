"""Device-resident level helpers: in-jit multiset digest folds and the
segment-append scatter the device level-pipeline composes.

The device pipeline (engine/pipeline.py `DevicePipeline`) keeps a whole
BFS level on the accelerator: a bounded ``lax.while_loop`` runs every
chunk's expand -> compact -> fingerprint -> dedup stages without a host
round trip, so the per-chunk host work the fused pipeline still does —
fingerprint-set bookkeeping, digest folds, frontier assembly — must be
reformulated as pure traced ops.  This module holds the two primitives
that reformulation needs beyond what ops/dedup.py already provides:

- ``masked_digest`` / ``combine_digest``: the PR 9 per-level
  (count, xor, wrapping-sum) fingerprint-multiset digest computed
  entirely in-jit over (hi, lo) uint32 lanes — **x64-free** (the CI
  platform runs without jax x64), carrying the 64-bit wrapping sum as
  four 16-bit limbs in uint32 registers.  ``digest_ints`` converts the
  accumulator back to the exact python ints
  ``resilience.integrity.digest_fps`` would have produced for the same
  multiset, so the host-side :class:`LevelDigestChain` folds the
  device-computed digest bit-identically to the per-chunk host folds.
- ``append_rows`` / ``append_vec``: the dynamic-offset segment append
  that assembles the next frontier (rows, parents, action ids) inside
  the level loop — each chunk's compacted novel prefix lands at the
  running output offset; rows past the live prefix are garbage the next
  chunk overwrites (and the final host slice clips).

The HOST-backend (deferred-probe) level programs compose the same
helpers with two deltas: ``append_vec`` additionally carries the
emitted prefix's fingerprint lanes out (the once-per-level batched
host probe consumes them instead of recomputing), and the digest
helpers are NOT used — the chain's multiset is only known after the
host probe, so the host folds the survivors.  ``level_new_capacity``
sizes the level-new set identically in both modes (in host mode it
bounds the PRE-probe candidate count, which is what that set holds).

Everything here is shape-static and jit-pure; the purity lint
(`cli analyze`) sweeps this module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the digest folds' stage scope (engine/pipeline.py STAGES)
_DIGEST = "kspec.digest"

#: 16-bit limb block: column sums of uint16 limbs over <= 2^16 rows fit
#: uint32 exactly ((2^16-1) * 2^16 < 2^32), so digests of arbitrarily
#: wide buffers reduce block-wise with no 64-bit ALU
_BLOCK = 1 << 16


def zero_digest():
    """Neutral digest accumulator: (count, xor_hi, xor_lo, limbs[4])."""
    return (
        jnp.int32(0),
        jnp.uint32(0),
        jnp.uint32(0),
        jnp.zeros((4,), jnp.uint32),
    )


def _add_limbs(acc, add):  # kspec: traced
    """acc + add over four 16-bit limbs (uint32 registers, mod 2^64).

    ``acc`` limbs are normalized (< 2^16); ``add`` limbs may carry full
    uint32 block column sums.  Each add[i] is split into its low half
    (added to limb i) and high half (carried into limb i+1), so every
    per-limb sum stays below 2^18 — adding the raw uint32 directly
    could reach exactly 2^32 and silently drop a carry (a full 65536-row
    block of 0xFFFF limbs; caught in review, regression-tested in
    tests/test_integrity.py).  The final carry past limb 3 drops — the
    sum is 64-bit wrapping by construction, exactly
    ``np.sum(fps, dtype=uint64)``'s overflow semantics."""
    mask16 = jnp.uint32(0xFFFF)
    out = []
    carry = jnp.uint32(0)
    for i in range(4):
        t = acc[i] + (add[i] & mask16) + carry
        out.append(t & mask16)
        carry = (t >> 16) + (add[i] >> 16)
    return jnp.stack(out)


@jax.named_scope(_DIGEST)
def masked_digest(hi, lo, valid):  # kspec: traced
    """(count, xor, sum) over the fingerprint pairs selected by `valid`.

    hi/lo: uint32[T] fingerprint lanes; valid: bool[T].  Returns the
    accumulator tuple ``(count i32, xor_hi u32, xor_lo u32,
    limbs u32[4])`` — fold into a running accumulator with
    :func:`combine_digest`, convert with :func:`digest_ints`."""
    z = jnp.uint32(0)
    mhi = jnp.where(valid, hi, z)
    mlo = jnp.where(valid, lo, z)
    count = jnp.sum(valid, dtype=jnp.int32)
    xor_hi = jax.lax.reduce(mhi, z, jax.lax.bitwise_xor, (0,))
    xor_lo = jax.lax.reduce(mlo, z, jax.lax.bitwise_xor, (0,))
    mask16 = jnp.uint32(0xFFFF)
    limb_cols = (mlo & mask16, mlo >> 16, mhi & mask16, mhi >> 16)
    T = hi.shape[0]
    nblk = -(-T // _BLOCK)
    pad = nblk * _BLOCK - T
    limbs = jnp.zeros((4,), jnp.uint32)
    per_block = []
    for col in limb_cols:
        if pad:
            col = jnp.concatenate([col, jnp.zeros((pad,), jnp.uint32)])
        per_block.append(
            jnp.sum(col.reshape(nblk, _BLOCK), axis=1, dtype=jnp.uint32)
        )
    for b in range(nblk):
        limbs = _add_limbs(limbs, [c[b] for c in per_block])
    return count, xor_hi, xor_lo, limbs


@jax.named_scope(_DIGEST)
def combine_digest(acc, new):  # kspec: traced
    """Fold one chunk digest into the running level accumulator."""
    c0, xh0, xl0, l0 = acc
    c1, xh1, xl1, l1 = new
    return c0 + c1, xh0 ^ xh1, xl0 ^ xl1, _add_limbs(l0, l1)


def digest_ints(acc) -> tuple:
    """Device accumulator -> (count, xor, sum) python ints, bit-exact
    with ``resilience.integrity.digest_fps`` over the same multiset.
    Host-side (materializes the accumulator)."""
    import numpy as np

    count, xor_hi, xor_lo, limbs = acc
    lim = [int(v) & 0xFFFF for v in np.asarray(limbs).tolist()]
    total = lim[0] | (lim[1] << 16) | (lim[2] << 32) | (lim[3] << 48)
    xor = (int(np.asarray(xor_hi)) << 32) | int(np.asarray(xor_lo))
    return int(np.asarray(count)), xor, total & 0xFFFFFFFFFFFFFFFF


def _next_pow2(n: int) -> int:
    """Local twin of engine.bfs._next_pow2 (importing the engine here
    would cycle: engine/pipeline.py imports this module)."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


#: headroom multiplier over the measured per-level new-state high water
#: (matches PooledWidths.HEADROOM — one sizing philosophy everywhere)
LN_HEADROOM = 1.35

#: below this many lanes the safe (cannot-overflow) bound is taken
#: outright instead of the high-water ladder: 64Ki u64 pairs = 512KiB —
#: the per-chunk merge over it is cheap next to a gated chunk's work,
#: while an overflow re-dispatch always discards a full level's compute.
#: During a run's growth phase the high water lags the frontier by one
#: level, so a ladder here would re-dispatch nearly every level; at
#: production scale `worst` is millions of lanes and the ladder governs.
LN_SAFE_SMALL = 1 << 16


def level_new_capacity(T: int, ln_hw: int, worst: int) -> int:
    """The level-new sorted set's high-water-LADDER capacity — the ONE
    sizing policy for every device-resident level path (the single-
    device DevicePipeline and the sharded per-shard variant; they must
    not drift on overflow bounds).

    The per-chunk level-new merge costs O(LN), so LN is sized from the
    run's measured per-level new-state high water `ln_hw` (with
    LN_HEADROOM), floored at one chunk's emit width `T` (a level can
    always produce at least one chunk's worth) and capped at the safe
    bound `worst` (= chunks x emit width — the level can't produce
    more).  Small levels (`worst` <= LN_SAFE_SMALL) take the safe bound
    outright — no overflow is possible there and the ladder could only
    lose re-dispatches.  Otherwise an overflow costs exactly one
    re-dispatch at :func:`level_new_bound`; steady state costs
    nothing."""
    safe = _next_pow2(worst)
    if safe <= LN_SAFE_SMALL:
        return safe
    return min(
        _next_pow2(max(T, int(LN_HEADROOM * ln_hw) + 1)),
        safe,
    )


def level_new_bound(worst: int) -> int:
    """The safe (cannot-overflow) level-new capacity for the exact-bound
    re-dispatch: `worst` = chunks x per-chunk emit width."""
    return _next_pow2(worst)


def append_rows(buf, seg, offset):  # kspec: traced
    """Write a [T, K] segment into `buf` at row `offset` (traced value).
    The caller advances its live-prefix counter by the segment's valid
    count; rows past it are garbage the next append overwrites."""
    return jax.lax.dynamic_update_slice(buf, seg, (offset, 0))


def append_vec(buf, seg, offset):  # kspec: traced
    """1-D twin of :func:`append_rows`."""
    return jax.lax.dynamic_update_slice(buf, seg, (offset,))
