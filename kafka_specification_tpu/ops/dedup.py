"""Sorting, batch-dedup and sorted-set membership over (hi, lo) uint32 pairs.

This is the device-resident replacement for TLC's FPSet + StateQueue: the
visited set is a sorted array of fingerprint pairs living in HBM; each BFS
level sorts the candidate fingerprints (XLA sort on TPU), drops in-batch
duplicates by adjacent comparison, and probes the visited set with a
fixed-iteration vectorized binary search (jit-friendly: no data-dependent
control flow).  The probe's insertion ranks are all the merge needs: it
places the new entries by them and counts the visited entries' shifts from
them (histogram + prefix sum), with no search of its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Stage scopes of this module (engine/pipeline.py STAGES holds the
# vocabulary; ops cannot import the engine, so the names are literal here).
_PROBE = "kspec.dedup_probe"
_MERGE = "kspec.dedup_merge"

# Sentinel (all-ones) sorts to the end; used to pad invalid slots.
# (kept as a Python int: a module-level jnp constant would initialize the
# default JAX backend at import time, which must not happen on TPU hosts
# where import != run)
SENT = 0xFFFFFFFF


def first_occurrence_mask(hi_s, lo_s, invalid_s):
    """After sorting: True for the first copy of each distinct valid pair."""
    prev_same = jnp.concatenate(
        [jnp.array([False]), (hi_s[1:] == hi_s[:-1]) & (lo_s[1:] == lo_s[:-1])]
    )
    return (~invalid_s) & (~prev_same)


def rank_sorted(set_hi, set_lo, set_n, q_hi, q_lo):
    """Vectorized lower-bound rank of queries in a sorted pair set (the
    ``dedup_probe`` stage).

    set_hi/set_lo: uint32[cap] sorted ascending on (hi, lo) for the first
    set_n entries (the rest is sentinel padding).  Fixed-iteration binary
    search — static trip count, fully vectorized over queries.  Returns
    (found_mask, rank) where rank is the insertion index (bisect_left).
    """
    with jax.named_scope(_PROBE):
        return _rank_sorted(set_hi, set_lo, set_n, q_hi, q_lo)


def _rank_sorted(set_hi, set_lo, set_n, q_hi, q_lo):
    """rank_sorted's body, in no stage scope of its own."""
    cap = set_hi.shape[0]
    n_q = q_hi.shape[0]
    lo_i = jnp.zeros((n_q,), jnp.int32)
    hi_i = jnp.broadcast_to(jnp.asarray(set_n, jnp.int32), (n_q,))
    iters = max(1, cap.bit_length())

    def body(_, carry):
        lo_i, hi_i = carry
        active = lo_i < hi_i  # guard: an empty interval must stay put (mid
        # would read one-past-the-end, which JAX clamps to the last element)
        mid = (lo_i + hi_i) // 2
        midc = jnp.minimum(mid, cap - 1)
        mh = set_hi[midc]
        ml = set_lo[midc]
        less = (mh < q_hi) | ((mh == q_hi) & (ml < q_lo))
        return (
            jnp.where(active & less, mid + 1, lo_i),
            jnp.where(active & ~less, mid, hi_i),
        )

    lo_i, _ = jax.lax.fori_loop(0, iters, body, (lo_i, hi_i))
    idx = jnp.minimum(lo_i, cap - 1)
    found = (lo_i < set_n) & (set_hi[idx] == q_hi) & (set_lo[idx] == q_lo)
    return found, lo_i


def member_sorted(set_hi, set_lo, set_n, q_hi, q_lo):
    """Membership probe (see rank_sorted)."""
    found, _ = rank_sorted(set_hi, set_lo, set_n, q_hi, q_lo)
    return found


def merge_ranked(set_hi, set_lo, set_n, new_hi, new_lo, new_rank, new_n, out_cap):
    """Scatter-merge: sorted visited set + compacted sorted new pairs.

    new_hi/new_lo: [M] with the first new_n entries sorted ascending and
    disjoint from the visited set; new_rank: each new entry's insertion index
    in the visited set (from rank_sorted).  Builds the merged sorted array
    with two scatters instead of re-sorting V+M keys:
      target(new[j])     = rank[j] + j
      target(visited[i]) = i + (# new entries below visited[i])
    New and visited are disjoint, so new[j] < visited[i] exactly when
    rank[j] <= i: the count is the inclusive prefix sum of a histogram of
    the live ranks, and nothing is searched.  Lanes j >= new_n may hold any
    rank and are masked out of it.  Out-of-range targets (sentinel tails)
    drop or overwrite padding with sentinels — both harmless.  Returns
    (hi[out_cap], lo[out_cap], n).  The ``dedup_merge`` stage.
    """
    with jax.named_scope(_MERGE):
        cap = set_hi.shape[0]
        M = new_hi.shape[0]
        j = jnp.arange(M, dtype=jnp.int32)
        valid_new = j < new_n
        tgt_new = jnp.where(valid_new, new_rank + j, out_cap)

        # new entries below each visited slot (a rank of cap, above every
        # slot, drops with the dead lanes)
        hist = jnp.zeros((cap,), jnp.int32)
        hist = hist.at[jnp.where(valid_new, new_rank, cap)].add(1, mode="drop")
        tgt_old = jnp.arange(cap, dtype=jnp.int32) + jnp.cumsum(hist)

        sent = jnp.uint32(SENT)
        out_hi = jnp.full((out_cap,), sent)
        out_lo = jnp.full((out_cap,), sent)
        out_hi = out_hi.at[tgt_old].set(set_hi).at[tgt_new].set(new_hi)
        out_lo = out_lo.at[tgt_old].set(set_lo).at[tgt_new].set(new_lo)
        return out_hi, out_lo, set_n + new_n
