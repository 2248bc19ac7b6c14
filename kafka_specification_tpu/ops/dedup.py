"""Sorting, batch-dedup and sorted-set membership over (hi, lo) uint32 pairs.

This is the device-resident replacement for TLC's FPSet + StateQueue: the
visited set is a sorted array of fingerprint pairs living in HBM; each BFS
level sorts the candidate fingerprints (XLA sort on TPU), drops in-batch
duplicates by adjacent comparison, and probes the visited set with a
vectorized binary search that a top-bits directory bounds: the probe first
finds where each value of the top ``k`` bits of ``hi`` starts in the sorted
set, then searches every query inside its own bucket only, for as many
rounds as the fullest bucket needs (a device value: 5-7 rounds on hashed
fingerprints where the pinned capacity alone would ask for 22; the bit
length of the set where every entry shares its top bits, and the search is
then the plain one).  Both lanes of the set ride one ``[2, cap]`` buffer,
so a round is one gather; where the pinned capacity is above
``PROBE_WINDOW`` the buffer holds the set's first ``PROBE_WINDOW`` slots
for as long as the set fits them, so a probe costs what the set holds and
not what its capacity pins.  Directory and buffer are rebuilt inside every
probe from the set it is handed: no carried state, exact for any data.
The queries are searched in blocks, and only the blocks of their live
prefix: a caller whose query list is sorted, sentinel pairs last (every
dedup stage's is: the sort puts them there), says how many lanes are live
(``q_n``, a device value) and the lanes at or past it are never searched
and read ``found`` False, ``rank`` 0; a caller with queries in any order
omits it and every lane is searched (``rank_sorted``, ``member_sorted``).
The probe's insertion ranks are all the merge needs:
it places the new entries by them and counts the visited entries' shifts
from them (histogram + prefix sum), with no search of its own, and it
moves the live entries only: two rolled loops over fixed blocks, as many
iterations as the set and the new list fill (device values), so a merge
into a large pinned capacity costs what the set holds.
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
    set_n entries (the rest is sentinel padding).  Binary search bounded
    by a top-bits directory (module docstring), fully vectorized over the
    queries, which may come in any order: every lane is searched (no
    ``q_n``; :func:`probe_sorted` is the form for a sorted list with a
    live prefix).  Returns (found_mask, rank) where rank is the insertion
    index (bisect_left).
    """
    found, rank, _work = probe_sorted(set_hi, set_lo, set_n, q_hi, q_lo)
    return found, rank


#: The most query lanes one iteration of the probe's block loop searches
#: (the block is a shape, :func:`even_block`; how many blocks run is a
#: device value).
#: Timed on a TPU v5e, the function alone (PERF.md section 6, PR 41), ms at
#: blocks of 2,048 / 8,192 / 16,384 / 65,536 against the search of every
#: lane: 278,528 lanes, 31% live, a set of 1.2 million in 4,194,304: 7.15 /
#: 7.13 / 7.49 / 8.89 against 16.82; 639,000 lanes, 44% live, 3.1 million
#: in 8,388,608: 20.45 / 20.06 / 21.71 / 24.06 against 43.93; every lane
#: live: 16.98 / 16.36 / 16.37 / 18.09 against 16.85 and 40.15 / 39.31 /
#: 42.38 / 43.24 against 43.89.  Up to 16,384 the ``[2, cap]`` buffer stays
#: in ``S(1)`` wherever the search reads it; from 32,768 the compiler
#: places it otherwise.
PROBE_BLOCK = 8192

#: The most slots of the sorted set one probe's search reads where the set
#: fits them: a pinned capacity above it is searched through its first
#: ``PROBE_WINDOW`` slots while ``set_n`` is at most that (a device value;
#: :func:`_rank_sorted`), and whole once the set has outgrown them.
#: The largest capacity whose ``[2, cap]`` buffer the compiler keeps in
#: ``S(1)`` (64 MiB; at 16,777,216 and above every round's gather reads it
#: from the default memory space).  Timed on a TPU v5e, the function alone
#: (PERF.md section 6, PR 45), a set of 1,189,826 and 1,203,489 query lanes
#: 62% live, ms at capacities 8,388,608 / 16,777,216 / 33,554,432: the
#: whole capacity searched 41.4 / 122.7 / 122.9, its first 8,388,608 slots
#: 41.3 / 41.5 / 41.7, its first 4,194,304 slots 41.4 / 41.4 / 41.3.
PROBE_WINDOW = 8_388_608


def windowed(cap: int, set_n: int) -> bool:
    """Whether a probe of a set of `set_n` entries in a capacity of `cap`
    searches the window and not the capacity: what :func:`_rank_sorted`
    decides from the shape and on the device, for a host that holds both
    numbers (the level record's ``probes_windowed``)."""
    return cap > PROBE_WINDOW and set_n <= PROBE_WINDOW


def even_block(n: int, most: int) -> int:
    """The block of a width: the fewest blocks no larger than ``most``
    that cover ``n``, all of one size, so a full width recomputes fewer
    rows than it has blocks (the last block starts early where the block
    does not divide the width).  (Blocks of 8,192 whatever the width ran
    16,384 rows a loop over 9,472 full lanes: 2.02 ms against the
    full-width form's 1.63; 4,736 twice: 1.70; PERF.md section 6, PR 37.)"""
    return -(-n // -(-n // most))


def probe_sorted(set_hi, set_lo, set_n, q_hi, q_lo, q_n=None):
    """:func:`rank_sorted` and what it cost: -> (found, rank, work).

    q_n: the length of the live prefix of the queries, a device value.
    The caller promises that every query it cares about lies below
    ``q_n`` (a sorted list whose sentinel pairs sort last); lanes at or
    past it are dead: ``found`` False, ``rank`` 0, never searched.  The
    lanes run in blocks of :func:`even_block` ``(T, PROBE_BLOCK)``,
    ``ceil(q_n / block)`` of them (a rolled loop), so a probe costs what
    the chunk holds and not what its layout pads.  ``None``: every lane
    is searched, in any order.

    ``work`` is int32[4]: the search rounds this probe ran over its query
    lanes (a device value) and the rounds a search over the whole
    capacity runs (``cap.bit_length()``, a shape); the query lanes it
    searched (blocks run x block size, a device value) and the lanes it
    was handed (``T``, a shape).  The level programs sum it over their
    probes and hand it to the host with their counts (level record
    ``probe_rounds`` / ``probe_rounds_plain`` / ``probe_lanes`` /
    ``probe_lanes_plain``)."""
    with jax.named_scope(_PROBE):
        found, rank, rounds, lanes = _rank_sorted(
            set_hi, set_lo, set_n, q_hi, q_lo, q_n)
        plain = max(1, set_hi.shape[0].bit_length())
        return found, rank, jnp.stack(
            [rounds, jnp.int32(plain), lanes, jnp.int32(q_hi.shape[0])])


def directory_bits(n_q: int) -> int:
    """Top bits of ``hi`` the probe's directory resolves, from the number
    of query lanes (a shape): about one bucket per 16 lanes, between 64
    and 65,536 buckets.  One more bit saves every lane a round and doubles
    the boundary queries, each a search of the whole set (B rounds), so
    the work 2^k B + n_q (log2 n - k) is least near 2^k = n_q / (B ln 2),
    whatever the set holds.  Timed on the chip at 2,048 to 1,769,472 lanes
    (PERF.md section 6, PR 31)."""
    return min(16, max(6, (max(n_q, 1) // 16).bit_length() - 1))


def _bit_length(x):
    return (32 - jax.lax.clz(x)).astype(jnp.int32)


def _search(pairs, lo_i, hi_i, q_hi, q_lo, rounds):
    """`rounds` halvings of every lane's interval [lo_i, hi_i) of the
    sorted `pairs` (uint32[2, cap]: the hi lanes, the lo lanes) -> lo_i.
    `rounds` may be a device value: the loop stays rolled, one body."""
    cap = pairs.shape[1]

    def body(_, carry):
        lo_i, hi_i = carry
        active = lo_i < hi_i  # guard: an empty interval must stay put (mid
        # would read one-past-the-end, which JAX clamps to the last element)
        mid = (lo_i + hi_i) // 2
        m = pairs[:, jnp.minimum(mid, cap - 1)]
        less = (m[0] < q_hi) | ((m[0] == q_hi) & (m[1] < q_lo))
        return (
            jnp.where(active & less, mid + 1, lo_i),
            jnp.where(active & ~less, mid, hi_i),
        )

    lo_i, _ = jax.lax.fori_loop(0, rounds, body, (lo_i, hi_i))
    return lo_i


def _rank_sorted(set_hi, set_lo, set_n, q_hi, q_lo, q_n=None):
    """probe_sorted's body, in no stage scope of its own: -> (found, rank,
    rounds run over the query lanes, query lanes searched).

    The search reads ONE ``[2, n]`` buffer of the set's two lanes: the
    whole capacity where that is at most :data:`PROBE_WINDOW` (a shape:
    nothing else is traced), else its first ``PROBE_WINDOW`` slots while
    the set fits them (``set_n``, a device value: one ``lax.cond``, both
    branches :func:`_rank_pairs`).  Same answers to the last bit for any
    data: no rank exceeds ``set_n``, and the one slot a clamp can read at
    or past the live prefix is read only under ``rank < set_n``."""
    cap = set_hi.shape[0]
    set_n = jnp.asarray(set_n, jnp.int32)

    def search(hi, lo):
        # both lanes in ONE buffer, read by one gather a round: on the chip
        # a gather out of one [2, n] operand costs a fifth of two gathers
        # out of the two [n] lanes (PERF.md section 6, PR 31)
        return _rank_pairs(jnp.stack([hi, lo]), set_n, q_hi, q_lo, q_n)

    if cap <= PROBE_WINDOW:
        return search(set_hi, set_lo)
    return jax.lax.cond(
        set_n <= PROBE_WINDOW,
        lambda: search(set_hi[:PROBE_WINDOW], set_lo[:PROBE_WINDOW]),
        lambda: search(set_hi, set_lo))


def _rank_pairs(pairs, set_n, q_hi, q_lo, q_n):
    """:func:`_rank_sorted` over `pairs` (uint32[2, n]: the first n slots
    of the set's hi lanes and lo lanes, ``set_n <= n``)."""
    cap, T = pairs.shape[1], q_hi.shape[0]
    k = directory_bits(T)
    nb = 1 << k
    # start[b] = lower bound of (b << (32 - k), 0) in the live prefix,
    # start[nb] = set_n.  (hi, lo) order is monotone in hi >> (32 - k), so
    # a query's lower bound in the set is its lower bound inside
    # [start[b], start[b + 1]) for its own b: exact for any data
    start = jnp.concatenate([
        _search(
            pairs,
            jnp.zeros((nb,), jnp.int32), jnp.broadcast_to(set_n, (nb,)),
            jnp.arange(nb, dtype=jnp.uint32) << (32 - k),
            jnp.zeros((nb,), jnp.uint32),
            _bit_length(set_n),
        ),
        set_n[None],
    ])
    # as many rounds as the fullest bucket needs: a handful on hashed
    # fingerprints, bit_length(set_n) where one bucket holds the set
    rounds = _bit_length(jnp.max(start[1:] - start[:-1]))

    def lanes(q_hi, q_lo):
        b = (q_hi >> (32 - k)).astype(jnp.int32)
        rank = _search(pairs, start[b], start[b + 1], q_hi, q_lo, rounds)
        at = pairs[:, jnp.minimum(rank, cap - 1)]
        return (rank < set_n) & (at[0] == q_hi) & (at[1] == q_lo), rank

    if q_n is None:
        return (*lanes(q_hi, q_lo), rounds, jnp.int32(T))
    B = even_block(T, PROBE_BLOCK)
    q_n = jnp.clip(jnp.asarray(q_n, jnp.int32), 0, T)
    blocks = (q_n + (B - 1)) // B

    def block(i, carry):
        found, rank = carry
        # a width that is no multiple of its block: the last block starts
        # early (a slice must lie inside its operand) and searches the
        # overlap again, to the same answers
        s = jnp.minimum(i * B, T - B)
        f, r = lanes(jax.lax.dynamic_slice(q_hi, (s,), (B,)),
                     jax.lax.dynamic_slice(q_lo, (s,), (B,)))
        live = (s + jnp.arange(B, dtype=jnp.int32)) < q_n
        return (jax.lax.dynamic_update_slice(found, f & live, (s,)),
                jax.lax.dynamic_update_slice(rank, jnp.where(live, r, 0),
                                             (s,)))

    found, rank = jax.lax.fori_loop(
        0, blocks, block,
        (jnp.zeros((T,), bool), jnp.zeros((T,), jnp.int32)))
    return found, rank, rounds, blocks * B


def member_sorted(set_hi, set_lo, set_n, q_hi, q_lo):
    """Membership probe (see rank_sorted)."""
    found, _ = rank_sorted(set_hi, set_lo, set_n, q_hi, q_lo)
    return found


#: Slots one iteration of the merge's loops moves (the block is a shape:
#: ``min(size, MERGE_BLOCK)``; how many blocks run is a device value).
#: Timed on the chip at 16,384, 65,536 and 262,144: a full set costs the
#: same at each, a small one least at the smallest (PERF.md section 6, PR 33).
MERGE_BLOCK = 16384


def merge_ranked(set_hi, set_lo, set_n, new_hi, new_lo, new_rank, new_n, out_cap):
    """Scatter-merge: sorted visited set + compacted sorted new pairs.

    new_hi/new_lo: [M] with the first new_n entries sorted ascending and
    disjoint from the visited set; new_rank: each new entry's insertion index
    in the visited set (from rank_sorted).  Builds the merged sorted array
    by scattering instead of re-sorting V+M keys:
      target(new[j])     = rank[j] + j
      target(visited[i]) = i + (# new entries below visited[i])
    New and visited are disjoint, so new[j] < visited[i] exactly when
    rank[j] <= i: the count is the inclusive prefix sum of a histogram of
    the live ranks, and nothing is searched.  Lanes j >= new_n may hold any
    rank and are masked out of it.  Only the LIVE entries move: the output
    starts as sentinels, and two rolled loops over fixed blocks of
    ``MERGE_BLOCK`` slots place the first new_n new entries and the first
    set_n visited entries, ``ceil(n / block)`` iterations each (a device
    value), so a merge costs what the set holds and not what its capacity
    pins; a set of one block runs one iteration.  Targets past out_cap
    drop.  Returns (hi[out_cap], lo[out_cap], n).  The ``dedup_merge``
    stage.
    """
    hi, lo, n, _slots = merge_counted(
        set_hi, set_lo, set_n, new_hi, new_lo, new_rank, new_n, out_cap
    )
    return hi, lo, n


def merge_counted(set_hi, set_lo, set_n, new_hi, new_lo, new_rank, new_n,
                  out_cap):
    """:func:`merge_ranked` and what it cost: -> (hi, lo, n, slots) with
    ``slots`` int32[2]: the slots this merge's loops touched (blocks run x
    block size, both sides: a device value) and the slots a merge over the
    whole capacity touches (``cap + M``, a shape).  The level programs sum
    it over their merges and hand it to the host with their counts (level
    record ``merge_slots`` / ``merge_slots_plain``)."""
    with jax.named_scope(_MERGE):
        cap, M = set_hi.shape[0], new_hi.shape[0]
        B, BM = min(cap, MERGE_BLOCK), min(M, MERGE_BLOCK)
        set_n = jnp.asarray(set_n, jnp.int32)
        new_n = jnp.asarray(new_n, jnp.int32)
        blocks = (jnp.clip(set_n, 0, cap) + (B - 1)) // B
        blocks_new = (jnp.clip(new_n, 0, M) + (BM - 1)) // BM
        # a list that is no multiple of its block: the last block starts
        # early (a slice must lie inside its operand) and overlaps the one
        # before it

        def place_new(k, carry):
            out_hi, out_lo, hist = carry
            s = jnp.minimum(k * BM, M - BM)
            j = s + jnp.arange(BM, dtype=jnp.int32)
            # the overlap is masked: its ranks are counted once
            live = (j >= k * BM) & (j < new_n)
            rank = jax.lax.dynamic_slice(new_rank, (s,), (BM,))
            tgt = jnp.where(live, rank + j, out_cap)
            # new entries below each visited slot (a rank of cap, above
            # every slot, drops with the dead lanes)
            hist = hist.at[jnp.where(live, rank, cap)].add(1, mode="drop")
            hi = jax.lax.dynamic_slice(new_hi, (s,), (BM,))
            lo = jax.lax.dynamic_slice(new_lo, (s,), (BM,))
            return (out_hi.at[tgt].set(hi, mode="drop"),
                    out_lo.at[tgt].set(lo, mode="drop"), hist)

        def move_visited(k, carry):
            out_hi, out_lo = carry
            s = jnp.minimum(k * B, cap - B)
            i = s + jnp.arange(B, dtype=jnp.int32)
            # the overlap moves again, to the same slots
            tgt = jnp.where(
                i < set_n, i + jax.lax.dynamic_slice(shift, (s,), (B,)),
                out_cap)
            hi = jax.lax.dynamic_slice(set_hi, (s,), (B,))
            lo = jax.lax.dynamic_slice(set_lo, (s,), (B,))
            return (out_hi.at[tgt].set(hi, mode="drop"),
                    out_lo.at[tgt].set(lo, mode="drop"))

        sent = jnp.uint32(SENT)
        out_hi, out_lo, hist = jax.lax.fori_loop(
            0, blocks_new, place_new,
            (jnp.full((out_cap,), sent), jnp.full((out_cap,), sent),
             jnp.zeros((cap,), jnp.int32)),
        )
        # the streaming passes over the capacity that stay (three fills,
        # this prefix sum) cost 1.5 ms at 4,194,304 slots where scattering
        # them cost 59.5 (PERF.md section 6, PR 33)
        shift = jnp.cumsum(hist)
        out_hi, out_lo = jax.lax.fori_loop(
            0, blocks, move_visited, (out_hi, out_lo)
        )
        slots = jnp.stack(
            [blocks * B + blocks_new * BM, jnp.int32(cap + M)]
        )
        return out_hi, out_lo, set_n + new_n, slots
