"""Device-resident open-addressing fingerprint set (the HBM FPSet).

The sorted-pair visited set (ops/dedup.py) pays O(capacity) per level to
scatter-merge new fingerprints into sorted order — profiled at 74% of the
whole level step on the flagship bench (engine/bfs.py notes).  This module
replaces sort + binary-search probe + rank-merge with one structure and one
kernel: a power-of-two hash table of (hi, lo) uint32 pairs in device
memory, probed and claimed with fixed-trip-count linear probing — O(batch)
per level, independent of table size, with all-deterministic tie-breaks
(scatter-min claims), so BFS discovery order and counterexample traces stay
reproducible.

Duplicate handling inside one batch needs no pre-sort: rows carrying the
same fingerprint land on the same probe slot; the claim scatter-min picks
the lowest row index as the winner, the losers observe the winner's
fingerprint on re-read and report "seen".

Insertion is insert-or-find: after `probe_insert`, `is_new[i]` is True for
exactly one row per distinct fingerprint not already in the table.  The
caller must re-run with a grown table when `overflow` is set (a row
exhausted its probe budget) — with load kept under ~0.5 the expected probe
count is ~1.5 and P=32 budgets are astronomically safe, but correctness
never depends on that: overflow is detected, never silently dropped.

TPU notes: fingerprints ride as two uint32 lanes (no 64-bit int ALU); the
probe loop is a `lax.fori_loop` with static trip count; gathers/scatters
are the only memory ops and vectorize over the batch.  Sharded engines give
each shard its own table over its owned fingerprint range.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# empty-slot sentinel: the all-ones pair never occurs as a fingerprint.
# Hashed mode: ops/fingerprint.hash_pair remaps it.  Exact64 mode: packing
# demotes any schema that could legally pack to all-ones in both lanes to
# hashed fingerprints at build time (StateSpec._may_hit_sentinel,
# ops/packing.py) — the guarantee is enforced by construction, not assumed.
# Engine padding is masked before reaching the table.
SENT = 0xFFFFFFFF


def _fmix32(h):
    """murmur3 finalizer: full 32-bit avalanche."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def new_table(cap: int):
    """Empty table of `cap` slots (cap must be a power of two)."""
    assert cap & (cap - 1) == 0, "hash table capacity must be a power of 2"
    return (
        jnp.full((cap,), SENT, jnp.uint32),
        jnp.full((cap,), SENT, jnp.uint32),
    )


CLAIM_FREE = 0x7FFFFFFF  # int32 max: "this slot was never claimed"


@jax.named_scope("kspec.dedup_probe")  # engine/pipeline.py STAGES
def probe_insert(t_hi, t_lo, q_hi, q_lo, valid, max_probes: int = 32, claim=None):
    """Insert-or-find a batch of fingerprints (the hash backend's
    ``dedup_probe`` stage).

    t_hi/t_lo: uint32[cap] table (cap power of two).
    q_hi/q_lo: uint32[M] batch; `valid` masks live rows.
    claim: optional int32[cap] claim lattice carried ACROSS calls (see
    below); pass the one returned by the previous call (or new_claim) to
    avoid the O(cap) per-call initialization, or None to allocate fresh.
    Returns (t_hi', t_lo', claim', is_new[M], n_new, overflow).

    Per probe round, every still-pending row:
      1. reads its current slot;
      2. on fingerprint match -> seen (done, not new);
      3. on empty slot -> claims it via scatter-min of the row index, the
         winner writes its pair and is new; losers (in-batch duplicates or
         colliding strangers) re-read and either match (dup, done) or move
         to the next slot;
      4. on a foreign occupant -> moves to the next slot.

    The claim lattice never needs resetting — between rounds or between
    calls: a slot's claim is only consulted in the round that scatter-mins
    into it, and every claimed slot receives its winner's pair in that
    same round, so a slot carrying a stale claim is never empty again and
    its claim is never read.  (Claim values are row indices, so the free
    sentinel is int32-max and min-scatter always prefers a real row.)
    """
    cap = t_hi.shape[0]
    M = q_hi.shape[0]
    mask = jnp.uint32(cap - 1)
    sent = jnp.uint32(SENT)
    rows = jnp.arange(M, dtype=jnp.int32)
    # full avalanche before slotting: exact64-mode fingerprints are raw
    # packed states whose low bits carry almost no entropy (structured
    # fields), and linear probing collapses under clustered home slots —
    # murmur fmix on both lanes makes the slot uniform for either mode
    pos0 = ((_fmix32(q_lo ^ _fmix32(q_hi)) & mask)).astype(jnp.int32)
    if claim is None:
        claim = new_claim(cap)

    def body(_, carry):
        t_hi, t_lo, claim, pos, pending, is_new = carry
        cur_hi = t_hi[pos]
        cur_lo = t_lo[pos]
        match = pending & (cur_hi == q_hi) & (cur_lo == q_lo)
        empty = pending & (cur_hi == sent) & (cur_lo == sent)
        # deterministic claim: lowest row index wins the slot
        claim = claim.at[jnp.where(empty, pos, cap)].min(rows, mode="drop")
        won = empty & (claim[pos] == rows)
        t_hi = t_hi.at[jnp.where(won, pos, cap)].set(q_hi, mode="drop")
        t_lo = t_lo.at[jnp.where(won, pos, cap)].set(q_lo, mode="drop")
        # losers of the claim re-check the slot next round (it now holds
        # the winner's pair: an in-batch duplicate will match there)
        advance = pending & ~match & ~won & ~empty
        pos = jnp.where(advance, (pos + 1) & (cap - 1), pos)
        pending = pending & ~match & ~won
        is_new = is_new | won
        return t_hi, t_lo, claim, pos, pending, is_new

    t_hi, t_lo, claim, _pos, pending, is_new = jax.lax.fori_loop(
        0,
        max_probes,
        body,
        (t_hi, t_lo, claim, pos0, valid, jnp.zeros((M,), bool)),
    )
    return (
        t_hi,
        t_lo,
        claim,
        is_new,
        jnp.sum(is_new, dtype=jnp.int32),
        jnp.any(pending),
    )


def new_claim(cap: int):
    """Fresh claim lattice for a `cap`-slot table (see probe_insert)."""
    return jnp.full((cap,), CLAIM_FREE, jnp.int32)


@jax.jit
def _insert_all(t_hi, t_lo, q_hi, q_lo):
    """probe_insert of an all-live batch, jitted once per shape: the eager
    call would trace and compile its probe loop anew every time (a fresh
    loop body each call), in every run that builds or grows a table."""
    return probe_insert(t_hi, t_lo, q_hi, q_lo,
                        jnp.ones(q_hi.shape[0], bool))


def table_from_pairs(hi, lo, min_cap: int = 1 << 10, chunk: int = 1 << 20):
    """Build a table containing exactly the given (assumed-distinct) pairs.

    Streams the pairs through probe_insert in chunks; a probe-budget
    overflow (possible in principle even at low load, just improbable)
    grows the table and retries instead of failing — shared by table
    growth and every checkpoint-resume/init reinsertion path.
    Returns (t_hi, t_lo) with capacity >= max(min_cap, 4*len) rounded up
    to a power of two.
    """
    n = int(hi.shape[0])
    cap = max(int(min_cap), 4 * n, 2)
    cap = 1 << (cap - 1).bit_length()
    while True:
        nh, nl = new_table(cap)
        ok = True
        for start in range(0, n, chunk):
            h = jnp.asarray(hi[start : start + chunk])
            lo_c = jnp.asarray(lo[start : start + chunk])
            nh, nl, _c, _m, _n2, ovf = _insert_all(nh, nl, h, lo_c)
            if bool(ovf):  # pragma: no cover - improbable at 1/4 load
                ok = False
                break
        if ok:
            return nh, nl
        cap *= 2


def rehash_into(t_hi, t_lo, new_cap: int, chunk: int = 1 << 20):
    """Grow: re-insert every live pair into a (>=) `new_cap` table.

    Host-driven (runs between BFS levels, amortized O(n) per doubling);
    streams the old table in chunks through probe_insert so peak memory is
    old + new + one chunk.
    """
    import numpy as np

    old_hi = np.asarray(t_hi)
    old_lo = np.asarray(t_lo)
    live = ~((old_hi == SENT) & (old_lo == SENT))
    return table_from_pairs(old_hi[live], old_lo[live], min_cap=new_cap, chunk=chunk)
