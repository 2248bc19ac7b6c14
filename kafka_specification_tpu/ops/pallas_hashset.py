"""Pallas TPU kernel: the HBM open-addressing FPSet probe (ops/hashset).

The device-resident dedup path's dominant kernel is `probe_insert` — the
insert-or-find over the open-addressing fingerprint table.  This module
provides the Pallas formulation of the ACTUAL dedup kernel, not just the
fingerprinting (VERDICT r3 item 7).

Design — sequential grid, row-serial probing:

- TPU Pallas grids execute sequentially on a core, so the racy part of
  the jnp path (the claim-lattice scatter-min that arbitrates *parallel*
  claims to one empty slot) is unnecessary here: rows are processed in
  index order, and "first claimant wins" IS "lowest row index wins".
  The observable contract is identical to hashset.probe_insert in
  non-overflow runs: `is_new[i]` marks exactly the lowest-index row of
  each distinct fingerprint not already in the table (winner identity
  matters — it carries the parent/action attribution for traces).
  Probe-path layouts can diverge from the jnp path only in mixed
  collision chains, which never changes membership or winners, only
  slot positions (and, in pathological cases, the overflow flag — which
  merely triggers the caller's grow-and-rerun, exact either way).
- The table rides as an input/output-aliased ref read and written in
  place across grid steps; the batch is blocked into VMEM.
- Row-serial scalar probing is the correctness-first formulation (the
  per-row dependent-load chain is what a hash probe IS); a vectorized
  variant (probe rounds across the whole resident block with in-register
  duplicate arbitration) is the staged next step once hardware profiling
  shows where this one lands.

Bit-identity with the jnp path is pinned by tests/test_pallas.py in
interpret mode on CPU; KSPEC_USE_PALLAS=1 routes the engine's
device-hash backend through this kernel (engine/bfs).

Hardware status (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21 —
scripts/tpu_mosaic_ladder.py): Mosaic REFUSES all three probe kernels
(`probe_insert_pallas` at group=1 and group=8, also at MAX_VMEM_CAP, and
`probe_insert_pallas_hbm`) with one message,

    MosaicError: INTERNAL: Mosaic failed to compile TPU kernel: cannot
    statically prove that index in dimension 0 is a multiple of 256
    ... "vector.load"(%13, %18) : (memref<256xi32,
    #tpu.memory_space<vmem>>, index) -> vector<1xi32>

— a dynamic index into a rank-1 VMEM ref must be provably 256-aligned.
The ladder's single-construct rungs fail the same way (`dyn_read`,
`dyn_slice`, `scalar_loop`), while vector / static-index kernels and the
Pallas fingerprint kernel compile and run bit-identical to jnp.  Every
scalar access these kernels are built from is such an index: the batch
reads `q_hi_ref[i]`, the table probes `t_hi_ref[pos]`, the (1,)-slice
stores, and in the HBM variant the batch reads and `is_new` stores around
the DMAs.  The refusal is of the formulation, not of one line; whether to
re-formulate (SMEM batch refs, a 2-D table addressed by sublane + lane
mask) or delete is ROADMAP S7/D4's call.  The jnp probe_insert
(ops/hashset) is the device-hash path on hardware; under
KSPEC_USE_PALLAS=1 on a TPU the engine raises rather than substituting it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import hashset
from .hashset import SENT

# The kernel stages BOTH table lanes (uint32[cap] x 2) plus the batch block
# in VMEM (~16 MiB/core on current TPUs).  8 bytes/slot => cap 2^20 is
# 8 MiB of table, leaving headroom for the batch block, outputs and
# compiler scratch.  Beyond this the pallas_call simply fails to fit —
# callers must take the jnp probe path (HBM-resident table) instead; the
# engine gates on fits_vmem() and falls back loudly (engine/bfs), or —
# with KSPEC_PALLAS_HBM=1 — routes to probe_insert_pallas_hbm, whose
# table stays in HBM (pl.ANY + per-slot DMA) and has no such gate.
MAX_VMEM_CAP = 1 << 20


def fits_vmem(cap: int) -> bool:
    """True when a cap-slot table can be VMEM-staged by this kernel.
    KSPEC_PALLAS_VMEM_CAP overrides the limit (shrink it to force the
    HBM-resident kernel on small workloads)."""
    import os

    lim = int(os.environ.get("KSPEC_PALLAS_VMEM_CAP", MAX_VMEM_CAP))
    return cap <= lim


def _kernel(max_probes, q_hi_ref, q_lo_ref, valid_ref, _ti, _tl,
            t_hi_ref, t_lo_ref, is_new_ref):
    """One batch block: probe/insert each row serially (see module doc).

    _ti/_tl are the aliased input views of the table; all access goes
    through the output refs (same memory) so grid steps see each other's
    inserts.

    is_new_ref is int32 and TERNARY: 0 = seen / invalid, 1 = new
    (this row claimed the slot), 2 = probe-budget overflow (row still
    pending after max_probes).  Real-TPU rank-1 tiling rejects both a
    (1,)-block scalar output and bool blocks at the engine's 256-row
    alignment (first hardware runs, July 2026), so the
    overflow flag rides in the one well-tiled output instead of its own
    lane, and the wrapper splits the encoding."""
    block = q_hi_ref.shape[0]
    cap = t_hi_ref.shape[0]
    mask = jnp.uint32(cap - 1)
    sent = jnp.uint32(SENT)

    def row_body(i, carry):
        qh = q_hi_ref[i]
        ql = q_lo_ref[i]
        v = valid_ref[i] != 0
        # same slotting as hashset.probe_insert (full avalanche on both
        # lanes so exact64 packs spread uniformly)
        pos0 = (hashset._fmix32(ql ^ hashset._fmix32(qh)) & mask).astype(
            jnp.int32
        )

        def probe_body(_p, carry):
            pos, pending, isnew = carry
            cur_hi = t_hi_ref[pos]
            cur_lo = t_lo_ref[pos]
            match = pending & (cur_hi == qh) & (cur_lo == ql)
            empty = pending & (cur_hi == sent) & (cur_lo == sent)
            # sequential claim: first (lowest-index) claimant wins; the
            # masked store keeps the slot unchanged for non-claimants.
            # (1,)-slice stores, not scalar stores: real-TPU lowering
            # rejects scalar stores to VMEM (hardware window 2)
            t_hi_ref[pl.ds(pos, 1)] = jnp.where(empty, qh, cur_hi)[None]
            t_lo_ref[pl.ds(pos, 1)] = jnp.where(empty, ql, cur_lo)[None]
            isnew = isnew | empty
            advance = pending & ~match & ~empty
            pos = jnp.where(advance, (pos + 1) & jnp.int32(cap - 1), pos)
            pending = pending & ~match & ~empty
            return pos, pending, isnew

        pos, pending, isnew = jax.lax.fori_loop(
            0, max_probes, probe_body, (pos0, v, jnp.bool_(False))
        )
        is_new_ref[pl.ds(i, 1)] = jnp.where(
            pending, jnp.int32(2), jnp.where(isnew, jnp.int32(1), jnp.int32(0))
        )[None]
        return carry

    jax.lax.fori_loop(0, block, row_body, 0)


def _kernel_grouped(max_probes, group, q_hi_ref, q_lo_ref, valid_ref, _ti,
                    _tl, t_hi_ref, t_lo_ref, is_new_ref):
    """Interleaved probe: G independent row chains in flight per round.

    TPU Pallas has no vector gather over VMEM (dynamic indexing is scalar
    or contiguous-slice — pallas guide "Dynamic Indexing"), so a hash
    probe is irreducibly a dependent-load chain PER ROW.  What CAN be
    parallelized is memory-level parallelism ACROSS rows: each round
    issues G independent scalar loads (no cross-dependences, so the
    scalar unit pipelines them) and then resolves the G rows in
    row-index order entirely in registers.

    In-register arbitration keeps the sequential-claim contract: row g's
    loaded value is patched with any slot written by rows h<g in the SAME
    round (ascending h, so the latest write wins), which makes the commit
    order strictly row-index order.  Same-fp rows share one probe chain,
    so the lowest-index row claims and the rest observe its write as a
    match — `is_new` winners are identical to the row-serial kernel and
    the jnp path.  Mixed collision chains may land at different slot
    POSITIONS than the serial kernel (same caveat as the module header:
    membership and winners never differ; pathological near-full tables
    can differ in the overflow flag, which only triggers the caller's
    grow-and-rerun).
    """
    block = q_hi_ref.shape[0]
    cap = t_hi_ref.shape[0]
    mask = jnp.uint32(cap - 1)
    sent = jnp.uint32(SENT)

    def group_body(gi, carry):
        base = gi * group
        qh = [q_hi_ref[base + g] for g in range(group)]
        ql = [q_lo_ref[base + g] for g in range(group)]
        pos0 = [
            (hashset._fmix32(ql[g] ^ hashset._fmix32(qh[g])) & mask).astype(
                jnp.int32
            )
            for g in range(group)
        ]
        pend0 = [valid_ref[base + g] != 0 for g in range(group)]

        def probe_round(_p, carry):
            pos, pending, isnew = carry
            # phase 1: G independent loads (the MLP win — no
            # cross-dependences inside one round)
            cur_hi = [t_hi_ref[pos[g]] for g in range(group)]
            cur_lo = [t_lo_ref[pos[g]] for g in range(group)]
            # phase 2: resolve in row-index order, patching each row's
            # view with same-round writes by earlier rows
            npos, npend, nnew = list(pos), list(pending), list(isnew)
            writes = []  # (slot, hi, lo) committed this round, ascending
            for g in range(group):
                ch, cl = cur_hi[g], cur_lo[g]
                for ws, wh, wl in writes:
                    hit = pos[g] == ws
                    ch = jnp.where(hit, wh, ch)
                    cl = jnp.where(hit, wl, cl)
                match = pending[g] & (ch == qh[g]) & (cl == ql[g])
                empty = pending[g] & (ch == sent) & (cl == sent)
                sh = jnp.where(empty, qh[g], ch)
                sl = jnp.where(empty, ql[g], cl)
                t_hi_ref[pl.ds(pos[g], 1)] = sh[None]
                t_lo_ref[pl.ds(pos[g], 1)] = sl[None]
                writes.append((pos[g], sh, sl))
                nnew[g] = isnew[g] | empty
                advance = pending[g] & ~match & ~empty
                npos[g] = jnp.where(
                    advance, (pos[g] + 1) & jnp.int32(cap - 1), pos[g]
                )
                npend[g] = advance
            return tuple(npos), tuple(npend), tuple(nnew)

        pos, pending, isnew = jax.lax.fori_loop(
            0,
            max_probes,
            probe_round,
            (
                tuple(pos0),
                tuple(pend0),
                tuple(jnp.bool_(False) for _ in range(group)),
            ),
        )
        for g in range(group):
            # ternary encoding (see _kernel): 2 = still pending after
            # max_probes rounds = probe-budget overflow
            is_new_ref[pl.ds(base + g, 1)] = jnp.where(
                pending[g],
                jnp.int32(2),
                jnp.where(isnew[g], jnp.int32(1), jnp.int32(0)),
            )[None]
        return carry

    jax.lax.fori_loop(0, block // group, group_body, 0)


def _kernel_hbm(max_probes, q_hi_ref, q_lo_ref, valid_ref, _ti, _tl,
                t_hi_any, t_lo_any, is_new_ref,
                s_rhi, s_rlo, s_whi, s_wlo, sem):
    """HBM-resident probe: the table never enters VMEM (round-5 item —
    lifts the MAX_VMEM_CAP gate for real workloads, where
    cap = pow2(4*states) blows the VMEM-staged kernel).

    The table lanes ride in `pl.ANY` memory space (HBM on hardware);
    every probe is an explicit single-slot DMA into a VMEM scratch, and
    every commit a single-slot DMA back (unconditional write-back of
    either the claim or the unchanged value — the sequential grid makes
    the read-modify-write race-free, same argument as the row-serial
    kernel).  The hi/lo lanes' DMAs are started together so the two
    loads overlap.  Winners/membership are bit-identical to the VMEM
    kernels and the jnp path (same probe order); per-element DMA is the
    correctness-first formulation — a block-granular double-buffered
    variant is the staged next step once a hardware window profiles the
    descriptor overhead."""
    block = q_hi_ref.shape[0]
    cap = t_hi_any.shape[0]
    mask = jnp.uint32(cap - 1)
    sent = jnp.uint32(SENT)

    def row_body(i, carry):
        qh = q_hi_ref[i]
        ql = q_lo_ref[i]
        v = valid_ref[i] != 0
        pos0 = (hashset._fmix32(ql ^ hashset._fmix32(qh)) & mask).astype(
            jnp.int32
        )

        def probe_body(_p, carry):
            pos, pending, isnew = carry
            r_hi = pltpu.make_async_copy(
                t_hi_any.at[pl.ds(pos, 1)], s_rhi, sem.at[0]
            )
            r_lo = pltpu.make_async_copy(
                t_lo_any.at[pl.ds(pos, 1)], s_rlo, sem.at[1]
            )
            r_hi.start()
            r_lo.start()
            r_hi.wait()
            r_lo.wait()
            cur_hi = s_rhi[0]
            cur_lo = s_rlo[0]
            match = pending & (cur_hi == qh) & (cur_lo == ql)
            empty = pending & (cur_hi == sent) & (cur_lo == sent)
            s_whi[:] = jnp.where(empty, qh, cur_hi)[None]
            s_wlo[:] = jnp.where(empty, ql, cur_lo)[None]
            w_hi = pltpu.make_async_copy(
                s_whi, t_hi_any.at[pl.ds(pos, 1)], sem.at[2]
            )
            w_lo = pltpu.make_async_copy(
                s_wlo, t_lo_any.at[pl.ds(pos, 1)], sem.at[3]
            )
            w_hi.start()
            w_lo.start()
            w_hi.wait()
            w_lo.wait()
            isnew = isnew | empty
            advance = pending & ~match & ~empty
            pos = jnp.where(advance, (pos + 1) & jnp.int32(cap - 1), pos)
            return pos, advance, isnew

        pos, pending, isnew = jax.lax.fori_loop(
            0, max_probes, probe_body, (pos0, v, jnp.bool_(False))
        )
        is_new_ref[pl.ds(i, 1)] = jnp.where(
            pending, jnp.int32(2), jnp.where(isnew, jnp.int32(1), jnp.int32(0))
        )[None]
        return carry

    jax.lax.fori_loop(0, block, row_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("max_probes", "block_rows", "interpret"),
)
@jax.named_scope("kspec.dedup_probe")  # engine/pipeline.py STAGES
def probe_insert_pallas_hbm(
    t_hi,
    t_lo,
    q_hi,
    q_lo,
    valid,
    max_probes: int = 32,
    block_rows: int = 4096,
    interpret: bool = False,
):
    """HBM-resident insert-or-find (no table-size VMEM gate); same
    contract and return shape as probe_insert_pallas."""
    import math

    cap = t_hi.shape[0]
    m = q_hi.shape[0]
    block = math.gcd(m, block_rows)
    grid = (m // block,)
    # real-TPU rank-1 tiling rejects a (1,)-block scalar output and bool
    # blocks at the engine's 256-row alignment (first hardware runs,
    # July 2026) — so flags cross the pallas_call boundary as ONE
    # ternary int32 lane (0 = seen, 1 = new, 2 = probe overflow) and the
    # wrapper splits the encoding.
    t_hi2, t_lo2, is_new3 = pl.pallas_call(
        functools.partial(_kernel_hbm, max_probes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap,), jnp.uint32),
            jax.ShapeDtypeStruct((cap,), jnp.uint32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.uint32),
            pltpu.VMEM((1,), jnp.uint32),
            pltpu.VMEM((1,), jnp.uint32),
            pltpu.VMEM((1,), jnp.uint32),
            pltpu.SemaphoreType.DMA((4,)),
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        name="kspec_dedup_probe",
    )(q_hi, q_lo, jnp.asarray(valid, jnp.int32), t_hi, t_lo)
    is_new = is_new3 == 1
    return (
        t_hi2,
        t_lo2,
        is_new,
        jnp.sum(is_new, dtype=jnp.int32),
        jnp.any(is_new3 == 2),
    )


@functools.partial(
    jax.jit,
    static_argnames=("max_probes", "block_rows", "interpret", "group"),
)
@jax.named_scope("kspec.dedup_probe")  # engine/pipeline.py STAGES
def probe_insert_pallas(
    t_hi,
    t_lo,
    q_hi,
    q_lo,
    valid,
    max_probes: int = 32,
    block_rows: int = 4096,
    interpret: bool = False,
    group: int = 1,
):
    """Pallas insert-or-find; same contract as hashset.probe_insert minus
    the claim lattice (sequential probing needs no parallel arbitration).

    Returns (t_hi', t_lo', is_new[M], n_new, overflow).  M must be a
    multiple of block_rows or smaller than it (the engine's buffers are
    powers of two).

    group > 1 selects the interleaved kernel (_kernel_grouped): `group`
    independent row chains probe per round, so the scalar unit pipelines
    their loads instead of serializing on one row's dependent-load chain;
    is_new winners and table membership are identical to group=1 (the
    in-register arbitration keeps commit order = row-index order).
    """
    import math

    cap = t_hi.shape[0]
    m = q_hi.shape[0]
    # largest divisor of m up to block_rows (engine buffers are 256-row
    # aligned, so blocks stay >= 256)
    block = math.gcd(m, block_rows)
    grid = (m // block,)
    if group > 1 and block % group == 0:
        kern = functools.partial(_kernel_grouped, max_probes, group)
    else:
        kern = functools.partial(_kernel, max_probes)
    # real-TPU rank-1 tiling rejects a (1,)-block scalar output and bool
    # blocks at the engine's 256-row alignment (first hardware runs,
    # July 2026) — so flags cross the pallas_call boundary as ONE
    # ternary int32 lane (0 = seen, 1 = new, 2 = probe overflow) and the
    # wrapper splits the encoding.
    t_hi2, t_lo2, is_new3 = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((cap,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((cap,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap,), jnp.uint32),
            jax.ShapeDtypeStruct((cap,), jnp.uint32),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        name="kspec_dedup_probe",
    )(q_hi, q_lo, jnp.asarray(valid, jnp.int32), t_hi, t_lo)
    is_new = is_new3 == 1
    return (
        t_hi2,
        t_lo2,
        is_new,
        jnp.sum(is_new, dtype=jnp.int32),
        jnp.any(is_new3 == 2),
    )
