"""Mesh-sharded BFS: the distributed engine (SURVEY.md §2.6).

TLC parallelizes with Java worker threads over a shared FPSet; the TPU-native
equivalent shards the frontier AND the fingerprint set across a 1-D device
mesh and exchanges ownership over ICI collectives:

- the frontier lives sharded across devices (axis 'd'); each device expands
  its shard with the same vmapped action kernels as the single-device engine
  (including the two-phase guard-sweep/compact expansion),
- every candidate successor is owned by the device selected by its
  fingerprint (owner = fp_lo mod D — fingerprint-range sharding),
- candidates are routed to their owner with bucket-by-owner `lax.all_to_all`
  (per-shard ICI traffic ≈ the candidate width, independent of mesh size —
  SURVEY §2.6), with `lax.all_gather` + ownership filtering kept as the
  simple fallback (exchange="all_gather"); the owner dedups them against its
  local sorted fingerprint shard and keeps its new states as its shard of
  the next frontier — hash ownership keeps shards balanced with no
  host-side reshuffle.

Everything runs under `jax.jit` + `shard_map` over a `jax.sharding.Mesh`, so
the same code drives 8 virtual CPU devices in CI, one real TPU chip, or a
v5e-8 pod slice — XLA inserts the ICI collectives.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.bfs import (
    AdaptiveCompact,
    CheckResult,
    Violation,
    _next_pow2,
    _Step,
    build_violation as _build_violation,
    chain_stamp,
    decode_packed,
    init_violation_result,
    readback_chain,
    u64 as _u64,
)
from ..engine.hostio import HostIO
from ..engine.pipeline import (
    WORK_FIELDS,
    counts_out,
    part,
    split_counts,
    stage,
    work_counts,
    work_record,
)
from ..ops import devlevel
from ..pipeline_registry import resolve_pipeline
from ..models.base import Model
from ..obs import metrics as _met
from ..obs.ledger import PROCESS as _LEDGER
from ..obs.observer import RunObserver
from ..obs.tracer import now as _now
from ..ops import dedup, hashset
from ..resilience import integrity as _integ
from ..resilience.checkpoints import CheckpointStore
from ..resilience.faults import FaultPlan
from ..resilience.heartbeat import append_jsonl, heartbeat_record
from ..resilience.integrity import IntegrityError
from ..resilience.resources import (
    ResourceExhausted,
    ResourceGovernor,
    is_disk_full,
)
from ..resilience.retry import ChunkRetryHandler
from ..storage.parent_log import ShardedParentLog
from ..utils.platform_guard import device_stamp
from .multihost import (
    fetch_global,
    is_coordinator,
    is_multiprocess,
    or_across_processes,
    put_global,
)
from ..ops.fingerprint import fingerprint_lanes


# per-shard hash-table floor (module-level so tests can shrink it to
# exercise growth at small state counts)
_HASH_MIN_CAP = 1 << 14

#: stage scope of the exchange body (routing, codec encode, the collective,
#: decode): the one stage of the device vocabulary (engine/pipeline.py
#: STAGES) that only the sharded programs have.  The framing digests on
#: either side of it carry the `digest` scope.
_EXCHANGE = "kspec.exchange"

#: cache tags (and, through pipeline.program_name, module names) of the
#: three sharded program kinds: the per-chunk step, the whole-level
#: program, and its deferred-probe twin for the host visited backend
STEP_TAG, LEVEL_TAG, LEVEL_HOST_TAG = "shs", "shl", "shh"
#: ... and of the invariant pass over host-held rows (the initial states;
#: the last frontier when a bound cut the search)
INVARIANT_TAG = "shi"


def _shard_tables_from_pairs(per_shard, min_cap: int):
    """Uniform-capacity per-shard tables from per-shard (hi, lo) pairs.

    All shards must share one capacity (the shard_map operand is one
    [D, cap] array); if any shard's build grows past the target (probe
    overflow — improbable at 1/4 load but handled, never asserted), every
    shard is rebuilt at the larger capacity.  Returns (vhi, vlo, cap)."""
    cap = _next_pow2(max(min_cap, 4 * max((len(h) for h, _ in per_shard), default=1)))
    while True:
        ths, tls = [], []
        redo = False
        for h, lo in per_shard:
            th, tl = hashset.table_from_pairs(h, lo, min_cap=cap)
            if th.shape[0] != cap:
                cap = int(th.shape[0])
                redo = True
                break
            ths.append(np.asarray(th))
            tls.append(np.asarray(tl))
        if not redo:
            return np.stack(ths), np.stack(tls), cap


def _grow_hash_tables(dev_vhi, dev_vlo, new_cap: int, shard1, io):
    """Rehash every shard's HBM hash table into (>=) `new_cap` slots.

    Host-driven (runs between chunk attempts, amortized O(n) per
    doubling); fetch_global/put_global (counted through `io`, the
    engine's HostIO) keep it multi-process-correct — every process
    computes the identical grown tables.  Returns (dev_vhi, dev_vlo,
    cap)."""
    old_hi = io.fetch(dev_vhi)  # [D, cap]
    old_lo = io.fetch(dev_vlo)
    live = ~((old_hi == hashset.SENT) & (old_lo == hashset.SENT))
    per_shard = [
        (old_hi[d][live[d]], old_lo[d][live[d]]) for d in range(old_hi.shape[0])
    ]
    nh, nl, cap = _shard_tables_from_pairs(per_shard, new_cap)
    return io.put(nh, shard1), io.put(nl, shard1), cap


def _norm_shift(bucket: int, shift: int) -> int:
    """Shift actually applied by the step for this bucket (single source of
    truth shared with check_sharded's buffer sizing)."""
    return 0 if (shift and (bucket >> shift) < 1) else shift


def _default_dest_w(T: int, D: int) -> int:
    return max(64, T // D)


def mesh_layouts(mesh: Mesh) -> dict:
    """EXPLICIT mesh-axis layouts for every mesh-resident tensor class
    (the sharding-rule pattern of SNIPPETS.md [1][3]): one named
    NamedSharding/PartitionSpec per logical tensor instead of the old
    implicit ``P('d')``-for-everything.  These are asserted in tests
    (tests/test_sharded_device.py), so a future real-ICI window inherits
    correct, named layouts for free:

    - ``frontier``  [D*B, K]  packed state rows: row dim sharded over the
      mesh axis, the K packed lanes replicated within a shard;
    - ``fvalid``    [D*B]     per-row validity mask, sharded like rows;
    - ``fpset``     [D, vcap] per-shard sorted fingerprint lanes (or the
      device-hash table slots): shard-major dim sharded, each shard's
      capacity dim local to its device;
    - ``pershard``  [D]       per-shard scalars (visited counts, pending
      lengths, chunk counts);
    - ``exchange``  [D*R(,K)] exchange receive buffers — what the
      all_to_all/all_gather fills, row dim sharded by OWNER shard.
    """
    return {
        "frontier": NamedSharding(mesh, P("d", None)),
        "fvalid": NamedSharding(mesh, P("d")),
        "fpset": NamedSharding(mesh, P("d", None)),
        "pershard": NamedSharding(mesh, P("d")),
        "exchange": NamedSharding(mesh, P("d", None)),
    }


def _fp_digest(dhi, dlo, mask):  # kspec: traced
    """Exchange framing record: order-invariant (count, xor_hi, xor_lo,
    sum_hi, sum_lo) over a masked fingerprint multiset — the payload's
    integrity stamp.  Computed per shard BEFORE and AFTER the
    collective; the host compares the global combines, so any bit the
    fabric (or a buffer in between) flips in a routed fingerprint
    desyncs the two (resilience.integrity).  uint32 lanes: TPUs have no
    64-bit ALU, and wrapping 32-bit sums/xors combine across shards
    just as commutatively."""
    z = jnp.uint32(0)
    with stage("digest"):
        mh = jnp.where(mask, dhi, z)
        ml = jnp.where(mask, dlo, z)
        return jnp.stack([
            jnp.sum(mask, dtype=jnp.uint32),
            jax.lax.reduce(mh, z, jax.lax.bitwise_xor, [0]),
            jax.lax.reduce(ml, z, jax.lax.bitwise_xor, [0]),
            jnp.sum(mh, dtype=jnp.uint32),
            jnp.sum(ml, dtype=jnp.uint32),
        ])


def _acc_digest(acc, dig, enabled):  # kspec: traced
    """Fold one chunk's [5] framing digest into a running per-level
    accumulator with the SAME combine rule the host applies across
    shards: counts and wrapping sums add, xors xor.  `enabled` masks
    out chunks the serial path would have discarded (overflowed
    attempts)."""
    with stage("digest"):
        z = jnp.zeros((5,), jnp.uint32)
        d = jnp.where(enabled, dig, z)
        return jnp.stack([
            acc[0] + d[0],
            acc[1] ^ d[1],
            acc[2] ^ d[2],
            acc[3] + d[3],
            acc[4] + d[4],
        ])


def _combine_digs(dig: np.ndarray) -> tuple:
    """Host-side global combine of per-shard [D, 5] framing digests
    (counts sum exactly, xors xor, wrapping-u32 sums wrap) — one shared
    implementation for the per-chunk and the device-level compares."""
    s64 = dig.astype(np.uint64)
    return (
        int(dig[:, 0].astype(np.int64).sum()),
        int(np.bitwise_xor.reduce(dig[:, 1])),
        int(np.bitwise_xor.reduce(dig[:, 2])),
        int(s64[:, 3].sum() & np.uint64(0xFFFFFFFF)),
        int(s64[:, 4].sum() & np.uint64(0xFFFFFFFF)),
    )


def _make_exchange(D: int, W: int, R: int, K: int, exchange: str,
                   compress: bool):
    """Build the traced per-chunk candidate exchange — ONE source for
    the per-chunk sharded step and the device-resident level program
    (the two must not drift on routing, codec or framing semantics).

    Returns fn(hi, lo, cand, parent_g, actid, valid, me) ->
    (r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest) with the received
    buffers R rows wide; see _make_sharded_step's docstring for the
    routing/codec/bit-identity contract."""
    sent = jnp.uint32(dedup.SENT)
    a2a = lambda x: jax.lax.all_to_all(  # noqa: E731
        x, "d", split_axis=0, concat_axis=0, tiled=True
    )
    if exchange == "all_to_all" and compress:
        from ..ops import fpcompress as _fpc

        Wr = max(32, W // 2)  # compact row budget (valid-first rows)
        NWc = _fpc.default_stream_words(W)

        def route(hi, lo, cand, parent_g, actid, valid, me):  # kspec: traced
            owner = jnp.where(
                valid, (lo % jnp.uint32(D)).astype(jnp.int32), D
            )
            s_hi, s_lo, s_cand, s_par, s_act, cnts = [], [], [], [], [], []
            for d in range(D):
                mask = owner == d
                cnts.append(jnp.sum(mask, dtype=jnp.int32))
                cpos = jnp.where(mask, jnp.cumsum(mask) - 1, W)
                s_hi.append(jnp.full((W,), sent).at[cpos].set(hi))
                s_lo.append(jnp.full((W,), sent).at[cpos].set(lo))
                s_cand.append(jnp.zeros((W, K), jnp.uint32).at[cpos].set(cand))
                s_par.append(jnp.full((W,), -1, jnp.int32).at[cpos].set(parent_g))
                s_act.append(jnp.full((W,), -1, jnp.int32).at[cpos].set(actid))
            b_hi = jnp.stack(s_hi)  # [D, W]
            b_lo = jnp.stack(s_lo)
            cnts_a = jnp.stack(cnts)  # [D]
            # STABLE per-bucket fingerprint sort (vmapped: ONE batched
            # sort program, not D copies — compile-time matters on this
            # engine's many step shapes): sentinels (max u64) sink last,
            # ties keep candidate order — the property the bit-identity
            # argument in _make_sharded_step's docstring rests on
            perm = jax.vmap(lambda h, l: jnp.lexsort((l, h)))(b_hi, b_lo)
            b_hi = jnp.take_along_axis(b_hi, perm, axis=1)
            b_lo = jnp.take_along_axis(b_lo, perm, axis=1)
            b_cand = jnp.take_along_axis(
                jnp.stack(s_cand), perm[:, :, None], axis=1
            )
            b_par = jnp.take_along_axis(jnp.stack(s_par), perm, axis=1)
            b_act = jnp.take_along_axis(jnp.stack(s_act), perm, axis=1)
            s_words, s_hdr, ovf_pack = jax.vmap(
                lambda h, l, c: _fpc.pack_sorted(h, l, c, NWc)
            )(b_hi, b_lo, cnts_a)
            ovf_dest = jnp.any(cnts_a > W) | jnp.any(
                ovf_pack | (cnts_a > Wr)
            )
            r_words = a2a(s_words)  # [D, NWc]
            r_hdr = a2a(s_hdr)  # [D, HDR + NB]
            r_cand_c = a2a(b_cand[:, :Wr])  # [D, Wr, K]
            r_par_c = a2a(b_par[:, :Wr])
            r_act_c = a2a(b_act[:, :Wr].astype(jnp.uint8))
            # in-jit decode per source segment; the framing digest the
            # caller computes runs over THESE decoded lanes, so fabric
            # integrity covers the packed stream, the header and the
            # codec
            dec_hi, dec_lo = jax.vmap(
                lambda wds, hd: _fpc.unpack_sorted(wds, hd, W)
            )(r_words, r_hdr)
            r_hi = dec_hi.reshape(R)
            r_lo = dec_lo.reshape(R)
            # compact rows pad back to W slots per source segment; the
            # live rows are the first cnt of each (valid-first after the
            # bucket sort), exactly aligned with the decoded lanes
            r_cand = (
                jnp.zeros((D, W, K), jnp.uint32)
                .at[:, :Wr].set(r_cand_c)
                .reshape(R, K)
            )
            r_parent = (
                jnp.full((D, W), -1, jnp.int32)
                .at[:, :Wr].set(r_par_c)
                .reshape(R)
            )
            r_act = (
                jnp.full((D, W), -1, jnp.int32)
                .at[:, :Wr].set(r_act_c.astype(jnp.int32))
                .reshape(R)
            )
            return r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest

    elif exchange == "all_to_all":

        def route(hi, lo, cand, parent_g, actid, valid, me):  # kspec: traced
            owner = jnp.where(
                valid, (lo % jnp.uint32(D)).astype(jnp.int32), D
            )
            s_hi, s_lo, s_cand, s_par, s_act, cnts = [], [], [], [], [], []
            for d in range(D):
                mask = owner == d
                cnt = jnp.sum(mask, dtype=jnp.int32)
                cnts.append(cnt)
                cpos = jnp.where(mask, jnp.cumsum(mask) - 1, W)
                s_hi.append(jnp.full((W,), sent).at[cpos].set(hi))
                s_lo.append(jnp.full((W,), sent).at[cpos].set(lo))
                s_cand.append(jnp.zeros((W, K), jnp.uint32).at[cpos].set(cand))
                s_par.append(jnp.full((W,), -1, jnp.int32).at[cpos].set(parent_g))
                s_act.append(jnp.full((W,), -1, jnp.int32).at[cpos].set(actid))
            ovf_dest = jnp.any(jnp.stack(cnts) > W)
            r_hi = a2a(jnp.stack(s_hi)).reshape(R)
            r_lo = a2a(jnp.stack(s_lo)).reshape(R)
            r_cand = a2a(jnp.stack(s_cand)).reshape(R, K)
            r_parent = a2a(jnp.stack(s_par)).reshape(R)
            r_act = a2a(jnp.stack(s_act)).reshape(R)
            return r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest

    else:

        def route(hi, lo, cand, parent_g, actid, valid, me):  # kspec: traced
            ovf_dest = jnp.bool_(False)
            r_hi = jax.lax.all_gather(hi, "d", tiled=True)  # [D*T]
            r_lo = jax.lax.all_gather(lo, "d", tiled=True)
            r_cand = jax.lax.all_gather(cand, "d", tiled=True)  # [D*T, K]
            r_valid = jax.lax.all_gather(valid, "d", tiled=True)
            r_parent = jax.lax.all_gather(parent_g, "d", tiled=True)
            r_act = jax.lax.all_gather(actid, "d", tiled=True)
            mine = r_valid & ((r_lo % jnp.uint32(D)).astype(jnp.int32) == me)
            r_hi = jnp.where(mine, r_hi, sent)
            r_lo = jnp.where(mine, r_lo, sent)
            return r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest

    def scoped(*operands):  # kspec: traced
        with jax.named_scope(_EXCHANGE):
            return route(*operands)

    return scoped


def _make_sharded_step(
    model: Model,
    mesh: Mesh,
    bucket: int,
    vcap: int,
    compact: Optional[int] = None,
    exchange: str = "all_to_all",
    dest_w: Optional[int] = None,
    with_merge: bool = True,
    hash_table: bool = False,
    compress: bool = False,
):
    """The sharded level step, un-jitted: check_sharded jits it under its
    cache tag through the model's step cache (engine.bfs._Step.cached).

    Global shapes (D = mesh size):
      frontier [D*bucket, K], fvalid [D*bucket]
      vhi/vlo  [D, vcap]  (per-device sorted fingerprint shard), vn [D]
    Returns per-shard compacted new states [D*R, K] (R = per-shard receive
    width), per-shard new counts [D], updated visited, violation flags, and
    two overflow flags (expansion compaction / destination buckets) — when
    either is set the outputs are incomplete and the caller must re-run the
    chunk at a larger width.

    compact: two-phase expansion shift (engine.bfs._Step.make_expand) — the
    guard sweep runs on the full lattice, update+pack+sort only on the
    enabled ~6%.

    exchange: how candidate fingerprints reach their owner shard
    (owner = fp_lo mod D — fingerprint-range sharding):
      - "all_to_all": bucket-by-owner + lax.all_to_all.  Each shard routes
        its candidates into D per-destination buckets of dest_w rows and
        sends each bucket only to its owner: per-shard ICI traffic is
        D*dest_w ≈ the candidate width, independent of mesh size (the
        SURVEY §2.6 design; docs/DISTRIBUTED.md has the padding-factor
        accounting).
      - "all_gather": every shard receives ALL candidates and filters to
        the ones it owns — D× the bytes, kept as the simple/robust
        fallback.

    compress (all_to_all only; KSPEC_OVERLAP's exchange leg, ROADMAP
    item 5): each destination bucket is stably SORTED by fingerprint
    (sentinels last), its fingerprint lanes ride the wire bit-packed/
    delta-encoded (ops/fpcompress — the padding tail packs to ~zero
    bits), its candidate rows/parents ride at a compacted half-width,
    and action ids travel as u8 — >=2x fewer exchange bytes per chunk.
    Decoding happens in-jit on the receiver, and the post-exchange
    framing digest is computed over the DECODED payload, so the PR 9
    fabric-integrity contract covers the codec itself.  Bit-identity
    holds because the per-bucket sort is STABLE: duplicate fingerprints
    keep their candidate order inside a bucket and buckets keep their
    source-shard order, so the receiver's stable lexsort elects exactly
    the winners the uncompressed path elects (same counts, same trace
    values).  A bucket too dense for its packed stream or compact row
    budget raises the destination-overflow flag and the chunk re-runs
    on the existing width ladder.
    """
    spec = model.spec
    expander = _Step(model)
    K, C = spec.num_lanes, expander.C
    D = mesh.devices.size
    # compact: None (full path), int (uniform legacy shift) or a per-action
    # width tuple (adaptive sizing — engine.bfs.make_expand handles both;
    # round-5 port of the single-device adaptive compact widths)
    if isinstance(compact, tuple):
        shift = compact
    else:
        shift = _norm_shift(bucket, int(compact) if compact else 0)
    expand = expander.make_expand(bucket, shift)
    T = expander.expand_width(bucket, shift)
    if exchange not in ("all_to_all", "all_gather"):
        raise ValueError(f"unknown exchange {exchange!r}")
    # per-destination row budget (all_to_all): default 4x headroom over a
    # uniform spread of the typical ~6%-enabled candidate load
    W = dest_w if dest_w is not None else _default_dest_w(T, D)
    R = D * W if exchange == "all_to_all" else D * T  # receive width
    route = _make_exchange(D, W, R, K, exchange, compress)

    def shard_body(frontier, fvalid, vhi, vlo, vn):  # kspec: traced
        # per-shard views: frontier [bucket, K], vhi [1, vcap], vn [1]
        vhi, vlo, vn = vhi[0], vlo[0], vn[0]
        me = jax.lax.axis_index("d")

        states = jax.vmap(spec.unpack)(frontier)
        en_pre, cand, valid, parent, actid, act_en, act_guard, ovf_expand = expand(
            frontier, states, fvalid
        )
        deadlocked = fvalid & ~jnp.any(en_pre, axis=1)

        sent = jnp.uint32(dedup.SENT)
        with stage("fingerprint"):
            hi, lo = fingerprint_lanes(cand, spec.exact64)
            hi = jnp.where(valid, hi, sent)
            lo = jnp.where(valid, lo, sent)
        # parent as a mesh-global frontier row id (survives the exchange)
        parent_g = me.astype(jnp.int32) * bucket + parent

        sent_dig = _fp_digest(hi, lo, valid)

        r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest = route(
            hi, lo, cand, parent_g, actid, valid, me
        )

        # post-exchange framing digest over the received (non-sentinel)
        # candidates: across all shards the received multiset must be
        # exactly the sent multiset (all_to_all routes each valid
        # candidate to exactly one owner; all_gather + ownership filter
        # partitions the same set) — compared host-side per committed
        # chunk (overflowed attempts are discarded before the compare)
        recv_dig = _fp_digest(
            r_hi, r_lo, ~((r_hi == sent) & (r_lo == sent))
        )

        # minimal-payload sort over the received (owned) candidates: the
        # sort both dedups the batch (first-occurrence) and fixes the
        # shard's discovery order deterministically
        with stage("dedup_sort"):
            order = jnp.lexsort((r_lo, r_hi))
            hi_s, lo_s = r_hi[order], r_lo[order]
            invalid_s = (hi_s == sent) & (lo_s == sent)
            first = dedup.first_occurrence_mask(hi_s, lo_s, invalid_s)
            # the sentinel pairs sort last: the probe's live prefix
            n_live = jnp.sum(~invalid_s, dtype=jnp.int32)
        ovf_probe = jnp.bool_(False)
        slots = None  # the rank-merge's slot counts, where one runs
        if hash_table:
            # per-shard HBM open-addressing table (ops/hashset): vhi/vlo
            # carry the table slots; insert-or-find replaces both the
            # sorted-visited probe AND the O(vcap) rank-merge.  The call
            # is functional (no donation here): a retried chunk simply
            # discards the attempt's returned tables, so the existing
            # overflow discipline stays exact.
            q_hi = jnp.where(first, hi_s, sent)
            q_lo = jnp.where(first, lo_s, sent)
            # claim=None: a fresh per-shard claim lattice per chunk (an
            # HBM memset of cap/D int32 — microseconds at pod scale);
            # carrying it across chunks would need a third shard_map
            # operand for little gain at per-shard table sizes
            vhi2, vlo2, _claim, is_new, _nn, ovf_probe = hashset.probe_insert(
                vhi, vlo, q_hi, q_lo, first
            )
            vn2 = vn
            rank = jnp.zeros((R,), jnp.int32)
            probe = None
        else:
            seen, rank, probe = dedup.probe_sorted(
                vhi, vlo, vn, hi_s, lo_s, n_live)
            is_new = first & ~seen

        with stage("compact"), part("novel"):
            pos = jnp.where(is_new, jnp.cumsum(is_new) - 1, R)
            out = jnp.zeros((R, K), jnp.uint32).at[pos].set(r_cand[order])
            out_parent = jnp.full((R,), -1, jnp.int32).at[pos].set(
                r_parent[order])
            out_act = jnp.full((R,), -1, jnp.int32).at[pos].set(r_act[order])
            out_hi = jnp.full((R,), sent).at[pos].set(hi_s)
            out_lo = jnp.full((R,), sent).at[pos].set(lo_s)
            out_rank = jnp.zeros((R,), jnp.int32).at[pos].set(rank)
            new_n = jnp.sum(is_new, dtype=jnp.int32)

        if hash_table:
            pass  # vhi2/vlo2 already hold the updated table
        elif with_merge:
            vhi2, vlo2, vn2, slots = dedup.merge_counted(
                vhi, vlo, vn, out_hi, out_lo, out_rank, new_n, vcap
            )
        else:
            # host-FpSet backend: the device holds no visited set (the
            # placeholder probe above sees vn=0); the host inserts each
            # shard's batch-deduped fingerprints into its own FpSet
            vhi2, vlo2, vn2 = vhi, vlo, vn

        # invariants on the frontier shard being expanded (checked once per
        # state, at expansion; `states` is already unpacked)
        viol_any, viol_idx = [], []
        if model.invariants:
            with stage("invariants"):
                for inv in model.invariants:
                    ok = jax.vmap(inv.pred)(states)
                    bad = fvalid & ~ok
                    viol_any.append(jnp.any(bad))
                    viol_idx.append(jnp.argmax(bad))
        else:
            viol_any, viol_idx = [jnp.bool_(False)], [jnp.int32(0)]

        return (
            out,  # [R, K] per-shard compacted (out_spec concatenates to [D*R])
            out_parent,
            out_act,
            new_n[None],
            vhi2[None],
            vlo2[None],
            vn2[None],
            jnp.stack(viol_any)[None],  # [1, n_inv] per shard -> [D, n_inv]
            jnp.stack(viol_idx)[None],
            jnp.any(deadlocked)[None],
            jnp.argmax(deadlocked)[None],
            # [1, n_actions + 8] -> [D, n_actions + 8]: the enabled counts
            # and the probe's and merge's work counts (pipeline.counts_out;
            # this step's own full-width compaction counts no rows)
            counts_out(act_en, work_counts(probe, slots))[None],
            # per-action expansion overflow + pre-constraint guard counts:
            # the host sizes adaptive per-action compact buffers from the
            # guard histogram exactly as the single-device engine does
            # (replicated-deterministic — every process sees the same
            # fetched globals)
            ovf_expand[None],  # [1, n_actions] -> [D, n_actions]
            act_guard[None],  # [1, n_actions] -> [D, n_actions]
            ovf_dest[None],
            ovf_probe[None],  # device-hash probe-budget overflow
            out_hi,  # [R] per shard (host-FpSet backend reads these)
            out_lo,
            sent_dig[None],  # [1, 5] -> [D, 5] exchange framing digests
            recv_dig[None],
        )

    # EXPLICIT per-tensor layouts (mesh_layouts): operands and results
    # name which dim rides the mesh axis instead of the old implicit
    # P("d")-for-everything (same placement, now spelled out and
    # asserted in tests so a real-ICI mesh inherits it unchanged)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P("d", None),  # frontier rows
            P("d"),        # fvalid
            P("d", None),  # visited hi lanes / hash slots
            P("d", None),  # visited lo lanes / hash slots
            P("d"),        # per-shard visited counts
        ),
        out_specs=(
            P("d", None),  # compacted new rows [D*R, K]
            P("d"),        # parents
            P("d"),        # action ids
            P("d"),        # per-shard new counts
            P("d", None),  # updated visited hi
            P("d", None),  # updated visited lo
            P("d"),        # updated visited counts
            P("d", None),  # viol_any [D, n_inv]
            P("d", None),  # viol_idx [D, n_inv]
            P("d"),        # deadlock any
            P("d"),        # deadlock idx
            P("d", None),  # counts [D, n_actions + 8]
            P("d", None),  # ovf_expand [D, n_actions]
            P("d", None),  # act_guard [D, n_actions]
            P("d"),        # ovf_dest
            P("d"),        # ovf_probe
            P("d"),        # out_hi
            P("d"),        # out_lo
            P("d", None),  # sent framing digests [D, 5]
            P("d", None),  # recv framing digests [D, 5]
        ),
        check_vma=False,
    )
    return sharded


def _grow_sorted_shards(dev_vhi, dev_vlo, vcap: int, new_cap: int,
                        layout, io):
    """Grow every shard's sorted visited pair set to `new_cap` slots
    (sentinel-padded) — the one growth path for the per-chunk loop and
    the device-resident level driver.  Multi-process takes the host
    round trip (every process must contribute its shards); single-
    process grows on device with no host copy."""
    D = dev_vhi.shape[0]
    if is_multiprocess():
        grown_hi = io.fetch(dev_vhi)
        grown_lo = io.fetch(dev_vlo)
        pad = np.full(
            (D, new_cap - grown_hi.shape[1]), 0xFFFFFFFF, np.uint32
        )
        dev_vhi = io.put(
            np.concatenate([grown_hi, pad], axis=1), layout
        )
        dev_vlo = io.put(
            np.concatenate([grown_lo, pad], axis=1), layout
        )
    else:
        pad = jnp.full(
            (D, new_cap - dev_vhi.shape[1]), 0xFFFFFFFF, jnp.uint32
        )
        dev_vhi = jax.device_put(
            jnp.concatenate([dev_vhi, pad], axis=1), layout
        )
        dev_vlo = jax.device_put(
            jnp.concatenate([dev_vlo, pad], axis=1), layout
        )
    return dev_vhi, dev_vlo, new_cap


def _make_sharded_level(
    model: Model,
    mesh: Mesh,
    expander: _Step,
    B: int,
    NCp: int,
    vcap: int,
    widths: tuple,
    LN: int,
    exchange: str,
    dest_w: int,
    compress: bool,
    check_deadlock: bool,
):
    """The sharded device-resident LEVEL program: every gated chunk of a
    BFS level runs inside ONE dispatched ``lax.while_loop`` per shard —
    the PR 12 single-device level body composed with the per-chunk
    collective exchange — so a level costs O(1) collective-bearing
    launches per shard instead of O(chunks).

    Per while_loop iteration (= one serial chunk), each shard:
    dynamic-slices its chunk from the device-resident frontier buffer
    [NCp*B, K] -> compacted expansion (make_expand's per-action in-jit
    cumsum/scatter — the exact action-major candidate order of the
    per-chunk path) -> fingerprints -> per-destination bucketing + the
    ``all_to_all`` (or all_gather) exchange, with the PR 10 compression
    codec in-loop when enabled (_make_exchange: ONE routing source with
    the per-chunk step) -> DUAL-PROBE dedup of the received candidates
    (stable lexsort winners vs the READ-ONLY visited shard AND a
    device-resident per-shard level-new sorted set) -> in-jit
    (count, xor, sum) digest folds (ops/devlevel) + framing-digest
    accumulation -> dynamic-offset next-frontier append.  The
    visited merge runs ONCE per shard after the loop.

    Bit-identity with the per-chunk path holds chunk for chunk: the
    routing, per-bucket stable sort and receiver lexsort are the same
    traced code (_make_exchange), novelty against (visited ∪ level-new)
    equals the per-chunk path's chunk-by-chunk merged visited set
    (routing sends a fingerprint to the same owner shard every time),
    and winners of equal fingerprints are decided by the same stable
    sort over the same candidate order.  Verdict priority mirrors the
    serial commit loop exactly — invariants beat deadlock within a
    chunk, the first invariant (in declaration order) violated by ANY
    shard wins, then the lowest shard — elected REPLICATED via
    all_gather so the while_loop condition stays uniform across the
    mesh (a collective inside a loop requires every participant to
    agree on the trip count).  Overflow flags (expansion segment,
    destination bucket / codec budget, level-new capacity) combine
    replicated via pmax: an overflowing level stops committing and the
    host re-dispatches ONCE from the pre-level visited state at exact
    measured widths — <=2 launches per level per shard even then.

    Returns the (un-jitted) program over global operands
    (fbuf [D*NCp*B, K], flen [D], ncs [D], vhi/vlo [D, vcap], vn [D])
    laid out per :func:`mesh_layouts`.
    """
    spec = model.spec
    K = spec.num_lanes
    D = mesh.devices.size
    expand = expander.make_expand(B, widths)
    T = expander.expand_width(B, widths)
    W = dest_w
    R = D * W if exchange == "all_to_all" else D * T
    OC = LN + R  # output buffer: one chunk of append headroom past LN
    F = NCp * B  # per-shard frontier buffer rows
    n_actions = len(model.actions)
    route = _make_exchange(D, W, R, K, exchange, compress)
    from ..engine.pipeline import sorted_dedup_stage

    def level_body(fbuf, flen, ncs, vhi, vlo, vn):  # kspec: traced
        flen = flen[0]
        ncs = ncs[0]
        vhi, vlo, vn = vhi[0], vlo[0], vn[0]
        me = jax.lax.axis_index("d")
        sent = jnp.uint32(dedup.SENT)

        def body(carry):  # kspec: traced
            (i, orows, opar, oact, on, lhi, llo, ln,
             vkind, vshard, vinv, vidx,
             act_en, agmax, dig, s_acc, r_acc, ovf, nclean, work) = carry
            start = i * B
            rows = jax.lax.dynamic_slice(fbuf, (start, 0), (B, K))
            fvalid = (
                start + jnp.arange(B, dtype=jnp.int32)
            ) < flen
            states = jax.vmap(spec.unpack)(rows)
            (en_pre, cand, valid, parent, actid, a_en, a_guard,
             exp_ovf) = expand(rows, states, fvalid)
            deadlocked = fvalid & ~jnp.any(en_pre, axis=1)
            with stage("fingerprint"):
                hi, lo = fingerprint_lanes(cand, spec.exact64)
                hi = jnp.where(valid, hi, sent)
                lo = jnp.where(valid, lo, sent)
            # parent as a mesh-global LEVEL row id: src shard * F +
            # (chunk offset + row) — the host decodes src_d = pg // F,
            # level row = pg % F (chunk offsets are i*B by plan)
            parent_g = me.astype(jnp.int32) * F + (start + parent)
            sent_dig = _fp_digest(hi, lo, valid)
            (r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest) = route(
                hi, lo, cand, parent_g, actid, valid, me
            )
            recv_dig = _fp_digest(
                r_hi, r_lo, ~((r_hi == sent) & (r_lo == sent))
            )
            # the SHARED winner-selection sequence (one source of truth
            # with the per-chunk paths): primary set = this shard's
            # level-new sorted set (its ranks drive the gated merge
            # below), also_seen_in = the read-only visited shard
            (n_out, n_par, n_act, new_n, n_hi, n_lo, _l1, _l2, _l3,
             n_rank, c_work) = sorted_dedup_stage(
                r_cand, r_parent, r_act,
                ~((r_hi == sent) & (r_lo == sent)),
                r_hi, r_lo, lhi, llo, ln, LN, R, K, False,
                also_seen_in=(vhi, vlo, vn),
            )
            # frontier verdicts, serial priority (the per-inv loop is
            # the per-chunk step's exact semantics)
            if model.invariants:
                v_any, v_idx = [], []
                with stage("invariants"):
                    for inv in model.invariants:
                        ok = jax.vmap(inv.pred)(states)
                        bad = fvalid & ~ok
                        v_any.append(jnp.any(bad))
                        v_idx.append(jnp.argmax(bad).astype(jnp.int32))
                    viol_any = jnp.stack(v_any)
                    viol_idx = jnp.stack(v_idx)
            else:
                viol_any = jnp.zeros((1,), bool)
                viol_idx = jnp.zeros((1,), jnp.int32)
            # REPLICATED verdict election: every shard derives the same
            # winner from the gathered flags, so the loop condition
            # stays uniform across the mesh
            g_viol = jax.lax.all_gather(
                viol_any[None], "d", tiled=True
            )  # [D, n_inv]
            g_vix = jax.lax.all_gather(viol_idx[None], "d", tiled=True)
            dl_pair = jnp.stack([
                jnp.any(deadlocked).astype(jnp.int32),
                jnp.argmax(deadlocked).astype(jnp.int32),
            ])
            g_dl = jax.lax.all_gather(dl_pair[None], "d", tiled=True)
            inv_any = jnp.any(g_viol)
            inv_i = jnp.argmax(jnp.any(g_viol, axis=0)).astype(jnp.int32)
            d_inv = jnp.argmax(g_viol[:, inv_i]).astype(jnp.int32)
            dl_any = jnp.bool_(check_deadlock) & jnp.any(g_dl[:, 0] > 0)
            d_dl = jnp.argmax(g_dl[:, 0]).astype(jnp.int32)
            kind = jnp.where(
                inv_any, jnp.int32(1),
                jnp.where(dl_any, jnp.int32(2), jnp.int32(0)),
            )
            vd = jnp.where(inv_any, d_inv, d_dl)
            vix_l = jnp.where(
                inv_any, g_vix[d_inv, inv_i], g_dl[d_dl, 1]
            ) + start
            take = (vkind == 0) & (kind != 0)
            commit = kind == 0  # a verdict chunk commits nothing
            # REPLICATED overflow flags (pmax): every shard must agree
            # on commit gating and the host's re-dispatch decision
            ln_ovf = jax.lax.pmax(
                (commit & ((ln + new_n) > LN)).astype(jnp.int32), "d"
            ) > 0
            this_ovf = jax.lax.pmax(
                (jnp.any(exp_ovf) | ovf_dest).astype(jnp.int32), "d"
            ) > 0
            commit_ok = commit & ~ovf & ~ln_ovf
            # framing accumulates for every chunk the serial path would
            # have COMPARED: clean chunks, including a verdict chunk
            # (the serial commit checks framing before the verdict)
            clean = ~ovf & ~this_ovf & ~ln_ovf
            app_n = jnp.where(commit_ok, new_n, 0)
            orows = devlevel.append_rows(orows, n_out, on)
            opar = devlevel.append_vec(opar, n_par, on)
            oact = devlevel.append_vec(oact, n_act, on)
            lhi, llo, ln, c_slots = dedup.merge_counted(
                lhi, llo, ln, n_hi, n_lo, n_rank, app_n, LN
            )
            dig = devlevel.combine_digest(
                dig,
                devlevel.masked_digest(
                    n_hi, n_lo, jnp.arange(R) < app_n
                ),
            )
            s_acc = _acc_digest(s_acc, sent_dig, clean)
            r_acc = _acc_digest(r_acc, recv_dig, clean)
            act_en = act_en + jnp.where(commit_ok, a_en, 0)
            agmax = jnp.maximum(agmax, a_guard)
            nclean = nclean + jnp.where(clean, 1, 0)
            ovf = ovf | this_ovf | ln_ovf
            return (i + 1, orows, opar, oact, on + app_n,
                    lhi, llo, ln,
                    jnp.where(take, kind, vkind),
                    jnp.where(take, vd, vshard),
                    jnp.where(take, inv_i, vinv),
                    jnp.where(take, vix_l, vidx),
                    act_en, agmax, dig, s_acc, r_acc, ovf, nclean,
                    work + c_work + work_counts(merge=c_slots))

        def cond(carry):  # kspec: traced
            return (carry[0] < ncs) & (carry[8] == 0)

        init = (
            jnp.int32(0),
            jnp.zeros((OC, K), jnp.uint32),
            jnp.full((OC,), -1, jnp.int32),
            jnp.full((OC,), -1, jnp.int32),
            jnp.int32(0),
            jnp.full((LN,), sent),
            jnp.full((LN,), sent),
            jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros((n_actions,), jnp.int32),
            jnp.zeros((n_actions,), jnp.int32),
            devlevel.zero_digest(),
            jnp.zeros((5,), jnp.uint32),
            jnp.zeros((5,), jnp.uint32),
            jnp.bool_(False),
            jnp.int32(0),
            work_counts(),
        )
        (_i, orows, opar, oact, on, lhi, llo, _ln, vkind, vshard,
         vinv, vidx, act_en, agmax, dig, s_acc, r_acc, ovf,
         nclean, work) = jax.lax.while_loop(cond, body, init)
        # ONE visited merge per shard per level (the per-chunk path
        # pays one per chunk): every level-new entry is disjoint from
        # the visited shard by construction, so the rank-scatter merge
        # of the sorted level-new prefix lands the identical sorted
        # visited array
        # (the level-new set is sorted and `on` long: its live prefix)
        _s, rank_v, m_probe = dedup.probe_sorted(vhi, vlo, vn, lhi, llo, on)
        vhi, vlo, vn, m_slots = dedup.merge_counted(
            vhi, vlo, vn, lhi, llo, rank_v, on, vcap
        )
        dc, dxh, dxl, dlimbs = dig
        return (
            orows,  # [OC, K] -> [D*OC, K]
            opar,
            oact,
            on[None],
            vhi[None],
            vlo[None],
            vn[None],
            vkind[None], vshard[None], vinv[None], vidx[None],
            # [1, n_actions + 8]: enabled counts, dedup work counts
            counts_out(act_en, work + work_counts(m_probe, m_slots))[None],
            agmax[None],
            dc[None], dxh[None], dxl[None],  # digest accumulator...
            dlimbs[None],  # ... (count, xors, 16-bit sum limbs)
            s_acc[None], r_acc[None],  # [1, 5] framing accumulators
            ovf[None],
            nclean[None],
        )

    sharded = jax.shard_map(
        level_body,
        mesh=mesh,
        in_specs=(
            P("d", None),  # frontier buffer rows [D*F, K]
            P("d"),        # per-shard pending lengths
            P("d"),        # per-shard (replicated-value) chunk counts
            P("d", None),  # visited hi lanes
            P("d", None),  # visited lo lanes
            P("d"),        # per-shard visited counts
        ),
        out_specs=(
            P("d", None),  # next-frontier rows [D*OC, K]
            P("d"),        # parents (mesh-global level row ids)
            P("d"),        # action ids
            P("d"),        # per-shard new counts
            P("d", None),  # merged visited hi
            P("d", None),  # merged visited lo
            P("d"),        # merged visited counts
            P("d"), P("d"), P("d"), P("d"),  # verdict kind/shard/inv/idx
            P("d", None),  # counts [D, n_actions + 8]
            P("d", None),  # agmax [D, n_actions]
            P("d"), P("d"), P("d"),  # digest count/xor_hi/xor_lo
            P("d", None),  # digest sum limbs [D, 4]
            P("d", None),  # sent framing accumulator [D, 5]
            P("d", None),  # recv framing accumulator [D, 5]
            P("d"),        # replicated overflow flag
            P("d"),        # clean (counted) chunks
        ),
        check_vma=False,
    )
    return sharded


def _make_sharded_level_host(
    model: Model,
    mesh: Mesh,
    expander: _Step,
    B: int,
    NCp: int,
    widths: tuple,
    LN: int,
    exchange: str,
    dest_w: int,
    compress: bool,
    check_deadlock: bool,
):
    """The sharded device-resident level program for the HOST (and
    disk-tier) visited backends — :func:`_make_sharded_level`'s
    deferred-probe twin.  Three deltas from the device-backend program:

    - no visited shards ride the program at all: novelty inside the
      level is decided against each shard's device-resident level-new
      sorted set alone (the same stable-lexsort winners — and the same
      SORTED emission order — as the per-chunk sharded host step), and
      each owner shard's host FpSet probes the level's novel candidates
      in ONE batched insert after the program completes
      (check_sharded._run_device_level's host branch): O(1) host syncs
      AND O(1) collective-bearing launches per shard per level;
    - the emitted prefix carries its fingerprint lanes out (ohi/olo
      accumulators) so the host probe never recomputes them;
    - no in-jit digest folds — the chain's multiset is only known after
      the probe, so the host folds the survivors exactly as the
      per-chunk host commit does (digest_rows over the kept rows).

    The exchange (+ codec) still runs inside the loop, and the framing
    digests still accumulate — fabric integrity is independent of where
    the visited set lives.  Bit-identity with the per-chunk sharded
    host path holds chunk for chunk: routing sends a fingerprint to the
    same owner shard every time, so (level-new ∪ host set) partitions
    novelty exactly as the per-chunk path's serial inserts do, with the
    earlier chunk winning cross-chunk intra-level duplicates — the same
    winner the serial per-chunk FpSet insert picks."""
    spec = model.spec
    K = spec.num_lanes
    D = mesh.devices.size
    expand = expander.make_expand(B, widths)
    T = expander.expand_width(B, widths)
    W = dest_w
    R = D * W if exchange == "all_to_all" else D * T
    OC = LN + R  # output buffer: one chunk of append headroom past LN
    F = NCp * B  # per-shard frontier buffer rows
    n_actions = len(model.actions)
    route = _make_exchange(D, W, R, K, exchange, compress)
    from ..engine.pipeline import sorted_dedup_stage

    def level_body(fbuf, flen, ncs):  # kspec: traced
        flen = flen[0]
        ncs = ncs[0]
        me = jax.lax.axis_index("d")
        sent = jnp.uint32(dedup.SENT)

        def body(carry):  # kspec: traced
            (i, orows, opar, oact, ohi, olo, on, lhi, llo, ln,
             vkind, vshard, vinv, vidx,
             act_en, agmax, s_acc, r_acc, ovf, nclean, work) = carry
            start = i * B
            rows = jax.lax.dynamic_slice(fbuf, (start, 0), (B, K))
            fvalid = (
                start + jnp.arange(B, dtype=jnp.int32)
            ) < flen
            states = jax.vmap(spec.unpack)(rows)
            (en_pre, cand, valid, parent, actid, a_en, a_guard,
             exp_ovf) = expand(rows, states, fvalid)
            deadlocked = fvalid & ~jnp.any(en_pre, axis=1)
            with stage("fingerprint"):
                hi, lo = fingerprint_lanes(cand, spec.exact64)
                hi = jnp.where(valid, hi, sent)
                lo = jnp.where(valid, lo, sent)
            parent_g = me.astype(jnp.int32) * F + (start + parent)
            sent_dig = _fp_digest(hi, lo, valid)
            (r_hi, r_lo, r_cand, r_parent, r_act, ovf_dest) = route(
                hi, lo, cand, parent_g, actid, valid, me
            )
            recv_dig = _fp_digest(
                r_hi, r_lo, ~((r_hi == sent) & (r_lo == sent))
            )
            # the SHARED winner-selection sequence, primary set = this
            # shard's level-new sorted set, NO visited probe (that is
            # the host's one batched call after the program)
            (n_out, n_par, n_act, new_n, n_hi, n_lo, _l1, _l2, _l3,
             n_rank, c_work) = sorted_dedup_stage(
                r_cand, r_parent, r_act,
                ~((r_hi == sent) & (r_lo == sent)),
                r_hi, r_lo, lhi, llo, ln, LN, R, K, False,
            )
            # frontier verdicts, replicated election (identical to the
            # device-backend program — verdicts derive from frontier
            # states only, so the deferred probe cannot change them)
            if model.invariants:
                v_any, v_idx = [], []
                with stage("invariants"):
                    for inv in model.invariants:
                        ok = jax.vmap(inv.pred)(states)
                        bad = fvalid & ~ok
                        v_any.append(jnp.any(bad))
                        v_idx.append(jnp.argmax(bad).astype(jnp.int32))
                    viol_any = jnp.stack(v_any)
                    viol_idx = jnp.stack(v_idx)
            else:
                viol_any = jnp.zeros((1,), bool)
                viol_idx = jnp.zeros((1,), jnp.int32)
            g_viol = jax.lax.all_gather(
                viol_any[None], "d", tiled=True
            )
            g_vix = jax.lax.all_gather(viol_idx[None], "d", tiled=True)
            dl_pair = jnp.stack([
                jnp.any(deadlocked).astype(jnp.int32),
                jnp.argmax(deadlocked).astype(jnp.int32),
            ])
            g_dl = jax.lax.all_gather(dl_pair[None], "d", tiled=True)
            inv_any = jnp.any(g_viol)
            inv_i = jnp.argmax(jnp.any(g_viol, axis=0)).astype(jnp.int32)
            d_inv = jnp.argmax(g_viol[:, inv_i]).astype(jnp.int32)
            dl_any = jnp.bool_(check_deadlock) & jnp.any(g_dl[:, 0] > 0)
            d_dl = jnp.argmax(g_dl[:, 0]).astype(jnp.int32)
            kind = jnp.where(
                inv_any, jnp.int32(1),
                jnp.where(dl_any, jnp.int32(2), jnp.int32(0)),
            )
            vd = jnp.where(inv_any, d_inv, d_dl)
            vix_l = jnp.where(
                inv_any, g_vix[d_inv, inv_i], g_dl[d_dl, 1]
            ) + start
            take = (vkind == 0) & (kind != 0)
            commit = kind == 0  # a verdict chunk commits nothing
            ln_ovf = jax.lax.pmax(
                (commit & ((ln + new_n) > LN)).astype(jnp.int32), "d"
            ) > 0
            this_ovf = jax.lax.pmax(
                (jnp.any(exp_ovf) | ovf_dest).astype(jnp.int32), "d"
            ) > 0
            commit_ok = commit & ~ovf & ~ln_ovf
            clean = ~ovf & ~this_ovf & ~ln_ovf
            app_n = jnp.where(commit_ok, new_n, 0)
            orows = devlevel.append_rows(orows, n_out, on)
            opar = devlevel.append_vec(opar, n_par, on)
            oact = devlevel.append_vec(oact, n_act, on)
            ohi = devlevel.append_vec(ohi, n_hi, on)
            olo = devlevel.append_vec(olo, n_lo, on)
            lhi, llo, ln, c_slots = dedup.merge_counted(
                lhi, llo, ln, n_hi, n_lo, n_rank, app_n, LN
            )
            s_acc = _acc_digest(s_acc, sent_dig, clean)
            r_acc = _acc_digest(r_acc, recv_dig, clean)
            act_en = act_en + jnp.where(commit_ok, a_en, 0)
            agmax = jnp.maximum(agmax, a_guard)
            nclean = nclean + jnp.where(clean, 1, 0)
            ovf = ovf | this_ovf | ln_ovf
            return (i + 1, orows, opar, oact, ohi, olo, on + app_n,
                    lhi, llo, ln,
                    jnp.where(take, kind, vkind),
                    jnp.where(take, vd, vshard),
                    jnp.where(take, inv_i, vinv),
                    jnp.where(take, vix_l, vidx),
                    act_en, agmax, s_acc, r_acc, ovf, nclean,
                    work + c_work + work_counts(merge=c_slots))

        def cond(carry):  # kspec: traced
            return (carry[0] < ncs) & (carry[10] == 0)

        init = (
            jnp.int32(0),
            jnp.zeros((OC, K), jnp.uint32),
            jnp.full((OC,), -1, jnp.int32),
            jnp.full((OC,), -1, jnp.int32),
            jnp.full((OC,), sent),
            jnp.full((OC,), sent),
            jnp.int32(0),
            jnp.full((LN,), sent),
            jnp.full((LN,), sent),
            jnp.int32(0),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros((n_actions,), jnp.int32),
            jnp.zeros((n_actions,), jnp.int32),
            jnp.zeros((5,), jnp.uint32),
            jnp.zeros((5,), jnp.uint32),
            jnp.bool_(False),
            jnp.int32(0),
            work_counts(),
        )
        (_i, orows, opar, oact, ohi, olo, on, _lh, _ll, _ln, vkind,
         vshard, vinv, vidx, act_en, agmax, s_acc, r_acc, ovf,
         nclean, work) = jax.lax.while_loop(cond, body, init)
        return (
            orows,  # [OC, K] -> [D*OC, K]
            opar,
            oact,
            ohi,  # [OC] novel-candidate fingerprint lanes (host probe)
            olo,
            on[None],
            vkind[None], vshard[None], vinv[None], vidx[None],
            counts_out(act_en, work)[None],
            agmax[None],
            s_acc[None], r_acc[None],  # [1, 5] framing accumulators
            ovf[None],
            nclean[None],
        )

    sharded = jax.shard_map(
        level_body,
        mesh=mesh,
        in_specs=(
            P("d", None),  # frontier buffer rows [D*F, K]
            P("d"),        # per-shard pending lengths
            P("d"),        # per-shard (replicated-value) chunk counts
        ),
        out_specs=(
            P("d", None),  # next-frontier candidate rows [D*OC, K]
            P("d"),        # parents (mesh-global level row ids)
            P("d"),        # action ids
            P("d"),        # candidate fingerprint hi lanes
            P("d"),        # candidate fingerprint lo lanes
            P("d"),        # per-shard pre-probe candidate counts
            P("d"), P("d"), P("d"), P("d"),  # verdict kind/shard/inv/idx
            P("d", None),  # counts [D, n_actions + 8]
            P("d", None),  # agmax [D, n_actions]
            P("d", None),  # sent framing accumulator [D, 5]
            P("d", None),  # recv framing accumulator [D, 5]
            P("d"),        # replicated overflow flag
            P("d"),        # clean (counted) chunks
        ),
        check_vma=False,
    )
    return sharded


class ShardedDeviceLevel:
    """Policy/state holder for the sharded device-resident level path
    (`--pipeline device`): the preconditions, the serial-chunking plan,
    and the width/level-new sizing ladders.  The dispatch/commit driver
    lives in check_sharded (it needs the engine loop's locals); this
    object is what survives across levels.

    Preconditions mirror the single-device DevicePipeline: a sorted-
    dedup visited backend — "device" (in-jit dual-probe + one merge per
    shard per level) or "host"/disk tier (deferred-probe mode: ONE
    batched per-shard host FpSet insert per level) — AND analyzer-
    proven per-field value hulls (engine.pipeline.device_hull_fallback
    — a HARD precondition, the in-jit pack stage has no host visibility
    between chunks).  The registry's per-backend matrix
    (pipeline_registry.backend_fallback_reason) is the one source of
    which backends serve natively; any unmet precondition or
    compile/dispatch failure sets `fallback` (sticky) and the run
    degrades to the per-chunk sharded ladder — results identical,
    launches O(chunks)."""

    def __init__(self, model: Model, mesh: Mesh, expander: _Step,
                 adapt: AdaptiveCompact, visited_backend: str,
                 check_deadlock: bool):
        from ..engine.pipeline import PooledWidths, device_hull_fallback
        from ..pipeline_registry import backend_fallback_reason

        self.model = model
        self.mesh = mesh
        self.expander = expander
        self.adapt = adapt
        self.check_deadlock = check_deadlock
        self.pool = PooledWidths(model.actions)
        self._ln_hw = 0  # per-level new-state high water (LN ladder)
        self.levels = 0  # levels actually run device-resident
        self.launches_last = 0
        #: deferred-probe mode: the per-shard level programs carry no
        #: visited shards; the host probes each shard's level batch once
        self.host_mode = visited_backend == "host"
        self.fallback: Optional[str] = backend_fallback_reason(
            "device", visited_backend
        )
        if self.fallback is None:
            self.fallback = device_hull_fallback(model)

    def _gated(self, B: int) -> bool:
        """The serial path must run the compacted (action-major)
        expansion at this bucket — below the gate it runs the full
        lattice in state-major order, which only the per-chunk path
        produces (the same bit-identity guard as the single-device
        plan_level)."""
        w = self.adapt.widths_for(B)
        if w is None:
            return False
        if isinstance(w, int):
            return _norm_shift(B, w) != 0
        return True

    def plan_level(self, lens, chunk: int, min_bucket: int):
        """-> (B, n_chunks) when the level program can serve (a prefix
        of) this level's serial chunks, else None.  The plan mirrors
        check_sharded's serial chunking EXACTLY: the serial bucket is
        min(next_pow2(max(rem, min_bucket//D, 32)), chunk) with rem the
        max remaining rows over shards — the device program covers the
        prefix of chunks whose serial bucket equals the uniform program
        bucket; a smaller-bucket tail runs through the per-chunk loop
        at its serial offsets afterwards (bit-identity)."""
        if self.fallback is not None:
            return None
        D = self.mesh.devices.size
        rem = max(lens) if lens else 0
        if rem <= 0:
            return None
        mb = max(min_bucket // D, 32)
        if rem <= chunk:
            B = min(_next_pow2(max(rem, mb)), chunk)
            return (B, 1) if self._gated(B) else None
        if not self._gated(chunk):
            return None
        nfull, r = 0, rem
        while r > 0 and min(_next_pow2(max(r, mb)), chunk) == chunk:
            nfull += 1
            r -= chunk
        return (chunk, nfull) if nfull else None

    def widths(self, B: int):
        n = len(self.model.actions)
        return self.expander.norm_widths(
            B, self.pool.widths_for(B, np.zeros(n), B)
        )

    def exact_widths(self, B: int, agmax: np.ndarray):
        return self.expander.norm_widths(
            B, self.pool.widths_for(B, agmax.astype(np.float64), B)
        )

    def observe(self, agmax: np.ndarray, B: int, new_total_max: int
                ) -> None:
        """Fold one committed level's measured maxima into the sizing
        ladders (pool widths + the shared LN high-water)."""
        np.maximum(
            self.pool.hw, agmax.astype(np.float64) / max(B, 1),
            out=self.pool.hw,
        )
        self._ln_hw = max(self._ln_hw, int(new_total_max))
        self.levels += 1

    def mark_fallback(self, reason: str, depth: int) -> None:
        self.fallback = reason
        from ..obs import tracer as _obs_t

        _obs_t.event(
            "pipeline-fallback", depth=depth, pipeline="sharded-device",
            to="per-chunk", error=reason[:200],
        )


def _elastic_reshard(
    snap,
    part_arrays,
    old_D: int,
    old_P: int,
    old_pending,
    *,
    D: int,
    spec,
    visited_backend: str,
    use_disk: bool,
    host_sets,
    shard_proc,
    my_proc: int,
    spill_base,
    vcap: int,
    shard_visited,
):
    """Re-bucket a D-shard checkpoint onto the current D-shard layout.

    Ownership is pure fingerprint arithmetic (owner = fp_lo mod D), so an
    elastic resume is a deterministic re-bucketing of every piece of
    persisted state — the pending frontiers and the visited fingerprints
    of whichever backend the run uses — with no re-exploration:

    - pending rows are re-fingerprinted and dealt to their new owners
      (within a shard the old concatenated order is preserved, so the
      re-bucketing is deterministic and the parent-log boundary rewrite
      can mirror it);
    - device / device-hash shards are rebuilt from the snapshot's live
      fingerprint pairs;
    - host FpSets are rebuilt from the (possibly per-host-part) dumps;
    - tiered disk sets re-insert every old shard's hot dump + run files
      into the new shards' sets.  Old run files are NOT deleted: they go
      behind the new sets' checkpoint-generation deletion barrier (new
      run numbering continues past them), so every retained pre-reshard
      generation still resolves until it rotates away.

    Returns (pending, host_sets, vhi, vlo, vn, vcap, shard_visited) with
    only the backend-relevant entries changed.
    """
    K = spec.num_lanes
    vhi = vlo = vn = None

    rows_all = (
        np.concatenate(old_pending)
        if any(p.shape[0] for p in old_pending)
        else np.empty((0, K), np.uint32)
    )
    if rows_all.shape[0]:
        rhi, rlo = fingerprint_lanes(jnp.asarray(rows_all), spec.exact64)
        rowner = np.asarray(rlo).astype(np.int64) % D
    else:
        rowner = np.empty(0, np.int64)
    pending = [rows_all[rowner == d] for d in range(D)]

    if visited_backend == "host" and use_disk:
        from ..storage.runs import SortedRun

        srcs = (
            [part_arrays[f"host{p}"] for p in range(old_P)]
            if old_P > 1
            else [snap]
        )
        old_mans = [None] * old_D
        old_hots = [np.empty(0, np.uint64)] * old_D
        for src in srcs:
            mans = json.loads(str(src["spill_manifest"]))
            hot_flat, lens = src["host_hot"], src["host_hot_lens"]
            at = 0
            for d, ln in enumerate(lens):
                ln = int(ln)
                if mans[d] is not None:
                    old_mans[d] = mans[d]
                    old_hots[d] = np.asarray(
                        hot_flat[at : at + ln], np.uint64
                    )
                at += ln
        # continue run numbering past every old layout's files so a
        # re-used shard directory never collides with barrier-protected
        # old runs
        next_seq = max(
            (int(m["seq"]) for m in old_mans if m is not None), default=0
        )
        for d in range(D):
            if host_sets[d] is not None:
                host_sets[d].seq = next_seq

        def deal(fps: np.ndarray) -> None:
            # re-bucket one source array; the new sets spill past their
            # budgets as usual, so peak residency stays O(one old run),
            # never O(visited) — the whole point of the disk tier
            fo = (fps & np.uint64(0xFFFFFFFF)).astype(np.int64) % D
            for d in range(D):
                if host_sets[d] is None:
                    continue
                sel = fps[fo == d]
                if len(sel):
                    host_sets[d].insert(sel)

        for k in range(old_D):
            old_files = []
            deal(old_hots[k])
            if old_mans[k] is not None:
                shard_dir = os.path.join(spill_base, f"shard{k}")
                for m in old_mans[k]["runs"]:
                    r = SortedRun(shard_dir, m, verify=True)
                    deal(np.asarray(r.arr))
                    old_files.append(r.path)
                # in-flight deferred deletions from the old layout keep
                # aging out under the new sets' barriers
                old_files.extend(
                    os.path.normpath(os.path.join(shard_dir, p))
                    for _, p in old_mans[k].get("pending_delete", ())
                )
            # retire the old layout's files behind the deletion barrier
            # of a deterministic owner (old shard k -> new set k mod D),
            # so every retained pre-reshard generation still resolves
            tgt = host_sets[k % D]
            if tgt is not None and old_files:
                tgt.deleter.schedule(old_files)
    elif visited_backend == "host":
        from ..native import FpSet

        if old_P > 1:
            all_fps = np.concatenate(
                [np.asarray(part_arrays[f"host{p}"]["host_fps"], np.uint64)
                 for p in range(old_P)]
            )
        else:
            all_fps = np.asarray(snap["host_fps"], np.uint64)
        fowner = (all_fps & np.uint64(0xFFFFFFFF)).astype(np.int64) % D
        host_sets = []
        for d in range(D):
            if shard_proc[d] != my_proc:
                host_sets.append(None)
                continue
            sel = all_fps[fowner == d]
            s = FpSet(initial_capacity=max(64, 2 * len(sel)))
            if len(sel):
                s.insert(sel)
            host_sets.append(s)
    elif visited_backend == "device-hash":
        flat_hi = np.asarray(snap["hash_hi"], np.uint32)
        flat_lo = np.asarray(snap["hash_lo"], np.uint32)
        howner = flat_lo.astype(np.int64) % D
        per_shard = [
            (flat_hi[howner == d], flat_lo[howner == d]) for d in range(D)
        ]
        shard_visited = np.asarray(
            [len(h) for h, _ in per_shard], np.int64
        )
        vhi, vlo, vcap = _shard_tables_from_pairs(per_shard, _HASH_MIN_CAP)
        vn = np.zeros((D,), np.int32)
    else:  # device: sorted per-shard pair sets
        vn_old = snap["vn"]
        his, los = [], []
        for d in range(old_D):
            n = int(vn_old[d])
            his.append(np.asarray(snap["vhi"])[d, :n])
            los.append(np.asarray(snap["vlo"])[d, :n])
        all_hi = np.concatenate(his) if his else np.empty(0, np.uint32)
        all_lo = np.concatenate(los) if los else np.empty(0, np.uint32)
        downer = all_lo.astype(np.int64) % D
        counts = np.bincount(downer, minlength=D)
        vcap = _next_pow2(max(1024, 2 * int(counts.max() if len(counts) else 1)))
        vhi = np.full((D, vcap), 0xFFFFFFFF, np.uint32)
        vlo = np.full((D, vcap), 0xFFFFFFFF, np.uint32)
        vn = np.zeros((D,), np.int32)
        for d in range(D):
            sel = np.nonzero(downer == d)[0]
            order = np.lexsort((all_lo[sel], all_hi[sel]))
            vhi[d, : len(sel)] = all_hi[sel][order]
            vlo[d, : len(sel)] = all_lo[sel][order]
            vn[d] = len(sel)

    return pending, host_sets, vhi, vlo, vn, vcap, shard_visited


def check_sharded(
    model: Model,
    mesh: Optional[Mesh] = None,
    max_depth: Optional[int] = None,
    max_states: Optional[int] = None,
    min_bucket: int = 256,
    progress=None,
    check_deadlock: bool = False,
    chunk_size: int = 16384,
    store_trace: bool = True,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 3,
    stats_path: Optional[str] = None,
    compact_shift: int = 2,
    compact_gate: int = 1024,
    exchange: str = "all_to_all",
    visited_backend: str = "device",
    mem_budget=None,
    spill_dir: Optional[str] = None,
    store: str = "auto",
    disk_budget=None,
    run=None,
    shard_heartbeat_dir: Optional[str] = None,
    overlap: Optional[bool] = None,
    pipeline: Optional[str] = None,
) -> CheckResult:
    """Exhaustive sharded BFS over `mesh` (default: 1-D mesh of all devices).

    Semantics match engine.check (same models, same counts).  With
    store_trace (default), per-level (states, parent, action) records are
    kept on the host in shard-major discovery order, and a violation is
    reported with the full parent-pointer counterexample path; disable for
    pure-throughput runs at pod scale.

    checkpoint_dir: level-synchronous checkpoint/resume — persists the
    per-shard pending frontiers and fingerprint shards every
    `checkpoint_every` levels (default 1 = per level; a crash loses at most
    checkpoint_every-1 levels); a run restarts from the last saved level.
    A checkpoint binds to (model, constants, invariant selection, deadlock
    flag) — NOT to the mesh layout: the writing layout is stamped
    (mesh_D/mesh_P) and resuming on a different shard or process count
    takes the ELASTIC path, re-bucketing fingerprint-range ownership onto
    the new mesh (docs/resilience.md § Distributed resilience).  With
    store_trace requested, each level's (rows, parent, action) slices are
    also published to per-shard on-disk parent logs under
    `<checkpoint_dir>/plog/`, so a violation found AFTER a resume still
    reports the full counterexample trace (the in-RAM trace store remains
    off for checkpointed runs).  Checkpoints are hardened as in
    engine.check (resilience.checkpoints): per-array checksums,
    keep-last-`checkpoint_keep` rotation with atomic promote, automatic
    fallback to the newest verifying generation, and — for the per-host
    FpSet part files — a cross-shard consistency check: a generation
    whose parts disagree with the main file's level (or mesh layout) is
    treated as torn and skipped.  Fault injection (`KSPEC_FAULT`,
    including shard-targeted `crash@shard<d>:level:N` scoping) and
    transient-error retry mirror engine.check, with the injection point
    at the exchange step.

    shard_heartbeat_dir (or $KSPEC_SHARD_HEARTBEAT_DIR, or `<run
    dir>/shards` when a run context is given): every process appends one
    heartbeat line per BFS level to `proc<i>.jsonl` there — the fleet
    supervisor's per-shard liveness signal and `cli report`'s
    died-mid-level shard attribution.

    compact_shift: two-phase expansion (see engine.check) — guards sweep the
    full lattice, update/pack/sort/exchange run at 1/2^shift of it.  0
    disables.  compact_gate: the bucket size below which chunks run the
    full (uncompacted) lattice — this engine's historical 1024; exposed
    (like engine.check's compact_gate) so tests can force small gated
    chunks through the compacted and device-resident paths.  exchange: "all_to_all" (bucket-by-owner routing, per-shard
    ICI traffic independent of mesh size) or "all_gather" (every shard sees
    every candidate — D× the bytes, simple fallback).  Both are exact; any
    buffer overflow is detected on device and the chunk re-runs wider.

    visited_backend: "device" keeps each shard's sorted fingerprint set in
    its own HBM (lexsort + probe + O(vcap) rank-merge per chunk);
    "device-hash" keeps each shard's set as an HBM open-addressing hash
    table instead (ops/hashset — O(batch) insert-or-find, no merge; the
    recommended device-resident backend); "host" gives each shard its own
    native C++ open-addressing FpSet on the host (keyed by owner —
    ownership routing guarantees a fingerprint always lands in the same
    shard's set), so the distributed engine can check state spaces whose
    fingerprints outgrow HBM — the TLC-FPSet spill mode of engine.check,
    now at pod scale.  Device memory then holds only O(chunk × fanout)
    transient data per shard.

    Out-of-core storage (storage/): `store` = "auto" | "ram" | "disk" and
    `mem_budget` activate the disk tier for the host backend — each
    shard's FpSet becomes a budget-bounded TieredFpSet spilling sorted,
    bloom-gated fingerprint runs under `spill_dir`/shard<d> (fingerprint-
    range ownership is unchanged: a fingerprint's owner shard, hence its
    run directory, never moves).  Bit-identical counts vs the in-RAM host
    path; checkpoints record each shard's run manifest + (budget-bounded)
    hot dump instead of the full fingerprint sets.  The frontier and
    traces stay in RAM in this engine (the single-device engine carries
    the disk frontier + parent log).

    run: an obs.RunContext (docs/observability.md) — per-level stats gain
    per-shard frontier/new/duplicate breakdowns and an exchange-imbalance
    gauge; spans/metrics/manifest land in the run directory.  In a
    multi-process job only the coordinator observes (the replicated host
    loops would otherwise write D copies of every artifact).

    overlap: async level-pipelined execution ($KSPEC_OVERLAP, default
    on; ``off`` = the historical serial behavior, the bit-identity
    oracle).  In this engine it enables (1) the COMPRESSED all_to_all —
    per-destination buckets stably sorted by fingerprint, fingerprint
    lanes bit-packed/delta-encoded (ops/fpcompress), rows/parents at a
    compacted half-width, action ids as u8, with the post-exchange
    framing digest computed over the DECODED payload (>=2x fewer
    exchange bytes, fabric integrity unweakened; defaults on only where
    a real fabric carries the collective — on the virtual CPU mesh the
    codec is pure compute overhead — and KSPEC_EXCHANGE_COMPRESS=1/0
    forces either way); (2) staged chunk commit on the
    host backend — chunk k+1's program (expand + exchange) is dispatched
    before chunk k's host commit runs, so per-shard FpSet inserts hide
    behind the in-flight exchange and vice versa; (3) background
    spill-run merges per shard and (4) async checkpoint writes, exactly
    as in engine.check.  Bit-identical results across the knob: counts,
    traces, digest chains (tests/test_overlap.py).

    disk_budget: spill + checkpoint directory byte budget
    (resilience.resources) — soft breach reclaims (tmp janitor, eager
    per-shard merges, checkpoint-generation prune, deletion-barrier
    flush), hard breach (or a real/injected ENOSPC from any storage
    writer, incl. the `enospc@...` / `stall@level:N` faults with
    `shard<d>:` scopes) performs checkpoint-then-clean-exit with a typed
    ResourceExhausted (CLI exit code 75).  In a multi-process fleet the
    breaching process exits typed, its peers wedge in the next
    collective, and the fleet supervisor classifies the rc-75 exit as a
    resource verdict instead of restarting into the same full disk.

    pipeline: level-pipeline selection (--pipeline / $KSPEC_PIPELINE;
    `cli pipelines --list` shows the per-ENGINE support matrix).  In
    this engine "device" selects the SHARDED DEVICE-RESIDENT LEVEL
    path: with visited_backend="device" and analyzer-proven per-field
    value hulls (engine.pipeline.device_hull_fallback — the same HARD
    precondition as the single-device device pipeline), each shard runs
    an entire level's worth of gated chunks inside ONE dispatched
    ``lax.while_loop`` program (expansion, the per-chunk collective
    exchange + compression codec, dual-probe dedup against the
    read-only visited shard + a per-shard level-new sorted set, in-jit
    digest folds), so a level costs O(1) collective-bearing launches
    per shard instead of O(chunks), with the O(capacity) visited merge
    paid once per level per shard — bit-identical to the per-chunk
    path (counts, duplicate accounting, first-violation rule, trace
    values, digest chains).  Unmet preconditions, sub-gate tail chunks
    and compile/dispatch failures degrade to the per-chunk sharded
    ladder (sticky, `pipeline-fallback` event, stats["device"]);
    "legacy" (and "fused", which has no sharded variant) run the
    per-chunk path — the bit-identity oracle.  Unknown names are
    rejected loudly (pipeline_registry.resolve_pipeline).

    What is kept across calls, and what is not.  Every compiled program
    of this engine — the per-chunk step (``shs``), the whole-level
    program (``shl``) and its host-backend twin (``shh``), the invariant
    pass over host-held rows (``shi``) — lives in the MODEL's step cache
    (``engine.bfs._Step.cached``, the cache ``PreparedKernels`` wraps),
    found by this function itself: no handle is passed.  A program is
    keyed by everything that shapes it: its tag, the mesh (devices and
    axis names), bucket, visited capacity, expansion widths, exchange
    kind, per-destination width, codec on/off, visited backend, level-new
    capacity and chunk count (level programs), the ordered invariant
    names and the deadlock flag.  A later call on the same model object,
    mesh and options therefore traces, lowers, compiles and loads nothing
    (no ``compile`` span in its run directory); a call with another mesh
    or option builds what it lacks beside the rest.  Programs at an
    outgrown visited capacity are NOT evicted (unlike engine.check's):
    every call replays the capacity ladder from its start and asks for
    them again.  Everything else starts fresh in every call, exactly as
    before: the visited shards and their capacity ladder, the frontier,
    the adaptive compact widths (``AdaptiveCompact``), the
    per-destination width floor (``w_extra``), the level-new and pooled
    width high waters of the device path (``_ln_hw``, ``pool.hw``), the
    digest chain, checkpoints and workers — so two calls with the same
    arguments walk the same sequence of program shapes, and a resumed or
    elastic call may build programs the cache lacks.
    """
    t_check = _now()  # the root `check` span starts at the first line
    _LEDGER.mark_backend()
    # encoding-soundness gate (analysis; KSPEC_ANALYZE=0 disables) —
    # same refusal contract as engine.check, memoized per model name
    from ..analysis import require_encoding_sound

    require_encoding_sound(model)
    if model.symmetry is not None:
        # this engine fingerprints candidates at five sites of its own
        # (fingerprint_lanes), none through pipeline.fp_stage: run here the
        # model would be searched UNREDUCED with no word said
        raise ValueError(
            f"{model.name}: check_sharded does not support SYMMETRY "
            f"{model.symmetry.operator} yet (the sharded engine keys states "
            "by their own fingerprints, not their orbits'); run the "
            "single-device engine, or drop the SYMMETRY stanza"
        )
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), ("d",))
    D = mesh.devices.size
    # per-process shard heartbeat stream (the fleet supervisor's per-shard
    # liveness signal and `cli report`'s died-mid-level attribution): every
    # process — not just the obs coordinator — appends one line per level
    # to <dir>/proc<i>.jsonl
    hb_dir = shard_heartbeat_dir or os.environ.get("KSPEC_SHARD_HEARTBEAT_DIR")
    if hb_dir is None and run is not None:
        hb_dir = os.path.join(run.dir, "shards")
    if run is not None and not is_coordinator():
        run = None
    # (root span `check` from this function's first line; `check-open`
    # until the first level begins, `check-close` after the last)
    obs_ = RunObserver(run, stats_path, engine="sharded",
                       annotate=jax.profiler.TraceAnnotation)
    obs_.check_begin(t_check, model=model.name)
    # counted transfers + named dispatches (engine/hostio.py)
    io = HostIO(obs_, fetch=fetch_global, put=put_global)
    spec = model.spec
    # width bookkeeping, and the OWNER of every compiled program of this
    # engine: `expander.cached` keeps them in the model's step cache
    # (the one PreparedKernels wraps), so a later call on the same model,
    # mesh and program-shaping options finds what this call built
    expander = _Step(model)
    C = expander.C
    K = spec.num_lanes
    obs_.shape(model, C, K)
    # explicit per-tensor mesh layouts (mesh_layouts; asserted in
    # tests/test_sharded_device.py)
    layouts = mesh_layouts(mesh)

    def _first_violation(rows: np.ndarray):
        """The invariant pass over host-held rows -> (invariant, row
        index) or None: the single-device engine's program and launch
        (``_Step.first_violation``), keyed by this mesh, with the rows
        sharded over it (XLA partitions the program)."""
        N = D * _next_pow2(max(-(-rows.shape[0] // D), 8))
        return expander.first_violation(
            (INVARIANT_TAG, mesh), N, rows, io, obs_, layouts["frontier"]
        )

    sp_ = obs_.open_span("init-states")
    inits = [
        {k: np.asarray(v, np.int32) for k, v in s.items()} for s in model.init_states()
    ]
    init_packed = np.unique(
        np.stack([np.asarray(spec.pack(s)) for s in inits]), axis=0
    )
    n0 = init_packed.shape[0]
    sp_.finish()

    t0 = time.perf_counter()
    # invariants on the init states (semantics must match engine.check)
    if model.invariants:
        sp_ = obs_.open_span("host-invariants", rows=n0)
        bad0 = _first_violation(init_packed)
        if bad0 is not None:
            inv, idx = bad0
            res = init_violation_result(
                model, inv, init_packed[idx], [n0], n0,
                time.perf_counter() - t0, stats={"devices": D},
            )
            sp_.finish()
            obs_.finish(res)
            obs_.close()
            return res
        sp_.finish()
    from ..storage import resolve_store

    use_disk = resolve_store(store, mem_budget)
    if use_disk:
        # the disk tier spills the HOST level of the hierarchy
        visited_backend = "host"
    if visited_backend not in ("device", "device-hash", "host"):
        raise ValueError(
            f"visited_backend must be 'device', 'device-hash' or 'host', "
            f"got {visited_backend!r}"
        )
    obs_.config(
        model=model.name,
        devices=D,
        exchange=exchange,
        visited_backend=visited_backend,
        store="disk" if use_disk else "ram",
        mem_budget=mem_budget,
        checkpoint_dir=checkpoint_dir,
        **device_stamp(),
    )
    host_sets = None
    spill_base = None
    ephemeral_spill = None

    # distribute inits to owner shards; per-shard sorted visited arrays
    hi0, lo0 = fingerprint_lanes(jnp.asarray(init_packed), spec.exact64)
    hi0, lo0 = np.asarray(hi0), np.asarray(lo0)
    owner0 = lo0 % D
    # which process hosts each shard's device (per-host FpSet ownership)
    shard_proc = [int(dev.process_index) for dev in mesh.devices.flat]
    my_proc = jax.process_index()
    my_shards = [d for d in range(D) if shard_proc[d] == my_proc]
    if visited_backend == "host":
        from ..native import FpSet

        # one FpSet per shard, living ONLY on the process that hosts the
        # shard's device: ownership routing sends a fingerprint to the same
        # shard every time, so per-shard sets never need cross-talk, and
        # per-host ownership divides set memory and insert work by the
        # process count (novelty masks are OR-merged across processes to
        # keep the replicated host loop in lockstep)
        if use_disk:
            from ..storage import (
                DEFAULT_MEM_BUDGET,
                TieredFpSet,
                parse_mem_budget,
            )

            budget = (
                parse_mem_budget(mem_budget)
                if mem_budget is not None
                else DEFAULT_MEM_BUDGET
            )
            spill_base = spill_dir or (
                os.path.join(checkpoint_dir, "spill") if checkpoint_dir else None
            )
            if spill_base is None:
                import tempfile

                # anonymous spill space: removed after a completed run
                spill_base = tempfile.mkdtemp(prefix="kspec-spill-")
                ephemeral_spill = spill_base
            # per-shard run directories; the byte budget divides across
            # the shards THIS PROCESS hosts (mem_budget is per-process
            # residency, matching engine.check — a multi-host job gets
            # budget bytes per host, not budget/P).  Init fingerprints
            # are inserted at the fresh/resume decision below (a resume
            # must not pre-wipe the runs its manifest references).
            n_local = max(1, sum(1 for p in shard_proc if p == my_proc))
            host_sets = [
                TieredFpSet(
                    os.path.join(spill_base, f"shard{d}"),
                    max(1, budget // n_local),
                    runs_per_merge=int(
                        os.environ.get("KSPEC_SPILL_RUNS_PER_MERGE", "8")
                    ),
                    gc_barrier=checkpoint_keep if checkpoint_dir else 0,
                )
                if shard_proc[d] == my_proc
                else None
                for d in range(D)
            ]
        else:
            host_sets = [
                FpSet() if shard_proc[d] == my_proc else None for d in range(D)
            ]
            for d in range(D):
                sel = np.nonzero(owner0 == d)[0]
                if len(sel) and host_sets[d] is not None:
                    host_sets[d].insert(_u64(hi0[sel], lo0[sel]))
        vcap = 64  # device placeholders; the device never holds the set
        vhi = np.full((D, vcap), 0xFFFFFFFF, np.uint32)
        vlo = np.full((D, vcap), 0xFFFFFFFF, np.uint32)
        vn = np.zeros((D,), np.int32)
    elif visited_backend == "device-hash":
        # per-shard HBM open-addressing tables (ops/hashset), carried in
        # the vhi/vlo slots; vn is unused (the tables track membership)
        per_shard = [
            (hi0[owner0 == d], lo0[owner0 == d]) for d in range(D)
        ]
        vhi, vlo, vcap = _shard_tables_from_pairs(per_shard, _HASH_MIN_CAP)
        vn = np.zeros((D,), np.int32)
    else:
        vcap = _next_pow2(max(1024, 4 * n0))
        vhi = np.full((D, vcap), 0xFFFFFFFF, np.uint32)
        vlo = np.full((D, vcap), 0xFFFFFFFF, np.uint32)
        vn = np.zeros((D,), np.int32)
        for d in range(D):
            sel = np.nonzero(owner0 == d)[0]
            order = np.lexsort((lo0[sel], hi0[sel]))
            vhi[d, : len(sel)] = hi0[sel][order]
            vlo[d, : len(sel)] = lo0[sel][order]
            vn[d] = len(sel)

    # per-shard pending frontiers live on the host; each level streams them
    # through the compiled step in fixed-size chunks (same scheme as
    # engine.check: cross-chunk dedup rides the per-shard visited sets, so
    # the compiled-shape count and device memory stay bounded at pod scale)
    pending = [init_packed[owner0 == d] for d in range(D)]
    chunk = _next_pow2(max(32, chunk_size))
    # per-shard distinct-state counts (device-hash growth policy + stats)
    shard_visited = np.bincount(owner0, minlength=D).astype(np.int64)

    if exchange not in ("all_to_all", "all_gather"):
        raise ValueError(f"unknown exchange {exchange!r}")
    levels = [n0]
    total = n0
    depth = 0
    violation = None
    result_levels: list = []  # per-level stats records (mirrors engine.check)
    w_extra = 0  # extra doublings of the all_to_all per-destination width
    exch_bytes_total = 0  # exchange wire bytes actually moved (all_to_all)
    exch_raw_bytes_total = 0  # ... and the raw-layout bytes at same widths
    overlap_staged_peak = 0  # most chunks ever staged at once (<= 2)

    def _io_counters():
        return worker_counters((io_worker, ckpt_worker))

    # Adaptive per-action compact sizing (round-5 port of the single-device
    # engine's policy — one shared implementation, engine.bfs.AdaptiveCompact).
    # All inputs derive from fetch_global'd arrays and host-known shard
    # sizes, so every process computes identical widths (replicated-
    # deterministic — the shard_map operands stay in lockstep).  The
    # sharded bucket gate stays at this engine's historical 1024.
    adapt = AdaptiveCompact(model.actions, compact_shift,
                            bucket_gate=compact_gate)
    adaptive_fallback = False

    # level-pipeline selection (pipeline_registry: loud rejection of
    # typos — the sharded engine no longer silently ignores --pipeline).
    # "device" arms the sharded device-resident level path below; every
    # other registered name runs the per-chunk step (the registry's
    # per-engine matrix documents which combinations degrade and why)
    pipe_name = resolve_pipeline(pipeline)
    sdev = (
        ShardedDeviceLevel(
            model, mesh, expander, adapt, visited_backend, check_deadlock
        )
        if pipe_name == "device"
        else None
    )

    def _shard_density(act_guard_np, took):
        """Per-state guard density for the policy: max over shards of
        guard_counts / shard_rows."""
        dens = act_guard_np.astype(np.float64) / np.maximum(
            took.astype(np.float64), 1.0
        )[:, None]
        return dens.max(axis=0)

    fault = FaultPlan.from_env()
    # shard-targeted faults (crash@shard<d>:..., docs/resilience.md) fire
    # only on the process hosting the named shard's device — in a fleet,
    # exactly one process dies and its peers wedge in the next collective,
    # which is the failure the fleet supervisor exists to catch
    fault.set_local_shards(my_shards)
    fault.validate_shards(D)
    # async overlap layer (overlap.py; $KSPEC_OVERLAP, default on) — the
    # same knob as engine.check: background per-shard merges + async
    # checkpoint writes ride worker threads, the staged chunk commit and
    # the compressed exchange ride the step itself.  The resolution is
    # env-replicated, so every process takes the same path (lockstep).
    from ..overlap import (
        AsyncWorker,
        close_workers,
        overlap_enabled,
        worker_counters,
    )

    overlap_on = overlap_enabled(overlap)
    # Compressed exchange default: ON where a real fabric carries the
    # all_to_all (the bytes are the scarce resource compression buys
    # back), OFF on the virtual CPU mesh (no wire — the codec's encode/
    # decode compute is pure overhead there; measured both ways on the
    # CPU mesh, PR 10).  KSPEC_EXCHANGE_COMPRESS=1/0 forces either.
    _comp_env = os.environ.get("KSPEC_EXCHANGE_COMPRESS", "")
    compress_on = (
        overlap_on
        and exchange == "all_to_all"
        and len(model.actions) < 255  # act ids ride the wire as u8
        and (
            _comp_env == "1"
            or (_comp_env != "0" and jax.default_backend() != "cpu")
        )
    )
    io_worker = AsyncWorker("kspec-io") if overlap_on else None
    ckpt_worker = (
        AsyncWorker("kspec-ckpt")
        if overlap_on and checkpoint_dir is not None
        else None
    )

    def _shutdown_async(drain: bool) -> None:
        close_workers((io_worker, ckpt_worker), drain)
    # state-integrity defense (resilience.integrity): the same always-on
    # level digest chain as the single-device engine — the digest is over
    # the new-state fingerprint MULTISET, which is shard-layout-invariant,
    # so chains are comparable across engines and survive elastic resumes
    # unchanged — plus the exchange framing check below
    chain = _integ.LevelDigestChain() if _integ.enabled() else None
    hb_path = None
    if hb_dir:
        os.makedirs(hb_dir, exist_ok=True)
        hb_path = os.path.join(hb_dir, f"proc{my_proc}.jsonl")

    def _shard_beat(done_depth: int, **extra) -> None:
        if hb_path is None:
            return
        append_jsonl(
            hb_path,
            heartbeat_record(
                "shard-heartbeat",
                proc=int(my_proc),
                pid=os.getpid(),
                shards=my_shards,
                depth=int(done_depth),
                **extra,
            ),
        )

    if use_disk:
        # the plan is parsed after the per-shard sets are built — hand it
        # to them now (mid-merge crash injection, crash@merge:N), along
        # with the background-merge worker (KSPEC_OVERLAP)
        for s in host_sets:
            if s is not None:
                s.fault_plan = fault
                s.merge_worker = io_worker
    chunk_retry = ChunkRetryHandler.from_env("[sharded]")
    ckpt_store = None
    # newest durably checkpointed level (None = not checkpointing):
    # level-crash faults defer until the target level is checkpointed so
    # a supervised restart converges (FaultPlan.crash)
    last_ckpt_depth = None
    resumed = False
    elastic_resumed = False
    plog = None  # per-shard on-disk parent log (checkpointed runs only)
    inv_names = ",".join(sorted(i.name for i in model.invariants))
    # NB: the mesh layout (D, P) is deliberately NOT part of the identity:
    # a checkpoint binds to the *search* (model, constants, invariants,
    # backend), and resuming it on a different shard/process count is the
    # elastic-resume path below, not a config mismatch.  The layout that
    # wrote a generation is stamped as mesh_D/mesh_P arrays instead.
    _fields_ident = ",".join(
        f"{f.name}:{f.shape}:{f.lo}:{f.hi}" for f in spec.fields
    ) + ("|store=disk" if use_disk else "")
    ckpt_ident = (
        f"{model.name}|lanes={spec.num_lanes}|"
        f"backend={visited_backend}|"
        f"inv={inv_names}|dl={check_deadlock}|" + _fields_ident
    )
    # the pre-elastic ident baked the layout in; accepting it (for THIS
    # mesh exactly) keeps checkpoints written by older code resumable
    # after an upgrade — a legacy checkpoint from a different layout
    # still refuses (it carries no mesh stamps to re-bucket from)
    ckpt_ident_legacy = (
        f"{model.name}|lanes={spec.num_lanes}|D={D}|"
        f"P={jax.process_count()}|backend={visited_backend}|"
        f"inv={inv_names}|dl={check_deadlock}|" + _fields_ident
    )
    if checkpoint_dir is not None:
        want_trace = store_trace
        store_trace = False
        last_ckpt_depth = 0
        checkpoint_every = max(1, int(checkpoint_every))
        def _spill_ref_errors(arrays: dict) -> list:
            """Disk-tier load validator: CRC-verify every per-shard spill
            run a generation references (flip@spill recovery: fall back
            to a generation predating the corrupt file — its
            deterministic re-exploration rewrites it)."""
            if not use_disk or "spill_manifest" not in arrays:
                return []
            errs = []
            for d, man in enumerate(json.loads(str(arrays["spill_manifest"]))):
                errs += _integ.spill_run_errors(
                    os.path.join(spill_base, f"shard{d}"),
                    (man or {}).get("runs", ()),
                )
            return errs

        ckpt_store = CheckpointStore(
            checkpoint_dir,
            "sharded_checkpoint.npz",
            ident=ckpt_ident,
            keep=checkpoint_keep,
            fault_plan=fault,
            ident_aliases=(ckpt_ident_legacy,),
            # CRC-consistent content corruption falls back exactly like a
            # checksum failure: resume from the newest CHAIN-VERIFIED
            # generation (resilience.integrity)
            validators=(
                (_integ.checkpoint_chain_errors, _spill_ref_errors)
                if chain is not None
                else (_spill_ref_errors,)
            ),
        )
        if ckpt_worker is not None:
            ckpt_store.attach_writer(ckpt_worker)
        if want_trace:
            # per-shard on-disk parent logs: counterexample traces that
            # survive checkpoint resume (the sharded twin of the single-
            # device engine's disk-tier parent log — docs/resilience.md)
            plog = ShardedParentLog(
                os.path.join(checkpoint_dir, "plog"),
                K,
                D,
                local_shards=my_shards,
                epoch_writer=is_coordinator(),
                fault_plan=fault,
            )

        def _parts_for(main):
            # per-host FpSet part files, derived from the layout recorded
            # in the MAIN file: a same-layout resume needs only this
            # process's part (cross-shard consistency is still enforced),
            # an elastic resume needs every old host's part to re-bucket.
            # A stamp-less main is a pre-elastic legacy checkpoint, which
            # can only have passed the ident check via the same-layout
            # alias — so its layout IS the current one
            old_P_ = (
                int(main["mesh_P"])
                if "mesh_P" in main
                else jax.process_count()
            )
            old_D_ = int(main["mesh_D"]) if "mesh_D" in main else D
            if visited_backend != "host" or old_P_ <= 1:
                return ()
            if old_D_ == D and old_P_ == jax.process_count():
                return (f"host{my_proc}",)
            return tuple(f"host{p}" for p in range(old_P_))

        loaded = ckpt_store.load(parts=_parts_for)
        if loaded is not None:
            resumed = True
            snap, part_arrays, _gen = loaded
            if chain is not None:
                # restore the digest chain (layout-invariant: an elastic
                # resume re-buckets rows, never the level multisets);
                # pre-integrity checkpoints rebuild unanchored from counts
                chain = (
                    _integ.LevelDigestChain.from_array(snap["digest_chain"])
                    if "digest_chain" in snap
                    else _integ.LevelDigestChain.from_levels(
                        snap["levels"].tolist()
                    )
                )
            # stamp-less legacy snapshots passed the ident check via the
            # same-layout alias, so their layout is by construction the
            # current one (never spuriously elastic)
            old_D = int(snap["mesh_D"]) if "mesh_D" in snap else D
            old_P = (
                int(snap["mesh_P"])
                if "mesh_P" in snap
                else jax.process_count()
            )
            elastic_resumed = old_D != D or old_P != jax.process_count()
            plens = snap["pending_lens"]
            flat = snap["pending"]
            pending, at = [], 0
            for ln in plens:
                pending.append(flat[at : at + int(ln)])
                at += int(ln)
            levels = snap["levels"].tolist()
            total = int(snap["total"])
            depth = int(snap["depth"])
            last_ckpt_depth = depth
            # crash faults at or below the resume level count as fired
            fault.set_start_depth(depth)
            if elastic_resumed:
                (
                    pending,
                    host_sets,
                    new_vhi,
                    new_vlo,
                    new_vn,
                    vcap,
                    shard_visited,
                ) = _elastic_reshard(
                    snap,
                    part_arrays,
                    old_D,
                    old_P,
                    pending,
                    D=D,
                    spec=spec,
                    visited_backend=visited_backend,
                    use_disk=use_disk,
                    host_sets=host_sets,
                    shard_proc=shard_proc,
                    my_proc=my_proc,
                    spill_base=spill_base,
                    vcap=vcap,
                    shard_visited=shard_visited,
                )
                if new_vhi is not None:
                    # device-resident backends got rebuilt shard arrays;
                    # host backends keep their placeholder device views
                    vhi, vlo, vn = new_vhi, new_vlo, new_vn
                if plog is not None and is_multiprocess():
                    # the boundary-level rewrite atomically replaces
                    # segments other processes may concurrently be
                    # reading to build their own permutation (shard dirs
                    # overlap between layouts) — without a barrier the
                    # rewrite is racy, so a MULTI-process elastic resume
                    # stays trace-less; single-process elastic (and all
                    # same-layout resumes) keep full traces
                    plog = None
                if plog is not None and not plog.reshard(depth, pending):
                    plog = None  # old segments unreadable: trace-less
                from ..obs import tracer as _obs_t

                _obs_t.event(
                    "elastic-reshard",
                    depth=depth,
                    from_shards=old_D,
                    to_shards=D,
                    from_procs=old_P,
                    to_procs=jax.process_count(),
                )
                _met.inc("kspec_elastic_reshards_total")
            elif host_sets is not None and use_disk:
                # per-shard tiered sets: restore IN PLACE from the
                # checkpointed run manifests + hot dumps (the runs stay on
                # disk; the checkpoint only references them)
                src = (
                    part_arrays[f"host{my_proc}"]
                    if is_multiprocess()
                    else snap
                )
                mans = json.loads(str(src["spill_manifest"]))
                hot_flat, lens = src["host_hot"], src["host_hot_lens"]
                at = 0
                for d, ln in enumerate(lens):
                    ln = int(ln)
                    if host_sets[d] is not None:
                        host_sets[d].restore(mans[d], hot_flat[at : at + ln])
                    at += ln
            elif host_sets is not None:
                from ..native import FpSet

                if is_multiprocess():
                    part = part_arrays[f"host{my_proc}"]
                    fps_flat, lens = part["host_fps"], part["host_lens"]
                else:
                    fps_flat, lens = snap["host_fps"], snap["host_lens"]
                at = 0
                host_sets = []
                for d, ln in enumerate(lens):
                    if shard_proc[d] != my_proc:
                        host_sets.append(None)
                        at += int(ln)
                        continue
                    s = FpSet(initial_capacity=max(64, 2 * int(ln)))
                    s.insert(fps_flat[at : at + int(ln)])
                    at += int(ln)
                    host_sets.append(s)
            elif visited_backend == "device-hash":
                lens = snap["hash_lens"]
                flat_hi, flat_lo = snap["hash_hi"], snap["hash_lo"]
                shard_visited = lens.astype(np.int64)
                per_shard, at = [], 0
                for ln in lens:
                    ln = int(ln)
                    per_shard.append(
                        (flat_hi[at : at + ln], flat_lo[at : at + ln])
                    )
                    at += ln
                vhi, vlo, vcap = _shard_tables_from_pairs(
                    per_shard, _HASH_MIN_CAP
                )
            else:
                vcap = int(snap["vcap"])
                vn = snap["vn"]
                w = snap["vhi"].shape[1]
                pad = np.full((D, vcap - w), 0xFFFFFFFF, np.uint32)
                vhi = np.concatenate([snap["vhi"], pad], axis=1)
                vlo = np.concatenate([snap["vlo"], pad], axis=1)
            if plog is not None and not elastic_resumed and not plog.resume(
                depth
            ):
                plog = None  # no resolvable epochs: trace-less as before
        if is_multiprocess():
            # split-brain guard: each process verifies its own part files,
            # so per-host corruption could make hosts fall back to
            # DIFFERENT generations — resuming the replicated lockstep
            # loop at mismatched depths would desync the collectives.
            # All processes vote their resume level (0 = fresh start) and
            # must agree exactly.  (64Ki levels is far beyond any real
            # diameter; the vote is one cheap allgather.)
            vote = np.zeros(1 << 16, bool)
            vote[min(depth, vote.size - 1)] = True
            if or_across_processes(vote).sum() != 1:
                raise ValueError(
                    "checkpoint resume disagreement: processes verified "
                    "different checkpoint generations (per-host part "
                    "corruption?) — restore or delete "
                    f"{checkpoint_dir} and restart"
                )

    if use_disk and not resumed:
        # fresh out-of-core run: each owned shard claims its run
        # directory and seeds its init fingerprints
        for d in range(D):
            if host_sets[d] is not None:
                host_sets[d].start_fresh()
                sel = np.nonzero(owner0 == d)[0]
                if len(sel):
                    host_sets[d].insert(_u64(hi0[sel], lo0[sel]))

    if chain is not None and not resumed:
        chain.fold(_integ.pair_u64(hi0, lo0))
        chain.seal(0, n0)

    # shard1 keeps its historical name as the [D, cap] per-shard-table
    # layout for the growth helpers
    shard1 = layouts["fpset"]
    dev_vhi = io.put(vhi, layouts["fpset"])
    dev_vlo = io.put(vlo, layouts["fpset"])
    dev_vn = io.put(vn, layouts["pershard"])

    # async-checkpoint bookkeeping (KSPEC_OVERLAP; mirrors engine.bfs):
    # `last_ckpt_depth` = submitted, `ckpt_durable_depth` = promoted.
    # Crash deferral / flip gating key on durability; completion
    # callbacks (deletion-barrier advance, chain read-back) run on THIS
    # thread in submission order as saves promote.
    ckpt_durable_depth = last_ckpt_depth
    ckpt_cbs: list = []

    def _ckpt_poll(block: bool = False) -> None:
        nonlocal ckpt_durable_depth
        if ckpt_worker is None or ckpt_store is None:
            return
        done = (
            ckpt_store.drain_async() if block else ckpt_store.poll_async()
        )
        for d, path in done:
            cb = ckpt_cbs.pop(0) if ckpt_cbs else None
            if cb is not None:
                cb(path)
            ckpt_durable_depth = (
                d if ckpt_durable_depth is None
                else max(ckpt_durable_depth, d)
            )

    def _store_save(arrays, part=None, on_done=None,
                    sync: bool = False) -> None:
        """One checkpoint-store write, sync or on the writer thread.
        `on_done(path)` runs after the atomic promote — on this thread
        at the next _ckpt_poll when async (barrier advances and chain
        read-backs stay on the engine thread / writer respectively)."""
        nonlocal ckpt_durable_depth
        if ckpt_worker is not None and not sync:
            ckpt_cbs.append(on_done)
            ckpt_store.save_async(depth, arrays, part=part)
            return
        path = ckpt_store.save(depth, arrays, part=part)
        if on_done is not None:
            on_done(path)
        ckpt_durable_depth = (
            depth if ckpt_durable_depth is None
            else max(ckpt_durable_depth, depth)
        )

    def _advance_spill_gc(marks=None):
        # a new durable generation exists: advance each owned tiered
        # set's deferred-deletion barrier (merged-away runs older than
        # every retained generation get unlinked).  `marks` (async
        # saves) restrict the advance to the files scheduled before the
        # save's snapshot — see storage.tiered.DeferredDeleter.mark
        if use_disk:
            for s in host_sets:
                if s is not None:
                    s.deleter.on_save(
                        upto=None if marks is None else marks.get(id(s))
                    )

    def _gc_marks():
        return (
            {
                id(s): s.deleter.mark()
                for s in host_sets
                if s is not None
            }
            if use_disk
            else None
        )

    def _levels_for_save():
        """The coordinator main's levels array, with the flip@ckpt
        CRC-consistent corruption injected BEFORE the manifest is built
        (resilience.integrity; the post-save read-back + the load-time
        chain validator are what must catch it)."""
        levels_arr = np.asarray(levels)
        # anchored-only, like every flip injection: an unanchored chain
        # cannot detect what it corrupts (engine.bfs._save_checkpoint)
        if chain is not None and chain.anchored and fault.flip(
            "ckpt", depth, ckpt_depth=ckpt_durable_depth
        ):
            levels_arr = levels_arr.copy()
            _integ.flip_bit(levels_arr)
        return levels_arr

    def _save_checkpoint(sync: bool = False):
        if host_sets is not None and use_disk:
            # record run manifests + hot dumps — the runs ARE the durable
            # state; the checkpoint references them
            hots = [
                s.hot_dump() if s is not None else np.empty(0, np.uint64)
                for s in host_sets
            ]
            payload = {
                "host_hot": np.concatenate(hots),
                "host_hot_lens": np.asarray([len(x) for x in hots]),
                "spill_manifest": json.dumps(
                    [s.manifest() if s is not None else None for s in host_sets]
                ),
                # layout stamp: parts pair with mains by (depth, layout) —
                # after an elastic re-save a stale old-layout part can
                # share the depth (resilience.checkpoints._find_part)
                "mesh_D": D,
                "mesh_P": jax.process_count(),
            }
            marks = _gc_marks()
            if is_multiprocess():
                # non-coordinators: the part save is their only write —
                # the deletion barrier advances when IT promotes
                _store_save(
                    payload,
                    part=f"host{my_proc}",
                    on_done=(
                        None
                        if is_coordinator()
                        else lambda _p, m=marks: _advance_spill_gc(m)
                    ),
                    sync=sync,
                )
                extra = {}
            else:
                extra = payload
            if not is_coordinator():
                return
            main = dict(
                pending=np.concatenate(pending)
                if any(p.shape[0] for p in pending)
                else np.empty((0, K), np.uint32),
                pending_lens=np.asarray([p.shape[0] for p in pending]),
                vcap=vcap,
                levels=_levels_for_save(),
                total=total,
                **extra,
                **chain_stamp(chain),
            )
            # single-process runs carry the payload (incl. its layout
            # stamp) inline; multi-process mains stamp their own
            main["mesh_D"] = D
            main["mesh_P"] = jax.process_count()

            def _main_done(path, m=marks, d=depth):
                _advance_spill_gc(m)
                readback_chain(chain, path, d)

            _store_save(main, on_done=_main_done, sync=sync)
            return
        if host_sets is not None:
            dumps = [
                s.dump() if s is not None else np.empty(0, np.uint64)
                for s in host_sets
            ]
            if is_multiprocess():
                # per-host ownership: each process persists its own shards
                # in a sidecar part file; a same-layout resume is symmetric
                # (the mesh_D/mesh_P stamps pair parts with mains), and an
                # elastic resume reads every old host's part to re-bucket.
                # The part carries the level it snapshots: a crash between
                # the part writes and the coordinator's main write would leave
                # parts one level ahead of (or behind) the main file, and
                # resuming such a torn pair would silently skip the
                # re-expanded frontier's subtrees — the depth cross-check
                # on load skips that generation (falling back to an older
                # consistent one) instead.
                _store_save(
                    dict(
                        host_fps=np.concatenate(dumps),
                        host_lens=np.asarray([len(x) for x in dumps]),
                        mesh_D=D,
                        mesh_P=jax.process_count(),
                    ),
                    part=f"host{my_proc}",
                    sync=sync,
                )
                extra = {}
            else:
                extra = {
                    "host_fps": np.concatenate(dumps)
                    if dumps
                    else np.empty(0, np.uint64),
                    "host_lens": np.asarray([len(x) for x in dumps]),
                }
        elif visited_backend == "device-hash":
            # dump each shard's live pairs (slot order is rebuilt on
            # resume by reinsertion)
            th = io.fetch(dev_vhi)
            tl = io.fetch(dev_vlo)
            live = ~((th == hashset.SENT) & (tl == hashset.SENT))
            extra = {
                "hash_hi": th[live],
                "hash_lo": tl[live],
                "hash_lens": live.sum(axis=1),
            }
        else:
            # trim the common sentinel tail (rebuilt on resume from vcap)
            vn_np = io.fetch(dev_vn)
            extra = {
                "vhi": io.fetch(dev_vhi)[:, : int(vn_np.max())],
                "vlo": io.fetch(dev_vlo)[:, : int(vn_np.max())],
                "vn": vn_np,
            }
        if chain is not None and chain.anchored:
            # flip@fpset injection + the save-time cumulative-digest
            # self-check (pre-write: detected corruption never enters a
            # checkpoint).  The full visited multiset is process-local
            # only outside the per-host-parts layout, so multiprocess
            # host runs skip (their per-host dumps are partial by design)
            pk = None
            if host_sets is not None and not is_multiprocess():
                pk = "host_fps"
            elif visited_backend == "device-hash":
                pk = "hash_hi"
            elif host_sets is None:
                pk = "vhi"
            if pk is not None and pk in extra:
                if fault.flip("fpset", depth, ckpt_depth=last_ckpt_depth):
                    corrupted = np.array(extra[pk], copy=True)
                    _integ.flip_bit(corrupted)
                    extra[pk] = corrupted
                if pk == "host_fps":
                    dump_fps = np.asarray(extra["host_fps"], np.uint64)
                elif pk == "hash_hi":
                    dump_fps = _integ.pair_u64(
                        extra["hash_hi"], extra["hash_lo"]
                    )
                else:
                    vhi_np = np.asarray(extra["vhi"])
                    vlo_np = np.asarray(extra["vlo"])
                    vns = np.asarray(extra["vn"]).ravel()
                    dump_fps = np.concatenate(
                        [
                            _integ.pair_u64(
                                vhi_np[d, : int(n)], vlo_np[d, : int(n)]
                            )
                            for d, n in enumerate(vns.tolist())
                        ]
                    ) if vns.size else np.empty(0, np.uint64)
                _integ.count_check()
                chain.verify_visited(dump_fps, depth=depth)
        if not is_coordinator():
            return  # one writer per job; all processes hold identical state
        _store_save(
            dict(
                pending=np.concatenate(pending)
                if any(p.shape[0] for p in pending)
                else np.empty((0, K), np.uint32),
                pending_lens=np.asarray([p.shape[0] for p in pending]),
                vcap=vcap,
                levels=_levels_for_save(),
                total=total,
                mesh_D=D,
                mesh_P=jax.process_count(),
                **extra,
                **chain_stamp(chain),
            ),
            on_done=lambda p, d=depth: readback_chain(chain, p, d),
            sync=sync,
        )

    # Resource governance (resilience.resources): disk budget over the
    # spill + checkpoint dirs, RSS/deadline watchdogs, injected stall —
    # per process (each host watches its own disk/RSS; in a fleet the
    # breaching process exits typed and the supervisor classifies it)
    governor = ResourceGovernor.from_env(
        disk_budget=disk_budget,
        watch_dirs=[spill_base, checkpoint_dir],
        fault_plan=fault,
    )

    def _final_save():
        # checkpoint-then-clean-exit: persist the just-completed level
        # even off the checkpoint_every cadence.  Synchronous + drained:
        # the typed exit's contract is a DURABLE on-disk state
        nonlocal last_ckpt_depth
        if ckpt_store is None:
            return
        _ckpt_poll(block=True)
        if last_ckpt_depth != depth or ckpt_durable_depth != depth:
            _save_checkpoint(sync=True)
            last_ckpt_depth = depth

    def _reclaim():
        # soft-breach reclamation (docs/resilience.md): tmp janitor ->
        # eager per-shard merges -> fresh checkpoint -> prune generations
        # (coordinator; parts of pruned gens go with them) -> flush each
        # owned shard's deletion barrier
        nonlocal last_ckpt_depth
        merged = False
        if use_disk:
            from ..storage.atomic import sweep_tmp

            for s in host_sets:
                if s is not None:
                    # quiesce the merge worker BEFORE the tmp sweep: a
                    # background merge's half-written tmp is live work,
                    # not a stray (PR 10 small fix; regression-tested)
                    s.quiesce()
                    sweep_tmp(s.dir)
                    if len(s.runs) > 1:
                        s.merge()
                        merged = True
        if ckpt_store is not None:
            _ckpt_poll(block=True)
            # save only when something changed since the periodic save at
            # this depth (same guard as engine.bfs._reclaim)
            if merged or last_ckpt_depth != depth or \
                    ckpt_durable_depth != depth:
                _save_checkpoint(sync=True)
                last_ckpt_depth = depth
            if is_coordinator():
                ckpt_store.prune(keep_gens=1)
            if use_disk:
                for s in host_sets:
                    if s is not None:
                        s.deleter.flush()

    if elastic_resumed:
        # persist one generation in the NEW layout immediately: a crash
        # before the next periodic save then resumes into this layout
        # without re-paying the re-bucketing read, and for the disk tier
        # the re-bucketed runs become durably referenced before any old
        # run can start aging out of the deletion barrier
        _save_checkpoint()

    # per level, shard-major discovery order: (rows, parent_global, act)
    trace_store = []
    if store_trace:
        init_rows = np.concatenate(pending) if n0 else np.empty((0, K), np.uint32)
        trace_store.append(
            (init_rows, np.full(n0, -1, np.int64), np.full(n0, -1, np.int64))
        )
    if plog is not None and not resumed:
        # level 0 = the init states, parentless, in shard-major order
        plog.start_fresh()
        plog.write_level(
            0,
            pending,
            [np.full(p.shape[0], -1, np.int64) for p in pending],
            [np.full(p.shape[0], -1, np.int64) for p in pending],
        )
    # parent/act bookkeeping is needed by EITHER trace consumer (the
    # in-RAM store or the on-disk per-shard parent logs)
    collect_trace = store_trace or plog is not None

    def build_violation(inv_name, d_level, idx):
        # (the per-shard on-disk parent logs are what makes sharded
        # traces survive a checkpoint resume)
        on_disk = not store_trace and plog is not None \
            and plog.has_levels(d_level)
        return _build_violation(
            model, trace_store if store_trace else None,
            plog.view() if on_disk else None, inv_name, d_level, idx,
            obs=obs_,
        )

    _shard_beat(depth, event="start", resumed=bool(resumed))
    cut = False
    exhausted: Optional[ResourceExhausted] = None
    integrity_fail: Optional[IntegrityError] = None
    from ..storage.parent_log import ParentLogCorrupt
    from ..storage.runs import RunCorrupt

    try:
        while any(p.shape[0] for p in pending):
            # async join point (every process joins identically — the
            # workers' job streams are replicated-deterministic): adopt
            # finished merges/checkpoint promotes, surface worker errors.
            # BLOCKING under an armed fault plan so deterministic
            # injection never depends on writer-thread timing
            _ckpt_poll(block=bool(fault.specs))
            if use_disk:
                for s in host_sets:
                    if s is not None:
                        if fault.specs:
                            s.quiesce()
                        s.poll_merge()
            lvl_io0 = _io_counters()
            # level-boundary fault injection point (resilience.faults); the
            # plan derives from the replicated env, so every process raises
            # (or not) in lockstep; crash deferral keys on the DURABLE
            # checkpoint depth (an in-flight async save must not arm a
            # crash whose restart would not converge)
            fault.crash("level", depth, ckpt_depth=ckpt_durable_depth)
            if chain is not None:
                sp = fault.flip(
                    "frontier", depth, ckpt_depth=ckpt_durable_depth
                )
                if sp:
                    # a shard scope targets THAT shard's pending buffer
                    # (falling back to the first non-empty one when the
                    # targeted shard happens to own no rows this level —
                    # an empty buffer has no bit to flip)
                    d0 = sp.shard if sp.shard is not None else 0
                    if pending[d0].size == 0:
                        d0 = next(
                            (d for d in range(D) if pending[d].size), d0
                        )
                    _integ.flip_bit(pending[d0])
                # frontier verify: the pending shards' combined multiset
                # must digest to the entry sealed at discovery (the
                # per-shard split is layout; the multiset is the search)
                _integ.count_check()
                sp_ = obs_.open_span(
                    "frontier-verify",
                    rows=int(sum(p.shape[0] for p in pending)), lanes=K,
                    native=_integ.native_twin(spec.exact64),
                )
                try:
                    chain.verify_level(
                        depth,
                        _integ.combine_digests(
                            _integ.digest_rows(p, spec.exact64)
                            for p in pending
                            if p.shape[0]
                        ),
                    )
                finally:
                    sp_.finish()
            if max_depth is not None and depth >= max_depth:
                cut = True
                break
            if max_states is not None and total >= max_states:
                cut = True
                break
            t_level = time.perf_counter()
            obs_.level_begin(depth + 1, int(sum(p.shape[0] for p in pending)))
            governor.level_begin(depth + 1)  # arm the per-level deadline
            next_pending = [[] for _ in range(D)]
            next_parent = [[] for _ in range(D)]
            next_act = [[] for _ in range(D)]
            lvl_act_en = np.zeros(len(model.actions), np.int64)
            lvl_new_per_shard = np.zeros(D, np.int64)
            # per-shard breakdowns for the stats stream (exchange imbalance is
            # invisible in coordinator-aggregated totals): enabled candidates
            # per SOURCE shard, and — host backend, where the coordinator sees
            # the novelty masks — received candidates per OWNER shard
            lvl_en_per_shard = np.zeros(D, np.int64)
            lvl_recv_per_shard = np.zeros(D, np.int64)
            # pipeline.work_counts of the committed dispatches, all shards:
            # probe rounds and merge slots, each beside what the form over
            # the whole capacity would have run
            lvl_work = np.zeros(len(WORK_FIELDS), np.int64)
            # chunks committed, and the width their dedup sides were handed
            # (`R` a shard a chunk), summed over chunks and shards: the
            # single-device record's `chunks` and `dedup_lanes`
            lvl_chunks = lvl_lanes = lvl_guard = 0
            lvl_exch_bytes = lvl_exch_raw_bytes = 0
            # dispatched collective-bearing programs this level — one
            # launch PER SHARD each (the kspec_shard_launches_level
            # gauge and the device path's O(1)/level contract)
            lvl_dispatches = 0
            lvl_probe_ms = 0.0  # deferred batched host-probe wall
            # step_ms / host_ms of the level record, as engine.check's:
            # step = dispatch + the blocking wait on a program's flags;
            # host = frontier assembly before it and the commit after it
            prof_step = prof_host_s = 0.0
            offs = [0] * D
            # base offset of each shard's rows in this level's shard-major order
            prev_base = np.concatenate([[0], np.cumsum([p.shape[0] for p in pending])])
            verdict = None  # (inv_name, frontier_row_np, global_idx)

            def _build_chunk():
                """Assemble the next chunk's per-shard frontier slice, or
                None when the level is exhausted."""
                nonlocal prof_host_s
                rem = max(p.shape[0] - o for p, o in zip(pending, offs))
                if rem <= 0:
                    return None
                t_build = time.perf_counter()
                governor.poll(depth)  # deadline watchdog (cheap)
                bucket = min(_next_pow2(max(rem, min_bucket // D, 32)), chunk)
                frontier = np.zeros((D, bucket, K), np.uint32)
                took = np.zeros(D, np.int32)
                chunk_off = np.asarray(offs, np.int64)
                for d in range(D):
                    rows = pending[d][offs[d] : offs[d] + bucket]
                    frontier[d, : rows.shape[0]] = rows
                    took[d] = rows.shape[0]
                    offs[d] += rows.shape[0]
                fvalid = np.arange(bucket)[None, :] < took[:, None]
                t_chunk = time.perf_counter()
                prof_host_s += t_chunk - t_build
                # (the last slot counts this chunk's dispatch attempts)
                return [bucket, frontier, took, chunk_off, fvalid,
                        t_chunk, 0]

            def _attempt_once(ctx, attempt, w_try, compress=None):
                """Dispatch ONE attempt of a chunk (no flag fetches) with
                the shared failure policy applied around the dispatch.
                -> (outs, (attempt, w_try, ca, T, W, R)).  The overflow-
                retry ladder lives in _flags_retry/_resolve_chunk: a
                uniform-shift expansion overflow escalates to per-action
                adaptive widths seeded from the overflowing attempt's
                guard counts (or, with adaptation off, steps the shift
                toward the full path); a per-action overflow doubles the
                offending buffers (floored for the rest of the run);
                destination-bucket (or compressed-payload) overflow
                doubles the per-dest width.  A failed attempt's visited
                arrays are simply discarded (the step is functional), so
                results stay exact at every width.  Width retries are
                CHUNK-LOCAL (learned floors persist)."""
                nonlocal vcap, dev_vhi, dev_vlo, chunk, adaptive_fallback
                nonlocal lvl_dispatches, prof_step
                if compress is None:
                    compress = compress_on
                bucket = ctx[0]
                t_att = time.perf_counter()
                while True:
                    launch = None
                    if isinstance(attempt, int):
                        ca = _norm_shift(bucket, attempt) or None
                    else:
                        ca = attempt  # per-action width tuple, or None (full)
                    T = expander.expand_width(bucket, ca)
                    W = min(T, _default_dest_w(T, D) << w_try)
                    R = D * W if exchange == "all_to_all" else D * T
                    if visited_backend == "device-hash":
                        # keep every shard's table under ~1/2 load so linear
                        # probing stays short (shard_visited is host-tracked)
                        if 2 * int(shard_visited.max()) > vcap:
                            dev_vhi, dev_vlo, vcap = _grow_hash_tables(
                                dev_vhi, dev_vlo, 2 * vcap, shard1, io
                            )
                    if visited_backend == "device":
                        # grow per-shard visited capacity for the worst-case merge
                        # (one shared growth path with the device level driver)
                        need = int(io.fetch(dev_vn).max()) + R
                        if need > vcap:
                            dev_vhi, dev_vlo, vcap = _grow_sorted_shards(
                                dev_vhi, dev_vlo, vcap, _next_pow2(need),
                                layouts["fpset"], io,
                            )

                    # everything that shapes the program: the cache
                    # outlives this call (it is the model's)
                    key = (STEP_TAG, mesh, bucket, vcap, ca, exchange, W,
                           compress, visited_backend,
                           expander.inv_sig(True))
                    try:
                        # exchange-step fault injection point (the jitted step
                        # below carries the all_to_all/all_gather exchange)
                        injected = fault.chunk_error(
                            escalated=isinstance(ca, (list, tuple))
                        )
                        if injected is not None:
                            raise injected
                        fn = expander.cached(
                            key,
                            lambda: _make_sharded_step(
                                model,
                                mesh,
                                bucket,
                                vcap,
                                compact=ca,
                                exchange=exchange,
                                dest_w=W,
                                with_merge=visited_backend == "device",
                                hash_table=visited_backend == "device-hash",
                                compress=compress,
                            ),
                            program=STEP_TAG, bucket=bucket, vcap=vcap,
                        )
                        rows_d = io.put(
                            ctx[1].reshape(D * bucket, K),
                            layouts["frontier"],
                        )
                        valid_d = io.put(
                            ctx[4].reshape(D * bucket), layouts["fvalid"]
                        )
                        launch = io.dispatch(
                            STEP_TAG, attempt=ctx[6], depth=depth,
                            bucket=bucket, vcap=vcap,
                        )
                        ctx[6] += 1
                        outs = fn(rows_d, valid_d, dev_vhi, dev_vlo, dev_vn)
                        lvl_dispatches += 1
                    except Exception as e:  # noqa: BLE001 — XLA compile/run
                        if launch is not None:
                            launch.finish(discarded=True)
                        # one failure policy for both engines (resilience
                        # .retry.ChunkRetryHandler): transient -> bounded-
                        # backoff re-run of the same attempt (the functional
                        # step committed nothing); failed ESCALATED compile ->
                        # uniform fallback; else re-raise.  Transient retry is
                        # single-process only: a REAL transient error is
                        # per-host, and one host re-issuing the collective
                        # while its peers don't would desync the replicated
                        # lockstep loop — multi-process jobs surface it to the
                        # supervisor's restart-from-checkpoint layer instead.
                        action = chunk_retry.handle(
                            e,
                            escalated=isinstance(ca, (list, tuple)),
                            depth=depth,
                            retry_transient=not is_multiprocess(),
                        )
                        if action == "retry":
                            continue
                        if action == "degrade_chunk":
                            # device RESOURCE_EXHAUSTED: identical shapes would
                            # die identically — halve the streaming chunk for
                            # the rest of the run (single-process only: the
                            # handler re-raises under multiprocess, where a
                            # lone process shrinking would desync the fleet)
                            chunk = max(_next_pow2(max(32, min_bucket // D)),
                                        chunk >> 1)
                        expander._cache.pop(key, None)
                        attempt = adapt.compile_fallback(bucket)
                        adaptive_fallback = True
                        continue
                    prof_step += time.perf_counter() - t_att
                    return outs, (attempt, w_try, ca, T, W, R, compress,
                                  launch)

            def _flags_retry(ctx, outs, meta):
                """Fetch the attempt's overflow flags; -> None when it
                committed clean, else the (attempt, w_try) to re-run
                with (applying the escalation/widening/table-growth
                policy — see _attempt_once's docstring)."""
                nonlocal vcap, dev_vhi, dev_vlo
                attempt, w_try, ca, T, W, R, compress, _launch = meta
                ovf_expand, act_guard = outs[12], outs[13]
                ovf_dest, ovf_probe = outs[14], outs[15]
                if ca is not None:
                    ovf_np = io.fetch(ovf_expand)  # [D, n_actions]
                    if ovf_np.any():
                        return (
                            adapt.escalate(
                                attempt,
                                ovf_np.any(axis=0),
                                ctx[0],
                                _shard_density(
                                    io.fetch(act_guard), ctx[2]
                                ),
                            ),
                            w_try,
                            compress,
                        )
                if exchange == "all_to_all" and io.fetch(
                    ovf_dest
                ).any():
                    if W < T:
                        return (attempt, w_try + 1, compress)
                    if compress:
                        # the raw path CANNOT overflow at full width (every
                        # candidate fits W == T slots) — only the codec's
                        # packed-stream / compact-row budgets can.  The
                        # ladder is topped out, so this chunk falls back
                        # to the RAW exchange (results identical; only
                        # the wire layout changes)
                        return (attempt, w_try, False)
                if visited_backend == "device-hash" and bool(
                    io.fetch(ovf_probe).any()
                ):
                    # a shard exhausted its probe budget: grow every
                    # shard's table and re-run the chunk (the attempt's
                    # returned tables are discarded — the step is
                    # functional, so nothing was committed)
                    dev_vhi, dev_vlo, vcap = _grow_hash_tables(
                        dev_vhi, dev_vlo, 2 * vcap, shard1, io
                    )
                    return (attempt, w_try, compress)
                return None

            def _resolve_chunk(st):
                """Flag-check a dispatched chunk, re-running the ladder
                synchronously on any overflow, then install the committed
                attempt's visited arrays."""
                nonlocal dev_vhi, dev_vlo, dev_vn, prof_step
                ctx, outs, meta = st
                while True:
                    t_wait = time.perf_counter()
                    nxt = _flags_retry(ctx, outs, meta)
                    prof_step += time.perf_counter() - t_wait
                    # the flag reads blocked on the program: its outputs
                    # are in hand, to keep or (an overflow) to throw away
                    meta[-1].finish(discarded=nxt is not None)
                    if nxt is None:
                        break
                    outs, meta = _attempt_once(
                        ctx, nxt[0], nxt[1], compress=nxt[2]
                    )
                st[1], st[2] = outs, meta
                dev_vhi, dev_vlo, dev_vn = outs[4], outs[5], outs[6]

            def _commit_sharded(st):
                """Commit one resolved chunk: exchange framing check,
                verdict checks, output fetches and per-shard host-set
                inserts/trace/digest accumulation.  Commits run strictly
                in dispatch order; returns True when a verdict fired."""
                nonlocal verdict, lvl_act_en, lvl_new_per_shard
                nonlocal lvl_en_per_shard, lvl_recv_per_shard
                nonlocal shard_visited, lvl_exch_bytes, lvl_exch_raw_bytes
                nonlocal lvl_chunks, lvl_lanes, lvl_guard
                ctx, outs, meta = st
                bucket, frontier, took, chunk_off, _fv, t_chunk, _n = ctx
                _attempt, _wt, _ca, T, W, R, compress, _launch = meta
                lvl_chunks += 1
                lvl_lanes += D * R
                lvl_guard += D * bucket * C
                (
                    out, out_parent, out_act, new_n, _vh, _vl, _vn,
                    viol_any, viol_idx, dl_any, dl_idx, act_en,
                    _ovfe, act_guard, _ovfd, _ovfp,
                    out_hi, out_lo, sent_dig, recv_dig,
                ) = outs
                # exchange framing check (resilience.integrity): across
                # the whole mesh, the received candidate multiset must
                # combine to exactly the sent one — XOR/sum digests are
                # commutative, so per-shard records compare globally.
                # flip@exchange drives the detector's observation (like
                # stall@level does the watchdog's): a real ICI bit flip
                # desyncs the same two in-jit digests.  With the
                # compressed exchange the received digest is computed
                # over the DECODED payload, so the codec + headers are
                # inside the protection boundary.
                if chain is not None:
                    sd = np.asarray(io.fetch(sent_dig), np.uint32)
                    rd = np.array(io.fetch(recv_dig), np.uint32)
                    sp = fault.flip(
                        "exchange", depth + 1, ckpt_depth=ckpt_durable_depth
                    )
                    if sp:
                        rd[sp.shard if sp.shard is not None else 0, 1] ^= 0x10
                    _integ.count_check()
                    if _combine_digs(sd) != _combine_digs(rd):
                        raise IntegrityError(
                            "exchange",
                            f"exchange payload framing mismatch at level "
                            f"{depth + 1}: sent digest {_combine_digs(sd)} "
                            f"!= received {_combine_digs(rd)} ({exchange}; "
                            f"a routed fingerprint was corrupted in "
                            f"flight)",
                            depth=depth,
                        )
                # adapt buffer sizing from the committed attempt's guard counts
                # (mirrors engine.check; no-op until escalation activates)
                adapt.observe(_shard_density(io.fetch(act_guard), took))
                # exchange wire accounting (ROADMAP item 5's measure):
                # bytes this chunk's all_to_all actually moved vs the raw
                # (uncompressed) layout's bytes at the same widths
                if exchange == "all_to_all":
                    raw_b = D * D * W * (8 + 4 * K + 4 + 4)
                    if compress:
                        from ..ops import fpcompress as _fpc

                        Wr = max(32, W // 2)
                        sent_b = D * D * (
                            4 * _fpc.default_stream_words(W)
                            + 4 * _fpc.header_words(W)
                            + Wr * (4 * K + 4 + 1)
                        )
                    else:
                        sent_b = raw_b
                    lvl_exch_bytes += sent_b
                    lvl_exch_raw_bytes += raw_b
                # (the span ends here, so its start is now minus the timer)
                obs_.chunk_span(
                    "exchange",
                    _now() - (time.perf_counter() - t_chunk),
                    depth=depth,
                    bucket=bucket,
                    exchange=exchange,
                    compressed=compress,
                )
                # frontier-level verdicts (states being expanded = level `depth`)
                viol_any_np = io.fetch(viol_any)  # [D, n_inv]
                if viol_any_np.any():
                    inv_i = int(np.argmax(viol_any_np.any(axis=0)))
                    d = int(np.argmax(viol_any_np[:, inv_i]))
                    idx = int(io.fetch(viol_idx)[d, inv_i])
                    gidx = int(prev_base[d] + chunk_off[d] + idx)
                    verdict = (model.invariants[inv_i].name, frontier[d, idx], gidx)
                    return True
                if check_deadlock and io.fetch(dl_any).any():
                    d = int(np.argmax(io.fetch(dl_any)))
                    idx = int(io.fetch(dl_idx)[d])
                    gidx = int(prev_base[d] + chunk_off[d] + idx)
                    verdict = ("Deadlock", frontier[d, idx], gidx)
                    return True
                counts = io.fetch(new_n)
                # received candidates per OWNER shard (post-exchange, pre-host-
                # dedup on the host backend; == novel on device backends)
                lvl_recv_per_shard += counts.astype(np.int64)
                M_per = out.shape[0] // D
                # device-side slice to the widest shard before the host copy —
                # the padded buffer is mostly empty
                cmax = int(counts.max())
                out3 = io.fetch(out.reshape(D, M_per, K)[:, :cmax])
                if collect_trace:
                    parent_np = io.fetch(out_parent.reshape(D, M_per)[:, :cmax])
                    act_np = io.fetch(out_act.reshape(D, M_per)[:, :cmax])
                if host_sets is not None and cmax:
                    hi3 = io.fetch(out_hi.reshape(D, M_per)[:, :cmax])
                    lo3 = io.fetch(out_lo.reshape(D, M_per)[:, :cmax])
                    # global dedup: each shard's OWNER process inserts into its
                    # FpSet (batch dedup already happened on device; insert()
                    # returns the first-time mask); the masks are OR-merged so
                    # every process sees the identical novelty decision
                    masks = np.zeros((D, cmax), bool)
                    for d in range(D):
                        c = int(counts[d])
                        if c and host_sets[d] is not None:
                            masks[d, :c] = host_sets[d].insert(
                                _u64(hi3[d, :c], lo3[d, :c])
                            ).astype(bool)
                    masks = or_across_processes(masks)
                newc = np.zeros(D, np.int64)
                for d in range(D):
                    c = int(counts[d])
                    if not c:
                        continue
                    rows = out3[d, :c]
                    p = parent_np[d, :c].astype(np.int64) if collect_trace else None
                    a = act_np[d, :c].astype(np.int64) if collect_trace else None
                    if host_sets is not None:
                        mask = masks[d, :c]
                        rows = rows[mask]
                        if collect_trace:
                            p, a = p[mask], a[mask]
                        c = rows.shape[0]
                        if not c:
                            continue
                    next_pending[d].append(rows)
                    if chain is not None:
                        # fold this shard's new states into the level
                        # digest via the host fingerprint twin (rows are
                        # what the host actually keeps — digesting them,
                        # then checking the chain against the device
                        # fingerprints at save time, cross-checks the
                        # two representations for free)
                        chain.fold_digest(
                            *_integ.digest_rows(rows, spec.exact64)
                        )
                    if collect_trace:
                        # step parents are d_src*bucket + i within this padded
                        # chunk -> level-global index in shard-major order
                        src_d = p // bucket
                        src_i = p % bucket
                        next_parent[d].append(
                            prev_base[src_d] + chunk_off[src_d] + src_i
                        )
                        next_act[d].append(a)
                    newc[d] = c
                lvl_new_per_shard += newc
                shard_visited += newc
                if obs_.collect:
                    act_en_np, work = split_counts(io.fetch(act_en))
                    lvl_act_en += act_en_np.sum(axis=0)
                    lvl_en_per_shard += act_en_np.sum(axis=1)
                    lvl_work[:] += work
                return False

            def _commit_timed(st):
                """_commit_sharded, its wall booked to `host_ms`."""
                nonlocal prof_host_s
                t_c = time.perf_counter()
                try:
                    return _commit_sharded(st)
                finally:
                    prof_host_s += time.perf_counter() - t_c

            def _run_device_level():
                """The sharded device-resident level path (--pipeline
                device): dispatch ONE _make_sharded_level program
                covering this level's full-size serial chunks, with the
                <=1 exact-bound re-dispatch on overflow, then commit its
                outputs exactly as the per-chunk commits would have —
                O(1) collective-bearing launches per shard per level.
                On success `offs` advances past the handled prefix so
                the per-chunk loop below runs only the (sub-bucket)
                tail at its serial offsets; on failure it marks the
                sticky fallback and leaves offs untouched (the
                per-chunk ladder runs the whole level)."""
                nonlocal vcap, dev_vhi, dev_vlo, dev_vn, verdict
                nonlocal lvl_act_en, lvl_new_per_shard, lvl_en_per_shard
                nonlocal lvl_recv_per_shard, shard_visited
                nonlocal lvl_exch_bytes, lvl_exch_raw_bytes
                nonlocal lvl_dispatches, lvl_probe_ms
                nonlocal prof_step, prof_host_s, lvl_chunks, lvl_lanes
                nonlocal lvl_guard
                lens = [p.shape[0] for p in pending]
                plan = sdev.plan_level(lens, chunk, min_bucket)
                if plan is None:
                    return
                B, nc = plan
                NCp = _next_pow2(nc)
                F = NCp * B
                chunk_retry.reset_chunk()
                widths = sdev.widths(B)
                T = expander.expand_width(B, widths)
                W = _default_dest_w(T, D)
                R = D * W if exchange == "all_to_all" else D * T
                # level-new ladder: ONE sizing policy with the single-
                # device device pipeline (ops/devlevel)
                LN = devlevel.level_new_capacity(T, sdev._ln_hw, nc * R)
                compress = compress_on
                exact = False
                dispatched = 0
                host_mode = sdev.host_mode
                # output-tuple indices differ between the two program
                # variants (the host program carries no visited shards
                # or digest folds, but adds the ohi/olo accumulators)
                (i_cnt, i_vk, i_vd, i_vinv, i_vix, i_aen, i_agm,
                 i_sd, i_rd, i_ovf, i_ncl) = (
                    (5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15)
                    if host_mode
                    else (3, 7, 8, 9, 10, 11, 12, 17, 18, 19, 20)
                )
                t0l = time.perf_counter()
                # only the handled prefix rides the device buffer; a
                # smaller-bucket serial tail runs per-chunk afterwards
                fbuf = np.zeros((D, F, K), np.uint32)
                flen = np.zeros(D, np.int32)
                for d in range(D):
                    n = min(nc * B, lens[d])
                    fbuf[d, :n] = pending[d][:n]
                    flen[d] = n
                pre_v = (dev_vhi, dev_vlo, dev_vn)
                program = LEVEL_HOST_TAG if host_mode else LEVEL_TAG
                inv_sig = expander.inv_sig(True)
                operands = None
                prof_host_s += time.perf_counter() - t0l
                while True:
                    t_att = time.perf_counter()
                    launch = None
                    try:
                        injected = fault.chunk_error(escalated=True)
                        if injected is not None:
                            raise injected
                        if operands is None:
                            # uploaded once: a re-dispatch reads the
                            # same device arrays
                            operands = (
                                io.put(fbuf.reshape(D * F, K),
                                       layouts["frontier"]),
                                io.put(flen, layouts["pershard"]),
                                io.put(np.full(D, nc, np.int32),
                                       layouts["pershard"]),
                            )
                        if host_mode:
                            # (no visited shards ride the host program)
                            make, at_vcap = _make_sharded_level_host, ()
                        else:
                            need = int(
                                io.fetch(pre_v[2]).max()
                            ) + min(nc * R, LN + R)
                            if need > vcap:
                                g_hi, g_lo, vcap = _grow_sorted_shards(
                                    pre_v[0], pre_v[1], vcap,
                                    _next_pow2(need), layouts["fpset"], io,
                                )
                                pre_v = (g_hi, g_lo, pre_v[2])
                            make, at_vcap = _make_sharded_level, (vcap,)
                        # everything that shapes the program: the cache
                        # outlives this call (it is the model's)
                        key = (program, mesh, B, NCp, *at_vcap, widths, LN,
                               W, exchange, compress, inv_sig,
                               check_deadlock)
                        shape = dict(bucket=B, **dict(zip(("vcap",), at_vcap)))
                        fn = expander.cached(
                            key,
                            lambda: make(
                                model, mesh, expander, B, NCp, *at_vcap,
                                widths, LN, exchange, W, compress,
                                check_deadlock,
                            ),
                            program=program, **shape,
                        )
                        launch = io.dispatch(
                            program, attempt=dispatched, depth=depth,
                            chunks=nc, level_new_cap=LN, **shape,
                        )
                        outs = fn(*operands, *(() if host_mode else pre_v))
                        dispatched += 1
                        lvl_dispatches += 1
                        # the one device sync per level: the overflow-
                        # flag read forces the whole level program
                        overflow = bool(io.fetch(outs[i_ovf]).any())
                    except Exception as e:  # noqa: BLE001 — XLA
                        if launch is not None:
                            launch.finish(discarded=True)
                        action = chunk_retry.handle(
                            e, escalated=True, depth=depth,
                            retry_transient=not is_multiprocess(),
                        )
                        if action == "retry":
                            continue
                        sdev.mark_fallback(
                            f"{type(e).__name__}: {e}"[:200], depth
                        )
                        return
                    agmax_np = io.fetch(outs[i_agm]).max(
                        axis=0
                    ).astype(np.int64)
                    vk = int(io.fetch(outs[i_vk])[0])
                    redo = overflow and vk == 0 and not exact
                    launch.finish(discarded=redo)
                    prof_step += time.perf_counter() - t_att
                    if redo:
                        # a segment / destination bucket / codec budget
                        # / the level-new set overflowed: outputs are
                        # incomplete — discard and re-dispatch ONCE from
                        # the pre-level visited state at exact measured
                        # widths, full per-destination width (the raw
                        # wire cannot overflow at W == T) and the safe
                        # level-new bound: <=2 launches per shard per
                        # level even on overflow levels.  A verdict
                        # overrides: it derives from frontier states
                        # only, so it is exact regardless.
                        widths = sdev.exact_widths(B, agmax_np)
                        T = expander.expand_width(B, widths)
                        W = T
                        R = D * W if exchange == "all_to_all" else D * T
                        LN = devlevel.level_new_bound(nc * R)
                        compress = False  # only codec budgets overflow at W==T
                        exact = True
                        continue
                    break
                # committed: install the merged visited arrays (the
                # host-mode program carries no visited shards — the
                # host sets below ARE the visited state)
                t_commit = time.perf_counter()
                lvl_chunks += nc
                lvl_lanes += nc * D * R
                lvl_guard += nc * D * B * C
                if not host_mode:
                    dev_vhi, dev_vlo, dev_vn = outs[4], outs[5], outs[6]
                counts = io.fetch(outs[i_cnt]).astype(np.int64)  # [D]
                sdev.observe(agmax_np, B, int(counts.max()))
                sdev.launches_last = dispatched
                adapt.observe(agmax_np.astype(np.float64) / max(B, 1))
                # exchange framing check over the LEVEL-accumulated
                # digests (count/xor/sum accumulate commutatively, so
                # one compare per level detects exactly what the
                # per-chunk compares detect).  A committed overflow
                # only reaches here under a verdict override; the
                # accumulators then cover the clean pre-overflow chunk
                # prefix (the `clean` mask is replicated, so every
                # shard accumulated the same subset) — compared anyway:
                # a corruption in those chunks must still alarm, it
                # must never be laundered by a later verdict
                if chain is not None:
                    sd = np.asarray(io.fetch(outs[i_sd]), np.uint32)
                    rd = np.array(io.fetch(outs[i_rd]), np.uint32)
                    sp = fault.flip(
                        "exchange", depth + 1,
                        ckpt_depth=ckpt_durable_depth,
                    )
                    if sp:
                        rd[sp.shard if sp.shard is not None else 0,
                           1] ^= 0x10
                    _integ.count_check()
                    if _combine_digs(sd) != _combine_digs(rd):
                        raise IntegrityError(
                            "exchange",
                            f"exchange payload framing mismatch across "
                            f"level {depth + 1}: sent digest "
                            f"{_combine_digs(sd)} != received "
                            f"{_combine_digs(rd)} ({exchange}, device "
                            f"level program; a routed fingerprint was "
                            f"corrupted in flight)",
                            depth=depth,
                        )
                obs_.chunk_span(
                    "exchange-level",
                    _now() - (time.perf_counter() - t0l),
                    depth=depth,
                    bucket=B,
                    chunks=nc,
                    launches=dispatched,
                    exchange=exchange,
                    compressed=compress,
                )
                # wire accounting: nclean counted chunks at the
                # committed dispatch's widths (same per-chunk formulas
                # as the per-chunk path)
                if exchange == "all_to_all":
                    ncl = int(io.fetch(outs[i_ncl])[0])
                    raw_b = D * D * W * (8 + 4 * K + 4 + 4)
                    if compress:
                        from ..ops import fpcompress as _fpc

                        Wr = max(32, W // 2)
                        sent_b = D * D * (
                            4 * _fpc.default_stream_words(W)
                            + 4 * _fpc.header_words(W)
                            + Wr * (4 * K + 4 + 1)
                        )
                    else:
                        sent_b = raw_b
                    lvl_exch_bytes += ncl * sent_b
                    lvl_exch_raw_bytes += ncl * raw_b
                if vk:
                    d = int(io.fetch(outs[i_vd])[0])
                    inv_i = int(io.fetch(outs[i_vinv])[0])
                    lidx = int(io.fetch(outs[i_vix])[0])
                    gidx = int(prev_base[d] + lidx)
                    name = (
                        model.invariants[inv_i].name
                        if vk == 1
                        else "Deadlock"
                    )
                    verdict = (name, pending[d][lidx], gidx)
                    for d2 in range(D):
                        # the serial break: the tail is never dispatched
                        offs[d2] = lens[d2]
                    prof_host_s += time.perf_counter() - t_commit
                    return
                OC = LN + R
                cmax = int(counts.max())
                if cmax:
                    out3 = io.fetch(
                        outs[0].reshape(D, OC, K)[:, :cmax]
                    )
                    if collect_trace:
                        par3 = io.fetch(
                            outs[1].reshape(D, OC)[:, :cmax]
                        )
                        act3 = io.fetch(
                            outs[2].reshape(D, OC)[:, :cmax]
                        )
                if host_mode:
                    # Deferred once-per-level batched host probe: each
                    # owner shard's FpSet / disk tier takes the level's
                    # novel candidates (unique within the level, the
                    # per-chunk sorted emission order the serial host
                    # commits replay) in ONE insert; masks are OR-merged
                    # across processes so every process sees the same
                    # novelty decision — host syncs O(1) per shard per
                    # level instead of O(chunks)
                    t_probe = time.perf_counter()
                    masks = np.zeros((D, max(cmax, 1)), bool)
                    if cmax:
                        hi3 = io.fetch(
                            outs[3].reshape(D, OC)[:, :cmax]
                        )
                        lo3 = io.fetch(
                            outs[4].reshape(D, OC)[:, :cmax]
                        )
                        for d in range(D):
                            c = int(counts[d])
                            if c and host_sets[d] is not None:
                                s = host_sets[d]
                                fps = _u64(hi3[d, :c], lo3[d, :c])
                                masks[d, :c] = (
                                    s.insert_level(fps)
                                    if hasattr(s, "insert_level")
                                    else s.insert(fps)
                                ).astype(bool)
                        masks = or_across_processes(masks)
                    newc = np.zeros(D, np.int64)
                    for d in range(D):
                        c = int(counts[d])
                        if not c:
                            continue
                        mask = masks[d, :c]
                        rows = out3[d, :c][mask]
                        c2 = rows.shape[0]
                        if not c2:
                            continue
                        next_pending[d].append(rows)
                        if chain is not None:
                            # fold the probe SURVIVORS via the host
                            # fingerprint twin, deliberately NOT the
                            # device lanes in hi3/lo3: digesting the
                            # rows the host actually keeps, then
                            # checking the chain against the device
                            # fingerprints at save time, cross-checks
                            # the two representations for free (the
                            # per-chunk host commit's exact rationale)
                            chain.fold_digest(
                                *_integ.digest_rows(rows, spec.exact64)
                            )
                        if collect_trace:
                            pg = par3[d, :c][mask].astype(np.int64)
                            next_parent[d].append(
                                prev_base[pg // F] + (pg % F)
                            )
                            next_act[d].append(
                                act3[d, :c][mask].astype(np.int64)
                            )
                        newc[d] = c2
                    lvl_probe_ms += (
                        time.perf_counter() - t_probe
                    ) * 1e3
                    obs_.chunk_span(
                        "host-probe",
                        _now() - (time.perf_counter() - t_probe),
                        depth=depth, rows=int(counts.sum()),
                        new=int(newc.sum()), batched="level",
                    )
                    lvl_new_per_shard += newc
                    lvl_recv_per_shard += counts
                    shard_visited += newc
                else:
                    for d in range(D):
                        c = int(counts[d])
                        if not c:
                            continue
                        next_pending[d].append(out3[d, :c])
                        if collect_trace:
                            pg = par3[d, :c].astype(np.int64)
                            # mesh-global level row ids -> level-global
                            # indices in shard-major order (the plan's
                            # chunk offsets are i*B, already inside pg)
                            next_parent[d].append(
                                prev_base[pg // F] + (pg % F)
                            )
                            next_act[d].append(
                                act3[d, :c].astype(np.int64)
                            )
                    if chain is not None:
                        # per-shard in-jit chain folds: the device-
                        # computed (count, xor, sum) accumulators fold
                        # bit-exactly like the per-chunk host folds
                        # over the same rows
                        _integ.fold_shard_device_digests(
                            chain,
                            io.fetch(outs[13]),
                            io.fetch(outs[14]),
                            io.fetch(outs[15]),
                            io.fetch(outs[16]),
                        )
                    lvl_new_per_shard += counts
                    lvl_recv_per_shard += counts
                    shard_visited += counts
                if obs_.collect:
                    act_en_np, work = split_counts(io.fetch(outs[i_aen]))
                    lvl_act_en += act_en_np.sum(axis=0)
                    lvl_en_per_shard += act_en_np.sum(axis=1)
                    lvl_work[:] += work
                for d in range(D):
                    offs[d] = min(nc * B, lens[d])
                prof_host_s += time.perf_counter() - t_commit

            if sdev is not None and sdev.fallback is None:
                # Device-resident level path: one dispatched while_loop
                # program per shard covers every full-size gated chunk
                # of this level; the per-chunk loop below then runs only
                # the remaining serial tail (or, on fallback, the whole
                # level) — bit-identical either way.
                governor.poll(depth)
                _run_device_level()

            # Staged commit (KSPEC_OVERLAP, host backend only — the at-
            # scale configuration; device backends chain each chunk's
            # visited arrays through the step, so their chunks serialize
            # by data flow): chunk k+1's program is dispatched — flags
            # UNREAD, so nothing blocks on it — before chunk k's flag
            # fetches and host commit run.  While the host inserts chunk
            # k's fingerprints, chunk k+1's expand + all_to_all drain;
            # on a per-shard imbalance the exchange wall hides behind
            # the host wall and vice versa.  An overflow discovered at
            # resolve time re-runs only that chunk (host-backend chunks
            # are independent until commit — the FpSets are only touched
            # here, in dispatch order), so results stay exact and
            # bit-identical to the serial path.
            stage_chunks = overlap_on and visited_backend == "host"
            staged_sh = None
            while verdict is None:
                ctx = _build_chunk()
                if ctx is None:
                    break
                outs, meta = _attempt_once(
                    ctx, adapt.widths_for(ctx[0]), w_extra
                )
                cur = [ctx, outs, meta]
                if stage_chunks:
                    overlap_staged_peak = max(
                        overlap_staged_peak,
                        2 if staged_sh is not None else 1,
                    )
                    if staged_sh is not None:
                        _resolve_chunk(staged_sh)
                        if _commit_timed(staged_sh):
                            staged_sh = None
                            break
                    staged_sh = cur
                else:
                    _resolve_chunk(cur)
                    if _commit_timed(cur):
                        break
            if staged_sh is not None and verdict is None:
                _resolve_chunk(staged_sh)
                _commit_timed(staged_sh)
            elif staged_sh is not None:
                # a verdict cut the level with a chunk still staged: its
                # outputs are never read
                staged_sh[2][-1].finish(discarded=True)
            staged_sh = None

            if verdict is not None:
                inv_name, row, gidx = verdict
                violation = build_violation(inv_name, depth, gidx) or Violation(
                    invariant=inv_name,
                    depth=depth,
                    state=decode_packed(model, row),
                    trace=[],
                )
                break

            n_new = int(lvl_new_per_shard.sum())
            exch_bytes_total += lvl_exch_bytes
            exch_raw_bytes_total += lvl_exch_raw_bytes
            depth += 1
            if n_new:
                levels.append(n_new)
                total += n_new
            if chain is not None:
                if n_new:
                    chain.seal(depth, n_new)
                else:
                    chain.reset_fold()
            if obs_.collect and is_coordinator():
                enabled_total = int(lvl_act_en.sum())
                # heartbeat-enveloped (kind/ts/unix): the per-level stats
                # stream doubles as the supervisor's liveness signal.  Beyond
                # the coordinator-aggregated totals, the record carries the
                # per-shard breakdowns (frontier rows expanded per shard,
                # enabled per source shard, new per owner shard, and — host
                # backend, where the coordinator computes the novelty masks —
                # duplicates per owner shard) so exchange imbalance is
                # visible without re-running the level
                shard_extra = {}
                if host_sets is not None:
                    shard_extra["shard_duplicates"] = (
                        lvl_recv_per_shard - lvl_new_per_shard
                    ).tolist()
                rec = obs_.level(
                    depth=depth,
                    frontier=int(prev_base[-1]),
                    enabled_candidates=enabled_total,
                    new=n_new,
                    duplicates=enabled_total - n_new,
                    total=total,
                    level_ms=round((time.perf_counter() - t_level) * 1e3, 1),
                    shard_new=lvl_new_per_shard.tolist(),
                    shard_frontier=np.diff(prev_base).astype(np.int64).tolist(),
                    shard_enabled=lvl_en_per_shard.tolist(),
                    **shard_extra,
                    action_enablement={
                        a.name: int(c) for a, c in zip(model.actions, lvl_act_en.tolist())
                    },
                )
                # exchange wire accounting + overlap attribution ride the
                # IN-MEMORY records only (the emitted stats stream is a
                # pinned historical contract, like the launch counters)
                busy1, blk1 = _io_counters()
                result_levels.append({
                    **rec,
                    "exch_bytes": int(lvl_exch_bytes),
                    "exch_raw_bytes": int(lvl_exch_raw_bytes),
                    # dispatched collective-bearing programs this level
                    # (= launches PER SHARD; in-memory only, like the
                    # launch counters of the single-device engine)
                    "shard_launches": int(lvl_dispatches),
                    "chunks": lvl_chunks,
                    "dedup_lanes": lvl_lanes,
                    # the lanes the guard sides evaluated: every shard's
                    # padded rows x the static fanout
                    "guard_lanes": lvl_guard,
                    **work_record(lvl_work),
                    # the single-device engine's host/device split and
                    # what the host launched and moved this level
                    # (engine/hostio.py; docs/observability.md)
                    "step_ms": round(prof_step * 1e3, 1),
                    "host_ms": round(prof_host_s * 1e3, 1),
                    **io.take(),
                    # deferred batched host-probe attribution (host-
                    # backend device path; in-memory records + gauge/
                    # span side channels only)
                    **(
                        {"host_probe_ms": round(lvl_probe_ms, 2)}
                        if lvl_probe_ms
                        else {}
                    ),
                    "io_hidden_ms": round(
                        max(0.0, (busy1 - lvl_io0[0])
                            - (blk1 - lvl_io0[1])) * 1e3, 2),
                    "io_exposed_ms": round((blk1 - lvl_io0[1]) * 1e3, 2),
                })
                _met.set_gauge(
                    "kspec_shard_launches_level", int(lvl_dispatches)
                )
                _met.inc("kspec_step_ms_total", round(prof_step * 1e3, 1))
                _met.inc("kspec_host_ms_total", round(prof_host_s * 1e3, 1))
                if lvl_probe_ms:
                    _met.set_gauge(
                        "kspec_host_probe_ms", round(lvl_probe_ms, 2)
                    )
                if lvl_exch_raw_bytes:
                    _met.set_gauge(
                        "kspec_exchange_bytes_level", int(lvl_exch_bytes)
                    )
                    _met.set_gauge(
                        "kspec_exchange_compression_ratio",
                        round(lvl_exch_raw_bytes / max(lvl_exch_bytes, 1), 3),
                    )
            if progress:
                progress(depth, n_new, total)
            _shard_beat(depth, new=n_new, total=total)
            pending = [
                np.concatenate(next_pending[d])
                if next_pending[d]
                else np.empty((0, K), np.uint32)
                for d in range(D)
            ]
            if plog is not None:
                # publish the level's per-shard parent-log segments BEFORE the
                # checkpoint save: a checkpoint at depth R then implies the
                # log resolves every level <= R (segments past a crash are
                # rewritten byte-identically by the deterministic re-run)
                plog.write_level(
                    depth,
                    pending,
                    [
                        np.concatenate(next_parent[d])
                        if next_parent[d]
                        else np.empty(0, np.int64)
                        for d in range(D)
                    ],
                    [
                        np.concatenate(next_act[d])
                        if next_act[d]
                        else np.empty(0, np.int64)
                        for d in range(D)
                    ],
                )
            if ckpt_store is not None and depth % checkpoint_every == 0:
                _save_checkpoint()
                last_ckpt_depth = depth
            if store_trace:
                trace_store.append(
                    (
                        np.concatenate(pending)
                        if n_new
                        else np.empty((0, K), np.uint32),
                        np.concatenate(
                            [x for lst in next_parent for x in lst]
                            or [np.empty(0, np.int64)]
                        ),
                        np.concatenate(
                            [x for lst in next_act for x in lst]
                            or [np.empty(0, np.int64)]
                        ),
                    )
                )
            # level-boundary resource governance: pressure gauges, injected
            # stall, soft-breach reclamation, hard-breach typed clean exit.
            # Multi-process: NO reclaim/save hooks — both reach
            # _save_checkpoint, whose device-backend dumps are collectives,
            # and a breach can be process-LOCAL (RSS, a host's own disk),
            # so a lone breacher issuing a collective would wedge forever
            # instead of exiting typed; it exits rc-75 from the last
            # lockstep checkpoint instead, which the fleet supervisor
            # classifies as the resource verdict
            multi = is_multiprocess()
            governor.level_end(
                depth,
                reclaim=None if multi else _reclaim,
                save_hook=None if multi else _final_save,
            )
        # drain the async tail INSIDE the typed-error scope: a pending
        # checkpoint's ENOSPC or a background merge's injected fault must
        # map to the same typed exits as their synchronous twins
        _ckpt_poll(block=True)
        if use_disk:
            for s in host_sets:
                if s is not None:
                    s.quiesce()
    except ResourceExhausted as e:
        exhausted = e
    except IntegrityError as e:
        integrity_fail = e
    except (RunCorrupt, ParentLogCorrupt) as e:
        # read-side storage checksum failure (spill runs / parent-log
        # segments): silent on-disk corruption caught at consumption
        integrity_fail = IntegrityError("storage", str(e), depth=depth)
    except OSError as e:
        if not is_disk_full(e):
            raise
        # a real ENOSPC from a storage/checkpoint writer outside the
        # injected paths: same typed clean exit (every writer cleans
        # up its tmp on failure, so the promoted state is intact)
        exhausted = ResourceExhausted("enospc", str(e), depth=depth)
    obs_.check_closing()
    if integrity_fail is not None:
        # typed terminal (resilience.integrity): stamp the run manifest +
        # shard heartbeat, then propagate for the CLI's exit-76 mapping;
        # the restart resumes from the newest chain-verified generation
        # (the load validators skip corrupted ones).  In a fleet the
        # raising process exits 76 and its peers wedge in the next
        # collective — the fleet supervisor tears down and restarts, the
        # same contract as every shard-scoped fault
        try:
            _integ.record_violation(integrity_fail)
            _shard_beat(
                depth,
                event="integrity-violation",
                site=integrity_fail.site,
                detail=integrity_fail.detail[:200],
            )
            obs_.abort(
                "integrity-violation",
                site=integrity_fail.site,
                depth=integrity_fail.depth,
                detail=integrity_fail.detail[:300],
                distinct_states=total,
            )
            obs_.close()
        except OSError:
            pass
        _shutdown_async(drain=False)
        raise integrity_fail
    if exhausted is not None:
        # typed terminal: stamp the run manifest, mark the shard
        # heartbeat (fleet supervisors and `cli report` attribute the
        # exit to this process), and propagate for the exit-75 mapping.
        # All best-effort: these writes hit the same full filesystem, and
        # a second ENOSPC must not demote the typed exit into a crash
        try:
            _shard_beat(
                depth,
                event="resource-exhausted",
                reason=exhausted.reason,
                detail=exhausted.detail[:200],
            )
            obs_.abort(
                "resource-exhausted",
                reason=exhausted.reason,
                depth=exhausted.depth,
                detail=exhausted.detail,
                distinct_states=total,
                **governor.stats(),
            )
            obs_.close()
        except OSError:
            pass
        _shutdown_async(drain=False)
        raise exhausted

    if violation is None and cut and model.invariants:
        # cutoff left the last frontier unexpanded — run its invariant pass
        # (shard-major order matches trace_store's level layout)
        rows = np.concatenate(pending) if pending else np.empty((0, K), np.uint32)
        if rows.shape[0]:
            sp_ = obs_.open_span("host-invariants", rows=int(rows.shape[0]))
            bad = _first_violation(rows)
            if bad is not None:
                inv, idx = bad
                violation = build_violation(
                    inv.name, depth, idx
                ) or Violation(
                    invariant=inv.name,
                    depth=depth,
                    state=decode_packed(model, rows[idx]),
                    trace=[],
                )
            sp_.finish()

    dt = time.perf_counter() - t0
    _shutdown_async(drain=True)
    _shard_beat(depth, event="finish", ok=violation is None)
    spill_stats = (
        {
            "spill": [s.stats() if s is not None else None for s in host_sets],
            "spill_dir": spill_base,
        }
        if use_disk
        else {}
    )
    if ephemeral_spill is not None:
        import shutil

        shutil.rmtree(ephemeral_spill, ignore_errors=True)
    res = CheckResult(
        model=model.name,
        levels=levels,
        total=total,
        diameter=len(levels) - 1,
        violation=violation,
        seconds=dt,
        states_per_sec=total / max(dt, 1e-9),
        stats={
            "devices": D,
            **({"levels": result_levels} if result_levels else {}),
            "visited_capacity_per_shard": int(vcap),
            "fanout": C,
            "visited_backend": visited_backend,
            "exchange": exchange,
            "pipeline": pipe_name,
            # explicit mesh-axis layouts (mesh_layouts): recorded so a
            # run artifact names the placement every tensor class used
            "mesh_layouts": {
                k: str(v.spec) for k, v in layouts.items()
            },
            **(
                {
                    "device": {
                        "levels": sdev.levels,
                        "fallback": sdev.fallback,
                    }
                }
                if sdev is not None
                else {}
            ),
            "adaptive_active": adapt.active,
            "adaptive_compile_fallback": adaptive_fallback,
            "transient_retries": chunk_retry.retries_total,
            "degradations": chunk_retry.degradations,
            "overlap": {
                "enabled": overlap_on,
                "staged_chunks_peak": overlap_staged_peak,
                **(
                    {"io_worker": io_worker.stats()}
                    if io_worker is not None
                    else {}
                ),
                **(
                    {"ckpt_worker": ckpt_worker.stats()}
                    if ckpt_worker is not None
                    else {}
                ),
            },
            "exchange_compressed": compress_on,
            "exchange_bytes_total": int(exch_bytes_total),
            "exchange_raw_bytes_total": int(exch_raw_bytes_total),
            **(
                {
                    "host_fpset_sizes": [
                        len(s) if s is not None else None for s in host_sets
                    ]
                }
                if host_sets is not None
                else {}
            ),
            **(
                {"shard_visited": shard_visited.tolist()}
                if visited_backend == "device-hash"
                else {}
            ),
            **spill_stats,
        },
    )
    obs_.finish(res)
    obs_.close()
    return res
