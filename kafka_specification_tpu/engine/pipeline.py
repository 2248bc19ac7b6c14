"""Pluggable level-pipeline: the single-device engine's per-chunk stages
behind one interface, with two interchangeable expansion implementations.

Every BFS level runs each frontier chunk through the same five stages
(SURVEY.md §2.3; the sharded engine mirrors them per shard):

  1. expand       — evaluate action guards, produce candidate successors
  2. squeeze      — compact enabled candidates into a dense buffer
  3. fingerprint  — 64-bit fingerprints of the packed candidate rows
  4. dedup        — in-batch + visited-set novelty (backend-specific: the
                    in-jit sort/probe/merge for the device backend, the
                    HBM hash table or native host FpSet outside the jit)
  5. invariants   — predicate kernels over the frontier being expanded
  (6. trace record — host side: parent/action arrays per level, owned by
      :func:`..bfs.check` because it is pure host bookkeeping)

A *pipeline* is the object that owns stages 1-3 (+5) and how they are
fused into jitted programs; :func:`..bfs.check` drives it one chunk at a
time through :meth:`run_chunk`, which returns the same committed-output
contract for every implementation — so the level loop, the visited
backends, checkpointing, resource governance and trace recording are all
pipeline-agnostic.  Two implementations ship:

``legacy`` — the historical per-action path: one monolithic jitted step
  per (bucket, capacity) whose expansion runs one successor-kernel pass
  per action (O(actions) kernel launches per chunk), two-phase compaction
  under :class:`..bfs.AdaptiveCompact`, overflow-retry escalation.

``fused`` — the successor mega-kernel path (the default): per chunk,
  exactly TWO dispatched successor programs —

    launch 1 (``guard matrix``): ONE batched uniform kernel evaluates
      every action guard over the whole padded (frontier x choice)
      lattice — a single predicate matrix [B, C] — plus the frontier
      invariant predicates and deadlock detection (stage 5 rides along
      because it reads the same unpacked states).

    host glue: the predicate matrix is compacted at C speed with
      ``np.flatnonzero`` into ONE shared candidate buffer laid out as
      per-action segments at *data-driven* widths (sized from this
      chunk's exact guard counts + the run's high-water density — the
      update skeleton's shape is data, not code).  Because the exact
      enabled counts are known BEFORE the successor program is
      dispatched, the legacy path's overflow-retry machinery disappears:
      a chunk can never overflow its buffer, widths just grow
      monotonically along a power-of-two ladder.

    launch 2 (``update skeleton``): ONE batched program applies, over
      the one shared buffer, the uniform skeleton
      gather-state -> action update -> CONSTRAINT -> pack -> fingerprint
      (-> sort/probe/merge for the device backend).  Guards are NOT
      re-evaluated (launch 1 already proved every pooled row enabled),
      and the squeeze / pack / fingerprint stages that the legacy path
      ran once per action run exactly once.

  The fused path is bit-identical to the legacy path — same level
  counts, duplicate accounting, first-violation rule, and trace values —
  because the pooled buffer preserves the legacy compact path's
  candidate order (action-major, state-then-choice within an action) and
  all dedup stages consume candidates in that order.  Below the compact
  gate (small buckets, where the legacy path itself runs the full
  uncompacted lattice) the fused pipeline delegates chunks to the legacy
  implementation verbatim, so the whole run stays bit-identical at every
  bucket.  tests/test_pipeline.py pins this across the model matrix.

A third implementation collapses the chunk loop itself into the
accelerator:

``device`` — the device-resident level pipeline: a bounded
  ``lax.while_loop`` processes EVERY gated chunk of a level inside ONE
  dispatched program — guard-matrix expansion, in-jit segmented
  compaction (the per-action cumsum/scatter the fused path had moved
  to the host), fingerprints, intra-level dedup against a
  device-resident level-new sorted set, invariant/deadlock verdicts,
  and next-frontier assembly.  On the sorted-set device visited
  backend the program additionally probes the (read-only) visited set
  in-jit, folds the PR 9 (count, xor, sum) digests on device
  (ops/devlevel.py), and defers the O(capacity) visited merge to ONE
  rank-scatter per level instead of one per chunk (the level-new set's
  content equals exactly the states the serial path would have merged
  chunk-by-chunk).  On the HOST visited backend — the C-arena FpSet
  and its disk tier, the production-scale configuration — the device
  holds no visited set at all: the level's novel candidates come back
  in one transfer (rows + fingerprint lanes, chunk-major CANDIDATE
  order — the exact order the serial commit loop feeds the FpSet) and
  the visited probe/insert runs as ONE batched host call per level, so
  host syncs drop from O(chunks) to O(1) per level and the serial
  winner rule is preserved (a cross-chunk intra-level duplicate is
  caught by the level-new set with the earlier chunk winning — the
  same winner the serial per-chunk insert picks).  A level costs <=2
  successor launches TOTAL — one steady-state, two when a
  segment-width overflow forces a re-dispatch at exact measured widths
  — instead of the fused path's 2 per chunk.  Bit-identity with
  ``legacy`` holds chunk for chunk (same candidate order, same
  stable-sort winners, same verdict priority, same digest multisets;
  docs/engine.md § Device-resident level pipeline states the
  argument), and anything the device program cannot serve — the
  device-hash backend, sub-gate chunks, shadow re-execution, kernels
  without analyzer-proven field hulls (analysis.field_hulls), compile
  failure — degrades to ``fused`` via the documented ladder
  (device -> fused -> legacy).

Plugging a new stage implementation: subclass (or parallel-implement)
a pipeline with the same ``run_chunk`` contract and register it in
:data:`PIPELINES` (kafka_specification_tpu/pipeline_registry.py — the
jax-free registry the CLI validates against); the stage helpers in this
module (``squeeze_stage``, ``fp_stage``, ``sorted_dedup_stage``,
``invariant_stage``) are the building blocks the implementations
compose, and docs/engine.md walks through the interface.
"""

from __future__ import annotations

from time import perf_counter
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracer import now as _now
from ..ops import dedup, devlevel
from ..ops.canon import canon_of
from ..ops.fingerprint import fingerprint_lanes
from ..pipeline_registry import (  # noqa: F401 — re-exported API
    PIPELINE_ENV,
    PIPELINE_REGISTRY,
    pipeline_names,
    resolve_pipeline,
)

#: registered pipeline names (resolve_pipeline validates against the
#: jax-free registry; kept as a tuple for the pre-registry callers)
PIPELINES = pipeline_names()

#: the ONE stage vocabulary of the level programs.  Every stage body is
#: wrapped, where the stage is DEFINED, in ``jax.named_scope("kspec." +
#: name)``: the scope lands in each HLO instruction's ``op_name`` metadata,
#: which is what a profile's device events carry, so device time reads by
#: stage instead of by XLA's fusion numbers (docs/observability.md
#: § Stage vocabulary).  ops/ modules use the literal ``kspec.<stage>``
#: strings (they cannot import the engine); tests/test_tracing.py
#: holds every scope found in a lowered program to this tuple.
STAGES = (
    "guard", "expand", "compact", "canon", "fingerprint", "dedup_sort",
    "dedup_probe", "dedup_merge", "invariants", "digest",
)
STAGE_PREFIX = "kspec."

#: the parts of ``compact``, the one stage that is four pieces of code.
#: Every ``with stage("compact"):`` body runs under exactly one
#: :func:`part` scope NESTED INSIDE the stage scope, so an operation's
#: path reads ``jit(step_n2)/kspec.compact/part.squeeze/scatter``:
#:
#: - ``select``: the per-action index compaction of the guard matrix on
#:   the device (engine/bfs.py ``_expand_compact``; the fused path does it
#:   on the host, its ``compact-host`` span);
#: - ``squeeze``: :func:`squeeze_stage`, enabled candidate rows to the
#:   front of a buffer of another width than the expansion's (a program
#:   whose sorted dedup runs at the expansion's own width has none);
#: - ``novel``: the compaction of the new states after dedup
#:   (:func:`novel_stage` inside :func:`sorted_dedup_stage`,
#:   :func:`candidate_dedup_stage`, the sharded step);
#: - ``append``: the whole-level programs' next-frontier append and the
#:   fills of their level buffers.
#:
#: The prefix is deliberately not ``kspec.``: a reader that takes the
#: innermost ``kspec.*`` component as the stage skips a ``part.*`` one,
#: so the stage split reads what it read before the parts existed.
#: ``expand`` has no parts: its unpack, gather, kernels and pack fuse
#: into one XLA fusion that a profile books whole to its root.
COMPACT_PARTS = ("select", "squeeze", "novel", "append")
PART_PREFIX = "part."

#: part of every step program's module name (``dvl_n2``).  JAX strips
#: debug metadata before it hashes a program for the persistent compile
#: cache but DOES hash the module name, so a program compiled before a
#: vocabulary change would be a cache hit that carries the old scopes.
#: Bump this whenever STAGES or a scope's placement changes: the renamed
#: programs recompile once and bake the new names in.  (``canon``, PR 38,
#: needed no bump: the scope exists only in the programs of a model with a
#: ``symmetry``, none of which was ever compiled without it.)
NAMING_VERSION = 2


def stage(name: str):
    """``jax.named_scope`` of one vocabulary stage."""
    assert name in STAGES, name
    return jax.named_scope(STAGE_PREFIX + name)


def part(name: str):
    """``jax.named_scope`` of one part of ``compact``; entered inside
    ``stage("compact")``, never alone."""
    assert name in COMPACT_PARTS, name
    return jax.named_scope(PART_PREFIX + name)


def program_name(tag: str) -> str:
    """Module name of the step-cache program with cache tag `tag`."""
    return f"{tag}_n{NAMING_VERSION}"


def key_vcap(key: tuple) -> Optional[int]:
    """The visited-capacity component of a step-cache key, or None for
    programs that don't embed the visited set (guard kernels).  Key
    shapes (engine.bfs._Step.get / FusedPipeline / DevicePipeline):

      ("step", bucket, vcap, inv_sig, with_merge, compact, sq_full)
      ("fgd",  bucket, inv_sig)                     — fused launch 1
      ("fsc",  bucket, vcap, widths, with_merge, device_out)
      ("dvl",  bucket, vcap, ncp, widths, ln, inv_sig, deadlock)
      ("dvh",  bucket, ncp, widths, ln, inv_sig, deadlock)
                 — the host-backend (deferred-probe) level program:
                   no vcap component, the program embeds no visited set
      ("hinv", bucket, inv_sig)   — the invariant pass over host-held rows
      ("init", bucket)            — the initial states' pack + fingerprint
                   (engine.bfs._Step.first_violation / init_rows; the
                   sharded engine keys the former ("shi", mesh, N,
                   inv_sig)): no vcap component, so rewarm skips them
                   and growth never evicts them
    """
    tag = key[0]
    if tag in ("step", "fsc", "dvl"):
        return key[2]
    return None


def evict_vcap(cache: dict, vcap: int) -> None:
    """Drop every step program compiled at an outgrown visited capacity
    — each is a full compiled program, dead weight in the
    Model-lifetime cache once growth is monotonic past it."""
    for k in [k for k in cache if key_vcap(k) == vcap]:
        del cache[k]


def grow_visited(vhi, vlo, vcap: int, need: int, cache: Optional[dict]
                 = None):
    """Grow the sorted visited pair set to the next power of two >=
    `need` (sentinel-padded) — the ONE growth policy for the per-chunk
    loop (engine/bfs.py) and the device level path.  When `cache` is
    given the outgrown capacity's programs are evicted immediately;
    pass None to defer eviction (the device path evicts only after a
    successful dispatch, so a growth followed by a compile failure
    leaves the per-chunk fallback's programs warm)."""
    from .bfs import _next_pow2

    new_cap = _next_pow2(need)
    with stage("dedup_merge"):
        pad = jnp.full(new_cap - vcap, 0xFFFFFFFF, jnp.uint32)
        vhi = jnp.concatenate([vhi, pad])
        vlo = jnp.concatenate([vlo, pad])
    if cache is not None:
        evict_vcap(cache, vcap)
    return vhi, vlo, new_cap


# --------------------------------------------------------------------------
# shared stage helpers (traced; composed by both pipelines)
# --------------------------------------------------------------------------


def squeeze_stage(cand, parent, actid, valid, width, K):  # kspec: traced
    """Stage 2: compact enabled candidate rows to the front of a `width`
    buffer; overflow=True iff more than `width` rows are enabled."""
    with stage("compact"), part("squeeze"):
        n_en = jnp.sum(valid, dtype=jnp.int32)
        spos = jnp.where(valid, jnp.cumsum(valid) - 1, width)
        out = jnp.zeros((width, K), jnp.uint32).at[spos].set(cand)
        out_parent = jnp.full((width,), -1, jnp.int32).at[spos].set(parent)
        out_act = jnp.full((width,), -1, jnp.int32).at[spos].set(actid)
        rowvalid = jnp.arange(width) < n_en
        return out, out_parent, out_act, rowvalid, n_en, n_en > width


def fp_stage(cand, valid, model):  # kspec: traced
    """Stage 3: masked (hi, lo) fingerprints -> (hi, lo, orbit).

    Under the model's ``symmetry`` (TLC's SYMMETRY) a candidate's key is
    its ORBIT's: the ``canon`` stage (ops/canon.py) forms every image of
    every valid row and fingerprints the least one.  The rows flow on
    unchanged, only the pair becomes the orbit's.  `orbit` is then (the
    orbit's size of every valid lane i32[T], the rows whose images the
    stage formed i32), which a sorted dedup sums over its new states
    (:func:`sorted_dedup_stage`); None for a model with no symmetry, whose
    programs are what they were.  Every single-device program that
    fingerprints candidates comes through here (`valid` None: every row
    is one, the initial states')."""
    if model.symmetry is not None:
        if valid is None:
            valid = jnp.ones((cand.shape[0],), bool)
        with stage("canon"):
            hi, lo, size, rows = canon_of(model).keys(cand, valid)
        return hi, lo, (size, rows)
    sent = jnp.uint32(dedup.SENT)
    with stage("fingerprint"):
        hi, lo = fingerprint_lanes(cand, model.spec.exact64)
        if valid is None:
            return hi, lo, None
        return jnp.where(valid, hi, sent), jnp.where(valid, lo, sent), None


def invariant_stage(model, states, fvalid, with_invariants: bool):  # kspec: traced
    """Stage 5: per-invariant (any-violated, first-index) on the frontier
    being expanded (each state checked exactly once, at expansion; BFS
    order: states before successors)."""
    with stage("invariants"):
        if not (with_invariants and model.invariants):
            return (jnp.stack([jnp.bool_(False)]),
                    jnp.stack([jnp.int32(0)]))
        if model.invariants_fused is not None:
            # one trace for all predicates: shared subtrees (e.g. the
            # WeakIsr/StrongIsr quantifier core in emitted models)
            # evaluate once
            ok = jax.vmap(model.invariants_fused)(states)  # [B, n_inv]
            bad = fvalid[:, None] & ~ok
            return jnp.any(bad, axis=0), jnp.argmax(bad, axis=0)
        viol_any, viol_idx = [], []
        for inv in model.invariants:
            ok = jax.vmap(inv.pred)(states)
            bad = fvalid & ~ok
            viol_any.append(jnp.any(bad))
            viol_idx.append(jnp.argmax(bad))
        return jnp.stack(viol_any), jnp.stack(viol_idx)


def invariant_rows_program(model, N: int):
    """The invariant pass over `N` HOST-HELD packed rows, un-jitted:
    (rows u32[N, K], n_valid i32) -> (any_bad[n_inv], first[n_inv]), the
    per-invariant verdict and lowest violating row index.  Rows at or
    past `n_valid` are padding and masked OUT (an all-zero row may well
    violate an invariant).  One body for both engines' start (the initial
    states) and finish (the frontier a max_depth/max_states cut left
    unexpanded): engine.bfs._Step.first_violation jits it once per row
    bucket and launches it."""
    spec = model.spec

    def invariant_rows(rows, n_valid):  # kspec: traced
        with stage("invariants"):
            live = jnp.arange(N) < n_valid
            states = jax.vmap(spec.unpack)(rows)
        return invariant_stage(model, states, live, True)

    return invariant_rows


def init_rows_program(model):
    """Pack + fingerprint the stacked initial states, un-jitted:
    {field: i32[N, ...]} -> (rows u32[N, K], hi u32[N], lo u32[N]); the
    pair is the orbit's under the model's symmetry (:func:`fp_stage`)."""
    spec = model.spec

    def init_rows(states):  # kspec: traced
        with stage("expand"):
            rows = jax.vmap(spec.pack)(states)
        hi, lo, _orbit = fp_stage(rows, None, model)
        return rows, hi, lo

    return init_rows


def _sort_first(hi, lo):  # kspec: traced
    """The sort half of stage 4: stable lexsort of the fingerprint pairs
    and the first-occurrence mask over the sorted order -> (hi_s, lo_s,
    order, first, n_live).  ONE source of the winner-selection order for
    both dedup stages.  The sentinel pairs sort last, so the live lanes
    are the first ``n_live`` (a device value): what the probes search
    (``dedup.probe_sorted``'s ``q_n``) and :func:`novel_stage` walks."""
    sent = jnp.uint32(dedup.SENT)
    with stage("dedup_sort"):
        order = jnp.lexsort((lo, hi))
        hi_s, lo_s = hi[order], lo[order]
        invalid_s = (hi_s == sent) & (lo_s == sent)
        first = dedup.first_occurrence_mask(hi_s, lo_s, invalid_s)
        n_live = jnp.sum(~invalid_s, dtype=jnp.int32)
    return hi_s, lo_s, order, first, n_live


#: The level-record fields of :func:`work_counts`, in the vector's order.
WORK_FIELDS = ("probe_rounds", "probe_rounds_plain",
               "probe_lanes", "probe_lanes_plain",
               "merge_slots", "merge_slots_plain",
               "novel_rows", "novel_rows_plain")
#: Two more behind them in the programs of a model with a ``symmetry``
#: that decide novelty on the device (the sorted visited backend): the
#: rows whose images the ``canon`` stage formed (blocks run x block size),
#: and the sum over the dispatch's NEW states of their orbits' sizes,
#: which over a level EQUALS the unreduced job's count of that level.
CANON_FIELDS = ("canon_rows", "orbit_states")


def work_width(model, visited_backend: str = "device") -> int:
    """How many work counts trail the counts vector of `model`'s programs
    (:func:`split_counts`'s `n`)."""
    symmetric = model.symmetry is not None and visited_backend == "device"
    return len(WORK_FIELDS) + (len(CANON_FIELDS) if symmetric else 0)


def work_counts(probe=None, merge=None, novel=None, canon=None,  # kspec: traced
                symmetric: bool = False):
    """int32[8] (:data:`WORK_FIELDS`; int32[10] in the programs of a model
    with a symmetry: `canon`, the pair of :data:`CANON_FIELDS`, or
    `symmetric` alone for a part that canonicalised nothing), the dedup work a program did beside its answers: the four
    counts of ``dedup.probe_sorted`` (rounds run, rounds a search of
    the whole capacity runs; query lanes searched, query lanes handed),
    the two slot counts of
    ``dedup.merge_counted`` (slots touched, slots a capacity-wide merge
    touches), then the two row counts of :func:`novel_stage` (rows its
    loops touched, rows the full-width compaction touches); zeros for the
    part not given.  Vectors of several probes, merges and compactions
    add."""
    parts = [(probe, 4), (merge, 2), (novel, 2)]
    if symmetric or canon is not None:
        parts.append((canon, 2))
    return jnp.concatenate([
        jnp.zeros((n,), jnp.int32) if x is None else x for x, n in parts])


def counts_out(act_en, work=None):  # kspec: traced
    """The counts a level program hands the host, in the ONE vector it
    already fetches: the per-action enabled counts, then the eight
    :func:`work_counts` summed over the program's probes, merges and
    compactions (zeros where it ran none).  :func:`split_counts` is the host's half."""
    if work is None:
        work = work_counts()
    return jnp.concatenate([act_en, work])


def split_counts(counts, n: int = len(WORK_FIELDS)):
    """A fetched :func:`counts_out` vector (or a [D, n] stack of them,
    one a shard) -> (act_en, work): the enabled counts as fetched, and
    int64[n], the :func:`work_counts` summed over the shards (`n`:
    :func:`work_width`)."""
    counts = np.asarray(counts, np.int64)
    return counts[..., :-n], counts[..., -n:].reshape(-1, n).sum(axis=0)


def work_record(work):
    """Summed :func:`work_counts` -> the eight level-record fields (ten
    under a symmetry)."""
    return dict(zip(WORK_FIELDS + CANON_FIELDS, (int(x) for x in work)))


def probes_run(work, cap: int) -> int:
    """How many sorted-set probes a dispatch ran whose probes all searched
    a capacity of `cap`, from its summed :func:`work_counts`: each probe
    adds ``cap.bit_length()`` to ``probe_rounds_plain``."""
    return (int(work[WORK_FIELDS.index("probe_rounds_plain")])
            // max(1, cap.bit_length()))


#: The most rows one iteration of :func:`novel_stage`'s loops moves (the
#: block is a shape, :func:`novel_block`; how many blocks run is a device
#: value).
#: Timed on a TPU v5e, the function alone (PERF.md section 6, PR 37), ms at
#: blocks of 8,192 / 16,384 / 32,768 against the full-width form: 278,528 x
#: 3 lanes, 31% live, 12% new: 3.61 / 9.46 / 9.70 against 25.50; 475,136 x
#: 4, 42% live, 10.5% new: 5.07 / 5.98 / 12.55 against 42.55; every lane
#: live and new: 26.33 / 29.95 / 63.08 against 42.62; 19,661 x 4 full (two
#: blocks a loop, the last one overlapping): 2.42 / 2.87 / 2.07 against
#: 2.10.  4,096 reads as 8,192 does; the larger blocks are no steadier.
NOVEL_BLOCK = 8192


def novel_block(T: int) -> int:
    """The block of a width: ``dedup.even_block`` at :data:`NOVEL_BLOCK`
    (the probe's blocks follow the same rule at its own constant)."""
    return dedup.even_block(T, NOVEL_BLOCK)


def novel_stage(is_new, order, hi_s, lo_s, rank,  # kspec: traced
                cand, parent, actid, n_live, T, K):
    """The ``novel`` part of ``compact``: the new states of a sorted
    dedup, compacted to the front in sorted-fingerprint order.

    is_new / hi_s / lo_s / rank are in SORTED order (lane i is candidate
    ``order[i]``); cand / parent / actid in candidate order; n_live is
    :func:`_sort_first`'s, the length of the live prefix.  -> (out[T, K],
    out_parent, out_act, out_hi, out_lo, out_rank, new_n, rows): the
    first new_n rows hold the new states, the rest the fills (zero rows,
    -1 parents and action ids, sentinel fingerprints, rank 0).

    Only the rows it keeps move.  One T-wide prefix sum numbers the new
    lanes; a rolled loop over the LIVE prefix of the sorted order (the
    sentinel pairs sort last, so every new lane lies in the first n_live)
    writes the inverse map ``src[j]`` = the sorted lane of the j-th new
    state, one lane scatter a block; a second rolled loop over the first
    ``ceil(new_n / block)`` OUTPUT blocks gathers each output through
    ``src`` and writes it as one slice into the pre-filled buffer.  Both
    trip counts are device values, so the stage costs what a chunk keeps
    and not what its layout pads.  The blocks of a width are of one size
    (:func:`novel_block`); where they do not divide it the last block
    starts early (a slice must lie inside its operand) and recomputes the
    few rows of the overlap.  ``rows`` is
    int32[2]: the rows the two loops touched (blocks run x block size)
    and the rows the full-width compaction touches (``T``, a shape); the
    level programs sum it over their dedup stages and hand it to the host
    with their counts (level record ``novel_rows`` / ``novel_rows_plain``).
    """
    assert is_new.shape == (T,) and cand.shape == (T, K), (
        is_new.shape, cand.shape, T, K)
    sent = jnp.uint32(dedup.SENT)
    with stage("compact"), part("novel"):
        B = novel_block(T)
        csum = jnp.cumsum(is_new, dtype=jnp.int32)
        new_n = jnp.sum(is_new, dtype=jnp.int32)
        blocks_live = (n_live + (B - 1)) // B
        blocks_new = (new_n + (B - 1)) // B

        def invert(k, src):
            s = jnp.minimum(k * B, T - B)
            i = s + jnp.arange(B, dtype=jnp.int32)
            new = jax.lax.dynamic_slice(is_new, (s,), (B,))
            pos = jax.lax.dynamic_slice(csum, (s,), (B,)) - 1
            # the overlap writes the same lanes again; dead lanes drop
            return src.at[jnp.where(new, pos, T)].set(i, mode="drop")

        src = jax.lax.fori_loop(0, blocks_live, invert,
                                jnp.zeros((T,), jnp.int32))

        def gather(k, outs):
            out, out_parent, out_act, out_hi, out_lo, out_rank = outs
            s = jnp.minimum(k * B, T - B)
            keep = (s + jnp.arange(B, dtype=jnp.int32)) < new_n
            at = jax.lax.dynamic_slice(src, (s,), (B,))
            row = order[at]

            def put(buf, val, fill):
                val = jnp.where(keep.reshape((B,) + (1,) * (val.ndim - 1)),
                                val, fill)
                return jax.lax.dynamic_update_slice(
                    buf, val, (s,) + (0,) * (val.ndim - 1))

            return (put(out, cand[row], jnp.uint32(0)),
                    put(out_parent, parent[row], jnp.int32(-1)),
                    put(out_act, actid[row], jnp.int32(-1)),
                    put(out_hi, hi_s[at], sent),
                    put(out_lo, lo_s[at], sent),
                    put(out_rank, rank[at], jnp.int32(0)))

        outs = jax.lax.fori_loop(
            0, blocks_new, gather,
            (jnp.zeros((T, K), jnp.uint32),
             jnp.full((T,), -1, jnp.int32), jnp.full((T,), -1, jnp.int32),
             jnp.full((T,), sent), jnp.full((T,), sent),
             jnp.zeros((T,), jnp.int32)),
        )
        rows = jnp.stack([(blocks_live + blocks_new) * B, jnp.int32(T)])
        return (*outs, new_n, rows)


def sorted_dedup_stage(cand, parent, actid, valid, hi, lo,  # kspec: traced
                       vhi, vlo, vn, vcap, T, K, with_merge: bool,
                       also_seen_in=None, orbit=None):
    """Stage 4 (device backend): minimal-payload lexsort, first-occurrence
    + visited-rank dedup, compaction of the new states to the front
    (:func:`novel_stage`), and (with_merge) the rank-scatter merge into the
    sorted visited set.
    Identical primitive sequence to the legacy in-step version — winners
    are decided by the stable sort over the same candidate order, which
    is what keeps the pipelines trace-bit-identical; this helper is the
    ONE source of that winner-selection sequence (the fused update
    skeleton and the device level program both compose it).

    also_seen_in: optional second sorted pair set (hi, lo, n) that also
    disqualifies candidates from being new — the device pipeline probes
    its read-only visited set here while (vhi, vlo, vn) is the
    device-resident level-new set the compacted rank indexes into.  The
    trailing out_rank return (insertion ranks of the compacted prefix in
    the PRIMARY set) lets with_merge=False callers run their own gated
    merge_ranked; the last return is the stage's :func:`work_counts` (the
    one or two probes' rounds, the merge's slots where with_merge, the
    compaction's rows).

    orbit: :func:`fp_stage`'s third return.  Where it is not None the work
    counts end in the ``canon`` pair: the stage's rows, and the orbit sizes
    of the NEW states summed (one masked gather through the sort order)."""
    # minimal-payload sort: only the original index rides through the
    # sort network; state rows/parents are gathered once afterwards
    hi_s, lo_s, order, first, n_live = _sort_first(hi, lo)
    seen, rank, probe = dedup.probe_sorted(vhi, vlo, vn, hi_s, lo_s, n_live)
    is_new = first & ~seen
    if also_seen_in is not None:
        a_hi, a_lo, a_n = also_seen_in
        a_seen, _ar, a_probe = dedup.probe_sorted(
            a_hi, a_lo, a_n, hi_s, lo_s, n_live)
        is_new = is_new & ~a_seen
        probe = probe + a_probe
    (out, out_parent, out_act, out_hi, out_lo, out_rank, new_n,
     rows) = novel_stage(is_new, order, hi_s, lo_s, rank,
                         cand, parent, actid, n_live, T, K)
    slots = None
    if with_merge:
        vhi, vlo, vn, slots = dedup.merge_counted(
            vhi, vlo, vn, out_hi, out_lo, out_rank, new_n, vcap
        )
    canon = None
    if orbit is not None:
        size, canon_rows = orbit
        with stage("canon"):
            canon = jnp.stack([canon_rows, jnp.sum(
                jnp.where(is_new, size[order], 0), dtype=jnp.int32)])
    return (out, out_parent, out_act, new_n, out_hi, out_lo,
            vhi, vlo, vn, out_rank, work_counts(probe, slots, rows, canon))


def candidate_dedup_stage(cand, parent, actid, valid, hi, lo,  # kspec: traced
                          lhi, llo, ln, T, K):
    """Stage 4 for the DEFERRED-probe host backends: intra-level novelty
    with the compacted novel prefix emitted in CANDIDATE order.

    Winners are elected by the SAME stable lexsort sequence as
    :func:`sorted_dedup_stage` (first occurrence among equal
    fingerprints in candidate order — exactly the row the serial host
    commit's first-come FpSet insert keeps), but the novel prefix is
    emitted in CANDIDATE order, because that is the order the serial
    per-chunk host path hands rows to the FpSet: the deferred batched
    probe replays the level in chunk-major candidate order, so the
    committed arena contents — rows, parents, action ids, and hence
    next-level chunk boundaries and trace values — are byte-identical
    to the serial path's.  (lhi, llo, ln) is the device-resident
    level-new sorted set; the sorted view (n_hi/n_lo/n_rank) feeds its
    gated merge exactly as sorted_dedup_stage's outputs do.  States
    already in the VISITED set are deliberately still emitted here —
    the device holds no visited set in this mode; the host's
    once-per-level batched probe filters them, which is the same
    novelty decision the serial per-chunk insert makes, one level
    later in wall time and with O(1) host syncs instead of O(chunks).

    Returns (out, out_parent, out_act, out_hi, out_lo, new_n,
    n_hi, n_lo, n_rank, work): the last is the probe's rounds as
    :func:`work_counts`."""
    sent = jnp.uint32(dedup.SENT)
    hi_s, lo_s, order, first, n_live = _sort_first(hi, lo)
    seen, rank, probe = dedup.probe_sorted(lhi, llo, ln, hi_s, lo_s, n_live)
    is_new = first & ~seen
    with stage("compact"), part("novel"):
        # sorted-order compaction: what the level-new merge consumes
        pos_s = jnp.where(is_new, jnp.cumsum(is_new) - 1, T)
        n_hi = jnp.full((T,), sent).at[pos_s].set(hi_s)
        n_lo = jnp.full((T,), sent).at[pos_s].set(lo_s)
        n_rank = jnp.zeros((T,), jnp.int32).at[pos_s].set(rank)
        new_n = jnp.sum(is_new, dtype=jnp.int32)
        # candidate-order compaction: scatter the sorted novelty decisions
        # back to candidate positions, then compact without re-sorting
        isnew_c = jnp.zeros((T,), bool).at[order].set(is_new)
        pos_c = jnp.where(isnew_c, jnp.cumsum(isnew_c) - 1, T)
        out = jnp.zeros((T, K), jnp.uint32).at[pos_c].set(cand)
        out_parent = jnp.full((T,), -1, jnp.int32).at[pos_c].set(parent)
        out_act = jnp.full((T,), -1, jnp.int32).at[pos_c].set(actid)
        out_hi = jnp.full((T,), sent).at[pos_c].set(hi)
        out_lo = jnp.full((T,), sent).at[pos_c].set(lo)
    return (out, out_parent, out_act, out_hi, out_lo, new_n,
            n_hi, n_lo, n_rank, work_counts(probe))


# --------------------------------------------------------------------------
# legacy pipeline: the per-action monolithic step + overflow escalation
# --------------------------------------------------------------------------


class LegacyPipeline:
    """The historical per-action expansion behind the pipeline interface:
    one monolithic jitted step per (bucket, vcap) running one successor
    pass per action, with AdaptiveCompact's two-phase compaction and the
    overflow-retry/escalation ladder (moved verbatim from check()'s inner
    loop).  Kernel launches per chunk: O(actions)."""

    name = "legacy"

    def __init__(self, step_builder, model, adapt, chunk_retry, fault,
                 check_invariants: bool, visited_backend: str,
                 on_degrade_chunk, io=None):
        from .hostio import HostIO

        self.step = step_builder
        self.model = model
        self.adapt = adapt
        self.chunk_retry = chunk_retry
        self.fault = fault
        self.check_invariants = check_invariants
        self.visited_backend = visited_backend
        self.on_degrade_chunk = on_degrade_chunk
        #: counted transfers + named dispatches (engine/hostio.py)
        self.io = io if io is not None else HostIO()
        self.squeeze_full = False  # sticky pre-sort-squeeze overflow relief
        self.compile_fallback = False

    @property
    def launches_per_chunk(self) -> int:
        """Successor-kernel passes dispatched per chunk: one per action
        (the per-action phase-B evaluation: the emitted source's 12 DNF
        action kernels against hand's 9)."""
        return len(self.model.actions)

    def run_chunk(self, piece, fp_n, bucket, depth, vhi, vlo, vn, vcap):
        from .bfs import _pad_rows  # cycle-free: bfs imports us lazily

        adapt, io = self.adapt, self.io
        compact_arg = adapt.widths_for(bucket)
        attempt_sq_full = self.squeeze_full
        self.chunk_retry.reset_chunk()
        dispatched = 0  # successor-kernel passes actually dispatched,
        # overflow/retry re-dispatches included
        attempt = 0
        while True:
            launch = None
            try:
                injected = self.fault.chunk_error(
                    escalated=isinstance(compact_arg, (list, tuple))
                )
                if injected is not None:
                    raise injected
                step = self.step.get(
                    bucket,
                    vcap,
                    self.check_invariants,
                    with_merge=self.visited_backend == "device",
                    compact=compact_arg,
                    squeeze_full=attempt_sq_full,
                )
                frontier = io.put(_pad_rows(piece, bucket))
                launch = io.dispatch("step", attempt=attempt, depth=depth,
                                     bucket=bucket, vcap=vcap)
                attempt += 1
                (
                    out, out_parent, out_act, new_n, vhi_n, vlo_n, vn_n,
                    viol_any, viol_idx, dl_any, dl_idx, act_en,
                    out_hi, out_lo, overflow, act_guard,
                ) = step(
                    frontier,
                    jnp.arange(bucket) < fp_n,
                    vhi,
                    vlo,
                    vn,
                )
                dispatched += self.launches_per_chunk
                # the overflow read forces the whole program
                ovf = io.fetch(overflow)
            except Exception as e:  # noqa: BLE001 — XLA compile/run
                if launch is not None:
                    launch.finish(discarded=True)
                # known failure ladder — one policy for both engines
                # (resilience.retry.ChunkRetryHandler); see check()'s
                # docstring for the degradation contract
                action = self.chunk_retry.handle(
                    e,
                    escalated=isinstance(compact_arg, (list, tuple)),
                    depth=depth,
                )
                if action == "retry":
                    continue
                if action == "degrade_chunk":
                    self.on_degrade_chunk()
                compact_arg = adapt.compile_fallback(bucket)
                self.compile_fallback = True
                continue
            if compact_arg is None or not ovf.any():
                launch.finish()
                vhi, vlo, vn = vhi_n, vlo_n, vn_n
                break
            launch.finish(discarded=True)  # outputs incomplete: re-run
            # retry this chunk with the offending buffers widened: a
            # per-action compact overflow doubles that action's width
            # (floored for the rest of the run); a squeeze overflow
            # disables the pre-sort width reduction (sticky); a
            # uniform-shift overflow escalates to measured widths
            if ovf[-1]:
                attempt_sq_full = self.squeeze_full = True
            if ovf[:-1].any():
                compact_arg = adapt.escalate(
                    compact_arg,
                    ovf[:-1],
                    bucket,
                    io.fetch(act_guard, np.int64) / max(fp_n, 1),
                )
        # adapt buffer sizing from the committed attempt's PRE-constraint
        # guard counts (what the buffers actually hold; act_en is
        # post-constraint and undercounts on pruning models)
        adapt.observe(io.fetch(act_guard, np.int64) / max(fp_n, 1))
        return (
            out, out_parent, out_act, new_n, vhi, vlo, vn,
            viol_any, viol_idx, dl_any, dl_idx, act_en,
            out_hi, out_lo, act_guard, dispatched,
            # the width the committed attempt handed its dedup side
            # (the level record's `dedup_lanes`)
            self.step.dedup_width(bucket, compact_arg, attempt_sq_full),
        )

    def run_chunk_staged(self, piece, fp_n, bucket, depth,
                         vhi, vlo, vn, vcap):
        """Staged form of :meth:`run_chunk` for the overlap driver:
        -> (vhi, vlo, vn, finalize).  The legacy path must read its
        overflow flags before committing (the retry ladder), which
        forces the whole program — so its dispatch is already complete
        and finalize is a no-op closure over the committed tuple.  The
        overlap win for legacy chunks is therefore only the reordering
        of host commits, never deferred device work (docs/engine.md)."""
        outs = self.run_chunk(piece, fp_n, bucket, depth, vhi, vlo, vn,
                              vcap)
        return outs[4], outs[5], outs[6], lambda: outs


# --------------------------------------------------------------------------
# fused pipeline: guard matrix + pooled update skeleton (2 launches)
# --------------------------------------------------------------------------


class PooledWidths:
    """Data-driven sizing of the fused path's shared candidate buffer.

    Each action owns one segment of the pooled buffer; its width rides a
    power-of-two ladder (floor 256: bfs._round256's alignment, capped at
    the action's full lattice width) sized from max(this chunk's EXACT
    guard count, the run's high-water per-state density x bucket x 1.35
    headroom).  Exact counts are known before the successor program is
    dispatched (launch 1 already ran), so a chunk can never overflow its
    segment — the ladder only climbs, keeping the set of compiled width
    vectors small and, across runs of the same shape, deterministic
    (warm serving runs replay the same keys; PreparedKernels)."""

    HEADROOM = 1.35

    def __init__(self, actions):
        self.actions = actions
        self.hw = np.zeros(len(actions), np.float64)  # density high-water

    @staticmethod
    def _rung(need: int) -> int:
        """Smallest half-octave rung >= need: {0.75 * 2^k, 2^k} rounded to
        the 256-row fingerprint-block alignment.  Two rungs per octave
        keeps the mean padding ~1.2x (vs ~1.5x for plain pow2) while the
        monotone ladder still bounds the number of compiled width
        vectors per run."""
        from .bfs import _next_pow2, _round256

        p = _next_pow2(need)
        q = _round256((3 * p) >> 2)
        return q if q >= need else _round256(p)

    def widths_for(self, bucket: int, counts: np.ndarray,
                   fp_n: int) -> tuple:
        from .bfs import _round256

        self.hw = np.maximum(self.hw, counts / max(fp_n, 1))
        out = []
        for a, hw, count in zip(self.actions, self.hw, counts):
            cap = _round256(bucket * a.n_choices)
            need = max(256, int(count), int(self.HEADROOM * hw * bucket))
            out.append(min(cap, self._rung(need)))
        return tuple(out)


class StagedGuard:
    """One fused chunk between its two launches: what
    :meth:`FusedPipeline.guard_stage` dispatched and, once
    :meth:`FusedPipeline.compact_stage` has run, what shapes launch 2."""

    __slots__ = ("fp_n", "bucket", "depth", "t0", "host_s", "frontier",
                 "outs", "launch", "dispatched", "error", "act_guard_np",
                 "widths", "compacted")

    def __init__(self, fp_n: int, bucket: int, depth: int):
        self.fp_n, self.bucket, self.depth = fp_n, bucket, depth
        #: when the chunk's first stage began (its `step` span's start) and
        #: the host's seconds in its stages so far (the span's dispatch_ms)
        self.t0, self.host_s = _now(), 0.0
        self.frontier = None  # the padded piece, on the device
        #: launch 1's outputs: ga, act_guard, viol_any, viol_idx, dl_any,
        #: dl_idx
        self.outs = None
        self.launch = None  # launch 1's dispatch, open until it is read
        self.dispatched = 0  # 1 once launch 1 went out
        self.error: Optional[Exception] = None  # what a stage raised
        self.act_guard_np = self.widths = None
        #: _compact's result: sidx on the host; sidx, chloc, rowvalid on
        #: the device
        self.compacted = None

    def drop(self) -> None:
        """The chunk will not be committed from this stage (a failure, a
        verdict in an older chunk, a fallback): launch 1, where it is
        still open, ends as discarded."""
        if self.launch is not None:
            self.launch.finish(discarded=True)

    def fail(self, e: Exception) -> None:
        self.error = e
        self.drop()


class FusedPipeline:
    """Successor mega-kernels: 2 dispatched programs per chunk (guard
    matrix -> host flatnonzero compaction -> update skeleton), bit-
    identical to the legacy path (module docstring).  Chunks below the
    compact gate delegate to the legacy pipeline verbatim — the legacy
    path runs the full uncompacted lattice there, and matching it
    instruction-for-instruction is what keeps whole runs bit-identical
    at every bucket."""

    name = "fused"
    launches_per_chunk = 2

    def __init__(self, step_builder, model, adapt, chunk_retry, fault,
                 check_invariants: bool, visited_backend: str,
                 on_degrade_chunk, compact_shift: int, compact_gate: int,
                 io=None):
        self.step = step_builder
        self.model = model
        self.spec = model.spec
        self.chunk_retry = chunk_retry
        self.fault = fault
        self.check_invariants = check_invariants
        self.visited_backend = visited_backend
        self.compact_shift = compact_shift
        self.compact_gate = compact_gate
        self.pool = PooledWidths(model.actions)
        self.fallback = False  # sticky: a failed fused compile pins legacy
        self.legacy = LegacyPipeline(
            step_builder, model, adapt, chunk_retry, fault,
            check_invariants, visited_backend, on_degrade_chunk, io=io,
        )
        self.io = self.legacy.io
        self.adapt = adapt
        self._bounds = np.cumsum(
            [0] + [a.n_choices for a in model.actions]
        )

    def _gate(self, bucket: int) -> bool:
        """Fused engages exactly where the legacy path would compact
        (same gate, same shift test) — below it the candidate order is
        the full lattice's state-major order, which only the legacy full
        path produces."""
        return (
            not self.fallback
            and self.compact_shift > 0
            and bucket >= self.compact_gate
            and (bucket >> self.compact_shift) >= 1
        )

    # --- jitted launches (cached on the model's step cache) ---------------
    def guard_step(self, bucket: int):
        """Launch 1: guard predicate matrix + invariants + deadlock.
        The invariant component of the key comes from _Step.inv_sig —
        the SAME source the legacy "step" keys use, so fused and legacy
        programs of one invariant-overlay view stay in lockstep in the
        shared per-base step cache (service/kernel_cache.py)."""
        key = ("fgd", bucket, self.step.inv_sig(self.check_invariants))
        return self.step.cached(
            key, lambda: self._build_guard(bucket),
            bucket=bucket, program="fused-guards",
        )

    def succ_step(self, bucket: int, widths: tuple, vcap: int):
        """Launch 2: the pooled update skeleton (+ device dedup)."""
        with_merge = self.visited_backend == "device"
        device_out = self.visited_backend != "host"
        key = ("fsc", bucket, vcap, widths, with_merge, device_out)
        return self.step.cached(
            key,
            lambda: self._build_succ(
                bucket, widths, vcap, with_merge, device_out),
            bucket=bucket, vcap=vcap, widths=repr(widths),
            program="fused-successors",
        )

    def _build_guard(self, bucket: int):
        model, spec = self.model, self.spec
        bounds = self._bounds
        n_actions = len(model.actions)
        check_invariants = self.check_invariants

        def guards_one(state):  # kspec: traced
            parts = []
            for a in model.actions:
                choices = jnp.arange(a.n_choices, dtype=jnp.int32)
                ok = jax.vmap(lambda c, s=state, a=a: a.kernel(s, c)[0])(
                    choices
                )
                parts.append(ok)
            return jnp.concatenate(parts)

        def step(frontier, fvalid):  # kspec: traced
            with stage("guard"):
                states = jax.vmap(spec.unpack)(frontier)
                # [B, C] predicate matrix
                en_pre = jax.vmap(guards_one)(states)
                ga = en_pre & fvalid[:, None]
                act_guard = jnp.stack(
                    [
                        jnp.sum(ga[:, bounds[i]: bounds[i + 1]],
                                dtype=jnp.int32)
                        for i in range(n_actions)
                    ]
                )
                deadlocked = fvalid & ~jnp.any(en_pre, axis=1)
                dl_any, dl_idx = jnp.any(deadlocked), jnp.argmax(deadlocked)
            viol_any, viol_idx = invariant_stage(
                model, states, fvalid, check_invariants
            )
            return ga, act_guard, viol_any, viol_idx, dl_any, dl_idx

        return step

    def _build_succ(self, bucket: int, widths: tuple, vcap: int,
                    with_merge: bool, device_out: bool):
        model, spec = self.model, self.spec
        K = spec.num_lanes
        offs = np.cumsum([0] + list(widths))
        W = int(offs[-1])
        # static action-id column for the pooled layout
        actid_f = jnp.concatenate(
            [
                jnp.full((widths[i],), i, jnp.int32)
                for i in range(len(model.actions))
            ]
        )

        def step(frontier, sidx, chloc, rowvalid, vhi, vlo, vn):  # kspec: traced
            with stage("expand"):
                # the chunk's parent rows gathered packed, once ([W, K]
                # lanes), then unpacked: not one gather a field
                gstate = jax.vmap(spec.unpack)(frontier[sidx])
                cand_parts, ok_parts = [], []
                for i, a in enumerate(model.actions):
                    # kspec: allow(host-materialization) offs is the
                    # static trace-time width table (np cumsum of Python
                    # ints), not a traced value
                    sl = slice(int(offs[i]), int(offs[i + 1]))
                    ga = jax.tree.map(lambda x: x[sl], gstate)
                    # guards are NOT re-evaluated: launch 1 proved every
                    # pooled row enabled, so the kernel's own ok bit is
                    # redundant here (same pure function, same inputs)
                    _, nxt_a = jax.vmap(a.kernel)(ga, chloc[sl])
                    ok_a = rowvalid[sl]
                    if model.constraint is not None:
                        ok_a = ok_a & jax.vmap(model.constraint)(nxt_a)
                    # pack per segment: only the K packed lanes are ever
                    # concatenated, never the full unpacked state tree
                    cand_parts.append(jax.vmap(spec.pack)(nxt_a))
                    ok_parts.append(ok_a)
                ok = jnp.concatenate(ok_parts)
                cand = jnp.concatenate(cand_parts, axis=0)
            if not device_out:
                # host backend: validity is resolved at C speed on the
                # host (run_chunk compacts by the ok mask), so no device
                # squeeze scatter is needed at all
                hi, lo, _orbit = fp_stage(cand, ok, model)
                return cand, ok, hi, lo
            with stage("expand"):
                act_en = jnp.stack(
                    [
                        # kspec: allow(host-materialization) static table
                        jnp.sum(ok[int(offs[i]): int(offs[i + 1])],
                                dtype=jnp.int32)
                        for i in range(len(model.actions))
                    ]
                )
            if with_merge:
                # no squeeze: at the pooled width it narrows nothing, and
                # the stable sort puts the masked rows last in any case
                # (see _Step.build_raw)
                hi, lo, orbit = fp_stage(cand, ok, model)
                (out, out_parent, out_act, new_n, out_hi, out_lo,
                 vhi, vlo, vn, _rank, work) = sorted_dedup_stage(
                    cand, sidx, actid_f, ok, hi, lo,
                    vhi, vlo, vn, vcap, W, K, with_merge, orbit=orbit,
                )
                return (out, out_parent, out_act, new_n, out_hi, out_lo,
                        vhi, vlo, vn, counts_out(act_en, work))
            # device-hash backend: the squeezed rows ARE the output
            out, out_parent, out_act, rowvalid2, n_en, _ovf = squeeze_stage(
                cand, sidx, actid_f, ok, W, K
            )
            hi, lo, _orbit = fp_stage(out, rowvalid2, model)
            return (out, out_parent, out_act, n_en, hi, lo,
                    vhi, vlo, vn, counts_out(act_en))

        return step

    # --- host glue --------------------------------------------------------
    def _compact(self, ga, widths: tuple, depth: int):
        """Stage 2, host half: C-speed stream compaction of the guard
        matrix into the pooled (state-index, choice) layout — replaces
        the legacy path's O(lattice) in-jit cumsum+scatter (measured
        ~13x cheaper on the flagship chunk) and preserves the legacy
        compact path's candidate order exactly (action-major, row-major
        within an action's [B, n_choices] slice).  Fetches the predicate
        matrix, uploads the index vectors, and is one ``compact-host``
        span: -> (sidx host, sidx, chloc, rowvalid on the device)."""
        t0 = _now()
        io = self.io
        fetch0, put0 = io.fetch_ms, io.put_ms
        ga_np = io.fetch(ga)
        bounds = self._bounds
        W = int(sum(widths))
        sidx = np.zeros(W, np.int32)
        chloc = np.zeros(W, np.int32)
        rowvalid = np.zeros(W, bool)
        off = 0
        counts = []
        for i, w in enumerate(widths):
            na = int(bounds[i + 1] - bounds[i])
            idx = np.flatnonzero(
                ga_np[:, bounds[i]: bounds[i + 1]].ravel()
            )
            n = idx.size
            counts.append(n)
            sidx[off: off + n] = idx // na
            chloc[off: off + n] = idx % na
            rowvalid[off: off + n] = True
            off += w
        on_device = (io.put(sidx), io.put(chloc), io.put(rowvalid))
        # the span's blocked part: the wait for launch 1's matrix and its
        # transfer, and the three uploads; the rest is numpy
        io.span("compact-host", t0, depth=depth, rows=int(sum(counts)),
                width=W, fetch_ms=round(io.fetch_ms - fetch0, 3),
                put_ms=round(io.put_ms - put0, 3))
        return (sidx,) + on_device

    # --- the chunk driver -------------------------------------------------
    def run_chunk(self, piece, fp_n, bucket, depth, vhi, vlo, vn, vcap):
        _h1, _h2, _h3, finalize = self.run_chunk_staged(
            piece, fp_n, bucket, depth, vhi, vlo, vn, vcap
        )
        return finalize()

    def guard_stage(self, piece, fp_n, bucket, depth,
                    attempt: int = 0) -> "StagedGuard":
        """First half of a fused chunk: upload the piece and dispatch
        launch 1.  It reads the frontier piece only, never the visited
        set, so the level loop may run it one chunk ahead (before the
        previous chunk's successor launch is queued).  Never raises: what
        goes wrong is kept on the result, and :meth:`run_chunk_staged`
        handles it at the chunk's own turn, on its one failure ladder."""
        from .bfs import _pad_rows

        t0 = perf_counter()
        g = StagedGuard(fp_n, bucket, depth)
        try:
            # escalated=True on BOTH inject and handle: the fused
            # programs are the adaptive (escalated-shape) family, so
            # KSPEC_FAULT=compile_oom rehearses exactly this path's
            # degradation to legacy
            injected = self.fault.chunk_error(escalated=True)
            if injected is not None:
                raise injected
            g.frontier = self.io.put(_pad_rows(piece, bucket))
            fvalid = jnp.arange(bucket) < fp_n
            g.launch = self.io.dispatch("fgd", attempt=attempt,
                                        depth=depth, bucket=bucket)
            g.outs = self.guard_step(bucket)(g.frontier, fvalid)
            g.dispatched = 1  # launch 1: the guard matrix
            # what the host reads of it (the matrix and its counts here,
            # the verdict flags at the commit) crosses as soon as it is
            # computed, not a round trip a read
            ga, act_guard, viol_any, _vi, dl_any, _di = g.outs
            self.io.prefetch(ga, act_guard, viol_any, dl_any)
        except Exception as e:  # noqa: BLE001 — XLA compile/run
            g.fail(e)
        g.host_s += perf_counter() - t0
        return g

    def compact_stage(self, g: "StagedGuard") -> None:
        """Between the launches, all of it on the host: read launch 1's
        counts (this read forces it), size the pooled widths, compact the
        guard matrix and upload the index vectors (``_compact``).  Runs
        chunk after chunk, ahead or not: ``PooledWidths.hw`` is
        order-dependent.  A second call, or one after a failure, does
        nothing; never raises (as :meth:`guard_stage`)."""
        if g.error is not None or g.widths is not None:
            return
        t0 = perf_counter()
        try:
            ga, act_guard = g.outs[0], g.outs[1]
            # the counts shape launch 2: this read forces launch 1
            g.act_guard_np = self.io.fetch(act_guard, np.int64)
            g.launch.finish()
            widths = self.pool.widths_for(
                g.bucket, g.act_guard_np.astype(np.float64), g.fp_n
            )
            g.compacted = self._compact(ga, widths, g.depth)
            g.widths = widths
        except Exception as e:  # noqa: BLE001 — XLA runtime
            g.fail(e)
        g.host_s += perf_counter() - t0

    def run_chunk_staged(self, piece, fp_n, bucket, depth,
                         vhi, vlo, vn, vcap, reset: bool = True,
                         ahead: Optional["StagedGuard"] = None):
        """Dispatch both fused launches; -> (vhi, vlo, vn, finalize).

        The composition of the chunk's two halves: :meth:`guard_stage`
        (the upload and launch 1), then :meth:`compact_stage` (the guard
        matrix is forced there: its counts drive the host compaction
        that shapes launch 2) and launch 2, whose outputs stay
        in-flight.  The overlap driver in check() runs the first half of
        chunk k+1, and this method for chunk k, BEFORE it commits chunk
        k-1, so a chunk's host work runs while another's update-skeleton/
        dedup launch drains on device (docs/engine.md § Async execution);
        `ahead` is this chunk's first half where the driver has run it
        already.  A failure there is raised HERE, into the one failure
        ladder (retry, then the sticky fallback to legacy), and the
        chunk is re-run in serial order.  finalize()
        blocks on the outputs and returns run_chunk's exact tuple —
        with overlap off check() finalizes immediately, which IS the
        historical serial behavior.  `finalize.launch` is launch 2's
        open dispatch: a caller that drops the chunk uncommitted (a
        verdict in the chunk before it) finishes it as discarded;
        `finalize.ahead` says the committed attempt took `ahead`.  The
        returned visited refs chain the next chunk's dispatch on the
        device backend (functional, still in-flight — JAX async dispatch
        pipelines them)."""
        if not self._gate(bucket):
            if ahead is not None:
                # the run fell back to legacy after this chunk's guard
                # launch went out
                ahead.drop()
            return self.legacy.run_chunk_staged(
                piece, fp_n, bucket, depth, vhi, vlo, vn, vcap
            )
        if reset:
            self.chunk_retry.reset_chunk()
        io = self.io
        dispatched = 0  # successor programs actually dispatched,
        # retries included — what "launches" honestly means
        attempt = 0
        while True:
            launch = None
            try:
                took_ahead = ahead is not None
                g, ahead = ahead, None
                if g is None:
                    g = self.guard_stage(piece, fp_n, bucket, depth,
                                         attempt)
                self.compact_stage(g)
                dispatched += g.dispatched
                if g.error is not None:
                    raise g.error
                frontier, act_guard_np, widths = (
                    g.frontier, g.act_guard_np, g.widths)
                viol_any, viol_idx, dl_any, dl_idx = g.outs[2:]
                sidx, sidx_d, chloc_d, rowvalid_d = g.compacted
                # launch 2 stays in flight: finalize() closes its span
                # where the host first blocks on its outputs
                launch = io.dispatch("fsc", attempt=attempt, depth=depth,
                                     bucket=bucket, vcap=vcap)
                attempt += 1
                outs = self.succ_step(bucket, widths, vcap)(
                    frontier, sidx_d, chloc_d, rowvalid_d, vhi, vlo, vn,
                )
                dispatched += 1  # launch 2: the update skeleton
                if self.visited_backend != "host":
                    (out, out_parent, out_act, new_n, out_hi, out_lo,
                     vhi, vlo, vn, act_en) = outs
                    # the three scalars-and-counts the level loop blocks
                    # on cross behind the program, in one wait
                    io.prefetch(act_en, new_n, vn)
            except Exception as e:  # noqa: BLE001 — XLA compile/run
                if launch is not None:
                    launch.finish(discarded=True)
                # escalated=True: the fused programs are the adaptive
                # (escalated-shape) family, so a compile/alloc failure
                # degrades to the always-compilable legacy uniform path
                # for the rest of the run instead of re-raising
                action = self.chunk_retry.handle(
                    e, escalated=True, depth=depth
                )
                if action == "retry":
                    continue
                self.fallback = True
                from ..obs import tracer as _obs

                _obs.event("pipeline-fallback", depth=depth,
                           error=f"{type(e).__name__}: {e}"[:200])
                return self.legacy.run_chunk_staged(
                    piece, fp_n, bucket, depth, vhi, vlo, vn, vcap
                )
            if self.visited_backend == "host":
                # host backend: validity is resolved at C speed on the
                # host — deferred into finalize so the np conversions
                # (the device-wait) land at commit time, off the next
                # chunk's dispatch path
                def finalize(outs=outs, sidx=sidx, widths=widths,
                             vhi=vhi, vlo=vlo, vn=vn,
                             act_guard_np=act_guard_np,
                             verdicts=(viol_any, viol_idx, dl_any,
                                       dl_idx),
                             dispatched=dispatched, launch=launch):
                    cand, ok, hi, lo = outs
                    viol_any, viol_idx, dl_any, dl_idx = verdicts
                    try:
                        # JAX async dispatch defers runtime errors to the
                        # first materialization — which is HERE, outside
                        # the dispatch-time try.  Route them through the
                        # same failure ladder: transients re-run the
                        # whole chunk synchronously; anything else
                        # degrades the run to legacy (the documented
                        # fused failure contract)
                        ok_np = io.fetch(ok)
                        launch.finish()
                    except Exception as e:  # noqa: BLE001 — XLA runtime
                        launch.finish(discarded=True)
                        action = self.chunk_retry.handle(
                            e, escalated=True, depth=depth
                        )
                        if action != "retry":
                            self.fallback = True
                            from ..obs import tracer as _obs

                            _obs.event(
                                "pipeline-fallback", depth=depth,
                                error=f"{type(e).__name__}: {e}"[:200],
                            )
                            return self.legacy.run_chunk(
                                piece, fp_n, bucket, depth,
                                vhi, vlo, vn, vcap,
                            )
                        # re-run the chunk WITHOUT resetting the
                        # per-chunk retry budget: handle() raises once
                        # it is exhausted, so the recursion is bounded
                        _r1, _r2, _r3, fin2 = self.run_chunk_staged(
                            piece, fp_n, bucket, depth, vhi, vlo, vn,
                            vcap, reset=False,
                        )
                        return fin2()
                    nn = int(ok_np.sum())
                    out = io.fetch(cand)[ok_np]
                    out_parent = sidx[ok_np]
                    out_act = self._actid_np(widths)[ok_np]
                    out_hi = io.fetch(hi)[ok_np]
                    out_lo = io.fetch(lo)[ok_np]
                    offs = np.cumsum([0] + list(widths))
                    act_en = np.asarray(
                        [
                            int(ok_np[offs[i]: offs[i + 1]].sum())
                            for i in range(len(widths))
                        ] + [0] * len(WORK_FIELDS),  # counts_out's
                        # layout: no dedup ran
                        np.int64,
                    )
                    return (
                        out, out_parent, out_act, nn, vhi, vlo, vn,
                        viol_any, viol_idx, dl_any, dl_idx, act_en,
                        out_hi, out_lo, act_guard_np, dispatched,
                        int(sum(widths)),
                    )

                finalize.launch, finalize.ahead = launch, took_ahead
                return vhi, vlo, vn, finalize

            def finalize(launch=launch, act_en=act_en, committed=(
                    out, out_parent, out_act, new_n, vhi, vlo, vn,
                    viol_any, viol_idx, dl_any, dl_idx, None,
                    out_hi, out_lo, act_guard_np, dispatched,
                    int(sum(widths)))):
                # the first read of launch 2's outputs: the host blocks
                # here until the update skeleton has run
                act_en_np = io.fetch(act_en, np.int64)
                launch.finish()
                return committed[:11] + (act_en_np,) + committed[12:]

            finalize.launch, finalize.ahead = launch, took_ahead
            return vhi, vlo, vn, finalize

    def _actid_np(self, widths: tuple) -> np.ndarray:
        return np.concatenate(
            [np.full(w, i, np.int32) for i, w in enumerate(widths)]
        )


# --------------------------------------------------------------------------
# device pipeline: the whole level as one dispatched program
# --------------------------------------------------------------------------


def device_hull_fallback(model) -> Optional[str]:
    """The field-hull HARD precondition shared by every device-resident
    level path (single-device DevicePipeline and the sharded per-shard
    variant): every field's proven reachable-value hull must sit inside
    its declared packed range.  Stricter than the engine's KSPEC_ANALYZE
    gate on purpose — the gate can be env-disabled, this cannot: a
    device-resident level has no host visibility between chunks, so the
    pack stage's no-truncation property must be PROVEN, not assumed.
    Returns None when proven, else the human-readable fallback reason."""
    from ..analysis.interval import AnalysisUnsupported

    try:
        from ..analysis import field_hulls

        hulls = field_hulls(model, strict=True)
    except AnalysisUnsupported as e:
        return f"no proven field hulls ({e})"
    except Exception as e:  # noqa: BLE001 — never break checking
        return (
            f"field-hull analysis failed "
            f"({type(e).__name__}: {e})"[:200]
        )
    bad = [
        f.name
        for f in model.spec.fields
        if hulls[f.name][0] < f.lo or hulls[f.name][1] > f.hi
    ]
    if bad:
        return (
            f"field hull escapes the declared packed range for "
            f"{bad} (encoding-unsound model; KSPEC_ANALYZE=0?)"
        )
    return None


class DevicePipeline:
    """Device-resident level pipeline (module docstring): one dispatched
    ``lax.while_loop`` program runs every gated chunk of a BFS level —
    <=2 successor launches per LEVEL.  Two native backends:

    - sorted-set ``device``: in-jit dual-probe dedup (read-only visited
      set + level-new set), the visited-set merge deferred to one
      rank-scatter per level, in-jit digest folds;
    - ``host`` (incl. the disk tier): deferred-probe mode — the device
      holds NO visited set, intra-level novelty is decided against the
      level-new sorted set alone, and the level's novel candidates come
      back (rows + fingerprint lanes, chunk-major CANDIDATE order) for
      ONE batched host FpSet / tiered-run probe per level
      (engine.level.commit_device_level) — host syncs drop from
      O(chunks) to O(1) per level on the production backend.

    Both require analyzer-proven per-field value hulls
    (analysis.field_hulls: the in-jit pack stage runs with no host-side
    validation between chunks, so the no-truncation proof is a hard
    precondition here, independent of the KSPEC_ANALYZE build-gate
    toggle); everything else — ``device-hash``, sub-gate chunks, shadow
    re-execution, and any compile/dispatch failure — degrades to the
    ``fused`` per-chunk path, which itself degrades to ``legacy`` (the
    documented ladder)."""

    name = "device"
    launches_per_chunk = 2  # nominal figure when delegating per-chunk

    def __init__(self, step_builder, model, adapt, chunk_retry, fault,
                 check_invariants: bool, visited_backend: str,
                 on_degrade_chunk, compact_shift: int, compact_gate: int,
                 check_deadlock: bool = False, io=None):
        self.step = step_builder
        self.model = model
        self.spec = model.spec
        self.chunk_retry = chunk_retry
        self.fault = fault
        self.check_invariants = check_invariants
        self.check_deadlock = check_deadlock
        self.visited_backend = visited_backend
        self.fused = FusedPipeline(
            step_builder, model, adapt, chunk_retry, fault,
            check_invariants, visited_backend, on_degrade_chunk,
            compact_shift, compact_gate, io=io,
        )
        self.io = self.fused.io
        self.pool = PooledWidths(model.actions)
        self._ln_hw = 0  # per-level new-state high water (LN ladder)
        #: what an earlier call of the same prepared model measured, by
        #: depth (seed_high_waters); empty on a cold call
        self._seed: dict = {}
        self.seeded = False  # True once a level took its sizes from it
        #: what THIS call measured, one record per device level: exact
        #: guard and new-state counts, whatever the buffers were sized
        #: from (stats["device"]["high_waters"])
        self.high_waters: list = []
        #: sticky fallback reason; None while the level path is live
        self.device_fallback: Optional[str] = None
        self.device_levels = 0  # levels actually run device-resident
        #: deferred-probe mode (host / disk-tier visited backends): the
        #: level program carries NO visited set — intra-level novelty
        #: against the level-new sorted set only, and the host probes
        #: the level's novel candidates in ONE batched call per level
        #: (engine.level.commit_device_level's host branch)
        self.host_mode = visited_backend == "host"
        from ..pipeline_registry import backend_fallback_reason

        # the registry's per-backend support matrix is the ONE source of
        # which backends this pipeline serves natively; unsupported
        # cells degrade with the registry's own (backend-naming) reason
        self.device_fallback = backend_fallback_reason(
            "device", visited_backend
        )
        if self.device_fallback is None:
            self._check_hulls()

    def _check_hulls(self) -> None:
        """The field-hull precondition (:func:`device_hull_fallback` —
        one shared check with the sharded device-resident variant)."""
        self.device_fallback = device_hull_fallback(self.model)

    # --- per-chunk interface: delegate to the fused ladder ----------------
    @property
    def fallback(self) -> bool:
        """fused->legacy degradation flag (stats['pipeline_fallback']
        keeps its historical meaning; the device->fused step is
        reported separately via device_fallback)."""
        return self.fused.fallback

    @property
    def legacy(self):
        return self.fused.legacy

    def _gate(self, bucket: int) -> bool:
        return self.fused._gate(bucket)

    def run_chunk(self, piece, fp_n, bucket, depth, vhi, vlo, vn, vcap):
        return self.fused.run_chunk(
            piece, fp_n, bucket, depth, vhi, vlo, vn, vcap
        )

    def run_chunk_staged(self, piece, fp_n, bucket, depth,
                         vhi, vlo, vn, vcap):
        return self.fused.run_chunk_staged(
            piece, fp_n, bucket, depth, vhi, vlo, vn, vcap
        )

    # --- the whole-level path ---------------------------------------------
    def plan_level(self, f_total: int, chunk: int, min_bucket: int):
        """-> (bucket, n_chunks, rows_handled) when the device program
        can serve (a prefix of) this level, else None.

        The plan mirrors the serial chunking EXACTLY: full chunks run at
        bucket == chunk; a trailing partial chunk joins the dispatch iff
        the serial loop would have taken the compacted (gated) path for
        it — a sub-gate tail instead runs through the per-chunk ladder
        after the device dispatch, preserving the legacy full-lattice
        candidate order the gate exists to protect (bit-identity)."""
        from .bfs import _next_pow2

        if self.device_fallback is not None or self.fused.fallback:
            return None
        if f_total <= 0:
            return None
        if f_total <= chunk:
            B = _next_pow2(max(f_total, min_bucket))
            return (B, 1, f_total) if self.fused._gate(B) else None
        if not self.fused._gate(chunk):
            return None
        n_full, rem = divmod(f_total, chunk)
        nc, handled = n_full, n_full * chunk
        if rem and self.fused._gate(_next_pow2(max(rem, min_bucket))):
            nc += 1
            handled = f_total
        return (chunk, nc, handled)

    def seed_high_waters(self, records: dict) -> None:
        """Start this call where the last call of the same prepared
        model ended: `records` is PreparedKernels.level_high_waters,
        depth -> a ``high_waters`` record of earlier runs (read, never
        written).  They only choose the FIRST dispatch's buffer sizes;
        a record that is too small (another chunk size, a level the
        earlier run never reached) costs the one re-dispatch a cold
        call pays."""
        self._seed = records

    def first_dispatch_sizes(self, depth: int, B: int, NCp: int):
        """-> (widths, T, LN) of a level's first dispatch: the two
        sizing policies (PooledWidths.widths_for, devlevel.
        level_new_capacity) over the high waters as they stand, raised
        first to what the seed holds for `depth`.  The high waters only
        climb, so a seeded level sizes exactly as a cold call's
        re-dispatch of it did."""
        seed = self._seed.get(depth)
        if seed is not None:
            np.maximum(self.pool.hw, seed["density"], out=self.pool.hw)
            self._ln_hw = max(self._ln_hw, seed["level_new"])
            self.seeded = True
        widths = self.step.norm_widths(
            B,
            self.pool.widths_for(
                B, np.zeros(len(self.model.actions)), B
            ),
        )
        T = self.step.expand_width(B, widths)
        # level-new capacity ladder (ops/devlevel.level_new_capacity —
        # ONE sizing policy shared with the sharded device-resident
        # variant): the per-chunk merge costs O(LN), so size LN from the
        # measured per-level new-state high water, NOT the NCp*T
        # worst case — an overflow costs exactly one re-dispatch at the
        # safe bound, steady state costs nothing.  This is where the
        # device pipeline's merge win comes from: the serial path
        # scatters O(visited capacity) per CHUNK, this path scatters
        # O(level) per chunk and O(capacity) once.
        LN = devlevel.level_new_capacity(T, self._ln_hw, NCp * T)
        return widths, T, LN

    def level_key(self, B: int, NCp: int, vcap: int, widths: tuple,
                  LN: int) -> tuple:
        """The step-cache key of a level program (layouts: key_vcap)."""
        tail = (NCp, widths, LN,
                self.step.inv_sig(self.check_invariants),
                self.check_deadlock)
        if self.host_mode:
            # no vcap component: the program embeds no visited set, so
            # capacity growth can never evict it (key_vcap -> None)
            return ("dvh", B) + tail
        return ("dvl", B, vcap) + tail

    def empty_level_args(self, B: int, NCp: int) -> tuple:
        """A level program's arguments for a level of no rows, short of
        the visited set (warm_key, warm_seeded_levels)."""
        return (
            jnp.zeros((NCp * B, self.spec.num_lanes), jnp.uint32),
            jnp.int32(0),
            jnp.int32(0),
        )

    def _level_program(self, B: int, NCp: int, vcap: int, widths: tuple,
                       LN: int):
        key = self.level_key(B, NCp, vcap, widths, LN)
        if self.host_mode:
            return self.step.cached(
                key,
                lambda: self._build_level_host(B, NCp, widths, LN),
                bucket=B, chunks=NCp, widths=repr(widths),
                level_new_cap=LN, program="device-level-host",
            )
        return self.step.cached(
            key,
            lambda: self._build_level(B, NCp, vcap, widths, LN),
            bucket=B, vcap=vcap, chunks=NCp, widths=repr(widths),
            level_new_cap=LN, program="device-level",
        )

    def _build_level(self, B: int, NCp: int, vcap: int, widths: tuple,
                     LN: int):
        """The whole-level program: while_loop over chunk index.

        Bit-identity argument (vs the serial fused/legacy chunk loop):
        every chunk runs the SAME compacted expansion (make_expand's
        per-action in-jit cumsum/scatter — action-major, row-major
        within an action, the exact candidate order the fused host
        compaction preserves), the same squeeze/fingerprint/stable-
        lexsort stages, and novelty against (visited ∪ level-new) ==
        the serial path's chunk-by-chunk merged visited set; winners of
        equal fingerprints are decided by the same stable sort over the
        same candidate order.  Chunks run at the full static bucket
        with padding rows masked — masked rows enable nothing, so the
        enabled-pair sequence (and hence every downstream decision) is
        identical to the serial path's smaller tail bucket.  Verdict
        priority mirrors the serial commit loop: invariants beat
        deadlock within a chunk, earlier chunks beat later ones, and a
        verdict chunk commits nothing.  The visited merge runs ONCE
        after the loop — set-equal to the serial per-chunk merges
        because levels are disjoint from the visited set by
        construction."""
        model, spec = self.model, self.spec
        K = spec.num_lanes
        T = self.step.expand_width(B, widths)
        # LN: the level-new sorted set's capacity — sized by run_level
        # from a high-water ladder (a level's TOTAL new states, usually
        # far below the NCp*T worst case) because the per-chunk merge's
        # cost is O(LN); an overflow re-dispatches once at the safe
        # bound.  OC: the output row buffer gets one chunk of headroom
        # past LN so a full-T append at offset <= LN can never hit the
        # dynamic_update_slice start-index clamp (which would silently
        # overwrite earlier rows instead of failing).
        OC = LN + T
        expand = self.step.make_expand(B, widths)
        check_invariants = self.check_invariants
        check_deadlock = self.check_deadlock
        n_actions = len(model.actions)
        # (under a symmetry every work vector of the program ends in the
        # canon pair: `work_counts(symmetric=)`)
        symmetric = model.symmetry is not None

        def level(fbuf, f_total, n_chunks, vhi, vlo, vn):  # kspec: traced
            sent = jnp.uint32(dedup.SENT)

            def body(carry):  # kspec: traced
                (i, orows, opar, oact, on, lhi, llo, ln,
                 vkind, vinv, vidx, act_en, agmax, dig, ovf, work) = carry
                with stage("guard"):
                    start = i * B
                    rows = jax.lax.dynamic_slice(fbuf, (start, 0), (B, K))
                    fvalid = (
                        start + jnp.arange(B, dtype=jnp.int32)
                    ) < f_total
                    states = jax.vmap(spec.unpack)(rows)
                (en_pre, cand, valid, parent, actid, a_en, a_guard,
                 exp_ovf) = expand(rows, states, fvalid)
                with stage("guard"):
                    deadlocked = fvalid & ~jnp.any(en_pre, axis=1)
                viol_any, viol_idx = invariant_stage(
                    model, states, fvalid, check_invariants
                )
                (cand, parent, actid, rowvalid, _n_en,
                 sq_ovf) = squeeze_stage(cand, parent, actid, valid,
                                         T, K)
                hi, lo, orbit = fp_stage(cand, rowvalid, model)
                # the SHARED winner-selection sequence (one source of
                # truth with the fused/legacy paths): primary set =
                # level-new (its ranks drive the gated merge below),
                # also_seen_in = the read-only visited set
                (n_out, n_par, n_act, new_n, n_hi, n_lo, _l1, _l2,
                 _l3, n_rank, c_work) = sorted_dedup_stage(
                    cand, parent, actid, rowvalid, hi, lo,
                    lhi, llo, ln, LN, T, K, False,
                    also_seen_in=(vhi, vlo, vn), orbit=orbit,
                )
                # verdicts, serial-commit priority
                with stage("invariants"):
                    inv_any = jnp.any(viol_any)
                    inv_i = jnp.argmax(viol_any).astype(jnp.int32)
                    dl_any = (jnp.bool_(check_deadlock)
                              & jnp.any(deadlocked))
                    kind = jnp.where(
                        inv_any, jnp.int32(1),
                        jnp.where(dl_any, jnp.int32(2), jnp.int32(0)),
                    )
                    g_idx = jnp.where(
                        inv_any, viol_idx[inv_i],
                        jnp.argmax(deadlocked).astype(jnp.int32),
                    ).astype(jnp.int32) + start
                    take = (vkind == 0) & (kind != 0)
                    commit = kind == 0  # a verdict chunk commits nothing
                # LN overflow: this level's new states outgrew the
                # ladder-sized level-new set — dropped merge scatters
                # would corrupt later chunks' novelty, so stop
                # committing (commit_ok) and flag for the exact-bound
                # re-dispatch.  Width/squeeze overflows flag the same
                # way (the whole level re-runs either way).
                with stage("dedup_merge"):
                    ln_ovf = commit & ((ln + new_n) > LN)
                    commit_ok = commit & ~ovf & ~ln_ovf
                    app_n = jnp.where(commit_ok, new_n, 0)
                if symmetric:
                    with stage("canon"):  # only committed states count
                        c_work = c_work.at[-1].set(
                            jnp.where(commit_ok, c_work[-1], 0))
                with stage("compact"), part("append"):
                    orows = devlevel.append_rows(orows, n_out, on)
                    opar = devlevel.append_vec(opar, n_par + start, on)
                    oact = devlevel.append_vec(oact, n_act, on)
                lhi, llo, ln, c_slots = dedup.merge_counted(
                    lhi, llo, ln, n_hi, n_lo, n_rank, app_n, LN
                )
                dig = devlevel.combine_digest(
                    dig,
                    devlevel.masked_digest(
                        n_hi, n_lo, jnp.arange(T) < app_n
                    ),
                )
                with stage("expand"):  # its counters and overflow flags
                    act_en = act_en + jnp.where(commit_ok, a_en, 0)
                    agmax = jnp.maximum(agmax, a_guard)
                    ovf = ovf | jnp.any(exp_ovf) | sq_ovf | ln_ovf
                with stage("invariants"):  # the first verdict wins
                    vkind = jnp.where(take, kind, vkind)
                    vinv = jnp.where(take, inv_i, vinv)
                    vidx = jnp.where(take, g_idx, vidx)
                return (i + 1, orows, opar, oact, on + app_n,
                        lhi, llo, ln, vkind, vinv, vidx,
                        act_en, agmax, dig, ovf,
                        work + c_work + work_counts(merge=c_slots,
                                                    symmetric=symmetric))

            def cond(carry):  # kspec: traced
                return (carry[0] < n_chunks) & (carry[8] == 0)

            # the level's output and level-new buffers
            with stage("compact"), part("append"):
                init = (
                    jnp.int32(0),
                    jnp.zeros((OC, K), jnp.uint32),
                    jnp.zeros((OC,), jnp.int32),
                    jnp.zeros((OC,), jnp.int32),
                    jnp.int32(0),
                    jnp.full((LN,), sent),
                    jnp.full((LN,), sent),
                    jnp.int32(0),
                    jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.zeros((n_actions,), jnp.int32),
                    jnp.zeros((n_actions,), jnp.int32),
                    devlevel.zero_digest(),
                    jnp.bool_(False),
                    work_counts(symmetric=symmetric),
                )
            (_i, orows, opar, oact, on, lhi, llo, _ln, vkind, vinv,
             vidx, act_en, agmax, dig, ovf, work) = jax.lax.while_loop(
                cond, body, init
            )
            # ONE visited merge per level (the serial path pays one
            # per chunk): every level-new entry is disjoint from the
            # visited set by construction, so the rank-scatter merge of
            # the sorted level-new prefix lands the identical sorted
            # visited array
            # (the level-new set is sorted and `on` long: its live prefix)
            _f, rank_v, m_probe = dedup.probe_sorted(
                vhi, vlo, vn, lhi, llo, on)
            vhi, vlo, vn, m_slots = dedup.merge_counted(
                vhi, vlo, vn, lhi, llo, rank_v, on, vcap
            )
            return (orows, opar, oact, on, vhi, vlo, vn, vkind, vinv,
                    vidx,
                    counts_out(act_en, work + work_counts(
                        m_probe, m_slots, symmetric=symmetric)),
                    agmax, dig, ovf)

        return level

    def _build_level_host(self, B: int, NCp: int, widths: tuple,
                          LN: int):
        """The whole-level program for the HOST (deferred-probe) visited
        backends — the C-arena FpSet and the disk tier.  Identical chunk
        walk to :meth:`_build_level`, with three deltas:

        - the device holds NO visited set: novelty inside the level is
          decided against the level-new sorted set alone
          (candidate_dedup_stage — same stable-sort winners as the
          device backend, but emitted in CANDIDATE order, the order the
          serial host commit feeds the FpSet), and the host filters
          already-visited states in ONE batched probe per level;
        - the emitted prefix carries its fingerprint lanes out (ohi/olo
          accumulators) so the host probe never recomputes them;
        - no in-jit digest: the multiset the chain folds is only known
          AFTER the probe, so the host folds the surviving fingerprints
          exactly as the serial per-chunk commit does.

        Verdicts derive from the FRONTIER states being expanded — states
        the previous level already probed and committed — so the
        deferred probe cannot change them; the serial priority
        (invariants beat deadlock within a chunk, earlier chunks beat
        later ones, a verdict chunk commits nothing) is mirrored
        unchanged.  docs/engine.md § Device-resident level pipeline
        states the full bit-identity argument."""
        model, spec = self.model, self.spec
        K = spec.num_lanes
        T = self.step.expand_width(B, widths)
        OC = LN + T  # one chunk of append headroom past LN (as _build_level)
        expand = self.step.make_expand(B, widths)
        check_invariants = self.check_invariants
        check_deadlock = self.check_deadlock
        n_actions = len(model.actions)

        def level(fbuf, f_total, n_chunks):  # kspec: traced
            sent = jnp.uint32(dedup.SENT)

            def body(carry):  # kspec: traced
                (i, orows, opar, oact, ohi, olo, on, lhi, llo, ln,
                 vkind, vinv, vidx, act_en, agmax, ovf, work) = carry
                with stage("guard"):
                    start = i * B
                    rows = jax.lax.dynamic_slice(fbuf, (start, 0), (B, K))
                    fvalid = (
                        start + jnp.arange(B, dtype=jnp.int32)
                    ) < f_total
                    states = jax.vmap(spec.unpack)(rows)
                (en_pre, cand, valid, parent, actid, a_en, a_guard,
                 exp_ovf) = expand(rows, states, fvalid)
                with stage("guard"):
                    deadlocked = fvalid & ~jnp.any(en_pre, axis=1)
                viol_any, viol_idx = invariant_stage(
                    model, states, fvalid, check_invariants
                )
                (cand, parent, actid, rowvalid, _n_en,
                 sq_ovf) = squeeze_stage(cand, parent, actid, valid,
                                         T, K)
                hi, lo, _orbit = fp_stage(cand, rowvalid, model)
                (n_out, n_par, n_act, n_ohi, n_olo, new_n,
                 s_hi, s_lo, s_rank, c_work) = candidate_dedup_stage(
                    cand, parent, actid, rowvalid, hi, lo,
                    lhi, llo, ln, T, K,
                )
                # verdicts, serial-commit priority (same as _build_level)
                with stage("invariants"):
                    inv_any = jnp.any(viol_any)
                    inv_i = jnp.argmax(viol_any).astype(jnp.int32)
                    dl_any = (jnp.bool_(check_deadlock)
                              & jnp.any(deadlocked))
                    kind = jnp.where(
                        inv_any, jnp.int32(1),
                        jnp.where(dl_any, jnp.int32(2), jnp.int32(0)),
                    )
                    g_idx = jnp.where(
                        inv_any, viol_idx[inv_i],
                        jnp.argmax(deadlocked).astype(jnp.int32),
                    ).astype(jnp.int32) + start
                    take = (vkind == 0) & (kind != 0)
                    commit = kind == 0  # a verdict chunk commits nothing
                with stage("dedup_merge"):
                    ln_ovf = commit & ((ln + new_n) > LN)
                    commit_ok = commit & ~ovf & ~ln_ovf
                    app_n = jnp.where(commit_ok, new_n, 0)
                with stage("compact"), part("append"):
                    orows = devlevel.append_rows(orows, n_out, on)
                    opar = devlevel.append_vec(opar, n_par + start, on)
                    oact = devlevel.append_vec(oact, n_act, on)
                    ohi = devlevel.append_vec(ohi, n_ohi, on)
                    olo = devlevel.append_vec(olo, n_olo, on)
                lhi, llo, ln, c_slots = dedup.merge_counted(
                    lhi, llo, ln, s_hi, s_lo, s_rank, app_n, LN
                )
                with stage("expand"):  # its counters and overflow flags
                    act_en = act_en + jnp.where(commit_ok, a_en, 0)
                    agmax = jnp.maximum(agmax, a_guard)
                    ovf = ovf | jnp.any(exp_ovf) | sq_ovf | ln_ovf
                with stage("invariants"):  # the first verdict wins
                    vkind = jnp.where(take, kind, vkind)
                    vinv = jnp.where(take, inv_i, vinv)
                    vidx = jnp.where(take, g_idx, vidx)
                return (i + 1, orows, opar, oact, ohi, olo,
                        on + app_n, lhi, llo, ln, vkind, vinv, vidx,
                        act_en, agmax, ovf,
                        work + c_work + work_counts(merge=c_slots))

            def cond(carry):  # kspec: traced
                return (carry[0] < n_chunks) & (carry[10] == 0)

            # the level's output and level-new buffers
            with stage("compact"), part("append"):
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
                    jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.zeros((n_actions,), jnp.int32),
                    jnp.zeros((n_actions,), jnp.int32),
                    jnp.bool_(False),
                    work_counts(),
                )
            (_i, orows, opar, oact, ohi, olo, on, _lh, _ll, _ln,
             vkind, vinv, vidx, act_en, agmax, ovf,
             work) = jax.lax.while_loop(cond, body, init)
            return (orows, opar, oact, ohi, olo, on, vkind, vinv,
                    vidx, counts_out(act_en, work), agmax, ovf)

        return level

    def run_level(self, frontier_np, f_total: int, depth: int,
                  vhi, vlo, vn, vcap: int, plan):
        """Run the whole-level program; -> (vhi, vlo, vn, vcap,
        finalize) or None to fall back to the per-chunk ladder.

        The first dispatch is sized from the high waters
        (first_dispatch_sizes).  On a cold call they hold what the
        earlier levels of this call measured, so a level that outgrows
        them overflows a segment and pays the <=1 exact-width
        re-dispatch; a call seeded from the last call of the same
        prepared model (seed_high_waters) starts each level at the
        sizes that re-dispatch ran at and dispatches once.

        The overflow-flag read is the one device sync per level, so
        this call BLOCKS until the level program completes (the
        overlap layer's checkpoint/merge workers are separate threads
        and keep draining while it runs); finalize() only performs the
        host-side output conversions.  The engine accounts the whole
        blocked wall as device-wait on the level's step span — there is
        no in-flight dispatch window to attribute separately, unlike
        the per-chunk staged contract."""
        from .bfs import _next_pow2, _pad_rows

        B, nc, handled = plan
        NCp = _next_pow2(nc)
        io = self.io
        program = "dvh" if self.host_mode else "dvl"
        self.chunk_retry.reset_chunk()
        widths, T, LN = self.first_dispatch_sizes(depth, B, NCp)
        exact = False  # True after an overflow re-dispatch (safe bounds)
        dispatched = 0
        fbuf = None
        outgrown: list = []  # vcaps outgrown this level; evicted on success
        pre_v = (vhi, vlo, vn)  # re-dispatch replays from pre-level state
        # output-tuple indices differ between the two program variants
        # (the host program has no visited set and no digest, but adds
        # the ohi/olo fingerprint accumulators)
        i_vkind, i_agmax, i_ovf = (
            (6, 10, 11) if self.host_mode else (7, 11, 13)
        )
        attempt = 0
        set_n = 0  # the visited set's length at the level's start
        while True:
            launch = None
            try:
                injected = self.fault.chunk_error(escalated=True)
                if injected is not None:
                    raise injected
                if not self.host_mode:
                    set_n = int(io.fetch(vn))
                    need = set_n + min(NCp * T, LN + T)
                    if need > vcap:
                        # eviction of the outgrown capacity's programs
                        # is DEFERRED until this level dispatches
                        # successfully: a growth followed by a device
                        # compile failure must leave the per-chunk
                        # fallback's programs warm
                        outgrown.append(vcap)
                        vhi, vlo, vcap = grow_visited(
                            vhi, vlo, vcap, need
                        )
                        pre_v = (vhi, vlo, vn)
                if fbuf is None:
                    # only the handled prefix rides the device buffer: an
                    # un-gated tail chunk (handled < f_total) runs through
                    # the per-chunk ladder afterwards, and NCp*B can be
                    # smaller than the full frontier in that case
                    fbuf = io.put(
                        _pad_rows(frontier_np[:handled], NCp * B)
                    )
                fn = self._level_program(B, NCp, vcap, widths, LN)
                launch = io.dispatch(program, attempt=attempt, depth=depth,
                                     bucket=B, vcap=vcap, chunks=nc,
                                     level_new_cap=LN)
                attempt += 1
                if self.host_mode:
                    outs = fn(fbuf, jnp.int32(handled), jnp.int32(nc))
                else:
                    outs = fn(fbuf, jnp.int32(handled), jnp.int32(nc),
                              *pre_v)
                dispatched += 1
                # forces the level program (the ONE device sync/level)
                overflow = bool(io.fetch(outs[i_ovf]))
            except Exception as e:  # noqa: BLE001 — XLA compile/run
                if launch is not None:
                    launch.finish(discarded=True)
                action = self.chunk_retry.handle(
                    e, escalated=True, depth=depth
                )
                if action == "retry":
                    continue
                self._mark_fallback(
                    f"{type(e).__name__}: {e}"[:200], depth
                )
                return None
            agmax_np = io.fetch(outs[i_agmax], np.int64)
            redo = (overflow and int(io.fetch(outs[i_vkind])) == 0
                    and not exact)
            launch.finish(discarded=redo)
            if redo:
                # a segment (or the level-new set) overflowed: outputs
                # are incomplete — discard and re-dispatch ONCE from the
                # pre-level visited state at widths sized from the
                # measured exact per-level max counts and the safe
                # level-new bound (neither can overflow again: <=2
                # launches per level even on growth levels).  A verdict
                # overrides: it derives from frontier states only, so
                # it is exact regardless of successor-buffer overflow.
                widths = self.step.norm_widths(
                    B,
                    self.pool.widths_for(
                        B, agmax_np.astype(np.float64), B
                    ),
                )
                T = self.step.expand_width(B, widths)
                LN = devlevel.level_new_bound(NCp * T)
                exact = True
                continue
            break
        for oc in outgrown:
            evict_vcap(self.step._cache, oc)
        # high waters for the next level's sizing, and the level's own
        # record for the next CALL's (PreparedKernels.note_result).  In
        # host mode the level-new count is the PRE-probe one (the
        # level-new set is what it sizes, and that set holds the
        # not-yet-probed candidates)
        density = agmax_np.astype(np.float64) / max(B, 1)
        level_new = int(io.fetch(outs[5 if self.host_mode else 3]))
        np.maximum(self.pool.hw, density, out=self.pool.hw)
        self._ln_hw = max(self._ln_hw, level_new)
        self.high_waters.append({
            "depth": depth, "bucket": B, "chunks": NCp,
            "density": density.tolist(), "level_new": level_new,
        })
        self.device_levels += 1
        if self.host_mode:

            def finalize(outs=outs, dispatched=dispatched, T=T, LN=LN):
                on = int(io.fetch(outs[5]))
                vk = int(io.fetch(outs[6]))
                verdict = None
                if vk:
                    verdict = (
                        "invariant" if vk == 1 else "deadlock",
                        int(io.fetch(outs[8])),
                        int(io.fetch(outs[7])),
                    )
                return dict(
                    rows=io.fetch(outs[0][:on]),
                    parent=io.fetch(outs[1][:on], np.int32),
                    act=io.fetch(outs[2][:on], np.int32),
                    hi=np.ascontiguousarray(
                        io.fetch(outs[3][:on]), np.uint32
                    ),
                    lo=np.ascontiguousarray(
                        io.fetch(outs[4][:on]), np.uint32
                    ),
                    new_n=on,
                    verdict=verdict,
                    counts=io.fetch(outs[9], np.int64),
                    digest=None,  # host folds the probe survivors
                    launches=dispatched,
                    lanes=T,
                    # the one set the program probes, once a chunk
                    probed=((LN, on, 1, 0),),
                )

            # visited refs unchanged: the host set is the visited state
            return vhi, vlo, vn, vcap, finalize
        new_vhi, new_vlo, new_vn = outs[4], outs[5], outs[6]

        def finalize(outs=outs, dispatched=dispatched, T=T, LN=LN,
                     vcap=vcap, set_n=set_n):
            on = int(io.fetch(outs[3]))
            vk = int(io.fetch(outs[7]))
            verdict = None
            if vk:
                verdict = (
                    "invariant" if vk == 1 else "deadlock",
                    int(io.fetch(outs[9])),
                    int(io.fetch(outs[8])),
                )
            return dict(
                rows=io.fetch(outs[0][:on]),
                parent=io.fetch(outs[1][:on], np.int64),
                act=io.fetch(outs[2][:on]),
                new_n=on,
                verdict=verdict,
                counts=io.fetch(outs[10], np.int64),
                digest=devlevel.digest_ints(
                    tuple(io.fetch(a) for a in outs[12])
                ),
                launches=dispatched,
                lanes=T,
                # the sets the program probes, (capacity, length, probes a
                # chunk, probes a level) each: the level-new set, which
                # only grows, at its last length; the visited set, which
                # the level reads and never writes, for every chunk and
                # once more for the level's one merge
                probed=((LN, on, 1, 0), (vcap, set_n, 1, 1)),
            )

        return new_vhi, new_vlo, new_vn, vcap, finalize

    def _mark_fallback(self, reason: str, depth: int) -> None:
        self.device_fallback = reason
        from ..obs import tracer as _obs

        _obs.event("pipeline-fallback", depth=depth, pipeline="device",
                   to="fused", error=reason)


def make_pipeline(name: str, *, step_builder, model, adapt, chunk_retry,
                  fault, check_invariants, visited_backend,
                  on_degrade_chunk, compact_shift, compact_gate,
                  check_deadlock: bool = False, io=None):
    """Pipeline factory (the one interface check() builds against).
    io: the run's :class:`..hostio.HostIO` (counted transfers, named
    dispatches); None gives the pipeline a private one."""
    if name == "legacy":
        return LegacyPipeline(
            step_builder, model, adapt, chunk_retry, fault,
            check_invariants, visited_backend, on_degrade_chunk, io=io,
        )
    if name == "device":
        return DevicePipeline(
            step_builder, model, adapt, chunk_retry, fault,
            check_invariants, visited_backend, on_degrade_chunk,
            compact_shift, compact_gate, check_deadlock=check_deadlock,
            io=io,
        )
    return FusedPipeline(
        step_builder, model, adapt, chunk_retry, fault,
        check_invariants, visited_backend, on_degrade_chunk,
        compact_shift, compact_gate, io=io,
    )


def _warm_pipeline(step_builder, model, tag: str, inv_sig, dl):
    """A DevicePipeline that builds programs only (warm_key,
    warm_seeded_levels): no run state, the production gate."""
    return DevicePipeline(
        step_builder, model, None, None, None,
        check_invariants=bool(inv_sig),
        visited_backend="host" if tag == "dvh" else "device",
        on_degrade_chunk=None, compact_shift=2, compact_gate=4096,
        check_deadlock=dl,
    )


def _sibling(model, inv_sig) -> bool:
    """True for the key of another invariant overlay's view of the model
    (service/kernel_cache.py shares one step cache per base model)."""
    return bool(inv_sig) and inv_sig != tuple(
        i.name for i in model.invariants
    )


def _run_empty(fn, args, vcap: Optional[int]):
    """Compile `fn` by running it on nothing: zero frontier rows and,
    where the program takes the visited set (`vcap`), an empty one."""
    if vcap is not None:
        # every capacity-keyed program ends in (vhi, vlo, vn)
        empty = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
        args = (*args, empty, empty, jnp.int32(0))
    jax.block_until_ready(fn(*args))


def warm_key(step_builder, model, key: tuple, vcap: int):
    """Re-compile one logged step-cache key at a new visited capacity —
    PreparedKernels.rewarm's per-key worker.  Returns the rebuilt key as
    the builder made it (``_Step.last_key``: each tag's layout is written
    in its builder alone), or None when the key has no capacity component
    (guard kernels never evict on growth)."""
    tag = key[0]
    K = model.spec.num_lanes

    if tag == "step":
        (_t, bucket, _vcap, inv_sig, with_merge, compact, sq_full) = key
        if _sibling(model, inv_sig):
            return None
        fn = step_builder.get(
            bucket, vcap, bool(inv_sig),
            with_merge=with_merge, compact=compact, squeeze_full=sq_full,
        )
        args = (
            jnp.zeros((bucket, K), jnp.uint32),
            jnp.zeros((bucket,), bool),
        )
    elif tag == "dvl":
        (_t, bucket, _vcap, ncp, widths, ln, inv_sig, dl) = key
        if _sibling(model, inv_sig):
            return None
        pipe = _warm_pipeline(step_builder, model, tag, inv_sig, dl)
        fn = pipe._level_program(bucket, ncp, vcap, widths, ln)
        args = pipe.empty_level_args(bucket, ncp)
    elif tag == "fsc":
        (_t, bucket, _vcap, widths, with_merge, device_out) = key
        pipe = FusedPipeline(
            step_builder, model, None, None, None,
            check_invariants=True,
            visited_backend=(
                "device" if with_merge
                else ("device-hash" if device_out else "host")
            ),
            on_degrade_chunk=None, compact_shift=2, compact_gate=4096,
        )
        fn = pipe.succ_step(bucket, widths, vcap)
        W = int(sum(widths))
        args = (
            jnp.zeros((bucket, K), jnp.uint32),
            jnp.zeros((W,), jnp.int32),
            jnp.zeros((W,), jnp.int32),
            jnp.zeros((W,), bool),
        )
    else:
        return None
    made = step_builder.last_key
    _run_empty(fn, args, vcap)
    return made


def warm_seeded_levels(step_builder, model, high_waters: dict,
                       vcap: Optional[int]) -> int:
    """Compile the first-dispatch level programs that a call seeded
    with `high_waters` (PreparedKernels.level_high_waters: depth ->
    record) will ask for and the cache does not hold —
    PreparedKernels.rewarm's second half.  One pass over the records in
    depth order through DevicePipeline.first_dispatch_sizes, the call
    run_level itself makes, for every level-program variant the log
    holds (tag, invariant view, deadlock flag); `vcap` is the capacity
    the next call starts at (None: only the capacity-free host-backend
    programs).  Returns the programs compiled.

    Where a level fits one chunk the seeded sizes ARE the sizes the
    cold call's re-dispatch ran at, so this finds everything in the
    cache; a multi-chunk level's seeded level-new capacity lies under
    the safe bound of that re-dispatch, and is built here."""
    variants = {(k[0], k[-2], k[-1]) for k in step_builder._compiled_log
                if k[0] in ("dvl", "dvh")}
    done = 0
    for tag, inv_sig, dl in sorted(variants):
        if _sibling(model, inv_sig) or (tag == "dvl" and not vcap):
            continue
        pipe = _warm_pipeline(step_builder, model, tag, inv_sig, dl)
        pipe.seed_high_waters(high_waters)
        cap = None if tag == "dvh" else vcap
        for depth in sorted(high_waters):
            rec = high_waters[depth]
            B, NCp = rec["bucket"], rec["chunks"]
            widths, _T, LN = pipe.first_dispatch_sizes(depth, B, NCp)
            if pipe.level_key(B, NCp, cap, widths, LN) in \
                    step_builder._cache:
                continue
            _run_empty(pipe._level_program(B, NCp, cap, widths, LN),
                       pipe.empty_level_args(B, NCp), cap)
            done += 1
    return done
