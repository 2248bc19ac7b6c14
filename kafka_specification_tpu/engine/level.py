"""The level and commit phases of a `check()` call (``engine/run.py`` has
the run object and the other three).

``run_levels`` is the level loop under its typed-error ladder; ``level`` is
one iteration: the boundary's joins and fault points, the whole-level device
span (``_device_span`` -> ``commit_device_level``), the staged chunk loop
(``_chunk_loop`` -> ``commit_wait`` / ``commit_chunk``), then ``_cut_level``
or ``_end_level`` (the record, the checkpoint cadence, the governor's
hooks).  Commits run strictly in dispatch order on the calling thread.
"""

from __future__ import annotations

import os
import time
from functools import partial

import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _met
from ..obs.tracer import now as _now
from ..ops import hashset
from ..overlap import worker_counters
from ..resilience import integrity as _integ
from ..resilience.integrity import IntegrityError
from ..resilience.resources import ResourceExhausted, is_disk_full
from .bfs import _f_all, _f_chunks, _f_rows, _hash_insert, _next_pow2, u64
from .pipeline import (
    grow_visited as _grow_visited, probes_run, split_counts, work_record,
)
from .run import (
    Level, Run, ckpt_poll, final_save, reclaim, save_checkpoint, violation_at,
)

# --- the commit phase


def shadow_exec(r: Run, piece, fp_n, bucket, start, pre_v, cvcap,
                out, out_hi, out_lo, nn, viol_any, dl_any):
    """Sampled shadow re-execution of one committed-candidate chunk
    (see check()'s integrity_shadow docstring).  Two independent
    oracles, both BEFORE the outputs feed the visited set:

    - host fingerprint oracle (every sampled chunk): the numpy twin
      recomputes each emitted row's fingerprint — rows and fps
      diverging means corruption between the kernel and the host;
    - legacy cross-execution (fused-gated chunks): the whole chunk
      re-runs through the legacy per-action pipeline from the same
      pre-chunk visited state — counts, the new-fingerprint multiset
      and the verdict flags must match the fused result exactly (the
      PR 7 bit-identity contract, used as a runtime oracle)."""
    t0 = _now()
    main_fps = _integ.pair_u64(
        np.asarray(out_hi[:nn]), np.asarray(out_lo[:nn])
    )
    rows = np.asarray(out[:nn])
    oracle = _integ.fingerprint_rows(rows, r.spec.exact64)
    mode = "host-oracle"
    if not np.array_equal(oracle, main_fps):
        bad = int(np.argmax(oracle != main_fps))
        raise IntegrityError(
            "shadow",
            f"host fingerprint oracle mismatch at depth {r.depth} chunk "
            f"start {start} row {bad}: recomputed {int(oracle[bad]):#x}"
            f" != emitted {int(main_fps[bad]):#x}",
            depth=r.depth,
        )
    # the device pipeline delegates shadowed runs to its fused
    # per-chunk ladder, so the cross-exec gate reads the FUSED
    # implementation either way
    fp = getattr(r.pipe, "fused", r.pipe)
    if (
        getattr(fp, "name", "") == "fused"
        and not getattr(fp, "fallback", False)
        and fp._gate(bucket)
    ):
        mode = "legacy-cross"
        (l_out, _lp, _la, l_new, _h1, _h2, _h3, l_viol, _vi,
         l_dl, _di, _ae, l_hi, l_lo, _ag, _launch, _lanes) = (
            fp.legacy.run_chunk(
                piece, fp_n, bucket, r.depth, *pre_v, cvcap
            )
        )
        ln = int(l_new)
        l_fps = _integ.pair_u64(
            np.asarray(l_hi[:ln]), np.asarray(l_lo[:ln])
        )
        if ln != nn or _integ.digest_fps(l_fps) != _integ.digest_fps(
            main_fps
        ):
            raise IntegrityError(
                "shadow",
                f"legacy cross-execution diverged at depth {r.depth} "
                f"chunk start {start}: fused emitted {nn} "
                f"fingerprints, legacy {ln} (or multiset digests "
                f"differ) — one of the two pipelines produced "
                f"corrupt successors",
                depth=r.depth,
            )
        if not np.array_equal(
            np.asarray(viol_any), np.asarray(l_viol)
        ) or bool(dl_any) != bool(l_dl):
            raise IntegrityError(
                "shadow",
                f"verdict flags diverged between fused and legacy at "
                f"depth {r.depth} chunk start {start}",
                depth=r.depth,
            )
    _met.inc("kspec_integrity_shadow_total")
    _integ.count_check()
    r.obs.chunk_span(
        "shadow", t0,
        depth=r.depth, start=start, rows=int(fp_n), mode=mode,
    )


def grow_arena(r: Run, nn: int) -> None:
    """Ensure the level arena holds >= nn more rows past a_w (the
    all-novel worst case insert_compact writes unchecked) — ONE
    growth policy for the per-chunk and device-level commits.
    Growth copies only the filled prefix (amortized O(level))."""
    if r.a_w + nn <= r.a_cap:
        return
    r.a_cap = max(2 * r.a_cap, r.a_w + nn)
    na = np.empty((r.a_cap, r.K), np.uint32)
    na[:r.a_w] = r.a_rows[:r.a_w]
    r.a_rows = na
    npar = np.empty(r.a_cap, np.int64)
    npar[:r.a_w] = r.a_parent[:r.a_w]
    r.a_parent = npar
    nact = np.empty(r.a_cap, np.int32)
    nact[:r.a_w] = r.a_act[:r.a_w]
    r.a_act = nact


def take_rows(r: Run, outs, nn: int):
    """The device-backend commit's slices of a chunk's outputs (rows,
    parents, action ids, and the fingerprint lanes where a chain
    folds them), enqueued on the device stream; None where nothing
    is new."""
    if not nn:
        return None
    # finalize()'s tuple: out, out_parent, out_act ... out_hi, out_lo
    return tuple(
        r.io.head(outs[i], nn)
        for i in ((0, 1, 2) + ((12, 13) if r.chain is not None else ()))
    )


def commit_wait(r: Run, st):
    """First half of a chunk's commit, and the loop's one blocking
    wait on a successor program: finalize() (the read of its counts
    vector), the level's counters, the verdict flags (outputs of the
    chunk's guard launch, long computed) and, where there is no
    verdict, `new_n`.  On the sorted `device` backend the slices of
    the chunk's rows are enqueued HERE: the loop calls this before it
    queues the next successor launch on the in-order device stream,
    so the commit's fetches do not wait behind that launch (and a
    chunk that holds the verdict is never sliced, as in serial
    order)."""
    start, fp_n, finalize, t_staged, pre_vcap, was_ahead, set_n = (
        st[0], st[1], st[3], st[7], st[9], st[11], st[12])
    queued_s = time.perf_counter() - t_staged
    t_wait = time.perf_counter()
    outs = finalize()
    new_n, viol_any, viol_idx, dl_any, dl_idx, counts = (
        outs[3], *outs[7:12])
    # the program's counts vector (pipeline.counts_out): a chunk that
    # holds the verdict ran its probe and merge like any other
    act_en_np, work = split_counts(r.io.fetch(counts, np.int64), r.n_work)
    r.lvl.work[:] += work
    # every probe of a chunk's programs searches the visited set, at the
    # capacity and from the length the chunk was dispatched with
    r.lvl.probed(probes_run(work, pre_vcap), pre_vcap, set_n)
    r.lvl.chunks += 1
    r.lvl.ahead += was_ahead
    r.lvl.rows_in += fp_n
    r.lvl.lanes += outs[16]
    r.lvl.guard += st[2] * r.C
    # frontier-level verdicts (states being expanded = level `depth`)
    if r.check_invariants:
        viol_any_np = r.io.fetch(viol_any)
        if viol_any_np.any():
            inv_i = int(np.argmax(viol_any_np))
            idx = start + int(r.io.fetch(viol_idx)[inv_i])
            r.verdict = ("invariant", idx, r.model.invariants[inv_i].name)
    if r.verdict is None and r.check_deadlock and bool(r.io.fetch(dl_any)):
        r.verdict = ("deadlock", start + int(r.io.fetch(dl_idx)),
                     "Deadlock")
    nn, rows = 0, None
    device_decides = r.host_set is None and r.ht_hi is None
    if r.verdict is None:
        nn = int(r.io.fetch(new_n))
        if device_decides:
            rows = take_rows(r, outs, nn)
    elif r.collect_stats and device_decides:
        # the verdict's chunk is never sliced and commits no row, but the
        # device expanded and deduplicated it like any other: where the
        # device decides novelty its counts are the cut level's (one
        # 4-byte fetch of a program long finished)
        r.lvl.act_en += act_en_np
        r.lvl.new += int(r.io.fetch(new_n))
    return (outs, act_en_np, queued_s, time.perf_counter() - t_wait,
            nn, rows)


def commit_chunk(r: Run, st, waited=None) -> bool:
    """Commit one staged chunk: block on its device outputs and read
    its verdict (commit_wait, unless the loop has run it already:
    `waited`), run the shadow oracle, then the backend-specific host
    assembly — the visited-set insert, arena/trace accumulation and
    digest folds.  Commits run strictly in dispatch order on this
    thread; returns True when a verdict fired (the level stops and
    any younger staged chunk is discarded uncommitted)."""
    (start, fp_n, bucket, _finalize, pre_v, shadow, dispatch_s,
     _t_staged, piece, pre_vcap, t_dispatch, was_ahead, _set_n) = st
    (outs, act_en_np, queued_s, wait_s, nn, rows
     ) = waited if waited is not None else commit_wait(r, st)
    (out, out_parent, out_act, new_n, _vh, _vl, _vn, viol_any,
     _viol_idx, dl_any, _dl_idx, _counts, out_hi, out_lo, _act_guard,
     launches, _lanes) = outs
    if r.verdict is None and shadow:
        # pre_vcap: the visited capacity AT DISPATCH — the next
        # chunk's dispatch may have grown `vcap` before this
        # commit, and the shadow cross-exec replays against the
        # pre-chunk visited refs, which are sized at the old
        # capacity
        t_shadow = time.perf_counter()
        shadow_exec(
            r, piece, fp_n, bucket, start, pre_v, pre_vcap,
            out, out_hi, out_lo, nn, viol_any, dl_any,
        )
        wait_s += time.perf_counter() - t_shadow
    # a chunk that holds the verdict is booked like any other (its
    # step time, launches and `step` span are the cut level's)
    step_s = dispatch_s + wait_s
    r.lvl.step_s += step_s
    r.lvl.launches += launches
    r.lvl.launches_max = max(r.lvl.launches_max, launches)
    r.run_launches_max = max(r.run_launches_max, launches)
    # dispatch vs device-wait attribution (overlap accounting):
    # dispatch_ms is the host's time in the chunk's own stages (the
    # upload and launch 1, the compaction between the launches,
    # launch 2), wherever in the schedule they ran; queued_ms is how
    # long the chunk sat staged after launch 2 while the host
    # committed the chunk before it and compacted the one after it —
    # device time hidden behind host work; wait_ms is the residual
    # block on the outputs, the verdict reads included (commit_wait).
    # The span is the true interval, first stage to the start of the
    # chunk's host assembly: in an `ahead` chunk it also spans the
    # other chunks' work between its stages.  step_ms stays dispatch
    # + wait
    r.obs.chunk_span(
        "step", t_dispatch, depth=r.depth, start=start, rows=fp_n,
        bucket=bucket, launches=launches,
        dispatch_ms=round(dispatch_s * 1e3, 2),
        wait_ms=round(wait_s * 1e3, 2),
        queued_ms=round(queued_s * 1e3, 2),
        **({"ahead": True} if was_ahead else {}),
        **({"verdict": r.verdict[0]} if r.verdict is not None else {}),
    )
    if r.verdict is not None:
        return True
    t_host = time.perf_counter()
    t_host_wall = _now()
    fetch0 = r.io.fetch_ms
    if r.host_set is not None and nn:
        if r.use_arena:
            grow_arena(r, nn)
            w = r.host_set.insert_compact(
                np.ascontiguousarray(out_hi[:nn], np.uint32),
                np.ascontiguousarray(out_lo[:nn], np.uint32),
                np.ascontiguousarray(out[:nn], np.uint32),
                np.ascontiguousarray(out_parent[:nn], np.int32),
                start,
                np.ascontiguousarray(out_act[:nn], np.int32),
                r.a_rows[r.a_w:],
                r.a_parent[r.a_w:],
                r.a_act[r.a_w:],
            )
            r.a_w += w
            r.lvl.new += w
            if r.chain is not None and w:
                # arena rows are the committed novel states;
                # the host twin digests them (the C pass hands
                # back rows, not fingerprints)
                r.chain.fold_digest(
                    *_integ.digest_rows(
                        r.a_rows[r.a_w - w : r.a_w], r.spec.exact64
                    )
                )
        else:  # tiered disk store, or no native toolchain
            rows = np.asarray(out[:nn])
            fps_u64 = u64(
                np.asarray(out_hi[:nn]), np.asarray(out_lo[:nn])
            )
            mask = r.host_set.insert(fps_u64)
            if r.disk is not None:
                # novel rows stream straight to the spilled
                # frontier + parent log in discovery order (int64
                # parents: level-global indices can pass 2^31 at
                # the scales this tier exists for)
                t_st = time.perf_counter()
                r.disk.append(
                    rows[mask],
                    np.asarray(out_parent[:nn], np.int64)[mask] + start,
                    np.asarray(out_act[:nn])[mask],
                )
                r.lvl.store_s += time.perf_counter() - t_st
            else:
                r.lvl.rows.append(rows[mask])
                r.lvl.parent.append(
                    np.asarray(out_parent[:nn])[mask] + start
                )
                r.lvl.act.append(np.asarray(out_act[:nn])[mask])
            r.lvl.new += int(mask.sum())
            if r.chain is not None:
                r.chain.fold(fps_u64[mask.astype(bool)])
    elif r.ht_hi is not None and nn:
        # device-hash backend: insert-or-find on the HBM table; a
        # probe-budget overflow grows the table and re-runs the
        # SAME batch, OR-accumulating novelty (rows inserted by the
        # failed attempt report "seen" on the re-run, so nothing is
        # double-counted or lost)
        valid = jnp.arange(out_hi.shape[0]) < new_n
        isnew = np.zeros(out_hi.shape[0], bool)
        while True:
            if r.ht_claim is None:
                r.ht_claim = hashset.new_claim(r.ht_hi.shape[0])
            r.ht_hi, r.ht_lo, r.ht_claim, m, _ni, ovf = _hash_insert(
                r.ht_hi, r.ht_lo, r.ht_claim, out_hi, out_lo, valid
            )
            isnew |= r.io.fetch(m)
            if not bool(r.io.fetch(ovf)):
                break
            r.ht_hi, r.ht_lo = hashset.rehash_into(
                r.ht_hi, r.ht_lo, 2 * r.ht_hi.shape[0]
            )
            r.ht_claim = None
        mask = isnew[:nn]
        r.hash_n += int(mask.sum())
        r.lvl.rows.append(r.io.fetch(out[:nn])[mask])
        r.lvl.parent.append(r.io.fetch(out_parent[:nn])[mask] + start)
        r.lvl.act.append(r.io.fetch(out_act[:nn])[mask])
        r.lvl.new += int(mask.sum())
        if r.chain is not None:
            r.chain.fold(
                _integ.pair_u64(
                    r.io.fetch(out_hi[:nn])[mask],
                    r.io.fetch(out_lo[:nn])[mask],
                )
            )
    elif nn:
        r.lvl.rows.append(r.io.fetch(rows[0]))
        r.lvl.parent.append(r.io.fetch(rows[1]) + start)
        r.lvl.act.append(r.io.fetch(rows[2]))
        r.lvl.new += nn
        if r.chain is not None:
            # device backend: the in-jit dedup already
            # compacted exactly the new states to the front
            r.chain.fold(
                _integ.pair_u64(r.io.fetch(rows[3]), r.io.fetch(rows[4]))
            )
    host_s = time.perf_counter() - t_host
    r.lvl.host_s += host_s
    r.obs.chunk_span(
        "host-assembly", t_host_wall, depth=r.depth, start=start, new=nn,
        backend=r.visited_backend,
        # the part of it blocked in fetches (the rest is numpy)
        fetch_ms=round(r.io.fetch_ms - fetch0, 3),
    )
    if r.collect_stats:
        r.lvl.act_en += act_en_np
    return False


def commit_device_level(r: Run, fin, dispatch_s: float, t_dispatch: float,
                         plan) -> bool:
    """Commit a whole device-resident level (DevicePipeline.run_level):
    block on the level program's outputs, apply the serial commit
    loop's verdict rule, then the host bookkeeping.

    Device backend: trace accumulation and the digest-chain fold
    from the DEVICE-computed (count, xor, sum) accumulator
    (bit-exact with the per-chunk host folds; ops/devlevel.py).

    Host backend (deferred-probe mode): the level's novel
    candidates — unique within the level, chunk-major candidate
    order — are probed/inserted against the host FpSet / disk tier
    in ONE batched call (the tentpole: host syncs O(1) per level).
    The serial winner rule is preserved because intra-level
    duplicates were already resolved on device with the earlier
    chunk winning, and the batch replays in exactly the order the
    serial per-chunk commits would have inserted; the digest chain
    folds the probe SURVIVORS, the same multiset the serial commits
    fold.  Verdicts derive from the frontier states being expanded
    (already probed/committed by the previous level), so the
    deferred probe cannot change them — nothing needs re-deriving.

    True when a verdict fired (the tail is never dispatched)."""
    t_wait = time.perf_counter()
    out = fin()
    act_en_np, work = split_counts(out["counts"], r.n_work)
    r.lvl.work[:] += work
    wait_s = time.perf_counter() - t_wait
    # the one program ran all the plan's chunks, or stopped at the
    # verdict's (its index is level-global, chunk i starts at i * B)
    ran = plan[1]
    if out["verdict"] is not None:
        ran = out["verdict"][1] // plan[0] + 1
    r.lvl.chunks += ran
    r.lvl.rows_in += min(ran * plan[0], plan[2])
    r.lvl.lanes += ran * out["lanes"]
    r.lvl.guard += ran * plan[0] * r.C
    for cap, set_n, a_chunk, a_level in out["probed"]:
        r.lvl.probed(ran * a_chunk + a_level, cap, set_n)
    step_s = dispatch_s + wait_s
    r.lvl.step_s += step_s
    launches = out["launches"]
    r.lvl.launches += launches
    r.lvl.launches_max = max(r.lvl.launches_max, launches)
    r.run_launches_max = max(r.run_launches_max, launches)
    # attribution: run_level BLOCKS on the level program (its
    # overflow-flag read is the one device sync per level), so the
    # whole blocked wall is device-wait — there is no in-flight
    # dispatch window like the per-chunk staged contract has
    r.obs.chunk_span(
        "step", t_dispatch, depth=r.depth, start=0, rows=plan[2],
        bucket=plan[0], launches=launches, chunks=plan[1],
        pipeline="device",
        dispatch_ms=0.0,
        wait_ms=round(step_s * 1e3, 2), queued_ms=0.0,
    )
    if out["verdict"] is not None:
        kind, idx, inv_i = out["verdict"]
        r.verdict = (
            kind,
            idx,
            r.model.invariants[inv_i].name
            if kind == "invariant"
            else "Deadlock",
        )
        if r.collect_stats and r.host_set is None:
            # the chunks before the verdict's: the program adds a chunk's
            # counts only where it commits the chunk
            r.lvl.act_en += act_en_np
            r.lvl.new += out["new_n"]
        return True
    t_host = time.perf_counter()
    t_host_wall = _now()
    nn = out["new_n"]
    if r.host_set is not None:
        # the deferred batched probe — ONE host call for the level
        t_probe = time.perf_counter()
        t_probe_wall = _now()
        committed = 0
        if nn:
            if r.use_arena:
                grow_arena(r, nn)
                # parents are already level-global (the device
                # program added each chunk's offset), so base 0
                committed = r.host_set.insert_compact(
                    out["hi"],
                    out["lo"],
                    np.ascontiguousarray(out["rows"], np.uint32),
                    np.ascontiguousarray(out["parent"], np.int32),
                    0,
                    np.ascontiguousarray(out["act"], np.int32),
                    r.a_rows[r.a_w:],
                    r.a_parent[r.a_w:],
                    r.a_act[r.a_w:],
                )
                if r.chain is not None and committed:
                    r.chain.fold_digest(
                        *_integ.digest_rows(
                            r.a_rows[r.a_w: r.a_w + committed],
                            r.spec.exact64,
                        )
                    )
                r.a_w += committed
            else:  # tiered disk store, or no native toolchain
                fps_u64 = u64(out["hi"], out["lo"])
                # the disk tier's level-batched form probes every
                # spilled run ONCE for the whole (sorted) level
                # batch; plain FpSets take the ordinary batch insert
                mask = (
                    r.host_set.insert_level(fps_u64)
                    if hasattr(r.host_set, "insert_level")
                    else r.host_set.insert(fps_u64)
                ).astype(bool)
                rows = out["rows"][mask]
                par = out["parent"].astype(np.int64)[mask]
                acts = out["act"][mask]
                if r.disk is not None:
                    t_st = time.perf_counter()
                    r.disk.append(rows, par, acts)
                    r.lvl.store_s += time.perf_counter() - t_st
                else:
                    r.lvl.rows.append(rows)
                    r.lvl.parent.append(par)
                    r.lvl.act.append(acts)
                committed = int(mask.sum())
                if r.chain is not None:
                    r.chain.fold(fps_u64[mask])
            r.lvl.new += committed
        probe_s = time.perf_counter() - t_probe
        r.lvl.probe_ms += probe_s * 1e3
        r.obs.chunk_span(
            "host-probe", t_probe_wall, depth=r.depth, rows=nn,
            new=committed, backend=r.visited_backend,
            batched="level",
        )
    elif nn:
        r.lvl.rows.append(out["rows"])
        r.lvl.parent.append(out["parent"])
        r.lvl.act.append(out["act"])
        r.lvl.new += nn
        if r.chain is not None:
            r.chain.fold_digest(*out["digest"])
    host_s = time.perf_counter() - t_host
    r.lvl.host_s += host_s
    r.obs.chunk_span(
        "host-assembly", t_host_wall, depth=r.depth, start=0, new=nn,
        backend=r.visited_backend,
    )
    if r.collect_stats:
        r.lvl.act_en += act_en_np
    return False


# --- the level phase


def run_levels(r: Run) -> None:
    """The level loop under the typed-error ladder: it leaves a breach in
    ``r.exhausted`` / ``r.integrity_fail`` for ``close_run`` to re-raise."""
    # storage read-side corruption (read-verified CRCs on spill runs /
    # frontier segments / parent-log levels) surfaces as these typed
    # exceptions mid-run — all integrity violations, exit 76
    from ..storage.frontier import SegmentCorrupt
    from ..storage.parent_log import ParentLogCorrupt
    from ..storage.runs import RunCorrupt

    try:
        while _f_rows(r.frontier_np) > 0 and level(r):
            pass
        # drain the async tail INSIDE the typed-error scope: a pending
        # checkpoint's ENOSPC or a background merge's injected fault must
        # map to the same typed exits as their synchronous twins
        ckpt_poll(r, block=True)
        if r.disk is not None:
            r.disk.quiesce()
    except ResourceExhausted as e:
        r.exhausted = e
    except IntegrityError as e:
        r.integrity_fail = e
    except (RunCorrupt, SegmentCorrupt, ParentLogCorrupt) as e:
        # read-side storage checksum failure: silent on-disk corruption
        # caught at consumption time — typed exactly like every other
        # integrity violation
        r.integrity_fail = IntegrityError("storage", str(e), depth=r.depth)
    except OSError as e:
        if not is_disk_full(e):
            raise
        # a real ENOSPC from a storage/checkpoint writer outside the
        # injected paths: same typed clean exit (every writer cleans
        # up its tmp on failure, so the promoted state is intact)
        r.exhausted = ResourceExhausted("enospc", str(e), depth=r.depth)


def level(r: Run) -> bool:
    """One iteration of the level loop: expand level ``r.depth``'s
    frontier into the next.  False where the loop ends here: a depth or
    state bound, or a verdict (``r.violation``)."""
    lvl_io0, lvl_sync_io0 = _boundary(r)
    if r.max_depth is not None and r.depth >= r.max_depth:
        return False
    if r.max_states is not None and r.total >= r.max_states:
        return False
    f_total = _f_rows(r.frontier_np)
    t_level = time.perf_counter()
    # begin marker (ph=B): a crash mid-level leaves it unmatched, which
    # is exactly what `cli report` uses to pin where the run died
    r.obs.level_begin(r.depth + 1, f_total)
    r.governor.level_begin(r.depth + 1)  # arm the per-level deadline
    # A frontier larger than `chunk` is streamed through the same
    # compiled step in chunk_size pieces: cross-chunk duplicates are
    # caught because each chunk probes the visited set updated by the
    # previous one.  This bounds both the number of compiled shapes
    # (O(log chunk) buckets, ever) and peak device memory (O(chunk*C)).
    r.lvl = Level(len(r.model.actions), r.n_work)
    r.verdict = None  # (kind, global_frontier_idx, inv_name)
    # Host-native backend: assemble the next level in a preallocated
    # arena via the fused C pass (native.FpSet.insert_compact) — one
    # cache-friendly sweep per chunk instead of u64 packing + novelty
    # mask + masked gathers + per-level concatenate.  Growth copies
    # only the filled prefix (amortized O(level)).
    if r.disk is not None:
        r.disk.begin_level(r.depth + 1)
    r.use_arena = r.host_set is not None and r.host_set.native
    if r.use_arena:
        r.a_cap = max(1 << 14, int(1.5 * f_total))
        r.a_rows = np.empty((r.a_cap, r.K), np.uint32)
        r.a_parent = np.empty(r.a_cap, np.int64)
        r.a_act = np.empty(r.a_cap, np.int32)
        r.a_w = 0
    tail_chunks, dev_handled = _device_span(r, f_total)
    _chunk_loop(r, tail_chunks, dev_handled)
    if r.verdict is not None:
        _cut_level(r, f_total, t_level)
        return False
    _end_level(r, f_total, t_level, lvl_io0, lvl_sync_io0)
    return True


def _boundary(r: Run):
    """The level boundary: the async join point, then the fault
    injection points and the frontier's chain verify -> the workers'
    counters and the synchronous checkpoint seconds after the join (what
    the level's overlap accounting starts from)."""
    # async join point: adopt finished background merges and
    # promoted checkpoints, surfacing any worker error (typed
    # faults, ENOSPC) on this thread before more work builds on
    # un-validated state.  With an armed fault plan the join is
    # BLOCKING: deterministic injection (crash deferral, flip
    # gating, enospc surfacing) must not depend on writer-thread
    # timing — fault rehearsals trade the overlap win for
    # reproducibility at level boundaries
    ckpt_poll(r, block=bool(r.fault.specs))
    if r.disk is not None:
        if r.fault.specs:
            r.disk.quiesce()
        r.disk.poll_async()
    lvl_io0 = worker_counters((r.io_worker, r.ckpt_worker))
    lvl_sync_io0 = r.sync_io_s
    # level-boundary fault injection point (resilience.faults);
    # crash deferral keys on the DURABLE checkpoint depth, so an
    # in-flight async save can never arm a crash whose restart
    # would not converge
    r.fault.crash("level", r.depth, ckpt_depth=r.ckpt_durable_depth)
    if r.chain is not None:
        sp = r.fault.flip(
            "frontier", r.depth, ckpt_depth=r.ckpt_durable_depth
        )
        if isinstance(r.frontier_np, np.ndarray):
            if sp:
                _integ.flip_bit(r.frontier_np)
            # the frontier about to be expanded must digest to
            # the entry sealed when its level was discovered — a
            # bit flipped in the buffer between levels (or a
            # frontier loaded from a CRC-consistent corrupted
            # checkpoint) is caught HERE, before it poisons
            # successors
            # (under SYMMETRY the chain holds orbit keys, which
            # the host cannot recompute from rows: the sealed
            # counts still chain, the rows are not re-read)
            if not r.symmetric:
                _integ.count_check()
                sp_ = r.obs.open_span(
                    "frontier-verify", rows=r.frontier_np.shape[0],
                    lanes=r.K, native=_integ.native_twin(r.spec.exact64),
                )
                try:
                    r.chain.verify_level(
                        r.depth,
                        _integ.digest_rows(r.frontier_np, r.spec.exact64),
                    )
                finally:
                    sp_.finish()
        elif sp and r.frontier_np.paths():
            # disk-spilled frontier: the flip lands in a segment
            # FILE (there is no long-lived host buffer to flip);
            # the read-side segment CRC catches it at the first
            # chunk read of this level
            from ..resilience.faults import corrupt_file

            r.frontier_np._read_verified.clear()
            corrupt_file(r.frontier_np.paths()[0])
    return lvl_io0, lvl_sync_io0


def _device_span(r: Run, f_total: int):
    """Device-resident level path (DevicePipeline, engine/pipeline.py):
    ONE dispatched while_loop program runs every gated chunk of this
    level (expansion, in-jit compaction, fingerprints, dedup, verdicts
    and digest folds on-device, the visited merge once per level: <= 2
    successor launches per LEVEL) -> (the chunks left to the per-chunk
    loop, the rows the program handled).  A sub-gate tail chunk (only
    ever the last, partial one) falls through to the per-chunk loop at
    its serial offset, preserving the legacy full-lattice candidate order
    below the gate (bit-identity).  A verdict inside the device span,
    like the serial break, leaves the tail undispatched."""
    dev_handled = 0
    dev_plan = (
        r.pipe.plan_level(f_total, r.chunk, r.min_bucket)
        if getattr(r.pipe, "name", "") == "device"
        else None
    )
    if dev_plan is not None:
        r.governor.poll(r.depth)
        # disk tier: the spilled frontier's handled prefix is
        # materialized for the device span — it must be staged
        # into the device buffer anyway, so this is one host
        # copy of what the per-chunk loop would read piecewise.
        # A level too large to materialize degrades to the
        # per-chunk ladder, which streams chunks from disk —
        # the same sticky-fallback contract as a compile
        # failure, never a crashed run.  Two layers: a PRE-SIZE
        # gate (Linux overcommit means a doomed allocation can
        # OOM-kill the process during the copy rather than
        # raise, so waiting for MemoryError is not enough) and
        # the MemoryError catch for allocators that do raise.
        mat_bytes = f_total * r.K * 4
        mat_budget = int(os.environ.get(
            "KSPEC_DEVLEVEL_MAT_BUDGET", str(1 << 31)
        ))
        if (not isinstance(r.frontier_np, np.ndarray)
                and mat_bytes > mat_budget):
            r.pipe._mark_fallback(
                f"spilled frontier too large to materialize "
                f"for the device span ({mat_bytes} B > "
                f"KSPEC_DEVLEVEL_MAT_BUDGET {mat_budget} B)",
                r.depth,
            )
            dev_plan = None
        else:
            try:
                dev_rows = (
                    r.frontier_np
                    if isinstance(r.frontier_np, np.ndarray)
                    else _f_all(r.frontier_np)
                )
            except MemoryError as e:
                r.pipe._mark_fallback(
                    f"frontier materialization failed "
                    f"({f_total} rows): {e}"[:200],
                    r.depth,
                )
                dev_plan = None
    if dev_plan is not None:
        t_attempt = time.perf_counter()
        t_dispatch = _now()
        dres = r.pipe.run_level(
            dev_rows, f_total, r.depth, r.vhi, r.vlo, r.vn, r.vcap,
            dev_plan,
        )
        if dres is not None:
            r.vhi, r.vlo, r.vn, r.vcap, dev_fin = dres
            dispatch_s = time.perf_counter() - t_attempt
            dev_handled = dev_plan[2]
            if commit_device_level(r, dev_fin, dispatch_s,
                                   t_dispatch, dev_plan):
                dev_handled = f_total  # verdict: skip the tail
    # Tail iteration after a device-resident span: a fully-
    # handled level skips it entirely, and a disk-tier tail
    # slices the ALREADY-materialized rows at the same serial
    # chunk boundaries (dev_handled is a chunk multiple by
    # plan) — the spilled frontier's iter_chunks performs real
    # segment reads even for skipped chunks, so neither case
    # may re-read the device-handled prefix from disk.
    if dev_handled >= f_total:
        tail_chunks = ()
    elif dev_handled and not isinstance(r.frontier_np, np.ndarray):
        tail_chunks = (
            (s, dev_rows[s: s + r.chunk])
            for s in range(dev_handled, f_total, r.chunk)
        )
    else:
        tail_chunks = _f_chunks(r.frontier_np, r.chunk)
    return tail_chunks, dev_handled


def _chunk_loop(r: Run, tail_chunks, dev_handled: int) -> None:
    """Staged chunk pipeline (KSPEC_OVERLAP, docs/engine.md § Async
    execution) over the chunks the device span left: each chunk's device
    programs are DISPATCHED first (pipe.run_chunk_staged: JAX async
    dispatch leaves the update-skeleton launch draining), and the
    PREVIOUS chunk's host commit (fingerprint-set insert, arena assembly,
    digest folds) runs while it drains; in a level of fused chunks the
    NEXT chunk's guard launch goes out before the launch and its host
    compaction runs behind it too.  At most two chunks are ever staged
    (the one committing + the one dispatched) plus one of which only the
    guard stage has run; commits happen strictly in chunk order, so
    counts, novelty decisions, first-violation and traces are
    bit-identical to the serial path, which is this same code with
    overlap_on False (dispatch, then an immediate commit, nothing
    ahead)."""
    staged = None
    chunks_it = (c for c in tail_chunks if c[0] >= dev_handled)
    nxt = next(chunks_it, None)
    ahead = None  # the NEXT chunk's guard stage, where it ran
    while nxt is not None:
        (start, piece), nxt = nxt, next(chunks_it, None)
        r.governor.poll(r.depth)  # deadline watchdog (cheap)
        fp_n = piece.shape[0]
        bucket = _next_pow2(max(fp_n, r.min_bucket))
        M = bucket * r.C
        mine, ahead = ahead, None
        from_ahead = mine is not None
        waited = None
        set_n = 0  # the sorted visited set's length at the dispatch
        if r.visited_backend == "device":
            if staged is not None:
                # the loop's one blocking wait on a successor
                # program: the staged chunk's counts, verdict
                # flags and `new_n`, its rows' slices enqueued
                # before this chunk's successor launch is
                waited = commit_wait(r, staged)
                if r.verdict is not None:
                    # the level stops HERE, before anything more
                    # is queued: of this chunk only the guard
                    # stage has run, where it ran ahead (its
                    # launch read and closed, nothing in flight)
                    commit_chunk(r, staged, waited)
                    staged = None
                    if mine is not None:
                        mine.drop()
                        r.lvl.discarded = 1
                    break
            set_n = int(r.io.fetch(r.vn))
            need = set_n + M
            if need > r.vcap:
                # one shared growth policy with the device level
                # path (pipeline.grow_visited); growth is
                # monotonic, so the outgrown capacity's compiled
                # steps are evicted immediately here
                r.vhi, r.vlo, r.vcap = _grow_visited(
                    r.vhi, r.vlo, r.vcap, need,
                    cache=r.step_builder._cache,
                )
        elif r.ht_hi is not None and 2 * r.hash_n > r.ht_hi.shape[0]:
            # keep load factor under ~1/2 so linear probing stays short
            r.ht_hi, r.ht_lo = hashset.rehash_into(
                r.ht_hi, r.ht_lo, 2 * r.ht_hi.shape[0]
            )
            r.ht_claim = None
        # One chunk through the level-pipeline: expand -> squeeze ->
        # fingerprint (+ the device backend's in-jit dedup), with
        # overflow retries / escalation / failure degradation owned
        # by the pipeline implementation (engine/pipeline.py).  The
        # outputs are COMMITTED — exact regardless of which
        # implementation or retry path produced them.
        shadow = r.shadow_rate > 0 and _integ.sample_chunk(
            r.depth, start, r.shadow_rate
        )
        # pre-chunk visited refs: the shadow legacy cross-exec
        # replays the chunk from the same starting state (jax
        # arrays are immutable, so holding them is free)
        pre_v = (r.vhi, r.vlo, r.vn) if shadow else None
        # The guard stage of the chunk AFTER this one goes out
        # before this chunk's successor launch (it reads the
        # frontier only), where the code can see that it will
        # run fused: overlap on, a further chunk, its bucket
        # through the gate.  This chunk's own stages then run
        # first, in serial order, unless they ran ahead too.
        # `mine` / `ahead`: a pipeline.StagedGuard
        n_fp = nxt[1].shape[0] if nxt is not None else 0
        n_bucket = _next_pow2(max(n_fp, r.min_bucket))
        if (r.overlap_on and r.stager is not None and n_fp
                and r.stager._gate(n_bucket)):
            if mine is None and r.stager._gate(bucket):
                mine = r.stager.guard_stage(piece, fp_n, bucket, r.depth)
                r.stager.compact_stage(mine)
            ahead = r.stager.guard_stage(nxt[1], n_fp, n_bucket, r.depth)
            r.overlap_ahead_peak = 1
        t_attempt = time.perf_counter()
        if mine is None:
            t_dispatch, dispatch_s = _now(), 0.0
            r.vhi, r.vlo, r.vn, finalize = r.pipe.run_chunk_staged(
                piece, fp_n, bucket, r.depth, r.vhi, r.vlo, r.vn, r.vcap
            )
        else:
            # the `step` span runs from the chunk's first stage
            t_dispatch, dispatch_s = mine.t0, mine.host_s
            r.vhi, r.vlo, r.vn, finalize = r.stager.run_chunk_staged(
                piece, fp_n, bucket, r.depth, r.vhi, r.vlo, r.vn, r.vcap,
                ahead=mine,
            )
        cur = (
            start, fp_n, bucket, finalize, pre_v, shadow,
            dispatch_s + time.perf_counter() - t_attempt,
            time.perf_counter(), piece, r.vcap, t_dispatch,
            # the committed attempt's guard launch went out
            # before the previous chunk's successor launch
            int(from_ahead and getattr(finalize, "ahead", False)),
            set_n,
        )
        if r.overlap_on:
            r.overlap_staged_peak = max(
                r.overlap_staged_peak, 2 if staged is not None else 1
            )
            if staged is not None and commit_chunk(r, staged, waited):
                # a verdict in chunk k: the just-dispatched chunk
                # k+1 is DISCARDED uncommitted — exactly what the
                # serial path's break does (its device work is
                # pure and side-effect-free until commit); its
                # open launch is closed as discarded, so the
                # level's counters hold it (a legacy chunk has
                # none: its dispatch is complete).  So is chunk
                # k+2's guard launch, where it went out ahead
                launch = getattr(finalize, "launch", None)
                if launch is not None:
                    launch.finish(discarded=True)
                staged = None
                r.lvl.discarded = 1
                if ahead is not None:
                    ahead.drop()
                    r.lvl.discarded = 2
                break
            staged = cur
            if ahead is not None:
                # the next chunk's host compaction, behind this
                # chunk's successor launch
                r.stager.compact_stage(ahead)
        else:
            if commit_chunk(r, cur):
                break
    if staged is not None and r.verdict is None:
        commit_chunk(r, staged)


def _cut_level(r: Run, f_total: int, t_level: float) -> None:
    """The level a verdict cuts: its record, then the counterexample."""
    _kind, idx, inv_name = r.verdict
    if r.disk is not None:
        r.disk.abort_level()  # partial next-level writer: discard
    if r.collect_stats:
        # the level a verdict cuts gets a completed record of
        # its own (never one of stats["levels"], whose length
        # is the number of committed levels) and its span ends
        # with cut=true; the counterexample is built after it
        enabled = int(r.lvl.act_en.sum())
        cut = r.result_stats["cut_level"] = dict(
            depth=r.depth + 1,
            frontier=f_total,
            # as a level record counts them, over the chunks whose counts
            # the host holds (docs/observability.md says which)
            enabled_candidates=enabled,
            new=r.lvl.new,
            duplicates=enabled - r.lvl.new,
            rows_committed=r.lvl.rows_in,
            chunks_committed=r.lvl.chunks,
            chunks_discarded=r.lvl.discarded,
            chunks=r.lvl.chunks + r.lvl.discarded,
            # of the committed ones (`chunks_committed`)
            chunks_ahead=r.lvl.ahead,
            dedup_lanes=r.lvl.lanes,
            guard_lanes=r.lvl.guard,
            probes=r.lvl.probes,
            probes_windowed=r.lvl.probes_windowed,
            level_ms=round((time.perf_counter() - t_level) * 1e3, 1),
            step_ms=round(r.lvl.step_s * 1e3, 1),
            host_ms=round(r.lvl.host_s * 1e3, 1),
            successor_launches=r.lvl.launches,
            **work_record(r.lvl.work),
            **r.io.take(),
        )
        r.obs.level_cut(cut)
    r.violation = violation_at(r, inv_name, idx)


def _end_level(r: Run, f_total: int, t_level: float, lvl_io0,
               lvl_sync_io0: float) -> None:
    """A committed level: the next frontier, the trace store, the level's
    record, the checkpoint cadence, the governor's hooks."""
    new_n = r.lvl.new
    # the next frontier (every run needs it) ...
    if r.use_arena:
        next_frontier = r.a_rows[:r.a_w]
    elif r.disk is None:
        next_frontier = (
            np.concatenate(r.lvl.rows)
            if r.lvl.rows
            else np.empty((0, r.K), np.uint32)
        )
    level_parent = level_act = None
    # ... and what only the trace store and the parent log need:
    # parents and action ids, the retained copy, the published
    # disk level.  One `store` span a level; `store_ms` adds the
    # parent-log appends the commits made (lvl_store_s)
    if r.store_trace or r.collect_levels is not None or r.disk is not None:
        st_span = r.obs.open_span("store", depth=r.depth + 1)
        t_st = time.perf_counter()
        if r.use_arena:
            level_parent = r.a_parent[:r.a_w]
            level_act = r.a_act[:r.a_w]
            if r.a_w < int(0.95 * r.a_cap):
                # retained levels: shrink-copy so the trace store
                # doesn't hold the arena's growth headroom for the
                # whole run
                next_frontier = next_frontier.copy()
                level_parent = level_parent.copy()
                level_act = level_act.copy()
        elif r.disk is not None:
            # publish the level: segments + parent-log frame become
            # the pending frontier; the consumed level's segments go
            # behind the checkpoint-generation deletion barrier
            # (the trace lives in the log)
            next_frontier = r.disk.end_level()
        else:
            level_parent = (
                np.concatenate(r.lvl.parent)
                if r.lvl.parent
                else np.empty(0, np.int64)
            )
            level_act = (
                np.concatenate(r.lvl.act)
                if r.lvl.act
                else np.empty(0, np.int64)
            )
        if r.store_trace:
            r.trace_store.append(
                (next_frontier, level_parent, level_act)
            )
        r.lvl.store_s += time.perf_counter() - t_st
        st_span.finish(
            rows=new_n,
            bytes=new_n * 4 * r.K + sum(
                a.nbytes for a in (level_parent, level_act)
                if a is not None
            ),
        )
    r.depth += 1
    if new_n:
        r.levels.append(new_n)
        r.total += new_n
    if r.chain is not None:
        if new_n:
            # seal the level: the folded multiset digest becomes
            # the chain entry (count disagreement raises typed)
            r.chain.seal(r.depth, new_n)
        else:
            r.chain.reset_fold()
    if r.collect_stats:
        enabled_total = int(r.lvl.act_en.sum())
        # heartbeat-enveloped (kind/ts/unix): the per-level stats
        # stream doubles as the supervisor's liveness signal.  The obs
        # shim emits the historical record shape (and, with a run
        # context, additionally stamps run_id, closes the level span,
        # and folds the metrics registry + Prometheus export)
        rec = r.obs.level(
            depth=r.depth,
            frontier=f_total,
            enabled_candidates=enabled_total,
            new=new_n,
            duplicates=enabled_total - new_n,
            total=r.total,
            level_ms=round((time.perf_counter() - t_level) * 1e3, 1),
            step_ms=round(r.lvl.step_s * 1e3, 1),
            host_ms=round(r.lvl.host_s * 1e3, 1),
            action_enablement={
                a.name: int(c)
                for a, c in zip(r.model.actions, r.lvl.act_en.tolist())
            },
        )
        # launch accounting rides only the in-memory result (and
        # the per-chunk step spans): the emitted stats stream is
        # a pinned record-for-record historical contract
        # (tests/test_obs.py shim equivalence)
        r.result_stats.setdefault("levels", []).append(
            {
                **rec,
                "successor_launches": r.lvl.launches,
                "launches_per_chunk_max": r.lvl.launches_max,
                # chunks the level streamed (a whole-level
                # program: the chunks it ran)
                "chunks": r.lvl.chunks,
                # those whose guard launch went out before the
                # chunk before them had its successor launch
                "chunks_ahead": r.lvl.ahead,
                # the lanes their dedup sides were handed
                "dedup_lanes": r.lvl.lanes,
                # the lanes their guard sides evaluated: padded rows
                # x static fanout (rows handed where no width is
                # padded: `frontier` x `fanout`)
                "guard_lanes": r.lvl.guard,
                # the sorted-set probes they ran, and those of them that
                # searched the window of a capacity above it
                # (`dedup.PROBE_WINDOW`)
                "probes": r.lvl.probes,
                "probes_windowed": r.lvl.probes_windowed,
                **work_record(r.lvl.work),
                # what the host launched, moved and stored this
                # level (engine/hostio.py; docs/observability.md)
                **r.io.take(),
                "store_ms": round(r.lvl.store_s * 1e3, 3),
                # deferred batched host-probe attribution (the
                # host-backend device path): in-memory records
                # + the gauge/span side channels only — the
                # emitted stats stream stays record-for-record
                # historical (PR 7/10/13 precedent)
                **(
                    {"host_probe_ms": round(r.lvl.probe_ms, 2)}
                    if r.lvl.probe_ms
                    else {}
                ),
            }
        )
        # launches/level gauge (obs): the device pipeline's
        # acceptance signal — <=2 steady-state on the
        # device-resident path, O(chunks)x2 on fused
        _met.set_gauge(
            "kspec_successor_launches_level", r.lvl.launches
        )
        if r.lvl.probe_ms:
            # probe-ms/level gauge: the deferred-probe beat
            # `cli report` renders next to launches/level
            _met.set_gauge(
                "kspec_host_probe_ms", round(r.lvl.probe_ms, 2)
            )
    if r.collect_levels is not None and new_n:
        r.collect_levels.append(_f_all(next_frontier))
    if r.progress:
        r.progress(r.depth, new_n, r.total)

    r.frontier_np = next_frontier
    if r.ckpt_store is not None and r.depth % r.checkpoint_every == 0:
        save_checkpoint(r)
        r.last_ckpt_depth = r.depth
    # level-boundary resource governance: pressure gauges, injected
    # stall, soft-breach reclamation, hard-breach typed clean exit
    r.governor.level_end(r.depth, reclaim=partial(reclaim, r),
                         save_hook=partial(final_save, r))
    # per-level overlap accounting (obs: `kspec_overlap_efficiency`
    # is how machine-readable "storage I/O fully hidden" is —
    # ROADMAP item 2's acceptance): hidden = worker-busy wall not
    # re-exposed as caller blocking; exposed = blocking waits on
    # workers + synchronous checkpoint writes.  Attached to the
    # IN-MEMORY level records only (the emitted stats stream is a
    # pinned historical contract, like the launch counters)
    if r.collect_stats and r.result_stats.get("levels"):
        busy1, blk1 = worker_counters((r.io_worker, r.ckpt_worker))
        hid = max(
            0.0, (busy1 - lvl_io0[0]) - (blk1 - lvl_io0[1])
        )
        exp = (blk1 - lvl_io0[1]) + (r.sync_io_s - lvl_sync_io0)
        eff = hid / (hid + exp) if (hid + exp) > 1e-9 else 1.0
        rec_mem = r.result_stats["levels"][-1]
        rec_mem["io_hidden_ms"] = round(hid * 1e3, 2)
        rec_mem["io_exposed_ms"] = round(exp * 1e3, 2)
        rec_mem["overlap_efficiency"] = round(eff, 4)
        _met.set_gauge("kspec_overlap_efficiency", round(eff, 4))
        _met.inc("kspec_io_hidden_ms_total", round(hid * 1e3, 2))
        _met.inc("kspec_io_exposed_ms_total", round(exp * 1e3, 2))
