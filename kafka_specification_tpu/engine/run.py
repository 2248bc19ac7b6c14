"""The state of one `check()` call and three of its five phases.

``engine.bfs.check`` builds a :class:`Run` and calls, in order, ``open_run``
(argument validation, backend and store resolution, the initial states and
their invariant pass, checkpoint load or seed, the pipeline),
``level.run_levels`` (``engine/level.py``: a ``level`` an iteration, which
commits its chunks and, at its end, calls the checkpoint phase here:
``save_checkpoint``, ``ckpt_poll`` / ``ckpt_reap``, ``final_save``,
``reclaim``, ``spill_ref_errors``) and ``close_run`` (the typed terminals,
the cut frontier's invariant pass, the stats block, the result).  What two
phases share is a field of the run object (docs/engine.md has the table).
"""

from __future__ import annotations

import json
import os
import time
import weakref
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.observer import RunObserver
from ..ops import dedup, hashset
from ..resilience import integrity as _integ
from ..resilience.checkpoints import CheckpointStore
from ..resilience.faults import FaultPlan
from ..resilience.resources import ResourceGovernor
from ..resilience.retry import ChunkRetryHandler
from ..utils.platform_guard import device_stamp
from . import bfs as _bfs
from .bfs import (
    AdaptiveCompact, CheckResult, Violation, _f_all, _f_row, _f_rows,
    _next_pow2, _Step, build_violation, chain_stamp, decode_packed,
    init_violation_result, readback_chain, sentinel_set, u64,
)
from .hostio import HostIO
from .pipeline import make_pipeline, resolve_pipeline, work_width


class Level:
    """One level's counters: the level loop makes a new one a level, the
    commits add to it, the level's record reads it."""

    def __init__(self, n_actions: int, n_work: int):
        # the next level's rows, parents and action ids, a chunk at a time
        # (where no arena assembles them)
        self.rows, self.parent, self.act = [], [], []
        self.new = 0
        self.act_en = np.zeros(n_actions, np.int64)
        # successor-kernel launches this level, and the per-chunk maximum
        self.launches = self.launches_max = 0
        # pipeline.work_counts of the committed dispatches: the probes'
        # search rounds and the merges' touched slots, each beside what
        # the form over the whole capacity would have run
        self.work = np.zeros(n_work, np.int64)
        self.probe_ms = 0.0  # deferred batched host-probe wall
        self.store_s = 0.0  # trace store / parent log wall (`store_ms`)
        # chunks committed, their rows, those of them whose guard launch
        # went out before the chunk before them had its successor launch
        # (`chunks_ahead`), and the width their dedup sides were handed,
        # summed: the lanes every sort, probe and compaction ran
        self.chunks = self.rows_in = self.ahead = self.lanes = 0
        # the lanes their guard sides evaluated (`guard_lanes`): a chunk's
        # padded rows x the model's static fanout
        self.guard = 0
        # the sorted-set probes they ran (`probes`), and those of them
        # that searched the window of a capacity above it
        self.probes = self.probes_windowed = 0
        self.discarded = 0  # chunks dispatched and dropped at a verdict
        self.step_s = self.host_s = 0.0  # `step_ms`, `host_ms`

    def probed(self, n: int, cap: int, set_n: int) -> None:
        """`n` probes of a sorted set of `set_n` entries in `cap` slots
        (what the host held when it dispatched them: no fetch of its
        own)."""
        self.probes += n
        self.probes_windowed += n * dedup.windowed(cap, set_n)


class Run:
    """What one `check()` call holds, grouped by who writes it.  The
    call's options sit under their parameter names; ``open_run`` resolves
    ``store_trace``, ``visited_backend``, ``checkpoint_every`` and
    ``governor`` in place."""

    def __init__(self, model, t_check: float, **options):
        vars(self).update(options)
        self.model, self.spec, self.t_check = model, model.spec, t_check
        # --- context: set by open_run, read-only afterwards
        self.step_builder = self.pipe = self.stager = self.adapt = None
        self.K = self.C = self.n_work = self.chunk_floor = 0
        self.obs = self.io = self.fault = self.chunk_retry = None
        self.chain = None  # LevelDigestChain, or None: KSPEC_INTEGRITY=0
        self.shadow_rate = self.t0 = 0.0
        self.disk = self.ckpt_store = self.ephemeral_spill = None
        self.io_worker = self.ckpt_worker = None
        self.symmetric = self.use_disk = self.overlap_on = False
        self.seeded = self.collect_stats = False
        # --- visited: the sorted device set / the device hash table (its
        # claim lattice allocated LAZILY at the insert site, so a table
        # (re)build resets it to None) / the host or disk set, and the
        # streaming chunk size
        self.vhi = self.vlo = self.vn = None
        self.ht_hi = self.ht_lo = self.ht_claim = self.host_set = None
        self.vcap = self.hash_n = self.chunk = 0
        # --- frontier and arena (the host-native backend assembles the
        # next level in a preallocated arena: level.grow_arena)
        self.frontier_np = self.a_rows = self.a_parent = self.a_act = None
        self.use_arena = False
        self.a_cap = self.a_w = 0
        self.trace_store: list = []
        # --- progress
        self.levels: list = []
        self.total = self.depth = 0
        self.verdict = None  # (kind, global_frontier_idx, inv_name)
        self.violation: Optional[Violation] = None
        self.result_stats: dict = {}
        self.run_launches_max = 0  # per-chunk max actually DISPATCHED
        # most chunks ever staged at once (<= 2), and most beside them
        # with only a guard stage run (<= 1)
        self.overlap_staged_peak = self.overlap_ahead_peak = 0
        self.exhausted = self.integrity_fail = None  # typed terminals
        # --- one level's counters (level.level makes a new one a level)
        self.lvl: Optional[Level] = None
        # --- checkpoint bookkeeping (open_run says what each means)
        self.last_ckpt_depth = self.ckpt_durable_depth = None
        self.ckpt_barrier_tokens: list = []
        self.sync_io_s = 0.0  # wall spent on SYNChronous checkpoint writes

    def degrade_chunk(self):
        # device RESOURCE_EXHAUSTED: halve the streaming chunk size for
        # the rest of the run (ChunkRetryHandler's degradation contract)
        self.chunk = max(self.chunk_floor, self.chunk >> 1)


def shutdown_async(r: Run, drain: bool) -> None:
    from ..overlap import close_workers

    close_workers((r.io_worker, r.ckpt_worker), drain)


def _drop_ephemeral_spill(r: Run) -> None:
    if r.ephemeral_spill is not None:
        import shutil

        shutil.rmtree(r.ephemeral_spill, ignore_errors=True)


def first_violation(r: Run, rows: np.ndarray):
    """The invariant pass over host-held rows (the initial states; the
    frontier a cut left unexpanded): launches of a cached program per
    power-of-two row bucket, a chunk of the level loop's size at most a
    launch -> (invariant, row index) or None."""
    sp_ = r.obs.open_span("host-invariants", rows=rows.shape[0])
    bad = r.step_builder.first_violation(
        ("hinv",),
        _next_pow2(max(min(rows.shape[0], r.chunk_size), r.min_bucket)),
        rows, r.io, r.obs,
    )
    sp_.finish()
    return bad


def violation_at(r: Run, inv_name: str, idx: int) -> Violation:
    """The Violation of frontier row `idx` (level ``r.depth``): with its
    trace where a store or the parent log holds the level (O(depth)
    single-record reads through the log's mmap'd segments: what makes
    traces survive checkpoint/resume), else the state alone."""
    on_disk = r.disk is not None and r.disk.has_trace(r.depth)
    if r.store_trace or on_disk:
        return build_violation(
            r.model, r.trace_store if r.store_trace else None,
            r.disk.plog.view() if on_disk else None,
            inv_name, r.depth, idx, obs=r.obs,
        )
    return Violation(
        invariant=inv_name,
        depth=r.depth,
        state=decode_packed(r.model, _f_row(r.frontier_np, idx)),
        trace=[],
    )


def open_run(r: Run) -> Optional[CheckResult]:
    """The open phase.  Returns a result only where an initial state
    breaks an invariant (the run is then closed already)."""
    # encoding-soundness gate (analysis; KSPEC_ANALYZE=0 disables): an
    # action that can write outside its declared field ranges would be
    # silently truncated by the bit packer — refuse to explore instead
    # of returning a wrong verdict (memoized per model name)
    from ..analysis import require_encoding_sound

    require_encoding_sound(r.model)
    if r.prepared is not None and r.prepared.model is not r.model:
        raise ValueError("prepared kernels wrap a different model object")
    # TLC's SYMMETRY (Model.symmetry): a state's key is its orbit's
    # (pipeline.fp_stage), which no host twin recomputes from a stored row
    # (resilience/integrity.fingerprint_rows is the PLAIN fingerprint), so
    # whatever validates rows against keys is refused, by name, rather
    # than run on keys it cannot check
    r.symmetric = r.model.symmetry is not None
    if r.symmetric:
        for what, given in (
            ("checkpoint_dir", r.checkpoint_dir is not None),
            ("seed", r.seed is not None),
            ("integrity_shadow", bool(r.integrity_shadow)),
        ):
            if given:
                raise ValueError(
                    f"{r.model.name}: {what}= is not supported under SYMMETRY "
                    f"{r.model.symmetry.operator} (a stored row's key is its "
                    "orbit's, and resilience/integrity.fingerprint_rows, "
                    "which validates a checkpoint, a seed and a shadowed "
                    "chunk, recomputes the plain fingerprint); drop the "
                    "SYMMETRY stanza or the option"
                )
    r.step_builder = (
        r.prepared.step if r.prepared is not None else _Step(r.model))
    r.K, r.C = r.spec.num_lanes, r.step_builder.C

    # unified telemetry: run_id-stamped stats/spans/metrics when a run
    # context is given; the bare stats_path stream otherwise (root span
    # `check`; `check-open` until the first level, `check-close` after)
    r.obs = RunObserver(r.run, r.stats_path, engine="bfs",
                       annotate=jax.profiler.TraceAnnotation)
    r.obs.check_begin(r.t_check, model=r.model.name)
    r.obs.shape(r.model, r.C, r.K)
    r.io = HostIO(r.obs)  # counted transfers + named dispatches

    from ..storage import resolve_store

    r.use_disk = resolve_store(r.store, r.mem_budget)
    want_trace = r.store_trace
    if r.use_disk:
        # the disk tier spills the HOST level of the hierarchy; traces
        # ride the on-disk parent log instead of the in-RAM trace store
        r.visited_backend = "host"
        r.store_trace = False

    r.fault = FaultPlan.from_env()
    r.chunk_retry = ChunkRetryHandler.from_env("[engine]")
    # async overlap layer (overlap.py; $KSPEC_OVERLAP, default on):
    # io_worker carries background spill-run merges, ckpt_worker the
    # async checkpoint writes; the two-slot chunk pipeline below needs
    # no thread (JAX async dispatch is the worker)
    from ..overlap import AsyncWorker, overlap_enabled

    r.overlap_on = overlap_enabled(r.overlap)
    r.io_worker = AsyncWorker("kspec-io") if r.overlap_on else None
    r.ckpt_worker = (
        AsyncWorker("kspec-ckpt")
        if r.overlap_on and r.checkpoint_dir is not None
        else None
    )
    # state-integrity defense (resilience.integrity): always-on level
    # digest chain + sampled shadow re-execution; KSPEC_INTEGRITY=0 is
    # the kill switch (bench baselines, emergency escape hatch)
    r.chain = _integ.LevelDigestChain() if _integ.enabled() else None
    r.shadow_rate = (
        _integ.shadow_rate(r.integrity_shadow)
        if r.chain is not None and not r.symmetric  # (an env-set rate too)
        else 0.0
    )
    # last_ckpt_depth: newest durably checkpointed level (None = not
    # checkpointing): level-crash faults defer until the target level is
    # checkpointed so a supervised restart converges (FaultPlan.crash)
    if r.checkpoint_dir is not None:
        r.store_trace = False
        r.last_ckpt_depth = 0
        r.checkpoint_every = max(1, int(r.checkpoint_every))
    if r.seed is not None:
        if r.checkpoint_dir is not None:
            raise ValueError(
                "seed= and checkpoint_dir are mutually exclusive (a seed "
                "IS a resume; layering the two would race their chains)"
            )
        if r.use_disk:
            raise ValueError("seed= requires the in-RAM store")
        # same limitation as checkpoint resume: parent pointers below the
        # seed do not exist, so traces cannot be reconstructed
        r.store_trace = False

    r.t0 = time.perf_counter()
    sp_ = r.obs.open_span("init-states")
    init_packed, hi0, lo0 = r.step_builder.init_rows(r.io, r.obs)
    sp_.finish()
    n0 = init_packed.shape[0]

    _open_visited(r, hi0, lo0, n0, want_trace)

    r.levels = [n0]
    r.total = n0
    # per level: (packed[np], parent[np], act[np]); aliased to the
    # caller's list when collect_trace is given (service/batch.py)
    r.trace_store = r.collect_trace if r.collect_trace is not None else []
    r.trace_store.clear()
    if r.store_trace:
        r.trace_store.append((init_packed, np.full(n0, -1), np.full(n0, -1)))
    if r.collect_levels is not None:
        r.collect_levels.append(init_packed)

    # invariants on init states
    if r.check_invariants and r.model.invariants:
        bad0 = first_violation(r, init_packed)
        if bad0 is not None:
            inv, idx = bad0
            res = init_violation_result(
                r.model, inv, init_packed[idx], r.levels, r.total,
                time.perf_counter() - r.t0,
            )
            _drop_ephemeral_spill(r)
            shutdown_async(r, True)
            r.obs.finish(res)
            r.obs.close()
            return res

    r.frontier_np = init_packed
    if r.symmetric:
        r.result_stats["symmetry"] = r.model.symmetry.describe()
    # the work counts behind the enabled counts of a program's vector
    r.n_work = work_width(r.model, r.visited_backend)
    r.collect_stats = r.obs.collect
    r.obs.config(
        model=r.model.name,
        visited_backend=r.visited_backend,
        store="disk" if r.use_disk else "ram",
        mem_budget=r.mem_budget,
        chunk_size=r.chunk_size,
        checkpoint_dir=r.checkpoint_dir,
        **device_stamp(),
    )

    # identity stamp: a checkpoint may only resume the same model, constants,
    # invariant selection, and deadlock setting (a resume never re-checks
    # already-explored levels, so a stricter check must start fresh)
    inv_names = (
        ",".join(sorted(i.name for i in r.model.invariants))
        if r.check_invariants else "-"
    )
    ckpt_ident = (
        f"{r.model.name}|lanes={r.spec.num_lanes}|"
        f"backend={r.visited_backend}|inv={inv_names}|dl={r.check_deadlock}|"
        + ",".join(f"{f.name}:{f.shape}:{f.lo}:{f.hi}" for f in r.spec.fields)
        + ("|store=disk" if r.use_disk else "")
    )
    resumed, resumed_chain_arr = (
        _load_checkpoint(r, ckpt_ident) if r.checkpoint_dir is not None
        else (False, None))
    if r.seed is not None:
        _load_seed(r)

    if r.disk is not None and not resumed:
        # fresh out-of-core run: the spill directory namespace belongs to
        # this run (stale runs must not pre-seed the visited set)
        r.disk.start_fresh(init_packed, np.asarray(u64(hi0, lo0)))
        r.frontier_np = r.disk.pending()

    if r.chain is not None:
        if r.seeded:
            # the cached chain IS the continuation proof, exactly like a
            # resumed checkpoint's: the level-boundary verify below must
            # prove the seeded frontier against its sealed entry before
            # anything is expanded
            r.chain = (
                _integ.LevelDigestChain.from_array(r.seed["digest_chain"])
                if r.seed.get("digest_chain") is not None
                else _integ.LevelDigestChain.from_levels(r.levels)
            )
        elif resumed:
            # the chain IS the continuation proof: a resumed run extends
            # the stamped chain, and the frontier verify below checks the
            # loaded frontier against its sealed entry.  Pre-integrity
            # checkpoints rebuild an unanchored chain (counts only)
            r.chain = (
                _integ.LevelDigestChain.from_array(resumed_chain_arr)
                if resumed_chain_arr is not None
                else _integ.LevelDigestChain.from_levels(r.levels)
            )
        else:
            r.chain.fold(_integ.pair_u64(hi0, lo0))
            r.chain.seal(0, n0)

    # async-checkpoint bookkeeping (KSPEC_OVERLAP): `last_ckpt_depth`
    # stays the SUBMITTED depth (save-cadence decisions), while
    # `ckpt_durable_depth` advances only when a write has atomically
    # promoted — crash-fault deferral and flip gating key on durability,
    # so a deferred crash can never fire ahead of the checkpoint that
    # makes its restart converge.  `ckpt_barrier_tokens` carries each
    # in-flight save's deletion-barrier watermark (DeferredDeleter.mark):
    # the barrier advances for exactly the files scheduled BEFORE that
    # save's snapshot, preserving the sync ordering contract.
    r.ckpt_durable_depth = r.last_ckpt_depth

    r.chunk = _next_pow2(max(r.min_bucket, r.chunk_size))
    r.chunk_floor = _next_pow2(max(32, r.min_bucket))

    # Resource governance (resilience.resources): disk/RSS budgets + the
    # per-level deadline watchdog, with soft-breach reclamation and a
    # typed checkpoint-then-clean-exit on hard breach.  A caller-supplied
    # governor (the serving daemon's per-tenant instances) takes
    # precedence over the env-derived one
    if r.governor is None:
        r.governor = ResourceGovernor.from_env(
            disk_budget=r.disk_budget,
            watch_dirs=[r.disk.dir if r.disk is not None else None,
                        r.checkpoint_dir],
            fault_plan=r.fault,
        )
    _open_pipeline(r)
    return None


def _open_visited(r: Run, hi0, lo0, n0: int,
                  want_trace: bool) -> None:
    """The visited set of the initial states, where the backend keeps it."""
    if r.visited_backend not in ("device", "host", "device-hash"):
        raise ValueError(
            "visited_backend must be 'device', 'device-hash' or 'host', "
            f"got {r.visited_backend!r}"
        )
    if r.visited_backend == "host":
        if r.use_disk:
            from ..storage import (
                DEFAULT_MEM_BUDGET,
                DiskTierStore,
                parse_mem_budget,
            )

            budget = (
                parse_mem_budget(r.mem_budget)
                if r.mem_budget is not None
                else DEFAULT_MEM_BUDGET
            )
            sd = r.spill_dir or (
                os.path.join(r.checkpoint_dir, "spill")
                if r.checkpoint_dir else None
            )
            if sd is None:
                import tempfile

                # anonymous spill space: removed after a completed run (a
                # crashed one cannot be resumed without a checkpoint, so
                # its temp data is dead weight either way)
                sd = tempfile.mkdtemp(prefix="kspec-spill-")
                r.ephemeral_spill = sd
            r.disk = DiskTierStore(
                sd,
                budget,
                lanes=r.K,
                gc_barrier=r.checkpoint_keep if r.checkpoint_dir else 0,
                seg_rows=int(
                    os.environ.get("KSPEC_SPILL_SEG_ROWS", str(1 << 18))
                ),
                runs_per_merge=int(
                    os.environ.get("KSPEC_SPILL_RUNS_PER_MERGE", "8")
                ),
                fault_plan=r.fault,
                trace=want_trace or r.checkpoint_dir is not None,
                merge_worker=r.io_worker,
            )
            # (the init fps are inserted at start_fresh / resume)
            r.host_set = r.disk.fpset
        else:
            from ..native import FpSet

            r.host_set = FpSet()
            r.host_set.insert(u64(hi0, lo0))
    elif r.visited_backend == "device-hash":
        r.ht_hi, r.ht_lo = hashset.table_from_pairs(
            np.asarray(hi0),
            np.asarray(lo0),
            min_cap=_next_pow2(
                max(
                    _bfs._HASH_MIN_CAP,
                    4 * (r.visited_capacity_hint
                         or r.visited_capacity_exact or 0),
                )
            ),
        )
        r.hash_n = n0
    if r.visited_backend != "device":
        # placeholder shapes for the step signature: the device holds no
        # sorted set
        r.vcap = 64
        r.vhi = jnp.full(r.vcap, 0xFFFFFFFF, jnp.uint32)
        r.vlo = jnp.full(r.vcap, 0xFFFFFFFF, jnp.uint32)
        r.vn = jnp.int32(0)
    else:
        order = np.lexsort((np.asarray(lo0), np.asarray(hi0)))
        chunk_clamped = _next_pow2(max(r.min_bucket, r.chunk_size))
        # hint: ~state count, padded with one chunk's worth of insert
        # headroom so the growth check never fires on a roughly-known run.
        # exact: a capacity floor (a prior run's FINAL vcap) used
        # verbatim, so warm serving runs land on the exact same capacity —
        # same step-cache keys, zero recompiles (PreparedKernels)
        r.vcap = _next_pow2(
            max(
                n0,
                r.min_bucket * r.C,
                2,
                r.visited_capacity_exact or 0,
                (r.visited_capacity_hint + chunk_clamped * r.C)
                if r.visited_capacity_hint
                else 0,
            )
        )
        # (prepared kernels keep the image across the jobs of a shape)
        image = (sentinel_set if r.prepared is None
                 else r.prepared.initial_visited)(
            r.vcap, np.asarray(hi0)[order], np.asarray(lo0)[order])
        r.vhi, r.vlo = r.io.put(image[0]), r.io.put(image[1])
        r.vn = jnp.int32(n0)


def spill_ref_errors(disk, arrays: dict) -> list:
    """Disk-tier load validator: CRC-verify every spill run and
    frontier segment a generation REFERENCES before accepting it —
    a generation whose referenced run rotted on disk (flip@spill)
    then falls back to an older one that predates the corrupt file
    (whose deterministic re-exploration rewrites it), instead of
    crashing mid-restore."""
    if disk is None or "spill_manifest" not in arrays:
        return []
    from ..storage.frontier import FrontierReader as _FR
    from ..storage.frontier import SegmentCorrupt as _SC

    man = json.loads(str(arrays["spill_manifest"]))
    errs = _integ.spill_run_errors(
        disk.fpset.dir, (man.get("fpset") or {}).get("runs", ())
    )
    try:
        _FR(disk.frontier_dir, man["frontier"], verify=True)
    except _SC as e:
        errs.append(f"referenced frontier segment corrupt: {e}")
    return errs


def _load_checkpoint(r: Run, ckpt_ident: str):
    """Open the checkpoint store and, where a generation verifies, resume
    from it -> (resumed, the stamped digest chain or None)."""
    resumed, resumed_chain_arr = False, None
    r.ckpt_store = CheckpointStore(
        r.checkpoint_dir,
        "bfs_checkpoint.npz",
        ident=ckpt_ident,
        keep=r.checkpoint_keep,
        fault_plan=r.fault,
        # chain-mismatch generations (CRC-consistent content
        # corruption) fall back exactly like checksum failures: the
        # run resumes from the newest CHAIN-VERIFIED generation
        validators=(
            (_integ.checkpoint_chain_errors,
             partial(spill_ref_errors, r.disk))
            if r.chain is not None
            else (partial(spill_ref_errors, r.disk),)
        ),
    )
    if r.ckpt_worker is not None:
        r.ckpt_store.attach_writer(r.ckpt_worker)
    loaded = r.ckpt_store.load()
    if loaded is not None:
        resumed = True
        snap, _, _gen = loaded
        if "digest_chain" in snap:
            resumed_chain_arr = snap["digest_chain"]
        if r.disk is not None:
            # the checkpoint references the disk tier, it does not
            # contain it: reopen the manifest's runs + frontier
            # segments IN PLACE (host_set aliases disk.fpset),
            # re-seed the budget-bounded hot set
            r.disk.resume(
                json.loads(str(snap["spill_manifest"])), snap["host_fps"]
            )
            r.frontier_np = r.disk.pending()
        elif r.host_set is not None:
            r.frontier_np = snap["frontier"]
            from ..native import FpSet

            r.host_set = FpSet(
                initial_capacity=max(64, 2 * len(snap["host_fps"])))
            r.host_set.insert(snap["host_fps"])
        elif r.ht_hi is not None:
            r.frontier_np = snap["frontier"]
            live_hi = snap["hash_hi"]
            live_lo = snap["hash_lo"]
            r.hash_n = live_hi.shape[0]
            r.ht_hi, r.ht_lo = hashset.table_from_pairs(
                live_hi, live_lo, min_cap=_bfs._HASH_MIN_CAP
            )
            r.ht_claim = None
        else:
            r.frontier_np = snap["frontier"]
            r.vcap = int(snap["vcap"])
            n = int(snap["vn"])
            pad = np.full(r.vcap - n, 0xFFFFFFFF, np.uint32)
            r.vhi = jnp.asarray(np.concatenate([snap["vhi"], pad]))
            r.vlo = jnp.asarray(np.concatenate([snap["vlo"], pad]))
            r.vn = jnp.int32(n)
        r.levels = snap["levels"].tolist()
        r.total = int(snap["total"])
        r.depth = int(snap["depth"])
        r.last_ckpt_depth = r.depth
        # crash faults at or below the resume level count as fired
        # (a supervised restart must converge, not crash-loop)
        r.fault.set_start_depth(r.depth)
    return resumed, resumed_chain_arr


def _load_seed(r: Run) -> None:
    """Warm start from a verified cached exploration (state_cache):
    structurally identical to the checkpoint-resume path, sourced from the
    portable artifact instead of a generation.  The visited set is
    reconstructed from the u64 fingerprint multiset (every backend's
    visited state is a pure function of it) and the boundary frontier is
    expanded next, so the level loop continues exactly where the cached
    run's bound cut it."""
    r.seeded = True
    seed_fps = np.sort(
        np.ascontiguousarray(np.asarray(r.seed["visited_fps"], np.uint64))
    )
    s_hi = (seed_fps >> np.uint64(32)).astype(np.uint32)
    s_lo = (seed_fps & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    r.frontier_np = np.ascontiguousarray(
        np.asarray(r.seed["frontier"], np.uint32)
    ).reshape(-1, r.K)
    n_seed = int(seed_fps.shape[0])
    if r.visited_backend == "host":
        from ..native import FpSet

        r.host_set = FpSet(initial_capacity=max(64, 2 * n_seed))
        r.host_set.insert(seed_fps)
    elif r.visited_backend == "device-hash":
        r.ht_hi, r.ht_lo = hashset.table_from_pairs(
            s_hi, s_lo, min_cap=_bfs._HASH_MIN_CAP
        )
        r.ht_claim = None
        r.hash_n = n_seed
    else:
        seed_chunk = _next_pow2(max(r.min_bucket, r.chunk_size))
        r.vcap = _next_pow2(
            max(
                n_seed + seed_chunk * r.C,
                r.min_bucket * r.C,
                2,
                r.visited_capacity_exact or 0,
            )
        )
        pad = np.full(r.vcap - n_seed, 0xFFFFFFFF, np.uint32)
        # u64 sort order == (hi, lo) lexsort order: the split lanes
        # land exactly as the sorted-set backend stores them
        r.vhi = jnp.asarray(np.concatenate([s_hi, pad]))
        r.vlo = jnp.asarray(np.concatenate([s_lo, pad]))
        r.vn = jnp.int32(n_seed)
    r.levels = [int(v) for v in r.seed["levels"]]
    r.total = int(r.seed["total"])
    r.depth = int(r.seed["depth"])
    # crash faults at or below the seed level count as fired, the
    # same convergence rule as a checkpoint resume
    r.fault.set_start_depth(r.depth)


def _open_pipeline(r: Run) -> None:
    """The compact-sizing policy and the level pipeline."""
    # per-action compact sizing: one policy, shared with the sharded
    # engine (AdaptiveCompact says what it is and why)
    r.adapt = AdaptiveCompact(r.model.actions, r.compact_shift,
                              bucket_gate=r.compact_gate)

    # The level-pipeline: per-chunk expand/squeeze/fingerprint (+ the
    # device backend's in-jit dedup) behind one interface — the
    # device-resident whole-level program, the fused 2-launch
    # mega-kernel path or the legacy per-action path
    # (engine/pipeline.py; all bit-identical)
    r.pipe = make_pipeline(
        resolve_pipeline(r.pipeline),
        step_builder=r.step_builder,
        model=r.model,
        adapt=r.adapt,
        chunk_retry=r.chunk_retry,
        fault=r.fault,
        check_invariants=r.check_invariants,
        visited_backend=r.visited_backend,
        # (weakly: a cycle would keep the run's device arrays past its end)
        on_degrade_chunk=lambda wr=weakref.ref(r): wr().degrade_chunk(),
        compact_shift=r.compact_shift,
        compact_gate=r.compact_gate,
        check_deadlock=r.check_deadlock,
        io=r.io,
    )
    if getattr(r.pipe, "name", "") == "device" and r.prepared is not None:
        # the warm protocol's second fixed point: the level programs'
        # first dispatches start where the last run of this prepared
        # model ended (PreparedKernels.level_high_waters)
        r.pipe.seed_high_waters(r.prepared.level_high_waters)
    if getattr(r.pipe, "name", "") == "device" and r.shadow_rate > 0 and \
            r.pipe.device_fallback is None:
        # shadow re-execution replays single chunks from their pre-chunk
        # visited state — a state the whole-level program never
        # materializes.  The documented ladder: shadowed runs take the
        # fused per-chunk path (docs/engine.md § Device-resident level
        # pipeline)
        r.pipe.device_fallback = (
            "integrity shadow re-execution needs per-chunk replay"
        )
    # the fused pipeline's two halves of a chunk, where the loop
    # may run them apart (never a whole-level `device` pipeline's
    # per-chunk tail, never `legacy`)
    r.stager = r.pipe if getattr(r.pipe, "name", "") == "fused" else None


# --- the checkpoint phase


def _durable(r: Run, d: int) -> None:
    r.ckpt_durable_depth = (
        d if r.ckpt_durable_depth is None else max(r.ckpt_durable_depth, d)
    )


def ckpt_reap(r: Run, completed) -> None:
    for d, _path in completed:
        _durable(r, d)
        if r.disk is not None:
            tok = r.ckpt_barrier_tokens.pop(0) if r.ckpt_barrier_tokens \
                else None
            r.disk.fpset.deleter.on_save(upto=tok)


def ckpt_poll(r: Run, block: bool = False) -> None:
    # join point for async saves: surfaces writer errors (typed
    # ENOSPC, injected crashes) on the engine thread and advances
    # the durable-depth + deletion-barrier bookkeeping
    if r.ckpt_worker is None or r.ckpt_store is None:
        return
    ckpt_reap(
        r, r.ckpt_store.drain_async() if block else r.ckpt_store.poll_async()
    )


def save_checkpoint(r: Run, sync: bool = False):
    # The async-checkpoint split (docs/resilience.md): everything
    # mutable is SNAPSHOTTED here, synchronously — level metadata,
    # the digest chain, the visited dump (a fresh array from every
    # backend), a copy of the frontier — and the checksummed write,
    # rotation and atomic promote run on the writer thread.  The
    # save-time chain verification moves to the writer too, still
    # BEFORE the promote (detected corruption never enters a
    # checkpoint); ENOSPC and injected faults re-raise at the next
    # ckpt_poll, preserving the typed exits.
    run_async = r.ckpt_worker is not None and not sync
    t_sync0 = time.perf_counter()
    # only the live prefix of the visited set is saved (the sentinel
    # padding is rebuilt on resume from vcap/vn); uncompressed — live
    # fingerprints are high-entropy and zlib only burns time
    n = int(r.vn)
    d_save = r.depth
    levels_arr = np.asarray(r.levels)
    # flip injections are gated on an ANCHORED chain: they rehearse
    # detection, and an unanchored chain (pre-integrity resume)
    # cannot detect — injecting there would just silently corrupt
    if r.chain is not None and r.chain.anchored and r.fault.flip(
        "ckpt", d_save, ckpt_depth=r.ckpt_durable_depth
    ):
        # CRC-consistent metadata corruption: the manifest is built
        # AFTER this flip, so every per-array checksum passes over
        # the corrupt content — only the digest chain flags it
        levels_arr = levels_arr.copy()
        _integ.flip_bit(levels_arr)

    def _dispatch(arrays: dict, pre_write=None, barrier: bool = False):
        if run_async:
            if barrier:
                r.ckpt_barrier_tokens.append(r.disk.fpset.deleter.mark())
            r.ckpt_store.save_async(
                d_save, arrays, pre_write=pre_write,
                after_promote=partial(readback_chain, r.chain, depth=d_save),
            )
            return
        if pre_write is not None:
            pre_write()
        path = r.ckpt_store.save(d_save, arrays)
        if barrier:
            # a new durable generation exists: advance the deferred-
            # deletion barrier (merged-away runs / consumed frontier
            # segments older than every retained generation unlink)
            r.disk.on_checkpoint_saved()
        readback_chain(r.chain, path, d_save)
        _durable(r, d_save)
        r.sync_io_s += time.perf_counter() - t_sync0

    if r.disk is not None:
        # the disk tier IS the durable state: record the run manifest
        # + frontier-segment offsets + the (budget-bounded) hot dump,
        # never the runs/segments themselves.  (The hot dump is a
        # SUBSET of the visited set, so the cumulative-digest
        # self-check does not apply here — the spilled runs carry
        # their own read-side-verified CRCs instead.)
        _dispatch(
            dict(
                spill_manifest=json.dumps(r.disk.manifest()),
                host_fps=r.disk.fpset.hot_dump(),
                vcap=r.vcap,
                levels=levels_arr,
                total=r.total,
                **chain_stamp(r.chain),
            ),
            barrier=True,
        )
        return
    if r.host_set is not None:
        extra = {"host_fps": r.host_set.dump()}
        pk = "host_fps"
    elif r.ht_hi is not None:
        th = np.asarray(r.ht_hi)
        tl = np.asarray(r.ht_lo)
        live = ~((th == hashset.SENT) & (tl == hashset.SENT))
        extra = {"hash_hi": th[live], "hash_lo": tl[live]}
        pk = "hash_hi"
    else:
        extra = {
            "vhi": np.asarray(r.vhi[:n]),
            "vlo": np.asarray(r.vlo[:n]),
            "vn": n,
        }
        pk = "vhi"
    pre_write = None
    if r.chain is not None and r.chain.anchored:
        if r.fault.flip("fpset", d_save, ckpt_depth=r.ckpt_durable_depth):
            corrupted = np.array(extra[pk], copy=True)
            _integ.flip_bit(corrupted)
            extra[pk] = corrupted
        if r.host_set is not None:
            dump_fps = np.asarray(extra["host_fps"], np.uint64)
        elif r.ht_hi is not None:
            dump_fps = _integ.pair_u64(extra["hash_hi"], extra["hash_lo"])
        else:
            dump_fps = _integ.pair_u64(extra["vhi"], extra["vlo"])
        # save-time self-check: the dump must digest to the chain's
        # running total BEFORE the write — corruption detected here
        # never enters a checkpoint.  Async: the chain is snapshotted
        # now (it keeps evolving on this thread) and the check runs
        # on the writer, still pre-promote.
        chain_snap = (
            _integ.LevelDigestChain.from_array(r.chain.to_array())
            if run_async
            else r.chain
        )

        def pre_write(chain_snap=chain_snap, dump_fps=dump_fps):
            _integ.count_check()
            chain_snap.verify_visited(dump_fps, depth=d_save)

    frontier_arr = r.frontier_np
    if run_async and isinstance(frontier_arr, np.ndarray):
        # the live frontier buffer stays mutable on this thread
        # (arena growth, flip injection) — the writer gets a copy
        frontier_arr = np.array(frontier_arr, copy=True)
    _dispatch(
        dict(
            frontier=frontier_arr,
            vcap=r.vcap,
            levels=levels_arr,
            total=r.total,
            **extra,
            **chain_stamp(r.chain),
        ),
        pre_write=pre_write,
    )


def final_save(r: Run):
    # checkpoint-then-clean-exit: persist the just-completed level
    # even off the checkpoint_every cadence, so the operator resumes
    # from the breach point, not checkpoint_every-1 levels earlier.
    # Synchronous + drained: the typed exit's contract is a DURABLE
    # state, so the async tail is joined first
    if r.ckpt_store is None:
        return
    ckpt_poll(r, block=True)
    if r.last_ckpt_depth != r.depth or r.ckpt_durable_depth != r.depth:
        save_checkpoint(r, sync=True)
        r.last_ckpt_depth = r.depth


def reclaim(r: Run):
    # soft-breach reclamation, in dependency order (docs/resilience.md):
    # quiesce background work -> tmp janitor -> eager run merge ->
    # fresh checkpoint (references the merged state) -> prune older
    # generations -> flush the deletion barrier (everything still
    # pending was referenced only by the generations just pruned).
    # The quiesce (inside sweep_tmp/reclaim_merge/flush_deleted and
    # the blocking ckpt poll here) is what keeps a reclaim from
    # racing a background merge promote or an in-flight checkpoint
    # write (PR 10 small fix; regression-tested)
    merged = False
    if r.disk is not None:
        r.disk.sweep_tmp()
        merged = r.disk.reclaim_merge()
    if r.ckpt_store is not None:
        ckpt_poll(r, block=True)
        # skip the save when the periodic one just ran at this depth
        # and no merge changed the on-disk state (the newest gen
        # already references everything the flush keeps) — the
        # pressure path is exactly where write bandwidth is scarcest
        if merged or r.last_ckpt_depth != r.depth or \
                r.ckpt_durable_depth != r.depth:
            save_checkpoint(r, sync=True)
            r.last_ckpt_depth = r.depth
        r.ckpt_store.prune(keep_gens=1)
        if r.disk is not None:
            r.disk.flush_deleted()


# --- the close phase


def close_run(r: Run) -> CheckResult:
    """The close phase: a typed terminal re-raised with its manifest
    stamped, else the cut frontier's invariant pass, the stats block and
    the result."""
    r.obs.check_closing()
    if r.integrity_fail is not None:
        # typed terminal (resilience.integrity): stamp the manifest so
        # `cli report` renders the integrity beat, then propagate for the
        # CLI's exit-76 mapping.  The supervisor restarts; the resume
        # path's chain validator skips corrupted generations, so the
        # restart resumes from the newest CHAIN-VERIFIED one.  Corrupt
        # in-memory state is deliberately NOT checkpointed here (unlike
        # the resource exit's final save): the newest durable generation
        # predates the detected corruption by construction.
        try:
            _integ.record_violation(r.integrity_fail)
            if r.disk is not None:
                r.disk.abort_level()  # partial next-level writer: discard
            r.obs.abort(
                "integrity-violation",
                site=r.integrity_fail.site,
                depth=r.integrity_fail.depth,
                detail=r.integrity_fail.detail[:300],
                distinct_states=r.total,
            )
            r.obs.close()
        except OSError:
            pass
        _drop_ephemeral_spill(r)
        shutdown_async(r, False)
        raise r.integrity_fail
    if r.exhausted is not None:
        # the terminal path itself writes (manifest rewrite, metrics
        # snapshot) to the same full filesystem — best-effort only, so a
        # second ENOSPC can't demote the typed exit-75 into a torn crash
        try:
            if r.disk is not None:
                r.disk.abort_level()  # partial next-level writer: discard
            # typed terminal: the run manifest records WHY (`cli report`
            # renders the RESOURCE_EXHAUSTED verdict beat from it), and the
            # exception propagates for the CLI's exit-code-75 mapping
            r.obs.abort(
                "resource-exhausted",
                reason=r.exhausted.reason,
                depth=r.exhausted.depth,
                detail=r.exhausted.detail,
                distinct_states=r.total,
                **r.governor.stats(),
            )
            r.obs.close()
        except OSError:
            pass
        shutdown_async(r, False)
        raise r.exhausted

    if r.violation is None and r.check_invariants and r.model.invariants \
            and _f_rows(r.frontier_np):
        # the loop was cut (max_depth/max_states) before the remaining
        # frontier was expanded — its states still need their invariant pass
        bad = first_violation(r, _f_all(r.frontier_np))
        if bad is not None:
            r.violation = violation_at(r, bad[0].name, bad[1])

    dt = time.perf_counter() - r.t0
    r.result_stats.update(
        {
            "visited_capacity": int(r.vcap),
            "fanout": r.C,
            "lanes": r.K,
            "visited_backend": r.visited_backend,
            "pipeline": r.pipe.name,
            "pipeline_fallback": bool(getattr(r.pipe, "fallback", False)),
            # measured, not the pipeline's nominal figure: sub-gate
            # chunks delegate to the per-action path and a fused
            # compile-fallback runs legacy for the rest of the run, so
            # only the observed per-chunk maximum is honest here
            "launches_per_chunk_max": r.run_launches_max,
            "adaptive_active": r.adapt.active,
            # state-space-cache seeding (service/state_cache.py): the
            # depth this run's frontier was seeded at instead of Init
            **({"seeded_from_depth": int(r.seed["depth"])}
               if r.seeded else {}),
            # device-resident level pipeline accounting (DevicePipeline):
            # how many levels ran as single dispatched programs, and why
            # (if ever) the run left the device path for the fused ladder
            **(
                {
                    "device": {
                        "levels": r.pipe.device_levels,
                        "fallback": r.pipe.device_fallback,
                        # what the level programs measured, per level,
                        # and whether a warm call's seed sized any
                        # (PreparedKernels.level_high_waters)
                        "high_waters": r.pipe.high_waters,
                        "seeded": r.pipe.seeded,
                    }
                }
                if getattr(r.pipe, "name", "") == "device"
                else {}
            ),
            "adaptive_compile_fallback": bool(
                getattr(r.pipe, "legacy", r.pipe).compile_fallback
            ),
            "transient_retries": r.chunk_retry.retries_total,
            "degradations": r.chunk_retry.degradations,
            # async-overlap accounting (overlap.py): the staging bound
            # is structural (two open successor launches, one chunk
            # beside them whose guard stage ran ahead) — tests pin both
            "overlap": {
                "enabled": r.overlap_on,
                "staged_chunks_peak": r.overlap_staged_peak,
                "guard_ahead_peak": r.overlap_ahead_peak,
                "sync_ckpt_io_s": round(r.sync_io_s, 4),
                **(
                    {"io_worker": r.io_worker.stats()}
                    if r.io_worker is not None
                    else {}
                ),
                **(
                    {"ckpt_worker": r.ckpt_worker.stats()}
                    if r.ckpt_worker is not None
                    else {}
                ),
            },
        }
    )
    if r.host_set is not None:
        r.result_stats["host_fpset_size"] = len(r.host_set)
    if r.disk is not None:
        r.result_stats["spill"] = r.disk.stats()
        r.result_stats["spill_dir"] = r.disk.dir
        r.result_stats["mem_budget"] = r.disk.fpset.mem_budget
    if r.ht_hi is not None:
        r.result_stats["hash_table_capacity"] = int(r.ht_hi.shape[0])
        r.result_stats["hash_table_size"] = r.hash_n
    _drop_ephemeral_spill(r)
    shutdown_async(r, True)
    res = CheckResult(
        model=r.model.name,
        levels=r.levels,
        total=r.total,
        diameter=len(r.levels) - 1,
        violation=r.violation,
        seconds=dt,
        states_per_sec=r.total / max(dt, 1e-9),
        stats=r.result_stats,
    )
    r.obs.finish(res)
    r.obs.close()
    return res
