"""Single-device level-synchronous BFS model checker.

This is the TPU-native replacement for TLC's worker loop (StateQueue + FPSet
+ per-state invariant evaluation) — the external Java engine the reference
corpus depends on (it vendors no checker; `*.toolbox` is gitignored,
/root/reference/.gitignore:1).

Per BFS level, one jitted step does:
  frontier[B, K] --unpack--> vmap over (state x choice) of every action kernel
  --> candidate successors [B, C, K] + enabled mask
  --> fingerprint pairs, lexsort, adjacent-dedup           (in-batch dedup)
  --> binary-search probe of the sorted visited set        (global dedup)
  --> compact new states to the front, merge fps into visited
  --> invariant predicate kernels on the new states

Shapes are static under jit: the frontier is padded to power-of-two buckets
and the visited set to a power-of-two capacity; the host loop re-pads and
lets a new (bucket, capacity) pair trigger a (cached) recompile — O(log n)
distinct shapes over a whole run, each compiled once.

Deadlock checking is off by default: the bounded models deadlock by design
once id sequences are exhausted and logs converge (every `Spec` in the corpus
is run with TLC's deadlock check disabled for the same reason).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import Model
from ..obs import metrics as _met
from ..obs.observer import RunObserver
from ..obs.tracer import now as _now
from ..ops import hashset
from ..resilience import integrity as _integ
from ..resilience.checkpoints import CheckpointStore
from ..resilience.faults import FaultPlan
from ..resilience.integrity import IntegrityError
from ..resilience.resources import (
    ResourceExhausted,
    ResourceGovernor,
    is_disk_full,
)
from ..resilience.retry import ChunkRetryHandler
from ..utils.platform_guard import device_stamp
from .hostio import HostIO
from .pipeline import (
    work_width,
    counts_out,
    fp_stage,
    grow_visited as _grow_visited,
    init_rows_program,
    invariant_rows_program,
    invariant_stage,
    make_pipeline,
    part,
    program_name,
    resolve_pipeline,
    sorted_dedup_stage,
    split_counts,
    squeeze_stage,
    stage,
    work_record,
)

# insert-or-find on the device hash table; table + claim lattice donated so
# XLA updates them in place instead of copying O(capacity) per chunk
def _hash_insert_impl(t_hi, t_lo, claim, q_hi, q_lo, valid):
    return hashset.probe_insert(t_hi, t_lo, q_hi, q_lo, valid, claim=claim)


_hash_insert = jax.jit(_hash_insert_impl, donate_argnums=(0, 1, 2))

# device-hash table floor (module-level so tests can shrink it to exercise
# the growth / overflow-re-run machinery at small state counts)
_HASH_MIN_CAP = 1 << 16


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def indexing_equations(jaxpr) -> tuple:
    """(``gather``, ``scatter*``) equations of a jaxpr, sub-jaxprs
    included: how often the program it lowers to indexes by gather or
    scatter (each one XLA gather / scatter, 6-8 ns an element on the chip
    whatever the axis's length: PERF.md section 6, PR 39)."""
    gathers = scatters = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        gathers += name == "gather"
        scatters += name.startswith("scatter")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            g, s = indexing_equations(sub)
            gathers, scatters = gathers + g, scatters + s
    return gathers, scatters


class _CompileOnFirstCall:
    """Cache entry for a freshly built jitted step: the FIRST call is where
    jax traces + XLA compiles (jax.jit is lazy), so exactly that call is
    wrapped in a ``compile`` span — then the wrapper replaces itself with
    the bare jitted function.  This is what lets a warm serving path PROVE
    its cache hits: a job that re-uses every step shows zero compile spans
    in its trace (service/kernel_cache, docs/service.md).  The span says
    what the program is made of too: ``gathers`` / ``scatters``
    (:func:`indexing_equations` of the jaxpr the call traced; read back
    from jit's own trace cache, so the program is traced once).  With no
    active tracer the wrapper costs one dict store and disappears."""

    def __init__(self, fn, cache: dict, key, **attrs):
        self.fn = fn
        self._cache = cache
        self._key = key
        self._attrs = attrs

    def __call__(self, *args):
        from ..obs import tracer as _tr

        t0 = _tr.now()
        out = self.fn(*args)
        cur = _tr.current_tracer()
        if cur is not None:
            t1 = _tr.now()
            gathers, scatters = indexing_equations(
                self.fn.trace(*args).jaxpr.jaxpr
            )
            cur.emit_span("compile", t0, t1, gathers=gathers,
                          scatters=scatters, **self._attrs)
        # swap in the bare jitted fn iff this entry is still current (a
        # capacity-growth eviction may already have dropped the key)
        if self._cache.get(self._key) is self:
            self._cache[self._key] = self.fn
        return out


def _round256(w: int) -> int:
    """Round up to the 256-row width alignment (single source of truth
    for widths_for and norm_widths)."""
    return -(-w // 256) * 256


class AdaptiveCompact:
    """Per-action compact-buffer sizing policy, shared by the single-device
    engine and the sharded engine (round-5 review item: one policy, two
    hand-synced copies otherwise).

    Escalation: stay on the uniform legacy shift until a uniform attempt
    actually overflows (the uniform path is cheaper when it fits),
    then size each action's buffer at ~1.35x the
    run's measured high-water per-state enablement, pow2-rounded with
    overflow-learned floors.  Callers supply the per-state guard density
    (single-device: act_guard / chunk rows; sharded: max over shards of
    act_guard / shard rows) so the policy itself is engine-agnostic, and
    all inputs are host-replicated values so multi-process runs stay in
    lockstep.  KSPEC_ADAPTIVE_COMPACT=0 pins the legacy uniform-only
    behavior.
    """

    def __init__(self, actions, compact_shift: int, bucket_gate: int):
        self.actions = actions
        self.shift = compact_shift
        self.gate = bucket_gate
        self.hw = np.zeros(len(actions), np.float64)
        self.floor = np.zeros(len(actions), np.int64)
        self.on = os.environ.get("KSPEC_ADAPTIVE_COMPACT", "1") != "0"
        # Wide-model guard (TODO round-5 finding): a fully escalated
        # program on the 27-action mixed product reproducibly OOMs
        # XLA:CPU's LLVM at compile, while the uniform-shift program with
        # the SAME pipeline count compiles fine — the blowup tracks how
        # far the escalated shapes stray from the uniform ones, not the
        # pipeline count itself.  Above this many actions, escalation
        # widens ONLY the actions whose measured need exceeds their
        # uniform buffer and pins every other action at (approximately —
        # tuple widths are 256-rounded, and the tuple form skips the
        # uniform path's pre-sort squeeze) its uniform width.  This
        # brings the escalated program's buffer shapes much closer to
        # the compiling uniform ones; it is a heuristic, not a shape
        # guarantee — compile_fallback remains the backstop.  Narrow
        # models (the 9-action flagship, where full adaptation is
        # profiled and wins) are unaffected.
        self.max_pipe = int(os.environ.get("KSPEC_ADAPTIVE_MAX_PIPE", "16"))
        self.active = False

    def widths_for(self, bucket: int):
        """compact arg for this bucket: None (full path), the uniform
        legacy shift, or a per-action width tuple once escalated."""
        if self.shift <= 0 or bucket < self.gate:
            return None
        if not (self.on and self.active and self.hw.any()):
            return self.shift
        hybrid = len(self.actions) > self.max_pipe
        uni_rows = max(1, bucket >> self.shift)
        out = []
        for a, hw, floor in zip(self.actions, self.hw, self.floor):
            need = _next_pow2(max(256, int(1.35 * hw * bucket) + 1))
            if hybrid:
                # hybrid floors are doubled 256-multiples of (possibly
                # non-pow2) pinned uniform widths — re-rounding them
                # through _next_pow2 could run up to ~2x wider than the
                # intended doubling, drifting further from the
                # uniform-adjacent shapes this mode exists to preserve
                # (round-5 advisor item): size from the floor with
                # _round256 instead
                w = max(need, _round256(int(floor)))
            else:
                w = max(need, _next_pow2(int(floor)))
            w = min(w, bucket * a.n_choices)
            if hybrid:
                # pre-apply norm_widths' 256-rounding so the width stated
                # here is the width the program actually runs at
                w_uni = _round256(
                    min(uni_rows * a.n_choices, bucket * a.n_choices)
                )
                if w <= w_uni:
                    w = w_uni
            out.append(w)
        return tuple(out)

    def observe(self, density: np.ndarray):
        """Fold one attempt's per-state guard densities into the
        high-water marks."""
        np.maximum(self.hw, density, out=self.hw)

    def escalate(self, attempt, ovf_a, bucket: int, density: np.ndarray):
        """Next attempt after an expansion overflow of `attempt`.

        attempt: the overflowed compact arg (int = uniform shift, tuple =
        per-action widths).  ovf_a: per-action overflow flags (tuple
        case).  density: the overflowing attempt's complete per-state
        guard densities (phase A sweeps the full lattice regardless of
        buffer overflow, so these are exact).
        """
        if isinstance(attempt, int):
            if self.on:
                self.observe(density)
                self.active = True
                attempt = self.widths_for(bucket)
            if isinstance(attempt, int):  # adaptation off (or degenerate)
                return attempt - 1 if attempt > 1 else None
            return attempt
        nxt = tuple(
            min(2 * w, bucket * a.n_choices) if o else w
            for w, o, a in zip(attempt, ovf_a, self.actions)
        )
        for ai, o in enumerate(ovf_a):
            if o:
                self.floor[ai] = max(self.floor[ai], nxt[ai])
        return nxt

    def compile_fallback(self, bucket: int):
        """Shared response to an escalated per-action program failing to
        COMPILE (XLA:CPU's LLVM has been seen OOMing on the 27-action
        mixed product's escalated step): escalation is purely a
        performance knob, so pin adaptation off for the rest of the run
        and return the uniform attempt to retry the chunk with — the
        uniform overflow ladder (shift-1 ... full lattice) keeps results
        exact at every density.  One copy for both engines (the same
        rationale as this class itself)."""
        self.on = False
        self.active = False
        return (
            self.shift
            if self.shift > 0 and bucket >= self.gate
            else None
        )


@dataclass
class Violation:
    invariant: str
    depth: int
    state: object  # decoded canonical state (or raw dict if no decoder)
    trace: list  # [(action_name | "<init>", decoded state), ...] root -> violation


@dataclass
class CheckResult:
    model: str
    levels: list[int]  # distinct new states per BFS level (level 0 = inits)
    total: int
    diameter: int
    violation: Optional[Violation]
    seconds: float
    states_per_sec: float
    stats: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.violation is None


class _Step:
    """Builds and caches the jitted level step for one model."""

    def __init__(self, model: Model):
        self.model = model
        self.spec = model.spec
        self.K = self.spec.num_lanes
        self.C = model.total_fanout
        # global action id per flattened choice column
        act_ids = np.concatenate(
            [np.full(a.n_choices, i, np.int32) for i, a in enumerate(model.actions)]
        )
        self.act_ids = jnp.asarray(act_ids)
        # jitted-step cache shared across check() calls on the same Model:
        # re-tracing is the dominant cost for models with large emitted
        # expression trees (utils/tla_emit: seconds per shape), and the
        # traced steps are pure functions of (model, shape key)
        cache = getattr(model, "_step_cache", None)
        if cache is None:
            cache = {}
            try:
                model._step_cache = cache
            except AttributeError:
                pass  # exotic model object without attribute support
        self._cache = cache
        # every key ever BUILT for this model, growth evictions included —
        # what PreparedKernels.rewarm replays at the capacity fixed point
        log = getattr(model, "_step_compiled_log", None)
        if log is None:
            log = set()
            try:
                model._step_compiled_log = log
            except AttributeError:
                pass
        self._compiled_log = log
        self.last_key = None  # key of the latest cached() call

    def norm_widths(self, bucket: int, compact):
        """Normalize a compact spec to per-action buffer widths (rows).

        compact: None/0 -> full path (returns None); int -> the uniform
        legacy form, W_a = n_choices_a * (bucket >> compact); sequence ->
        explicit per-action widths, clamped to the action's full lattice
        width (at which overflow is impossible)."""
        acts = self.model.actions
        if not compact:
            return None
        if isinstance(compact, int):
            if (bucket >> compact) < 1:
                return None
            return tuple(max(1, bucket >> compact) * a.n_choices for a in acts)
        assert len(compact) == len(acts), (len(compact), len(acts))
        # Round caller-supplied widths up to a multiple of 256 (unless the
        # full lattice width — always a pow2 multiple of n_choices — is
        # smaller): the widths are shapes of the compiled programs, so
        # the coarse 256-row ladder bounds how many distinct programs
        # the measured maxima can ask for and keeps every buffer
        # tile-aligned.  The alignment invariant is enforced HERE, where
        # the widths are created.
        return tuple(
            min(_round256(max(1, int(w))), bucket * a.n_choices)
            for w, a in zip(compact, acts)
        )

    def expand_width(self, bucket: int, compact) -> int:
        """Candidate rows produced by make_expand(bucket, compact)."""
        widths = self.norm_widths(bucket, compact)
        return bucket * self.C if widths is None else sum(widths)

    def dedup_width(self, bucket: int, compact,
                    squeeze_full: bool = False) -> int:
        """Candidate rows the step program's fingerprint, sort and probe
        run at: the expansion's width, halved by the pre-sort squeeze on
        the uniform-shift compact path (:meth:`_build` says why)."""
        T_exp = self.expand_width(bucket, compact)
        shift = self.norm_widths(bucket, compact) is not None
        per_action = isinstance(compact, (list, tuple))
        if not shift or squeeze_full or per_action:
            return T_exp
        return max(256, T_exp >> 1)

    def make_expand(self, bucket: int, shift):
        """Expansion kernel: (frontier[B, K], states[B], fvalid[B]) ->
        (en_pre[B, C], cand[T, K], valid[T], parent[T], actid[T],
         act_en[n_actions], act_guard[n_actions], overflow[n_actions])
        with T = expand_width(bucket, shift).  act_en counts enabled
        successors post-CONSTRAINT (the action-coverage histogram);
        act_guard counts guard-enabled pairs pre-CONSTRAINT — the load the
        compact buffers actually hold, hence what adaptive sizing must
        track (on constraint-pruning models like AsyncIsr the two can
        differ widely).

        shift falsy (or an int shifting the bucket away): one phase over
        the full padded lattice (T = B*C; overflow is constant False).
        otherwise: two phases — a full-lattice guard sweep whose state
        *updates* are dead code (XLA eliminates them; guards alone are a few
        % of the kernel cost), then per-action compaction of the enabled
        (state, choice) pairs into a W_a-row buffer where the kernel,
        functional update, constraint and lane packing actually run.
        `shift` may be a single int (the uniform legacy form,
        W_a = n_choices_a * (B >> shift)) or a per-action width sequence —
        enablement density varies an order of magnitude across actions
        (26-29%% for LeaderWrite/BecomeLeader/Truncate vs <0.1%% for the
        fenced ISR mutations on the deep 5-broker workload), so per-action
        widths sized from measured enablement avoid both the dense
        actions' overflow-retry and the sparse actions' padding waste.
        overflow[a]=True iff action `a` enabled more pairs than its W_a
        buffer holds — the caller must re-run with a wider buffer for that
        action; outputs are incomplete in that case but never wrong-state
        (valid rows are always real successors)."""
        model, spec = self.model, self.spec
        C = self.C
        act_ids = self.act_ids
        widths = self.norm_widths(bucket, shift)
        n_actions = len(model.actions)
        # action boundaries for the enablement histogram (TLC's action
        # coverage analogue, SURVEY.md §5 "Metrics")
        bounds = np.cumsum([0] + [a.n_choices for a in model.actions])
        B = bucket
        M = B * C

        @stage("expand")
        def _expand_full(frontier, states, fvalid):
            en_pre, en, packed = jax.vmap(self._expand_one)(states)  # [B,C]x2, [B,C,K]
            en = en & fvalid[:, None]
            guard_en = en_pre & fvalid[:, None]
            act_en = jnp.stack(
                [
                    jnp.sum(en[:, bounds[i] : bounds[i + 1]], dtype=jnp.int32)
                    for i in range(len(model.actions))
                ]
            )
            act_guard = jnp.stack(
                [
                    jnp.sum(
                        guard_en[:, bounds[i] : bounds[i + 1]],
                        dtype=jnp.int32,
                    )
                    for i in range(len(model.actions))
                ]
            )
            cand = packed.reshape(M, spec.num_lanes)
            valid = en.reshape(M)
            flat = jnp.arange(M, dtype=jnp.int32)
            return (
                en_pre,
                cand,
                valid,
                flat // C,
                act_ids[flat % C],
                act_en,
                act_guard,
                jnp.zeros((n_actions,), bool),
            )

        def _expand_compact(frontier, states, fvalid):
            def _guards_one(state):
                parts = []
                for a in model.actions:
                    choices = jnp.arange(a.n_choices, dtype=jnp.int32)
                    ok = jax.vmap(lambda c, s=state, a=a: a.kernel(s, c)[0])(choices)
                    parts.append(ok)
                return jnp.concatenate(parts)

            with stage("guard"):
                # [B, C] pre-constraint
                en_pre = jax.vmap(_guards_one)(states)
            cand_parts, valid_parts, parent_parts, act_parts = [], [], [], []
            act_en_parts, act_guard_parts, ovf_parts = [], [], []
            for ai, a in enumerate(model.actions):
                na = a.n_choices
                W = widths[ai]
                with stage("compact"), part("select"):
                    ga = (
                        en_pre[:, bounds[ai] : bounds[ai + 1]]
                        & fvalid[:, None]
                    ).reshape(B * na)
                    n_en = jnp.sum(ga, dtype=jnp.int32)
                    act_guard_parts.append(n_en)
                    ovf_parts.append(n_en > W)
                    cpos = jnp.where(ga, jnp.cumsum(ga) - 1, W)
                    cidx = jnp.zeros((W,), jnp.int32).at[cpos].set(
                        jnp.arange(B * na, dtype=jnp.int32)
                    )
                    rowvalid = jnp.arange(W) < n_en
                with stage("expand"):
                    sidx = cidx // na
                    ch = cidx % na
                    # the parent rows gathered packed, once, then unpacked:
                    # one K-lane gather an action, not one a field
                    gstate = jax.vmap(spec.unpack)(frontier[sidx])
                    ok, nxt = jax.vmap(a.kernel)(gstate, ch)
                    ok = ok & rowvalid
                    if model.constraint is not None:
                        ok = ok & jax.vmap(model.constraint)(nxt)
                    cand_parts.append(jax.vmap(spec.pack)(nxt))
                    valid_parts.append(ok)
                    parent_parts.append(sidx)
                    act_parts.append(jnp.full((W,), ai, jnp.int32))
                    act_en_parts.append(jnp.sum(ok, dtype=jnp.int32))
            with stage("expand"):
                return (
                    en_pre,
                    jnp.concatenate(cand_parts, axis=0),
                    jnp.concatenate(valid_parts),
                    jnp.concatenate(parent_parts),
                    jnp.concatenate(act_parts),
                    jnp.stack(act_en_parts),
                    jnp.stack(act_guard_parts),
                    jnp.stack(ovf_parts),
                )

        return _expand_compact if widths is not None else _expand_full

    def _expand_one(self, state: dict):
        """All successors of one state: (enabled_pre_constraint[C],
        enabled[C], packed[C, K]).  The pre-constraint mask feeds deadlock
        detection (a state is deadlocked when no action is enabled,
        regardless of CONSTRAINT pruning)."""
        model, spec = self.model, self.spec
        pre_parts, ok_parts, packed_parts = [], [], []
        for a in model.actions:
            choices = jnp.arange(a.n_choices, dtype=jnp.int32)
            ok, nxt = jax.vmap(lambda c, s=state, a=a: a.kernel(s, c))(choices)
            pre_parts.append(ok)
            if model.constraint is not None:
                ok = ok & jax.vmap(model.constraint)(nxt)
            ok_parts.append(ok)
            packed_parts.append(jax.vmap(spec.pack)(nxt))
        return (
            jnp.concatenate(pre_parts),
            jnp.concatenate(ok_parts),
            jnp.concatenate(packed_parts, axis=0),
        )

    def inv_sig(self, with_invariants: bool) -> tuple:
        """The invariant-selection component of step-cache keys: the
        ORDERED invariant names when the program embeds the predicates,
        () otherwise.  Keying on the names (not a bool) lets invariant
        overlays of one base model (service/kernel_cache) share one step
        cache — invariant-free programs are shared across overlays, while
        each ordering's invariant-bearing programs key separately (the
        stack order fixes the first-violation rule)."""
        return (
            tuple(i.name for i in self.model.invariants)
            if with_invariants and self.model.invariants
            else ()
        )

    def cached(self, key, build, **attrs):
        """Compile-cache insert-or-get: `build()` returns the un-jitted
        program, which is jitted here under its cache tag and the naming
        version (``dvl_n2``; pipeline.program_name), so the HLO module
        and the profiler's module line say which program ran.  The first
        call of a fresh entry is wrapped in a ``compile`` span
        (_CompileOnFirstCall) and the key is appended to the compiled
        log PreparedKernels.rewarm replays.  `last_key` hands the key back
        to a caller of a builder (pipeline.warm_key), so each tag's layout
        is written in its builder alone."""
        self.last_key = key
        if key not in self._cache:
            self._compiled_log.add(key)
            fn = build()
            fn.__name__ = fn.__qualname__ = program_name(key[0])
            self._cache[key] = _CompileOnFirstCall(
                jax.jit(fn), self._cache, key, **attrs
            )
        return self._cache[key]

    def first_violation(self, key_head: tuple, N: int, rows: np.ndarray,
                        io: HostIO, obs_: RunObserver, *where):
        """The invariant pass over host-held `rows` -> (invariant, row
        index) of the first invariant, in declaration order, that some
        row violates (within it the lowest row), or None.  One jitted
        program per padded row count `N` (pipeline.invariant_rows_program),
        kept with the level programs under (*key_head, N, inv_sig) — so
        it costs a launch, not an eager dispatch per operation of every
        predicate.  `key_head`: the cache tag, then whatever else shapes
        the program (the sharded engine's mesh); `where`: that engine's
        placement, handed to ``io.put``."""
        tag = key_head[0]
        fn = self.cached(
            (*key_head, N, self.inv_sig(True)),
            lambda: invariant_rows_program(self.model, N),
            program=tag, bucket=N,
        )
        # (a span and a profiler annotation, not a level's dispatch: it
        # runs before the first level and after the last)
        launch = obs_.dispatch(tag, bucket=N)
        any_bad, first = fn(
            io.put(_pad_rows(rows, N), *where), np.int32(rows.shape[0])
        )
        any_bad = io.fetch(any_bad)
        launch.finish()
        if not any_bad.any():
            return None
        i = int(np.argmax(any_bad))
        return self.model.invariants[i], int(io.fetch(first)[i])

    def init_rows(self, io: HostIO, obs_: RunObserver):
        """The model's distinct initial states -> (rows u32[n0, K], hi,
        lo), numpy, rows in ``np.unique`` order.  Pack and fingerprint
        run as one cached program per padded state count; the padding
        repeats the first state, which ``np.unique`` drops again."""
        inits = [
            {k: np.asarray(v, np.int32) for k, v in s.items()}
            for s in self.model.init_states()
        ]
        N = _next_pow2(len(inits))
        inits += inits[:1] * (N - len(inits))
        fn = self.cached(
            ("init", N), lambda: init_rows_program(self.model),
            program="init", bucket=N,
        )
        launch = obs_.dispatch("init", bucket=N)
        rows, hi, lo = fn(
            {k: io.put(np.stack([s[k] for s in inits])) for k in inits[0]}
        )
        rows = io.fetch(rows)
        launch.finish()
        # dedup inits (all corpus models have a single deterministic init)
        rows, at = np.unique(rows, axis=0, return_index=True)
        return rows, io.fetch(hi)[at], io.fetch(lo)[at]

    def get(
        self,
        bucket: int,
        vcap: int,
        with_invariants: bool = True,
        with_merge: bool = True,
        compact=None,
        squeeze_full: bool = False,
    ):
        # squeeze_full only changes the program on the uniform-shift
        # compact path (per-action and full paths already run T = T_exp) —
        # normalize it so the sticky flag can't force recompiles of
        # byte-identical steps under fresh keys
        squeeze_full = (
            squeeze_full
            and isinstance(compact, int)
            and self.norm_widths(bucket, compact) is not None
        )
        compact_key = (
            tuple(compact) if isinstance(compact, (list, tuple)) else compact
        )
        key = (
            "step",
            bucket,
            vcap,
            self.inv_sig(with_invariants),
            with_merge,
            compact_key,
            squeeze_full,
        )
        return self.cached(
            key,
            lambda: self.build_raw(
                bucket, vcap, with_invariants, with_merge, compact,
                squeeze_full,
            ),
            bucket=bucket,
            vcap=vcap,
            compact=repr(compact_key),
        )

    def build_raw(
        self,
        bucket: int,
        vcap: int,
        with_invariants: bool = True,
        with_merge: bool = True,
        compact=None,
        squeeze_full: bool = False,
    ):
        """The un-jitted level step (frontier, fvalid, vhi, vlo, vn) -> ...;
        exposed for the driver's compile checks and custom jit wrapping.
        with_merge=False skips the visited-set merge (host FpSet backend).

        compact: a right-shift amount — one int (uniform) or a per-action
        sequence — enabling the two-phase expansion.  Phase A sweeps all
        guards over the full padded choice lattice with the state *updates*
        dead-code-eliminated by XLA (guards alone are ~3% of the kernel
        cost — the expensive parts, the functional updates and the lane
        packing, never run for disabled candidates).  Phase B compacts each
        action's enabled (state, choice) pairs into a buffer of
        W_a = n_choices_a * (bucket >> shift_a) rows and re-runs that
        action's kernel, update and pack at the compacted width only.  The
        sort / visited-probe / merge then also run at the compacted total
        width (only a few percent of the lattice is ever enabled —
        RESULTS.md measures ~6% on Kip320).  The step returns a per-action
        overflow vector (plus one trailing squeeze-overflow flag): where
        set, that action enabled more pairs than its buffer holds, the
        outputs are INCOMPLETE, and the caller must re-run the chunk with a
        smaller shift for that action (the host loop retries and adapts;
        results stay exact either way).  squeeze_full=True disables the
        pre-sort squeeze width reduction (the retry fallback when the
        squeeze itself overflows)."""
        return self._build(
            bucket, vcap, with_invariants, with_merge, compact, squeeze_full
        )

    def _build(
        self,
        bucket: int,
        vcap: int,
        with_invariants: bool,
        with_merge: bool = True,
        compact=None,
        squeeze_full: bool = False,
    ):
        spec, model = self.spec, self.model
        K = self.K
        widths = self.norm_widths(bucket, compact)
        shift = widths is not None  # truthy iff the compact path is on
        expand = self.make_expand(bucket, compact)
        # Candidate width the sort/probe/outputs run at.  On the compact
        # path a second-stage squeeze gathers the enabled candidates into a
        # narrower buffer before fingerprint/sort/probe — the sort is the
        # single most expensive stage, and its cost is set by this width.
        # Uniform-shift buffers are ~4x oversized (~25% occupied), so the
        # squeeze halves (squeeze overflow re-runs with squeeze_full — the
        # retry keeps results exact at every density).  The squeeze is a
        # change of width and nothing else: where T is the expansion's own
        # width (per-action widths, sized tight from measured enablement;
        # squeeze_full; the fused path's pooled layout) it would only move
        # the enabled rows to the front in order, which the stable sort
        # does again (masked rows fingerprint to the sentinel pair and
        # sort last, ties break by index), so the sorted dedup skips it
        # there and fingerprints the rows where they lie.
        T = self.dedup_width(bucket, compact, squeeze_full)
        resizes = shift and T != self.expand_width(bucket, compact)

        # Host-FpSet backend: the device holds no visited set, and the
        # native C++ open-addressing FpSet already dedups both in-batch and
        # globally on insert — so the device-side sort / visited-probe /
        # rank-merge stages are pure waste there.  Profiled on the flagship
        # bench chunk (32k rows, CPU): sort 56ms + probe 24ms + compact+
        # merge 410ms out of a 663ms step — 74% of the level step spent
        # deduplicating what the C++ set re-dedups anyway.  This branch
        # squeezes the enabled candidates to the front, fingerprints them,
        # and hands (rows, fps) straight to the host.
        host_dedup = not with_merge

        def step(frontier, fvalid, vhi, vlo, vn):
            # the frontier unpack belongs to whichever stage reads the
            # states first: the guard sweep on the compact path, the
            # full-lattice expansion otherwise
            with stage("guard" if shift else "expand"):
                states = jax.vmap(spec.unpack)(frontier)
            (
                en_pre,
                cand,
                valid,
                parent,
                actid,
                act_en,
                act_guard,
                exp_ovf,
            ) = expand(frontier, states, fvalid)
            with stage("guard" if shift else "expand"):
                deadlocked = fvalid & ~jnp.any(en_pre, axis=1)
                dl_any = jnp.any(deadlocked)
                dl_idx = jnp.argmax(deadlocked)

            # overflow contract: bool[n_actions + 1] — per-action compact-
            # buffer overflow plus one trailing squeeze-overflow flag
            def ovf_vec(sq_ovf=None):
                tail = (
                    jnp.zeros((1,), bool)
                    if sq_ovf is None
                    else jnp.atleast_1d(sq_ovf)
                )
                return jnp.concatenate([exp_ovf, tail])

            if host_dedup:
                out, out_parent, out_act, rowvalid, n_en, sq_ovf = (
                    squeeze_stage(cand, parent, actid, valid, T, K)
                )
                out_hi, out_lo, _orbit = fp_stage(out, rowvalid, model)
                viol_any, viol_idx = invariant_stage(
                    model, states, fvalid, with_invariants
                )
                return (
                    out, out_parent, out_act, n_en, vhi, vlo, vn,
                    viol_any, viol_idx, dl_any, dl_idx, counts_out(act_en),
                    out_hi, out_lo, ovf_vec(sq_ovf), act_guard,
                )

            if resizes:
                cand, parent, actid, valid, _, sq_ovf = squeeze_stage(
                    cand, parent, actid, valid, T, K
                )
                overflow = ovf_vec(sq_ovf)
            else:
                overflow = ovf_vec()

            hi, lo, orbit = fp_stage(cand, valid, model)
            # the shared winner-selection sequence (sort, first
            # occurrence, visited rank, compaction, rank-scatter merge)
            (out, out_parent, out_act, new_n, out_hi, out_lo,
             vhi2, vlo2, vn2, _rank, work) = sorted_dedup_stage(
                cand, parent, actid, valid, hi, lo,
                vhi, vlo, vn, vcap, T, K, True, orbit=orbit,
            )
            viol_any, viol_idx = invariant_stage(
                model, states, fvalid, with_invariants
            )
            return (
                out, out_parent, out_act, new_n, vhi2, vlo2, vn2,
                viol_any, viol_idx, dl_any, dl_idx,
                counts_out(act_en, work),
                out_hi, out_lo, overflow, act_guard,
            )

        return step


class PreparedKernels:
    """Reusable, warm engine kernels for one model — the serving daemon's
    unit of caching (service/kernel_cache.py), split out of :func:`check`.

    ``check`` builds a ``_Step`` per call; because the jitted-step cache
    lives on the Model object it already re-warms across calls, but the
    serving path needs the preparation to be an explicit, inspectable
    artifact: ``prepare(model)`` once, then ``check(model,
    prepared=pk)`` any number of times — the second and every later check
    of the same schema shape re-uses every compiled step (zero ``compile``
    spans in its trace, the daemon's warm-path proof).  ``warmup``
    optionally pre-compiles the step for a given frontier bucket so even
    the FIRST job of a shape pays no compile inside its latency budget.

    Two sizing facts of the last run ride along, each a fixed point of
    the warm protocol ``note_result`` -> ``rewarm`` -> ``check``: the
    final visited capacity (``capacity_hint``: no growth, no eviction,
    every step-cache key matches) and, for the ``device`` pipeline, what
    each whole-level program measured (``level_high_waters``: every level
    program's first dispatch fits, none is discarded and re-run).
    """

    def __init__(self, model: Model):
        self.model = model
        self.step = _Step(model)
        # The last run's FINAL visited capacity, fed back as check()'s
        # visited_capacity_hint so WARM runs preallocate the device
        # visited set at exactly the size the shape needs.  Without it
        # every run replays the capacity-doubling ladder, and each
        # doubling EVICTS the steps compiled for the outgrown capacity —
        # i.e. a "warm" run would recompile the whole ladder again
        # (measured 5s/run on the tiny truncate model).  Feeding back the
        # final CAPACITY (a power of two the engine itself derived) makes
        # the hint a fixed point: the next run of the same knobs starts
        # at the same vcap, so every step-cache key matches and the warm
        # trace shows zero compile spans.  The hash backend sizes its
        # table from a state count instead, so non-device runs feed back
        # res.total.
        self.capacity_hint = None
        self._hint_is_capacity = False  # True iff hint is a device vcap
        # The second sizing fact a warm run starts from: what each
        # whole-level program of the last run measured (the `device`
        # pipeline's stats["device"]["high_waters"]: per depth, the
        # exact per-action guard density and new-state count).  A level
        # program's first dispatch is sized from high waters that a
        # call would otherwise start at zero, so every growing level
        # would overflow a buffer, throw the dispatch away and run
        # again, in every call (a third of a 5-broker pass on the
        # chip).  Fed back, each level's first dispatch takes the sizes
        # its re-dispatch ran at, which are a fixed point for the same
        # reason the capacity is: the records are counts, not shapes,
        # so a seeded run reports what the run that seeded it did.
        self.level_high_waters: dict = {}  # depth -> record

    def note_result(self, res: "CheckResult") -> None:
        """Feed a finished run's sizing back: the final visited capacity
        into the hint, and a device-pipeline run's per-level high waters
        (unless it fell back) into `level_high_waters`, both
        max-merged."""
        stats = res.stats or {}
        if stats.get("visited_backend") == "device":
            cap = stats.get("visited_capacity") or res.total
            self._hint_is_capacity = True
        else:
            cap = res.total
            self._hint_is_capacity = False
        self.capacity_hint = max(self.capacity_hint or 0, cap)
        dev = stats.get("device") or {}
        if dev.get("fallback") is None:
            for rec in dev.get("high_waters", ()):
                old = self.level_high_waters.get(rec["depth"])
                if old is not None:
                    rec = {
                        **rec,
                        "density": np.maximum(
                            old["density"], rec["density"]).tolist(),
                        "level_new": max(old["level_new"],
                                         rec["level_new"]),
                    }
                self.level_high_waters[rec["depth"]] = rec

    def rewarm(self) -> int:
        """Build, off any job's latency path, the programs the next run
        of this shape will ask for and the cache does not hold (the
        serving warm-path contract: the second job of a shape shows
        zero compile spans; the daemon calls this right after a run,
        still inside its busy-heartbeat window).  Two gaps:

        - a run that GREW the device visited set evicted the steps
          compiled at every outgrown capacity, but the buckets those
          steps served (the small early levels) recur on the next run,
          which starts at the new capacity fixed point: re-compile them
          there;
        - a run seeded with `level_high_waters` sizes each level
          program's first dispatch from them: build the ones that
          differ from what the run itself compiled
          (pipeline.warm_seeded_levels).

        Returns the number of programs compiled."""
        from .pipeline import key_vcap, warm_key, warm_seeded_levels

        # non-device backends never evict on growth: no `cap`, no loop
        cap = self.capacity_hint if self._hint_is_capacity else None
        done = 0
        for key in list(self.step._compiled_log) if cap else ():
            vcap = key_vcap(key)
            if vcap is None or vcap == cap:
                continue  # no capacity component, or already at the
                # fixed point (guard kernels never evict on growth)
            target = tuple(
                cap if i == 2 else f for i, f in enumerate(key)
            )
            if target in self.step._cache:
                continue
            if warm_key(self.step, self.model, key, cap) is not None:
                done += 1
        if self.level_high_waters:
            done += warm_seeded_levels(
                self.step, self.model, self.level_high_waters, cap)
        return done

    @property
    def compiled_steps(self) -> int:
        """Distinct (shape, variant) step programs built so far."""
        return len(self.step._cache)

    def warmup(
        self,
        bucket: int = 256,
        vcap: int = 1 << 12,
        check_invariants: bool = True,
        with_merge: bool = True,
        compact=None,
        squeeze_full: bool = False,
    ) -> None:
        """Force trace + XLA compile of one step shape by running it on an
        all-invalid frontier (fvalid all False: no successor is enabled, no
        verdict can fire — pure compilation, results discarded)."""
        bucket = _next_pow2(max(32, bucket))
        vcap = _next_pow2(max(64, vcap))
        step = self.step.get(
            bucket, vcap, check_invariants, with_merge=with_merge,
            compact=compact, squeeze_full=squeeze_full,
        )
        K = self.model.spec.num_lanes
        out = step(
            jnp.zeros((bucket, K), jnp.uint32),
            jnp.zeros((bucket,), bool),
            jnp.full(vcap, 0xFFFFFFFF, jnp.uint32),
            jnp.full(vcap, 0xFFFFFFFF, jnp.uint32),
            jnp.int32(0),
        )
        jax.block_until_ready(out)


def prepare(model: Model) -> PreparedKernels:
    """Prepare (and cache on the model) the reusable jitted engine kernels
    for `model` — the explicit warm entry point ``check(...,
    prepared=...)`` consumes."""
    return PreparedKernels(model)


def _pad_rows(arr: np.ndarray, n: int, fill=0):
    if arr.shape[0] == n:
        return arr
    pad_shape = (n - arr.shape[0],) + arr.shape[1:]
    return np.concatenate([arr, np.full(pad_shape, fill, arr.dtype)])


# --- frontier adapters: the level loop runs identically over an in-RAM
# array or a disk-spilled FrontierReader (storage/frontier) — same global
# offsets, same chunk boundaries, hence bit-identical counts and traces
def _f_rows(f) -> int:
    return f.shape[0] if isinstance(f, np.ndarray) else f.rows


def _f_chunks(f, chunk: int):
    if isinstance(f, np.ndarray):
        for s in range(0, f.shape[0], chunk):
            yield s, f[s : s + chunk]
    else:
        yield from f.iter_chunks(chunk)


def _f_row(f, i: int) -> np.ndarray:
    return f[i] if isinstance(f, np.ndarray) else f.row(i)


def _f_all(f) -> np.ndarray:
    return f if isinstance(f, np.ndarray) else f.read_all()


def walk_trace(trace_store, actions, decode_row, inv_name, depth, idx,
               obs=None, source="ram") -> Violation:
    """Parent-pointer counterexample reconstruction, shared by both engines.

    trace_store[level] = (rows, parent, act): the level's states in discovery
    order, each new state's parent index into the previous level, and the
    action id that produced it.  Walks level `depth` index `idx` back to an
    init state and returns the Violation with the root->violation trace.

    With `obs` (a RunObserver) the walk is one `counterexample` span:
    `source` says where the store lives (`ram` | `disk`), `decode_ms` is the
    wall of the `decode_row` calls and `walk_ms` the rest (the pointer
    reads).
    """
    span = obs.open_span("counterexample", invariant=inv_name, depth=depth,
                         source=source) if obs is not None else None
    t_walk = time.perf_counter()
    decode_s = 0.0

    def decode(row):
        nonlocal decode_s
        t = time.perf_counter()
        state = decode_row(row)
        decode_s += time.perf_counter() - t
        return state

    chain = []
    i = idx
    for d in range(depth, 0, -1):
        rows, parent, act = trace_store[d]
        chain.append((actions[int(act[i])].name, decode(rows[i])))
        i = int(parent[i])
    rows0, _, _ = trace_store[0]
    chain.append(("<init>", decode(rows0[i])))
    chain.reverse()
    if span is not None:
        walk_s = time.perf_counter() - t_walk - decode_s
        span.finish(trace_len=len(chain), walk_ms=round(walk_s * 1e3, 3),
                    decode_ms=round(decode_s * 1e3, 3))
    return Violation(invariant=inv_name, depth=depth, state=chain[-1][1], trace=chain)


def check(
    model: Model,
    max_depth: Optional[int] = None,
    max_states: Optional[int] = None,
    store_trace: bool = True,
    min_bucket: int = 256,
    check_invariants: bool = True,
    progress=None,
    collect_levels: Optional[list] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 3,
    check_deadlock: bool = False,
    stats_path: Optional[str] = None,
    visited_backend: str = "device",
    chunk_size: int = 32768,
    visited_capacity_hint: Optional[int] = None,
    visited_capacity_exact: Optional[int] = None,
    compact_shift: int = 2,
    compact_gate: int = 4096,
    pipeline: Optional[str] = None,
    mem_budget=None,
    spill_dir: Optional[str] = None,
    store: str = "auto",
    disk_budget=None,
    run=None,
    prepared: Optional[PreparedKernels] = None,
    collect_trace: Optional[list] = None,
    governor: Optional[ResourceGovernor] = None,
    integrity_shadow: Optional[float] = None,
    overlap: Optional[bool] = None,
    seed: Optional[dict] = None,
) -> CheckResult:
    """Breadth-first exhaustive check of `model`. Stops at first violation.

    check_deadlock: when True (TLC's CHECK_DEADLOCK TRUE), a reachable state
    with no enabled action is reported as a violation of the pseudo-invariant
    "Deadlock" (CONSTRAINT pruning does not mask enabledness).  Default off:
    the bounded corpus models deadlock by design (SURVEY.md §2.4).

    stats_path: append one JSON line per BFS level (depth, frontier size,
    enabled candidates, new/dup counts, per-action enablement histogram,
    wall ms) — the PROGRESS.jsonl observability stream (SURVEY.md §5); the
    same records land in CheckResult.stats["levels"].

    visited_backend:
    - "device": sorted fingerprint pair set in HBM — dedup by lexsort +
      binary-search probe + rank-scatter merge.  The merge rebuilds
      O(capacity) per chunk, which dominates at small frontiers.
    - "device-hash": open-addressing hash table in HBM (ops/hashset) —
      insert-or-find in O(batch · expected-probes) per chunk, independent
      of table size; no sort, no merge.  The recommended device-resident
      backend.
    - "host": the native C++ open-addressing FpSet (native/fpset.cpp) does
      ALL dedup on the host — the TLC-FPSet spill mode for state spaces
      whose fingerprints outgrow device memory (device HBM then holds only
      O(chunk x fanout) transient data), and the fastest mode on a CPU
      "device".
    With hashed (non-exact64) fingerprints all backends accept TLC's usual
    64-bit collision risk; all three produce identical counts and traces.

    chunk_size: frontiers larger than this stream through the compiled step
    in pieces (cross-chunk dedup via the shared visited set), bounding the
    number of jit-compiled shapes and peak device memory regardless of
    state-space size.

    visited_capacity_hint: preallocate the device visited set for ~this many
    states (plus one chunk of insert headroom) so capacity doubling (one
    recompile per doubling) never triggers on runs whose state-space size
    is roughly known.

    visited_capacity_exact: preallocate the device visited set at exactly
    this capacity (no headroom added) — for callers replaying a PRIOR
    run's final capacity (PreparedKernels.capacity_hint), where an exact
    fixed point is what keeps every warm step-cache key identical.

    compact_shift: two-phase expansion — sweep guards over the full padded
    lattice (state updates dead-code-eliminated), then run each action's
    update+pack and the sort/probe/merge at 1/2^compact_shift of the lattice
    width (only a few percent is ever enabled).  Purely a performance knob:
    a chunk whose enabled count overflows a compact buffer is re-run at
    double the width (the step reports overflow; results stay exact).  0
    disables compaction.

    pipeline: level-pipeline implementation (engine/pipeline.py; the
    jax-free registry in pipeline_registry.py is the validated name
    set — unknown names raise, `cli pipelines --list` describes them):
    "fused" (default; $KSPEC_PIPELINE overrides) = successor mega-kernels
    — per chunk, ONE batched guard-predicate-matrix launch over the
    (frontier x choice) lattice, C-speed host compaction into one shared
    data-driven-width buffer, and ONE update-skeleton launch
    (gather -> action update -> CONSTRAINT -> pack -> fingerprint), i.e.
    2 successor launches per chunk instead of one per action;
    "device" = the device-resident level pipeline — a bounded
    lax.while_loop processes EVERY gated chunk of a level inside one
    dispatched program (expansion, in-jit segmented compaction,
    fingerprints, intra-level dedup, verdicts all on-device), i.e. <=2
    successor launches per level.  On the sorted-set "device" backend
    the visited probe + digest folds run in-jit and the O(capacity)
    visited merge runs once per LEVEL instead of once per chunk; on
    the "host" backend (incl. the disk tier) the visited probe is
    DEFERRED to ONE batched host FpSet/tiered-run call per level
    (host syncs O(1)/level instead of O(chunks), serial winner rule
    preserved).  Requires analyzer-proven per-field value hulls
    (analysis.field_hulls — a hard precondition, not env-disablable
    like the build gate); the "device-hash" backend and any other
    unmet precondition degrade to the fused per-chunk ladder
    (stats["device"]["fallback"] records why, naming the backend);
    "legacy" = the historical per-action monolithic step.  All are
    bit-identical — same level counts, duplicate accounting,
    first-violation rule, trace values and digest chains
    (tests/test_pipeline.py, tests/test_integrity.py); a fused program
    that fails to compile degrades the run to legacy (recorded in
    stats["degradations"] and stats["pipeline_fallback"]).
    compact_gate: frontier-bucket floor below which every pipeline runs
    the uncompacted full-lattice path (small levels; default 4096).

    checkpoint_dir: when set, the (visited set, frontier, level counters) are
    persisted every `checkpoint_every` BFS levels (default 1 = per level; a
    crash loses at most checkpoint_every-1 levels of work) and a run restarts
    from the last saved level if a checkpoint exists — the natural fit for a
    level-synchronous engine (SURVEY.md §5 "Checkpoint / resume"; TLC keeps
    this externally).  Checkpoints are hardened (resilience.checkpoints):
    every array is checksummed into an in-file manifest, the newest
    `checkpoint_keep` generations rotate under atomic promotes, and a
    corrupt/truncated newest generation falls back automatically to the
    newest verifying one instead of aborting the run.  Checkpointed runs
    don't retain parent-pointer traces across restarts, so store_trace is
    forced off — a violation found after a resume reports the violating
    state with an EMPTY trace (known trace-loss limitation: re-deriving the
    path would need a re-walk from the init states; docs/resilience.md).

    Fault injection (resilience.faults): a `KSPEC_FAULT` plan exercises the
    recovery paths deterministically — level-boundary / checkpoint-write
    crashes, mid-merge disk-tier crashes (`crash@merge:N`), checkpoint
    corruption, transient backend errors (retried with bounded exponential
    backoff; count in result.stats["transient_retries"]) and the
    escalated-compile OOM (degrades to the uniform compact path; recorded
    in result.stats["degradations"]).

    Out-of-core storage (storage/): `store` = "auto" | "ram" | "disk".
    "disk" (or "auto" with a `mem_budget`) activates the disk tier for
    state spaces that outgrow RAM: the host FpSet is bounded at
    `mem_budget` bytes and spills sorted, bloom-gated fingerprint runs to
    `spill_dir` (periodic k-way merge; lookups touch disk only on probable
    hits), the frontier spills to chunked segments consumed in discovery
    order, and parent pointers go to an append-only on-disk log so
    counterexample traces are reconstructed from the log — including after
    a checkpoint resume (this retires the empty-trace-after-resume
    limitation for this engine).  The disk tier implies
    visited_backend="host" (the disk tier spills the host level of the
    hierarchy; device backends stay the in-HBM hot path) and is
    bit-identical to the in-RAM path: same counts, depths, and trace
    values (tests/test_storage.py forces tiny budgets to prove it).
    Checkpoints record the storage manifest (run names + frontier segment
    offsets) instead of re-serializing state — the disk tier itself is the
    durable state.

    run: an obs.RunContext — correlates this run's stats/spans/metrics
    under one run_id in the run directory (docs/observability.md).  With
    run=None and a bare stats_path the per-level stream is emitted exactly
    as before the obs subsystem existed (the shim contract,
    tests/test_obs.py).

    prepared: a :class:`PreparedKernels` for this model (``prepare``):
    the serving daemon's warm path — every compiled step is re-used, so a
    warm check pays zero trace/compile (its span trace shows zero
    ``compile`` spans), and on the ``device`` pipeline each whole-level
    program's first dispatch is sized from what the last run fed to
    ``note_result`` measured, so none is discarded and re-run.  Must
    wrap the SAME model object.

    collect_trace: external list receiving the per-level trace store
    ``(rows, parent, act)`` tuples (filled only while store_trace is on) —
    the batched multi-config runner (service/batch.py) derives per-job
    counterexample traces from a shared exploration through this.

    governor: a pre-built :class:`ResourceGovernor` to use instead of the
    env-derived one — the serving daemon's per-TENANT budget instances
    (service/scheduler.py); a breach inside this check raises the same
    typed ResourceExhausted without touching any other job's budgets.

    integrity_shadow: sampled shadow re-execution rate in [0, 1]
    ($KSPEC_INTEGRITY_SHADOW is the env twin; default 0 = off).  A
    deterministically sampled chunk is re-executed through an independent
    path BEFORE its outputs are committed — the legacy pipeline for
    fused-gated chunks (counts, new-fingerprint multiset and verdict
    flags must match the fused result bit-for-bit), and the host
    fingerprint oracle (numpy recomputation of every emitted row's
    fingerprint) for every sampled chunk — so silent device/compaction
    corruption is caught in-flight, typed, and never enters a
    checkpoint.  Always-on independent of the rate: the per-level digest
    chain over the new-state fingerprint multiset (stamped into
    checkpoints + verified at every level boundary, on resume, and by
    the offline `cli verify-checkpoint`), the save-time visited-set
    self-check, and read-side storage checksums.  Any failure raises the
    typed :class:`IntegrityError` (CLI exit 76) with the run manifest
    stamped ``integrity-violation`` (resilience.integrity,
    docs/resilience.md).  KSPEC_INTEGRITY=0 disables the whole layer.

    seed: resume-shaped warm start from a VERIFIED prior exploration of
    the same model (the service's persistent state-space cache,
    service/state_cache.py): a dict of ``visited_fps`` (uint64 multiset
    of every visited fingerprint), ``frontier`` (the boundary level's
    packed uint32 rows), ``levels``, ``total``, ``depth`` and
    ``digest_chain`` (the [L, 4] chain array).  The run then starts by
    expanding the boundary at ``depth`` instead of Init — exactly the
    checkpoint-resume semantics, including the limitation: parent
    pointers below the seed do not exist, so ``store_trace`` is forced
    off and a violation found past the seed reports its state with an
    empty trace.  The level-boundary chain verify re-proves the seeded
    frontier against the seeded chain before anything is expanded.
    Counts, levels, verdicts are bit-identical to a cold run of the
    larger bound (tests/test_fleet.py).  Mutually exclusive with
    ``checkpoint_dir`` and the disk tier.

    overlap: async level-pipelined execution ($KSPEC_OVERLAP is the env
    twin; default ON, ``off``/False = the historical serial behavior and
    the bit-identity oracle).  Three overlaps (docs/engine.md § Async
    execution): (1) a staged chunk pipeline — chunk k+1's guard launch
    and chunk k's update-skeleton launch are dispatched before chunk
    k-1's host commit (fingerprint-set insert, arena assembly, digest
    folds) and chunk k+1's host compaction run, so host work drains
    behind the in-flight update-skeleton launch (JAX async dispatch;
    per-chunk ``step`` spans carry dispatch/device-wait attribution); (2) disk-tier spill-run merges run on a background
    worker (storage/tiered.py — lookups keep serving from the immutable
    inputs, adoption and error propagation happen on this thread);
    (3) checkpoint writes move to a writer thread (the engine snapshots
    metadata + digest chain + dumps synchronously; verification, the
    checksummed write and the atomic promote run in the background,
    with ENOSPC/fault errors re-raised here at the next level
    boundary).  Results are bit-identical either way — counts,
    duplicate accounting, first-violation rule, trace values, digest
    chains (tests/test_overlap.py pins the matrix).

    disk_budget: byte budget for the spill + checkpoint directories
    (resilience.resources.ResourceGovernor; KSPEC_DISK_BUDGET is the env
    twin, KSPEC_RSS_BUDGET / KSPEC_LEVEL_DEADLINE arm the RSS and
    per-level-deadline watchdogs).  Crossing the soft fraction triggers
    reclamation (tmp janitor, eager merges, checkpoint-generation prune,
    deletion-barrier flush); a hard breach — or a real/injected ENOSPC
    from any storage writer — performs checkpoint-then-clean-exit: the
    newest consistent state is saved, the run directory is stamped
    `resource-exhausted`, and a typed ResourceExhausted propagates (the
    CLI maps it to exit code 75).  The on-disk state still passes `cli
    verify-checkpoint`, and resuming after the operator frees space is
    bit-identical to an uninterrupted run (tests/test_resources.py).
    """
    t_check = _now()
    spec = model.spec
    # encoding-soundness gate (analysis; KSPEC_ANALYZE=0 disables): an
    # action that can write outside its declared field ranges would be
    # silently truncated by the bit packer — refuse to explore instead
    # of returning a wrong verdict (memoized per model name)
    from ..analysis import require_encoding_sound

    require_encoding_sound(model)
    if prepared is not None and prepared.model is not model:
        raise ValueError("prepared kernels wrap a different model object")
    # TLC's SYMMETRY (Model.symmetry): a state's key is its orbit's
    # (pipeline.fp_stage), which no host twin recomputes from a stored row
    # (resilience/integrity.fingerprint_rows is the PLAIN fingerprint), so
    # whatever validates rows against keys is refused, by name, rather
    # than run on keys it cannot check
    symmetric = model.symmetry is not None
    if symmetric:
        for what, given in (
            ("checkpoint_dir", checkpoint_dir is not None),
            ("seed", seed is not None),
            ("integrity_shadow", bool(integrity_shadow)),
        ):
            if given:
                raise ValueError(
                    f"{model.name}: {what}= is not supported under SYMMETRY "
                    f"{model.symmetry.operator} (a stored row's key is its "
                    "orbit's, and resilience/integrity.fingerprint_rows, "
                    "which validates a checkpoint, a seed and a shadowed "
                    "chunk, recomputes the plain fingerprint); drop the "
                    "SYMMETRY stanza or the option"
                )
    step_builder = prepared.step if prepared is not None else _Step(model)
    K, C = spec.num_lanes, step_builder.C

    # unified telemetry: run_id-stamped stats/spans/metrics when a run
    # context is given; the exact historical stats_path stream otherwise
    # (root span `check` from this function's first line; `check-open`
    # until the first level begins, `check-close` after the last)
    obs_ = RunObserver(run, stats_path, engine="bfs",
                       annotate=jax.profiler.TraceAnnotation)
    obs_.check_begin(t_check, model=model.name)
    io = HostIO(obs_)  # counted transfers + named dispatches

    from ..storage import resolve_store

    use_disk = resolve_store(store, mem_budget)
    want_trace = store_trace
    if use_disk:
        # the disk tier spills the HOST level of the hierarchy; traces
        # ride the on-disk parent log instead of the in-RAM trace store
        visited_backend = "host"
        store_trace = False

    fault = FaultPlan.from_env()
    chunk_retry = ChunkRetryHandler.from_env("[engine]")
    # async overlap layer (overlap.py; $KSPEC_OVERLAP, default on):
    # io_worker carries background spill-run merges, ckpt_worker the
    # async checkpoint writes; the two-slot chunk pipeline below needs
    # no thread (JAX async dispatch is the worker)
    from ..overlap import (
        AsyncWorker,
        close_workers,
        overlap_enabled,
        worker_counters,
    )

    overlap_on = overlap_enabled(overlap)
    io_worker = AsyncWorker("kspec-io") if overlap_on else None
    ckpt_worker = (
        AsyncWorker("kspec-ckpt")
        if overlap_on and checkpoint_dir is not None
        else None
    )

    def _shutdown_async(drain: bool) -> None:
        close_workers((io_worker, ckpt_worker), drain)
    # state-integrity defense (resilience.integrity): always-on level
    # digest chain + sampled shadow re-execution; KSPEC_INTEGRITY=0 is
    # the kill switch (bench baselines, emergency escape hatch)
    chain = _integ.LevelDigestChain() if _integ.enabled() else None
    shadow_rate = (
        _integ.shadow_rate(integrity_shadow)
        if chain is not None and not symmetric  # (an env-set rate too)
        else 0.0
    )
    ckpt_store = None  # built once ckpt_ident is known
    # newest durably checkpointed level (None = not checkpointing):
    # level-crash faults defer until the target level is checkpointed so
    # a supervised restart converges (FaultPlan.crash)
    last_ckpt_depth = None
    if checkpoint_dir is not None:
        store_trace = False
        last_ckpt_depth = 0
        checkpoint_every = max(1, int(checkpoint_every))
    if seed is not None:
        if checkpoint_dir is not None:
            raise ValueError(
                "seed= and checkpoint_dir are mutually exclusive (a seed "
                "IS a resume; layering the two would race their chains)"
            )
        if use_disk:
            raise ValueError("seed= requires the in-RAM store")
        # same limitation as checkpoint resume: parent pointers below the
        # seed do not exist, so traces cannot be reconstructed
        store_trace = False

    t0 = time.perf_counter()
    sp_ = obs_.open_span("init-states")
    init_packed, hi0, lo0 = step_builder.init_rows(io, obs_)
    sp_.finish()
    n0 = init_packed.shape[0]

    if visited_backend not in ("device", "host", "device-hash"):
        raise ValueError(
            "visited_backend must be 'device', 'device-hash' or 'host', "
            f"got {visited_backend!r}"
        )
    host_set = None
    ht_hi = ht_lo = ht_claim = None  # device-hash table (ops/hashset)
    hash_n = 0
    # ht_claim is allocated LAZILY at the insert site, so table (re)builds
    # just reset it to None.

    def _u64(hi, lo):
        return (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
            lo
        ).astype(np.uint64)

    disk = None
    ephemeral_spill = None
    if visited_backend == "host":
        if use_disk:
            from ..storage import (
                DEFAULT_MEM_BUDGET,
                DiskTierStore,
                parse_mem_budget,
            )

            budget = (
                parse_mem_budget(mem_budget)
                if mem_budget is not None
                else DEFAULT_MEM_BUDGET
            )
            sd = spill_dir or (
                os.path.join(checkpoint_dir, "spill") if checkpoint_dir else None
            )
            if sd is None:
                import tempfile

                # anonymous spill space: removed after a completed run (a
                # crashed one cannot be resumed without a checkpoint, so
                # its temp data is dead weight either way)
                sd = tempfile.mkdtemp(prefix="kspec-spill-")
                ephemeral_spill = sd
            disk = DiskTierStore(
                sd,
                budget,
                lanes=K,
                gc_barrier=checkpoint_keep if checkpoint_dir else 0,
                seg_rows=int(
                    os.environ.get("KSPEC_SPILL_SEG_ROWS", str(1 << 18))
                ),
                runs_per_merge=int(
                    os.environ.get("KSPEC_SPILL_RUNS_PER_MERGE", "8")
                ),
                fault_plan=fault,
                trace=want_trace or checkpoint_dir is not None,
                merge_worker=io_worker,
            )
            host_set = disk.fpset  # init fps inserted at start_fresh/resume
        else:
            from ..native import FpSet

            host_set = FpSet()
            host_set.insert(_u64(hi0, lo0))
        vcap = 64  # placeholder shapes; the device never holds the visited set
        vhi = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
        vlo = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
        vn = jnp.int32(0)
    elif visited_backend == "device-hash":
        ht_hi, ht_lo = hashset.table_from_pairs(
            np.asarray(hi0),
            np.asarray(lo0),
            min_cap=_next_pow2(
                max(
                    _HASH_MIN_CAP,
                    4 * (visited_capacity_hint
                         or visited_capacity_exact or 0),
                )
            ),
        )
        ht_claim = None
        hash_n = n0
        vcap = 64  # placeholder shapes for the step signature
        vhi = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
        vlo = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
        vn = jnp.int32(0)
    else:
        order = np.lexsort((np.asarray(lo0), np.asarray(hi0)))
        chunk_clamped = _next_pow2(max(min_bucket, chunk_size))
        # hint: ~state count, padded with one chunk's worth of insert
        # headroom so the growth check never fires on a roughly-known run.
        # exact: a capacity floor (a prior run's FINAL vcap) used
        # verbatim, so warm serving runs land on the exact same capacity —
        # same step-cache keys, zero recompiles (PreparedKernels)
        vcap = _next_pow2(
            max(
                n0,
                min_bucket * C,
                2,
                visited_capacity_exact or 0,
                (visited_capacity_hint + chunk_clamped * C)
                if visited_capacity_hint
                else 0,
            )
        )
        vhi = np.full(vcap, 0xFFFFFFFF, np.uint32)
        vlo = np.full(vcap, 0xFFFFFFFF, np.uint32)
        vhi[:n0] = np.asarray(hi0)[order]
        vlo[:n0] = np.asarray(lo0)[order]
        vhi, vlo = io.put(vhi), io.put(vlo)
        vn = jnp.int32(n0)

    levels = [n0]
    total = n0
    # per level: (packed[np], parent[np], act[np]); aliased to the
    # caller's list when collect_trace is given (service/batch.py)
    trace_store = collect_trace if collect_trace is not None else []
    trace_store.clear()
    if store_trace:
        trace_store.append((init_packed, np.full(n0, -1), np.full(n0, -1)))
    if collect_levels is not None:
        collect_levels.append(init_packed)

    def decode_state(packed_row: np.ndarray):
        s = {k: np.asarray(v) for k, v in spec.unpack(jnp.asarray(packed_row)).items()}
        return model.decode(s) if model.decode else s

    def _drop_ephemeral_spill():
        if ephemeral_spill is not None:
            import shutil

            shutil.rmtree(ephemeral_spill, ignore_errors=True)

    def build_violation(inv_name, depth, idx):
        if disk is not None and disk.has_trace(depth):
            # reconstruct from the on-disk parent log: O(depth) single-
            # record reads through the mmap'd level segments — this is
            # what makes traces survive checkpoint/resume
            return walk_trace(
                disk.plog.view(), model.actions, decode_state, inv_name,
                depth, idx, obs=obs_, source="disk",
            )
        return walk_trace(trace_store, model.actions, decode_state, inv_name,
                          depth, idx, obs=obs_)

    def have_trace(depth) -> bool:
        return store_trace or (disk is not None and disk.has_trace(depth))

    def first_violation(rows: np.ndarray):
        """The invariant pass over host-held rows (the initial states; the
        frontier a cut left unexpanded): one launch of a cached program
        per power-of-two row bucket -> (invariant, row index) or None."""
        sp_ = obs_.open_span("host-invariants", rows=rows.shape[0])
        bad = step_builder.first_violation(
            ("hinv",), _next_pow2(max(rows.shape[0], min_bucket)),
            rows, io, obs_,
        )
        sp_.finish()
        return bad

    # invariants on init states
    if check_invariants and model.invariants:
        bad0 = first_violation(init_packed)
        if bad0 is not None:
            inv, idx = bad0
            dt = time.perf_counter() - t0
            viol = Violation(
                invariant=inv.name,
                depth=0,
                state=decode_state(init_packed[idx]),
                trace=[("<init>", decode_state(init_packed[idx]))],
            )
            _drop_ephemeral_spill()
            _shutdown_async(drain=True)
            res = CheckResult(
                model.name, levels, total, 0, viol, dt, total / max(dt, 1e-9)
            )
            obs_.finish(res)
            obs_.close()
            return res

    frontier_np = init_packed
    depth = 0
    violation = None
    result_stats: dict = {}
    if symmetric:
        result_stats["symmetry"] = model.symmetry.describe()
    # the work counts behind the enabled counts of a program's vector
    n_work = work_width(model, visited_backend)
    collect_stats = obs_.collect
    obs_.config(
        model=model.name,
        visited_backend=visited_backend,
        store="disk" if use_disk else "ram",
        mem_budget=mem_budget,
        chunk_size=chunk_size,
        checkpoint_dir=checkpoint_dir,
        **device_stamp(),
    )

    # identity stamp: a checkpoint may only resume the same model, constants,
    # invariant selection, and deadlock setting (a resume never re-checks
    # already-explored levels, so a stricter check must start fresh)
    inv_names = ",".join(sorted(i.name for i in model.invariants)) if check_invariants else "-"
    ckpt_ident = (
        f"{model.name}|lanes={spec.num_lanes}|backend={visited_backend}|"
        f"inv={inv_names}|dl={check_deadlock}|"
        + ",".join(f"{f.name}:{f.shape}:{f.lo}:{f.hi}" for f in spec.fields)
        + ("|store=disk" if use_disk else "")
    )
    def _spill_ref_errors(arrays: dict) -> list:
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

    resumed = False
    resumed_chain_arr = None
    if checkpoint_dir is not None:
        ckpt_store = CheckpointStore(
            checkpoint_dir,
            "bfs_checkpoint.npz",
            ident=ckpt_ident,
            keep=checkpoint_keep,
            fault_plan=fault,
            # chain-mismatch generations (CRC-consistent content
            # corruption) fall back exactly like checksum failures: the
            # run resumes from the newest CHAIN-VERIFIED generation
            validators=(
                (_integ.checkpoint_chain_errors, _spill_ref_errors)
                if chain is not None
                else (_spill_ref_errors,)
            ),
        )
        if ckpt_worker is not None:
            ckpt_store.attach_writer(ckpt_worker)
        loaded = ckpt_store.load()
        if loaded is not None:
            resumed = True
            snap, _, _gen = loaded
            if "digest_chain" in snap:
                resumed_chain_arr = snap["digest_chain"]
            if disk is not None:
                # the checkpoint references the disk tier, it does not
                # contain it: reopen the manifest's runs + frontier
                # segments IN PLACE (host_set aliases disk.fpset),
                # re-seed the budget-bounded hot set
                disk.resume(
                    json.loads(str(snap["spill_manifest"])), snap["host_fps"]
                )
                frontier_np = disk.pending()
            elif host_set is not None:
                frontier_np = snap["frontier"]
                from ..native import FpSet

                host_set = FpSet(initial_capacity=max(64, 2 * len(snap["host_fps"])))
                host_set.insert(snap["host_fps"])
            elif ht_hi is not None:
                frontier_np = snap["frontier"]
                live_hi = snap["hash_hi"]
                live_lo = snap["hash_lo"]
                hash_n = live_hi.shape[0]
                ht_hi, ht_lo = hashset.table_from_pairs(
                    live_hi, live_lo, min_cap=_HASH_MIN_CAP
                )
                ht_claim = None
            else:
                frontier_np = snap["frontier"]
                vcap = int(snap["vcap"])
                n = int(snap["vn"])
                pad = np.full(vcap - n, 0xFFFFFFFF, np.uint32)
                vhi = jnp.asarray(np.concatenate([snap["vhi"], pad]))
                vlo = jnp.asarray(np.concatenate([snap["vlo"], pad]))
                vn = jnp.int32(n)
            levels = snap["levels"].tolist()
            total = int(snap["total"])
            depth = int(snap["depth"])
            last_ckpt_depth = depth
            # crash faults at or below the resume level count as fired
            # (a supervised restart must converge, not crash-loop)
            fault.set_start_depth(depth)

    seeded = False
    if seed is not None:
        # warm start from a verified cached exploration (state_cache):
        # structurally identical to the checkpoint-resume path above,
        # sourced from the portable artifact instead of a generation.
        # The visited set is reconstructed from the u64 fingerprint
        # multiset — every backend's visited state is a pure function of
        # it — and the boundary frontier is expanded next, so the level
        # loop continues exactly where the cached run's bound cut it.
        seeded = True
        seed_fps = np.sort(
            np.ascontiguousarray(np.asarray(seed["visited_fps"], np.uint64))
        )
        s_hi = (seed_fps >> np.uint64(32)).astype(np.uint32)
        s_lo = (seed_fps & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        frontier_np = np.ascontiguousarray(
            np.asarray(seed["frontier"], np.uint32)
        ).reshape(-1, K)
        n_seed = int(seed_fps.shape[0])
        if visited_backend == "host":
            from ..native import FpSet

            host_set = FpSet(initial_capacity=max(64, 2 * n_seed))
            host_set.insert(seed_fps)
        elif visited_backend == "device-hash":
            ht_hi, ht_lo = hashset.table_from_pairs(
                s_hi, s_lo, min_cap=_HASH_MIN_CAP
            )
            ht_claim = None
            hash_n = n_seed
        else:
            seed_chunk = _next_pow2(max(min_bucket, chunk_size))
            vcap = _next_pow2(
                max(
                    n_seed + seed_chunk * C,
                    min_bucket * C,
                    2,
                    visited_capacity_exact or 0,
                )
            )
            pad = np.full(vcap - n_seed, 0xFFFFFFFF, np.uint32)
            # u64 sort order == (hi, lo) lexsort order: the split lanes
            # land exactly as the sorted-set backend stores them
            vhi = jnp.asarray(np.concatenate([s_hi, pad]))
            vlo = jnp.asarray(np.concatenate([s_lo, pad]))
            vn = jnp.int32(n_seed)
        levels = [int(v) for v in seed["levels"]]
        total = int(seed["total"])
        depth = int(seed["depth"])
        # crash faults at or below the seed level count as fired, the
        # same convergence rule as a checkpoint resume
        fault.set_start_depth(depth)

    if disk is not None and not resumed:
        # fresh out-of-core run: the spill directory namespace belongs to
        # this run (stale runs must not pre-seed the visited set)
        disk.start_fresh(init_packed, np.asarray(_u64(hi0, lo0)))
        frontier_np = disk.pending()

    if chain is not None:
        if seeded:
            # the cached chain IS the continuation proof, exactly like a
            # resumed checkpoint's: the level-boundary verify below must
            # prove the seeded frontier against its sealed entry before
            # anything is expanded
            chain = (
                _integ.LevelDigestChain.from_array(seed["digest_chain"])
                if seed.get("digest_chain") is not None
                else _integ.LevelDigestChain.from_levels(levels)
            )
        elif resumed:
            # the chain IS the continuation proof: a resumed run extends
            # the stamped chain, and the frontier verify below checks the
            # loaded frontier against its sealed entry.  Pre-integrity
            # checkpoints rebuild an unanchored chain (counts only)
            chain = (
                _integ.LevelDigestChain.from_array(resumed_chain_arr)
                if resumed_chain_arr is not None
                else _integ.LevelDigestChain.from_levels(levels)
            )
        else:
            chain.fold(_integ.pair_u64(hi0, lo0))
            chain.seal(0, n0)

    def _chain_stamp() -> dict:
        # an UNANCHORED chain (rebuilt from a pre-integrity checkpoint's
        # counts — its digests are unknown, stored as zeros) must never
        # be stamped: a stamped zero-digest chain would fail the
        # cumulative visited check on the NEXT load and permanently
        # reject every post-upgrade generation.  Such runs keep saving
        # chain-less checkpoints; anchoring restarts with the next fresh
        # run
        return (
            {"digest_chain": chain.to_array()}
            if chain is not None and chain.anchored
            else {}
        )

    def _readback_chain(path: str, at_depth: int) -> None:
        if chain is not None and chain.anchored:
            _integ.readback_chain(path, depth=at_depth)

    # async-checkpoint bookkeeping (KSPEC_OVERLAP): `last_ckpt_depth`
    # stays the SUBMITTED depth (save-cadence decisions), while
    # `ckpt_durable_depth` advances only when a write has atomically
    # promoted — crash-fault deferral and flip gating key on durability,
    # so a deferred crash can never fire ahead of the checkpoint that
    # makes its restart converge.  `ckpt_barrier_tokens` carries each
    # in-flight save's deletion-barrier watermark (DeferredDeleter.mark):
    # the barrier advances for exactly the files scheduled BEFORE that
    # save's snapshot, preserving the sync ordering contract.
    ckpt_durable_depth = last_ckpt_depth
    ckpt_barrier_tokens: list = []
    sync_io_s = 0.0  # wall spent on SYNChronous checkpoint writes

    def _ckpt_reap(completed) -> None:
        nonlocal ckpt_durable_depth
        for d, _path in completed:
            ckpt_durable_depth = (
                d if ckpt_durable_depth is None
                else max(ckpt_durable_depth, d)
            )
            if disk is not None:
                tok = ckpt_barrier_tokens.pop(0) if ckpt_barrier_tokens \
                    else None
                disk.fpset.deleter.on_save(upto=tok)

    def _ckpt_poll(block: bool = False) -> None:
        # join point for async saves: surfaces writer errors (typed
        # ENOSPC, injected crashes) on the engine thread and advances
        # the durable-depth + deletion-barrier bookkeeping
        if ckpt_worker is None or ckpt_store is None:
            return
        _ckpt_reap(
            ckpt_store.drain_async() if block else ckpt_store.poll_async()
        )

    def _save_checkpoint(sync: bool = False):
        # The async-checkpoint split (docs/resilience.md): everything
        # mutable is SNAPSHOTTED here, synchronously — level metadata,
        # the digest chain, the visited dump (a fresh array from every
        # backend), a copy of the frontier — and the checksummed write,
        # rotation and atomic promote run on the writer thread.  The
        # save-time chain verification moves to the writer too, still
        # BEFORE the promote (detected corruption never enters a
        # checkpoint); ENOSPC and injected faults re-raise at the next
        # _ckpt_poll, preserving the typed exits.
        nonlocal ckpt_durable_depth, sync_io_s
        run_async = ckpt_worker is not None and not sync
        t_sync0 = time.perf_counter()
        # only the live prefix of the visited set is saved (the sentinel
        # padding is rebuilt on resume from vcap/vn); uncompressed — live
        # fingerprints are high-entropy and zlib only burns time
        n = int(vn)
        d_save = depth
        levels_arr = np.asarray(levels)
        # flip injections are gated on an ANCHORED chain: they rehearse
        # detection, and an unanchored chain (pre-integrity resume)
        # cannot detect — injecting there would just silently corrupt
        if chain is not None and chain.anchored and fault.flip(
            "ckpt", d_save, ckpt_depth=ckpt_durable_depth
        ):
            # CRC-consistent metadata corruption: the manifest is built
            # AFTER this flip, so every per-array checksum passes over
            # the corrupt content — only the digest chain flags it
            levels_arr = levels_arr.copy()
            _integ.flip_bit(levels_arr)

        def _dispatch(arrays: dict, pre_write=None, barrier: bool = False):
            nonlocal ckpt_durable_depth, sync_io_s
            if run_async:
                if barrier:
                    ckpt_barrier_tokens.append(disk.fpset.deleter.mark())
                ckpt_store.save_async(
                    d_save, arrays, pre_write=pre_write,
                    after_promote=lambda p: _readback_chain(p, d_save),
                )
                return
            if pre_write is not None:
                pre_write()
            path = ckpt_store.save(d_save, arrays)
            if barrier:
                # a new durable generation exists: advance the deferred-
                # deletion barrier (merged-away runs / consumed frontier
                # segments older than every retained generation unlink)
                disk.on_checkpoint_saved()
            _readback_chain(path, d_save)
            ckpt_durable_depth = (
                d_save if ckpt_durable_depth is None
                else max(ckpt_durable_depth, d_save)
            )
            sync_io_s += time.perf_counter() - t_sync0

        if disk is not None:
            # the disk tier IS the durable state: record the run manifest
            # + frontier-segment offsets + the (budget-bounded) hot dump,
            # never the runs/segments themselves.  (The hot dump is a
            # SUBSET of the visited set, so the cumulative-digest
            # self-check does not apply here — the spilled runs carry
            # their own read-side-verified CRCs instead.)
            _dispatch(
                dict(
                    spill_manifest=json.dumps(disk.manifest()),
                    host_fps=disk.fpset.hot_dump(),
                    vcap=vcap,
                    levels=levels_arr,
                    total=total,
                    **_chain_stamp(),
                ),
                barrier=True,
            )
            return
        if host_set is not None:
            extra = {"host_fps": host_set.dump()}
            pk = "host_fps"
        elif ht_hi is not None:
            th = np.asarray(ht_hi)
            tl = np.asarray(ht_lo)
            live = ~((th == hashset.SENT) & (tl == hashset.SENT))
            extra = {"hash_hi": th[live], "hash_lo": tl[live]}
            pk = "hash_hi"
        else:
            extra = {
                "vhi": np.asarray(vhi[:n]),
                "vlo": np.asarray(vlo[:n]),
                "vn": n,
            }
            pk = "vhi"
        pre_write = None
        if chain is not None and chain.anchored:
            if fault.flip("fpset", d_save, ckpt_depth=ckpt_durable_depth):
                corrupted = np.array(extra[pk], copy=True)
                _integ.flip_bit(corrupted)
                extra[pk] = corrupted
            if host_set is not None:
                dump_fps = np.asarray(extra["host_fps"], np.uint64)
            elif ht_hi is not None:
                dump_fps = _integ.pair_u64(extra["hash_hi"], extra["hash_lo"])
            else:
                dump_fps = _integ.pair_u64(extra["vhi"], extra["vlo"])
            # save-time self-check: the dump must digest to the chain's
            # running total BEFORE the write — corruption detected here
            # never enters a checkpoint.  Async: the chain is snapshotted
            # now (it keeps evolving on this thread) and the check runs
            # on the writer, still pre-promote.
            chain_snap = (
                _integ.LevelDigestChain.from_array(chain.to_array())
                if run_async
                else chain
            )

            def pre_write(chain_snap=chain_snap, dump_fps=dump_fps):
                _integ.count_check()
                chain_snap.verify_visited(dump_fps, depth=d_save)

        frontier_arr = frontier_np
        if run_async and isinstance(frontier_arr, np.ndarray):
            # the live frontier buffer stays mutable on this thread
            # (arena growth, flip injection) — the writer gets a copy
            frontier_arr = np.array(frontier_arr, copy=True)
        _dispatch(
            dict(
                frontier=frontier_arr,
                vcap=vcap,
                levels=levels_arr,
                total=total,
                **extra,
                **_chain_stamp(),
            ),
            pre_write=pre_write,
        )

    chunk = _next_pow2(max(min_bucket, chunk_size))
    chunk_floor = _next_pow2(max(32, min_bucket))

    # Resource governance (resilience.resources): disk/RSS budgets + the
    # per-level deadline watchdog, with soft-breach reclamation and a
    # typed checkpoint-then-clean-exit on hard breach.  A caller-supplied
    # governor (the serving daemon's per-tenant instances) takes
    # precedence over the env-derived one
    if governor is None:
        governor = ResourceGovernor.from_env(
            disk_budget=disk_budget,
            watch_dirs=[disk.dir if disk is not None else None, checkpoint_dir],
            fault_plan=fault,
        )

    def _final_save():
        # checkpoint-then-clean-exit: persist the just-completed level
        # even off the checkpoint_every cadence, so the operator resumes
        # from the breach point, not checkpoint_every-1 levels earlier.
        # Synchronous + drained: the typed exit's contract is a DURABLE
        # state, so the async tail is joined first
        nonlocal last_ckpt_depth
        if ckpt_store is None:
            return
        _ckpt_poll(block=True)
        if last_ckpt_depth != depth or ckpt_durable_depth != depth:
            _save_checkpoint(sync=True)
            last_ckpt_depth = depth

    def _reclaim():
        # soft-breach reclamation, in dependency order (docs/resilience.md):
        # quiesce background work -> tmp janitor -> eager run merge ->
        # fresh checkpoint (references the merged state) -> prune older
        # generations -> flush the deletion barrier (everything still
        # pending was referenced only by the generations just pruned).
        # The quiesce (inside sweep_tmp/reclaim_merge/flush_deleted and
        # the blocking ckpt poll here) is what keeps a reclaim from
        # racing a background merge promote or an in-flight checkpoint
        # write (PR 10 small fix; regression-tested)
        nonlocal last_ckpt_depth
        merged = False
        if disk is not None:
            disk.sweep_tmp()
            merged = disk.reclaim_merge()
        if ckpt_store is not None:
            _ckpt_poll(block=True)
            # skip the save when the periodic one just ran at this depth
            # and no merge changed the on-disk state (the newest gen
            # already references everything the flush keeps) — the
            # pressure path is exactly where write bandwidth is scarcest
            if merged or last_ckpt_depth != depth or \
                    ckpt_durable_depth != depth:
                _save_checkpoint(sync=True)
                last_ckpt_depth = depth
            ckpt_store.prune(keep_gens=1)
            if disk is not None:
                disk.flush_deleted()

    # Adaptive per-action compact sizing (two-phase expansion, SURVEY §2.3):
    # enablement density varies two orders of magnitude across actions
    # (deep 5-broker chunks: LeaderWrite/Truncate at 26-29% of their
    # lattice vs fenced ISR mutations at <0.1%).  The policy — uniform
    # shift until a uniform attempt overflows, then measured high-water
    # widths with learned floors — lives in AdaptiveCompact, shared with
    # the sharded engine.
    adapt = AdaptiveCompact(model.actions, compact_shift,
                            bucket_gate=compact_gate)

    def _degrade_chunk():
        # device RESOURCE_EXHAUSTED: halve the streaming chunk size for
        # the rest of the run (ChunkRetryHandler's degradation contract)
        nonlocal chunk
        chunk = max(chunk_floor, chunk >> 1)

    # The level-pipeline: per-chunk expand/squeeze/fingerprint (+ the
    # device backend's in-jit dedup) behind one interface — the
    # device-resident whole-level program, the fused 2-launch
    # mega-kernel path or the legacy per-action path
    # (engine/pipeline.py; all bit-identical)
    pipe = make_pipeline(
        resolve_pipeline(pipeline),
        step_builder=step_builder,
        model=model,
        adapt=adapt,
        chunk_retry=chunk_retry,
        fault=fault,
        check_invariants=check_invariants,
        visited_backend=visited_backend,
        on_degrade_chunk=_degrade_chunk,
        compact_shift=compact_shift,
        compact_gate=compact_gate,
        check_deadlock=check_deadlock,
        io=io,
    )
    if getattr(pipe, "name", "") == "device" and prepared is not None:
        # the warm protocol's second fixed point: the level programs'
        # first dispatches start where the last run of this prepared
        # model ended (PreparedKernels.level_high_waters)
        pipe.seed_high_waters(prepared.level_high_waters)
    if getattr(pipe, "name", "") == "device" and shadow_rate > 0 and \
            pipe.device_fallback is None:
        # shadow re-execution replays single chunks from their pre-chunk
        # visited state — a state the whole-level program never
        # materializes.  The documented ladder: shadowed runs take the
        # fused per-chunk path (docs/engine.md § Device-resident level
        # pipeline)
        pipe.device_fallback = (
            "integrity shadow re-execution needs per-chunk replay"
        )

    def _shadow_exec(piece, fp_n, bucket, start, pre_v, cvcap,
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
        from ..obs import metrics as _met

        t0 = _now()
        main_fps = _integ.pair_u64(
            np.asarray(out_hi[:nn]), np.asarray(out_lo[:nn])
        )
        rows = np.asarray(out[:nn])
        oracle = _integ.fingerprint_rows(rows, spec.exact64)
        mode = "host-oracle"
        if not np.array_equal(oracle, main_fps):
            bad = int(np.argmax(oracle != main_fps))
            raise IntegrityError(
                "shadow",
                f"host fingerprint oracle mismatch at depth {depth} chunk "
                f"start {start} row {bad}: recomputed {int(oracle[bad]):#x}"
                f" != emitted {int(main_fps[bad]):#x}",
                depth=depth,
            )
        # the device pipeline delegates shadowed runs to its fused
        # per-chunk ladder, so the cross-exec gate reads the FUSED
        # implementation either way
        fp = getattr(pipe, "fused", pipe)
        if (
            getattr(fp, "name", "") == "fused"
            and not getattr(fp, "fallback", False)
            and fp._gate(bucket)
        ):
            mode = "legacy-cross"
            (l_out, _lp, _la, l_new, _h1, _h2, _h3, l_viol, _vi,
             l_dl, _di, _ae, l_hi, l_lo, _ag, _launch, _lanes) = (
                fp.legacy.run_chunk(
                    piece, fp_n, bucket, depth, *pre_v, cvcap
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
                    f"legacy cross-execution diverged at depth {depth} "
                    f"chunk start {start}: fused emitted {nn} "
                    f"fingerprints, legacy {ln} (or multiset digests "
                    f"differ) — one of the two pipelines produced "
                    f"corrupt successors",
                    depth=depth,
                )
            if not np.array_equal(
                np.asarray(viol_any), np.asarray(l_viol)
            ) or bool(dl_any) != bool(l_dl):
                raise IntegrityError(
                    "shadow",
                    f"verdict flags diverged between fused and legacy at "
                    f"depth {depth} chunk start {start}",
                    depth=depth,
                )
        _met.inc("kspec_integrity_shadow_total")
        _integ.count_check()
        obs_.chunk_span(
            "shadow", t0,
            depth=depth, start=start, rows=int(fp_n), mode=mode,
        )


    def _grow_arena(nn: int) -> None:
        """Ensure the level arena holds >= nn more rows past a_w (the
        all-novel worst case insert_compact writes unchecked) — ONE
        growth policy for the per-chunk and device-level commits.
        Growth copies only the filled prefix (amortized O(level))."""
        nonlocal a_rows, a_parent, a_act, a_cap
        if a_w + nn <= a_cap:
            return
        a_cap = max(2 * a_cap, a_w + nn)
        na = np.empty((a_cap, K), np.uint32)
        na[:a_w] = a_rows[:a_w]
        a_rows = na
        npar = np.empty(a_cap, np.int64)
        npar[:a_w] = a_parent[:a_w]
        a_parent = npar
        nact = np.empty(a_cap, np.int32)
        nact[:a_w] = a_act[:a_w]
        a_act = nact

    def _take_rows(outs, nn: int):
        """The device-backend commit's slices of a chunk's outputs (rows,
        parents, action ids, and the fingerprint lanes where a chain
        folds them), enqueued on the device stream; None where nothing
        is new."""
        if not nn:
            return None
        # finalize()'s tuple: out, out_parent, out_act ... out_hi, out_lo
        return tuple(
            io.head(outs[i], nn)
            for i in ((0, 1, 2) + ((12, 13) if chain is not None else ()))
        )

    def _commit_wait(st):
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
        nonlocal verdict, lvl_chunks, lvl_rows_in, lvl_lanes, lvl_ahead
        start, fp_n, finalize, t_staged, was_ahead = (
            st[0], st[1], st[3], st[7], st[11])
        queued_s = time.perf_counter() - t_staged
        t_wait = time.perf_counter()
        outs = finalize()
        new_n, viol_any, viol_idx, dl_any, dl_idx, counts = (
            outs[3], *outs[7:12])
        # the program's counts vector (pipeline.counts_out): a chunk that
        # holds the verdict ran its probe and merge like any other
        act_en_np, work = split_counts(io.fetch(counts, np.int64), n_work)
        lvl_work[:] += work
        lvl_chunks += 1
        lvl_ahead += was_ahead
        lvl_rows_in += fp_n
        lvl_lanes += outs[16]
        # frontier-level verdicts (states being expanded = level `depth`)
        if check_invariants:
            viol_any_np = io.fetch(viol_any)
            if viol_any_np.any():
                inv_i = int(np.argmax(viol_any_np))
                idx = start + int(io.fetch(viol_idx)[inv_i])
                verdict = ("invariant", idx, model.invariants[inv_i].name)
        if verdict is None and check_deadlock and bool(io.fetch(dl_any)):
            verdict = ("deadlock", start + int(io.fetch(dl_idx)),
                       "Deadlock")
        nn, rows = 0, None
        if verdict is None:
            nn = int(io.fetch(new_n))
            if host_set is None and ht_hi is None:
                rows = _take_rows(outs, nn)
        return (outs, act_en_np, queued_s, time.perf_counter() - t_wait,
                nn, rows)

    def _commit_chunk(st, waited=None) -> bool:
        """Commit one staged chunk: block on its device outputs and read
        its verdict (_commit_wait, unless the loop has run it already:
        `waited`), run the shadow oracle, then the backend-specific host
        assembly — the visited-set insert, arena/trace accumulation and
        digest folds.  Commits run strictly in dispatch order on this
        thread; returns True when a verdict fired (the level stops and
        any younger staged chunk is discarded uncommitted)."""
        nonlocal vhi, vlo, vn, lvl_new, prof_step, prof_host_s
        nonlocal lvl_launches, lvl_launches_max, run_launches_max
        nonlocal lvl_act_en, a_w  # arena buffers grow via _grow_arena
        nonlocal ht_hi, ht_lo, ht_claim, hash_n
        nonlocal lvl_store_s
        (start, fp_n, bucket, _finalize, pre_v, shadow, dispatch_s,
         _t_staged, piece, pre_vcap, t_dispatch, was_ahead) = st
        (outs, act_en_np, queued_s, wait_s, nn, rows
         ) = waited if waited is not None else _commit_wait(st)
        (out, out_parent, out_act, new_n, _vh, _vl, _vn, viol_any,
         _viol_idx, dl_any, _dl_idx, _counts, out_hi, out_lo, _act_guard,
         launches, _lanes) = outs
        if verdict is None and shadow:
            # pre_vcap: the visited capacity AT DISPATCH — the next
            # chunk's dispatch may have grown `vcap` before this
            # commit, and the shadow cross-exec replays against the
            # pre-chunk visited refs, which are sized at the old
            # capacity
            t_shadow = time.perf_counter()
            _shadow_exec(
                piece, fp_n, bucket, start, pre_v, pre_vcap,
                out, out_hi, out_lo, nn, viol_any, dl_any,
            )
            wait_s += time.perf_counter() - t_shadow
        # a chunk that holds the verdict is booked like any other (its
        # step time, launches and `step` span are the cut level's)
        step_s = dispatch_s + wait_s
        prof_step += step_s
        lvl_launches += launches
        lvl_launches_max = max(lvl_launches_max, launches)
        run_launches_max = max(run_launches_max, launches)
        # dispatch vs device-wait attribution (overlap accounting):
        # dispatch_ms is the host's time in the chunk's own stages (the
        # upload and launch 1, the compaction between the launches,
        # launch 2), wherever in the schedule they ran; queued_ms is how
        # long the chunk sat staged after launch 2 while the host
        # committed the chunk before it and compacted the one after it —
        # device time hidden behind host work; wait_ms is the residual
        # block on the outputs, the verdict reads included (_commit_wait).
        # The span is the true interval, first stage to the start of the
        # chunk's host assembly: in an `ahead` chunk it also spans the
        # other chunks' work between its stages.  step_ms stays dispatch
        # + wait
        obs_.chunk_span(
            "step", t_dispatch, depth=depth, start=start, rows=fp_n,
            bucket=bucket, launches=launches,
            dispatch_ms=round(dispatch_s * 1e3, 2),
            wait_ms=round(wait_s * 1e3, 2),
            queued_ms=round(queued_s * 1e3, 2),
            **({"ahead": True} if was_ahead else {}),
            **({"verdict": verdict[0]} if verdict is not None else {}),
        )
        if verdict is not None:
            return True
        t_host = time.perf_counter()
        t_host_wall = _now()
        fetch0 = io.fetch_ms
        if host_set is not None and nn:
            if use_arena:
                _grow_arena(nn)
                w = host_set.insert_compact(
                    np.ascontiguousarray(out_hi[:nn], np.uint32),
                    np.ascontiguousarray(out_lo[:nn], np.uint32),
                    np.ascontiguousarray(out[:nn], np.uint32),
                    np.ascontiguousarray(out_parent[:nn], np.int32),
                    start,
                    np.ascontiguousarray(out_act[:nn], np.int32),
                    a_rows[a_w:],
                    a_parent[a_w:],
                    a_act[a_w:],
                )
                a_w += w
                lvl_new += w
                if chain is not None and w:
                    # arena rows are the committed novel states;
                    # the numpy twin recomputes their fps (the C
                    # pass hands back rows, not fingerprints)
                    chain.fold(
                        _integ.fingerprint_rows(
                            a_rows[a_w - w : a_w], spec.exact64
                        )
                    )
            else:  # tiered disk store, or no native toolchain
                rows = np.asarray(out[:nn])
                fps_u64 = _u64(
                    np.asarray(out_hi[:nn]), np.asarray(out_lo[:nn])
                )
                mask = host_set.insert(fps_u64)
                if disk is not None:
                    # novel rows stream straight to the spilled
                    # frontier + parent log in discovery order (int64
                    # parents: level-global indices can pass 2^31 at
                    # the scales this tier exists for)
                    t_st = time.perf_counter()
                    disk.append(
                        rows[mask],
                        np.asarray(out_parent[:nn], np.int64)[mask] + start,
                        np.asarray(out_act[:nn])[mask],
                    )
                    lvl_store_s += time.perf_counter() - t_st
                else:
                    lvl_rows.append(rows[mask])
                    lvl_parent.append(
                        np.asarray(out_parent[:nn])[mask] + start
                    )
                    lvl_act.append(np.asarray(out_act[:nn])[mask])
                lvl_new += int(mask.sum())
                if chain is not None:
                    chain.fold(fps_u64[mask.astype(bool)])
        elif ht_hi is not None and nn:
            # device-hash backend: insert-or-find on the HBM table; a
            # probe-budget overflow grows the table and re-runs the
            # SAME batch, OR-accumulating novelty (rows inserted by the
            # failed attempt report "seen" on the re-run, so nothing is
            # double-counted or lost)
            valid = jnp.arange(out_hi.shape[0]) < new_n
            isnew = np.zeros(out_hi.shape[0], bool)
            while True:
                if ht_claim is None:
                    ht_claim = hashset.new_claim(ht_hi.shape[0])
                ht_hi, ht_lo, ht_claim, m, _ni, ovf = _hash_insert(
                    ht_hi, ht_lo, ht_claim, out_hi, out_lo, valid
                )
                isnew |= io.fetch(m)
                if not bool(io.fetch(ovf)):
                    break
                ht_hi, ht_lo = hashset.rehash_into(
                    ht_hi, ht_lo, 2 * ht_hi.shape[0]
                )
                ht_claim = None
            mask = isnew[:nn]
            hash_n += int(mask.sum())
            lvl_rows.append(io.fetch(out[:nn])[mask])
            lvl_parent.append(io.fetch(out_parent[:nn])[mask] + start)
            lvl_act.append(io.fetch(out_act[:nn])[mask])
            lvl_new += int(mask.sum())
            if chain is not None:
                chain.fold(
                    _integ.pair_u64(
                        io.fetch(out_hi[:nn])[mask],
                        io.fetch(out_lo[:nn])[mask],
                    )
                )
        elif nn:
            lvl_rows.append(io.fetch(rows[0]))
            lvl_parent.append(io.fetch(rows[1]) + start)
            lvl_act.append(io.fetch(rows[2]))
            lvl_new += nn
            if chain is not None:
                # device backend: the in-jit dedup already
                # compacted exactly the new states to the front
                chain.fold(
                    _integ.pair_u64(io.fetch(rows[3]), io.fetch(rows[4]))
                )
        host_s = time.perf_counter() - t_host
        prof_host_s += host_s
        obs_.chunk_span(
            "host-assembly", t_host_wall, depth=depth, start=start, new=nn,
            backend=visited_backend,
            # the part of it blocked in fetches (the rest is numpy)
            fetch_ms=round(io.fetch_ms - fetch0, 3),
        )
        if collect_stats:
            lvl_act_en += act_en_np

        return False

    def _commit_device_level(fin, dispatch_s: float, t_dispatch: float,
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

        Returns True when a verdict fired (the level's tail chunks are
        never dispatched — the serial break)."""
        nonlocal verdict, lvl_new, prof_step, prof_host_s
        nonlocal lvl_launches, lvl_launches_max, run_launches_max
        nonlocal lvl_act_en, lvl_probe_ms, a_w, lvl_store_s
        nonlocal lvl_chunks, lvl_rows_in, lvl_lanes
        t_wait = time.perf_counter()
        out = fin()
        act_en_np, work = split_counts(out["counts"], n_work)
        lvl_work[:] += work
        wait_s = time.perf_counter() - t_wait
        # the one program ran all the plan's chunks, or stopped at the
        # verdict's (its index is level-global, chunk i starts at i * B)
        ran = plan[1]
        if out["verdict"] is not None:
            ran = out["verdict"][1] // plan[0] + 1
        lvl_chunks += ran
        lvl_rows_in += min(ran * plan[0], plan[2])
        lvl_lanes += ran * out["lanes"]
        step_s = dispatch_s + wait_s
        prof_step += step_s
        launches = out["launches"]
        lvl_launches += launches
        lvl_launches_max = max(lvl_launches_max, launches)
        run_launches_max = max(run_launches_max, launches)
        # attribution: run_level BLOCKS on the level program (its
        # overflow-flag read is the one device sync per level), so the
        # whole blocked wall is device-wait — there is no in-flight
        # dispatch window like the per-chunk staged contract has
        obs_.chunk_span(
            "step", t_dispatch, depth=depth, start=0, rows=plan[2],
            bucket=plan[0], launches=launches, chunks=plan[1],
            pipeline="device",
            dispatch_ms=0.0,
            wait_ms=round(step_s * 1e3, 2), queued_ms=0.0,
        )
        if out["verdict"] is not None:
            kind, idx, inv_i = out["verdict"]
            verdict = (
                kind,
                idx,
                model.invariants[inv_i].name
                if kind == "invariant"
                else "Deadlock",
            )
            return True
        t_host = time.perf_counter()
        t_host_wall = _now()
        nn = out["new_n"]
        if host_set is not None:
            # the deferred batched probe — ONE host call for the level
            t_probe = time.perf_counter()
            t_probe_wall = _now()
            committed = 0
            if nn:
                if use_arena:
                    _grow_arena(nn)
                    # parents are already level-global (the device
                    # program added each chunk's offset), so base 0
                    committed = host_set.insert_compact(
                        out["hi"],
                        out["lo"],
                        np.ascontiguousarray(out["rows"], np.uint32),
                        np.ascontiguousarray(out["parent"], np.int32),
                        0,
                        np.ascontiguousarray(out["act"], np.int32),
                        a_rows[a_w:],
                        a_parent[a_w:],
                        a_act[a_w:],
                    )
                    if chain is not None and committed:
                        chain.fold(
                            _integ.fingerprint_rows(
                                a_rows[a_w: a_w + committed],
                                spec.exact64,
                            )
                        )
                    a_w += committed
                else:  # tiered disk store, or no native toolchain
                    fps_u64 = _u64(out["hi"], out["lo"])
                    # the disk tier's level-batched form probes every
                    # spilled run ONCE for the whole (sorted) level
                    # batch; plain FpSets take the ordinary batch insert
                    mask = (
                        host_set.insert_level(fps_u64)
                        if hasattr(host_set, "insert_level")
                        else host_set.insert(fps_u64)
                    ).astype(bool)
                    rows = out["rows"][mask]
                    par = out["parent"].astype(np.int64)[mask]
                    acts = out["act"][mask]
                    if disk is not None:
                        t_st = time.perf_counter()
                        disk.append(rows, par, acts)
                        lvl_store_s += time.perf_counter() - t_st
                    else:
                        lvl_rows.append(rows)
                        lvl_parent.append(par)
                        lvl_act.append(acts)
                    committed = int(mask.sum())
                    if chain is not None:
                        chain.fold(fps_u64[mask])
                lvl_new += committed
            probe_s = time.perf_counter() - t_probe
            lvl_probe_ms += probe_s * 1e3
            obs_.chunk_span(
                "host-probe", t_probe_wall, depth=depth, rows=nn,
                new=committed, backend=visited_backend,
                batched="level",
            )
        elif nn:
            lvl_rows.append(out["rows"])
            lvl_parent.append(out["parent"])
            lvl_act.append(out["act"])
            lvl_new += nn
            if chain is not None:
                chain.fold_digest(*out["digest"])
        host_s = time.perf_counter() - t_host
        prof_host_s += host_s
        obs_.chunk_span(
            "host-assembly", t_host_wall, depth=depth, start=0, new=nn,
            backend=visited_backend,
        )
        if collect_stats:
            lvl_act_en += act_en_np
        return False

    # storage read-side corruption (read-verified CRCs on spill runs /
    # frontier segments / parent-log levels) surfaces as these typed
    # exceptions mid-run — all integrity violations, exit 76
    from ..storage.frontier import SegmentCorrupt
    from ..storage.parent_log import ParentLogCorrupt
    from ..storage.runs import RunCorrupt

    exhausted: Optional[ResourceExhausted] = None
    integrity_fail: Optional[IntegrityError] = None
    run_launches_max = 0  # per-chunk max actually DISPATCHED this run
    overlap_staged_peak = 0  # most chunks ever staged at once (<= 2)
    # ... and most chunks beside them with only a guard stage run (<= 1)
    overlap_ahead_peak = 0

    def _io_counters():
        return worker_counters((io_worker, ckpt_worker))
    try:
        while _f_rows(frontier_np) > 0:
            # async join point: adopt finished background merges and
            # promoted checkpoints, surfacing any worker error (typed
            # faults, ENOSPC) on this thread before more work builds on
            # un-validated state.  With an armed fault plan the join is
            # BLOCKING: deterministic injection (crash deferral, flip
            # gating, enospc surfacing) must not depend on writer-thread
            # timing — fault rehearsals trade the overlap win for
            # reproducibility at level boundaries
            _ckpt_poll(block=bool(fault.specs))
            if disk is not None:
                if fault.specs:
                    disk.quiesce()
                disk.poll_async()
            lvl_io0 = _io_counters()
            lvl_sync_io0 = sync_io_s
            # level-boundary fault injection point (resilience.faults);
            # crash deferral keys on the DURABLE checkpoint depth, so an
            # in-flight async save can never arm a crash whose restart
            # would not converge
            fault.crash("level", depth, ckpt_depth=ckpt_durable_depth)
            if chain is not None:
                sp = fault.flip(
                    "frontier", depth, ckpt_depth=ckpt_durable_depth
                )
                if isinstance(frontier_np, np.ndarray):
                    if sp:
                        _integ.flip_bit(frontier_np)
                    # the frontier about to be expanded must digest to
                    # the entry sealed when its level was discovered — a
                    # bit flipped in the buffer between levels (or a
                    # frontier loaded from a CRC-consistent corrupted
                    # checkpoint) is caught HERE, before it poisons
                    # successors
                    # (under SYMMETRY the chain holds orbit keys, which
                    # the host cannot recompute from rows: the sealed
                    # counts still chain, the rows are not re-read)
                    if not symmetric:
                        _integ.count_check()
                        chain.verify_level(
                            depth,
                            _integ.fingerprint_rows(
                                frontier_np, spec.exact64),
                        )
                elif sp and frontier_np.paths():
                    # disk-spilled frontier: the flip lands in a segment
                    # FILE (there is no long-lived host buffer to flip);
                    # the read-side segment CRC catches it at the first
                    # chunk read of this level
                    from ..resilience.faults import corrupt_file

                    frontier_np._read_verified.clear()
                    corrupt_file(frontier_np.paths()[0])
            if max_depth is not None and depth >= max_depth:
                break
            if max_states is not None and total >= max_states:
                break
            f_total = _f_rows(frontier_np)
            t_level = time.perf_counter()
            # begin marker (ph=B): a crash mid-level leaves it unmatched, which
            # is exactly what `cli report` uses to pin where the run died
            obs_.level_begin(depth + 1, f_total)
            governor.level_begin(depth + 1)  # arm the per-level deadline
            # A frontier larger than `chunk` is streamed through the same
            # compiled step in chunk_size pieces: cross-chunk duplicates are
            # caught because each chunk probes the visited set updated by the
            # previous one.  This bounds both the number of compiled shapes
            # (O(log chunk) buckets, ever) and peak device memory (O(chunk*C)).
            lvl_rows, lvl_parent, lvl_act = [], [], []
            lvl_new = 0
            lvl_act_en = np.zeros(len(model.actions), np.int64)
            lvl_launches = 0  # successor-kernel launches this level
            lvl_launches_max = 0  # ... and the per-chunk maximum
            # pipeline.work_counts of the committed dispatches: the probes'
            # search rounds and the merges' touched slots, each beside what
            # the form over the whole capacity would have run
            lvl_work = np.zeros(n_work, np.int64)
            lvl_probe_ms = 0.0  # deferred batched host-probe wall
            lvl_store_s = 0.0  # trace store / parent log wall (`store_ms`)
            lvl_chunks = lvl_rows_in = 0  # chunks committed, their rows
            # those of them whose guard launch went out before the chunk
            # before them had its successor launch (`chunks_ahead`)
            lvl_ahead = 0
            # the width the dedup side was handed, summed over them: the
            # lanes every sort, probe and compaction ran, live or padding
            lvl_lanes = 0
            lvl_discarded = 0  # chunks dispatched and dropped at a verdict
            verdict = None  # (kind, global_frontier_idx, inv_name)
            # Host-native backend: assemble the next level in a preallocated
            # arena via the fused C pass (native.FpSet.insert_compact) — one
            # cache-friendly sweep per chunk instead of u64 packing + novelty
            # mask + masked gathers + per-level concatenate.  Growth copies
            # only the filled prefix (amortized O(level)).
            if disk is not None:
                disk.begin_level(depth + 1)
            use_arena = host_set is not None and host_set.native
            if use_arena:
                a_cap = max(1 << 14, int(1.5 * f_total))
                a_rows = np.empty((a_cap, K), np.uint32)
                a_parent = np.empty(a_cap, np.int64)
                a_act = np.empty(a_cap, np.int32)
                a_w = 0
            prof_step = prof_host_s = 0.0
            # Staged chunk pipeline (KSPEC_OVERLAP, docs/engine.md
            # § Async execution): each chunk's device programs are
            # DISPATCHED first (pipe.run_chunk_staged — JAX async
            # dispatch leaves the update-skeleton launch draining), and
            # the PREVIOUS chunk's host commit (fingerprint-set insert,
            # arena assembly, digest folds) runs while it drains; in a
            # level of fused chunks the NEXT chunk's guard launch goes
            # out before the launch and its host compaction runs behind
            # it too.  At most two chunks are ever staged (the one
            # committing + the one dispatched) plus one of which only
            # the guard stage has run; commits happen strictly in chunk
            # order, so counts, novelty decisions, first-violation and
            # traces are bit-identical to the serial path — which is
            # literally this same code with overlap_on False (dispatch
            # followed by an immediate commit, nothing ahead).
            staged = None
            # Device-resident level path (DevicePipeline, engine/
            # pipeline.py): ONE dispatched while_loop program runs every
            # gated chunk of this level — expansion, in-jit compaction,
            # fingerprints, dedup, verdicts and digest folds all
            # on-device, the visited merge once per level — <=2
            # successor launches per LEVEL.  A sub-gate tail chunk (only
            # ever the last, partial one) falls through to the per-chunk
            # loop below at its serial offset, preserving the legacy
            # full-lattice candidate order below the gate
            # (bit-identity).  A verdict inside the device span, like
            # the serial break, leaves the tail undispatched.
            dev_handled = 0
            dev_plan = (
                pipe.plan_level(f_total, chunk, min_bucket)
                if getattr(pipe, "name", "") == "device"
                else None
            )
            if dev_plan is not None:
                governor.poll(depth)
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
                mat_bytes = f_total * K * 4
                mat_budget = int(os.environ.get(
                    "KSPEC_DEVLEVEL_MAT_BUDGET", str(1 << 31)
                ))
                if (not isinstance(frontier_np, np.ndarray)
                        and mat_bytes > mat_budget):
                    pipe._mark_fallback(
                        f"spilled frontier too large to materialize "
                        f"for the device span ({mat_bytes} B > "
                        f"KSPEC_DEVLEVEL_MAT_BUDGET {mat_budget} B)",
                        depth,
                    )
                    dev_plan = None
                else:
                    try:
                        dev_rows = (
                            frontier_np
                            if isinstance(frontier_np, np.ndarray)
                            else _f_all(frontier_np)
                        )
                    except MemoryError as e:
                        pipe._mark_fallback(
                            f"frontier materialization failed "
                            f"({f_total} rows): {e}"[:200],
                            depth,
                        )
                        dev_plan = None
            if dev_plan is not None:
                t_attempt = time.perf_counter()
                t_dispatch = _now()
                dres = pipe.run_level(
                    dev_rows, f_total, depth, vhi, vlo, vn, vcap,
                    dev_plan,
                )
                if dres is not None:
                    vhi, vlo, vn, vcap, dev_fin = dres
                    dispatch_s = time.perf_counter() - t_attempt
                    dev_handled = dev_plan[2]
                    if _commit_device_level(dev_fin, dispatch_s,
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
            elif dev_handled and not isinstance(frontier_np, np.ndarray):
                tail_chunks = (
                    (s, dev_rows[s: s + chunk])
                    for s in range(dev_handled, f_total, chunk)
                )
            else:
                tail_chunks = _f_chunks(frontier_np, chunk)
            # the fused pipeline's two halves of a chunk, where the loop
            # may run them apart (never a whole-level `device` pipeline's
            # per-chunk tail, never `legacy`)
            stager = pipe if getattr(pipe, "name", "") == "fused" else None
            chunks_it = (c for c in tail_chunks if c[0] >= dev_handled)
            nxt = next(chunks_it, None)
            ahead = None  # the NEXT chunk's guard stage, where it ran
            while nxt is not None:
                (start, piece), nxt = nxt, next(chunks_it, None)
                governor.poll(depth)  # deadline watchdog (cheap)
                fp_n = piece.shape[0]
                bucket = _next_pow2(max(fp_n, min_bucket))
                M = bucket * C
                mine, ahead = ahead, None
                from_ahead = mine is not None
                waited = None
                if visited_backend == "device":
                    if staged is not None:
                        # the loop's one blocking wait on a successor
                        # program: the staged chunk's counts, verdict
                        # flags and `new_n`, its rows' slices enqueued
                        # before this chunk's successor launch is
                        waited = _commit_wait(staged)
                        if verdict is not None:
                            # the level stops HERE, before anything more
                            # is queued: of this chunk only the guard
                            # stage has run, where it ran ahead (its
                            # launch read and closed, nothing in flight)
                            _commit_chunk(staged, waited)
                            staged = None
                            if mine is not None:
                                mine.drop()
                                lvl_discarded = 1
                            break
                    need = int(io.fetch(vn)) + M
                    if need > vcap:
                        # one shared growth policy with the device level
                        # path (pipeline.grow_visited); growth is
                        # monotonic, so the outgrown capacity's compiled
                        # steps are evicted immediately here
                        vhi, vlo, vcap = _grow_visited(
                            vhi, vlo, vcap, need,
                            cache=step_builder._cache,
                        )
                elif ht_hi is not None and 2 * hash_n > ht_hi.shape[0]:
                    # keep load factor under ~1/2 so linear probing stays short
                    ht_hi, ht_lo = hashset.rehash_into(
                        ht_hi, ht_lo, 2 * ht_hi.shape[0]
                    )
                    ht_claim = None
                # One chunk through the level-pipeline: expand -> squeeze ->
                # fingerprint (+ the device backend's in-jit dedup), with
                # overflow retries / escalation / failure degradation owned
                # by the pipeline implementation (engine/pipeline.py).  The
                # outputs are COMMITTED — exact regardless of which
                # implementation or retry path produced them.
                shadow = shadow_rate > 0 and _integ.sample_chunk(
                    depth, start, shadow_rate
                )
                # pre-chunk visited refs: the shadow legacy cross-exec
                # replays the chunk from the same starting state (jax
                # arrays are immutable, so holding them is free)
                pre_v = (vhi, vlo, vn) if shadow else None
                # The guard stage of the chunk AFTER this one goes out
                # before this chunk's successor launch (it reads the
                # frontier only), where the code can see that it will
                # run fused: overlap on, a further chunk, its bucket
                # through the gate.  This chunk's own stages then run
                # first, in serial order, unless they ran ahead too.
                # `mine` / `ahead`: a pipeline.StagedGuard
                n_fp = nxt[1].shape[0] if nxt is not None else 0
                n_bucket = _next_pow2(max(n_fp, min_bucket))
                if (overlap_on and stager is not None and n_fp
                        and stager._gate(n_bucket)):
                    if mine is None and stager._gate(bucket):
                        mine = stager.guard_stage(piece, fp_n, bucket, depth)
                        stager.compact_stage(mine)
                    ahead = stager.guard_stage(nxt[1], n_fp, n_bucket, depth)
                    overlap_ahead_peak = 1
                t_attempt = time.perf_counter()
                if mine is None:
                    t_dispatch, dispatch_s = _now(), 0.0
                    vhi, vlo, vn, finalize = pipe.run_chunk_staged(
                        piece, fp_n, bucket, depth, vhi, vlo, vn, vcap
                    )
                else:
                    # the `step` span runs from the chunk's first stage
                    t_dispatch, dispatch_s = mine.t0, mine.host_s
                    vhi, vlo, vn, finalize = stager.run_chunk_staged(
                        piece, fp_n, bucket, depth, vhi, vlo, vn, vcap,
                        ahead=mine,
                    )
                cur = (
                    start, fp_n, bucket, finalize, pre_v, shadow,
                    dispatch_s + time.perf_counter() - t_attempt,
                    time.perf_counter(), piece, vcap, t_dispatch,
                    # the committed attempt's guard launch went out
                    # before the previous chunk's successor launch
                    int(from_ahead and getattr(finalize, "ahead", False)),
                )
                if overlap_on:
                    overlap_staged_peak = max(
                        overlap_staged_peak, 2 if staged is not None else 1
                    )
                    if staged is not None and _commit_chunk(staged, waited):
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
                        lvl_discarded = 1
                        if ahead is not None:
                            ahead.drop()
                            lvl_discarded = 2
                        break
                    staged = cur
                    if ahead is not None:
                        # the next chunk's host compaction, behind this
                        # chunk's successor launch
                        stager.compact_stage(ahead)
                else:
                    if _commit_chunk(cur):
                        break
            if staged is not None and verdict is None:
                _commit_chunk(staged)
            staged = None

            if verdict is not None:
                kind, idx, inv_name = verdict
                if disk is not None:
                    disk.abort_level()  # partial next-level writer: discard
                if collect_stats:
                    # the level a verdict cuts gets a completed record of
                    # its own (never one of stats["levels"], whose length
                    # is the number of committed levels) and its span ends
                    # with cut=true; the counterexample is built after it
                    cut = result_stats["cut_level"] = dict(
                        depth=depth + 1,
                        frontier=f_total,
                        rows_committed=lvl_rows_in,
                        chunks_committed=lvl_chunks,
                        chunks_discarded=lvl_discarded,
                        chunks=lvl_chunks + lvl_discarded,
                        # of the committed ones (`chunks_committed`)
                        chunks_ahead=lvl_ahead,
                        dedup_lanes=lvl_lanes,
                        level_ms=round((time.perf_counter() - t_level) * 1e3, 1),
                        step_ms=round(prof_step * 1e3, 1),
                        host_ms=round(prof_host_s * 1e3, 1),
                        successor_launches=lvl_launches,
                        **work_record(lvl_work),
                        **io.take(),
                    )
                    obs_.level_cut(cut)
                if have_trace(depth):
                    violation = build_violation(inv_name, depth, idx)
                else:
                    violation = Violation(
                        invariant=inv_name,
                        depth=depth,
                        state=decode_state(_f_row(frontier_np, idx)),
                        trace=[],
                    )
                break

            new_n = lvl_new
            # the next frontier (every run needs it) ...
            if use_arena:
                next_frontier = a_rows[:a_w]
            elif disk is None:
                next_frontier = (
                    np.concatenate(lvl_rows)
                    if lvl_rows
                    else np.empty((0, K), np.uint32)
                )
            level_parent = level_act = None
            # ... and what only the trace store and the parent log need:
            # parents and action ids, the retained copy, the published
            # disk level.  One `store` span a level; `store_ms` adds the
            # parent-log appends the commits made (lvl_store_s)
            if store_trace or collect_levels is not None or disk is not None:
                st_span = obs_.open_span("store", depth=depth + 1)
                t_st = time.perf_counter()
                if use_arena:
                    level_parent = a_parent[:a_w]
                    level_act = a_act[:a_w]
                    if a_w < int(0.95 * a_cap):
                        # retained levels: shrink-copy so the trace store
                        # doesn't hold the arena's growth headroom for the
                        # whole run
                        next_frontier = next_frontier.copy()
                        level_parent = level_parent.copy()
                        level_act = level_act.copy()
                elif disk is not None:
                    # publish the level: segments + parent-log frame become
                    # the pending frontier; the consumed level's segments go
                    # behind the checkpoint-generation deletion barrier
                    # (the trace lives in the log)
                    next_frontier = disk.end_level()
                else:
                    level_parent = (
                        np.concatenate(lvl_parent)
                        if lvl_parent
                        else np.empty(0, np.int64)
                    )
                    level_act = (
                        np.concatenate(lvl_act)
                        if lvl_act
                        else np.empty(0, np.int64)
                    )
                if store_trace:
                    trace_store.append(
                        (next_frontier, level_parent, level_act)
                    )
                lvl_store_s += time.perf_counter() - t_st
                st_span.finish(
                    rows=new_n,
                    bytes=new_n * 4 * K + sum(
                        a.nbytes for a in (level_parent, level_act)
                        if a is not None
                    ),
                )
            depth += 1
            if new_n:
                levels.append(new_n)
                total += new_n
            if chain is not None:
                if new_n:
                    # seal the level: the folded multiset digest becomes
                    # the chain entry (count disagreement raises typed)
                    chain.seal(depth, new_n)
                else:
                    chain.reset_fold()
            if collect_stats:
                enabled_total = int(lvl_act_en.sum())
                # heartbeat-enveloped (kind/ts/unix): the per-level stats
                # stream doubles as the supervisor's liveness signal.  The obs
                # shim emits the historical record shape (and, with a run
                # context, additionally stamps run_id, closes the level span,
                # and folds the metrics registry + Prometheus export)
                rec = obs_.level(
                    depth=depth,
                    frontier=f_total,
                    enabled_candidates=enabled_total,
                    new=new_n,
                    duplicates=enabled_total - new_n,
                    total=total,
                    level_ms=round((time.perf_counter() - t_level) * 1e3, 1),
                    step_ms=round(prof_step * 1e3, 1),
                    host_ms=round(prof_host_s * 1e3, 1),
                    action_enablement={
                        a.name: int(c) for a, c in zip(model.actions, lvl_act_en.tolist())
                    },
                )
                # launch accounting rides only the in-memory result (and
                # the per-chunk step spans): the emitted stats stream is
                # a pinned record-for-record historical contract
                # (tests/test_obs.py shim equivalence)
                result_stats.setdefault("levels", []).append(
                    {
                        **rec,
                        "successor_launches": lvl_launches,
                        "launches_per_chunk_max": lvl_launches_max,
                        # chunks the level streamed (a whole-level
                        # program: the chunks it ran)
                        "chunks": lvl_chunks,
                        # those whose guard launch went out before the
                        # chunk before them had its successor launch
                        "chunks_ahead": lvl_ahead,
                        # the lanes their dedup sides were handed
                        "dedup_lanes": lvl_lanes,
                        **work_record(lvl_work),
                        # what the host launched, moved and stored this
                        # level (engine/hostio.py; docs/observability.md)
                        **io.take(),
                        "store_ms": round(lvl_store_s * 1e3, 3),
                        # deferred batched host-probe attribution (the
                        # host-backend device path): in-memory records
                        # + the gauge/span side channels only — the
                        # emitted stats stream stays record-for-record
                        # historical (PR 7/10/13 precedent)
                        **(
                            {"host_probe_ms": round(lvl_probe_ms, 2)}
                            if lvl_probe_ms
                            else {}
                        ),
                    }
                )
                # launches/level gauge (obs): the device pipeline's
                # acceptance signal — <=2 steady-state on the
                # device-resident path, O(chunks)x2 on fused
                _met.set_gauge(
                    "kspec_successor_launches_level", lvl_launches
                )
                if lvl_probe_ms:
                    # probe-ms/level gauge: the deferred-probe beat
                    # `cli report` renders next to launches/level
                    _met.set_gauge(
                        "kspec_host_probe_ms", round(lvl_probe_ms, 2)
                    )
            if collect_levels is not None and new_n:
                collect_levels.append(_f_all(next_frontier))
            if progress:
                progress(depth, new_n, total)

            frontier_np = next_frontier
            if ckpt_store is not None and depth % checkpoint_every == 0:
                _save_checkpoint()
                last_ckpt_depth = depth
            # level-boundary resource governance: pressure gauges, injected
            # stall, soft-breach reclamation, hard-breach typed clean exit
            governor.level_end(depth, reclaim=_reclaim, save_hook=_final_save)
            # per-level overlap accounting (obs: `kspec_overlap_efficiency`
            # is how machine-readable "storage I/O fully hidden" is —
            # ROADMAP item 2's acceptance): hidden = worker-busy wall not
            # re-exposed as caller blocking; exposed = blocking waits on
            # workers + synchronous checkpoint writes.  Attached to the
            # IN-MEMORY level records only (the emitted stats stream is a
            # pinned historical contract, like the launch counters)
            if collect_stats and result_stats.get("levels"):
                busy1, blk1 = _io_counters()
                hid = max(
                    0.0, (busy1 - lvl_io0[0]) - (blk1 - lvl_io0[1])
                )
                exp = (blk1 - lvl_io0[1]) + (sync_io_s - lvl_sync_io0)
                eff = hid / (hid + exp) if (hid + exp) > 1e-9 else 1.0
                rec_mem = result_stats["levels"][-1]
                rec_mem["io_hidden_ms"] = round(hid * 1e3, 2)
                rec_mem["io_exposed_ms"] = round(exp * 1e3, 2)
                rec_mem["overlap_efficiency"] = round(eff, 4)
                _met.set_gauge("kspec_overlap_efficiency", round(eff, 4))
                _met.inc("kspec_io_hidden_ms_total", round(hid * 1e3, 2))
                _met.inc("kspec_io_exposed_ms_total", round(exp * 1e3, 2))
        # drain the async tail INSIDE the typed-error scope: a pending
        # checkpoint's ENOSPC or a background merge's injected fault must
        # map to the same typed exits as their synchronous twins
        _ckpt_poll(block=True)
        if disk is not None:
            disk.quiesce()
    except ResourceExhausted as e:
        exhausted = e
    except IntegrityError as e:
        integrity_fail = e
    except (RunCorrupt, SegmentCorrupt, ParentLogCorrupt) as e:
        # read-side storage checksum failure: silent on-disk corruption
        # caught at consumption time — typed exactly like every other
        # integrity violation
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
        # typed terminal (resilience.integrity): stamp the manifest so
        # `cli report` renders the integrity beat, then propagate for the
        # CLI's exit-76 mapping.  The supervisor restarts; the resume
        # path's chain validator skips corrupted generations, so the
        # restart resumes from the newest CHAIN-VERIFIED one.  Corrupt
        # in-memory state is deliberately NOT checkpointed here (unlike
        # the resource exit's final save): the newest durable generation
        # predates the detected corruption by construction.
        try:
            _integ.record_violation(integrity_fail)
            if disk is not None:
                disk.abort_level()  # partial next-level writer: discard
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
        _drop_ephemeral_spill()
        _shutdown_async(drain=False)
        raise integrity_fail
    if exhausted is not None:
        # the terminal path itself writes (manifest rewrite, metrics
        # snapshot) to the same full filesystem — best-effort only, so a
        # second ENOSPC can't demote the typed exit-75 into a torn crash
        try:
            if disk is not None:
                disk.abort_level()  # partial next-level writer: discard
            # typed terminal: the run manifest records WHY (`cli report`
            # renders the RESOURCE_EXHAUSTED verdict beat from it), and the
            # exception propagates for the CLI's exit-code-75 mapping
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

    if violation is None and check_invariants and model.invariants and _f_rows(frontier_np):
        # the loop was cut (max_depth/max_states) before the remaining
        # frontier was expanded — its states still need their invariant pass
        bad = first_violation(_f_all(frontier_np))
        if bad is not None:
            inv, idx = bad
            violation = (
                build_violation(inv.name, depth, idx)
                if have_trace(depth)
                else Violation(
                    invariant=inv.name,
                    depth=depth,
                    state=decode_state(_f_row(frontier_np, idx)),
                    trace=[],
                )
            )

    dt = time.perf_counter() - t0
    result_stats.update(
        {
            "visited_capacity": int(vcap),
            "fanout": C,
            "lanes": K,
            "visited_backend": visited_backend,
            "pipeline": pipe.name,
            "pipeline_fallback": bool(getattr(pipe, "fallback", False)),
            # measured, not the pipeline's nominal figure: sub-gate
            # chunks delegate to the per-action path and a fused
            # compile-fallback runs legacy for the rest of the run, so
            # only the observed per-chunk maximum is honest here
            "launches_per_chunk_max": run_launches_max,
            "adaptive_active": adapt.active,
            # state-space-cache seeding (service/state_cache.py): the
            # depth this run's frontier was seeded at instead of Init
            **({"seeded_from_depth": int(seed["depth"])} if seeded else {}),
            # device-resident level pipeline accounting (DevicePipeline):
            # how many levels ran as single dispatched programs, and why
            # (if ever) the run left the device path for the fused ladder
            **(
                {
                    "device": {
                        "levels": pipe.device_levels,
                        "fallback": pipe.device_fallback,
                        # what the level programs measured, per level,
                        # and whether a warm call's seed sized any
                        # (PreparedKernels.level_high_waters)
                        "high_waters": pipe.high_waters,
                        "seeded": pipe.seeded,
                    }
                }
                if getattr(pipe, "name", "") == "device"
                else {}
            ),
            "adaptive_compile_fallback": bool(
                getattr(pipe, "legacy", pipe).compile_fallback
            ),
            "transient_retries": chunk_retry.retries_total,
            "degradations": chunk_retry.degradations,
            # async-overlap accounting (overlap.py): the staging bound
            # is structural (two open successor launches, one chunk
            # beside them whose guard stage ran ahead) — tests pin both
            "overlap": {
                "enabled": overlap_on,
                "staged_chunks_peak": overlap_staged_peak,
                "guard_ahead_peak": overlap_ahead_peak,
                "sync_ckpt_io_s": round(sync_io_s, 4),
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
        }
    )
    if host_set is not None:
        result_stats["host_fpset_size"] = len(host_set)
    if disk is not None:
        result_stats["spill"] = disk.stats()
        result_stats["spill_dir"] = disk.dir
        result_stats["mem_budget"] = disk.fpset.mem_budget
    if ht_hi is not None:
        result_stats["hash_table_capacity"] = int(ht_hi.shape[0])
        result_stats["hash_table_size"] = hash_n
    _drop_ephemeral_spill()
    _shutdown_async(drain=True)
    res = CheckResult(
        model=model.name,
        levels=levels,
        total=total,
        diameter=len(levels) - 1,
        violation=violation,
        seconds=dt,
        states_per_sec=total / max(dt, 1e-9),
        stats=result_stats,
    )
    obs_.finish(res)
    obs_.close()
    return res
