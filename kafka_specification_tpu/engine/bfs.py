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

import collections
import os
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import Model
from ..obs.ledger import PROCESS as _LEDGER
from ..obs.observer import RunObserver
from ..obs.tracer import now as _now
from ..ops import hashset
from ..resilience import integrity as _integ
from ..resilience.resources import ResourceGovernor
from .hostio import HostIO
from .pipeline import (
    counts_out,
    fp_stage,
    init_rows_program,
    invariant_rows_program,
    invariant_stage,
    part,
    program_name,
    sorted_dedup_stage,
    squeeze_stage,
    stage,
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
    what the call was made of: ``trace_ms`` / ``lower_ms`` /
    ``backend_ms`` / ``cache`` / ``retrieval_ms`` / ``rest_ms``, what JAX
    itself reported on this thread while the call was open
    (obs/ledger.py, which books the call whether or not a run is traced:
    ``rewarm`` has none), and what the program is made of: ``gathers`` /
    ``scatters`` (:func:`indexing_equations` of the jaxpr the call traced;
    read back from jit's own trace cache, so the program is traced once,
    and after the call is booked: the read-back fires a trace event of
    its own).  The program is called as jit calls it, never through a
    ``Compiled`` object: the level loop dispatches it 20-110 times a
    level."""

    def __init__(self, fn, cache: dict, key, **attrs):
        self.fn = fn
        self._cache = cache
        self._key = key
        self._attrs = attrs

    def __call__(self, *args):
        from ..obs import tracer as _tr

        with _LEDGER.first_call(**self._attrs) as call:
            out = self.fn(*args)
            parts = call.done()
            cur = _tr.current_tracer()
            if cur is not None:
                gathers, scatters = indexing_equations(
                    self.fn.trace(*args).jaxpr.jaxpr
                )
                cur.emit_span("compile", call.t0, call.t1, gathers=gathers,
                              scatters=scatters, **self._attrs, **parts)
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

    # pieces of a long invariant pass uploaded and launched ahead of the
    # one whose verdict is read (`first_violation`): enough to keep upload,
    # program and read-back overlapped, bounded so that a frontier of any
    # length holds a few pieces on the device, not all of itself
    PIECES_AHEAD = 4

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
        predicate.  Rows beyond `N` go through in pieces of `N`, a launch
        each, `PIECES_AHEAD` of them uploaded and launched ahead of the
        verdict being read (the frontier a depth cut leaves is a level's
        worth: as ONE padded launch, 1,075,905 rows of 15 lanes held the
        device idle for 0.35-0.8 s of a 1.5-2.0 s pass while the host
        padded and uploaded 126 MB, and the level after it unpacks to
        more than the device holds).  `key_head`: the cache tag, then
        whatever else shapes the program (the sharded engine's mesh);
        `where`: that engine's placement, handed to ``io.put``."""
        tag = key_head[0]
        key = (*key_head, N, self.inv_sig(True))
        # (spans and profiler annotations, not a level's dispatches: they
        # run before the first level and after the last)
        lowest = {}  # invariant -> its lowest violating row (pieces ascend)

        def read(start, launch, any_bad, first):
            any_bad = io.fetch(any_bad)
            launch.finish()
            if any_bad.any():
                first = io.fetch(first)
                for i in np.flatnonzero(any_bad):
                    lowest.setdefault(int(i), start + int(first[i]))

        ahead = collections.deque()  # launched, verdict not yet read
        for start in range(0, max(rows.shape[0], 1), N):
            piece = rows[start:start + N]
            # (looked up a piece: a fresh entry is its `compile` span's
            # wrapper for its first call only)
            fn = self.cached(
                key, lambda: invariant_rows_program(self.model, N),
                program=tag, bucket=N,
            )
            launch = obs_.dispatch(tag, bucket=N)
            ahead.append((start, launch, *fn(
                io.put(_pad_rows(piece, N), *where),
                np.int32(piece.shape[0]),
            )))
            if len(ahead) > self.PIECES_AHEAD:
                read(*ahead.popleft())
        while ahead:
            read(*ahead.popleft())
        if not lowest:
            return None
        i = min(lowest)
        return self.model.invariants[i], lowest[i]

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

        compact: a right-shift amount, one int (uniform) or a per-action
        width sequence, enabling the two-phase expansion (:meth:`make_expand`
        says how); the sort / probe / merge then run at the compacted
        width too.  The step returns a per-action overflow vector (plus one
        trailing squeeze-overflow flag): where set, that action enabled
        more pairs than its buffer holds, the outputs are INCOMPLETE, and
        the caller must re-run the chunk wider for that action (results
        stay exact either way).  squeeze_full=True disables the pre-sort
        squeeze (the retry fallback when the squeeze itself overflows)."""
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
    spans in its trace, the daemon's warm-path proof).

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
        self._visited0 = None  # (key, (vhi, vlo)): `initial_visited`

    def initial_visited(self, vcap: int, hi: np.ndarray, lo: np.ndarray):
        """The sorted visited set a run opens with, on the host: `vcap`
        sentinel pairs, the initial states' sorted fingerprints first ->
        (vhi, vlo), read-only.  The jobs of a shape open at one capacity
        with the same initial states, so the image is built once and
        uploaded by each (the same bytes a run without prepared kernels
        fills afresh).  At 16,777,216 slots it is 128 MiB, and filling it
        anew each pass faulted its pages in for 8 ms in one process and
        67 ms in the next (huge pages or not: the allocator's luck), a
        process-to-process step of 4% in a 1.5 s pass."""
        key = (vcap, hi.tobytes(), lo.tobytes())
        if self._visited0 is None or self._visited0[0] != key:
            pair = sentinel_set(vcap, hi, lo)
            for a in pair:
                a.setflags(write=False)
            self._visited0 = (key, pair)
        return self._visited0[1]

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

        Returns the number of programs compiled.  No caller has a run
        open around this, so the programs it builds leave no ``compile``
        span: the process ledger's `rewarm` and `programs` (`slowest`
        marks them `during: rewarm`) are their record."""
        with _LEDGER.rewarming() as booked:
            booked.built = self._rewarm()
        return booked.built

    def _rewarm(self) -> int:
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


def prepare(model: Model) -> PreparedKernels:
    """Prepare (and cache on the model) the reusable jitted engine kernels
    for `model` — the explicit warm entry point ``check(...,
    prepared=...)`` consumes."""
    _LEDGER.mark_backend()
    with _LEDGER.model():
        return PreparedKernels(model)


def sentinel_set(vcap: int, hi: np.ndarray, lo: np.ndarray):
    """A sorted pair set of capacity `vcap` holding the sorted pairs
    (`hi`, `lo`), on the host: sentinel pairs (all ones) sort last."""
    vhi = np.full(vcap, 0xFFFFFFFF, np.uint32)
    vlo = np.full(vcap, 0xFFFFFFFF, np.uint32)
    vhi[:hi.shape[0]] = hi
    vlo[:lo.shape[0]] = lo
    return vhi, vlo


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


def walk_trace(trace_store, model: Model, inv_name, depth, idx, obs=None,
               source="ram") -> Violation:
    """Parent-pointer counterexample reconstruction, shared by both engines.

    trace_store[level] = (rows, parent, act): the level's states in discovery
    order, each new state's parent index into the previous level, and the
    action id that produced it.  Walks level `depth` index `idx` back to an
    init state and returns the Violation with the root->violation trace.
    The walk collects the chain's packed rows first; they are decoded after
    it in one `decode_rows` call, on the host.

    With `obs` (a RunObserver) the walk is one `counterexample` span:
    `source` says where the store lives (`ram` | `disk`), `walk_ms` is the
    pointer reads and `decode_ms` the `decode_rows` call (`decode="host"`:
    numpy and the model's decoder, no device operation; `decoded_rows` its
    batch).  Under the model's `symmetry` the span names its operator and
    order: the rows walked were stored as found and keyed by their orbits,
    so the trace is a behaviour of the unreduced spec.
    """
    symmetry = model.symmetry
    sym = {} if symmetry is None else {
        "symmetry": symmetry.operator, "symmetry_order": symmetry.order}
    span = obs.open_span("counterexample", invariant=inv_name, depth=depth,
                         source=source, **sym) if obs is not None else None
    t_walk = time.perf_counter()
    names, rows = [], []
    i = idx
    for d in range(depth, 0, -1):
        level_rows, parent, act = trace_store[d]
        names.append(model.actions[int(act[i])].name)
        rows.append(level_rows[i])
        i = int(parent[i])
    names.append("<init>")
    rows.append(trace_store[0][0][i])
    t_decode = time.perf_counter()
    states = decode_rows(model, np.stack(rows))
    chain = list(zip(names, states))[::-1]
    if span is not None:
        t_end = time.perf_counter()
        span.finish(trace_len=len(chain), decode="host",
                    decoded_rows=len(rows),
                    walk_ms=round((t_decode - t_walk) * 1e3, 3),
                    decode_ms=round((t_end - t_decode) * 1e3, 3))
    return Violation(invariant=inv_name, depth=depth, state=chain[-1][1], trace=chain)


def decode_rows(model: Model, rows: np.ndarray) -> list:
    """Packed rows on the host, `[n, num_lanes]` -> the model's decoded
    canonical state of each (the field dict where the model has no
    decoder), through `StateSpec.unpack_rows`: numpy alone."""
    fields = model.spec.unpack_rows(rows)
    states = [{k: v[j, ...] for k, v in fields.items()}
              for j in range(len(rows))]
    return [model.decode(s) for s in states] if model.decode else states


def decode_packed(model: Model, row: np.ndarray):
    """One packed row -> its decoded state: the one-row case of
    `decode_rows`.  Both engines' verdict paths."""
    return decode_rows(model, np.asarray(row)[None])[0]


def init_violation_result(model: Model, inv, row, levels, total,
                          seconds: float, stats=None) -> CheckResult:
    """The result of a run whose initial state `row` breaks `inv`."""
    state = decode_packed(model, row)
    viol = Violation(invariant=inv.name, depth=0, state=state,
                     trace=[("<init>", state)])
    return CheckResult(model.name, levels, total, 0, viol, seconds,
                       total / max(seconds, 1e-9), stats=stats or {})


def build_violation(model: Model, trace_store, plog_view, inv_name, depth,
                    idx, obs=None) -> Optional[Violation]:
    """The Violation with its trace, walked from `trace_store` (the in-RAM
    store, None where the run keeps none), else from `plog_view` (an
    on-disk parent log's view, None where the log lacks the level); else
    None, and the caller reports the violating state trace-less."""
    for store, source in ((trace_store, "ram"), (plog_view, "disk")):
        if store is not None:
            return walk_trace(store, model, inv_name, depth, idx, obs=obs,
                              source=source)
    return None


#: fingerprint lanes (hi, lo) -> the uint64 keys the host sets hold
u64 = _integ.pair_u64


def chain_stamp(chain) -> dict:
    """The digest chain as a checkpoint's ``digest_chain`` array."""
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


def readback_chain(chain, path: str, depth: int) -> None:
    """Re-read a promoted checkpoint's stamped chain (anchored ones)."""
    if chain is not None and chain.anchored:
        _integ.readback_chain(path, depth=depth)


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
    """Breadth-first exhaustive check of `model`.  Stops at first violation.

    A driver over one run-state object and five phases (``engine/run.py``,
    ``engine/level.py``; docs/engine.md § The run and its phases).

    max_depth / max_states: stop before expanding a level at this depth /
    once this many states are held; the frontier left unexpanded still
    gets its invariant pass.  check_invariants=False skips every
    predicate.  min_bucket: the smallest power-of-two frontier bucket.
    progress: called ``(depth, new, total)`` after each level.
    collect_levels: an external list receiving each level's packed rows.

    store_trace: keep parent pointers, so a violation comes with its
    root-to-violation trace.  Forced off by `checkpoint_dir` and `seed`
    (a violation then reports its state with an EMPTY trace) and by the
    disk tier, whose parent log carries the trace, also across a resume.
    collect_trace: an external list receiving the per-level ``(rows,
    parent, act)`` store (service/batch.py).

    check_deadlock: TLC's CHECK_DEADLOCK TRUE: a reachable state with no
    enabled action violates the pseudo-invariant "Deadlock" (CONSTRAINT
    pruning does not mask enabledness).  Default off: the bounded corpus
    models deadlock by design (SURVEY.md §2.4).

    stats_path: append one JSON line per BFS level (the PROGRESS.jsonl
    stream; the same records land in ``CheckResult.stats["levels"]``).
    run: an obs.RunContext: stats, spans and metrics under one run_id in
    a run directory (docs/observability.md).

    visited_backend: where the fingerprint set lives.  "device" (the CLI
    default; what seven of the benchmark's eight cells run): a sorted
    fingerprint pair set in HBM: lexsort, binary search over the live
    prefix of the sorted queries, a merge that moves the live entries.
    "device-hash": an open-addressing table in HBM (ops/hashset),
    insert-or-find, no sort, no merge.  "host": the native C++ FpSet
    (native/fpset.cpp) does ALL dedup on the host, for fingerprint sets
    that outgrow device memory.  All three give identical counts and
    traces (with hashed fingerprints, at TLC's 64-bit collision risk);
    "device-hash" and "host" have not been timed on a chip (PERF.md).

    chunk_size: a larger frontier streams through the compiled step in
    pieces (cross-chunk dedup through the shared visited set), which
    bounds the compiled shapes and peak device memory.
    visited_capacity_hint: preallocate the device set for ~this many
    states plus a chunk of headroom, so capacity doubling (a recompile
    each) never triggers.  visited_capacity_exact: preallocate at exactly
    this capacity: a prior run's FINAL one (PreparedKernels.
    capacity_hint), which keeps every warm step-cache key identical.

    compact_shift: two-phase expansion: guards over the full padded
    lattice, then each action's update + pack and the sort / probe /
    merge at 1/2^compact_shift of the lattice width; a chunk that
    overflows a compact buffer is re-run wider (results stay exact); 0
    disables.  compact_gate: the frontier bucket below which every
    pipeline runs the uncompacted full-lattice path.

    pipeline: "fused" (default; $KSPEC_PIPELINE overrides): per chunk one
    guard-matrix launch, host compaction, one update-skeleton launch;
    "device": one dispatched program runs every gated chunk of a level
    (needs analyzer-proven field hulls; "device-hash" or any unmet
    precondition degrades to the fused ladder, ``stats["device"]
    ["fallback"]`` says why); "legacy": the per-action step, the tests'
    bit-identity reference.  All are bit-identical in counts, duplicate
    accounting, first-violation rule, traces and digest chains; a fused
    program that fails to compile degrades the run to legacy
    (``stats["degradations"]``).  docs/engine.md describes each.

    checkpoint_dir: persist (visited set, frontier, level counters) every
    `checkpoint_every` levels and restart from the last saved level where
    a checkpoint exists; the newest `checkpoint_keep` generations rotate
    under atomic promotes, each array checksummed, and a corrupt newest
    one falls back to the newest that verifies (docs/resilience.md).

    store / mem_budget / spill_dir: store="disk" (or "auto" with a
    `mem_budget`) bounds the host FpSet at `mem_budget` bytes and spills
    fingerprint runs, the frontier and the parent log to `spill_dir`
    (storage/); implies visited_backend="host"; bit-identical to RAM.
    disk_budget / governor: resource governance (resilience.resources):
    a soft breach of the spill + checkpoint byte budget reclaims, a hard
    one (or an ENOSPC) saves a checkpoint, stamps the run
    `resource-exhausted` and raises ResourceExhausted (CLI exit 75);
    `governor` is a pre-built one (the daemon's per-tenant budgets).

    prepared: a :class:`PreparedKernels` wrapping the SAME model object:
    every compiled step is re-used (no ``compile`` span in a warm check)
    and each whole-level program's first dispatch is sized from what the
    last run fed to ``note_result``.

    integrity_shadow: sampled shadow re-execution rate in [0, 1]
    ($KSPEC_INTEGRITY_SHADOW; default 0): a sampled chunk is re-executed
    through the legacy pipeline and the host fingerprint oracle BEFORE
    its outputs are committed.  Always on, whatever the rate: the
    per-level digest chain (stamped into checkpoints, verified at every
    level boundary and on resume), the save-time visited-set self-check,
    the read-side storage checksums.  A failure raises the typed
    IntegrityError (CLI exit 76).  KSPEC_INTEGRITY=0 disables the layer.

    seed: resume-shaped warm start from a VERIFIED prior exploration of
    the same model (service/state_cache.py): ``visited_fps``,
    ``frontier``, ``levels``, ``total``, ``depth``, ``digest_chain``; the
    run expands the boundary at ``depth`` after the chain verify has
    re-proved the seeded frontier.  Not with ``checkpoint_dir`` or disk.

    overlap: async level-pipelined execution ($KSPEC_OVERLAP; default ON;
    off = the serial order, the reference of tests/test_overlap.py): a
    chunk's programs are dispatched before the previous chunk's host
    commit, disk-tier merges and checkpoint writes run on worker threads
    (docs/engine.md § Async execution).  Bit-identical either way.
    """
    options = dict(locals())  # every parameter, under its own name
    t_check = _now()  # (the root span `check` starts here)
    _LEDGER.mark_backend()
    from .level import run_levels
    from .run import Run, close_run, open_run

    r = Run(options.pop("model"), t_check, **options)
    res = open_run(r)  # a result already where an initial state violates
    if res is None:
        run_levels(r)
        res = close_run(r)
    return res
