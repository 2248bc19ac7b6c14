"""Batched multi-config checking: one exploration, N verdicts.

N jobs whose configs share a schema shape (scheduler.group_key: module,
kernel source, constants, constraints — the GPUexplore insight that
batched expansion dominates explicit-state throughput, PAPERS.md
arXiv:1801.05857) are advanced by ONE engine run: the per-level vmapped
successor kernels launch once for the whole group instead of once per
job, so N toy checks cost ~1 launch per level.  Members may differ in
invariant selection (a .cfg-level difference) and in ``max_depth`` /
``max_states``.

How it stays bit-identical to ``cli check`` run solo (the acceptance
contract):

1.  Exploration is invariant-agnostic: successor generation, CONSTRAINT
    pruning, fingerprinting, dedup and chunking depend only on (model,
    engine knobs) — a solo run differs from the shared run only in
    *stopping earlier*.  The shared run uses the same knobs and explores
    to the envelope of the members' bounds (max of max_depth/max_states,
    unbounded if any member is unbounded), so every member's solo
    exploration is a prefix of the shared one, level for level, row for
    row.
2.  The shared run records everything a verdict needs: per-level state
    arrays (``collect_levels``), the parent/action trace store
    (``collect_trace``), and per-level counts.
3.  Each member's verdict is then *replayed* against the shared record
    with exactly the solo engine's semantics: init-state invariant pass
    first; then per level, chunk by chunk (same ``_next_pow2`` chunk
    boundaries), first chunk with a violation wins, first invariant in
    the member's model order within that chunk, first row within that
    invariant; ``max_depth``/``max_states`` cut at the same loop points;
    the cut-off run's final frontier gets the solo post-loop invariant
    pass (whole-frontier, per-invariant order).  Counterexample traces
    walk the shared trace store through the same ``walk_trace`` the
    engine uses — identical states, identical actions.

The derived verdicts are therefore equal to the solo runs' in counts,
depths, invariant names, and trace values (tests/test_service.py pins
this against real solo runs, violation and all).

Memory note: the shared record holds every level's states in RAM — this
runner is for the toy/small configs a multi-tenant service coalesces,
not for out-of-core runs (job specs carry no storage knobs; big runs
belong on `cli check`).  Singleton groups never come here at all: the
daemon runs them through the real solo engine path — first-violation
early exit, streamed levels — so only genuine coalescing pays the
full-envelope exploration (service/daemon.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from ..engine.bfs import (
    CheckResult,
    PreparedKernels,
    Violation,
    _next_pow2,
    check,
    decode_packed,
    walk_trace,
)


@dataclass
class Member:
    """One job's verdict-relevant view of a shared exploration."""

    job_id: str
    invariants: tuple  # names, in the member's solo model order
    max_depth: Optional[int] = None
    max_states: Optional[int] = None


class SharedExploration:
    """The shared run's record + lazy per-(level, invariant) evaluation."""

    def __init__(self, model, result: CheckResult, collected: list,
                 trace: list, chunk: int):
        self.model = model
        self.result = result
        self.levels = result.levels
        self.collected = collected
        self.trace = trace
        self.chunk = chunk
        self._preds = {i.name: i.pred for i in model.invariants}
        self._ok: dict = {}  # (level, name) -> np.bool_ array
        self._viol: dict = {}  # (name, depth, idx) -> Violation

    def _pred_fn(self, name: str, bucket: int):
        """Jitted unpack+predicate over a power-of-two state bucket,
        cached on the MODEL (like the engine's step cache) so later
        groups of the same shape pay zero re-trace: eager vmap re-traces
        per call, which dominated warm derive latency on quantifier-heavy
        invariants."""
        cache = getattr(self.model, "_inv_eval_cache", None)
        if cache is None:
            cache = {}
            try:
                self.model._inv_eval_cache = cache
            except AttributeError:
                pass
        key = (name, bucket)
        if key not in cache:
            pred = self._preds[name]
            unpack = self.model.spec.unpack

            cache[key] = jax.jit(
                lambda packed: jax.vmap(lambda row: pred(unpack(row)))(packed)
            )
        return cache[key]

    def ok(self, level: int, name: str) -> np.ndarray:
        """invariant `name` holds per state of `level` (evaluated once per
        (level, name) for the whole group — members share the cache)."""
        key = (level, name)
        if key not in self._ok:
            import jax.numpy as jnp

            rows = self.collected[level]
            n = rows.shape[0]
            bucket = _next_pow2(max(32, n))
            if bucket != n:
                pad = np.zeros((bucket - n, rows.shape[1]), rows.dtype)
                rows = np.concatenate([rows, pad])
            ok = np.asarray(self._pred_fn(name, bucket)(jnp.asarray(rows)))
            self._ok[key] = ok[:n]  # padding rows are garbage: sliced off
        return self._ok[key]

    def violation(self, name: str, depth: int, idx: int) -> Violation:
        """Walk the shared trace store once per distinct (invariant,
        depth, row) — members of a group that trip the same violation
        (the common case: N tenants checking the same buggy config) share
        the decoded trace instead of re-walking it N times."""
        key = (name, depth, idx)
        if key not in self._viol:
            self._viol[key] = walk_trace(
                self.trace, self.model, name, depth, idx
            )
        return self._viol[key]

    def decode(self, packed_row: np.ndarray):
        return decode_packed(self.model, packed_row)


def shared_bounds(members: list) -> tuple:
    """Envelope of the members' depth/state bounds (None dominates)."""
    md = None
    if all(m.max_depth is not None for m in members):
        md = max(m.max_depth for m in members)
    ms = None
    if all(m.max_states is not None for m in members):
        ms = max(m.max_states for m in members)
    return md, ms


def pack_members(group: list, max_group: int) -> list:
    """Split one planned group into submit-order packs of at most
    ``max_group`` members — the group-width planning hook the scheduler
    (plan_groups max_group=) and the sweep portfolio share.  The shared
    record holds every level of the ENVELOPE exploration in RAM and a
    group runs at the max of its members' bounds, so a thousand-member
    sweep group must be width-capped; contiguous submit-order packs keep
    the bounds of a sorted sweep (shallow..deep) clustered, which keeps
    each pack's envelope near its members' own bounds."""
    if max_group <= 0 or len(group) <= max_group:
        return [list(group)]
    return [
        list(group[i:i + max_group])
        for i in range(0, len(group), max_group)
    ]


def explore_shared(
    model,
    members: list,
    prepared: Optional[PreparedKernels] = None,
    min_bucket: int = 256,
    chunk_size: int = 32768,
    visited_backend: str = "device",
    run=None,
    governor=None,
    stats_path: Optional[str] = None,
) -> SharedExploration:
    """One invariant-agnostic engine run covering every member's bounds."""
    md, ms = shared_bounds(members)
    collected: list = []
    trace: list = []
    res = check(
        model,
        max_depth=md,
        max_states=ms,
        store_trace=True,
        min_bucket=min_bucket,
        check_invariants=False,
        collect_levels=collected,
        collect_trace=trace,
        chunk_size=chunk_size,
        visited_backend=visited_backend,
        prepared=prepared,
        run=run,
        governor=governor,
        stats_path=stats_path,
        # warm-path: preallocate the visited set at EXACTLY the capacity
        # the last run of this shape reached — no capacity growth, no
        # step eviction, no warm recompiles (PreparedKernels.capacity_hint)
        visited_capacity_exact=(
            prepared.capacity_hint if prepared is not None else None
        ),
    )
    if prepared is not None:
        prepared.note_result(res)
    return SharedExploration(
        model, res, collected, trace,
        chunk=_next_pow2(max(min_bucket, chunk_size)),
    )


def derive_member(shared: SharedExploration, member: Member) -> CheckResult:
    """Replay one member's solo verdict from the shared record (see module
    docstring for the exact-equivalence argument)."""
    t0 = time.perf_counter()
    L, C, T = shared.levels, shared.collected, shared.trace
    model = shared.model
    n0 = L[0]
    levels = [n0]
    total = n0
    violation = None

    def finish(depth: int) -> CheckResult:
        dt = max(time.perf_counter() - t0, 1e-9)
        return CheckResult(
            model=model.name,
            levels=levels,
            total=total,
            diameter=len(levels) - 1,
            violation=violation,
            seconds=shared.result.seconds,
            states_per_sec=total / max(shared.result.seconds, 1e-9),
            stats={"derive_ms": round(dt * 1e3, 2)},
        )

    # init-state invariant pass (solo engine: before the level loop,
    # per-invariant in model order, whole init set)
    for name in member.invariants:
        ok = shared.ok(0, name)
        if not ok.all():
            idx = int(np.argmax(~ok))
            state = shared.decode(C[0][idx])
            violation = Violation(
                invariant=name, depth=0, state=state,
                trace=[("<init>", state)],
            )
            return finish(0)

    depth = 0
    cut = False
    while True:
        n_frontier = C[depth].shape[0] if depth < len(C) else 0
        if n_frontier == 0:
            break
        if member.max_depth is not None and depth >= member.max_depth:
            cut = True
            break
        if member.max_states is not None and total >= member.max_states:
            cut = True
            break
        # mid-level scan: first chunk (solo chunk boundaries) with any
        # member-invariant violation; within it, first invariant in the
        # member's model order; within that, first row
        verdict = None
        for start in range(0, n_frontier, shared.chunk):
            end = min(start + shared.chunk, n_frontier)
            for name in member.invariants:
                bad = ~shared.ok(depth, name)[start:end]
                if bad.any():
                    verdict = (name, start + int(np.argmax(bad)))
                    break
            if verdict is not None:
                break
        if verdict is not None:
            name, idx = verdict
            violation = shared.violation(name, depth, idx)
            break
        if depth + 1 >= len(C):
            # expanding this level produced nothing new: the solo loop's
            # next iteration sees an empty frontier and exits
            break
        depth += 1
        levels.append(L[depth])
        total += L[depth]

    if violation is None and member.invariants and cut \
            and depth < len(C) and C[depth].shape[0]:
        # solo post-loop pass: the cut left this frontier unexpanded, so
        # its states still owe their invariant check (whole-frontier,
        # per-invariant order — NOT the chunked mid-level rule)
        for name in member.invariants:
            ok = shared.ok(depth, name)
            if not ok.all():
                idx = int(np.argmax(~ok))
                violation = shared.violation(name, depth, idx)
                break
    return finish(depth)


def run_group(
    model,
    members: list,
    prepared: Optional[PreparedKernels] = None,
    **explore_kw,
) -> dict:
    """Explore once, derive every member.
    -> ({job_id: CheckResult}, SharedExploration)."""
    shared = explore_shared(model, members, prepared=prepared, **explore_kw)
    return {
        m.job_id: derive_member(shared, m) for m in members
    }, shared
