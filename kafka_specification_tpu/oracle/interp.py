"""Slow, obviously-correct reference interpreter ("the oracle").

Plays the role stock TLC would play for golden outputs (TLC is a Java tool
and is not available in this environment): each TLA+ module of the reference
corpus is transcribed 1:1 into Python set semantics (states as canonical
immutable values, actions as successor generators), and an explicit BFS
produces distinct-state counts, per-level counts, diameters and first
violations.  The JAX kernels are validated against this interpreter by exact
state-set comparison per BFS level (tests/), which is how we keep the tensor
kernels *provably* equivalent to the TLA+ semantics (SURVEY.md §7 step 2).

The interpreter deliberately shares no code with the kernel path.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional, Sequence


@dataclass(frozen=True)
class OracleAction:
    name: str
    # state -> iterable of successor states (already canonical/immutable)
    successors: Callable[[object], Iterable[object]]


@dataclass(frozen=True)
class OracleSymmetry:
    """TLC's ``SYMMETRY`` over the full permutation group of a constant
    set, on the oracle's own state values.  `permute(s, g)` is the image of
    state `s` under `g` (a tuple, ``g[i]`` the image of member ``i``);
    `canonical(s)` -> (the one member every state of `s`'s orbit maps to,
    the orbit's size ``order / |stabiliser|``)."""

    set_name: str
    n: int
    order: int
    permute: Callable[[object, tuple], object]
    canonical: Callable[[object], tuple]


@dataclass
class OracleModel:
    name: str
    init_states: Callable[[], Sequence[object]]
    actions: Sequence[OracleAction]
    invariants: Sequence[tuple[str, Callable[[object], bool]]]
    constraint: Optional[Callable[[object], bool]] = None
    # same vocabulary as Model.meta (drives TLA-style trace rendering)
    meta: dict = field(default_factory=dict)
    # set by :func:`reduce_by_symmetry`: the model's states are orbits
    symmetry: Optional[OracleSymmetry] = None


def reduce_by_symmetry(model: OracleModel, sym: OracleSymmetry) -> OracleModel:
    """The same transition relation over ORBITS: every initial state and
    every successor is replaced by its orbit's canonical member, so a
    caller that keeps a plain set of what the model hands it (oracle_bfs,
    the benchmark's own loop) counts orbits, as TLC does under SYMMETRY.
    Distance from Init is the same for every member of an orbit, so the
    per-level counts do not depend on which member stands for it."""
    canon = sym.canonical

    def reduced(action):
        def successors(s, _succ=action.successors):
            for t in _succ(s):
                yield canon(t)[0]

        return OracleAction(action.name, successors)

    inits = model.init_states
    return OracleModel(
        name=f"{model.name}/SYMMETRY({sym.set_name})",
        init_states=lambda: list(dict.fromkeys(canon(s)[0] for s in inits())),
        actions=[reduced(a) for a in model.actions],
        invariants=model.invariants,
        constraint=model.constraint,
        meta=dict(model.meta),
        symmetry=sym,
    )


@dataclass
class OracleResult:
    levels: list[int]
    level_sets: list[set]
    total: int
    diameter: int
    violation: Optional[tuple[str, int, object]]  # (invariant, depth, state)
    trace: list = field(default_factory=list)  # [(action_name, state), ...]

    @property
    def ok(self) -> bool:
        return self.violation is None


def oracle_bfs(
    model: OracleModel,
    max_depth: Optional[int] = None,
    max_states: Optional[int] = None,
    stop_on_violation: bool = True,
    keep_level_sets: bool = True,
    check_deadlock: bool = False,
) -> OracleResult:
    """check_deadlock: report a state with no successors as a violation of
    the pseudo-invariant "Deadlock" (TLC's CHECK_DEADLOCK TRUE).  Note: an
    oracle model whose generators bake constraint bounds into the guards
    (AsyncIsr) treats constraint-pruned successors as absent here."""
    inits = list(dict.fromkeys(model.init_states()))
    visited = set(inits)
    parent = {s: (None, "<init>") for s in inits}
    frontier = inits
    levels = [len(inits)]
    level_sets = [set(inits)] if keep_level_sets else []
    violation = None
    depth = 0

    def check(states, d):
        for name, pred in model.invariants:
            for s in states:
                if not pred(s):
                    return (name, d, s)
        return None

    violation = check(frontier, 0)
    while frontier and violation is None:
        if max_depth is not None and depth >= max_depth:
            break
        if max_states is not None and len(visited) >= max_states:
            break
        nxt = []
        for s in frontier:
            any_succ = False
            for a in model.actions:
                for t in a.successors(s):
                    any_succ = True
                    if model.constraint is not None and not model.constraint(t):
                        continue
                    if t not in visited:
                        visited.add(t)
                        parent[t] = (s, a.name)
                        nxt.append(t)
            if check_deadlock and not any_succ and violation is None:
                violation = ("Deadlock", depth, s)
        if violation is not None and check_deadlock and violation[0] == "Deadlock":
            frontier = []
            break
        depth += 1
        if nxt:
            levels.append(len(nxt))
            if keep_level_sets:
                level_sets.append(set(nxt))
        if stop_on_violation:
            violation = check(nxt, depth)
        frontier = nxt

    trace = []
    if violation is not None:
        s = violation[2]
        while s is not None:
            p, aname = parent[s]
            trace.append((aname, s))
            s = p
        trace.reverse()

    return OracleResult(
        levels=levels,
        level_sets=level_sets,
        total=len(visited),
        diameter=len(levels) - 1,
        violation=violation,
        trace=trace,
    )
