"""Model API: a TLA+ spec compiled to tensor form.

A Model is the TPU-native analogue of (TLA+ module + TLC .cfg):

- `spec` defines the canonical tensor encoding of one state,
- each Action is one disjunct of `Next`, compiled to a successor kernel over a
  *fixed* choice space (the bounded existentials of the TLA+ action, e.g.
  `\\E replica \\in Replicas` -> choice = replica index).  The kernel returns
  (enabled?, next_state) for a given (state, choice); the engine vmaps it over
  states x choices and masks disabled combinations — this is how TLC's
  nondeterministic disjunct expansion becomes a dense TPU computation,
- each Invariant is a predicate kernel (True = state OK),
- `constraint`, if set, is TLC's CONSTRAINT: successors violating it are
  pruned (not explored, not counted) — required to bound AsyncIsr, whose
  LeaderWrite has no MaxOffset guard (/root/reference/AsyncIsr.tla:117-119).

A kernel or predicate indexes a state axis through :func:`read` and
:func:`write`, never by ``x[traced]`` or ``x.at[traced].set(v)``: under the
engine's ``vmap`` those become an XLA gather or scatter a row, and the
corpus's axes are 2 to 16 long (tests/test_expansion_gathers.py pins the
hand models' programs at none).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..analysis.interval import IVal
from ..ops.packing import StateSpec

# kernel: (state: dict[str, Array], choice: int32 scalar) -> (enabled: bool, next_state: dict)
SuccessorKernel = Callable
# pred: (state: dict[str, Array]) -> bool  (True = invariant holds)
PredicateKernel = Callable


# The longest axis `read` / `write` lower by compare-and-select; a longer
# one keeps plain indexing (a gather / scatter a row under `vmap`).  On a
# v5e chip, 262,144 rows under vmap, ms a call (PR 39's microbenchmark,
# chiprun_out/pr39/bench/table_C.json): a gathered read costs 2.7-3.6 and a
# scattered write 2.0-2.2 whatever the axis's length n; the select read
# 0.60 / 0.63 / 0.76 / 0.98 / 1.41 and the select write 0.62 / 0.59 / 0.60 /
# 0.65 / 0.84 at n = 3 / 5 / 16 / 32 / 64 (the `[r, off]` pair alike: 0.66-
# 0.99 against 3.9-4.3).  Select grows by ~0.013 ms a unit of n and is still
# 2.4 x ahead at 64, the longest axis measured, so the bound stands there;
# the crossover extrapolates to n ~ 200 and no model has an axis to show it
# (every axis of the corpus is 2-16 long).
SELECT_MAX = 64


def _selects(x, idx) -> bool:
    """Whether `x` indexed by `idx` lowers by compare-and-select: a real
    array (the interval domain has its own, precise indexed read / update:
    analysis/interval.py) whose indexed axes are all short."""
    return not isinstance(x, IVal) and all(
        x.shape[a] <= SELECT_MAX
        for a, i in enumerate(idx) if not isinstance(i, slice)
    )


def _wrapped(i, n: int):
    """numpy's negative index: ``i + n`` where ``i < 0``."""
    return jnp.where(i < 0, i + n, i)


def _pick(x, i, axis: int):
    """`x` at a traced `i` along `axis`, by a chain of selects."""
    n = x.shape[axis]
    i = _wrapped(i, n)
    lead = (slice(None),) * axis
    out = x[lead + (n - 1,)]  # every i >= n - 1: the clamp above
    for k in range(n - 2, 0, -1):
        out = jnp.where(i == k, x[lead + (k,)], out)
    if n > 1:
        out = jnp.where(i <= 0, x[lead + (0,)], out)  # and below
    return out


def _put(x, v, *idx):
    """`v` into `x` at traced `idx`, one index an axis from the first."""
    hit = True
    for axis, i in enumerate(idx):
        n = x.shape[axis]
        at = jnp.arange(n, dtype=jnp.int32) == _wrapped(i, n)
        hit = hit & at.reshape((n,) + (1,) * (x.ndim - axis - 1))
    return jnp.where(hit, v.astype(x.dtype), x)


@functools.cache
def _inlined():
    """`_pick` and `_put` as inlined jits: a kernel calls them a dozen
    times at a handful of shapes, and jit's trace cache hands every call
    after a shape's first its jaxpr, which is batched and spliced into the
    caller without running the `jnp` calls above again (a program's trace
    time is what a fresh process pays 150 times in set-up).  Built on
    first use: `cli analyze` imports this module with no jax at all."""
    import jax

    return (jax.jit(_pick, static_argnames="axis", inline=True),
            jax.jit(_put, inline=True))


def read(x, *idx):
    """``x[idx]`` for indices that may be traced, to the bit, in and out of
    range (a negative index wraps once, then the read clamps to the axis).
    `idx` is one index an axis from the first; a leading ``slice(None)``
    keeps its axis (``read(x, slice(None), off)`` is ``x[:, off]``).  Over
    an axis of at most ``SELECT_MAX`` the element is picked by a chain of
    selects over the axis's static slices, which stays elementwise under
    ``vmap``; over a longer one, or in the interval domain, it is
    ``x[idx]`` itself."""
    if not _selects(x, idx):
        return x[idx]
    axis = 0
    for i in idx:
        if isinstance(i, slice):
            assert i == slice(None), i
            axis += 1
        elif isinstance(i, (int, np.integer)):  # a static slice as it was
            x = x[(slice(None),) * axis + (i,)]
        else:
            x = _inlined()[0](x, i, axis=axis)
    return x


def write(x, idx, v):
    """``x.at[idx].set(v)`` for an index or a tuple of indices (one an axis
    from the first) that may be traced, to the bit, in and out of range (a
    negative index wraps once; a write out of range is dropped whole).
    Over axes of at most ``SELECT_MAX`` it is one select of `v` against `x`
    under the conjunction of a comparison an axis; over a longer one, or
    in the interval domain, it is ``x.at[idx].set(v)`` itself."""
    idx = idx if isinstance(idx, tuple) else (idx,)
    if not _selects(x, idx):
        return x.at[idx].set(v)
    return _inlined()[1](x, jnp.asarray(v), *(jnp.int32(i) for i in idx))


@dataclass(frozen=True)
class Action:
    name: str
    n_choices: int
    kernel: SuccessorKernel
    # declared write set (TLA+ frame condition: the variables this
    # action's disjunct primes).  None = undeclared (emitted models,
    # ad-hoc test kernels); when declared, the static analyzer's
    # frame-condition pass proves the kernel writes nothing else
    # (analysis/encoding.py; docs/analysis.md)
    writes: Optional[frozenset] = None


@dataclass(frozen=True)
class Invariant:
    name: str
    pred: PredicateKernel


@dataclass(frozen=True)
class FieldRole:
    """How one state field moves under a permutation ``g`` of a symmetric
    constant set (see :class:`Symmetry`).  Three roles and their products:

    - ``axis``: the axis of the field's shape that the set indexes
      (``end[replica]``: 0); the image's slot ``g(i)`` holds slot ``i``;
    - ``value`` ``"member"``: the elements ARE members of the set (a leader
      id); a value in ``[0, n)`` maps to ``g(value)``, every other value is
      a fixed sentinel (None, Nil, an absent slot);
    - ``value`` ``"mask"``: the elements are subsets of the set as bitmasks
      (an ISR); bit ``j`` of the image is bit ``g^-1(j)`` of the element.

    ``ldr[replica]`` is indexed AND member-valued, ``isr[replica]`` indexed
    AND a mask; a field the set touches in neither way has no entry."""

    axis: Optional[int] = None
    value: Optional[str] = None  # None | "member" | "mask"

    def __post_init__(self):
        assert self.value in (None, "member", "mask"), self.value
        assert self.axis is not None or self.value is not None


@dataclass(frozen=True)
class Symmetry:
    """TLC's ``SYMMETRY`` over ``Permutations(set_name)``: the full
    permutation group of a constant set of ``n`` model values, and per
    field of the state how it moves under one (:class:`FieldRole`).  Two
    states are one iff some permutation maps one to the other; the engine
    keys a state by its orbit (ops/canon.py), the oracle twin yields
    canonical members (its own code).  `operator` is the identifier a
    ``.cfg`` names in its ``SYMMETRY`` stanza."""

    set_name: str
    n: int
    roles: dict  # field name -> FieldRole
    operator: str = "Symm"

    @property
    def order(self) -> int:
        """|G| = n!"""
        import math

        return math.factorial(self.n)

    def describe(self) -> dict:
        """The manifest's ``result.symmetry``."""
        return {"set": self.set_name, "order": self.order}


@dataclass
class Model:
    name: str
    spec: StateSpec
    init_states: Callable[[], Sequence[dict]]
    actions: Sequence[Action]
    invariants: Sequence[Invariant]
    constraint: Optional[PredicateKernel] = None
    # canonical Python value for a decoded state; must equal the oracle
    # interpreter's state representation so state *sets* can be compared.
    decode: Optional[Callable[[dict], object]] = None
    meta: dict = field(default_factory=dict)
    # optional fused evaluator: state -> bool[len(invariants)] (column i =
    # invariants[i] holds).  Lets an implementation share work ACROSS
    # invariant predicates within one trace (the emitted models' WeakIsr
    # and StrongIsr share their quantifier core); engines fall back to the
    # per-invariant preds when None (and for single-invariant re-checks).
    invariants_fused: Optional[Callable] = None
    # TLC's SYMMETRY, switched on by the .cfg's stanza (utils/cfg.py): the
    # single-device engine then stores one state an orbit.  None: every
    # state is its own orbit, and no program of the model changes.
    symmetry: Optional[Symmetry] = None

    def __post_init__(self):
        # spec-width soundness at EVERY model construction: each declared
        # field range must fit the int32 packed-element dtype and a
        # 32-bit lane (the general form of the AsyncIsr N<=4 cliff; the
        # interval pass over the action kernels runs at the engine/CLI
        # gates — analysis/encoding.py, docs/analysis.md).  jax-free.
        from ..analysis.encoding import check_spec_fields

        check_spec_fields(self.spec.fields, context=self.name)

    @property
    def total_fanout(self) -> int:
        return sum(a.n_choices for a in self.actions)

    def invariant(self, name: str) -> Invariant:
        for inv in self.invariants:
            if inv.name == name:
                return inv
        raise KeyError(name)
