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
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

from ..ops.packing import StateSpec

# kernel: (state: dict[str, Array], choice: int32 scalar) -> (enabled: bool, next_state: dict)
SuccessorKernel = Callable
# pred: (state: dict[str, Array]) -> bool  (True = invariant holds)
PredicateKernel = Callable


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
