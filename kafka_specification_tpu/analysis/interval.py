"""Interval abstract interpretation of the models' successor kernels.

The encoding-soundness pass (analysis/encoding.py) must prove, for every
shipped model and every CONSTANTS valuation, that every field an action
writes stays within the field's declared [lo, hi] — the range the bit
packer (ops/packing.StateSpec) silently truncates to.  The proof runs the
*actual shipped kernel code*: each action kernel is executed once per
choice with the state fields bound to interval values (lo/hi hulls over
the declared field ranges) and the module-level ``jnp`` name temporarily
rebound to the abstract namespace below — so there is no second
transcription of the update semantics that could drift from the kernels
the engine runs (the alpha-normalize capture bug class this subsystem
exists to close).

Domain: non-relational intervals over arbitrary-precision Python ints
(numpy ``object`` arrays carry the element lattice so field shapes and
broadcasting come for free; Python ints mean a 2^32-bit bitset bound can
never overflow the *analyzer*).  Two refinements keep the shipped
kernels precise enough to verify clean:

- **guard refinement**: scalar comparisons whose operand is a direct
  field read (``s["end"][r] < L``) record a constraint on the enabled
  value they flow into through ``&``; each (action, choice) is evaluated
  twice — once to collect the guard's constraints, once against the
  state refined by them.  This is sound because the engine only commits
  successors whose guard held.  Disjunctions (``|``) and negations drop
  constraints (weaker, still sound).
- **per-element arrays**: indexed reads/updates with concrete indices
  (choice-derived) are strong; abstract indices join over the index
  hull, clipped to the axis like XLA's gather/scatter clamp/drop rule.

Everything here is jax-free: the abstract ``jnp`` is this module's, and
``cli analyze`` imports the model modules under the stub installed by
:func:`..analysis.install_jax_stub`.
"""

from __future__ import annotations

import sys
from typing import Optional

import numpy as np


class AnalysisUnsupported(Exception):
    """The kernel used a construct the abstract domain does not model.
    Callers skip the action (recorded as an INFO finding) rather than
    guessing — an imprecise skip is visible, a wrong hull is not."""


def _obj(x) -> np.ndarray:
    """Coerce to an object-dtype ndarray of Python ints."""
    a = np.asarray(x, dtype=object)
    if a.shape == ():
        a = a.reshape(())
    return a


def _aint(x):
    """Normalize a numpy scalar / bool to a Python int."""
    if isinstance(x, bool) or isinstance(x, np.bool_):
        return int(x)
    if isinstance(x, np.generic):
        return int(x)
    return x


class IVal:
    """An interval-valued tensor: elementwise [lo, hi] (inclusive), with
    optional provenance for guard refinement and field-dependency taint.

    - ``origin``: (field, idx_tuple) when this value IS a direct (chain
      of concrete-index) read of a state field — the only values guard
      refinement may constrain.
    - ``deps``: frozenset of field names whose values flowed into this
      one (read-set accounting for the action lint).
    - ``constraints``: guard facts of the form (field, idx, "le"|"ge",
      bound) collected from scalar comparisons; survive only ``&``.
    """

    __slots__ = ("lo", "hi", "origin", "deps", "constraints", "is_bool")

    def __init__(self, lo, hi, origin=None, deps=frozenset(),
                 constraints=(), is_bool=False):
        self.lo = _obj(lo)
        self.hi = _obj(hi)
        if self.lo.shape != self.hi.shape:
            lo_b, hi_b = np.broadcast_arrays(self.lo, self.hi)
            self.lo, self.hi = lo_b.copy(), hi_b.copy()
        self.origin = origin
        self.deps = deps
        self.constraints = tuple(constraints)
        self.is_bool = bool(is_bool)

    # -- constructors ------------------------------------------------------
    @classmethod
    def const(cls, v):
        v = _aint(v)
        return cls(v, v)

    @classmethod
    def coerce(cls, v) -> "IVal":
        if isinstance(v, IVal):
            return v
        if isinstance(v, (bool, np.bool_)):
            return cls(int(v), int(v), is_bool=True)
        if isinstance(v, (int, np.integer)):
            return cls.const(v)
        if isinstance(v, (list, tuple, np.ndarray)):
            a = _obj([_aint(x) for x in np.asarray(v).reshape(-1)])
            a = a.reshape(np.asarray(v).shape)
            return cls(a, a.copy())
        raise AnalysisUnsupported(f"cannot abstract {type(v).__name__}")

    # -- shape plumbing ----------------------------------------------------
    @property
    def shape(self):
        return self.lo.shape

    @property
    def ndim(self):
        return self.lo.ndim

    def concrete_scalar(self) -> Optional[int]:
        if self.shape == () and self.lo.item() == self.hi.item():
            return int(self.lo.item())
        return None

    def _bin_deps(self, other) -> frozenset:
        o = other.deps if isinstance(other, IVal) else frozenset()
        return self.deps | o

    def __repr__(self):
        if self.shape == ():
            return f"IVal[{self.lo.item()}, {self.hi.item()}]"
        return f"IVal(shape={self.shape})"

    # -- arithmetic --------------------------------------------------------
    def __add__(self, other):
        o = IVal.coerce(other)
        return IVal(self.lo + o.lo, self.hi + o.hi,
                    deps=self._bin_deps(o))

    __radd__ = __add__

    def __sub__(self, other):
        o = IVal.coerce(other)
        return IVal(self.lo - o.hi, self.hi - o.lo,
                    deps=self._bin_deps(o))

    def __rsub__(self, other):
        return IVal.coerce(other).__sub__(self)

    def __mul__(self, other):
        o = IVal.coerce(other)
        cands = [self.lo * o.lo, self.lo * o.hi,
                 self.hi * o.lo, self.hi * o.hi]
        return IVal(np.minimum.reduce(cands), np.maximum.reduce(cands),
                    deps=self._bin_deps(o))

    __rmul__ = __mul__

    def __neg__(self):
        return IVal(-self.hi, -self.lo, deps=self.deps)

    def _div_corners(self, o, op):
        if not bool(np.all(o.lo > 0)):
            raise AnalysisUnsupported("division by non-positive interval")
        cands = [op(self.lo, o.lo), op(self.lo, o.hi),
                 op(self.hi, o.lo), op(self.hi, o.hi)]
        return IVal(np.minimum.reduce(cands), np.maximum.reduce(cands),
                    deps=self._bin_deps(o))

    def __floordiv__(self, other):
        return self._div_corners(IVal.coerce(other),
                                 lambda a, b: a // b)

    def __mod__(self, other):
        o = IVal.coerce(other)
        n = o.concrete_scalar()
        if n is None or n <= 0:
            raise AnalysisUnsupported("modulo by non-constant")
        same_block = (self.lo // n) == (self.hi // n)
        lo = np.where(same_block, self.lo % n, 0)
        hi = np.where(same_block, self.hi % n, n - 1)
        if bool(np.any(self.lo < 0)):
            lo = np.minimum(lo, self.lo)  # conservative for negatives
        return IVal(lo, hi, deps=self._bin_deps(o))

    # -- shifts (monotone in both operands; 4-corner hull) ----------------
    def _shift(self, other, op):
        o = IVal.coerce(other)
        if bool(np.any(o.lo < 0)):
            raise AnalysisUnsupported("negative shift amount")
        if bool(np.any(o.hi > 1 << 20)):
            raise AnalysisUnsupported("shift amount too large to bound")
        cands = [op(self.lo, o.lo), op(self.lo, o.hi),
                 op(self.hi, o.lo), op(self.hi, o.hi)]
        return IVal(np.minimum.reduce(cands), np.maximum.reduce(cands),
                    deps=self._bin_deps(o))

    def __lshift__(self, other):
        return self._shift(other, lambda a, b: a << b)

    def __rlshift__(self, other):
        return IVal.coerce(other)._shift(self, lambda a, b: a << b)

    def __rshift__(self, other):
        return self._shift(other, lambda a, b: a >> b)

    def __rrshift__(self, other):
        return IVal.coerce(other)._shift(self, lambda a, b: a >> b)

    # -- bitwise hulls -----------------------------------------------------
    @staticmethod
    def _mask_hull(a_hi, b_hi):
        """All-ones hull >= a|b for nonneg operands (elementwise)."""
        def bits(x):
            return int(x).bit_length()
        vb = np.frompyfunc(
            lambda x, y: (1 << max(bits(max(x, 0)), bits(max(y, 0)))) - 1,
            2, 1,
        )
        return vb(a_hi, b_hi)

    def _is_boolish(self) -> bool:
        return bool(np.all(self.lo >= 0)) and bool(np.all(self.hi <= 1))

    def __and__(self, other):
        o = IVal.coerce(other)
        deps = self._bin_deps(o)
        # guard conjunction: `enabled = c1 & c2 & ...` — the ONLY operator
        # that propagates refinement constraints (if a & b is true, both
        # conjuncts held); sound for {0,1}-valued operands only
        cons = (self.constraints + o.constraints
                if self._is_boolish() and o._is_boolish() else ())
        if self._is_boolish() and o._is_boolish():
            # logical conjunction on {0,1}: products keep definiteness
            return IVal(self.lo * o.lo, self.hi * o.hi,
                        deps=deps, constraints=cons,
                        is_bool=self.is_bool and o.is_bool)
        a_nn = bool(np.all(self.lo >= 0))
        b_nn = bool(np.all(o.lo >= 0))
        if a_nn and b_nn:
            shape = np.broadcast(self.lo, o.lo).shape
            return IVal(np.zeros(shape, object),
                        np.minimum(self.hi + 0 * o.hi, o.hi + 0 * self.hi),
                        deps=deps, constraints=cons)
        if b_nn:  # a & b with b >= 0 is in [0, b.hi]
            z = 0 * self.hi
            return IVal(z + 0 * o.lo, o.hi + z, deps=deps)
        if a_nn:
            z = 0 * o.hi
            return IVal(z + 0 * self.lo, self.hi + z, deps=deps)
        # both may be negative: bound by the wider two's-complement width
        m = self._mask_hull(np.maximum(np.abs(self.lo), np.abs(self.hi)),
                            np.maximum(np.abs(o.lo), np.abs(o.hi)))
        return IVal(-(m + 1), np.maximum(self.hi + 0 * o.hi,
                                         o.hi + 0 * self.hi), deps=deps)

    __rand__ = __and__

    def __or__(self, other):
        o = IVal.coerce(other)
        deps = self._bin_deps(o)
        if self._is_boolish() and o._is_boolish():
            # logical disjunction on {0,1} (constraints drop: a true
            # disjunction pins neither side)
            return IVal(np.maximum(self.lo + 0 * o.lo, o.lo + 0 * self.lo),
                        np.maximum(self.hi + 0 * o.hi, o.hi + 0 * self.hi),
                        deps=deps, is_bool=self.is_bool and o.is_bool)
        lo = np.minimum(self.lo + 0 * o.lo, o.lo + 0 * self.lo)
        # a | b < 0 iff either operand < 0; definitely-negative => hi = -1
        both_nn_possible = (self.hi >= 0) & (o.hi >= 0)
        hull = self._mask_hull(self.hi, o.hi)
        hi = np.where(both_nn_possible, hull, -1)
        return IVal(lo, hi, deps=deps)

    __ror__ = __or__

    def __xor__(self, other):
        o = IVal.coerce(other)
        m = self._mask_hull(np.maximum(np.abs(self.lo), np.abs(self.hi)),
                            np.maximum(np.abs(o.lo), np.abs(o.hi)))
        return IVal(-(m + 1), m, deps=self._bin_deps(o))

    __rxor__ = __xor__

    def __invert__(self):
        if self.is_bool:
            # jnp logical-not on bool arrays (constraints drop: they
            # describe the un-negated fact)
            return IVal(1 - self.hi, 1 - self.lo, deps=self.deps,
                        is_bool=True)
        return IVal(-self.hi - 1, -self.lo - 1, deps=self.deps)

    # -- comparisons -> abstract booleans in {0, 1} -----------------------
    def _cmp(self, other, defi_true, defi_false, facts):
        o = IVal.coerce(other)
        t = defi_true(self, o)
        f = defi_false(self, o)
        lo = np.where(t, 1, 0)
        hi = np.where(f, 0, 1)
        cons = []
        if self.shape == () and o.shape == ():
            for side, mirror, val in facts:
                src = self if side == "a" else o
                if src.origin is not None:
                    cons.append((src.origin[0], src.origin[1], mirror,
                                 int(val(self, o))))
        return IVal(lo, hi, deps=self._bin_deps(o), constraints=cons,
                    is_bool=True)

    def __lt__(self, other):
        return self._cmp(
            other,
            lambda a, b: a.hi < b.lo,
            lambda a, b: a.lo >= b.hi,
            facts=[("a", "le", lambda a, b: b.hi.item() - 1),
                   ("b", "ge", lambda a, b: a.lo.item() + 1)],
        )

    def __le__(self, other):
        return self._cmp(
            other,
            lambda a, b: a.hi <= b.lo,
            lambda a, b: a.lo > b.hi,
            facts=[("a", "le", lambda a, b: b.hi.item()),
                   ("b", "ge", lambda a, b: a.lo.item())],
        )

    def __gt__(self, other):
        return self._cmp(
            other,
            lambda a, b: a.lo > b.hi,
            lambda a, b: a.hi <= b.lo,
            facts=[("a", "ge", lambda a, b: b.lo.item() + 1),
                   ("b", "le", lambda a, b: a.hi.item() - 1)],
        )

    def __ge__(self, other):
        return self._cmp(
            other,
            lambda a, b: a.lo >= b.hi,
            lambda a, b: a.hi < b.lo,
            facts=[("a", "ge", lambda a, b: b.lo.item()),
                   ("b", "le", lambda a, b: a.hi.item())],
        )

    def __eq__(self, other):  # noqa: D105 — abstract, not identity
        return self._cmp(
            other,
            lambda a, b: (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo),
            lambda a, b: (a.hi < b.lo) | (a.lo > b.hi),
            facts=[("a", "le", lambda a, b: b.hi.item()),
                   ("a", "ge", lambda a, b: b.lo.item()),
                   ("b", "le", lambda a, b: a.hi.item()),
                   ("b", "ge", lambda a, b: a.lo.item())],
        )

    def __ne__(self, other):  # noqa: D105
        return self._cmp(
            other,
            lambda a, b: (a.hi < b.lo) | (a.lo > b.hi),
            lambda a, b: (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo),
            facts=[],
        )

    __hash__ = None  # abstract == is not an equivalence

    def __bool__(self):
        c = self.concrete_scalar()
        if c is None:
            raise AnalysisUnsupported(
                "data-dependent Python branch on an abstract value"
            )
        return bool(c)

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        lo, hi = self.lo, self.hi
        origin = self.origin
        deps = self.deps
        axis = 0
        for part in idx:
            part = _aint(part) if isinstance(part, np.generic) else part
            if isinstance(part, IVal):
                c = part.concrete_scalar()
                deps = deps | part.deps
                if c is not None:
                    part = c
                else:
                    # abstract gather: join over the index hull, clipped
                    # to the axis (XLA gather clamps out-of-bounds)
                    n = lo.shape[axis]
                    a = max(0, min(int(part.lo.item()), n - 1))
                    b = max(0, min(int(part.hi.item()), n - 1))
                    sl = [slice(None)] * lo.ndim
                    sl[axis] = slice(a, b + 1)
                    lo = np.minimum.reduce(lo[tuple(sl)], axis=axis)
                    hi = np.maximum.reduce(hi[tuple(sl)], axis=axis)
                    origin = None
                    continue
            if isinstance(part, (bool, np.bool_)):
                raise AnalysisUnsupported("boolean mask indexing")
            if isinstance(part, int):
                n = lo.shape[axis]
                p = max(-n, min(part, n - 1))  # numpy/jnp clamp semantics
                lo = np.take(lo, p, axis=axis)
                hi = np.take(hi, p, axis=axis)
                if origin is not None:
                    origin = (origin[0], origin[1] + (p,))
                continue
            if isinstance(part, slice):
                sl = [slice(None)] * lo.ndim
                sl[axis] = part
                lo = lo[tuple(sl)]
                hi = hi[tuple(sl)]
                origin = None
                axis += 1
                continue
            raise AnalysisUnsupported(f"index kind {type(part).__name__}")
        lo, hi = _obj(lo), _obj(hi)  # np.take on object arrays may
        # return the bare element
        if origin is not None and lo.ndim != 0:
            origin = None  # refinement constrains fully-indexed scalars only
        return IVal(lo.copy() if isinstance(lo, np.ndarray) else lo,
                    hi.copy() if isinstance(hi, np.ndarray) else hi,
                    origin=origin, deps=deps, is_bool=self.is_bool)

    # -- functional updates (.at[idx].set(v)) ------------------------------
    @property
    def at(self):
        return _At(self)

    def join(self, other: "IVal") -> "IVal":
        o = IVal.coerce(other)
        return IVal(np.minimum(self.lo + 0 * o.lo, o.lo + 0 * self.lo),
                    np.maximum(self.hi + 0 * o.hi, o.hi + 0 * self.hi),
                    deps=self._bin_deps(o))


class _At:
    def __init__(self, base: IVal):
        self.base = base

    def __getitem__(self, idx):
        return _AtIndexed(self.base, idx)


class _AtIndexed:
    def __init__(self, base: IVal, idx):
        self.base = base
        self.idx = idx if isinstance(idx, tuple) else (idx,)

    def set(self, v):
        base = self.base
        v = IVal.coerce(v)
        lo = base.lo.copy()
        hi = base.hi.copy()
        deps = base.deps | v.deps
        # resolve leading concrete indices into a target sub-view
        concrete: list = []
        rest = list(self.idx)
        abstract = None
        for part in rest:
            part = _aint(part) if isinstance(part, np.generic) else part
            if isinstance(part, IVal):
                c = part.concrete_scalar()
                deps = deps | part.deps
                if c is not None:
                    concrete.append(c)
                    continue
                abstract = part
                break
            elif isinstance(part, int):
                concrete.append(part)
            else:
                raise AnalysisUnsupported(
                    f".at index kind {type(part).__name__}"
                )
        n_abs = len(self.idx) - len(concrete)
        if abstract is None:
            # strong update at a fully/partially concrete position
            pos = tuple(concrete)
            for d, p in enumerate(pos):
                n = base.lo.shape[d]
                if not (-n <= p < n):
                    return IVal(lo, hi, deps=deps)  # XLA scatter drop
            tgt_shape = lo[pos].shape if isinstance(lo[pos], np.ndarray) \
                else ()
            lo[pos] = np.broadcast_to(v.lo, tgt_shape) if tgt_shape \
                else v.lo.item() if v.lo.shape == () else v.lo
            hi[pos] = np.broadcast_to(v.hi, tgt_shape) if tgt_shape \
                else v.hi.item() if v.hi.shape == () else v.hi
            return IVal(lo, hi, deps=deps)
        if n_abs != 1 or v.shape != ():
            raise AnalysisUnsupported(
                "abstract scatter supports one abstract axis and a "
                "scalar value"
            )
        # weak update: every position the abstract index may hit joins
        # with the written value (out-of-range portions drop, like XLA)
        axis = len(concrete)
        n = base.lo.shape[axis]
        a = max(0, min(int(abstract.lo.item()), n - 1))
        b = max(0, min(int(abstract.hi.item()), n - 1))
        if int(abstract.hi.item()) < 0 or int(abstract.lo.item()) > n - 1:
            return IVal(lo, hi, deps=deps)  # entirely out of range: drop
        for p in range(a, b + 1):
            pos = tuple(concrete) + (p,)
            lo[pos] = min(lo[pos], v.lo.item())
            hi[pos] = max(hi[pos], v.hi.item())
        return IVal(lo, hi, deps=deps)


# --------------------------------------------------------------------------
# abstract jnp namespace
# --------------------------------------------------------------------------


def _defi(x: IVal):
    """(definitely-true mask, definitely-false mask) under jnp TRUTHINESS
    — any nonzero value is true, so definitely-true means 0 is outside
    the interval (lo > 0 or hi < 0) and definitely-false means the
    interval IS {0}.  Comparison results are {0,1}-valued so this
    degenerates to the boolean rule there, but a kernel branching on a
    raw integer (`jnp.where(x - 5, a, b)`) must not have its negative
    range read as false."""
    return ((x.lo >= 1) | (x.hi <= -1)), ((x.lo == 0) & (x.hi == 0))


class AbstractJnp:
    """Duck-typed stand-in for the ``jnp`` module name inside kernels.

    Covers exactly the operation set the shipped model kernels use
    (jnp.where/minimum/maximum/clip/all/any/min/max/arange/int32/
    broadcast_to); anything else raises AnalysisUnsupported so the
    caller records an honest skip instead of a wrong hull.
    """

    int32 = staticmethod(lambda x=0: IVal.coerce(x))
    int64 = staticmethod(lambda x=0: IVal.coerce(x))

    @staticmethod
    def arange(n, dtype=None):
        return IVal.coerce(list(range(int(n))))

    @staticmethod
    def asarray(x, dtype=None):
        return IVal.coerce(x)

    @staticmethod
    def array(x, dtype=None):
        return IVal.coerce(x)

    @staticmethod
    def bool_(x):
        return IVal.coerce(int(bool(x)) if isinstance(x, bool) else x)

    @staticmethod
    def minimum(a, b):
        a, b = IVal.coerce(a), IVal.coerce(b)
        return IVal(np.minimum(a.lo + 0 * b.lo, b.lo + 0 * a.lo),
                    np.minimum(a.hi + 0 * b.hi, b.hi + 0 * a.hi),
                    deps=a.deps | b.deps)

    @staticmethod
    def maximum(a, b):
        a, b = IVal.coerce(a), IVal.coerce(b)
        return IVal(np.maximum(a.lo + 0 * b.lo, b.lo + 0 * a.lo),
                    np.maximum(a.hi + 0 * b.hi, b.hi + 0 * a.hi),
                    deps=a.deps | b.deps)

    @classmethod
    def clip(cls, x, lo, hi):
        return cls.maximum(cls.minimum(IVal.coerce(x), hi), lo)

    @staticmethod
    def where(cond, a, b):
        if isinstance(cond, (bool, np.bool_)):
            return IVal.coerce(a if cond else b)
        cond = IVal.coerce(cond)
        a, b = IVal.coerce(a), IVal.coerce(b)
        t, f = _defi(cond)
        shape = np.broadcast(cond.lo, a.lo, b.lo).shape
        t = np.broadcast_to(t, shape)
        f = np.broadcast_to(f, shape)
        alo = np.broadcast_to(a.lo, shape)
        ahi = np.broadcast_to(a.hi, shape)
        blo = np.broadcast_to(b.lo, shape)
        bhi = np.broadcast_to(b.hi, shape)
        lo = np.where(t, alo, np.where(f, blo, np.minimum(alo, blo)))
        hi = np.where(t, ahi, np.where(f, bhi, np.maximum(ahi, bhi)))
        return IVal(lo, hi, deps=cond.deps | a.deps | b.deps,
                    is_bool=a.is_bool and b.is_bool)

    @staticmethod
    def all(x, axis=None):
        x = IVal.coerce(x)
        if axis is not None:
            raise AnalysisUnsupported("axis reductions")
        t, f = _defi(x)
        lo = 1 if bool(np.all(t)) else 0
        hi = 0 if bool(np.any(f)) else 1
        return IVal(lo, hi, deps=x.deps, is_bool=True)

    @staticmethod
    def any(x, axis=None):
        x = IVal.coerce(x)
        if axis is not None:
            raise AnalysisUnsupported("axis reductions")
        t, f = _defi(x)
        lo = 1 if bool(np.any(t)) else 0
        hi = 0 if bool(np.all(f)) else 1
        return IVal(lo, hi, deps=x.deps, is_bool=True)

    @staticmethod
    def min(x, axis=None):
        x = IVal.coerce(x)
        if axis is not None:
            raise AnalysisUnsupported("axis reductions")
        return IVal(np.min(x.lo), np.min(x.hi), deps=x.deps)

    @staticmethod
    def max(x, axis=None):
        x = IVal.coerce(x)
        if axis is not None:
            raise AnalysisUnsupported("axis reductions")
        return IVal(np.max(x.lo), np.max(x.hi), deps=x.deps)

    @staticmethod
    def sum(x, axis=None, dtype=None):
        x = IVal.coerce(x)
        if axis is not None:
            raise AnalysisUnsupported("axis reductions")
        return IVal(np.sum(x.lo), np.sum(x.hi), deps=x.deps)

    @staticmethod
    def broadcast_to(x, shape):
        x = IVal.coerce(x)
        return IVal(np.broadcast_to(x.lo, shape).copy(),
                    np.broadcast_to(x.hi, shape).copy(), deps=x.deps)

    def __getattr__(self, name):
        raise AnalysisUnsupported(f"jnp.{name} is not modeled")


ABSTRACT_JNP = AbstractJnp()


# --------------------------------------------------------------------------
# abstract state + kernel execution
# --------------------------------------------------------------------------


def field_hull(field) -> IVal:
    """The declared-range hull of one packing Field, origin-tagged."""
    shape = field.shape or ()
    lo = np.full(shape, field.lo, dtype=object) if shape else \
        _obj(field.lo)
    hi = np.full(shape, field.hi, dtype=object) if shape else \
        _obj(field.hi)
    return IVal(lo, hi, origin=(field.name, ()),
                deps=frozenset([field.name]))


def state_hull(fields) -> dict:
    """Abstract state: every field at its declared-range hull."""
    return {f.name: field_hull(f) for f in fields}


def refine_state(state: dict, constraints):
    """Apply guard constraints (field, idx, 'le'|'ge', bound) to a fresh
    copy of the abstract state.  -> (refined_state, empty: bool); empty
    means some constraint contradicts the domain — the guard is
    statically unsatisfiable under the declared bounds."""
    out = {k: IVal(v.lo.copy(), v.hi.copy(), origin=v.origin,
                   deps=v.deps, is_bool=v.is_bool)
           for k, v in state.items()}
    empty = False
    for (field, idx, kind, bound) in constraints:
        if field not in out:
            continue
        v = out[field]
        lo, hi = v.lo, v.hi
        key = idx if idx else ()
        try:
            if kind == "le":
                hi[key] = min(hi[key], bound)
            else:
                lo[key] = max(lo[key], bound)
            if lo[key] > hi[key]:
                empty = True
        except IndexError:
            continue
    return out, empty


class _PatchedJnp:
    """Context manager: rebind the module-global ``jnp`` of every loaded
    model module (and the kernel's own defining module) to the abstract
    namespace for the duration of an abstract run.

    Kernel closures resolve ``jnp`` through their defining module's
    globals, so this is what makes the *shipped* kernel code run over
    the interval domain with zero transcription.  Single-threaded by
    contract: abstract runs happen at model-build/analyze time, never
    concurrently with an engine executing the same kernels.
    """

    def __init__(self, extra_globals=()):
        self._saved = []
        self._extra = list(extra_globals)

    def __enter__(self):
        seen = set()
        targets = []
        for name, mod in list(sys.modules.items()):
            if (mod is not None
                    and name.startswith("kafka_specification_tpu.models")
                    and hasattr(mod, "jnp")):
                targets.append(mod.__dict__)
        targets.extend(self._extra)
        for g in targets:
            gid = id(g)
            if gid in seen or "jnp" not in g:
                continue
            seen.add(gid)
            self._saved.append((g, g["jnp"]))
            g["jnp"] = ABSTRACT_JNP
        return self

    def __exit__(self, *exc):
        for g, old in self._saved:
            g["jnp"] = old
        return False


def run_kernel_abstract(kernel, state: dict, choice: int):
    """One abstract execution of an action kernel: returns
    (enabled: IVal, next_state: dict[str, IVal]).  The caller owns
    refinement and result interpretation."""
    extra = [kernel.__globals__] if hasattr(kernel, "__globals__") else []
    with _PatchedJnp(extra_globals=extra):
        try:
            enabled, nxt = kernel(dict(state), choice)
        except AnalysisUnsupported:
            raise
        except Exception as e:  # noqa: BLE001 — kernel outside the domain
            # e.g. the emitted models' symbolic-evaluator closures, which
            # drive jnp through machinery this domain does not model: an
            # honest skip (INFO finding), never a guessed hull
            raise AnalysisUnsupported(
                f"kernel not abstractly executable "
                f"({type(e).__name__}: {e})"
            ) from e
    if not isinstance(enabled, IVal):
        enabled = IVal.coerce(int(bool(enabled)) if
                              isinstance(enabled, (bool, np.bool_))
                              else enabled)
    return enabled, nxt


def definitely_disabled(enabled: IVal) -> bool:
    """jnp truthiness: a guard is statically false iff its interval is
    exactly {0} (a negative hull is NONZERO, i.e. possibly enabled)."""
    e = IVal.coerce(enabled)
    return e.shape == () and e.lo.item() == 0 and e.hi.item() == 0


def analyze_action_choice(kernel, fields, choice: int):
    """The two-pass (collect guards, re-run refined) abstract execution
    of one (action, choice) pair.

    -> dict with:
       enabled: IVal (refined run's guard value)
       next:    {field: IVal} (refined run's next state)
       base:    {field: IVal} (the hull state the run started from —
                identity anchor for written-field detection)
    """
    base = state_hull(fields)
    enabled0, nxt0 = run_kernel_abstract(kernel, base, choice)
    cons = enabled0.constraints
    if not cons or definitely_disabled(enabled0):
        return {"enabled": IVal.coerce(enabled0), "next": nxt0,
                "base": base}
    refined, empty = refine_state(base, cons)
    if empty:
        # the guard's own conjuncts contradict the declared bounds:
        # statically unsatisfiable — report definitely-disabled and keep
        # the unrefined next (the successor is unreachable)
        return {"enabled": IVal(0, 0, is_bool=True), "next": nxt0,
                "base": base}
    # the refined state's IVals are fresh objects; written-field
    # detection compares identities against THIS state dict
    enabled, nxt = run_kernel_abstract(kernel, refined, choice)
    return {"enabled": IVal.coerce(enabled), "next": nxt,
            "base": refined}
