"""Product-space combinator: K independent partitions of a base model.

BASELINE.json's stretch workload is "Kip320 at 5 brokers / 3 partitions"; the
reference models a single partition (KafkaReplication.tla:22), so the
framework defines the multi-partition reading explicitly (BASELINE.md note):
the K-partition model is the product state machine of K independent
instances — `Next` is the disjoint union of per-partition actions (one
partition steps at a time, matching how independent single-partition state
machines interleave), invariants are the conjunction over partitions.

The product's reachable space is NOT the K-th power of the base space level
by level (interleaving matters for BFS levels), but its reachable-set size
is |base|^K, which is how the stretch crosses 10^9 states: 737,794^3 at the
bench constants.  Encoding: base fields are replicated with a partition
prefix; kernels are lifted by slicing the partition's sub-state in and out.
"""

from __future__ import annotations

from ..oracle.interp import OracleAction, OracleModel
from ..ops.packing import Field, StateSpec
from .base import Action, Invariant, Model


def product_model(base: Model, k: int, name: str | None = None) -> Model:
    """K independent copies of `base` interleaved as one model."""
    assert k >= 1
    return product_models(
        [base] * k,
        name=name or f"{base.name} x{k}partitions",
        meta={**base.meta, "partitions": k, "base": base.name,
              "base_fanout": base.total_fanout},
    )


def _refuse_symmetry(base) -> None:
    """A base model (or oracle twin) under TLC's SYMMETRY has no product:
    no field roles are declared for the product state, and run without
    them it would be searched unreduced with no word said."""
    if getattr(base, "symmetry", None) is not None:
        raise ValueError(
            f"{base.name}: models/product.py declares no field roles for "
            "the product state, so its SYMMETRY cannot carry over to it; "
            "run one partition, or drop the SYMMETRY stanza"
        )


def product_models(bases, name: str | None = None, meta: dict | None = None) -> Model:
    """Product of HETEROGENEOUS independent partitions (round-5 verdict
    item 5: mixed-base exact products like 277^2 x 5,973 need partitions
    with different constants, hence different specs and fanouts).

    Per-partition sub-specs may differ; invariant NAMES must agree across
    bases (the product invariant is the conjunction of each partition's
    same-named predicate over its own sub-state)."""
    assert bases
    for b in bases:
        _refuse_symmetry(b)
    specs = [b.spec for b in bases]
    k = len(bases)

    fields = []
    for p, bspec in enumerate(specs):
        for f in bspec.fields:
            fields.append(Field(f"p{p}.{f.name}", f.shape, f.lo, f.hi))
    spec = StateSpec(fields)

    def split(state, p):
        return {f.name: state[f"p{p}.{f.name}"] for f in specs[p].fields}

    def embed(state, p, sub):
        out = dict(state)
        for f in specs[p].fields:
            out[f"p{p}.{f.name}"] = sub[f.name]
        return out

    def init_states():
        # independent instances: the init set is the cross product of the
        # per-partition init sets (every corpus model has one
        # deterministic init, but the combinator must not silently drop
        # mixed-init tuples for bases that don't)
        import itertools

        outs = []
        for combo in itertools.product(*[b.init_states() for b in bases]):
            s = {}
            for p, binit in enumerate(combo):
                for key, v in binit.items():
                    s[f"p{p}.{key}"] = v
            outs.append(s)
        return outs

    actions = []
    for p, b in enumerate(bases):
        for a in b.actions:
            def kernel(state, choice, p=p, a=a):
                ok, nxt = a.kernel(split(state, p), choice)
                return ok, embed(state, p, nxt)

            writes = (
                frozenset(f"p{p}.{w}" for w in a.writes)
                if a.writes is not None else None
            )
            actions.append(
                Action(f"p{p}.{a.name}", a.n_choices, kernel,
                       writes=writes)
            )

    inv_names = [i.name for i in bases[0].invariants]
    for b in bases[1:]:
        assert [i.name for i in b.invariants] == inv_names, (
            "product bases must agree on invariant selection: "
            f"{inv_names} vs {[i.name for i in b.invariants]}"
        )
    invariants = []
    for i_idx, inv_name in enumerate(inv_names):
        def pred(state, i_idx=i_idx):
            ok = None
            for p, b in enumerate(bases):
                r = b.invariants[i_idx].pred(split(state, p))
                ok = r if ok is None else (ok & r)
            return ok

        invariants.append(Invariant(inv_name, pred))

    constraint = None
    if any(b.constraint is not None for b in bases):
        def constraint(state):
            ok = None
            for p, b in enumerate(bases):
                if b.constraint is None:
                    continue
                r = b.constraint(split(state, p))
                ok = r if ok is None else (ok & r)
            return ok

    decode = None
    if all(b.decode is not None for b in bases):
        def decode(s):
            return tuple(
                bases[p].decode(
                    {f.name: s[f"p{p}.{f.name}"] for f in specs[p].fields}
                )
                for p in range(k)
            )

    return Model(
        name=name or " x ".join(b.name for b in bases),
        spec=spec,
        init_states=init_states,
        actions=actions,
        invariants=invariants,
        constraint=constraint,
        decode=decode,
        meta=meta
        or {
            **bases[0].meta,
            "partitions": k,
            "base": [b.name for b in bases],
            "base_fanout": [b.total_fanout for b in bases],
        },
    )


def product_oracle(base: OracleModel, k: int) -> OracleModel:
    """Oracle twin of product_model: state = k-tuple of base states; each
    action steps one partition.  Canonical form matches product_model's
    decode (a tuple of per-partition decodes)."""
    assert k >= 1
    _refuse_symmetry(base)

    def init():
        import itertools

        return [tuple(c) for c in itertools.product(base.init_states(), repeat=k)]

    actions = []
    for p in range(k):
        for a in base.actions:
            def succ(s, p=p, a=a):
                for t in a.successors(s[p]):
                    yield s[:p] + (t,) + s[p + 1 :]

            actions.append(OracleAction(f"p{p}.{a.name}", succ))

    invariants = [
        (name, lambda s, pred=pred: all(pred(x) for x in s))
        for name, pred in base.invariants
    ]
    constraint = None
    if base.constraint is not None:
        def constraint(s):
            return all(base.constraint(x) for x in s)

    return OracleModel(
        name=f"{base.name} x{k}partitions",
        init_states=init,
        actions=actions,
        invariants=invariants,
        constraint=constraint,
        meta={**base.meta, "partitions": k, "base": base.name},
    )
