"""Expression-level front-end: parse reference TLA+ -> IR -> mechanical
kernel emission, cross-checked against the hand-written models.

This retires (for L1/L2) the round-1 fidelity caveat that guards/updates
were hand-translated with the same author on both sides: here the kernels
come out of the reference text itself (utils/tla_expr + utils/tla_emit),
and must produce bit-identical per-level state sets to the hand models.
"""

import numpy as np
import pytest
from conftest import REFERENCE as REF, needs_reference

from kafka_specification_tpu.engine import check
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import id_sequence
from kafka_specification_tpu.ops.packing import Field, StateSpec
from kafka_specification_tpu.utils.tla_concrete import ConcreteEval, _freeze
from kafka_specification_tpu.utils.tla_emit import (
    SFun,
    SInt,
    SRec,
    build_model,
)
from kafka_specification_tpu.utils.tla_expr import parse_definition, parse_expr
from kafka_specification_tpu.utils.tla_frontend import parse_tla


def _defs(module: str) -> dict:
    mod = parse_tla(REF / f"{module}.tla")
    out = {}
    for name, body in mod.definitions.items():
        if name == "Spec":
            continue
        txt = "\n".join(
            ln
            for ln in body.splitlines()
            if not ln.strip().startswith(("THEOREM", "ASSUME"))
        )
        n, params, ast = parse_definition(txt)
        out[n] = (params, ast)
    return out


@needs_reference
def test_parser_covers_l1_l2_modules():
    """Every definition of Util/IdSequence/FiniteReplicatedLog parses."""
    for module, expect in (("Util", 3), ("IdSequence", 6), ("FiniteReplicatedLog", 25)):
        defs = _defs(module)
        assert len(defs) == expect, (module, sorted(defs))


@needs_reference
def test_util_min_max_range_from_choose_definitions():
    """Util's operators evaluated mechanically from their CHOOSE bodies
    (Util.tla:22-24) — no hand translation anywhere in the path."""
    defs = _defs("Util")
    ev = ConcreteEval(defs, {})
    assert ev.eval(parse_expr("Max({3, 9, 4})"), {}) == 9
    assert ev.eval(parse_expr("Min({3, 9, 4})"), {}) == 3
    rng = ev.eval(parse_expr("Range([x \\in 1 .. 3 |-> x * 2])"), {})
    assert rng == frozenset({2, 4, 6})


def _emit_id_sequence(max_id: int):
    mod = parse_tla(REF / "IdSequence.tla")
    spec = StateSpec([Field("nextId", (), 0, max_id + 1)])
    return build_model(
        mod, {"MaxId": max_id}, {"nextId": SInt("nextId", 0, max_id + 1)}, spec
    )


def _emit_frl(N: int, L: int, R: int):
    mod = parse_tla(REF / "FiniteReplicatedLog.tla")
    spec = StateSpec([Field("end", (N,), 0, L), Field("rec", (N, L), -1, R - 1)])
    schema = SFun(
        N,
        SRec(
            {
                "endOffset": SInt("end", 0, L),
                "records": SFun(L, SInt("rec", -1, R - 1)),
            }
        ),
    )
    return build_model(
        mod,
        {"Replicas": (0, N - 1), "LogRecords": (0, R - 1), "Nil": -1, "LogSize": L},
        {"logs": schema},
        spec,
    )


@needs_reference
def test_emitted_id_sequence_matches_hand_model():
    r = check(_emit_id_sequence(5))
    rh = check(id_sequence.make_model(5))
    assert r.ok and rh.ok
    assert r.total == rh.total == 7
    assert r.levels == rh.levels


def _assert_same_level_sets(m_emitted, m_hand):
    lv_e, lv_h = [], []
    r_e = check(m_emitted, collect_levels=lv_e, store_trace=False)
    r_h = check(m_hand, collect_levels=lv_h, store_trace=False)
    assert r_e.ok and r_h.ok
    assert r_e.total == r_h.total
    assert len(lv_e) == len(lv_h)
    for d, (a, b) in enumerate(zip(lv_e, lv_h)):
        sa = set(map(tuple, np.asarray(a).tolist()))
        sb = set(map(tuple, np.asarray(b).tolist()))
        assert sa == sb, f"level {d} differs"
    return r_e


@needs_reference
def test_emitted_frl_matches_hand_model_small():
    r = _assert_same_level_sets(_emit_frl(2, 2, 2), frl.make_model(2, 2, 2))
    assert r.total == 49


@needs_reference
def test_emitted_frl_matches_hand_model_single_record():
    r = _assert_same_level_sets(_emit_frl(3, 4, 1), frl.make_model(3, 4, 1))
    assert r.total == 125


@needs_reference
@pytest.mark.slow
def test_emitted_frl_matches_hand_model_golden():
    r = _assert_same_level_sets(_emit_frl(3, 4, 2), frl.make_model(3, 4, 2))
    assert r.total == 29791  # the closed-form golden count (RESULTS.md)


@needs_reference
def test_concrete_successors_match_hand_oracle():
    """Third path: IR-driven concrete successor enumeration (tla_concrete)
    vs the hand-written set-semantics oracle, from a nontrivial state."""
    N, L, R = 2, 2, 2
    defs = _defs("FiniteReplicatedLog")
    ev = ConcreteEval(
        defs,
        {
            "Replicas": frozenset(range(N)),
            "LogRecords": frozenset(range(R)),
            "Nil": -1,
            "LogSize": L,
        },
    )
    # logs = r0: [0], r1: []
    logs = {
        0: {"endOffset": 1, "records": {0: 0, 1: -1}},
        1: {"endOffset": 0, "records": {0: -1, 1: -1}},
    }
    _, next_ast = defs["Next"]
    succs = {
        _freeze(p["logs"]) for p in ev.successors(next_ast, {"logs": logs})
    }

    hand = frl.make_oracle(N, L, R)
    state = ((0,), ())  # same state in the oracle's tuple encoding

    def to_logs(s):
        return _freeze(
            {
                r: {
                    "endOffset": len(s[r]),
                    "records": {
                        o: (s[r][o] if o < len(s[r]) else -1) for o in range(L)
                    },
                }
                for r in range(N)
            }
        )

    hand_succs = {
        to_logs(t) for a in hand.actions for t in a.successors(state)
    }
    assert succs == hand_succs and len(succs) == 6


def test_alpha_normalize_dependent_domain_nested_binder():
    """Regression (round-5 advisor, high): a nested binder inside a later
    bind's dependent domain must not reuse an earlier sibling's β-name.

    `∃ r1 ∈ S, r2 ∈ {x ∈ S : x # r1} : r2 # r1` used to normalize the
    filter to `β0 # β0` (always false) because every bind domain was
    walked at the quantifier's entry depth — so the checker would have
    silently verified a wrong model for any spec with a dependent
    quantifier domain containing a nested binder."""
    from kafka_specification_tpu.utils.tla_emit import alpha_normalize

    ast = parse_expr(
        "\\E r1 \\in {1, 2}, r2 \\in {x \\in {1, 2} : x # r1} : r2 # r1"
    )
    ev = ConcreteEval({}, {})
    assert ev.eval(ast, {})  # sanity: the raw tree is satisfiable
    norm = alpha_normalize(ast)
    assert ev.eval(norm, {}), (
        "normalized tree must agree with the raw tree"
    )
    # And the universal dual: ∀ r1, r2 ∈ {x : x # r1} : r2 # r1 is
    # vacuously-true-per-r1 only if the filter keeps its dependency.
    ast2 = parse_expr(
        "\\A r1 \\in {1, 2}, r2 \\in {x \\in {1, 2} : x # r1} : r2 # r1"
    )
    assert ev.eval(ast2, {}) and ev.eval(alpha_normalize(ast2), {})
