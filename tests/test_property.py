"""Property-based tests (Hypothesis) for the codec and dedup primitives —
the per-operator layer of the test strategy (SURVEY.md §4: kernels vs a slow
reference, property-based)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from kafka_specification_tpu.ops import dedup
from kafka_specification_tpu.ops.packing import Field, StateSpec


@st.composite
def spec_and_states(draw):
    n_fields = draw(st.integers(1, 4))
    fields = []
    for i in range(n_fields):
        lo = draw(st.integers(-8, 4))
        hi = lo + draw(st.integers(0, 40))
        shape = draw(
            st.sampled_from([(), (draw(st.integers(1, 4)),), (2, draw(st.integers(1, 3)))])
        )
        fields.append(Field(f"f{i}", shape, lo, hi))
    spec = StateSpec(fields)
    rng_seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(rng_seed)
    states = [
        {
            f.name: rng.integers(f.lo, f.hi + 1, size=f.shape).astype(np.int32)
            for f in fields
        }
        for _ in range(draw(st.integers(1, 8)))
    ]
    return spec, states


@settings(max_examples=25, deadline=None)
@given(spec_and_states())
def test_pack_unpack_roundtrip_property(sas):
    spec, states = sas
    for s in states:
        out = spec.unpack(spec.pack(s))
        for k, v in s.items():
            np.testing.assert_array_equal(np.asarray(out[k]), v)


@settings(max_examples=25, deadline=None)
@given(spec_and_states())
def test_pack_injective_property(sas):
    """Distinct states pack to distinct lane vectors (canonical encoding)."""
    spec, states = sas
    packs = {}
    for s in states:
        key = tuple(np.asarray(spec.pack(s)).tolist())
        canon = tuple(np.asarray(s[f.name]).tobytes() for f in spec.fields)
        assert packs.setdefault(key, canon) == canon


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.integers(0, 2**32 - 2), min_size=0, max_size=60, unique=True),
    st.lists(st.integers(0, 2**32 - 2), min_size=0, max_size=60, unique=True),
)
def test_merge_ranked_equals_sorted_union(visited_vals, new_vals):
    """merge_ranked(visited, new) == sorted(visited | new) for disjoint sets,
    against a plain numpy reference."""
    visited = np.array(sorted(set(visited_vals) - set(new_vals)), np.uint32)
    new = np.array(sorted(set(new_vals) - set(visited_vals)), np.uint32)
    vn, nn = len(visited), len(new)
    cap = 1 << max(3, (vn + nn).bit_length())
    SENT = np.uint32(0xFFFFFFFF)

    vhi = np.full(cap, SENT)
    vlo = np.full(cap, SENT)
    # use value as lo, a pseudo hi derived deterministically (here: value >> 16)
    vhi[:vn] = visited >> np.uint32(16)
    vlo[:vn] = visited
    order = np.lexsort((vlo[:vn], vhi[:vn]))
    vhi[:vn], vlo[:vn] = vhi[:vn][order], vlo[:vn][order]

    M = max(8, 1 << max(0, (nn - 1)).bit_length())
    nhi = np.full(M, SENT)
    nlo = np.full(M, SENT)
    nhi[:nn] = new >> np.uint32(16)
    nlo[:nn] = new
    norder = np.lexsort((nlo[:nn], nhi[:nn]))
    nhi[:nn], nlo[:nn] = nhi[:nn][norder], nlo[:nn][norder]

    _, rank = dedup.rank_sorted(
        jnp.asarray(vhi), jnp.asarray(vlo), jnp.int32(vn),
        jnp.asarray(nhi), jnp.asarray(nlo),
    )
    mhi, mlo, mn = dedup.merge_ranked(
        jnp.asarray(vhi), jnp.asarray(vlo), jnp.int32(vn),
        jnp.asarray(nhi), jnp.asarray(nlo), rank, jnp.int32(nn), cap,
    )
    mhi, mlo = np.asarray(mhi), np.asarray(mlo)
    assert int(mn) == vn + nn
    want = np.array(
        sorted(
            [(int(v >> np.uint32(16)), int(v)) for v in visited]
            + [(int(v >> np.uint32(16)), int(v)) for v in new]
        ),
        dtype=np.int64,
    ).reshape(-1, 2)
    got = np.stack([mhi[: vn + nn], mlo[: vn + nn]], axis=1).astype(np.int64)
    np.testing.assert_array_equal(got, want)
    assert (mhi[vn + nn :] == SENT).all() and (mlo[vn + nn :] == SENT).all()


def _pairs(keys, size):
    """uint64 keys -> (hi, lo) uint32[size], sorted, sentinel-padded."""
    SENT = np.uint32(0xFFFFFFFF)
    keys = np.sort(np.asarray(keys, np.uint64))
    hi = np.full(size, SENT)
    lo = np.full(size, SENT)
    hi[: len(keys)] = (keys >> np.uint64(32)).astype(np.uint32)
    lo[: len(keys)] = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo


def _np_merge(vkeys, nkeys, out_cap):
    """Plain numpy merge of two disjoint key lists, the reference for
    merge_ranked: sorted union in front, sentinel tail behind."""
    union = list(vkeys) + list(nkeys)
    return (*_pairs(union, out_cap), len(union))


def _k(hi, lo):
    return (hi << 32) | lo


# (name, visited keys, cap, new keys, M, out_cap, gate new_n to 0?,
#  what the dead lanes j >= new_n hold as ranks)
_MERGE_CASES = [
    ("out_cap_wider", [_k(0, 5), _k(1, 0), _k(1, 9), _k(7, 7)], 8,
     [_k(0, 1), _k(1, 4), _k(9, 0)], 8, 16, False, 0),
    ("gated_new_n_0_ranks_left", [_k(0, 5), _k(1, 0), _k(1, 9)], 8,
     [_k(0, 1), _k(1, 4), _k(9, 0)], 8, 8, True, None),
    ("dead_lanes_arbitrary_ranks", [_k(2, 2), _k(3, 3), _k(4, 4), _k(5, 5)], 16,
     [_k(1, 1), _k(3, 9)], 8, 16, False, [0, 3, 7, -4, 15, 16, 1 << 30]),
    ("run_sharing_one_rank", [_k(0, 1), _k(5, 0), _k(9, 9)], 8,
     [_k(1, 0), _k(1, 1), _k(2, 7), _k(4, 4), _k(7, 0)], 8, 8, False, 1),
    ("all_new_below_first", [_k(5, 0), _k(6, 0)], 8,
     [_k(0, 0), _k(0, 1), _k(1, 0)], 4, 8, False, 0),
    ("all_new_above_last", [_k(0, 0), _k(0, 1)], 8,
     [_k(3, 0), _k(3, 1), _k(4, 0)], 4, 8, False, 2),
    ("set_n_0", [], 8, [_k(0, 3), _k(2, 2), _k(8, 1)], 4, 8, False, 0),
    ("set_n_cap_out_cap_double", [_k(i, i) for i in range(1, 9)], 8,
     [_k(0, 0), _k(4, 9), _k(4, 10), _k(9, 9)], 4, 16, False, 8),
    ("both_empty", [], 8, [], 4, 8, False, 5),
    ("full_lanes_no_dead", [_k(1, 1), _k(3, 3)], 4,
     [_k(0, 0), _k(2, 2), _k(2, 3), _k(4, 4)], 4, 8, False, 0),
]


@pytest.mark.parametrize("case", _MERGE_CASES, ids=[c[0] for c in _MERGE_CASES])
def test_merge_ranked_cases(case):
    """The shapes a count-from-ranks merge could get wrong and a searching
    one could not: each against the plain numpy merge, hi, lo, n and the
    sentinel tail."""
    _name, vkeys, cap, nkeys, M, out_cap, gate, dead = case
    vn, nn = len(vkeys), len(nkeys)
    vhi, vlo = _pairs(vkeys, cap)
    nhi, nlo = _pairs(nkeys, M)
    _, rank = dedup.rank_sorted(
        jnp.asarray(vhi), jnp.asarray(vlo), jnp.int32(vn),
        jnp.asarray(nhi), jnp.asarray(nlo),
    )
    rank = np.array(rank)
    live = 0 if gate else nn
    if dead is not None:
        fill = np.resize(np.asarray(dead, np.int64), M)
        rank[live:] = fill[: M - live].astype(np.int32)
    mhi, mlo, mn = dedup.merge_ranked(
        jnp.asarray(vhi), jnp.asarray(vlo), jnp.int32(vn),
        jnp.asarray(nhi), jnp.asarray(nlo), jnp.asarray(rank),
        jnp.int32(live), out_cap,
    )
    whi, wlo, wn = _np_merge(vkeys, nkeys[:live], out_cap)
    assert int(mn) == wn
    np.testing.assert_array_equal(np.asarray(mhi), whi)
    np.testing.assert_array_equal(np.asarray(mlo), wlo)


def _eqn_names(jaxpr, inside_loop=False):
    """(primitive name, inside a loop?, output shapes) of every equation,
    sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_loop, [
            getattr(v.aval, "shape", ()) for v in eqn.outvars
        ]
        loop = inside_loop or eqn.primitive.name in ("while", "scan")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqn_names(sub, loop)


def test_merge_ranked_has_no_search_loop():
    """merge_ranked counts from the ranks it is handed: no loop at all, so
    no capacity-wide gather inside one (the per-slot binary search of the
    new list, 12-19 gather pairs over the whole capacity per chunk, must
    not come back unnoticed on a CPU-only check)."""
    cap, M = 1024, 64
    u = jax.ShapeDtypeStruct((cap,), jnp.uint32)
    m = jax.ShapeDtypeStruct((M,), jnp.uint32)
    i = jax.ShapeDtypeStruct((), jnp.int32)
    r = jax.ShapeDtypeStruct((M,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: dedup.merge_ranked(*a, cap)
    )(u, u, i, m, m, r, i).jaxpr
    eqns = list(_eqn_names(jaxpr))
    assert eqns
    assert not [n for n, _, _ in eqns if n in ("while", "scan")]
    assert not [
        (n, shapes) for n, loop, shapes in eqns
        if loop and n == "gather" and any(s[:1] == (cap,) for s in shapes)
    ]
