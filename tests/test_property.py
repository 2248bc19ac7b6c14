"""Property-based tests (Hypothesis) for the codec and dedup primitives —
the per-operator layer of the test strategy (SURVEY.md §4: kernels vs a slow
reference, property-based)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax.extend.core import Literal

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


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.integers(0, 2**64 - 2), min_size=0, max_size=80, unique=True),
    st.lists(st.integers(0, 2**64 - 1), min_size=1, max_size=80),
    st.integers(0, 9),
    st.sampled_from([0, 16, 32, 48, 60]),
)
def test_rank_sorted_equals_searchsorted(set_keys, queries, slack, squeeze):
    """rank_sorted == np.searchsorted(side="left") on the pairs as 64-bit
    keys, and found == membership: any set, queries in any order with
    duplicates; `squeeze` shifts the keys down so that every entry shares
    its top bits (what an exact64 model's raw lanes look like)."""
    keys = np.unique(np.array(set_keys, np.uint64) >> np.uint64(squeeze))
    q = np.array(queries, np.uint64)
    q = np.where(q == np.uint64(2**64 - 1), q, q >> np.uint64(squeeze))
    # one capacity and one lane count, so one compiled program serves every
    # example; `slack` entries fewer leave a sentinel tail of that length
    keys = keys[: max(0, 80 - slack)]
    _assert_rank(keys, 80, np.resize(q, 80))


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


_RANK_SORTED = jax.jit(dedup.rank_sorted)
_MEMBER_SORTED = jax.jit(dedup.member_sorted)
_PROBE_SORTED = jax.jit(dedup.probe_sorted)


def _assert_rank(keys, cap, q):
    """rank_sorted(the sorted `keys` in a capacity of `cap`, queries `q`)
    against numpy on the 64-bit keys -> the probe's round counts."""
    keys = np.sort(np.asarray(keys, np.uint64))
    q = np.asarray(q, np.uint64)
    n = len(keys)
    hi, lo = _pairs(keys, cap)
    q_hi = (q >> np.uint64(32)).astype(np.uint32)
    q_lo = (q & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    args = (jnp.asarray(hi), jnp.asarray(lo), jnp.int32(n),
            jnp.asarray(q_hi), jnp.asarray(q_lo))
    found, rank = _RANK_SORTED(*args)
    want = np.searchsorted(keys, q, side="left")
    np.testing.assert_array_equal(np.asarray(rank), want)
    np.testing.assert_array_equal(np.asarray(found), np.isin(q, keys))
    np.testing.assert_array_equal(
        np.asarray(_MEMBER_SORTED(*args)), np.isin(q, keys))
    f2, r2, rounds = _PROBE_SORTED(*args)
    np.testing.assert_array_equal(np.asarray(r2), want)
    np.testing.assert_array_equal(np.asarray(f2), np.asarray(found))
    rounds = np.asarray(rounds)
    assert 0 <= rounds[0] <= max(n, 1).bit_length() <= rounds[1]
    assert rounds[1] == max(1, cap.bit_length())
    return rounds


_ALL_ONES = 2**64 - 1
_BUCKET = [_k(0x00080000, 7), _k(0x00080000, 9), _k(0x000800FF, 0),
           _k(0x000FFFFF, 0xFFFFFFFF)]  # one bucket of a 13-bit directory
# (name, set keys, capacity, queries)
_RANK_CASES = [
    ("empty_set", [], 8, [0, _k(3, 3), _ALL_ONES]),
    ("set_n_eq_cap", [_k(i * 0x11111111, i) for i in range(8)], 8,
     [0, _k(0x11111111, 1), _k(0x11111111, 2), _k(0x77777777, 7),
      _k(0x77777777, 8), _ALL_ONES]),
    ("set_n_1", [_k(5, 5)], 8, [0, _k(5, 4), _k(5, 5), _k(5, 6), _ALL_ONES]),
    ("one_bucket_equal_hi", [_k(9, i * 3) for i in range(40)], 64,
     [_k(9, i) for i in range(0, 125, 2)] + [_k(8, 1), _k(10, 0)]),
    ("raw_lanes_zero_top_bits", list(range(0, 3000, 7)), 512,
     list(range(0, 3100, 5))),
    ("queries_unsorted", [_k(i * 0x01000193 % 2**32, i) for i in range(50)],
     64, [_k(i * 0x01000193 % 2**32, i % 3) for i in range(70, -1, -1)]),
    ("queries_duplicated", [_k(i << 24, i) for i in range(30)], 32,
     [_k(7 << 24, 7)] * 9 + [_k(7 << 24, 8)] * 9 + [_k(3 << 24, 0)] * 5),
    ("below_first_above_last", [_k(0x40000000 + i, 0) for i in range(20)],
     32, [0, 1, _k(0x3FFFFFFF, 0xFFFFFFFF), _k(0x40000014, 0),
          _k(0xF0000000, 0), _ALL_ONES - 1]),
    ("bucket_first_and_last_entry",
     [_k(0x0007FFFF, 0xFFFFFFFF)] + _BUCKET + [_k(0x00100000, 0)], 16,
     _BUCKET + [_k(0x00080000, 0), _k(0x00080000, 8), _k(0x00100000, 0),
                _k(0x0007FFFF, 0xFFFFFFFF)]),
    ("sentinel_lanes", [_k(1, 1), _k(0xFFFFFFFF, 0xFFFFFFFE)], 8,
     [_k(1, 1)] + [_ALL_ONES] * 12),
    ("cap_not_a_power_of_two", [_k(i * 0x9E3779B1 % 2**32, i)
                                for i in range(37)], 41,
     [_k(i * 0x9E3779B1 % 2**32, i & 1) for i in range(64)]),
    ("many_lanes_wide_directory", [_k(i * 0x9E3779B1 % 2**32, 1)
                                   for i in range(300)], 300,
     [_k(i * 0x85EBCA6B % 2**32, 1) for i in range(5000)]),
]


@pytest.mark.parametrize("case", _RANK_CASES, ids=[c[0] for c in _RANK_CASES])
def test_rank_sorted_cases(case):
    """The inputs a directory-bounded search could get wrong and the plain
    one could not, each against numpy's searchsorted on the 64-bit keys."""
    _name, keys, cap, q = case
    _assert_rank(keys, cap, q)


def test_probe_rounds_follow_the_fullest_bucket():
    """The mechanism: on hashed pairs the probe runs the rounds its fullest
    bucket needs, a third of what the capacity asks for; on a set whose
    entries share their top bits it runs the set's bit length, the plain
    search's count; the answers are numpy's in both."""
    rng = np.random.default_rng(31)
    n, cap = 1 << 17, 1 << 21
    uniform = np.unique(rng.integers(0, 2**64 - 2**33, size=n + 64,
                                     dtype=np.uint64))[:n]
    one_bucket = np.unique(rng.integers(0, 2**40, size=n + 64,
                                        dtype=np.uint64))[:n]
    for keys, limit in ((uniform, 8), (one_bucket, n.bit_length())):
        q = np.concatenate([
            keys[rng.integers(0, n, size=n // 2)],
            rng.integers(0, int(keys[-1]) + 2**20, size=n // 4,
                         dtype=np.uint64),
            np.full(n // 4, _ALL_ONES, np.uint64),  # the sentinel tail
        ])
        rounds = _assert_rank(keys, cap, q)
        assert rounds[0] <= limit and rounds[1] == cap.bit_length()
    # the one-bucket set ran (nearly) the plain count: the search is today's
    assert rounds[0] >= n.bit_length() - 1


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


def _block_case(cap, M, set_n, new_n, seed):
    """Random disjoint sorted key lists of set_n and new_n entries as
    sentinel-padded lanes, the new entries' ranks, and ranks no live lane
    could hold (beyond cap, negative) in the dead lanes j >= new_n."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, 2**64 - 2**33, size=4 * (set_n + new_n) + 8,
                                  dtype=np.uint64))
    keys = rng.permutation(keys)[: set_n + new_n]
    vkeys, nkeys = np.sort(keys[:set_n]), np.sort(keys[set_n:])
    rank = np.resize(np.array([cap + 1, -3, 1 << 30, 0, cap], np.int32), M)
    rank[:new_n] = np.searchsorted(vkeys, nkeys)
    return vkeys, nkeys, _pairs(vkeys, cap), _pairs(nkeys, M), rank


def _merge_counted_at(monkeypatch, block, lanes, out_cap):
    """dedup.merge_counted traced at MERGE_BLOCK = block (a fresh jit: the
    block is read when the function is traced)."""
    monkeypatch.setattr(dedup, "MERGE_BLOCK", block)
    (vhi, vlo), vn, (nhi, nlo), rank, nn = lanes
    return jax.jit(lambda *a: dedup.merge_counted(*a, out_cap))(
        jnp.asarray(vhi), jnp.asarray(vlo), jnp.int32(vn), jnp.asarray(nhi),
        jnp.asarray(nlo), jnp.asarray(rank), jnp.int32(nn),
    )


_B = 8
# (cap, M, out_cap): neither list a multiple of the block and the output
# twice the capacity; both multiples and the output the capacity itself
_BLOCK_SHAPES = {"ragged": (20, 12, 40), "aligned": (32, 16, 32)}
_BLOCK_EDGES = [
    (shape, set_n, new_n)
    for shape, (cap, M, out_cap) in _BLOCK_SHAPES.items()
    for set_n in (0, 1, _B - 1, _B, _B + 1, cap)
    for new_n in (0, 1, _B - 1, _B, _B + 1, M)
    if set_n + new_n <= out_cap
]


@pytest.mark.parametrize(
    "shape,set_n,new_n", _BLOCK_EDGES,
    ids=[f"{s}-set{a}-new{b}" for s, a, b in _BLOCK_EDGES])
def test_merge_ranked_block_edges(monkeypatch, shape, set_n, new_n):
    """The loops over fixed blocks at a block of 8 slots: a list that ends
    just before, at and just past a block edge, an empty, a one-entry and
    a full one, on both sides; a last block that starts early because its
    list is no multiple of the block; each against the plain numpy merge,
    hi, lo, n and the sentinel tail, and the slot counts against the
    blocks the two lists fill."""
    cap, M, out_cap = _BLOCK_SHAPES[shape]
    vkeys, nkeys, v, n, rank = _block_case(cap, M, set_n, new_n,
                                           seed=1000 * set_n + new_n)
    mhi, mlo, mn, slots = _merge_counted_at(
        monkeypatch, _B, (v, set_n, n, rank, new_n), out_cap)
    whi, wlo, wn = _np_merge(vkeys, nkeys, out_cap)
    assert int(mn) == wn
    np.testing.assert_array_equal(np.asarray(mhi), whi)
    np.testing.assert_array_equal(np.asarray(mlo), wlo)
    assert list(np.asarray(slots)) == [
        -(-set_n // _B) * _B + -(-new_n // _B) * _B, cap + M]


@pytest.mark.parametrize("set_n,new_n", [
    (0, 0), (1, 0), (0, 1), (1000, 300), (16384, 16384), (16385, 16383),
    (65536, 1), (65537, 4096), (190000, 65536), (262144 - 70000, 70000)])
def test_merge_counts_the_blocks_it_runs(set_n, new_n):
    """At the block the engine runs and a capacity of several blocks:
    slots[0] is ceil(set_n / B) + ceil(new_n / B) blocks, slots[1]
    the capacity-wide form's cap + M, and the merged set is numpy's."""
    cap, M, B = 262144, 131072, dedup.MERGE_BLOCK
    vkeys, nkeys, (vhi, vlo), (nhi, nlo), rank = _block_case(
        cap, M, set_n, new_n, seed=set_n + new_n)
    mhi, mlo, mn, slots = _MERGE_COUNTED(
        jnp.asarray(vhi), jnp.asarray(vlo), jnp.int32(set_n), jnp.asarray(nhi),
        jnp.asarray(nlo), jnp.asarray(rank), jnp.int32(new_n), cap)
    assert list(np.asarray(slots)) == [
        (-(-set_n // B) + -(-new_n // B)) * B, cap + M]
    whi, wlo, wn = _np_merge(vkeys, nkeys, cap)
    assert int(mn) == wn
    np.testing.assert_array_equal(np.asarray(mhi), whi)
    np.testing.assert_array_equal(np.asarray(mlo), wlo)


_MERGE_COUNTED = jax.jit(dedup.merge_counted, static_argnums=7)


def _input_deps(jaxpr):
    """var -> the indices of the jaxpr's inputs it is computed from."""
    deps = {v: {i} for i, v in enumerate(jaxpr.invars)}
    for eqn in jaxpr.eqns:
        d = set().union(*[deps.get(v, set()) for v in eqn.invars
                          if not isinstance(v, Literal)])
        deps.update({o: d for o in eqn.outvars})
    return deps


def test_merge_ranked_moves_blocks_for_live_entries_only():
    """What makes the merge cost what the set holds, held on a CPU-only
    check: no gather or scatter anywhere in it (inside a loop or outside)
    takes indices or updates wider than one block, so nothing per-element
    runs over the capacity or over the M lanes; and each of its two loops
    stops on a value computed from set_n (the visited side) or from new_n
    (the new side), so the blocks run follow the live entries."""
    cap, M, B = 1 << 20, 1 << 18, dedup.MERGE_BLOCK
    u = jax.ShapeDtypeStruct((cap,), jnp.uint32)
    m = jax.ShapeDtypeStruct((M,), jnp.uint32)
    i = jax.ShapeDtypeStruct((), jnp.int32)
    r = jax.ShapeDtypeStruct((M,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: dedup.merge_ranked(*a, cap)
    )(u, u, i, m, m, r, i).jaxpr

    def indexed(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name.startswith(("gather", "scatter")):
                yield eqn.primitive.name, [v.aval.shape for v in eqn.invars[1:]]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from indexed(sub)

    found = list(indexed(jaxpr))
    assert any(n.startswith("scatter") for n, _ in found)
    assert not [(n, shapes) for n, shapes in found
                if any(max(s, default=0) > B for s in shapes)]

    deps = _input_deps(jaxpr)
    stops_on = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "while":
            continue
        cond = eqn.params["cond_jaxpr"].jaxpr
        nc, nb = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        read = {v for e in cond.eqns for v in e.invars
                if not isinstance(v, Literal)}
        outer = eqn.invars[:nc] + eqn.invars[nc + nb:]  # cond's own inputs
        stops_on.append(set().union(*[
            deps[o] for c, o in zip(cond.invars, outer)
            if c in read and not isinstance(o, Literal)
        ]))
    SET_N, NEW_N = 2, 6
    assert len(stops_on) == 2
    assert sorted(d & {SET_N, NEW_N} and min(d & {SET_N, NEW_N})
                  for d in stops_on) == [SET_N, NEW_N]
