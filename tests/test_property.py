"""Property-based tests (Hypothesis) for the codec and dedup primitives —
the per-operator layer of the test strategy (SURVEY.md §4: kernels vs a slow
reference, property-based)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from kafka_specification_tpu.engine import pipeline as pl
from kafka_specification_tpu.ops import dedup
from kafka_specification_tpu.ops.fingerprint import fingerprint_lanes
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
    # no q_n: every lane is searched, whatever order the queries come in
    assert rounds[2] == rounds[3] == len(q)
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


# --------------------------------------------------------------------------
# the live prefix (probe_sorted's q_n): sorted queries, sentinel pairs last,
# searched in blocks of the first q_n lanes only
# --------------------------------------------------------------------------

def _assert_live_prefix(keys, cap, q, T, q_n, block, monkeypatch):
    """probe_sorted(..., q_n) at a block of `block` lanes over the sorted
    queries `q` padded with sentinel pairs to `T` lanes: `found` and `rank`
    are the present form's (no q_n) bit for bit on every lane below q_n,
    and numpy's; False and 0 at and past it; the lanes it says it searched
    are the blocks the prefix fills.  -> the four work counts."""
    monkeypatch.setattr(dedup, "PROBE_BLOCK", block)
    keys = np.sort(np.asarray(keys, np.uint64))
    q = np.sort(np.asarray(q, np.uint64))
    assert len(q) <= T and 0 <= q_n <= T
    hi, lo = _pairs(keys, cap)
    q_hi, q_lo = _pairs(q, T)
    args = (jnp.asarray(hi), jnp.asarray(lo), jnp.int32(len(keys)),
            jnp.asarray(q_hi), jnp.asarray(q_lo))
    found, rank, work = jax.jit(
        lambda *a: dedup.probe_sorted(*a))(*args, jnp.int32(q_n))
    f0, r0, w0 = _PROBE_SORTED(*args)
    found, rank, f0, r0 = (np.asarray(x) for x in (found, rank, f0, r0))
    assert found.dtype == f0.dtype and rank.dtype == r0.dtype
    assert found.shape == rank.shape == (T,)
    np.testing.assert_array_equal(found[:q_n], f0[:q_n])
    np.testing.assert_array_equal(rank[:q_n], r0[:q_n])
    padded = _k(q_hi.astype(np.uint64), q_lo.astype(np.uint64))[:q_n]
    np.testing.assert_array_equal(
        rank[:q_n], np.searchsorted(keys, padded, side="left"))
    np.testing.assert_array_equal(found[:q_n], np.isin(padded, keys))
    assert not found[q_n:].any() and not rank[q_n:].any()
    work, w0 = np.asarray(work), np.asarray(w0)
    B = dedup.even_block(T, block)
    assert list(work) == [w0[0], w0[1], -(-q_n // B) * B, T]
    assert list(w0[2:]) == [T, T]
    return work


_ONE_BUCKET = [_k(9, i * 3) for i in range(40)]
_HASHED = [_k(i * 0x9E3779B1 % 2**32, i) for i in range(37)]
# (name, set keys, capacity, live queries)
_LIVE_SETS = [
    ("empty_set", [], 8, [0, _k(3, 3), _k(3, 3), _k(0xFFFFFFFF, 0)]),
    ("full_set", _HASHED, 37,
     [_k(i * 0x9E3779B1 % 2**32, i & 1) for i in range(20)]),
    ("one_bucket_equal_hi", _ONE_BUCKET, 64,
     [_k(9, i) for i in range(0, 125, 5)] + [_k(8, 1), _k(10, 0)]),
    ("queries_duplicated", [_k(i << 24, i) for i in range(30)], 32,
     [_k(7 << 24, 7)] * 9 + [_k(7 << 24, 8)] * 9 + [_k(3 << 24, 0)] * 5),
]


def _live_prefix_cases():
    """Every set above at a block of 8 lanes and a width below the block,
    equal to it, a multiple of it and none (T 20: three blocks of 7, the
    last one starting a lane early), with q_n at 0, 1, around a block
    edge, the live lanes' count and T."""
    cases = []
    for name, keys, cap, q in _LIVE_SETS:
        for T in (5, 8, 20, 32):
            live = q[:T]
            for q_n in sorted({0, 1, 7, 8, 9, len(live), T}):
                if q_n <= T:
                    cases.append((f"{name}-T{T}-q_n{q_n}", keys, cap, live,
                                  T, q_n))
    return cases


_LIVE_PREFIX = _live_prefix_cases()


@pytest.mark.parametrize("case", _LIVE_PREFIX,
                         ids=[c[0] for c in _LIVE_PREFIX])
def test_probe_sorted_live_prefix_edges(monkeypatch, case):
    """`probe_sorted(..., q_n)` against the form that searches every lane:
    each lane below q_n bit for bit, each lane at or past it dead, a q_n
    short of the live lanes included (the contract is the caller's count,
    not the sentinels)."""
    _id, keys, cap, q, T, q_n = case
    _assert_live_prefix(keys, cap, q, T, q_n, _B, monkeypatch)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_probe_sorted_live_prefix_equals_the_full_search(data):
    """Random sets, sorted query lists, widths, blocks and prefixes."""
    T = data.draw(st.integers(1, 70))
    block = data.draw(st.sampled_from([1, 4, 8, 16, 64]))
    top = data.draw(st.sampled_from([2**12, 2**40, 2**64 - 2**33]))
    draw_keys = st.lists(st.integers(0, top - 1), max_size=60)
    keys = sorted(set(data.draw(draw_keys)))
    cap = len(keys) + data.draw(st.integers(0, 9)) or 1
    fresh = data.draw(st.lists(st.integers(0, top - 1), max_size=T))
    old = data.draw(st.lists(st.sampled_from(keys), max_size=T)) if keys else []
    q = (fresh + old)[:T]
    q_n = data.draw(st.integers(0, T))
    with pytest.MonkeyPatch.context() as mp:
        _assert_live_prefix(keys, cap, q, T, q_n, block, mp)


_PB100K = dedup.even_block(100000, dedup.PROBE_BLOCK)


@pytest.mark.parametrize("q_n", [0, 1, _PB100K, _PB100K + 1, 40000, 100000])
def test_probe_sorted_counts_the_blocks_it_runs(q_n):
    """At the block the engine runs and a width of several blocks that is
    no multiple of it: `probe_lanes` is ceil(q_n / B) x B exactly,
    `probe_lanes_plain` the width."""
    T, cap = 100000, 1 << 17
    B = _PB100K
    assert T % B and -(-T // B) * B - T < -(-T // B)
    rng = np.random.default_rng(q_n)
    keys = np.unique(rng.integers(0, 2**64 - 2**33, size=50000,
                                  dtype=np.uint64))
    q = np.concatenate([keys[rng.integers(0, len(keys), size=q_n // 2)],
                        rng.integers(0, 2**64 - 2**33, size=q_n - q_n // 2,
                                     dtype=np.uint64)])
    with pytest.MonkeyPatch.context() as mp:
        work = _assert_live_prefix(keys, cap, q, T, q_n, dedup.PROBE_BLOCK,
                                   mp)
    assert list(work[2:]) == [-(-q_n // B) * B, T]


def test_probe_sorted_searches_blocks_of_the_live_prefix_only():
    """What makes the probe cost what a chunk holds, held on a CPU-only
    check: with a q_n no gather in it takes indices wider than one block
    of queries or the directory's boundary list (a shape, built from the
    SET), and its block loop stops on a value computed from q_n alone;
    without one the search is the T-wide one."""
    cap, T, B = 1 << 22, 1 << 20, dedup.PROBE_BLOCK
    nb = 1 << dedup.directory_bits(T)
    u = jax.ShapeDtypeStruct((cap,), jnp.uint32)
    q = jax.ShapeDtypeStruct((T,), jnp.uint32)
    i = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(dedup.probe_sorted)(u, u, i, q, q, i).jaxpr
    found = list(_indexed_ops(jaxpr))
    assert {n.split("-")[0] for n, _ in found} == {"gather"}
    assert not [(n, shapes) for n, shapes in found
                if any(max(s, default=0) > max(B, nb + 1) for s in shapes)]
    SET_N, Q_N = 2, 5
    stops_on = _loops_stop_on(jaxpr)
    assert len(stops_on) == 2 and Q_N not in stops_on[0]  # the directory's
    assert stops_on[1] == {Q_N}
    plain = jax.make_jaxpr(
        lambda *a: dedup.probe_sorted(*a))(u, u, i, q, q).jaxpr
    assert any(max(s, default=0) == T for _n, shapes in _indexed_ops(plain)
               for s in shapes)
    assert SET_N in _loops_stop_on(plain)[1]


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


def _indexed_ops(jaxpr):
    """(name, operand shapes past the first) of every gather and scatter
    of a jaxpr, inside its loops too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith(("gather", "scatter")):
            yield eqn.primitive.name, [v.aval.shape for v in eqn.invars[1:]]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _indexed_ops(sub)


def _loops_stop_on(jaxpr):
    """For each top-level `while` of a jaxpr, in order: the indices of the
    jaxpr's inputs its stopping condition is computed from."""
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
    return stops_on


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

    found = list(_indexed_ops(jaxpr))
    assert any(n.startswith("scatter") for n, _ in found)
    assert not [(n, shapes) for n, shapes in found
                if any(max(s, default=0) > B for s in shapes)]
    stops_on = _loops_stop_on(jaxpr)
    SET_N, NEW_N = 2, 6
    assert len(stops_on) == 2
    assert sorted(d & {SET_N, NEW_N} and min(d & {SET_N, NEW_N})
                  for d in stops_on) == [SET_N, NEW_N]


# --------------------------------------------------------------------------
# the `novel` part of `compact` (engine/pipeline.py novel_stage): the rows a
# chunk keeps, against the full-width form it replaced
# --------------------------------------------------------------------------

def _plain_novel(is_new, order, hi_s, lo_s, rank, cand, parent, actid,
                 _n_live, T, K):
    """The full-width compaction, kept here as the reference: three
    gathers and six scatters over all T lanes, dead lanes' updates sent
    out of bounds and dropped (it needs no live prefix)."""
    sent = jnp.uint32(dedup.SENT)
    pos = jnp.where(is_new, jnp.cumsum(is_new) - 1, T)
    out = jnp.zeros((T, K), jnp.uint32).at[pos].set(cand[order])
    out_parent = jnp.full((T,), -1, jnp.int32).at[pos].set(parent[order])
    out_act = jnp.full((T,), -1, jnp.int32).at[pos].set(actid[order])
    out_hi = jnp.full((T,), sent).at[pos].set(hi_s)
    out_lo = jnp.full((T,), sent).at[pos].set(lo_s)
    out_rank = jnp.zeros((T,), jnp.int32).at[pos].set(rank)
    new_n = jnp.sum(is_new, dtype=jnp.int32)
    return out, out_parent, out_act, out_hi, out_lo, out_rank, new_n


_NOVEL_OUTPUTS = ("out", "out_parent", "out_act", "out_hi", "out_lo",
                  "out_rank", "new_n")


def _novel_case(T, K, n_live, new_lanes, seed):
    """Sorted-order lanes of a dedup whose first n_live lanes are live
    (distinct sorted fingerprints, the rest the sentinel pair) and whose
    `new_lanes` (sorted indices below n_live) are new; candidate-order
    rows, parents and action ids behind a random `order`; ranks and
    payloads that no fill value could pass for; n_live last, as
    `_sort_first` counts it from the sorted lanes."""
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(2**40, size=n_live, replace=False)
                   .astype(np.uint64) << np.uint64(20))
    hi_s, lo_s = _pairs(keys, T)
    is_new = np.zeros(T, bool)
    is_new[np.asarray(new_lanes, np.int64)] = True
    order = rng.permutation(T).astype(np.int32)
    rank = rng.integers(1, 1 << 20, size=T).astype(np.int32)
    cand = rng.integers(1, 2**32, size=(T, K), dtype=np.uint32)
    parent = rng.integers(0, 1 << 20, size=T).astype(np.int32)
    actid = rng.integers(0, 50, size=T).astype(np.int32)
    counted = pl._sort_first(jnp.asarray(hi_s), jnp.asarray(lo_s))[4]
    assert int(counted) == n_live
    return tuple(jnp.asarray(x) for x in (
        is_new, order, hi_s, lo_s, rank, cand, parent, actid)) + (counted,)


def _assert_novel(block, T, K, n_live, new_lanes, seed, monkeypatch):
    monkeypatch.setattr(pl, "NOVEL_BLOCK", block)
    args = _novel_case(T, K, n_live, new_lanes, seed)
    got = jax.jit(lambda *a: pl.novel_stage(*a, T, K))(*args)
    want = jax.jit(lambda *a: _plain_novel(*a, T, K))(*args)
    for name, g, w in zip(_NOVEL_OUTPUTS, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
        assert g.dtype == w.dtype and g.shape == w.shape, name
    B = pl.novel_block(T)  # 8 rows at T 32, 7 at T 20 (three blocks, one row recomputed), 5 at T 5
    assert list(np.asarray(got[-1])) == [
        (-(-n_live // B) + -(-len(new_lanes) // B)) * B, T]


def _novel_edges():
    """(id, T, n_live, new lanes) at a block of 8 rows: T a multiple of
    the block, none, and smaller than one; n_live and new_n at 0, 1, T and
    around a block edge; the new lanes the first, the last, a scattered
    draw and one run laid across a block boundary."""
    cases = []
    for T in (32, 20, 5):
        for n_live in sorted({0, 1, _B - 1, _B, _B + 1, T - 1, T}):
            if not 0 <= n_live <= T:
                continue
            picks = {"none": [], "all": list(range(n_live))}
            if n_live:
                picks["first"] = [0]
                picks["last"] = [n_live - 1]
                picks["every3rd"] = list(range(0, n_live, 3))
            if n_live > _B + 2:
                # a run of new rows with a block boundary inside it, in
                # the input (lanes 5..10) and so in the output too
                picks["run"] = list(range(_B - 3, _B + 3))
                picks["allbut1"] = list(range(1, n_live))
            for name, lanes in picks.items():
                cases.append((f"T{T}-live{n_live}-{name}", T, n_live, lanes))
    return cases


_NOVEL_EDGES = _novel_edges()


@pytest.mark.parametrize("case", _NOVEL_EDGES, ids=[c[0] for c in _NOVEL_EDGES])
def test_novel_stage_block_edges(monkeypatch, case):
    """`novel_stage` at a block of 8 rows against the full-width form:
    every output array bit for bit, fills included, and the rows it says
    its loops touched against the blocks the live prefix and the new
    states fill."""
    _id, T, n_live, lanes = case
    _assert_novel(_B, T, 3, n_live, lanes, seed=T * 100 + n_live,
                  monkeypatch=monkeypatch)


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_novel_stage_equals_the_full_width_form(data):
    """Random masks at random widths, blocks and lane counts."""
    T = data.draw(st.integers(1, 70))
    block = data.draw(st.sampled_from([1, 4, 8, 16, 64]))
    K = data.draw(st.integers(1, 4))
    n_live = data.draw(st.integers(0, T))
    mask = data.draw(st.lists(st.booleans(), min_size=n_live, max_size=n_live))
    lanes = [i for i, b in enumerate(mask) if b]
    with pytest.MonkeyPatch.context() as mp:
        _assert_novel(block, T, K, n_live, lanes,
                      seed=data.draw(st.integers(0, 2**16)), monkeypatch=mp)


_B100K = pl.novel_block(100000)


@pytest.mark.parametrize("n_live,new_n", [
    (0, 0), (1, 1), (_B100K, _B100K), (_B100K + 1, 1),
    (40000, _B100K + 1), (100000, 30000), (100000, 100000)])
def test_novel_stage_counts_the_blocks_it_runs(n_live, new_n):
    """At the block the engine runs and a width of several blocks (no
    multiple of NOVEL_BLOCK: thirteen blocks of 7,693 rows): rows[0] is
    ceil(n_live / B) + ceil(new_n / B) blocks, rows[1] the width, and the
    outputs are the full-width form's."""
    T, K, B = 100000, 4, _B100K
    assert B == 7693 and 13 * B - T < 13
    rng = np.random.default_rng(n_live + new_n)
    lanes = np.sort(rng.choice(n_live, size=new_n, replace=False))
    args = _novel_case(T, K, n_live, lanes, seed=n_live)
    got = _NOVEL(*args, T, K)
    want = _PLAIN_NOVEL(*args, T, K)
    for name, g, w in zip(_NOVEL_OUTPUTS, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    assert list(np.asarray(got[-1])) == [
        (-(-n_live // B) + -(-new_n // B)) * B, T]


_NOVEL = jax.jit(pl.novel_stage, static_argnums=(9, 10))
_PLAIN_NOVEL = jax.jit(_plain_novel, static_argnums=(9, 10))


def test_novel_stage_moves_blocks_for_the_rows_it_keeps_only():
    """What makes `novel` cost what a chunk keeps, held on a CPU-only
    check: no gather or scatter in it takes indices or updates wider than
    one block, and its two loops stop on the live prefix it is handed
    (`_sort_first`'s count) and on a value computed from is_new (the new
    states)."""
    T, K, B = 1 << 20, 4, pl.NOVEL_BLOCK
    u = jax.ShapeDtypeStruct((T,), jnp.uint32)
    i = jax.ShapeDtypeStruct((T,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: pl.novel_stage(*a, T, K))(
        jax.ShapeDtypeStruct((T,), bool), i, u, u, i,
        jax.ShapeDtypeStruct((T, K), jnp.uint32), i, i,
        jax.ShapeDtypeStruct((), jnp.int32)).jaxpr

    found = list(_indexed_ops(jaxpr))
    assert {n.split("-")[0] for n, _ in found} == {"gather", "scatter"}
    assert not [(n, shapes) for n, shapes in found
                if any(max(s, default=0) > B for s in shapes)]
    stops_on = _loops_stop_on(jaxpr)
    IS_NEW, N_LIVE = 0, 8
    assert stops_on == [{N_LIVE}, {IS_NEW}]


def _sorted_dedup(with_plain_novel, monkeypatch, *args, **kw):
    if with_plain_novel:
        monkeypatch.setattr(
            pl, "novel_stage",
            lambda *a: (*_plain_novel(*a), jnp.zeros((2,), jnp.int32)))
    return jax.jit(lambda *a: pl.sorted_dedup_stage(*a, **kw))(*args)


def test_sorted_dedup_first_copy_decides_the_parent(monkeypatch):
    """The whole dedup stage over candidates with duplicate fingerprints
    (in the chunk and against the visited set) at a block of 8 rows:
    every output the full-width form's, and each new state's parent and
    action id those of its FIRST copy in candidate order."""
    monkeypatch.setattr(pl, "NOVEL_BLOCK", _B)
    T, K, vcap = 44, 2, 64
    rng = np.random.default_rng(7)
    keys = rng.integers(1, 2**32, size=(12, K), dtype=np.uint32)
    pick = rng.integers(0, 12, size=T)
    cand = keys[pick]
    valid = rng.random(T) < 0.8
    parent = np.arange(T, dtype=np.int32) + 100
    actid = (np.arange(T, dtype=np.int32) * 7) % 5
    hi, lo = fingerprint_lanes(jnp.asarray(cand), True)
    hi = jnp.where(valid, hi, jnp.uint32(dedup.SENT))
    lo = jnp.where(valid, lo, jnp.uint32(dedup.SENT))
    # three of the twelve states are visited already
    vk = _k(*(np.asarray(x, np.uint64)
              for x in fingerprint_lanes(jnp.asarray(keys[:3]), True)))
    vhi, vlo = _pairs(vk, vcap)
    args = (jnp.asarray(cand), jnp.asarray(parent), jnp.asarray(actid),
            jnp.asarray(valid), hi, lo, jnp.asarray(vhi), jnp.asarray(vlo),
            jnp.int32(3))
    kw = dict(vcap=vcap, T=T, K=K, with_merge=True)
    got = _sorted_dedup(False, monkeypatch, *args, **kw)
    want = _sorted_dedup(True, monkeypatch, *args, **kw)
    for g, w in zip(got[:-1], want[:-1]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    out, out_parent, out_act, new_n = (np.asarray(x) for x in got[:4])
    assert 0 < int(new_n) < 10
    for row, par, act in zip(out[:new_n], out_parent[:new_n], out_act[:new_n]):
        first = next(i for i in range(T)
                     if valid[i] and (cand[i] == row).all())
        assert (par, act) == (parent[first], actid[first])
        assert not any((row == k).all() for k in keys[:3])


@pytest.fixture(scope="module")
def constrained_chunk():
    """One chunk of AsyncIsr at 3 brokers under an explicit CONSTRAINT, as
    the fused path lays it out: the frontier of level 6, the pooled
    (state, choice) index vectors of its guard matrix, and a visited set
    of the levels before it."""
    from kafka_specification_tpu.engine import check
    from kafka_specification_tpu.engine.bfs import _Step
    from helpers import async_isr_under_constraint

    model = async_isr_under_constraint()
    levels = []
    res = check(model, max_depth=6, min_bucket=32, pipeline="legacy",
                collect_levels=levels)
    assert res.levels == [1, 5, 16, 42, 92, 171, 282]
    bucket, vcap = 512, 1024
    K = model.spec.num_lanes
    rows = np.zeros((bucket, K), np.uint32)
    rows[:282] = np.asarray(levels[6])
    fvalid = np.arange(bucket) < 282
    fused = pl.FusedPipeline(_Step(model), model, None, None, None, True,
                             "device", None, 2, 32)
    ga = fused.guard_step(bucket)(jnp.asarray(rows), jnp.asarray(fvalid))[0]
    counts = np.asarray(ga).reshape(bucket, -1)
    bounds = fused._bounds
    widths = tuple(
        256 * -(-max(1, int(counts[:, bounds[i]:bounds[i + 1]].sum())) // 256)
        for i in range(len(model.actions)))
    _sidx, sidx, chloc, rowvalid = fused._compact(ga, widths, 7)
    seen = np.concatenate([np.asarray(lv) for lv in levels])
    vk = _k(*(np.asarray(x, np.uint64) for x in fingerprint_lanes(
        jnp.asarray(seen), model.spec.exact64)))
    vhi, vlo = _pairs(vk, vcap)
    args = (jnp.asarray(rows), sidx, chloc, rowvalid, jnp.asarray(vhi),
            jnp.asarray(vlo), jnp.int32(len(vk)))
    return fused, bucket, widths, vcap, args


def test_fused_successors_without_the_squeeze(constrained_chunk, monkeypatch):
    """`_build_succ`'s program, which hands the pooled layout straight to
    the sorted dedup, against the same program with the squeeze its
    parent ran before it: all ten outputs equal, on a chunk whose
    segments have constraint-pruned holes inside their enabled prefixes
    and whose candidates repeat fingerprints across segments."""
    fused, bucket, widths, vcap, args = constrained_chunk
    K, W = fused.spec.num_lanes, sum(widths)
    got = jax.jit(fused._build_succ(bucket, widths, vcap, True, True))(*args)

    real = pl.sorted_dedup_stage
    seen_inputs = {}

    def squeezed_first(cand, parent, actid, valid, hi, lo, *rest, **kw):
        seen_inputs["w"] = cand.shape[0]
        cand, parent, actid, valid, _n, _ovf = pl.squeeze_stage(
            cand, parent, actid, valid, W, K)
        hi, lo, _orbit = pl.fp_stage(cand, valid, fused.model)
        return real(cand, parent, actid, valid, hi, lo, *rest, **kw)

    monkeypatch.setattr(pl, "sorted_dedup_stage", squeezed_first)
    want = jax.jit(fused._build_succ(bucket, widths, vcap, True, True))(*args)
    assert seen_inputs == {"w": W}
    assert len(got) == len(want) == 10
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    # the chunk is the shape the test is for
    raw = jax.jit(fused._build_succ(bucket, widths, vcap, True, False))(*args)
    cand, ok = np.asarray(raw[0]), np.asarray(raw[1])
    rowvalid = np.asarray(args[3])
    offs = np.cumsum((0,) + widths)
    assert any((rowvalid[a:b] & ~ok[a:b]).any() and
               ok[a:b][np.flatnonzero(rowvalid[a:b] & ~ok[a:b])[0]:].any()
               for a, b in zip(offs[:-1], offs[1:])), "no hole inside a segment"
    seg = np.searchsorted(offs, np.arange(W), side="right")
    first_seg = {}
    assert any(first_seg.setdefault(cand[i].tobytes(), seg[i]) != seg[i]
               for i in np.flatnonzero(ok)), "no fingerprint in two segments"
    new_n = int(got[3])
    assert 0 < new_n < int(ok.sum())
