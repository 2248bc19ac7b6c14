"""Codec round-trip and canonicality tests (SURVEY.md §7 step 3), and the
numpy twin of `unpack` the verdict path decodes with (PR 48)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_specification_tpu.ops.packing import Field, StateSpec
from kafka_specification_tpu.ops.fingerprint import fingerprint_lanes
from kafka_specification_tpu.ops import dedup
from kafka_specification_tpu.models import id_sequence
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg


def _random_state(spec, rng):
    return {
        f.name: rng.integers(f.lo, f.hi + 1, size=f.shape).astype(np.int32)
        for f in spec.fields
    }


SPECS = [
    StateSpec([Field("a", (), 0, 5)]),
    StateSpec([Field("a", (3,), -1, 7), Field("b", (), 0, 1)]),
    StateSpec(
        [
            Field("end", (5,), 0, 4),
            Field("rec", (5, 4), -1, 4),
            Field("isr", (5,), 0, 31),
            Field("scalar", (), -1, 6),
        ]
    ),
]


@pytest.mark.parametrize("spec", SPECS)
def test_roundtrip(spec):
    rng = np.random.default_rng(0)
    for _ in range(50):
        s = _random_state(spec, rng)
        packed = spec.pack(s)
        assert packed.dtype == jnp.uint32
        assert packed.shape == (spec.num_lanes,)
        out = spec.unpack(packed)
        for k, v in s.items():
            np.testing.assert_array_equal(np.asarray(out[k]), v, err_msg=k)


@pytest.mark.parametrize("spec", SPECS)
def test_pack_is_injective(spec):
    rng = np.random.default_rng(1)
    seen = {}
    for _ in range(200):
        s = _random_state(spec, rng)
        key = tuple(np.asarray(spec.pack(s)).tolist())
        canon = tuple(np.asarray(s[f.name]).tobytes() for f in spec.fields)
        if key in seen:
            assert seen[key] == canon
        seen[key] = canon


def test_vmapped_roundtrip():
    spec = SPECS[2]
    rng = np.random.default_rng(2)
    states = [_random_state(spec, rng) for _ in range(32)]
    batched = {
        f.name: np.stack([s[f.name] for s in states]) for f in spec.fields
    }
    packed = jax.vmap(spec.pack)(batched)
    out = jax.vmap(spec.unpack)(packed)
    for f in spec.fields:
        np.testing.assert_array_equal(np.asarray(out[f.name]), batched[f.name])


def test_exact64_flag():
    small = StateSpec([Field("a", (), 0, 100), Field("b", (), 0, 100)])
    assert small.exact64
    big = SPECS[2]
    assert big.num_lanes > 2 and not big.exact64


def test_fingerprint_distinguishes():
    spec = SPECS[2]
    rng = np.random.default_rng(3)
    packs = np.stack(
        [np.asarray(spec.pack(_random_state(spec, rng))) for _ in range(500)]
    )
    uniq = np.unique(packs, axis=0)
    hi, lo = fingerprint_lanes(jnp.asarray(uniq), exact=False)
    pairs = set(zip(np.asarray(hi).tolist(), np.asarray(lo).tolist()))
    assert len(pairs) == uniq.shape[0]  # no collisions on 500 random states


def test_member_sorted():
    rng = np.random.default_rng(4)
    n, cap = 100, 128
    vals = rng.integers(0, 2**31, size=(n, 2)).astype(np.uint32)
    vals = np.unique(vals, axis=0)
    n = vals.shape[0]
    order = np.lexsort((vals[:, 1], vals[:, 0]))
    shi = np.full(cap, 0xFFFFFFFF, np.uint32)
    slo = np.full(cap, 0xFFFFFFFF, np.uint32)
    shi[:n], slo[:n] = vals[order, 0], vals[order, 1]
    # queries: half members, half misses
    q_in = vals[rng.integers(0, n, 50)]
    q_out = rng.integers(0, 2**31, size=(50, 2)).astype(np.uint32)
    member_keys = {(int(a), int(b)) for a, b in vals}
    q = np.concatenate([q_in, q_out])
    got = np.asarray(
        dedup.member_sorted(
            jnp.asarray(shi), jnp.asarray(slo), jnp.int32(n),
            jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1]),
        )
    )
    want = np.array([(int(a), int(b)) in member_keys for a, b in q])
    np.testing.assert_array_equal(got, want)


# --- `unpack_rows`: `unpack` on the host -------------------------------------

# the layouts the benchmark's configurations build (perfbench/configs/*.json:
# `module`, `cfg`), 3 to 15 lanes
CELL_LAYOUTS = {
    "kip320-3b": ("Kip320", "configs/Kip320.cfg"),
    "firsttry-3b": ("Kip320FirstTry", "configs/Kip320FirstTry.cfg"),
    "kip279-4b": ("Kip279", "configs/Kip279FourBroker.cfg"),
    "kip279-5b-symmetry": ("MCKip279", "configs/MCKip279FiveBroker.cfg"),
    "asyncisr-4b": ("AsyncIsr", "configs/AsyncIsrFourBroker.cfg"),
    "kip320-5b": ("Kip320", "configs/Kip320FiveBroker.cfg"),
    "kip320-5b-3p": ("Kip320", "configs/Kip320Stretch.cfg"),
}
# every lane full, a 32-bit field with a negative bias (its all-ones pattern
# wraps in int32, in both forms), hashed fingerprints
HASHED = StateSpec(
    [
        Field("wide", (), -(2**31), 2**31 - 1),
        Field("bytes", (2, 4), -128, 127),
        Field("bit", (32,), 0, 1),
    ],
    force_hashed=True,
)
MOST_ROWS = 13  # the longest trace a cell walks


def _reachable(model, n):
    """The first `n` states of the model's search, packed, breadth-first
    from its initial states (one jitted expansion of a row)."""
    spec = model.spec

    @jax.jit
    def successors(row):
        state = spec.unpack(row)
        enabled, rows = [], []
        for a in model.actions:
            en, nxt = jax.vmap(lambda c: a.kernel(state, c))(
                jnp.arange(a.n_choices, dtype=jnp.int32))
            enabled.append(en)
            rows.append(jax.vmap(spec.pack)(nxt))
        return jnp.concatenate(enabled), jnp.concatenate(rows)

    rows = [np.asarray(spec.pack(s)) for s in model.init_states()]
    seen = {r.tobytes() for r in rows}
    at = 0
    while len(rows) < n:
        enabled, nxt = map(np.asarray, successors(rows[at]))
        at += 1
        for r in nxt[enabled]:
            if r.tobytes() not in seen:
                seen.add(r.tobytes())
                rows.append(r)
    return np.stack(rows[:n])


@functools.lru_cache(maxsize=None)
def _layout(name):
    """(spec, MOST_ROWS packed states the spec can hold)."""
    if name == "hashed":
        rng = np.random.default_rng(5)
        states = [_random_state(HASHED, rng) for _ in range(MOST_ROWS)]
        return HASHED, np.stack(
            [np.asarray(HASHED.pack(s)) for s in states])
    model = (id_sequence.make_model(MOST_ROWS) if name == "id_sequence"
             else build_model(CELL_LAYOUTS[name][0],
                              parse_cfg(CELL_LAYOUTS[name][1])))
    return model.spec, _reachable(model, MOST_ROWS)


@pytest.mark.parametrize("n", [0, 1, MOST_ROWS])
@pytest.mark.parametrize("name", [*CELL_LAYOUTS, "id_sequence", "hashed"])
def test_unpack_rows_is_unpack_on_the_host(name, n):
    """The same integers as `jax.vmap(spec.unpack)`, field for field, in
    value, shape and dtype: for any bit pattern (a reachable state or not:
    pad bits set, a biased value past its range) and for packed states."""
    spec, reachable = _layout(name)
    assert reachable.shape == (MOST_ROWS, spec.num_lanes)
    rng = np.random.default_rng(6)
    noise = rng.integers(0, 2**32, size=(n, spec.num_lanes), dtype=np.uint32)
    noise[:1] = 0xFFFFFFFF  # (where there is a row) every bit of every lane
    for rows in (noise, reachable[:n]):
        want = jax.vmap(spec.unpack)(jnp.asarray(rows))
        got = spec.unpack_rows(rows)
        # (in the spec's field order, as `unpack` gives them; `vmap` sorts)
        assert list(got) == [f.name for f in spec.fields]
        assert set(got) == set(want)
        for f in spec.fields:
            assert type(got[f.name]) is np.ndarray
            assert got[f.name].dtype == want[f.name].dtype == np.int32
            assert got[f.name].shape == want[f.name].shape == (n,) + f.shape
            np.testing.assert_array_equal(got[f.name], want[f.name])
    # the states are states: every field inside its declared range
    fields = spec.unpack_rows(reachable[:n])
    for f in spec.fields:
        assert ((fields[f.name] >= f.lo) & (fields[f.name] <= f.hi)).all()
