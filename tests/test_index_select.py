"""`models.base.read` / `write` against ``x[i]`` / ``x.at[i].set(v)``, bit for
bit, in and out of range, on both sides of ``SELECT_MAX``; and `StateSpec.pack`
/ `unpack` against the gather / scatter-add form they replaced (ISSUE 39).

A negative index wraps once, a read out of range clamps, a write out of range
is dropped: disabled rows run the kernels too, and a guard may read through a
``NONE`` leader, so the helper has to agree with the indexing it replaces at
EVERY index, not only at the ones a reachable state produces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_specification_tpu.engine.bfs import indexing_equations
from kafka_specification_tpu.models import (
    async_isr,
    base,
    finite_replicated_log as frl,
    id_sequence,
    kafka_replication as kr,
    kip320,
    product,
)
from kafka_specification_tpu.models.base import read, write

# every short length, and both sides of the bound
LENGTHS = list(range(1, 18)) + [
    base.SELECT_MAX - 1, base.SELECT_MAX, base.SELECT_MAX + 1]
MODES = ("plain", "vmap", "jit")


def _indices(n):
    return np.arange(-2 * n, 2 * n + 1, dtype=np.int32)


def _run(mode, fn, x, *cols):
    """`fn(x, *scalars)` at every row of `cols`, stacked."""
    if mode != "vmap" and len(cols[0]) > 80:
        # one call an index: a long axis keeps its ends, where the rules bite
        keep = np.r_[0:40, len(cols[0]) - 40:len(cols[0])]
        cols = tuple(c[keep] for c in cols)
    if mode == "vmap":
        return np.asarray(
            jax.vmap(fn, in_axes=(None,) + (0,) * len(cols))(x, *cols)
        )
    f = jax.jit(fn) if mode == "jit" else fn
    return np.stack(
        [
            np.asarray(f(x, *(jnp.asarray(c[k]) for c in cols)))
            for k in range(len(cols[0]))
        ]
    )


def _array(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-9, 99, size=shape).astype(np.int32))


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n", LENGTHS)
def test_read_one_axis(n, mode):
    i = _indices(n)
    for shape in ((n,), (n, 3)):  # an element, a row
        x = _array(shape)
        got = _run(mode, lambda x, i: read(x, i), x, i)
        want = _run(mode, lambda x, i: x[i], x, i)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == want.dtype


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n", LENGTHS)
def test_write_one_axis(n, mode):
    i = _indices(n)
    v = np.arange(100, 100 + len(i), dtype=np.int32)
    x = _array((n,))
    got = _run(mode, lambda x, i, v: write(x, i, v), x, i, v)
    want = _run(mode, lambda x, i, v: x.at[i].set(v), x, i, v)
    np.testing.assert_array_equal(got, want)
    # a vector value: the row write of `_truncate_log`
    x = _array((n, 3))
    vec = np.stack([v, v + 1000, v + 2000], axis=1)
    got = _run(mode, lambda x, i, v: write(x, i, v), x, i, vec)
    want = _run(mode, lambda x, i, v: x.at[i].set(v), x, i, vec)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == want.dtype


def _pairs(n, m):
    i, j = np.meshgrid(_indices(n), _indices(m), indexing="ij")
    return i.reshape(-1), j.reshape(-1)


@pytest.mark.parametrize("mode", ("vmap", "jit"))
@pytest.mark.parametrize("n", LENGTHS)
def test_read_and_write_a_pair_of_axes(n, mode):
    """The ``[r, off]`` form: each index wraps and clamps on its own axis;
    a write is dropped whole if either is out of range."""
    m = 2
    i, j = _pairs(n, m)
    if mode == "jit":  # one call an index pair: keep it short
        i, j = i[:: max(1, n // 2)], j[:: max(1, n // 2)]
    x = _array((n, m))
    got = _run(mode, lambda x, i, j: read(x, i, j), x, i, j)
    want = _run(mode, lambda x, i, j: x[i, j], x, i, j)
    np.testing.assert_array_equal(got, want)
    v = np.arange(100, 100 + len(i), dtype=np.int32)
    got = _run(mode, lambda x, i, j, v: write(x, (i, j), v), x, i, j, v)
    want = _run(mode, lambda x, i, j, v: x.at[i, j].set(v), x, i, j, v)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", LENGTHS)
def test_read_a_column(n):
    """``read(x, slice(None), off)`` is ``x[:, off]``."""
    x = _array((3, n))
    i = _indices(n)
    got = _run("vmap", lambda x, i: read(x, slice(None), i), x, i)
    want = _run("vmap", lambda x, i: x[:, i], x, i)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", LENGTHS)
def test_the_bound_decides_the_lowering(n):
    """Up to SELECT_MAX no gather or scatter under vmap; above it the
    indexing it replaces, as it was."""
    x = jnp.zeros((4, n, 2), jnp.int32)
    i = jnp.zeros((4,), jnp.int32)
    v = jnp.ones((4,), jnp.int32)

    def body(x, i, v):
        return (read(x, i, i), read(x, i), write(x, i, read(x, i) + v),
                write(x, (i, i), v))

    counts = indexing_equations(jax.make_jaxpr(jax.vmap(body))(x, i, v).jaxpr)
    assert counts == ((0, 0) if n <= base.SELECT_MAX else (3, 2))


def test_a_static_index_is_a_static_slice():
    x = _array((4, 3))
    np.testing.assert_array_equal(read(x, 2), x[2])
    np.testing.assert_array_equal(read(x, -1, 1), x[-1, 1])
    np.testing.assert_array_equal(write(x, 0, 7), x.at[0].set(7))
    np.testing.assert_array_equal(write(x, (-1, 2), 7), x.at[-1, 2].set(7))
    jaxpr = jax.make_jaxpr(lambda x: write(x, 0, read(x, 1)))(x)
    assert indexing_equations(jaxpr.jaxpr) == (0, 0)


# --- pack / unpack against the form they replaced ---------------------------


def _pack_by_scatter_add(spec, state):
    """StateSpec.pack as the parent of PR 39 wrote it."""
    flat = spec._flatten(state)
    biased = (flat - spec._los).astype(jnp.uint32) & spec._masks
    shifted = biased << spec._shifts
    lanes = jnp.zeros((spec.num_lanes,), jnp.uint32)
    return lanes.at[spec._lane_ids].add(shifted)


def _unpack_by_gather(spec, lanes):
    """StateSpec.unpack as the parent of PR 39 wrote it."""
    vals = (lanes[spec._lane_ids] >> spec._shifts) & spec._masks
    return spec._unflatten(vals.astype(jnp.int32) + spec._los)


_C3, _C5 = kr.Config(3, 2, 2, 2), kr.Config(5, 2, 2, 2)
SHIPPED_SPECS = {
    "KafkaReplication/3": lambda: kr.make_spec(_C3),
    "KafkaReplication/5": lambda: kr.make_spec(_C5),
    "AsyncIsr/3": lambda: async_isr.make_spec(
        async_isr.AsyncIsrConfig(3, 2, 2)),
    "AsyncIsr/4": lambda: async_isr.make_spec(
        async_isr.AsyncIsrConfig(4, 3, 3)),
    "FiniteReplicatedLog": lambda: frl.make_model(3, 4, 1).spec,
    "IdSequence": lambda: id_sequence.make_model(5).spec,
    "Kip320/5 x 3 partitions": lambda: product.product_model(
        kip320.make_model(_C5), 3).spec,
}


@pytest.mark.parametrize("name", sorted(SHIPPED_SPECS))
def test_pack_and_unpack_equal_the_scatter_add_form(name):
    spec = SHIPPED_SPECS[name]()
    rng = np.random.default_rng(39)
    n = 64
    states = {
        f.name: jnp.asarray(
            rng.integers(f.lo, f.hi + 1, size=(n,) + tuple(f.shape),
                         dtype=np.int64).astype(np.int32))
        for f in spec.fields
    }
    rows = jax.vmap(spec.pack)(states)
    want = jax.vmap(lambda s: _pack_by_scatter_add(spec, s))(states)
    assert rows.dtype == want.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want))
    # any bit pattern unpacks alike, a reachable state's or not
    noise = jnp.asarray(
        rng.integers(0, 1 << 32, size=(n, spec.num_lanes), dtype=np.uint64)
        .astype(np.uint32))
    for lanes in (rows, noise):
        got = jax.vmap(spec.unpack)(lanes)
        ref = jax.vmap(lambda r: _unpack_by_gather(spec, r))(lanes)
        for f in spec.fields:
            np.testing.assert_array_equal(
                np.asarray(got[f.name]), np.asarray(ref[f.name]), f.name)
            assert got[f.name].dtype == ref[f.name].dtype
    back = jax.vmap(spec.unpack)(rows)
    for f in spec.fields:
        np.testing.assert_array_equal(
            np.asarray(back[f.name]), np.asarray(states[f.name]), f.name)
    # one state, no vmap: the form decode_state and init use
    one = {k: v[0] for k, v in states.items()}
    np.testing.assert_array_equal(
        np.asarray(spec.pack(one)), np.asarray(want[0]))
    assert indexing_equations(
        jax.make_jaxpr(
            jax.vmap(lambda r: spec.pack(spec.unpack(r))))(rows).jaxpr
    ) == (0, 0)
