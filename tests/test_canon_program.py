"""The `canon` stage's block schedule (PR 47): whole WIDE blocks while the
live rows fill them, the rest in blocks of `canon_block(T)`.

On the CPU, small sizes: the schedule forms the keys, orbit sizes and row
count that one block over the whole width forms, whatever the live count;
a width under two wide blocks lowers as it did before there were any.

Without a chip, for a DESCRIBED v5e (on-chip-measurement guide, section 2):
the optimised HLO of the stage alone has the loops the schedule says and no
more operations in the loop over the group than it had.  That loop runs 120
times a block; each operation of its body is a launch on the device and an
event `jax.profiler` collects: at forty blocks of 8,192 rows a chunk the
profile of one 2.1 s pass of `kip279-5b-symmetry-cex` took ~50 s to stop
(PERF.md section 6, PR 47).  Every test of this file that compiles for the
TPU does so in the test's own process, through the fixtures below: only one
process may hold the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_specification_tpu.models import kafka_replication as kr
from kafka_specification_tpu.models import kip320
from kafka_specification_tpu.ops import canon as canon_mod
from kafka_specification_tpu.ops.canon import Canon, canon_of
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: instructions of an optimised HLO computation that run nothing
FREE = {"get-tuple-element", "bitcast", "constant", "tuple", "parameter"}

#: the two symmetric jobs of the benchmark, (cfg, module)
JOBS = {
    "kip279-5b": ("MCKip279FiveBroker.cfg", "MCKip279"),
    "kip320-5b": ("MCKip320FiveBroker.cfg", "MCKip320"),
}


@pytest.fixture(scope="module")
def small():
    """Kip320 at 3 brokers under its symmetry (|G| = 6), 1,024 random rows."""
    model = kip320.make_model(kr.Config(3, 2, 2, 2), symmetric=True)
    rng = np.random.default_rng(47)
    rows = rng.integers(0, 2**32, size=(1024, model.spec.num_lanes),
                        dtype=np.uint32)
    return model, jnp.asarray(rows)


def keys_at(monkeypatch, model, block, cand, valid):
    monkeypatch.setattr(canon_mod, "CANON_BLOCK", block)
    out = jax.jit(Canon(model.spec, model.symmetry).keys)(cand, valid)
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("n_live", [0, 1, 63, 64, 255, 256, 257, 300, 582,
                                    1023, 1024])
def test_wide_blocks_and_the_rest_form_what_one_block_forms(
        n_live, small, monkeypatch):
    model, cand = small
    T = cand.shape[0]
    rng = np.random.default_rng(n_live)
    valid = np.zeros(T, bool)
    valid[rng.choice(T, size=n_live, replace=False)] = True
    valid = jnp.asarray(valid)
    # blocks of 64 rows, wide blocks of 256: four wide blocks fit the width
    hi, lo, orbit, rows = keys_at(monkeypatch, model, 64, cand, valid)
    assert (canon_mod.canon_block(T), canon_mod.canon_wide_block(T)) == (
        64, 256)
    one = keys_at(monkeypatch, model, T, cand, valid)  # one block, no wide
    assert canon_mod.canon_wide_block(T) == 0
    assert np.array_equal(hi, one[0]) and np.array_equal(lo, one[1])
    assert np.array_equal(orbit, one[2])
    wide = n_live // 256
    assert int(rows) == wide * 256 + -(-(n_live - wide * 256) // 64) * 64
    assert int(one[3]) == (T if n_live else 0)


def test_a_narrow_width_lowers_as_it_did_without_wide_blocks(small,
                                                             monkeypatch):
    model, _ = small
    K = model.spec.num_lanes

    def lowered(T):
        cand = jax.ShapeDtypeStruct((T, K), jnp.uint32)
        valid = jax.ShapeDtypeStruct((T,), jnp.bool_)
        return jax.jit(Canon(model.spec, model.symmetry).keys).lower(
            cand, valid).as_text()

    W = canon_mod.CANON_WIDE * canon_mod.CANON_BLOCK
    narrow, wide = 2 * W - canon_mod.CANON_BLOCK, 2 * W
    assert canon_mod.canon_wide_block(narrow) == 0
    assert canon_mod.canon_wide_block(wide) == W
    with_wide = {T: lowered(T) for T in (narrow, wide)}
    monkeypatch.setattr(canon_mod, "CANON_WIDE", 1)
    assert canon_mod.canon_wide_block(wide) == 0
    assert lowered(narrow) == with_wide[narrow]
    assert lowered(wide) != with_wide[wide]


# --- for a described v5e, no chip --------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def computations(hlo):
    """name -> the text of each computation of an optimised HLO module."""
    out = {}
    for m in re.finditer(r"^(?:ENTRY )?%([\w.\-]+) \(.*?^}", hlo, re.M | re.S):
        out[m.group(1)] = m.group(0)
    return out


def executing(text):
    """The instructions of a computation that run something: (name, op)."""
    ops = []
    for line in text.split("\n")[1:]:
        # (a tuple's type has spaces: only `while` matters among those)
        m = (re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? (while)\(", line)
             or re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \S+ ([\w\-]+)\(", line))
        if m and m.group(2) not in FREE:
            ops.append((m.group(1), m.group(2)))
    return ops


def loop_bodies(hlo):
    comps = computations(hlo)
    bodies = re.findall(r" while\(.*?body=%([\w.\-]+)", hlo)
    return [executing(comps[b]) for b in bodies]


@pytest.mark.parametrize("job", sorted(JOBS))
def test_the_loops_of_the_stage_on_a_v5e(job, one_chip, no_compile_cache):
    cfg_name, module = JOBS[job]
    model = build_model(module, parse_cfg(os.path.join(ROOT, "configs",
                                                       cfg_name)))
    canon = canon_of(model)
    assert canon.G == 120
    K = model.spec.num_lanes
    B, wide = canon_mod.CANON_BLOCK, canon_mod.CANON_WIDE
    for T, block_loops in ((2 * B, 1), (2 * wide * B, 2)):
        cand = jax.ShapeDtypeStruct((T, K), jnp.uint32, sharding=one_chip)
        valid = jax.ShapeDtypeStruct((T,), jnp.bool_, sharding=one_chip)
        hlo = jax.jit(canon.keys).lower(cand, valid).compile().as_text()
        bodies = loop_bodies(hlo)
        group = [b for b in bodies if not any(op == "while" for _, op in b)]
        # a block loop and, inside it, the loop over the group: once for
        # the blocks of B rows, once more where the width holds wide blocks
        assert len(bodies) == 2 * block_loops, (T, [len(b) for b in bodies])
        assert len(group) == block_loops
        for ops in group:
            # the gather of the block's elements (its index row, clamp and
            # bounds mask), the value maps, the shifts, three fusions that
            # pack and compare the lanes, five scalar conversions of the
            # permutation's entries, the loop's own counter and test
            assert len(ops) <= 17, (T, ops)
