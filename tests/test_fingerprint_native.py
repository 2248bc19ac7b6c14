"""The host's fingerprint-and-digest twin (ISSUE 46): one native pass over the
rows (`native/fpset.cpp` `rows_digest`) where the library built, the same
arithmetic in numpy over blocks of 16,384 rows where it did not, and both
held to the jax kernel (`ops.fingerprint.fingerprint_lanes`) to the last bit.

Every case runs twice: with the library as this box built it, and with it
forced absent (`native._build_error` set, as on a box with no toolchain), so
the fallback is what `cli verify-checkpoint` gets there.  The level boundary's
`frontier-verify` span, the chain a run stamps and the frontier flip are held
here too: the verify is the one caller on every pass's path.

CPU, seconds."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kafka_specification_tpu import native
from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import kip320
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.ops import fingerprint as jax_fp
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.resilience import integrity
from kafka_specification_tpu.resilience.checkpoints import verify_file
from kafka_specification_tpu.resilience.integrity import (
    IntegrityError, LevelDigestChain, digest_fps, digest_rows,
    fingerprint_rows, pair_u64,
)

M32 = 0xFFFFFFFF
LANES = (1, 2, 3, 4, 5, 15)
# the numpy twin's block edges, and a length that is no multiple of anything
ROWS = (0, 1, 16_383, 16_384, 16_385, 100_003)
assert integrity._BLOCK == 16_384

# `kip320-3b` (3 brokers, every bound of the corpus) to depth 6, the digest
# chain the parent commit (06eff7b) stamps into its checkpoint, under the
# device and under the host visited backend alike: count, xor, sum, link
PARENT_CHAIN = [
    [1, 12011820598133251379, 12011820598133251379, 13849427026772819506],
    [6, 18372161587107121860, 6160954931632470864, 11382583688540889627],
    [30, 17214403228554796781, 3825346324688471211, 8299852312644553952],
    [138, 8044668281308504004, 3800555846887568756, 16534018748826962331],
    [366, 9987615603957522705, 9260860297788141505, 6143212986325468288],
    [1170, 1172514259437847689, 10361758332513944253, 11562455273886837880],
    [2715, 16933999894851225188, 11177971086172723006, 13571678595086103906],
]


@pytest.fixture(params=["native", "numpy"])
def twin(request, monkeypatch):
    """Which implementation answers: the library, or (forced absent, as
    where `g++` is missing) the blocked numpy twin."""
    if request.param == "numpy":
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_build_error", OSError("no toolchain"))
        assert not native.native_available()
    elif not native.native_available():
        pytest.skip(f"no native library here: {native._build_error}")
    assert integrity.native_twin(False) == (request.param == "native")
    assert not integrity.native_twin(True)
    return request.param


def _jax(rows, exact):
    hi, lo = jax_fp.fingerprint_lanes(jnp.asarray(rows), exact)
    return pair_u64(np.asarray(hi), np.asarray(lo))


@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("k", LANES)
def test_the_twin_is_the_jax_kernel_to_the_bit(twin, k, n):
    rng = np.random.default_rng(1000 * k + n)
    rows = rng.integers(0, 2**32, size=(n, k), dtype=np.uint32)
    for exact in (False, True):
        want = _jax(rows, exact)
        got = fingerprint_rows(rows, exact)
        assert got.dtype == np.uint64 and np.array_equal(got, want)
        assert digest_rows(rows, exact) == digest_fps(want)
    # the other implementation, whichever answered above
    assert np.array_equal(
        integrity._hashed_blocks(rows, True)[0], _jax(rows, False))


@pytest.mark.parametrize("k", (2, 4, 15))
def test_rows_that_are_no_contiguous_uint32(twin, k):
    rng = np.random.default_rng(k)
    wide = rng.integers(0, 2**32, size=(40_001, 2 * k + 1), dtype=np.uint32)
    for rows in (wide[::3, 1::2][:, :k],  # strided both ways
                 np.asfortranarray(wide[:, :k]),
                 wide[:, :k].astype(np.int64)):
        assert not (rows.flags.c_contiguous and rows.dtype == np.uint32)
        dense = np.ascontiguousarray(rows, np.uint32)
        assert np.array_equal(fingerprint_rows(rows, False),
                              _jax(dense, False))
        assert digest_rows(rows, False) == digest_fps(_jax(dense, False))


def test_the_native_pass_refuses_what_it_cannot_read():
    if not native.native_available():
        pytest.skip("no native library here")
    rows = np.zeros((8, 3), np.uint32)
    for bad in (rows.astype(np.int32), rows[:, ::2], rows.ravel()):
        with pytest.raises(ValueError):
            native.rows_digest(bad, 1, 2, True)


# --- the sentinel remap ----------------------------------------------------
#
# No row reaches it under the two seeds as they are: a lane's step
# h -> rotl(h ^ kx, 13) * 5 + c is a bijection of h for the kx both seeds
# share, so two seeds that differ keep two states that differ, and hi == lo
# (all-ones or not) never happens.  The branch guards the padding sentinel
# all the same, in all three implementations; the test reaches it by making
# the seeds equal and solving murmur3 backwards for the last lane.


def _unshift(y, s):
    """x from y = x ^ (x >> s)."""
    x = y
    for _ in range(32 // s):
        x = y ^ (x >> s)
    return x


def _rotr(x, r):
    return ((x >> r) | (x << (32 - r))) & M32


def _inv(c):
    return pow(c, -1, 1 << 32)


def _last_lane_for(prefix, seed, want):
    """The last lane that makes murmur3(prefix + [lane], seed) == want."""
    k = len(prefix) + 1
    h = seed
    for lane in prefix:
        kx = (lane * 0xCC9E2D51) & M32
        kx = (((kx << 15) | (kx >> 17)) & M32) * 0x1B873593 & M32
        h ^= kx
        h = ((((h << 13) | (h >> 19)) & M32) * 5 + 0xE6546B64) & M32
    t = _unshift(want, 16)
    t = _unshift(t * _inv(0xC2B2AE35) & M32, 13)
    t = _unshift(t * _inv(0x85EBCA6B) & M32, 16) ^ (4 * k)
    kx = _rotr((t - 0xE6546B64) * _inv(5) & M32, 13) ^ h
    return _rotr(kx * _inv(0x1B873593) & M32, 15) * _inv(0xCC9E2D51) & M32


@pytest.mark.parametrize("k", (1, 3, 15))
def test_the_sentinel_remap_is_reached_under_equal_seeds(
        twin, monkeypatch, k):
    seed = int(integrity._SEED_HI)
    monkeypatch.setattr(integrity, "_SEED_LO", integrity._SEED_HI)
    monkeypatch.setattr(jax_fp, "SEED_LO", jax_fp.SEED_HI)
    rng = np.random.default_rng(k)
    rows = rng.integers(0, 2**32, size=(20_000, k), dtype=np.uint32)
    hits = (0, 16_383, 16_384, 19_999)
    for i in hits:
        rows[i, -1] = _last_lane_for(
            [int(v) for v in rows[i, :-1]], seed, M32)
    want = _jax(rows, False)
    remapped = np.uint64(0xFFFFFFFF_FFFFFFFE)
    assert np.flatnonzero(want == remapped).tolist() == list(hits)
    # every other row keeps hi == lo, untouched
    assert np.all((want >> np.uint64(32) == want & np.uint64(M32))
                  | (want == remapped))
    assert np.array_equal(fingerprint_rows(rows, False), want)
    assert digest_rows(rows, False) == digest_fps(want)


# --- the callers -------------------------------------------------------------


def test_verify_level_takes_fingerprints_or_their_digest():
    fps = fingerprint_rows(
        np.arange(60, dtype=np.uint32).reshape(20, 3), False)
    chain = LevelDigestChain()
    chain.fold(fps)
    chain.seal(0, 20)
    chain.verify_level(0, fps)
    chain.verify_level(0, digest_fps(fps))
    bad = fps.copy()
    bad[7] ^= np.uint64(1 << 20)
    texts = []
    for handed in (bad, digest_fps(bad)):
        with pytest.raises(IntegrityError) as ei:
            chain.verify_level(0, handed)
        assert ei.value.site == "frontier" and ei.value.depth == 0
        texts.append(str(ei.value))
    assert texts[0] == texts[1]


def _chain_of(ck, name="bfs_checkpoint.npz"):
    return np.asarray(verify_file(os.path.join(ck, name))["digest_chain"])


@pytest.mark.parametrize("backend", ("device", "host"))
def test_a_run_stamps_the_parent_commits_chain(twin, tmp_path, backend):
    """The device backend folds the device's fingerprints and verifies the
    frontier through `digest_rows`; the host backend folds through it too
    (with the library absent it commits through `FpSet`'s python set)."""
    ck = str(tmp_path / "ck")
    res = check(kip320.make_model(Config(3, 2, 2, 2)), checkpoint_dir=ck,
                max_depth=6, store_trace=False, visited_backend=backend)
    assert res.levels == [row[0] for row in PARENT_CHAIN]
    assert _chain_of(ck).tolist() == PARENT_CHAIN


def _verify_spans(run_dir):
    return [r for r in read_jsonl_tolerant(os.path.join(run_dir,
                                                        "spans.jsonl"))
            if r.get("kind") == "span" and r.get("span") == "frontier-verify"]


@pytest.mark.parametrize("engine", ("single", "sharded"))
def test_a_frontier_flip_lands_at_the_boundary_and_closes_its_span(
        twin, tmp_path, monkeypatch, engine):
    """`flip("frontier")` still raises `IntegrityError("frontier",
    depth=d)` from the boundary's verify on both engines, and the
    `frontier-verify` span it raised under is closed like the others."""
    model = kip320.make_model(Config(3, 2, 2, 2))  # 3 lanes: hashed mode
    run_dir = str(tmp_path / "run")
    kw = dict(min_bucket=32, checkpoint_dir=str(tmp_path / "ck"),
              max_depth=4, run=RunContext(run_dir))
    monkeypatch.setenv("KSPEC_FAULT", "flip@frontier:2")
    with pytest.raises(IntegrityError) as ei:
        if engine == "single":
            check(model, **kw)
        else:
            check_sharded(model, mesh=Mesh(
                np.array(jax.devices("cpu")[:2]), ("d",)), **kw)
    assert (ei.value.site, ei.value.depth) == ("frontier", 2)
    spans = _verify_spans(run_dir)
    # boundaries 0, 1 and 2, each ended (no begin marker is left open)
    assert [s["ph"] for s in spans] == ["E"] * 3
    assert [s["rows"] for s in spans] == [1, 6, 30]
    assert all(s["lanes"] == 3 and s["native"] == (twin == "native")
               for s in spans)
    man = json.load(open(os.path.join(run_dir, "manifest.json")))
    assert man["status"] == "integrity-violation"
    assert man["result"]["site"] == "frontier"


def test_under_symmetry_no_row_is_re_read(tmp_path):
    """The cell that bypasses the mechanism: the chain holds orbit keys, the
    host cannot recompute them from rows, and no `frontier-verify` opens."""
    run_dir = str(tmp_path / "run")
    check(kip320.make_model(Config(2, 2, 1, 1), symmetric=True),
          min_bucket=32, store_trace=False, run=RunContext(run_dir))
    assert _verify_spans(run_dir) == []
