"""TLC's SYMMETRY over Permutations(Replicas) (ISSUE 38).

CPU, small sizes.  What is held here:

(a) equivariance, the proof the declaration (`kafka_replication.symmetry`) is
    right: on states sampled from the reachable set, the oracle's
    `successors(g(s))` is `g(successors(s))` and every invariant agrees on
    `s` and `g(s)`, for every `g`; and `ops/canon.py`'s image of a packed
    row is the packed oracle image, field by field (role by role);
(b) the kernel against the oracle: the reduced `check` counts the oracle's
    orbits level by level, on every pipeline;
(c) the reduction against the unreduced model: each level's `orbit_states`
    is the unreduced level count, and the orbits of the unreduced reachable
    set grouped by brute force are as many as the reduced search stored;
(d) a counterexample found under SYMMETRY is a behaviour of the unreduced
    spec;
(e) every loud refusal, the keys that keep a reduced and an unreduced job
    apart, and a model with no symmetry lowering with no `kspec.canon`;
(f) the oracle's signature-ordered canonical form against the brute force
    over all N!.

A kernel that splits or merges an orbit fails (b) and (c):
`test_a_canon_that_forgets_a_role_is_caught` shows it on a declaration
without the mask bits of `isr`.
"""

import dataclasses
import functools
import json
import logging
import os
import random
from itertools import permutations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kafka_specification_tpu.engine.bfs import check, prepare
from kafka_specification_tpu.engine.pipeline import CANON_FIELDS, WORK_FIELDS
from kafka_specification_tpu.models import kafka_replication as kr
from kafka_specification_tpu.models import kip320, variants
from kafka_specification_tpu.models.base import FieldRole, Symmetry
from kafka_specification_tpu.obs import RunContext
from kafka_specification_tpu.ops.canon import Canon
from kafka_specification_tpu.oracle.interp import oracle_bfs
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg
from test_differential_walk import _kafka_encode_back

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG3 = kr.Config(3, 2, 2, 2)
CFG5 = kr.Config(5, 2, 2, 2)
SPECS = {
    "Kip320": (kip320.make_model, kip320.make_oracle),
    "Kip320FirstTry": (kip320.make_first_try_model,
                       kip320.make_first_try_oracle),
}
PERMS3 = list(permutations(range(3)))
# perfbench/golden/kip320-3b.json, levels 0-9 (oracle-derived, PR 22)
UNREDUCED_3B = [1, 6, 30, 138, 366, 1170, 2715, 5673, 10836, 18648]
ORBITS_3B = [1, 2, 6, 24, 63, 198, 458, 955, 1821, 3128]
ORBITS_5B = [1, 2, 6, 24, 63, 251]
UNREDUCED_5B = [1, 10, 90, 770, 2370, 14635]
SMALL = dict(min_bucket=64, compact_gate=64, chunk_size=1024)


def _cfg_text(name="Kip320.cfg", symmetry="Symm"):
    with open(os.path.join(ROOT, "configs", name)) as fh:
        text = fh.read()
    return text + (f"\nSYMMETRY {symmetry}\n" if symmetry else "")


def _rows(model, cfg, states):
    encode = _kafka_encode_back(cfg)  # the inverse of `make_decode`
    enc = [encode(s) for s in states]
    return np.asarray(jax.vmap(model.spec.pack)(
        {k: np.stack([e[k] for e in enc]) for k in enc[0]}))


@functools.lru_cache(maxsize=None)
def _reachable(spec_name, n, depth):
    """(oracle model, every level's states) of the unreduced spec."""
    om = SPECS[spec_name][1](kr.Config(n, 2, 2, 2))
    return om, oracle_bfs(om, max_depth=depth).level_sets


def _sample(spec_name, n, depth, k, seed=0):
    _om, levels = _reachable(spec_name, n, depth)
    states = sorted((s for lv in levels for s in lv), key=kr._o_order_key)
    return random.Random(seed).sample(states, min(k, len(states)))


# -- (a) equivariance ---------------------------------------------------------

@pytest.mark.parametrize("g", PERMS3, ids=lambda g: "g" + "".join(map(str, g)))
@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_the_oracle_is_equivariant(spec_name, g):
    om, _levels = _reachable(spec_name, 3, 8)
    for s in _sample(spec_name, 3, 8, 120):
        t = kr.o_permute(CFG3, s, g)
        for a in om.actions:
            assert set(a.successors(t)) == {
                kr.o_permute(CFG3, u, g) for u in a.successors(s)}, a.name
        for name, pred in om.invariants:
            assert pred(t) == pred(s), name


def test_the_oracle_is_equivariant_at_five_brokers():
    om = kip320.make_oracle(CFG5)
    states = [s for lv in oracle_bfs(om, max_depth=4).level_sets for s in lv]
    rng = random.Random(1)
    perms = rng.sample(list(permutations(range(5))), 12)
    for s in rng.sample(states, 60):
        for g in perms:
            t = kr.o_permute(CFG5, s, g)
            for a in om.actions:
                assert set(a.successors(t)) == {
                    kr.o_permute(CFG5, u, g) for u in a.successors(s)}
            assert all(pred(t) == pred(s) for _n, pred in om.invariants)


@pytest.fixture(scope="module")
def images3():
    model = kip320.make_model(CFG3, symmetric=True)
    states = _sample("Kip320FirstTry", 3, 9, 200)
    canon = Canon(model.spec, model.symmetry)
    got = np.asarray(jax.jit(canon.images)(
        jnp.asarray(_rows(model, CFG3, states))))
    return model, states, canon, got


@pytest.mark.parametrize("gi", range(len(PERMS3)))
def test_the_kernels_image_is_the_packed_oracle_image(images3, gi):
    model, states, canon, got = images3
    g = tuple(int(x) for x in canon.perms[gi])
    want = _rows(model, CFG3, [kr.o_permute(CFG3, s, g) for s in states])
    unpack = jax.vmap(model.spec.unpack)
    got_f, want_f = unpack(jnp.asarray(got[gi])), unpack(jnp.asarray(want))
    for name in got_f:  # role by role: a wrong one is named
        assert np.array_equal(got_f[name], want_f[name]), (name, g)
    assert np.array_equal(got[gi], want)


def test_the_kernels_images_at_five_brokers_cover_every_role():
    model = kip320.make_model(CFG5, symmetric=True)
    om = kip320.make_oracle(CFG5)
    states = [s for lv in oracle_bfs(om, max_depth=5).level_sets for s in lv]
    states = random.Random(2).sample(states, 64)
    canon = Canon(model.spec, model.symmetry)
    assert canon.G == 120 and (canon.n_member, canon.n_mask) == (9, 9)
    got = np.asarray(jax.jit(canon.images)(
        jnp.asarray(_rows(model, CFG5, states))))
    for gi in range(0, 120, 7):
        g = tuple(int(x) for x in canon.perms[gi])
        assert np.array_equal(got[gi], _rows(
            model, CFG5, [kr.o_permute(CFG5, s, g) for s in states])), g


def test_the_kernels_keys_are_the_oracles_orbits():
    """Least image, stabiliser and masked lanes of `Canon.keys`."""
    model = kip320.make_model(CFG3, symmetric=True)
    states = _sample("Kip320", 3, 8, 300, seed=3)
    canon = Canon(model.spec, model.symmetry)
    valid = np.arange(len(states)) % 3 != 0
    hi, lo, size, rows = map(np.asarray, jax.jit(canon.keys)(
        jnp.asarray(_rows(model, CFG3, states)), jnp.asarray(valid)))
    by_orbit = {}
    for i, s in enumerate(states):
        if not valid[i]:
            assert (hi[i], lo[i], size[i]) == (0xFFFFFFFF, 0xFFFFFFFF, 0)
            continue
        member, orbit = kr.o_canonical_brute(CFG3, s)
        assert size[i] == orbit
        by_orbit.setdefault(member, set()).add((int(hi[i]), int(lo[i])))
    assert all(len(keys) == 1 for keys in by_orbit.values())  # none split
    assert len(set.union(*by_orbit.values())) == len(by_orbit)  # none merged
    assert int(rows) == len(states)  # one block of the whole width


# -- (b), (c) the reduced search against the oracle and the unreduced job -----

def _reduced(spec_name, cfg, **kw):
    return SPECS[spec_name][0](cfg, symmetric=True, **kw)


@functools.lru_cache(maxsize=None)
def _searched(spec_name, n, depth, pipeline):
    import tempfile

    cfg = kr.Config(n, 2, 2, 2)
    res = check(_reduced(spec_name, cfg), max_depth=depth, pipeline=pipeline,
                store_trace=False, run=RunContext(tempfile.mkdtemp()), **SMALL)
    return res


@pytest.mark.parametrize("pipeline", ["fused", "device", "legacy"])
def test_the_reduced_check_counts_the_oracles_orbits(pipeline):
    res = _searched("Kip320", 3, 9, pipeline)
    om = kip320.make_oracle(CFG3, symmetric=True)
    want = oracle_bfs(om, max_depth=9, keep_level_sets=False)
    assert res.levels == want.levels == ORBITS_3B
    assert res.total == sum(ORBITS_3B) and res.violation is None
    assert res.stats["symmetry"] == {"set": "Replicas", "order": 6}
    assert not res.stats.get("degradations")


@pytest.mark.parametrize("pipeline", ["fused", "device"])
def test_the_reduced_check_at_five_brokers(pipeline):
    res = _searched("Kip320", 5, 5, pipeline)
    want = oracle_bfs(kip320.make_oracle(CFG5, symmetric=True), max_depth=5,
                      keep_level_sets=False)
    assert res.levels == want.levels == ORBITS_5B
    recs = res.stats["levels"]
    assert [r["orbit_states"] for r in recs] == UNREDUCED_5B[1:]
    assert res.stats["symmetry"]["order"] == 120


def test_the_reduced_check_of_a_variant():
    import tempfile

    res = check(variants.make_model("Kip279", CFG3, symmetric=True),
                max_depth=7, store_trace=False,
                run=RunContext(tempfile.mkdtemp()), **SMALL)
    want = oracle_bfs(variants.make_oracle("Kip279", CFG3, symmetric=True),
                      max_depth=7, keep_level_sets=False)
    plain = oracle_bfs(variants.make_oracle("Kip279", CFG3), max_depth=7,
                       keep_level_sets=False)
    assert res.levels == want.levels
    assert [r["orbit_states"] for r in res.stats["levels"]] == plain.levels[1:]


@pytest.mark.parametrize("depth", range(1, 10))
@pytest.mark.parametrize("pipeline", ["fused", "device"])
def test_orbit_states_is_the_unreduced_level_count(pipeline, depth):
    with open(os.path.join(ROOT, "perfbench", "golden",
                           "kip320-3b.json")) as fh:
        golden = json.load(fh)["levels"]
    assert golden[:10] == UNREDUCED_3B
    rec = _searched("Kip320", 3, 9, pipeline).stats["levels"][depth - 1]
    assert rec["depth"] == depth and rec["new"] == ORBITS_3B[depth]
    assert rec["orbit_states"] == golden[depth]
    # the stage forms images of whole blocks of live rows, never fewer
    assert rec["canon_rows"] >= rec["enabled_candidates"]


def test_the_unreduced_reachable_set_has_as_many_orbits():
    _om, levels = _reachable("Kip320", 3, 9)
    for depth, states in enumerate(levels):
        orbits = {kr.o_canonical_brute(CFG3, s)[0] for s in states}
        assert len(orbits) == ORBITS_3B[depth], depth
    assert _searched("Kip320", 3, 9, "fused").levels == ORBITS_3B


def test_a_canon_that_forgets_a_role_is_caught():
    """The same search under a declaration without the mask bits of `isr`:
    orbits split, and (b) and (c) both say so."""
    import tempfile

    sym = kr.symmetry(CFG3)
    wrong = Symmetry(sym.set_name, sym.n,
                     {**sym.roles, "isr": FieldRole(axis=0)})
    model = dataclasses.replace(kip320.make_model(CFG3), symmetry=wrong)
    res = check(model, max_depth=8, store_trace=False,
                run=RunContext(tempfile.mkdtemp()), **SMALL)
    assert res.levels != ORBITS_3B[:9]
    assert sum(res.levels) > sum(ORBITS_3B[:9])  # split, never merged
    assert [r["orbit_states"] for r in res.stats["levels"]] \
        != UNREDUCED_3B[1:9]


# -- (d) a counterexample under SYMMETRY --------------------------------------

@pytest.fixture(scope="module")
def first_try_violation():
    cfg = parse_cfg(_cfg_text("Kip320FirstTry.cfg"))
    model = build_model("MCKip320FirstTry", cfg)
    return model, check(model, **SMALL)


def test_first_try_violates_weak_isr_at_depth_11(first_try_violation):
    _model, res = first_try_violation
    assert res.violation is not None
    assert (res.violation.invariant, res.violation.depth) == ("WeakIsr", 11)
    assert len(res.violation.trace) == 12
    # fewer states than the unreduced job stores before it (184,141)
    assert res.total < 184141 // 3


def test_the_trace_is_a_behaviour_of_the_unreduced_spec(first_try_violation):
    _model, res = first_try_violation
    om = kip320.make_first_try_oracle(CFG3, ("TypeOk", "WeakIsr", "StrongIsr"))
    trace = res.violation.trace
    assert trace[0][0] == "<init>" and trace[0][1] in om.init_states()
    by_name = {a.name: a for a in om.actions}
    for (_a, s), (name, t) in zip(trace, trace[1:]):
        assert t in set(by_name[name].successors(s)), name
    weak = dict(om.invariants)["WeakIsr"]
    assert [weak(s) for _a, s in trace] == [True] * 11 + [False]


# -- (e) refusals and keys -----------------------------------------------------

def _mc(text=None):
    return build_model("MCKip320", parse_cfg(text or _cfg_text()))


def test_check_sharded_refuses_the_model():
    from kafka_specification_tpu.parallel.sharded import check_sharded

    with pytest.raises(ValueError, match="check_sharded does not support "
                                         "SYMMETRY Symm"):
        check_sharded(_mc(), max_depth=2)


@pytest.mark.parametrize("option,value", [
    ("checkpoint_dir", "ckpt"), ("seed", {"levels": [1]}),
    ("integrity_shadow", 0.5)])
def test_check_refuses_what_recomputes_plain_fingerprints(
        tmp_path, option, value):
    if option == "checkpoint_dir":
        value = str(tmp_path / value)
    with pytest.raises(ValueError, match=f"{option}= is not supported under "
                                         "SYMMETRY Symm"):
        check(_mc(), max_depth=2, **{option: value})


@pytest.mark.parametrize("module,text,message", [
    ("Kip320", _cfg_text(), "module 'Kip320' defines no such operator"),
    ("MCKip320", _cfg_text(symmetry="Perms"), "defines 'Symm', not 'Perms'"),
    ("AsyncIsr", _cfg_text("AsyncIsr.cfg"), "no symmetry set is declared"),
    ("MCKip320", _cfg_text() + "Partitions = 2\n", None),
], ids=["unknown-operator", "wrong-operator", "asyncisr", "product"])
def test_build_model_refuses(module, text, message):
    if message is None:  # the Partitions constant belongs under CONSTANTS
        text = _cfg_text(symmetry=None).replace(
            "MaxLeaderEpoch = 2", "MaxLeaderEpoch = 2\n    Partitions = 2"
        ) + "\nSYMMETRY Symm\n"
        message = "models/product.py"
    with pytest.raises(ValueError, match=message):
        build_model(module, parse_cfg(text))


def test_the_emitted_source_and_the_product_refuse_the_model():
    from kafka_specification_tpu.models.product import product_model

    with pytest.raises(ValueError, match="emitted kernel source declares no "
                                         "field roles"):
        build_model("MCKip320", parse_cfg(_cfg_text()), emitted=True)
    with pytest.raises(ValueError, match="models/product.py declares no "
                                         "field roles"):
        product_model(_mc(), 2)


def test_a_second_symmetry_line_is_an_error():
    with pytest.raises(ValueError, match="SYMMETRY takes one operator"):
        parse_cfg(_cfg_text() + "SYMMETRY Other\n")


def test_a_wrapper_module_without_the_stanza_is_the_module_it_extends():
    plain = build_model("MCKip320", parse_cfg(_cfg_text(symmetry=None)))
    assert plain.symmetry is None
    assert plain.name == build_model(
        "Kip320", parse_cfg(_cfg_text(symmetry=None))).name
    assert _mc().name == plain.name + "/SYMMETRY(Replicas)"
    oracle = build_model("MCKip320", parse_cfg(_cfg_text()), oracle=True)
    assert oracle.symmetry.order == 6


def test_init_and_next_say_that_they_are_ignored(caplog):
    with caplog.at_level(logging.WARNING):
        cfg = parse_cfg(_cfg_text(symmetry=None) + "INIT Init\nNEXT Next\n")
    assert cfg.symmetry is None
    assert [r.getMessage() for r in caplog.records] == [
        "INIT Init: ignored (each module's own Init is checked)",
        "NEXT Next: ignored (each module's own Next is checked)"]


def test_a_reduced_and_an_unreduced_job_share_no_cache_entry():
    from kafka_specification_tpu.service import kernel_cache, state_cache

    with_s, without = parse_cfg(_cfg_text()), parse_cfg(
        _cfg_text(symmetry=None))
    invs = ("TypeOk",)
    for key in (kernel_cache.shape_key, ):
        assert key("MCKip320", with_s, False, invs) != key(
            "MCKip320", without, False, invs)
    assert kernel_cache.model_key("MCKip320", with_s, False) != \
        kernel_cache.model_key("MCKip320", without, False)
    spec = {"module": "MCKip320", "max_depth": 5}
    a = state_cache.key_for_job(spec, with_s, False, invs)
    b = state_cache.key_for_job(spec, without, False, invs)
    assert a != b and a.base_digest() != b.base_digest()
    assert "symmetry" not in b.base_dict()  # older entries keep their digest


def test_a_reduced_and_an_unreduced_model_share_no_program():
    reduced, plain = _mc(), build_model(
        "MCKip320", parse_cfg(_cfg_text(symmetry=None)))
    pk_r, pk_p = prepare(reduced), prepare(plain)
    assert pk_r.step._cache is not pk_p.step._cache
    check(reduced, max_depth=2, prepared=pk_r, store_trace=False)
    assert pk_r.step._cache and not pk_p.step._cache
    assert reduced.name != plain.name  # the manifest's `model`
    with pytest.raises(ValueError, match="different model object"):
        check(plain, max_depth=2, prepared=pk_r)


def _lowered(model, bucket=64):
    pk = prepare(model)
    step = pk.step.build_raw(bucket, 4096, True, True, None)
    K = model.spec.num_lanes
    return jax.jit(step).lower(
        jnp.zeros((bucket, K), jnp.uint32), jnp.zeros((bucket,), bool),
        jnp.zeros((4096,), jnp.uint32), jnp.zeros((4096,), jnp.uint32),
        jnp.int32(0)).as_text(debug_info=True)


def test_a_model_without_symmetry_lowers_with_no_canon_operation():
    plain = _lowered(kip320.make_model(CFG3))
    assert "kspec.canon" not in plain and "kspec.fingerprint" in plain
    reduced = _lowered(kip320.make_model(CFG3, symmetric=True))
    assert "kspec.canon" in reduced
    # the counts vector grows by the two canon counts, and only there
    n_act = len(kip320.make_model(CFG3).actions)
    assert f"tensor<{n_act + len(WORK_FIELDS)}xi32>" in plain
    assert f"tensor<{n_act + len(WORK_FIELDS) + len(CANON_FIELDS)}xi32>" in reduced


# -- (f) the oracle's canonical form against the brute force ------------------

@pytest.mark.parametrize("spec_name,n,depth,k", [
    ("Kip320", 3, 9, 600), ("Kip320FirstTry", 3, 9, 600),
    ("Kip320", 4, 5, 300), ("Kip320", 5, 4, 120)])
def test_the_signature_order_and_the_brute_force_agree(spec_name, n, depth, k):
    cfg = kr.Config(n, 2, 2, 2)
    orbits = {}
    for s in _sample(spec_name, n, depth, k, seed=4):
        member, size = kr.o_canonical(cfg, s)
        brute, brute_size = kr.o_canonical_brute(cfg, s)
        assert size == brute_size
        # the member is of the same orbit, and canonical: a fixed point
        assert kr.o_canonical_brute(cfg, member)[0] == brute
        assert kr.o_canonical(cfg, member)[0] == member
        orbits.setdefault(brute, set()).add(member)
    assert all(len(members) == 1 for members in orbits.values())
