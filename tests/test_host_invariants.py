"""The engines' start and finish as cached programs (ISSUE 26): the
invariant pass over host-held rows (`hinv_n2`; the sharded engine's
`shi_n2` is the same body) and the initial states' pack + fingerprint
(`init_n2`).

CPU, a two-replica FiniteReplicatedLog whose initial state is NOT the
all-zero packed row, so the empty state (what a padding row unpacks to)
is an ordinary reachable state that an invariant can forbid.  Verdicts are
held to the plain oracle (oracle/interp.py); the row the engine names is
held to the engine's own level order, read through the oracle's predicate.
"""

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kafka_specification_tpu.engine import pipeline as pl
from kafka_specification_tpu.engine.bfs import _Step, check
from kafka_specification_tpu.engine.hostio import HostIO
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models.base import Invariant, Model
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.obs.observer import RunObserver
from kafka_specification_tpu.oracle.interp import OracleModel, oracle_bfs
from kafka_specification_tpu.parallel import sharded
from kafka_specification_tpu.parallel.sharded import check_sharded

N, L, R = 2, 3, 2
MIN_BUCKET = 32

# name: (kernel predicate over the tensor state, the oracle's over the
# decoded one).  SomeLog fails on the empty state alone: every padding row.
INVARIANTS = {
    "SomeLog": (lambda s: (s["end"] > 0).any(),
                lambda s: any(len(log) > 0 for log in s)),
    "ShortLogs": (lambda s: (s["end"] < L).all(),
                  lambda s: all(len(log) < L for log in s)),
    "NotInit": (lambda s: (s["end"] != 1).any(),
                lambda s: any(len(log) != 1 for log in s)),
}


def _pair(names):
    """(model, oracle) with every log holding one record at the start."""
    # (hashed fingerprints: the exact 64-bit form sorts the all-zero row,
    # the empty state, to row 0 of its level)
    base = frl.make_model(N, L, R, force_hashed=True)
    obase = frl.make_oracle(N, L, R)
    model = Model(
        name=base.name + "/one-record",
        spec=base.spec,
        init_states=lambda: [{"end": [1] * N,
                              "rec": [[0] + [frl.NIL] * (L - 1)] * N}],
        actions=base.actions,
        invariants=[Invariant(n, INVARIANTS[n][0]) for n in names],
        decode=base.decode,
    )
    oracle = OracleModel(
        name=model.name,
        init_states=lambda: [((0,),) * N],
        actions=obase.actions,
        invariants=[(n, INVARIANTS[n][1]) for n in names],
    )
    return model, oracle


def _decoded(model, rows):
    st = jax.vmap(model.spec.unpack)(rows)
    st = {k: np.asarray(v) for k, v in st.items()}
    return [model.decode({k: v[i] for k, v in st.items()})
            for i in range(rows.shape[0])]


CASES = {
    # (a) the initial state violates
    "init-violates": (("NotInit",), 2, "NotInit", 0),
    # (b) depth 1 holds no empty state, but every padding row of its
    # invariant pass is one: masked out, the verdict is clean ...
    "padding-masked": (("SomeLog",), 1, None, None),
    # ... and at depth 2 the one real empty state is found, not at row 0
    "cut-frontier": (("SomeLog",), 2, "SomeLog", 2),
    # (c) two invariants, different rows: declaration order decides
    "order-short-first": (("ShortLogs", "SomeLog"), 2, "ShortLogs", 2),
    "order-some-first": (("SomeLog", "ShortLogs"), 2, "SomeLog", 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_verdict_row_state_and_trace_match_the_oracle(case):
    names, max_depth, want_inv, want_depth = CASES[case]
    model, oracle = _pair(names)
    levels = []
    res = check(model, max_depth=max_depth, min_bucket=MIN_BUCKET,
                collect_levels=levels)
    ores = oracle_bfs(oracle, max_depth=max_depth)
    if want_inv is None:
        assert ores.violation is None and res.violation is None
        assert res.levels == ores.levels
        return
    v = res.violation
    assert (v.invariant, v.depth) == ores.violation[:2] == (want_inv,
                                                            want_depth)
    # the row: the lowest of the engine's level that the ORACLE's
    # predicate rejects (the oracle's own discovery order is another)
    pred = dict(oracle.invariants)[want_inv]
    states = _decoded(model, levels[want_depth])
    bad = [i for i, s in enumerate(states) if not pred(s)]
    assert bad and v.state == states[bad[0]]
    if case == "cut-frontier":
        assert bad[0] > 0 and len(states) < MIN_BUCKET  # premise of (b)
    if case.startswith("order"):
        other = dict(oracle.invariants)[names[1]]
        assert any(not other(s) for s in states)  # premise of (c)
        assert states[bad[0]] != next(s for s in states if not other(s))
    # the trace: an oracle path from an initial state to that state
    assert len(v.trace) == want_depth + 1 and v.trace[-1][1] == v.state
    assert v.trace[0] == ("<init>", oracle.init_states()[0])
    succ = {a.name: a.successors for a in oracle.actions}
    for (_, s), (act, t) in zip(v.trace, v.trace[1:]):
        assert t in set(succ[act](s)), (s, act, t)


@pytest.fixture(scope="module")
def reachable():
    """(model, rows of every reachable state, rows that satisfy both
    invariants, the empty state's row) of the two-invariant model."""
    model, oracle = _pair(("ShortLogs", "SomeLog"))
    levels = []
    check(model, min_bucket=MIN_BUCKET, check_invariants=False,
          collect_levels=levels)
    rows = np.concatenate(levels)
    preds = dict(oracle.invariants)
    states = _decoded(model, rows)
    fine = [i for i, s in enumerate(states)
            if preds["SomeLog"](s) and preds["ShortLogs"](s)]
    empty = next(i for i, s in enumerate(states) if not preds["SomeLog"](s))
    assert len(fine) > MIN_BUCKET
    return model, rows, fine, empty


@pytest.mark.parametrize("case", ["clean", "order", "lowest", "last-piece"])
def test_rows_beyond_the_bucket_go_through_in_pieces(reachable, case):
    """(e) PR 44: rows longer than the bucket are launched a piece of
    `bucket` rows at a time (a depth cut leaves a level's worth of rows:
    1,075,905 of them in the 3-partition cell) and the verdict is the
    one launch's: the first invariant in declaration order that ANY piece
    violates, and its lowest row over all pieces."""
    model, rows, fine, empty = reachable
    preds = {i.name: i for i in model.invariants}
    states = _decoded(model, rows)
    long_log = next(i for i, s in enumerate(states)
                    if any(len(log) >= L for log in s))
    assert [i.name for i in model.invariants] == ["ShortLogs", "SomeLog"]
    # six pieces, the last of five rows: more than `PIECES_AHEAD`, so
    # verdicts are read while later pieces are still being launched
    n = 5 * MIN_BUCKET + 5
    assert n // MIN_BUCKET + 1 > _Step.PIECES_AHEAD + 1
    pick = (fine * 6)[:n]
    want = None
    if case == "order":
        # SomeLog falls in piece 0, ShortLogs in piece 2: declaration order
        pick[3], pick[2 * MIN_BUCKET + 7] = empty, long_log
        want = ("ShortLogs", 2 * MIN_BUCKET + 7)
    elif case == "lowest":
        pick[MIN_BUCKET + 9] = pick[2 * MIN_BUCKET + 1] = empty
        want = ("SomeLog", MIN_BUCKET + 9)
    elif case == "last-piece":
        pick[n - 1] = empty
        want = ("SomeLog", n - 1)
    io = HostIO()
    got = _Step(model).first_violation(
        ("hinv",), MIN_BUCKET, rows[pick], io,
        RunObserver(None, None, engine="bfs"))
    assert (got and (got[0].name, got[1])) == want
    if want:
        assert got[0] is preds[want[0]]
    # six uploads of one bucket each, never the whole padded length
    taken = io.take()
    assert taken["h2d_puts"] == 6
    assert taken["h2d_bytes"] == 6 * MIN_BUCKET * rows.shape[1] * 4


@pytest.mark.parametrize("place", ["absent", "last"])
@pytest.mark.parametrize("n", [1, MIN_BUCKET, MIN_BUCKET + 1])
def test_row_counts_at_the_bucket_edges(reachable, n, place):
    """(d) one row, a full bucket (no padding), one row more (a bucket of
    padding less one): the live-row mask is exact at every count."""
    model, rows, fine, empty = reachable
    pick = fine[:n] if place == "absent" else fine[:n - 1] + [empty]
    bucket = MIN_BUCKET if n <= MIN_BUCKET else 2 * MIN_BUCKET
    got = _Step(model).first_violation(
        ("hinv",), bucket, rows[pick], HostIO(),
        RunObserver(None, None, engine="bfs"))
    if place == "absent":
        assert got is None
    else:
        assert (got[0].name, got[1]) == ("SomeLog", n - 1)


def _program_spans(run):
    recs = read_jsonl_tolerant(run.spans_path)
    return [(r["span"], r.get("program"), r.get("bucket")) for r in recs
            if r.get("kind") == "span" and r["ph"] == "E"
            and r["span"] in ("compile", "dispatch")
            and r.get("program") in ("hinv", "init")]


def test_second_check_launches_three_programs_and_compiles_none(tmp_path):
    """One `init` and two `hinv` launches a pass (the initial states; the
    frontier `max_depth` cut), each a `dispatch` span; both programs live
    in the model's step cache, so a second call compiles neither."""
    model, _ = _pair(("ShortLogs",))
    runs = [RunContext(str(tmp_path / f"run{i}")) for i in range(2)]
    for run in runs:
        res = check(model, max_depth=1, min_bucket=MIN_BUCKET, run=run)
        assert res.ok and res.levels == [1, 6]
    launches = [("dispatch", "init", 1), ("dispatch", "hinv", MIN_BUCKET),
                ("dispatch", "hinv", MIN_BUCKET)]
    first, second = (_program_spans(run) for run in runs)
    assert [s for s in first if s[0] == "dispatch"] == launches
    assert sorted(s for s in first if s[0] == "compile") == [
        ("compile", "hinv", MIN_BUCKET), ("compile", "init", 1)]
    assert second == launches
    recs = read_jsonl_tolerant(runs[1].spans_path)
    assert not [r for r in recs if r.get("span") == "compile"]
    # no capacity component: rewarm skips them, growth never evicts them
    keys = [k for k in model._step_cache if k[0] in ("hinv", "init")]
    assert sorted(keys) == [("hinv", MIN_BUCKET, ("ShortLogs",)),
                            ("init", 1)]
    assert all(pl.key_vcap(k) is None for k in keys)


def test_both_engines_name_and_key_their_invariant_program_as_before():
    """`shi_n2`, keyed ("shi", mesh, N, inv_sig): what `kip320-5b-x4`'s
    compile cache and set-up hold; the single-device twin is `hinv_n2`."""
    model, _ = _pair(("ShortLogs", "SomeLog"))
    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
    res = check_sharded(model, mesh=mesh, max_depth=1, min_bucket=8,
                        store_trace=False)
    assert res.ok and res.levels == [1, 6]
    check(model, max_depth=1, min_bucket=MIN_BUCKET)
    assert sharded.INVARIANT_TAG == "shi"
    sig = ("ShortLogs", "SomeLog")
    keys = {k for k in model._step_cache if k[0] in ("shi", "hinv")}
    # 1 and 6 rows over 4 shards: 8 rows a shard, the floor
    assert keys == {("shi", mesh, 32, sig), ("hinv", MIN_BUCKET, sig)}
    K = model.spec.num_lanes
    for key in keys:
        fn = model._step_cache[key]
        assert fn.__name__ == pl.program_name(key[0]) == f"{key[0]}_n{pl.NAMING_VERSION}"
        rows = jax.ShapeDtypeStruct((key[-2], K), jax.numpy.uint32)
        text = fn.lower(rows, np.int32(1)).as_text(debug_info=True)
        assert f"module @jit_{key[0]}_n{pl.NAMING_VERSION}" in text
        assert set(re.findall(r"kspec\.([a-z_]+)", text)) == {"invariants"}
