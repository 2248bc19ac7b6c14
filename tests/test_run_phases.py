"""`check()` as a driver over a run-state object and five phases (ISSUE 43;
docs/engine.md § The run and its phases): what a run does, held to a golden
that the SAME test body wrote at the parent commit (4fec30a, before `check`
was taken apart), and the one verdict path both engines call.

The golden (`tests/data/run_phases_golden.json`) holds, for the small Kip320
job (2 brokers, 277 states, diameter 11; chunks of 16 rows through an
8-row gate, so levels stream several chunks) under each pipeline and visited
backend: the level counts, every level record with its host timings dropped
(keys in order), the digest chain a checkpointed run stamps, and the names of
the engine thread's spans in the order they ended.  It also holds the
rendered counterexample of each engine.  Written by
`python tests/test_run_phases.py --write` on the parent's tree; never by a
test run.  PR 44 wrote it again for the one key it added to every level
record, `guard_lanes` (nothing else differs from the parent's file, key
for key), and holds that key to the `step` spans' buckets here; PR 45
wrote it once more for `probes` and `probes_windowed` behind it (nothing else
differs, key for key) and holds them to the chunks here; PR 46 wrote it
for the `frontier-verify` span each level boundary now ends (twelve a run;
with that name taken out every list of spans, and everything else, is the
file PR 45 left: the chain too, entry for entry).  PR 48 left it as it
was and holds the verdict path to the host here: `build_violation` (both
sources, a model with and without a decoder), `init_violation_result` and
the trace-less decode run with `StateSpec.unpack` raising and every
transfer disallowed.  A
statement of the commit path that moves across another shows here as a
counter, a key or a span out of place."""

import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

if __name__ == "__main__":  # (pytest runs from the root, with tests/ added)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import conftest  # noqa: F401  (the suite's platform: CPU, 8 devices)

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kafka_specification_tpu.engine.bfs import (
    build_violation, check, decode_packed, decode_rows, init_violation_result)
from kafka_specification_tpu.engine.hostio import LEVEL_TIMINGS
from kafka_specification_tpu.models import id_sequence, kip320
from kafka_specification_tpu.models.base import Invariant
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.ops.packing import StateSpec
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.storage.parent_log import ParentLog
from kafka_specification_tpu.utils.pretty import render_trace

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "run_phases_golden.json")
CFG = Config(2, 2, 1, 1)
KW = dict(min_bucket=8, compact_gate=8, chunk_size=16)
PIPELINES = ("fused", "device", "legacy")
BACKENDS = ("device", "device-hash", "host")
# host clocks: two runs do not repeat them
DROP = ("ts", "unix", "run_id", "level_ms", "step_ms", "host_ms", "store_ms",
        "io_hidden_ms", "io_exposed_ms", "overlap_efficiency",
        "host_probe_ms") + LEVEL_TIMINGS
# the span another thread ends (the checkpoint writer's), and the one
# whose presence says what an earlier call left in the step cache
OFF_THREAD = ("compile", "checkpoint-write")
FIRST_TRY = Config(3, 1, 1, 2)  # WeakIsr at depth 11, 78,832 states


def _golden():
    with open(GOLDEN) as f:
        return json.load(f)


def _span_names(run_dir):
    names = []
    for r in read_jsonl_tolerant(os.path.join(run_dir, "spans.jsonl")):
        if r.get("kind") != "span" or r.get("ph") != "E":
            continue
        if r["span"] in OFF_THREAD:
            continue
        names.append(r["span"] + (":" + r["program"]
                                  if r["span"] == "dispatch" else ""))
    return names


def _observe(tmp, pipeline, backend):
    """One checkpointed run of the small job on a fresh model -> what the
    golden holds of it (JSON types only)."""
    model = kip320.make_model(CFG)
    run_dir, ck = os.path.join(tmp, "run"), os.path.join(tmp, "ck")
    res = check(model, run=RunContext(run_dir), checkpoint_dir=ck,
                pipeline=pipeline, visited_backend=backend, **KW)
    with np.load(os.path.join(ck, "bfs_checkpoint.npz")) as z:
        chain = np.array(z["digest_chain"]).tolist()
    # what `guard_lanes` (PR 44) must read: every `step` span's padded
    # rows (a whole-level program: its chunks' too) x the static fanout
    guard = {}
    for r in read_jsonl_tolerant(os.path.join(run_dir, "spans.jsonl")):
        if r.get("span") == "step" and r.get("ph") == "E":
            guard[r["depth"] + 1] = guard.get(r["depth"] + 1, 0) + (
                r["bucket"] * r.get("chunks", 1) * model.total_fanout)
    return {
        "levels": res.levels,
        "total": res.total,
        "records": [[[k, v] for k, v in rec.items() if k not in DROP]
                    for rec in res.stats["levels"]],
        "chain": chain,
        "spans": _span_names(run_dir),
        "guard_lanes_of_spans": [guard[rec["depth"]]
                                 for rec in res.stats["levels"]],
    }


def _violation(res, model):
    v = res.violation
    return {
        "levels": res.levels,
        "total": res.total,
        "invariant": v.invariant,
        "depth": v.depth,
        "state": repr(v.state),
        "trace": [[a, repr(s)] for a, s in v.trace],
        "rendered": render_trace(model.meta, v.trace),
    }


def _init_violation_model():
    base = id_sequence.make_model(3)
    return dataclasses.replace(base, invariants=[
        Invariant("NotZero", lambda s: s["nextId"] != 0)])


def _verdicts(store=None):
    """The verdict of each engine on the violating jobs; `store` receives
    the single engine's trace store of the first-try job."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
    out = {}
    for name, run in (
            ("single", lambda m, **kw: check(m, min_bucket=1024, **kw)),
            ("sharded-2", lambda m: check_sharded(m, mesh=mesh,
                                                  min_bucket=1024))):
        m = kip320.make_first_try_model(FIRST_TRY)
        kw = {"collect_trace": store} if name == "single" else {}
        out[f"first-try/{name}"] = _violation(run(m, **kw), m)
        m = _init_violation_model()
        out[f"init/{name}"] = _violation(run(m), m)
    return out


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("pipeline", PIPELINES)
def test_a_run_repeats_the_parent_commits_records(tmp_path, pipeline,
                                                  backend):
    want = _golden()["runs"][f"{pipeline}/{backend}"]
    got = json.loads(json.dumps(_observe(str(tmp_path), pipeline, backend)))
    assert got["levels"] == want["levels"] and got["total"] == want["total"]
    assert got["chain"] == want["chain"]
    for depth, (a, b) in enumerate(zip(got["records"], want["records"]), 1):
        assert a == b, f"level record {depth}"
    assert len(got["records"]) == len(want["records"])
    assert got["spans"] == want["spans"]
    assert [dict(rec)["guard_lanes"] for rec in got["records"]] == got[
        "guard_lanes_of_spans"]
    # the sorted-set probes of a level (PR 45): a chunk's program probes
    # the visited set once where that lives sorted on the device; a
    # whole-level program probes its level-new set once a chunk and, on
    # that backend, the visited set once a chunk and once for its merge
    # (the `device-hash` backend leaves that pipeline for the chunk loop);
    # no capacity here is above `dedup.PROBE_WINDOW`
    a_chunk, a_level = {
        ("device", "device"): (2, 1), ("device", "host"): (1, 0),
    }.get((pipeline, backend), (int(backend == "device"), 0))
    for rec in map(dict, got["records"]):
        assert rec["probes"] == a_chunk * rec["chunks"] + a_level
        assert rec["probes_windowed"] == 0


def test_a_finished_run_is_freed_without_the_collector(tmp_path):
    """The run object holds the visited set, the frontier and the trace
    store: nothing it owns may point back at it (the pipeline's
    `on_degrade_chunk`, a checkpoint validator, a writer's callback), or a
    served job's device memory waits for the cyclic collector (on the chip
    `peak_hbm_MiB` read 485 for 293 and 653 for 307 with such a cycle)."""
    from kafka_specification_tpu.engine.run import Run

    gc.collect()
    gc.disable()
    try:
        for kw in (dict(), dict(checkpoint_dir=str(tmp_path / "ck")),
                   dict(pipeline="device", store="disk",
                        mem_budget="64K", spill_dir=str(tmp_path / "sp"))):
            # (runs of earlier tests in this process may be held by a
            # traceback some test keeps: only this call's run counts)
            old = {id(o) for o in gc.get_objects() if isinstance(o, Run)}
            assert check(kip320.make_model(CFG), **KW, **kw).total == 277
            for _ in range(50):  # (a worker thread may be on its way out)
                alive = [o for o in gc.get_objects()
                         if isinstance(o, Run) and id(o) not in old]
                if not alive:
                    break
                time.sleep(0.1)
            assert not alive, (kw, [type(x).__name__ for x in
                                    gc.get_referrers(alive[0])])
    finally:
        gc.enable()


@pytest.fixture(scope="module")
def first_try_store():
    """The trace store of the single engine's first-try run (`verdicts`
    fills it: one run of the job serves both)."""
    return []


@pytest.fixture(scope="module")
def verdicts(first_try_store):
    return json.loads(json.dumps(_verdicts(first_try_store)))


@pytest.fixture(scope="module")
def first_try_idx(verdicts, first_try_store):
    """The violating row's index in its level of the store: the one row
    of the level that decodes to the verdict's state."""
    want = verdicts["first-try/single"]
    states = decode_rows(kip320.make_first_try_model(FIRST_TRY),
                         first_try_store[want["depth"]][0])
    (idx,) = [i for i, s in enumerate(states) if repr(s) == want["state"]]
    return idx


@pytest.mark.parametrize("job", ["first-try", "init"])
@pytest.mark.parametrize("engine", ["single", "sharded-2"])
def test_each_engine_returns_the_parent_commits_violation(verdicts, job,
                                                          engine):
    """Both engines build their Violation through `engine.bfs`'s
    `decode_packed`, `init_violation_result` and `build_violation`."""
    got, want = verdicts[f"{job}/{engine}"], _golden()["verdicts"]
    assert got == want[f"{job}/{engine}"]
    # the two engines agree on what a user reads first; which violating
    # state of the level each meets first is its own discovery order's
    other = verdicts[f"{job}/single"]
    for key in ("levels", "total", "invariant", "depth"):
        assert got[key] == other[key], key
    assert len(got["trace"]) == len(other["trace"]) == got["depth"] + 1
    if job == "init":
        assert got == other and got["trace"] == [["<init>", "0"]]


@contextlib.contextmanager
def _no_device():
    """Under it the verdict path can run no device operation: `unpack`,
    the form the kernels trace, raises, and so does a transfer."""
    def unpack(self, lanes):
        raise AssertionError("StateSpec.unpack on the verdict path")

    with pytest.MonkeyPatch.context() as mp, jax.transfer_guard("disallow"):
        mp.setattr(StateSpec, "unpack", unpack)
        yield


class _Spans:
    """An observer that keeps what each span it opened ended with."""

    def __init__(self):
        self.ended = []

    def open_span(self, kind, **attrs):
        return _Span(self.ended, {"span": kind, **attrs})


class _Span:
    def __init__(self, ended, attrs):
        self.ended, self.attrs = ended, attrs

    def finish(self, **attrs):
        self.ended.append({**self.attrs, **attrs})


def _eager(spec, row):
    """What the verdict path did before PR 48: `unpack` op by op on the
    device, then a fetch a field."""
    return {k: np.asarray(v)
            for k, v in spec.unpack(jax.numpy.asarray(row)).items()}


def _same_fields(got, want):
    assert list(got) == list(want)
    for k in want:
        assert type(got[k]) is np.ndarray
        assert (got[k].dtype, got[k].shape) == (want[k].dtype, want[k].shape)
        np.testing.assert_array_equal(got[k], want[k])


@pytest.mark.parametrize("decoder", ["decoder", "no-decoder"])
@pytest.mark.parametrize("source", ["ram", "disk"])
def test_the_verdict_path_decodes_on_the_host(verdicts, first_try_store,
                                              first_try_idx, tmp_path,
                                              source, decoder):
    """`build_violation` over the run's own trace store, and over a parent
    log that holds the same levels, with no device under it: the golden's
    trace to the byte where the model has a decoder, and the field dicts
    the eager `unpack` gave where it has none (PR 48: a row that is on the
    host is unpacked on the host)."""
    want = verdicts["first-try/single"]
    store, depth, idx = first_try_store, want["depth"], first_try_idx
    model = kip320.make_first_try_model(FIRST_TRY)
    stores = (store, None)
    if source == "disk":
        plog = ParentLog(str(tmp_path / "plog"), model.spec.num_lanes)
        for d, (rows, parent, act) in enumerate(store):
            plog.begin_level(d)
            plog.append(rows, parent, act)
            plog.end_level()
        stores = (None, plog.view())
    if decoder == "no-decoder":
        model = dataclasses.replace(model, decode=None)
        chain, i = [], idx
        for d in range(depth, -1, -1):
            chain.append(_eager(model.spec, store[d][0][i]))
            i = int(store[d][1][i])
        chain.reverse()
    obs = _Spans()
    with _no_device():
        v = build_violation(model, *stores, want["invariant"], depth, idx,
                            obs=obs)
    assert (v.invariant, v.depth) == (want["invariant"], depth)
    assert [a for a, _ in v.trace] == [a for a, _ in want["trace"]]
    assert v.state is v.trace[-1][1]
    if decoder == "decoder":
        assert [repr(s) for _, s in v.trace] == [s for _, s in want["trace"]]
        assert render_trace(model.meta, v.trace) == want["rendered"]
    else:
        for (_, got), ref in zip(v.trace, chain):
            _same_fields(got, ref)
    (cex,) = obs.ended
    assert (cex["span"], cex["source"], cex["decode"], cex["decoded_rows"],
            cex["trace_len"]) == ("counterexample", source, "host",
                                  depth + 1, depth + 1)
    assert cex["walk_ms"] >= 0 and cex["decode_ms"] >= 0


@pytest.mark.parametrize("decoder", ["decoder", "no-decoder"])
def test_a_violation_with_no_trace_decodes_on_the_host(decoder):
    """The two verdicts that walk nothing: an initial state that breaks an
    invariant (`init_violation_result`) and the trace-less `Violation` of
    a job that keeps no store (`engine/run.py`: `decode_packed` of the
    frontier's row), with no device under them."""
    model = _init_violation_model()
    if decoder == "no-decoder":
        model = dataclasses.replace(model, decode=None)
    (init,) = model.init_states()
    row = np.asarray(model.spec.pack(init))
    eager = _eager(model.spec, row)
    with _no_device():
        res = init_violation_result(model, model.invariants[0], row, [1], 1,
                                    0.5)
        state = decode_packed(model, row)
    v = res.violation
    assert (v.invariant, v.depth, v.trace) == ("NotZero", 0,
                                               [("<init>", v.state)])
    assert (res.levels, res.total, res.diameter) == ([1], 1, 0)
    for got in (v.state, state):
        if decoder == "decoder":
            assert got == model.decode(eager) and repr(got) == "0"
        else:
            _same_fields(got, eager)  # `nextId`: a 0-d int32, as it was


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        raise SystemExit("usage: python tests/test_run_phases.py --write")
    import tempfile

    runs = {}
    for p in PIPELINES:
        for b in BACKENDS:
            with tempfile.TemporaryDirectory() as tmp:
                runs[f"{p}/{b}"] = _observe(tmp, p, b)
    with open(GOLDEN, "w") as f:
        json.dump({"runs": runs, "verdicts": _verdicts()}, f, indent=0,
                  sort_keys=False)
        f.write("\n")
    print(f"wrote {GOLDEN}")
