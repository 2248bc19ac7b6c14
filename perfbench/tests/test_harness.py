"""Tests of the harness's own judgement.  CPU, no chip.

    python3 -m pytest perfbench/tests -q

What a pass owes (`golden_for`), what it is asked (`pass_options`), why it
fails (`judge_pass`), what the oracle prefix accepts, what the window's rate
is, which levels the host and step readers read; then two whole runs of
the harness's control flow on the CPU (`--rehearse` skips the look for a
chip and cuts the depth, nothing else): one with the timed path broken
underneath, which must read `correct: false`, and the control, a reference
that breaks the configuration's guarantee of exact counts, which must not
pass either.

Under perfbench/, not tests/: a benchmark PR adds no file outside the
benchmark's own directories (PERF.md section 7 lists the move).
`selfcheck.py --all` runs this file.
"""

import importlib
import json
import os
import subprocess
import sys
import zlib

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)


@pytest.fixture
def harness(monkeypatch):
    """`perfbench/run.py` as a module, on the CPU, with the path its lazy
    imports need; path and environment are put back after the test."""
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(PERFBENCH)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return importlib.import_module("run")


VIOLATING = {"levels": [1, 4, 14, 44], "exhaustive": False,
             "violation": {"invariant": "WeakIsr", "depth": 3,
                           "trace_len": 4}}
CUT = {"levels": [1, 10, 90, 770], "exhaustive": False, "violation": None}
WHOLE = {"levels": [1, 6, 30], "exhaustive": True, "violation": None}


# --- golden_for -------------------------------------------------------------

@pytest.mark.parametrize("golden,max_depth,levels,violating", [
    (VIOLATING, None, [1, 4, 14, 44], True),    # the job a user runs
    (VIOLATING, 3, [1, 4, 14, 44], True),       # cut at the violation's depth
    (VIOLATING, 2, [1, 4, 14], False),          # cut above it: never met
    (VIOLATING, 9, [1, 4, 14, 44], True),       # the search ends before the cut
    (CUT, 2, [1, 10, 90], False),
    (WHOLE, None, [1, 6, 30], False),
    (WHOLE, 7, [1, 6, 30], False),              # past the diameter
])
def test_golden_for(golden, max_depth, levels, violating, harness):
    want = harness.golden_for(golden, max_depth)
    assert want["levels"] == levels
    assert want["total"] == sum(levels)
    assert want["diameter"] == len(levels) - 1
    assert want["violation"] == (golden["violation"] if violating else None)


@pytest.mark.parametrize("max_depth", [None, 4])
def test_golden_for_refuses_a_depth_the_golden_does_not_hold(max_depth, harness):
    with pytest.raises(SystemExit):
        harness.golden_for(CUT, max_depth)


# --- pass_options -----------------------------------------------------------

@pytest.mark.parametrize("config_depth,job,override,want", [
    (10, {}, None, 10),
    (10, {}, 4, 4),                             # a rehearsal cuts a cut job
    (3, {}, 4, 3),
    (None, {}, None, None),                     # uncut ...
    (None, {}, 4, None),                        # ... in a rehearsal too
    (10, {"max_depth": 2}, 4, 2),               # the job's own depth
    (None, {"max_depth": 6}, 4, 4),
])
def test_pass_options_depth(config_depth, job, override, want, harness):
    config = {"options": {"pipeline": "device"}, "max_depth": config_depth}
    traffic = {"options": {"store_trace": False}}
    opts = harness.pass_options(config, traffic, {"options": job}, override)
    assert opts["max_depth"] == want
    assert opts["pipeline"] == "device" and opts["store_trace"] is False


# --- judge_pass -------------------------------------------------------------

def _rec(levels, violation=None, events=(), compile_spans=0,
         backend_compiles=0, fallback=None, degradations=None):
    return {"levels": levels, "total": sum(levels),
            "diameter": len(levels) - 1, "violation": violation,
            "spans": {"events": list(events),
                      "spans": [["compile", 1.0, 0.1, None]] * compile_spans},
            "manifest": {"result": {"device": {"fallback": fallback}}},
            "stats": {"degradations": degradations},
            "jax": {"backend_compiles": backend_compiles}}


def _kinds(harness, rec, golden, max_depth=None):
    return sorted({kind for kind, _text in harness.judge_pass(
        rec, harness.golden_for(golden, max_depth))})


FOUND = {"invariant": "WeakIsr", "depth": 3, "trace_len": 4,
         "rendered_chars": 900}


@pytest.mark.parametrize("rec,golden,max_depth,kinds", [
    (_rec([1, 4, 14, 44], FOUND), VIOLATING, None, []),
    (_rec([1, 4, 14]), VIOLATING, 2, []),       # cut above: nothing owed
    (_rec([1, 4, 14, 44]), VIOLATING, None, ["answer"]),   # missed it
    (_rec([1, 4, 14], FOUND), VIOLATING, 2, ["answer"]),   # found one not owed
    (_rec([1, 4, 14, 44], dict(FOUND, invariant="StrongIsr")), VIOLATING,
     None, ["answer"]),                          # another invariant
    (_rec([1, 4, 14, 44], dict(FOUND, trace_len=5)), VIOLATING, None,
     ["answer"]),
    (_rec([1, 10, 90]), CUT, 2, []),
    (_rec([1, 10, 89]), CUT, 2, ["answer"]),    # one state short
    (_rec([1, 10, 90, 770]), CUT, 2, ["answer"]),
    (_rec([1, 10, 90], events=["retry"]), CUT, 2, ["degraded"]),
    (_rec([1, 10, 90], fallback="host"), CUT, 2, ["degraded"]),
    (_rec([1, 10, 90], degradations=["chunk"]), CUT, 2, ["degraded"]),
    (_rec([1, 10, 90], compile_spans=1), CUT, 2, ["compiled"]),
    # whatever the engine: a backend compile or cache load fails the pass
    (_rec([1, 10, 90], backend_compiles=2), CUT, 2, ["compiled"]),
])
def test_judge_pass(rec, golden, max_depth, kinds, harness):
    assert _kinds(harness, rec, golden, max_depth) == kinds


# --- oracle_prefix ----------------------------------------------------------

class _Action:
    def __init__(self, step):
        self.successors = step


class _Oracle:
    """States 0..; n -> 2n+1, 2n+2 below `size`: levels 1, 2, 4, ..."""

    constraint = None

    def __init__(self, size, bad=None, name="WeakIsr"):
        self.actions = [_Action(lambda s: [t for t in (2 * s + 1, 2 * s + 2)
                                           if t < size])]
        self.invariants = [(name, lambda s: s != bad)]

    def init_states(self):
        return [0]


class _Job:
    def __init__(self, model):
        self._model = model

    def oracle_model(self):
        return self._model


TREE = {"levels": [1, 2, 4, 8], "exhaustive": True, "violation": None}
TREE_CEX = {"levels": [1, 2, 4], "exhaustive": False,
            "violation": {"invariant": "WeakIsr", "depth": 2}}


@pytest.mark.parametrize("model,golden,ok,violation", [
    (_Oracle(15), TREE, True, None),
    (_Oracle(14), TREE, False, None),                       # a state short
    (_Oracle(15, bad=5), TREE_CEX, True, "WeakIsr"),
    (_Oracle(15, bad=5, name="StrongIsr"), TREE_CEX, False, "StrongIsr"),
    (_Oracle(15, bad=2), TREE_CEX, False, "WeakIsr"),       # a level early
    (_Oracle(15, bad=5), TREE, False, "WeakIsr"),           # golden has none
])
def test_oracle_prefix_holds_the_verdict_to_the_golden(model, golden, ok,
                                                       violation, harness):
    got = harness.oracle_prefix(_Job(model), golden, 60.0)
    assert got["ok"] is ok and got["violation"] == violation


def test_oracle_prefix_may_stop_short_of_the_violation(harness):
    got = harness.oracle_prefix(_Job(_Oracle(15, bad=5)), TREE_CEX, 60.0,
                                max_depth=1)
    assert got == {"levels": [1, 2], "ok": True, "violation": None}


# --- the window's rate and the readers ---------------------------------------

class _Bench:
    bench = {"end_to_end": [{"name": n, "unit": u} for n, u in (
        ("states_per_s", "states/s"), ("verdict_s", "s"), ("setup_s", "s"))]}


@pytest.mark.parametrize("walls,window_s,rate,verdict", [
    ([4.0] * 9, 36.0, 250.0, 4.0),
    # one pass of nine stalls: the median pass does not move, the rate does
    ([4.0] * 4 + [8.0] + [4.0] * 4, 40.0, 225.0, 4.0),
    # the seconds between passes are the window's too
    ([4.0] * 9, 37.5, 240.0, 4.0),
])
def test_the_rate_is_all_states_over_all_seconds(walls, window_s, rate,
                                                 verdict, harness):
    passes = [{"total": 1000, "wall_s": w} for w in walls]
    got = harness.Cell.end_to_end_metrics(_Bench, passes, {"setup_s": 50.0},
                                          window_s)
    assert got["states_per_s"] == {"value": rate, "unit": "states/s"}
    assert got["verdict_s"]["value"] == verdict
    assert got["setup_s"]["value"] == 50.0


def _level(step, host, level, new):
    return {"step_ms": step, "host_ms": host, "level_ms": level, "new": new}


ONE_CHUNK = [_level(31.0, 6.5, 38.3, 5), _level(349.3, 6.6, 356.7, 10170)]
# chip records of levels 12 and 13 at 3 brokers: host_ms holds device time
MULTI_CHUNK = [_level(45.6, 72.1, 457.5, 13062), _level(64.4, 329.3, 759.6,
                                                        12741)]


@pytest.mark.parametrize("records,host,step", [
    (ONE_CHUNK, 100 * 13.1 / 395.0, 1000 * 380.3 / 10175),
    (ONE_CHUNK + MULTI_CHUNK, 100 * 13.1 / 395.0, 1000 * 380.3 / 10175),
    (MULTI_CHUNK, None, None),      # nothing to read: no number, never 0
    ([{"level_ms": 5.0, "new": 1}], None, None),
])
def test_host_and_step_read_the_levels_their_fields_account_for(
        records, host, step, harness):
    readers = harness.load_metric_readers()
    ctx = {"passes": [{"level_records": records, "total": 1}]}
    for name, want in (("host_share", host), ("step_us_per_state", step)):
        got = readers[name].read(ctx)
        assert got == pytest.approx(want) if want is not None else got is None


# --- whole runs on the CPU --------------------------------------------------

_DRIVER = """
import sys
sys.path.insert(0, {perfbench!r})
import run as harness
import adapter
{patch}
sys.exit(harness.main(["--workload", "kip320-3b-notrace", "--seed",
                       {seed!r}, "--trace", "0", "--rehearse"]))
"""

# the timed path broken underneath: once set-up has passed, the engine's
# answer loses one state of its last level where it is produced
_BROKEN = """
real = adapter.Job.run_pass
def run_pass(self, run_dir, options):
    rec = real(self, run_dir, options)
    if "/pass" in run_dir:
        rec["levels"][-1] -= 1
        rec["total"] -= 1
    return rec
adapter.Job.run_pass = run_pass
"""


def _rehearse(patch, seed="2147483777"):
    p = subprocess.run(
        [sys.executable, "-c", _DRIVER.format(
            perfbench=PERFBENCH, patch=patch, seed=seed)],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1])


def test_a_sound_run_reads_correct():
    rc, last = _rehearse("")
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert last["compared"]["passes_off_golden"] == {"value": 0, "limit": 0}
    assert last["compared"]["window_passes"]["value"] >= 3


def test_a_broken_timed_path_reads_not_correct():
    rc, last = _rehearse(_BROKEN)
    assert rc != 0 and last["correct"] is False
    assert last["failed"] == last["attempted"] >= 3
    assert last["compared"]["passes_off_golden"]["value"] >= 3
    assert last["compared"]["setup_problems"]["value"] == 0


# --- the control ------------------------------------------------------------

@pytest.mark.parametrize("seed", [3, 2147483651, 2147483777])
def test_the_control_is_not_correct(seed, harness):
    """Exact 64-bit-fingerprint counts are the guarantee every configuration
    states.  The plain reference with that guarantee broken: two states are
    one where 16 bits of a salted checksum agree (what a narrower
    fingerprint would do).  It loses states by depth 8 of the 3-broker job
    (20,935 states in 65,536 slots) on every seed, and the comparison that
    decides `correct` (every level against the golden, limit 0) refuses it;
    the exact reference passes it."""
    import adapter

    config = harness.load_json(
        os.path.join(PERFBENCH, "configs", "kip320-3b.json"))
    golden = harness.load_json(
        os.path.join(PERFBENCH, "golden", "kip320-3b.json"))
    job = adapter.Job(config, ROOT)
    want = harness.golden_for(golden, 8)
    exact = harness.oracle_prefix(job, golden, float("inf"), max_depth=8)
    assert exact["ok"]
    assert harness.judge_pass(_rec(exact["levels"]), want) == []
    control = harness.oracle_prefix(
        job, golden, float("inf"), max_depth=8,
        key=lambda s: zlib.crc32(repr((seed, s)).encode()) & 0xFFFF)
    assert not control["ok"]
    why = harness.judge_pass(_rec(control["levels"]), want)
    assert why and all(kind == "answer" for kind, _ in why)
    assert sum(control["levels"]) < want["total"]
