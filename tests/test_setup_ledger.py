"""`setup_s` gets its parts (ISSUE 49): the `compile` span says what a
first call was made of, and the process ledger (`obs/ledger.py`) carries
start, model, programs, helpers, `rewarm` and the checks into every result,
the manifest and `cli report`.

CPU, toy models, seconds.  The ledger is ONE object a process and a tier-1
worker runs hundreds of tests in one process, so every case here asserts on
the ledger's GROWTH across its own calls, never on an absolute value.
"""

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring
from jax.sharding import Mesh

from kafka_specification_tpu.engine.bfs import (
    _CompileOnFirstCall, check, prepare)
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs import (
    RunContext, read_jsonl_tolerant, render_report)
from kafka_specification_tpu.obs import ledger as ledger_mod
from kafka_specification_tpu.obs.ledger import PROCESS, ProcessLedger
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.service.kernel_cache import KernelCache
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

PARTS = ("trace_ms", "lower_ms", "backend_ms", "cache", "retrieval_ms",
         "rest_ms")
BUILD = ("trace_s", "lower_s", "backend_s", "cache_hits", "cache_misses",
         "retrieval_s", "built")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
ID_CFG = """
SPECIFICATION Spec
CONSTANTS
    MaxId = 5
INVARIANTS TypeOk
CHECK_DEADLOCK FALSE
"""


@pytest.fixture(scope="module")
def events():
    """[unix, name, seconds or None] of every event JAX reports while this
    file runs, which starts from empty JAX caches, whatever its worker ran
    before it: an eager primitive first called under an eager
    transformation stays off jit's fast path for the life of the process
    and reports a "trace" at every later call (`tests/test_sharded_warm.py`
    `events` has the story), and a warm check here must fire nothing."""
    from jax._src import monitoring as m

    jax.clear_caches()
    seen = []

    def on_duration(name, secs, **kw):
        seen.append([time.time(), name, secs])

    def on_event(name, **kw):
        seen.append([time.time(), name, None])

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    yield seen
    m.unregister_event_duration_listener(on_duration)
    m.unregister_event_listener(on_event)


@pytest.fixture
def cache_off():
    """JAX's persistent compilation cache off around one test."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _grown(before: dict, after: dict) -> dict:
    """What moved between two snapshots, numbers as differences."""
    out = {}
    for k, v in after.items():
        was = before.get(k)
        if isinstance(v, dict):
            sub = _grown(was or {}, v)
            if sub:
                out[k] = sub
        elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                and isinstance(was, (int, float, type(None))):
            if v != (was or 0):
                out[k] = v - (was or 0)
        elif v != was:
            out[k] = v
    return out


def _compile_spans(run_dir):
    return [r for r in read_jsonl_tolerant(os.path.join(run_dir, "spans.jsonl"))
            if r.get("span") == "compile" and r.get("ph") == "E"]


def _cold_check(tmp_path, name, **kw):
    """A fresh toy model (so a fresh step cache) checked once under a run
    context -> (model, prepared, result, compile spans, ledger growth)."""
    model = frl.make_model(3, 4, 1)
    pk = prepare(model)
    before = PROCESS.snapshot()
    run_dir = str(tmp_path / name)
    res = check(model, min_bucket=64, prepared=pk, run=RunContext(run_dir),
                **kw)
    assert res.ok and res.total == 125
    return model, pk, res, _compile_spans(run_dir), _grown(
        before, res.stats["process"])


# --- the compile span ---------------------------------------------------------

def _cache_says(events, span):
    """`hit` / `miss` / `off` by the cache events JAX fired inside `span`."""
    t0, t1 = span["t0"], span["t0"] + span["ms"] / 1e3
    inside = [name for t, name, _ in events if t0 <= t <= t1 + 1e-4]
    return "miss" if MISS in inside else "hit" if HIT in inside else "off"


@pytest.mark.parametrize("cache", ["off", "suite"])
def test_a_compile_span_says_what_it_is_made_of(cache, events, tmp_path,
                                                request):
    if cache == "off":
        request.getfixturevalue("cache_off")
    _, _, _, spans, grown = _cold_check(tmp_path, cache)
    assert len(spans) >= 3  # init, hinv and the level step at least
    for s in spans:
        assert all(k in s for k in PARTS), s
        built = s["trace_ms"] + s["lower_ms"] + s["backend_ms"]
        assert 0 < built <= s["ms"] + 0.01
        assert built + s["rest_ms"] == pytest.approx(s["ms"], abs=0.01)
        assert s["retrieval_ms"] <= s["backend_ms"] + 0.01
        assert s["cache"] in ("hit", "miss", "off")
        assert s["cache"] == _cache_says(events, s)
        assert s["cache"] == "off" or cache == "suite"
    # the ledger booked exactly these first calls
    programs = grown["programs"]
    assert programs["built"] == len(spans)
    assert programs["call_s"] == pytest.approx(
        sum(s["ms"] for s in spans) / 1e3, abs=1e-4)
    for part in ("trace", "lower", "backend"):
        assert programs[part + "_s"] == pytest.approx(
            sum(s[part + "_ms"] for s in spans) / 1e3, abs=1e-4)
    assert programs["trace_s"] <= programs["call_s"]
    slowest = PROCESS.snapshot()["programs"]["slowest"]
    assert 1 <= len(slowest) <= ledger_mod.SLOWEST
    assert all(set(PARTS) | {"ms", "during"} <= set(c) for c in slowest)
    assert [c["ms"] for c in slowest] == sorted(
        (c["ms"] for c in slowest), reverse=True)


def test_a_nested_trace_is_counted_once(events):
    """A program of jitted functions inside jitted functions: JAX fires a
    trace event for each, the outer one's seconds holding the inner ones'.
    The raw sum counts that time more than once; the span and the ledger
    hold the outermost trace."""
    @jax.jit
    def inner(x):
        for _ in range(8):
            x = jnp.sort(x) * 2 + jnp.cumsum(x)
        return x

    @jax.jit
    def middle(x):
        return inner(x) + inner(x + 1)

    def outer(x):
        return middle(x) - middle(x * 3)

    cache = {}
    entry = cache["k"] = _CompileOnFirstCall(
        jax.jit(outer), cache, "k", program="nest")
    before, mark = PROCESS.snapshot(), len(events)
    out = entry(jnp.arange(64, dtype=jnp.int32))
    jax.block_until_ready(out)
    grown = _grown(before, PROCESS.snapshot())["programs"]
    raw = [secs for _, name, secs in events[mark:] if name == TRACE]
    assert len(raw) >= 3  # outer, middle, inner at least
    assert grown["built"] == 1
    assert sum(raw) > grown["trace_s"] * 1.2  # nested time, counted twice
    assert grown["trace_s"] == pytest.approx(max(raw), rel=0.02)
    assert grown["trace_s"] <= grown["call_s"]
    # the wrapper has stepped aside for the bare jitted function
    assert cache["k"] is entry.fn


@pytest.mark.parametrize("arrivals,want", [
    # (name, seconds, arrival): an inner trace, then the outer one over it
    ([(TRACE, 1.0, 10.0), (TRACE, 3.0, 11.0)], {"trace_s": 3.0}),
    # two traces side by side, neither inside the other
    ([(TRACE, 1.0, 10.0), (TRACE, 1.0, 11.5)], {"trace_s": 2.0}),
    # inner, inner, their parent, then a sibling of the parent
    ([(TRACE, 0.5, 10.0), (TRACE, 0.5, 10.6), (TRACE, 2.0, 11.0),
      (TRACE, 1.0, 12.5)], {"trace_s": 3.0}),
    # a trace a lowering rule made lies inside the lowering, whose it is
    ([(TRACE, 2.0, 10.0), (TRACE, 0.25, 10.5), (LOWER, 1.0, 11.0),
      (BACKEND, 4.0, 15.0)],
     {"trace_s": 2.0, "lower_s": 1.0, "backend_s": 4.0}),
])
def test_outermost_intervals(arrivals, want, monkeypatch):
    led = ProcessLedger()
    for name, secs, arrival in arrivals:
        monkeypatch.setattr(ledger_mod, "now", lambda a=arrival: a)
        led.on_duration(name, secs, fun_name="f")
    monkeypatch.undo()
    got = led.snapshot()["helpers"]
    for key in ("trace_s", "lower_s", "backend_s"):
        assert got[key] == pytest.approx(want.get(key, 0.0))
    assert got["built"] == sum(n == BACKEND for n, _, _ in arrivals)
    assert got["by_name"] == ({"f": got["built"]} if got["built"] else {})


# --- the ledger across calls ---------------------------------------------------

def test_a_warm_check_moves_nothing_but_checks(events, tmp_path):
    model, pk, res, spans, cold = _cold_check(tmp_path, "cold")
    assert spans and cold["programs"]["built"] == len(spans)
    assert cold["checks"]["calls"] == 1
    run_dir = str(tmp_path / "warm")
    warm = check(model, min_bucket=64, prepared=pk,
                 run=RunContext(run_dir))
    assert _compile_spans(run_dir) == []
    grown = _grown(res.stats["process"], warm.stats["process"])
    assert set(grown) == {"checks"}
    assert grown["checks"]["calls"] == 1
    assert grown["checks"]["s"] == pytest.approx(
        warm.stats["process"]["checks"]["last_s"], abs=1e-5)
    # a reader takes this call's own seconds out again
    p = warm.stats["process"]["checks"]
    assert p["s"] - p["last_s"] == pytest.approx(
        res.stats["process"]["checks"]["s"], abs=1e-5)


@pytest.fixture
def own_ledger(monkeypatch):
    """A ledger of this test's own in the single-device engine's place,
    fed by JAX beside the process's: `slowest` keeps the eight longest
    first calls of a PROCESS, which a tier-1 worker's earlier tests hold."""
    from jax._src import monitoring as m

    from kafka_specification_tpu.engine import bfs

    led = ProcessLedger()
    led._installed = True  # registered here, so that it can be taken out
    monitoring.register_event_duration_secs_listener(led.on_duration)
    monitoring.register_event_listener(led.on_event)
    monkeypatch.setattr(bfs, "_LEDGER", led)
    yield led
    m.unregister_event_duration_listener(led.on_duration)
    m.unregister_event_listener(led.on_event)


def test_rewarm_with_no_run_open_moves_the_ledger(own_ledger):
    """`rewarm` runs between two jobs with no run context active: the
    programs it builds leave no span, and the ledger is their record."""
    from kafka_specification_tpu.obs import tracer

    model = variants.make_model(
        "Kip101", Config(2, 2, 1, 1), invariants=("TypeOk", "WeakIsr"))
    pk = prepare(model)
    res = check(model, min_bucket=32, pipeline="fused", prepared=pk,
                store_trace=False)
    pk.note_result(res)
    # a growth run's fixed point: one doubling above what the run ended at
    pk.capacity_hint = int(res.stats["visited_capacity"]) * 2
    assert tracer.current_tracer() is None
    before = own_ledger.snapshot()
    assert all(c["during"] == "check" for c in before["programs"]["slowest"])
    built = pk.rewarm()
    after = own_ledger.snapshot()
    grown = _grown(before, after)
    assert built > 0
    assert grown["rewarm"] == {"calls": 1, "s": grown["rewarm"]["s"],
                               "built": built}
    assert grown["programs"]["built"] >= built
    assert 0 < grown["programs"]["call_s"] <= grown["rewarm"]["s"]
    marked = [c for c in after["programs"]["slowest"]
              if c["during"] == "rewarm"]
    assert marked and all(c["vcap"] == pk.capacity_hint for c in marked)
    # and nothing more to build: a second call books a call and no program
    assert pk.rewarm() == 0
    again = _grown(after, own_ledger.snapshot())
    assert set(again) == {"rewarm"} and set(again["rewarm"]) == {"calls", "s"}


def test_two_threads_book_their_own_events():
    """Two first calls open at once on two threads (the daemon's workers):
    an event belongs to the call open on the thread it arrives on."""
    led = ProcessLedger()
    led._installed = True  # fed by hand: no listener of this one in JAX
    opened = threading.Barrier(2, timeout=30)
    parts, errors = {}, []

    def worker(name, secs):
        try:
            with led.first_call(program=name) as call:
                opened.wait()
                led.on_duration(TRACE, secs, fun_name=name)
                led.on_event(HIT if name == "a" else MISS)
                opened.wait()  # both have fired before either closes
                parts[name] = call.done()
        except Exception as e:  # noqa: BLE001 — read on the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=a)
                   for a in (("a", 1.0), ("b", 2.0))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert parts["a"]["trace_ms"] == 1000.0 and parts["a"]["cache"] == "hit"
    assert parts["b"]["trace_ms"] == 2000.0 and parts["b"]["cache"] == "miss"
    got = led.snapshot()
    assert got["programs"]["built"] == 2
    assert got["programs"]["trace_s"] == pytest.approx(3.0)
    assert (got["programs"]["cache_hits"], got["programs"]["cache_misses"]) \
        == (1, 1)
    assert got["helpers"]["trace_s"] == 0  # none was open on no thread


def test_two_threads_building_at_once(cache_off):
    """The same with JAX itself firing: two programs built at once."""
    def program(k):
        def f(x):
            for i in range(k):
                x = jnp.sort(x + i) * 2
            return x
        f.__name__ = f"built_at_once_{k}"
        return jax.jit(f)

    before = PROCESS.snapshot()
    opened = threading.Barrier(2, timeout=60)
    parts, errors = {}, []

    def worker(k):
        try:
            fn = program(k)
            with PROCESS.first_call(program=f"p{k}") as call:
                opened.wait()
                jax.block_until_ready(fn(jnp.arange(128, dtype=jnp.int32)))
                parts[k] = (call.done(), call.t1 - call.t0)
        except Exception as e:  # noqa: BLE001 — read on the main thread
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in (2, 40)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    for p, call_s in parts.values():
        built = p["trace_ms"] + p["lower_ms"] + p["backend_ms"]
        assert 0 < built <= call_s * 1e3 + 0.01  # none of the other's
        assert p["trace_ms"] > 0 and p["backend_ms"] > 0
    assert parts[40][0]["trace_ms"] > parts[2][0]["trace_ms"]
    grown = _grown(before, PROCESS.snapshot())
    assert grown["programs"]["built"] == 2


def test_an_event_with_no_call_open_is_a_helpers(cache_off):
    def helper(x):
        return jnp.cumsum(x) + 7

    helper.__name__ = "a_helper_of_the_ledger_test"
    x = jnp.arange(96)  # (its own helper is built before the reading)
    before = PROCESS.snapshot()
    jax.block_until_ready(jax.jit(helper)(x))
    grown = _grown(before, PROCESS.snapshot())
    assert set(grown) == {"helpers"}
    h = grown["helpers"]
    assert h["built"] == 1 and h["trace_s"] > 0 and h["backend_s"] > 0
    assert "cache_hits" not in h and "cache_misses" not in h  # cache off


# --- models, marks, and where the snapshot goes --------------------------------

def test_build_model_and_the_kernel_cache_book_one_build_each(tmp_path):
    cfg_path = tmp_path / "Id.cfg"
    cfg_path.write_text(ID_CFG)
    cfg = parse_cfg(str(cfg_path))
    before = PROCESS.snapshot()
    build_model("IdSequence", cfg)
    grown = _grown(before, PROCESS.snapshot())
    assert grown["models"] == 1 and grown["model_s"] > 0
    # a kernel-cache miss is build_model + prepare, nested: ONE build
    cache = KernelCache()
    before = PROCESS.snapshot()
    entry = cache.get("IdSequence", cfg, False, ("TypeOk",))
    grown = _grown(before, PROCESS.snapshot())
    assert not entry["hit"] and grown["models"] == 1
    assert grown["model_s"] == pytest.approx(entry["build_s"], abs=0.05)
    before = PROCESS.snapshot()
    assert cache.get("IdSequence", cfg, False, ("TypeOk",))["hit"]
    assert "models" not in _grown(before, PROCESS.snapshot())


@pytest.mark.parametrize("engine", ["bfs", "sharded"])
def test_the_snapshot_is_json_and_reaches_the_manifest(engine, tmp_path):
    model = frl.make_model(2, 2, 2)
    run_dir = str(tmp_path / engine)
    before = PROCESS.snapshot()
    if engine == "bfs":
        res = check(model, min_bucket=32, run=RunContext(run_dir))
    else:
        mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
        res = check_sharded(model, mesh=mesh, min_bucket=32,
                            run=RunContext(run_dir))
    p = res.stats["process"]
    assert json.loads(json.dumps(p)) == p
    assert set(p) == {"start_unix", "jax_unix", "backend_ready_unix",
                      "model_s", "models", "programs", "helpers", "rewarm",
                      "checks"}
    assert set(BUILD) | {"call_s", "slowest"} == set(p["programs"])
    assert set(BUILD) | {"by_name"} == set(p["helpers"])
    assert p["start_unix"] <= p["jax_unix"] <= p["backend_ready_unix"]
    assert _grown(before, p)["checks"]["calls"] == 1
    with open(os.path.join(run_dir, "manifest.json")) as fh:
        manifest = json.load(fh)
    assert manifest["result"]["process"] == p
    # with no run context the result carries it all the same
    bare = check(model, min_bucket=32) if engine == "bfs" else check_sharded(
        model, mesh=mesh, min_bucket=32)
    assert bare.stats["process"]["checks"]["calls"] == p["checks"]["calls"] + 1


def test_cli_report_prints_the_set_up_table(tmp_path):
    _, _, res, spans, _ = _cold_check(tmp_path, "run")
    report = render_report(str(tmp_path / "run"))
    assert "Set-up of the process" in report
    for line in ("start", "model", "trace", "lower", "backend", "rewarm",
                 "checks"):
        assert any(row.startswith("  " + line + " ")
                   for row in report.splitlines()), line
    assert "hits" in report and "misses" in report
    assert "slowest first calls:" in report
    slowest = res.stats["process"]["programs"]["slowest"]
    assert spans and slowest  # (the process's eight longest, not this run's)
    for c in slowest:
        assert f"{c['ms']:.1f} ms = trace {c['trace_ms']:.1f}" in report
        assert f"[{c['during']}]" in report
    # the verdict line does not repeat the ledger
    assert '"process"' not in report
    # a manifest without the record (a run from before it) prints no table
    path = tmp_path / "run" / "manifest.json"
    manifest = json.loads(path.read_text())
    del manifest["result"]["process"]
    path.write_text(json.dumps(manifest))
    assert "Set-up of the process" not in render_report(str(tmp_path / "run"))
