"""The order in which the fused chunk loop dispatches and fetches (ISSUE 42;
docs/engine.md § Async execution), read from a run's `dispatch` spans and
from `HostIO` on the CPU.

In a level of n >= 2 fused chunks with overlap on, chunk k+1's guard launch
goes out before chunk k's successor launch, chunk k-1's rows are cut
(`HostIO.head`) before chunk k's successor launch is queued, and the level's
record counts n - 1 `chunks_ahead`; overlap off, a one-chunk level and a
chunk below the compact gate read 0.  A fault in a stage that ran ahead is
handled at the chunk's own turn, on `run_chunk_staged`'s one ladder.

`configs/Kip101.cfg` in chunks of 256 rows: levels 7-11 stream 2 / 2 / 3 / 4
/ 6 chunks, and the verdict (WeakIsr at depth 11) lies in the fourth of the
next level's six."""

import functools

import pytest

from kafka_specification_tpu.engine import hostio, pipeline
from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.obs import RunContext, read_jsonl_tolerant
from kafka_specification_tpu.resilience import faults
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

LEVELS = [1, 4, 14, 44, 100, 166, 268, 456, 684, 976, 1292, 1486]
CHUNKS = [1, 1, 1, 1, 1, 1, 2, 2, 3, 4, 6]  # the eleven committed levels
# the fused path from 64 rows up, in chunks of 256
FUSED = dict(min_bucket=64, compact_gate=64, chunk_size=256)
EXACT = ("frontier", "enabled_candidates", "new", "duplicates", "chunks",
         "successor_launches", "dedup_lanes", "dispatches",
         "discarded_dispatches", "d2h_fetches", "d2h_bytes", "h2d_puts",
         "h2d_bytes", "probe_lanes", "merge_slots", "novel_rows")


@functools.lru_cache(maxsize=None)
def _kip101():
    """One model object: its step cache serves every case."""
    cfg = parse_cfg("configs/Kip101.cfg")
    return build_model("Kip101", cfg), dict(
        check_deadlock=cfg.check_deadlock)


def _run(tmp_path, name="run", **over):
    model, kw = _kip101()
    res = check(model, run=RunContext(str(tmp_path / name)),
                **{**FUSED, **kw, **over})
    spans = [r for r in read_jsonl_tolerant(
        str(tmp_path / name / "spans.jsonl"))
        if r.get("kind") == "span" and r.get("ph") == "E"]
    return res, spans


def _verdict(res):
    v = res.violation
    return (res.levels, res.total, v.invariant, v.depth,
            [(n, repr(s)) for n, s in v.trace])


def _programs_by_start(spans, depth):
    """The level's `dispatch` spans in the order they OPENED."""
    return [s["program"] for s in sorted(
        (s for s in spans if s["span"] == "dispatch"
         and s.get("depth") == depth), key=lambda s: s["t0"])]


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    base = tmp_path_factory.mktemp("schedule")
    return _run(base, "on", overlap=True), _run(base, "off", overlap=False)


def test_guard_launch_opens_before_the_previous_successor_launch(pair):
    (res, spans), (_, serial) = pair
    assert [lv["chunks"] for lv in res.stats["levels"]] == CHUNKS
    for depth, n in enumerate(CHUNKS):  # the spans' depth: the frontier's
        ahead = ["fgd"] + ["fgd", "fsc"] * (n - 1) + ["fsc"]
        assert _programs_by_start(spans, depth) == ahead, depth
        # overlap off is the serial order: a chunk's two launches,
        # then its commit
        assert _programs_by_start(serial, depth) == ["fgd", "fsc"] * n
    steps = [s for s in spans if s["span"] == "step"]
    assert [bool(s.get("ahead")) for s in steps if s["depth"] == 10] == [
        False] + [True] * 5
    assert not any(s.get("ahead") for s in serial if s["span"] == "step")


def test_chunks_ahead_counts_the_hidden_boundaries(pair):
    (res, _), (off, _) = pair
    assert [lv["chunks_ahead"] for lv in res.stats["levels"]] == [
        n - 1 for n in CHUNKS]
    assert [lv["chunks_ahead"] for lv in off.stats["levels"]] == [0] * 11
    # the cut level: six chunks, the verdict in the fourth; of the four
    # committed, three went out ahead.  The verdict flags are read with the
    # fourth chunk's counts, BEFORE the fifth's successor launch is queued:
    # the fifth's guard stage, run ahead, is all that is dropped, its launch
    # long read, so nothing in flight is discarded
    cut = res.stats["cut_level"]
    assert cut["chunks_ahead"] == 3
    assert (cut["chunks_committed"], cut["chunks_discarded"],
            cut["chunks"], cut["discarded_dispatches"]) == (4, 1, 5, 0)
    assert cut["dispatches"] == 2 * 4 + 1
    assert off.stats["cut_level"]["chunks_ahead"] == 0
    # the verdict's chunk is read exactly as in serial order (never sliced;
    # `new_n` fetched as a count on both sides since PR 47); the two fetches
    # more are the dropped guard stage's counts and matrix
    assert cut["d2h_fetches"] == off.stats["cut_level"]["d2h_fetches"] + 2
    assert res.stats["overlap"]["staged_chunks_peak"] == 2
    assert res.stats["overlap"]["guard_ahead_peak"] == 1
    assert off.stats["overlap"]["guard_ahead_peak"] == 0
    # the same search, the same work, to the last counter of every
    # committed level
    assert _verdict(res) == _verdict(off)
    for a, b in zip(res.stats["levels"], off.stats["levels"]):
        assert [a[k] for k in EXACT] == [b[k] for k in EXACT], a["depth"]


def test_below_the_gate_nothing_is_ahead(tmp_path):
    """The default gate (4,096): every chunk of 256 rows is a legacy chunk,
    whose dispatch is complete when it returns."""
    res, spans = _run(tmp_path, overlap=True, compact_gate=4096)
    assert {s["program"] for s in spans if s["span"] == "dispatch"
            and "depth" in s} == {"step"}
    assert [lv["chunks"] for lv in res.stats["levels"]] == CHUNKS
    assert [lv["chunks_ahead"] for lv in res.stats["levels"]] == [0] * 11
    assert res.stats["overlap"]["guard_ahead_peak"] == 0
    assert res.levels == LEVELS


def test_rows_are_cut_before_the_next_successor_launch(tmp_path, monkeypatch):
    """`HostIO` says when: with overlap on the slices of chunk k-1 (rows,
    parents, action ids, and the two fingerprint lanes the digest chain
    folds) are enqueued before `fsc(k)` is dispatched, so the device runs
    them ahead of it; serially a chunk's slices follow its own launch."""
    log = []
    dispatch, head = hostio.HostIO.dispatch, hostio.HostIO.head

    def logged_dispatch(self, program, **attrs):
        log.append((program, attrs.get("depth")))
        return dispatch(self, program, **attrs)

    def logged_head(self, x, n):
        log.append(("head", None))
        return head(self, x, n)

    monkeypatch.setattr(hostio.HostIO, "dispatch", logged_dispatch)
    monkeypatch.setattr(hostio.HostIO, "head", logged_head)
    for overlap in (True, False):
        del log[:]
        _run(tmp_path, f"ov{overlap}", overlap=overlap)
        # level 11 (frontier depth 10): six chunks, every one with new rows
        lo = log.index(("fgd", 10))
        hi = log.index(("fgd", 11))
        level = [e[0] for e in log[lo:hi]]
        assert level.count("fsc") == 6 and level.count("head") == 30
        seen, heads = 0, []
        for e in level:
            if e == "fsc":
                heads.append(seen)
            seen += e == "head"
        # (slices queued behind the launch after a chunk's own, the order
        # this schedule replaced, read [0, 0, 5, 10, 15, 20] with overlap on)
        assert heads == [5 * k for k in range(6)]
        if overlap:
            # ... and the guard launch of chunk k+1 after chunk k-1's cut
            assert level[:9] == ["fgd", "fgd", "fsc"] + ["head"] * 5 + ["fgd"]
        else:
            assert level[:9] == ["fgd", "fsc"] + ["head"] * 5 + ["fgd", "fsc"]


def _nth_attempt(n, marker):
    """A `FaultPlan.chunk_error` that answers `marker` on its `n`-th
    escalated call and nothing otherwise."""
    calls = []

    def chunk_error(plan, escalated):
        calls.append(escalated)
        if escalated and sum(calls) == n:
            return RuntimeError(marker)
        return None

    return chunk_error


# the eighth fused attempt is chunk 1 of level 7 (six one-chunk levels, then
# chunk 0): the first guard stage of the run that goes out ahead
FIRST_AHEAD = 8


def test_transient_fault_in_an_ahead_guard_stage_is_retried_in_order(
        pair, tmp_path, monkeypatch):
    (ref, _), _ = pair
    monkeypatch.setattr(faults.FaultPlan, "chunk_error",
                        _nth_attempt(FIRST_AHEAD, faults.TRANSIENT_MARKER))
    monkeypatch.setenv("KSPEC_RETRY_BASE_S", "0")
    res, spans = _run(tmp_path, overlap=True)
    assert _verdict(res) == _verdict(ref)
    assert res.stats["transient_retries"] == 1
    assert not res.stats["pipeline_fallback"]
    assert any(s.get("kind") == "retry" or s.get("event") == "retry"
               for s in read_jsonl_tolerant(
                   str(tmp_path / "run" / "spans.jsonl")))
    # the chunk was re-run in serial order: its committed guard launch went
    # out after the previous chunk's successor launch, every other chunk's
    # as before
    want = [n - 1 for n in CHUNKS]
    want[6] = 0
    assert [lv["chunks_ahead"] for lv in res.stats["levels"]] == want
    assert _programs_by_start(spans, 6) == ["fgd", "fsc", "fgd", "fsc"]
    for a, b in zip(res.stats["levels"], ref.stats["levels"]):
        assert [a[k] for k in EXACT[:7]] == [b[k] for k in EXACT[:7]]


@pytest.mark.parametrize("stage", ["guard", "compact"])
def test_fault_in_an_ahead_stage_degrades_to_legacy_with_the_same_counts(
        pair, tmp_path, monkeypatch, stage):
    (ref, _), _ = pair
    if stage == "guard":
        monkeypatch.setattr(faults.FaultPlan, "chunk_error",
                            _nth_attempt(FIRST_AHEAD, faults.OOM_MARKER))
    else:
        compact, calls = pipeline.FusedPipeline._compact, []

        def failing(self, ga, widths, depth):
            calls.append(depth)
            if len(calls) == FIRST_AHEAD:
                raise RuntimeError(faults.OOM_MARKER)
            return compact(self, ga, widths, depth)

        monkeypatch.setattr(pipeline.FusedPipeline, "_compact", failing)
    res, spans = _run(tmp_path, overlap=True)
    assert _verdict(res) == _verdict(ref)
    assert res.stats["pipeline_fallback"] is True
    assert any(d["kind"] == "compile_fallback"
               for d in res.stats["degradations"])
    keys = ("frontier", "enabled_candidates", "new", "duplicates", "chunks")
    for a, b in zip(res.stats["levels"], ref.stats["levels"]):
        assert [a[k] for k in keys] == [b[k] for k in keys]
    # sticky: from that chunk on every chunk is a legacy chunk, and nothing
    # is ahead of anything
    assert [lv["chunks_ahead"] for lv in res.stats["levels"]] == [0] * 11
    assert "fgd" not in _programs_by_start(spans, 7)
    # no guard launch is left open or booked twice: the injected fault fired
    # before the launch, and `_compact` runs after launch 1 was read
    assert not [s for s in spans if s["span"] == "dispatch"
                and s["program"] == "fgd" and s.get("discarded")]
    # the verdict is read before the chunk behind it is dispatched: a legacy
    # chunk has no stage that runs ahead, so nothing is dropped
    cut = res.stats["cut_level"]
    assert (cut["chunks_committed"], cut["chunks_discarded"]) == (4, 0)
