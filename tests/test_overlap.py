"""Async level-pipelined execution (KSPEC_OVERLAP; overlap.py,
docs/engine.md § Async execution).

Pins the PR 10 contract: overlap-on is BIT-IDENTICAL to overlap-off —
level counts, duplicate accounting, first-violation rule, trace values
and digest chains — across the model x backend x disk-tier x resume
matrix on both engines; the two-slot staging queue is structurally
bounded; background I/O actually overlaps device compute (span
evidence); faults firing on the worker threads (crash@merge, enospc@
ckpt, flip@spill) still produce the typed exits, a chain-verified
checkpoint, and bit-identical resume; the compressed exchange
round-trips exactly and stays inside the fabric-integrity boundary;
and a reclaim quiesces the merge worker before touching its files
(the PR 10 small fix).
"""

import os
import threading
import time

import numpy as np
import pytest
from jax.sharding import Mesh

import jax

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.obs.runctx import RunContext
from kafka_specification_tpu.obs.tracer import read_jsonl_tolerant
from kafka_specification_tpu.ops import fpcompress as fpc
from kafka_specification_tpu.overlap import AsyncWorker, overlap_enabled
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.resilience.checkpoints import (
    verify_checkpoint_dir,
)
from kafka_specification_tpu.resilience.faults import InjectedCrash
from kafka_specification_tpu.resilience.integrity import IntegrityError
from kafka_specification_tpu.resilience.resources import ResourceExhausted

pytestmark = pytest.mark.overlap

TINY = Config(n_replicas=2, log_size=2, max_records=1, max_leader_epoch=1)


def _mesh(n):
    return Mesh(np.array(jax.devices("cpu")[:n]), ("d",))


def _mk_violating():
    return variants.make_model(
        "KafkaTruncateToHighWatermark", TINY, ("TypeOk", "WeakIsr")
    )


def _verdict(res):
    return (
        res.total,
        res.diameter,
        tuple(res.levels),
        res.ok,
        (res.violation.invariant, res.violation.depth)
        if res.violation
        else None,
    )


def _trace_values(res):
    if res.violation is None:
        return None
    return [(name, repr(st)) for name, st in res.violation.trace]


# --- knob resolution ------------------------------------------------------


def test_overlap_knob_resolution(monkeypatch):
    monkeypatch.delenv("KSPEC_OVERLAP", raising=False)
    assert overlap_enabled(None) is True  # default ON
    assert overlap_enabled("off") is False
    assert overlap_enabled("on") is True
    assert overlap_enabled(False) is False
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    assert overlap_enabled(None) is False
    monkeypatch.setenv("KSPEC_OVERLAP", "on")
    assert overlap_enabled(None) is True


# --- the worker primitive -------------------------------------------------


def test_async_worker_runs_in_order_and_propagates_errors():
    w = AsyncWorker("t-worker")
    seen = []
    jobs = [w.submit(f"j{i}", lambda i=i: seen.append(i)) for i in range(5)]
    w.drain()
    assert seen == [0, 1, 2, 3, 4]

    def boom():
        raise OSError(28, "No space left on device (test)")

    w.submit("boom", boom)
    w.submit("after", lambda: seen.append(99))
    with pytest.raises(OSError):
        w.drain()
    assert seen[-1] == 99  # the failed job never blocks later jobs
    w.drain()  # error raised exactly once
    assert all(j.done.is_set() for j in jobs)
    w.close()


# --- compressed-exchange codec (satellite: round-trip unit) ---------------


def test_fpcompress_roundtrip_jit_matches_numpy():
    rng = np.random.default_rng(7)
    import jax.numpy as jnp

    for W, n in [(64, 0), (64, 17), (128, 1), (128, 60), (256, 100),
                 (512, 200)]:
        vals = np.sort(
            rng.integers(0, 2**64 - 2, size=n, dtype=np.uint64)
        )
        if n > 3:
            vals[2] = vals[1]  # duplicate fingerprints must survive
            vals = np.sort(vals)
        full = np.concatenate(
            [vals, np.full(W - n, np.uint64(0xFFFFFFFFFFFFFFFF))]
        )
        hi = (full >> np.uint64(32)).astype(np.uint32)
        lo = (full & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        NW = fpc.default_stream_words(W)
        words, hdr, ovf = jax.jit(
            lambda h, l, c: fpc.pack_sorted(h, l, c, NW)
        )(jnp.asarray(hi), jnp.asarray(lo), jnp.int32(n))
        words, hdr, ovf = np.asarray(words), np.asarray(hdr), bool(ovf)
        wn, hn, on = fpc.pack_np(hi, lo, n, NW)
        assert np.array_equal(words, wn) and np.array_equal(hdr, hn)
        assert ovf == on
        assert not ovf, (W, n)
        h2, l2 = jax.jit(lambda w, h: fpc.unpack_sorted(w, h, W))(
            jnp.asarray(words), jnp.asarray(hdr)
        )
        assert np.array_equal(np.asarray(h2), hi)
        assert np.array_equal(np.asarray(l2), lo)
        h3, l3 = fpc.unpack_np(words, hdr, W)
        assert np.array_equal(h3, hi) and np.array_equal(l3, lo)
        # the wire actually shrinks: stream+header vs raw hi/lo lanes
        assert fpc.packed_bytes(W, NW) < fpc.raw_bytes(W)


def test_fpcompress_overflow_flag_on_dense_bucket():
    rng = np.random.default_rng(3)
    W = 128
    vals = np.sort(rng.integers(0, 2**64 - 2, size=W, dtype=np.uint64))
    hi = (vals >> np.uint64(32)).astype(np.uint32)
    lo = (vals & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    _w, _h, ovf = fpc.pack_np(hi, lo, W, fpc.default_stream_words(W))
    assert ovf  # a full bucket of random fps cannot fit 1 word/slot


# --- bit-identity matrix (the tentpole contract) --------------------------


@pytest.mark.parametrize("backend", ["device", "device-hash", "host"])
def test_overlap_bit_identity_backends(monkeypatch, backend):
    mk = lambda: frl.make_model(2, 2, 2)  # noqa: E731
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    base = check(mk(), min_bucket=32, chunk_size=64,
                 visited_backend=backend)
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    on = check(mk(), min_bucket=32, chunk_size=64,
               visited_backend=backend)
    assert _verdict(on) == _verdict(base)
    assert on.stats["overlap"]["enabled"]
    assert not base.stats["overlap"]["enabled"]


def test_overlap_bit_identity_violation_trace(monkeypatch):
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    base = check(_mk_violating(), min_bucket=32, chunk_size=64)
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    on = check(_mk_violating(), min_bucket=32, chunk_size=64)
    assert not base.ok and _verdict(on) == _verdict(base)
    assert _trace_values(on) == _trace_values(base)


def test_overlap_bit_identity_disk_tier_and_chains(monkeypatch, tmp_path):
    """Forced-spill disk tier + checkpoints: counts AND the stamped
    digest chains must match across the knob."""
    import numpy.testing as npt

    from kafka_specification_tpu.resilience.checkpoints import verify_file

    chains = {}
    for flag, sub in (("0", "off"), ("1", "on")):
        monkeypatch.setenv("KSPEC_OVERLAP", flag)
        ck = str(tmp_path / f"ck-{sub}")
        res = check(
            frl.make_model(2, 2, 2),
            min_bucket=32,
            chunk_size=64,
            mem_budget=256,
            store="disk",
            checkpoint_dir=ck,
        )
        chains[sub] = (
            _verdict(res),
            verify_file(os.path.join(ck, "bfs_checkpoint.npz"))[
                "digest_chain"
            ],
        )
        assert verify_checkpoint_dir(ck)["ok"]
    assert chains["on"][0] == chains["off"][0]
    npt.assert_array_equal(chains["on"][1], chains["off"][1])


def test_overlap_resume_across_knob(monkeypatch, tmp_path):
    """A checkpoint written with overlap ON resumes bit-identically with
    overlap OFF (and vice versa) — the knob is execution strategy, not
    state."""
    mk = lambda: frl.make_model(2, 2, 2)  # noqa: E731
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    golden = check(mk(), min_bucket=32)
    for first, second in (("1", "0"), ("0", "1")):
        ck = str(tmp_path / f"ck-{first}{second}")
        monkeypatch.setenv("KSPEC_OVERLAP", first)
        check(mk(), min_bucket=32, checkpoint_dir=ck, max_depth=3)
        monkeypatch.setenv("KSPEC_OVERLAP", second)
        res = check(mk(), min_bucket=32, checkpoint_dir=ck)
        assert _verdict(res)[:3] == _verdict(golden)[:3]


def test_overlap_bit_identity_sharded_compressed(monkeypatch):
    """Sharded engine: overlap ON (staged commit + compressed exchange)
    vs OFF (raw exchange) — counts AND trace values identical, and the
    compressed wire moved >= 2x fewer bytes."""
    mk = _mk_violating
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    base = check_sharded(mk(), mesh=_mesh(4), min_bucket=64)
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    # the codec defaults off on the virtual CPU mesh (no wire to save);
    # force it on — measuring/pinning it IS the point here
    monkeypatch.setenv("KSPEC_EXCHANGE_COMPRESS", "1")
    on = check_sharded(mk(), mesh=_mesh(4), min_bucket=64)
    assert _verdict(on) == _verdict(base)
    assert _trace_values(on) == _trace_values(base)
    assert on.stats["exchange_compressed"]
    assert not base.stats["exchange_compressed"]
    sent = on.stats["exchange_bytes_total"]
    raw = on.stats["exchange_raw_bytes_total"]
    assert raw and sent and raw / sent >= 2.0, (sent, raw)


def test_overlap_bit_identity_sharded_host_backend(monkeypatch):
    mk = lambda: frl.make_model(2, 2, 2)  # noqa: E731
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    base = check_sharded(mk(), mesh=_mesh(2), min_bucket=64,
                         visited_backend="host")
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    on = check_sharded(mk(), mesh=_mesh(2), min_bucket=64,
                       visited_backend="host")
    assert _verdict(on) == _verdict(base)
    assert on.stats["overlap"]["staged_chunks_peak"] <= 2


# --- the fused chunk loop's schedule (ISSUE 42) ----------------------------

# the fused path from 32 rows up, in chunks of 32: frl(2,2,3)'s widest
# levels stream three chunks, each through the two fused launches
FUSED32 = dict(min_bucket=32, chunk_size=32, compact_gate=32)


def _level_counts(res):
    return [(lv["frontier"], lv["enabled_candidates"], lv["new"],
             lv["duplicates"], lv["chunks"]) for lv in res.stats["levels"]]


@pytest.mark.parametrize("backend", ["device", "device-hash", "host"])
def test_overlap_bit_identity_levels_of_three_fused_chunks(
        monkeypatch, tmp_path, backend):
    """A level of three or more FUSED chunks: with overlap on a chunk's
    guard launch goes out one chunk ahead and (sorted `device` backend)
    its rows are cut before the next successor launch is queued; level
    counts, duplicates and the stamped digest chains equal overlap
    off's."""
    import numpy.testing as npt

    from kafka_specification_tpu.resilience.checkpoints import verify_file

    seen = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("KSPEC_OVERLAP", flag)
        ck = str(tmp_path / f"ck-{flag}")
        res = check(frl.make_model(2, 2, 3), visited_backend=backend,
                    checkpoint_dir=ck, run=RunContext(str(tmp_path / flag)),
                    **FUSED32)
        assert res.stats["pipeline"] == "fused"
        assert not res.stats.get("degradations")
        seen[flag] = (
            _verdict(res), _level_counts(res),
            verify_file(os.path.join(ck, "bfs_checkpoint.npz"))[
                "digest_chain"],
            [lv["chunks_ahead"] for lv in res.stats["levels"]],
            res.stats["overlap"],
        )
    off, on = seen["0"], seen["1"]
    assert on[0] == off[0] and on[1] == off[1]
    npt.assert_array_equal(on[2], off[2])
    chunks = [c[4] for c in on[1]]
    assert max(chunks) >= 3
    # every chunk but a level's first went out ahead, whatever the backend
    assert on[3] == [n - 1 for n in chunks] and not any(off[3])
    assert (on[4]["staged_chunks_peak"], on[4]["guard_ahead_peak"]) == (2, 1)
    assert (off[4]["staged_chunks_peak"], off[4]["guard_ahead_peak"]) == (0, 0)


@pytest.mark.parametrize("backend", ["device", "host"])
def test_overlap_bit_identity_verdict_in_a_middle_chunk(
        monkeypatch, tmp_path, backend):
    """`configs/Kip101.cfg` in chunks of 256: the violating row lies in the
    fourth of level 12's six chunks (the first, on the `host` backend).
    With overlap on, on the `host` backend the next chunk's successor
    launch AND the guard launch of the one after it are out when the
    commit reads the verdict: both are dropped and booked `discarded`.  On the sorted `device` backend the
    verdict flags are read with the fourth chunk's counts, before the
    fifth's successor launch is queued: only the fifth's guard stage has
    run, and nothing in flight is discarded.  The first violation, the
    trace and every committed level equal overlap off's."""
    from kafka_specification_tpu.utils.cfg import build_model, parse_cfg

    cfg = parse_cfg("configs/Kip101.cfg")
    model = build_model("Kip101", cfg)
    seen = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("KSPEC_OVERLAP", flag)
        res = check(model, run=RunContext(str(tmp_path / flag)),
                    check_deadlock=cfg.check_deadlock, min_bucket=64,
                    compact_gate=64, chunk_size=256,
                    visited_backend=backend)
        dropped = sorted(
            r["program"] for r in read_jsonl_tolerant(
                str(tmp_path / flag / "spans.jsonl"))
            if r.get("span") == "dispatch" and r.get("discarded"))
        seen[flag] = (res, dropped)
    (off, off_dropped), (on, on_dropped) = seen["0"], seen["1"]
    assert not off.ok and _verdict(on) == _verdict(off)
    assert _trace_values(on) == _trace_values(off)
    assert _level_counts(on) == _level_counts(off)
    assert max(c[4] for c in _level_counts(on)) >= 3
    cut, cut_off = on.stats["cut_level"], off.stats["cut_level"]
    # (the `host` backend's arena holds a level in another order: its
    # violating row lies in another chunk, with two more behind it)
    done = cut_off["chunks_committed"]
    assert cut["chunks_committed"] == done == (4 if backend == "device" else 1)
    assert cut["rows_committed"] == cut_off["rows_committed"]
    assert cut["chunks_ahead"] == done - 1
    assert off_dropped == []
    assert (cut_off["chunks_discarded"],
            cut_off["discarded_dispatches"]) == (0, 0)
    if backend == "host":
        assert on_dropped == ["fgd", "fsc"]
        assert (cut["chunks_discarded"], cut["discarded_dispatches"],
                cut["chunks"]) == (2, 2, done + 2)
        assert cut["discarded_ms"] > 0
    else:
        assert on_dropped == []
        assert (cut["chunks_discarded"], cut["discarded_dispatches"],
                cut["chunks"]) == (1, 0, done + 1)


# --- staging bounds + span evidence (satellite: test coverage) ------------


@pytest.mark.perf
def test_two_slot_pipeline_never_holds_more_than_two_chunks(monkeypatch):
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    # frl(2,2,3) levels reach 81 rows: chunk 32 -> multiple chunks/level
    res = check(frl.make_model(2, 2, 3), min_bucket=32, chunk_size=32)
    ov = res.stats["overlap"]
    assert ov["enabled"]
    # multiple chunks per level -> both slots used, and the structural
    # bound holds
    assert ov["staged_chunks_peak"] == 2
    # ... and beside the two open successor launches at most one chunk
    # whose guard stage has run ahead (none here: below the default gate
    # a chunk is a legacy chunk, which is never split)
    assert ov["guard_ahead_peak"] == 0
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    res2 = check(frl.make_model(2, 2, 3), min_bucket=32, chunk_size=32)
    assert res2.stats["overlap"]["staged_chunks_peak"] <= 1
    assert res2.stats["overlap"]["guard_ahead_peak"] == 0


@pytest.mark.perf
def test_staging_bound_two_successor_launches_and_one_guard_ahead(
        monkeypatch):
    """The bound restated for fused chunks (ISSUE 42): at most two open
    successor launches (the chunk committing and the one dispatched) plus
    at most one chunk of which only the guard stage has run, counted where
    the loop stages them, in every level of every length."""
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    from kafka_specification_tpu.engine import pipeline as pl

    open_guards, peak = set(), [0]
    guard_stage = pl.FusedPipeline.guard_stage
    staged = pl.FusedPipeline.run_chunk_staged

    def counting_guard(self, *a, **kw):
        g = guard_stage(self, *a, **kw)
        open_guards.add(id(g))
        peak[0] = max(peak[0], len(open_guards))
        return g

    def counting_staged(self, *a, ahead=None, **kw):
        before = set(open_guards)
        out = staged(self, *a, ahead=ahead, **kw)
        # the chunk has its successor launch: neither its guard stage, run
        # ahead or inside this call, is a guard alone any longer
        open_guards.intersection_update(before)
        open_guards.discard(id(ahead))
        return out

    monkeypatch.setattr(pl.FusedPipeline, "guard_stage", counting_guard)
    monkeypatch.setattr(pl.FusedPipeline, "run_chunk_staged",
                        counting_staged)
    res = check(frl.make_model(2, 2, 3), **FUSED32)
    ov = res.stats["overlap"]
    assert (ov["staged_chunks_peak"], ov["guard_ahead_peak"]) == (2, 1)
    # a chunk's own guard and the next chunk's: two StagedGuards exist for
    # the moment between them, of which one is ahead
    assert peak[0] == 2 and len(open_guards) <= 1


@pytest.mark.perf
def test_checkpoint_write_span_overlaps_step_span(tmp_path, monkeypatch):
    """The async checkpoint's write span (emitted on the writer thread,
    obs context propagated) must overlap some chunk `step` span in wall
    time — the direct evidence a write ran behind device compute.  The
    write is slowed so the overlap window cannot vanish into scheduling
    noise on a loaded CI box (everything here is warm and sub-ms)."""
    orig_savez = np.savez

    def slow_savez(*a, **kw):
        time.sleep(0.05)
        return orig_savez(*a, **kw)

    monkeypatch.setattr(np, "savez", slow_savez)
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    run = RunContext(str(tmp_path / "run"))
    res = check(
        frl.make_model(2, 2, 3),
        min_bucket=32,
        chunk_size=64,
        checkpoint_dir=str(tmp_path / "ck"),
        checkpoint_every=1,
        run=run,
    )
    assert res.total > 0
    spans = read_jsonl_tolerant(run.spans_path)

    def _ivals(kind):
        return [
            (s["t0"], s["t0"] + s["ms"] / 1e3)
            for s in spans
            if s.get("span") == kind and s.get("ph") == "E"
        ]

    steps = _ivals("step")
    writes = _ivals("checkpoint-write")
    assert steps and writes, "expected step and checkpoint-write spans"
    overlapped = any(
        w0 < s1 and s0 < w1 for (w0, w1) in writes for (s0, s1) in steps
    )
    assert overlapped, (
        "no checkpoint-write span overlapped a step span — the async "
        "writer is not off the critical path"
    )


# --- fault matrix on the async paths (satellite) --------------------------


def _spilling_kwargs(ck):
    return dict(
        min_bucket=32,
        chunk_size=64,
        mem_budget=128,
        store="disk",
        checkpoint_dir=ck,
    )


def test_crash_at_merge_fires_on_worker_and_resumes(monkeypatch, tmp_path):
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    golden = check(frl.make_model(2, 2, 2), min_bucket=32, chunk_size=64)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    monkeypatch.setenv("KSPEC_SPILL_RUNS_PER_MERGE", "2")
    monkeypatch.setenv("KSPEC_FAULT", "crash@merge:1")
    with pytest.raises(InjectedCrash):
        check(frl.make_model(2, 2, 2), **_spilling_kwargs(ck))
    assert verify_checkpoint_dir(ck)["ok"]
    monkeypatch.delenv("KSPEC_FAULT")
    res = check(frl.make_model(2, 2, 2), **_spilling_kwargs(ck))
    assert _verdict(res)[:3] == _verdict(golden)[:3]


def test_enospc_at_ckpt_async_still_typed_exit_75(monkeypatch, tmp_path):
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    golden = check(frl.make_model(2, 2, 2), min_bucket=32, chunk_size=64)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    monkeypatch.setenv("KSPEC_FAULT", "enospc@ckpt:2")
    with pytest.raises(ResourceExhausted) as ei:
        check(frl.make_model(2, 2, 2), **_spilling_kwargs(ck))
    assert ei.value.reason == "enospc"
    # the failed write cleaned its tmp; the promoted state verifies
    assert verify_checkpoint_dir(ck)["ok"]
    monkeypatch.delenv("KSPEC_FAULT")
    res = check(frl.make_model(2, 2, 2), **_spilling_kwargs(ck))
    assert _verdict(res)[:3] == _verdict(golden)[:3]


def test_flip_at_spill_detected_with_background_merges(
    monkeypatch, tmp_path
):
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    golden = check(frl.make_model(2, 2, 2), min_bucket=32, chunk_size=64)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    monkeypatch.setenv("KSPEC_FAULT", "flip@spill:1")
    with pytest.raises(IntegrityError):
        check(frl.make_model(2, 2, 2), **_spilling_kwargs(ck))
    monkeypatch.delenv("KSPEC_FAULT")
    res = check(frl.make_model(2, 2, 2), **_spilling_kwargs(ck))
    assert _verdict(res)[:3] == _verdict(golden)[:3]


def test_compressed_overflow_at_full_width_falls_back_to_raw(monkeypatch):
    """Review regression: the raw exchange cannot overflow at W == T,
    but the codec's stream/row budgets can — once the width ladder tops
    out, the chunk must fall back to the RAW wire (bit-identically)
    instead of committing a truncated payload.  A starved stream budget
    forces the codec to overflow at EVERY width."""
    monkeypatch.setattr(fpc, "default_stream_words", lambda w: fpc.BLK)
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    base = check_sharded(frl.make_model(2, 2, 3), mesh=_mesh(2),
                         min_bucket=64)
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    monkeypatch.setenv("KSPEC_EXCHANGE_COMPRESS", "1")
    on = check_sharded(frl.make_model(2, 2, 3), mesh=_mesh(2),
                       min_bucket=64)
    assert _verdict(on) == _verdict(base)
    # the codec was requested but every real chunk fell back: the wire
    # accounting must reflect raw-dominated traffic, not claim savings
    assert on.stats["exchange_bytes_total"] >= \
        0.5 * on.stats["exchange_raw_bytes_total"]


def test_sharded_flip_exchange_detected_through_compression(monkeypatch):
    """flip@exchange must still trip the framing digests when the wire
    is compressed — the digests frame the DECODED payload."""
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    monkeypatch.setenv("KSPEC_EXCHANGE_COMPRESS", "1")
    monkeypatch.setenv("KSPEC_FAULT", "flip@exchange:2")
    with pytest.raises(IntegrityError) as ei:
        check_sharded(frl.make_model(2, 2, 2), mesh=_mesh(2),
                      min_bucket=64)
    assert ei.value.site == "exchange"


def test_sharded_crash_merge_on_worker_resumes(monkeypatch, tmp_path):
    monkeypatch.setenv("KSPEC_OVERLAP", "0")
    golden = check_sharded(frl.make_model(2, 2, 2), mesh=_mesh(2),
                           min_bucket=64)
    ck = str(tmp_path / "ck")
    kwargs = dict(
        mesh=_mesh(2),
        min_bucket=64,
        mem_budget=128,
        store="disk",
        checkpoint_dir=ck,
        spill_dir=str(tmp_path / "spill"),
    )
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    monkeypatch.setenv("KSPEC_SPILL_RUNS_PER_MERGE", "2")
    monkeypatch.setenv("KSPEC_FAULT", "crash@merge:1")
    with pytest.raises(InjectedCrash):
        check_sharded(frl.make_model(2, 2, 2), **kwargs)
    monkeypatch.delenv("KSPEC_FAULT")
    res = check_sharded(frl.make_model(2, 2, 2), **kwargs)
    assert _verdict(res)[:3] == _verdict(golden)[:3]


# --- background merges + the reclaim race (satellite: small fix) ----------


def test_background_merge_bit_identical_membership(tmp_path):
    from kafka_specification_tpu.storage.tiered import TieredFpSet

    rng = np.random.default_rng(11)
    fps = rng.integers(1, 2**63, size=6000, dtype=np.uint64)
    w = AsyncWorker("t-merge")
    ts = TieredFpSet(
        str(tmp_path / "async"), mem_budget=16 * 200,
        runs_per_merge=2, merge_worker=w,
    )
    ref = TieredFpSet(
        str(tmp_path / "sync"), mem_budget=16 * 200, runs_per_merge=2
    )
    for i in range(0, fps.size, 500):
        batch = fps[i : i + 500]
        assert np.array_equal(ts.insert(batch), ref.insert(batch))
    ts.quiesce()
    assert len(ts) == len(ref)
    probe = np.concatenate([fps[:100], np.array([7, 8, 9], np.uint64)])
    assert np.array_equal(ts.contains(probe), ref.contains(probe))
    assert ts.merges > 0
    w.close()


def test_reclaim_quiesces_merge_worker_first(tmp_path, monkeypatch):
    """PR 10 small fix: an eager reclaim merge / tmp sweep while a
    background merge is mid-write must quiesce the worker first — the
    in-flight merge's tmp is live work, and a racing second merge over
    the same inputs would double-schedule them on the deletion
    barrier."""
    from kafka_specification_tpu.storage import runs as runs_mod
    from kafka_specification_tpu.storage.tiered import TieredFpSet

    real_merge = runs_mod.merge_runs
    started = threading.Event()

    def slow_merge(rs, path, block=1 << 20, crash_hook=None):
        started.set()
        time.sleep(0.4)  # hold the merge mid-flight
        return real_merge(rs, path, block=block, crash_hook=crash_hook)

    monkeypatch.setattr(
        "kafka_specification_tpu.storage.tiered.merge_runs", slow_merge
    )
    rng = np.random.default_rng(5)
    w = AsyncWorker("t-reclaim")
    ts = TieredFpSet(
        str(tmp_path / "t"), mem_budget=16 * 50,
        runs_per_merge=2, merge_worker=w,
    )
    fps = rng.integers(1, 2**63, size=400, dtype=np.uint64)
    for i in range(0, fps.size, 50):
        ts.insert(fps[i : i + 50])
    # the worker picks the job up on its own thread: wait for it (bounded)
    # so that the reclaim below finds the merge mid-flight, not queued
    assert started.wait(timeout=30), "background merge should have started"
    # the reclaim path: sync merge must quiesce (adopt) first
    ts.merge()
    assert ts._merge_job is None
    pending = [p for _n, p in ts.deleter.pending]
    assert len(pending) == len(set(pending)), (
        "merge inputs double-scheduled on the deletion barrier"
    )
    assert np.all(ts.contains(fps))
    w.close()


def test_report_overlap_beat_and_exposed_io_stall(tmp_path, monkeypatch):
    """`cli report`'s overlap beat (satellite): the efficiency gauge
    renders, and a run whose exposed I/O dominates gets the
    machine-readable EXPOSED-I/O STALL verdict line."""
    from kafka_specification_tpu.obs.report import _overlap, render_report

    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    run = RunContext(str(tmp_path / "run"))
    check(
        frl.make_model(2, 2, 3), min_bucket=32, chunk_size=64,
        mem_budget=128, store="disk",
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=1, run=run,
    )
    text = render_report(run.dir)
    assert "overlap" in text and "I/O hidden" in text
    # synthetic exposed-dominated data -> the stall beat fires
    stalled = _overlap(
        {
            "metrics": {
                "counters": {
                    "kspec_io_hidden_ms_total": 10,
                    "kspec_io_exposed_ms_total": 500,
                },
                "gauges": {"kspec_overlap_efficiency": 0.02},
            },
            "metrics_history": [],
        }
    )
    assert stalled["exposed_io_stalled"] is True
    healthy = _overlap(
        {
            "metrics": {
                "counters": {
                    "kspec_io_hidden_ms_total": 500,
                    "kspec_io_exposed_ms_total": 10,
                },
                "gauges": {"kspec_overlap_efficiency": 0.98},
            },
            "metrics_history": [],
        }
    )
    assert healthy["exposed_io_stalled"] is False


def test_overlap_run_clean_without_checkpointing(monkeypatch):
    # overlap on, nothing to overlap with (no disk tier, no checkpoints)
    monkeypatch.setenv("KSPEC_OVERLAP", "1")
    res = check(frl.make_model(2, 2, 2), min_bucket=32)
    assert res.ok and res.stats["overlap"]["enabled"]
