"""Resilience subsystem: deterministic fault injection, hardened
checkpoints (checksums / rotation / fallback), transient-error retry,
degradation accounting, and the supervised auto-resume runner.

Everything here drives the REAL recovery paths via the KSPEC_FAULT
grammar on CPU (resilience.faults) — no hardware failures needed.  The
acceptance bar: a run killed mid-search and auto-resumed must report
bit-identical distinct-state counts, diameter, and invariant verdicts to
an uninterrupted run, for both engines; a corrupted newest checkpoint
must fall back to the previous good generation without manual
intervention.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.resilience import (
    CheckpointCorrupt,
    CheckpointStore,
    FaultPlan,
    InjectedCrash,
    RetryPolicy,
    classify,
    corrupt_file,
    heartbeat_record,
)

pytestmark = pytest.mark.fault

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fast_retry(monkeypatch):
    """Keep injected-transient backoff sleeps out of the tier-1 budget."""
    monkeypatch.setenv("KSPEC_RETRY_BASE_DELAY", "0.001")
    monkeypatch.setenv("KSPEC_RETRY_MAX_DELAY", "0.01")


def _verdict(res):
    """The bit-identity tuple the acceptance criteria compare."""
    return (
        res.total,
        res.diameter,
        tuple(res.levels),
        res.ok,
        (res.violation.invariant, res.violation.depth) if res.violation else None,
    )


# --- fault plan grammar -------------------------------------------------


def test_fault_plan_grammar():
    p = FaultPlan("crash@level:7,corrupt_ckpt, compile_oom,transient_device_err:2")
    assert len(p.specs) == 4
    with pytest.raises(InjectedCrash):
        p.crash("level", 7)
    p.crash("level", 7)  # budget consumed: no re-fire
    # transient budget: two errors then clean
    assert classify(p.chunk_error(escalated=False)) == "transient"
    assert p.chunk_error(escalated=False) is not None
    assert p.chunk_error(escalated=False) is None
    # compile_oom only fires on escalated attempts
    assert classify(p.chunk_error(escalated=True)) == "compile_oom"
    assert p.should_corrupt(1) and not p.should_corrupt(2)
    for bad in (
        "bogus",
        "crash@lvl:3",
        "crash@level",
        "corrupt_ckpt:4",
        "crash@level:0",  # could never fire (start_depth < N guard)
    ):
        with pytest.raises(ValueError):
            FaultPlan(bad)


def test_level_crash_defers_until_checkpointed():
    """On a checkpointing run, crash@level:N waits for a checkpoint at or
    past level N (else checkpoint_every>1 would resume below N and
    re-fire forever) and fires at the first boundary after it."""
    p = FaultPlan("crash@level:7")
    p.crash("level", 7, ckpt_depth=6)  # level 7 not yet durable: defer
    with pytest.raises(InjectedCrash):
        p.crash("level", 8, ckpt_depth=8)
    # the restarted run resumes at the checkpointed level 8 >= 7: no fire
    p2 = FaultPlan("crash@level:7")
    p2.set_start_depth(8)
    p2.crash("level", 8, ckpt_depth=8)


def test_crash_resume_converges_with_checkpoint_every_2(tmp_path, monkeypatch):
    """End-to-end: an odd crash level with checkpoint_every=2 (the prod464
    shape) still crashes exactly once and resumes to the exact result."""
    ck = str(tmp_path / "ck")
    model = frl.make_model(2, 2, 2)
    golden = _verdict(check(model, min_bucket=32, store_trace=False))
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:3")
    with pytest.raises(InjectedCrash):
        check(model, min_bucket=32, checkpoint_dir=ck, checkpoint_every=2)
    # env still set (a supervisor restart inherits it): must NOT re-fire
    resumed = check(model, min_bucket=32, checkpoint_dir=ck, checkpoint_every=2)
    assert _verdict(resumed) == golden


def test_crash_faults_skip_resumed_levels():
    """A run resumed at the crash level must not crash-loop (restart
    convergence for the supervisor)."""
    p = FaultPlan("crash@level:5")
    p.set_start_depth(5)
    p.crash("level", 5)  # no raise
    p.set_start_depth(3)
    with pytest.raises(InjectedCrash):
        p.crash("level", 5)


# --- checkpoint store ---------------------------------------------------


def test_checkpoint_rotation_and_manifest(tmp_path):
    st = CheckpointStore(str(tmp_path), "bfs_checkpoint.npz", ident="m", keep=3)
    for depth in range(1, 6):
        st.save(depth, {"frontier": np.arange(depth, dtype=np.uint32)})
    # keep-last-3: newest at the legacy name, older rotated
    assert sorted(os.listdir(tmp_path)) == [
        "bfs_checkpoint.1.npz",
        "bfs_checkpoint.2.npz",
        "bfs_checkpoint.npz",
    ]
    main, _, gen = st.load()
    assert gen == 0 and int(main["depth"]) == 5
    man = json.loads(str(np.load(st.path(0))["__manifest__"]))
    assert set(man) >= {"frontier", "ident", "depth"}
    assert all("crc32" in v for v in man.values())


def test_checkpoint_corrupt_falls_back_then_raises(tmp_path):
    st = CheckpointStore(str(tmp_path), "bfs_checkpoint.npz", ident="m", keep=3)
    for depth in (1, 2, 3):
        st.save(depth, {"x": np.full(8, depth, np.int64)})
    corrupt_file(st.path(0))
    main, _, gen = st.load()  # automatic fallback, no raise
    assert gen == 1 and int(main["depth"]) == 2
    corrupt_file(st.path(1))
    corrupt_file(st.path(2))
    with pytest.raises(CheckpointCorrupt):
        st.load()  # files exist but none verify: never silently restart


def test_checkpoint_ident_mismatch_never_falls_back(tmp_path):
    CheckpointStore(str(tmp_path), "c.npz", ident="model-A", keep=2).save(
        4, {"x": np.zeros(2)}
    )
    with pytest.raises(ValueError, match="different"):
        CheckpointStore(str(tmp_path), "c.npz", ident="model-B", keep=2).load()


def test_checkpoint_part_level_consistency(tmp_path):
    """Cross-shard check: parts pair with the main file BY LEVEL.  A crash
    between the part and main promotes (chains skewed by one generation)
    must fall back to the newest level both sides agree on — and only
    when NO level agrees is the store unrecoverable."""
    st = CheckpointStore(str(tmp_path), "s.npz", ident="m", keep=2)
    st.save(3, {"a": np.ones(2)})
    st.save(3, {"b": np.ones(3)}, part="host0")
    main, parts, _ = st.load(parts=("host0",))
    assert int(parts["host0"]["depth"]) == 3
    # crash-between-promotes skew: part advanced to level 4, main did not
    st.save(4, {"b": np.ones(3)}, part="host0")
    main, parts, _ = st.load(parts=("host0",))
    assert int(main["depth"]) == 3 and int(parts["host0"]["depth"]) == 3
    # two more main-only advances: no part exists at either main level
    st.save(4, {"a": np.ones(2)})
    st.save(5, {"a": np.ones(2)})  # keep=2: main levels {4, 5}, parts {3, 4}
    main, parts, _ = st.load(parts=("host0",))
    assert int(main["depth"]) == 4 and int(parts["host0"]["depth"]) == 4
    st.save(6, {"a": np.ones(2)})  # main levels {5, 6} vs parts {3, 4}
    with pytest.raises(CheckpointCorrupt):
        st.load(parts=("host0",))


# --- engine recovery paths ----------------------------------------------


def test_crash_resume_bit_identical_single_core(tmp_path, monkeypatch):
    """KSPEC_FAULT=crash@level:N mid-run -> resume from checkpoint ->
    state count / diameter / per-level counts identical to an
    uninterrupted run (acceptance criterion, single-core engine)."""
    ck = str(tmp_path / "ck")
    model = frl.make_model(2, 2, 2)
    golden = _verdict(check(model, min_bucket=32, store_trace=False))
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check(model, min_bucket=32, checkpoint_dir=ck)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check(model, min_bucket=32, checkpoint_dir=ck)
    assert _verdict(resumed) == golden
    assert resumed.total == 49


def test_crash_resume_bit_identical_sharded(tmp_path, monkeypatch):
    """Sharded twin of the acceptance criterion."""
    ck = str(tmp_path / "sck")
    model = frl.make_model(2, 2, 2)
    golden = _verdict(check_sharded(model, min_bucket=32, store_trace=False))
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check_sharded(model, min_bucket=32, checkpoint_dir=ck)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(model, min_bucket=32, checkpoint_dir=ck)
    assert _verdict(resumed) == golden
    assert resumed.total == 49


def test_crash_resume_same_invariant_verdict(tmp_path, monkeypatch):
    """A violation found after a resume reports the same invariant at the
    same depth as the uninterrupted run (verdict bit-identity)."""
    ck = str(tmp_path / "ck")

    def mk():
        return variants.make_model(
            "KafkaTruncateToHighWatermark", Config(2, 2, 1, 1), ("TypeOk", "WeakIsr")
        )

    golden = check(mk(), min_bucket=32, store_trace=False)
    assert golden.violation is not None and golden.violation.depth == 8
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:4")
    with pytest.raises(InjectedCrash):
        check(mk(), min_bucket=32, checkpoint_dir=ck)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check(mk(), min_bucket=32, checkpoint_dir=ck)
    assert _verdict(resumed) == _verdict(golden)
    assert resumed.violation.invariant == "WeakIsr"


def test_corrupt_newest_checkpoint_auto_fallback(tmp_path, monkeypatch):
    """A corrupted newest checkpoint is detected by checksum and the run
    resumes from the previous good generation without manual intervention
    (acceptance criterion)."""
    ck = str(tmp_path / "ck")
    model = frl.make_model(2, 2, 2)
    golden = _verdict(check(model, min_bucket=32, store_trace=False))
    # run the first 3 levels, corrupting the level-3 checkpoint as written
    monkeypatch.setenv("KSPEC_FAULT", "corrupt_ckpt@ckpt:3")
    partial = check(model, max_depth=3, min_bucket=32, checkpoint_dir=ck)
    assert partial.total < 49
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check(model, min_bucket=32, checkpoint_dir=ck)
    assert _verdict(resumed) == golden


def test_corrupt_newest_checkpoint_auto_fallback_sharded(tmp_path):
    """Sharded twin, corrupting the newest generation on disk directly."""
    ck = tmp_path / "sck"
    model = frl.make_model(2, 2, 2)
    golden = _verdict(check_sharded(model, min_bucket=32, store_trace=False))
    check_sharded(model, max_depth=3, min_bucket=32, checkpoint_dir=str(ck))
    corrupt_file(str(ck / "sharded_checkpoint.npz"))
    resumed = check_sharded(model, min_bucket=32, checkpoint_dir=str(ck))
    assert _verdict(resumed) == golden


def test_transient_device_error_retried_single_core(monkeypatch):
    """Injected transient backend errors are absorbed by bounded backoff
    retry; results stay exact and the retries land in result.stats."""
    monkeypatch.setenv("KSPEC_FAULT", "transient_device_err:2")
    res = check(frl.make_model(2, 2, 2), min_bucket=32, store_trace=False)
    assert res.ok and res.total == 49
    assert res.stats["transient_retries"] == 2


def test_transient_exchange_error_retried_sharded(monkeypatch):
    monkeypatch.setenv("KSPEC_FAULT", "transient_device_err:1")
    res = check_sharded(frl.make_model(2, 2, 2), min_bucket=32, store_trace=False)
    assert res.ok and res.total == 49
    assert res.stats["transient_retries"] == 1


def test_transient_budget_exhaustion_raises(monkeypatch):
    """More consecutive transient errors than the retry budget must still
    surface (the supervisor's restart layer owns that case)."""
    monkeypatch.setenv("KSPEC_FAULT", "transient_device_err:50")
    monkeypatch.setenv("KSPEC_RETRY_MAX", "2")
    with pytest.raises(RuntimeError, match="injected transient"):
        check(frl.make_model(2, 2, 2), min_bucket=32, store_trace=False)


def test_transient_exhaustion_on_escalated_attempt_raises(monkeypatch):
    """An exhausted transient budget must surface even on an escalated
    (per-action tuple) attempt — NOT slide into the compile-OOM degrade
    path, which would mislabel an outage as a compile failure and pin
    adaptation off for the rest of the run."""
    from kafka_specification_tpu.engine import bfs as bfs_mod

    orig_wf = bfs_mod.AdaptiveCompact.widths_for

    def tuple_widths(self, bucket):
        if self.on:
            return tuple(256 for _ in self.actions)
        return orig_wf(self, bucket)

    monkeypatch.setattr(bfs_mod.AdaptiveCompact, "widths_for", tuple_widths)
    monkeypatch.setenv("KSPEC_FAULT", "transient_device_err:50")
    monkeypatch.setenv("KSPEC_RETRY_MAX", "2")
    with pytest.raises(RuntimeError, match="injected transient"):
        check(frl.make_model(2, 2, 2), min_bucket=32, store_trace=False)


def test_injected_compile_oom_degrades_to_uniform(monkeypatch):
    """KSPEC_FAULT=compile_oom on an escalated attempt triggers the
    compile fallback (adaptation pinned off, uniform path) and records
    the degradation in result.stats instead of dying.  Escalated state is
    injected via widths_for, as in test_engine's fallback test."""
    from kafka_specification_tpu.engine import bfs as bfs_mod

    orig_wf = bfs_mod.AdaptiveCompact.widths_for

    def tuple_widths(self, bucket):
        if self.on:
            return tuple(256 for _ in self.actions)
        return orig_wf(self, bucket)

    monkeypatch.setattr(bfs_mod.AdaptiveCompact, "widths_for", tuple_widths)
    monkeypatch.setenv("KSPEC_FAULT", "compile_oom")
    res = check(
        frl.make_model(2, 2, 2),
        store_trace=False,
        compact_shift=2,
        visited_backend="host",
    )
    assert res.ok and res.total == 49
    assert res.stats["adaptive_compile_fallback"] is True
    assert res.stats["degradations"]
    deg = res.stats["degradations"][0]
    assert deg["kind"] == "compile_fallback" and "out of memory" in deg["error"]


# --- heartbeat schema ---------------------------------------------------


def test_heartbeat_schema_shared(tmp_path):
    """Engine per-level stats lines carry the same envelope the
    supervisor's stall detector consumes."""
    rec = heartbeat_record("supervisor", event="start")
    assert set(rec) >= {"kind", "ts", "unix", "event"}
    # engine stats stream
    stats = tmp_path / "stats.jsonl"
    check(
        frl.make_model(2, 2, 1),
        min_bucket=32,
        store_trace=False,
        stats_path=str(stats),
    )
    lines = [json.loads(l) for l in stats.read_text().splitlines()]
    assert lines and all(
        r["kind"] == "level" and "unix" in r and "ts" in r and "depth" in r
        for r in lines
    )


# --- supervisor ---------------------------------------------------------


def _supervise_cli(tmp_path, tag, extra_args, env_extra):
    """Run resilient_run.py around a CLI check; -> (rc, events, last_json)."""
    hb = str(tmp_path / f"{tag}_hb.jsonl")
    ev = str(tmp_path / f"{tag}_events.jsonl")
    logs = str(tmp_path / f"{tag}_logs")
    ck = str(tmp_path / f"{tag}_ck")
    env = dict(os.environ, **env_extra)
    rc = subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "scripts", "resilient_run.py"),
            "--heartbeat", hb,
            "--events", ev,
            "--log-dir", logs,
            "--stall-timeout", "300",
            "--max-restarts", "3",
            "--backoff", "0.05",
            "--",
            sys.executable, "-m", "kafka_specification_tpu.utils.cli",
            "check", os.path.join(_REPO, "configs", "IdSequence.cfg"),
            "--hand", "--cpu", "--json",
            "--checkpoint", ck, "--stats", hb,
        ]
        + extra_args,
        cwd=_REPO,
        env=env,
        timeout=540,
    ).returncode
    events = [
        json.loads(l) for l in open(ev).read().splitlines()
    ]
    last_json = None
    for name in sorted(os.listdir(logs), reverse=True):
        for line in reversed(
            open(os.path.join(logs, name), errors="replace").read().splitlines()
        ):
            if line.startswith("{"):
                last_json = json.loads(line)
                break
        if last_json:
            break
    return rc, events, last_json


def test_supervised_crash_auto_resume_single_core(tmp_path):
    """scripts/resilient_run.py end-to-end (acceptance criterion): the
    child crashes at an injected level, the supervisor restarts it, the
    resumed run completes with results identical to an uninterrupted
    run."""
    rc0, _, golden = _supervise_cli(tmp_path, "clean", [], {})
    assert rc0 == 0 and golden is not None
    rc, events, final = _supervise_cli(
        tmp_path, "crash", [], {"KSPEC_FAULT": "crash@level:4"}
    )
    assert rc == 0
    kinds = [e["event"] for e in events]
    assert kinds.count("start") == 2  # crashed once, restarted once
    assert "restart" in kinds and kinds[-1] == "complete"
    assert all(e["kind"] == "supervisor" for e in events)
    for key in ("distinct_states", "diameter", "levels", "violation"):
        assert final[key] == golden[key], key


def test_supervised_crash_auto_resume_sharded(tmp_path):
    """Sharded engine under the supervisor (acceptance criterion)."""
    rc0, _, golden = _supervise_cli(tmp_path, "sclean", ["--sharded"], {})
    assert rc0 == 0 and golden is not None
    rc, events, final = _supervise_cli(
        tmp_path, "scrash", ["--sharded"], {"KSPEC_FAULT": "crash@level:4"}
    )
    assert rc == 0
    assert [e["event"] for e in events].count("start") == 2
    for key in ("distinct_states", "diameter", "levels", "violation"):
        assert final[key] == golden[key], key


def test_supervisor_stall_kill_and_budget(tmp_path):
    """A child that hangs without heartbeating is stall-killed; the
    restart budget bounds the attempts and the rc is nonzero."""
    ev = str(tmp_path / "events.jsonl")
    rc = subprocess.run(
        [
            sys.executable,
            os.path.join(_REPO, "scripts", "resilient_run.py"),
            "--heartbeat", str(tmp_path / "never_written.jsonl"),
            "--events", ev,
            "--stall-timeout", "1",
            "--max-restarts", "1",
            "--backoff", "0.05",
            "--",
            sys.executable, "-c", "import time; time.sleep(600)",
        ],
        cwd=_REPO,
        timeout=120,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    ).returncode
    assert rc != 0
    events = [json.loads(l) for l in open(ev).read().splitlines()]
    kinds = [e["event"] for e in events]
    assert kinds.count("stall-kill") == 2  # initial attempt + 1 restart
    assert kinds[-1] == "give-up"


def test_retry_policy_backoff_bounded():
    p = RetryPolicy(max_retries=3, base_delay=0.5, factor=2.0, max_delay=2.0, jitter=0.0)
    assert [p.delay(i) for i in (1, 2, 3, 4)] == [0.5, 1.0, 2.0, 2.0]
    assert classify(RuntimeError("UNAVAILABLE: socket closed")) == "transient"
    assert classify(RuntimeError("LLVM ERROR: out of memory")) == "compile_oom"
    assert classify(RuntimeError("shape mismatch")) == "other"
