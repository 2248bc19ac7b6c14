"""Distributed resilience for the sharded engine (PR 4).

Shard-targeted deterministic fault injection, fleet supervision, elastic
resume (a D-shard checkpoint resumed on D' != D shards), and post-resume
counterexample traces from the per-shard on-disk parent logs — every path
drivable from tier-1 on the virtual CPU mesh, no real fabric needed.

The acceptance bar mirrors PR 1's: a sharded run crashed on a *specific
shard* mid-search and resumed must be bit-identical (counts + trace
values) to the fault-free run; a checkpoint written at one shard count
must resume at another with the same exact counts and a valid full trace.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.parallel.sharded import check_sharded
from kafka_specification_tpu.resilience import FaultPlan, InjectedCrash
from kafka_specification_tpu.resilience.checkpoints import (
    verify_checkpoint_dir,
)

pytestmark = pytest.mark.fault

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = Config(2, 2, 1, 1)


@pytest.fixture(autouse=True)
def _fast_retry(monkeypatch):
    monkeypatch.setenv("KSPEC_RETRY_BASE_DELAY", "0.001")
    monkeypatch.setenv("KSPEC_RETRY_MAX_DELAY", "0.01")


def _verdict(res):
    return (
        res.total,
        res.diameter,
        tuple(res.levels),
        res.ok,
        (res.violation.invariant, res.violation.depth) if res.violation else None,
    )


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("d",))


def _mk_violating():
    return variants.make_model(
        "KafkaTruncateToHighWatermark", TINY, ("TypeOk", "WeakIsr")
    )


def _replay_trace_through_oracle(trace):
    """Every step of a reported trace must be a legal oracle transition
    ending in the state the engine reported (test_sharded's idiom)."""
    o = variants.make_oracle("KafkaTruncateToHighWatermark", TINY, ("TypeOk",))
    actions = {a.name: a for a in o.actions}
    cur = o.init_states()[0]
    assert trace[0] == ("<init>", cur)
    for name, nxt in trace[1:]:
        assert nxt in set(actions[name].successors(cur)), name
        cur = nxt


# --- shard-scoped fault grammar ------------------------------------------


def test_shard_scoped_fault_grammar():
    p = FaultPlan(
        "crash@shard2:level:5,corrupt_ckpt@shard0,"
        "transient_device_err@shard1:3,crash@shard0:ckpt:4"
    )
    assert [s.shard for s in p.specs] == [2, 0, 1, 0]
    assert [s.kind for s in p.specs] == [
        "crash", "corrupt_ckpt", "transient_device_err", "crash",
    ]
    assert p.specs[2].budget == 3
    for bad in (
        "crash@shard:level:5",     # missing shard index
        "crash@shardX:level:5",    # non-integer shard
        "crash@shard1:bogus:5",    # unknown point under the scope
        "transient_device_err@shard1:x",
        "corrupt_ckpt@shard1:3",   # needs the ckpt:N form
    ):
        with pytest.raises(ValueError):
            FaultPlan(bad)


def test_shard_scoped_crash_fires_only_on_owner():
    p = FaultPlan("crash@shard2:level:3")
    p.set_local_shards([0, 1])  # another host owns shard 2
    p.crash("level", 3)  # not local: no fire
    p.set_local_shards([2, 3])
    with pytest.raises(InjectedCrash):
        p.crash("level", 3)
    # budget consumed exactly once
    p.crash("level", 3)


def test_shard_scoped_transient_and_corrupt_respect_scope():
    p = FaultPlan("transient_device_err@shard1:2,corrupt_ckpt@shard0")
    p.set_local_shards([0])
    assert p.chunk_error(escalated=False) is None  # shard 1 not local
    assert p.should_corrupt(1) is True  # shard 0 is local
    p2 = FaultPlan("transient_device_err@shard1:2")
    p2.set_local_shards([1])
    assert p2.chunk_error(escalated=False) is not None
    assert p2.chunk_error(escalated=False) is not None
    assert p2.chunk_error(escalated=False) is None  # budget spent


def test_unscoped_plan_unaffected_by_local_shards():
    p = FaultPlan("crash@level:2")
    p.set_local_shards([3])
    with pytest.raises(InjectedCrash):
        p.crash("level", 2)


def test_out_of_range_shard_scope_fails_loudly():
    """A typo'd shard index must not silently rehearse nothing (review
    finding): the plan validates against the mesh size, both at the
    FaultPlan level and end-to-end through check_sharded."""
    p = FaultPlan("crash@shard5:level:3")
    p.validate_shards(8)  # in range: fine
    with pytest.raises(ValueError, match="out of range"):
        p.validate_shards(2)
    import os as _os

    _os.environ["KSPEC_FAULT"] = "crash@shard5:level:3"
    try:
        with pytest.raises(ValueError, match="out of range"):
            check_sharded(frl.make_model(2, 2, 1), mesh=_mesh(2),
                          min_bucket=32, store_trace=False)
    finally:
        del _os.environ["KSPEC_FAULT"]


def test_sharded_plog_start_fresh_wipes_only_local_shards(tmp_path):
    """Multiprocess safety (review finding): each process's start_fresh
    must only touch its OWN shard dirs — a non-epoch-writer peer must
    never delete the coordinator's epochs.json or other shards' files."""
    import numpy as np

    from kafka_specification_tpu.storage.parent_log import ShardedParentLog

    d = str(tmp_path / "plog")
    coord = ShardedParentLog(d, 3, 2, local_shards={0}, epoch_writer=True)
    coord.start_fresh()
    rows = np.arange(3, dtype=np.uint32).reshape(1, 3)
    coord.write_level(0, [rows, rows], [np.array([-1])] * 2,
                      [np.array([-1])] * 2)  # writes shard 0 only (local)
    peer = ShardedParentLog(d, 3, 2, local_shards={1}, epoch_writer=False)
    peer.start_fresh()
    assert os.path.exists(os.path.join(d, "epochs.json"))
    assert os.path.exists(os.path.join(d, "shard0", "level-00000.plog"))
    # the epoch writer does clear stale dirs from an abandoned layout
    os.makedirs(os.path.join(d, "shard7"))
    coord2 = ShardedParentLog(d, 3, 2, local_shards={0}, epoch_writer=True)
    coord2.start_fresh()
    assert not os.path.exists(os.path.join(d, "shard7"))


def test_verify_checkpoint_ignores_stale_old_layout_parts(tmp_path):
    """After an elastic re-shard onto fewer processes, the old layout's
    part files linger; the offline verifier must derive the REQUIRED
    part set from each main's own mesh stamp (as the resume path does)
    instead of failing the directory on the stale leftovers."""
    from kafka_specification_tpu.resilience.checkpoints import (
        CheckpointStore,
    )

    st = CheckpointStore(str(tmp_path), "sharded_checkpoint.npz",
                         ident="m|backend=host|inv=-", keep=3)
    # old 2-process layout: main + both parts at depth 3
    for p in (0, 1):
        st.save(3, {"host_fps": np.zeros(2, np.uint64),
                    "mesh_D": 2, "mesh_P": 2}, part=f"host{p}")
    st.save(3, {"pending": np.zeros((0, 3), np.uint32),
                "mesh_D": 2, "mesh_P": 2})
    # elastic re-save as a single process: data inline, parts stale
    st.save(3, {"pending": np.zeros((0, 3), np.uint32),
                "host_fps": np.zeros(4, np.uint64),
                "mesh_D": 1, "mesh_P": 1})
    rep = verify_checkpoint_dir(str(tmp_path))
    assert rep["ok"], rep
    gens = rep["stores"][0]["generations"]
    assert gens[0]["mesh_P"] == 1 and gens[0]["parts"] == {}
    assert gens[1]["mesh_P"] == 2 and gens[1]["parts"] == {
        "host0": 0, "host1": 0
    }


def test_verify_checkpoint_device_backend_needs_no_parts(tmp_path):
    """Multiprocess device/device-hash checkpoints are main-only (only
    the host backend writes per-host part files); the verifier must read
    the backend from the ident stamp instead of demanding parts that
    were never written (review finding)."""
    from kafka_specification_tpu.resilience.checkpoints import (
        CheckpointStore,
    )

    st = CheckpointStore(
        str(tmp_path), "sharded_checkpoint.npz",
        ident="M|lanes=3|backend=device-hash|inv=-|", keep=2,
    )
    st.save(5, {"hash_hi": np.zeros(4, np.uint32),
                "mesh_D": 4, "mesh_P": 4})
    rep = verify_checkpoint_dir(str(tmp_path))
    assert rep["ok"], rep
    assert rep["stores"][0]["generations"][0]["parts"] == {}


def test_verify_checkpoint_resolves_part_spill_manifests(tmp_path):
    """Multiprocess disk-tier checkpoints record each host's spill
    manifest ONLY in its part file; the verifier must resolve run files
    referenced there too, or a lost run goes undetected (review
    finding)."""
    from kafka_specification_tpu.resilience.checkpoints import (
        CheckpointStore,
    )

    ident = "M|lanes=3|backend=host|inv=-|x|store=disk"
    st = CheckpointStore(str(tmp_path), "sharded_checkpoint.npz",
                         ident=ident, keep=2)
    man = [{"mem_budget": 64, "seq": 1, "runs": [
        {"name": "run-000000.fps", "count": 7, "crc32": 0,
         "lo": 0, "hi": 9}], "pending_delete": []}, None]
    st.save(3, {"spill_manifest": json.dumps(man),
                "host_hot": np.zeros(0, np.uint64),
                "host_hot_lens": np.zeros(2, np.int64),
                "mesh_D": 2, "mesh_P": 2}, part="host0")
    st.save(3, {"spill_manifest": json.dumps([None, {"mem_budget": 64,
                "seq": 0, "runs": [], "pending_delete": []}]),
                "host_hot": np.zeros(0, np.uint64),
                "host_hot_lens": np.zeros(2, np.int64),
                "mesh_D": 2, "mesh_P": 2}, part="host1")
    st.save(3, {"pending": np.zeros((0, 3), np.uint32),
                "mesh_D": 2, "mesh_P": 2})
    rep = verify_checkpoint_dir(str(tmp_path))  # run-000000.fps missing
    assert not rep["ok"]
    errs = rep["stores"][0]["generations"][0]["errors"]
    assert any("missing run file" in e for e in errs), errs
    # materialize the run file at its manifest size: now resumable
    spill = tmp_path / "spill" / "shard0"
    spill.mkdir(parents=True)
    from kafka_specification_tpu.storage.runs import _HEADER

    (spill / "run-000000.fps").write_bytes(b"\0" * (_HEADER + 8 * 7))
    rep2 = verify_checkpoint_dir(str(tmp_path))
    assert rep2["ok"], rep2
    g0 = rep2["stores"][0]["generations"][0]
    assert g0["part_spill"]["host0"]["files_checked"] == 1


# --- fault matrix: crash each shard at several levels, both exchanges ----


@pytest.mark.parametrize(
    "shard,level,exchange",
    [
        (0, 2, "all_to_all"),
        (1, 4, "all_to_all"),
        (0, 6, "all_gather"),
        (1, 3, "all_gather"),
    ],
)
def test_shard_crash_resume_bit_identical(tmp_path, monkeypatch, shard, level, exchange):
    """crash@shard<d>:level:N kills the run mid-search; the resumed run is
    bit-identical (counts + full trace values) to the fault-free run —
    the trace reconstructed from the per-shard parent logs."""
    mesh = _mesh(2)
    golden = check_sharded(_mk_violating(), mesh=mesh, min_bucket=32,
                           exchange=exchange)
    assert golden.violation is not None and golden.violation.depth == 8
    assert len(golden.violation.trace) == 9
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", f"crash@shard{shard}:level:{level}")
    with pytest.raises(InjectedCrash):
        check_sharded(_mk_violating(), mesh=mesh, min_bucket=32,
                      checkpoint_dir=ck, exchange=exchange)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(_mk_violating(), mesh=mesh, min_bucket=32,
                            checkpoint_dir=ck, exchange=exchange)
    assert _verdict(resumed) == _verdict(golden)
    assert resumed.violation.trace == golden.violation.trace


def test_sharded_resume_trace_from_parent_log(tmp_path, monkeypatch):
    """THE sharded trace-less-resume retirement test (PR 2's last
    limitation): a checkpointed sharded run killed and resumed reports
    the FULL counterexample trace, identical to the uninterrupted run."""
    golden = check_sharded(_mk_violating(), min_bucket=32)
    assert golden.violation is not None and golden.violation.trace
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:4")
    with pytest.raises(InjectedCrash):
        check_sharded(_mk_violating(), min_bucket=32, checkpoint_dir=ck)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(_mk_violating(), min_bucket=32, checkpoint_dir=ck)
    assert _verdict(resumed) == _verdict(golden)
    assert resumed.violation.trace, "post-resume sharded trace must be full"
    assert resumed.violation.trace == golden.violation.trace
    assert resumed.violation.trace[0][0] == "<init>"


def test_sharded_no_trace_run_skips_parent_log(tmp_path, monkeypatch):
    """store_trace=False (pure-throughput) checkpointed runs write no
    parent log and still resume exactly, trace-less as before."""
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:3")
    with pytest.raises(InjectedCrash):
        check_sharded(_mk_violating(), min_bucket=32, checkpoint_dir=ck,
                      store_trace=False)
    monkeypatch.delenv("KSPEC_FAULT")
    assert not os.path.isdir(os.path.join(ck, "plog"))
    resumed = check_sharded(_mk_violating(), min_bucket=32,
                            checkpoint_dir=ck, store_trace=False)
    assert resumed.violation is not None and resumed.violation.trace == []


def test_shard_scoped_transient_retried_in_engine(monkeypatch):
    monkeypatch.setenv("KSPEC_FAULT", "transient_device_err@shard0:1")
    res = check_sharded(frl.make_model(2, 2, 2), min_bucket=32,
                        store_trace=False)
    assert res.ok and res.total == 49
    assert res.stats["transient_retries"] == 1


# --- elastic resume: D-shard checkpoint resumed at D' != D ---------------


@pytest.mark.parametrize("backend", ["device", "device-hash", "host"])
def test_elastic_resume_4_to_2_exact_counts(tmp_path, monkeypatch, backend):
    """A 4-shard checkpoint resumed on a 2-shard mesh re-buckets
    fingerprint ownership and completes with exact counts (all visited
    backends)."""
    model = frl.make_model(2, 2, 2)
    golden = _verdict(check(model, min_bucket=32, store_trace=False))
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check_sharded(model, mesh=_mesh(4), min_bucket=32,
                      checkpoint_dir=ck, visited_backend=backend)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(model, mesh=_mesh(2), min_bucket=32,
                            checkpoint_dir=ck, visited_backend=backend)
    assert _verdict(resumed) == golden
    assert resumed.total == 49


def test_elastic_resume_2_to_4_exact_counts(tmp_path, monkeypatch):
    """Scaling UP is elastic too (2-shard checkpoint onto 4 shards)."""
    model = frl.make_model(2, 2, 2)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check_sharded(model, mesh=_mesh(2), min_bucket=32, checkpoint_dir=ck)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(model, mesh=_mesh(4), min_bucket=32,
                            checkpoint_dir=ck)
    assert resumed.ok and resumed.total == 49


def test_elastic_resume_reports_full_trace(tmp_path, monkeypatch):
    """The ISSUE acceptance shape: a D=4 checkpoint resumed at D=2
    produces the same exact counts AND a full root->violation trace
    (level-<resume> parent-log segments rewritten into the new shard
    order, earlier levels read through the old layout epoch)."""
    golden = check_sharded(_mk_violating(), mesh=_mesh(4), min_bucket=32)
    assert golden.violation is not None and golden.violation.depth == 8
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:4")
    with pytest.raises(InjectedCrash):
        check_sharded(_mk_violating(), mesh=_mesh(4), min_bucket=32,
                      checkpoint_dir=ck)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(_mk_violating(), mesh=_mesh(2), min_bucket=32,
                            checkpoint_dir=ck)
    assert _verdict(resumed) == _verdict(golden)
    assert resumed.violation.trace, "elastic resume must keep the trace"
    assert len(resumed.violation.trace) == 9
    assert resumed.violation.trace[0][0] == "<init>"
    # the path must replay through the oracle and end in the reported state
    _replay_trace_through_oracle(resumed.violation.trace)
    assert resumed.violation.trace[-1][1] == resumed.violation.state


def test_elastic_resume_disk_tier(tmp_path, monkeypatch):
    """Elastic re-shard with the out-of-core tier: per-shard run files are
    re-bucketed through the new layout (old runs retired behind the
    deletion barrier) and the resumed run is exact."""
    model = frl.make_model(2, 2, 2)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check_sharded(model, mesh=_mesh(4), min_bucket=32, checkpoint_dir=ck,
                      mem_budget=256)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(model, mesh=_mesh(2), min_bucket=32,
                            checkpoint_dir=ck, mem_budget=256)
    assert resumed.ok and resumed.total == 49
    spilled = [s for s in resumed.stats["spill"] if s]
    assert sum(x["disk"] + x["hot"] for x in spilled) == 49


def test_legacy_layout_baked_ident_still_resumes_same_mesh(tmp_path, monkeypatch):
    """Checkpoints written by the pre-elastic code baked `D=..|P=..` into
    the identity string; on the SAME mesh they must keep resuming after
    the upgrade (review finding — an ident mismatch never falls back, so
    without the alias every pre-upgrade checkpoint would be dead)."""
    from kafka_specification_tpu.resilience.checkpoints import (
        CheckpointStore,
        verify_file,
    )

    model = frl.make_model(2, 2, 2)
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:2")
    with pytest.raises(InjectedCrash):
        check_sharded(model, min_bucket=32, checkpoint_dir=ck,
                      store_trace=False)
    monkeypatch.delenv("KSPEC_FAULT")
    # rewrite the newest generation the way the OLD code wrote it: the
    # layout baked into the ident, no mesh stamps in the arrays
    path = os.path.join(ck, "sharded_checkpoint.npz")
    arrays = verify_file(path)
    new_ident = str(arrays.pop("ident"))
    depth = int(arrays.pop("depth"))
    D = int(arrays.pop("mesh_D"))
    P = int(arrays.pop("mesh_P"))
    head, _, tail = new_ident.partition("|backend=")
    legacy = f"{head}|D={D}|P={P}|backend={tail}"
    for name in os.listdir(ck):  # keep only the rewritten generation
        if name != "plog" and name != "sharded_checkpoint.npz":
            os.unlink(os.path.join(ck, name))
    CheckpointStore(ck, "sharded_checkpoint.npz", ident=legacy,
                    keep=1).save(depth, arrays)
    resumed = check_sharded(model, min_bucket=32, checkpoint_dir=ck,
                            store_trace=False)
    assert resumed.ok and resumed.total == 49


def test_elastic_resume_disk_tier_streams_per_run(tmp_path, monkeypatch):
    """The disk-tier re-shard must re-bucket one source array at a time
    (review finding: concatenating every shard's hot+runs rebuilds the
    whole visited set in RAM, defeating mem_budget).  Pin the contract
    by forcing multiple spilled runs and checking the resumed counts
    stay exact with spills happening DURING the re-shard inserts."""
    model = kip320_model()
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:5")
    with pytest.raises(InjectedCrash):
        check_sharded(model, mesh=_mesh(4), min_bucket=32,
                      checkpoint_dir=ck, mem_budget=512)
    monkeypatch.delenv("KSPEC_FAULT")
    resumed = check_sharded(model, mesh=_mesh(2), min_bucket=32,
                            checkpoint_dir=ck, mem_budget=512)
    assert resumed.ok and resumed.total == 277
    spilled = [s for s in resumed.stats["spill"] if s]
    assert sum(x["disk"] + x["hot"] for x in spilled) == 277
    assert sum(x["spills"] for x in spilled) > 0


def kip320_model():
    from kafka_specification_tpu.models import kip320

    return kip320.make_model(TINY, ("TypeOk",))


def test_elastic_resume_still_rejects_other_model(tmp_path):
    """Elastic covers layout changes ONLY — a different model/constants
    still refuses to resume (never silently continue the wrong search)."""
    ck = str(tmp_path / "ck")
    check_sharded(frl.make_model(2, 2, 2), max_depth=1, min_bucket=32,
                  checkpoint_dir=ck)
    with pytest.raises(ValueError, match="different"):
        check_sharded(frl.make_model(2, 3, 2), min_bucket=32,
                      checkpoint_dir=ck)


# --- offline checkpoint verification (cli verify-checkpoint) -------------


def test_verify_checkpoint_dir_clean_and_corrupt(tmp_path, monkeypatch):
    ck = str(tmp_path / "ck")
    monkeypatch.setenv("KSPEC_FAULT", "crash@level:3")
    with pytest.raises(InjectedCrash):
        check_sharded(frl.make_model(2, 2, 2), min_bucket=32,
                      checkpoint_dir=ck, mem_budget=256)
    monkeypatch.delenv("KSPEC_FAULT")
    rep = verify_checkpoint_dir(ck)
    assert rep["ok"], rep
    store = rep["stores"][0]
    assert store["basename"] == "sharded_checkpoint.npz"
    gen0 = store["generations"][0]
    assert gen0["ok"] and gen0["depth"] >= 1
    assert gen0["spill"]["ok"]  # storage manifest resolves on disk
    # corrupt every generation: the report must flag the store unusable
    from kafka_specification_tpu.resilience import corrupt_file

    for g in range(3):
        p = os.path.join(ck, "sharded_checkpoint.npz" if g == 0
                         else f"sharded_checkpoint.{g}.npz")
        if os.path.exists(p):
            corrupt_file(p)
    rep2 = verify_checkpoint_dir(ck)
    assert not rep2["ok"]


def test_cli_verify_checkpoint_is_jax_free(tmp_path):
    """`cli verify-checkpoint` must run with jax imports poisoned (the
    operator/CI case: a box whose accelerator stack is broken)."""
    ck = str(tmp_path / "ck")
    check(frl.make_model(2, 2, 2), max_depth=2, min_bucket=32,
          checkpoint_dir=ck)
    out = subprocess.run(
        [
            sys.executable, "-c",
            "import sys; sys.modules['jax'] = None\n"
            "from kafka_specification_tpu.utils.cli import main\n"
            "sys.exit(main(['verify-checkpoint', sys.argv[1], '--json']))",
            ck,
        ],
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout)
    assert rep["ok"] and rep["stores"][0]["basename"] == "bfs_checkpoint.npz"


# --- fleet supervisor (fast, jax-free children) --------------------------

_FLEET_CHILD = """
import json, os, sys, time
hb_dir = os.environ["KSPEC_SHARD_HEARTBEAT_DIR"]
pid = os.environ["JAX_PROCESS_ID"]
os.makedirs(hb_dir, exist_ok=True)
marker = os.path.join(sys.argv[1], "crashed-once")
for depth in range(4):
    with open(os.path.join(hb_dir, f"proc{pid}.jsonl"), "a") as fh:
        fh.write(json.dumps({"kind": "shard-heartbeat", "proc": int(pid),
                             "pid": os.getpid(), "depth": depth,
                             "unix": time.time()}) + "\\n")
    if pid == "1" and depth == 2 and not os.path.exists(marker):
        open(marker, "w").close()
        sys.exit(7)  # shard 1 dies mid-run, exactly once
    time.sleep(0.05)
"""


def test_fleet_supervisor_restarts_after_shard_death(tmp_path):
    """One process of the fleet dies -> the supervisor tears the whole
    fleet down and restarts it; the second attempt completes (rc 0) and
    the event log attributes the death to the process."""
    from kafka_specification_tpu.resilience.supervisor import (
        FleetConfig,
        supervise_fleet,
    )

    ev = str(tmp_path / "events.jsonl")
    cfg = FleetConfig(
        cmd=[sys.executable, "-c", _FLEET_CHILD, str(tmp_path)],
        num_processes=3,
        events=ev,
        heartbeat_dir=str(tmp_path / "shards"),
        log_dir=str(tmp_path / "logs"),
        stall_timeout=60.0,
        max_restarts=2,
        backoff_base=0.05,
        backoff_cap=0.1,
    )
    assert supervise_fleet(cfg) == 0
    events = [json.loads(l) for l in open(ev).read().splitlines()]
    kinds = [e["event"] for e in events]
    assert kinds.count("fleet-start") == 2  # initial + 1 restart
    assert "shard-exit" in kinds and "fleet-teardown" in kinds
    dead = next(e for e in events if e["event"] == "shard-exit")
    assert dead["proc"] == 1 and dead["rc"] == 7
    assert kinds[-1] == "fleet-complete"
    assert all(e["kind"] == "supervisor" for e in events)
    # per-attempt, per-process child logs landed
    logs = os.listdir(str(tmp_path / "logs"))
    assert any("proc2" in name for name in logs)


def test_fleet_supervisor_stall_kill_and_give_up(tmp_path):
    """A fleet whose processes stop heartbeating is stall-killed and the
    restart budget bounds the attempts (nonzero rc, give-up event)."""
    from kafka_specification_tpu.resilience.supervisor import (
        FleetConfig,
        supervise_fleet,
    )

    ev = str(tmp_path / "events.jsonl")
    cfg = FleetConfig(
        cmd=[sys.executable, "-c", "import time; time.sleep(600)"],
        num_processes=2,
        events=ev,
        heartbeat_dir=str(tmp_path / "shards"),
        stall_timeout=0.5,
        max_restarts=1,
        backoff_base=0.05,
        backoff_cap=0.1,
        poll=0.1,
        term_grace=2.0,
    )
    assert supervise_fleet(cfg) != 0
    events = [json.loads(l) for l in open(ev).read().splitlines()]
    kinds = [e["event"] for e in events]
    assert kinds.count("shard-stall") == 2  # initial attempt + 1 restart
    assert kinds[-1] == "fleet-give-up"


# --- cli report: died-mid-level shard attribution ------------------------


def test_report_attributes_death_to_shard(tmp_path):
    """A multiprocess run dir where one process stopped a level behind the
    others is attributed to that shard/process (pid + shard index)."""
    from kafka_specification_tpu.obs.report import render_report, report_data

    run_dir = str(tmp_path / "run")
    shards = os.path.join(run_dir, "shards")
    os.makedirs(shards)
    man = {
        "run_id": "r-test", "status": "running", "pid": 1,
        "config": {"module": "Frl", "engine": "sharded",
                   "stall_timeout": 1.0},
        "unix": 1000.0,
    }
    with open(os.path.join(run_dir, "manifest.json"), "w") as fh:
        json.dump(man, fh)
    # three processes; proc1 (shard 1, dead pid) stopped at level 5 while
    # the others reached 6
    for proc, depth in ((0, 6), (1, 5), (2, 6)):
        with open(os.path.join(shards, f"proc{proc}.jsonl"), "w") as fh:
            for d in range(depth + 1):
                fh.write(json.dumps({
                    "kind": "shard-heartbeat", "proc": proc,
                    "pid": 999999900 + proc, "shards": [proc],
                    "depth": d, "unix": 1000.0 + d,
                }) + "\n")
    data = report_data(run_dir, now=5000.0)
    assert data["verdict"]["status"] in ("stalled", "crashed")
    sp = data["shard_procs"]
    assert len(sp) == 3
    culprits = data["died_shards"]
    assert len(culprits) == 1
    assert culprits[0]["proc"] == 1 and culprits[0]["shards"] == [1]
    assert culprits[0]["pid"] == 999999901
    text = render_report(run_dir, now=5000.0)
    assert "shard(s) 1" in text and "process 1" in text
    assert "999999901" in text


# --- supervised fleet e2e (the ISSUE acceptance run; slow tier) ----------


@pytest.mark.slow
def test_fleet_e2e_kill_one_process_bit_identical(tmp_path):
    """4-process sharded run killed mid-level by crash@shard2:level:N,
    auto-restarted by the fleet supervisor, finishing with counts AND a
    full violation trace bit-identical to the fault-free run."""
    from kafka_specification_tpu.resilience.supervisor import (
        FleetConfig,
        supervise_fleet,
    )

    golden = check_sharded(_mk_violating(), mesh=_mesh(4), min_bucket=32)
    assert golden.violation is not None

    ck = str(tmp_path / "ck")
    out = str(tmp_path / "out")
    os.makedirs(out)
    worker = (
        "import json, sys\n"
        "from kafka_specification_tpu.utils.platform_guard import "
        "enable_compile_cache, pin_cpu_in_process\n"
        "pin_cpu_in_process()\n"
        "enable_compile_cache()\n"
        "from kafka_specification_tpu.parallel.multihost import "
        "init_distributed\n"
        "info = init_distributed()\n"
        "from kafka_specification_tpu.models import variants\n"
        "from kafka_specification_tpu.models.kafka_replication import Config\n"
        "from kafka_specification_tpu.parallel.sharded import check_sharded\n"
        "m = variants.make_model('KafkaTruncateToHighWatermark', "
        "Config(2, 2, 1, 1), ('TypeOk', 'WeakIsr'))\n"
        f"res = check_sharded(m, min_bucket=32, checkpoint_dir={ck!r})\n"
        "if info['process_id'] == 0:\n"
        f"    open({os.path.join(out, 'result.json')!r}, 'w').write(\n"
        "        json.dumps({'total': res.total, 'levels': res.levels,\n"
        "                    'depth': res.violation.depth,\n"
        "                    'inv': res.violation.invariant,\n"
        "                    'trace_len': len(res.violation.trace),\n"
        "                    'trace_repr': repr(res.violation.trace)}))\n"
        "sys.exit(0)\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["KSPEC_FAULT"] = "crash@shard2:level:4"
    env["KSPEC_RETRY_BASE_DELAY"] = "0.001"
    cfg = FleetConfig(
        cmd=[sys.executable, "-c", worker],
        num_processes=4,
        devices_per_proc=1,
        events=str(tmp_path / "events.jsonl"),
        heartbeat_dir=str(tmp_path / "shards"),
        log_dir=str(tmp_path / "logs"),
        stall_timeout=300.0,
        max_restarts=2,
        backoff_base=0.05,
        backoff_cap=0.1,
        env=env,
    )
    rc = supervise_fleet(cfg)
    for name in sorted(os.listdir(str(tmp_path / "logs"))):
        text = open(os.path.join(str(tmp_path / "logs"), name),
                    errors="replace").read()
        if "Multiprocess computations aren't implemented" in text:
            # see tests/test_multiprocess.py: some jaxlib builds ship an
            # XLA:CPU without cross-process collectives — environment
            # gap, not a code failure
            pytest.skip(
                "this environment's XLA:CPU backend cannot run "
                "multiprocess collectives"
            )
    assert rc == 0
    events = [json.loads(l)
              for l in open(str(tmp_path / "events.jsonl")).read().splitlines()]
    kinds = [e["event"] for e in events]
    assert kinds.count("fleet-start") == 2  # crashed once, restarted once
    assert "shard-exit" in kinds and kinds[-1] == "fleet-complete"
    final = json.loads(open(os.path.join(out, "result.json")).read())
    assert final["total"] == golden.total
    assert final["levels"] == golden.levels
    assert (final["inv"], final["depth"]) == (
        golden.violation.invariant, golden.violation.depth)
    assert final["trace_len"] == len(golden.violation.trace)
    assert final["trace_repr"] == repr(golden.violation.trace)
