"""Checking-as-a-service: queue, daemon, compile cache, batching, tenancy.

Fast tier (`service` marker).  The daemon runs IN-PROCESS here (its
public Daemon.drain_once) so the suite pays jax/XLA compiles once per
model through the normal test cache; the jax-free client contract and the
CLI e2e use subprocesses.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from kafka_specification_tpu.engine.bfs import check
from kafka_specification_tpu.models import variants
from kafka_specification_tpu.models.kafka_replication import Config
from kafka_specification_tpu.service.daemon import Daemon, ServeConfig
from kafka_specification_tpu.service.queue import JobQueue
from kafka_specification_tpu.utils.cli import main as cli_main

pytestmark = pytest.mark.service

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ID_CFG = """
SPECIFICATION Spec
CONSTANTS
    MaxId = 6
INVARIANTS TypeOk
CHECK_DEADLOCK FALSE
"""

# KafkaTruncateToHighWatermark at the TINY config: 353 states clean under
# TypeOk, WeakIsr VIOLATED at depth 8 (tests/test_variants.py) — the
# smallest real violation workload, ideal for trace-exactness checks
TTW_TINY = Config(n_replicas=2, log_size=2, max_records=1, max_leader_epoch=1)
TTW_CFG_TYPEOK = """
SPECIFICATION Spec
CONSTANTS
    Replicas = {b1, b2}
    LogSize = 2
    MaxRecords = 1
    MaxLeaderEpoch = 1
INVARIANTS TypeOk
CHECK_DEADLOCK FALSE
"""
TTW_CFG_WEAK = TTW_CFG_TYPEOK.replace(
    "INVARIANTS TypeOk", "INVARIANTS TypeOk WeakIsr"
)


def _daemon(svc_dir, **kw) -> Daemon:
    kw.setdefault("linger_s", 0.0)
    kw.setdefault("min_bucket", 32)
    # this suite pins the KERNEL-cache / batching layer: the persistent
    # state-space cache (PR 14) would short-circuit repeat jobs before
    # they ever reach it (its own suite is tests/test_fleet.py)
    kw.setdefault("state_cache", False)
    return Daemon(ServeConfig(service_dir=str(svc_dir), **kw))


def _submit_id(q: JobQueue, tenant="default", **kw) -> dict:
    return q.submit(ID_CFG, "IdSequence", tenant=tenant,
                    kernel_source="hand", **kw)


def _kill_leases(q: JobQueue, job_ids, pid=999_999_999,
                 age: float = 0.0) -> None:
    """Rewrite claim leases to simulate a dead/expired claimer (our own
    claims carry this process's live pid, which a janitor must spare)."""
    for jid in job_ids:
        with open(q._lease_path(jid), "w") as fh:
            json.dump({"pid": pid, "lease_unix": time.time() - age}, fh)


# --- queue ----------------------------------------------------------------


def test_queue_submit_claim_finish_roundtrip(tmp_path):
    q = JobQueue(str(tmp_path / "svc"))
    spec = _submit_id(q)
    jid = spec["job_id"]
    assert q.status(jid)["state"] == "pending"
    claimed = q.claim_pending()
    assert [s["job_id"] for s in claimed] == [jid]
    assert q.status(jid)["state"] == "claimed"
    assert q.claim_pending() == []  # claims are exclusive
    q.finish(jid, {"schema": "kspec-verdict/1", "job_id": jid,
                   "status": "complete", "exit_code": 0})
    st = q.status(jid)
    assert st["state"] == "done"
    assert st["result"]["exit_code"] == 0


def test_queue_orphan_requeue_and_verdict_shortcircuit(tmp_path):
    """Claims of a dead daemon requeue; a job whose verdict already
    published is retired WITHOUT re-running (at-most-once visibility)."""
    q = JobQueue(str(tmp_path / "svc"))
    j1 = _submit_id(q)["job_id"]
    j2 = _submit_id(q)["job_id"]
    q.claim_pending()
    # j1's verdict landed before the "crash"; j2's did not
    q_result = {"schema": "kspec-verdict/1", "job_id": j1,
                "status": "complete", "exit_code": 0,
                "distinct_states": 8}
    from kafka_specification_tpu.obs import atomic_write_json

    atomic_write_json(q.result_path(j1), q_result)
    # the claimer "died": stamp its leases with a dead pid (our own live
    # pid would read as a live sibling daemon and be left alone — see
    # test_janitor_spares_live_sibling_claims)
    _kill_leases(q, [j1, j2])
    # next daemon: janitor requeues both claims
    q2 = JobQueue(str(tmp_path / "svc"))
    moved = q2.requeue_orphans()
    assert sorted(moved) == sorted([j1, j2])
    d = _daemon(tmp_path / "svc")
    d.drain_once()
    # j1 kept its ORIGINAL verdict (not re-run: distinct_states marker
    # survives), j2 ran for real
    assert q2.result(j1)["distinct_states"] == 8
    assert q2.result(j2)["status"] == "complete"
    assert q2.status(j1)["state"] == "done"
    # the short-circuited verdict counts like any other published one:
    # `serve --max-jobs N` must terminate on it, not serve past it
    assert d.jobs_done == 2


def test_claim_transient_oserror_requeues_not_quarantines(
    tmp_path, monkeypatch
):
    """A transient read failure (EMFILE/EIO) on a just-claimed spec must
    put the claim back for a later sweep — never permanently fail a
    valid job with an exit-2 'bad job spec' verdict."""
    q = JobQueue(str(tmp_path / "svc"))
    jid = _submit_id(q)["job_id"]
    real_open = open
    fired = []

    def flaky_open(path, *a, **kw):
        p = str(path)
        if (not fired and os.sep + "claimed" + os.sep in p and jid in p
                and p.endswith(".json")):  # the spec read, not the lease
            fired.append(p)
            raise OSError(24, "too many open files")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", flaky_open)
    assert q.claim_pending() == []  # transient failure: nothing claimed...
    assert fired
    assert q.result(jid) is None  # ...and NO quarantine verdict published
    assert q.status(jid)["state"] == "pending"
    assert [s["job_id"] for s in q.claim_pending()] == [jid]  # next sweep


def test_janitor_spares_live_sibling_claims(tmp_path):
    """Claim leases (pid + timestamp) let a janitor tell a LIVE sibling
    daemon's in-flight claim from an orphan — the prerequisite for two
    daemons sharing one queue directory.  A live-pid fresh lease is
    spared; a dead pid or an expired lease is requeued."""
    q = JobQueue(str(tmp_path / "svc"))
    j1 = _submit_id(q)["job_id"]
    claimed = q.claim_pending()  # leaves OUR live-pid lease on j1
    assert [s["job_id"] for s in claimed] == [j1]
    lease = q.read_lease(j1)
    assert lease["pid"] == os.getpid()

    sibling = JobQueue(str(tmp_path / "svc"))  # "second daemon" starts up
    assert sibling.requeue_orphans() == []  # live sibling claim: spared
    assert q.status(j1)["state"] == "claimed"

    # the claimer wedges: its lease stops renewing and expires
    _kill_leases(q, [j1], pid=os.getpid(), age=3600.0)
    assert sibling.requeue_orphans(lease_ttl=900.0) == [j1]
    assert q.status(j1)["state"] == "pending"

    # dead pid (fresh timestamp): the crash case, requeued immediately
    j2 = _submit_id(q)["job_id"]
    q.claim_pending()
    _kill_leases(q, [j2])  # pid that cannot exist
    assert sibling.requeue_orphans() == [j2]
    assert q.status(j2)["state"] == "pending"

    # recycled pid: OUR live pid but a dead predecessor's (missing)
    # token — must read as the orphan it is, not "our own claim"
    j3 = _submit_id(q)["job_id"]
    q.claim_pending()
    _kill_leases(q, [j3], pid=os.getpid())  # fresh, our pid, no token
    assert sibling.requeue_orphans() == [j3]
    assert q.status(j3)["state"] == "pending"


def test_janitor_leaseless_claim_grace_window(tmp_path):
    """A leaseless claim is only an orphan once it has SAT there: a
    sibling writes its lease right after winning the claim rename, so a
    fresh leaseless claim must survive a concurrently-starting janitor
    (the pre-lease race this grace window closes)."""
    q = JobQueue(str(tmp_path / "svc"))
    jid = _submit_id(q)["job_id"]
    q.claim_pending()
    q._drop_lease(jid)  # simulate mid-stamp: claim renamed, lease not yet
    sibling = JobQueue(str(tmp_path / "svc"))
    assert sibling.requeue_orphans() == []  # fresh: inside the grace
    # age the claim file past the grace window -> genuine pre-lease orphan
    old = time.time() - 60.0
    os.utime(q._job_path("claimed", jid), (old, old))
    assert sibling.requeue_orphans() == [jid]


def test_renew_leases_keeps_claim_live(tmp_path):
    """The busy-heartbeat loop's lease renewal moves the timestamp, so a
    long-running job never reads as expired to a sibling."""
    q = JobQueue(str(tmp_path / "svc"))
    jid = _submit_id(q)["job_id"]
    q.claim_pending()
    _kill_leases(q, [jid], pid=os.getpid(), age=3600.0)  # nearly expired
    q.renew_leases([jid])  # what the daemon does every few seconds
    assert not JobQueue(str(tmp_path / "svc")).lease_orphaned(
        jid, lease_ttl=900.0
    )
    assert q.result(jid) is None
    q.finish(jid, {"schema": "kspec-verdict/1", "job_id": jid,
                   "status": "complete", "exit_code": 0})
    assert q.read_lease(jid) is None  # finish retires the lease sidecar


# --- kernel cache: model layer + invariant overlay ------------------------


def test_cache_split_one_model_build_for_mixed_orders(tmp_path):
    """Mixed solo/batched traffic of ONE schema shape builds ONE model:
    the solo job's .cfg-order invariants and the batched union's sorted
    invariants land as overlays over a shared model layer (shared step
    cache), not two full cache lines (ROADMAP item-3 open note)."""
    q = JobQueue(str(tmp_path / "svc"))
    d = _daemon(tmp_path / "svc")
    # solo first: cfg order (WeakIsr, TypeOk) != sorted union order
    cfg_rev = TTW_CFG_TYPEOK.replace(
        "INVARIANTS TypeOk", "INVARIANTS WeakIsr TypeOk"
    )
    j1 = q.submit(cfg_rev, "KafkaTruncateToHighWatermark",
                  kernel_source="hand")["job_id"]
    d.drain_once()
    # then a coalescing pair of the same schema shape (union = sorted)
    j2 = q.submit(TTW_CFG_WEAK, "KafkaTruncateToHighWatermark",
                  kernel_source="hand")["job_id"]
    j3 = q.submit(TTW_CFG_WEAK, "KafkaTruncateToHighWatermark",
                  kernel_source="hand")["job_id"]
    d.drain_once()
    s = d.cache.stats()
    assert s["model_layer"]["builds"] == 1  # ONE build for both orders
    assert s["model_layer"]["entries"] == 1
    assert s["model_layer"]["overlay_derives"] >= 1
    assert len(d.cache) == 2  # two thin overlays over the one base
    # the overlays share one step cache (the expensive artifact)
    entries = list(d.cache._entries.values())
    caches = {id(e["model"]._step_cache) for e in entries}
    assert len(caches) == 1
    # and every member still gets the solo-exact verdict: WeakIsr
    # violated at depth 8 (tests/test_variants.py's pinned answer)
    for j in (j1, j2, j3):
        rec = q.result(j)
        assert rec["status"] == "violation"
        assert rec["exit_code"] == 1
        assert rec["violation"]["invariant"] == "WeakIsr"
        assert rec["violation"]["depth"] == 8


def test_cache_overlay_first_violation_order(tmp_path):
    """An overlay's invariant ORDER is its own: the first-violation rule
    follows the .cfg order even when the base model was built in sorted
    order (the reordered view + column-permuted fused evaluator)."""
    from kafka_specification_tpu.service.kernel_cache import KernelCache
    from kafka_specification_tpu.utils.cfg import parse_cfg

    cache = KernelCache()
    cfg_sorted = parse_cfg(TTW_CFG_WEAK)  # TypeOk, WeakIsr (sorted)
    cfg_rev = parse_cfg(TTW_CFG_WEAK.replace(
        "INVARIANTS TypeOk WeakIsr", "INVARIANTS WeakIsr TypeOk"
    ))
    e1 = cache.get("KafkaTruncateToHighWatermark", cfg_sorted, False,
                   ("TypeOk", "WeakIsr"))
    e2 = cache.get("KafkaTruncateToHighWatermark", cfg_rev, False,
                   ("WeakIsr", "TypeOk"))
    assert cache.stats()["model_layer"]["builds"] == 1
    assert [i.name for i in e1["model"].invariants] == ["TypeOk", "WeakIsr"]
    assert [i.name for i in e2["model"].invariants] == ["WeakIsr", "TypeOk"]
    r1 = check(e1["model"], min_bucket=32, store_trace=True)
    r2 = check(e2["model"], min_bucket=32, store_trace=True)
    for r in (r1, r2):
        assert r.violation is not None
        assert r.violation.invariant == "WeakIsr"
        assert r.violation.depth == 8
    # identical counterexample trace values through the overlay view
    assert [(a, repr(s)) for a, s in r1.violation.trace] == [
        (a, repr(s)) for a, s in r2.violation.trace
    ]


def test_tenant_index_markers_retire_lazily(tmp_path):
    """Admission counting is O(the tenant's own markers): markers whose
    pending spec moved on (claimed/finished) are lazily removed."""
    q = JobQueue(str(tmp_path / "svc"))
    _submit_id(q, tenant="acme")
    _submit_id(q, tenant="acme")
    _submit_id(q, tenant="other")
    assert q.pending_for_tenant("acme") == 2
    assert q.pending_for_tenant("other") == 1
    assert q.pending_for_tenant("acme", stop_at=1) == 1
    q.claim_pending()  # everything leaves pending/
    assert q.pending_for_tenant("acme") == 0
    assert os.listdir(q._tenant_dir("acme")) == []  # stale markers gone
    assert q.pending_for_tenant("nonexistent") == 0


def test_tenant_max_pending_admission(tmp_path):
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    (svc / "tenants.json").write_text(
        json.dumps({"capped": {"max_pending": 1}})
    )
    cfg_path = tmp_path / "IdSequence.cfg"
    cfg_path.write_text(ID_CFG)
    rc1 = cli_main(["submit", str(cfg_path), "--service-dir", str(svc),
                    "--tenant", "capped", "--hand"])
    rc2 = cli_main(["submit", str(cfg_path), "--service-dir", str(svc),
                    "--tenant", "capped", "--hand"])
    assert rc1 == 0 and rc2 == 2  # second submit rejected at the cap
    assert q.pending_for_tenant("capped") == 1


# --- daemon: warm path, batching, verdict fidelity ------------------------


def test_daemon_end_to_end_and_warm_second_job(tmp_path):
    """Job 1 of a shape compiles (compile spans in its trace); job 2 of
    the same shape rides the shape-keyed cache: zero compile spans, and
    its manifest records the cache hit — the serving warm-path proof."""
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    d = _daemon(svc)
    j1 = _submit_id(q)["job_id"]
    assert d.drain_once() == 1
    j2 = _submit_id(q)["job_id"]
    assert d.drain_once() == 1

    for jid in (j1, j2):
        rec = q.result(jid)
        assert rec["schema"] == "kspec-verdict/1"
        assert rec["status"] == "complete"
        assert rec["distinct_states"] == 8  # MaxId=6 -> 0..7
        assert rec["exit_code"] == 0
        assert rec["timing"]["latency_s"] is not None

    assert len(_compile_spans(q, j1)) > 0  # cold shape: compiles visible
    assert _compile_spans(q, j2) == []  # warm shape: ZERO compile spans
    man2 = json.load(open(os.path.join(q.run_dir(j2), "manifest.json")))
    assert man2["config"]["service"]["cache_hit"] is True
    assert d.cache.stats()["hits"] == 1


def test_compile_explore_split_reads_the_process_ledger(tmp_path):
    """`compile_ms` of a job's run is what the PROCESS spent building over
    it, by the process ledger's growth (obs/ledger.py): the model and its
    prepared kernels AND every program the engine traced, compiled or
    loaded inside `check`, which the kernel cache's `build_s` never held
    (a cold shape's compiles were booked to `explore`).  Cold job of a
    shape: compile > 0 and more than the model build; warm job of the
    same shape: exactly 0.  Same names and units: `svc-run.compile_ms`,
    `kspec_svc_stage_compile_ms`, `cli trace`'s stage table."""
    from kafka_specification_tpu.obs import fleettrace

    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    d = _daemon(svc)
    cfg = ID_CFG.replace("MaxId = 6", "MaxId = 11")  # a shape of its own
    runs = []
    for _ in range(2):
        jid = q.submit(cfg, "IdSequence", kernel_source="hand")["job_id"]
        assert d.drain_once() == 1
        (run,) = [s for s in fleettrace.load_trace([str(svc)], jid)
                  if s.get("span") == "svc-run"]
        stages = fleettrace.stage_decomposition(
            fleettrace.assemble(fleettrace.load_trace([str(svc)], jid),
                                jid)["spans"])
        assert stages["compile"] == run["compile_ms"]
        runs.append((run, _compile_spans(q, jid)))
    (cold, cold_spans), (warm, warm_spans) = runs
    assert cold["cache_hit"] is False and warm["cache_hit"] is True
    first_calls_ms = sum(s["ms"] for s in cold_spans)
    assert cold_spans and cold["compile_ms"] >= first_calls_ms > 0
    assert cold["compile_ms"] <= cold["ms"]  # explore = run - compile >= 0
    assert warm_spans == [] and warm["compile_ms"] == 0.0
    hist = d.metrics.snapshot()["histograms"]["kspec_svc_stage_compile_ms"]
    assert hist["count"] == 1  # the warm job observed no compile stage


def _compile_spans(q: JobQueue, jid: str) -> list:
    path = os.path.join(q.run_dir(jid), "spans.jsonl")
    with open(path) as fh:
        spans = [json.loads(line) for line in fh]
    return [s for s in spans if s.get("span") == "compile"]


def test_warm_zero_compiles_even_after_capacity_growth(tmp_path):
    """A cold run that GROWS the device visited set evicts the steps
    compiled at outgrown capacities; the daemon's post-run rewarm
    re-compiles them at the new fixed point, so the SECOND job of the
    shape still shows zero compile spans (the warm-path contract is not
    limited to shapes that fit their initial preallocation)."""
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    d = _daemon(svc)
    j1 = q.submit(TTW_CFG_WEAK, "KafkaTruncateToHighWatermark",
                  kernel_source="hand")["job_id"]
    assert d.drain_once() == 1
    cold = _compile_spans(q, j1)
    # the premise: this shape outgrows its initial vcap mid-run (compile
    # spans at >= 2 capacities).  If engine sizing ever changes so it no
    # longer grows, swap in a config that does — the test exists to pin
    # the post-growth rewarm.
    # (the start-and-finish programs `init` / `hinv` embed no visited set)
    assert len({s["vcap"] for s in cold if "vcap" in s}) >= 2
    j2 = q.submit(TTW_CFG_WEAK, "KafkaTruncateToHighWatermark",
                  kernel_source="hand")["job_id"]
    assert d.drain_once() == 1
    assert _compile_spans(q, j2) == []
    assert q.result(j2)["violation"]["depth"] == 8


KIP320_3B_CFG = """
SPECIFICATION Spec
CONSTANTS
    Replicas = {b1, b2, b3}
    LogSize = 2
    MaxRecords = 2
    MaxLeaderEpoch = 2
INVARIANTS TypeOk LeaderInIsr WeakIsr StrongIsr
CHECK_DEADLOCK FALSE
"""


def test_warm_device_pipeline_job_discards_no_dispatch(tmp_path,
                                                       monkeypatch):
    """The warm-path contract on the device pipeline: the first solo job
    of a shape sizes its whole-level program from high waters that start
    at zero, overflows and re-dispatches; the daemon feeds what the run
    measured back (note_result) and rewarms, so the SECOND job of the
    shape dispatches every level program once and still shows zero
    compile spans.  The daemon's gate is the production one (4,096
    rows), so the job is Kip320 at 3 brokers cut where its frontier
    first passes 2,048 rows (depth 7: 2,715 rows)."""
    monkeypatch.setenv("KSPEC_PIPELINE", "device")
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    d = _daemon(svc, min_bucket=256)

    def job():
        jid = q.submit(KIP320_3B_CFG, "Kip320", kernel_source="hand",
                       max_depth=7, solo=True)["job_id"]
        assert d.drain_once() == 1
        with open(os.path.join(q.run_dir(jid), "spans.jsonl")) as fh:
            spans = [json.loads(line) for line in fh]
        levels = [s for s in spans if s.get("span") == "dispatch"
                  and s.get("ph") != "B" and s.get("program") == "dvl"]
        return jid, levels

    j1, cold = job()
    j2, warm = job()
    assert [s for s in cold if s.get("discarded")]  # the premise
    assert len(_compile_spans(q, j1)) > 0
    assert warm and not [s for s in warm if s.get("discarded")]
    assert [s["attempt"] for s in warm] == [0] * len(warm)
    assert _compile_spans(q, j2) == []
    for jid in (j1, j2):
        rec = q.result(jid)
        assert rec["status"] == "complete"
        assert rec["distinct_states"] == 10099  # levels 0-7 of the golden
    assert q.result(j1)["levels"] == q.result(j2)["levels"]


def test_batched_group_bit_identical_to_solo(tmp_path):
    """Jobs sharing a schema shape but differing in invariant selection
    and depth bounds coalesce into ONE engine run; every member's verdict
    — counts AND violation trace values — equals its solo `cli check`."""
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    jobs = {
        "typeok": q.submit(TTW_CFG_TYPEOK, "KafkaTruncateToHighWatermark",
                           kernel_source="hand"),
        "weak": q.submit(TTW_CFG_WEAK, "KafkaTruncateToHighWatermark",
                         kernel_source="hand"),
        "depth5": q.submit(TTW_CFG_WEAK, "KafkaTruncateToHighWatermark",
                           kernel_source="hand", max_depth=5),
    }
    d = _daemon(svc)
    assert d.drain_once() == 3
    # one group, one engine run: 3 batched jobs, 1 cache build
    assert d.groups_run == 1
    assert d.cache.stats()["misses"] == 1

    solo = {
        "typeok": check(
            variants.make_model("KafkaTruncateToHighWatermark", TTW_TINY,
                                invariants=("TypeOk",)),
            min_bucket=32,
        ),
        "weak": check(
            variants.make_model("KafkaTruncateToHighWatermark", TTW_TINY,
                                invariants=("TypeOk", "WeakIsr")),
            min_bucket=32,
        ),
        "depth5": check(
            variants.make_model("KafkaTruncateToHighWatermark", TTW_TINY,
                                invariants=("TypeOk", "WeakIsr")),
            min_bucket=32,
            max_depth=5,
        ),
    }
    assert solo["weak"].violation is not None  # the known depth-8 WeakIsr

    for name, job in jobs.items():
        rec = q.result(job["job_id"])
        s = solo[name]
        assert rec["levels"] == s.levels, name
        assert rec["distinct_states"] == s.total, name
        assert rec["diameter"] == s.diameter, name
        assert rec["batch"]["group_size"] == 3, name
        if s.violation is None:
            assert rec["violation"] is None, name
        else:
            assert rec["violation"]["invariant"] == s.violation.invariant
            assert rec["violation"]["depth"] == s.violation.depth
            assert rec["violation"]["trace_len"] == len(s.violation.trace)
            # the verdict carries trace_len only: the rendered trace is
            # the run directory's record, equal to the solo rendering
            # under the .cfg's own replica names
            from kafka_specification_tpu.utils.cfg import build_model, parse_cfg
            from kafka_specification_tpu.utils.pretty import render_trace

            meta = build_model(
                "KafkaTruncateToHighWatermark", parse_cfg(TTW_CFG_WEAK)
            ).meta
            cex = os.path.join(q.run_dir(job["job_id"]), "counterexample.txt")
            with open(cex) as fh:
                assert fh.read() == render_trace(
                    meta, s.violation.trace
                ) + "\n", name
    # trace VALUES: replay the batched runner directly against solo
    from kafka_specification_tpu.engine.bfs import prepare
    from kafka_specification_tpu.service.batch import Member, run_group

    union = variants.make_model(
        "KafkaTruncateToHighWatermark", TTW_TINY,
        invariants=("TypeOk", "WeakIsr"),
    )
    derived, _shared = run_group(
        union,
        [Member("weak", ("TypeOk", "WeakIsr"))],
        prepared=prepare(union),
        min_bucket=32,
    )
    dv = derived["weak"].violation
    sv = solo["weak"].violation
    assert [a for a, _s in dv.trace] == [a for a, _s in sv.trace]
    assert [s_ for _a, s_ in dv.trace] == [s_ for _a, s_ in sv.trace]


def test_tenant_budget_breach_is_typed_and_isolated(tmp_path):
    """A job breaching its per-tenant budget exits THAT job rc-75 typed;
    sibling tenants' jobs and the daemon itself are untouched."""
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    # tenant "starved" gets an impossible deadline: every level is
    # instantly late (the deterministic breach the resource suite uses)
    (svc / "tenants.json").write_text(
        json.dumps({"starved": {"level_deadline": 0}})
    )
    j_ok = _submit_id(q, tenant="healthy")["job_id"]
    j_bad = _submit_id(q, tenant="starved")["job_id"]
    d = _daemon(svc)
    assert d.drain_once() == 2
    bad = q.result(j_bad)
    assert bad["status"] == "resource-exhausted"
    assert bad["exit_code"] == 75
    assert "RESOURCE_EXHAUSTED[deadline]" in bad["error"]
    ok = q.result(j_ok)
    assert ok["status"] == "complete" and ok["exit_code"] == 0
    # the daemon survives and keeps serving
    j_next = _submit_id(q, tenant="healthy")["job_id"]
    assert d.drain_once() == 1
    assert q.result(j_next)["status"] == "complete"


def test_bad_job_is_error_verdict_not_daemon_death(tmp_path):
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    j_bad = q.submit("CONSTANTS\n  MaxId = 3\n", "NoSuchModule",
                     kernel_source="hand")["job_id"]
    j_ok = _submit_id(q)["job_id"]
    d = _daemon(svc)
    assert d.drain_once() == 2
    bad = q.result(j_bad)
    assert bad["status"] == "error" and bad["exit_code"] == 2
    assert q.result(j_ok)["status"] == "complete"


def test_malformed_fault_plan_is_error_verdict_not_daemon_death(tmp_path):
    """`cli submit` pre-validates --fault, but the queue API does not: a
    spec carrying an unparsable plan must cost THAT job an error verdict
    (FaultPlan raising inside the daemon), never crash the daemon into
    the janitor-requeue -> identical-crash loop."""
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    j_bad = _submit_id(q, fault="bogus@x")["job_id"]
    j_ok = _submit_id(q)["job_id"]
    d = _daemon(svc)
    assert d.drain_once() == 2
    bad = q.result(j_bad)
    assert bad["status"] == "error" and bad["exit_code"] == 2
    assert "cannot start job" in bad["error"]
    assert q.result(j_ok)["status"] == "complete"


# --- jax-free client contract ---------------------------------------------


def test_client_commands_are_jax_free(tmp_path):
    """submit/status/result (and the no-arg report index) run with jax
    imports POISONED — the tenant side never pays the jax cold start."""
    svc = str(tmp_path / "svc")
    cfg_path = tmp_path / "IdSequence.cfg"
    cfg_path.write_text(ID_CFG)

    def client(*argv):
        return subprocess.run(
            [
                sys.executable, "-c",
                "import sys; sys.modules['jax'] = None; "
                "sys.modules['jaxlib'] = None\n"
                "from kafka_specification_tpu.utils.cli import main\n"
                "sys.exit(main(sys.argv[1:]))",
                *argv,
            ],
            cwd=_REPO,
            capture_output=True,
            text=True,
            timeout=60,
        )

    out = client("submit", str(cfg_path), "--service-dir", svc, "--hand",
                 "--json")
    assert out.returncode == 0, out.stderr[-2000:]
    jid = json.loads(out.stdout)["job_id"]

    out = client("status", jid, "--service-dir", svc, "--json")
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout)["state"] == "pending"

    # verdict published (by a daemon elsewhere); result reads it jax-free
    q = JobQueue(svc)
    q.claim_pending()
    q.finish(jid, {"schema": "kspec-verdict/1", "job_id": jid,
                   "status": "complete", "exit_code": 0, "model": "X",
                   "distinct_states": 1, "diameter": 0, "levels": [1],
                   "states_per_sec": 1.0, "seconds": 0.1,
                   "violation": None})
    out = client("result", jid, "--service-dir", svc, "--json")
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout)["exit_code"] == 0

    out = client("report", "--root", str(tmp_path / "no-runs"))
    assert out.returncode == 0, out.stderr[-2000:]

    # read-only clients must ERROR on a mistyped service dir, never mint
    # an empty service tree that masks the typo as "no such job"
    out = client("status", "--service-dir", str(tmp_path / "typo"))
    assert out.returncode == 2
    assert "no service directory" in out.stderr
    assert not (tmp_path / "typo").exists()


def test_result_exit_codes_follow_verdict(tmp_path):
    q = JobQueue(str(tmp_path / "svc"))
    q.finish("job-x", {"schema": "kspec-verdict/1", "job_id": "job-x",
                       "status": "violation", "exit_code": 1})
    rc = cli_main(["result", "job-x", "--service-dir",
                   str(tmp_path / "svc"), "--json"])
    assert rc == 1
    rc = cli_main(["result", "job-missing", "--service-dir",
                   str(tmp_path / "svc")])
    assert rc == 2


# --- verdict schema shared with `cli check --json` ------------------------


def test_check_json_is_stable_verdict_schema(tmp_path, capsys):
    rc = cli_main(["check", "configs/IdSequence.cfg", "--json",
                   "--run-dir", str(tmp_path / "run")])
    rec = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert rec["schema"] == "kspec-verdict/1"
    assert rec["distinct_states"] == 12
    assert rec["exit_code"] == 0
    assert rec["run_id"]  # correlates the verdict to its run dir
    assert rec["violation"] is None


# --- report index ---------------------------------------------------------


def test_report_index_and_latest(tmp_path, capsys):
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    jid = _submit_id(q)["job_id"]
    _daemon(svc).drain_once()
    root = str(svc / "runs")
    rc = cli_main(["report", "--root", root, "--json"])
    rows = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert len(rows) == 1
    assert rows[0]["status"] == "complete"
    assert rows[0]["service"] == jid
    rc = cli_main(["report", "--latest", "--root", root])
    out = capsys.readouterr().out
    assert rc == 0
    assert "service: job " + jid in out
    assert "[COMPLETE]" in out
    # empty root: friendly listing, not a crash
    rc = cli_main(["report", "--root", str(tmp_path / "none")])
    assert rc == 0


# --- CLI serve e2e (one real daemon subprocess) ---------------------------


def test_cli_serve_subprocess_e2e(tmp_path):
    """Full CLI path: daemon subprocess drains a submitted job; the
    client submits with --wait and inherits the verdict's exit code."""
    svc = str(tmp_path / "svc")
    cfg_path = tmp_path / "IdSequence.cfg"
    cfg_path.write_text(ID_CFG)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    daemon = subprocess.Popen(
        [sys.executable, "-m", "kafka_specification_tpu.utils.cli",
         "serve", svc, "--max-jobs", "1", "--idle-exit", "60",
         "--min-bucket", "32"],
        cwd=_REPO,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )
    try:
        out = subprocess.run(
            [sys.executable, "-m", "kafka_specification_tpu.utils.cli",
             "submit", str(cfg_path), "--service-dir", svc, "--hand",
             "--wait", "--timeout", "240", "--json"],
            cwd=_REPO,
            env=env,
            capture_output=True,
            text=True,
            timeout=300,
        )
        assert out.returncode == 0, (out.stdout, out.stderr[-2000:])
        rec = json.loads(out.stdout.splitlines()[-1])
        assert rec["status"] == "complete"
        assert rec["distinct_states"] == 8
        daemon.wait(timeout=120)  # --max-jobs 1: exits after the verdict
        assert daemon.returncode == 0
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()


# --- concurrency: many submitters against one live daemon ----------------


def test_concurrent_submitters_coalesce(tmp_path):
    """A burst of concurrent submitters sharing one schema shape is
    served by far fewer engine runs than jobs (the batched economics the
    serve bench banks at full scale)."""
    svc = tmp_path / "svc"
    q = JobQueue(str(svc))
    d = _daemon(svc, linger_s=0.05)
    # warm the shape first so the burst measures batching, not compiles
    _submit_id(q)
    d.drain_once()
    n = 12
    ids = []
    lock = threading.Lock()

    def submit():
        spec = _submit_id(q)
        with lock:
            ids.append(spec["job_id"])

    threads = [threading.Thread(target=submit) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    groups_before = d.groups_run
    t0 = time.perf_counter()
    done = 0
    while done < n and time.perf_counter() - t0 < 120:
        done += d.drain_once()
    assert done == n
    for jid in ids:
        assert q.result(jid)["status"] == "complete"
    # 12 jobs cost at most a couple of engine runs, not 12
    assert d.groups_run - groups_before <= 3
    # one cold build total (the warmup); every burst group hit the cache
    assert d.cache.stats()["misses"] == 1
    assert d.cache.stats()["hits"] >= 1


# --- two daemons sharing one queue directory (ROADMAP item 3 open) --------


def test_two_daemons_one_queue_exactly_once(tmp_path):
    """TWO `cli serve` processes drain ONE queue directory concurrently:
    every job is executed exactly once (lease-guarded claims — neither
    daemon steals the other's live work) and every verdict is correct.
    Closes the PR 7 open in ROADMAP item 3 (the claim-lease machinery
    existed; the actual two-daemon e2e did not)."""
    svc = str(tmp_path / "svc")
    n_jobs = 6
    q = JobQueue(svc)
    ids = [_submit_id(q)["job_id"] for _ in range(n_jobs)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    daemons = [
        subprocess.Popen(
            [sys.executable, "-m", "kafka_specification_tpu.utils.cli",
             "serve", svc, "--idle-exit", "8", "--min-bucket", "32",
             "--no-batching"],
            cwd=_REPO,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for _ in range(2)
    ]
    try:
        t0 = time.time()
        while time.time() - t0 < 240:
            if all(q.result(j) is not None for j in ids):
                break
            if all(d.poll() is not None for d in daemons):
                break  # both exited (idle or crash): stop waiting
            time.sleep(0.5)
        outs = []
        for d in daemons:
            try:
                out, _ = d.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                d.kill()
                out, _ = d.communicate()
            outs.append(out.decode(errors="replace"))
        # every job exactly-once with a correct verdict
        for j in ids:
            rec = q.result(j)
            assert rec is not None, (j, outs[0][-2000:], outs[1][-2000:])
            assert rec["status"] == "complete", rec
            assert rec["distinct_states"] == 8, rec
        # exactly-once execution: the done/ records are the only copies —
        # no job may still be claimed or pending, and each daemon exited
        # clean after its idle window
        ov = q.overview()
        assert ov["counts"]["pending"] == 0
        assert ov["counts"]["claimed"] == 0
        assert ov["counts"]["done"] == n_jobs
        for d, out in zip(daemons, outs):
            assert d.returncode == 0, out[-2000:]
        # exactly-once across BOTH daemons: the per-daemon daemon-stop
        # events record how many verdicts each produced; they must sum to
        # the job count (one daemon winning every race is legal — double
        # execution is not)
        stops = [
            json.loads(line)
            for line in open(
                os.path.join(svc, "service", "events.jsonl")
            ).read().splitlines()
            if '"daemon-stop"' in line or '"daemon-max-jobs"' in line
        ]
        if stops:
            assert sum(e.get("jobs", 0) for e in stops
                       if e.get("event") == "daemon-stop") == n_jobs
    finally:
        for d in daemons:
            if d.poll() is None:
                d.kill()
                d.wait()
