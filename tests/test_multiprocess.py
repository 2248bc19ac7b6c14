"""Real multi-process (DCN-regime) execution of the sharded engine.

N OS processes join one jax.distributed job on localhost (the same
`jax.distributed.initialize` path a TPU pod uses, with the coordinator on
127.0.0.1 and 1-2 virtual CPU devices per process).  Every process runs
the identical replicated host loop (parallel/multihost.py) and must agree
on exact distinct-state counts — through BOTH visited backends:

- device: per-shard sorted sets in (virtual) device memory;
- host: per-HOST FpSet ownership — each process keeps C++ sets only for
  the shards whose devices it hosts, and the novelty masks are OR-merged
  across processes (multihost.or_across_processes).

Coverage (VERDICT r2 item 5 + r3 item 5): 2 processes x 2 devices, 4
processes x 1 device (one owned shard per process — the TLC distributed-
mode shape), and a 4-process checkpoint/resume cycle across two separate
jax.distributed jobs (coordinator-only main file + per-host part files).
Slow marker: each fresh interpreter pays its own XLA compile chain.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import json, sys
from kafka_specification_tpu.utils.platform_guard import (
    enable_compile_cache, pin_cpu_in_process)
pin_cpu_in_process()
enable_compile_cache()
cfg = json.loads(sys.argv[1])
from kafka_specification_tpu.parallel.multihost import init_distributed
info = init_distributed()
from kafka_specification_tpu.models import finite_replicated_log as frl
from kafka_specification_tpu.parallel.sharded import check_sharded
model = frl.make_model(3, 4, cfg["max_records"])
res = check_sharded(model, min_bucket=64, store_trace=False,
                    visited_backend=cfg["backend"],
                    max_depth=cfg.get("max_depth"),
                    checkpoint_dir=cfg.get("ckpt"))
print("RESULT " + json.dumps({
    "pid": info["process_id"], "procs": info["process_count"],
    "devices": info["global_devices"], "total": res.total,
    "levels": res.levels, "ok": res.ok,
    "host_sizes": res.stats.get("host_fpset_sizes"),
}))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_procs(worker_cfg: dict, n_procs: int = 2, devs_per_proc: int = 2):
    port = _free_port()
    procs = []
    for pid in range(n_procs):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devs_per_proc}"
        )
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = str(n_procs)
        env["JAX_PROCESS_ID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _WORKER, json.dumps(worker_cfg)],
                env=env,
                cwd=_REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        if p.returncode != 0 and (
            "Multiprocess computations aren't implemented" in err
        ):
            # some jaxlib builds ship an XLA:CPU without cross-process
            # collectives (observed: jax 0.4.37 in this container) — the
            # multi-process regime is then untestable here at all, which
            # is an environment gap, not a code failure
            for q in procs:
                q.kill()
            pytest.skip(
                "this environment's XLA:CPU backend cannot run "
                "multiprocess collectives"
            )
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert line, f"no RESULT line:\n{out[-1000:]}\n{err[-2000:]}"
        outs.append(json.loads(line[-1][len("RESULT "):]))
    return outs


def test_two_process_device_backend_exact_counts():
    """FRL (3,4,1) = 125 states: both processes of a 2-process / 4-device
    job report the identical exhaustive result."""
    outs = _run_procs({"backend": "device", "max_records": 1})
    for o in outs:
        assert o["procs"] == 2 and o["devices"] == 4
        assert o["ok"] and o["total"] == 125
    assert outs[0]["levels"] == outs[1]["levels"]
    assert {o["pid"] for o in outs} == {0, 1}


def test_two_process_host_fpset_per_host_ownership():
    """FRL (3,4,2) = 29,791 states through the per-host-owned C++ FpSets:
    exact global count on both processes, and each process holds sets ONLY
    for its own 2 of the 4 shards (the other entries are None) — inserts
    are no longer replicated per process."""
    outs = _run_procs({"backend": "host", "max_records": 2})
    for o in outs:
        assert o["ok"] and o["total"] == 29791
        sizes = o["host_sizes"]
        assert len(sizes) == 4
        owned = [s for s in sizes if s is not None]
        assert len(owned) == 2  # 2 local devices -> 2 owned shards
    # the two processes own disjoint shard halves and together cover all
    # 29,791 fingerprints exactly once
    merged = [
        a if a is not None else b
        for a, b in zip(outs[0]["host_sizes"], outs[1]["host_sizes"])
    ]
    assert sum(merged) == 29791
    assert all(
        (a is None) != (b is None)
        for a, b in zip(outs[0]["host_sizes"], outs[1]["host_sizes"])
    )


def test_four_process_single_device_each_exact_counts():
    """4 processes x 1 device — the TLC distributed-mode shape (one owned
    shard per process, every exchange crossing a process boundary): exact
    29,791-state agreement on all four processes, per-host FpSet ownership
    covering each shard exactly once."""
    outs = _run_procs(
        {"backend": "host", "max_records": 2}, n_procs=4, devs_per_proc=1
    )
    assert {o["pid"] for o in outs} == {0, 1, 2, 3}
    for o in outs:
        assert o["procs"] == 4 and o["devices"] == 4
        assert o["ok"] and o["total"] == 29791
        sizes = o["host_sizes"]
        assert len(sizes) == 4
        assert len([s for s in sizes if s is not None]) == 1
        assert sizes[o["pid"]] is not None  # owns exactly its own shard
    assert len({tuple(o["levels"]) for o in outs}) == 1
    assert sum(o["host_sizes"][o["pid"]] for o in outs) == 29791


def test_four_to_two_process_elastic_resume(tmp_path):
    """ELASTIC resume across process counts: a checkpoint written by a
    4-process / 4-shard job (per-host FpSet part files host0..host3) is
    resumed by a 2-process / 2-shard job — every old host's part is read,
    fingerprint-range ownership is re-bucketed onto the new layout, and
    the resumed job completes to the exact global count."""
    ckdir = str(tmp_path / "eck")
    partial = _run_procs(
        {"backend": "host", "max_records": 2, "ckpt": ckdir, "max_depth": 6},
        n_procs=4,
        devs_per_proc=1,
    )
    assert all(o["total"] < 29791 for o in partial)
    resumed = _run_procs(
        {"backend": "host", "max_records": 2, "ckpt": ckdir},
        n_procs=2,
        devs_per_proc=1,
    )
    for o in resumed:
        assert o["procs"] == 2 and o["devices"] == 2
        assert o["ok"] and o["total"] == 29791
        assert len(o["host_sizes"]) == 2
    assert sum(o["host_sizes"][o["pid"]] for o in resumed) == 29791


def test_four_process_checkpoint_resume(tmp_path):
    """Checkpoint under one 4-process job, resume under a SECOND 4-process
    job: the coordinator writes the single main checkpoint, every process
    writes its own host-FpSet part file, and the resumed job completes to
    the exact global count (all-process resume, VERDICT r3 item 5)."""
    ckdir = str(tmp_path / "mck")
    partial = _run_procs(
        {"backend": "host", "max_records": 2, "ckpt": ckdir, "max_depth": 6},
        n_procs=4,
        devs_per_proc=1,
    )
    assert all(o["total"] < 29791 for o in partial)
    files = sorted(os.listdir(ckdir))
    assert "sharded_checkpoint.npz" in files  # coordinator's main file
    for pid in range(4):  # per-host part files (per-host set ownership)
        assert f"sharded_checkpoint.npz.host{pid}" in files
    resumed = _run_procs(
        {"backend": "host", "max_records": 2, "ckpt": ckdir},
        n_procs=4,
        devs_per_proc=1,
    )
    for o in resumed:
        assert o["ok"] and o["total"] == 29791
    assert sum(o["host_sizes"][o["pid"]] for o in resumed) == 29791
