"""Supervised auto-resume runner (replaces scripts/supervise_prod464.sh).

Spawns a run as a child process, watches its per-level JSONL heartbeat
(the engines' --stats / stats_path stream), kills the child when the
heartbeat stalls past --stall-timeout (a hang, the failure mode a bash
restart loop never notices), and restarts from the engine checkpoint
with a bounded restart budget and jittered exponential backoff.  One
heartbeat-enveloped JSONL event lands in --events per transition
(start / stall-kill / exit / restart / complete / give-up).

The child owns its resume: the engines restart from --checkpoint
automatically (hardened keep-last-K checkpoints, resilience.checkpoints),
so "restart" is exactly "run the same command again".

Usage:

    # supervise any command (after --); heartbeat = its stats JSONL
    python scripts/resilient_run.py --heartbeat RUN_stats.jsonl \\
        --events EVENTS.jsonl --stall-timeout 1800 --max-restarts 8 -- \\
        python -m kafka_specification_tpu.utils.cli check configs/Kip320.cfg \\
            --checkpoint .ckpt --stats RUN_stats.jsonl

    # the half-billion mixed464 product run the bash supervisor drove
    # (round-5 verdict item 5): same env pins, Python watchdog
    python scripts/resilient_run.py --preset prod464

    # fleet mode: supervise a whole 4-process jax.distributed sharded
    # run (one dead/stalled process tears down and restarts the fleet
    # from the newest cross-shard-consistent checkpoint generation)
    python scripts/resilient_run.py --fleet 4 --devices-per-proc 1 -- \\
        python -m kafka_specification_tpu.utils.cli check \\
            configs/Kip320.cfg --sharded --cpu --checkpoint .ckpt

This script never imports jax (a parent that touched JAX would hold the
accelerator its child needs).
"""

import argparse
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from kafka_specification_tpu.obs import RunContext  # noqa: E402 (jax-free)
from kafka_specification_tpu.resilience.supervisor import (  # noqa: E402
    SupervisorConfig,
    supervise,
)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="supervised auto-resume runner",
        usage="%(prog)s [options] [--preset prod464 | -- CMD ...]",
    )
    ap.add_argument(
        "--run-dir",
        help="obs run directory (default: runs/<run_id>/) — the manifest, "
        "supervisor events, per-attempt logs, and (when the child doesn't "
        "say otherwise) the heartbeat all land here, correlated by one "
        "run_id; render with `cli report` (docs/observability.md)",
    )
    ap.add_argument(
        "--heartbeat",
        help="JSONL file the child appends progress to (growth = liveness; "
        "default: <run-dir>/stats.jsonl)",
    )
    ap.add_argument(
        "--events",
        help="supervisor JSONL event log (default: <run-dir>/events.jsonl)",
    )
    ap.add_argument(
        "--log-dir",
        help="directory for per-attempt child stdout/stderr logs "
        "(default: <run-dir>/logs/)",
    )
    ap.add_argument(
        "--stall-timeout",
        type=float,
        default=1800.0,
        help="kill the child after this many seconds without heartbeat "
        "growth (default 1800).  The heartbeat is one line per BFS level: "
        "set this ABOVE the longest level you expect, or a healthy "
        "mid-level run reads as a stall",
    )
    ap.add_argument("--max-restarts", type=int, default=8)
    ap.add_argument("--backoff", type=float, default=5.0)
    ap.add_argument("--backoff-cap", type=float, default=300.0)
    ap.add_argument(
        "--reclaim",
        action="store_true",
        help="on a child RESOURCE_EXHAUSTED exit (code 75: full disk / "
        "breached budget, checkpointed clean — docs/resilience.md), prune "
        "stale tmp files + rotated checkpoint generations under "
        "--reclaim-dir and retry EXACTLY once.  Default: halt with an "
        "actionable verdict; the supervisor never hot-loops restarts "
        "into an unreclaimed full disk",
    )
    ap.add_argument(
        "--reclaim-dir",
        action="append",
        default=[],
        metavar="DIR",
        help="directory the --reclaim sweep prunes (repeatable; typically "
        "the checkpoint and spill dirs)",
    )
    ap.add_argument(
        "--fleet",
        type=int,
        metavar="P",
        help="supervise a P-process jax.distributed fleet: the command "
        "after -- is launched P times (JAX_COORDINATOR_ADDRESS / "
        "JAX_NUM_PROCESSES / JAX_PROCESS_ID injected, fresh coordinator "
        "port per attempt).  Per-process shard heartbeats land in "
        "<run-dir>/shards/ (KSPEC_SHARD_HEARTBEAT_DIR); a dead or "
        "stalled process tears the WHOLE fleet down and restarts it from "
        "the newest cross-shard-consistent checkpoint generation",
    )
    ap.add_argument(
        "--devices-per-proc",
        type=int,
        help="[--fleet] virtual CPU devices per process "
        "(--xla_force_host_platform_device_count; for CI/rehearsal "
        "fleets without real accelerators)",
    )
    ap.add_argument(
        "--preset",
        choices=["prod464"],
        help="prod464: the half-billion mixed464 exact product "
        "(run_product_tiny3.py --base mixed464, uniform compact path, "
        "checkpoint in $KSPEC_PROD_CKPT)",
    )
    ap.add_argument(
        "--mem-budget",
        help="[--preset] host fingerprint-set byte budget (K/M/G "
        "suffixes): re-run the preset out-of-core through the disk tier "
        "(exported as KSPEC_PROD_MEMBUDGET to the child)",
    )
    ap.add_argument(
        "--spill-dir",
        help="[--preset] disk-tier directory for the preset child "
        "(exported as KSPEC_PROD_SPILL)",
    )
    ap.add_argument(
        "cmd",
        nargs=argparse.REMAINDER,
        metavar="-- CMD ...",
        help="child command (everything after --)",
    )
    args = ap.parse_args(argv)

    env = dict(os.environ)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    # one run_id for the whole supervised run: the manifest records the
    # command + restart lineage, the events/heartbeat/logs live together,
    # and `cli report <run-dir>` renders the result (legacy repo-root
    # RUN*/TPU_* artifact paths remain honored when passed explicitly)
    run_ctx = RunContext(args.run_dir)
    heartbeat = args.heartbeat
    if args.preset == "prod464":
        if cmd:
            ap.error("--preset and an explicit command are mutually exclusive")
        # the env pins the bash supervisor exported, reproduced here
        env.setdefault("KSPEC_PROD_CKPT", os.path.join(_REPO, ".prod464_ckpt"))
        env.setdefault("KSPEC_ADAPTIVE_COMPACT", "0")  # known-good config
        # watch the SAME path the child writes: a pre-set KSPEC_PROD_STATS
        # wins over both the --heartbeat flag and the run-dir default
        heartbeat = (
            env.get("KSPEC_PROD_STATS") or heartbeat or run_ctx.stats_path
        )
        env["KSPEC_PROD_STATS"] = heartbeat
        if args.mem_budget:
            # out-of-core re-run: the child's engine spills past the
            # budget into the disk tier (restarts resume from the
            # checkpointed run manifest — docs/storage.md)
            env["KSPEC_PROD_MEMBUDGET"] = args.mem_budget
        if args.spill_dir:
            env["KSPEC_PROD_SPILL"] = args.spill_dir
        cmd = [
            sys.executable,
            os.path.join(_REPO, "scripts", "run_product_tiny3.py"),
            "--base",
            "mixed464",
        ]
    if not cmd:
        ap.error("no command given (use -- CMD ... or --preset)")
    heartbeat = heartbeat or run_ctx.stats_path
    run_ctx.record_config(
        supervised=True,
        preset=args.preset,
        cmd=cmd,
        heartbeat=heartbeat,
        fleet=args.fleet,
        stall_timeout=args.stall_timeout,
        max_restarts=args.max_restarts,
    )
    print(
        f"[obs] run dir: {run_ctx.dir} (run {run_ctx.run_id})",
        file=sys.stderr,
    )
    if args.fleet:
        if args.preset:
            ap.error("--fleet and --preset are mutually exclusive")
        from kafka_specification_tpu.resilience.supervisor import (
            FleetConfig,
            supervise_fleet,
        )

        fcfg = FleetConfig(
            cmd=cmd,
            num_processes=args.fleet,
            events=args.events or run_ctx.events_path,
            heartbeat_dir=os.path.join(run_ctx.dir, "shards"),
            log_dir=args.log_dir or run_ctx.log_dir,
            stall_timeout=args.stall_timeout,
            max_restarts=args.max_restarts,
            backoff_base=args.backoff,
            backoff_cap=args.backoff_cap,
            env=env,
            run_id=run_ctx.run_id,
            devices_per_proc=args.devices_per_proc,
            reclaim=args.reclaim,
            reclaim_dirs=tuple(args.reclaim_dir),
        )
        return supervise_fleet(fcfg)
    cfg = SupervisorConfig(
        cmd=cmd,
        heartbeat=heartbeat,
        events=args.events or run_ctx.events_path,
        log_dir=args.log_dir or run_ctx.log_dir,
        stall_timeout=args.stall_timeout,
        max_restarts=args.max_restarts,
        backoff_base=args.backoff,
        backoff_cap=args.backoff_cap,
        env=env,
        run_id=run_ctx.run_id,
        reclaim=args.reclaim,
        reclaim_dirs=tuple(args.reclaim_dir),
    )
    return supervise(cfg)


if __name__ == "__main__":
    raise SystemExit(main())
