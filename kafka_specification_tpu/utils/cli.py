"""CLI: the TLC-equivalent front door.

    python -m kafka_specification_tpu.utils.cli check configs/Kip320.cfg
    python -m kafka_specification_tpu.utils.cli check configs/AsyncIsr.cfg \\
        --sharded --progress
    python -m kafka_specification_tpu.utils.cli oracle configs/Kip101.cfg

`check` runs the TPU/JAX engine (single-device by default, --sharded for the
mesh engine); `oracle` runs the pure-Python reference interpreter on the same
config (the golden cross-check).  The module name defaults to the .cfg file
stem, mirroring how TLC pairs Model.cfg with Model.tla.

Output mirrors TLC's closing summary: distinct states, diameter, and on
violation the invariant name plus a numbered counterexample trace.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ..pipeline_registry import pipeline_names as _pipeline_names
from .cfg import CFG_MODULE_ALIASES, build_model, parse_cfg

# typed resource exit (resilience.resources) — duplicated as a literal for
# help strings; asserted equal at the use site
_EXIT_RESOURCE_EXHAUSTED = 75
# typed integrity exit (resilience.integrity): a state-integrity check
# tripped; resume skips chain-failed generations automatically
_EXIT_INTEGRITY = 76


def _init_platform(cpu: bool) -> None:
    """Platform + compile cache for the jax-running subcommands.

    The command runs in-process on whatever platform JAX gives it
    (`--cpu` pins the CPU); a platform that fails to initialize is an
    error with JAX's own message, never a retry somewhere else.
    """
    from .platform_guard import enable_compile_cache, pin_cpu_in_process

    if cpu:
        pin_cpu_in_process()
    # small jitted programs dominate toy configs — cache everything
    enable_compile_cache(min_compile_secs=0)


def _print_result(res, as_json: bool, model_meta=None, run_id=None):
    if as_json:
        # the STABLE machine-readable verdict (kspec-verdict/1): the same
        # record the service's `cli result` returns, so clients switch
        # between local runs and submitted jobs without re-parsing
        # (service/verdict.py; docs/service.md)
        from ..service.verdict import verdict_from_result

        print(json.dumps(verdict_from_result(res, run_id=run_id)))
        return
    print(f"Model: {res.model}")
    print(
        f"{res.total} distinct states found, diameter {res.diameter}, "
        f"{res.seconds:.2f}s ({res.states_per_sec:,.0f} states/sec)"
    )
    if res.violation is None:
        print("No invariant violations. Exhaustive check complete.")
    else:
        v = res.violation
        print(f"Invariant {v.invariant} is VIOLATED at depth {v.depth}.")
        from .pretty import render_state, render_trace

        meta = model_meta or {}
        if v.trace:
            print("Counterexample trace:")
            print(render_trace(meta, v.trace))
        else:
            print("Violating state:")
            print(render_state(meta, v.state))


def main(argv=None):
    p = argparse.ArgumentParser(prog="kafka_specification_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    pc = sub.add_parser("check", help="run the TPU/JAX engine on a TLC .cfg")
    pc.add_argument("cfg")
    pc.add_argument("--module", help="TLA+ module (default: cfg file stem)")
    pc.add_argument(
        "--run-dir",
        help="run directory for this invocation's manifest + stats + spans "
        "+ metrics (default: runs/<run_id>/ under $KSPEC_RUNS_ROOT or the "
        "cwd; reopening an existing run dir resumes its run_id — "
        "docs/observability.md).  Render it later with `cli report`",
    )
    pc.add_argument("--sharded", action="store_true", help="mesh-sharded engine")
    pc.add_argument("--max-depth", type=int)
    pc.add_argument("--max-states", type=int)
    pc.add_argument("--no-trace", action="store_true", help="skip trace storage")
    pc.add_argument("--min-bucket", type=int, default=256)
    pc.add_argument(
        "--chunk-size",
        type=int,
        default=None,
        help="max frontier rows per compiled step call (bounds compiles + "
        "memory); defaults to each engine's own default",
    )
    pc.add_argument("--progress", action="store_true")
    pc.add_argument("--json", action="store_true")
    pc.add_argument(
        "--checkpoint", help="directory for level-synchronous checkpoint/resume"
    )
    pc.add_argument(
        "--checkpoint-every",
        type=int,
        default=1,
        help="persist a checkpoint every N BFS levels (default 1)",
    )
    pc.add_argument(
        "--checkpoint-keep",
        type=int,
        default=3,
        help="rotated checkpoint generations to keep (default 3; corrupt "
        "newest falls back to the next verifying one)",
    )
    pc.add_argument(
        "--stats", help="append per-level JSONL stats (e.g. PROGRESS.jsonl)"
    )
    pc.add_argument(
        "--fault",
        metavar="PLAN",
        help="deterministic fault injection plan (sets KSPEC_FAULT; e.g. "
        "'crash@level:7', 'corrupt_ckpt', 'flip@frontier:3', "
        "'transient_device_err:2' — `cli faults --list` enumerates every "
        "injectable site; grammar in docs/resilience.md)",
    )
    pc.add_argument(
        "--integrity-shadow",
        type=float,
        metavar="RATE",
        help="sampled shadow re-execution rate in [0,1] "
        "(KSPEC_INTEGRITY_SHADOW is the env twin): deterministically "
        "sampled chunks re-run through an independent path (the legacy "
        "pipeline / host fingerprint oracle) and must match the primary "
        "result bit-for-bit; a mismatch exits typed "
        f"INTEGRITY_VIOLATION (code {_EXIT_INTEGRITY}).  The per-level "
        "digest chain and storage read-side checksums are always on "
        "regardless (KSPEC_INTEGRITY=0 disables; docs/resilience.md).  "
        "Single-device engine only",
    )
    pc.add_argument(
        "--resilient",
        action="store_true",
        help="run under the auto-resume supervisor: spawn the check as a "
        "child, watch the --stats heartbeat, kill on stall, restart from "
        "--checkpoint with a bounded budget (scripts/resilient_run.py is "
        "the standalone form)",
    )
    pc.add_argument(
        "--stall-timeout",
        type=float,
        default=1800.0,
        help="[--resilient] kill the child after this many seconds "
        "without heartbeat growth (default 1800)",
    )
    pc.add_argument(
        "--max-restarts",
        type=int,
        default=8,
        help="[--resilient] restart budget (default 8)",
    )
    pc.add_argument(
        "--events",
        help="[--resilient] supervisor JSONL event log (default: "
        "<checkpoint>/supervisor_events.jsonl)",
    )
    pc.add_argument(
        "--visited-backend",
        choices=["device", "device-hash", "host"],
        default="device",
        help="fingerprint set: 'device' = sorted pair set in HBM, "
        "'device-hash' = open-addressing hash table in HBM (O(batch) per "
        "level instead of O(capacity) — ops/hashset), 'host' = the native "
        "C++ FpSet (spill mode for huge state spaces)",
    )
    pc.add_argument(
        "--mem-budget",
        metavar="BYTES",
        help="host fingerprint-set byte budget before spilling to the "
        "disk tier (suffixes K/M/G, e.g. 4G).  Setting this activates "
        "--store=auto's disk tier: sorted bloom-gated runs + spilled "
        "frontier + on-disk parent log under --spill-dir (docs/storage.md)",
    )
    pc.add_argument(
        "--spill-dir",
        metavar="DIR",
        help="directory for the disk tier's runs/frontier/parent log "
        "(default: <--checkpoint>/spill, else a temp dir)",
    )
    pc.add_argument(
        "--store",
        choices=["auto", "ram", "disk"],
        default="auto",
        help="state-storage tier: 'ram' = in-memory only, 'disk' = tiered "
        "out-of-core store (implies the host fingerprint backend), 'auto' "
        "= disk exactly when --mem-budget is set (default)",
    )
    pc.add_argument(
        "--disk-budget",
        metavar="BYTES",
        help="byte budget for the spill + checkpoint directories "
        "(suffixes K/M/G).  Crossing the soft fraction triggers "
        "reclamation (eager merges, generation pruning); a hard breach "
        "checkpoints and exits with the typed RESOURCE_EXHAUSTED status "
        f"(exit code {_EXIT_RESOURCE_EXHAUSTED}), resumable after space "
        "is freed (docs/resilience.md).  KSPEC_DISK_BUDGET is the env "
        "twin; KSPEC_RSS_BUDGET / KSPEC_LEVEL_DEADLINE arm the RSS and "
        "per-level-deadline watchdogs",
    )
    pc.add_argument(
        "--reclaim",
        action="store_true",
        help="[--resilient] on a RESOURCE_EXHAUSTED child exit, prune "
        "stale tmp files + rotated checkpoint generations and retry "
        "exactly once (default: halt with an actionable verdict; the "
        "supervisor never restarts into an unreclaimed full disk)",
    )
    pc.add_argument(
        "--profile",
        metavar="DIR",
        help="wrap the run in a jax.profiler trace (TensorBoard format)",
    )
    pc.add_argument(
        "--pipeline",
        choices=list(_pipeline_names()),
        default=None,
        help="level-pipeline implementation (engine/pipeline.py; "
        "`cli pipelines --list` shows the registry incl. the per-ENGINE "
        "support matrix): 'fused' (default; $KSPEC_PIPELINE overrides) "
        "= successor mega-kernels — one guard-predicate-matrix launch "
        "+ one update-skeleton launch per chunk; 'device' = the "
        "device-resident level pipeline — a bounded lax.while_loop runs "
        "every gated chunk of a level in ONE dispatched program (<=2 "
        "successor launches per LEVEL single-device; with --sharded, "
        "per-SHARD one-dispatch level programs with the exchange inside "
        "the loop — O(1) collective-bearing launches per level per "
        "shard; needs the sorted-set device visited backend + "
        "analyzer-proven field hulls, degrades per-chunk otherwise); "
        "'legacy' = the historical per-action step (the bit-identity "
        "oracle; with --sharded, the per-chunk sharded step).  "
        "Bit-identical results in every case (counts, duplicate "
        "accounting, first-violation rule, trace values, digest "
        "chains).  Unknown names are rejected here and by the engine's "
        "registry — a typo can never silently select a different "
        "implementation",
    )
    pc.add_argument(
        "--overlap",
        choices=["on", "off"],
        default=None,
        help="async level-pipelined execution (engine + sharded; "
        "$KSPEC_OVERLAP is the env twin; default on): two-slot staged "
        "chunk pipeline (host assembly drains behind the in-flight "
        "update-skeleton launch), background spill-run merges, "
        "checkpoint writes on a writer thread, and — sharded — the "
        "staged exchange commit + bit-packed/delta-encoded fingerprint "
        "payload compression (codec defaults on for real accelerator "
        "fabrics; KSPEC_EXCHANGE_COMPRESS=1/0 forces).  'off' restores "
        "the exact serial "
        "behavior (the bit-identity oracle): counts, traces and digest "
        "chains are identical either way (docs/engine.md § Async "
        "execution)",
    )
    pc.add_argument("--cpu", action="store_true", help="force the CPU platform")
    pc.add_argument(
        "--emitted",
        action="store_true",
        default=None,
        help="build the model mechanically from the reference TLA+ text "
        "(utils/tla_emit — no hand-translated kernels).  This is the "
        "DEFAULT when the reference checkout is present (KSPEC_REFERENCE, "
        "/root/reference); the hand-translated kernels remain as the "
        "cross-check path (--hand)",
    )
    pc.add_argument(
        "--hand",
        action="store_true",
        help="use the hand-translated kernels (models/*.py) instead of the "
        "emitted ones — the independent cross-check path (also the "
        "fallback when no reference checkout exists)",
    )

    pvc = sub.add_parser(
        "verify-checkpoint",
        help="offline integrity check of a checkpoint directory: per-array "
        "CRC manifests of every generation/part, cross-shard depth+mesh "
        "consistency, and storage-manifest resolvability (disk-tier run "
        "files).  Never imports jax — usable from CI or an operator shell "
        "on a box whose accelerator stack is wedged.  Exit 0 iff every "
        "checkpoint chain has a resumable generation",
    )
    pvc.add_argument("ckpt_dir")
    pvc.add_argument(
        "--spill-dir",
        help="disk-tier directory the storage manifests resolve against "
        "(default: <ckpt_dir>/spill, the engines' default placement)",
    )
    pvc.add_argument("--json", action="store_true",
                     help="machine-readable report")

    pf = sub.add_parser(
        "faults",
        help="enumerate every injectable fault site (the KSPEC_FAULT / "
        "--fault grammar) from the single registry the parser validates "
        "against — never imports jax",
    )
    pf.add_argument(
        "--list", action="store_true", dest="list_faults",
        help="list the fault registry (the default action)",
    )
    pf.add_argument("--json", action="store_true")

    pcc = sub.add_parser(
        "crashcheck",
        help="crash-consistency torture harness (docs/resilience.md "
        "§ Crash consistency): record every durable filesystem op each "
        "recovery protocol issues, enumerate every legal post-crash "
        "state (torn writes, reverted renames, lost journal tails), and "
        "run the protocol's own recovery against each one — never "
        "imports jax.  Exits 1 on any non-convergent state; findings "
        "carry the op-log prefix and crash state as a machine-readable "
        "repro.  --json emits the schema-versioned kspec-crashcheck/1 "
        "record",
    )
    pcc.add_argument(
        "--protocol", action="append", dest="protocols", metavar="P",
        help="restrict to one protocol or scenario name (repeatable; "
        "see `cli faults --list` for the scenario registry)",
    )
    pcc.add_argument("--json", action="store_true",
                     help="machine-readable kspec-crashcheck/1 record")

    psf = sub.add_parser(
        "simfleet",
        help="deterministic fleet simulation (docs/resilience.md "
        "§ Deterministic simulation): run the REAL router/queue/daemon/"
        "cache control plane under a virtual clock and a seeded "
        "scheduler, search interleavings across seeds (kill, partition, "
        "clock skew, flaky fs), judge every run with invariant oracles, "
        "and shrink any violation to a minimal kspec-simfleet/1 repro — "
        "never imports jax.  `run` exits 1 on any violation; `replay` "
        "re-runs a repro and exits 0 if it still reproduces, 2 if stale",
    )
    sfsub = psf.add_subparsers(dest="sf_cmd", required=True)
    psr = sfsub.add_parser("run", help="sweep seeds, shrink violations")
    psr.add_argument("--seeds", type=int, default=50,
                     help="how many seeds to run (default 50)")
    psr.add_argument("--start-seed", type=int, default=0,
                     help="first seed (default 0)")
    psr.add_argument("--hosts", type=int, default=2)
    psr.add_argument("--jobs", type=int, default=4)
    psr.add_argument("--steps", type=int, default=60,
                     help="schedule length per seed (default 60)")
    psr.add_argument(
        "--coverage", action="store_true",
        help="coverage-guided: seeds that reach new adjacent event-type "
        "pairs queue derived seeds behind them",
    )
    psr.add_argument(
        "--out", default="simfleet-repros", metavar="DIR",
        help="directory violations' shrunk repros are banked in "
        "(default ./simfleet-repros)",
    )
    psr.add_argument("--json", action="store_true")
    psp = sfsub.add_parser("replay",
                           help="replay a kspec-simfleet/1 repro")
    psp.add_argument("repro", help="kspec-simfleet/1 file")
    psp.add_argument(
        "--trace", action="store_true",
        help="assemble the violating job's fleet trace from the "
        "simulated run and render the same waterfall `cli trace` "
        "gives real runs",
    )
    psp.add_argument("--json", action="store_true")

    pp = sub.add_parser(
        "pipelines",
        help="enumerate the registered level-pipeline implementations "
        "(the --pipeline / $KSPEC_PIPELINE registry, "
        "kafka_specification_tpu/pipeline_registry.py) with their launch "
        "contracts and degradation ladder — never imports jax",
    )
    pp.add_argument(
        "--list", action="store_true", dest="list_pipelines",
        help="list the pipeline registry (the default action)",
    )
    pp.add_argument("--json", action="store_true")

    pan = sub.add_parser(
        "analyze",
        help="static analysis of the specs and the engine (docs/"
        "analysis.md): encoding-soundness proofs (interval abstract "
        "interpretation of every action kernel against its packed field "
        "ranges), action/guard lint (vacuous guards, frame violations, "
        "dead fields), and the concurrency-ownership + purity checks "
        "over the engine sources.  NEVER imports jax (the model modules "
        "load under a stub; kernels run abstractly) — usable on a box "
        "with no accelerator stack.  Exits non-zero on any HIGH finding; "
        "--json emits the schema-versioned kspec-analysis/1 record",
    )
    pan.add_argument(
        "cfgs", nargs="*",
        help="TLC .cfg files to analyze (default: every configs/*.cfg "
        "— the full shipped-model matrix)",
    )
    pan.add_argument(
        "--module",
        help="TLA+ module for a single .cfg (default: the cfg stem)",
    )
    pan.add_argument(
        "--no-models", action="store_true",
        help="skip the per-model encoding/lint passes",
    )
    pan.add_argument(
        "--no-engine", action="store_true",
        help="skip the engine ownership/purity passes",
    )
    pan.add_argument(
        "--info", action="store_true",
        help="also print INFO findings (suppressions, skips)",
    )
    pan.add_argument("--json", action="store_true",
                     help="machine-readable kspec-analysis/1 record")

    pr = sub.add_parser(
        "report",
        help="render a run directory (manifest + stats + spans + metrics + "
        "events) into a human summary: per-level throughput, action "
        "enablement, spill accounting, restart timeline, ETA, stall "
        "verdict.  Works on live and crashed-mid-run directories; never "
        "touches an accelerator.  With no run dir: index the recent runs "
        "under --root (the service multiplies run dirs; this is the "
        "operator's ls)",
    )
    pr.add_argument(
        "run_dir", nargs="?",
        help="run directory to render (omit to list recent runs)",
    )
    pr.add_argument(
        "--latest", action="store_true",
        help="render the newest run under --root instead of listing",
    )
    pr.add_argument(
        "--root",
        help="runs root for the no-argument index / --latest "
        "(default: $KSPEC_RUNS_ROOT or ./runs)",
    )
    pr.add_argument("--json", action="store_true",
                    help="machine-readable report")

    # --- checking-as-a-service (docs/service.md) -------------------------
    svc_help = (
        "service directory (queue + results + run dirs; default: "
        "$KSPEC_SERVICE_DIR or ./service)"
    )

    pserve = sub.add_parser(
        "serve",
        help="run the checking-as-a-service daemon: import jax once, hold "
        "jitted engine kernels in a shape-keyed compile cache, drain the "
        "durable job queue under per-tenant resource budgets, coalesce "
        "jobs sharing a schema shape into one batched engine run "
        "(docs/service.md)",
    )
    pserve.add_argument("service_dir", nargs="?", help=svc_help)
    pserve.add_argument("--poll", type=float, default=0.2,
                        help="queue poll interval seconds (default 0.2)")
    pserve.add_argument(
        "--max-jobs", type=int,
        help="exit after this many verdicts (benchmarks / tests)",
    )
    pserve.add_argument(
        "--idle-exit", type=float,
        help="exit after this many seconds with an empty queue "
        "(default: serve forever)",
    )
    pserve.add_argument("--min-bucket", type=int, default=256)
    pserve.add_argument(
        "--chunk-size", type=int, default=32768,
        help="engine streaming chunk (one value for the whole daemon: "
        "batched verdict derivation depends on chunk boundaries)",
    )
    pserve.add_argument(
        "--visited-backend",
        choices=["device", "device-hash", "host"],
        default="device",
    )
    pserve.add_argument(
        "--no-batching", action="store_true",
        help="disable multi-config coalescing (every job runs solo; the "
        "compile cache still amortizes)",
    )
    pserve.add_argument(
        "--cache-entries", type=int, default=32,
        help="kernel-cache LRU capacity (distinct schema shapes held "
        "warm; default 32)",
    )
    pserve.add_argument(
        "--supervised", action="store_true",
        help="run the daemon under the auto-restart supervisor (heartbeat "
        "stall-kill + bounded restarts; resilience.supervisor)",
    )
    pserve.add_argument(
        "--stall-timeout", type=float, default=120.0,
        help="[--supervised] kill the daemon after this many seconds "
        "without a heartbeat tick (default 120; an idle daemon still "
        "ticks every --poll)",
    )
    pserve.add_argument(
        "--max-restarts", type=int, default=8,
        help="[--supervised] restart budget (default 8)",
    )
    pserve.add_argument(
        "--no-state-cache", action="store_true",
        help="disable the persistent state-space cache (default on: "
        "repeat checks of an unchanged config become chain-verified "
        "cache hits, config-delta checks seed from the cached boundary; "
        "every artifact problem degrades to a cold run with a typed "
        "cache-fallback event — docs/service.md § State-space cache)",
    )
    pserve.add_argument(
        "--state-cache-dir", metavar="DIR",
        help="shared state-space cache root (default: <service_dir>/"
        "state-cache).  Point every host of a fleet at one directory to "
        "federate the cache: entries are content-addressed and "
        "self-verifying, so a hit published by another host is "
        "chain-verified before it is served (docs/service.md § "
        "Cross-host deployment)",
    )
    pserve.add_argument("--cpu", action="store_true",
                        help="force the CPU platform")

    pfleet = sub.add_parser(
        "serve-fleet",
        help="run an N-daemon serving fleet over one service directory: "
        "per-daemon heartbeat supervision (death/wedge/rc-75/rc-76 "
        "taxonomy, bounded jittered restarts), queue-depth autoscaling "
        "between --min/--max with graceful drain, lease-based takeover "
        "of a dead or wedged daemon's claims (docs/service.md § Fleet "
        "lifecycle).  The parent never imports jax",
    )
    pfleet.add_argument("service_dir", nargs="?", help=svc_help)
    pfleet.add_argument(
        "--daemons", type=int, default=2,
        help="initial fleet size (default 2).  A chip belongs to one "
        "process: on a chip host run one daemon per chip — an extra one "
        "fails at platform init; --cpu fleets are unlimited",
    )
    pfleet.add_argument(
        "--min", type=int, default=None, dest="min_daemons",
        help="autoscale floor (default: --daemons)",
    )
    pfleet.add_argument(
        "--max", type=int, default=None, dest="max_daemons",
        help="autoscale ceiling (default: --daemons)",
    )
    pfleet.add_argument("--poll", type=float, default=0.5)
    pfleet.add_argument(
        "--stall-timeout", type=float, default=120.0,
        help="kill + restart a daemon whose own heartbeat file freezes "
        "for this long (an idle daemon still ticks every few seconds, "
        "so frozen means wedged; default 120)",
    )
    pfleet.add_argument(
        "--max-restarts", type=int, default=8,
        help="per-daemon restart budget (default 8)",
    )
    pfleet.add_argument("--backoff-base", type=float, default=1.0)
    pfleet.add_argument(
        "--scale-up-pending", type=int, default=4,
        help="pending jobs per live daemon that triggers a scale-up "
        "(default 4)",
    )
    pfleet.add_argument("--scale-interval", type=float, default=5.0)
    pfleet.add_argument(
        "--scale-down-idle", type=float, default=60.0,
        help="seconds of empty queue before one daemon is gracefully "
        "drained (finishes claimed jobs, takes no new ones, exits 0; "
        "default 60)",
    )
    pfleet.add_argument("--min-bucket", type=int, default=256)
    pfleet.add_argument("--chunk-size", type=int, default=32768)
    pfleet.add_argument(
        "--visited-backend", choices=["device", "device-hash", "host"],
        default="device",
    )
    pfleet.add_argument("--no-batching", action="store_true")
    pfleet.add_argument("--cache-entries", type=int, default=32)
    pfleet.add_argument("--no-state-cache", action="store_true")
    pfleet.add_argument(
        "--state-cache-dir", metavar="DIR",
        help="shared state-space cache root for every daemon (see "
        "`serve --state-cache-dir`; point multiple hosts' fleets at one "
        "directory to federate the cache)",
    )
    pfleet.add_argument(
        "--host-instance", type=int, metavar="I",
        help="this fleet's host index in a cross-host deployment "
        "(exported as KSPEC_HOST_INSTANCE to every daemon; scopes "
        "host-targeted faults like kill@host<i> and skew@host<i>)",
    )
    pfleet.add_argument("--cpu", action="store_true",
                        help="force the CPU platform in every daemon")

    psub = sub.add_parser(
        "submit",
        help="submit a check to the service queue and return the job id — "
        "NEVER imports jax (the tenant side pays no cold start); the .cfg "
        "travels inline in the job spec",
    )
    psub.add_argument("cfg")
    psub.add_argument("--module", help="TLA+ module (default: cfg stem)")
    psub.add_argument("--service-dir", help=svc_help)
    psub.add_argument(
        "--router", metavar="DIR",
        help="submit through a cross-host router directory (`cli route`) "
        "instead of a single service dir: the router places the job on "
        "the healthiest live host and enforces the tenant's max_pending "
        "cap fleet-WIDE",
    )
    psub.add_argument("--tenant", default="default")
    psub.add_argument("--max-depth", type=int)
    psub.add_argument("--max-states", type=int)
    psub.add_argument(
        "--emitted", action="store_true", default=None,
        help="force the mechanically emitted kernels (default: auto — "
        "emitted when the daemon's reference checkout has the module)",
    )
    psub.add_argument(
        "--hand", action="store_true",
        help="force the hand-translated kernels",
    )
    psub.add_argument(
        "--fault", metavar="PLAN",
        help="deterministic fault plan for THIS job (testing/ops; the "
        "daemon scopes it to the job's run)",
    )
    psub.add_argument(
        "--wait", action="store_true",
        help="block until the verdict and exit with its exit code",
    )
    psub.add_argument(
        "--timeout", type=float, default=300.0,
        help="[--wait] give up after this many seconds (default 300)",
    )
    psub.add_argument("--json", action="store_true")

    pst = sub.add_parser(
        "status",
        help="job state (pending/claimed/done) or, with no job id, the "
        "queue overview — never imports jax",
    )
    pst.add_argument("job_id", nargs="?")
    pst.add_argument("--service-dir", help=svc_help)
    pst.add_argument(
        "--router", metavar="DIR",
        help="resolve the job through a router directory (locates the "
        "host it was routed to, following reroutes)",
    )
    pst.add_argument("--json", action="store_true")

    pres = sub.add_parser(
        "result",
        help="fetch a job's verdict (kspec-verdict/1, the same record "
        "`cli check --json` prints) and exit with its exit code — never "
        "imports jax",
    )
    pres.add_argument("job_id")
    pres.add_argument("--service-dir", help=svc_help)
    pres.add_argument(
        "--router", metavar="DIR",
        help="fetch the verdict through a router directory (checks the "
        "routed host first, then every host — a rerouted job's verdict "
        "is found wherever it landed)",
    )
    pres.add_argument(
        "--wait", action="store_true",
        help="block until the verdict exists",
    )
    pres.add_argument("--timeout", type=float, default=300.0)
    pres.add_argument("--json", action="store_true")

    proute = sub.add_parser(
        "route",
        help="run the cross-host router over N per-host service "
        "directories: health-aware placement (heartbeat freshness, queue "
        "depth), fleet-wide tenant admission, dead-host detection with "
        "exactly-once re-routing of pending jobs to survivors — never "
        "imports jax (docs/service.md § Cross-host deployment)",
    )
    proute.add_argument(
        "router_dir",
        help="router state directory (created on first run; holds "
        "router.json, route records, and the router event log)",
    )
    proute.add_argument(
        "--hosts", nargs="+", metavar="DIR",
        help="per-host service directories to front (required on first "
        "run; persisted in router.json and optional afterwards)",
    )
    proute.add_argument(
        "--dead-after", type=float, default=None,
        help="seconds without a daemon heartbeat before a host is "
        "declared dead and its pending jobs re-route (default 30; the "
        "comparison tolerates KSPEC_CLOCK_SKEW)",
    )
    proute.add_argument(
        "--poll", type=float, default=1.0,
        help="sweep interval seconds (default 1.0)",
    )
    proute.add_argument(
        "--once", action="store_true",
        help="run a single sweep (takeover + re-route pass) and exit",
    )
    proute.add_argument(
        "--status", action="store_true",
        help="print per-host health and queue depths, run no sweep",
    )
    proute.add_argument("--json", action="store_true")

    ptr = sub.add_parser(
        "trace",
        help="render one job's fleet-wide distributed trace "
        "(submit -> placement -> claim -> run -> publish) as a "
        "skew-normalized cross-host span waterfall with the typed "
        "stage decomposition — never imports jax "
        "(docs/observability.md § Fleet traces)",
    )
    ptr.add_argument("job_id")
    ptr.add_argument(
        "--service-dir", action="append", metavar="DIR",
        help="service root(s) whose traces/ to read (repeatable; "
        "default: $KSPEC_SERVICE_DIR or ./service)",
    )
    ptr.add_argument(
        "--router", metavar="DIR",
        help="read the router dir's traces/ plus every fronted host's "
        "(a re-routed job's spans live on both sides)",
    )
    ptr.add_argument("--json", action="store_true")

    ptop = sub.add_parser(
        "top",
        help="live fleet view from on-disk state only: queue depths, "
        "daemon heartbeats, per-stage p50/p95, cache hit ratio, sweep "
        "progress — never imports jax",
    )
    ptop.add_argument("--service-dir", action="append", metavar="DIR",
                      help="service root(s) to watch (repeatable)")
    ptop.add_argument("--router", metavar="DIR",
                      help="watch every host behind a router directory")
    ptop.add_argument("--once", action="store_true",
                      help="print one frame and exit")
    ptop.add_argument("--interval", type=float, default=2.0,
                      help="refresh seconds (default 2.0)")
    ptop.add_argument("--json", action="store_true",
                      help="print one JSON frame and exit (implies --once)")

    pfr = sub.add_parser(
        "fleet-report",
        help="SLO artifact over every completed trace: per-stage "
        "latency histograms (p50/p95), cache hit ratio, slowest-job "
        "exemplars, chaos annotations (re-routes, requeues) — never "
        "imports jax; nightly_sweep.sh banks it per night",
    )
    pfr.add_argument("--service-dir", action="append", metavar="DIR",
                     help="service root(s) whose traces/ to aggregate")
    pfr.add_argument("--router", metavar="DIR",
                     help="aggregate the router dir plus every fronted host")
    pfr.add_argument("--exemplars", type=int, default=5,
                     help="slowest-job exemplar count (default 5)")
    pfr.add_argument("--json", action="store_true")

    psw = sub.add_parser(
        "sweep",
        help="coverage sweeps over a config lattice (kspec-sweep-lattice/1"
        "): enumerate canonical points, skip statically-vacuous configs, "
        "predict cost from the standing corpus, schedule the portfolio "
        "through the service queue or a router (cheap points batch, "
        "expensive points run solo, repeats are cache hits), and report "
        "coverage / violation frontiers / scaling laws — never imports "
        "jax (docs/sweep.md)",
    )
    swsub = psw.add_subparsers(dest="sweep_cmd", required=True)
    swp = swsub.add_parser(
        "plan",
        help="enumerate + annotate + predict, dispatch nothing: the "
        "dry-run view of what a sweep would do (point count, vacuous "
        "skips with their findings, predicted cost, solo/batch split)",
    )
    swp.add_argument("lattice", help="kspec-sweep-lattice/1 JSON file")
    swp.add_argument("--state-cache-dir", metavar="DIR",
                     help="corpus root for the cost-model fit (default: "
                     "$KSPEC_STATE_CACHE_DIR or <service>/state-cache)")
    swp.add_argument("--service-dir", help=svc_help)
    swp.add_argument("--json", action="store_true")
    swr = swsub.add_parser(
        "run",
        help="run (or crash-resume — only incomplete points re-submit) "
        "one sweep to completion against a live daemon/fleet; the "
        "durable kspec-sweep/1 manifest lands in --sweep-dir",
    )
    swr.add_argument("lattice", help="kspec-sweep-lattice/1 JSON file")
    swr.add_argument("--sweep-dir", required=True,
                     help="sweep state directory (sweep.json manifest; "
                     "reuse to crash-resume, use a fresh one to re-run)")
    swr.add_argument("--service-dir", help=svc_help)
    swr.add_argument(
        "--router", metavar="DIR",
        help="dispatch through a cross-host router directory instead of "
        "one service dir",
    )
    swr.add_argument("--tenant", default="sweep")
    swr.add_argument("--max-inflight", type=int, default=64,
                     help="portfolio submit-window width (default 64; "
                     "clamped under the tenant's max_pending cap)")
    swr.add_argument(
        "--solo-threshold", type=int, default=200_000,
        help="predicted distinct-states at/past which a point submits "
        "solo instead of joining a batched group (default 200000)",
    )
    swr.add_argument("--timeout", type=float, default=900.0,
                     help="give up after this many seconds without a "
                     "verdict landing (default 900; resume later)")
    swr.add_argument("--state-cache-dir", metavar="DIR")
    swr.add_argument("--json", action="store_true",
                     help="print the final manifest record")
    swrep = swsub.add_parser(
        "report",
        help="render a sweep directory's manifest: coverage (done/hit/"
        "seeded/skipped/pending), the typed vacuous-skip rows, the "
        "minimal-violating-config frontier per invariant, scaling-law "
        "curves (states vs axis value), estimator accuracy",
    )
    swrep.add_argument("sweep_dir")
    swrep.add_argument("--json", action="store_true")
    swb = swsub.add_parser(
        "bisect",
        help="witness the minimal-violating-config frontier: check every "
        "frontier point's lower neighbors from the manifest, and "
        "(with --service-dir/--router) actually RUN the neighbors the "
        "sweep never ran — the frontier is witnessed, not guessed",
    )
    swb.add_argument("sweep_dir")
    swb.add_argument("--invariant", help="restrict to one invariant")
    swb.add_argument("--service-dir", help=svc_help)
    swb.add_argument("--router", metavar="DIR")
    swb.add_argument("--tenant", default="sweep")
    swb.add_argument("--max-probes", type=int, default=64,
                     help="budget of neighbor runs (default 64)")
    swb.add_argument("--timeout", type=float, default=300.0,
                     help="per-probe verdict timeout (default 300)")
    swb.add_argument("--json", action="store_true")

    po = sub.add_parser("oracle", help="run the Python reference interpreter")
    po.add_argument("cfg")
    po.add_argument("--module")
    po.add_argument("--max-depth", type=int)
    po.add_argument("--max-states", type=int)

    ps = sub.add_parser(
        "simulate", help="random-walk checking (TLC -simulate equivalent)"
    )
    ps.add_argument("cfg")
    ps.add_argument("--module")
    ps.add_argument("--walks", type=int, default=100)
    ps.add_argument("--depth", type=int, default=100)
    ps.add_argument("--seed", type=int, default=0)
    ps.add_argument("--cpu", action="store_true", help="force the CPU platform")
    ps.add_argument("--json", action="store_true")
    ps.add_argument(
        "--emitted",
        action="store_true",
        default=None,
        help="simulate the mechanically emitted model (the default when "
        "the reference checkout is present — see `check --emitted`)",
    )
    ps.add_argument(
        "--hand",
        action="store_true",
        help="simulate the hand-translated kernels (see `check --hand`)",
    )

    pv = sub.add_parser(
        "validate",
        help="cross-check a model's action inventory against the reference "
        "TLA+ module's Next disjuncts (structural front-end)",
    )
    pv.add_argument("cfg")
    pv.add_argument("--module")
    pv.add_argument(
        "--reference",
        default=os.environ.get("KSPEC_REFERENCE", "/root/reference"),
        help="reference checkout to validate against (default: "
        "$KSPEC_REFERENCE or /root/reference — same resolution as the "
        "emitted model builder)",
    )
    pv.add_argument(
        "--emitted",
        action="store_true",
        help="validate the mechanically emitted model's action inventory "
        "(its `Name~k` DNF branches map back to their source disjunct)",
    )

    args = p.parse_args(argv)

    if args.cmd == "faults":
        # pure registry dump (resilience.faults.FAULT_REGISTRY): jax-free
        from ..resilience.crashcheck import list_scenarios
        from ..resilience.faults import list_faults

        entries = list_faults()
        scenarios = list_scenarios()
        if args.json:
            # scenario rows ride along as extra entries (same flat-list
            # shape every existing consumer parses), tagged by kind
            print(json.dumps(entries + [
                {"kind": "crashcheck-scenario",
                 "grammar": f"crashcheck --protocol {s['protocol']}",
                 "sites": [s["name"]],
                 "description": s["description"],
                 "scopeable": False}
                for s in scenarios
            ]))
            return 0
        print("Injectable faults (KSPEC_FAULT / --fault; comma-separate "
              "to compose; every fault takes a `shard<d>:` scope after "
              "the '@'):")
        for e in entries:
            print(f"  {e['grammar']}")
            print(f"      {e['description']}")
        print("Examples: crash@level:7   enospc@spill:2   "
              "flip@shard1:exchange:3   corrupt_ckpt@ckpt:4")
        print()
        print("Crashcheck scenarios (`cli crashcheck --protocol P`; "
              "enumerated crash states, not injected faults):")
        for s in scenarios:
            print(f"  {s['protocol']}: {s['name']}")
            print(f"      {s['description']}")
        return 0

    if args.cmd == "crashcheck":
        # crash-consistency torture harness: jax-free by construction
        # (queue/router/cache/checkpoint recovery paths never touch the
        # accelerator stack)
        from ..resilience.crashcheck import run_crashcheck

        try:
            rec = run_crashcheck(protocols=args.protocols)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(rec))
        else:
            print(f"kspec crashcheck: {rec['states']} crash states / "
                  f"{len(rec['protocols'])} protocol(s) in "
                  f"{rec['seconds']}s — "
                  f"{rec['non_convergent']} non-convergent")
            for s in rec["scenarios"]:
                print(f"  {s['protocol']:<8} {s['name']:<18} "
                      f"{s['states']:>4} states  "
                      f"{s['non_convergent']} non-convergent")
            for f in rec["findings"]:
                print(f"  FINDING {f['scenario']} prefix={f['prefix']} "
                      f"degraded={f['degraded']} "
                      f"state={f['state_digest']}")
                for v in f["violations"]:
                    print(f"    {v}")
        return 0 if rec["ok"] else 1

    if args.cmd == "simfleet":
        # deterministic fleet simulation: jax-free by construction (the
        # whole simulated plane is the jax-free control plane)
        return _run_simfleet(args)

    if args.cmd == "pipelines":
        # pure registry dump (pipeline_registry.PIPELINE_REGISTRY, the
        # fault-registry pattern): jax-free, the same source the
        # --pipeline parser and the engine's resolve_pipeline validate
        # against — a typo'd name is rejected loudly at parse time, it
        # can never silently fall back to a different implementation
        from ..pipeline_registry import list_pipelines

        entries = list_pipelines()
        if args.json:
            print(json.dumps(entries))
            return 0
        print("Registered level pipelines (--pipeline / $KSPEC_PIPELINE; "
              "engine/pipeline.py):")
        for e in entries:
            tag = " (default)" if e["default"] else ""
            fb = (f" -> degrades to '{e['fallback']}'"
                  if e["fallback"] else " (the bit-identity oracle)")
            print(f"  {e['name']}{tag}: {e['launches']}{fb}")
            print(f"      {e['description']}")
            # per-engine support matrix: which engine (plain vs
            # --sharded) serves this name, and why a combination
            # degrades — the sharded engine used to silently ignore
            # --pipeline; every cell is now stated
            for eng, cell in e.get("engines", {}).items():
                mark = "supported" if cell["supported"] else "degrades"
                print(f"      [{eng}] {mark}: {cell['detail']}")
            # per-BACKEND support matrix: which visited backends the
            # pipeline serves natively vs degrades from — the detail of
            # an unsupported cell is the exact fallback reason
            # stats['device']['fallback'] records (one source,
            # pipeline_registry.backend_fallback_reason)
            for be, cell in e.get("backends", {}).items():
                mark = "native" if cell["supported"] else "degrades"
                print(f"      [backend {be}] {mark}: {cell['detail']}")
        return 0

    if args.cmd == "analyze":
        # the static-analysis front door: jax-free by contract (the
        # model modules import under analysis.install_jax_stub and the
        # kernels execute abstractly) — it must run in CI and on
        # operator boxes whose accelerator stack is wedged
        return _run_analyze(args)

    if args.cmd == "verify-checkpoint":
        # like `report`, this must run on a box whose accelerator is
        # wedged (that is when an operator reaches for it): jax-free
        from ..resilience.checkpoints import verify_checkpoint_dir

        rep = verify_checkpoint_dir(args.ckpt_dir, spill_dir=args.spill_dir)
        if args.json:
            print(json.dumps(rep, default=str))
        else:
            _print_verify_checkpoint(rep)
        return 0 if rep["ok"] else 1

    if args.cmd == "report":
        # a report must render on a box whose accelerator is wedged (that
        # is when you want it most): obs never imports jax
        from ..obs.report import (
            list_runs,
            render_report,
            render_run_index,
            report_data,
        )

        run_dir = args.run_dir
        if run_dir is not None and os.path.isfile(
            os.path.join(run_dir, "router.json")
        ):
            # a router directory: render the cross-host rollup instead
            # of a (nonexistent) single-run report
            from ..obs.report import render_router_report, router_report_data

            data = router_report_data(run_dir)
            print(json.dumps(data) if args.json
                  else render_router_report(data))
            return 0
        if run_dir is not None and os.path.isfile(
            os.path.join(run_dir, "sweep.json")
        ):
            # a sweep directory (kspec-sweep/1 manifest): render the
            # sweep beat — same detection pattern as router.json above
            from ..obs.report import render_sweep_report, sweep_report_data

            data = sweep_report_data(run_dir)
            print(json.dumps(data) if args.json
                  else render_sweep_report(data))
            return 0
        if run_dir is None:
            root = args.root or os.environ.get("KSPEC_RUNS_ROOT", "runs")
            if args.latest:
                runs = list_runs(root, limit=1)
                if not runs:
                    print(f"no runs under {root}", file=sys.stderr)
                    return 1
                run_dir = runs[0]["dir"]
            else:
                runs = list_runs(root)
                if args.json:
                    print(json.dumps(runs, default=str))
                else:
                    print(render_run_index(root, runs))
                return 0
        if args.json:
            print(json.dumps(report_data(run_dir), default=str))
        else:
            print(render_report(run_dir))
        return 0

    if args.cmd == "route":
        # the router is operator infrastructure for a degraded fleet:
        # jax-free by contract, like the clients it fronts
        return _run_router(args)

    if args.cmd in ("trace", "top", "fleet-report"):
        # fleet observability reads side-channel files only (traces/,
        # heartbeats, metrics.prom): jax-free by contract — it is the
        # view an operator opens WHILE the fleet is degraded
        return _run_fleet_obs(args)

    if args.cmd == "sweep":
        # sweep planning/dispatch/reporting is a queue/router CLIENT:
        # jax-free by contract — the only engine work a sweep causes
        # happens inside serving daemons
        return _run_sweep(args)

    if args.cmd in ("submit", "status", "result"):
        # the tenant side of the service: MUST stay jax-free — clients
        # never pay the cold start (tests pin this with a poisoned jax)
        return _run_service_client(args)

    if args.cmd == "serve-fleet":
        # the fleet parent is jax-free (children are full `cli serve`
        # processes with their own platform hygiene)
        from ..service.fleet import FleetServeConfig, serve_fleet_daemons

        serve_args = [
            "--min-bucket", str(args.min_bucket),
            "--chunk-size", str(args.chunk_size),
            "--visited-backend", args.visited_backend,
            "--cache-entries", str(args.cache_entries),
        ]
        if args.no_batching:
            serve_args.append("--no-batching")
        if args.no_state_cache:
            serve_args.append("--no-state-cache")
        if args.cpu:
            serve_args.append("--cpu")
        daemons = max(1, args.daemons)
        return serve_fleet_daemons(
            FleetServeConfig(
                service_dir=_service_dir(args.service_dir),
                daemons=daemons,
                min_daemons=(
                    daemons if args.min_daemons is None
                    else max(1, args.min_daemons)
                ),
                max_daemons=args.max_daemons,
                poll_s=args.poll,
                stall_timeout=args.stall_timeout,
                max_restarts=args.max_restarts,
                backoff_base=args.backoff_base,
                scale_interval_s=args.scale_interval,
                scale_up_pending=args.scale_up_pending,
                scale_down_idle_s=args.scale_down_idle,
                serve_args=tuple(serve_args),
                state_cache_dir=args.state_cache_dir,
                host_instance=args.host_instance,
            )
        )

    if args.cmd == "serve" and args.supervised:
        # daemon supervision: same watchdog as engine runs, pointed at the
        # daemon's own heartbeat (it ticks every poll even when idle)
        from ..resilience.supervisor import daemon_supervisor_config, supervise

        child_argv = [
            a
            for a in (argv if argv is not None else sys.argv[1:])
            if not (a.startswith("--su") and "--supervised".startswith(a))
        ]
        svc_dir = _service_dir(args.service_dir)
        cfg = daemon_supervisor_config(
            svc_dir,
            [sys.executable, "-m", "kafka_specification_tpu.utils.cli"]
            + child_argv,
            stall_timeout=args.stall_timeout,
            max_restarts=args.max_restarts,
        )
        return supervise(cfg)

    if args.cmd == "serve":
        # the daemon IS the jax process: it runs in-process on the platform
        # JAX gives it (a platform that fails to initialize fails the
        # daemon), with the persistent compile cache so even a restarted
        # daemon re-warms from disk
        _init_platform(args.cpu)
        from ..service.daemon import ServeConfig
        from ..service.daemon import serve as _serve

        return _serve(
            ServeConfig(
                service_dir=_service_dir(args.service_dir),
                poll_s=args.poll,
                max_jobs=args.max_jobs,
                idle_exit_s=args.idle_exit,
                min_bucket=args.min_bucket,
                chunk_size=args.chunk_size,
                visited_backend=args.visited_backend,
                cache_entries=args.cache_entries,
                batching=not args.no_batching,
                state_cache=not args.no_state_cache,
                state_cache_dir=args.state_cache_dir,
            )
        )

    from pathlib import Path

    module = args.module or Path(args.cfg).stem
    try:
        tlc_cfg = parse_cfg(args.cfg)
    except (OSError, ValueError) as e:
        print(f"error: cannot parse {args.cfg}: {e}", file=sys.stderr)
        return 2

    if args.cmd == "check" and (args.checkpoint_every < 1 or args.checkpoint_keep < 1):
        print(
            "error: --checkpoint-every and --checkpoint-keep must be >= 1",
            file=sys.stderr,
        )
        return 2

    if args.cmd == "check" and args.mem_budget is not None:
        from ..storage import parse_mem_budget

        try:
            args.mem_budget = parse_mem_budget(args.mem_budget)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    if args.cmd == "check" and args.disk_budget is not None:
        from ..resilience.resources import parse_bytes

        try:
            args.disk_budget = parse_bytes(args.disk_budget)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    if args.cmd == "check" and args.sharded \
            and getattr(args, "integrity_shadow", None):
        # shadow re-execution is a single-device-engine oracle; silently
        # dropping the flag on a sharded run would report a clean pass an
        # operator (sent here by the report's own guidance) would trust
        print(
            "error: --integrity-shadow is single-device only (the shadow "
            "oracles are the legacy pipeline + host fingerprint oracle); "
            "re-run without --sharded to localize corruption",
            file=sys.stderr,
        )
        return 2

    if args.cmd == "check" and args.fault:
        from ..resilience.faults import FaultPlan

        try:
            FaultPlan(args.fault)  # validate the grammar before running
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        os.environ["KSPEC_FAULT"] = args.fault

    if args.cmd == "check" and args.resilient:
        return _run_resilient(args, argv if argv is not None else sys.argv[1:])

    if args.cmd in ("check", "simulate"):
        _init_platform(args.cpu)
        if (
            os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("KSPEC_MULTIHOST") == "1"
        ):
            # fleet-launched process (scripts/resilient_run.py --fleet, or
            # any jax.distributed job): join the job BEFORE anything
            # initializes the XLA backend — this is what lets the plain
            # CLI be the per-process command of a supervised fleet
            from ..parallel.multihost import init_distributed

            info = init_distributed()
            if info["process_count"] > 1:
                print(
                    f"[fleet] process {info['process_id']}/"
                    f"{info['process_count']} "
                    f"({info['local_devices']} local / "
                    f"{info['global_devices']} global devices)",
                    file=sys.stderr,
                )

    if args.cmd == "validate":
        # structural validation never needs an accelerator, but building
        # the emitted model initializes a backend — keep it off the chip
        # another process may hold
        from .platform_guard import pin_cpu_in_process

        pin_cpu_in_process()
        from .tla_frontend import validate_cfg_constants, validate_model

        problems = validate_cfg_constants(tlc_cfg, args.reference, module)
        # validate the base (single-partition) model: Partitions is an
        # authored product-space constant with no reference counterpart,
        # and the combinator renames actions to p<k>.<Name>
        tlc_cfg.constants.pop("Partitions", None)
        model = _build_or_fail(
            module, tlc_cfg, emitted=args.emitted, reference=args.reference
        )
        problems += validate_model(model, args.reference, module)
        if problems:
            for pr in problems:
                print(f"MISMATCH: {pr}")
            return 1
        kind = "emitted DNF branches" if args.emitted else "actions"
        print(
            f"{module}: constants assigned; {len(model.actions)} {kind} "
            f"cover the reference Next disjuncts exactly."
        )
        return 0

    if args.cmd == "simulate":
        from ..engine.simulate import simulate

        model = _build_or_fail(
            module, tlc_cfg, emitted=_kernel_source(args, module)
        )
        res = simulate(
            model, num_walks=args.walks, max_depth=args.depth, seed=args.seed
        )
        if res.violation is None:
            print(
                f"Simulation: {args.walks} walks x depth {args.depth}, "
                f"{res.total} states visited, no violations "
                f"({res.states_per_sec:,.0f} states/sec)."
            )
        else:
            _print_result(res, args.json, model_meta=model.meta)
        return 0 if res.violation is None else 1

    if args.cmd == "oracle":
        from ..oracle.interp import oracle_bfs

        om = _build_or_fail(module, tlc_cfg, oracle=True)
        t0 = time.perf_counter()
        r = oracle_bfs(
            om,
            max_depth=args.max_depth,
            max_states=args.max_states,
            keep_level_sets=False,
            check_deadlock=tlc_cfg.check_deadlock,
        )
        dt = time.perf_counter() - t0
        print(
            f"Oracle: {r.total} distinct states, diameter {r.diameter}, "
            f"{dt:.2f}s ({r.total / max(dt, 1e-9):,.0f} states/sec)"
        )
        if r.violation:
            name, depth, _ = r.violation
            print(f"Invariant {name} is VIOLATED at depth {depth}.")
            from .pretty import render_trace

            print("Counterexample trace:")
            print(render_trace(om.meta, r.trace))
        else:
            print("No invariant violations. Exhaustive check complete.")
        return 0 if r.violation is None else 1

    model = _build_or_fail(
        module, tlc_cfg, emitted=_kernel_source(args, module)
    )
    if args.cmd == "check" and getattr(model, "symmetry", None) is not None:
        # the engines raise on these too (engine.bfs.check, check_sharded);
        # said here first, as a usage error and before a run directory opens
        refused = [flag for flag, given in (
            ("--sharded", args.sharded),
            ("--checkpoint", args.checkpoint),
            ("--integrity-shadow", getattr(args, "integrity_shadow", None)),
        ) if given]
        if refused:
            print(
                f"error: SYMMETRY {model.symmetry.operator} "
                f"({tlc_cfg.symmetry!r} in the .cfg) is supported on the "
                f"single-device engine without {' / '.join(refused)}: a "
                "state's key is its orbit's there, which the sharded "
                "engine does not compute and a checkpoint's validation "
                "cannot recompute; drop the option or the SYMMETRY stanza",
                file=sys.stderr,
            )
            return 2
    run_ctx = None
    if args.cmd == "check" and _is_obs_coordinator():
        # every check invocation gets a run directory: manifest + stats +
        # spans + metrics correlated under one run_id (cli report renders
        # it, live or post-mortem — docs/observability.md).  One writer
        # per job: in a multi-process sharded run only process 0 opens
        # the run dir (the replicated loops would otherwise race the
        # manifest or strand never-finished orphan dirs)
        from ..obs import RunContext

        run_ctx = RunContext(args.run_dir)
        run_ctx.record_config(
            module=module,
            cfg=args.cfg,
            sharded=bool(args.sharded),
            checkpoint=args.checkpoint,
            stats=args.stats,
        )
        spill_defaulted = False
        if args.mem_budget is not None and args.spill_dir is None \
                and args.checkpoint is None:
            # un-homed disk tier: spill under the run dir instead of an
            # ephemeral tmp dir — a crashed run's spill is then
            # inspectable next to its stats/spans.  Like the ephemeral
            # tmp it replaces, it is deleted once the run completes
            # (checkpointed runs keep <checkpoint>/spill: the tier lives
            # and dies with the checkpoints that reference it)
            args.spill_dir = run_ctx.spill_dir
            spill_defaulted = True
        from .platform_guard import device_label, device_stamp

        print(
            f"[obs] run dir: {run_ctx.dir} (run {run_ctx.run_id}) on "
            f"{device_label(device_stamp())}",
            file=sys.stderr,
        )
    progress = None
    if args.progress:
        def progress(depth, new_n, total):
            print(f"  level {depth}: {new_n} new, {total} total", file=sys.stderr)

    import contextlib

    prof = contextlib.nullcontext()
    if args.profile:
        import jax

        prof = jax.profiler.trace(args.profile)
    chunk_kw = {} if args.chunk_size is None else {"chunk_size": args.chunk_size}
    from ..resilience.integrity import EXIT_INTEGRITY, IntegrityError
    from ..resilience.resources import (
        EXIT_RESOURCE_EXHAUSTED,
        ResourceExhausted,
    )

    assert EXIT_RESOURCE_EXHAUSTED == _EXIT_RESOURCE_EXHAUSTED
    assert EXIT_INTEGRITY == _EXIT_INTEGRITY
    try:
        with prof:
            res = _run_engine(args, model, tlc_cfg, progress, chunk_kw,
                              run=run_ctx)
    except IntegrityError as e:
        # typed integrity terminal: the run's DATA failed a check (digest
        # chain / shadow / framing / read-side CRC), the manifest is
        # stamped `integrity-violation`, and the distinct exit code lets
        # supervisors restart from the newest chain-verified generation
        # (corrupted ones are skipped by the resume-path validators)
        print(f"INTEGRITY VIOLATION: {e}", file=sys.stderr)
        if args.json:
            from ..service.verdict import error_verdict

            json.dump(
                error_verdict(
                    f"INTEGRITY_VIOLATION[{e.site}]: {e.detail}",
                    run_id=run_ctx.run_id if run_ctx is not None else None,
                    exit_code=EXIT_INTEGRITY,
                ),
                sys.stdout,
            )
            print()
        if args.checkpoint:
            print(
                f"  re-running resumes from the newest chain-verified "
                f"generation in {args.checkpoint} (verify offline with "
                f"`... verify-checkpoint {args.checkpoint}`).  Recurring "
                f"violations on one host suggest failing hardware",
                file=sys.stderr,
            )
        else:
            print(
                "  no --checkpoint was configured: a re-run starts over "
                "(add --checkpoint so integrity exits resume from the "
                "newest chain-verified generation)",
                file=sys.stderr,
            )
        return EXIT_INTEGRITY
    except ResourceExhausted as e:
        # the typed terminal: the engine already checkpointed what it
        # could, stamped the run manifest, and left every promoted
        # generation verifiable — tell the operator what ran out and how
        # to resume, and exit with the distinct resource code (75) so
        # supervisors never classify this as a crash
        print(f"RESOURCE EXHAUSTED: {e}", file=sys.stderr)
        if args.json:
            # the stable verdict record covers ALL exits (0/1/75/2): a
            # client switching between local runs and submitted jobs must
            # get a kspec-verdict/1 object on the rc-75 path too, exactly
            # like `cli result` does for a resource-exhausted service job
            from ..service.verdict import error_verdict

            json.dump(
                error_verdict(
                    f"RESOURCE_EXHAUSTED[{e.reason}]: {e.detail}",
                    run_id=run_ctx.run_id if run_ctx is not None else None,
                    exit_code=EXIT_RESOURCE_EXHAUSTED,
                ),
                sys.stdout,
            )
            print()
        if args.checkpoint:
            print(
                f"  checkpoint intact at {args.checkpoint} — verify with "
                f"`... verify-checkpoint {args.checkpoint}`, free space "
                f"(or raise --disk-budget), then re-run the same command "
                f"to resume",
                file=sys.stderr,
            )
        else:
            print(
                "  no --checkpoint was configured: a re-run starts over "
                "(add --checkpoint to make resource exits resumable)",
                file=sys.stderr,
            )
        return EXIT_RESOURCE_EXHAUSTED
    if run_ctx is not None and spill_defaulted:
        # completed run: the spilled fingerprint data is dead weight (the
        # spill accounting lives on in metrics/spans); only a crash —
        # which never reaches here — leaves it behind for post-mortems
        import shutil

        shutil.rmtree(run_ctx.spill_dir, ignore_errors=True)
    _print_result(
        res, args.json, model_meta=model.meta,
        run_id=run_ctx.run_id if run_ctx is not None else None,
    )
    return 0 if res.violation is None else 1



def _run_simfleet(args) -> int:
    """`cli simfleet run|replay`: the deterministic fleet simulator.

    Exit codes — run: 0 = every seed clean, 1 = violations (repros
    banked under --out), 2 = bad arguments.  replay: 0 = the repro
    still reproduces its recorded violation, 2 = stale."""
    from ..resilience import simfleet as sf

    if args.sf_cmd == "run":
        cfg = sf.SimConfig(hosts=args.hosts, jobs=args.jobs,
                           steps=args.steps)
        if args.seeds < 1 or args.hosts < 1 or args.jobs < 0:
            print("error: --seeds/--hosts must be >= 1", file=sys.stderr)
            return 2
        seeds = range(args.start_seed, args.start_seed + args.seeds)
        summary = sf.sweep_seeds(
            seeds, config=cfg, coverage=args.coverage,
            max_extra=max(2, args.seeds // 5) if args.coverage else 0,
        )
        banked = []
        for hit in summary["violating"]:
            seed, record = hit["seed"], hit["record"]
            v = record["violations"][0]
            try:
                small, srec = sf.shrink(record["schedule"], cfg, seed,
                                        v["oracle"])
            except ValueError:
                # drain-phase-only violation on an empty-ish schedule:
                # the full schedule IS the minimal repro
                small, srec = record["schedule"], record
            os.makedirs(args.out, exist_ok=True)
            path = os.path.join(
                args.out, f"repro-seed{seed}-{v['oracle']}.json")
            # bank the violation as the SHRUNK run reports it: job ids
            # shift when submit events drop out of the schedule, and a
            # repro must name a job that exists in its own replay
            sv = next((w for w in srec["violations"]
                       if w["oracle"] == v["oracle"]), v)
            sf.save_repro(path, seed, cfg, sv, small, srec,
                          shrunk_from=len(record["schedule"]))
            banked.append({"seed": seed, "oracle": v["oracle"],
                           "events": len(small), "path": path})
        rec = {
            "schema": "kspec-simfleet-sweep/1",
            "config": summary["config"],
            "runs": summary["runs"],
            "clean": summary["clean"],
            "pair_coverage": summary["pair_coverage"],
            "violations": banked,
            "ok": not banked,
        }
        if args.json:
            print(json.dumps(rec))
        else:
            print(f"kspec simfleet: {rec['runs']} seed(s) — "
                  f"{rec['clean']} clean, {len(banked)} violating "
                  f"({rec['pair_coverage']} event-pair(s) covered)")
            for b in banked:
                print(f"  VIOLATION seed {b['seed']}: {b['oracle']} — "
                      f"shrunk to {b['events']} event(s), repro at "
                      f"{b['path']}")
        return 0 if rec["ok"] else 1

    # replay
    try:
        repro = sf.load_repro(args.repro)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    out = sf.replay_repro(repro, keep_root=args.trace)
    record = out["record"]
    rec = {
        "schema": "kspec-simfleet-replay/1",
        "repro": {k: repro[k] for k in
                  ("seed", "violation", "events_digest", "shrunk_from")},
        "reproduced": out["reproduced"],
        "digest_match": out["digest_match"],
        "violations": record["violations"],
    }
    try:
        if args.json:
            print(json.dumps(rec))
        else:
            v = repro["violation"]
            state = ("REPRODUCED" if out["reproduced"] else
                     "STALE (violation no longer fires)")
            print(f"kspec simfleet replay: {state} — {v['oracle']} "
                  f"on {v['job']} over {len(repro['schedule'])} "
                  f"event(s); digest "
                  f"{'match' if out['digest_match'] else 'DRIFT'}")
            for got in record["violations"]:
                print(f"  {got['oracle']} @step {got['step']} "
                      f"job={got['job']}: {got['detail']}")
            if args.trace and out["kernel"] is not None:
                from ..obs import fleettrace as ft

                job = (next((g["job"] for g in record["violations"]
                             if g.get("job")), None)
                       or v.get("job")
                       or next(iter(record["verdicts"]), None))
                if job:
                    recs = ft.load_trace(out["kernel"].trace_roots(),
                                         job)
                    if recs:
                        print()
                        print(ft.render_trace(ft.assemble(recs, job)))
                    else:
                        print(f"  (no trace records for {job})")
    finally:
        if out["kernel"] is not None:
            out["kernel"].cleanup()
    return 0 if out["reproduced"] else 2


def _run_analyze(args) -> int:
    """`cli analyze`: the spec & engine static-analysis driver.

    Exit codes: 0 = no HIGH findings, 1 = HIGH findings, 2 = a target
    could not even be analyzed (unreadable cfg, unknown module)."""
    from pathlib import Path

    from ..analysis import (
        analysis_record,
        analyze_engine_sources,
        install_jax_stub,
        repo_root,
    )

    install_jax_stub()
    findings = []
    targets = []
    rc_error = 0

    if not args.no_models:
        from ..analysis.encoding import EncodingUnsound, analyze_model

        cfg_paths = list(args.cfgs)
        if args.module and len(cfg_paths) != 1:
            # never silently drop an explicit flag: --module pairs with
            # exactly one .cfg (the default matrix resolves its own)
            print(
                "error: --module requires exactly one .cfg argument "
                f"(got {len(cfg_paths)})",
                file=sys.stderr,
            )
            return 2
        if not cfg_paths:
            cfg_paths = sorted(
                str(p) for p in Path(repo_root(), "configs").glob("*.cfg")
            )
        for path in cfg_paths:
            stem = Path(path).stem
            module = args.module or CFG_MODULE_ALIASES.get(stem, stem)
            targets.append(f"{module} ({path})")
            try:
                tlc_cfg = parse_cfg(path)
                # analysis_gate=False: the gate raises on the FIRST HIGH
                # finding; the analyzer wants the full list instead
                model = build_model(module, tlc_cfg, analysis_gate=False)
            except EncodingUnsound as e:
                findings.extend(e.findings)
                continue
            except (OSError, ValueError, KeyError) as e:
                # the record must reflect the failure too: a JSON
                # consumer keying off `ok` must never read a partially
                # analyzed matrix as verified clean
                from ..analysis import Finding

                findings.append(Finding(
                    kind="analysis-error", severity="HIGH",
                    target=f"{module} ({path})",
                    message=f"cannot analyze: {e}",
                    data={"path": str(path), "module": module},
                ))
                print(f"error: cannot analyze {path}: {e}",
                      file=sys.stderr)
                rc_error = 2
                continue
            findings.extend(analyze_model(model))

    if not args.no_engine:
        targets.append("engine sources (ownership + purity)")
        findings.extend(analyze_engine_sources())
        # span-kind vocabulary lint (obs/fleettrace registries): every
        # span/event emitted anywhere in the package must name a
        # registered kind, and every registered kind must appear in
        # docs/observability.md — an undocumented or typo'd kind would
        # silently vanish from `cli trace`'s stage decomposition
        targets.append("trace vocabulary (obs/fleettrace registries)")
        from ..analysis import Finding
        from ..obs.fleettrace import lint_trace_vocabulary

        for prob in lint_trace_vocabulary():
            findings.append(Finding(
                kind="trace-vocab", severity="HIGH",
                target=f"{prob['path']}:{prob['line']}",
                message=prob["problem"],
                data=dict(prob),
            ))
        # durable-write discipline lint (analysis/durable_lint): every
        # rename/replace and append journal must route through the
        # durable_io shim (or a registered emitter) so the crashcheck
        # harness records it — an unrecorded durable effect is a crash
        # state the torture harness silently never enumerates
        targets.append("durable-write discipline (durable_io boundary)")
        from ..analysis.durable_lint import lint_durable_io

        for prob in lint_durable_io():
            findings.append(Finding(
                kind="durable-io", severity="HIGH",
                target=f"{prob['path']}:{prob['line']}",
                message=prob["problem"],
                data=dict(prob),
            ))
        # raw-clock discipline lint (analysis/clock_lint): every timing
        # decision in a clock-migrated module must route through
        # utils/clock.py so the simfleet virtual clock owns it — a raw
        # time.time()/sleep()/monotonic() site silently reads the real
        # wall clock under simulation and breaks seed determinism
        targets.append("raw-clock discipline (utils/clock boundary)")
        from ..analysis.clock_lint import lint_raw_clock

        for prob in lint_raw_clock():
            findings.append(Finding(
                kind="raw-clock", severity="HIGH",
                target=f"{prob['path']}:{prob['line']}",
                message=prob["problem"],
                data=dict(prob),
            ))

    rec = analysis_record(findings, targets=targets)
    if args.json:
        print(json.dumps(rec))
    else:
        c = rec["counts"]
        print(
            f"kspec analyze: {len(targets)} target(s) — "
            f"{c['HIGH']} high / {c['MEDIUM']} medium / {c['LOW']} low / "
            f"{c['INFO']} info"
        )
        shown = [f for f in findings
                 if args.info or f.severity != "INFO"]
        for f in shown:
            tag = f" [suppressed: {f.suppressed}]" if f.suppressed else ""
            print(f"  {f.severity:<6} {f.kind:<24} {f.target}{tag}")
            print(f"         {f.message}")
        if not shown:
            print("  clean: encoding sound, frames honored, ownership "
                  "contracts verified")
    if rc_error:
        return rc_error
    return 0 if rec["ok"] else 1


def _service_dir(given) -> str:
    return given or os.environ.get("KSPEC_SERVICE_DIR", "service")


def _run_router(args) -> int:
    """`cli route`: cross-host placement + dead-host recovery.  Jax-free
    by contract (it runs on the operator box, often while a host is
    down — the worst possible moment for a cold start)."""
    from ..service.router import Router

    try:
        router = Router(
            args.router_dir,
            hosts=args.hosts,
            dead_after_s=args.dead_after,
        )
    except (FileNotFoundError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.status:
        ov = router.overview()
        if args.json:
            print(json.dumps(ov))
        else:
            print(
                f"router {ov['dir']}: {len(ov['hosts'])} hosts, "
                f"{ov['routes']} routed jobs, dead after "
                f"{ov['dead_after_s']}s (+{ov['clock_skew_s']}s skew)"
            )
            for h in ov["hosts"]:
                age = h["hb_age_s"]
                age_s = "never" if age is None else f"{age:.1f}s ago"
                print(
                    f"  host{h['host']} [{h['state']:>6}] {h['dir']}: "
                    f"{h['pending']} pending, {h['claimed']} in flight, "
                    f"heartbeat {age_s}"
                )
        return 0

    if args.once:
        out = router.sweep()
        if args.json:
            print(json.dumps(out))
        else:
            dead = [h["host"] for h in out["hosts"]
                    if h["state"] == "dead"]
            took = sum(len(v) for v in out["takeover"].values())
            moved = sum(len(v) for v in out["rerouted"].values())
            print(
                f"sweep: {len(dead)} dead hosts"
                + (f" ({', '.join(f'host{i}' for i in dead)})"
                   if dead else "")
                + f", {took} claims taken over, "
                f"{moved} pending jobs re-routed"
            )
        return 0

    print(
        f"router serving {len(router.hosts)} hosts from {router.dir} "
        f"(poll {args.poll}s)",
        file=sys.stderr,
    )
    import signal

    signal.signal(signal.SIGTERM, lambda *_: router.request_stop())
    try:
        router.serve(poll_s=args.poll)
    except KeyboardInterrupt:
        pass
    return 0


def _run_fleet_obs(args) -> int:
    """`cli trace|top|fleet-report`: the fleet trace plane's read side
    (obs/fleettrace.py, docs/observability.md § Fleet traces).  Jax-free
    by contract — everything renders from side-channel files."""
    from ..obs import fleettrace as ft

    router_dir = getattr(args, "router", None)
    if router_dir:
        from ..service.router import Router

        try:
            router = Router(router_dir)
        except (FileNotFoundError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        roots = [router.dir] + [q.dir for q in router.queues]
        host_roots = [q.dir for q in router.queues]
    else:
        host_roots = [
            os.path.normpath(d)
            for d in (getattr(args, "service_dir", None)
                      or [_service_dir(None)])
        ]
        roots = host_roots

    if args.cmd == "trace":
        recs = ft.load_trace(roots, args.job_id)
        if not recs:
            print(
                f"no trace for job {args.job_id} under "
                + ", ".join(roots),
                file=sys.stderr,
            )
            return 1
        data = ft.assemble(recs, job_id=args.job_id)
        print(json.dumps(data, default=str) if args.json
              else ft.render_trace(data))
        return 0

    if args.cmd == "fleet-report":
        data = ft.fleet_report_data(roots, exemplars=args.exemplars)
        if args.json:
            print(json.dumps(data, default=str))
        else:
            print(ft.render_fleet_report(data))
        return 0

    # top: one frame under --once/--json, else redraw until interrupted
    if args.json:
        print(json.dumps(
            ft.top_data(host_roots, router_dir=router_dir), default=str
        ))
        return 0
    try:
        while True:
            frame = ft.render_top(
                ft.top_data(host_roots, router_dir=router_dir)
            )
            if args.once:
                print(frame)
                return 0
            # whole-frame redraw: clear + home, then the frame (the
            # watch(1) idiom; no curses dependency)
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(max(0.2, args.interval))
    except KeyboardInterrupt:
        return 0


def _run_sweep(args) -> int:
    """`cli sweep plan|run|report|bisect`: the coverage-sweep subsystem
    (sweep/ package, docs/sweep.md).  Jax-free by contract — a sweep is
    a queue/router client; daemons do the engine work."""
    from ..sweep import (
        SweepConfig,
        load_lattice,
        load_manifest,
        plan_sweep,
        run_sweep,
    )

    if args.sweep_cmd == "plan":
        try:
            lattice = load_lattice(args.lattice)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        cfg = SweepConfig(
            sweep_dir=".",  # plan never writes
            service_dir=_service_dir(getattr(args, "service_dir", None)),
            state_cache_dir=args.state_cache_dir,
        )
        plan = plan_sweep(lattice, cfg)
        if args.json:
            print(json.dumps({
                "lattice": lattice.record(),
                "points": len(plan["points"]),
                "runnable": len(plan["runnable"]),
                "deferred": len(plan["deferred"]),
                "skipped": [
                    {"point": p.record(), "findings": p.vacuous}
                    for p in plan["skipped"]
                ],
                "cost_model": plan["model"].to_dict(),
                "predictions": plan["predictions"],
            }))
            return 0
        m = plan["model"]
        total_states = sum(
            plan["predictions"][p.point_id]["states"]
            for p in plan["runnable"] + plan["deferred"]
        )
        total_s = sum(
            plan["predictions"][p.point_id]["seconds"] or 0.0
            for p in plan["runnable"] + plan["deferred"]
        )
        print(
            f"lattice {lattice.name}: {len(plan['points'])} points "
            f"({len(plan['runnable'])} runnable, "
            f"{len(plan['deferred'])} deferred, "
            f"{len(plan['skipped'])} skipped as statically vacuous)"
        )
        print(
            f"cost model: {m.n_records} corpus records, predicted "
            f"~{total_states} states, ~{total_s:.1f}s engine wall "
            "(flat-throughput; honesty limits in docs/sweep.md)"
        )
        for p in plan["skipped"][:8]:
            acts = ", ".join(
                f.get("target", "?") for f in p.vacuous[:3]
            )
            print(f"  skipped: vacuous {dict(p.coords)} [{acts}]")
        if len(plan["skipped"]) > 8:
            print(f"  ... and {len(plan['skipped']) - 8} more")
        return 0

    if args.sweep_cmd == "run":
        try:
            lattice = load_lattice(args.lattice)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        cfg = SweepConfig(
            sweep_dir=args.sweep_dir,
            service_dir=(
                None if args.router
                else _service_dir(args.service_dir)
            ),
            router_dir=args.router,
            tenant=args.tenant,
            max_inflight=args.max_inflight,
            solo_threshold_states=args.solo_threshold,
            wait_timeout_s=args.timeout,
            state_cache_dir=args.state_cache_dir,
        )
        rec = run_sweep(lattice, cfg, log=lambda s: print(s))
        if args.json:
            print(json.dumps(rec))
        incomplete = sum(
            1 for row in rec["points"].values()
            if row.get("status") in ("pending", "submitted")
        )
        errors = sum(
            1 for row in rec["points"].values()
            if row.get("status") == "error"
        )
        return 1 if errors else (75 if incomplete else 0)

    if args.sweep_cmd == "report":
        from ..obs.report import render_sweep_report, sweep_report_data

        try:
            data = sweep_report_data(args.sweep_dir)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        print(json.dumps(data) if args.json else render_sweep_report(data))
        return 0

    # bisect: witness the frontier (runs neighbors through the service)
    from ..sweep.bisect import refine_frontier
    from ..sweep.lattice import enumerate_points
    from ..sweep.portfolio import Dispatcher

    try:
        man = load_manifest(args.sweep_dir)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    runner = None
    if args.service_dir or args.router:
        cfg = SweepConfig(
            sweep_dir=args.sweep_dir,
            service_dir=(
                None if args.router
                else _service_dir(args.service_dir)
            ),
            router_dir=args.router,
            tenant=args.tenant,
        )
        dispatch = Dispatcher(cfg)

        def runner(coords):
            # synthesize the probe point by re-enumerating the lattice
            # restricted to these coordinates: same canonical keys, so
            # the probe may itself be a state-cache hit
            from ..sweep.lattice import load_lattice as _ll

            spec = _ll(dict(man["lattice"]))
            want = dict(coords)
            for p in enumerate_points(spec):
                if dict(p.coords) == want:
                    import os as _os

                    jid = (
                        f"probe-{man['sweep_id']}-"
                        f"{p.point_id.replace(':', '-')}-"
                        f"{_os.urandom(2).hex()}"
                    )
                    dispatch.submit(p, jid, solo=True)
                    rec = dispatch.backend.wait_result(
                        jid, timeout=args.timeout
                    )
                    return rec or {}
            return {}
    else:

        def runner(coords):
            return {}  # manifest-only mode: unknown neighbors stay unrun

    out = refine_frontier(
        man, runner, log=lambda s: print(s, file=sys.stderr),
        invariant=args.invariant, max_probes=args.max_probes,
    )
    if args.json:
        print(json.dumps(out))
        return 0
    if not out:
        print("no violating points in the manifest — nothing to bisect")
        return 0
    for inv in sorted(out):
        rep = out[inv]
        print(f"{inv}: frontier of {len(rep['frontier'])} minimal "
              f"violating configs ({len(rep['witnesses'])} neighbors "
              f"witnessed, {len(rep['demoted'])} claims demoted)")
        for r in rep["frontier"]:
            coords = r.get("coords")
            print(f"  {dict(coords) if coords else r.get('_indices')}")
    return 0


def _run_service_client(args) -> int:
    """submit / status / result: the tenants' side of the service.  Only
    jax-free imports allowed here — the zero-cold-start contract."""
    from ..service.queue import JobQueue
    from ..service.verdict import render_verdict, verdict_exit_code

    router = None
    if getattr(args, "router", None):
        # --router: resolve through the cross-host router instead of a
        # single service dir (still jax-free — router.py never imports
        # jax).  Placement and the fleet-WIDE tenant admission check
        # live inside Router.submit
        from ..service.router import Router

        try:
            router = Router(args.router)
        except (FileNotFoundError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        q = None
    else:
        try:
            # submit creates the tree (tenants may enqueue before the
            # first daemon start); status/result are read-only so a
            # mistyped --service-dir errors instead of minting an empty
            # service tree
            q = JobQueue(
                _service_dir(args.service_dir), create=args.cmd == "submit"
            )
        except FileNotFoundError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    if args.cmd == "submit":
        from pathlib import Path

        try:
            cfg_text = Path(args.cfg).read_text()
        except OSError as e:
            print(f"error: cannot read {args.cfg}: {e}", file=sys.stderr)
            return 2
        module = args.module or Path(args.cfg).stem
        try:
            tlc_cfg = parse_cfg(cfg_text)  # validate before queueing
        except ValueError as e:
            print(f"error: cannot parse {args.cfg}: {e}", file=sys.stderr)
            return 2
        if args.hand and args.emitted:
            print("error: --hand and --emitted are mutually exclusive",
                  file=sys.stderr)
            return 2
        if args.fault:
            from ..resilience.faults import FaultPlan

            try:
                FaultPlan(args.fault)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
        if router is None:
            # admission control: the tenant's max_pending cap (advisory
            # — the check is client-side so a racing burst can
            # overshoot; the budget that matters, the resource
            # governor, is daemon-side).  With --router the check moves
            # inside Router.submit, where it is fleet-wide
            from ..resilience.resources import (
                budget_for_tenant,
                load_tenant_budgets,
            )

            try:
                budgets = load_tenant_budgets(q.tenants_path)
            except (OSError, ValueError) as e:
                print(f"error: bad tenants.json: {e}", file=sys.stderr)
                return 2
            b = budget_for_tenant(budgets, args.tenant)
            if b is not None and b.max_pending is not None:
                mine = q.pending_for_tenant(
                    args.tenant, stop_at=b.max_pending
                )
                if mine >= b.max_pending:
                    print(
                        f"error: tenant {args.tenant!r} at max_pending="
                        f"{b.max_pending} ({mine} queued) — drain or raise "
                        f"the cap in tenants.json",
                        file=sys.stderr,
                    )
                    return 2
        kernel_source = (
            "emitted" if args.emitted else "hand" if args.hand else "auto"
        )
        try:
            # the submit-side router retries transient queue-dir errors
            # (EAGAIN/EIO/ESTALE — network filesystems) with bounded
            # backoff inside JobQueue.submit; only a PERSISTENT failure
            # reaches here, rendered cleanly instead of as a traceback
            spec = (router or q).submit(
                cfg_text,
                module,
                tenant=args.tenant,
                cfg_path=args.cfg,
                kernel_source=kernel_source,
                max_depth=args.max_depth,
                max_states=args.max_states,
                fault=args.fault,
            )
        except OSError as e:
            where = router.dir if router is not None else q.dir
            print(
                f"error: cannot publish job to {where!r} after retries: "
                f"{e}",
                file=sys.stderr,
            )
            return 2
        except RuntimeError as e:
            # AdmissionDenied: the router's fleet-wide tenant cap
            print(f"error: {e}", file=sys.stderr)
            return 2
        where = (
            f"host{spec['host']} ({router.hosts[spec['host']]})"
            if router is not None
            else q.dir
        )
        if args.json and not args.wait:
            out = {"job_id": spec["job_id"]}
            if router is not None:
                out["host"] = spec["host"]
                out["service_dir"] = router.hosts[spec["host"]]
            else:
                out["service_dir"] = q.dir
            print(json.dumps(out))
        else:
            print(f"submitted {spec['job_id']} (tenant {args.tenant}) -> "
                  f"{where}", file=sys.stderr)
        if not args.wait:
            if not args.json:
                print(spec["job_id"])
            return 0
        rec = (router or q).wait_result(spec["job_id"], timeout=args.timeout)
        if rec is None:
            hint = (
                f"`cli route {router.dir} --status`" if router is not None
                else f"`cli serve {q.dir}`"
            )
            print(
                f"error: no verdict for {spec['job_id']} within "
                f"{args.timeout}s (is the daemon up?  {hint})",
                file=sys.stderr,
            )
            return 2
        print(json.dumps(rec) if args.json else render_verdict(rec))
        return verdict_exit_code(rec)

    if args.cmd == "status":
        if args.job_id is None:
            ov = (router or q).overview()
            if args.json:
                print(json.dumps(ov))
            elif router is not None:
                print(
                    f"router {ov['dir']}: {len(ov['hosts'])} hosts, "
                    f"{ov['routes']} routed jobs"
                )
                for h in ov["hosts"]:
                    print(
                        f"  host{h['host']} [{h['state']:>6}] {h['dir']}: "
                        f"{h['pending']} pending, {h['claimed']} in flight"
                    )
            else:
                c = ov["counts"]
                print(
                    f"service {ov['dir']}: {c['pending']} pending, "
                    f"{c['claimed']} in flight, {c['done']} done"
                )
                for jid in ov["recent_done"]:
                    rec = q.result(jid) or {}
                    print(f"  {jid}  {rec.get('status', '?')}")
            return 0
        st = (router or q).status(args.job_id)
        if args.json:
            print(json.dumps(st))
        else:
            line = f"{st['job_id']}: {st['state']}"
            if st.get("host") is not None:
                line += f" @ host{st['host']}"
            rec = st.get("result")
            if rec:
                line += f" ({rec.get('status', '?')})"
            print(line)
        return 0 if st["state"] != "unknown" else 1

    # result
    rec = (
        (router or q).wait_result(args.job_id, timeout=args.timeout)
        if args.wait
        else (router or q).result(args.job_id)
    )
    if rec is None:
        print(
            f"error: no verdict for {args.job_id}"
            + ("" if args.wait else " (yet — use --wait)"),
            file=sys.stderr,
        )
        return 2
    print(json.dumps(rec) if args.json else render_verdict(rec))
    return verdict_exit_code(rec)


def _print_verify_checkpoint(rep: dict) -> None:
    print(f"Checkpoint directory: {rep['dir']}")
    if rep.get("error"):
        print(f"  ERROR: {rep['error']}")
    if not rep["stores"]:
        print("  no checkpoint files found")
    for store in rep["stores"]:
        print(f"  {store['basename']}: "
              f"{'OK' if store['ok'] else 'NOT RESUMABLE'}")
        for g in store["generations"]:
            bits = [f"gen {g['gen']}", f"depth {g.get('depth')}"]
            if "mesh_D" in g:
                bits.append(f"shards {g['mesh_D']} x procs {g.get('mesh_P')}")
            if g.get("digest_chain") and g["digest_chain"] != "absent":
                bits.append(f"chain {g['digest_chain']}")
            if g.get("parts"):
                bits.append(
                    "parts " + ",".join(
                        f"{p}@{gen}" if gen is not None else f"{p}@MISSING"
                        for p, gen in sorted(g["parts"].items())
                    )
                )
            if "spill" in g:
                bits.append(
                    f"spill {g['spill']['files_checked']} files "
                    + ("resolved" if g["spill"]["ok"] else "BROKEN")
                )
            status = "ok" if g["ok"] else "FAILED"
            print(f"    {status:>6}  " + "  ".join(bits))
            for e in g["errors"]:
                print(f"            - {e}")
    print(f"Verdict: {'resumable' if rep['ok'] else 'NOT resumable'}")


def _is_obs_coordinator() -> bool:
    """True unless this is a non-coordinator process of a multi-process
    jax job (jax is initialized by model building before this runs)."""
    try:
        import jax

        return jax.process_index() == 0
    except Exception:
        return True


def _run_resilient(args, argv) -> int:
    """`check --resilient`: re-run this command under the supervisor.

    The child is this same CLI minus --resilient; engines resume from
    --checkpoint automatically, so a restart is just a re-run.  The parent
    opens the run directory and hands it to every child attempt: one
    run_id correlates the supervisor's events with each attempt's stats
    and spans (a restart reopens the run, appending to its lineage)."""
    from pathlib import Path

    from ..obs import RunContext
    from ..resilience.supervisor import SupervisorConfig, supervise

    # strip the flag AND its argparse prefix abbreviations ("--resil" also
    # sets args.resilient; letting it through would make every child spawn
    # its own supervisor recursively)
    child_argv = [
        a
        for a in argv
        if not (a.startswith("--re") and "--resilient".startswith(a))
    ]
    run_ctx = RunContext(args.run_dir)
    if args.run_dir is None:
        child_argv += ["--run-dir", run_ctx.dir]
    if not args.stats:
        # heartbeat lives in the run dir by default — the stall detector
        # always has a stream to watch
        args.stats = run_ctx.stats_path
        child_argv += ["--stats", args.stats]
    if not args.checkpoint:
        print(
            "warning: --resilient without --checkpoint — a restarted run "
            "starts over from the initial states",
            file=sys.stderr,
        )
    events = args.events or run_ctx.events_path
    run_ctx.record_config(
        module=args.module or Path(args.cfg).stem,
        cfg=args.cfg,
        supervised=True,
        stall_timeout=args.stall_timeout,
        max_restarts=args.max_restarts,
    )
    if args.checkpoint:
        os.makedirs(args.checkpoint, exist_ok=True)
    print(
        f"[obs] run dir: {run_ctx.dir} (run {run_ctx.run_id})",
        file=sys.stderr,
    )
    cfg = SupervisorConfig(
        cmd=[sys.executable, "-m", "kafka_specification_tpu.utils.cli"]
        + child_argv,
        heartbeat=args.stats,
        events=events,
        log_dir=run_ctx.log_dir,
        stall_timeout=args.stall_timeout,
        max_restarts=args.max_restarts,
        env=dict(os.environ),
        run_id=run_ctx.run_id,
        # resource-exit policy: halt with a verdict, or prune + retry
        # once under --reclaim (never restart into a full disk)
        reclaim=bool(args.reclaim),
        reclaim_dirs=tuple(
            d for d in (args.checkpoint, args.spill_dir) if d
        ),
    )
    return supervise(cfg)


def _kernel_source(args, module) -> bool:
    """Resolve check/simulate kernel source: True = emitted (the default
    when the reference corpus is on disk), False = hand-translated.

    The north star wants stock specs + .cfg to drive the checker — so the
    mechanical path is the default engine and the hand kernels are the
    independent cross-check (`--hand`), mirroring how the test suite holds
    the two to exact state-set equality."""
    if args.hand and args.emitted:
        print("error: --hand and --emitted are mutually exclusive", file=sys.stderr)
        raise SystemExit(2)
    if args.hand:
        return False
    if args.emitted:
        return True
    from ..models.emitted import ref_path

    ref = ref_path()
    if (ref / f"{module}.tla").exists():
        return True
    print(
        f"note: no reference checkout at {ref} (set KSPEC_REFERENCE) — "
        f"using hand-translated kernels",
        file=sys.stderr,
    )
    return False


def _build_or_fail(module, tlc_cfg, oracle=False, emitted=False, reference=None):
    try:
        return build_model(
            module, tlc_cfg, oracle=oracle, emitted=emitted, reference=reference
        )
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        raise SystemExit(2)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)


def _run_engine(args, model, tlc_cfg, progress, chunk_kw, run=None):
    store_kw = dict(
        mem_budget=args.mem_budget,
        spill_dir=args.spill_dir,
        store=args.store,
        disk_budget=args.disk_budget,
        run=run,
        overlap=getattr(args, "overlap", None),
    )
    if args.sharded:
        from ..parallel.sharded import check_sharded

        res = check_sharded(
            model,
            max_depth=args.max_depth,
            max_states=args.max_states,
            min_bucket=args.min_bucket,
            progress=progress,
            check_deadlock=tlc_cfg.check_deadlock,
            store_trace=not args.no_trace,
            checkpoint_dir=args.checkpoint,
            checkpoint_every=args.checkpoint_every,
            checkpoint_keep=args.checkpoint_keep,
            stats_path=args.stats,
            visited_backend=args.visited_backend,
            pipeline=getattr(args, "pipeline", None),
            **store_kw,
            **chunk_kw,
        )
    else:
        from ..engine.bfs import check

        res = check(
            model,
            max_depth=args.max_depth,
            max_states=args.max_states,
            store_trace=not args.no_trace,
            min_bucket=args.min_bucket,
            progress=progress,
            checkpoint_dir=args.checkpoint,
            checkpoint_every=args.checkpoint_every,
            checkpoint_keep=args.checkpoint_keep,
            check_deadlock=tlc_cfg.check_deadlock,
            stats_path=args.stats,
            visited_backend=args.visited_backend,
            pipeline=getattr(args, "pipeline", None),
            integrity_shadow=getattr(args, "integrity_shadow", None),
            **store_kw,
            **chunk_kw,
        )
    return res


if __name__ == "__main__":
    sys.exit(main())
