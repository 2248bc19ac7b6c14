"""Resilience subsystem: fault injection, hardened checkpoints, retries.

Long-horizon exhaustive searches (the 10.7-hour half-billion-state product
runs, RESULTS.md) treat a crash as a restartable event, not a lost
run.  This package supplies the four pieces the engines and the supervisor
share:

- `faults`      — deterministic fault injection (`KSPEC_FAULT` env grammar)
                  so every recovery path below is testable in tier-1 on CPU;
- `checkpoints` — checksummed, keep-last-K rotating checkpoint store with
                  atomic promote and automatic fallback to the newest
                  verifying generation on load corruption;
- `retry`       — error classification (transient backend error vs device
                  RESOURCE_EXHAUSTED vs the reproducible wide-product
                  compile OOM vs fatal) and a bounded exponential-backoff
                  policy;
- `resources`   — resource-exhaustion governance (disk/RSS budgets,
                  per-level deadline watchdog, soft-breach reclamation,
                  the typed RESOURCE_EXHAUSTED clean exit, and the
                  supervisor's --reclaim sweep);
- `integrity`   — the silent-corruption defense (level digest chains,
                  shadow re-execution sampling, the typed
                  INTEGRITY_VIOLATION exit 76, and the jax-free chain
                  validator shared by resume and `cli verify-checkpoint`);
- `heartbeat`   — the shared JSONL heartbeat envelope ({kind, ts, unix})
                  written by the engines' per-level stats streams and
                  consumed by the supervisor's stall detector;
- `supervisor`  — the auto-resume run loop behind scripts/resilient_run.py
                  (spawn, watch heartbeat, kill on stall, restart from
                  checkpoint with a bounded budget and jittered backoff).

Nothing in this package imports jax: the supervisor runs in a parent, and
a parent that touched JAX would hold the accelerator its child needs.
"""

from .checkpoints import CheckpointCorrupt, CheckpointStore
from .faults import FaultPlan, InjectedCrash, InjectedFault, corrupt_file
from .integrity import EXIT_INTEGRITY, IntegrityError, LevelDigestChain
from .heartbeat import append_jsonl, heartbeat_record
from .resources import (
    EXIT_RESOURCE_EXHAUSTED,
    ResourceExhausted,
    ResourceGovernor,
    is_disk_full,
    reclaim_disk,
)
from .retry import RetryPolicy, classify

__all__ = [
    "CheckpointCorrupt",
    "CheckpointStore",
    "EXIT_INTEGRITY",
    "EXIT_RESOURCE_EXHAUSTED",
    "FaultPlan",
    "IntegrityError",
    "LevelDigestChain",
    "InjectedCrash",
    "InjectedFault",
    "ResourceExhausted",
    "ResourceGovernor",
    "RetryPolicy",
    "append_jsonl",
    "classify",
    "corrupt_file",
    "heartbeat_record",
    "is_disk_full",
    "reclaim_disk",
]
