"""Checking-as-a-service: a warm multi-tenant serving daemon.

Every check used to be a fresh CLI process — ~2 minutes cold, ~9 seconds
warm — which caps "heavy traffic from millions of users" at one
run per operator.  This package turns the checker into a service
(ROADMAP item 3):

- :mod:`queue` — a durable on-disk job queue (atomic rename state
  machine); the jax-FREE tenant side: ``cli submit`` writes a job spec,
  ``cli status`` / ``cli result`` read verdicts — clients never pay the
  jax import.
- :mod:`daemon` — ``cli serve``: one process imports jax once, holds
  jitted engine kernels in a shape-keyed in-process cache, and drains the
  queue under per-tenant resource budgets.
- :mod:`kernel_cache` — the compile cache, keyed by model schema shape
  (module, kernel source, constants, invariants): the O(1) keyed-artifact
  pattern of arXiv:2603.09555 (PAPERS.md).
- :mod:`scheduler` — batching plan + per-tenant admission/budgets
  (re-using PR 5's ResourceGovernor: a breach exits that job rc-75 typed
  without touching the daemon or siblings).
- :mod:`batch` — batched multi-config checking: jobs sharing a schema
  shape are advanced by ONE engine run (one vmapped kernel launch per
  level for the whole group) and each member's verdict is derived
  bit-identically to a solo ``cli check``.
- :mod:`verdict` — the shared ``kspec-verdict/1`` record ``cli check
  --json``, the result files, and ``cli result`` all speak.

Importing this package is jax-free; only :mod:`daemon` /
:mod:`kernel_cache` touch jax, and only when the daemon actually runs —
docs/service.md is the operator guide.
"""

from .queue import JOB_SCHEMA, JobQueue, new_job_id
from .verdict import (
    VERDICT_SCHEMA,
    error_verdict,
    render_verdict,
    verdict_exit_code,
    verdict_from_result,
)

__all__ = [
    "JOB_SCHEMA",
    "JobQueue",
    "VERDICT_SCHEMA",
    "error_verdict",
    "new_job_id",
    "render_verdict",
    "verdict_exit_code",
    "verdict_from_result",
]
