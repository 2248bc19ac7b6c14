"""The one machine-readable verdict format (``kspec-verdict/1``).

``cli check --json``, the service's ``results/<job_id>.json`` files, and
``cli result`` all emit/consume this record — one schema, stamped with a
version, a run_id, and the process exit code, so a service client can
switch between "run it locally" and "submit it to the daemon" without
changing its parser:

    {"schema": "kspec-verdict/1",
     "model": ..., "distinct_states": ..., "diameter": ..., "levels": [...],
     "states_per_sec": ..., "seconds": ...,
     "violation": null | {"invariant": ..., "depth": ..., "trace_len": ...},
     "run_id": ..., "exit_code": 0|1|75|2,
     ...service jobs add: job_id, tenant, status, timing, batch}

Exit-code vocabulary (mirrors the CLI's):
  0   exhaustive pass, no violation
  1   invariant violated (the verdict IS the product — not an error)
  75  RESOURCE_EXHAUSTED (resilience.resources): the job ran out of its
      budgeted disk/RSS/time and exited typed; resubmit after the
      operator/tenant frees the budget
  2   error (bad config, unknown module, engine failure)

Must stay jax-free: ``cli result`` renders these on operator boxes whose
accelerator stack is wedged.
"""

from __future__ import annotations

from typing import Optional

# the canonical rc-75 constant (resilience.resources is jax-free too)
from ..resilience.resources import EXIT_RESOURCE_EXHAUSTED as EXIT_RESOURCE

VERDICT_SCHEMA = "kspec-verdict/1"
# the verdict carries trace_len only; the daemon leaves the rendered
# counterexample under this name in the job's run directory
COUNTEREXAMPLE = "counterexample.txt"

EXIT_OK = 0
EXIT_VIOLATION = 1
EXIT_ERROR = 2


def verdict_from_result(res, run_id: Optional[str] = None) -> dict:
    """Build the verdict record from an engine CheckResult (duck-typed:
    anything with model/total/diameter/levels/seconds/states_per_sec/
    violation attributes)."""
    violation = None
    if res.violation is not None:
        violation = {
            "invariant": res.violation.invariant,
            "depth": res.violation.depth,
            "trace_len": len(res.violation.trace),
        }
    return {
        "schema": VERDICT_SCHEMA,
        "model": res.model,
        "distinct_states": res.total,
        "diameter": res.diameter,
        "levels": list(res.levels),
        "states_per_sec": round(res.states_per_sec, 1),
        "seconds": round(res.seconds, 3),
        "violation": violation,
        "run_id": run_id,
        "exit_code": EXIT_OK if res.violation is None else EXIT_VIOLATION,
    }


def error_verdict(message: str, run_id: Optional[str] = None,
                  exit_code: int = EXIT_ERROR) -> dict:
    """Verdict for a job that produced no CheckResult (build failure,
    resource exhaustion, daemon-side crash)."""
    return {
        "schema": VERDICT_SCHEMA,
        "model": None,
        "distinct_states": None,
        "diameter": None,
        "levels": None,
        "states_per_sec": None,
        "seconds": None,
        "violation": None,
        "error": message,
        "run_id": run_id,
        "exit_code": exit_code,
    }


def verdict_exit_code(rec: dict) -> int:
    """The process exit code a consumer of this verdict should use."""
    code = rec.get("exit_code")
    return EXIT_ERROR if code is None else int(code)


def render_verdict(rec: dict) -> str:
    """Human one-glance rendering (``cli result`` without --json)."""
    lines = []
    status = rec.get("status")
    head = f"Job {rec['job_id']}" if rec.get("job_id") else "Verdict"
    if status:
        head += f"  [{status.upper()}]"
    lines.append(head)
    if rec.get("tenant"):
        lines.append(f"  tenant: {rec['tenant']}")
    if rec.get("run_id"):
        lines.append(f"  run: {rec['run_id']}")
    if rec.get("error"):
        lines.append(f"  error: {rec['error']}")
    if rec.get("model") is not None:
        lines.append(
            f"  {rec['model']}: {rec['distinct_states']} distinct states, "
            f"diameter {rec['diameter']}, {rec['seconds']}s "
            f"({rec['states_per_sec']:,.0f} states/sec)"
        )
    v = rec.get("violation")
    if v:
        lines.append(
            f"  Invariant {v['invariant']} is VIOLATED at depth "
            f"{v['depth']} (trace of {v['trace_len']} states in the run "
            f"directory's {COUNTEREXAMPLE})"
        )
    elif rec.get("model") is not None:
        lines.append("  No invariant violations. Exhaustive check complete.")
    t = rec.get("timing") or {}
    if t:
        lines.append(
            f"  latency: wait {t.get('wait_s', '?')}s + "
            f"run {t.get('wall_s', '?')}s = {t.get('latency_s', '?')}s "
            f"submit->verdict"
        )
    b = rec.get("batch") or {}
    if b.get("group_size", 0) > 1:
        lines.append(
            f"  batched: group of {b['group_size']} jobs sharing schema "
            f"shape (leader run {b.get('leader_run_id')})"
        )
    lines.append(f"  exit code: {verdict_exit_code(rec)}")
    return "\n".join(lines)
