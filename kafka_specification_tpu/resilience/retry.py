"""Failure classification + bounded exponential backoff for chunk steps.

The engines' known failure ladder (seen on the half-billion-state runs):

- **transient**: the backend hiccuped (RPC drop, preempted device,
  transient DATA_LOSS/UNAVAILABLE status).  The chunk is side-effect-free
  until its results are committed, so the right response is to re-run the
  same attempt after a short, bounded, exponentially-backed-off sleep.
- **compile_oom**: the reproducible wide-product XLA:CPU LLVM OOM on
  escalated per-action programs.  Retrying identically cannot help; the
  engines instead pin adaptation off (`AdaptiveCompact.compile_fallback`)
  and record the degradation in `result.stats`.
- **device_resource**: the backend ran out of device memory executing a
  chunk (`RESOURCE_EXHAUSTED` in the XLA status).  Re-running the
  identical chunk would allocate the identical buffers and die
  identically, so the engines degrade the WORK SHAPE instead: the
  current chunk re-runs on the uniform compact path (smaller device
  buffers) and the streaming chunk size halves for the rest of the run —
  both recorded in `result.stats["degradations"]`
  (`kind: "chunk_degrade"`).
- **other**: a real bug — propagate.

Classification is substring-based over the exception text (JAX surfaces
backend errors as `XlaRuntimeError` with the gRPC status name embedded),
with the injected-fault markers from `faults` matching their families.
"""

from __future__ import annotations

import os
import random
import sys
from dataclasses import dataclass, field

from ..utils import clock as _clk

TRANSIENT_PATTERNS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "DATA_LOSS",
    "ABORTED",
    "CANCELLED",
    "Socket closed",
    "connection reset",
)
OOM_PATTERNS = (
    "LLVM ERROR",
    "out of memory",
    "bad_alloc",
)
# device allocation failure at chunk-execute time: its own class (it used
# to be lumped into the compile-OOM family, but pinning *adaptation* off
# does nothing for a table/buffer that simply doesn't fit — the right
# degradation is a smaller chunk)
DEVICE_RESOURCE_PATTERNS = ("RESOURCE_EXHAUSTED",)


def classify(exc: BaseException) -> str:
    """-> 'transient' | 'device_resource' | 'compile_oom' | 'other'."""
    text = f"{type(exc).__name__}: {exc}"
    if any(p in text for p in TRANSIENT_PATTERNS):
        return "transient"
    if any(p in text for p in DEVICE_RESOURCE_PATTERNS):
        return "device_resource"
    if any(p in text for p in OOM_PATTERNS):
        return "compile_oom"
    return "other"


@dataclass
class RetryPolicy:
    """Bounded exponential backoff with jitter for transient errors."""

    max_retries: int = 3
    base_delay: float = 0.5
    factor: float = 2.0
    max_delay: float = 30.0
    jitter: float = 0.5  # delay *= 1 + U(0, jitter)
    rng: random.Random = field(default_factory=random.Random, repr=False)

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        return cls(
            max_retries=int(os.environ.get("KSPEC_RETRY_MAX", "3")),
            base_delay=float(os.environ.get("KSPEC_RETRY_BASE_DELAY", "0.5")),
            max_delay=float(os.environ.get("KSPEC_RETRY_MAX_DELAY", "30")),
        )

    def delay(self, attempt: int) -> float:
        """Backoff before retry number `attempt` (1-based)."""
        d = min(self.base_delay * self.factor ** (attempt - 1), self.max_delay)
        return d * (1.0 + self.jitter * self.rng.random())


@dataclass
class ChunkRetryHandler:
    """One copy of the chunk-step failure policy for both engines.

    Called from the engines' chunk-attempt except blocks; decides between
    - 'retry'   — transient error with budget left: sleeps the backoff and
                  tells the caller to re-run the same attempt;
    - 'degrade' — non-transient failure of an ESCALATED (per-action tuple)
                  program: records the degradation and tells the caller to
                  fall back to the uniform compact path;
    - 'degrade_chunk' — a device RESOURCE_EXHAUSTED on a NON-escalated
                  attempt: the identical chunk would allocate the
                  identical buffers and die again, so the caller re-runs
                  it on the uniform compact path AND halves its streaming
                  chunk size for the rest of the run (bounded by
                  `max_chunk_degrades`; recorded in
                  result.stats["degradations"]).  An escalated attempt's
                  RESOURCE_EXHAUSTED instead takes the 'degrade' path
                  below (lockstep-safe, same as before the class split);
    - re-raise  — anything else, including a transient error that exhausted
                  its retry budget (the supervisor's restart-from-checkpoint
                  layer owns that case; degrading on it would mislabel an
                  outage as a compile failure and pin adaptation off for the
                  rest of the run).

    The transient counter is per-chunk (`reset_chunk`); the totals and the
    degradation records accumulate per-run and land in result.stats.
    """

    policy: RetryPolicy
    tag: str  # "[engine]" / "[sharded]" stderr prefix
    transient_try: int = 0
    retries_total: int = 0
    chunk_degrades: int = 0
    max_chunk_degrades: int = 6  # 64x shrink, then surface the outage
    degradations: list = field(default_factory=list)

    @classmethod
    def from_env(cls, tag: str) -> "ChunkRetryHandler":
        return cls(policy=RetryPolicy.from_env(), tag=tag)

    def reset_chunk(self) -> None:
        self.transient_try = 0

    def handle(
        self,
        e: BaseException,
        escalated: bool,
        depth: int,
        retry_transient: bool = True,
    ) -> str:
        kind = classify(e)
        if kind == "transient":
            if not retry_transient:
                # retry-in-place is unsound here (e.g. a per-host error in
                # a multi-process collective: one host re-issuing the step
                # would desync the lockstep loop) — surface it instead
                raise e
            if self.transient_try >= self.policy.max_retries:
                raise e  # budget exhausted: surface the outage
            self.transient_try += 1
            self.retries_total += 1
            pause = self.policy.delay(self.transient_try)
            print(
                f"{self.tag} transient backend error "
                f"({type(e).__name__}: {e}); retry "
                f"{self.transient_try}/{self.policy.max_retries} in "
                f"{pause:.2f}s",
                file=sys.stderr,
            )
            # run-correlated retry record (obs/tracer; no-op without a run
            # context — lazy import keeps obs <-> resilience acyclic)
            from ..obs import tracer as _obs

            _obs.event(
                "retry",
                depth=depth,
                attempt=self.transient_try,
                backoff_s=round(pause, 2),
                error=f"{type(e).__name__}: {e}"[:200],
            )
            _clk.sleep(pause)
            return "retry"
        if kind == "device_resource" and not escalated:
            # (an ESCALATED attempt's RESOURCE_EXHAUSTED falls through to
            # the uniform-path degrade below — the family it shared with
            # compile_oom before this class existed; that response is
            # deterministic and replicated, hence lockstep-safe, whereas
            # the chunk shrink here is only sound where a lone
            # retry-in-place is: a multi-process peer shrinking its chunk
            # alone would desync the lockstep loop, so fleets surface it
            # to the supervisor instead)
            if not retry_transient:
                raise e
            if self.chunk_degrades >= self.max_chunk_degrades:
                raise e  # shrinking isn't helping: a real capacity wall
            self.chunk_degrades += 1
            print(
                f"{self.tag} device RESOURCE_EXHAUSTED executing a chunk "
                f"({type(e).__name__}); degrading work shape "
                f"({self.chunk_degrades}/{self.max_chunk_degrades}: uniform "
                f"compact now, half chunk size from the next level)",
                file=sys.stderr,
            )
            self.degradations.append(
                {
                    "kind": "chunk_degrade",
                    "depth": depth,
                    "error": f"{type(e).__name__}: {e}"[:300],
                }
            )
            from ..obs import tracer as _obs

            _obs.event(
                "chunk-degrade",
                depth=depth,
                attempt=self.chunk_degrades,
                error=f"{type(e).__name__}: {e}"[:200],
            )
            return "degrade_chunk"
        if not escalated:
            raise e
        print(
            f"{self.tag} adaptive compact step failed "
            f"({type(e).__name__}); falling back to the uniform compact "
            f"path for the rest of the run",
            file=sys.stderr,
        )
        self.degradations.append(
            {
                "kind": "compile_fallback",
                "depth": depth,
                "error": f"{type(e).__name__}: {e}"[:300],
            }
        )
        from ..obs import tracer as _obs

        _obs.event(
            "compile-fallback",
            depth=depth,
            error=f"{type(e).__name__}: {e}"[:200],
        )
        return "degrade"
