"""Span tracer: nested, run_id-stamped spans to an untearable JSONL.

Every span record is one JSON line written with a single ``os.write`` on an
``O_APPEND`` file descriptor — the same append-only idiom the mosaic
ladder's per-rung banking uses: a hard kill can tear at most the final
line (the reader tolerates exactly that), never an earlier one.

Record shapes (all carry the shared heartbeat envelope kind/ts/unix from
``resilience.heartbeat`` plus ``run_id``):

    {"kind": "span",  "ph": "E", "span": "<span kind>", "span_id": ...,
     "parent_id": ..., "t0": ..., "ms": ..., <attrs>}       completed span
    {"kind": "span",  "ph": "B", "span": "<span kind>", ...}  begin marker
    {"kind": "event", "event": "<event kind>", <attrs>}     point-in-time

Begin markers are emitted only for the long-lived kinds the engines mark
explicitly (``check``, ``level``) so a crash mid-level is visible in the
log; a begin marker and its completed span share one ``span_id``.  Every
other span lands as one "E" record at exit (span bodies that crash emit
nothing — the surrounding begin marker and the heartbeat stream carry the
forensics).

One clock: every ``t0`` is the start the span really had, taken from
``time.time_ns()`` when the work starts (:func:`now`), recorded to the
microsecond; ``ms`` is recorded to the microsecond too.  The idle gaps
these spans must explain on a device trace are about a millisecond each.
An open span (context manager or begin marker) is the current parent of
everything recorded on its thread until it closes, so each record names
its cause in ``parent_id``.

Deep call sites (storage spills, checkpoint writes, retry backoff) use the
module-level :func:`span` / :func:`event` helpers, which no-op unless a
run context is active — so the storage and resilience layers need no
plumbing and stay usable without the obs subsystem.

Must stay jax-free at import time (it is imported by supervisor parents,
which leave the accelerator to their child).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from ..resilience.heartbeat import heartbeat_record


def now() -> float:
    """Unix seconds on the tracer's one clock (``time.time_ns()``)."""
    return time.time_ns() / 1e9


class _SpanCM:
    """One open span: a context manager (``with tracer.span(...)``), or
    opened and closed by hand (``start()`` ... ``finish(**attrs)``) where
    wrapping the work in a ``with`` block is not practical."""

    def __init__(self, tracer: "SpanTracer", kind: str, attrs: dict,
                 marker: bool = False):
        self.tracer = tracer
        self.kind = kind
        self.attrs = attrs
        self.marker = marker
        self.span_id = None
        self.t0 = None

    def start(self, t0: Optional[float] = None) -> "_SpanCM":
        """Open the span (at `t0` where the work began before the tracer
        could be told) and make it this thread's current parent."""
        tr = self.tracer
        self.span_id = tr._next_id()
        self.t0 = now() if t0 is None else t0
        stack = tr._stack
        if self.marker:
            tr._write(heartbeat_record(
                "span", run_id=tr.run_id, ph="B", span=self.kind,
                span_id=self.span_id,
                parent_id=stack[-1] if stack else None, **self.attrs,
            ))
        stack.append(self.span_id)
        return self

    def abandon(self):
        """Stop being the current parent with no completed record (a
        begin marker then stays unmatched).  -> the parent's id."""
        stack = self.tracer._stack
        if self.span_id in stack:  # drops any child left open by a raise
            del stack[stack.index(self.span_id):]
        return stack[-1] if stack else None

    def finish(self, error: Optional[str] = None, **attrs) -> None:
        self.tracer._record(self.kind, self.span_id, self.abandon(),
                            self.t0, now(), {**self.attrs, **attrs}, error)

    __enter__ = start

    def __exit__(self, exc_type, exc, tb):
        self.finish(error=exc_type.__name__ if exc_type else None)
        return False


class _NullCM:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL_CM = _NullCM()


class SpanTracer:
    def __init__(self, path: str, run_id: str):
        self.path = path
        self.run_id = run_id
        self._fd = None
        self._seq = 0
        # one tracer may be shared by concurrent in-process jobs (the
        # serving daemon's worker threads): the fd open / seq allocation /
        # close races are guarded here, and each record is a SINGLE
        # os.write on the O_APPEND fd — lines interleave whole, never torn.
        # The NESTING stack is per-thread (not merely locked): a shared
        # stack would attribute thread A's span to thread B's open parent,
        # which is nesting that never happened
        self._lock = threading.Lock()
        self._tls = threading.local()

    # --- untearable append ------------------------------------------------
    def _write(self, rec: dict) -> None:
        payload = (json.dumps(rec) + "\n").encode()
        with self._lock:
            if self._fd is None:
                self._fd = os.open(
                    self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
                )
            os.write(self._fd, payload)

    def close(self) -> None:
        with self._lock:
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None

    # --- span protocol ----------------------------------------------------
    @property
    def _stack(self) -> list:
        """This thread's open-span-id stack (parent attribution)."""
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _next_id(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def _record(self, kind, span_id, parent, t0, t1, attrs, error=None):
        rec = heartbeat_record(
            "span",
            t=t1,
            run_id=self.run_id,
            ph="E",
            span=kind,
            span_id=span_id,
            parent_id=parent,
            t0=round(t0, 6),
            ms=round((t1 - t0) * 1e3, 3),
            **attrs,
        )
        if error is not None:
            rec["error"] = error
        self._write(rec)

    def span(self, kind: str, **attrs) -> _SpanCM:
        return _SpanCM(self, kind, attrs)

    def begin(self, kind: str, t0: Optional[float] = None,
              **attrs) -> _SpanCM:
        """Open a long-lived span with a begin marker (ph=B) — crash
        forensics: a 'B' with no matching 'E' pins where the run died.
        Close it with the returned handle's ``finish(**attrs)``."""
        return _SpanCM(self, kind, attrs, marker=True).start(t0)

    def emit_span(self, kind: str, t0: float, t1: float, **attrs) -> None:
        """Record an already-completed span from explicit timestamps
        (:func:`now` at its true start and end) — the zero-intrusion form
        for engine hot loops (no reindentation, no context manager)."""
        stack = self._stack
        self._record(kind, self._next_id(), stack[-1] if stack else None,
                     t0, t1, attrs)

    def event(self, kind: str, **attrs) -> None:
        self._write(
            heartbeat_record("event", run_id=self.run_id, event=kind, **attrs)
        )


# --- module-level current tracer (deep call sites, zero plumbing) ---------
#
# Thread-LOCAL, not process-global: the serving daemon (service/daemon.py)
# runs multiple jobs in one process, each with its own RunContext — a
# global would let job B's activate() cross-stamp job A's spans with B's
# run_id.  Each thread sees only the tracer it activated; single-threaded
# callers (the CLI engines) behave exactly as before.
_active = threading.local()


def set_tracer(tracer: Optional[SpanTracer]) -> None:
    _active.tracer = tracer


def current_tracer() -> Optional[SpanTracer]:
    return getattr(_active, "tracer", None)


def span(kind: str, **attrs):
    """Span context manager on the active tracer; no-op when none."""
    cur = current_tracer()
    return cur.span(kind, **attrs) if cur is not None else _NULL_CM


def event(kind: str, **attrs) -> None:
    """Point event on the active tracer; no-op when none."""
    cur = current_tracer()
    if cur is not None:
        cur.event(kind, **attrs)


def read_jsonl_tolerant(path: str) -> list:
    """Parse a JSONL file, skipping torn lines and blanks.

    The O_APPEND writers can tear only the FINAL line — but a supervised
    restart appends past its predecessor's torn tail (one shared
    stats/events file per run directory), so by the time `cli report`
    reads the stream a tear can sit anywhere.  Unparsable lines are
    skipped, never fatal: a report over a crashed run must render from
    whatever survived."""
    out = []
    try:
        with open(path, "rb") as fh:
            raw = fh.read()
    except OSError:
        return out
    for line in raw.split(b"\n"):
        if not line.strip():
            continue
        try:
            out.append(json.loads(line))
        except ValueError:
            continue  # torn by a kill; the surrounding records stand alone
    return out
