"""The process ledger: what a process paid before and between its searches.

A verdict of half a second follows a set-up of a minute (minutes, on a
machine that compiles), and most of it happens where no run context is
open: the interpreter and JAX start, the model is built, ``rewarm`` builds
programs between two jobs.  Spans cannot hold that (``tracer.span`` is a
no-op there), so it is kept here: one object a process, of monotone
counters on the tracer's clock (:func:`tracer.now`), written where the work
happens whether or not a run is being traced.  Both engines copy a
:meth:`ProcessLedger.snapshot` into ``CheckResult.stats["process"]`` as a
check closes, and ``RunObserver.finish`` copies it into the manifest's
``result``; ``docs/observability.md`` has the fields and who reads each.

The build counters come from JAX itself: one ``jax.monitoring`` listener
pair, registered once a process (:meth:`ProcessLedger.install`; listeners
cannot be unregistered through the public API).  An event belongs to the
first call (``engine.bfs._CompileOnFirstCall``) open on the thread it
arrives on, and to ``helpers`` where none is: the small jitted helpers and
eager operations that carry no ``compile`` span.  A warm pass fires none.

**Nested events are counted once.**  JAX fires one
``jaxpr_trace_duration`` for every jitted function it traces, the ``jnp``
functions inside a program included, and the outer event's seconds contain
the inner ones' (a 200-operation program fires 803 events).  A sum of them
is no wall time.  Events of one thread arrive in the order they end, so an
event whose interval ``[arrival - seconds, arrival]`` starts at or after a
later one's start lies inside it and is taken out again when the later one
arrives (:class:`_Outermost`): every ``*_s`` here is seconds of the clock.

Jax-free at import (supervisor parents import ``obs``); nothing here starts
a backend unless asked to (:meth:`ProcessLedger.mark_backend`).
"""

from __future__ import annotations

import collections
import heapq
import os
import sys
import threading
from contextlib import contextmanager
from typing import Optional

from .tracer import now

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_PART = {_TRACE: "trace_s", _LOWER: "lower_s", _BACKEND: "backend_s"}
_COUNT = {_HIT: "cache_hits", _MISS: "cache_misses"}

# an inner event's listener runs microseconds after the event ended, as the
# outer one's does: two starts closer than this are one instant
_SAME_START_S = 20e-6
# intervals a thread's `helpers` stream keeps to take nested ones out of: a
# helper traced outside any first call is a few events deep, and the stream
# never closes
_HELPER_OPEN = 4096
SLOWEST = 8  # first calls kept in `programs.slowest`
BY_NAME = 8  # names kept in `helpers.by_name`


def _build_counters() -> dict:
    return {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_hits": 0, "cache_misses": 0, "retrieval_s": 0.0}


def process_start_unix() -> float:
    """When this process started, from the kernel's own record
    (``/proc/self/stat`` field 22 against ``/proc/uptime``); the first call
    of this function where there is none."""
    t = now()
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        # the kernel's tick is coarse: never later than the first use
        return min(t, t - (uptime - ticks / os.sysconf("SC_CLK_TCK")))
    except (OSError, ValueError, IndexError):
        return t


class _Outermost:
    """Seconds of one thread's duration events by part, each instant once:
    `totals[part]` holds the intervals no later interval contained."""

    def __init__(self, totals: dict, keep: Optional[int] = None):
        self.totals = totals
        self._open = collections.deque(maxlen=keep)  # (start, seconds, part)

    def add(self, part: str, seconds: float, arrival: float) -> None:
        start = arrival - seconds
        while self._open and self._open[-1][0] >= start - _SAME_START_S:
            _, inner_s, inner_part = self._open.pop()
            self.totals[inner_part] -= inner_s
        self._open.append((start, seconds, part))
        self.totals[part] += seconds


class _FirstCall:
    """One open first call of a step-cache entry: what JAX reports on this
    thread until :meth:`done` belongs to it."""

    def __init__(self, ledger: "ProcessLedger", attrs: dict):
        self._ledger = ledger
        self._attrs = attrs
        self._c = _build_counters()
        self._parts = _Outermost(self._c)
        self._taking = True
        self.t0 = now()
        self.t1 = None

    def _duration(self, name: str, seconds: float, arrival: float) -> None:
        if not self._taking:
            return  # (the jaxpr read back from jit's cache after the call)
        if name in _PART:
            self._parts.add(_PART[name], seconds, arrival)
        elif name == _RETRIEVAL:
            self._c["retrieval_s"] += seconds

    def _event(self, name: str) -> None:
        if self._taking and name in _COUNT:
            self._c[_COUNT[name]] += 1

    def done(self) -> dict:
        """The call has returned: book it, and -> what its ``compile`` span
        says it was made of (``trace_ms`` + ``lower_ms`` + ``backend_ms`` +
        ``rest_ms`` is the span's ``ms``)."""
        self.t1 = now()
        self._taking = False
        c = self._c
        call_s = self.t1 - self.t0
        built_s = c["trace_s"] + c["lower_s"] + c["backend_s"]
        parts = {
            "trace_ms": round(c["trace_s"] * 1e3, 3),
            "lower_ms": round(c["lower_s"] * 1e3, 3),
            "backend_ms": round(c["backend_s"] * 1e3, 3),
            # `hit` / `miss` as JAX's persistent cache reports them; `off`
            # where it reported neither: the cache is disabled, or the
            # program compiled under the thresholds an entry is written at
            "cache": ("miss" if c["cache_misses"] else
                      "hit" if c["cache_hits"] else "off"),
            "retrieval_ms": round(c["retrieval_s"] * 1e3, 3),
            "rest_ms": round((call_s - built_s) * 1e3, 3),
        }
        self._ledger._book_call(c, call_s, {
            **self._attrs, "ms": round(call_s * 1e3, 3), **parts})
        return parts


class _Rewarm:
    """What one ``PreparedKernels.rewarm`` call reports back."""

    built = 0


class ProcessLedger:
    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._installed = False
        self._seq = 0  # orders `slowest` entries of equal length
        self._slowest: list = []  # heap of (call_s, seq, attrs)
        self._by_name: collections.Counter = collections.Counter()
        self._start_unix = None
        self.jax_unix = None
        self.backend_ready_unix = None
        self.model_s, self.models = 0.0, 0
        self.programs = dict(_build_counters(), built=0, call_s=0.0)
        self.helpers = dict(_build_counters(), built=0)
        self.rewarm = {"calls": 0, "s": 0.0, "built": 0}
        self.checks = {"calls": 0, "s": 0.0, "last_s": 0.0}

    @property
    def start_unix(self) -> float:
        if self._start_unix is None:
            self._start_unix = process_start_unix()
        return self._start_unix

    # --- JAX's own events ---------------------------------------------------
    def install(self) -> None:
        """Register the listener pair with ``jax.monitoring``, once, and
        note that JAX is imported (the first time this is asked is the
        ``enable_compile_cache`` every entry point starts with)."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring

        self.jax_unix = now()
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)

    def _open_call(self) -> Optional[_FirstCall]:
        calls = getattr(self._tls, "calls", None)
        return calls[-1] if calls else None

    def on_duration(self, name: str, seconds: float, **kw) -> None:
        if name not in _PART and name != _RETRIEVAL:
            return
        arrival = now()
        call = self._open_call()
        if call is not None:
            call._duration(name, seconds, arrival)
            return
        with self._lock:
            if name == _RETRIEVAL:
                self.helpers["retrieval_s"] += seconds
                return
            parts = getattr(self._tls, "helpers", None)
            if parts is None:
                parts = self._tls.helpers = _Outermost(
                    self.helpers, keep=_HELPER_OPEN)
            parts.add(_PART[name], seconds, arrival)
            if name == _BACKEND:
                self.helpers["built"] += 1
                self._by_name[str(kw.get("fun_name", "?"))] += 1

    def on_event(self, name: str, **kw) -> None:
        if name not in _COUNT:
            return
        call = self._open_call()
        if call is not None:
            call._event(name)
            return
        with self._lock:
            self.helpers[_COUNT[name]] += 1

    # --- marks the program sets ---------------------------------------------
    def mark_backend(self, start: bool = True) -> None:
        """Note when the program first saw JAX's backend up.  `start`:
        bring it up where the caller has not (``jax.devices()``), as the
        caller is about to; without it only a backend that is up already is
        noted, and jax is not even imported (``build_model`` also runs in
        jax-free processes, under the analyzer's stub)."""
        if self.backend_ready_unix is not None:
            return
        if start:
            import jax

            self.install()
            jax.devices()
        else:
            bridge = sys.modules.get("jax._src.xla_bridge")
            if not getattr(bridge, "backends_are_initialized",
                           lambda: False)():
                return
        with self._lock:
            if self.backend_ready_unix is None:
                self.backend_ready_unix = now()

    @contextmanager
    def model(self):
        """Around a model build (``build_model``, ``prepare``, a
        ``KernelCache.get`` miss): `model_s` takes the outermost one's
        seconds on this thread, so a build inside a build counts once."""
        depth = getattr(self._tls, "model_depth", 0)
        self._tls.model_depth = depth + 1
        t0 = now()
        try:
            yield
        finally:
            self._tls.model_depth = depth
            if depth == 0:
                with self._lock:
                    self.model_s += now() - t0
                    self.models += 1

    @contextmanager
    def first_call(self, **attrs):
        """Around the first call of a fresh step-cache entry -> the open
        :class:`_FirstCall`; a call that raises books nothing."""
        self.install()
        calls = getattr(self._tls, "calls", None)
        if calls is None:
            calls = self._tls.calls = []
        call = _FirstCall(self, attrs)
        calls.append(call)
        try:
            yield call
        finally:
            calls.pop()  # (first calls on one thread nest, never interleave)

    def _book_call(self, c: dict, call_s: float, attrs: dict) -> None:
        during = getattr(self._tls, "during", "check")
        with self._lock:
            p = self.programs
            for k, v in c.items():
                p[k] += v
            p["built"] += 1
            p["call_s"] += call_s
            self._seq += 1
            entry = (call_s, self._seq, dict(attrs, during=during))
            if len(self._slowest) < SLOWEST:
                heapq.heappush(self._slowest, entry)
            else:
                heapq.heappushpop(self._slowest, entry)

    @contextmanager
    def rewarming(self):
        """Around ``PreparedKernels.rewarm`` -> a :class:`_Rewarm` whose
        `built` the caller sets; first calls meanwhile are `during`
        ``rewarm``."""
        rw = _Rewarm()
        self._tls.during = "rewarm"
        t0 = now()
        try:
            yield rw
        finally:
            del self._tls.during
            with self._lock:
                self.rewarm["calls"] += 1
                self.rewarm["s"] += now() - t0
                self.rewarm["built"] += rw.built

    def check_done(self, t0: float) -> dict:
        """An engine call that began at `t0` has its result -> the
        snapshot that result carries."""
        last = now() - t0
        with self._lock:
            self.checks["calls"] += 1
            self.checks["s"] += last
            self.checks["last_s"] = last
        return self.snapshot()

    # --- what readers take --------------------------------------------------
    def build_s(self) -> float:
        """Seconds this process has spent building: the step cache's first
        calls, the helpers' traces, lowerings and compiles or loads, and the
        models.  The serving daemon's compile / explore split is this
        number's growth over a job."""
        with self._lock:
            h = self.helpers
            return (self.programs["call_s"] + h["trace_s"] + h["lower_s"]
                    + h["backend_s"] + self.model_s)

    def snapshot(self) -> dict:
        """The ledger as plain JSON values (seconds to the microsecond)."""
        def plain(d):
            return {k: round(max(v, 0.0), 6) if isinstance(v, float) else v
                    for k, v in d.items()}

        with self._lock:
            return {
                "start_unix": round(self.start_unix, 6),
                "jax_unix": self.jax_unix and round(self.jax_unix, 6),
                "backend_ready_unix": (self.backend_ready_unix
                                       and round(self.backend_ready_unix, 6)),
                "model_s": round(self.model_s, 6), "models": self.models,
                "programs": dict(
                    plain(self.programs),
                    slowest=[a for _, _, a in
                             sorted(self._slowest, reverse=True)]),
                "helpers": dict(
                    plain(self.helpers),
                    by_name=dict(self._by_name.most_common(BY_NAME))),
                "rewarm": plain(self.rewarm),
                "checks": plain(self.checks),
            }


#: the one ledger of this process
PROCESS = ProcessLedger()
