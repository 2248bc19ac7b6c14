"""The hot path's one door between host and device: counted transfers and
named dispatches.

Both per-chunk pipelines, the whole-level pipeline, the level loop and the
sharded engine (``parallel/sharded.py``, through its own transfer functions)
fetch device outputs and upload host arrays through :class:`HostIO`, so a
level record can say what crossed (``d2h_bytes``, ``d2h_fetches``,
``h2d_bytes``, ``h2d_puts``), how long the host was blocked in the
crossing (``fetch_ms``: a fetch waits for the value to be computed AND
for its transfer, one number; ``put_ms``) and what was launched
(``dispatches``, ``discarded_dispatches``, ``discarded_ms``).  Counting is
two integer additions and two clock reads a call and there is no span per
transfer; a ``dispatch`` span
(and, inside a profile, a ``kspec.dispatch <program>`` annotation) is
written per program launched.  Lives in the engine, not in ``obs/``: it
touches JAX arrays, and ``obs/`` stays jax-free.
"""

from __future__ import annotations

from time import perf_counter

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracer import now

#: the level-record fields this module owns, in record order
LEVEL_COUNTERS = (
    "dispatches", "discarded_dispatches", "discarded_ms",
    "d2h_bytes", "d2h_fetches", "h2d_bytes", "h2d_puts",
    "fetch_ms", "put_ms",
)
#: those of them that are host timings: two runs do not repeat them
LEVEL_TIMINGS = ("discarded_ms", "fetch_ms", "put_ms")


class _Dispatch:
    """One launched program, open until the host blocks on its outputs."""

    def __init__(self, io: "HostIO", handle):
        self.io, self.handle, self.t0 = io, handle, now()

    def finish(self, discarded: bool = False) -> None:
        """The host has the outputs in hand (or an error): close the span.
        `discarded`: the outputs are thrown away (and the work re-run,
        unless a verdict ended the level).  A second call does nothing."""
        if self.io is None:
            return
        attrs = {}
        if discarded:
            self.io.discarded_dispatches += 1
            self.io.discarded_ms += (now() - self.t0) * 1e3
            attrs["discarded"] = True
        if self.handle is not None:
            self.handle.finish(**attrs)
        self.io = None


class HostIO:
    def __init__(self, obs=None, fetch=None, put=None):
        """`fetch` / `put`: the engine's own transfer functions where a
        plain ``np.asarray`` / ``jnp.asarray`` will not do: the sharded
        engine hands in ``fetch_global`` / ``put_global`` (a mesh layout
        rides ``put``'s second argument; on a multi-process mesh a
        replicated fetch counts once in each process)."""
        self.obs = obs  # a RunObserver, or None: count only
        self._fetch, self._put = fetch, put
        self.reset()

    def reset(self) -> None:
        self.dispatches = self.discarded_dispatches = 0
        self.discarded_ms = 0.0
        self.d2h_bytes = self.d2h_fetches = 0
        self.h2d_bytes = self.h2d_puts = 0
        self.fetch_ms = self.put_ms = 0.0

    def take(self) -> dict:
        """This level's counters (LEVEL_COUNTERS), then zero them."""
        rec = {k: getattr(self, k) for k in LEVEL_COUNTERS}
        for k in LEVEL_TIMINGS:
            rec[k] = round(rec[k], 3)
        self.reset()
        return rec

    # --- transfers ----------------------------------------------------------
    def fetch(self, x, dtype=None) -> np.ndarray:
        """``np.asarray(x, dtype)``; a device array is counted as one
        fetch of its bytes, and the time the host is blocked in it (until
        the value is computed and has crossed) goes to ``fetch_ms``."""
        if not isinstance(x, jax.Array):
            return np.asarray(x, dtype)
        self.d2h_fetches += 1
        self.d2h_bytes += x.nbytes
        t0 = perf_counter()
        if self._fetch is not None:
            x = self._fetch(x)
        out = np.asarray(x, dtype)
        self.fetch_ms += (perf_counter() - t0) * 1e3
        return out

    def put(self, x, *where) -> jax.Array:
        """``jnp.asarray(x)`` (or the engine's own ``put(x, *where)``); a
        host array is counted as one upload and timed into ``put_ms``."""
        counted = isinstance(x, np.ndarray)
        if counted:
            self.h2d_puts += 1
            self.h2d_bytes += x.nbytes
            t0 = perf_counter()
        out = jnp.asarray(x) if self._put is None else self._put(x, *where)
        if counted:
            self.put_ms += (perf_counter() - t0) * 1e3
        return out

    def prefetch(self, *xs) -> None:
        """Start the device-to-host copy of `xs` NOW, behind the program
        that computes them: the `fetch` that reads each later finds it
        on the host (or on its way) and pays no round trip of its own
        after the program ends.  Counts nothing; `fetch` does."""
        for x in xs:
            if isinstance(x, jax.Array):
                x.copy_to_host_async()

    def head(self, x, n: int):
        """``x[:n]`` of a device array: a slice program enqueued on the
        in-order device stream NOW, no transfer and nothing counted.  The
        level loop cuts a committed chunk's rows with it before it queues
        the next successor program, so their fetch does not wait behind
        that program (docs/engine.md § Async execution)."""
        return x[:n]

    # --- spans and dispatches -----------------------------------------------
    def span(self, kind: str, t0: float, **attrs) -> None:
        """A completed host span that started at `t0` and ends now."""
        if self.obs is not None:
            self.obs.chunk_span(kind, t0, **attrs)

    def dispatch(self, program: str, **attrs) -> _Dispatch:
        """Call just before launching `program`; ``finish()`` the result
        where the host next blocks on the launch's outputs."""
        self.dispatches += 1
        return _Dispatch(
            self,
            self.obs.dispatch(program, **attrs)
            if self.obs is not None else None,
        )
