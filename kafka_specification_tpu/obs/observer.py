"""RunObserver: the engines' one window into the obs subsystem.

Both engines used to hand-roll their per-level stats emission
(``heartbeat_record`` + ``append_jsonl``).  That call site is now a thin
shim over this class:

- with only ``stats_path`` (the pre-obs interface), the emitted records
  are **identical** to the historical stream — same envelope, same
  fields, same order, no run_id — so every existing consumer (the
  supervisor's stall detector, ``tail -f | jq``, the banked RUN*_stats
  artifacts) keeps working unchanged (tier-1 test: shim equivalence);
- with a :class:`~.runctx.RunContext`, the same records are additionally
  run_id-stamped, routed to the run directory's ``stats.jsonl``, folded
  into the metrics registry (states/sec, duplicate ratio, per-shard
  imbalance, wall-share counters), snapshotted to ``metrics.jsonl`` +
  ``metrics.prom`` every level, and bracketed by spans: a root ``check``
  span over the whole engine call, ``level`` spans under it, and the
  chunk-phase and dispatch spans under the level that caused them.  While
  a ``jax.profiler`` trace is being recorded, ``check``, ``level`` and
  ``dispatch`` are also written as profiler annotations (``kspec.check``,
  ``kspec.level d=<depth>``, ``kspec.dispatch <program>``), so they sit on
  the profiler's own clock above the device operations; the engine hands
  in the annotation factory, this package never imports jax.

Constructing an observer also (de)activates the module-global tracer and
metrics registry: a ``run=None`` engine call always *clears* them, so a
crashed traced run can never leak spans into a later untraced run in the
same process.

Must stay jax-free (the class; engines pass platform strings in).
"""

from __future__ import annotations

import time
from typing import Optional

from ..resilience.heartbeat import append_jsonl, heartbeat_record
from .ledger import PROCESS as _LEDGER
from .metrics import set_registry
from .tracer import now, set_tracer


# metrics export cadence: toy models run thousands of millisecond-scale
# levels, and metrics.prom is an fsync'd whole-file rewrite — snapshot at
# most this often (scrapers poll in tens of seconds; finish() always
# writes the terminal snapshot)
_SNAPSHOT_MIN_INTERVAL_S = 5.0


class _Both:
    """A span handle and a profiler annotation closed together."""

    def __init__(self, span, annotation):
        self.span, self.annotation = span, annotation

    def finish(self, **attrs) -> None:
        if self.span is not None:
            self.span.finish(**attrs)
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)

    def abandon(self) -> None:
        """Close with no completed record (the begin marker then stays
        unmatched: a level the run died or was stopped in)."""
        if self.span is not None:
            self.span.abandon()
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)


class _Emitted:
    """A span recorded when it ends, from the start it really had, and
    never a parent: for work that overlaps other spans without nesting
    (a dispatch still in flight while the next chunk is staged)."""

    def __init__(self, tracer, kind: str, attrs: dict):
        self.tracer, self.kind, self.attrs = tracer, kind, attrs
        self.t0 = now()

    def finish(self, **attrs) -> None:
        self.tracer.emit_span(self.kind, self.t0, now(),
                              **self.attrs, **attrs)


class RunObserver:
    def __init__(self, run=None, stats_path: Optional[str] = None,
                 engine: str = "bfs", annotate=None):
        """annotate: the engine's profiler-annotation factory
        (``jax.profiler.TraceAnnotation``) or None.  An annotation made
        while no profile is being recorded costs the profiler's own flag
        test."""
        self.run = run
        self.engine = engine
        self._annotate = annotate
        self._check = None  # the open root span of this engine call
        self._phase = None  # the open check-open / check-close span
        self._phase_attrs = {}  # what that span's end carries
        self._level = None  # the open level span
        self._t_begin = now()
        self._last_snapshot = 0.0
        # legacy stream: exactly where the caller pointed it; the run
        # directory's stats.jsonl is the default only when a run is active
        self.stats_path = stats_path or (run.stats_path if run else None)
        self.active = run is not None
        # stats collection is on iff anyone consumes it (pre-obs semantics:
        # `collect_stats = stats_path is not None`)
        self.collect = self.stats_path is not None or self.active
        if run is not None:
            run.activate()
        else:
            set_tracer(None)
            set_registry(None)

    # --- configuration stamping -------------------------------------------
    def config(self, **fields) -> None:
        if self.run is not None:
            self.run.record_config(engine=self.engine, **fields)

    def shape(self, model, fanout: int, lanes: int) -> None:
        """What the engine was handed, on the manifest's `config` and on
        the end of `check-open`: the static fanout and the packed lanes,
        and of a product (models/product.py) the partitions and one
        partition's fanout (a list where the partitions differ)."""
        fields = dict(
            fanout=fanout, lanes=lanes,
            partitions=model.meta.get("partitions", 1),
            base_fanout=model.meta.get("base_fanout", fanout),
        )
        self._phase_attrs = fields
        self.config(**fields)

    # --- spans -------------------------------------------------------------
    def _open(self, kind: str, name: Optional[str], marker: bool,
              t0=None, nested: bool = True, **attrs) -> _Both:
        """A span (begin-marked or not; not `nested`: recorded at its end
        and never a parent) and, where `name` is given and the engine
        handed in a factory, the profiler annotation beside it."""
        span = annotation = None
        # the annotation first and closed last, so it encloses the span by
        # microseconds and the two clocks can be compared start to start
        if name is not None and self._annotate is not None:
            annotation = self._annotate(name)
            annotation.__enter__()
        if self.run is not None:
            tr = self.run.tracer
            if not nested:
                span = _Emitted(tr, kind, attrs)
            elif marker:
                span = tr.begin(kind, t0, **attrs)
            else:
                span = tr.span(kind, **attrs).start(t0)
        return _Both(span, annotation)

    def check_begin(self, t0: float, **attrs) -> None:
        """Root span `check` over the whole engine call, and under it
        `check-open`, which lasts until the first level begins.  `t0` is
        the engine's first line (the observer is built a little later)."""
        self._t_begin = t0
        self._check = self._open("check", "kspec.check", True, t0, **attrs)
        self._phase = self.open_span("check-open", t0)

    def check_closing(self) -> None:
        """The level loop is over: `check-close` lasts until close()."""
        self.level_abandon()
        self._end_phase()
        if self._check is not None:
            self._phase = self.open_span("check-close")

    def _end_phase(self) -> None:
        if self._phase is not None:
            self._phase.finish(**self._phase_attrs)
            self._phase, self._phase_attrs = None, {}

    def open_span(self, kind: str, t0=None, **attrs) -> _Both:
        """A span closed by hand (``.finish(**attrs)``) that is the parent
        of what is recorded meanwhile: ``check-open``, ``check-close``,
        ``store``.  No-op handle without a run."""
        return self._open(kind, None, False, t0, **attrs)

    def dispatch(self, program: str, **attrs) -> _Both:
        """One device program launched: open at the call, ``finish()`` at
        the point the host next blocks on its outputs."""
        return self._open("dispatch", "kspec.dispatch " + program, False,
                          nested=False, program=program, **attrs)

    # --- per-level emission -----------------------------------------------
    def level_begin(self, depth: int, frontier: int) -> None:
        """Begin marker for the level span (crash forensics: a 'B' with no
        matching 'E' pins the level the run died in).  Until its end the
        level is the parent of every span recorded on this thread."""
        self._end_phase()
        self._level = self._open(
            "level", f"kspec.level d={depth}", True,
            depth=depth, frontier=frontier,
        )

    def level_abandon(self) -> None:
        """The level loop left a level open (a typed exit mid-level): its
        begin marker stays unmatched and it stops being the current
        parent.  A level a verdict cuts is completed by `level_cut`."""
        if self._level is not None:
            self._level.abandon()
            self._level = None

    def level_cut(self, record: dict) -> None:
        """A verdict cut the level: its span ends here with `cut=true`, so
        the begin marker is matched and what follows (the counterexample)
        is no child of it.  `record` is result.stats["cut_level"]; nothing
        goes to the stats stream, whose records are the committed levels."""
        if self._level is not None:
            self._level.finish(
                cut=True, **{k: record[k] for k in (
                    "rows_committed", "chunks_committed", "chunks_discarded",
                    "enabled_candidates", "new", "duplicates")})
            self._level = None

    def level(self, **fields) -> dict:
        """Build + route the per-level heartbeat record.

        `fields` is the engine's historical record payload, in its
        historical order.  Returns the record (engines also keep it in
        result.stats['levels'])."""
        if self.run is not None:
            rec = heartbeat_record("level", run_id=self.run.run_id, **fields)
        else:
            rec = heartbeat_record("level", **fields)
        if self.stats_path is not None:
            append_jsonl(self.stats_path, rec)
        if self._level is not None:
            self._level.finish(new=fields.get("new"),
                               total=fields.get("total"))
            self._level = None
        if self.run is not None:
            self._fold_metrics(fields)
            t = time.time()
            if t - self._last_snapshot >= _SNAPSHOT_MIN_INTERVAL_S:
                self._last_snapshot = t
                self.run.snapshot_metrics()
        return rec

    def _fold_metrics(self, f: dict) -> None:
        m = self.run.metrics
        new = f.get("new", 0)
        dup = f.get("duplicates", 0)
        en = f.get("enabled_candidates", 0)
        lvl_ms = f.get("level_ms", 0.0)
        m.inc("kspec_levels_total")
        m.inc("kspec_states_total", new)
        m.inc("kspec_duplicates_total", dup)
        m.inc("kspec_enabled_candidates_total", en)
        m.set_gauge("kspec_depth", f.get("depth", 0))
        m.set_gauge("kspec_frontier", f.get("frontier", 0))
        m.set_gauge("kspec_states_distinct", f.get("total", 0))
        m.set_gauge("kspec_duplicate_ratio",
                    round(dup / en, 4) if en else 0.0)
        # the run's rate so far: distinct states over the seconds since
        # the check began (not the last level's rate)
        elapsed = now() - self._t_begin
        m.set_gauge("kspec_states_per_sec",
                    round(f.get("total", 0) / elapsed, 1)
                    if elapsed > 0 else 0.0)
        m.observe("kspec_level_ms", lvl_ms)
        # host-vs-step wall share (single-device engine records both)
        if "step_ms" in f:
            m.inc("kspec_step_ms_total", f["step_ms"])
        if "host_ms" in f:
            m.inc("kspec_host_ms_total", f["host_ms"])
        # per-shard exchange balance (sharded engine)
        shard_new = f.get("shard_new")
        if shard_new:
            for d, v in enumerate(shard_new):
                m.set_gauge("kspec_shard_new", v, shard=d)
            mean = sum(shard_new) / len(shard_new)
            m.set_gauge(
                "kspec_shard_imbalance",
                round(max(shard_new) / mean, 3) if mean else 0.0,
            )
        for key, name in (
            ("shard_frontier", "kspec_shard_frontier"),
            ("shard_duplicates", "kspec_shard_duplicates"),
        ):
            vals = f.get(key)
            if vals:
                for d, v in enumerate(vals):
                    m.set_gauge(name, v, shard=d)

    # --- sub-level spans ---------------------------------------------------
    def chunk_span(self, kind: str, t0: float, **attrs) -> None:
        """Record a chunk-phase span (step / host-assembly / host-probe /
        exchange) that ends now, given the start it really had
        (``tracer.now()`` taken when the work began) — no-op without a
        run."""
        if self.run is not None:
            self.run.tracer.emit_span(kind, t0, now(), **attrs)

    # --- terminal ----------------------------------------------------------
    def abort(self, status: str, **detail) -> None:
        """Terminal manifest update for a non-CheckResult ending — the
        typed RESOURCE_EXHAUSTED clean exit (resilience.resources): the
        manifest's status is what `cli report`'s verdict keys on, and the
        detail (reason / depth / states so far) lands under result."""
        if self.run is not None:
            self.run.finish(status, **detail)

    def finish(self, result) -> None:
        """The engine call has its result: book it in the process ledger,
        whose snapshot the result carries (``stats["process"]``), and fold
        the result into metrics + manifest."""
        result.stats["process"] = _LEDGER.check_done(self._t_begin)
        if self.run is None:
            return
        m = self.run.metrics
        s = result.stats or {}
        m.inc("kspec_transient_retries_total", s.get("transient_retries", 0))
        m.set_gauge("kspec_degradations", len(s.get("degradations", ())))
        spill = s.get("spill")
        spills = spill if isinstance(spill, list) else [spill]
        for d, sp in enumerate(spills):
            if not sp:
                continue
            labels = {"shard": d} if isinstance(spill, list) else {}
            m.set_gauge("kspec_spill_runs", sp.get("runs", 0), **labels)
            m.set_gauge("kspec_spill_hot_fps", sp.get("hot", 0), **labels)
            m.set_gauge("kspec_spill_disk_fps", sp.get("disk", 0), **labels)
            m.set_gauge("kspec_spill_spills", sp.get("spills", 0), **labels)
            m.set_gauge("kspec_spill_merges", sp.get("merges", 0), **labels)
            bt = sp.get("bloom_totals")
            if bt:
                m.inc("kspec_bloom_maybe_total", bt["bloom_maybe"])
                m.inc(
                    "kspec_bloom_filtered_total",
                    bt["probes"] - bt["bloom_maybe"],
                )
                m.inc("kspec_bloom_hits_total", bt["hits"])
        status = "violation" if result.violation is not None else "complete"
        summary = dict(
            model=result.model,
            distinct_states=result.total,
            diameter=result.diameter,
            seconds=round(result.seconds, 3),
            states_per_sec=round(result.states_per_sec, 1),
        )
        # the run directory's own record of which path produced the
        # answer: whole-level programs run + why (if ever) the run left
        # them (`--pipeline device`); mesh size and what the exchange
        # carried (sharded engine); the level a verdict cut; what the
        # process had paid in set-up when the check closed (`cli report`)
        for key in ("device", "devices", "exchange_compressed",
                    "exchange_bytes_total", "cut_level", "symmetry",
                    "process"):
            if key in s:
                summary[key] = s[key]
        if result.violation is not None:
            summary["violation"] = {
                "invariant": result.violation.invariant,
                "depth": result.violation.depth,
                "trace_len": len(result.violation.trace),
            }
        self.run.finish(status, **summary)

    def close(self) -> None:
        self.level_abandon()
        self._end_phase()
        if self._check is not None:
            self._check.finish()
            self._check = None
        if self.run is not None:
            self.run.deactivate()
