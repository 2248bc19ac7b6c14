"""Unified telemetry: run manifests, span tracing, metrics, run reports.

The checker grew three instrumentation dialects ad hoc — per-level stats
JSONL (engine/bfs), heartbeat envelopes (resilience), and
supervisor/ladder event logs — none correlated by run, none aggregated;
the 10.7 h half-billion-state run was monitored by tailing raw logs.
This package makes observability a subsystem instead of a side effect:

- :class:`RunContext` (obs/runctx) — a run_id + run directory
  (``runs/<run_id>/``) holding a ``manifest.json`` (config, engine, git,
  knobs, checkpoint lineage across resumes, terminal status) and all the
  artifacts that previously scattered across the repo root;
- :class:`SpanTracer` (obs/tracer) — nested run_id-stamped spans to an
  append-only untearable JSONL, each with the start it really had and
  the span that caused it;
- :class:`MetricsRegistry` (obs/metrics) — counters/gauges/histograms
  exported as JSONL snapshots and an atomically-replaced Prometheus
  textfile for scraping during multi-day runs;
- :func:`render_report` (obs/report) — ``cli report <run-dir>``: per-level
  throughput, action-enablement table, spill accounting, restart/fallback
  timeline, growth-rate ETA, and a stall verdict that uses the
  supervisor's own liveness rule;
- :class:`RunObserver` (obs/observer) — the engines' shim: with only a
  ``stats_path`` it reproduces the historical per-level stream
  record-for-record; with a run context it additionally stamps, traces,
  and aggregates.

The whole package is jax-free at import (a supervisor parent that touched
JAX would hold the chip its child needs); deep call sites in storage/
resilience reach the active tracer/registry through the module-level
``tracer.span/event`` and ``metrics.inc/set_gauge`` helpers, imported
lazily at the call site to keep the obs <-> resilience import graph
acyclic.

Beyond the per-run boundary, :mod:`obs.fleettrace` carries one trace per
*job* across the whole serving fleet (submit -> placement -> claim ->
run -> publish; ``cli trace`` / ``top`` / ``fleet-report``), and
:mod:`obs.atomicio` holds the shared atomic-publication idiom every
side-channel writer (manifests, specs, verdicts, routes, metrics.prom,
sweep manifests) rides.
"""

from . import fleettrace
from .atomicio import atomic_write_json, atomic_write_text
from .metrics import MetricsRegistry
from .observer import RunObserver
from .report import render_report, report_data
from .runctx import RunContext, default_run_dir, new_run_id
from .tracer import SpanTracer, read_jsonl_tolerant

__all__ = [
    "MetricsRegistry",
    "RunContext",
    "RunObserver",
    "SpanTracer",
    "atomic_write_json",
    "atomic_write_text",
    "default_run_dir",
    "fleettrace",
    "new_run_id",
    "read_jsonl_tolerant",
    "render_report",
    "report_data",
]
