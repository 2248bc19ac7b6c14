"""Small helpers shared by the per-layer metric readers (the harness puts
this directory on `sys.path` before it loads them).

A reader is a file `perfbench/metrics/<name>.py` with

    META = {"name", "unit", "better", "source", "layer", "moves", "what"}
    def read(ctx): ...   # a number, or None where there is nothing to read

`ctx` holds: `passes` (the window's untraced passes: `wall_s`, `total`,
`level_records`, `stats`, `manifest`, `jax`), `traced` (the traced pass, or
None), `trace` (tracereduce.reduce_trace of it, or None), `setup`, `lanes`,
`peaks`, `chips`, `memory_peak_bytes`, `cell`, `config`.  BENCHMARK.json's
`per_layer` entry of the same name carries the same unit, layer, moves and
source (selfcheck.py holds the two together).
"""

import statistics


def median_over_passes(ctx, per_pass):
    """Median over the window's passes of `per_pass(pass)`, skipping
    passes for which it returns None."""
    vals = [v for v in (per_pass(p) for p in ctx["passes"]) if v is not None]
    return statistics.median(vals) if vals else None


def has(records, key):
    return bool(records) and all(key in r for r in records)


def accounted_levels(records, share=0.9):
    """The level records whose `step_ms` + `host_ms` reach `share` of their
    `level_ms`.  In a level of several chunks with overlap on, a chunk's
    device time hides behind the previous commit and the wait for it lands
    in the next commit's `host_ms`, so neither field is what its name says
    and their sum falls to 19-70% of the level (one-chunk levels: 93-100%;
    chip records, PR 29).  A host or step metric reads the others."""
    return [r for r in records if r["level_ms"]
            and r["step_ms"] + r["host_ms"] >= share * r["level_ms"]]
