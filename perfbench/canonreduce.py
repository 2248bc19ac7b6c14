"""Device seconds of `kspec.canon`, the stage TLC's SYMMETRY adds (PR 38).

A model with a `symmetry` (a cfg with a `SYMMETRY` stanza) keys every
candidate by its orbit: the programs that fingerprint candidates wrap the
canonicalisation kernel (ops/canon.py: every image of every live row, the
least one, its fingerprint) in `jax.named_scope("kspec.canon")`:

    jit(dvl_n2)/while/body/kspec.canon/while/body/gather

`stagereduce.STAGES` is the benchmark's own list and books a `kspec.*` scope
it does not know as `unnamed`, so this file walks the same `.xplane.pb` once
more, as `partreduce` does for the parts of `compact`: `stagereduce`'s own
loader, `opcode` and `CONTAINERS`, on the plane `stagereduce.for_ctx(ctx)`
chose and in its window, and sums the leaf seconds whose innermost `kspec.*`
component is `canon`.

A program without the scope (every model with no symmetry; the parent of
PR 38) has no such operation: there is nothing to read and every reader
returns None.
"""

import bisect
import json
import os
import time

import stagereduce
from tracereduce import PASS_ANNOTATION, find_xplane

STAGE = "canon"


def is_canon(op_path):
    """Whether the innermost `kspec.*` component of an `op_name` path is
    `kspec.canon`."""
    for comp in reversed(op_path.split("/")):
        if comp.startswith(stagereduce.STAGE_PREFIX):
            return comp[len(stagereduce.STAGE_PREFIX):].rstrip(":") == STAGE
    return False


def reduce_canon(trace, stages):
    """Leaf seconds under `kspec.canon`, overall, by level and by program,
    on the plane and in the levels `stages` (stagereduce.reduce_stages of
    the same trace) took and in the same window: the traced pass.  None
    where the trace holds no pass annotation, no such plane, or no
    `kspec.canon` operation at all."""
    passes = stagereduce.annotations(trace, PASS_ANNOTATION)
    planes = [p for p in trace["planes"] if p["name"] == stages["plane"]]
    if not passes or not planes:
        return None
    lo, hi = passes[0][1], passes[0][1] + passes[0][2]
    levels = [(s, e, d) for d, s, e in stages["levels"]]
    level_starts = [lv[0] for lv in levels]
    canon_ns, found = 0.0, False
    by_level, by_program = {}, {}
    opcodes = {}
    for line in planes[0]["lines"]:
        if line["name"] != stagereduce._OP_LINE:
            continue
        for name, start, dur, path in line["events"]:
            if not (start + dur > lo and start < hi) or not is_canon(path):
                continue
            found = True
            code = opcodes.get(name)
            if code is None:
                code = opcodes[name] = stagereduce.opcode(name)
            if code in stagereduce.CONTAINERS:
                continue
            canon_ns += dur
            prog = stagereduce.program_of(path) or "(no program)"
            by_program[prog] = by_program.get(prog, 0.0) + dur
            i = bisect.bisect_right(level_starts, start) - 1
            if i >= 0 and start < levels[i][1]:
                by_level[levels[i][2]] = by_level.get(levels[i][2], 0.0) + dur
    if not found:
        return None
    return {
        "plane": stages["plane"],
        "canon_s": canon_ns / 1e9,
        "leaf_s": stages["leaf_s"],
        "by_level": {d: v / 1e9 for d, v in sorted(by_level.items())},
        "by_program": {p: v / 1e9 for p, v in sorted(by_program.items())},
    }


_CACHE = {}


def for_ctx(ctx):
    """The `kspec.canon` reduction of this run's traced pass, or None where
    `stagereduce.for_ctx` has nothing to read or the program has no such
    scope.  Also leaves `trace_canon.json` beside `trace_stages.json`;
    `reduce_s` in it is what this walk of the profile cost the run."""
    stages = stagereduce.for_ctx(ctx)
    if not stages:
        return None
    out_dir = os.path.dirname(ctx["traced"]["manifest"]["dir"])
    xplane = find_xplane(os.path.join(out_dir, "trace"))
    if xplane not in _CACHE:
        t0 = time.perf_counter()
        reduced = reduce_canon(stagereduce.load_xplane(xplane), stages)
        if reduced is not None:
            reduced["states"] = stages.get("states")
            reduced["reduce_s"] = time.perf_counter() - t0
            with open(os.path.join(out_dir, "trace_canon.json"), "w") as fh:
                json.dump(reduced, fh, indent=1)
        _CACHE[xplane] = reduced
    return _CACHE[xplane]


def us_per_state(ctx):
    """Leaf device seconds under `kspec.canon` in the traced pass, busiest
    device, x 1e6 over that pass's stored states."""
    reduced = for_ctx(ctx)
    if not reduced or not reduced.get("states"):
        return None
    return reduced["canon_s"] * 1e6 / reduced["states"]


def share(ctx):
    """Those seconds as a percentage of all leaf device seconds of the
    pass."""
    reduced = for_ctx(ctx)
    if not reduced or not reduced["leaf_s"]:
        return None
    return 100.0 * reduced["canon_s"] / reduced["leaf_s"]
