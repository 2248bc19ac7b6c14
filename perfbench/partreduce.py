"""Device seconds of `kspec.compact` by its parts (PR 34).

`compact` is four pieces of code under one stage name, and since
`NAMING_VERSION` 2 the program nests one `jax.named_scope("part.<name>")`
inside every `kspec.compact` scope (engine/pipeline.py `COMPACT_PARTS`):

    jit(fsc_n2)/kspec.compact/part.squeeze/scatter

  select    per-action index compaction of the guard matrix on the device
  squeeze   enabled candidate rows to the front of the buffer dedup gets
  novel     the new states' compaction after dedup
  append    the whole-level programs' next-frontier append and buffer fills

`stagereduce.stage_of` takes the innermost `kspec.*` component and skips a
`part.*` one, so the stage metrics read what they read before the parts
existed.  This file walks the same `.xplane.pb` once more with
`stagereduce`'s own loader, `opcode` and `CONTAINERS`, on the plane
`stagereduce.for_ctx(ctx)` chose and in its window, and splits the leaf
seconds it books to `compact` by part.  Seconds under no part are
`unparted`; the five sum to `stage_s["compact"]`.

A program without the scopes (the parent of PR 34) carries no `part.*`
component anywhere: there is nothing to read and every reader returns None.
"""

import bisect
import json
import os
import time

import stagereduce
from tracereduce import PASS_ANNOTATION, find_xplane

PARTS = ("select", "squeeze", "novel", "append")
PART_PREFIX = "part."
UNPARTED = "unparted"
STAGE = "compact"


def part_of(op_path):
    """The part an operation of `compact` ran under: the innermost `part.*`
    component INSIDE the innermost `kspec.*` one, `unparted` where there is
    none (or one outside the vocabulary).  None for an operation that
    `stagereduce.stage_of` books to another stage."""
    part = None
    for comp in reversed(op_path.split("/")):
        if comp.startswith(stagereduce.STAGE_PREFIX):
            if comp[len(stagereduce.STAGE_PREFIX):].rstrip(":") != STAGE:
                return None
            return part if part in PARTS else UNPARTED
        if part is None and comp.startswith(PART_PREFIX):
            part = comp[len(PART_PREFIX):].rstrip(":")
    return None


def reduce_parts(trace, stages):
    """Leaf seconds of `compact` by part, overall, by level and by program,
    on the plane and in the levels `stages` (stagereduce.reduce_stages of
    the same trace) took and in the same window: the traced pass.  None
    where the trace holds no pass annotation, no such plane, or no `part.*`
    scope at all (a program from before the parts)."""
    passes = stagereduce.annotations(trace, PASS_ANNOTATION)
    planes = [p for p in trace["planes"] if p["name"] == stages["plane"]]
    if not passes or not planes:
        return None
    lo, hi = passes[0][1], passes[0][1] + passes[0][2]
    levels = [(s, e, d) for d, s, e in stages["levels"]]
    level_starts = [lv[0] for lv in levels]
    part_ns = {p: 0.0 for p in PARTS + (UNPARTED,)}
    by_level, by_program = {}, {}
    parted = False
    opcodes = {}
    for line in planes[0]["lines"]:
        if line["name"] != stagereduce._OP_LINE:
            continue
        for name, start, dur, path in line["events"]:
            if not (start + dur > lo and start < hi):
                continue
            parted = parted or PART_PREFIX in path
            code = opcodes.get(name)
            if code is None:
                code = opcodes[name] = stagereduce.opcode(name)
            if code in stagereduce.CONTAINERS:
                continue
            part = part_of(path)
            if part is None:
                continue
            part_ns[part] += dur
            prog = by_program.setdefault(
                stagereduce.program_of(path) or "(no program)", {})
            prog[part] = prog.get(part, 0.0) + dur
            i = bisect.bisect_right(level_starts, start) - 1
            if i >= 0 and start < levels[i][1]:
                lvl = by_level.setdefault(levels[i][2], {})
                lvl[part] = lvl.get(part, 0.0) + dur
    if not parted:
        return None

    def seconds(d):
        return {k: v / 1e9 for k, v in d.items()}

    return {
        "plane": stages["plane"],
        "compact_s": sum(part_ns.values()) / 1e9,
        "part_s": seconds(part_ns),
        "by_level": {d: seconds(v) for d, v in sorted(by_level.items())},
        "by_program": {p: seconds(v) for p, v in sorted(by_program.items())},
    }


_CACHE = {}


def for_ctx(ctx):
    """The by-part reduction of this run's traced pass, or None where
    `stagereduce.for_ctx` has nothing to read or the program has no parts.
    Also leaves `trace_parts.json` beside `trace_stages.json`; `reduce_s`
    in it is what this second walk of the profile cost the run."""
    stages = stagereduce.for_ctx(ctx)
    if not stages:
        return None
    out_dir = os.path.dirname(ctx["traced"]["manifest"]["dir"])
    xplane = find_xplane(os.path.join(out_dir, "trace"))
    if xplane not in _CACHE:
        t0 = time.perf_counter()
        reduced = reduce_parts(stagereduce.load_xplane(xplane), stages)
        if reduced is not None:
            reduced["states"] = stages.get("states")
            reduced["reduce_s"] = time.perf_counter() - t0
            with open(os.path.join(out_dir, "trace_parts.json"), "w") as fh:
                json.dump(reduced, fh, indent=1)
        _CACHE[xplane] = reduced
    return _CACHE[xplane]


def part_us_per_state(ctx, part):
    """Leaf device seconds under `kspec.compact/part.<part>` in the traced
    pass, busiest device, x 1e6 over that pass's distinct states."""
    reduced = for_ctx(ctx)
    if not reduced or not reduced.get("states"):
        return None
    return reduced["part_s"][part] * 1e6 / reduced["states"]


def unparted_share(ctx):
    """`compact` leaf seconds under no part, as a percentage of all
    `compact` leaf seconds."""
    reduced = for_ctx(ctx)
    if not reduced or not reduced["compact_s"]:
        return None
    return 100.0 * reduced["part_s"][UNPARTED] / reduced["compact_s"]
