"""Device seconds of a traced pass by stage of the level programs.

The program wraps each stage of its level programs in
`jax.named_scope("kspec.<stage>")`; the scope becomes part of every HLO
instruction's `op_name`, and the TPU's profile keeps that path in the
`tf_op` stat of each operation's *metadata*.  `jax.profiler.ProfileData`
shows an event's own stats only, so this file reads the `.xplane.pb` wire
format itself (no dependency beyond the standard library).

Two steps, as in tracereduce.py, so the arithmetic is checked without a chip
(`selfcheck_stages.py`):

  load_xplane(path)    `.xplane.pb` -> a plain dict: the device planes'
                       "XLA Ops" lines with each event's scope path, and the
                       host's `perfbench.*` / `kspec.*` annotations
  reduce_stages(trace) the dict -> seconds by stage, by level, by program

  {"planes": [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [[name, start_ns, dur_ns, op_path], ...]}]},
              {"name": "/host:CPU",
               "lines": [{"name": "python",
                          "events": [["kspec.level d=3", start_ns, dur_ns, ""]]}]}]}

Only leaf operations are summed: a `while`, `conditional` or `call` event is
a container that spans the operations under it, so counting it would count
their time twice.  A fusion is a leaf, booked whole to the stage of its root
instruction (PERF.md section 7 lists the largest that mix two stages).
"""

import bisect
import json
import os

from tracereduce import PASS_ANNOTATION, _clip, _merge, find_xplane, short_name

# the program's vocabulary (engine/pipeline.py STAGES) and the one stage only
# the sharded programs have (parallel/sharded.py: routing by owner, codec
# encode, the collective, decode)
STAGES = ("guard", "expand", "compact", "fingerprint", "dedup_sort",
          "dedup_probe", "dedup_merge", "invariants", "digest", "exchange")
STAGE_PREFIX = "kspec."
UNNAMED = "unnamed"
CONTAINERS = ("while", "conditional", "call")
LEVEL_ANNOTATION = "kspec.level d="
_ANNOTATION_PREFIXES = ("perfbench.", "kspec.")
_OP_LINE = "XLA Ops"


# --------------------------------------------------------------------------
# step 1: the wire format
# --------------------------------------------------------------------------
# tsl/profiler/protobuf/xplane.proto, the fields read here:
#   XSpace        planes=1
#   XPlane        name=2 lines=3 event_metadata=4 (map) stat_metadata=5 (map)
#   XLine         name=2 timestamp_ns=3 events=4
#   XEvent        metadata_id=1 offset_ps=2 duration_ps=3
#   XEventMetadata  id=1 name=2 stats=5
#   XStat         metadata_id=1 str_value=5 ref_value=7
#   XStatMetadata id=1 name=2

def _varint(buf, i):
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of one message: an int for a varint field, a
    (start, end) pair for a length-delimited one; fixed-width fields are
    skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, (i, i + size)
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = value = None
    for num, val in _fields(buf, *span):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _read_plane(buf, span):
    name, lines, event_meta, stat_names = "", [], {}, {}
    for num, val in _fields(buf, *span):
        if num == 2:
            name = _text(buf, val)
        elif num == 3:
            lines.append(val)
        elif num == 4:
            key, value = _map_entry(buf, val)
            event_meta[key] = value
        elif num == 5:
            key, value = _map_entry(buf, val)
            for n2, v2 in _fields(buf, *value):
                if n2 == 2:
                    stat_names[key] = _text(buf, v2)
    return name, lines, event_meta, stat_names


def _event_metadata(buf, span, stat_names):
    """(name, op_path) of one XEventMetadata: `tf_op` is where the TPU's
    profile keeps an instruction's `op_name`, scopes and all."""
    name = path = ""
    for num, val in _fields(buf, *span):
        if num == 2:
            name = _text(buf, val)
        elif num == 5:
            stat, text = None, None
            for n2, v2 in _fields(buf, *val):
                if n2 == 1:
                    stat = stat_names.get(v2)
                elif n2 == 5:
                    text = _text(buf, v2)
                elif n2 == 7:
                    text = stat_names.get(v2, "")
            if stat == "tf_op" and text:
                path = text
    return name, path


def _read_line(buf, span, names, wanted, only_line):
    """One XLine -> (name, [[event name, start_ns, dur_ns, op_path]]) for
    the events whose metadata id is in `wanted` (None: all); nothing for
    a line not named `only_line` (None: any)."""
    line_name, t_line, events = "", 0, []
    for num, val in _fields(buf, *span):
        if num == 2:
            line_name = _text(buf, val)
        elif num == 3:
            t_line = val
        elif num == 4:
            events.append(val)
    out = []
    if only_line is not None and line_name != only_line:
        return line_name, out
    for ev in events:
        meta = offset_ps = dur_ps = 0
        for num, val in _fields(buf, *ev):
            if num == 1:
                meta = val
            elif num == 2:
                offset_ps = val
            elif num == 3:
                dur_ps = val
        if wanted is None or meta in wanted:
            name, path = names.get(meta, ("", ""))
            out.append([name, t_line + offset_ps / 1000.0, dur_ps / 1000.0,
                        path])
    return line_name, out


def load_xplane(path):
    """Read an `.xplane.pb` into the plain form (module docstring)."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    planes = []
    for num, val in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, lines, event_meta, stat_names = _read_plane(buf, val)
        names = {k: _event_metadata(buf, v, stat_names)
                 for k, v in event_meta.items()}
        is_device = name.startswith("/device:")
        wanted = None if is_device else {
            k for k, (n, _p) in names.items()
            if n.startswith(_ANNOTATION_PREFIXES)}
        if not is_device and not wanted:
            continue
        out_lines = []
        for span in lines:
            line_name, events = _read_line(
                buf, span, names, wanted, _OP_LINE if is_device else None)
            if events:
                out_lines.append({"name": line_name, "events": events})
        if out_lines:
            planes.append({"name": name, "lines": out_lines})
    return {"planes": planes}


# --------------------------------------------------------------------------
# step 2: the arithmetic
# --------------------------------------------------------------------------

def opcode(name):
    """`%while.7 = (s32[], u32[8]{0}) while(...)` -> `while`: the HLO
    opcode of an operation event's name ("" where there is none)."""
    _lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return ""
    if rhs.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    rhs = rhs[i + 1:].lstrip()
                    break
    else:
        rhs = rhs.partition(" ")[2]
    return rhs.partition("(")[0].strip()


def stage_of(op_path):
    """The innermost `kspec.<stage>` component of an `op_name` path, or
    `unnamed`."""
    for part in reversed(op_path.split("/")):
        if part.startswith(STAGE_PREFIX):
            stage = part[len(STAGE_PREFIX):].rstrip(":")
            return stage if stage in STAGES else UNNAMED
    return UNNAMED


def program_of(op_path):
    """`jit(dvl_n1)/while/...` -> `dvl_n1` ("" outside a jitted program)."""
    head = op_path.partition("/")[0]
    return head[4:-1] if head.startswith("jit(") and head.endswith(")") \
        else ""


def annotations(trace, prefix):
    """Host annotations whose name starts with `prefix`, by start time."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            out += [e for e in line["events"] if e[0].startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def reduce_stages(trace, top_n=5):
    """Seconds by stage of the traced pass, on the busiest device.

    Returns None when the trace holds no pass annotation or no device
    operation: a reader given None returns nothing."""
    passes = annotations(trace, PASS_ANNOTATION)
    if not passes:
        return None
    lo, hi = passes[0][1], passes[0][1] + passes[0][2]
    levels = []  # (start_ns, end_ns, depth)
    for name, start, dur, _p in annotations(trace, LEVEL_ANNOTATION):
        try:
            depth = int(name[len(LEVEL_ANNOTATION):].split()[0])
        except ValueError:
            continue
        if lo <= start <= hi:
            levels.append((start, start + dur, depth))
    level_starts = [lv[0] for lv in levels]
    best = None
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        ops = [e for line in plane["lines"] if line["name"] == _OP_LINE
               for e in line["events"] if e[1] + e[2] > lo and e[1] < hi]
        if not ops:
            continue
        busy = sum(e - s for s, e in _merge(_clip(
            [[s, s + d] for _n, s, d, _p in ops], lo, hi)))
        if best is None or busy > best[0]:
            best = (busy, plane["name"], ops)
    if best is None:
        return None
    busy_ns, plane_name, ops = best
    stage_ns = {s: 0.0 for s in STAGES + (UNNAMED,)}
    by_level, by_program, by_op = {}, {}, {}
    containers_ns = 0.0
    opcodes = {}
    for name, start, dur, path in ops:
        code = opcodes.get(name)
        if code is None:
            code = opcodes[name] = opcode(name)
        if code in CONTAINERS:
            containers_ns += dur
            continue
        stage = stage_of(path)
        stage_ns[stage] += dur
        prog = by_program.setdefault(program_of(path) or "(no program)", {})
        prog[stage] = prog.get(stage, 0.0) + dur
        key = (stage, program_of(path), short_name(name))
        by_op[key] = by_op.get(key, 0.0) + dur
        i = bisect.bisect_right(level_starts, start) - 1
        if i >= 0 and start < levels[i][1]:
            lvl = by_level.setdefault(levels[i][2], {})
            lvl[stage] = lvl.get(stage, 0.0) + dur
    leaf_ns = sum(stage_ns.values())
    top_ops = {}
    for (stage, _prog, op), ns in sorted(by_op.items(),
                                         key=lambda kv: -kv[1]):
        rows = top_ops.setdefault(stage, [])
        if len(rows) < top_n:
            rows.append([op, ns / 1e9])

    def seconds(d):
        return {k: v / 1e9 for k, v in d.items()}

    return {
        "plane": plane_name,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "leaf_s": leaf_ns / 1e9,
        # time inside a container and in no leaf under it (loop control),
        # so in no stage and not in `unnamed` either
        "container_only_s": (busy_ns - leaf_ns) / 1e9,
        "containers_s": containers_ns / 1e9,
        "stage_s": seconds(stage_ns),
        "by_level": {d: seconds(v) for d, v in sorted(by_level.items())},
        "by_program": {p: seconds(v) for p, v in sorted(by_program.items())},
        "top_ops": top_ops,
        # every leaf operation, to set two runs side by side:
        # [stage, program, name and shape, seconds]
        "op_table": [[st, prog, op, ns / 1e9] for (st, prog, op), ns
                     in sorted(by_op.items(), key=lambda kv: -kv[1])],
        "levels": [[d, s, e] for s, e, d in levels],
        "pass_start_ns": lo,
    }


def clock_tie(reduced, host_spans, pass_t0_unix):
    """How well the harness's clock tie holds: for every `kspec.level`
    annotation, its start on the profiler's clock against the program's
    own `level` span start (`spans.jsonl` `t0`) moved onto that clock with
    the harness's offset (pass annotation start minus the unix time the
    harness took beside it).  -> {"max_abs_ms", "per_level_ms": {depth: ms}}
    or None when there is nothing to compare."""
    if not reduced or pass_t0_unix is None:
        return None
    offset_ns = reduced["pass_start_ns"] - pass_t0_unix * 1e9
    span_t0 = {depth: t0 for kind, t0, _s, depth in host_spans
               if kind == "level" and t0 is not None}
    per_level = {}
    for depth, start_ns, _end in reduced["levels"]:
        if depth in span_t0:
            per_level[depth] = (
                start_ns - offset_ns - span_t0[depth] * 1e9) / 1e6
    if not per_level:
        return None
    return {"max_abs_ms": max(abs(v) for v in per_level.values()),
            "per_level_ms": per_level}


# --------------------------------------------------------------------------
# the readers' entry
# --------------------------------------------------------------------------

_CACHE = {}


def for_ctx(ctx):
    """The reduction of this run's traced pass, or None where there is
    nothing to read: no traced pass, a rehearsal (a CPU has no device
    plane), or a program whose manifest does not say where it ran (its
    `dir`; the harness's trace directory is that directory's sibling
    `trace/`).  Also leaves `trace_stages.json` beside the trace."""
    traced = ctx.get("traced")
    if not traced or ctx.get("rehearsal"):
        return None
    run_dir = (traced.get("manifest") or {}).get("dir")
    if not run_dir:
        return None
    out_dir = os.path.dirname(run_dir)
    xplane = find_xplane(os.path.join(out_dir, "trace"))
    if not xplane:
        return None
    if xplane not in _CACHE:
        reduced = reduce_stages(load_xplane(xplane))
        if reduced is not None:
            reduced["clock_tie"] = clock_tie(
                reduced, traced["spans"]["spans"], traced.get("t0_unix"))
            reduced["states"] = traced.get("total")
            with open(os.path.join(out_dir, "trace_stages.json"), "w") as fh:
                json.dump(reduced, fh, indent=1)
        _CACHE[xplane] = reduced
    return _CACHE[xplane]


def stage_us_per_state(ctx, stage):
    """Leaf device seconds under `kspec.<stage>` in the traced pass, on the
    busiest device, x 1e6 over that pass's distinct states."""
    reduced = for_ctx(ctx)
    if not reduced or not reduced.get("states"):
        return None
    return reduced["stage_s"][stage] * 1e6 / reduced["states"]
