"""Reduction of a profiler trace to device busy time, device operations by
total time, and idle gaps attributed to what the host was doing.

Two steps, so that the arithmetic can be checked without a chip:

  load_xplane(path)   `.xplane.pb` -> a plain dict (device lines in full,
                      host events only where the harness wrote them)
  reduce_trace(trace, spans)   the dict -> numbers

The plain dict is also the form of the small recorded trace that
`selfcheck.py` holds the arithmetic to:

  {"planes": [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops",
                          "events": [[name, start_ns, dur_ns], ...]}]}]}

All times in a trace are nanoseconds on the profiler's clock.  The
harness's own annotation (`perfbench.pass`), whose start it also took on
the host's wall clock, ties that clock to the program's spans.
"""

import glob
import os

ANNOTATION_PREFIX = "perfbench."
PASS_ANNOTATION = "perfbench.pass"
# the device line that holds one event per executed operation, and the
# fallback when a backend names its lines differently
_OP_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)
_COLLECTIVE_MARKS = ("all-to-all", "all-gather", "all-reduce",
                     "reduce-scatter", "collective-permute", "alltoall",
                     "allgather", "allreduce")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path):
    """Read an `.xplane.pb` with JAX alone into the plain form."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if is_device or name.startswith(ANNOTATION_PREFIX):
                    events.append([name, int(ev.start_ns),
                                   int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace):
    """Planes, lines and event counts: what to look at by hand first."""
    return [
        {"plane": p["name"],
         "lines": [{"line": l["name"], "events": len(l["events"]),
                    "first": [e[0] for e in l["events"][:3]]}
                   for l in p["lines"]]}
        for p in trace["planes"]
    ]


def excerpt(trace, per_line=300):
    """The first `per_line` events of every line, in the plain form: small
    enough to keep as a recorded trace for selfcheck.py."""
    return {"planes": [
        {"name": p["name"],
         "lines": [{"name": l["name"], "events": l["events"][:per_line]}
                   for l in p["lines"]]}
        for p in trace["planes"]]}


def _pick_line(plane, wanted):
    for line in plane["lines"]:
        if line["name"] in wanted:
            return line
    return None


def _merge(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def pass_window(trace):
    """[start_ns, end_ns] of the traced pass: the harness's annotation."""
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == PASS_ANNOTATION:
                    return start, start + dur
    return None


def short_name(name):
    """`%fusion.320 = u32[2097152]{0:T(1024)} fusion(...)`, which is how the
    TPU's op line names an event, as `fusion.320 u32[2097152]`: the
    instruction and its result's shape, without the operand list."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = "(tuple)" if rhs.startswith("(") else rhs.split("{")[0].split(" ")[0]
    return f"{lhs.lstrip('%')} {shape}"[:80]


def is_collective(name):
    low = name.lower()
    return any(mark in low for mark in _COLLECTIVE_MARKS)


def reduce_trace(trace, host_spans=(), span_clock_offset_ns=None):
    """The numbers of one traced pass.

    host_spans: the program's spans of that pass, `[kind, t0_unix_s,
    seconds, depth]`, innermost wins.  span_clock_offset_ns: profiler
    clock minus unix clock, in ns (None: taken as unknown, every gap is
    attributed to the harness's pass annotation or `unattributed`).

    Returns None when the trace holds no device operation or no pass
    annotation: a reader given None returns nothing.
    """
    window = pass_window(trace)
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    if window is None or not devices:
        return None
    lo, hi = window
    per_device = []
    op_seconds = {}
    collective_ns = 0
    for plane in devices:
        op_line = (_pick_line(plane, _OP_LINES)
                   or _pick_line(plane, _MODULE_LINES))
        if op_line is None:
            continue
        busy = _merge(_clip(
            [[s, s + d] for _, s, d in op_line["events"]], lo, hi))
        busy_ns = sum(e - s for s, e in busy)
        if busy_ns <= 0:
            continue
        coll = []
        for name, s, d in op_line["events"]:
            if s + d <= lo or s >= hi:
                continue
            key = short_name(name)
            op_seconds[key] = op_seconds.get(key, 0.0) + d / 1e9
            if is_collective(name):
                coll.append([s, s + d])
        coll_ns = sum(e - s for s, e in _merge(_clip(coll, lo, hi)))
        collective_ns += coll_ns
        per_device.append({"plane": plane["name"], "busy_ns": busy_ns,
                           "collective_ns": coll_ns, "busy": busy})
    if not per_device:
        return None
    n = len(per_device)
    busiest = max(per_device, key=lambda d: d["busy_ns"])
    # idle gaps of the busiest device, inside the pass
    gaps = []
    cursor = lo
    for s, e in busiest["busy"]:
        if s > cursor:
            gaps.append([cursor, s])
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append([cursor, hi])
    covering = _spans_on_profiler_clock(host_spans, span_clock_offset_ns)
    idle_by = {}
    for s, e in gaps:
        who = _attribute((s + e) / 2.0, covering)
        idle_by[who] = idle_by.get(who, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "busy_s_mean": sum(d["busy_ns"] for d in per_device) / n / 1e9,
        "busy_s_max": busiest["busy_ns"] / 1e9,
        "collective_s_mean": collective_ns / n / 1e9,
        "op_seconds": op_seconds,
        "idle_by": idle_by,
        "gaps": len(gaps),
        "longest_gap_s": max((e - s for s, e in gaps), default=0) / 1e9,
    }


def _spans_on_profiler_clock(host_spans, offset_ns):
    """`[lo_ns, hi_ns, kind]`, shortest first, or None when the two clocks
    could not be tied."""
    if offset_ns is None:
        return None
    out = [[t0 * 1e9 + offset_ns, (t0 + seconds) * 1e9 + offset_ns, kind]
           for kind, t0, seconds, _depth in host_spans if t0 is not None]
    out.sort(key=lambda x: x[1] - x[0])
    return out


def _attribute(t_ns, covering):
    """The innermost program span that covers profiler time `t_ns`; outside
    every program span it is the engine's own start and finish (manifest,
    metrics export), which only the harness's pass annotation covers."""
    if covering is None:
        return "unattributed"
    for lo, hi, kind in covering:
        if lo <= t_ns <= hi:
            return kind
    return PASS_ANNOTATION


def top(mapping, n=10):
    """The `n` largest entries of {name: seconds} as [[name, seconds]]."""
    return [[k, v] for k, v in
            sorted(mapping.items(), key=lambda kv: -kv[1])[:n]]
