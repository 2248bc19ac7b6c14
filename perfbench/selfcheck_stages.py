#!/usr/bin/env python3
"""Self-check of the stage reduction and of the readers that ride the
program's dispatch, transfer and store records.  Needs no chip.

    python3 perfbench/selfcheck_stages.py

Holds `stagereduce.reduce_stages` to hand-worked numbers on a small recorded
trace (`selfcheck_data/stages_small.json`, the expectations and how they
were worked beside it): containers left out, stage sums plus unnamed equal
all leaf seconds, levels and programs split right, the clock tie measured
right.  Holds the `.xplane.pb` wire reader to a tiny profile encoded here
by hand, the seven record-based readers to a hand-worked pass, and the
`exchange` stage of a sharded program to a hand-worked two-device trace.

Not under tests/: tier-1's count does not move with the benchmark.
"""

import contextlib
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402
import stagereduce  # noqa: E402

FAILURES = []


def check(cond, what):
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        FAILURES.append(what)


def close(a, b, tol=1e-15):
    return abs(a - b) <= tol


def same_ns(got_s, want_ns):
    """{name: seconds} against {name: nanoseconds}, zero entries aside."""
    got = {k: round(v * 1e9, 6) for k, v in got_s.items() if v}
    return got == {k: float(v) for k, v in want_ns.items() if v}


def check_reduction():
    data = os.path.join(HERE, "selfcheck_data")
    trace = harness.load_json(os.path.join(data, "stages_small.json"))
    want = harness.load_json(os.path.join(data, "stages_small.expect.json"))
    got = stagereduce.reduce_stages(trace)
    check(got["plane"] == want["plane"], "stages: the busiest device is taken")
    for key in ("window_s", "busy_s", "leaf_s", "container_only_s",
                "containers_s"):
        check(close(got[key], want[key]), f"stages: {key} = {want[key]}")
    check(same_ns(got["stage_s"], want["stage_ns"])
          and set(got["stage_s"]) == set(want["stage_ns"]),
          f"stages: seconds by stage = {want['stage_ns']} ns")
    check(close(sum(got["stage_s"].values()), got["leaf_s"]),
          "stages: the ten stages plus unnamed equal all leaf seconds")
    check(close(got["leaf_s"] + got["container_only_s"], got["busy_s"]),
          "stages: leaf seconds plus container-only time equal busy time")
    check({str(d): v for d, v in got["by_level"].items()}.keys()
          == want["by_level_ns"].keys()
          and all(same_ns(got["by_level"][int(d)], v)
                  for d, v in want["by_level_ns"].items()),
          "stages: by level (an operation between two levels is in neither)")
    check(got["by_program"].keys() == want["by_program_ns"].keys()
          and all(same_ns(got["by_program"][p], v)
                  for p, v in want["by_program_ns"].items()),
          "stages: by program, from the op_name's jit(<module>) head")
    top = got["top_ops"][stagereduce.UNNAMED]
    check([n for n, _ in top] == [n for n, _ in want["top_unnamed"]]
          and all(close(a[1], b[1]) for a, b in zip(top, want["top_unnamed"])),
          f"stages: largest unnamed operations = {want['top_unnamed']}")
    tie = stagereduce.clock_tie(got, want["host_spans"], want["pass_t0_unix"])
    check(close(tie["max_abs_ms"], want["clock_tie"]["max_abs_ms"], 1e-6)
          and all(close(tie["per_level_ms"][int(d)], v, 1e-6)
                  for d, v in want["clock_tie"]["per_level_ms"].items()),
          f"stages: clock tie = {want['clock_tie']}")
    check(stagereduce.reduce_stages({"planes": []}) is None
          and stagereduce.clock_tie(None, [], 1.0) is None,
          "stages: nothing to read gives nothing")
    return trace, want


def check_names():
    for name, want in (
            ("%while.7 = (s32[], u32[8]{0:T(256)}) while((s32[]) %t), "
             "condition=%c, body=%b", "while"),
            ("%fusion.320 = u32[2097152]{0:T(1024)} fusion(u32[8]{0} %p), "
             "kind=kLoop, calls=%f", "fusion"),
            ("%sort.1 = (u32[16]{0}, s32[16]{0}) sort(u32[16]{0} %a)", "sort"),
            ("%call.3 = u32[4]{0} call(u32[4]{0} %a), to_apply=%g", "call"),
            ("perfbench.pass", "")):
        check(stagereduce.opcode(name) == want,
              f"opcode of {name[:28]!r}... is {want!r}")
    for path, stage, prog in (
            ("jit(dvl_n1)/while/body/kspec.dedup_merge/while/body/"
             "closed_call/gather:", "dedup_merge", "dvl_n1"),
            ("jit(fsc_n1)/kspec.compact/jit(cumsum)/add:", "compact",
             "fsc_n1"),
            ("jit(dvl_n1)/while:", "unnamed", "dvl_n1"),
            ("jit(x)/kspec.not_a_stage/add:", "unnamed", "x"),
            ("jit(shl_n1)/while/body/kspec.exchange/all_to_all:", "exchange",
             "shl_n1"),
            ("jit(shl_n1)/while/body/kspec.exchange/closed_call/kspec.digest/"
             "xor:", "digest", "shl_n1"),
            ("", "unnamed", "")):
        check(stagereduce.stage_of(path) == stage
              and stagereduce.program_of(path) == prog,
              f"stage and program of {path!r}: {stage}, {prog!r}")


# --- a tiny .xplane.pb, encoded by hand -----------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _vint(field, n):
    return _varint(field << 3) + _varint(n)


def _blob(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, stat_names, metas, lines):
    """metas: {id: (name, {stat id: text})}; lines: [(name, t_ns,
    [(meta id, offset_ps, dur_ps)])]."""
    out = _blob(2, name)
    for sid, sname in stat_names.items():
        out += _blob(5, _vint(1, sid) + _blob(2, _vint(1, sid) + _blob(2, sname)))
    for mid, (mname, stats) in metas.items():
        meta = _vint(1, mid) + _blob(2, mname)
        for sid, text in stats.items():
            meta += _blob(5, _vint(1, sid) + _blob(5, text))
        out += _blob(4, _vint(1, mid) + _blob(2, meta))
    for lname, t_ns, events in lines:
        line = _blob(2, lname) + _vint(3, t_ns)
        for mid, offset_ps, dur_ps in events:
            line += _blob(4, _vint(1, mid) + _vint(2, offset_ps)
                          + _vint(3, dur_ps)
                          # a double-valued stat of the event: skipped
                          + _blob(4, _vint(1, 9) + b"\x11" + b"\0" * 8))
        out += _blob(3, line)
    return out


def check_wire_reader():
    op = "%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop, calls=%f"
    path = "jit(dvl_n1)/while/body/kspec.guard/and:"
    space = _blob(1, _plane(
        "/device:TPU:0", {7: "tf_op", 8: "hlo_category"},
        {1: (op, {8: "loop fusion", 7: path}), 2: ("%copy.1 = u32[8]{0} copy(u32[8]{0} %q)", {})},
        [("XLA Ops", 5000, [(1, 2000000, 300000), (2, 2400000, 1500)]),
         ("XLA Modules", 5000, [(1, 2000000, 300000)])]))
    space += _blob(1, _plane(
        "/host:CPU", {},
        {1: ("perfbench.pass", {}), 2: ("shard_args", {}),
         3: ("kspec.level d=4", {})},
        [("python", 1000, [(1, 0, 9000000), (2, 500, 100), (3, 70000, 800000)]),
         ("pjrt", 1000, [(2, 500, 100)])]))
    space += _blob(1, _plane("/host:metadata", {}, {1: ("noise", {})},
                             [("x", 0, [(1, 0, 1)])]))
    with tempfile.TemporaryDirectory() as tmp:
        prof = os.path.join(tmp, "plugins", "profile", "2026_01_01")
        os.makedirs(prof)
        with open(os.path.join(prof, "vm.xplane.pb"), "wb") as fh:
            fh.write(space)
        found = stagereduce.find_xplane(tmp)
        got = stagereduce.load_xplane(found)
    want = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            [op, 7000.0, 300.0, path],
            ["%copy.1 = u32[8]{0} copy(u32[8]{0} %q)", 7400.0, 1.5, ""]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["perfbench.pass", 1000.0, 9000.0, ""],
            ["kspec.level d=4", 1070.0, 800.0, ""]]}]}]}
    check(got == want,
          "wire reader: op lines with their tf_op path, host annotations, "
          "nothing else")


# --- the record-based readers ----------------------------------------------

def check_record_readers():
    readers = harness.load_metric_readers()
    rec = {"level_ms": 100.0, "discarded_ms": 0.0, "d2h_bytes": 3000,
           "d2h_fetches": 11, "h2d_bytes": 500, "h2d_puts": 4,
           "store_ms": 0.5, "dispatches": 2, "discarded_dispatches": 0}
    redo = dict(rec, level_ms=300.0, discarded_ms=120.0, dispatches=2,
                discarded_dispatches=1, d2h_bytes=7000, d2h_fetches=15)
    spans = [["run-open", 1.0, 0.004, None], ["check", 1.0, 0.5, None],
             ["check-open", 1.0, 0.020, None], ["level", 1.1, 0.1, 1],
             ["check-close", 1.4, 0.006, None]]
    one = {"level_records": [rec, redo], "total": 1000,
           "spans": {"spans": spans, "events": []}}
    ctx = {"passes": [one, one, one]}
    for name, want in (("discarded_dispatch_share", 30.0),
                       ("d2h_bytes_per_state", 10.0),
                       ("h2d_bytes_per_state", 1.0),
                       ("fetches_per_level", 13.0),
                       ("store_share", 0.25),
                       ("pass_overhead_ms", 30.0)):
        got = readers[name].read(ctx)
        check(got is not None and close(got, want, 1e-9),
              f"reader {name}: hand-worked pass gives {want}")
    old = {"passes": [{"level_records": [{"level_ms": 1.0}], "total": 5,
                       "spans": {"spans": [["level", 1.0, 0.1, 1]],
                                 "events": []}}],
           "traced": {"manifest": {}, "total": 5,
                      "spans": {"spans": [], "events": []}},
           "rehearsal": False}
    new_names = [n for n in readers
                 if n.startswith("stage_") or n in (
                     "discarded_dispatch_share", "d2h_bytes_per_state",
                     "h2d_bytes_per_state", "fetches_per_level",
                     "store_share", "pass_overhead_ms")]
    check(len(new_names) == 17 and all(
        readers[n].read(old) is None for n in new_names),
        "a program without these records or a `dir` in its manifest: all "
        "seventeen readers return nothing and do not raise")


@contextlib.contextmanager
def reduction_in_place(reduced):
    """`stagereduce.for_ctx` finds `reduced` for a context whose traced pass
    names a run directory, without a profile on disk."""
    found, stagereduce.find_xplane = stagereduce.find_xplane, lambda d: "x"
    stagereduce._CACHE["x"] = reduced
    try:
        yield {"traced": {"manifest": {"dir": "/nowhere/traced.0"}},
               "rehearsal": False}
    finally:
        stagereduce.find_xplane = found
        del stagereduce._CACHE["x"]


def check_stage_readers(trace, want):
    """The ten trace readers through `for_ctx`, on the recorded trace."""
    readers = harness.load_metric_readers()
    reduced = stagereduce.reduce_stages(trace)
    reduced["states"] = want["states"]
    with reduction_in_place(reduced) as ctx:
        check(close(readers["stage_guard_us_per_state"].read(ctx),
                    want["stage_guard_us_per_state"], 1e-12)
              and readers["stage_digest_us_per_state"].read(ctx) == 0.0,
              "reader stage_guard_us_per_state: 1000 ns over 1000 states; "
              "a stage with no work reads 0, not nothing")
        check(close(readers["stage_unnamed_share"].read(ctx),
                    want["stage_unnamed_share"], 1e-12),
              "reader stage_unnamed_share: 200 of 5910 leaf ns")
        total = sum(readers[f"stage_{s}_us_per_state"].read(ctx)
                    for s in stagereduce.STAGES)
        check(close(total + reduced["stage_s"]["unnamed"] * 1e6 / 1000,
                    reduced["leaf_s"] * 1e6 / 1000, 1e-12),
              "the ten stage metrics plus unnamed give the leaf time per "
              "state")


def check_exchange_stage():
    """A sharded level program on two devices: the operations under
    `kspec.exchange` (routing, the collective) are the `exchange` stage on
    the busiest device, and `stage_exchange_us_per_state` reads them.
    Hand-worked: device 1 is busy 3000 ns (its while), device 0 600; on
    device 1 the leaves are 600 + 400 ns of exchange and 900 of the probe,
    so 1100 ns lie in the container alone; 1000 ns over 1000 states is
    0.001 us a state."""
    def op(name, kind, shape="u32[8]{0}"):
        return f"%{name} = {shape} {kind}({shape} %p), calls=%c"

    head = "jit(shl_n1)/while"
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            [op("fusion.1", "fusion"), 1100, 600,
             head + "/body/kspec.exchange/rem:"]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ["%while.1 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), "
             "condition=%c, body=%b", 1000, 3000, head + ":"],
            [op("fusion.1", "fusion"), 1100, 600,
             head + "/body/kspec.exchange/rem:"],
            [op("all-to-all.1", "all-to-all", "(u32[4,8]{1,0})"), 1800, 400,
             head + "/body/kspec.exchange/all_to_all:"],
            [op("fusion.3", "fusion"), 2600, 900,
             head + "/body/kspec.dedup_probe/while/body/gather:"]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["perfbench.pass", 1000, 5000, ""],
            ["kspec.level d=7", 1050, 4000, ""]]}]}]}
    got = stagereduce.reduce_stages(trace)
    check(got["plane"] == "/device:TPU:1"
          and same_ns(got["stage_s"], {"exchange": 1000, "dedup_probe": 900})
          and same_ns(got["by_level"][7],
                      {"exchange": 1000, "dedup_probe": 900})
          and same_ns(got["by_program"]["shl_n1"],
                      {"exchange": 1000, "dedup_probe": 900})
          and close(got["container_only_s"], 1100e-9),
          "exchange: routing and the collective under kspec.exchange are the "
          "exchange stage, on the busiest device, by level and by program")
    readers = harness.load_metric_readers()
    got["states"] = 1000
    with reduction_in_place(got) as ctx:
        check(close(readers["stage_exchange_us_per_state"].read(ctx), 0.001,
                    1e-15)
              and readers["stage_unnamed_share"].read(ctx) == 0.0,
              "reader stage_exchange_us_per_state: 1000 ns over 1000 states; "
              "nothing of the exchange reads as unnamed")
    trace = {"busy_s_mean": 1.0, "window_s": 2.0, "op_seconds": {"a": 1.0},
             "idle_by": {}}
    line = harness.result_line(True, 3, 0, {}, {}, 0, trace, got)
    check(line["breakdown"]["device_ops"] == [
        ["exchange/shl_n1", 1000e-9], ["dedup_probe/shl_n1", 900e-9]],
        "breakdown: device seconds grouped as <stage>/<program>, "
        "largest first")
    line = harness.result_line(True, 3, 0, {}, {}, 0, trace, None)
    check("device_ops" not in line["breakdown"],
          "breakdown: no stage reduction, no grouping")


def main():
    trace, want = check_reduction()
    check_names()
    check_wire_reader()
    check_record_readers()
    check_stage_readers(trace, want)
    check_exchange_stage()
    print(f"{len(FAILURES)} failed")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
