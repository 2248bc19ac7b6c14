#!/usr/bin/env python3
"""Self-check of the benchmark's own yardstick.  Needs no chip.

    python3 perfbench/selfcheck.py            the fast checks (seconds)
    python3 perfbench/selfcheck.py --all      plus the scratch-copy dry run and
                                              a rehearsal of every cell (minutes)
    python3 perfbench/selfcheck.py --derive <config> <depth>
                                              re-derive a golden with the plain
                                              oracle on the CPU and record it

Fast checks: BENCHMARK.json against the contract's limits and against the
files it names; the trace reduction against a small recorded trace; the
bytes function against a hand-worked level; the output line's keys; a
chipless run that exits non-zero naming the TPU and prints no result.

`--all` adds: a dry run in a scratch copy (under perfbench_out/) showing that
a new configuration, traffic mix, per-layer metric and cell are picked up
from new files and one new BENCHMARK.json entry each (one of them a sharded
configuration on four virtual devices; one a violating job with no depth
cut, derived there with the plain oracle, and the same job cut above its
violation), with every existing file byte-identical; and `run.py
--rehearse` for every cell, with and without `--trace 1` (CPU, depth 4,
counts only).

`--derive` writes perfbench/golden/<config>.derived.json: the per-level
counts the oracle found (to `depth`, or to the violation it meets first),
the invariant that failed if one did, the command, and whether they equal
the golden.
The fast checks hold every golden to its derivation record.

Not under tests/: tier-1's count does not move with the benchmark.
"""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import roofline  # noqa: E402
import run as harness  # noqa: E402
import tracereduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
FAILURES = []


def check(cond, what):
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        FAILURES.append(what)


def check_benchmark_json():
    path = os.path.join(ROOT, "BENCHMARK.json")
    bench = harness.load_json(path)
    check(os.path.getsize(path) <= 64 * 1024, "BENCHMARK.json is at most 64 KiB")
    check(set(bench) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json has exactly the contract's keys")
    check(isinstance(bench["run_seconds"], int)
          and 1 <= bench["run_seconds"] <= 51, "run_seconds is 1..51")
    cells = len(bench["workloads"])
    budget = (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200
    check(budget <= 43200, f"a full check of 24 cells fits ({budget} s <= 43200)")
    check(2 <= cells <= 24, "2 to 24 cells")
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    check(all(NAME.match(n) for n in names), "every name is in the allowed characters")
    for k in ("configs", "workloads"):
        ns = [e["name"] for e in bench[k]]
        check(len(ns) == len(set(ns)), f"no two {k} share a name")
    mnames = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    check(len(mnames) == len(set(mnames)), "no two metrics share a name")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    check(four <= max(1, cells // 2), "at most half the cells (or one) take 4 chips")
    check(len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == cells,
          "a pair of configuration and traffic appears once")
    used = {w["config"] for w in bench["workloads"]}
    check(used == {c["name"] for c in bench["configs"]},
          "every configuration is used by some cell")
    for w in bench["workloads"]:
        check(set(w) == {"name", "config", "traffic", "chips", "why"}
              and len(w["why"]) <= 200 and NAME.match(w["traffic"]) is not None,
              f"cell {w['name']}: keys, why <= 200 characters")
        check(os.path.isfile(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
              f"cell {w['name']}: traffic file exists")
    for c in bench["configs"]:
        check(set(c) == {"name", "source", "file", "reduced", "why"}
              and len(c["source"]) <= 200 and len(c["why"]) <= 200,
              f"configuration {c['name']}: keys and lengths")
        f = harness.load_json(os.path.join(ROOT, c["file"]))
        check(f["reduced"] == c["reduced"] and f["source"] == c["source"],
              f"configuration {c['name']}: file agrees on source and reduced")
        check(all(k in f for k in ("cfg", "module", "engine", "options",
                                   "chips", "max_depth", "assumed",
                                   "guarantees", "cut")),
              f"configuration {c['name']}: file states the job and its guarantees")
        check_golden(c["name"], f["max_depth"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    check("setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25,
          "setup_s is end to end, bound at most 0.25")
    for m in bench["end_to_end"]:
        check(set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
              and 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"]) is not None
              and m["source"] in ("host_clock", "device_trace")
              and m["better"] in ("lower", "higher"),
              f"end-to-end {m['name']}: keys, bound, unit, source")
    readers = harness.load_metric_readers()
    for m in bench["per_layer"]:
        ok = (set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                         "workloads"}
              and UNIT.match(m["unit"]) is not None and m["source"] in SOURCES
              and m["moves"] in e2e and m["better"] in ("lower", "higher")
              and set(m.get("workloads", [])) <= {w["name"] for w in bench["workloads"]})
        check(ok, f"per-layer {m['name']}: keys, unit, source, moves")
        meta = readers.get(m["name"], None)
        check(meta is not None and all(
            meta.META[k] == m[k] for k in ("unit", "better", "source", "layer",
                                           "moves")),
              f"per-layer {m['name']}: its reader file says the same")
    return bench


def check_golden(config_name, max_depth, hdir=HERE):
    """A configuration's golden against its oracle derivation.  A golden
    ends at the diameter of an exhaustive space, at its violation (the
    derivation then names the golden's invariant, at its depth, and every
    level and the total are equal), or where its derivation stopped; a job
    cut in depth lies inside it, an uncut one (`max_depth` null) needs a
    golden that ends."""
    g = harness.load_json(os.path.join(hdir, "golden", config_name + ".json"))
    d = harness.load_json(os.path.join(hdir, "golden",
                                       config_name + ".derived.json"))
    n = len(d["levels"])
    violating = g["violation"] is not None
    ends = g["exhaustive"] or violating
    cut_inside = max_depth is not None and len(g["levels"]) > max_depth
    check((ends or cut_inside) and "provenance" in g,
          f"configuration {config_name}: golden reaches its depth "
          f"({'uncut' if max_depth is None else max_depth}), with provenance")
    total = g.get("total", g.get("total_so_far"))
    same = (d["levels"] == g["levels"][:n] and d["total"] == sum(d["levels"])
            and sum(g["levels"]) == total
            and (ends or max_depth is None or n > max_depth))
    if violating:
        same = (same and d["levels"] == g["levels"] and d["total"] == total
                and d["violation"] == g["violation"]["invariant"]
                and n - 1 == g["violation"]["depth"])
    else:
        same = same and d["violation"] is None and (
            not g["exhaustive"] or d["levels"] == g["levels"])
    check(same, f"configuration {config_name}: golden equals its oracle "
          f"derivation ({n - 1} levels, {d['total']:,} states"
          + (f", {d['violation']} at depth {n - 1})" if violating else ")"))


def check_trace_reduction():
    trace = harness.load_json(os.path.join(HERE, "selfcheck_data", "trace_small.json"))
    want = harness.load_json(os.path.join(HERE, "selfcheck_data", "trace_small.expect.json"))
    spans = want["host_spans"]
    got = tracereduce.reduce_trace(trace, spans, want["offset_ns"])
    for key in ("window_s", "devices", "busy_s_mean", "busy_s_max",
                "collective_s_mean", "gaps"):
        check(abs(got[key] - want[key]) < 1e-12, f"trace reduction: {key} = {want[key]}")
    check({k: round(v, 12) for k, v in got["idle_by"].items()} == want["idle_by"],
          f"trace reduction: idle gaps by host span = {want['idle_by']}")
    top = tracereduce.top(got["op_seconds"], 2)
    check([n for n, _ in top] == [n for n, _ in want["top_ops"]]
          and all(abs(a[1] - b[1]) < 1e-15 for a, b in zip(top, want["top_ops"])),
          f"trace reduction: top operations = {want['top_ops']}")
    check(tracereduce.reduce_trace({"planes": []}) is None,
          "trace reduction: nothing to read gives nothing")


def check_bytes():
    # hand-worked: 3 lanes, 1,000 frontier rows, 5,000 enabled candidates,
    # 2,000 new: 12*1000 + 12*2000 + 8*5000 + 8*5000 + 8*2000 = 132,000
    check(roofline.level_min_bytes(1000, 5000, 2000, 10000, 3) == 132000,
          "bytes function: hand-worked level is 132,000 bytes")
    recs = [{"frontier": 1000, "enabled_candidates": 5000, "new": 2000, "total": 3000}] * 2
    check(roofline.pass_min_bytes(recs, 3) == 264000, "bytes function: a pass sums its levels")
    check(roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9,
          "peaks: TPU v5 lite is 819 GB/s")
    try:
        roofline.peaks("TPU v9")
        check(False, "peaks: an unknown device kind is an error")
    except KeyError:
        check(True, "peaks: an unknown device kind is an error")


def check_output_line():
    trace = {"busy_s_mean": 1.5, "window_s": 3.0, "op_seconds": {"a": 1.0},
             "idle_by": {"step": 0.5}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    metrics = {"host_share": {"value": 12.5, "unit": "%"}}
    line = harness.result_line(True, 5, 0, metrics, device, 123, trace)
    check(set(line) == {"correct", "attempted", "failed", "metrics", "device",
                        "breakdown"}
          and set(line["device"]) == {"platform", "kind", "count",
                                      "memory_peak_bytes", "busy_s", "window_s"}
          and line["breakdown"] == {"idle_gaps": [["step", 0.5]],
                                    "raw_ops": [["a", 1.0]]},
          "output line: --trace 1 has the contract's keys (no stage "
          "reduction: no device_ops, the raw operation names as raw_ops)")
    stages = {"by_program": {"fsc_n1": {"dedup_probe": 0.75, "guard": 0.0},
                             "step_n1": {"dedup_merge": 0.25}}}
    compared = {"passes_off_golden": {"value": 0, "limit": 0}}
    line = harness.result_line(True, 5, 0, metrics, device, 123, trace, stages,
                               compared)
    check(line["breakdown"]["device_ops"] == [["dedup_probe/fsc_n1", 0.75],
                                              ["dedup_merge/step_n1", 0.25]]
          and line["breakdown"]["raw_ops"] == [["a", 1.0]]
          and list(line)[-1] == "compared",
          "output line: device_ops grouped by stage and program, raw names "
          "beside them, the numbers compared last")
    line = harness.result_line(True, 5, 0, metrics, device, 123, None)
    check(set(line) == {"correct", "attempted", "failed", "metrics", "device"}
          and set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"},
          "output line: --trace 0 has the contract's keys")
    check(json.loads(json.dumps(line)) == line, "output line: one JSON object")


def run_harness(argv, cwd=ROOT, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    p = subprocess.run([sys.executable, os.path.join(cwd, "perfbench", "run.py")] + argv,
                       cwd=cwd, env=env, capture_output=True, text=True, timeout=1200)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return p.returncode, lines, p.stderr


def check_chipless(bench):
    cell = bench["workloads"][0]["name"]
    rc, lines, err = run_harness(["--workload", cell, "--seed", "0", "--seconds", "1",
                                  "--trace", "0"], env_extra={"JAX_PLATFORMS": "cpu"})
    check(rc != 0 and "TPU" in err, "chipless run: exits non-zero naming the TPU")
    check(not any(l.startswith("{") for l in lines), "chipless run: prints no result")


def rehearse(cell, trace, cwd=ROOT):
    rc, lines, err = run_harness(["--workload", cell, "--seed", "3", "--trace", str(trace),
                                  "--rehearse"], cwd=cwd)
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = {}
    if rc != 0:
        print(err[-2000:])
    return rc, last


def check_rehearsals(bench):
    for w in bench["workloads"]:
        for trace in (0, 1):
            rc, last = rehearse(w["name"], trace)
            check(rc == 0 and last.get("correct") is True and last.get("attempted") >= 3
                  and last.get("failed") == 0,
                  f"rehearsal {w['name']} --trace {trace}: counts golden, 3 passes, "
                  f"metrics {last.get('metric_names')}")


def _tree_hash(root):
    out = {}
    for d, _dirs, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def check_dry_run_additions():
    """A later PR's view: add files and one entry each, edit nothing."""
    copy = os.path.join(ROOT, "perfbench_out", "selfcheck_copy")
    shutil.rmtree(copy, ignore_errors=True)
    os.makedirs(copy)
    for name in ("perfbench", "kafka_specification_tpu", "configs"):
        shutil.copytree(os.path.join(ROOT, name), os.path.join(copy, name),
                        ignore=shutil.ignore_patterns("__pycache__", "*.so"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    before = _tree_hash(os.path.join(copy, "perfbench"))
    pb = os.path.join(copy, "perfbench")
    # a new configuration (Kip101 from the corpus), golden, traffic, metric
    with open(os.path.join(pb, "configs", "dry-kip320-3b-d3.json"), "w") as fh:
        base = harness.load_json(os.path.join(pb, "configs", "kip320-3b.json"))
        base.update(name="dry-kip320-3b-d3", max_depth=3,
                    source="dry run: " + base["source"][:150])
        json.dump(base, fh)
    shutil.copy(os.path.join(pb, "golden", "kip320-3b.json"),
                os.path.join(pb, "golden", "dry-kip320-3b-d3.json"))
    with open(os.path.join(pb, "traffic", "dry-two-jobs.json"), "w") as fh:
        json.dump({"name": "dry-two-jobs", "options": {"store_trace": True},
                   "jobs": [{"name": "shallow", "options": {"max_depth": 2}},
                            {"name": "deep", "options": {}}],
                   "order": "seeded-shuffle"}, fh)
    with open(os.path.join(pb, "metrics", "dry_levels.py"), "w") as fh:
        fh.write('META = {"name": "dry_levels", "unit": "count", "better": "higher",\n'
                 '        "source": "program_counter", "layer": "level programs",\n'
                 '        "moves": "states_per_s", "what": "levels per pass"}\n\n\n'
                 'def read(ctx):\n'
                 '    return len(ctx["passes"][0]["level_records"])\n')
    bench = harness.load_json(os.path.join(copy, "BENCHMARK.json"))
    bench["configs"].append({"name": "dry-kip320-3b-d3", "source": base["source"],
                             "file": "perfbench/configs/dry-kip320-3b-d3.json",
                             "reduced": base["reduced"], "why": "dry run"})
    bench["workloads"].append({"name": "dry-cell", "config": "dry-kip320-3b-d3",
                               "traffic": "dry-two-jobs", "chips": 1, "why": "dry run"})
    bench["per_layer"].append({"name": "dry_levels", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "level programs",
                               "moves": "states_per_s", "workloads": ["dry-cell"]})
    # a sharded configuration on four (virtual) devices with a metric of the
    # exchange layer: what the PR that measures the four-chip cell adds
    x4 = harness.load_json(os.path.join(pb, "configs", "kip320-5b.json"))
    x4.update(name="dry-kip320-5b-x4", engine="sharded", chips=4)
    with open(os.path.join(pb, "configs", "dry-kip320-5b-x4.json"), "w") as fh:
        json.dump(x4, fh)
    shutil.copy(os.path.join(pb, "golden", "kip320-5b.json"),
                os.path.join(pb, "golden", "dry-kip320-5b-x4.json"))
    with open(os.path.join(pb, "metrics", "dry_exchange_bytes_per_state.py"), "w") as fh:
        fh.write('from metriclib import median_over_passes\n\n'
                 'META = {"name": "dry_exchange_bytes_per_state", "unit": "B",\n'
                 '        "better": "lower", "source": "program_counter",\n'
                 '        "layer": "exchange", "moves": "states_per_s",\n'
                 '        "what": "manifest exchange_bytes_total over distinct states"}\n\n\n'
                 'def read(ctx):\n'
                 '    def one(p):\n'
                 '        total = (p["manifest"].get("result") or {}).get("exchange_bytes_total")\n'
                 '        return None if total is None else total / p["total"]\n\n'
                 '    return median_over_passes(ctx, one)\n')
    bench["configs"].append({"name": "dry-kip320-5b-x4", "source": x4["source"],
                             "file": "perfbench/configs/dry-kip320-5b-x4.json",
                             "reduced": x4["reduced"], "why": "dry run"})
    bench["workloads"].append({"name": "dry-x4", "config": "dry-kip320-5b-x4",
                               "traffic": "exhaustive-notrace", "chips": 4,
                               "why": "dry run"})
    bench["per_layer"].append({"name": "dry_exchange_bytes_per_state", "unit": "B",
                               "better": "lower", "source": "program_counter",
                               "layer": "exchange", "moves": "states_per_s",
                               "workloads": ["dry-x4"]})
    # a violating job with no depth cut, and the same job cut above its
    # violation: what the PR that brings a counterexample cell adds
    # (configs/Kip101.cfg: WeakIsr at depth 11 after 5,491 states, CPU-sized)
    cex = dict(base, name="dry-kip101-cex", cfg="configs/Kip101.cfg",
               module="Kip101", max_depth=None, reduced=[],
               source="dry run: Kip101.tla under configs/Kip101.cfg")
    cut = dict(cex, name="dry-kip101-d8", max_depth=8, reduced=["max_depth"])
    for conf in (cex, cut):
        with open(os.path.join(pb, "configs", conf["name"] + ".json"), "w") as fh:
            json.dump(conf, fh)
        with open(os.path.join(pb, "golden", conf["name"] + ".json"), "w") as fh:
            json.dump({"config": conf["name"], "exhaustive": False,
                       "violation": {"invariant": "WeakIsr", "depth": 11,
                                     "trace_len": 12},
                       "total": 5491, "provenance": "dry run: chip_smoke.py "
                       "_KIP101_LEVELS, re-derived in this dry run",
                       "levels": [1, 4, 14, 44, 100, 166, 268, 456, 684, 976,
                                  1292, 1486]}, fh)
        bench["configs"].append({"name": conf["name"], "source": conf["source"],
                                 "file": f"perfbench/configs/{conf['name']}.json",
                                 "reduced": conf["reduced"], "why": "dry run"})
    bench["workloads"].append({"name": "dry-cex", "config": "dry-kip101-cex",
                               "traffic": "exhaustive-trace", "chips": 1,
                               "why": "dry run"})
    bench["workloads"].append({"name": "dry-cex-d8", "config": "dry-kip101-d8",
                               "traffic": "exhaustive-notrace", "chips": 1,
                               "why": "dry run"})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    for conf in (cex, cut):
        p = subprocess.run([sys.executable, os.path.join(pb, "selfcheck.py"),
                            "--derive", conf["name"], "99"], cwd=copy,
                           capture_output=True, text=True, timeout=600)
        check(p.returncode == 0, f"dry run: {conf['name']} derives with the "
              "plain oracle to its violation, equal to its golden")
        check_golden(conf["name"], conf["max_depth"], hdir=pb)
    def rehearsed_passes(cell, trace):
        return harness.load_json(os.path.join(
            copy, "perfbench_out", cell, f"seed3-trace{trace}-rehearsal",
            "run.json"))["passes"]

    rc, last = rehearse("dry-cex", 1, cwd=copy)
    seen = rehearsed_passes("dry-cex", 1)
    want_v = {"invariant": "WeakIsr", "depth": 11, "trace_len": 12}
    check(rc == 0 and last.get("correct") is True and len(seen) >= 3
          and all(p["total"] == 5491 and p["violation"]["rendered_chars"] > 0
                  and {k: p["violation"][k] for k in want_v} == want_v
                  for p in seen),
          "dry run: a violating, uncut configuration enters by files and "
          "entries alone (every pass: WeakIsr at depth 11, 5,491 states, a "
          "rendered 12-state trace)")
    rc, last = rehearse("dry-cex-d8", 0, cwd=copy)
    seen = rehearsed_passes("dry-cex-d8", 0)
    check(rc == 0 and last.get("correct") is True
          and all(p["violation"] is None and p["total"] == 163 for p in seen),
          "dry run: the same job cut above its violation's depth owes no "
          "violation (cut at 8, rehearsed at depth 4: 163 states)")
    rc, last = rehearse("dry-x4", 1, cwd=copy)
    check(rc == 0 and last.get("correct") is True
          and last.get("device", {}).get("count") == 4
          and "dry_exchange_bytes_per_state" in last.get("metric_names", []),
          "dry run: a sharded cell and an exchange metric enter by files and "
          "entries alone (4 virtual devices, counts golden)")
    rc, last = rehearse("dry-cell", 1, cwd=copy)
    check(rc == 0 and last.get("correct") is True
          and "dry_levels" in last.get("metric_names", []),
          "dry run: new configuration, traffic, metric and cell picked up from new files")
    rc2, last2 = rehearse("kip320-3b-notrace", 1, cwd=copy)
    check(rc2 == 0 and "dry_levels" not in last2.get("metric_names", ["dry_levels"]),
          "dry run: the new metric stays out of the cells it does not list")
    after = _tree_hash(pb)
    check(all(after.get(k) == v for k, v in before.items()),
          "dry run: every existing benchmark file is byte-identical")
    added = sorted(set(after) - set(before))
    print("     added files:", added)
    shutil.rmtree(copy, ignore_errors=True)


def check_harness_tests():
    """The harness's own tests (CPU): what a pass owes and why it fails, the
    window's rate, the readers, the broken timed path and the control."""
    p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p",
                        "no:cacheprovider", os.path.join(HERE, "tests")],
                       cwd=ROOT, capture_output=True, text=True, timeout=3600)
    tail = (p.stdout.strip().splitlines() or [""])[-1]
    if p.returncode != 0:
        print(p.stdout[-4000:], p.stderr[-2000:])
    check(p.returncode == 0, f"pytest perfbench/tests: {tail}")


def derive_golden(config_name, depth):
    """Plain oracle, CPU, to `depth`: the record a golden is held to."""
    import time

    os.environ["JAX_PLATFORMS"] = "cpu"  # the tensor model is built, never run
    sys.path.insert(0, ROOT)
    import adapter

    config = harness.load_json(os.path.join(HERE, "configs", config_name + ".json"))
    golden = harness.load_json(os.path.join(HERE, "golden", config_name + ".json"))
    job = adapter.Job(config, ROOT)
    t0 = time.perf_counter()
    got = harness.oracle_prefix(job, golden, float("inf"), max_depth=depth)
    record = {
        "config": config_name,
        "command": f"python3 perfbench/selfcheck.py --derive {config_name} {depth}",
        "what": "breadth-first search over the program's oracle twin of the "
                "same cfg constants (run.py oracle_prefix), every invariant "
                "checked on every state; CPU, host Python only",
        "derived": time.strftime("%Y-%m-%d", time.gmtime()),
        "oracle_seconds_on_this_cpu": round(time.perf_counter() - t0, 1),
        "invariants": [name for name, _ in job.oracle_model().invariants],
        "violation": got["violation"],
        "levels": got["levels"],
        "total": sum(got["levels"]),
        "equal_to_golden": got["ok"],
    }
    path = os.path.join(HERE, "golden", config_name + ".derived.json")
    with open(path, "w") as fh:
        json.dump(record, fh, indent=1)
        fh.write("\n")
    print(json.dumps(record))
    return 0 if got["ok"] else 1


def main():
    if sys.argv[1:2] == ["--derive"]:
        return derive_golden(sys.argv[2], int(sys.argv[3]))
    bench = check_benchmark_json()
    check_trace_reduction()
    check_bytes()
    check_output_line()
    check_chipless(bench)
    if "--all" in sys.argv[1:]:
        check_dry_run_additions()
        check_rehearsals(bench)
        check_harness_tests()
    print(f"{len(FAILURES)} failed")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
