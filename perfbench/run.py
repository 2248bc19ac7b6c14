#!/usr/bin/env python3
"""The benchmark: warm whole-check passes on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell.  Set-up builds the cell's job as `cli check` does,
runs it untimed until every program it uses is compiled or loaded, and holds
it to the golden and to the plain oracle.  The window then runs whole checks
back to back; each pass is one `attempted`.  The last line of standard output
is the one JSON object the driver reads.  PERF.md says what each number means.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file this harness finds by name (BENCHMARK.json names them):

    perfbench/configs/<config>.json     the job: cfg, module, engine, depth
    perfbench/traffic/<traffic>.json    trace store on/off, jobs, order rule
    perfbench/golden/<config>.json      verdict and per-level counts
    perfbench/metrics/<metric>.py       META + read(ctx) for one per-layer metric

`--rehearse` (never given by the driver) runs the same control flow on the
CPU, a depth-cut job at depth 4 and an uncut one (`max_depth` null) whole,
and prints counts only: no timing, rate or device metric.
"""

import argparse
import importlib.util
import json
import os
import random
import shutil
import statistics
import sys
import time

_T_IMPORT_UNIX = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_ROOT = os.path.join(ROOT, "perfbench_out")
REHEARSAL_DEPTH = 4
MIN_PASSES = 3
ORACLE_SECONDS = 2.0
MAX_SETUP_PASSES = 3

JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JAXPR_TO_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def process_start_unix():
    """When this process started, from the kernel's own record."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        start = time.time() - age
        # the kernel's tick is coarse; never later than this module's import
        return min(start, _T_IMPORT_UNIX)
    except (OSError, ValueError, IndexError):
        return _T_IMPORT_UNIX


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(workload):
    """The cell's entry, configuration, traffic and golden, found by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no cell {workload!r} in BENCHMARK.json "
                         f"(cells: {', '.join(sorted(cells))})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    golden = load_json(os.path.join(HERE, "golden", cell["config"] + ".json"))
    if int(config["chips"]) != int(cell["chips"]):
        raise SystemExit(f"perfbench: cell {workload} asks for {cell['chips']} "
                         f"chips, its configuration for {config['chips']}")
    return bench, cell, config, traffic, golden


def load_metric_readers():
    """Every per-layer metric file present: {name: module}."""
    readers = {}
    mdir = os.path.join(HERE, "metrics")
    for fname in sorted(os.listdir(mdir)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "perfbench_metric_" + fname[:-3], os.path.join(mdir, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        readers[mod.META["name"]] = mod
    return readers


class JaxEvents:
    """What JAX itself reports of tracing, lowering and compiling."""

    def __init__(self):
        self.events = []  # [perf_counter, name, seconds or None]

    def install(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.events.append(
                [time.perf_counter(), name, secs]))
        monitoring.register_event_listener(
            lambda name, **kw: self.events.append(
                [time.perf_counter(), name, None]))

    def mark(self):
        return len(self.events)

    def since(self, mark):
        evs = self.events[mark:]

        def total(name):
            return sum(s for _, n, s in evs if n == name and s is not None)

        def count(name):
            return sum(1 for _, n, _s in evs if n == name)

        return {
            "backend_compiles": count(BACKEND_COMPILE_EVENT),
            "backend_compile_s": total(BACKEND_COMPILE_EVENT),
            "cache_hits": count(CACHE_HIT_EVENT),
            "cache_misses": count(CACHE_MISS_EVENT),
            "traces": count(JAXPR_TRACE_EVENT),
            "trace_s": total(JAXPR_TRACE_EVENT),
            "lowerings": count(JAXPR_TO_MLIR_EVENT),
            "lowering_s": total(JAXPR_TO_MLIR_EVENT),
        }


def golden_for(golden, max_depth):
    """What a pass cut at `max_depth` (None: not cut) must return.  A
    golden ends where the search does: at the diameter of an exhaustive
    space, at its violation's depth, or where its derivation stopped.  A
    pass cut above the violation's depth never meets it."""
    levels, violation = golden["levels"], golden["violation"]
    ends = golden["exhaustive"] or violation is not None
    if not ends and (max_depth is None or max_depth + 1 > len(levels)):
        raise SystemExit(
            f"perfbench: golden holds {len(levels) - 1} levels of a space "
            f"it does not end, the job asks for depth {max_depth}")
    if max_depth is not None:
        levels = levels[: max_depth + 1]
        if violation is not None and violation["depth"] > max_depth:
            violation = None
    return {"levels": levels, "total": sum(levels),
            "diameter": len(levels) - 1, "violation": violation}


def judge_pass(rec, want):
    """Why this pass fails, as (kind, text); empty when it passed.  Kinds:
    `answer` (differs from the golden), `degraded` (the recovery ladder
    ran), `compiled` (a program was built, loaded or re-traced)."""
    from adapter import DEGRADE_EVENTS

    why = []
    for key in ("levels", "total", "diameter"):
        if rec[key] != want[key]:
            why.append(("answer", f"{key} {rec[key]} != golden {want[key]}"))
    got_v, want_v = rec["violation"], want["violation"]
    if (got_v is None) != (want_v is None) or (
            got_v and any(got_v.get(k) != want_v.get(k) for k in want_v)):
        why.append(("answer", f"verdict {got_v} != golden {want_v}"))
    bad = sorted({e for e in rec["spans"]["events"] if e in DEGRADE_EVENTS})
    if bad:
        why.append(("degraded", f"events {bad}"))
    dev = (rec["manifest"].get("result") or {}).get("device") or {}
    if dev.get("fallback") is not None:
        why.append(("degraded", f"device.fallback = {dev['fallback']!r}"))
    if rec["stats"].get("degradations"):
        why.append(("degraded", f"{rec['stats']['degradations']}"))
    n_compile = sum(1 for s in rec["spans"]["spans"] if s[0] == "compile")
    if n_compile:
        why.append(("compiled", f"{n_compile} compile spans"))
    if rec["jax"]["backend_compiles"]:
        why.append(("compiled", f"JAX reports {rec['jax']['backend_compiles']}"
                    " backend compiles or cache loads"))
    return why


def oracle_prefix(job, golden, seconds, max_depth=None, key=None):
    """Plain breadth-first search over the oracle twin for `seconds` (or to
    `max_depth`): per-level counts of every level it finished, held to the
    golden.  The loop is the benchmark's own; only the transition relation
    and the invariants are the program's oracle model.  `key` is what the
    visited set holds of a state: the state itself, or (the control) less."""
    key = key or (lambda s: s)
    om = job.oracle_model()
    deadline = time.perf_counter() + seconds
    frontier = list(dict.fromkeys(om.init_states()))
    visited = {key(s) for s in frontier}
    levels = [len(frontier)]
    violation = None
    while frontier and violation is None and (
            max_depth is None or len(levels) <= max_depth):
        nxt = []
        cut = False
        for i, s in enumerate(frontier):
            if i % 64 == 0 and time.perf_counter() > deadline:
                cut = True
                break
            for a in om.actions:
                for t in a.successors(s):
                    if om.constraint is not None and not om.constraint(t):
                        continue
                    if key(t) not in visited:
                        visited.add(key(t))
                        nxt.append(t)
        if cut:
            break
        for name, pred in om.invariants:
            if violation is None and not all(pred(s) for s in nxt):
                violation = name
        if nxt:
            levels.append(len(nxt))
        frontier = nxt
    want = golden["levels"][: len(levels)]
    # a prefix may stop short of the golden's violation; one it does find
    # is the golden's invariant, at the golden's depth
    want_v = golden["violation"] or {}
    ok = levels == want and (violation is None or (
        violation == want_v.get("invariant")
        and len(levels) - 1 == want_v.get("depth")))
    return {"levels": levels, "ok": ok, "violation": violation}


def memory_snapshot(jax):
    """Where the device's memory stands: the allocator's own counters per
    device and the address and size of every live buffer.  A record for
    whoever hunts a layout-dependent time (PERF.md section 6), read by no
    metric."""
    buffers = []
    for arr in jax.live_arrays():
        try:
            buffers += [[sh.data.unsafe_buffer_pointer(), int(sh.data.nbytes)]
                        for sh in arr.addressable_shards]
        except Exception:  # noqa: BLE001 — a backend without addresses
            continue
    return {"stats": [d.memory_stats() or {} for d in jax.devices()],
            "live_buffers": sorted(buffers)}


def pass_options(config, traffic, job_spec, depth_override):
    """Engine keywords of one job: the configuration's, then the traffic's,
    then the job's own.  `max_depth` null is the job a user runs with no
    depth cut, and stays uncut under `depth_override` (a rehearsal) too:
    a cut would keep a violating job from its violation."""
    opts = dict(config.get("options", {}))
    opts.update(traffic.get("options", {}))
    opts.update(job_spec.get("options", {}))
    opts.setdefault("max_depth", config["max_depth"])
    if depth_override is not None and opts["max_depth"] is not None:
        opts["max_depth"] = min(depth_override, opts["max_depth"])
    return opts


class Cell:
    """One cell in one process: its job, its passes, its records."""

    def __init__(self, args, bench, cell, config, traffic, golden, jax):
        import adapter
        import tracereduce

        self.args, self.bench, self.cell = args, bench, cell
        self.config, self.traffic, self.golden = config, traffic, golden
        self.jax, self.tracereduce = jax, tracereduce
        self.events = JaxEvents()
        self.events.install()
        self.out_dir = os.path.join(
            OUT_ROOT, cell["name"], f"seed{args.seed}-trace{args.trace}"
            + ("-rehearsal" if args.rehearse else ""))
        shutil.rmtree(self.out_dir, ignore_errors=True)  # a reopened run
        os.makedirs(self.out_dir)                        # directory resumes
        self.job = adapter.Job(config, ROOT)
        self.depth_override = REHEARSAL_DEPTH if args.rehearse else None
        self.jobs = list(traffic["jobs"])
        if traffic.get("order", "as-listed") == "seeded-shuffle":
            random.Random(args.seed).shuffle(self.jobs)

    def one_pass(self, tag):
        """Every job of the traffic once, in the seed's order."""
        recs = []
        for j, spec in enumerate(self.jobs):
            opts = pass_options(self.config, self.traffic, spec,
                                self.depth_override)
            mark = self.events.mark()
            with self.jax.profiler.TraceAnnotation(
                    self.tracereduce.PASS_ANNOTATION):
                rec = self.job.run_pass(
                    os.path.join(self.out_dir, f"{tag}.{j}"), opts)
            rec["jax"] = self.events.since(mark)
            rec["job"] = spec.get("name", str(j))
            rec["failed_because"] = judge_pass(
                rec, golden_for(self.golden, opts["max_depth"]))
            recs.append(rec)
        if len(recs) == 1:
            return recs[0]
        return {
            "jobs": recs,
            "wall_s": sum(r["wall_s"] for r in recs),
            "total": sum(r["total"] for r in recs),
            "level_records": [x for r in recs for x in r["level_records"]],
            "failed_because": [w for r in recs for w in r["failed_because"]],
            "jax": {k: sum(r["jax"][k] for r in recs) for k in recs[0]["jax"]},
            "spans": {"spans": [x for r in recs for x in r["spans"]["spans"]],
                      "events": [x for r in recs
                                 for x in r["spans"]["events"]]},
            "stats": recs[-1]["stats"], "manifest": recs[-1]["manifest"],
            "t0_unix": recs[0]["t0_unix"],
        }

    def set_up(self, t_start_unix):
        """Step 1: untimed passes until one after the first neither compiles
        nor loads a program (either engine), then the oracle prefix."""
        mark = self.events.mark()
        passes, problems, rewarmed = [], [], 0
        for i in range(MAX_SETUP_PASSES):
            rec = self.one_pass(f"setup{i}")
            passes.append(rec)
            # compiling is what set-up is for: only a wrong or degraded pass
            # fails it, and a pass that compiled means "not warm yet"
            wrong = [w for w in rec["failed_because"] if w[0] != "compiled"]
            if wrong:
                problems += [w[1] for w in wrong]
                break
            if i > 0 and not rec["failed_because"]:
                break
            if i == MAX_SETUP_PASSES - 1:
                problems.append(f"pass {i} of set-up still compiled: "
                                f"{rec['failed_because']}")
                break
            rewarmed += self.job.after_setup_pass()
        jax_counts = self.events.since(mark)
        oracle = oracle_prefix(self.job, self.golden, ORACLE_SECONDS)
        if not oracle["ok"]:
            problems.append(
                f"oracle prefix {oracle['levels']} != golden "
                f"{self.golden['levels'][:len(oracle['levels'])]}")
        setup = {
            "passes": len(passes), "rewarmed_variants": rewarmed,
            "jax": jax_counts,
            "compile_spans": sum(1 for p in passes for s in p["spans"]["spans"]
                                 if s[0] == "compile"),
            "oracle_levels": oracle["levels"], "problems": problems,
            "kernel_source": self.job.kernel_source,
        }
        if not self.args.rehearse:
            setup["setup_s"] = time.time() - t_start_unix
            setup["pass_walls_s"] = [p["wall_s"] for p in passes]
        print("# setup " + json.dumps(setup), flush=True)
        if (self.job.engine == "single"
                and jax_counts["backend_compiles"] != setup["compile_spans"]):
            print(f"# note: JAX reports {jax_counts['backend_compiles']} "
                  f"backend compiles or cache loads in set-up, the program "
                  f"{setup['compile_spans']} compile spans (small jitted "
                  f"helpers carry no span)", flush=True)
        return setup

    def window(self, seconds):
        """Step 2: whole passes back to back; a pass is never cut.  With
        `--trace 1`, one more pass under the profiler after the first.
        Returns the passes, the traced pass, and the window's seconds: first
        pass's start to last pass's end, the profiler's stretch left out."""
        args = self.args
        passes, traced = [], None
        longest = 0.0  # of the window's own passes: the first always starts
        traced_s = 0.0
        t_window = time.perf_counter()
        while True:
            if args.trace and len(passes) == 1 and traced is None:
                # no sample of the window's rate or medians
                t_traced = time.perf_counter()
                traced = self.traced_pass()
                traced_s = time.perf_counter() - t_traced
                continue
            elapsed = time.perf_counter() - t_window
            if args.rehearse:
                if len(passes) >= MIN_PASSES:
                    break
            elif elapsed + longest > seconds:
                break
            rec = self.one_pass(f"pass{len(passes)}")
            passes.append(rec)
            longest = max(longest, rec["wall_s"])
            timing = "" if args.rehearse else f" wall_s={rec['wall_s']:.4f}"
            print(f"# pass {len(passes)}{timing} states={rec['total']} "
                  f"failed_because={rec['failed_because']}", flush=True)
        return passes, traced, time.perf_counter() - t_window - traced_s

    def traced_pass(self):
        trace_dir = os.path.join(self.out_dir, "trace")
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the Python tracer slows the host loop
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            return self.one_pass("traced")
        finally:
            self.jax.profiler.stop_trace()

    def reduce_trace(self, traced):
        """The traced pass's profile -> numbers (None: nothing to read)."""
        tr = self.tracereduce
        xplane = tr.find_xplane(os.path.join(self.out_dir, "trace"))
        if not xplane:
            return None
        raw = tr.load_xplane(xplane)
        with open(os.path.join(self.out_dir, "trace_lines.json"), "w") as fh:
            json.dump(tr.describe(raw), fh, indent=1)
        with open(os.path.join(self.out_dir, "trace_excerpt.json"), "w") as fh:
            json.dump(tr.excerpt(raw), fh)
        win = tr.pass_window(raw)
        # profiler clock minus unix clock, from the harness's own annotation
        offset = None if win is None else win[0] - traced["t0_unix"] * 1e9
        trace = tr.reduce_trace(raw, traced["spans"]["spans"], offset)
        if trace is not None:  # every operation, to set two processes side by side
            with open(os.path.join(self.out_dir, "trace_ops.json"), "w") as fh:
                json.dump(trace["op_seconds"], fh, indent=0)
        return trace

    def per_layer_metrics(self, ctx):
        metrics = {}
        readers = load_metric_readers()
        for entry in self.bench["per_layer"]:
            name = entry["name"]
            if name not in readers or self.cell["name"] not in entry.get(
                    "workloads", [self.cell["name"]]):
                continue
            value = readers[name].read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": entry["unit"]}
        return metrics

    def end_to_end_metrics(self, passes, setup, window_s):
        """The rate is all the window's states over all its seconds, so a
        stall in any pass moves it; `verdict_s` is the median pass."""
        if not passes:
            return {}
        units = {m["name"]: m["unit"] for m in self.bench["end_to_end"]}
        values = {
            "states_per_s": sum(p["total"] for p in passes) / window_s,
            "verdict_s": statistics.median(p["wall_s"] for p in passes),
            "setup_s": setup.get("setup_s"),
        }
        return {k: {"value": v, "unit": units[k]} for k, v in values.items()}


def start_jax(args, chips):
    """JAX on the cell's chips, or None: no CPU fallback."""
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    os.chdir(ROOT)
    import adapter

    adapter.init_compile_cache()
    import jax

    devices = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != want or len(devices) != chips:
        log(f"perfbench: cell {args.workload} needs {chips} TPU chip(s); JAX "
            f"reports {len(devices)} device(s) of platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}). "
            f"No CPU fallback: not measured.")
        return None
    return jax


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, depth %d where the job is cut in depth, counts "
                    "only; never a measurement"
                    % REHEARSAL_DEPTH)
    args = ap.parse_args(argv)
    t_start_unix = process_start_unix()

    bench, cell, config, traffic, golden = load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    chips = int(cell["chips"])
    jax = start_jax(args, chips)
    if jax is None:
        return 3
    import roofline

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks = None if args.rehearse else roofline.peaks(device["kind"])

    run = Cell(args, bench, cell, config, traffic, golden, jax)
    setup = run.set_up(t_start_unix)
    memory = {"after_setup": memory_snapshot(jax)}
    passes, traced, window_s = ([], None, 0.0) if setup["problems"] \
        else run.window(seconds)
    memory["after_window"] = memory_snapshot(jax)
    attempted = len(passes)
    failed = sum(1 for p in passes if p["failed_because"])
    compared = compare(setup, passes, traced)
    correct = all(c["value"] >= c["at_least"] if "at_least" in c
                  else c["value"] <= c["limit"] for c in compared.values())

    memory_peak = max((m.get("peak_bytes_in_use", 0)
                       for m in memory["after_window"]["stats"]), default=0)
    trace = stages = None
    if traced is not None:
        try:
            trace = run.reduce_trace(traced)
        except Exception as e:  # noqa: BLE001 — the run's counters must
            # still be written; without busy_s the driver refuses the line
            log(f"perfbench: trace reduction failed: {type(e).__name__}: {e}")
    if args.trace:
        import stagereduce

        ctx = {
            "cell": cell, "config": config, "lanes": run.job.lanes,
            "setup": setup, "passes": passes, "traced": traced, "trace": trace,
            "device_kind": device["kind"], "peaks": peaks, "chips": chips,
            "memory_peak_bytes": memory_peak, "rehearsal": args.rehearse,
        }
        metrics = run.per_layer_metrics(ctx)
        stages = stagereduce.for_ctx(ctx)
    else:
        metrics = run.end_to_end_metrics(passes, setup, window_s)

    keep = ("wall_s", "total", "levels", "violation", "failed_because",
            "jax", "level_records", "stats")
    with open(os.path.join(run.out_dir, "run.json"), "w") as fh:
        json.dump({
            "workload": cell["name"], "seed": args.seed, "trace": args.trace,
            "seconds": seconds, "window_s": window_s, "setup": setup,
            "memory": memory, "compared": compared,
            "passes": [{k: p.get(k) for k in keep} for p in passes],
            "traced": traced and {k: traced.get(k) for k in keep},
            "trace": trace and {k: v for k, v in trace.items()
                                if k != "op_seconds"},
        }, fh, indent=1)

    if args.rehearse:
        # counts and control flow only: no timing, rate or device metric
        print(json.dumps({
            "rehearsal": True, "correct": correct, "attempted": attempted,
            "failed": failed, "metric_names": sorted(metrics),
            "device": device, "problems": setup["problems"],
            "compared": compared}))
        return 0 if correct else 1
    for why in setup["problems"] + [
            w[1] for p in passes + [traced or {}]
            for w in p.get("failed_because", [])][:8]:
        log(f"perfbench: not correct: {why}"[:400])
    for name, c in compared.items():
        log(f"perfbench: compared {name} = {json.dumps(c)}")
    print(json.dumps(result_line(correct, attempted, failed, metrics, device,
                                 memory_peak, trace, stages, compared)),
          flush=True)
    return 0


def compare(setup, passes, traced):
    """Every number `correct` rests on, beside its limit.  The answers are
    exact (per-level distinct-state counts, total, diameter and verdict
    against the oracle-derived golden), so their limit is 0 passes that
    differ; so is the limit on passes that degraded or built a program."""
    judged = passes + ([traced] if traced else [])

    def count(kind):
        return sum(1 for p in judged
                   if any(w[0] == kind for w in p["failed_because"]))

    return {
        "setup_problems": {"value": len(setup["problems"]), "limit": 0},
        "passes_off_golden": {"value": count("answer"), "limit": 0},
        "passes_degraded": {"value": count("degraded"), "limit": 0},
        "passes_building_a_program": {"value": count("compiled"), "limit": 0},
        "window_passes": {"value": len(passes), "at_least": MIN_PASSES},
    }


def result_line(correct, attempted, failed, metrics, device, memory_peak,
                trace, stages=None, compared=None):
    """The one object the driver reads, with the contract's keys; the
    numbers compared come last."""
    device = dict(device, memory_peak_bytes=memory_peak)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        import tracereduce

        device["busy_s"] = trace["busy_s_mean"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {
            "idle_gaps": tracereduce.top(trace["idle_by"]),
            # the raw operation names, summed over the devices
            "raw_ops": tracereduce.top(trace["op_seconds"]),
        }
        if stages:
            # device seconds by stage and program on the busiest device
            # (`dedup_probe/fsc_n1`), readable without the HLO
            line["breakdown"]["device_ops"] = tracereduce.top(
                {f"{stage}/{prog}": secs
                 for prog, by_stage in stages["by_program"].items()
                 for stage, secs in by_stage.items() if secs})
    if compared is not None:
        line["compared"] = compared
    return line


if __name__ == "__main__":
    sys.exit(main())
