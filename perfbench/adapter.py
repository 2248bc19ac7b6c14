"""The benchmark's only window into the program under test.

Every import from `kafka_specification_tpu` sits in this file (PERF.md lists
it as the surface a refactor has to keep).  The rest of the benchmark sees
plain Python values: dicts, lists and numbers.

What is taken from the program:

  utils.platform_guard.enable_compile_cache   the one compile-cache rule
  utils.cfg.parse_cfg, build_model            the model, as `cli check` builds it
  engine.bfs.check, prepare                   the single-device engine
  parallel.sharded.check_sharded              the sharded engine
  obs.RunContext                              the run directory `cli check` opens
  CheckResult.{levels,total,diameter,violation,stats}
  <run dir>/manifest.json, spans.jsonl        the documented records
"""

import json
import os
import time

from kafka_specification_tpu.obs import RunContext
from kafka_specification_tpu.utils.cfg import build_model, parse_cfg
from kafka_specification_tpu.utils.platform_guard import enable_compile_cache

# events that mean the recovery ladder, not the main path, gave the answer
DEGRADE_EVENTS = ("pipeline-fallback", "compile-fallback", "chunk-degrade",
                  "retry")


def init_compile_cache():
    """Route JAX's persistent cache exactly as `cli check` does
    (`utils/cli.py::_init_platform`)."""
    enable_compile_cache(min_compile_secs=0)


class Job:
    """One configuration, built once in set-up as `cli check` builds it."""

    def __init__(self, config: dict, root: str):
        self.config = config
        self.engine = config["engine"]
        module = config["module"]
        self.tlc_cfg = parse_cfg(os.path.join(root, config["cfg"]))
        # pinned by the configuration file, not by what lies beside the
        # checkout: `cli check` takes the emitted kernels where a reference
        # checkout holds the module, and two machines must build the same
        self.kernel_source = config["kernel_source"]
        if self.kernel_source not in ("hand", "emitted"):
            raise ValueError(f"unknown kernel_source {self.kernel_source!r}")
        self.model = build_model(module, self.tlc_cfg,
                                 emitted=self.kernel_source == "emitted")
        self.lanes = int(self.model.spec.num_lanes)
        self.prepared = None
        self._last_result = None
        if self.engine == "single":
            from kafka_specification_tpu.engine.bfs import prepare

            self.prepared = prepare(self.model)
        elif self.engine != "sharded":
            raise ValueError(f"unknown engine {self.engine!r}")

    def oracle_model(self):
        """The plain reference of the same constants (oracle twin)."""
        return build_model(self.config["module"], self.tlc_cfg, oracle=True)

    def run_pass(self, run_dir: str, options: dict) -> dict:
        """One whole check, call to verdict in hand, in a fresh run
        directory.  `options` are engine keywords (configuration options
        merged with the traffic's).  Returns plain records."""
        t0 = time.perf_counter()
        t0_unix = time.time()
        run = RunContext(run_dir)
        run.record_config(module=self.config["module"],
                          cfg=self.config["cfg"],
                          sharded=self.engine == "sharded")
        kw = dict(options)
        kw["check_deadlock"] = self.tlc_cfg.check_deadlock
        kw["run"] = run
        if self.engine == "single":
            from kafka_specification_tpu.engine.bfs import check

            # the serving daemon's warm protocol (service/daemon.py): the
            # prepared kernels and the last run's final visited capacity
            res = check(self.model, prepared=self.prepared,
                        visited_capacity_exact=self.prepared.capacity_hint,
                        **kw)
        else:
            from kafka_specification_tpu.parallel.sharded import check_sharded

            res = check_sharded(self.model, **kw)
        self._last_result = res
        violation = None
        if res.violation is not None:
            # the verdict is in hand once the counterexample is rendered
            from kafka_specification_tpu.utils.pretty import render_trace

            text = render_trace(self.model.meta, res.violation.trace)
            violation = {"invariant": res.violation.invariant,
                         "depth": res.violation.depth,
                         "trace_len": len(res.violation.trace),
                         "rendered_chars": len(text)}
        wall_s = time.perf_counter() - t0
        stats = res.stats or {}
        return {
            "wall_s": wall_s,
            "t0_unix": t0_unix,
            "levels": [int(n) for n in res.levels],
            "total": int(res.total),
            "diameter": int(res.diameter),
            "violation": violation,
            "level_records": [
                {k: v for k, v in rec.items() if k != "action_enablement"}
                for rec in stats.get("levels", [])
            ],
            "stats": {k: v for k, v in stats.items()
                      if k not in ("levels", "mesh_layouts")},
            "manifest": _read_json(os.path.join(run_dir, "manifest.json")),
            "spans": _read_spans(os.path.join(run_dir, "spans.jsonl")),
        }

    def after_setup_pass(self) -> int:
        """Between set-up passes: what the daemon does after a job — feed
        the final visited capacity back (`note_result`) and compile the
        step variants the growth ladder evicted (`rewarm`).  Returns the
        variants compiled."""
        if self.prepared is None or self._last_result is None:
            return 0
        self.prepared.note_result(self._last_result)
        return int(self.prepared.rewarm())


def _read_json(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return {}


def _read_spans(path):
    """spans.jsonl reduced to what the benchmark reads: every completed
    span as (kind, t0 unix, seconds, depth) and every event kind."""
    spans, events = [], []
    try:
        fh = open(path)
    except OSError:
        return {"spans": spans, "events": events}
    with fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # a torn last line
            if rec.get("kind") == "event":
                events.append(rec.get("event"))
            elif rec.get("kind") == "span" and rec.get("ph") == "E":
                spans.append([rec.get("span"), rec.get("t0"),
                              rec.get("ms", 0.0) / 1e3, rec.get("depth")])
    return {"spans": spans, "events": events}
