"""Chip smoke: drive the main path once on the accelerator, through the
entry points a user calls, and hold every phase to its golden.

    python chip_smoke.py          (on a machine with a TPU; ~12 minutes cold)

Phases, each its own process, strictly one chip process alive at a time
(this parent never imports jax — a parent that touched JAX would hold the
chip its children need):

  P1  `cli check configs/Kip320.cfg` — the flagship on the defaults a user
      gets (fused pipeline, device visited backend).
  P2  `cli check configs/Kip320FiveBroker.cfg --pipeline device` — the
      headline width (5 brokers, every bound uncut) on the whole-level
      device programs.
  P3  one `cli serve` daemon answering three `cli submit --wait` jobs:
      the second encoder under a CONSTRAINT (AsyncIsr), a violation with
      its rendered counterexample (Kip101), and the flagship again.
  P4  `cli check configs/Kip320.cfg --sharded --pipeline device` on every
      device the child sees.

Broker counts and bounds are never cut; DEPTH is, because the contract
gives the whole smoke 1200 s on a cold compile cache and the chip's
compiler takes 15-40 s per level program at 3 brokers and 30-120 s at 5
(measured, PR 21: the exhaustive flagship compiles 23 programs — 552 s of
its 642 s cold).  A depth-cut run's golden total is the prefix sum of the
banked per-level counts.  The violating job runs to its violation, uncut.

Every child runs with JAX_PLATFORMS=tpu, so on a box without the chip JAX
itself refuses and the smoke exits non-zero with JAX's reason.  For every
phase the smoke asserts the golden counts and verdict, `platform == "tpu"`
in the run directory's manifest, and zero degradations, retries and
pipeline fallbacks (the recovery ladder may exist; on the chip it may not
be what produced the answer).  It stops at the first failed assertion.

Artifacts (run directories, child logs, summary.json) land under
chiprun_out/chip_smoke/.  The wall seconds in the summary are smoke
observations on a cold compile cache, not benchmark numbers.

The last stdout line is the contract object
`{"ok": true, "device": {"platform", "kind", "count"}}`.
"""

import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "chiprun_out", "chip_smoke")
_PLATFORM = "tpu"
_CLI = [sys.executable, "-m", "kafka_specification_tpu.utils.cli"]
# the contract allows 1200 s, compilation included
_BUDGET_S = 1150.0

# Goldens.  Kip320 3 brokers: the oracle-pinned exhaustive space
# (BASELINE.md; diameter 25).  Kip320 5 brokers: RUN5R_r5_stats.jsonl
# (banked to depth 15).  A depth-cut run's total is the prefix sum.
_KIP320_LEVELS = [
    1, 6, 30, 138, 366, 1170, 2715, 5673, 10836, 18648, 28818, 40629,
    53691, 66432, 77400, 84072, 85404, 78909, 66447, 49422, 32916, 19542,
    9939, 3660, 834, 96,
]
_FIVE_BROKER_LEVELS = [
    1, 10, 90, 770, 2370, 14635, 58100, 195095, 597860, 1650700, 4071215,
]
_KIP101_LEVELS = [1, 4, 14, 44, 100, 166, 268, 456, 684, 976, 1292, 1486]
_ASYNC_ISR_LEVELS = [
    1, 5, 16, 42, 92, 171, 282, 414, 535, 614, 620, 536, 390, 232, 104,
    30, 4,
]
# `cli check configs/Kip101.cfg --hand --cpu`: WeakIsr at depth 11, 12
# rendered states; sha256 of the rendered trace text
_KIP101_VIOLATION = {"invariant": "WeakIsr", "depth": 11, "trace_len": 12}
_KIP101_TRACE_SHA = (
    "f6c279d74f51a59fdc09bb951bd27bd50953ef9c15aa9bf7e25039e8718f43e9"
)
# Depth cuts, sized from the cold per-level times of the PR 21 chip runs.
# P1's depth is also the served Kip320 job's, so the daemon finds P1's
# programs in the persistent compile cache instead of compiling them again.
_P1_DEPTH = 8
_P2_DEPTH = 5  # the first level past the compact gate: one device-level program
# the first gated per-shard bucket is level 6's on one device and level 7's
# on a four-way mesh
_P4_DEPTH_ONE_DEVICE = 6
_P4_DEPTH_MESH = 7

_DEGRADE_EVENTS = {
    "pipeline-fallback", "compile-fallback", "chunk-degrade", "retry",
}


class SmokeFailure(Exception):
    pass


def _require(cond, what):
    if not cond:
        raise SmokeFailure(what)


class _Children:
    """Every process the smoke starts, so all of them can be stopped."""

    def __init__(self, deadline):
        self.deadline = deadline
        self.live = []

    def start(self, name, argv):
        out = open(os.path.join(_OUT, name + ".stdout"), "w")
        err = open(os.path.join(_OUT, name + ".stderr"), "w")
        # cwd is the checkout, so `python -m` finds the package there
        p = subprocess.Popen(
            argv, cwd=_ROOT, env={**os.environ, "JAX_PLATFORMS": _PLATFORM},
            stdout=out, stderr=err, start_new_session=True,
        )
        p.smoke_name, p.smoke_files = name, (out, err)
        self.live.append(p)
        return p

    def wait(self, p, daemon=None):
        """Wait for `p` inside the budget.  A client whose `daemon` is
        gone gets a few seconds to pick up an already-published verdict
        (the daemon exits right after its last one), then fails."""
        orphaned_at = None
        while p.poll() is None:
            now = time.monotonic()
            _require(now < self.deadline,
                     f"{p.smoke_name}: out of time budget ({_BUDGET_S}s)")
            if daemon is not None and daemon.poll() is not None:
                orphaned_at = orphaned_at or now
                _require(
                    now - orphaned_at < 10.0,
                    f"{p.smoke_name}: daemon exited rc={daemon.returncode} "
                    f"without a verdict\n"
                    f"{_tail(daemon.smoke_name + '.stderr')}")
            time.sleep(0.2)
        self.reap(p)
        return p.returncode

    def reap(self, p):
        for fh in p.smoke_files:
            fh.close()
        if p in self.live:
            self.live.remove(p)

    def stop_all(self):
        for p in list(self.live):
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
                p.wait()
            self.reap(p)


def _tail(name, n=2000):
    try:
        with open(os.path.join(_OUT, name)) as fh:
            return fh.read()[-n:]
    except OSError:
        return ""


def _verdict(name):
    """The kspec-verdict/1 object a `--json` child printed last."""
    lines = [l for l in _tail(name + ".stdout", 1 << 20).splitlines() if l]
    _require(lines, f"{name}: printed no verdict\n{_tail(name + '.stderr')}")
    try:
        return json.loads(lines[-1])
    except ValueError:
        raise SmokeFailure(f"{name}: last stdout line is no JSON: "
                           f"{lines[-1][:300]}")


def _check_counts(name, v, levels, exit_code=0):
    _require(v.get("exit_code") == exit_code,
             f"{name}: exit_code {v.get('exit_code')} != {exit_code} "
             f"({v.get('error')})")
    _require(v.get("levels") == levels,
             f"{name}: level counts {v.get('levels')} != golden {levels}")
    _require(v.get("distinct_states") == sum(levels),
             f"{name}: {v.get('distinct_states')} states != {sum(levels)}")
    _require(v.get("diameter") == len(levels) - 1,
             f"{name}: diameter {v.get('diameter')} != {len(levels) - 1}")


def _check_run_dir(name, run_dir):
    """Platform stamp + zero degradations, from the run directory's own
    records.  Returns the manifest."""
    with open(os.path.join(run_dir, "manifest.json")) as fh:
        man = json.load(fh)
    cfg = man.get("config", {})
    _require(cfg.get("platform") == _PLATFORM,
             f"{name}: manifest platform {cfg.get('platform')!r}, "
             f"not {_PLATFORM!r}")
    prom = {}
    with open(os.path.join(run_dir, "metrics.prom")) as fh:
        for line in fh:
            if line.startswith(("kspec_degradations",
                                "kspec_transient_retries_total")):
                key, val = line.rsplit(None, 1)
                prom[key.split("{")[0]] = float(val)
    _require(prom.get("kspec_degradations") == 0,
             f"{name}: kspec_degradations = "
             f"{prom.get('kspec_degradations')}")
    _require(prom.get("kspec_transient_retries_total") == 0,
             f"{name}: kspec_transient_retries_total = "
             f"{prom.get('kspec_transient_retries_total')}")
    with open(os.path.join(run_dir, "spans.jsonl")) as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            _require(rec.get("event") not in _DEGRADE_EVENTS,
                     f"{name}: degraded on the chip: {line.strip()[:400]}")
    return man


def _device(man):
    cfg = man["config"]
    return {"platform": cfg["platform"], "kind": cfg["device_kind"],
            "count": cfg["device_count"]}


def _phase_record(man, v, wall_s, **extra):
    return {**_device(man), "states": v["distinct_states"],
            "depth": len(v["levels"]) - 1, "wall_s": round(wall_s, 1),
            "engine_s": v["seconds"], **extra}


def _check_phase(ch, name, args, levels):
    run_dir = os.path.join(_OUT, name)
    t0 = time.monotonic()
    p = ch.start(name, _CLI + ["check"] + args
                 + ["--hand", "--no-trace", "--json", "--run-dir", run_dir])
    rc = ch.wait(p)
    wall = time.monotonic() - t0
    _require(rc == 0, f"{name}: exited {rc}\n{_tail(name + '.stderr')}")
    v = _verdict(name)
    _check_counts(name, v, levels)
    man = _check_run_dir(name, run_dir)
    return man, v, wall


def _p1(ch):
    man, v, wall = _check_phase(
        ch, "p1", ["configs/Kip320.cfg", "--max-depth", str(_P1_DEPTH)],
        _KIP320_LEVELS[: _P1_DEPTH + 1])
    return _phase_record(man, v, wall)


def _p2(ch):
    man, v, wall = _check_phase(
        ch, "p2",
        ["configs/Kip320FiveBroker.cfg", "--module", "Kip320",
         "--pipeline", "device", "--max-depth", str(_P2_DEPTH)],
        _FIVE_BROKER_LEVELS[: _P2_DEPTH + 1])
    dev = man.get("result", {}).get("device") or {}
    _require(dev.get("levels", 0) > 0 and dev.get("fallback") is None,
             f"p2: whole-level device programs did not carry the run: "
             f"{dev}")
    return _phase_record(man, v, wall, device_levels=dev["levels"])


def _p3(ch):
    svc = os.path.join(_OUT, "p3", "svc")
    t0 = time.monotonic()
    daemon = ch.start("p3.daemon", _CLI + ["serve", svc, "--max-jobs", "3"])
    jobs = {}
    # Kip320 last: after a run that grew its visited set the daemon
    # re-compiles that shape's steps for the next job before it takes one
    # (97 s after this job on the chip, PR 21), except on its final job
    for cfg, levels, exit_code, bound in (
        ("AsyncIsr", _ASYNC_ISR_LEVELS, 0, []),
        ("Kip101", _KIP101_LEVELS, 1, []),
        ("Kip320", _KIP320_LEVELS[: _P1_DEPTH + 1], 0,
         ["--max-depth", str(_P1_DEPTH)]),
    ):
        name = f"p3.{cfg}"
        t1 = time.monotonic()
        p = ch.start(name, _CLI + [
            "submit", f"configs/{cfg}.cfg", "--service-dir", svc, "--hand",
            "--wait", "--json", "--timeout", str(int(_BUDGET_S))] + bound)
        rc = ch.wait(p, daemon=daemon)
        wall = time.monotonic() - t1
        # judged on the verdict's own exit_code, which `submit --wait`
        # also exits with
        _require(rc == exit_code,
                 f"{name}: submit exited {rc}\n{_tail(name + '.stderr')}"
                 f"\n{_tail('p3.daemon.stderr')}")
        v = _verdict(name)
        _check_counts(name, v, levels, exit_code=exit_code)
        run_dir = os.path.join(svc, "runs", v["job_id"])
        man = _check_run_dir(name, run_dir)
        jobs[cfg] = _phase_record(man, v, wall)
        if exit_code == 1:
            _require(v["violation"] == _KIP101_VIOLATION,
                     f"{name}: violation {v['violation']}")
            with open(os.path.join(run_dir, "counterexample.txt"),
                      "rb") as fh:
                sha = hashlib.sha256(fh.read()).hexdigest()
            _require(sha == _KIP101_TRACE_SHA,
                     f"{name}: rendered counterexample differs from the "
                     f"--cpu golden (sha256 {sha})")
    # the daemon must be gone before the next chip process starts
    ch.wait(daemon)
    starts = []
    with open(os.path.join(svc, "service", "events.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("event") == "daemon-start":
                starts.append(rec)
    _require(len(starts) == 1 and starts[0].get("platform") == _PLATFORM,
             f"p3: daemon-start events {starts}")
    return {"jobs": jobs, "wall_s": round(time.monotonic() - t0, 1),
            "daemon_rc": daemon.returncode}


def _p4(ch, device_count):
    depth = _P4_DEPTH_ONE_DEVICE if device_count == 1 else _P4_DEPTH_MESH
    man, v, wall = _check_phase(
        ch, "p4",
        ["configs/Kip320.cfg", "--sharded", "--pipeline", "device",
         "--max-depth", str(depth)],
        _KIP320_LEVELS[: depth + 1])
    cfg = man["config"]
    _require(cfg.get("devices") == cfg["device_count"],
             f"p4: mesh of {cfg.get('devices')} devices, JAX sees "
             f"{cfg['device_count']}")
    dev = man.get("result", {}).get("device") or {}
    _require(dev.get("levels", 0) > 0 and dev.get("fallback") is None,
             f"p4: sharded whole-level programs did not carry the run: "
             f"{dev}")
    return _phase_record(man, v, wall, mesh_devices=cfg["devices"],
                         device_levels=dev["levels"])


def main():
    if not os.path.isdir(os.path.join(_ROOT, "kafka_specification_tpu")):
        print("chip_smoke: no kafka_specification_tpu package beside this "
              "script — run it from a checkout", file=sys.stderr)
        return 2
    shutil.rmtree(_OUT, ignore_errors=True)  # a reopened run dir resumes
    os.makedirs(os.path.join(_OUT, "p3"))
    ch = _Children(time.monotonic() + _BUDGET_S)
    phases = {}
    t0 = time.monotonic()
    try:
        for name, fn in (
            ("p1", _p1), ("p2", _p2), ("p3", _p3),
            ("p4", lambda ch: _p4(ch, phases["p1"]["count"])),
        ):
            phases[name] = fn(ch)
            print(f"# {name} ok: {json.dumps(phases[name])}", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        ch.stop_all()
    devices = [phases[p] for p in ("p1", "p2", "p4")] + list(
        phases["p3"]["jobs"].values())
    device = {k: devices[0][k] for k in ("platform", "kind", "count")}
    if any({k: d[k] for k in device} != device for d in devices):
        print(f"chip_smoke FAILED: phases disagree on the device: "
              f"{devices}", file=sys.stderr)
        return 1
    summary = {
        "note": "smoke observations, not benchmark numbers: wall_s is "
                "dominated by compiles on a cold cache; every phase but the "
                "violating job is cut in depth (its states are the prefix "
                "sum of the golden level counts), never in brokers or bounds",
        "device": device,
        "total_wall_s": round(time.monotonic() - t0, 1),
        "phases": phases,
    }
    with open(os.path.join(_OUT, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
