"""`canon.keys` alone on the chip, by block schedule (PERF.md section 6,
PR 47): seconds a call, us a live row, and what `jax.profiler` takes to stop
after ONE call (every device operation of the loop over the group is an
event it collects; the cell `kip279-5b-symmetry-cex` runs ~12 such calls'
worth a pass, and its `--trace 1` run loses its window when the stretch
passes ~34 s).

    chiprun -- python scripts/canon_block_bench.py
    JAX_PLATFORMS=cpu python scripts/canon_block_bench.py --lanes 20000 --live 3000,100

The job is `configs/MCKip279FiveBroker.cfg` (120 images of 56 elements in 5
lanes), the width the widest `fgd` chunk's (32,768 rows x 113 candidates).
A schedule is `<CANON_BLOCK>x<CANON_WIDE>`: `8192x1` is blocks of 8,192 and
no wide block, `8192x4` the program's.  Every schedule's keys, orbit sizes
and rows are held to the first one's.  The stage alone does NOT carry into a
level program for the per-row cost (PR 47: 15% faster alone at blocks of
16,384, 7% slower inside `fsc`): use it for the fixed cost of a block and
the profile's size, and time a change inside the cell.  Writes
`chiprun_out/canon_block_bench/bench.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import kafka_specification_tpu as kspec  # noqa: E402
from kafka_specification_tpu.ops import canon  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", type=int, default=32768 * 113)
    ap.add_argument("--live", default="330000,1000",
                    help="live lanes of each timed call; the first is traced")
    ap.add_argument("--schedules", default="8192x1,16384x1,32768x1,8192x4")
    args = ap.parse_args(argv)
    live = [int(n) for n in args.live.split(",")]
    cfg = kspec.load_config(os.path.join(ROOT, "configs",
                                         "MCKip279FiveBroker.cfg"))
    model = kspec.build_model("MCKip279", cfg)
    T, K = args.lanes, model.spec.num_lanes
    rng = np.random.default_rng(7)
    cand = jax.device_put(
        rng.integers(0, 2**32, size=(T, K), dtype=np.uint32))
    valids = {}
    for n in live:
        v = np.zeros(T, bool)
        v[rng.choice(T, size=n, replace=False)] = True
        valids[n] = jax.device_put(v)
    rows, first = [], {}
    for schedule in args.schedules.split(","):
        block, wide = (int(x) for x in schedule.split("x"))
        canon.CANON_BLOCK, canon.CANON_WIDE = block, wide
        keys = jax.jit(canon.Canon(model.spec, model.symmetry).keys)
        row = {"schedule": schedule, "block": canon.canon_block(T),
               "wide_block": canon.canon_wide_block(T)}
        for n, valid in valids.items():
            jax.block_until_ready(keys(cand, valid))  # compiles
            secs = []
            for _ in range(5):
                t0 = time.perf_counter()
                out = jax.block_until_ready(keys(cand, valid))
                secs.append(time.perf_counter() - t0)
            got = [np.asarray(x) for x in out[:3]]
            row[f"ms_{n}"] = min(secs) * 1e3
            row[f"rows_{n}"] = int(out[3])
            row[f"same_{n}"] = all(
                np.array_equal(a, b) for a, b in zip(first.setdefault(n, got),
                                                     got))
        row["us_per_live_row"] = row[f"ms_{live[0]}"] * 1e3 / live[0]
        trace_dir = tempfile.mkdtemp()
        opts = jax.profiler.ProfileOptions()  # perfbench/run.py's options
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        jax.block_until_ready(keys(cand, valids[live[0]]))
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        row["profiler_stop_s"] = time.perf_counter() - t0
        row["trace_bytes"] = sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, fs in os.walk(trace_dir) for f in fs)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(row), flush=True)
        rows.append(row)
    out_dir = os.path.join(ROOT, "chiprun_out", "canon_block_bench")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench.json"), "w") as fh:
        json.dump({"device": str(jax.devices()[0]), "lanes": T,
                   "rows": rows}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
