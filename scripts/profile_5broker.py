"""Per-stage profile of the 5-broker (stretch base factor) BFS step.

The 100M+-state regime is expand-bound (~36-45k states/sec/core on the
host-FpSet backend — RESULTS.md), and the round-3 dedup rewrites barely
move it.  This script maps where those cycles go: it grows a real deep
frontier (bounded BFS to a target depth), then times each stage of the
host-backend level step — guard sweep, per-action compacted
gather+kernel+pack, squeeze, fingerprint — plus the C++ FpSet insert, at
several compact shifts, and prints per-level throughput for whole-step
comparisons.  Output is a JSON-lines stream suitable for committing next
to RESULTS.md.

Usage: python scripts/profile_5broker.py [depth=8] [chunk=131072]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from kafka_specification_tpu.utils.platform_guard import (  # noqa: E402
    enable_compile_cache,
    pin_cpu_in_process,
)

pin_cpu_in_process()
import jax  # noqa: E402

enable_compile_cache()
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kafka_specification_tpu.engine import check  # noqa: E402
from kafka_specification_tpu.engine.bfs import _Step, _next_pow2, _pad_rows  # noqa: E402
from kafka_specification_tpu.models import kip320  # noqa: E402
from kafka_specification_tpu.models.kafka_replication import Config  # noqa: E402
from kafka_specification_tpu.native import FpSet  # noqa: E402

DEPTH = int(sys.argv[1]) if len(sys.argv) > 1 else 8
CHUNK = int(sys.argv[2]) if len(sys.argv) > 2 else 131072


def timeit(fn, *args, n=3):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    model = kip320.make_model(Config(5, 2, 2, 2))
    sb = _Step(model)
    spec = model.spec
    K, C = spec.num_lanes, sb.C
    print(
        json.dumps(
            {
                "workload": "Kip320 5r L2 R2 E2 (stretch base factor)",
                "lanes": K,
                "fanout": C,
                "exact64": bool(spec.exact64),
                "actions": [[a.name, a.n_choices] for a in model.actions],
            }
        ),
        flush=True,
    )

    levels = []
    t0 = time.perf_counter()
    res = check(
        model,
        max_depth=DEPTH,
        store_trace=False,
        collect_levels=levels,
        visited_backend="host",
        chunk_size=CHUNK,
        min_bucket=8192,
    )
    print(
        json.dumps(
            {
                "frontier_depth": DEPTH,
                "total_states": res.total,
                "frontier_rows": int(levels[-1].shape[0]),
                "grow_seconds": round(time.perf_counter() - t0, 1),
            }
        ),
        flush=True,
    )

    frontier = levels[-1]
    piece = frontier[:CHUNK]
    fp_n = piece.shape[0]
    bucket = _next_pow2(max(fp_n, 8192))
    fr = jnp.asarray(_pad_rows(piece, bucket))
    fv = jnp.arange(bucket) < fp_n
    vcap = 64
    vhi = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
    vlo = jnp.full(vcap, 0xFFFFFFFF, jnp.uint32)
    vn = jnp.int32(0)

    unpack = jax.jit(lambda f: jax.vmap(spec.unpack)(f))
    states = unpack(fr)
    t_unpack = timeit(unpack, fr)

    # adaptive per-action widths (what the engine converges to): exact
    # per-action enablement from a full-lattice sweep, then the same
    # 1.35x/pow2 sizing check()'s widths_for applies
    step0 = sb.get(bucket, vcap, True, with_merge=False, compact=None)
    out0 = step0(fr, fv, vhi, vlo, vn)
    act_en0 = np.asarray(out0[11], np.int64)
    # size from PRE-constraint guard counts (out[15]) exactly as check()'s
    # widths_for does — act_en undercounts on constraint-pruning models
    act_guard0 = np.asarray(out0[15], np.int64)
    hw0 = act_guard0 / fp_n
    widths = tuple(
        min(
            _next_pow2(max(256, int(1.35 * h * bucket) + 1)),
            bucket * a.n_choices,
        )
        for a, h in zip(model.actions, hw0)
    )
    print(
        json.dumps(
            {
                "adaptive_widths": list(widths),
                "per_action_enabled": {
                    a.name: int(e) for a, e in zip(model.actions, act_en0)
                },
            }
        ),
        flush=True,
    )

    # stage timings: adaptive widths vs each uniform compact shift
    for shift in (widths, 2, 3, 4):
        expand = sb.make_expand(bucket, shift)
        T_exp = sb.expand_width(bucket, shift)
        # mirror _Step._build: per-action widths run with T = T_exp (no
        # pre-sort width reduction); uniform shifts squeeze to half
        T = T_exp if isinstance(shift, tuple) else max(256, T_exp >> 1)

        exp_j = jax.jit(expand)
        t_expand = timeit(exp_j, states, fv)
        en_pre, cand, valid, parent, actid, act_en, act_guard, ovf = exp_j(states, fv)

        def guards_only(states):
            parts = []
            for a in model.actions:
                choices = jnp.arange(a.n_choices, dtype=jnp.int32)
                ok = jax.vmap(
                    lambda s: jax.vmap(lambda c, s=s, a=a: a.kernel(s, c)[0])(
                        choices
                    )
                )(states)
                parts.append(ok)
            return jnp.concatenate(parts, axis=1)

        t_guards = timeit(jax.jit(guards_only), states)

        # full host-backend step (squeeze+fingerprint included) for the
        # whole-step number the engine actually runs
        step = sb.get(bucket, vcap, True, with_merge=False, compact=shift)
        t_step = timeit(step, fr, fv, vhi, vlo, vn)
        out = step(fr, fv, vhi, vlo, vn)
        n_en = int(out[3])
        out_hi, out_lo = np.asarray(out[12][:n_en]), np.asarray(out[13][:n_en])

        fps = (out_hi.astype(np.uint64) << np.uint64(32)) | out_lo.astype(
            np.uint64
        )
        hs = FpSet()
        t_ins0 = time.perf_counter()
        hs.insert(fps)
        t_insert = time.perf_counter() - t_ins0

        print(
            json.dumps(
                {
                    "shift": "adaptive" if isinstance(shift, tuple) else shift,
                    "bucket": bucket,
                    "lattice": bucket * C,
                    "compact_rows": T_exp,
                    "squeeze_rows": T,
                    "enabled": n_en,
                    "overflow": bool(np.asarray(out[14]).any()),
                    "ms_unpack": round(t_unpack * 1e3, 1),
                    "ms_guard_sweep": round(t_guards * 1e3, 1),
                    "ms_expand_two_phase": round(t_expand * 1e3, 1),
                    "ms_full_step": round(t_step * 1e3, 1),
                    "ms_host_insert": round(t_insert * 1e3, 1),
                    "step_states_per_sec": round(fp_n / t_step, 1),
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
