#!/usr/bin/env python3
"""Why does host code run slower in a process that has not profiled yet?

    chiprun -- python3 scripts/host_loop_diag.py      (about three minutes)

PR 46 found that the same native loop (`native.rows_digest` over the
product's last frontier) costs 119-128 ms in a `--trace 0` process of the
benchmark and 42 ms in the window passes that follow a `jax.profiler`
session, and that every pass of both trees is 0.14 s shorter after one
(PERF.md section 7, D 8; ROADMAP T 7).  This times one fixed host loop (the
native pass, the blocked numpy twin, a 64 MB copy) before JAX starts, after
its start, after device work and transfers, after a profile session and
after more device work, and lists the threads that burn CPU while the main
thread sleeps.  Written for PR 46; no chip came to run it.  On the CPU it
only shows that it runs."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
from kafka_specification_tpu import native
from kafka_specification_tpu.resilience import integrity as I

rows = np.random.default_rng(0).integers(0, 2**32, size=(1075905, 15), dtype=np.uint32)
out = {}

def timeit(tag):
    ts = []
    for _ in range(5):
        t = time.perf_counter(); native.rows_digest(rows, 0x9747B28C, 0x3C6EF372, False)
        ts.append(time.perf_counter() - t)
    tb = []
    for _ in range(3):
        t = time.perf_counter(); I._hashed_blocks(rows, False); tb.append(time.perf_counter() - t)
    tc = []
    for _ in range(3):
        t = time.perf_counter(); rows.copy(); tc.append(time.perf_counter() - t)
    out[tag] = {"native_ms": [round(1e3 * x, 1) for x in ts], "blocks_ms": [round(1e3 * x, 1) for x in tb],
                "copy64MB_ms": [round(1e3 * x, 1) for x in tc], "threads": len(os.listdir("/proc/self/task")),
                "affinity": len(os.sched_getaffinity(0)), "spinners": spinners()}
    print(tag, json.dumps(out[tag]), flush=True)

def spinners():
    """threads that burn cpu while the main thread sleeps 0.5 s: (tid, name, cpu seconds)"""
    def snap():
        d = {}
        for tid in os.listdir("/proc/self/task"):
            try:
                with open(f"/proc/self/task/{tid}/stat") as fh:
                    s = fh.read()
                name = s[s.index("(") + 1:s.rindex(")")]
                f = s.rsplit(")", 1)[1].split()
                d[tid] = (name, (int(f[11]) + int(f[12])) / os.sysconf("SC_CLK_TCK"))
            except OSError:
                pass
        return d
    a = snap(); time.sleep(0.5); b = snap()
    return sorted(((b[t][0], round(b[t][1] - a[t][1], 2)) for t in b if t in a and b[t][1] - a[t][1] >= 0.05),
                  key=lambda x: -x[1])[:8]

print("nproc", os.cpu_count(), "loadavg", open("/proc/loadavg").read().strip(), flush=True)
timeit("a_before_jax")
import jax, jax.numpy as jnp
dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
timeit("b_after_jax_init")
x = jnp.ones((4096, 4096), jnp.float32)
f = jax.jit(lambda a: (a @ a).sum())
for _ in range(20):
    f(x).block_until_ready()
big = jnp.zeros((1075905, 15), jnp.uint32) + 7
for _ in range(3):
    np.asarray(big + 1)
timeit("c_after_device_work_and_d2h")
import tempfile
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0; opts.enable_hlo_proto = False
d = tempfile.mkdtemp()
jax.profiler.start_trace(d, profiler_options=opts)
f(x).block_until_ready()
jax.profiler.stop_trace()
timeit("d_after_profile_session")
for _ in range(20):
    f(x).block_until_ready()
np.asarray(big + 2)
timeit("e_more_device_work_after")
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "host_loop_diag")
os.makedirs(OUT, exist_ok=True)
with open(os.path.join(OUT, "diag.json"), "w") as fh:
    json.dump(out, fh, indent=1)
