"""Sharded adaptive-vs-uniform compact measurement (round-5 verdict item 2).

Runs the mesh-sharded engine on the dense 5-broker Kip320 base factor
(the expand-bound regime of docs/PROFILE_5R.md), bounded to a fixed
depth, once with the shared adaptive sizing policy enabled (default) and
once pinned to the legacy uniform shift (KSPEC_ADAPTIVE_COMPACT=0), on
an 8-virtual-device CPU mesh.  Counts must match exactly; the comparison
is wall clock.  On one physical core the virtual devices serialize, so
the measured ratio understates a real pod's win (each shard's overflow
retry serializes too) — the number still answers "does the port help or
hurt on the dense regime".

Usage: python scripts/profile_sharded_adaptive.py [depth=9]
"""

import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from kafka_specification_tpu.utils.platform_guard import (  # noqa: E402
    enable_compile_cache,
    pin_cpu_in_process,
)

pin_cpu_in_process()
enable_compile_cache()

from kafka_specification_tpu.models import kip320  # noqa: E402
from kafka_specification_tpu.models.kafka_replication import Config  # noqa: E402
from kafka_specification_tpu.parallel.sharded import check_sharded  # noqa: E402

DEPTH = int(sys.argv[1]) if len(sys.argv) > 1 else 9


def run(tag, adaptive):
    os.environ["KSPEC_ADAPTIVE_COMPACT"] = "1" if adaptive else "0"
    model = kip320.make_model(Config(5, 2, 2, 2))
    t0 = time.perf_counter()
    res = check_sharded(
        model,
        max_depth=DEPTH,
        store_trace=False,
        min_bucket=8192,
        chunk_size=16384,
        visited_backend="host",
        compact_shift=2,
    )
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "run": tag,
                "depth": DEPTH,
                "total": res.total,
                "seconds": round(dt, 1),
                "states_per_sec": round(res.total / dt, 1),
                "adaptive_active": res.stats.get("adaptive_active"),
                "devices": res.stats.get("devices"),
            }
        ),
        flush=True,
    )
    return res


def main():
    ra = run("adaptive", True)
    ru = run("uniform", False)
    assert ra.total == ru.total, (ra.total, ru.total)
    print(
        json.dumps(
            {
                "match": True,
                "ratio_adaptive_over_uniform": round(
                    (ra.total / ra.seconds) / (ru.total / ru.seconds), 3
                ),
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
