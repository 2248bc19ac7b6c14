"""Emitted-vs-hand flagship profile (round-5 verdict item 4).

The CLI's default engine path is the mechanically emitted kernels; round 4
measured them at 57.7k states/sec vs 125.8k hand on the Kip320 3-broker
flagship.  This script localizes the gap: model shape (choice columns /
fanout / lane count), per-level engine throughput on each path, and the
engine stats' step/host split, so the emitter lever to pull is measured
rather than guessed.

Usage: python scripts/profile_emitted.py [--quick]
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
enable_compile_cache()

from kafka_specification_tpu.engine import check  # noqa: E402
from kafka_specification_tpu.models import kip320  # noqa: E402
from kafka_specification_tpu.models.emitted import make_emitted_model  # noqa: E402
from kafka_specification_tpu.models.kafka_replication import Config  # noqa: E402


def describe(tag, model):
    acts = model.actions
    print(
        json.dumps(
            {
                "model": tag,
                "n_actions": len(acts),
                "total_fanout_C": model.total_fanout,
                "lanes": model.spec.num_lanes,
                "choices": {a.name: a.n_choices for a in acts},
            }
        ),
        flush=True,
    )


def run(tag, model, **kw):
    kwargs = dict(
        store_trace=False,
        min_bucket=4096,
        chunk_size=32768,
        visited_capacity_hint=800_000,
        visited_backend="host",
    )
    kwargs.update(kw)
    check(model, **kwargs)  # warm
    t0 = time.perf_counter()
    res = check(model, **kwargs)
    dt = time.perf_counter() - t0
    assert res.total == 737_794, res.total
    print(
        json.dumps(
            {
                "run": tag,
                "seconds": round(dt, 2),
                "states_per_sec": round(res.states_per_sec, 1),
                "adaptive_active": res.stats.get("adaptive_active"),
            }
        ),
        flush=True,
    )
    return res


def main():
    cfg = Config(3, 2, 2, 2)
    invs = ("TypeOk", "LeaderInIsr", "WeakIsr", "StrongIsr")
    hand = kip320.make_model(cfg)
    emitted = make_emitted_model("Kip320", cfg, invariants=invs)
    describe("hand", hand)
    describe("emitted", emitted)
    if "--shape-only" in sys.argv:
        return
    run("hand", hand)
    run("emitted", emitted)


if __name__ == "__main__":
    main()
