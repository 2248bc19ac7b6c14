"""Closed-form product-space validation run (VERDICT r2 item 4).

Kip320 TINY (2 brokers, L=2, R=1, E=1) has exactly 277 reachable states
(oracle-pinned).  Three independent partitions interleaved
(models/product.py) must reach exactly 277^3 = 21,253,933 distinct states —
a golden count for the product combinator, the host-FpSet spill path and
the |base|^K claim (BASELINE.json stretch definition) at a scale this box
reaches in minutes.  Appends the result to RESULTS.md by hand afterwards.

Usage:  python scripts/run_product_tiny3.py [--partitions K]
"""

import argparse
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
from kafka_specification_tpu.models.kafka_replication import Config  # noqa: E402
from kafka_specification_tpu.models.product import (  # noqa: E402
    product_model,
    product_models,
)
from kafka_specification_tpu.oracle.interp import oracle_bfs  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--partitions", type=int, default=3)
    ap.add_argument("--chunk-size", type=int, default=131072)
    ap.add_argument(
        "--mem-budget",
        default=os.environ.get("KSPEC_PROD_MEMBUDGET"),
        help="host fingerprint-set byte budget (K/M/G suffixes) before "
        "spilling to the disk tier — lets the prod464 preset (and the "
        "next decade) run out-of-core (docs/storage.md); also settable "
        "via KSPEC_PROD_MEMBUDGET for the supervisor preset",
    )
    ap.add_argument(
        "--spill-dir",
        default=os.environ.get("KSPEC_PROD_SPILL"),
        help="disk-tier directory (default: <checkpoint>/spill); also "
        "settable via KSPEC_PROD_SPILL",
    )
    ap.add_argument(
        "--base",
        choices=["tiny", "2r", "mixed", "mixed107", "mixed464"],
        default="tiny",
        help="base factor: tiny = Kip320 (2r,L2,R1,E1) = 277 states; "
        "2r = Kip320 (2r,L2,R2,E2) = 5,973 states (5,973^2 = 35,676,729 "
        "— the next closed-form decade, VERDICT r3 item 6); "
        "mixed = tiny^2 x 2r (heterogeneous partitions, "
        "277^2 x 5,973 = 458,302,317 — the half-billion exact product, "
        "round-5 verdict item 5; --partitions is ignored); "
        "mixed107 = 2r^2 x IdSequence(MaxId=1) "
        "(5,973^2 x 3 = 107,030,187 — a mixed-base decade past the "
        "round-4 35.7M, sized to land inside a round; TypeOk only, the "
        "partitions must agree on invariant names); "
        "mixed464 = 2r^2 x IdSequence(MaxId=11) "
        "(5,973^2 x 13 = 463,797,477 — the half-billion exact product in "
        "the kernel shape the 107M run proved sustains ~20k states/sec; "
        "the tiny^2 x 2r shape degraded to ~9k/s and cannot finish in a "
        "round from scratch on this box)",
    )
    args = ap.parse_args()

    if args.base in ("mixed107", "mixed464"):
        from kafka_specification_tpu.models import id_sequence
        max_id = 1 if args.base == "mixed107" else 11
        chain = max_id + 2
        cfg_2r = Config(2, 2, 2, 2)
        tot_2r = oracle_bfs(kip320.make_oracle(cfg_2r), keep_level_sets=False).total
        print(
            f"# base Kip320 2r: {tot_2r} states (oracle); "
            f"IdSequence({max_id}): {chain}",
            flush=True,
        )
        model = product_models(
            [
                kip320.make_model(cfg_2r, invariants=("TypeOk",)),
                kip320.make_model(cfg_2r, invariants=("TypeOk",)),
                id_sequence.make_model(max_id),
            ],
            name=f"Kip320 2r^2 x IdSeq{max_id} (mixed product)",
        )
        golden = tot_2r * tot_2r * chain
        workload = (
            f"Kip320 2r^2 x IdSequence({max_id}) mixed product exhaustive"
        )
    elif args.base == "mixed":
        # heterogeneous partitions: two TINY factors and one 2r factor
        # (product_models) — closed form |tiny|^2 * |2r|
        cfg_t, cfg_2r = Config(2, 2, 1, 1), Config(2, 2, 2, 2)
        tot_t = oracle_bfs(kip320.make_oracle(cfg_t), keep_level_sets=False).total
        tot_2r = oracle_bfs(kip320.make_oracle(cfg_2r), keep_level_sets=False).total
        print(f"# bases: tiny={tot_t}, 2r={tot_2r} (oracle)", flush=True)
        model = product_models(
            [
                kip320.make_model(cfg_t),
                kip320.make_model(cfg_t),
                kip320.make_model(cfg_2r),
            ],
            name="Kip320 tiny^2 x 2r (mixed product)",
        )
        golden = tot_t * tot_t * tot_2r
        workload = "Kip320 tiny^2 x 2r mixed product exhaustive"
    else:
        base_cfg = Config(2, 2, 1, 1) if args.base == "tiny" else Config(2, 2, 2, 2)
        base_total = oracle_bfs(
            kip320.make_oracle(base_cfg), keep_level_sets=False
        ).total
        print(f"# base Kip320 {args.base}: {base_total} states (oracle)", flush=True)

        model = product_model(kip320.make_model(base_cfg), args.partitions)
        golden = base_total ** args.partitions
        workload = f"Kip320 {args.base.upper()} ^{args.partitions} product exhaustive"
    print(
        f"# product: expect {golden:,} distinct states; "
        f"fanout={model.total_fanout}, lanes={model.spec.num_lanes}",
        flush=True,
    )

    t0 = time.perf_counter()
    res = check(
        model,
        store_trace=False,
        visited_backend="host",
        chunk_size=args.chunk_size,
        min_bucket=4096,
        mem_budget=args.mem_budget or None,
        spill_dir=args.spill_dir or None,
        checkpoint_dir=os.environ.get("KSPEC_PROD_CKPT") or None,
        checkpoint_every=2,
        # per-level heartbeat stream for the supervisor's stall detector
        # (scripts/resilient_run.py --preset prod464 sets this)
        stats_path=os.environ.get("KSPEC_PROD_STATS") or None,
        compact_shift=int(os.environ.get("KSPEC_PROD_SHIFT") or 2),
        progress=lambda d, n, t: print(
            f"#   level {d}: +{n:,} -> {t:,} ({time.perf_counter()-t0:.0f}s)",
            flush=True,
        ),
    )
    print(
        json.dumps(
            {
                "workload": workload,
                "distinct_states": res.total,
                "expected": golden,
                "match": res.total == golden,
                "ok": res.ok,
                "diameter": res.diameter,
                "seconds": round(res.seconds, 1),
                "states_per_sec": round(res.states_per_sec, 1),
            }
        ),
        flush=True,
    )
    assert res.ok
    assert res.total == golden, (res.total, golden)


if __name__ == "__main__":
    main()
