#!/usr/bin/env python3
"""Self-check of the exchange layer's arithmetic and of its four readers.
Needs no chip.

    python3 perfbench/selfcheck_exchange.py

Holds `exchange.bytes_leaving_one_chip` and `exchange.ici_floor_seconds` to
hand-worked numbers, and the readers `exchange_bytes_per_state`,
`collective_share`, `shard_imbalance` and `exchange_ici_share` to a
hand-worked pass: what each reads, that each returns nothing (and does not
raise) where the program has no such record, as the parent commit has not,
and that the roofline share of a physically possible trace stays under 100%.

Not under tests/: tier-1's count does not move with the benchmark.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import exchange  # noqa: E402
import roofline  # noqa: E402
import run as harness  # noqa: E402

FAILURES = []
NAMES = ("exchange_bytes_per_state", "collective_share", "shard_imbalance",
         "exchange_ici_share")


def check(cond, what):
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        FAILURES.append(what)


def close(a, b, tol=1e-12):
    return a is not None and abs(a - b) <= tol


def check_bytes():
    # 4 shards, 1.6e9 bytes counted over all 16 buckets: 4e8 are one
    # chip's, 3 of its 4 buckets leave it: 3e8 bytes; at 1,600 Gbit/s
    # (2e11 bytes/s) that is 1.5 ms
    check(exchange.bytes_leaving_one_chip(1.6e9, 4) == 3e8,
          "bytes: 1.6e9 counted on 4 shards, 3e8 leave one chip")
    check(close(exchange.ici_floor_seconds(1.6e9, 4, 1600e9), 1.5e-3),
          "bytes: 3e8 bytes at 1,600 Gbit/s are 1.5 ms")
    check(exchange.bytes_leaving_one_chip(1e6, 1) == 0,
          "bytes: one shard keeps everything, nothing leaves")
    check(exchange.bytes_leaving_one_chip(0, 4) == 0,
          "bytes: an all_gather run counts no bytes")
    for bad in ((-1, 4), (1, 0)):
        try:
            exchange.bytes_leaving_one_chip(*bad)
            check(False, f"bytes: {bad} is refused")
        except ValueError:
            check(True, f"bytes: {bad} is refused")
    check(roofline.peaks("TPU v5 lite")["ici_bits_per_s"] == 1600e9,
          "peaks: TPU v5 lite's interconnect is 1,600 Gbit/s")


def a_pass(total_bytes, states, shard_new_by_level):
    return {"total": states,
            "manifest": {"result": {"exchange_bytes_total": total_bytes}},
            "level_records": [{"shard_new": s} for s in shard_new_by_level]}


def check_readers():
    readers = harness.load_metric_readers()
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        check(name in readers and name in entries
              and entries[name].get("workloads") == ["kip320-5b-x4"]
              and entries[name]["layer"] == "exchange"
              and entries[name]["moves"] == "states_per_s",
              f"reader {name}: listed for the four-chip cell, layer exchange")
    # three passes of 1,000 states: 640,000 / 650,000 / 900,000 bytes
    passes = [a_pass(640000, 1000, [[100, 110], [140, 150]]),
              a_pass(650000, 1000, [[125, 125], [125, 125]]),
              a_pass(900000, 1000, [[0, 250], [0, 250]])]
    ctx = {"passes": passes, "traced": passes[0], "chips": 4,
           "peaks": {"ici_bits_per_s": 1600e9},
           "trace": {"collective_s_mean": 0.006, "busy_s_mean": 0.6}}
    check(close(readers["exchange_bytes_per_state"].read(ctx), 650.0),
          "reader exchange_bytes_per_state: median of 640, 650, 900 B")
    # per shard 240 / 260 of 500 (1.04), 250 / 250 (1.0), 0 / 500 (2.0)
    check(close(readers["shard_imbalance"].read(ctx), 1.04),
          "reader shard_imbalance: fullest shard over the mean, median 1.04")
    check(close(readers["collective_share"].read(ctx), 1.0),
          "reader collective_share: 6 ms of 600 ms busy is 1%")
    # 640,000 * 3/16 = 120,000 bytes at 2e11 bytes/s = 0.6 us of 6 ms
    check(close(readers["exchange_ici_share"].read(ctx), 0.01),
          "reader exchange_ici_share: 0.6 us of floor in 6 ms is 0.01%")
    # a trace at the physical limit: the collective took exactly the floor
    at_peak = dict(ctx, trace={"collective_s_mean": 0.6e-6,
                               "busy_s_mean": 0.6})
    check(close(readers["exchange_ici_share"].read(at_peak), 100.0, 1e-9),
          "reader exchange_ici_share: a collective at the links' peak is "
          "100%, and no slower one can read more")
    # a program with none of these records (the parent commit), a run with
    # no trace, an all_gather run: nothing to read, and no exception
    bare = {"total": 1000, "manifest": {}, "level_records": [{"new": 5}]}
    empty = {"passes": [bare], "traced": bare, "trace": None, "chips": 4,
             "peaks": {"ici_bits_per_s": 1600e9}}
    for name in NAMES:
        try:
            got = readers[name].read(empty)
        except Exception as e:  # noqa: BLE001 — the point of the check
            got = e
        check(got is None, f"reader {name}: nothing to read gives nothing")
    gather = dict(ctx, traced=a_pass(0, 1000, [[1, 1]]))
    check(readers["exchange_ici_share"].read(gather) is None,
          "reader exchange_ici_share: an all_gather run (no bytes counted) "
          "gives nothing")
    no_coll = dict(ctx, trace={"collective_s_mean": 0.0, "busy_s_mean": 0.6})
    check(readers["exchange_ici_share"].read(no_coll) is None
          and readers["collective_share"].read(no_coll) == 0.0,
          "a trace with no collective: no roofline share, 0% collective")


def main():
    check_bytes()
    check_readers()
    print(f"{len(FAILURES)} failed")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
