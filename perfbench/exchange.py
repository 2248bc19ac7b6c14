"""What the sharded engine's exchange puts on the wire, per chip.

The engine's manifest carries `result.exchange_bytes_total`: for every
committed chunk of an all_to_all run, D x D per-destination buckets at their
padded widths (D = shards; `parallel/sharded.py`, "exchange wire
accounting").  Each of the D shards fills D buckets and keeps the one it owns
itself, so D - 1 of a shard's D buckets leave its chip:

    bytes leaving one chip = total * (D - 1) / D / D

A floor, not what the links carried: chunks of a discarded dispatch moved
bytes too and are not in the total, nor are the small all_gathers that elect
a verdict inside the level program.  An all_gather run records no bytes
(total 0): there is nothing to read then.
"""


def bytes_leaving_one_chip(exchange_bytes_total, shards):
    if exchange_bytes_total < 0 or shards < 1:
        raise ValueError("a byte total is non-negative, shards at least 1")
    return exchange_bytes_total * (shards - 1) / shards / shards


def ici_floor_seconds(exchange_bytes_total, shards, ici_bits_per_s):
    """Seconds one chip's links need for its share of the exchange at the
    published peak of ALL its links."""
    return bytes_leaving_one_chip(exchange_bytes_total, shards) / (
        ici_bits_per_s / 8.0)
