from metriclib import median_over_passes

META = {
    "name": "exchange_bytes_per_state", "unit": "B", "better": "lower",
    "source": "program_counter", "layer": "exchange",
    "moves": "states_per_s",
    "what": "manifest result.exchange_bytes_total over the pass's distinct "
            "states, median over passes.  The total counts, for every "
            "committed chunk, all D x D per-destination buckets of all "
            "shards at their PADDED widths (compressed: packed fingerprint "
            "stream + header + half-width rows, parents and u8 action ids), "
            "the bucket a shard keeps for itself included; chunks of a "
            "discarded dispatch are not counted; an all_gather run counts 0",
}


def read(ctx):
    def one(p):
        total = (p["manifest"].get("result") or {}).get("exchange_bytes_total")
        return None if total is None or not p["total"] else total / p["total"]

    return median_over_passes(ctx, one)
