from metriclib import has, median_over_passes

META = {
    "name": "shard_imbalance", "unit": "ratio", "better": "lower",
    "source": "program_counter", "layer": "exchange",
    "moves": "states_per_s",
    "what": "new states per shard summed over a pass's levels (level "
            "records' shard_new): the fullest shard over the mean shard; "
            "1.0 is an even split of owner = fp_lo mod D; median over passes",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "shard_new"):
            return None
        per_shard = [sum(col) for col in zip(*(r["shard_new"] for r in recs))]
        if not per_shard or not sum(per_shard):
            return None
        return max(per_shard) * len(per_shard) / sum(per_shard)

    return median_over_passes(ctx, one)
