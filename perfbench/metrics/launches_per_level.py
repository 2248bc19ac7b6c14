from metriclib import has, median_over_passes

META = {
    "name": "launches_per_level", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "level programs",
    "moves": "states_per_s",
    "what": "successor launches (single engine: successor_launches; sharded: "
            "shard_launches) summed over a pass's levels, over the levels; a "
            "count, repeats exactly",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        for key in ("successor_launches", "shard_launches"):
            if has(recs, key):
                return sum(r[key] for r in recs) / len(recs)
        return None

    return median_over_passes(ctx, one)
