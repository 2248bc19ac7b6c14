from metriclib import has, median_over_passes

META = {
    "name": "fetches_per_level", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of d2h_fetches (device arrays the hot path read back, each "
            "a blocking round trip) over a pass's levels; repeats exactly",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "d2h_fetches"):
            return None
        return sum(r["d2h_fetches"] for r in recs) / len(recs)

    return median_over_passes(ctx, one)
