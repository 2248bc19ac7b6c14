from metriclib import has, median_over_passes

META = {
    "name": "d2h_bytes_per_state", "unit": "B", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of d2h_bytes over a pass's level records (bytes the hot path fetched from the device) over its "
            "distinct states; repeats exactly",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "d2h_bytes") or not p["total"]:
            return None
        return sum(r["d2h_bytes"] for r in recs) / p["total"]

    return median_over_passes(ctx, one)
