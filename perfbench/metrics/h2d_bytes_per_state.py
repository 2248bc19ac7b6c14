from metriclib import has, median_over_passes

META = {
    "name": "h2d_bytes_per_state", "unit": "B", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of h2d_bytes over a pass's level records (bytes the hot path uploaded to the device (level 1 carries the visited set's first upload)) over its "
            "distinct states; repeats exactly",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "h2d_bytes") or not p["total"]:
            return None
        return sum(r["h2d_bytes"] for r in recs) / p["total"]

    return median_over_passes(ctx, one)
