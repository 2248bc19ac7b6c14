from metriclib import has, median_over_passes

META = {
    "name": "dup_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "sum of the level records' duplicates over sum of their "
            "enabled_candidates over a pass: the share of dedup's input "
            "(sort, probe, merge) that yields no state; exact counts, so it "
            "repeats exactly; median over the passes",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "duplicates") or not has(recs, "enabled_candidates"):
            return None
        enabled = sum(r["enabled_candidates"] for r in recs)
        return 100.0 * sum(r["duplicates"] for r in recs) / enabled \
            if enabled else None

    return median_over_passes(ctx, one)
