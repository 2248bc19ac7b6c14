from metriclib import has, median_over_passes

META = {
    "name": "store_share", "unit": "%", "better": "lower",
    "source": "program_span", "layer": "storage",
    "moves": "verdict_s",
    "what": "sum of store_ms (host wall of the trace store and the parent "
            "log: parent/action assembly, the retained copy, appends, the "
            "disk tier's level publish) over sum of level_ms, median over "
            "the passes; 0 with the trace store off",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "store_ms") or not has(recs, "level_ms"):
            return None
        total = sum(r["level_ms"] for r in recs)
        return 100.0 * sum(r["store_ms"] for r in recs) / total \
            if total else None

    return median_over_passes(ctx, one)
