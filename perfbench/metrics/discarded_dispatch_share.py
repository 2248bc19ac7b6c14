from metriclib import has, median_over_passes

META = {
    "name": "discarded_dispatch_share", "unit": "%", "better": "lower",
    "source": "program_span", "layer": "level programs",
    "moves": "states_per_s",
    "what": "sum of discarded_ms (host wall of the dispatches whose outputs "
            "were thrown away and the work re-run; the overflow read blocks, "
            "so device time plus launch) over sum of level_ms, median over "
            "the passes",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "discarded_ms") or not has(recs, "level_ms"):
            return None
        total = sum(r["level_ms"] for r in recs)
        return 100.0 * sum(r["discarded_ms"] for r in recs) / total \
            if total else None

    return median_over_passes(ctx, one)
