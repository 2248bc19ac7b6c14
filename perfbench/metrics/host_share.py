from metriclib import accounted_levels, has, median_over_passes

META = {
    "name": "host_share", "unit": "%", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of host_ms over sum of level_ms of a pass's level records "
            "(the engine's own host clocks; step_ms ends in a blocking fetch, "
            "host_ms is the commit work after it), median over the passes. "
            "Only the levels whose step_ms + host_ms account for their "
            "level_ms: in a level of several chunks host_ms holds the wait "
            "for the next chunk's device work (metriclib.accounted_levels), "
            "and device_idle_share says what the host costs there",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not all(has(recs, k) for k in ("host_ms", "step_ms", "level_ms")):
            return None
        recs = accounted_levels(recs)
        total = sum(r["level_ms"] for r in recs)
        return 100.0 * sum(r["host_ms"] for r in recs) / total if total else None

    return median_over_passes(ctx, one)
