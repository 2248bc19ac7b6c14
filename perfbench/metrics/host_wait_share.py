from metriclib import has, median_over_passes

META = {
    "name": "host_wait_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of the level records' fetch_ms (the time HostIO.fetch was "
            "blocked in np.asarray of a device array: the wait for the "
            "value to be computed AND its transfer, one number) over sum of "
            "level_ms, over ALL levels of a pass (no accounted_levels "
            "filter: a blocked fetch is counted where it blocks, so a level "
            "of ten chunks reads true), median over the passes; nothing to "
            "read on a program whose records have no fetch_ms",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "fetch_ms") or not has(recs, "level_ms"):
            return None
        total = sum(r["level_ms"] for r in recs)
        return 100.0 * sum(r["fetch_ms"] for r in recs) / total \
            if total else None

    return median_over_passes(ctx, one)
