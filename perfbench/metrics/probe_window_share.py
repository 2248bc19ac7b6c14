from metriclib import has, median_over_passes

META = {
    "name": "probe_window_share", "unit": "%", "better": "higher",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "sorted-set probes that searched a bounded window of the set "
            "(level records' probes_windowed: the probes of the committed "
            "dispatches that ran with the set's capacity above "
            "dedup.PROBE_WINDOW and its length, as the host held it before "
            "the dispatch, at or under it) over the probes the dispatches "
            "ran (probes), summed over a pass's levels, median over the "
            "passes; 0 where no capacity has outgrown the window; exact "
            "counts, so it repeats exactly, and on the CPU; nothing to "
            "read on a program whose records lack the fields",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "probes") or not has(recs, "probes_windowed"):
            return None
        probes = sum(r["probes"] for r in recs)
        return 100.0 * sum(r["probes_windowed"] for r in recs) / probes \
            if probes else None

    return median_over_passes(ctx, one)
