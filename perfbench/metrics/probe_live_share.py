from metriclib import has, median_over_passes

META = {
    "name": "probe_live_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "query lanes the sorted-set probes searched (level records' "
            "probe_lanes: a device count, blocks run x block size over the "
            "live prefix of each probe's sorted queries) over the lanes "
            "the probes were handed (probe_lanes_plain: T a probe, what a "
            "search of every lane runs), summed over a pass's levels, "
            "median over the passes; 100% is the full-width search; "
            "nothing to read on a program whose records lack the fields",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "probe_lanes") or not has(recs, "probe_lanes_plain"):
            return None
        plain = sum(r["probe_lanes_plain"] for r in recs)
        return 100.0 * sum(r["probe_lanes"] for r in recs) / plain \
            if plain else None

    return median_over_passes(ctx, one)
