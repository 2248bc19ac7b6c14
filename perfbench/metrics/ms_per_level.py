import statistics

from metriclib import has, median_over_passes

META = {
    "name": "ms_per_level", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "median level_ms over a pass's levels, median over the passes: "
            "the per-level fixed cost where most levels are small",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "level_ms"):
            return None
        return statistics.median(r["level_ms"] for r in recs)

    return median_over_passes(ctx, one)
