from metriclib import has, median_over_passes

META = {
    "name": "step_us_per_state", "unit": "us", "better": "lower",
    "source": "program_span", "layer": "level programs",
    "moves": "states_per_s",
    "what": "sum of step_ms (dispatch plus the blocking wait on the level "
            "programs' outputs) x 1000 over the pass's distinct states",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "step_ms") or not p["total"]:
            return None
        return 1000.0 * sum(r["step_ms"] for r in recs) / p["total"]

    return median_over_passes(ctx, one)
