from metriclib import accounted_levels, has, median_over_passes

META = {
    "name": "step_us_per_state", "unit": "us", "better": "lower",
    "source": "program_span", "layer": "level programs",
    "moves": "states_per_s",
    "what": "sum of step_ms (dispatch plus the blocking wait on the level "
            "programs' outputs) x 1000 over the new states of the same "
            "levels. Only the levels whose step_ms + host_ms account for "
            "their level_ms: in a level of several chunks step_ms leaves out "
            "the device time hidden behind a commit "
            "(metriclib.accounted_levels); the stage metrics cover those",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not all(has(recs, k) for k in
                   ("host_ms", "step_ms", "level_ms", "new")):
            return None
        recs = accounted_levels(recs)
        new = sum(r["new"] for r in recs)
        return 1000.0 * sum(r["step_ms"] for r in recs) / new if new else None

    return median_over_passes(ctx, one)
