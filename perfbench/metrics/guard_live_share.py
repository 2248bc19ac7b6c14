from metriclib import has, median_over_passes

META = {
    "name": "guard_live_share", "unit": "%", "better": "higher",
    "source": "program_counter", "layer": "level programs",
    "moves": "states_per_s",
    "what": "sum of the level records' enabled_candidates over sum of "
            "guard_lanes (the lanes the guard side of every committed "
            "dispatch evaluated: rows handed x the model's static fanout, "
            "padded widths included; a shape the host holds, no device "
            "work) over a pass's levels, median over the passes: the share "
            "of the choice lattice that is enabled, which is what guard, "
            "expand and compact are handed per state they yield; exact "
            "counts, so it repeats exactly; nothing to read on a program "
            "whose records have no guard_lanes",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "guard_lanes") or not has(recs, "enabled_candidates"):
            return None
        lanes = sum(r["guard_lanes"] for r in recs)
        return 100.0 * sum(r["enabled_candidates"] for r in recs) / lanes \
            if lanes else None

    return median_over_passes(ctx, one)
