from metriclib import has, median_over_passes

META = {
    "name": "symmetry_reduction", "unit": "ratio", "better": "higher",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "unreduced states covered per state stored: the level records' "
            "orbit_states (the sum over a level's new states of their "
            "orbits' sizes, |G| over the stabiliser: it EQUALS the "
            "unreduced job's count of that level) over new, summed over a "
            "pass's levels, median over the passes; at most |G| (120 at 5 "
            "brokers); nothing to read on a program whose records lack the "
            "field",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "orbit_states") or not has(recs, "new"):
            return None
        new = sum(r["new"] for r in recs)
        return sum(r["orbit_states"] for r in recs) / new if new else None

    return median_over_passes(ctx, one)
