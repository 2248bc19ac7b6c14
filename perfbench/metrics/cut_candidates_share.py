from metriclib import has, median_over_passes

META = {
    "name": "cut_candidates_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "enabled_candidates of the level the verdict cut (stats "
            "`cut_level`: the candidates of the chunks it ran to its "
            "verdict, the verdict's own included) over all the candidates "
            "of the pass (the level records' sum plus the cut level's), "
            "median over the passes: how much of a verdict's expansion and "
            "dedup work lies in the level no level record holds, and so in "
            "no reader that sums `stats[\"levels\"]`.  Exact counts: "
            "repeats between runs, and on the CPU.  Nothing to read on a "
            "pass with no verdict or a program whose cut level's record "
            "lacks the count",
}


def read(ctx):
    def one(p):
        cut = p["stats"].get("cut_level") or {}
        recs = p["level_records"]
        if "enabled_candidates" not in cut or not has(
                recs, "enabled_candidates"):
            return None
        total = cut["enabled_candidates"] + sum(
            r["enabled_candidates"] for r in recs)
        return 100.0 * cut["enabled_candidates"] / total if total else None

    return median_over_passes(ctx, one)
