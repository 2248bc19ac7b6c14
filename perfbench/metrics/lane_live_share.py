from metriclib import has, median_over_passes

META = {
    "name": "lane_live_share", "unit": "%", "better": "higher",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "sum of the level records' enabled_candidates over sum of "
            "dedup_lanes (the width the dedup side of every committed "
            "dispatch was handed: the pooled widths of a fused chunk, T of "
            "a step or whole-level chunk, R a shard) over a pass's levels, "
            "median over the passes: the share of every sort, probe, "
            "squeeze and compaction lane that held a candidate; nothing to "
            "read on a program whose records have no dedup_lanes",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "dedup_lanes") or not has(recs, "enabled_candidates"):
            return None
        lanes = sum(r["dedup_lanes"] for r in recs)
        return 100.0 * sum(r["enabled_candidates"] for r in recs) / lanes \
            if lanes else None

    return median_over_passes(ctx, one)
