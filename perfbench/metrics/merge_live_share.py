from metriclib import has, median_over_passes

META = {
    "name": "merge_live_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "slots the sorted-set merges' loops touched (level records' "
            "merge_slots: a device count, blocks run x block size, the "
            "visited side and the new side) over the slots merges over the "
            "whole pinned capacity touch (merge_slots_plain: cap + M a "
            "merge), summed over a pass's levels, median over the passes; "
            "100% is the capacity-wide merge; nothing to read on a program "
            "whose records lack the fields",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "merge_slots") or not has(recs, "merge_slots_plain"):
            return None
        plain = sum(r["merge_slots_plain"] for r in recs)
        return 100.0 * sum(r["merge_slots"] for r in recs) / plain \
            if plain else None

    return median_over_passes(ctx, one)
