from metriclib import has, median_over_passes

META = {
    "name": "novel_live_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "level programs",
    "moves": "states_per_s",
    "what": "rows the loops of the new states' compaction touched (level "
            "records' novel_rows: a device count, blocks run x block size, "
            "the live prefix of the sorted candidates and the new states) "
            "over the rows a compaction of the whole width handed to dedup "
            "touches (novel_rows_plain: T a dispatch), summed over a "
            "pass's levels, median over the passes; 100% is the full-width "
            "compaction; nothing to read on a program whose records lack "
            "the fields",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "novel_rows") or not has(recs, "novel_rows_plain"):
            return None
        plain = sum(r["novel_rows_plain"] for r in recs)
        return 100.0 * sum(r["novel_rows"] for r in recs) / plain \
            if plain else None

    return median_over_passes(ctx, one)
