from metriclib import has, median_over_passes

META = {
    "name": "canon_rows_per_candidate", "unit": "ratio", "better": "lower",
    "source": "program_counter", "layer": "kernels",
    "moves": "states_per_s",
    "what": "rows whose images the canon stage formed (level records' "
            "canon_rows: a device count, blocks run x block size) over the "
            "enabled candidates, summed over a pass's levels, median over "
            "the passes: 1.0 is a stage that pays for live candidates "
            "only, ~3 one that pays for the padding of its layout; nothing "
            "to read on a program whose records lack the field",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "canon_rows") or not has(recs, "enabled_candidates"):
            return None
        enabled = sum(r["enabled_candidates"] for r in recs)
        return sum(r["canon_rows"] for r in recs) / enabled \
            if enabled else None

    return median_over_passes(ctx, one)
