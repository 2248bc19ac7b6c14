from metriclib import has, median_over_passes

META = {
    "name": "chunk_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of level_ms over sum of chunks (the level record's count of "
            "the chunks the level streamed) over a pass's levels of two or "
            "more chunks, median over the passes: the wall cost of one "
            "streamed chunk where a level is made of chunks; nothing to "
            "read where no level has two, or on a program whose records "
            "have no `chunks`",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "chunks") or not has(recs, "level_ms"):
            return None
        wide = [r for r in recs if r["chunks"] >= 2]
        if not wide:
            return None
        return sum(r["level_ms"] for r in wide) / sum(r["chunks"] for r in wide)

    return median_over_passes(ctx, one)
