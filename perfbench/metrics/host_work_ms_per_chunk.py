from metriclib import has, median_over_passes

META = {
    "name": "host_work_ms_per_chunk", "unit": "ms", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "states_per_s",
    "what": "sum of (level_ms - fetch_ms) over sum of chunks, over ALL "
            "levels of a pass, median over the passes: what the host itself "
            "costs a chunk (Python, numpy, dispatch, upload) once its "
            "blocked fetches are taken out, true in a level of ten chunks "
            "too; nothing to read on a program whose records have no "
            "fetch_ms or no chunks",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not all(has(recs, k) for k in ("fetch_ms", "level_ms", "chunks")):
            return None
        chunks = sum(r["chunks"] for r in recs)
        return sum(r["level_ms"] - r["fetch_ms"] for r in recs) / chunks \
            if chunks else None

    return median_over_passes(ctx, one)
