from metriclib import has, median_over_passes

META = {
    "name": "ahead_share", "unit": "%", "better": "higher",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "fused chunks whose guard launch was dispatched before the "
            "previous chunk's successor launch (level records' "
            "chunks_ahead) over the chunks the levels streamed (chunks), "
            "summed over a pass's committed levels, median over the "
            "passes: the share of chunk boundaries whose host work runs "
            "behind a successor program.  A level of n fused chunks reads "
            "n - 1 of n; a one-chunk level, a whole-level program, a "
            "sub-gate chunk and overlap off read 0.  An exact count: "
            "repeats between runs, and on the CPU.  Nothing to read on a "
            "program whose records lack the field",
}


def read(ctx):
    def one(p):
        recs = p["level_records"]
        if not has(recs, "chunks_ahead") or not has(recs, "chunks"):
            return None
        chunks = sum(r["chunks"] for r in recs)
        return 100.0 * sum(r["chunks_ahead"] for r in recs) / chunks \
            if chunks else None

    return median_over_passes(ctx, one)
