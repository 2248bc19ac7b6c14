from metriclib import median_over_passes

META = {
    "name": "cut_chunks_committed", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "`chunks_committed` of the level the verdict cut (stats "
            "`cut_level`), median over the passes: the chunks that level "
            "ran to its verdict, the verdict's own included.  It is what "
            "`cut_level_share` and `cex_ms` are the cost OF, and the count "
            "that moves if a change to chunk order or size moves the "
            "first-violation rule.  An exact count: repeats between runs, "
            "and on the CPU.  Nothing to read on a pass with no verdict or "
            "a program whose record lacks the field",
}


def read(ctx):
    def one(p):
        cut = p["stats"].get("cut_level") or {}
        return cut.get("chunks_committed")

    return median_over_passes(ctx, one)
