from metriclib import median_over_passes

META = {
    "name": "cex_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "the `counterexample` span of a pass (the parent-pointer walk "
            "over the trace store and the decode of every state of the "
            "trace, after the level the verdict cut has ended and before "
            "the trace is rendered), median over the passes. The walk "
            "(`walk_ms`, storage) is microseconds; the span is its "
            "`decode_ms`, which is host wall of eager device operations and "
            "so includes the wait for whatever the level loop left on the "
            "device queue (the dropped chunk's launch): the level loop owns "
            "the time. Nothing to read on a pass with no verdict or a "
            "program with no such span",
}


def read(ctx):
    def one(p):
        spans = [s for s in p["spans"]["spans"] if s[0] == "counterexample"]
        return 1e3 * sum(s[2] for s in spans) if spans else None

    return median_over_passes(ctx, one)
