from metriclib import median_over_passes

META = {
    "name": "frontier_verify_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "the `frontier-verify` spans of a pass summed (one a level "
            "boundary, the last one included, the one the depth bound then "
            "ends: the host re-fingerprints the frontier about to be "
            "expanded and holds its digest to the entry the chain sealed "
            "when the level was discovered; nothing is in flight, so the "
            "device is idle for all of it), median over the passes. Every "
            "state of a pass goes through it once. Nothing to read under "
            "SYMMETRY (the rows are not re-read) or on a program with no "
            "such span",
}


def read(ctx):
    def one(p):
        spans = [s for s in p["spans"]["spans"] if s[0] == "frontier-verify"]
        return 1e3 * sum(s[2] for s in spans) if spans else None

    return median_over_passes(ctx, one)
