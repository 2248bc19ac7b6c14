from metriclib import median_over_passes

META = {
    "name": "pass_overhead_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "level loop on the host",
    "moves": "verdict_s",
    "what": "check-open + check-close + run-open span milliseconds of a "
            "pass (engine start and finish: run directory, manifests, "
            "initial states and first transfers, metrics export, result), "
            "median over the passes",
}

_KINDS = ("check-open", "check-close", "run-open")


def read(ctx):
    def one(p):
        spans = [s for s in p["spans"]["spans"] if s[0] in _KINDS]
        if not any(s[0] == "check-open" for s in spans):
            return None
        return 1e3 * sum(s[2] for s in spans)

    return median_over_passes(ctx, one)
