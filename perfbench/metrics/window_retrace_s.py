from metriclib import median_over_passes

META = {
    "name": "window_retrace_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "verdict_s",
    "what": "trace, lowering and backend-compile (or cache-load) durations "
            "JAX itself reports inside a window pass, median over the passes; "
            "0 in the one-chip cells",
}


def read(ctx):
    if ctx["rehearsal"]:
        return None
    return median_over_passes(
        ctx, lambda p: p["jax"]["trace_s"] + p["jax"]["lowering_s"]
        + p["jax"]["backend_compile_s"])
