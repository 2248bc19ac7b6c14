from setupparts import part

META = {
    "name": "setup_trace_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "`programs.trace_s` + `helpers.trace_s` of the process ledger: "
            "WALL seconds of Python tracing before the window, the "
            "outermost `jaxpr_trace_duration` events only.  JAX fires one "
            "for every jitted function it traces, the `jnp` functions "
            "inside a program included, and the outer event's seconds "
            "contain the inner ones': the harness's `setup.jax.trace_s` "
            "sums them all and is no wall time.  This is what a process "
            "that found its programs without tracing them would save",
}


def read(ctx):
    return part(ctx, "trace")
