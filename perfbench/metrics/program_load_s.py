META = {
    "name": "program_load_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "summed duration of those events: compiling on a first run, "
            "cache loads on a warm one",
}


def read(ctx):
    if ctx["rehearsal"]:
        return None
    return ctx["setup"]["jax"]["backend_compile_s"]
