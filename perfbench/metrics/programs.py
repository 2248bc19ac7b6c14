META = {
    "name": "programs", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "backend compiles or cache loads JAX reports (jax.monitoring "
            "backend_compile_duration events) during set-up",
}


def read(ctx):
    return ctx["setup"]["jax"]["backend_compiles"]
