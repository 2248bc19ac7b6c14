from setupparts import part

META = {
    "name": "setup_lower_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "`programs.lower_s` + `helpers.lower_s` of the process ledger: "
            "wall seconds of jaxpr-to-MLIR lowering before the window, by "
            "JAX's own `jaxpr_to_mlir_module_duration` events",
}


def read(ctx):
    return part(ctx, "lower")
