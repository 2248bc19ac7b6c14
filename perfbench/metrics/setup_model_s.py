from setupparts import part

META = {
    "name": "setup_model_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "the process ledger's `model_s`: wall seconds of `build_model` "
            "and `prepare` (the outermost build on a thread counts, so a "
            "build inside a build counts once), the oracle twin's build "
            "for the 2 s prefix included",
}


def read(ctx):
    return part(ctx, "model")
