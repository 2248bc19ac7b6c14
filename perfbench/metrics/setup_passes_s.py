from setupparts import part

META = {
    "name": "setup_passes_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "the process ledger's `checks.s` less the first window pass's "
            "own `checks.last_s`: the engine wall of the set-up passes, "
            "call to result, their builds included (`programs.call_s` says "
            "how much of it is first calls)",
}


def read(ctx):
    return part(ctx, "passes")
