import stagereduce

EXPANSION = ("guard", "expand", "compact")

META = {
    "name": "expansion_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.guard, kspec.expand and "
            "kspec.compact over all leaf device seconds of the traced pass "
            "(stagereduce's stage_s and leaf_s, busiest device): the share "
            "of the device's work that is the expansion side (the guard "
            "sweep over the choice lattice, the successor kernels, the "
            "compactions around them) and not fingerprint, dedup, "
            "invariants or digest; says whether a wide lattice or the "
            "visited set does most of the work in a cell",
}


def read(ctx):
    reduced = stagereduce.for_ctx(ctx)
    if not reduced or not reduced["leaf_s"]:
        return None
    return 100.0 * sum(reduced["stage_s"][s] for s in EXPANSION) \
        / reduced["leaf_s"]
