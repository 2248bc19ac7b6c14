import stagereduce

META = {
    "name": "stage_unnamed_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under no kspec.<stage> scope over all leaf "
            "device seconds of the traced pass: the check on the nine "
            "stage_*_us_per_state metrics",
}


def read(ctx):
    reduced = stagereduce.for_ctx(ctx)
    if not reduced or not reduced["leaf_s"]:
        return None
    return 100.0 * reduced["stage_s"][stagereduce.UNNAMED] / reduced["leaf_s"]
