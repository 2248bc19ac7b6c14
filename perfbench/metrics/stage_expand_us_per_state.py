import stagereduce

META = {
    "name": "stage_expand_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.expand in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "successor generation (state gather, action update, CONSTRAINT, lane packing)",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "expand")
