import stagereduce

META = {
    "name": "stage_invariants_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.invariants in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "the invariant predicates and the verdict fold",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "invariants")
