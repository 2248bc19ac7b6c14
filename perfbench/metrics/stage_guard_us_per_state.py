import stagereduce

META = {
    "name": "stage_guard_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.guard in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "the guard sweep (frontier unpack, every action's guard over the choice lattice, deadlock test)",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "guard")
