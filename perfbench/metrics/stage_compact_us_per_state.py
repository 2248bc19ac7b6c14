import stagereduce

META = {
    "name": "stage_compact_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.compact in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "stream compaction (squeeze, per-action segment scatter, new-row compaction, next-frontier append)",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "compact")
