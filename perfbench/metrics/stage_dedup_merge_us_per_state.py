import stagereduce

META = {
    "name": "stage_dedup_merge_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.dedup_merge in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "the rank-scatter merge into the sorted visited and level-new sets, its own rank search included",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "dedup_merge")
