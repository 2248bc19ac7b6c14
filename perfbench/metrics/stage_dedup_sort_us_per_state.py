import stagereduce

META = {
    "name": "stage_dedup_sort_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.dedup_sort in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "the fingerprint lexsort and the first-occurrence mask",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "dedup_sort")
