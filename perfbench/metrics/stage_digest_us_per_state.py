import stagereduce

META = {
    "name": "stage_digest_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "kernels",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.digest in the traced pass, "
            "busiest device, x 1e6 over that pass's distinct states: "
            "the in-jit (count, xor, sum) digest folds",
}


def read(ctx):
    return stagereduce.stage_us_per_state(ctx, "digest")
