import partreduce

META = {
    "name": "compact_unparted_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "kspec.compact leaf device seconds under no part.* scope over "
            "all kspec.compact leaf device seconds of the traced pass: the "
            "check on the four compact_*_us_per_state, which with it sum to "
            "stage_compact_us_per_state; nothing to read on a program "
            "without part scopes",
}


def read(ctx):
    return partreduce.unparted_share(ctx)
