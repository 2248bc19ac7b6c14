import partreduce

META = {
    "name": "compact_squeeze_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.compact/part.squeeze in the "
            "traced pass, on the plane and in the window of the stage "
            "metrics, x 1e6 over that pass's distinct states: "
            "squeeze_stage, enabled candidate rows to the front of the "
            "buffer dedup is handed; nothing to read on a program without "
            "part scopes",
}


def read(ctx):
    return partreduce.part_us_per_state(ctx, "squeeze")
