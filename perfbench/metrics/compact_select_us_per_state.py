import partreduce

META = {
    "name": "compact_select_us_per_state", "unit": "us", "better": "lower",
    "source": "device_trace", "layer": "level programs",
    "moves": "states_per_s",
    "what": "leaf device seconds under kspec.compact/part.select in the "
            "traced pass, on the plane and in the window of the stage "
            "metrics, x 1e6 over that pass's distinct states: the "
            "per-action index compaction of the guard matrix on the device "
            "(the fused path does it on the host: its compact-host span); "
            "nothing to read on a program without part scopes",
}


def read(ctx):
    return partreduce.part_us_per_state(ctx, "select")
